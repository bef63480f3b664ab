"""paddle_tpu.models — NLP model families (the PaddleNLP-capability surface
BASELINE exercises; vision models live in paddle_tpu.vision.models)."""
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaForCausalLM,
    LlamaModel,
    LlamaPretrainingCriterion,
    llama_7b,
    llama_pipeline_descs,
    llama_tiny,
)
from .generation import generate, greedy_decode  # noqa: F401,E402
from .gpt import (  # noqa: F401,E402
    GPTConfig,
    GPTForCausalLM,
    GPTModel,
    GPTPretrainingCriterion,
    gpt3_1_3b,
    gpt_pipeline_descs,
    gpt_tiny,
)
from .pangu_moe import (  # noqa: F401,E402
    PanguMLAttention,
    PanguSparseMoE,
    PanguUltraMoEConfig,
    PanguUltraMoEForCausalLM,
    PanguUltraMoEModel,
    pangu_ultra_moe_tiny,
)
from .ouro import (  # noqa: F401,E402
    OuroConfig,
    OuroForCausalLM,
    OuroModel,
)
from .deepseek_v32 import (  # noqa: F401,E402
    DeepseekV32Config,
    DeepseekV32ForCausalLM,
    DeepseekV32Model,
    deepseek_v32_tiny,
)
from .lfm2_moe import (  # noqa: F401,E402
    Lfm2MoeConfig,
    Lfm2MoeForCausalLM,
    Lfm2MoeModel,
    lfm2_moe_tiny,
)
from .smallthinker import (  # noqa: F401,E402
    SmallThinkerConfig,
    SmallThinkerForCausalLM,
    SmallThinkerModel,
    smallthinker_tiny,
)
from .cohere2_moe import (  # noqa: F401,E402
    Cohere2MoeConfig,
    Cohere2MoeForCausalLM,
    Cohere2MoeModel,
    cohere2_moe_tiny,
)
