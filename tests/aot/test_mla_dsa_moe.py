"""deepseek-v3.2-exp.serve1: latent attention under a learned selection, three
programs."""
import re

import pytest

from described_device import compiled_program, engine_of, fits_as_the_file_says, on_the_chip


@pytest.fixture(scope="module")
def dsa_engine():
    """DeepSeek-V3.2-Exp at deepseek-v3.2-exp.serve1's geometry."""
    return engine_of("benchmark/configs/deepseek-v3.2-exp.serve1.json")


@pytest.mark.parametrize("kind", ["step_prefill_T512", "mixed_K8", "mega_K8"])
def test_a_selected_latent_models_programs_fit_the_chip(chip, dsa_engine, kind, monkeypatch):
    """The prefill step, the mixed scan and the decode scan of
    deepseek-v3.2-exp.serve1 (4.6 B parameters, a pool of 5,120 blocks x two
    arrays x five layers) compiled as the chip will run them: both pool
    arrays keep the argument's row-major layout and neither is copied (at the
    latent's own width 576 the compiler transposed every layer's pool: PR 26),
    the score buffer and the selection are temporaries, and the largest
    program leaves 1.5 GB of the chip free.  The figures are the
    configuration file's ``memory.compiled_for_v5e``."""
    on_the_chip(monkeypatch)
    cfg, eng = dsa_engine
    nb, bs = cfg["engine"]["num_blocks"], eng.bs
    assert (eng.B, eng.T, eng.P, eng.megastep_k, eng.pc) == (24, 512, 268, 8, 64)
    assert [c[0].shape for c in eng.caches] == [(2, bs, 640), (2, bs, 128)]
    compiled = compiled_program(eng, cfg, kind, chip)
    text = compiled.as_text()
    for width in (640, 128):
        pool = f"{nb},{bs},{width}"
        orders = set(re.findall(rf"bf16\[{pool}\]\{{([0-9,]+)", text))
        assert orders == {"2,1,0"}, (width, orders)
        assert not re.search(rf"= bf16\[{pool}\][^\n]* copy\(", text)
    fits_as_the_file_says(cfg, kind, compiled, margin=1.5e9)
