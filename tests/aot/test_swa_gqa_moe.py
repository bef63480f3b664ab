"""smallthinker-21b-a3b.serve1: twelve GQA layers of two kinds (3 global, 9 of a
window of 4,096), a pool and a block table a kind, 7 query heads a key/value
head, all 64 ReGLU experts of every layer; six programs."""
import re

import jax.numpy as jnp
import pytest

from described_device import (V5E_BYTES_LIMIT, compiled_program, engine_of,
                              fits_as_the_file_says, kernel_calls, on_the_chip)

PROGRAMS = ("step_prefill_T512", "step_decode", "mixed_K8", "mega_K2", "mega_K4", "mega_K8")


@pytest.fixture(scope="module")
def swa_engine():
    """SmallThinker-21BA3B-Instruct's first twelve layers at
    smallthinker-21b-a3b.serve1's geometry."""
    return engine_of("benchmark/configs/smallthinker-21b-a3b.serve1.json")


def _expert_layout(eng, kind, text):
    """The expert layers' operands in a compiled program's text: the sorted
    rows a chunk gathers are ``layout``'s (row tile x chunk, E), and the loop
    over chunks is in the text only where a chunk is less than the bound."""
    from paddle_tpu.ops import held_experts as he

    tokens = {"step_prefill_T512": eng.T, "mixed_K8": eng.T}.get(kind, eng.B)
    row_tile, bound, chunk = he.layout(tokens, 6, 64, 64, 2 * 2560)
    assert (row_tile, bound, chunk) == ((64, 112, 72) if tokens == 512 else (32, 73, 73))
    assert re.search(rf"bf16\[{row_tile * chunk},2560\]", text)
    assert not re.search(rf"bf16\[{32 * 102},2560\]", text)      # the parent's chunk of tile 32
    assert ("experts/while" in text) == (chunk < bound)


@pytest.mark.parametrize("kind", PROGRAMS)
def test_a_model_of_two_cache_kinds_fits_the_chip_as_its_file_says(chip, swa_engine, kind,
                                                                    monkeypatch):
    """The six programs smallthinker21b.serve.mixed-length can reach, compiled as
    the chip will run them: 5.56 B parameters with every expert of twelve layers,
    a pool of 3,456 blocks for the three global layers and one of 2,560 for the
    nine window layers (keys and values ``[blocks, 4, 64, 128]`` a layer), TWO
    block tables in the ONE control block.  Heads of 128 keep the kernels and 7
    query heads a key/value head ride ``paged_decode`` as a sublane tile of 8:
    one ``paged_decode`` and one ``paged_write`` a cache layer, window or not,
    and where a row may feed more than one token (the prefill step, the mixed
    scan) one ``paged_chunk``, 28 heads a token riding as 32 in its copies, with
    no loop over chunk rows or context blocks left under ``paged_attention``; a
    pool array keeps ONE layout, the argument's row-major order, and is copied
    in or out of no program; the expert layer is three grouped products
    (``expert_gmm`` three times a layer) over a layout that follows the call
    (ISSUE 47: 512 tokens x 6 picks over 64 experts are 48 rows an expert, so a
    row tile of 64 and a chunk of the 64 expected tiles and eight more; the
    decode scans' 48 tokens a tile of 32 and a chunk of the bound, no loop);
    ``arguments`` and ``live`` are the
    configuration file's ``memory.compiled_for_v5e``, and the fullest program
    stands over 90 % of the chip."""
    on_the_chip(monkeypatch)
    cfg, eng = swa_engine
    nb, bs = cfg["engine"]["num_blocks"], eng.bs
    assert (eng.B, eng.T, eng.P, eng.megastep_k, eng.pc) == (48, 512, 256, 8, 64)
    assert [(k.name, k.layers, k.window) for k in eng.kinds] == [
        ("global", 3, None), ("window", 9, 4096)]
    assert [[a.shape for a in c] for c in eng.caches] == [[(2, 4, bs, 128)] * 12] * 2
    assert eng._kind_hold(1, 256) == 73 and eng._kind_hold(1, 10) == 10
    compiled = compiled_program(eng, cfg, kind, chip)
    text = compiled.as_text()
    assert kernel_calls(text, "paged_decode") == kernel_calls(text, "paged_write") == 12
    assert kernel_calls(text, "paged_chunk") == (12 if kind in ("step_prefill_T512", "mixed_K8")
                                                 else 0)
    assert "kv_gather" not in text and "paged_attention/while" not in text
    assert kernel_calls(text, "expert_gmm") == 3 * 12
    _expert_layout(eng, kind, text)
    for blocks in (nb["global"], nb["window"]):
        pool = rf"bf16\[{blocks},4,{bs},128\]"
        assert set(re.findall(pool + r"\{([0-9,]+)", text)) == {"3,2,1,0"}
        assert not re.search(rf"= {pool}[^\n]* copy\(", text)
    mem, live, said = fits_as_the_file_says(cfg, kind, compiled, margin=1.2e9)
    assert 0.9 * V5E_BYTES_LIMIT < live
    assert said["arguments"] == mem.argument_size_in_bytes
