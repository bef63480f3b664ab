"""command-a-plus-05-2026.serve1: four layers of a PARALLEL block (three of a
window of 4,096, one global), 16 query heads a key/value head, 16 of 128 experts
held beside four averaged shared experts, a pool and a block table a kind, a
head that is the table; six programs."""
import re

import pytest

from described_device import (V5E_BYTES_LIMIT, compiled_program, engine_of,
                              fits_as_the_file_says, kernel_calls, on_the_chip)

PROGRAMS = ("step_prefill_T512", "step_decode", "mixed_K8", "mega_K2", "mega_K4", "mega_K8")


@pytest.fixture(scope="module")
def parallel_engine():
    """Command A+'s first period at command-a-plus-05-2026.serve1's geometry."""
    return engine_of("benchmark/configs/command-a-plus-05-2026.serve1.json")


@pytest.mark.parametrize("kind", PROGRAMS)
def test_a_parallel_block_of_two_cache_kinds_fits_the_chip_as_its_file_says(
        chip, parallel_engine, kind, monkeypatch):
    """The six programs commandaplus.serve.rag-batch can reach, compiled as the
    chip will run them: 4.73 B parameters (16 of 128 experts and four shared
    experts a layer, an eighth of the table), a pool of 12,288 blocks for the one
    global layer and one of 2,336 for the three window layers (keys and values
    ``[blocks, 8, 64, 128]`` a layer), TWO block tables of 524 entries a row in
    the ONE control block.  128 query heads over 8 key/value heads ride the
    kernels PR 29 / 31 / 46 wrote at 16 queries a head, the widest group a cell
    has run: one ``paged_decode`` and one ``paged_write`` a cache layer, window
    or not, and where a row may feed more than one token (the prefill step, the
    mixed scan) one ``paged_chunk``, with no loop over chunk rows or context
    blocks left under ``paged_attention``; a pool array keeps ONE layout and is
    copied in or out of no program; the routed part is three grouped products a
    layer (``expert_gmm``) at F 4,096 over ``layout``'s tiles (512 tokens x 8
    picks over 128 experts are 32 rows an expert: a row tile of 32 and a chunk of
    64 tiles, 16 MB of gathered rows, of which the 16 held experts expect 16; the
    decode scans' 32 tokens one chunk of the bound and no loop);
    the table is read as the head where it lies: no transposed copy of its
    268 MB; ``arguments`` and ``live`` are the configuration file's
    ``memory.compiled_for_v5e``, and the fullest program stands over 80 % of the
    chip."""
    from paddle_tpu.ops import held_experts as he

    on_the_chip(monkeypatch)
    cfg, eng = parallel_engine
    nb, bs = cfg["engine"]["num_blocks"], eng.bs
    assert (eng.B, eng.T, eng.P, eng.megastep_k, eng.pc) == (32, 512, 524, 8, 64)
    assert [(k.name, k.layers, k.window) for k in eng.kinds] == [
        ("global", 1, None), ("window", 3, 4096)]
    assert [[a.shape for a in c] for c in eng.caches] == [[(2, 8, bs, 128)] * 4] * 2
    assert eng._kind_hold(1, 524) == 73 and eng._kind_hold(1, 40) == 40
    assert "head" not in eng._weights and eng._weights["embed"].shape == (32768, 4096)
    compiled = compiled_program(eng, cfg, kind, chip)
    text = compiled.as_text()
    assert kernel_calls(text, "paged_decode") == kernel_calls(text, "paged_write") == 4
    chunks = kind in ("step_prefill_T512", "mixed_K8")
    assert kernel_calls(text, "paged_chunk") == (4 if chunks else 0)
    assert "kv_gather" not in text and "paged_attention/while" not in text
    assert kernel_calls(text, "expert_gmm") == 3 * 4
    tokens = eng.T if chunks else eng.B
    row_tile, bound, chunk = he.layout(tokens, 8, 16, 128, 2 * 4096)
    assert (row_tile, bound, chunk) == ((32, 144, 64) if chunks else (32, 24, 24))
    assert re.search(rf"bf16\[{row_tile * chunk},4096\]", text)
    assert ("experts/while" in text) == (chunk < bound)
    for blocks in (nb["global"], nb["window"]):
        pool = rf"bf16\[{blocks},8,{bs},128\]"
        assert set(re.findall(pool + r"\{([0-9,]+)", text)) == {"3,2,1,0"}
        assert not re.search(rf"= {pool}[^\n]* copy\(", text)
    assert not re.search(r"= bf16\[(4096,32768|32768,4096)\][^\n]* (copy|transpose)\(", text)
    mem, live, said = fits_as_the_file_says(cfg, kind, compiled, margin=1.0e9)
    assert 0.8 * V5E_BYTES_LIMIT < live
    assert said["arguments"] == mem.argument_size_in_bytes
