"""ouro-2.6b.serve1: a looped model's three programs."""
import math
import re

import pytest

from described_device import (compiled_program, engine_of, fits_as_the_file_says,
                              kernel_calls, on_the_chip)


@pytest.fixture(scope="module")
def ouro_engine():
    """Ouro-2.6B at ouro-2.6b.serve1's geometry."""
    return engine_of("benchmark/configs/ouro-2.6b.serve1.json")


@pytest.mark.parametrize("kind", ["step_prefill_T256", "mixed_K8", "mega_K8"])
def test_a_looped_models_programs_keep_one_pool_in_place(chip, ouro_engine, kind, monkeypatch):
    """The prefill step, the mixed scan and the decode scan of Ouro-2.6B
    whole (48 layers' weights stacked, four passes, a pool of 192 cache
    layers x 6,144 tokens: 9.66 GB) compiled as the chip will run them. Both
    loops are loops of the program: ONE ``paged_decode`` call and ONE
    ``paged_write`` call in its text, ONE ``paged_chunk`` call where a row may
    feed more than one token (the stacked pool's layer is block numbers moved
    before the call), no scatter under ``kv_write`` and no gather.
    The pool is written and read in place at a traced layer index: one
    layout of it (its stacked form and the same bytes seen as layers x blocks),
    no copy, no temporary the size of one cache layer; the stacked weights
    are not copied either (held as three matrices, q, k and v were: 1.21 GB).
    And the figures that sized the pool: a GB and more free under each."""
    on_the_chip(monkeypatch)
    cfg, eng = ouro_engine
    nb = cfg["engine"]["num_blocks"]
    assert (eng.T, eng.megastep_k) == (256, 8)
    compiled = compiled_program(eng, cfg, kind, chip)
    text = compiled.as_text()
    assert kernel_calls(text, "paged_decode") == 1 and kernel_calls(text, "paged_write") == 1
    assert kernel_calls(text, "paged_chunk") == int(kind != "mega_K8")
    assert "kv_write/scatter" not in text and "kv_gather" not in text
    pool = (192, nb, 16, eng.bs, 128)
    assert eng.caches[0].shape == (192, 2) + pool[2:] and nb * eng.bs == 6144
    stacked = ",".join(map(str, pool))
    flat = ",".join(map(str, (pool[0] * pool[1],) + pool[2:]))
    orders = set(re.findall(rf"bf16\[(?:{stacked}|{flat})\]\{{([0-9,]+)", text))
    assert orders == {"4,3,2,1,0", "3,2,1,0"}, orders
    assert not re.search(rf"= bf16\[(?:{stacked}|{flat})\][^\n]* copy\(", text)
    assert not re.search(r"= bf16\[48,[0-9,]+\][^\n]* copy\(", text)
    mem, _, said = fits_as_the_file_says(cfg, kind, compiled, margin=10 ** 9)
    one_cache_layer = math.prod(pool[1:]) * 2
    assert mem.temp_size_in_bytes < one_cache_layer, mem.temp_size_in_bytes
    assert said["temporaries"] < one_cache_layer
