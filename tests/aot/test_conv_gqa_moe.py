"""lfm2-24b-a2b.serve1: conv layers with state a slot, GQA at heads of 64, 64
experts; six programs."""
import re

import jax.numpy as jnp
import pytest

from described_device import (V5E_BYTES_LIMIT, compiled_program, engine_of,
                              fits_as_the_file_says, kernel_calls, on_the_chip)

LFM2_PROGRAMS = ("step_prefill_T512", "step_decode", "mixed_K8", "mega_K2", "mega_K4",
                 "mega_K8")


@pytest.fixture(scope="module")
def lfm2_engine():
    """LFM2-24B-A2B's first ten layers at lfm2-24b-a2b.serve1's geometry."""
    return engine_of("benchmark/configs/lfm2-24b-a2b.serve1.json")


@pytest.mark.parametrize("kind", LFM2_PROGRAMS)
def test_a_conv_state_models_programs_fit_the_chip(chip, lfm2_engine, kind, monkeypatch):
    """The six programs lfm2-24b.serve.chat-batch can reach (the step at a
    prefill's and at a decode's ``mq``, the decode scan at K 2, 4 and 8, the
    mixed scan) of lfm2-24b-a2b.serve1 (5.27 B parameters with every expert of
    eight layers, a pool of 2,048 blocks x keys and values x two attention
    layers, conv state ``[8, 128, 2, 2048]`` a slot) compiled as the chip will
    run them: heads of 64 take the XLA attention and the scatter (no
    ``paged_decode`` / ``paged_write`` custom call); the state a slot is
    donated and updated in place, never copied whole but ONCE in the decode
    scan's body (8.4 MB: what a row the scan has frozen keeps); a pool array
    ``[2048, 8, 64, 64]``, whose last axis is half a lane tile, gets a device
    layout of the compiler's own and is copied ONCE into the layout the
    scatter and the gather want and once back, outside the scan's loop (8
    copies of 134 MB a launch, 0.5 GB of temporaries: ROADMAP's speed item for
    heads of 64; one more copy would be a copy an iteration); and the largest
    program leaves 1.5 GB of the chip free.  The figures are the configuration
    file's ``memory.compiled_for_v5e``.  The expert layer is three grouped
    products (ISSUE 39): ``expert_gmm`` three times a sparse layer, and the
    mixed scan, the largest program of the window, no larger than it was with
    the tile loop (PR 38's 12.99 GB)."""
    on_the_chip(monkeypatch)
    cfg, eng = lfm2_engine
    nb, bs = cfg["engine"]["num_blocks"], eng.bs
    assert (eng.B, eng.T, eng.P, eng.megastep_k, eng.pc) == (128, 512, 44, 8, 64)
    assert [[a.shape for a in c] for c in eng.caches] == [[(2, 8, bs, 64)] * 2] * 2
    (state,) = eng.slot_state
    assert state.shape == (8, 128, 2, 2048) and state.dtype == jnp.bfloat16
    compiled = compiled_program(eng, cfg, kind, chip)
    text = compiled.as_text()
    assert "paged_decode" not in text and "paged_write" not in text
    state_copies = len(re.findall(r"= bf16\[8,128,2,2048\][^\n]* copy\(", text))
    assert state_copies == (1 if kind.startswith("mega") else 0)
    assert len(re.findall(rf"= bf16\[{nb},8,{bs},64\][^\n]* copy\(", text)) <= 8
    mem, live, said = fits_as_the_file_says(cfg, kind, compiled, margin=1.5e9)
    assert 0.25 * V5E_BYTES_LIMIT < live
    assert said["arguments"] == mem.argument_size_in_bytes
    sparse = sum("router" in lw for lw in eng._weights["layers"])
    assert kernel_calls(text, "expert_gmm") == 3 * sparse
    if kind == "mixed_K8":
        assert live <= 12_986_028_032, live
