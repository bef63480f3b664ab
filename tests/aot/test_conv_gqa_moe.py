"""lfm2-24b-a2b.serve1: conv layers with state a slot, GQA at heads of 64, 64
experts; six programs."""
import re

import jax.numpy as jnp
import pytest

from described_device import (V5E_BYTES_LIMIT, compiled_program, engine_of,
                              fits_as_the_file_says, kernel_calls, on_the_chip)

LFM2_PROGRAMS = ("step_prefill_T512", "step_decode", "mixed_K8", "mega_K2", "mega_K4",
                 "mega_K8")
# ``live`` as the programs compile since ISSUE 41.  The file's (the
# benchmark's, which that PR could not edit) are those of a pool a head a row
# of 64 lanes: 12.18 GB in the steps and 12.98-12.99 in the scans, of which
# the four pool arrays in a second layout and the XLA attention's gathered
# copies were 0.73 GB in the steps and 1.53 in the scans.  ``arguments`` stand
# to the byte: ``[2048, 4, 64, 128]`` is the same 134 MB as ``[2048, 8, 64, 64]``
LIVE_SINCE_ISSUE_41 = {"step_prefill_T512": 11_450_521_088, "step_decode": 11_447_775_232,
                       "mixed_K8": 11_453_187_072, "mega_K2": 11_452_613_120,
                       "mega_K4": 11_452_489_216, "mega_K8": 11_452_494_848}


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
    run them: heads of 64 lie two to a lane tile of the pool (ISSUE 41), so
    each attention layer's one-token rows take ``paged_decode``, its write
    ``paged_write`` and (the prefill step, the mixed scan) its chunk rows
    ``paged_chunk`` over the packed problem, once a layer; a pool array ``[2048, 4, 64, 128]`` keeps
    ONE layout, the argument's row-major order, and is copied in or out of no
    program (a head a row, ``[2048, 8, 64, 64]``, the compiler gave it a
    layout of its own and copied each array in and out of every program: 8
    copies of 134 MB a launch); the state a slot is donated and updated in
    place, never copied whole but ONCE in the decode scan's body (8.4 MB:
    what a row the scan has frozen keeps); and the largest program leaves
    5.4 GB of the chip free.  ``arguments`` are the configuration file's
    ``memory.compiled_for_v5e`` and ``live`` no more than it says.  The expert
    layer is three grouped products (ISSUE 39): ``expert_gmm`` three times a
    sparse layer."""
    on_the_chip(monkeypatch)
    cfg, eng = lfm2_engine
    nb, bs = cfg["engine"]["num_blocks"], eng.bs
    assert (eng.B, eng.T, eng.P, eng.megastep_k, eng.pc) == (128, 512, 44, 8, 64)
    assert [[a.shape for a in c] for c in eng.caches] == [[(2, 4, bs, 128)] * 2] * 2
    (state,) = eng.slot_state
    assert state.shape == (8, 128, 2, 2048) and state.dtype == jnp.bfloat16
    compiled = compiled_program(eng, cfg, kind, chip)
    text = compiled.as_text()
    attention = len(eng.caches[0])
    assert kernel_calls(text, "paged_decode") == kernel_calls(text, "paged_write") == attention
    assert kernel_calls(text, "paged_chunk") == (
        attention if kind in ("step_prefill_T512", "mixed_K8") else 0)
    assert "kv_gather" not in text and "paged_attention/while" not in text
    state_copies = len(re.findall(r"= bf16\[8,128,2,2048\][^\n]* copy\(", text))
    assert state_copies == (1 if kind.startswith("mega") else 0)
    pool = rf"bf16\[{nb},4,{bs},128\]"
    assert set(re.findall(pool + r"\{([0-9,]+)", text)) == {"3,2,1,0"}
    assert not re.search(rf"= {pool}[^\n]* copy\(", text)
    mem, live, said = fits_as_the_file_says(cfg, kind, compiled, margin=5.4e9,
                                            live_now=LIVE_SINCE_ISSUE_41[kind])
    assert 0.25 * V5E_BYTES_LIMIT < live
    assert said["arguments"] == mem.argument_size_in_bytes
    sparse = sum("router" in lw for lw in eng._weights["layers"])
    assert kernel_calls(text, "expert_gmm") == 3 * sparse
