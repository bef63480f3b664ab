"""mistral-7b-v0.3.serve1: twelve dense GQA layers, 4 query heads a key/value
head of 128; the two programs whose rows may feed more than one token."""
import re

import pytest

from described_device import (compiled_program, engine_of, fits_as_the_file_says,
                              kernel_calls, on_the_chip)

# ``live`` as the programs compile since ISSUE 46.  The file's (the benchmark's,
# which that PR could not edit) date from ISSUE 23, when the attention was XLA's
# tile loops: 15.50 GB under the prefill step and 14.25 under the mixed scan
LIVE_SINCE_ISSUE_46 = {"step_prefill_T256": 9_019_142_144, "mixed_K8": 9_019_469_824}


@pytest.fixture(scope="module")
def mistral_engine():
    """Mistral-7B-v0.3's first twelve layers at mistral-7b-v0.3.serve1's geometry."""
    return engine_of("benchmark/configs/mistral-7b-v0.3.serve1.json")


@pytest.mark.parametrize("kind", sorted(LIVE_SINCE_ISSUE_46))
def test_a_dense_models_chunk_rows_attend_in_one_kernel_a_layer(chip, mistral_engine, kind,
                                                                 monkeypatch):
    """The prefill step and the mixed scan of mistral7b.serve.batch and
    .decode-heavy compiled as the chip will run them: a cache layer is ONE
    ``paged_write``, ONE ``paged_decode`` and ONE ``paged_chunk`` call, twelve of
    each a program; no loop over chunk rows or context blocks is left under
    ``paged_attention`` (no ``kv_gather``), a pool array ``[1024, 8, 64, 128]``
    keeps ONE layout and is copied in or out of no program; ``arguments`` are the
    configuration file's ``memory.compiled_for_v5e`` and ``live`` no more than
    it says."""
    on_the_chip(monkeypatch)
    cfg, eng = mistral_engine
    nb, bs = cfg["engine"]["num_blocks"], eng.bs
    assert (eng.B, eng.T, eng.P, eng.megastep_k, eng.pc) == (32, 256, 40, 8, 64)
    compiled = compiled_program(eng, cfg, kind, chip)
    text = compiled.as_text()
    for kernel in ("paged_write", "paged_decode", "paged_chunk"):
        assert kernel_calls(text, kernel) == 12, kernel
    assert "kv_gather" not in text and "paged_attention/while" not in text
    pool = rf"bf16\[{nb},8,{bs},128\]"
    assert set(re.findall(pool + r"\{([0-9,]+)", text)) == {"3,2,1,0"}
    assert not re.search(rf"= {pool}[^\n]* copy\(", text)
    fits_as_the_file_says(cfg, kind, compiled, margin=10 ** 9,
                          live_now=LIVE_SINCE_ISSUE_46[kind])
