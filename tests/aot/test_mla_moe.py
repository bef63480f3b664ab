"""openpangu-ultra-moe-718b.serve1: latent attention over sixteen held experts a
layer; the mixed scan."""
import pytest

from described_device import (V5E_BYTES_LIMIT, compiled_program, engine_of, kernel_calls,
                              live_bytes, on_the_chip)


@pytest.fixture(scope="module")
def pangu_engine():
    """openPangu-Ultra-MoE-718B's stage at openpangu-ultra-moe-718b.serve1's geometry."""
    return engine_of("benchmark/configs/openpangu-ultra-moe-718b.serve1.json")


def test_a_latent_expert_models_mixed_scan_is_no_larger_than_with_the_loop(
        chip, pangu_engine, monkeypatch):
    """The mixed scan of openpangu-ultra-moe-718b.serve1 compiled as the chip
    will run it: ``expert_gmm`` three times a sparse layer (gate, up, down: no
    tile loop), and the program no larger than the largest PR 26 compiled with
    the loop (the prefill step's 13.22 GB)."""
    on_the_chip(monkeypatch)
    cfg, eng = pangu_engine
    compiled = compiled_program(eng, cfg, "mixed_K8", chip)
    sparse = sum("router" in lw for lw in eng._weights["layers"])
    assert sparse and kernel_calls(compiled.as_text(), "expert_gmm") == 3 * sparse
    live = live_bytes(compiled.memory_analysis())
    assert live <= 13_223_340_544 < V5E_BYTES_LIMIT, live


@pytest.mark.parametrize("kind", ["step_prefill_T512", "mixed_K8"])
def test_a_latent_models_rows_attend_in_the_kernel(chip, pangu_engine, kind, monkeypatch):
    """ISSUE 43: the prefill step and the mixed scan hold ONE ``latent_rows``
    call a layer (chunk rows and one-token rows alike: no selection), none of
    the XLA loops' score temporaries (a chunk row's ``[mq, 128, 512]``, the
    one-token rows' ``[64, 128, 512]``, float32), and fit as the configuration
    file says (its ``live`` is an upper bound since: the files are the
    benchmark's)."""
    on_the_chip(monkeypatch)
    cfg, eng = pangu_engine
    compiled = compiled_program(eng, cfg, kind, chip)
    text = compiled.as_text()
    assert kernel_calls(text, "latent_rows") == len(eng._weights["layers"]) == 5
    assert "f32[64,128,512]" not in text and "f32[512,128,512]" not in text
    said = cfg["memory"]["compiled_for_v5e"][kind]
    live = live_bytes(compiled.memory_analysis())
    assert 0.9 * said["live"] < live <= said["live"], (live, said)
