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
