"""deepseek-v3.2-exp.serve1: latent attention under a learned selection, three
programs."""
import re

import pytest

from described_device import (compiled_program, engine_of, fits_as_the_file_says, kernel_calls,
                              on_the_chip)


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
    # ISSUE 43: the chunk rows attend in ``latent_rows`` (a decode scan's rows
    # gather their selection: no kernel), so the loops' scores are gone from
    # the prefill step, whose 512-token chunk row held 0.52 GB of them: it
    # compiles to 12.17 GB live where the file (the benchmark's) says 12.69; the
    # mixed scan's temporaries rose 0.03 GB with the mask's realigned rows, inside
    # the file's figure to 1 %
    layers = len(eng._weights["layers"])
    assert kernel_calls(text, "latent_rows") == (0 if kind == "mega_K8" else layers)
    assert "f32[64,128,512]" not in text and "f32[512,128,512]" not in text
    fits_as_the_file_says(cfg, kind, compiled, margin=1.5e9,
                          live_now={"step_prefill_T512": 12_166_132_224}.get(kind))
