"""Honest-surface accounting: every public name that
resolves but raises NotImplementedError is listed HERE, and the ledger must
only SHRINK. A name leaving stub-hood must be deleted from the ledger (the
test fails if a listed name stops raising), so "surface closed" claims stay
behavioral, not hasattr-deep.

History: r4's honest stub list had 12 entries.
r5 graduated: block_multihead_attention, fused_multi_transformer,
static.py_func (see GRADUATED below; more move as the round progresses).
"""
import numpy as np
import pytest

import paddle_tpu as P

pytestmark = pytest.mark.quick

# (import path, attribute, minimal call) — call must raise NotImplementedError.
# r5 closed EVERY entry from r4's honest stub list: the ledger is
# empty.
KNOWN_STUBS = []

# r4 stubs that must now be REAL (regression guard: resolving is no longer
# enough — these must not raise NotImplementedError on resolution). Behavior
# tests: test_paged_attention, test_fused_multi_transformer, test_static_nn,
# test_varlen_attention, test_detection_ops, test_last_stubs.
GRADUATED = [
    ("paddle_tpu.incubate.nn.functional", "block_multihead_attention"),
    ("paddle_tpu.incubate.nn.functional", "fused_multi_transformer"),
    ("paddle_tpu.incubate.nn.functional", "fused_multi_head_attention"),
    ("paddle_tpu.static", "py_func"),
    ("paddle_tpu.nn.functional.flash_attention", "flash_attn_unpadded"),
    ("paddle_tpu.nn.functional.extra", "flash_attn_varlen_qkvpacked"),
    ("paddle_tpu.nn.functional.extra", "flash_attention_with_sparse_mask"),
    ("paddle_tpu.nn.functional.extra", "sparse_attention"),
    ("paddle_tpu.vision.ops", "generate_proposals"),
    ("paddle_tpu.vision.ops", "yolo_loss"),
    ("paddle_tpu.vision.ops", "decode_jpeg"),
    ("paddle_tpu.incubate", "inference"),
]


def _resolve(mod_path, attr):
    import importlib

    mod = importlib.import_module(mod_path)
    return getattr(mod, attr)


class TestStubLedger:
    def test_ledger_entries_are_genuine_stubs(self):
        for mod_path, attr, call in KNOWN_STUBS:
            fn = _resolve(mod_path, attr)
            with pytest.raises(NotImplementedError):
                call(fn)

    def test_ledger_only_shrinks(self):
        # the committed ceiling; lower it whenever a stub graduates
        assert len(KNOWN_STUBS) == 0

    def test_graduated_names_are_callable_objects(self):
        for mod_path, attr in GRADUATED:
            fn = _resolve(mod_path, attr)
            assert callable(fn)
            # none of these may be a bare raise-stub: their behavior tests
            # live in test_paged_attention / test_fused_multi_transformer /
            # test_static_nn
