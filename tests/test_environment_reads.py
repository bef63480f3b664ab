"""Which ``PADDLE_TPU_*`` environment variables the package reads, and why.

A kernel or code path chosen by a variable that a model layer reads while it
is traced is a path nobody can see from the call (ROADMAP D5): a variable
stays only as a deployment setting (a path, a data source, a fault spec) or
as a written debt. Anything else is decided by the code from what it can
observe, or measured and made the path."""
import ast
import glob
import os
import re

import paddle_tpu

# name -> (the files that read it, why they may)
ALLOWED = {
    # deployment settings
    "PADDLE_TPU_FAULTS": ("inference/faults.py distributed/rpc/__init__.py",
                          "the chaos harness's fault spec, JSON; rpc workers inherit it"),
    "PADDLE_TPU_PRETRAINED_HOME": ("vision/models/_utils.py", "where weights are cached"),
    "PADDLE_TPU_SYNTHETIC_DATA": ("vision/datasets.py", "no network: synthetic samples"),
    "PADDLE_TPU_SYNTHETIC_N": ("vision/datasets.py", "how many synthetic samples"),
    "PADDLE_TPU_PROFILE_DIR": ("profiler/__init__.py", "where traces are written"),
    "PADDLE_TPU_AUTOTUNE_CACHE": ("ops/pallas/autotune.py", "where tuned block sizes are kept"),
    # debts (ROADMAP D5)
    "PADDLE_TPU_ATTN": ("nn/functional/flash_attention.py",
                        "the platform chooses the kernel; tests/test_chip_smoke.py runs the "
                        "kernel path on the CPU through it"),
    "PADDLE_TPU_VPP_INTERLEAVED": ("distributed/fleet/meta_parallel/compiled_pipeline.py",
                                   "three pipeline ticks the tests hold to each other; no "
                                   "four-chip cell says which wins yet"),
    "PADDLE_TPU_VPP_INTERLEAVED_IMPL": ("distributed/fleet/meta_parallel/compiled_pipeline.py",
                                        "as PADDLE_TPU_VPP_INTERLEAVED"),
}
NAME = re.compile(r"PADDLE_TPU_[A-Z0-9_]+")


def _docstrings(tree):
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef, ast.AsyncFunctionDef)):
            body = node.body
            if body and isinstance(body[0], ast.Expr) and isinstance(body[0].value, ast.Constant):
                out.add(id(body[0].value))
    return out


def test_the_package_reads_only_the_listed_variables():
    """Every string constant in the package's code that IS such a name (what
    ``os.environ.get``, ``os.environ[...]``, ``os.getenv`` or a constant handed
    to them is given) stands in the list above, in the files the list names."""
    root = os.path.dirname(paddle_tpu.__file__)
    found = {}
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        tree = ast.parse(open(path).read())
        docs = _docstrings(tree)
        for node in ast.walk(tree):
            if (isinstance(node, ast.Constant) and isinstance(node.value, str)
                    and id(node) not in docs and NAME.fullmatch(node.value)):
                found.setdefault(node.value, set()).add(
                    os.path.relpath(path, root).replace(os.sep, "/"))
    unlisted = {k: sorted(v) for k, v in found.items() if k not in ALLOWED}
    assert not unlisted, f"environment switches outside the written list: {unlisted}"
    assert found == {k: set(files.split()) for k, (files, _) in ALLOWED.items()}
