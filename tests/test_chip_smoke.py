"""chip_smoke.py's train and serve phases at a tiny width on the CPU, where the
platform picks the jnp attention reference, and the script's own refusal to
pass without a chip. The steering is here: the script has no CPU option."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
            num_attention_heads=2, max_position_embeddings=256)


@pytest.fixture(autouse=True)
def _no_mesh():
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    yield
    set_hybrid_communicate_group(None)


@pytest.fixture(scope="module")
def meter():
    return chip_smoke.CompileMeter()


def test_train_phase(tmp_path, meter):
    spec = dict(model=dict(TINY, num_hidden_layers=2, dtype="bfloat16",
                           recompute=True),
                reduced={}, batch=2, seq=32, steps=2, lr=1e-3, entry_tol=0.05)
    out = chip_smoke.phase_train(spec, str(tmp_path), meter)
    assert out["losses_call"][-1] < out["losses_call"][0]
    assert out["tpu_custom_call_in_step"] is False      # CPU: the reference
    assert out["programs_compiled"] >= 2
    json.dumps(out)


def test_serve_phase(meter):
    spec = dict(model=dict(TINY, num_hidden_layers=1), reduced={},
                engine=dict(max_batch_size=4, max_seq_len=128, block_size=8,
                            token_budget=16, num_blocks=64, megastep_k=4),
                first=(40, 24), staggered=[(12, 8), (30, 8)], last=(20, 8),
                spec_k=3, spec_request=(48, 8), spec_period=6,
                logit_tol=1e-3)     # float32 here
    out = chip_smoke.phase_serve(spec, meter)
    assert out["counters"]["megasteps_mixed"] >= 1
    assert out["counters"]["megasteps"] > out["counters"]["megasteps_mixed"]
    assert out["spec"]["verify_forwards"] >= 1
    assert out["tokens_served"] == 24 + 8 + 8 + 8 + 8 * out["spec"]["requests"]
    json.dumps(out)


def test_mesh_phase(tmp_path, meter, monkeypatch):
    """dp 2 x mp 2 on four of the virtual CPU devices, with the attention
    sent through the shard_map the kernel needs on a mesh (around the jnp
    reference here, since the CPU has no kernel)."""
    monkeypatch.setenv("PADDLE_TPU_ATTN", "pallas")
    spec = dict(model=dict(TINY, num_hidden_layers=2, dtype="bfloat16",
                           recompute=True),
                reduced={}, batch=4, seq=32, steps=2, lr=1e-3, loss_tol=0.05)
    out = chip_smoke.phase_mesh(spec, str(tmp_path), meter)
    assert out["q_proj_spec"] == "PartitionSpec(None, 'mp')"
    assert len(out["q_proj_shard_devices"]) == 4
    assert out["collectives"]["all-reduce"] >= 1
    assert out["max_gap"] <= 0.05
    json.dumps(out)


def test_the_output_check_can_fail(serving_model):
    """Tokens the model would not emit are far below the reference's best."""
    import paddle_tpu as P
    from paddle_tpu.inference.control_plane import RequestResult, RequestStatus

    wrong = RequestResult(0, RequestStatus.COMPLETED, tokens=[1, 2, 3, 4],
                          logprobs=[0.0] * 4)
    with pytest.raises(chip_smoke.CheckFailed, match="below the reference"):
        chip_smoke._check_tokens(P.jit.to_static(serving_model), 16,
                                 [5, 6, 7, 8, 9], wrong, tol=1e-3)


def _run_script(tmp_path, body=None):
    script = os.path.join(REPO, "chip_smoke.py")
    if body is not None:
        script = str(tmp_path / "run.py")
        with open(script, "w") as f:
            f.write(body)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, script], cwd=str(tmp_path), env=env,
                       capture_output=True, text=True, timeout=120)
    return r.returncode, json.loads(r.stdout.strip().splitlines()[-1])


def test_script_fails_without_a_chip(tmp_path):
    rc, last = _run_script(tmp_path)
    assert rc != 0
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert "needs a TPU" in last["error"]


def test_script_fails_when_a_phase_raises(tmp_path):
    """Past the platform check (faked here) a phase that raises ends the run:
    non-zero, ``"ok": false`` last, no later phase."""
    rc, last = _run_script(tmp_path, body=(
        "import chip_smoke, jax\n"
        "class D:\n"
        "    platform, device_kind = 'tpu', 'fake'\n"
        "jax.devices = lambda *a: [D()]\n"
        "def boom():\n"
        "    raise RuntimeError('phase blew up')\n"
        "chip_smoke.phase_device = boom\n"
        "chip_smoke.phase_kernels = lambda: {'phase': 'must not run'}\n"
        "chip_smoke.main([])\n"))
    assert rc != 0
    assert last["ok"] is False and "phase blew up" in last["error"]
