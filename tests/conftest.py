"""Test harness config.

Runs the whole suite on CPU with 8 virtual XLA devices so multi-chip sharding
paths compile and execute without TPU hardware — the same trick the reference
uses with its fake custom_cpu plugin device
(/root/reference/test/custom_runtime/test_custom_cpu_plugin.py:23).
"""
import os

# the suite runs on the CPU: JAX_PLATFORMS=cpu is honoured, and it is set
# here so a bare `pytest` on a chip host does not claim the chip; opt out
# with PADDLE_TPU_TEST_ON_TPU=1
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

if os.environ.get("PADDLE_TPU_TEST_ON_TPU") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax  # noqa: F401

    # Persistent XLA compilation cache for the tests' SUBPROCESSES, by the
    # rule of paddle_tpu.jit.compile_cache: the variable where it is set,
    # else the one fixed directory in the checkout.  The fleet and
    # standalone-serving tests each pay a jax import + engine first-step
    # compile per spawned worker; every worker after the first hits the
    # disk cache.  Set AFTER `import jax` above, deliberately: jax reads the
    # variable at import, so the pytest process itself keeps the cache off
    # (the described-device compiles of tests/aot/ must not be
    # written to it, and turning it on for the whole suite is ROADMAP D6's
    # decision, not a side effect).
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     ".jax_cache"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "quick: fast cross-subsystem verification tier (~3 min total; "
        "run with -m quick to re-check a round's claims without the full "
        "suite)")
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 'not slow' run. A case is marked only "
        "if it still takes over 60 s alone after its set-up was shared and "
        "its forward traced once, it is no serving, training, kernel or "
        "benchmark test of a family the benchmark has (subprocess-spawning "
        "fleet tests, the vision zoo, the 4-D pipeline demos), and CI's "
        "shards run its file without the filter, so it still gates merges")


@pytest.fixture(autouse=True)
def _seed_everything():
    import paddle_tpu as P

    P.seed(2024)
    np.random.seed(2024)
    yield


def _mappings() -> int:
    """This process's memory mappings (0 where /proc says nothing)."""
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """A compiled CPU program holds memory mappings for as long as it lives
    (an engine's set of programs some 2,700 of them), jax's caches and the
    engines' process-wide ``_PROGRAM_CACHE`` keep every program a test built,
    and the kernel gives a process 65,530 (``vm.max_map_count``): a worker
    that had run three or four engine-heavy files died of a segmentation fault
    or an abort inside XLA's NEXT compile or serialize, whichever test that
    fell in (tests/test_smallthinker.py, test_lfm2_moe.py, test_megastep.py and
    then test_serving_engine.py in one process reach it at the 102nd test, on
    PR 47's tree as on PR 48's; PERF.md section 6, PR 48 (9)).  Past 20,000 after a
    module both caches are let go of: live engines keep their own programs,
    anything else compiles again when it is next called."""
    yield
    if _mappings() > 20_000:
        import gc

        import jax
        from paddle_tpu.inference import serving

        serving._PROGRAM_CACHE.clear()
        jax.clear_caches()
        gc.collect()


@pytest.fixture(scope="session")
def serving_model():
    """The canonical sub-tiny serving-test model (1 layer, 64 hidden,
    vocab 256, seed 11), built ONCE per pytest session (ROADMAP item 6,
    tier-1 budget).  Five serving test files used to build this exact
    config per-module — five identical weight inits and five jax
    dispatch warmups inside the 870 s tier-1 cliff.  Module fixtures
    delegate here (and re-clear any leaked topology group themselves);
    the weights are seeded at build, so sharing the instance changes no
    reference tokens.  Treat it as READ-ONLY: a test that must mutate
    weights (bfloat16(), load_state) builds its own copy.  The one thing
    tests leave on it is ``generate``'s own memo of its traced forward
    (``_decode_cache["_static_fwd"]``, through the files' ``ref_greedy``):
    it is what makes that reference two programs a shape, it reads the
    weights where they are, and no engine looks at it."""
    import paddle_tpu as P
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    set_hybrid_communicate_group(None)
    P.seed(11)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=160,
        num_hidden_layers=1, num_attention_heads=2,
        max_position_embeddings=256))
    m.eval()
    return m


@pytest.fixture
def host_spans(tmp_path):
    """``with host_spans("engine.", "frontend.") as spans:`` profiles the
    block the way the benchmark does (host spans on, no Python frames); after
    it ``spans`` holds [(name, start_ns, end_ns, stats)] of the
    ``RecordEvent``s with those prefixes, read back through ``ProfileData``."""
    import contextlib
    import glob

    @contextlib.contextmanager
    def traced(*prefixes):
        import jax
        from jax.profiler import ProfileData

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        spans = []
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            yield spans
        finally:
            jax.profiler.stop_trace()
        pb = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
        spans += sorted(
            ((e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(pb[-1]).planes
             for line in plane.lines for e in line.events
             if e.name.startswith(prefixes)), key=lambda e: (e[1], -e[2]))

    return traced
