"""Programs of the main paths compiled at real widths for a TPU v5e that is
described and not attached (libtpu's compiler is installed wherever jax[tpu]
is). Interpret mode cannot see what this sees: a block that does not fit VMEM,
a slice the tiling refuses, a pool the compiler copies. Nothing runs, so this
says nothing about results or speed, and a pass here is not a chip run.

One file a family (``described_device.py`` holds what they share), so that the
scheduler can give each to a worker of its own."""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")
# a second process that describes a topology while the first holds libtpu's
# lock would skip every case: five files on five workers make that usual
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to compile against
        pytest.skip(f"cannot describe a TPU v5e topology: {e!r}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A described-device executable is written to the persistent cache but
    cannot be read back without a chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
