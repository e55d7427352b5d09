"""Test configuration.

Tests run on a virtual 8-device CPU mesh (the analog of the reference's
CPU-only stub build, /root/reference/paddle/cuda/include/stub/, which lets
the whole suite run without accelerators): sharding/collective tests get 8
devices; numerics match the TPU path because both are XLA.

The two environment variables that make the CPU backend stand in for
eight devices are set by paddle_tpu.utils.backend_guard, which the dry-run
entry points share.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.utils.backend_guard import ensure_cpu_mesh  # noqa: E402

ensure_cpu_mesh(8)

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)
# persistent compilation cache: repeat suite runs skip recompiling the
# big jitted steps (~30% wall-clock on warm cache). It lives where every
# entry point's does — JAX_COMPILATION_CACHE_DIR where set, else the fixed
# in-checkout directory — and a cold cache is merely the old speed
from paddle_tpu.observability.compile_log import resolve_cache_dir  # noqa: E402

jax.config.update("jax_compilation_cache_dir", resolve_cache_dir())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

assert len(jax.devices()) == 8, (
    "test suite expects 8 virtual CPU devices; got "
    f"{jax.devices()} — check conftest ordering"
)


@pytest.fixture(autouse=True)
def _flags_restored_after_test():
    """FLAGS is one process-wide object and an xdist worker runs many
    files: whatever a test sets is put back when it ends, so no file's
    outcome depends on which files ran before it on the same worker.
    (A module's shared fixture builds `_Flags()` of its own and hands
    them to `Trainer`: this restore runs per test.)"""
    from paddle_tpu.utils.flags import FLAGS

    saved = dict(vars(FLAGS))
    yield
    vars(FLAGS).clear()
    vars(FLAGS).update(saved)
