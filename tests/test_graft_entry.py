"""The driver-facing entry points, tested the way the driver runs them.

Round 1's dryrun hung while 90 tests passed — because nothing tested
__graft_entry__ itself. These tests run it in SUBPROCESSES and enforce a
hard wall-clock budget. The dry runs get no pre-set platform: they ask
for their own virtual CPU mesh.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, timeout, extra_env=None):
    env = dict(os.environ)
    # no pre-forced platform or device count: the entry point asks for
    # its own virtual CPU mesh (utils/backend_guard.ensure_cpu_mesh)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO
    if extra_env:
        env.update(extra_env)
    return subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_dryrun_multichip_under_budget():
    out = _run(
        "import __graft_entry__ as g; g.dryrun_multichip(8)",
        timeout=240,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_entry_compiles_single_device():
    code = (
        "from paddle_tpu.utils.backend_guard import ensure_cpu_mesh;"
        "ensure_cpu_mesh(1);"
        "import __graft_entry__ as g, jax;"
        "fn, args = g.entry();"
        "out = jax.jit(fn)(*args);"
        "print('shape', out.shape)"
    )
    out = _run(code, timeout=240)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "shape" in out.stdout
