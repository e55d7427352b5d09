"""A virtual CPU mesh for the tests and the multi-device dry runs.

The CPU backend stands in for N devices when asked before it starts:
two environment variables, read once when jax initialises its backends.
Role analog in the reference: the CPU-only stub build
(/root/reference/paddle/cuda/include/stub/) that lets everything run
without accelerators.
"""

from __future__ import annotations

import os


def ensure_cpu_mesh(n_devices: int = 8) -> None:
    """Put jax on the CPU platform with ``n_devices`` virtual devices.

    Call before anything initialises a jax backend (importing jax is
    fine). Raises when the backend is already up with fewer devices —
    that cannot be repaired from inside the process."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    key = "--xla_force_host_platform_device_count="
    flags = os.environ.get("XLA_FLAGS", "").split()
    have = max((int(f[len(key):]) for f in flags if f.startswith(key)),
               default=0)
    if have < n_devices:  # a caller's larger mesh stays as it is
        flags = [f for f in flags if not f.startswith(key)]
        os.environ["XLA_FLAGS"] = " ".join(flags + [f"{key}{n_devices}"])

    import jax

    # jax read JAX_PLATFORMS at import; if that was before the line above,
    # set the config it fed (a no-op otherwise)
    jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    if len(devices) < n_devices or devices[0].platform != "cpu":
        raise RuntimeError(
            f"ensure_cpu_mesh({n_devices}) came too late: the jax backend "
            f"is already up with {devices}"
        )
