"""Which platform a run lands on — chosen once, said once, never silent.

``--use_tpu`` (default true) used to do nothing: jax picked whatever it
found, so on a machine with no chip `paddle train` quietly trained on the
CPU. Now the flag is a requirement: with it set and ``JAX_PLATFORMS`` not
set by the caller, the run ends up on a TPU or fails at start-up naming
the reason. An explicit ``JAX_PLATFORMS`` (``cpu`` in the tests and the
verify recipe) is the caller's own choice and is left alone.
"""

from __future__ import annotations

import os
import sys
from typing import Any, Dict, Optional, Set, Tuple

from paddle_tpu.utils.logging import logger

INTERPRET_ENV = "PADDLE_TPU_PALLAS_INTERPRET"


def select_platform(use_tpu: bool) -> None:
    """Set ``JAX_PLATFORMS`` from ``--use_tpu`` unless the caller already
    did. jax-free; runs before the first backend initialises (best before
    jax is imported at all: it reads the variable once at import)."""
    if os.environ.get("JAX_PLATFORMS"):
        return
    platform = "tpu" if use_tpu else "cpu"
    os.environ["JAX_PLATFORMS"] = platform
    if "jax" in sys.modules:
        # already imported (api.initPaddle): the variable was read at
        # import, so set the config it fed — still before backend init
        sys.modules["jax"].config.update("jax_platforms", platform)


def device_stamp() -> Dict[str, Any]:
    """The device as jax reports it — what every compile record and
    every bench line is stamped with."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def pallas_mode() -> Optional[str]:
    """How a Pallas kernel would run here: ``"compiled"`` on a TPU
    backend, ``"interpret"`` off it when ``PADDLE_TPU_PALLAS_INTERPRET=1``
    (the CPU parity tests' way to run a kernel body), else None — no
    kernel, the caller takes its XLA path. On a TPU backend the variable
    is ignored: a kernel never runs interpreted there unnoticed
    (:func:`describe_devices` says so at start-up)."""
    import jax

    if jax.default_backend() == "tpu":
        return "compiled"
    return "interpret" if os.environ.get(INTERPRET_ENV) == "1" else None


def why_no_pallas() -> str:
    """The reason a selection site gives when :func:`pallas_mode` is None."""
    import jax

    return f"backend is {jax.default_backend()}, not tpu"


_said: Set[Tuple[str, str, str]] = set()


def log_selection(site: str, layer: str, choice: str) -> None:
    """One debug line per (site, layer, outcome): which way a kernel
    selection went and why. Selection is legitimate and silent by
    default, which means "I set pallas_rnn=True" proves nothing — this
    line (and the compiled HLO) is where it shows."""
    key = (site, layer, choice)
    if key not in _said:
        _said.add(key)
        logger.debug("%s %s: %s", site, layer, choice)


def describe_devices(who: str) -> Dict[str, Any]:
    """Initialise the backend and log platform / device_kind / count once
    for ``who`` (trainer, server). A backend that cannot initialise —
    ``--use_tpu`` on a machine with no chip — ends the run here with the
    reason, not later on some other platform."""
    try:
        desc = device_stamp()
    except RuntimeError as e:
        raise SystemExit(
            f"error: {who}: no usable {os.environ.get('JAX_PLATFORMS') or 'jax'}"
            f" backend: {e}\n(--use_tpu=1, the default, requires a TPU; pass "
            "--use_tpu=0 or set JAX_PLATFORMS=cpu to run on the CPU)"
        ) from e
    logger.info("%s: platform=%s device_kind=%s devices=%d", who,
                desc["platform"], desc["device_kind"], desc["device_count"])
    if desc["platform"] == "tpu" and os.environ.get(INTERPRET_ENV) == "1":
        logger.warning(
            "%s=1 is IGNORED on a TPU backend: kernels run compiled "
            "(interpret mode is for the CPU parity tests)", INTERPRET_ENV)
    return desc
