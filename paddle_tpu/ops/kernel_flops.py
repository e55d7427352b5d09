"""Analytic FLOP accounting for the fused Pallas recurrent kernels.

XLA's cost analysis (``compiled.cost_analysis()['flops']``) cannot see
inside a ``pallas_call`` custom call, so a train step that runs the fused
LSTM/GRU kernels would report an MFU that excludes the kernels' matmul
FLOPs — the dominant term. The kernel wrappers therefore ``record()``
their analytic FLOP count at TRACE time; a caller that wraps its
``step.lower(...)`` in ``capture()`` can add the recorded counts to the
cost-analysis number, for a comparable-basis MFU between the pallas and
XLA-scan paths.

FLOP conventions match HloCostAnalysis: a [M,K]x[K,N] dot is 2·M·K·N;
elementwise add/mul count 1 per output element; transcendentals
(tanh/sigmoid exp) are NOT counted as flops. Matmul terms below are exact
per the kernel bodies (ops/pallas_lstm.py, ops/pallas_gru.py); the
elementwise coefficients are close counts of the gate math (within a few
ops — at the flagship H=512 the matmul term is ~200x larger, so the
approximation is irrelevant to MFU). Verified against XLA's own count of
the fully-unrolled scan path in tests/test_kernel_flops.py.

Interpret-mode runs record too (the wrapper cannot know whether the
interpreter's ops also land in the HLO); interpret mode is a CPU
debugging path whose MFU is never quoted, so the double count is
accepted for simplicity.
"""

from __future__ import annotations

import contextlib
from typing import List, Optional

# ---------------------------------------------------------------- formulas


def lstm_fwd_flops(T: int, B: int, H: int) -> float:
    """Fused LSTM forward: per step one [B,H]x[H,4H] dot (8·B·H²) plus
    gate/peephole/carry-mask elementwise math (~21·B·H: x4+dot add 4BH,
    3 peephole mul+add 6BH, c_new 3BH, h_new+y 2BH, two masked carry
    merges 6BH)."""
    return float(T) * (8.0 * B * H * H + 21.0 * B * H)


def lstm_bwd_flops(T: int, B: int, H: int) -> float:
    """Fused LSTM backward: per step dgates@Wᵀ ([B,4H]x[4H,H]) and the
    dW accumulation ([H,B]x[B,4H]) — 16·B·H² — plus the dgate chain,
    peephole grads and masked carry merges (~40·B·H)."""
    return float(T) * (16.0 * B * H * H + 40.0 * B * H)


def gru_fwd_flops(T: int, B: int, H: int) -> float:
    """Fused GRU forward: per step gates [B,H]x[H,2H] (4·B·H²) and
    candidate [B,H]x[H,H] (2·B·H²), plus r·h, the update blend and the
    masked carry merge (~14·B·H)."""
    return float(T) * (6.0 * B * H * H + 14.0 * B * H)


def gru_bwd_flops(T: int, B: int, H: int) -> float:
    """Fused GRU backward: per step dcand@Wcᵀ (2·B·H²), dg@Wgᵀ (4·B·H²),
    dWg ([H,B]x[B,2H], 4·B·H²), dWc (2·B·H²) — 12·B·H² — plus the dgate
    chain and merges (~25·B·H)."""
    return float(T) * (12.0 * B * H * H + 25.0 * B * H)


# ----------------------------------------------------- jaxpr matmul counter
#
# XLA's HloCostAnalysis counts a while/scan BODY once regardless of trip
# count, so `compiled.cost_analysis()['flops']` understates any scanned
# computation by ~T — in a recurrent model the recurrence is the
# dominant FLOP term (the hoisted x-projections are counted, the T-step
# recurrence effectively not). The honest basis for MFU is analytic MODEL
# matmul FLOPs (the MLPerf / scaling-book convention); this counter
# computes them exactly by walking the train step's jaxpr: dot_general and
# conv_general_dilated FLOPs, scan bodies multiplied by their static
# `length`, pallas_call bodies multiplied by their grid size, cond taking
# the max branch, while bodies counted once (trip count unknowable).
# Elementwise/transcendental ops are deliberately excluded — matmul FLOPs
# over peak-matmul throughput is the standard MFU definition.


def _prod(xs) -> float:
    r = 1.0
    for x in xs:
        r *= float(x)
    return r


def _dot_flops(eqn) -> float:
    (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = _prod(lhs[d] for d in lb)
    k = _prod(lhs[d] for d in lc)
    m = _prod(lhs[d] for d in range(len(lhs)) if d not in set(lc) | set(lb))
    n = _prod(rhs[d] for d in range(len(rhs)) if d not in set(rc) | set(_rb))
    return 2.0 * batch * m * n * k


def _conv_flops(eqn) -> float:
    # 2 * out_elements * (kernel_spatial * C_in_per_group); prod(rhs
    # shape) = kspatial * C_in_per_group * C_out, so divide out C_out.
    # lhs_dilation marks a transposed conv (the dX of a strided forward
    # conv): only 1/prod(lhs_dilation) of its taps hit non-inserted-zero
    # inputs, so discount to count canonical model FLOPs, not zeros.
    out = eqn.outvars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    dn = eqn.params["dimension_numbers"]
    c_out = rhs[dn.rhs_spec[0]]
    lhs_dil = _prod(eqn.params.get("lhs_dilation") or (1,))
    return 2.0 * _prod(out) * _prod(rhs) / float(c_out) / lhs_dil


def jaxpr_flops(jaxpr, scale: float = 1.0) -> float:
    """Matmul/conv FLOPs of a (possibly closed) jaxpr, with exact scan /
    pallas grid trip counts."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += scale * _dot_flops(eqn)
        elif name == "conv_general_dilated":
            total += scale * _conv_flops(eqn)
        elif name == "scan":
            total += jaxpr_flops(
                eqn.params["jaxpr"], scale * float(eqn.params["length"])
            )
        elif name == "pallas_call":
            grid = tuple(getattr(eqn.params.get("grid_mapping"), "grid", ()) or ())
            total += jaxpr_flops(eqn.params["jaxpr"], scale * _prod(grid or (1,)))
        elif name == "while":
            # trip count is dynamic: count the body once (the generation
            # decoder is the only while user; train steps are scans)
            total += jaxpr_flops(eqn.params["body_jaxpr"], scale)
        elif name == "cond":
            total += max(
                (jaxpr_flops(b, scale) for b in eqn.params["branches"]),
                default=0.0,
            )
        else:
            # pjit / remat / custom_vjp / closed_call / ...: recurse into
            # every jaxpr-valued param once
            for v in eqn.params.values():
                if hasattr(v, "jaxpr") or hasattr(v, "eqns"):
                    total += jaxpr_flops(v, scale)
    return total


def train_step_flops(fn, *args, **kwargs) -> float:
    """Model matmul FLOPs of one call of ``fn(*args)`` (jaxpr-traced; works
    on plain or jit-wrapped functions)."""
    import jax

    return jaxpr_flops(jax.make_jaxpr(fn, **kwargs)(*args))


# ------------------------------------------------------------ chip peaks

# substring (lowercased device_kind) -> peak bf16 TFLOP/s per jax device
# (Google's published TPU specs; v3 entry is per core = one jax device)
_PEAK_BF16_TFLOPS = [
    ("v6e", 918.0),
    ("v6 lite", 918.0),
    ("v5p", 459.0),
    ("v5e", 197.0),
    ("v5 lite", 197.0),
    ("v5litepod", 197.0),
    ("v4", 275.0),
    ("v3", 61.5),
    ("v2", 23.0),
]

# substring (lowercased device_kind) -> peak HBM bandwidth GB/s per jax
# device (same published specs; v3 entry is per core). The ratio
# peak_flops/peak_bytes is the roofline ridge point the cost-attribution
# layer classifies launch groups against (observability/costs.py).
_PEAK_HBM_GBPS = [
    ("v6e", 1640.0),
    ("v6 lite", 1640.0),
    ("v5p", 2765.0),
    ("v5e", 819.0),
    ("v5 lite", 819.0),
    ("v5litepod", 819.0),
    ("v4", 1228.0),
    ("v3", 450.0),
    ("v2", 350.0),
]


# substring (lowercased device_kind) -> HBM capacity GB per jax device
# (same published specs; v2/v3 entries are per core). The memory
# analyzer (observability/memory.py) computes peak-vs-capacity headroom
# against this when the allocator reported no bytes_limit — same
# omitted-never-guessed contract as the peak tables above.
_PEAK_HBM_GB = [
    ("v6e", 32.0),
    ("v6 lite", 32.0),
    ("v5p", 95.0),
    ("v5e", 16.0),
    ("v5 lite", 16.0),
    ("v5litepod", 16.0),
    ("v4", 32.0),
    ("v3", 16.0),
    ("v2", 8.0),
]


def _peak_of(table, device_kind: str):
    dk = device_kind.lower()
    for key, peak in table:
        if key in dk:
            return peak
    return None


def peak_tflops(device_kind: str):
    """Peak bf16 TFLOP/s for a jax device kind; None when unknown (MFU
    is omitted, never guessed)."""
    return _peak_of(_PEAK_BF16_TFLOPS, device_kind)


def peak_gbps(device_kind: str):
    """Peak HBM GB/s for a jax device kind; None when unknown (roofline
    buckets degrade to 'unknown', never guessed)."""
    return _peak_of(_PEAK_HBM_GBPS, device_kind)


def peak_hbm_gb(device_kind: str):
    """HBM capacity GB for a jax device kind; None when unknown (the
    memory analyzer omits the headroom line, never guessed)."""
    return _peak_of(_PEAK_HBM_GB, device_kind)


# ------------------------------------------------------------- trace capture

_LOG: Optional[List[float]] = None


def record(flops: float) -> None:
    """Called by the pallas kernel wrappers at TRACE time (their Python
    bodies run exactly once per jit trace). No-op outside capture()."""
    if _LOG is not None:
        _LOG.append(float(flops))


@contextlib.contextmanager
def capture():
    """Collect analytic FLOP records from every pallas kernel traced in
    the body. Yields the (mutable) list; re-entrant (inner capture wins,
    restoring the outer log on exit)."""
    global _LOG
    prev = _LOG
    _LOG = log = []
    try:
        yield log
    finally:
        _LOG = prev
