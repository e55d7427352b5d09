"""Fused LSTM sequence kernel (Pallas TPU): the whole time scan in ONE
kernel launch.

The XLA path (`layers/recurrent.py` ``_scan_time``) compiles the LSTM to a
`lax.while` whose per-step body is a small [B, H]x[H, 4H] matmul plus ~7
separate gate/mask/slice fusions, each its own small launch inside the
while-loop's wrappers. Here one Pallas kernel walks the sequential grid
over T with the recurrent weight and the (h, c) carry resident in VMEM:
per step, one MXU dot plus VPU gate math, no HBM round-trips for the
carry and no per-step kernel launches. Backward is a second sequential
kernel (reverse grid) that accumulates dW / peephole grads in VMEM across
steps — the classic fused-LSTM backward.

Cell semantics are exactly `lstm_cell_step` (reference LstmLayer.cpp /
LstmCompute.cu contract, see layers/recurrent.py:79): gate order
[candidate, input, forget, output]; bias = 4 gate biases + 3 peephole
vectors; carry masking keeps padded steps transparent. Activation
derivatives are computed from the SAVED post-activation values (tanh' =
1-y², sigmoid' = y(1-y)), so the forward saves (a, i, f, o) once and the
backward rebuilds everything else.

Correctness: interpret-mode parity against the XLA scan path in
tests/test_pallas_lstm.py (forward + grads, masked + reversed + peephole
cases). Enabled per-config via settings(pallas_rnn=True); the layer
falls back to the scan path for unsupported shapes/activations.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_ACTS = ("tanh", "sigmoid", "relu", "linear")


def _act(name: str, v: Array) -> Array:
    if name == "tanh":
        return jnp.tanh(v)
    if name == "sigmoid":
        return jax.nn.sigmoid(v)
    if name == "relu":
        return jnp.maximum(v, 0.0)
    return v  # linear


def _dact(name: str, y: Array) -> Array:
    """Derivative from the SAVED post-activation value y = act(x)."""
    if name == "tanh":
        return 1.0 - y * y
    if name == "sigmoid":
        return y * (1.0 - y)
    if name == "relu":
        return (y > 0.0).astype(y.dtype)
    return jnp.ones_like(y)  # linear


# VMEM budget for one kernel invocation (per-core VMEM is ~16MB; leave
# headroom for the compiler's own buffers). The backward kernel is the
# binding case: it holds the recurrent weight, an f32 dW accumulator,
# carry scratch, and double-buffered per-step blocks simultaneously —
# configurations over budget fall back to the scan path instead of dying
# in a VMEM-exceeded compile error. (bf16 flagship shapes: LSTM
# B=256,H=512 ≈ 12.3MB; GRU encoder B=256,H=512 ≈ 8MB; an H=1024 LSTM
# ≈ 25MB is correctly rejected.) PADDLE_TPU_PALLAS_VMEM_BUDGET (bytes)
# overrides for A/B experiments near the boundary — the measured edge:
# the GRU at B=448 compiles, at B=512 Mosaic rejects (2026-08-01).
_VMEM_BUDGET_BYTES = (
    int(os.environ.get("PADDLE_TPU_PALLAS_VMEM_BUDGET", 0)) or 14 * 1024 * 1024
)


def _bwd_vmem_bytes(B: int, H: int, gates: int, itemsize: int,
                    f32_state: bool) -> int:
    w_and_dw = H * gates * H * (itemsize + 4)
    per_step_in = B * gates * H * itemsize + 2 * B * H * itemsize
    if f32_state:
        per_step_in += B * H * 4                   # saved c_prev rides in f32
    out_block = B * gates * H * itemsize
    scratch = (2 if f32_state else 1) * B * H * 4
    return w_and_dw + 2 * per_step_in + out_block + scratch


def shape_ok(acts, B: int, H: int, gates: int, itemsize: int,
             f32_state: bool) -> bool:
    """Shared kernel gate: whitelisted activations, MXU-friendly tiling,
    and the backward's VMEM residency fits."""
    return (
        all(a in _ACTS for a in acts)
        and H % 128 == 0 and B % 8 == 0
        and _bwd_vmem_bytes(B, H, gates, itemsize, f32_state) < _VMEM_BUDGET_BYTES
    )


def supported(act_in: str, act_gate: str, act_state: str, B: int, H: int,
              itemsize: int = 4) -> bool:
    return shape_ok((act_in, act_gate, act_state), B, H, gates=4,
                    itemsize=itemsize, f32_state=True)


def _split4(g: Array, H: int):
    return g[:, :H], g[:, H : 2 * H], g[:, 2 * H : 3 * H], g[:, 3 * H :]


def _load_step(ref, flat: bool):
    """Per-step [B, width] tile: 2-D block in flat mode, [0] of a
    (1, B, width) time-major block otherwise (shared by both kernels)."""
    return ref[...] if flat else ref[0]


def _store_step(ref, v, flat: bool):
    if flat:
        ref[...] = v
    else:
        ref[0] = v


def _cell_fwd(x4_ref, w_ref, peep_ref, h_scr, c_scr, act_in, act_gate,
              act_state, flat=False):
    """One forward cell step from the VMEM carry; returns everything the
    residual-saving kernel needs. ``flat`` = the x4 block is the 2-D
    [B, 4H] lane slice of a [B, T*4H] array (see _run_fwd)."""
    H = w_ref.shape[0]
    h_prev = h_scr[:]                                   # [B, H] f32
    c_prev = c_scr[:]
    w = w_ref[:]
    x4 = _load_step(x4_ref, flat).astype(jnp.float32)   # [B, 4H]
    gates = x4 + jax.lax.dot(
        h_prev.astype(w.dtype), w, preferred_element_type=jnp.float32
    )
    peep = peep_ref[:].astype(jnp.float32)              # [3, H]
    pi, pf, po = peep[0:1], peep[1:2], peep[2:3]        # [1, H] each
    ga, gi, gf, go = _split4(gates, H)
    i = _act(act_gate, gi + pi * c_prev)
    f = _act(act_gate, gf + pf * c_prev)
    a = _act(act_in, ga)
    c_new = f * c_prev + i * a
    o = _act(act_gate, go + po * c_new)
    h_new = o * _act(act_state, c_new)
    return h_prev, c_prev, h_new, c_new, a, i, f, o


def _fwd_kernel(x4_ref, m_ref, w_ref, peep_ref,
                y_ref, acts_ref, hprev_ref, cprev_ref,
                h_scr, c_scr, *, act_in, act_gate, act_state, flat=False):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[:] = jnp.zeros_like(h_scr)
        c_scr[:] = jnp.zeros_like(c_scr)

    h_prev, c_prev, h_new, c_new, a, i, f, o = _cell_fwd(
        x4_ref, w_ref, peep_ref, h_scr, c_scr, act_in, act_gate, act_state,
        flat,
    )
    m = m_ref[0].astype(jnp.float32)                    # [B, 1]

    hprev_ref[0] = h_prev.astype(hprev_ref.dtype)       # residuals (pre-update)
    cprev_ref[0] = c_prev
    acts_ref[0] = jnp.concatenate([a, i, f, o], axis=1).astype(acts_ref.dtype)
    _store_step(y_ref, (m * h_new).astype(y_ref.dtype), flat)
    h_scr[:] = m * h_new + (1.0 - m) * h_prev
    c_scr[:] = m * c_new + (1.0 - m) * c_prev


def _fwd_kernel_light(x4_ref, m_ref, w_ref, peep_ref, y_ref,
                      h_scr, c_scr, *, act_in, act_gate, act_state,
                      flat=False):
    """Inference/eval variant: ys only, no residual writes (pallas outputs
    are never DCE'd, so the primal must not emit them at all)."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[:] = jnp.zeros_like(h_scr)
        c_scr[:] = jnp.zeros_like(c_scr)

    h_prev, c_prev, h_new, c_new, _a, _i, _f, _o = _cell_fwd(
        x4_ref, w_ref, peep_ref, h_scr, c_scr, act_in, act_gate, act_state,
        flat,
    )
    m = m_ref[0].astype(jnp.float32)
    _store_step(y_ref, (m * h_new).astype(y_ref.dtype), flat)
    h_scr[:] = m * h_new + (1.0 - m) * h_prev
    c_scr[:] = m * c_new + (1.0 - m) * c_prev


def _bwd_kernel(dy_ref, acts_ref, hprev_ref, cprev_ref, m_ref, w_ref, peep_ref,
                dx4_ref, dw_ref, dpeep_ref,
                dh_scr, dc_scr, *, act_in, act_gate, act_state, flat=False):
    idx = pl.program_id(0)  # walks t = T-1 .. 0 via the index maps

    @pl.when(idx == 0)
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)
        dc_scr[:] = jnp.zeros_like(dc_scr)
        dw_ref[:] = jnp.zeros_like(dw_ref)
        dpeep_ref[:] = jnp.zeros_like(dpeep_ref)

    H = w_ref.shape[0]
    acts = acts_ref[0].astype(jnp.float32)
    a, i, f, o = _split4(acts, H)
    c_prev = cprev_ref[0]
    h_prev = hprev_ref[0]
    m = m_ref[0].astype(jnp.float32)
    peep = peep_ref[:].astype(jnp.float32)
    pi, pf, po = peep[0:1], peep[1:2], peep[2:3]
    DH = dh_scr[:]
    DC = dc_scr[:]

    c_new = f * c_prev + i * a
    s_c = _act(act_state, c_new)
    dy = _load_step(dy_ref, flat).astype(jnp.float32)
    dh_new = m * (DH + dy)                    # cell path; (1-m) passes through
    dgo = dh_new * s_c * _dact(act_gate, o)
    dc_new = dh_new * o * _dact(act_state, s_c) + m * DC + dgo * po
    dgi = dc_new * a * _dact(act_gate, i)
    dgf = dc_new * c_prev * _dact(act_gate, f)
    dga = dc_new * i * _dact(act_in, a)
    dgates = jnp.concatenate([dga, dgi, dgf, dgo], axis=1)   # [B, 4H]
    _store_step(dx4_ref, dgates.astype(dx4_ref.dtype), flat)

    w = w_ref[:]
    dh_prev = jax.lax.dot_general(
        dgates.astype(w.dtype), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                        # [B, H]
    dh_scr[:] = dh_prev + (1.0 - m) * DH
    dc_scr[:] = dc_new * f + dgi * pi + dgf * pf + (1.0 - m) * DC
    dw_ref[:] += jax.lax.dot_general(
        h_prev.astype(jnp.float32), dgates, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                        # [H, 4H]
    dpeep_ref[:] += jnp.concatenate(
        [
            jnp.sum(dgi * c_prev, axis=0, keepdims=True),
            jnp.sum(dgf * c_prev, axis=0, keepdims=True),
            jnp.sum(dgo * c_new, axis=0, keepdims=True),
        ],
        axis=0,
    )                                                        # [3, H]


def _params(n):
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",) * n)


def _run_fwd(x4, mask_tb1, w, peep, acts, interpret, residuals=True,
             flat=False):
    """``flat``: x4 is [B, T*4H] (the x-projection's natural row-major
    reshape) and ys comes back [B, T*H]; the per-step blocks are the
    same [B, 4H]/[B, H] tiles, addressed at lane offset t*width, so the
    boundary transposes the time-major interface forced on the x4/ys
    cotangent path disappear. Residual streams stay time-major: they
    never cross the kernel boundary."""
    if flat:
        B = mask_tb1.shape[1]
        T = mask_tb1.shape[0]
        H4 = x4.shape[1] // T
    else:
        T, B, H4 = x4.shape
    H = H4 // 4
    step_spec4 = pl.BlockSpec((1, B, H4), lambda t: (t, 0, 0))
    step_spec = pl.BlockSpec((1, B, H), lambda t: (t, 0, 0))
    if flat:
        x_spec = pl.BlockSpec((B, H4), lambda t: (0, t))
        y_spec = pl.BlockSpec((B, H), lambda t: (0, t))
        ys_shape = jax.ShapeDtypeStruct((B, T * H), x4.dtype)
    else:
        x_spec, y_spec = step_spec4, step_spec
        ys_shape = jax.ShapeDtypeStruct((T, B, H), x4.dtype)
    # mask rides time-major as [T, B, 1] so the block's last two dims are
    # (B, 1) with the lane dim EQUAL to the overall array's — Mosaic
    # rejects a (B, 1) block over a [B, T] array (lane dim 1 is neither
    # 128-divisible nor the full T)
    mask_spec = pl.BlockSpec((1, B, 1), lambda t: (t, 0, 0))
    const2 = lambda shape: pl.BlockSpec(shape, lambda t: (0, 0))
    kern = functools.partial(
        _fwd_kernel if residuals else _fwd_kernel_light,
        act_in=acts[0], act_gate=acts[1], act_state=acts[2], flat=flat,
    )
    out_specs = [y_spec]
    out_shape = [ys_shape]
    if residuals:
        out_specs += [step_spec4, step_spec, step_spec]
        out_shape += [
            jax.ShapeDtypeStruct((T, B, H4), x4.dtype),      # acts (a,i,f,o)
            jax.ShapeDtypeStruct((T, B, H), x4.dtype),       # h_prev
            jax.ShapeDtypeStruct((T, B, H), jnp.float32),    # c_prev
        ]
    return pl.pallas_call(
        kern,
        name="lstm_fwd",
        grid=(T,),
        in_specs=[x_spec, mask_spec, const2(w.shape), const2(peep.shape)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((B, H), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_params(1),
    )(x4, mask_tb1, w, peep)


def _run_bwd(dy, saved, mask_tb1, w, peep, acts, interpret, flat=False):
    acts_seq, hprev, cprev = saved
    T, B, H4 = acts_seq.shape
    H = H4 // 4
    rev4 = pl.BlockSpec((1, B, H4), lambda i: (T - 1 - i, 0, 0))
    rev = pl.BlockSpec((1, B, H), lambda i: (T - 1 - i, 0, 0))
    if flat:
        dy_spec = pl.BlockSpec((B, H), lambda i: (0, T - 1 - i))
        dx_spec = pl.BlockSpec((B, H4), lambda i: (0, T - 1 - i))
        dx_shape = jax.ShapeDtypeStruct((B, T * H4), dy.dtype)
    else:
        dy_spec, dx_spec = rev, rev4
        dx_shape = jax.ShapeDtypeStruct((T, B, H4), dy.dtype)
    mask_spec = pl.BlockSpec((1, B, 1), lambda i: (T - 1 - i, 0, 0))
    const2 = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    kern = functools.partial(
        _bwd_kernel, act_in=acts[0], act_gate=acts[1], act_state=acts[2],
        flat=flat,
    )
    dx4, dw, dpeep = pl.pallas_call(
        kern,
        name="lstm_bwd",
        grid=(T,),
        in_specs=[dy_spec, rev4, rev, rev, mask_spec, const2(w.shape), const2(peep.shape)],
        out_specs=[dx_spec, const2(w.shape), const2(peep.shape)],
        out_shape=[
            dx_shape,
            jax.ShapeDtypeStruct(w.shape, jnp.float32),
            jax.ShapeDtypeStruct(peep.shape, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((B, H), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_params(1),
    )(dy, acts_seq, hprev, cprev, mask_tb1, w, peep)
    return dx4, dw.astype(w.dtype), dpeep.astype(peep.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def fused_lstm(x4, mask, w, peep, acts, interpret, flat=False):
    """Masked LSTM over the whole sequence in one kernel launch.

    Time-major interface (flat=False): x4 [T, B, 4H], ys [T, B, H].
    Flat interface (flat=True): x4 [B, T*4H] — the x-projection's
    row-major reshape, no transpose — and ys [B, T*H]; removes the
    boundary transposes on the x4/ys cotangent path. mask is [T, B] in
    BOTH modes (tiny).
    x4 carries the gate biases already added; w [H, 4H]; peep [3, H]
    (zeros when absent); acts = (act_in, act_gate, act_state).
    """
    from paddle_tpu.ops import kernel_flops

    T, B = mask.shape
    H4 = x4.shape[2] if not flat else x4.shape[1] // T
    kernel_flops.record(kernel_flops.lstm_fwd_flops(T, B, H4 // 4))
    (ys,) = _run_fwd(x4, mask[:, :, None], w, peep, acts, interpret,
                     residuals=False, flat=flat)
    return ys


def _fused_fwd(x4, mask, w, peep, acts, interpret, flat=False):
    from paddle_tpu.ops import kernel_flops

    T, B = mask.shape
    H4 = x4.shape[2] if not flat else x4.shape[1] // T
    kernel_flops.record(kernel_flops.lstm_fwd_flops(T, B, H4 // 4))
    ys, acts_seq, hprev, cprev = _run_fwd(
        x4, mask[:, :, None], w, peep, acts, interpret, flat=flat
    )
    return ys, (acts_seq, hprev, cprev, mask, w, peep)


def _fused_bwd(acts, interpret, flat, res, dy):
    from paddle_tpu.ops import kernel_flops

    acts_seq, hprev, cprev, mask, w, peep = res
    T, B, H4 = acts_seq.shape
    kernel_flops.record(kernel_flops.lstm_bwd_flops(T, B, H4 // 4))
    dx4, dw, dpeep = _run_bwd(
        dy, (acts_seq, hprev, cprev), mask[:, :, None], w, peep, acts,
        interpret, flat=flat,
    )
    return dx4, jnp.zeros_like(mask), dw, dpeep


fused_lstm.defvjp(_fused_fwd, _fused_bwd)


def lstm_layer_forward(cfg, x, mask, w, bias, interpret, x_bt=None):
    """The lstmemory layer body on the fused kernel: returns ys
    [T, B, H] (time-major interface) or [B, T, H] (x_bt flat interface).

    x: [T, B, 4H] (pre-bias x-projection), mask: [T, B], w: [H, 4H],
    bias: [7H] (4 gate biases + 3 peepholes) or None. Handles
    cfg.reversed by flipping time outside the kernel (padded steps then
    run first with mask 0, which leaves the carry at init — the same
    semantics as lax.scan(reverse=True) with carry masking).

    ``x_bt`` (PADDLE_TPU_PALLAS_FLAT=1): the batch-major [B, T, 4H]
    projection output — the kernel then runs on its free row-major
    [B, T*4H] reshape and returns ys without any boundary transpose
    (the time-major interface relayouts x4, ys and dx4)."""
    H = cfg.size
    flat = x_bt is not None
    T = mask.shape[0]
    if flat:
        x = x_bt
        if bias is not None:
            x = x + bias[: 4 * H].astype(x.dtype)
        if cfg.reversed:
            x = jnp.flip(x, 1)
            mask = jnp.flip(mask, 0)
        x = x.reshape(x.shape[0], T * 4 * H)
    elif bias is not None:
        x = x + bias[: 4 * H].astype(x.dtype)
    if bias is not None:
        peep = jnp.stack(
            [bias[4 * H : 5 * H], bias[5 * H : 6 * H], bias[6 * H : 7 * H]]
        )
    else:
        peep = jnp.zeros((3, H), x.dtype)
    if not flat and cfg.reversed:
        x = jnp.flip(x, 0)
        mask = jnp.flip(mask, 0)
    acts = (
        cfg.active_type or "tanh",
        cfg.active_gate_type or "sigmoid",
        cfg.active_state_type or "sigmoid",
    )
    ys = fused_lstm(x, mask, w, peep, acts, interpret, flat)
    if flat:
        ys = ys.reshape(ys.shape[0], T, H)
        if cfg.reversed:
            ys = jnp.flip(ys, 1)
        return ys                          # batch-major [B, T, H]
    if cfg.reversed:
        ys = jnp.flip(ys, 0)
    return ys                              # time-major [T, B, H]


def usable(cfg, x) -> bool:
    """Shapes/activations the kernel handles (layer falls back otherwise)."""
    T, B, H4 = x.shape
    if x.dtype not in (jnp.float32, jnp.bfloat16) or H4 != 4 * cfg.size:
        return False
    return supported(
        cfg.active_type or "tanh",
        cfg.active_gate_type or "sigmoid",
        cfg.active_state_type or "sigmoid",
        B,
        cfg.size,
        itemsize=jnp.dtype(x.dtype).itemsize,
    )
