"""Fused GRU sequence kernel (Pallas TPU) — the gated_recurrent analog of
ops/pallas_lstm.py: the whole time scan in one kernel launch, carry and
both recurrent weight blocks resident in VMEM.

Cell semantics are exactly `gru_cell_step` (reference
GatedRecurrentLayer.cpp / GruCompute contract, layers/recurrent.py:127):
weight [H, 3H] split [update, reset | candidate]; bias 3H = 2H gate +
H candidate (pre-added to the x-projection outside the kernel, so bias
gradients ride the dx3 sum); output = update * prev + (1-update) * cand.
Per step the kernel runs TWO MXU dots (gates: [B,H]x[H,2H]; candidate:
[B,H]x[H,H]) plus VPU gate math. Backward is a reverse-grid kernel
accumulating dW in VMEM, derivatives rebuilt from the saved
post-activation (u, r, c) values.

Correctness: interpret-mode parity in tests/test_pallas_gru.py.
Enabled together with the LSTM kernel via settings(pallas_rnn=True).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas_lstm import (_act, _dact, _load_step, _params,
                                        _store_step, pltpu, shape_ok)

Array = jax.Array


def supported(act_in: str, act_gate: str, B: int, H: int,
              itemsize: int = 4) -> bool:
    return shape_ok((act_in, act_gate), B, H, gates=3, itemsize=itemsize,
                    f32_state=False)


def _cell_fwd(x3_ref, w_ref, h_scr, act_in, act_gate, flat=False):
    H = w_ref.shape[0]
    h_prev = h_scr[:]                                   # [B, H] f32
    w = w_ref[:]
    wg, wc = w[:, : 2 * H], w[:, 2 * H :]
    x3 = _load_step(x3_ref, flat).astype(jnp.float32)   # [B, 3H]
    xg, xc = x3[:, : 2 * H], x3[:, 2 * H :]
    hp = h_prev.astype(w.dtype)
    g = _act(act_gate, xg + jax.lax.dot(hp, wg, preferred_element_type=jnp.float32))
    u, r = g[:, :H], g[:, H:]
    cand = xc + jax.lax.dot(
        (r * h_prev).astype(w.dtype), wc, preferred_element_type=jnp.float32
    )
    c = _act(act_in, cand)
    h_new = u * h_prev + (1.0 - u) * c
    return h_prev, h_new, u, r, c


def _fwd_kernel(x3_ref, m_ref, w_ref, y_ref, acts_ref, hprev_ref,
                h_scr, *, act_in, act_gate, flat=False):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[:] = jnp.zeros_like(h_scr)

    h_prev, h_new, u, r, c = _cell_fwd(x3_ref, w_ref, h_scr, act_in, act_gate,
                                       flat)
    m = m_ref[0].astype(jnp.float32)                    # [B, 1]

    hprev_ref[0] = h_prev.astype(hprev_ref.dtype)       # residuals (pre-update)
    acts_ref[0] = jnp.concatenate([u, r, c], axis=1).astype(acts_ref.dtype)
    _store_step(y_ref, (m * h_new).astype(y_ref.dtype), flat)
    h_scr[:] = m * h_new + (1.0 - m) * h_prev


def _fwd_kernel_light(x3_ref, m_ref, w_ref, y_ref, h_scr, *, act_in,
                      act_gate, flat=False):
    """Inference/eval variant: ys only (pallas outputs are never DCE'd)."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[:] = jnp.zeros_like(h_scr)

    h_prev, h_new, _u, _r, _c = _cell_fwd(x3_ref, w_ref, h_scr, act_in,
                                          act_gate, flat)
    m = m_ref[0].astype(jnp.float32)
    _store_step(y_ref, (m * h_new).astype(y_ref.dtype), flat)
    h_scr[:] = m * h_new + (1.0 - m) * h_prev


def _bwd_kernel(dy_ref, acts_ref, hprev_ref, m_ref, w_ref,
                dx3_ref, dw_ref, dh_scr, *, act_in, act_gate, flat=False):
    idx = pl.program_id(0)  # walks t = T-1 .. 0 via the index maps

    @pl.when(idx == 0)
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)
        dw_ref[:] = jnp.zeros_like(dw_ref)

    H = w_ref.shape[0]
    acts = acts_ref[0].astype(jnp.float32)
    u, r, c = acts[:, :H], acts[:, H : 2 * H], acts[:, 2 * H :]
    h_prev = hprev_ref[0].astype(jnp.float32)
    m = m_ref[0].astype(jnp.float32)
    DH = dh_scr[:]

    dy = _load_step(dy_ref, flat).astype(jnp.float32)
    dh = m * (DH + dy)                        # cell path; (1-m) passes through
    du = dh * (h_prev - c)
    dcand = dh * (1.0 - u) * _dact(act_in, c)
    w = w_ref[:]
    wg, wc = w[:, : 2 * H], w[:, 2 * H :]
    drh = jax.lax.dot_general(
        dcand.astype(w.dtype), wc, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                          # d(r*h_prev) [B, H]
    dr = drh * h_prev
    dgu = du * _dact(act_gate, u)
    dgr = dr * _dact(act_gate, r)
    dg = jnp.concatenate([dgu, dgr], axis=1)   # [B, 2H]
    _store_step(dx3_ref, jnp.concatenate([dg, dcand], axis=1).astype(dx3_ref.dtype), flat)

    dh_prev = (
        dh * u
        + drh * r
        + jax.lax.dot_general(
            dg.astype(w.dtype), wg, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    )
    dh_scr[:] = dh_prev + (1.0 - m) * DH
    dwg = jax.lax.dot_general(
        h_prev, dg, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dwc = jax.lax.dot_general(
        r * h_prev, dcand, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dw_ref[:] += jnp.concatenate([dwg, dwc], axis=1)     # [H, 3H]


def _run_fwd(x3, mask_tb1, w, acts, interpret, residuals=True, flat=False):
    """``flat``: x3 is [B, T*3H] (the x-projection's natural row-major
    reshape) and ys comes back [B, T*H] — same per-step [B, *] tiles at
    lane offset t*width, no boundary transposes (pallas_lstm._run_fwd)."""
    if flat:
        T, B = mask_tb1.shape[0], mask_tb1.shape[1]
        H3 = x3.shape[1] // T
    else:
        T, B, H3 = x3.shape
    H = H3 // 3
    step3 = pl.BlockSpec((1, B, H3), lambda t: (t, 0, 0))
    step1 = pl.BlockSpec((1, B, H), lambda t: (t, 0, 0))
    if flat:
        x_spec = pl.BlockSpec((B, H3), lambda t: (0, t))
        y_spec = pl.BlockSpec((B, H), lambda t: (0, t))
        ys_shape = jax.ShapeDtypeStruct((B, T * H), x3.dtype)
    else:
        x_spec, y_spec = step3, step1
        ys_shape = jax.ShapeDtypeStruct((T, B, H), x3.dtype)
    # mask rides time-major as [T, B, 1]: a (B, 1) block over [B, T] has
    # a lane dim that is neither 128-divisible nor the full array dim,
    # which Mosaic rejects (see pallas_lstm._run_fwd)
    mask_spec = pl.BlockSpec((1, B, 1), lambda t: (t, 0, 0))
    wspec = pl.BlockSpec(w.shape, lambda t: (0, 0))
    kern = functools.partial(
        _fwd_kernel if residuals else _fwd_kernel_light,
        act_in=acts[0], act_gate=acts[1], flat=flat,
    )
    out_specs = [y_spec]
    out_shape = [ys_shape]
    if residuals:
        out_specs += [step3, step1]
        out_shape += [
            jax.ShapeDtypeStruct((T, B, H3), x3.dtype),  # acts (u, r, c)
            jax.ShapeDtypeStruct((T, B, H), x3.dtype),   # h_prev
        ]
    return pl.pallas_call(
        kern,
        name="gru_fwd",
        grid=(T,),
        in_specs=[x_spec, mask_spec, wspec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32)],
        interpret=interpret,
        compiler_params=_params(1),
    )(x3, mask_tb1, w)


def _run_bwd(dy, acts_seq, hprev, mask_tb1, w, acts, interpret, flat=False):
    T, B, H3 = acts_seq.shape
    H = H3 // 3
    rev3 = pl.BlockSpec((1, B, H3), lambda i: (T - 1 - i, 0, 0))
    rev1 = pl.BlockSpec((1, B, H), lambda i: (T - 1 - i, 0, 0))
    if flat:
        dy_spec = pl.BlockSpec((B, H), lambda i: (0, T - 1 - i))
        dx_spec = pl.BlockSpec((B, H3), lambda i: (0, T - 1 - i))
        dx_shape = jax.ShapeDtypeStruct((B, T * H3), dy.dtype)
    else:
        dy_spec, dx_spec = rev1, rev3
        dx_shape = jax.ShapeDtypeStruct((T, B, H3), dy.dtype)
    mask_spec = pl.BlockSpec((1, B, 1), lambda i: (T - 1 - i, 0, 0))
    wspec = pl.BlockSpec(w.shape, lambda i: (0, 0))
    kern = functools.partial(_bwd_kernel, act_in=acts[0], act_gate=acts[1],
                             flat=flat)
    dx3, dw = pl.pallas_call(
        kern,
        name="gru_bwd",
        grid=(T,),
        in_specs=[dy_spec, rev3, rev1, mask_spec, wspec],
        out_specs=[dx_spec, wspec],
        out_shape=[
            dx_shape,
            jax.ShapeDtypeStruct(w.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32)],
        interpret=interpret,
        compiler_params=_params(1),
    )(dy, acts_seq, hprev, mask_tb1, w)
    return dx3, dw.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_gru(x3, mask, w, acts, interpret, flat=False):
    """Masked GRU over the whole sequence in one kernel launch.

    Time-major (flat=False): x3 [T, B, 3H], ys [T, B, H]. Flat
    (flat=True): x3 [B, T*3H], ys [B, T*H] — no boundary transposes
    (see fused_lstm). mask [T, B] in both modes; x3 carries biases;
    w [H, 3H]; acts = (act_in, act_gate)."""
    from paddle_tpu.ops import kernel_flops

    T, B = mask.shape
    H3 = x3.shape[2] if not flat else x3.shape[1] // T
    kernel_flops.record(kernel_flops.gru_fwd_flops(T, B, H3 // 3))
    (ys,) = _run_fwd(x3, mask[:, :, None], w, acts, interpret,
                     residuals=False, flat=flat)
    return ys


def _fused_fwd(x3, mask, w, acts, interpret, flat=False):
    from paddle_tpu.ops import kernel_flops

    T, B = mask.shape
    H3 = x3.shape[2] if not flat else x3.shape[1] // T
    kernel_flops.record(kernel_flops.gru_fwd_flops(T, B, H3 // 3))
    ys, acts_seq, hprev = _run_fwd(x3, mask[:, :, None], w, acts, interpret,
                                   flat=flat)
    return ys, (acts_seq, hprev, mask, w)


def _fused_bwd(acts, interpret, flat, res, dy):
    from paddle_tpu.ops import kernel_flops

    acts_seq, hprev, mask, w = res
    T, B, H3 = acts_seq.shape
    kernel_flops.record(kernel_flops.gru_bwd_flops(T, B, H3 // 3))
    dx3, dw = _run_bwd(dy, acts_seq, hprev, mask[:, :, None], w, acts,
                       interpret, flat=flat)
    return dx3, jnp.zeros_like(mask), dw


fused_gru.defvjp(_fused_fwd, _fused_bwd)


def gru_layer_forward(cfg, x, mask, w, bias, interpret, x_bt=None):
    """The gated_recurrent layer body on the fused kernel: ys [T, B, H]
    (time-major) or [B, T, H] (x_bt flat interface).

    x: [T, B, 3H] pre-bias x-projection, bias: [3H] or None; handles
    cfg.reversed by flipping time outside the kernel (same carry-masking
    argument as the LSTM kernel). ``x_bt``: batch-major [B, T, 3H] for
    the transpose-free flat interface (see pallas_lstm)."""
    H = cfg.size
    flat = x_bt is not None
    T = mask.shape[0]
    if flat:
        x = x_bt
        if bias is not None:
            x = x + bias.astype(x.dtype)
        if cfg.reversed:
            x = jnp.flip(x, 1)
            mask = jnp.flip(mask, 0)
        x = x.reshape(x.shape[0], T * 3 * H)
    else:
        if bias is not None:
            x = x + bias.astype(x.dtype)
        if cfg.reversed:
            x = jnp.flip(x, 0)
            mask = jnp.flip(mask, 0)
    acts = (cfg.active_type or "tanh", cfg.active_gate_type or "sigmoid")
    ys = fused_gru(x, mask, w, acts, interpret, flat)
    if flat:
        ys = ys.reshape(ys.shape[0], T, H)
        if cfg.reversed:
            ys = jnp.flip(ys, 1)
        return ys                          # batch-major [B, T, H]
    if cfg.reversed:
        ys = jnp.flip(ys, 0)
    return ys                              # time-major [T, B, H]


def usable(cfg, x) -> bool:
    T, B, H3 = x.shape
    if x.dtype not in (jnp.float32, jnp.bfloat16) or H3 != 3 * cfg.size:
        return False
    return supported(
        cfg.active_type or "tanh", cfg.active_gate_type or "sigmoid", B, cfg.size,
        itemsize=jnp.dtype(x.dtype).itemsize,
    )
