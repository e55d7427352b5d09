"""Fused attention-GRU decoder-step kernel (Pallas TPU).

The seqToseq NMT decoder's per-step machinery — Bahdanau attention
(transform/combine/softmax/scaling/pooling), the context projection and
the GRU cell — is the larger part of the NMT train step's device time
(`decoder_scan_ms.train` in PERF.md), because every decoder step pays
XLA while-loop bookkeeping plus a handful of sub-MXU kernel launches.
The kernel compiles for the chip and has never run on it. It runs
the WHOLE decoder time loop in one launch, batch-blocked so the encoder
states stay VMEM-resident across all decoder steps of a batch block:

    grid = (B/bB, Td), b outer, t inner
    resident per b-block: enc_proj [Te,bB,D], enc_vec [Te,bB,E],
        W_att [D,D], v [D], W_ctx [E,3D], W_gru [D,3D], carry h [bB,D]

Per step (semantics exactly the step-graph layers they replace —
trainer_config_helpers.networks.simple_attention (ref networks.py:943),
layers/sequence.py sequence pooling, layers/recurrent.py gru_cell_step
(ref GruStepLayer.cpp)):

    m_t   = h @ W_att + b_att                     (attention transform,
                                                   combine bias folded)
    s_t   = sum_D(tanh(ep + m_t) * v)             [Te, bB] scores
    a_t   = masked softmax over Te (f32, pads 0)  (sequence_softmax)
    ctx_t = sum_Te(a_t * ev)                      [bB, E] (sum pooling)
    din_t = ctx_t @ W_ctx + xw_t                  (mixed projection; the
             word-side projection and every bias ride xw_t, which the
             recurrent group's prologue hoisting already computes as one
             time-parallel matmul)
    GRU(h, din_t) -> h_new; carry h = dmask ? h_new : h

The frontier output stream is the RAW h_new (matching the scan path,
which masks only the carry and the out-link; the hoisted epilogue masks
at the end). Backward is a reverse-grid kernel: dW_att/dv/db_att and
d_enc_proj accumulate in VMEM f32; dW_gru, dW_ctx and d_enc_vec are
reconstructed OUTSIDE from the streamed (h_prev, r, d_din),
(ctx, d_din) and (alpha, d_ctx) pairs as large time-parallel matmuls —
keeping the backward kernel inside the 14MB VMEM budget (the measured
ceiling discipline from ops/pallas_lstm.py) and the sequential critical
path free of weight-gradient dots. Forward and backward size their
batch blocks independently (fwd bb=64 / bwd bb=32 at flagship shapes).

Correctness: interpret-mode parity vs the unfused recurrent-group scan
in tests/test_fused_decoder.py. Enabled via
settings(pallas_decoder=True) — a separate knob from pallas_rnn so the
unmeasured kernel can never silently become a default.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas_lstm import _act, _dact, _params, pltpu

Array = jax.Array

_VMEM_BUDGET_BYTES = (
    int(os.environ.get("PADDLE_TPU_PALLAS_VMEM_BUDGET", 0)) or 14 * 1024 * 1024
)


def _pick_bb(B: int, vmem_fn=None) -> int | None:
    """Largest batch block that divides B AND keeps the calling kernel
    under the VMEM budget (``vmem_fn(bb) -> bytes``). Forward and
    backward pick INDEPENDENTLY — they communicate only through
    [Td,B,*]/[Te,B,*] HBM streams, and the forward is ~2x lighter (no
    dW/d_enc accumulators), so it gets larger, better-MXU-filling row
    blocks (bb=64 vs the backward's 32 at flagship shapes)."""
    for bb in (64, 32, 16, 8):
        if B % bb != 0:
            continue
        if vmem_fn is not None and vmem_fn(bb) >= _VMEM_BUDGET_BYTES:
            continue
        return bb
    if B < 8 and (vmem_fn is None or vmem_fn(B) < _VMEM_BUDGET_BYTES):
        return B
    return None


def _vmem_fwd(bb: int, Te: int, D: int, E: int, itemsize: int,
              residuals: bool = True) -> int:
    enc_in = Te * bb * (D + E + 1) * itemsize      # ep + ev + emask blocks
    w_in = (D * D + E * 3 * D + D * 3 * D + 2 * D) * itemsize
    step_widths = 3 * D + 1 + D                    # xw + dmask + ys
    if residuals:
        step_widths += D + 3 * D + Te + E          # h_prev, acts, alpha, ctx
    steps = 2 * bb * step_widths * itemsize
    scr = bb * D * 4
    # the attention step materializes `combined` (tanh(ep + m)) as a
    # live [Te,bB,D] f32 temporary every iteration — the largest single
    # buffer in the step and previously unaccounted, so marginal shapes
    # passed the estimate and OOM'd VMEM at compile time
    tmp = Te * bb * D * 4
    return enc_in + w_in + steps + scr + tmp


def _vmem_bwd(bb: int, Te: int, D: int, E: int, itemsize: int) -> int:
    """dW_gru/dW_ctx/d_enc_vec live OUTSIDE the kernel (rebuilt from the
    streamed pairs); in-kernel f32 accumulators are dW_att, db_att, dv
    and the d_enc_proj block."""
    enc_in = Te * bb * (D + E + 1) * itemsize
    w_in = (D * D + E * 3 * D + D * 3 * D + 2 * D) * itemsize
    dw_acc = (D * D + 2 * D) * 4                   # dW_att + db_att + dv f32
    dep_acc = Te * bb * D * 4                      # d_enc_proj f32
    steps = 2 * bb * (D + 1 + D + 3 * D + Te + 3 * D + E) * itemsize
    scr = bb * D * 4
    # the attention backward recomputes `combined` and holds `d_comb`
    # and `dtanh` beside it — three live [Te,bB,D] f32 temporaries per
    # step (see _bwd_step), previously unaccounted in the estimate
    tmp = 3 * Te * bb * D * 4
    return enc_in + w_in + dw_acc + dep_acc + steps + scr + tmp


def supported(B: int, Te: int, D: int, E: int, itemsize: int = 2) -> bool:
    if D % 128 != 0 or E % 128 != 0:
        return False
    bwd = lambda bb: _vmem_bwd(bb, Te, D, E, itemsize)
    return _pick_bb(B, bwd) is not None


# --------------------------------------------------------------- forward


def _attention(ep, em, v, m, Te):
    """Scores + masked softmax + d-less pieces shared by fwd/bwd.

    ep [Te,bB,D] f32-able, em [Te,bB,1], v [1,D], m [bB,D].
    Returns (combined [Te,bB,D] f32, alpha [Te,bB] f32)."""
    f32 = jnp.float32
    combined = jnp.tanh(ep.astype(f32) + m.astype(f32)[None, :, :])
    s = jnp.sum(combined * v.astype(f32)[None, :, :], axis=-1)      # [Te,bB]
    valid = em.astype(f32)[:, :, 0] > 0
    s = jnp.where(valid, s, -1e30)
    smax = jnp.max(s, axis=0, keepdims=True)
    e = jnp.exp(s - smax)
    alpha = e / jnp.sum(e, axis=0, keepdims=True)
    alpha = jnp.where(valid, alpha, 0.0)
    return combined, alpha


def _gru(h_prev, din, wg, wc, act_in, act_gate, D):
    f32 = jnp.float32
    xg, xc = din[:, : 2 * D], din[:, 2 * D :]
    hp = h_prev.astype(wg.dtype)
    g = _act(act_gate, xg + jax.lax.dot(hp, wg, preferred_element_type=f32))
    u, r = g[:, :D], g[:, D:]
    cand = xc + jax.lax.dot(
        (r * h_prev).astype(wc.dtype), wc, preferred_element_type=f32
    )
    c = _act(act_in, cand)
    return u * h_prev + (1.0 - u) * c, u, r, c


def attention_gru_step(h_prev, ep, ev, em, xw_t, wa, ba, v, wctx, wg,
                       acts=("tanh", "sigmoid")):
    """ONE decoder step of the fused attention-GRU math, as a plain jnp
    function — the per-step seam for iteration-level (continuous-
    batching) decode, where the time loop lives on the HOST scheduler
    instead of inside a kernel grid or a ``lax.while_loop``. The
    serving engine wires it in behind ``--serve_fused_step``
    (graph/decode_step.plan_fused_step template-matches the generation
    step graph and feeds this function the extracted weights); a
    TPU-fused ``serve_decode`` kernel plugs into the same seam.

    Exactly the `_fwd_kernel` step body (attention transform → masked
    softmax → sum-pooled context → mixed projection → GRU), so the
    serve-side step and the training kernel cannot diverge; pinned
    against `fused_attention_gru` in tests/test_engine.py.

    Shapes: ``h_prev [B, D]``, ``ep [Te, B, D]`` (encoder projection),
    ``ev [Te, B, E]`` (encoder values), ``em [Te, B, 1]`` (encoder
    mask), ``xw_t [B, 3D]`` (the step's hoisted word-side projection,
    biases folded), weights as in :func:`fused_attention_gru`. Returns
    ``h_new [B, D]`` in f32."""
    f32 = jnp.float32
    act_in, act_gate = acts
    D = h_prev.shape[-1]
    m = jax.lax.dot(
        h_prev.astype(wa.dtype), wa, preferred_element_type=f32
    ) + ba.astype(f32)                                   # [B, D]
    _, alpha = _attention(ep, em, v.reshape(1, D), m, ep.shape[0])
    ctx = jnp.sum(alpha[:, :, None] * ev.astype(f32), axis=0)     # [B, E]
    din = jax.lax.dot(
        ctx.astype(wctx.dtype), wctx, preferred_element_type=f32
    ) + xw_t.astype(f32)                                 # [B, 3D]
    h_new, _, _, _ = _gru(
        h_prev.astype(f32), din, wg[:, : 2 * D], wg[:, 2 * D:],
        act_in, act_gate, D,
    )
    return h_new


def _fwd_kernel(ep_ref, ev_ref, em_ref, xw_ref, dm_ref, h0_ref,
                wa_ref, ba_ref, v_ref, wctx_ref, wg_ref,
                y_ref, hprev_ref, acts_ref, alpha_ref, ctx_ref,
                h_scr, *, act_in, act_gate, Te, D, residuals):
    t = pl.program_id(1)
    f32 = jnp.float32

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(f32)

    h_prev = h_scr[...]                                  # [bB, D] f32
    m = jax.lax.dot(
        h_prev.astype(wa_ref.dtype), wa_ref[...], preferred_element_type=f32
    ) + ba_ref[...].astype(f32)                          # [bB, D]
    combined, alpha = _attention(ep_ref[...], em_ref[...], v_ref[...], m, Te)
    ev = ev_ref[...].astype(f32)                         # [Te, bB, E]
    ctx = jnp.sum(alpha[:, :, None] * ev, axis=0)        # [bB, E]
    din = jax.lax.dot(
        ctx.astype(wctx_ref.dtype), wctx_ref[...], preferred_element_type=f32
    ) + xw_ref[0].astype(f32)                            # [bB, 3D]
    wg_all = wg_ref[...]
    h_new, u, r, c = _gru(
        h_prev, din, wg_all[:, : 2 * D], wg_all[:, 2 * D :], act_in, act_gate, D
    )
    dm = dm_ref[0].astype(f32)                           # [bB, 1]
    y_ref[0] = h_new.astype(y_ref.dtype)                 # RAW frontier stream
    if residuals:
        hprev_ref[0] = h_prev.astype(hprev_ref.dtype)
        acts_ref[0] = jnp.concatenate([u, r, c], axis=1).astype(acts_ref.dtype)
        alpha_ref[0] = alpha.T.astype(alpha_ref.dtype)   # [bB, Te]
        ctx_ref[0] = ctx.astype(ctx_ref.dtype)
    h_scr[...] = dm * h_new + (1.0 - dm) * h_prev


def _run_fwd(ep, ev, em, xw, dmask, h0, wa, ba, v, wctx, wg,
             acts, interpret, residuals=True):
    Te, B, D = ep.shape
    E = ev.shape[2]
    Td = xw.shape[0]
    # interpret mode (CPU parity tests) takes any shape: fall back to a
    # single whole-batch block when no hardware block fits
    bb = _pick_bb(
        B, lambda n: _vmem_fwd(n, Te, D, E, ep.dtype.itemsize, residuals)
    ) or (B if interpret else None)
    assert bb is not None, (B, Te, D, E)  # callers gate on supported()
    enc3 = lambda width: pl.BlockSpec((Te, bb, width), lambda b, t: (0, b, 0))
    step = lambda width: pl.BlockSpec((1, bb, width), lambda b, t: (t, b, 0))
    wspec = lambda shp: pl.BlockSpec(shp, lambda b, t: (0, 0))
    bspec = pl.BlockSpec((bb, D), lambda b, t: (b, 0))
    kern = functools.partial(
        _fwd_kernel, act_in=acts[0], act_gate=acts[1], Te=Te, D=D,
        residuals=residuals,
    )
    out_specs = [step(D), step(D), step(3 * D), step(Te), step(E)]
    out_shape = [
        jax.ShapeDtypeStruct((Td, B, D), ep.dtype),       # raw h_new stream
        jax.ShapeDtypeStruct((Td, B, D), ep.dtype),       # h_prev residuals
        jax.ShapeDtypeStruct((Td, B, 3 * D), ep.dtype),   # u, r, c
        jax.ShapeDtypeStruct((Td, B, Te), ep.dtype),      # alpha
        jax.ShapeDtypeStruct((Td, B, E), ep.dtype),       # ctx
    ]
    if not residuals:
        out_specs, out_shape = out_specs[:1], out_shape[:1]
        kern = functools.partial(
            _fwd_kernel_light, act_in=acts[0], act_gate=acts[1], Te=Te, D=D
        )
    outs = pl.pallas_call(
        kern,
        name="attention_gru_fwd",
        grid=(B // bb, Td),
        in_specs=[
            enc3(D), enc3(E), enc3(1), step(3 * D), step(1), bspec,
            wspec(wa.shape), wspec(ba.shape), wspec(v.shape),
            wspec(wctx.shape), wspec(wg.shape),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bb, D), jnp.float32)],
        interpret=interpret,
        compiler_params=_params(2),
    )(ep, ev, em, xw, dmask, h0, wa, ba, v, wctx, wg)
    return outs


def _fwd_kernel_light(ep_ref, ev_ref, em_ref, xw_ref, dm_ref, h0_ref,
                      wa_ref, ba_ref, v_ref, wctx_ref, wg_ref, y_ref,
                      h_scr, *, act_in, act_gate, Te, D):
    _fwd_kernel(ep_ref, ev_ref, em_ref, xw_ref, dm_ref, h0_ref,
                wa_ref, ba_ref, v_ref, wctx_ref, wg_ref,
                y_ref, None, None, None, None, h_scr,
                act_in=act_in, act_gate=act_gate, Te=Te, D=D,
                residuals=False)


# -------------------------------------------------------------- backward


def _bwd_kernel(dy_ref, ep_ref, ev_ref, em_ref, dm_ref,
                hprev_ref, acts_ref, alpha_ref,
                wa_ref, ba_ref, v_ref, wctx_ref, wg_ref,
                dxw_ref, dctx_ref, dh0_ref, dep_ref,
                dwa_ref, dba_ref, dv_ref,
                dh_scr, *, act_in, act_gate, Te, D):
    b = pl.program_id(0)
    idx = pl.program_id(1)            # walks t = Td-1 .. 0 via index maps
    nb = pl.num_programs(0)
    nt = pl.num_programs(1)
    f32 = jnp.float32

    @pl.when(idx == 0)
    def _init_block():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        dep_ref[...] = jnp.zeros_like(dep_ref)

    @pl.when((b == 0) & (idx == 0))
    def _init_weights():
        dwa_ref[...] = jnp.zeros_like(dwa_ref)
        dba_ref[...] = jnp.zeros_like(dba_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    h_prev = hprev_ref[0].astype(f32)                    # [bB, D]
    acts = acts_ref[0].astype(f32)
    u, r, c = acts[:, :D], acts[:, D : 2 * D], acts[:, 2 * D :]
    alpha = alpha_ref[0].astype(f32).T                   # [Te, bB]
    dmv = dm_ref[0].astype(f32)                          # [bB, 1]
    DH = dh_scr[...]

    # frontier stream is RAW h_new; carry is masked
    dh_new = dy_ref[0].astype(f32) + dmv * DH
    du = dh_new * (h_prev - c)
    dcand = dh_new * (1.0 - u) * _dact(act_in, c)
    wg_all = wg_ref[...]
    wgg, wgc = wg_all[:, : 2 * D], wg_all[:, 2 * D :]
    drh = jax.lax.dot_general(
        dcand.astype(wgc.dtype), wgc, (((1,), (1,)), ((), ())),
        preferred_element_type=f32,
    )
    dr = drh * h_prev
    dgu = du * _dact(act_gate, u)
    dgr = dr * _dact(act_gate, r)
    dg = jnp.concatenate([dgu, dgr], axis=1)             # [bB, 2D]
    d_din = jnp.concatenate([dg, dcand], axis=1)         # [bB, 3D]
    dxw_ref[0] = d_din.astype(dxw_ref.dtype)
    # dW_gru is NOT accumulated here: it is rebuilt outside the kernel
    # from the streamed (h_prev, r, d_din) as two time-parallel matmuls
    # — saves 3MB of f32 VMEM (bb 16 -> 32 at flagship shapes) and two
    # MXU dots from the sequential critical path

    # context projection: d_ctx in-kernel (needed for the attention
    # chain); dW_ctx reconstructed OUTSIDE from the (ctx, d_din) streams
    d_ctx = jax.lax.dot_general(
        d_din.astype(wctx_ref.dtype), wctx_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=f32,
    )                                                    # [bB, E]
    dctx_ref[0] = d_ctx.astype(dctx_ref.dtype)

    # attention backward; combined is recomputed from the resident
    # enc_proj and the transform output (cheaper than streaming
    # [Td,Te,bB,D] activations through HBM)
    m = jax.lax.dot(
        h_prev.astype(wa_ref.dtype), wa_ref[...], preferred_element_type=f32
    ) + ba_ref[...].astype(f32)
    ev = ev_ref[...].astype(f32)
    combined = jnp.tanh(ep_ref[...].astype(f32) + m[None, :, :])
    dalpha = jnp.sum(ev * d_ctx[None, :, :], axis=-1)    # [Te, bB]
    # masked softmax backward (pads have alpha = 0, so they drop out)
    ds = alpha * (dalpha - jnp.sum(alpha * dalpha, axis=0, keepdims=True))
    v32 = v_ref[...].astype(f32)                         # [1, D]
    d_comb = ds[:, :, None] * v32[None, :, :]            # [Te, bB, D]
    dv_ref[...] += jnp.sum(combined * ds[:, :, None], axis=(0, 1))[None, :]
    dtanh = (1.0 - combined * combined) * d_comb
    dep_ref[...] += dtanh.astype(dep_ref.dtype)
    d_m = jnp.sum(dtanh, axis=0)                         # [bB, D]
    dba_ref[...] += jnp.sum(d_m, axis=0)[None, :]
    dwa_ref[...] += jax.lax.dot_general(
        h_prev, d_m, (((0,), (0,)), ((), ())), preferred_element_type=f32
    )

    dh_prev = (
        dh_new * u
        + drh * r
        + jax.lax.dot_general(
            dg.astype(wgg.dtype), wgg, (((1,), (1,)), ((), ())),
            preferred_element_type=f32,
        )
        + jax.lax.dot_general(
            d_m.astype(wa_ref.dtype), wa_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=f32,
        )
    )
    dh_scr[...] = dh_prev + (1.0 - dmv) * DH

    @pl.when(idx == nt - 1)
    def _final():
        dh0_ref[...] = dh_scr[...].astype(dh0_ref.dtype)


def _run_bwd(dy, ep, ev, em, dmask, hprev, acts3, alphas,
             wa, ba, v, wctx, wg, acts, interpret):
    Te, B, D = ep.shape
    E = ev.shape[2]
    Td = dy.shape[0]
    bb = _pick_bb(
        B, lambda n: _vmem_bwd(n, Te, D, E, ep.dtype.itemsize)
    ) or (B if interpret else None)
    assert bb is not None, (B, Te, D, E)  # callers gate on supported()
    enc3 = lambda width: pl.BlockSpec((Te, bb, width), lambda b, i: (0, b, 0))
    rev = lambda width: pl.BlockSpec((1, bb, width), lambda b, i: (Td - 1 - i, b, 0))
    wspec = lambda shp: pl.BlockSpec(shp, lambda b, i: (0, 0))
    bspec = pl.BlockSpec((bb, D), lambda b, i: (b, 0))
    kern = functools.partial(
        _bwd_kernel, act_in=acts[0], act_gate=acts[1], Te=Te, D=D
    )
    f32 = jnp.float32
    dxw, dctxs, dh0, dep, dwa, dba, dv = pl.pallas_call(
        kern,
        name="attention_gru_bwd",
        grid=(B // bb, Td),
        in_specs=[
            rev(D),                       # dy
            enc3(D), enc3(E), enc3(1),    # ep, ev, emask
            rev(1),                       # dmask
            rev(D), rev(3 * D), rev(Te),  # hprev, acts, alpha
            wspec(wa.shape), wspec(ba.shape), wspec(v.shape),
            wspec(wctx.shape), wspec(wg.shape),
        ],
        out_specs=[
            rev(3 * D),                   # dxw (= d_din)
            rev(E),                       # d_ctx stream
            bspec,                        # dh0
            enc3(D),                      # d_enc_proj (per b-block)
            wspec(wa.shape), wspec(ba.shape), wspec(v.shape),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Td, B, 3 * D), dy.dtype),
            jax.ShapeDtypeStruct((Td, B, E), dy.dtype),
            jax.ShapeDtypeStruct((B, D), dy.dtype),
            jax.ShapeDtypeStruct((Te, B, D), f32),
            jax.ShapeDtypeStruct(wa.shape, f32),
            jax.ShapeDtypeStruct(ba.shape, f32),
            jax.ShapeDtypeStruct(v.shape, f32),
        ],
        scratch_shapes=[pltpu.VMEM((bb, D), jnp.float32)],
        interpret=interpret,
        compiler_params=_params(2),
    )(dy, ep, ev, em, dmask, hprev, acts3, alphas, wa, ba, v, wctx, wg)
    return dxw, dctxs, dh0, dep, dwa, dba, dv


# ------------------------------------------------------------ public API


def _flops(Td, B, Te, D, E, bwd: bool) -> float:
    att = 2.0 * B * D * D + 4.0 * B * Te * D + 2.0 * B * Te * E
    proj = 2.0 * B * E * 3 * D
    gru = 2.0 * B * D * 2 * D + 2.0 * B * D * D
    per_step = att + proj + gru
    return Td * per_step * (3.0 if bwd else 1.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(11, 12))
def fused_attention_gru(ep, ev, em, xw, dmask, h0, wa, ba, v, wctx, wg,
                        acts, interpret):
    """Raw per-step GRU outputs [Td, B, D] of the fused decoder loop.

    ep [Te,B,D] encoder projection; ev [Te,B,E] encoder states;
    em [Te,B,1] encoder validity; xw [Td,B,3D] hoisted word-side
    decoder inputs WITH all biases folded in; dmask [Td,B,1] target
    validity; h0 [B,D] boot state; wa [D,D] + ba [1,D] attention
    transform (+ folded combine bias); v [1,D] scoring vector;
    wctx [E,3D]; wg [D,3D] GRU weight. acts = (act_in, act_gate)."""
    from paddle_tpu.ops import kernel_flops

    Td, B = xw.shape[0], xw.shape[1]
    Te, D, E = ep.shape[0], ep.shape[2], ev.shape[2]
    kernel_flops.record(_flops(Td, B, Te, D, E, bwd=False))
    (ys,) = _run_fwd(ep, ev, em, xw, dmask, h0, wa, ba, v, wctx, wg,
                     acts, interpret, residuals=False)
    return ys


def _fused_fwd(ep, ev, em, xw, dmask, h0, wa, ba, v, wctx, wg,
               acts, interpret):
    from paddle_tpu.ops import kernel_flops

    Td, B = xw.shape[0], xw.shape[1]
    Te, D, E = ep.shape[0], ep.shape[2], ev.shape[2]
    kernel_flops.record(_flops(Td, B, Te, D, E, bwd=False))
    ys, hprev, acts3, alphas, ctxs = _run_fwd(
        ep, ev, em, xw, dmask, h0, wa, ba, v, wctx, wg, acts, interpret
    )
    return ys, (ep, ev, em, dmask, hprev, acts3, alphas, ctxs,
                wa, ba, v, wctx, wg)


def _fused_bwd(acts, interpret, res, dy):
    from paddle_tpu.ops import kernel_flops

    (ep, ev, em, dmask, hprev, acts3, alphas, ctxs, wa, ba, v, wctx, wg) = res
    Td, B = dy.shape[0], dy.shape[1]
    Te, D, E = ep.shape[0], ep.shape[2], ev.shape[2]
    kernel_flops.record(_flops(Td, B, Te, D, E, bwd=True))
    dxw, dctxs, dh0, dep, dwa, dba, dv = _run_bwd(
        dy, ep, ev, em, dmask, hprev, acts3, alphas,
        wa, ba, v, wctx, wg, acts, interpret,
    )
    f32 = jnp.float32
    # dW_ctx, dW_gru and d_enc_vec as large time-parallel contractions
    # OUTSIDE the kernel (VMEM budget — see module docstring)
    dwctx = jax.lax.dot_general(
        ctxs.reshape(-1, E), dxw.reshape(-1, 3 * D),
        (((0,), (0,)), ((), ())), preferred_element_type=f32,
    ).astype(wctx.dtype)
    hp2 = hprev.reshape(-1, D)
    dxw2 = dxw.reshape(-1, 3 * D)
    r2 = acts3.reshape(-1, 3 * D)[:, D : 2 * D]
    dwg = jnp.concatenate(
        [
            jax.lax.dot_general(hp2, dxw2[:, : 2 * D],
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=f32),
            jax.lax.dot_general((r2.astype(f32) * hp2.astype(f32)).astype(hp2.dtype),
                                dxw2[:, 2 * D :],
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=f32),
        ],
        axis=1,
    )
    # d_ev[te, b, :] = sum_td alpha[td, b, te] * d_ctx[td, b, :]
    dev = jnp.einsum(
        "tbe,tbd->ebd", alphas.astype(f32), dctxs.astype(f32),
        preferred_element_type=f32,
    ).astype(ev.dtype)
    return (
        dep.astype(ep.dtype),
        dev,
        jnp.zeros_like(em),
        dxw,
        jnp.zeros_like(dmask),
        dh0,
        dwa.astype(wa.dtype),
        dba.astype(ba.dtype),
        dv.astype(v.dtype),
        dwctx,
        dwg.astype(wg.dtype),
    )


fused_attention_gru.defvjp(_fused_fwd, _fused_bwd)
