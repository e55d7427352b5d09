"""Flash attention as a Pallas TPU kernel (O(T) memory local attention).

The XLA `full_attention` materializes the [B, H, T, T] score matrix; this
kernel streams K/V blocks through an online-softmax accumulator in VMEM so
activation memory stays O(T·D) — the per-chip building block that, combined
with ring attention (paddle_tpu.parallel.sequence_parallel), sets the max
context length. Forward saves only (out, logsumexp); backward recomputes
scores blockwise (flash-attention-2 style) in two kernels (dQ; dK/dV).

Layout: [B, H, T, D] inside the kernels (callers transpose from the
[B, T, H, D] sequence_parallel layout). T must divide the block sizes;
callers fall back to the XLA path otherwise (see
sequence_parallel.full_attention). Correctness is tested in interpret mode
on CPU against the XLA reference (tests/test_pallas_attention.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_NEG = -1e30
BLOCK_Q = 128
BLOCK_K = 128


def _positions(start, n):
    return start + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)[:, 0]


def _mask(q_pos, kv_pos, length, causal):
    m = kv_pos[None, :] < length
    if causal:
        m = m & (kv_pos[None, :] <= q_pos[:, None])
    return m


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=jnp.float32)


def _fwd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, block_k, scale):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    bq, D = q_ref.shape[2], q_ref.shape[3]
    T = k_ref.shape[2]
    length = len_ref[b]
    q = q_ref[0, 0].astype(jnp.float32) * scale               # [bq, D]
    q_pos = _positions(iq * bq, bq)

    def body(ik, carry):
        o, m, l = carry
        kv_idx = (0, 0, pl.ds(ik * block_k, block_k), slice(None))
        k_blk = k_ref[kv_idx].astype(jnp.float32)
        v_blk = v_ref[kv_idx].astype(jnp.float32)
        kv_pos = _positions(ik * block_k, block_k)
        s = _dot(q, k_blk, ((1,), (1,)))                      # [bq, bk]
        msk = _mask(q_pos, kv_pos, length, causal)
        s = jnp.where(msk, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(msk, jnp.exp(s - m_new[:, None]), 0.0)
        l = l * alpha + jnp.sum(p, axis=1)
        o = o * alpha[:, None] + _dot(p, v_blk, ((1,), (0,)))
        return o, m_new, l

    n_k = (iq + 1) * bq // block_k if causal else T // block_k
    o0 = jnp.zeros((bq, D), jnp.float32)
    m0 = jnp.full((bq,), _NEG, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, n_k, body, (o0, m0, l0))
    l_safe = jnp.maximum(l, 1e-20)
    o_ref[0, 0] = (o / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.where(l > 0, m + jnp.log(l_safe), _NEG)


def _dq_kernel(len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *, causal, block_k, scale):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    bq, D = q_ref.shape[2], q_ref.shape[3]
    T = k_ref.shape[2]
    length = len_ref[b]
    q = q_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    q_pos = _positions(iq * bq, bq)

    def body(ik, dq):
        kv_idx = (0, 0, pl.ds(ik * block_k, block_k), slice(None))
        k_blk = k_ref[kv_idx].astype(jnp.float32)
        v_blk = v_ref[kv_idx].astype(jnp.float32)
        kv_pos = _positions(ik * block_k, block_k)
        s = _dot(q, k_blk, ((1,), (1,))) * scale
        msk = _mask(q_pos, kv_pos, length, causal)
        p = jnp.where(msk, jnp.exp(s - lse[:, None]), 0.0)
        dp = _dot(do, v_blk, ((1,), (1,)))
        ds = p * (dp - delta[:, None]) * scale
        return dq + _dot(ds, k_blk, ((1,), (0,)))

    n_k = (iq + 1) * bq // block_k if causal else T // block_k
    dq = jax.lax.fori_loop(0, n_k, body, jnp.zeros((bq, D), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, causal, block_q, scale):
    b = pl.program_id(0)
    ik = pl.program_id(2)
    bk, D = k_ref.shape[2], k_ref.shape[3]
    T = q_ref.shape[2]
    length = len_ref[b]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    kv_pos = _positions(ik * bk, bk)

    def body(jq, carry):
        dk, dv = carry
        q_idx = (0, 0, pl.ds(jq * block_q, block_q), slice(None))
        q_blk = q_ref[q_idx].astype(jnp.float32)
        do_blk = do_ref[q_idx].astype(jnp.float32)
        stat_idx = (0, 0, pl.ds(jq * block_q, block_q))
        lse_blk = lse_ref[stat_idx]
        delta_blk = delta_ref[stat_idx]
        q_pos = _positions(jq * block_q, block_q)
        s = _dot(q_blk, k, ((1,), (1,))) * scale              # [bq, bk]
        msk = _mask(q_pos, kv_pos, length, causal)
        p = jnp.where(msk, jnp.exp(s - lse_blk[:, None]), 0.0)
        dv = dv + _dot(p, do_blk, ((0,), (0,)))
        dp = _dot(do_blk, v, ((1,), (1,)))
        ds = p * (dp - delta_blk[:, None]) * scale
        dk = dk + _dot(ds, q_blk, ((0,), (0,)))
        return dk, dv

    start = ik * bk // block_q if causal else 0
    dk, dv = jax.lax.fori_loop(
        start, T // block_q, body,
        (jnp.zeros((bk, D), jnp.float32), jnp.zeros((bk, D), jnp.float32)),
    )
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


# full lengths vector visible to every program — scalar memory
_LEN_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _run_fwd(q, k, v, lengths, causal, bq, bk, interpret):
    B, H, T, D = q.shape
    scale = 1.0 / math.sqrt(D)
    qspec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0))
    kvspec = pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h, 0, 0))
    lse_spec = pl.BlockSpec((1, 1, bq), lambda b, h, i: (b, h, i))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, block_k=bk, scale=scale),
        name="attention_fwd",
        grid=(B, H, T // bq),
        in_specs=[_LEN_SPEC, qspec, kvspec, kvspec],
        out_specs=[qspec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, T), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(lengths, q, k, v)
    # barrier: stop XLA's alternate-memory pass from pinning the whole
    # output in VMEM (scoped-vmem OOM on real chips)
    out, lse = jax.lax.optimization_barrier((out, lse))
    return out, lse


def _run_bwd(q, k, v, do, out, lse, lengths, causal, bq, bk, interpret):
    B, H, T, D = q.shape
    scale = 1.0 / math.sqrt(D)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    qspec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0))
    kv_full = pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h, 0, 0))
    stat_q = pl.BlockSpec((1, 1, bq), lambda b, h, i: (b, h, i))
    stat_full = pl.BlockSpec((1, 1, T), lambda b, h, i: (b, h, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, block_k=bk, scale=scale),
        name="attention_dq",
        grid=(B, H, T // bq),
        in_specs=[_LEN_SPEC, qspec, kv_full, kv_full, qspec, stat_q, stat_q],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(lengths, q, k, v, do, lse, delta)

    k_blk = pl.BlockSpec((1, 1, bk, D), lambda b, h, i: (b, h, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, block_q=bq, scale=scale),
        name="attention_dkv",
        grid=(B, H, T // bk),
        in_specs=[_LEN_SPEC, kv_full, k_blk, k_blk, kv_full, stat_full, stat_full],
        out_specs=[k_blk, k_blk],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, T, D), v.dtype),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(lengths, q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash(q, k, v, lengths, causal, interpret):
    out, _ = _run_fwd(q, k, v, lengths, causal, BLOCK_Q, BLOCK_K, interpret)
    return out


def _flash_fwd(q, k, v, lengths, causal, interpret):
    out, lse = _run_fwd(q, k, v, lengths, causal, BLOCK_Q, BLOCK_K, interpret)
    return out, (q, k, v, out, lse, lengths)


def _flash_bwd(causal, interpret, res, g):
    q, k, v, out, lse, lengths = res
    dq, dk, dv = _run_bwd(q, k, v, g, out, lse, lengths, causal, BLOCK_Q, BLOCK_K, interpret)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def supported(T: int, D: int) -> bool:
    """Shapes the kernel handles: T divisible by the block sizes."""
    return T % BLOCK_Q == 0 and T % BLOCK_K == 0 and D <= 256


def tpu_flash_attention(
    q: Array, k: Array, v: Array,
    lengths: Optional[Array] = None,
    causal: bool = False,
) -> Array:
    """Flash attention on a real TPU via jax's production Mosaic kernel
    (jax.experimental.pallas.ops.tpu.flash_attention), with padding masked
    through segment ids (valid positions = segment 1, padding = 0 → no
    cross-attention between them). Layout [B, T, H, D] like
    sequence_parallel. The hand-rolled kernels above remain the
    interpret-mode-tested specification of the same math; the library
    kernel carries the battle-tested Mosaic scheduling on hardware.
    """
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    B, T, H, D = q.shape
    qt, kt, vt = (jnp.transpose(x, (0, 2, 1, 3)) for x in (q, k, v))
    segment_ids = None
    if lengths is not None:
        valid = (jnp.arange(T)[None, :] < lengths[:, None]).astype(jnp.int32)
        segment_ids = fa.SegmentIds(q=valid, kv=valid)
    out = fa.flash_attention(
        qt, kt, vt,
        causal=causal,
        segment_ids=segment_ids,
        sm_scale=1.0 / math.sqrt(D),
    )
    return jnp.transpose(out, (0, 2, 1, 3))


def flash_attention(
    q: Array, k: Array, v: Array,
    lengths: Optional[Array] = None,
    causal: bool = False,
    interpret: bool = False,
) -> Array:
    """Flash attention over [B, T, H, D] (the sequence_parallel layout)."""
    B, T, H, D = q.shape
    assert supported(T, D), f"unsupported shape T={T}, D={D}"
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    qt, kt, vt = (jnp.transpose(x, (0, 2, 1, 3)) for x in (q, k, v))
    out = _flash(qt, kt, vt, jnp.asarray(lengths, jnp.int32), causal, interpret)
    return jnp.transpose(out, (0, 2, 1, 3))
