"""Flash attention as a Pallas TPU kernel (O(T) memory local attention).

The XLA path materializes the [B, H, T, T] score matrix; this kernel
streams K/V tiles through an online-softmax accumulator in VMEM so
activation memory stays O(T·D). Forward saves only (out, logsumexp);
backward recomputes scores tile by tile (flash-attention-2 style) in two
kernels (dQ; dK/dV).

The mask is a rule over positions (`ops/attention_mask.py`): the kernels
get the rule's per-index attributes as small int arrays and, from the
same rule evaluated on the host, a table of the tiles it leaves
non-empty. A grid step walks only its row's non-empty tiles (a causal
mask skips the upper triangle, the block-diffusion mask three quarters
of the 2L x 2L tiles, a sliding window all but a band) and applies the
rule only inside tiles it cuts.
Grouped-query heads: query head h reads K/V head ``h // (H / Hkv)``;
dK/dV come back a query head and are summed over each group outside.

Layout: q [B, H, T, D], k/v [B, Hkv, T, D] inside the kernels (callers
transpose from the [B, T, H, D] sequence_parallel layout). A K/V head
stays whole in VMEM while its query heads' tiles pass. Correctness is
tested in interpret mode on CPU against the XLA path
(tests/test_pallas_attention.py, tests/test_block_diffusion_moe.py); what
Mosaic accepts, by compiling for a described v5e
(tests/test_chip_compile.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.attention_mask import MaskRule, rule_of, tile_lists, tile_occupancy

Array = jax.Array

_NEG = -1e30
_LANES = 128
# scoped VMEM the kernels may use, and what `supported` lets them plan for
# (a K/V head, or a query head and its cotangent, double-buffered, plus the
# tile temporaries); v5e has 128 MiB
_VMEM_LIMIT = 64 * 1024 * 1024
_VMEM_PLAN = 40 * 1024 * 1024


def default_block(T: int) -> int:
    """The tile edge for a T-long axis: the largest of 512/256/128 that
    divides it (0 where none does)."""
    return next((b for b in (512, 256, 128) if T % b == 0), 0)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b


def _col(row):
    """f32 [1, n] -> [n, 1] through an aligned 2-D transpose."""
    n = row.shape[1]
    return jnp.broadcast_to(row, (_LANES, n)).T[:, 0:1]


def _row(col):
    """f32 [n, 1] -> [1, n]."""
    n = col.shape[0]
    return jnp.broadcast_to(col, (n, _LANES)).T[0:1, :]


def _scaled(x, scale):
    """x * scale, and nothing at all where the caller folded the scale into
    q (scale 1): a multiply a score is a sixth of the forward's vector work."""
    return x if scale == 1.0 else x * scale


def _masked(s, rule, q_attrs, k_attrs, k_idx, length):
    """Scores with the pairs the rule (or the padding) forbids at -inf."""
    ok = rule.allowed_from(q_attrs, k_attrs) & (k_idx < length)
    return jnp.where(ok, s, -jnp.inf)


def _fwd_kernel(len_ref, tab_ref, cnt_ref, qa_ref, ka_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, *, rule, n_attr, block_k, width, scale):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    bq, D = q_ref.shape[2], q_ref.shape[3]
    length = len_ref[b]
    q = q_ref[0, 0]                                           # [bq, D]
    q_attrs = tuple(qa_ref[a] for a in range(n_attr))         # each [bq, 1]

    def body(j, carry):
        o, m, l = carry
        code = tab_ref[iq * width + j]
        kt = jax.lax.shift_right_logical(code, 1)
        start = pl.multiple_of(kt * block_k, block_k)
        k_blk = k_ref[0, 0, pl.ds(start, block_k), :]
        v_blk = v_ref[0, 0, pl.ds(start, block_k), :]
        s = _scaled(_dot(q, k_blk, _NT), scale)               # [bq, bk]

        def cut(s):
            k_attrs = tuple(ka_ref[a, pl.ds(kt, 1), :] for a in range(n_attr))
            k_idx = start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            return _masked(s, rule, q_attrs, k_attrs, k_idx, length)

        partial = ((code & 1) == 1) | (start + block_k > length)
        s = jax.lax.cond(partial, cut, lambda s: s, s)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)                                # -inf -> 0
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        o = o * alpha + _dot(p.astype(v_blk.dtype), v_blk, _NN)
        return o, m_new, l

    o0 = jnp.zeros((bq, D), jnp.float32)
    m0 = jnp.full((bq, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, cnt_ref[iq], body, (o0, m0, l0))
    l_safe = jnp.maximum(l, 1e-20)
    o_ref[0, 0] = (o / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0, 0] = _row(jnp.where(l > 0, m + jnp.log(l_safe), _NEG))


def _dq_kernel(len_ref, tab_ref, cnt_ref, qa_ref, ka_ref, q_ref, k_ref, v_ref,
               do_ref, lse_ref, delta_ref, dq_ref,
               *, rule, n_attr, block_k, width, scale):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    bq, D = q_ref.shape[2], q_ref.shape[3]
    length = len_ref[b]
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    lse = _col(lse_ref[0, 0, 0])                              # [bq, 1]
    delta = _col(delta_ref[0, 0, 0])
    q_attrs = tuple(qa_ref[a] for a in range(n_attr))

    def body(j, dq):
        code = tab_ref[iq * width + j]
        kt = jax.lax.shift_right_logical(code, 1)
        start = pl.multiple_of(kt * block_k, block_k)
        k_blk = k_ref[0, 0, pl.ds(start, block_k), :]
        v_blk = v_ref[0, 0, pl.ds(start, block_k), :]
        s = _scaled(_dot(q, k_blk, _NT), scale)

        def cut(s):
            k_attrs = tuple(ka_ref[a, pl.ds(kt, 1), :] for a in range(n_attr))
            k_idx = start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            return _masked(s, rule, q_attrs, k_attrs, k_idx, length)

        partial = ((code & 1) == 1) | (start + block_k > length)
        s = jax.lax.cond(partial, cut, lambda s: s, s)
        p = jnp.exp(s - lse)
        dp = _dot(do, v_blk, _NT)
        ds = p * (dp - delta)
        return dq + _dot(ds.astype(k_blk.dtype), k_blk, _NN)

    dq = jax.lax.fori_loop(0, cnt_ref[iq], body, jnp.zeros((bq, D), jnp.float32))
    # the score's scale, once a tile of rows and not once a score
    dq_ref[0, 0] = _scaled(dq, scale).astype(dq_ref.dtype)


def _dkv_kernel(len_ref, tab_ref, cnt_ref, qa_ref, ka_ref, q_ref, k_ref, v_ref,
                do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                *, rule, n_attr, block_q, width, scale):
    """One K/V tile of one QUERY head; scores are held transposed,
    [bk, bq], so that the per-query statistics broadcast along sublanes."""
    b = pl.program_id(0)
    ik = pl.program_id(2)
    bk, D = k_ref.shape[2], k_ref.shape[3]
    length = len_ref[b]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    k_attrs = tuple(ka_ref[a] for a in range(n_attr))         # each [bk, 1]
    k_idx = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
    past_end = (ik + 1) * bk > length

    def body(j, carry):
        dk, dv = carry
        code = tab_ref[ik * width + j]
        qt = jax.lax.shift_right_logical(code, 1)
        start = pl.multiple_of(qt * block_q, block_q)
        q_blk = q_ref[0, 0, pl.ds(start, block_q), :]
        do_blk = do_ref[0, 0, pl.ds(start, block_q), :]
        lse = lse_ref[0, 0, pl.ds(qt, 1), :]                  # [1, bq]
        delta = delta_ref[0, 0, pl.ds(qt, 1), :]
        s = _scaled(_dot(k, q_blk, _NT), scale)               # [bk, bq]

        def cut(s):
            q_attrs = tuple(qa_ref[a, pl.ds(qt, 1), :] for a in range(n_attr))
            return _masked(s, rule, q_attrs, k_attrs, k_idx, length)

        partial = ((code & 1) == 1) | past_end
        s = jax.lax.cond(partial, cut, lambda s: s, s)
        p = jnp.exp(s - lse)
        dv = dv + _dot(p.astype(do_blk.dtype), do_blk, _NN)
        dp = _dot(v, do_blk, _NT)
        ds = p * (dp - delta)
        dk = dk + _dot(ds.astype(q_blk.dtype), q_blk, _NN)
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        0, cnt_ref[ik], body,
        (jnp.zeros((bk, D), jnp.float32), jnp.zeros((bk, D), jnp.float32)),
    )
    dk_ref[0, 0] = _scaled(dk, scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


# small int tables visible to every program: scalar memory
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT,
    )


def _attr_arrays(rule: MaskRule, T: int, block: int):
    """The rule's per-index attributes, as a column a tile ([A, T, 1]) and
    as a row a tile ([A, T/block, block])."""
    attrs = jnp.stack([jnp.asarray(a, jnp.int32)
                       for a in rule.attrs(np.arange(T), T)])
    return attrs[:, :, None], attrs.reshape(len(attrs), T // block, block)


def _tables(rule: MaskRule, T: int, bq: int, bk: int, transpose: bool):
    occ = tile_occupancy(rule, T, bq, bk)
    table, counts, width = tile_lists(occ.T if transpose else occ)
    return jnp.asarray(table), jnp.asarray(counts), width


def _run_fwd(q, k, v, lengths, rule, bq, bk, scale, interpret):
    B, H, T, D = q.shape
    group = H // k.shape[1]
    table, counts, width = _tables(rule, T, bq, bk, False)
    qa, _ = _attr_arrays(rule, T, bq)
    _, ka = _attr_arrays(rule, T, bk)
    n_attr = qa.shape[0]
    qspec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0))
    kvspec = pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h // group, 0, 0))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, rule=rule, n_attr=n_attr, block_k=bk,
                          width=width, scale=scale),
        name="attention_fwd",
        grid=(B, H, T // bq),
        in_specs=[
            _SMEM, _SMEM, _SMEM,
            pl.BlockSpec((n_attr, bq, 1), lambda b, h, i: (0, i, 0)),
            pl.BlockSpec(ka.shape, lambda b, h, i: (0, 0, 0)),
            qspec, kvspec, kvspec,
        ],
        out_specs=[
            qspec,
            pl.BlockSpec((1, 1, 1, 1, bq), lambda b, h, i: (b, h, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, T // bq, 1, bq), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_params(),
    )(lengths, table, counts, qa, ka, q, k, v)
    return out, lse


def _run_bwd(q, k, v, do, out, lse, lengths, rule, bq, bk, scale, interpret):
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    nq = T // bq
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = delta.reshape(B, H, nq, 1, bq)
    qa_col, qa_row = _attr_arrays(rule, T, bq)
    ka_col, ka_row = _attr_arrays(rule, T, bk)
    n_attr = qa_col.shape[0]

    table, counts, width = _tables(rule, T, bq, bk, False)
    qspec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0))
    kv_full = pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h // group, 0, 0))
    stat_q = pl.BlockSpec((1, 1, 1, 1, bq), lambda b, h, i: (b, h, i, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, rule=rule, n_attr=n_attr, block_k=bk,
                          width=width, scale=scale),
        name="attention_dq",
        grid=(B, H, nq),
        in_specs=[
            _SMEM, _SMEM, _SMEM,
            pl.BlockSpec((n_attr, bq, 1), lambda b, h, i: (0, i, 0)),
            pl.BlockSpec(ka_row.shape, lambda b, h, i: (0, 0, 0)),
            qspec, kv_full, kv_full, qspec, stat_q, stat_q,
        ],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        interpret=interpret,
        compiler_params=_params(),
    )(lengths, table, counts, qa_col, ka_row, q, k, v, do, lse, delta)

    table_t, counts_t, width_t = _tables(rule, T, bq, bk, True)
    q_full = pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h, 0, 0))
    k_blk = pl.BlockSpec((1, 1, bk, D), lambda b, h, i: (b, h // group, i, 0))
    d_blk = pl.BlockSpec((1, 1, bk, D), lambda b, h, i: (b, h, i, 0))
    stat_full = pl.BlockSpec((1, 1, nq, bq), lambda b, h, i: (b, h, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, rule=rule, n_attr=n_attr, block_q=bq,
                          width=width_t, scale=scale),
        name="attention_dkv",
        grid=(B, H, T // bk),
        in_specs=[
            _SMEM, _SMEM, _SMEM,
            pl.BlockSpec(qa_row.shape, lambda b, h, i: (0, 0, 0)),
            pl.BlockSpec((n_attr, bk, 1), lambda b, h, i: (0, i, 0)),
            q_full, k_blk, k_blk, q_full, stat_full, stat_full,
        ],
        out_specs=[d_blk, d_blk],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, T, D), v.dtype),
        ],
        interpret=interpret,
        compiler_params=_params(),
    )(lengths, table_t, counts_t, qa_row, ka_col, q, k, v, do,
      lse.reshape(B, H, nq, bq), delta.reshape(B, H, nq, bq))
    if group > 1:
        # the query heads that share a K/V head: summed in float32
        fold = lambda x: jnp.sum(
            x.reshape(B, Hkv, group, T, D).astype(jnp.float32), axis=2
        ).astype(x.dtype)
        dk, dv = fold(dk), fold(dv)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, lengths, rule, blocks, interpret):  # blocks: (bq, bk, scale)
    out, _ = _run_fwd(q, k, v, lengths, rule, *blocks, interpret)
    return out


# What the backward needs and only the forward KERNEL can remake: named, so
# that a recomputation block (`graph/network.py::_forward_block`) keeps them
# and does not run `attention_fwd` a second time. `out` is as large as q,
# `lse` a 32nd of it in float32 at D = 128. Outside a `jax.checkpoint` with a
# policy a name is the identity.
KEPT_RESIDUALS = ("flash_attention_out", "flash_attention_lse")


def _flash_fwd(q, k, v, lengths, rule, blocks, interpret):
    out, lse = _run_fwd(q, k, v, lengths, rule, *blocks, interpret)
    out, lse = map(checkpoint_name, (out, lse), KEPT_RESIDUALS)
    return out, (q, k, v, out, lse, lengths)


def _flash_bwd(rule, blocks, interpret, res, g):
    q, k, v, out, lse, lengths = res
    dq, dk, dv = _run_bwd(q, k, v, g, out, lse, lengths, rule, *blocks, interpret)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def supported(T: int, D: int, itemsize: int = 2) -> bool:
    """Shapes the kernels handle: T a multiple of a tile edge, a head the
    MXU takes whole, and a K/V head (backward: a query head and its
    cotangent) that fits the planned VMEM twice over beside the tiles'
    float32 temporaries."""
    block = default_block(T)
    if not block or D > 256 or D % 8:
        return False
    resident = 2 * 2 * T * D * itemsize
    tiles = 8 * block * block * 4 + 8 * block * D * 4
    return resident + tiles <= _VMEM_PLAN


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
    rule: Optional[MaskRule] = None,
    block: Optional[int] = None,
    scale: Optional[float] = None,
) -> Array:
    """Flash attention over [B, T, H, D] queries and [B, T, Hkv, D] keys
    and values (the sequence_parallel layout), masked by ``rule``
    (``causal`` is the old flag for the causal rule). ``block``: the tile
    edge, `default_block(T)` unless a test wants small tiles. ``scale``:
    what the scores are multiplied by, 1/sqrt(D) by default; a caller that
    folded it into q passes 1."""
    B, T, H, D = q.shape
    rule = rule or rule_of(causal=causal)
    rule.check(T)
    block = block or default_block(T)
    assert block and T % block == 0, f"unsupported shape T={T}, D={D}"
    assert H % k.shape[2] == 0, f"{H} query heads over {k.shape[2]} K/V heads"
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    qt, kt, vt = (jnp.transpose(x, (0, 2, 1, 3)) for x in (q, k, v))
    out = _flash(qt, kt, vt, jnp.asarray(lengths, jnp.int32), rule,
                 (block, block, 1.0 / math.sqrt(D) if scale is None else float(scale)),
                 interpret)
    return jnp.transpose(out, (0, 2, 1, 3))
