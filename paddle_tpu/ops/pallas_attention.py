"""Flash attention as a Pallas TPU kernel (O(T) memory local attention).

The XLA path materializes the [B, H, T, T] score matrix; this kernel
streams K/V tiles through an online-softmax accumulator in VMEM so
activation memory stays O(T·D). Forward saves only (out, logsumexp);
backward recomputes scores tile by tile (flash-attention-2 style) in two
kernels (dQ; dK/dV).

The mask is a rule over positions (`ops/attention_mask.py`): the kernels
get the rule's per-index attributes as small int arrays and, from the
same rule evaluated on the host, the walk of the tiles it leaves
non-empty (`attention_mask.tile_walk`). A grid step walks only its
row's non-empty tiles (a causal mask skips the upper triangle, the
block-diffusion mask three quarters of the 2L x 2L tiles, a sliding
window all but a band) in three loops, one a kind of step and no branch
inside a step (a `lax.cond` round the mask cost every tile about a
third of its time): the row's whole tiles short of the sequence's
length, with no mask at all; its partial tiles (and a whole tile the
padding cuts) under the rule and the length; its pairs.

Two partial tiles of a row whose allowed sets are disjoint in tile-local
coordinates (a window's far and near triangle; a noised block-diffusion
query tile's own noised keys and its clean keys of earlier blocks) are
walked as ONE pair step: both score products, the two tiles merged
element by element under their masks, then max, exp, sum and the
rescaling once for the pair, the probabilities split again for the two
value products. The vector unit sets these kernels' pace, so a pair costs
about one tile's pass and not two; every allowed score is still computed
once, in float32. Which tiles pair is read from the rule's masks on the
host; a rule with no pair (causal, full) compiles no pair step.
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

from paddle_tpu.ops.attention_mask import MaskRule, rule_of, tile_walk

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


def _pair_merged(rule, side_a, side_b, a, b, union_whole):
    """ONE tile out of the two of a pair (`attention_mask.tile_walk`): a
    where the rule allows the pair in tile A, b where it does in tile B
    (the two sets are disjoint), -inf elsewhere; with A's mask, which
    splits the tile again. A side is ``(q_attrs, k_attrs)``. Where the
    host found the two sets to be the whole tile together, B's is A's
    complement and the rule is evaluated once."""
    ok_a = rule.allowed_from(*side_a)
    if union_whole:
        return ok_a, jnp.where(ok_a, a, b)
    return ok_a, jnp.where(ok_a, a, jnp.where(rule.allowed_from(*side_b), b, -jnp.inf))


def _split(ok_a, x, dtype):
    """A merged tile's two parts again, as MXU operands (x is 0 wherever
    neither side allows)."""
    x_a = jnp.where(ok_a, x, 0.0)
    return x_a.astype(dtype), (x - x_a).astype(dtype)


def _past(inside):
    """The padding as an addend: 0 on a key short of the sequence's length,
    -inf past it. A pair's step adds it to each tile (a row or a column of
    it) and has no branch."""
    return jnp.where(inside, 0.0, -jnp.inf)


def _walk_row(tab_ref, ptab_ref, cnt_ref, row, width, pair_width):
    """A row of the host's walk: ``single(j)`` and ``pair(j)``, its j-th
    single tile and its j-th pair of tiles; how many whole tiles lead its
    singles; and ``run(n_plain, single_step, pair_step, carry)``, its three
    loops, one a kind of step: the first ``n_plain`` singles with no mask
    (``single_step(False)``), the other singles masked, the pairs."""
    single = lambda j: tab_ref[row * width + j]
    pair = lambda j: (ptab_ref[2 * (row * pair_width + j)], ptab_ref[2 * (row * pair_width + j) + 1])
    n_whole, n_single, n_pair = (cnt_ref[3 * row + c] for c in range(3))

    def run(n_plain, single_step, pair_step, carry):
        carry = jax.lax.fori_loop(0, n_plain, single_step(False), carry)
        carry = jax.lax.fori_loop(n_plain, n_single, single_step(True), carry)
        if pair_width:
            carry = jax.lax.fori_loop(0, n_pair, pair_step, carry)
        return carry

    return single, pair, n_whole, run


def _whole_inside(single, n_whole, block_k, length):
    """How many of a query tile's whole key tiles end short of the
    sequence's length: they lead the row (tiles ascend), and their step has
    no mask at all; the others go with the partial tiles."""
    return jax.lax.fori_loop(
        0, n_whole, lambda j, n: n + ((single(j) + 1) * block_k <= length).astype(jnp.int32), 0)


def _key_masks(rule, q_attrs, ka_ref, block_k, length, union_whole):
    """What the forward and the dQ kernel share of a query tile's walk over
    key tiles: ``cut(kt, start, s)``, a single tile's scores with what the
    rule or the padding forbids at -inf, and ``pair_scores``, the same for
    a pair of tiles merged into one (with A's mask, which splits it again)."""

    def side(kt):
        return q_attrs, tuple(ka_ref[a, pl.ds(kt, 1), :] for a in range(len(q_attrs)))

    def inside(start):                                        # [1, bk]
        return start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1) < length

    def cut(kt, start, s):
        return jnp.where(rule.allowed_from(*side(kt)) & inside(start), s, -jnp.inf)

    def pair_scores(ta, tb, start_a, start_b, s_a, s_b):
        return _pair_merged(rule, side(ta), side(tb), s_a + _past(inside(start_a)),
                            s_b + _past(inside(start_b)), union_whole)

    return cut, pair_scores


def _fwd_kernel(len_ref, tab_ref, ptab_ref, cnt_ref, qa_ref, ka_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, *, rule, n_attr, block_k, width, pair_width, union_whole, scale):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    bq, D = q_ref.shape[2], q_ref.shape[3]
    length = len_ref[b]
    q = q_ref[0, 0]                                           # [bq, D]
    q_attrs = tuple(qa_ref[a] for a in range(n_attr))         # each [bq, 1]
    single, pair, n_whole, run = _walk_row(tab_ref, ptab_ref, cnt_ref, iq, width, pair_width)
    cut, pair_scores = _key_masks(rule, q_attrs, ka_ref, block_k, length, union_whole)

    def tile(kt):
        start = pl.multiple_of(kt * block_k, block_k)
        k_blk = k_ref[0, 0, pl.ds(start, block_k), :]
        v_blk = v_ref[0, 0, pl.ds(start, block_k), :]
        return start, v_blk, _scaled(_dot(q, k_blk, _NT), scale)           # s [bq, bk]

    def softmax_step(carry, s, pv):
        o, m, l = carry
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)                                # -inf -> 0
        return o * alpha + pv(p), m_new, l * alpha + jnp.sum(p, axis=1, keepdims=True)

    def single_step(masked):
        def step(j, carry):
            kt = single(j)
            start, v_blk, s = tile(kt)
            s = cut(kt, start, s) if masked else s
            return softmax_step(carry, s, lambda p: _dot(p.astype(v_blk.dtype), v_blk, _NN))
        return step

    def pair_step(j, carry):
        ta, tb = pair(j)
        start_a, v_a, s_a = tile(ta)
        start_b, v_b, s_b = tile(tb)
        ok_a, s = pair_scores(ta, tb, start_a, start_b, s_a, s_b)

        def pv(p):
            p_a, p_b = _split(ok_a, p, v_a.dtype)
            return _dot(p_a, v_a, _NN) + _dot(p_b, v_b, _NN)

        return softmax_step(carry, s, pv)

    o0 = jnp.zeros((bq, D), jnp.float32)
    m0 = jnp.full((bq, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    o, m, l = run(_whole_inside(single, n_whole, block_k, length), single_step, pair_step,
                  (o0, m0, l0))
    l_safe = jnp.maximum(l, 1e-20)
    o_ref[0, 0] = (o / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0, 0] = _row(jnp.where(l > 0, m + jnp.log(l_safe), _NEG))


def _dq_kernel(len_ref, tab_ref, ptab_ref, cnt_ref, qa_ref, ka_ref, q_ref, k_ref, v_ref,
               do_ref, lse_ref, delta_ref, dq_ref,
               *, rule, n_attr, block_k, width, pair_width, union_whole, scale):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    bq, D = q_ref.shape[2], q_ref.shape[3]
    length = len_ref[b]
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    lse = _col(lse_ref[0, 0, 0])                              # [bq, 1]
    delta = _col(delta_ref[0, 0, 0])
    q_attrs = tuple(qa_ref[a] for a in range(n_attr))
    single, pair, n_whole, run = _walk_row(tab_ref, ptab_ref, cnt_ref, iq, width, pair_width)
    cut, pair_scores = _key_masks(rule, q_attrs, ka_ref, block_k, length, union_whole)

    def tile(kt):
        start = pl.multiple_of(kt * block_k, block_k)
        k_blk = k_ref[0, 0, pl.ds(start, block_k), :]
        v_blk = v_ref[0, 0, pl.ds(start, block_k), :]
        return start, k_blk, _scaled(_dot(q, k_blk, _NT), scale), _dot(do, v_blk, _NT)

    def single_step(masked):
        def step(j, dq):
            kt = single(j)
            start, k_blk, s, dp = tile(kt)
            s = cut(kt, start, s) if masked else s
            ds = jnp.exp(s - lse) * (dp - delta)
            return dq + _dot(ds.astype(k_blk.dtype), k_blk, _NN)
        return step

    def pair_step(j, dq):
        ta, tb = pair(j)
        start_a, k_a, s_a, dp_a = tile(ta)
        start_b, k_b, s_b, dp_b = tile(tb)
        ok_a, s = pair_scores(ta, tb, start_a, start_b, s_a, s_b)
        p = jnp.exp(s - lse)
        ds_a, ds_b = _split(ok_a, p * (jnp.where(ok_a, dp_a, dp_b) - delta), k_a.dtype)
        return dq + _dot(ds_a, k_a, _NN) + _dot(ds_b, k_b, _NN)

    dq = run(_whole_inside(single, n_whole, block_k, length), single_step, pair_step,
             jnp.zeros((bq, D), jnp.float32))
    # the score's scale, once a tile of rows and not once a score
    dq_ref[0, 0] = _scaled(dq, scale).astype(dq_ref.dtype)


def _dkv_kernel(len_ref, tab_ref, ptab_ref, cnt_ref, qa_ref, ka_ref, q_ref, k_ref, v_ref,
                do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                *, rule, n_attr, block_q, width, pair_width, union_whole, scale):
    """One K/V tile of one QUERY head; scores are held transposed,
    [bk, bq], so that the per-query statistics broadcast along sublanes.
    A pair's two query tiles have their own statistics: `s - lse` and
    `dp - delta` are merged, then one exp and one product for both."""
    b = pl.program_id(0)
    ik = pl.program_id(2)
    bk, D = k_ref.shape[2], k_ref.shape[3]
    length = len_ref[b]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    k_attrs = tuple(ka_ref[a] for a in range(n_attr))         # each [bk, 1]
    inside = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0) < length
    single, pair, n_whole, run = _walk_row(tab_ref, ptab_ref, cnt_ref, ik, width, pair_width)

    def tile(qt):
        start = pl.multiple_of(qt * block_q, block_q)
        q_blk = q_ref[0, 0, pl.ds(start, block_q), :]
        do_blk = do_ref[0, 0, pl.ds(start, block_q), :]
        lse = lse_ref[0, 0, pl.ds(qt, 1), :]                  # [1, bq]
        delta = delta_ref[0, 0, pl.ds(qt, 1), :]
        return (q_blk, do_blk, _scaled(_dot(k, q_blk, _NT), scale) - lse,  # s - lse [bk, bq]
                _dot(v, do_blk, _NT) - delta)

    def side(qt):
        return tuple(qa_ref[a, pl.ds(qt, 1), :] for a in range(n_attr)), k_attrs

    def single_step(masked):
        def step(j, carry):
            dk, dv = carry
            qt = single(j)
            q_blk, do_blk, x, dpd = tile(qt)
            if masked:
                x = jnp.where(rule.allowed_from(*side(qt)) & inside, x, -jnp.inf)
            p = jnp.exp(x)
            dv = dv + _dot(p.astype(do_blk.dtype), do_blk, _NN)
            dk = dk + _dot((p * dpd).astype(q_blk.dtype), q_blk, _NN)
            return dk, dv
        return step

    def pair_step(j, carry):
        dk, dv = carry
        ta, tb = pair(j)
        q_a, do_a, x_a, dpd_a = tile(ta)
        q_b, do_b, x_b, dpd_b = tile(tb)
        ok_a, x = _pair_merged(rule, side(ta), side(tb), x_a, x_b, union_whole)
        p = jnp.exp(x + _past(inside))
        p_a, p_b = _split(ok_a, p, do_a.dtype)
        dv = dv + _dot(p_a, do_a, _NN) + _dot(p_b, do_b, _NN)
        ds_a, ds_b = _split(ok_a, p * jnp.where(ok_a, dpd_a, dpd_b), q_a.dtype)
        dk = dk + _dot(ds_a, q_a, _NN) + _dot(ds_b, q_b, _NN)
        return dk, dv

    # a key tile the sequence's end cuts masks every one of its query tiles
    n_plain = jnp.where((ik + 1) * bk > length, 0, n_whole)
    zeros = jnp.zeros((bk, D), jnp.float32)
    dk, dv = run(n_plain, single_step, pair_step, (zeros, zeros))
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


def _walk(rule: MaskRule, T: int, bq: int, bk: int, transpose: bool):
    """The host's walk as the kernels take it: the three int tables (scalar
    memory) and the static facts of it."""
    w = tile_walk(rule, T, bq, bk, transpose)
    tables = tuple(map(jnp.asarray, (w.table, w.pairs, w.counts)))
    return tables, dict(width=w.width, pair_width=w.pair_width, union_whole=w.union_whole)


def _run_fwd(q, k, v, lengths, rule, bq, bk, scale, interpret):
    B, H, T, D = q.shape
    group = H // k.shape[1]
    tables, walk = _walk(rule, T, bq, bk, False)
    qa, _ = _attr_arrays(rule, T, bq)
    _, ka = _attr_arrays(rule, T, bk)
    n_attr = qa.shape[0]
    qspec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0))
    kvspec = pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h // group, 0, 0))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, rule=rule, n_attr=n_attr, block_k=bk,
                          scale=scale, **walk),
        name="attention_fwd",
        grid=(B, H, T // bq),
        in_specs=[
            *[_SMEM] * 4,
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
    )(lengths, *tables, qa, ka, q, k, v)
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

    tables, walk = _walk(rule, T, bq, bk, False)
    qspec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0))
    kv_full = pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h // group, 0, 0))
    stat_q = pl.BlockSpec((1, 1, 1, 1, bq), lambda b, h, i: (b, h, i, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, rule=rule, n_attr=n_attr, block_k=bk,
                          scale=scale, **walk),
        name="attention_dq",
        grid=(B, H, nq),
        in_specs=[
            *[_SMEM] * 4,
            pl.BlockSpec((n_attr, bq, 1), lambda b, h, i: (0, i, 0)),
            pl.BlockSpec(ka_row.shape, lambda b, h, i: (0, 0, 0)),
            qspec, kv_full, kv_full, qspec, stat_q, stat_q,
        ],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        interpret=interpret,
        compiler_params=_params(),
    )(lengths, *tables, qa_col, ka_row, q, k, v, do, lse, delta)

    tables, walk = _walk(rule, T, bq, bk, True)
    q_full = pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h, 0, 0))
    k_blk = pl.BlockSpec((1, 1, bk, D), lambda b, h, i: (b, h // group, i, 0))
    d_blk = pl.BlockSpec((1, 1, bk, D), lambda b, h, i: (b, h, i, 0))
    stat_full = pl.BlockSpec((1, 1, nq, bq), lambda b, h, i: (b, h, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, rule=rule, n_attr=n_attr, block_q=bq,
                          scale=scale, **walk),
        name="attention_dkv",
        grid=(B, H, T // bk),
        in_specs=[
            *[_SMEM] * 4,
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
    )(lengths, *tables, qa_row, ka_col, q, k, v, do,
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
    float32 temporaries: 12 score-sized ones, for a pair's step holds two
    score tiles and their two cotangents where a single's holds one of
    each (8 were planned before tiles were paired)."""
    block = default_block(T)
    if not block or D > 256 or D % 8:
        return False
    resident = 2 * 2 * T * D * itemsize
    tiles = 12 * block * block * 4 + 8 * block * D * 4
    return resident + tiles <= _VMEM_PLAN


def walk_census(rule: MaskRule, T: int) -> str:
    """What the kernels walk under ``rule`` at `default_block(T)`, for the
    log: a constant of the compiled program, no measurement."""
    block = default_block(T)
    return "; ".join(f"{name} {tile_walk(rule, T, block, block, transpose).census}"
                     for name, transpose in (("fwd/dq", False), ("dkv", True)))


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
