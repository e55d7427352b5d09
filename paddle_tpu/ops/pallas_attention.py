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
stays whole in VMEM while its query heads' tiles pass.

The scores may come in PARTS: q and k are then tuples of as many arrays,
part i ``[B, H, T, D_i]`` against ``[B, Hk_i, T, D_i]`` with a head count of
its own (latent attention: 128 lanes that differ by head, and 64 rotary
lanes that every query head reads from ONE key head, which is never
broadcast in memory); a tile's score products are summed before the mask
and the softmax's vector work, dq and dk come back a part, and a part's dk
is summed over the query heads that share its key head. The values have a
width of their own (``v [B, Hkv, T, Dv]``, and so the output). One part of
the values' width is the kernel as it always was. Correctness is
tested in interpret mode on CPU against the XLA path
(tests/test_pallas_attention.py, tests/test_block_diffusion_moe.py); what
Mosaic accepts, by compiling for a described v5e
(tests/test_chip_compile.py).
"""

from __future__ import annotations

import functools
import math
import operator
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


def _scores(a_parts, b_parts):
    """sum over the score parts of a_i @ b_i.T."""
    return functools.reduce(operator.add, (_dot(a, b, _NT) for a, b in zip(a_parts, b_parts)))


def _rows(refs, start, size):
    """A tile of rows of each part's [1, 1, T, D_i] block."""
    return tuple(r[0, 0, pl.ds(start, size), :] for r in refs)


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


def _fwd_kernel(len_ref, tab_ref, ptab_ref, cnt_ref, qa_ref, ka_ref, *refs,
                rule, n_attr, parts, block_k, width, pair_width, union_whole, scale):
    q_refs, k_refs, (v_ref, o_ref, lse_ref) = refs[:parts], refs[parts:2 * parts], refs[2 * parts:]
    b = pl.program_id(0)
    iq = pl.program_id(2)
    bq, D = q_refs[0].shape[2], v_ref.shape[3]
    length = len_ref[b]
    q = tuple(r[0, 0] for r in q_refs)                        # each [bq, D_i]
    q_attrs = tuple(qa_ref[a] for a in range(n_attr))         # each [bq, 1]
    single, pair, n_whole, run = _walk_row(tab_ref, ptab_ref, cnt_ref, iq, width, pair_width)
    cut, pair_scores = _key_masks(rule, q_attrs, ka_ref, block_k, length, union_whole)

    def tile(kt):
        start = pl.multiple_of(kt * block_k, block_k)
        k_blk = _rows(k_refs, start, block_k)
        v_blk = v_ref[0, 0, pl.ds(start, block_k), :]
        return start, v_blk, _scaled(_scores(q, k_blk), scale)             # s [bq, bk]

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


def _dq_kernel(len_ref, tab_ref, ptab_ref, cnt_ref, qa_ref, ka_ref, *refs,
               rule, n_attr, parts, block_k, width, pair_width, union_whole, scale):
    q_refs, k_refs = refs[:parts], refs[parts:2 * parts]
    v_ref, do_ref, lse_ref, delta_ref = refs[2 * parts:2 * parts + 4]
    dq_refs = refs[2 * parts + 4:]
    b = pl.program_id(0)
    iq = pl.program_id(2)
    bq = q_refs[0].shape[2]
    length = len_ref[b]
    q = tuple(r[0, 0] for r in q_refs)
    do = do_ref[0, 0]
    lse = _col(lse_ref[0, 0, 0])                              # [bq, 1]
    delta = _col(delta_ref[0, 0, 0])
    q_attrs = tuple(qa_ref[a] for a in range(n_attr))
    single, pair, n_whole, run = _walk_row(tab_ref, ptab_ref, cnt_ref, iq, width, pair_width)
    cut, pair_scores = _key_masks(rule, q_attrs, ka_ref, block_k, length, union_whole)

    def tile(kt):
        start = pl.multiple_of(kt * block_k, block_k)
        k_blk = _rows(k_refs, start, block_k)
        v_blk = v_ref[0, 0, pl.ds(start, block_k), :]
        return start, k_blk, _scaled(_scores(q, k_blk), scale), _dot(do, v_blk, _NT)

    def add(dq, ds, k_blk):
        ds = ds.astype(k_blk[0].dtype)
        return tuple(d + _dot(ds, k, _NN) for d, k in zip(dq, k_blk))

    def single_step(masked):
        def step(j, dq):
            kt = single(j)
            start, k_blk, s, dp = tile(kt)
            s = cut(kt, start, s) if masked else s
            return add(dq, jnp.exp(s - lse) * (dp - delta), k_blk)
        return step

    def pair_step(j, dq):
        ta, tb = pair(j)
        start_a, k_a, s_a, dp_a = tile(ta)
        start_b, k_b, s_b, dp_b = tile(tb)
        ok_a, s = pair_scores(ta, tb, start_a, start_b, s_a, s_b)
        p = jnp.exp(s - lse)
        ds_a, ds_b = _split(ok_a, p * (jnp.where(ok_a, dp_a, dp_b) - delta), k_a[0].dtype)
        return tuple(d + _dot(ds_a, a, _NN) + _dot(ds_b, b, _NN) for d, a, b in zip(dq, k_a, k_b))

    dq = run(_whole_inside(single, n_whole, block_k, length), single_step, pair_step,
             tuple(jnp.zeros((bq, r.shape[3]), jnp.float32) for r in q_refs))
    # the score's scale, once a tile of rows and not once a score
    for d, ref in zip(dq, dq_refs):
        ref[0, 0] = _scaled(d, scale).astype(ref.dtype)


def _dkv_kernel(len_ref, tab_ref, ptab_ref, cnt_ref, qa_ref, ka_ref, *refs,
                rule, n_attr, parts, block_q, width, pair_width, union_whole, scale):
    """One K/V tile of one QUERY head; scores are held transposed,
    [bk, bq], so that the per-query statistics broadcast along sublanes.
    A pair's two query tiles have their own statistics: `s - lse` and
    `dp - delta` are merged, then one exp and one product for both."""
    q_refs, k_refs = refs[:parts], refs[parts:2 * parts]
    v_ref, do_ref, lse_ref, delta_ref = refs[2 * parts:2 * parts + 4]
    dk_refs, dv_ref = refs[2 * parts + 4:-1], refs[-1]
    b = pl.program_id(0)
    ik = pl.program_id(2)
    bk = k_refs[0].shape[2]
    length = len_ref[b]
    k = tuple(r[0, 0] for r in k_refs)
    v = v_ref[0, 0]
    k_attrs = tuple(ka_ref[a] for a in range(n_attr))         # each [bk, 1]
    inside = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0) < length
    single, pair, n_whole, run = _walk_row(tab_ref, ptab_ref, cnt_ref, ik, width, pair_width)

    def tile(qt):
        start = pl.multiple_of(qt * block_q, block_q)
        q_blk = _rows(q_refs, start, block_q)
        do_blk = do_ref[0, 0, pl.ds(start, block_q), :]
        lse = lse_ref[0, 0, pl.ds(qt, 1), :]                  # [1, bq]
        delta = delta_ref[0, 0, pl.ds(qt, 1), :]
        return (q_blk, do_blk, _scaled(_scores(k, q_blk), scale) - lse,    # s - lse [bk, bq]
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
            ds = (p * dpd).astype(q_blk[0].dtype)
            return tuple(d + _dot(ds, q, _NN) for d, q in zip(dk, q_blk)), dv
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
        ds_a, ds_b = _split(ok_a, p * jnp.where(ok_a, dpd_a, dpd_b), q_a[0].dtype)
        return tuple(d + _dot(ds_a, a, _NN) + _dot(ds_b, b, _NN)
                     for d, a, b in zip(dk, q_a, q_b)), dv

    # a key tile the sequence's end cuts masks every one of its query tiles
    n_plain = jnp.where((ik + 1) * bk > length, 0, n_whole)
    zeros = lambda ref: jnp.zeros((bk, ref.shape[3]), jnp.float32)
    dk, dv = run(n_plain, single_step, pair_step, (tuple(map(zeros, k_refs)), zeros(v_ref)))
    for d, ref in zip(dk, dk_refs):
        ref[0, 0] = _scaled(d, scale).astype(ref.dtype)
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


def _head_blocks(arrays, rows, tiled, H=0):
    """A BlockSpec a [B, heads, T, D] array for a grid (batch, query head,
    tile): ``rows`` rows of a head, the grid's tile of them where
    ``tiled``, else all (rows = T). The head is the grid's query head, or
    with ``H`` (the query heads: a key or value operand, whose heads may be
    fewer) the one that query head h reads, ``h // (H / heads)``."""
    def spec(x):
        group = H // x.shape[1] if H else 0
        head = (lambda h: h // group) if H else (lambda h: h)
        tile = (lambda i: i) if tiled else (lambda i: 0)
        return pl.BlockSpec((1, 1, rows, x.shape[3]), lambda b, h, i: (b, head(h), tile(i), 0))
    return [spec(x) for x in arrays]


def _run_fwd(q, k, v, lengths, rule, bq, bk, scale, interpret):
    """q, k: tuples of the score parts (module docstring)."""
    B, H, T, _ = q[0].shape
    tables, walk = _walk(rule, T, bq, bk, False)
    qa, _ = _attr_arrays(rule, T, bq)
    _, ka = _attr_arrays(rule, T, bk)
    n_attr = qa.shape[0]
    out_shape = jax.ShapeDtypeStruct((B, H, T, v.shape[3]), q[0].dtype)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, rule=rule, n_attr=n_attr, parts=len(q), block_k=bk,
                          scale=scale, **walk),
        name="attention_fwd",
        grid=(B, H, T // bq),
        in_specs=[
            *[_SMEM] * 4,
            pl.BlockSpec((n_attr, bq, 1), lambda b, h, i: (0, i, 0)),
            pl.BlockSpec(ka.shape, lambda b, h, i: (0, 0, 0)),
            *_head_blocks(q, bq, True), *_head_blocks(k + (v,), T, False, H),
        ],
        out_specs=[
            *_head_blocks([out_shape], bq, True),
            pl.BlockSpec((1, 1, 1, 1, bq), lambda b, h, i: (b, h, i, 0, 0)),
        ],
        out_shape=[
            out_shape,
            jax.ShapeDtypeStruct((B, H, T // bq, 1, bq), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_params(),
    )(lengths, *tables, qa, ka, *q, *k, v)
    return out, lse


def _run_bwd(q, k, v, do, out, lse, lengths, rule, bq, bk, scale, interpret):
    B, H, T, _ = q[0].shape
    parts = len(q)
    nq = T // bq
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = delta.reshape(B, H, nq, 1, bq)
    qa_col, qa_row = _attr_arrays(rule, T, bq)
    ka_col, ka_row = _attr_arrays(rule, T, bk)
    n_attr = qa_col.shape[0]

    tables, walk = _walk(rule, T, bq, bk, False)
    stat_q = pl.BlockSpec((1, 1, 1, 1, bq), lambda b, h, i: (b, h, i, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, rule=rule, n_attr=n_attr, parts=parts, block_k=bk,
                          scale=scale, **walk),
        name="attention_dq",
        grid=(B, H, nq),
        in_specs=[
            *[_SMEM] * 4,
            pl.BlockSpec((n_attr, bq, 1), lambda b, h, i: (0, i, 0)),
            pl.BlockSpec(ka_row.shape, lambda b, h, i: (0, 0, 0)),
            *_head_blocks(q, bq, True), *_head_blocks(k + (v,), T, False, H),
            *_head_blocks([do], bq, True), stat_q, stat_q,
        ],
        out_specs=_head_blocks(q, bq, True),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in q],
        interpret=interpret,
        compiler_params=_params(),
    )(lengths, *tables, qa_col, ka_row, *q, *k, v, do, lse, delta)

    tables, walk = _walk(rule, T, bq, bk, True)
    stat_full = pl.BlockSpec((1, 1, nq, bq), lambda b, h, i: (b, h, 0, 0))
    # a gradient a QUERY head: [B, H, T, D_i] for part i's keys, [B, H, T, Dv]
    d_shapes = [jax.ShapeDtypeStruct((B, H, T, x.shape[3]), x.dtype) for x in k + (v,)]
    *dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, rule=rule, n_attr=n_attr, parts=parts, block_q=bq,
                          scale=scale, **walk),
        name="attention_dkv",
        grid=(B, H, T // bk),
        in_specs=[
            *[_SMEM] * 4,
            pl.BlockSpec(qa_row.shape, lambda b, h, i: (0, 0, 0)),
            pl.BlockSpec((n_attr, bk, 1), lambda b, h, i: (0, i, 0)),
            *_head_blocks(q, T, False), *_head_blocks(k + (v,), bk, True, H),
            *_head_blocks([do], T, False), stat_full, stat_full,
        ],
        out_specs=_head_blocks(d_shapes, bk, True),
        out_shape=d_shapes,
        interpret=interpret,
        compiler_params=_params(),
    )(lengths, *tables, qa_row, ka_col, *q, *k, v, do,
      lse.reshape(B, H, nq, bq), delta.reshape(B, H, nq, bq))

    def fold(d, x):
        """The query heads that share a K/V head: summed in float32."""
        heads = x.shape[1]
        if heads == H:
            return d
        return jnp.sum(d.reshape(B, heads, H // heads, T, d.shape[3]).astype(jnp.float32),
                       axis=2).astype(d.dtype)

    return tuple(dq), tuple(map(fold, dk, k)), fold(dv, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, lengths, rule, blocks, interpret):  # q, k: tuples of parts; blocks: (bq, bk, scale)
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


def as_parts(x) -> tuple:
    """The score parts of an operand: a tuple as it is, one array as a 1-tuple."""
    return tuple(x) if isinstance(x, (tuple, list)) else (x,)


def supported(T: int, D, itemsize: int = 2, value_dim: int = 0) -> bool:
    """Shapes the kernels handle: T a multiple of a tile edge, heads the
    MXU takes whole, and a K/V head (backward: a query head and its
    cotangent) that fits the planned VMEM twice over beside the tiles'
    float32 temporaries: 12 score-sized ones, for a pair's step holds two
    score tiles and their two cotangents where a single's holds one of
    each (8 were planned before tiles were paired). ``D``: the scores'
    width, or the widths of their parts; ``value_dim``: the values' (the
    scores' by default). A part narrower than a lane tile sits in VMEM as
    a whole one."""
    widths = as_parts(D)
    value_dim = value_dim or sum(widths)
    block = default_block(T)
    if not block or any(d > 256 or d % 8 for d in widths + (value_dim,)):
        return False
    lanes = lambda d: -(-d // _LANES) * _LANES
    held = sum(map(lanes, widths)) + lanes(value_dim)
    resident = 2 * T * held * itemsize
    tiles = 12 * block * block * 4 + 4 * block * held * 4
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
    (``causal`` is the old flag for the causal rule). ``q`` and ``k`` may
    be tuples of score parts, part i [B, T, H, D_i] against [B, T, Hk_i,
    D_i] (module docstring); the values' width is their own. ``block``: the
    tile edge, `default_block(T)` unless a test wants small tiles.
    ``scale``: what the scores are multiplied by, 1/sqrt(D) (D the parts'
    sum) by default; a caller that folded it into q passes 1."""
    q, k = as_parts(q), as_parts(k)
    B, T, H, _ = q[0].shape
    D = sum(x.shape[3] for x in q)
    rule = rule or rule_of(causal=causal)
    rule.check(T)
    block = block or default_block(T)
    assert block and T % block == 0, f"unsupported shape T={T}, D={D}"
    assert len(q) == len(k) and all(a.shape[3] == b.shape[3] for a, b in zip(q, k)), (
        "a score part's queries and keys have one width")
    for x in (*k, v):
        assert H % x.shape[2] == 0, f"{H} query heads over {x.shape[2]} K/V heads"
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    by_head = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    out = _flash(tuple(map(by_head, q)), tuple(map(by_head, k)), by_head(v),
                 jnp.asarray(lengths, jnp.int32), rule,
                 (block, block, 1.0 / math.sqrt(D) if scale is None else float(scale)),
                 interpret)
    return jnp.transpose(out, (0, 2, 1, 3))
