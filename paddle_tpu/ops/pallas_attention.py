"""Flash attention as a Pallas TPU kernel (O(T) memory local attention).

The XLA path materializes the [B, H, T, T] score matrix; this kernel
streams K/V tiles through an online-softmax accumulator in VMEM so
activation memory stays O(T·D). Forward saves only (out, logsumexp);
backward recomputes scores tile by tile (flash-attention-2 style) in ONE
kernel, `attention_bwd`: its grid step is a key tile of a query head, which
leaves with the tile's dK and dV; the head's dQ is summed in float32 in
VMEM while the head's key tiles pass and rounded once after the last (a
head's [T, D] float32 fits there: no partial dQ in HBM, no atomic add), so
a tile's scores, mask and exp are worked out once in the backward pass.

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
dK/dV come back a query head and are summed over each group outside; dQ
is a query head's own.

Layout: operands and results are the caller's [B, T, heads, D] arrays
(the sequence_parallel layout, and what a projection leaves: a free
reshape of its [B, T, heads*D]). A kernel sees one head's [rows, D] tile in
VMEM either way; where it lies in HBM is the BlockSpec's business
(`by_column`): a head of whole lane tiles (D a multiple of 128), or a
part's only head, is a COLUMN BLOCK of [B, T, heads*D], ``rows`` runs of D
lanes at the pitch of a position, so nothing is transposed before or after
the kernels; a part with several heads narrower than a lane tile (latent
attention's 32 rotary query heads of 64) is transposed to [B, heads, T, D]
round them, the one place a transpose is left, for Mosaic cannot cut a
64-lane block out of a wider row. What else touches these arrays stays on
[B, T, heads*D] and never splits its lanes into two axes (XLA would answer
with a relayout copy of the whole array: a tiled array's lane tile is 128
wide): the backward's per-row sum of do * out is a third small kernel,
`attention_delta`, the fold of a group's dK and dV adds 128-lane slices,
the `out` a recomputation block keeps is kept as the kernel wrote it, and
a per-head output gate is `gate_heads`, one more small kernel each way.
Forward: a K/V head stays whole in VMEM while its query heads' tiles pass.
Backward: a query head, its cotangent and its dQ stay whole while its key
tiles pass.

The scores may come in PARTS: q and k are then tuples of as many arrays,
part i ``[B, T, H, D_i]`` against ``[B, T, Hk_i, D_i]`` with a head count of
its own (latent attention: 128 lanes that differ by head, and 64 rotary
lanes that every query head reads from ONE key head, which is never
broadcast in memory); a tile's score products are summed before the mask
and the softmax's vector work, dq and dk come back a part, and a part's dk
is summed over the query heads that share its key head. The values have a
width of their own (``v [B, T, Hkv, Dv]``, and so the output). One part of
the values' width is the kernel as it always was. Correctness is
tested in interpret mode on CPU against the XLA path
(tests/test_pallas_attention.py, tests/test_block_diffusion_moe.py); what
Mosaic accepts, by compiling for a described v5e
(tests/test_chip_compile.py).
"""

from __future__ import annotations

import functools
import itertools
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
# (a K/V head; in the backward a query head, its cotangent and its dQ; plus
# the tile temporaries); v5e has 128 MiB
_VMEM_LIMIT = 64 * 1024 * 1024
_VMEM_PLAN = 48 * 1024 * 1024


def default_block(T: int) -> int:
    """The tile edge for a T-long axis: the largest of 512/256/128 that
    divides it (0 where none does)."""
    return next((b for b in (512, 256, 128) if T % b == 0), 0)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b
_TN = ((0,), (0,))      # a.T @ b


def _scores(a_parts, b_parts):
    """sum over the score parts of a_i @ b_i.T."""
    return functools.reduce(operator.add, (_dot(a, b, _NT) for a, b in zip(a_parts, b_parts)))


def _rows(refs, rows):
    """A tile of rows of each part's [T, D_i] block."""
    return tuple(r[rows, :] for r in refs)


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


def _fwd_kernel(len_ref, tab_ref, ptab_ref, cnt_ref, qa_ref, ka_ref, *refs,
                rule, n_attr, parts, block_k, width, pair_width, union_whole, scale):
    q_refs, k_refs, (v_ref, o_ref, lse_ref) = refs[:parts], refs[parts:2 * parts], refs[2 * parts:]
    b = pl.program_id(0)
    iq = pl.program_id(2)
    bq, D = q_refs[0].shape[0], v_ref.shape[1]
    length = len_ref[b]
    q = tuple(r[...] for r in q_refs)                         # each [bq, D_i]
    q_attrs = tuple(qa_ref[a] for a in range(n_attr))         # each [bq, 1]
    single, pair, n_whole, run = _walk_row(tab_ref, ptab_ref, cnt_ref, iq, width, pair_width)

    def tile(kt):
        start = pl.multiple_of(kt * block_k, block_k)
        k_blk = _rows(k_refs, pl.ds(start, block_k))
        v_blk = v_ref[pl.ds(start, block_k), :]
        return start, v_blk, _scaled(_scores(q, k_blk), scale)             # s [bq, bk]

    def side(kt):
        return q_attrs, tuple(ka_ref[a, pl.ds(kt, 1), :] for a in range(n_attr))

    def inside(start):                                        # [1, bk]
        return start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1) < length

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
            if masked:
                s = jnp.where(rule.allowed_from(*side(kt)) & inside(start), s, -jnp.inf)
            return softmax_step(carry, s, lambda p: _dot(p.astype(v_blk.dtype), v_blk, _NN))
        return step

    def pair_step(j, carry):
        ta, tb = pair(j)
        start_a, v_a, s_a = tile(ta)
        start_b, v_b, s_b = tile(tb)
        ok_a, s = _pair_merged(rule, side(ta), side(tb), s_a + _past(inside(start_a)),
                               s_b + _past(inside(start_b)), union_whole)

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
    o_ref[...] = (o / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0, 0] = _row(jnp.where(l > 0, m + jnp.log(l_safe), _NEG))


def _bwd_kernel(len_ref, tab_ref, ptab_ref, cnt_ref, qa_ref, ka_ref, *refs,
                rule, n_attr, parts, block_q, width, pair_width, union_whole, scale):
    """One K/V tile of one QUERY head: the tile's dK and dV, and what it
    adds to the head's dQ. Scores are held transposed, [bk, bq], so that
    the per-query statistics broadcast along sublanes. A pair's two query
    tiles have their own statistics: `s - lse` and `dp - delta` are merged,
    then one exp and one product for both. dQ of the whole head is summed
    in float32 in VMEM (``acc_refs``) while the head's key tiles pass, the
    grid's last axis: zeroed at the first, scaled and rounded once into
    ``dq_refs`` at the last."""
    # operands, results, scratch: q and k a part, v, do, lse, delta; dq and dk a part, dv; dq's sums
    sizes = (parts, parts, 4, parts, parts, 1, parts)
    ends = list(itertools.accumulate(sizes))
    q_refs, k_refs, (v_ref, do_ref, lse_ref, delta_ref), dq_refs, dk_refs, (dv_ref,), acc_refs = (
        refs[end - n:end] for n, end in zip(sizes, ends))
    b = pl.program_id(0)
    ik = pl.program_id(2)
    bk = k_refs[0].shape[0]
    n_q = q_refs[0].shape[0] // block_q
    length = len_ref[b]
    k = tuple(r[...] for r in k_refs)
    v = v_ref[...]
    k_attrs = tuple(ka_ref[a] for a in range(n_attr))         # each [bk, 1]
    inside = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0) < length
    single, pair, n_whole, run = _walk_row(tab_ref, ptab_ref, cnt_ref, ik, width, pair_width)

    def rows_of(qt):
        return pl.ds(pl.multiple_of(qt * block_q, block_q), block_q)

    def query_tiles(step):
        """``step(rows)`` for the rows of each of the head's query tiles."""
        def body(qt, carry):
            step(rows_of(qt))
            return carry
        jax.lax.fori_loop(0, n_q, body, 0)

    @pl.when(ik == 0)
    def _():
        def zero(rows):
            for acc in acc_refs:
                acc[rows, :] = jnp.zeros((block_q, acc.shape[1]), jnp.float32)
        query_tiles(zero)

    def tile(qt):
        rows = rows_of(qt)
        q_blk = _rows(q_refs, rows)
        do_blk = do_ref[rows, :]
        lse = lse_ref[0, 0, pl.ds(qt, 1), :]                  # [1, bq]
        delta = delta_ref[0, 0, pl.ds(qt, 1), :]
        return (rows, q_blk, do_blk, _scaled(_scores(k, q_blk), scale) - lse,  # s - lse [bk, bq]
                _dot(v, do_blk, _NT) - delta)

    def add_dq(rows, ds):
        """ds^T @ k into the query tile's rows of the head's dQ."""
        for acc, k_part in zip(acc_refs, k):
            acc[rows, :] += _dot(ds, k_part, _TN)

    def side(qt):
        return tuple(qa_ref[a, pl.ds(qt, 1), :] for a in range(n_attr)), k_attrs

    def single_step(masked):
        def step(j, carry):
            dk, dv = carry
            qt = single(j)
            rows, q_blk, do_blk, x, dpd = tile(qt)
            if masked:
                x = jnp.where(rule.allowed_from(*side(qt)) & inside, x, -jnp.inf)
            p = jnp.exp(x)
            dv = dv + _dot(p.astype(do_blk.dtype), do_blk, _NN)
            ds = (p * dpd).astype(q_blk[0].dtype)
            add_dq(rows, ds)
            return tuple(d + _dot(ds, q, _NN) for d, q in zip(dk, q_blk)), dv
        return step

    def pair_step(j, carry):
        dk, dv = carry
        ta, tb = pair(j)
        rows_a, q_a, do_a, x_a, dpd_a = tile(ta)
        rows_b, q_b, do_b, x_b, dpd_b = tile(tb)
        ok_a, x = _pair_merged(rule, side(ta), side(tb), x_a, x_b, union_whole)
        p = jnp.exp(x + _past(inside))
        p_a, p_b = _split(ok_a, p, do_a.dtype)
        dv = dv + _dot(p_a, do_a, _NN) + _dot(p_b, do_b, _NN)
        ds_a, ds_b = _split(ok_a, p * jnp.where(ok_a, dpd_a, dpd_b), q_a[0].dtype)
        add_dq(rows_a, ds_a)
        add_dq(rows_b, ds_b)
        return tuple(d + _dot(ds_a, a, _NN) + _dot(ds_b, b, _NN)
                     for d, a, b in zip(dk, q_a, q_b)), dv

    # a key tile the sequence's end cuts masks every one of its query tiles
    n_plain = jnp.where((ik + 1) * bk > length, 0, n_whole)
    zeros = lambda ref: jnp.zeros((bk, ref.shape[1]), jnp.float32)
    dk, dv = run(n_plain, single_step, pair_step, (tuple(map(zeros, k_refs)), zeros(v_ref)))
    # the score's scale, once a tile of rows and not once a score
    for d, ref in zip(dk, dk_refs):
        ref[...] = _scaled(d, scale).astype(ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _():
        def finish(rows):
            for acc, ref in zip(acc_refs, dq_refs):
                ref[rows, :] = _scaled(acc[rows, :], scale).astype(ref.dtype)
        query_tiles(finish)


def _head_of(ref, h, heads):
    """Head h of the ``heads`` in a block: a [heads, rows, D] block's h-th
    slab, or a [rows, heads*D] block's h-th block of lanes."""
    if len(ref.shape) == 3:
        return ref[h]
    D = ref.shape[1] // heads
    return ref[:, h * D:(h + 1) * D]


def _delta_kernel(do_ref, out_ref, delta_ref):
    """sum over a head's lanes of do * out, in float32, for a tile of rows
    of a few heads: a row a head, [heads, rows]."""
    heads = delta_ref.shape[0]
    for h in range(heads):
        prod = _head_of(do_ref, h, heads).astype(jnp.float32) * _head_of(out_ref, h, heads).astype(jnp.float32)
        delta_ref[h:h + 1, :] = _row(jnp.sum(prod, axis=1, keepdims=True))


def _gate_kernel(x_ref, g_ref, y_ref):
    """A tile [rows, heads*D] times one float32 number a head a row, ``g``
    [heads, rows] (a row a head, as `_delta_kernel` writes its sums):
    rounded once."""
    heads, rows = g_ref.shape
    D = x_ref.shape[1] // heads
    for h in range(heads):
        lanes = slice(h * D, (h + 1) * D)
        col = jnp.broadcast_to(g_ref[h:h + 1, :], (_LANES, rows)).T[:, 0:1]         # [rows, 1]
        y_ref[:, lanes] = (x_ref[:, lanes].astype(jnp.float32) * col).astype(y_ref.dtype)


# small int tables visible to every program: scalar memory
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _params(last="arbitrary"):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", last),
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


def by_column(heads: int, D: int) -> bool:
    """Whether the kernels address the heads of a [B, T, heads, D] array
    where the projection left them, as column blocks of its free reshape
    [B, T, heads*D]: a head of whole lane tiles, or the only head (its
    block is the whole last dimension). Mosaic cannot cut a narrower block
    out of a wider row: such a part (several heads of fewer than 128
    lanes) is transposed to [B, heads, T, D] round the kernels, the one
    place a transpose is left."""
    return heads == 1 or D % _LANES == 0


def _to_kernel(x):
    """[B, T, heads, D] as the kernels address it (`by_column`)."""
    B, T, heads, D = x.shape
    if by_column(heads, D):
        return x.reshape(B, T, heads * D)
    return jnp.transpose(x, (0, 2, 1, 3))


def _kernel_struct(x):
    """The kernels' form of a [B, T, heads, D] result, to be written."""
    return jax.eval_shape(_to_kernel, x)


def _from_kernel(y, like):
    """A kernel's result as ``like``, [B, T, heads, D]."""
    return y.reshape(like.shape) if y.ndim == 3 else jnp.transpose(y, (0, 2, 1, 3))


def _head_views(y, heads):
    """The heads of an array in the kernels' form, [B, T, D] each, cut
    where they lie: XLA keeps a lane tile whole (a reshape that splits the
    lanes of a [B, T, heads*D] array into two axes is a relayout copy of
    all of it; a 128-lane slice is a view a fusion reads in place)."""
    if y.ndim == 4:
        return [y[:, h] for h in range(heads)]
    D = y.shape[2] // heads
    return [y[:, :, h * D:(h + 1) * D] for h in range(heads)]


def _head_blocks(arrays, rows, tiled, H=0):
    """A BlockSpec a [B, T, heads, D] array (in its `_to_kernel` form) for
    a grid (batch, query head, tile): ``rows`` rows of a head, the grid's
    tile of them where ``tiled``, else all (rows = T); the kernel sees
    [rows, D]. The head is the grid's query head, or with ``H`` (the query
    heads: a key or value operand, whose heads may be fewer) the one that
    query head h reads, ``h // (H / heads)``. By column the head picks the
    block of lanes, ``rows`` runs of D lanes at the pitch of a position."""
    def spec(x):
        heads, D = x.shape[2:]
        group = H // heads if H else 0
        head = (lambda h: h // group) if H else (lambda h: h)
        tile = (lambda i: i) if tiled else (lambda i: 0)
        if by_column(heads, D):
            return pl.BlockSpec((None, rows, D), lambda b, h, i: (b, tile(i), head(h)))
        return pl.BlockSpec((None, None, rows, D), lambda b, h, i: (b, head(h), tile(i), 0))
    return [spec(x) for x in arrays]


def _out_like(q, v):
    """The result's [B, T, H, Dv]."""
    return jax.ShapeDtypeStruct(q[0].shape[:3] + v.shape[3:], q[0].dtype)


def _run_fwd(q, k, v, lengths, rule, bq, bk, scale, interpret):
    """q, k: tuples of the score parts (module docstring), every operand
    [B, T, heads, D]. The result is left in the kernels' form
    (`_from_kernel` names its heads): what is kept of it for the backward
    is kept as the kernel wrote it."""
    B, T, H, _ = q[0].shape
    tables, walk = _walk(rule, T, bq, bk, False)
    qa, _ = _attr_arrays(rule, T, bq)
    _, ka = _attr_arrays(rule, T, bk)
    n_attr = qa.shape[0]
    out_like = _out_like(q, v)
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
            *_head_blocks([out_like], bq, True),
            pl.BlockSpec((1, 1, 1, 1, bq), lambda b, h, i: (b, h, i, 0, 0)),
        ],
        out_shape=[
            _kernel_struct(out_like),
            jax.ShapeDtypeStruct((B, H, T // bq, 1, bq), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_params(),
    )(lengths, *tables, qa, ka, *map(_to_kernel, q + k + (v,)))
    return out, lse


def _head_tiles(x, H, rows):
    """(the grid, a BlockSpec of ``x`` in the kernels' form, one of a [B, H,
    T] array of a number a head a position) for `attention_delta` and
    `attention_gate`: tiles of ``rows`` positions by 8 heads (all, where 8
    does not divide them)."""
    n = 8 if H % 8 == 0 else H
    B, T = x.shape[0], x.shape[-2]
    if x.ndim == 3:
        block = pl.BlockSpec((None, rows, n * (x.shape[2] // H)), lambda b, g, i: (b, i, g))
    else:
        block = pl.BlockSpec((None, n, rows, x.shape[3]), lambda b, g, i: (b, g, i, 0))
    return (B, H // n, T // rows), block, pl.BlockSpec((None, n, rows), lambda b, g, i: (b, g, i))


def _run_delta(do, out, H, rows, interpret):
    """[B, H, T] float32: sum over a head's lanes of do * out, both in the
    kernels' form and read where they lie. A kernel, `attention_delta`,
    because XLA has no cheap form of it on [B, T, H*Dv]: summing each
    head's lanes apart needs the lanes split into two axes, which is a
    relayout copy of both arrays, and a slice a head makes XLA lay the
    product that computes ``do`` out position-minor for the reductions'
    sake and copy it back for `attention_bwd`."""
    grid, block, per_head = _head_tiles(do, H, rows)
    return pl.pallas_call(
        _delta_kernel, name="attention_delta", grid=grid,
        in_specs=[block, block], out_specs=per_head,
        out_shape=jax.ShapeDtypeStruct((do.shape[0], H, do.shape[-2]), jnp.float32),
        interpret=interpret, compiler_params=_params("parallel"),
    )(do, out)


def _run_bwd(q, k, v, do, out, lse, lengths, rule, bq, bk, scale, interpret):
    B, T, H, _ = q[0].shape
    parts = len(q)
    nq = T // bq
    delta = _run_delta(_to_kernel(do), out, H, bq, interpret)
    _, qa_row = _attr_arrays(rule, T, bq)
    ka_col, _ = _attr_arrays(rule, T, bk)
    n_attr = qa_row.shape[0]
    tables, walk = _walk(rule, T, bq, bk, True)
    stat_full = pl.BlockSpec((1, 1, nq, bq), lambda b, h, i: (b, h, 0, 0))
    # dQ a query head whole, its block the same for all the head's key tiles;
    # dK and dV a QUERY head too: [B, T, H, D_i] for part i's keys, [B, T, H, Dv]
    dq_like = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in q]
    d_like = [jax.ShapeDtypeStruct((B, T, H, x.shape[3]), x.dtype) for x in k + (v,)]
    grads = pl.pallas_call(
        functools.partial(_bwd_kernel, rule=rule, n_attr=n_attr, parts=parts, block_q=bq,
                          scale=scale, **walk),
        name="attention_bwd",
        grid=(B, H, T // bk),
        in_specs=[
            *[_SMEM] * 4,
            pl.BlockSpec(qa_row.shape, lambda b, h, i: (0, 0, 0)),
            pl.BlockSpec((n_attr, bk, 1), lambda b, h, i: (0, i, 0)),
            *_head_blocks(q, T, False), *_head_blocks(k + (v,), bk, True, H),
            *_head_blocks([do], T, False), stat_full, stat_full,
        ],
        out_specs=[*_head_blocks(dq_like, T, False), *_head_blocks(d_like, bk, True)],
        out_shape=list(map(_kernel_struct, dq_like + d_like)),
        scratch_shapes=[pltpu.VMEM((T, x.shape[3]), jnp.float32) for x in q],
        interpret=interpret,
        compiler_params=_params(),
    )(lengths, *tables, qa_row, ka_col, *map(_to_kernel, q + k + (v, do)),
      lse.reshape(B, H, nq, bq), delta.reshape(B, H, nq, bq))
    dq, dk, dv = grads[:parts], grads[parts:-1], grads[-1]

    def fold(d, x):
        """A query head's dK or dV onto the K/V head it read, ``x``'s
        layout: the heads that share one are summed in float32."""
        heads = x.shape[2]
        if heads == H:
            return _from_kernel(d, x)
        per_head = _head_views(d, H)
        group = H // heads
        folded = [functools.reduce(operator.add, (g.astype(jnp.float32)
                                                  for g in per_head[j * group:(j + 1) * group]))
                  for j in range(heads)]
        return jnp.concatenate(folded, axis=-1).astype(d.dtype).reshape(x.shape)

    return tuple(map(_from_kernel, dq, q)), tuple(map(fold, dk, k)), fold(dv, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, lengths, rule, blocks, interpret):  # q, k: tuples of parts; blocks: (bq, bk, scale)
    return _flash_fwd(q, k, v, lengths, rule, blocks, interpret)[0]




# What the backward needs and only the forward KERNEL can remake: named, so
# that a recomputation block (`graph/network.py::_forward_block`) keeps them
# and does not run `attention_fwd` a second time. `out` is as large as q,
# `lse` a 32nd of it in float32 at D = 128. Outside a `jax.checkpoint` with a
# policy a name is the identity. `out` is kept in the kernels' form: a kept
# [B, T, H, Dv] array would be laid out with (H, Dv) as its tiled axes, a
# relayout copy of the kernel's [B, T, H*Dv] on the way in and on the way out.
KEPT_RESIDUALS = ("flash_attention_out", "flash_attention_lse")


def _flash_fwd(q, k, v, lengths, rule, blocks, interpret):
    out, lse = _run_fwd(q, k, v, lengths, rule, *blocks, interpret)
    out, lse = map(checkpoint_name, (out, lse), KEPT_RESIDUALS)
    return _from_kernel(out, _out_like(q, v)), (q, k, v, out, lse, lengths)


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
    MXU takes whole, and what the backward kernel keeps of a QUERY head in
    VMEM while the head's key tiles pass, planned beside the tiles' float32
    temporaries: the head's q and its cotangent twice over (the forward's
    K/V head twice over is no larger), and the head's dQ, a score part its
    output block twice over and its float32 accumulator. The temporaries
    are 12 score-sized ones, for a pair's step holds two score tiles and
    their two cotangents where a single's holds one of each. ``D``: the
    scores' width, or the widths of their parts; ``value_dim``: the values'
    (the scores' by default). A part narrower than a lane tile sits in VMEM
    as a whole one. How a part's heads are addressed in HBM, in place or
    from a head-major copy, is `by_column`'s answer and changes nothing
    here: the tiles in VMEM are the same."""
    widths = as_parts(D)
    value_dim = value_dim or sum(widths)
    block = default_block(T)
    if not block or any(d > 256 or d % 8 for d in widths + (value_dim,)):
        return False
    lanes = lambda d: -(-d // _LANES) * _LANES
    scores = sum(map(lanes, widths))
    held = scores + lanes(value_dim)
    resident = 2 * T * held * itemsize + T * scores * (2 * itemsize + 4)
    tiles = 12 * block * block * 4 + 4 * block * held * 4
    return resident + tiles <= _VMEM_PLAN


def walk_census(rule: MaskRule, T: int) -> str:
    """What the kernels walk under ``rule`` at `default_block(T)`, for the
    log: a constant of the compiled program, no measurement."""
    block = default_block(T)
    return "; ".join(f"{name} {tile_walk(rule, T, block, block, transpose).census}"
                     for name, transpose in (("fwd", False), ("bwd", True)))


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
    and values (the sequence_parallel layout, read and written where it
    lies: `by_column`), masked by ``rule`` (``causal`` is the old flag for
    the causal rule). ``q`` and ``k`` may
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
    return _flash(q, k, v, jnp.asarray(lengths, jnp.int32), rule,
                  (block, block, 1.0 / math.sqrt(D) if scale is None else float(scale)),
                  interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gate(x, g, rows, interpret):
    """x [B, T, H*D] times g [B, T, H], a head's lanes by its number."""
    grid, block, per_head = _head_tiles(x, g.shape[2], rows)
    return pl.pallas_call(
        _gate_kernel, name="attention_gate", grid=grid,
        in_specs=[block, per_head], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret, compiler_params=_params("parallel"),
    )(x, g.transpose(0, 2, 1))


def _gate_fwd(x, g, rows, interpret):
    return _gate(x, g, rows, interpret), (x, g)


def _gate_bwd(rows, interpret, kept, dy):
    x, g = kept
    d_g = _run_delta(dy, x, g.shape[2], rows, interpret).transpose(0, 2, 1)
    return _gate(dy, g, rows, interpret), d_g.astype(g.dtype)


_gate.defvjp(_gate_fwd, _gate_bwd)


def gate_heads(x: Array, g: Array) -> Array:
    """The heads' results ``x`` [B, T, H, D], each multiplied by its own
    number a position, ``g`` [B, T, H] in float32, and rounded once to x's
    dtype. On [B, T, H*D], as the flash kernels leave x, XLA writes the
    numbers out over the lanes first (a float32 array of x's size) and, for
    d g, splits the lanes by a relayout copy: where a kernel can run
    (`device.pallas_mode`) and a head is whole lane tiles, two small
    kernels do it in one pass each way over the arrays where they lie,
    `attention_gate` and, for d g, `attention_delta`."""
    from paddle_tpu.utils import device

    B, T, H, D = x.shape
    mode, rows = device.pallas_mode(), default_block(T)
    if mode is None or not rows or D % _LANES:
        return (x.astype(jnp.float32) * g[..., None]).astype(x.dtype)
    return _gate(x.reshape(B, T, H * D), g, rows, mode == "interpret").reshape(x.shape)
