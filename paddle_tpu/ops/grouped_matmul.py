"""Token-sorted dispatch and the grouped matrix product of a sparse-expert
layer, with their backward.

`route` sorts the (token, expert) pairs whose expert this program holds
by expert; `expert_ffn` runs the held experts' SwiGLU over the sorted
pairs and scatter-adds each pair's output, times its weight, into its
token's row. No capacity and no dropped pair: how many pairs each expert
gets is data, so the pairs are walked in CHUNKS of a fixed number of
rows by a loop whose trip count is the data's (`ceil(pairs / chunk)`):
the step's memory is one chunk's whatever the imbalance, and its time is
what the pairs held cost. A loop with a data-dependent trip count has no
reverse-mode rule, so `expert_ffn` carries its own: the backward walks
the same chunks, recomputes a chunk's hidden activations and takes that
chunk's vjp.

The product itself, `grouped_matmul` (rows sorted by group times a
group's matrix), is `jax.lax.ragged_dot`.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array

# rows of the sorted pair list one trip of the loop computes
CHUNK_ROWS = 8192


def grouped_matmul(x: Array, w: Array, group_sizes: Array) -> Array:
    """x [M, K], rows sorted by group; w [G, K, N]; group_sizes int32 [G].
    Row r of the result is x[r] @ w[group of r]; rows past the groups'
    total are 0. x's dtype out (the MXU accumulates in float32 either
    way; a float32 result would hand the backward float32 operands)."""
    return jax.lax.ragged_dot(x, w, group_sizes, preferred_element_type=x.dtype)


class Routing(NamedTuple):
    """The held pairs, sorted by expert, padded with unheld ones."""
    token: Array        # int32 [P]: the pair's token row
    slot: Array         # int32 [P]: which of the token's choices it is
    group_sizes: Array  # int32 [G]: pairs of each held expert
    pairs_held: Array   # int32 []: sum(group_sizes)


def route(chosen: Array, first: int, count: int) -> Routing:
    """chosen int32 [N, k]: the experts each token chose (of all the
    experts). Sorts the N*k pairs so that the pairs of held expert
    ``first`` come first, then ``first + 1``, ...; pairs of experts held
    elsewhere sort last and are never computed."""
    n, k = chosen.shape
    local = chosen.reshape(-1) - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    group_sizes = jnp.sum(
        key[:, None] == jnp.arange(count, dtype=key.dtype)[None, :], axis=0,
        dtype=jnp.int32)
    return Routing(token=order // k, slot=order % k, group_sizes=group_sizes,
                   pairs_held=jnp.sum(group_sizes))


def _chunk_groups(group_sizes, start, rows):
    """The part of each group that lies in rows [start, start + rows)."""
    ends = jnp.cumsum(group_sizes)
    begins = ends - group_sizes
    return jnp.clip(jnp.minimum(ends, start + rows) - jnp.maximum(begins, start),
                    0, rows).astype(jnp.int32)


def _swiglu(xc, wg, wu, wd, sizes):
    g = grouped_matmul(xc, wg, sizes)
    u = grouped_matmul(xc, wu, sizes)
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(xc.dtype)
    return grouped_matmul(h, wd, sizes)


def _chunk(x, weight, routing, c, rows):
    """Chunk c of the sorted pairs: the pairs' token rows and choice
    slots, which rows are pairs at all, the part of each group in the
    chunk, the tokens' inputs gathered, and the pairs' combine weights."""
    start = c * rows
    tok = jax.lax.dynamic_slice(routing.token, (start,), (rows,))
    slot = jax.lax.dynamic_slice(routing.slot, (start,), (rows,))
    live = (start + jnp.arange(rows, dtype=jnp.int32)) < routing.pairs_held
    sizes = _chunk_groups(routing.group_sizes, start, rows)
    with jax.named_scope("dispatch"):
        xc = x[tok]
        wc = jnp.where(live, weight[tok, slot], 0.0)
    return tok, slot, live, sizes, xc, wc


def _trips(routing, rows):
    return (routing.pairs_held + rows - 1) // rows


def _rows(routing):
    p = routing.token.shape[0]
    rows = min(CHUNK_ROWS, p)
    assert p % rows == 0, f"{p} pairs do not cut into chunks of {rows}"
    return rows


@jax.custom_vjp
def expert_ffn(x, weight, wg, wu, wd, routing):
    """sum over a token's held pairs of weight * SwiGLU_expert(x[token]).

    x [N, D]; weight float32 [N, k], the combine weight of each of a
    token's choices; wg, wu [G, D, F], wd [G, F, D] the held experts,
    stacked; routing from :func:`route`. Returns [N, D] in x's dtype."""
    return _ffn_fwd(x, weight, wg, wu, wd, routing)[0]


def _ffn_fwd(x, weight, wg, wu, wd, routing):
    rows = _rows(routing)

    def body(c, y):
        tok, _, live, sizes, xc, wc = _chunk(x, weight, routing, c, rows)
        with jax.named_scope("experts"):
            o = _swiglu(xc, wg, wu, wd, sizes)
        with jax.named_scope("combine"):
            o = jnp.where(live[:, None], o.astype(jnp.float32) * wc[:, None], 0.0)
            return y.at[tok].add(o)

    y = jax.lax.fori_loop(0, _trips(routing, rows), body,
                          jnp.zeros(x.shape, jnp.float32))
    return y.astype(x.dtype), (x, weight, wg, wu, wd, routing)


def _ffn_bwd(res, dy):
    x, weight, wg, wu, wd, routing = res
    rows = _rows(routing)
    dyf = dy.astype(jnp.float32)

    def body(c, acc):
        dx, dweight, dwg, dwu, dwd = acc
        tok, slot, live, sizes, xc, wc = _chunk(x, weight, routing, c, rows)
        with jax.named_scope("dispatch"):
            dyc = jnp.where(live[:, None], dyf[tok], 0.0)
        with jax.named_scope("experts"):
            o, vjp = jax.vjp(lambda xc, wg, wu, wd: _swiglu(xc, wg, wu, wd, sizes),
                             xc, wg, wu, wd)
            do = (dyc * wc[:, None]).astype(o.dtype)
            dxc, gwg, gwu, gwd = vjp(do)
        with jax.named_scope("combine"):
            dwc = jnp.where(live, jnp.sum(o.astype(jnp.float32) * dyc, axis=-1), 0.0)
            dx = dx.at[tok].add(jnp.where(live[:, None], dxc.astype(jnp.float32), 0.0))
            dweight = dweight.at[tok, slot].add(dwc)
        return (dx, dweight, dwg + gwg.astype(jnp.float32),
                dwu + gwu.astype(jnp.float32), dwd + gwd.astype(jnp.float32))

    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)
    dx, dweight, dwg, dwu, dwd = jax.lax.fori_loop(
        0, _trips(routing, rows), body,
        (zeros(x), zeros(weight), zeros(wg), zeros(wu), zeros(wd)))
    return (dx.astype(x.dtype), dweight.astype(weight.dtype), dwg.astype(wg.dtype),
            dwu.astype(wu.dtype), dwd.astype(wd.dtype), None)


expert_ffn.defvjp(_ffn_fwd, _ffn_bwd)
