"""Plain reference of SDAR-30B-A3B (`model_type: sdar_moe`): a Qwen3-MoE
body trained by block diffusion (Block Diffusion, arXiv:2503.09573; SDAR,
arXiv:2510.06303), as ONE CHIP'S SHARE of an expert-parallel deployment.

Straightforward `jax.numpy`, float32, matrix products at `highest`
precision, a dense mask, a dense loop over experts, no kernels. It imports
nothing from `paddle_tpu` and takes nothing the program made: the weights
come from `init_params(sizes, seed)` here, and the harness hands the SAME
arrays to the program.

Input of one sequence: 2L ids, the noised copy x_t (indices 0..L-1) then
the clean copy x_0 (indices L..2L-1). pos(i) = i mod L, blk(i) =
pos(i) // B, B the block length.

- Embedding E[ids], E of [V, H]. No bias anywhere, embedding and head
  untied.
- A layer: h <- h + Attn(RMS(h; g1)); h <- h + MoE(RMS(h; g2));
  RMS(x; g) = x / sqrt(mean(x^2) + eps) * g.
- Attn(x): q = x Wq -> [heads, hd], k = x Wk, v = x Wv -> [kv_heads, hd];
  q <- RMS(q; gq), k <- RMS(k; gk) over hd (the Qwen3-MoE body; the
  config has no key for it: `assumed`); rotary embedding at pos(i),
  rotate-half; scores q_i . k_j / sqrt(hd), query head h reading K/V head
  h // (heads / kv_heads); allowed pairs by M; softmax; Wo.
- M: noised query, noised key: blk(i) = blk(j); noised query, clean key:
  blk(j) < blk(i); clean query, clean key: blk(j) <= blk(i); clean query,
  noised key: never.
- MoE(x): r = softmax(x Wr) over ALL the routed experts; S = the
  experts-per-token largest; w_e = r_e / sum_{S} r (norm_topk_prob);
  y = sum_{e in S and HELD HERE} w_e (silu(x Wg_e) * (x Wu_e)) Wd_e.
- Head: RMS(h; gf); logits = h[:L] W_head. Loss = mean over the batch's
  sequences of sum_{i<L} weight_i * CE(logits_i, x_0[i]).

Departures from the published model, each marked DEPARTURE at its line:
the experts held (a range of the routed ones), the vocabulary slice, the
depth (all three are the configuration's `reduced`), and no auxiliary
load-balancing loss (the config has no coefficient).

The noise is data: the batch brings x_t ; x_0, the labels and the
per-position weights (1/t where x_t is the mask id, else 0); nothing is
drawn here.

`mode` selects the arithmetic of the linear layers' matrix products:
"highest" is the reference; "fp8" is the control of lower precision;
"bfloat16" is the witness, the configuration's own precision written a
second time (`perfbench/tests/routing_witness.py`).

The choice S is discrete: where the eighth and the ninth expert of a token
lie closer than the rounding of a lower precision, two sound computations
choose differently, and under a loss weight of 1/t one such token can carry
a leaf's gradient. So a batch may bring `routing`, the choices another
computation made ([sequences, layers, 2L, experts a token] ids): S is then
DATA, r and w_e are still computed here from this side's own x, and the two
sides are compared on the same function (`compare/train_steps_lean.py`
feeds the program's; `own_routing` gives this side's own, to count how many
differ).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
QUERY_ROWS = 512        # attention is computed this many query rows at a time
TOKEN_ROWS = 1024       # the expert layer and the head, this many rows at a time


def _layers(sizes):
    return range(int(sizes["num_hidden_layers"]))   # DEPARTURE: depth cut (`reduced`)


def param_shapes(sizes):
    """Reference name -> shape. A leading 1 marks a norm's gain."""
    h, nh, nkv, hd = (sizes["hidden_size"], sizes["num_attention_heads"],
                      sizes["num_key_value_heads"], sizes["head_dim"])
    f, routed = sizes["moe_intermediate_size"], sizes["num_experts_routed"]
    held = sizes["num_experts"]     # DEPARTURE: the experts held here (`reduced`)
    v = sizes["vocab_size"]         # DEPARTURE: the vocabulary slice (`reduced`)
    shapes = {"embed": (v, h), "head": (h, v), "final_norm": (1, h)}
    for l in _layers(sizes):
        shapes.update({
            f"l{l}_norm1": (1, h), f"l{l}_wq": (h, nh * hd),
            f"l{l}_wk": (h, nkv * hd), f"l{l}_wv": (h, nkv * hd),
            f"l{l}_wo": (nh * hd, h), f"l{l}_q_norm": (1, hd),
            f"l{l}_k_norm": (1, hd), f"l{l}_norm2": (1, h),
            f"l{l}_router": (h, routed), f"l{l}_gate": (held, h, f),
            f"l{l}_up": (held, h, f), f"l{l}_down": (held, f, h)})
    return shapes


@functools.partial(jax.jit, static_argnums=(0,))
def _init(shape_items, key):
    out = {}
    for i, (name, shape) in enumerate(shape_items):
        k = jax.random.fold_in(key, i)
        if shape[0] == 1:                    # a gain: starts at 1
            out[name] = jnp.ones(shape, jnp.float32)
        elif name == "embed":                # unit rows: the stream starts at scale 1
            out[name] = jax.random.normal(k, shape, jnp.float32)
        else:                                # N(0, 1/sqrt(fan-in))
            out[name] = shape[-2] ** -0.5 * jax.random.normal(k, shape, jnp.float32)
    return out


_SIZE_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
              "moe_intermediate_size", "num_experts", "num_experts_routed",
              "experts_held_first", "num_experts_per_tok", "num_hidden_layers",
              "vocab_size", "rms_norm_eps", "rope_theta", "norm_topk_prob",
              "block_length")


# the configuration `init_params` was last asked for: `loss_and_grad(p,
# batch, mode)` is handed arrays only (perfbench/compare/train_steps.py), and
# what is no shape (experts a token, block length, eps, theta) comes from here
_CONFIG = {}


def configure(sizes):
    _CONFIG.clear()
    _CONFIG.update({k: sizes[k] for k in _SIZE_KEYS if k in sizes})


def init_params(sizes, seed):
    """All weights in one jitted call on the device, float32. Remembers
    `sizes` for `loss_and_grad`."""
    configure(sizes)
    key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    return _init(tuple(sorted(param_shapes(sizes).items())), key)


def _as_fp8(x):
    """x rounded to float8_e4m3fn as an fp8 recipe does it: scaled so that
    the tensor's largest magnitude lands on the type's (448), rounded, and
    scaled back; the backward pass sees the rounded values and passes the
    rounding straight through."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def _as_bf16(x):
    # `reduce_precision`, not a cast there and back: the TPU compiler drops a
    # float32 -> bfloat16 -> float32 pair as excess precision it may keep (my
    # chip run, PR 28: the witness then read 1e-7 from the reference)
    q = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x + jax.lax.stop_gradient(q - x)


def mm(a, b, mode):
    """A linear layer's matrix product (projections, experts, head). "fp8"
    is the control: both inputs rounded to float8_e4m3fn (per-tensor
    scale), exact products, float32 accumulation. "bfloat16" is the
    witness: both inputs rounded to bfloat16, the same way. The router, the
    softmax and attention's own products stay at `highest` in every mode,
    as an fp8 recipe keeps them."""
    if mode == "fp8":
        a, b = _as_fp8(a), _as_fp8(b)
    elif mode == "bfloat16":
        a, b = _as_bf16(a), _as_bf16(b)
    elif mode != "highest":
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.dot(a, b, precision=HI)


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def allowed(q_idx, k_idx, L, B):
    """M: [len(q_idx), len(k_idx)] bool."""
    qn, kn = (q_idx < L)[:, None], (k_idx < L)[None, :]
    qb, kb = ((q_idx % L) // B)[:, None], ((k_idx % L) // B)[None, :]
    return (qn & kn & (qb == kb)) | (qn & ~kn & (kb < qb)) | (~qn & ~kn & (kb <= qb))


def rotary(x, pos, theta):
    """x [T, heads, hd] turned at positions pos [T]; rotate-half."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


def attention(p, l, x, sizes, mode):
    T = x.shape[0]
    L, B = T // 2, sizes["block_length"]
    nh, nkv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                   sizes["head_dim"])
    eps = sizes["rms_norm_eps"]
    q = mm(x, p[f"l{l}_wq"], mode).reshape(T, nh, hd)
    k = mm(x, p[f"l{l}_wk"], mode).reshape(T, nkv, hd)
    v = mm(x, p[f"l{l}_wv"], mode).reshape(T, nkv, hd)
    q, k = rms(q, p[f"l{l}_q_norm"][0], eps), rms(k, p[f"l{l}_k_norm"][0], eps)
    idx = jnp.arange(T)
    q, k = rotary(q, idx % L, sizes["rope_theta"]), rotary(k, idx % L, sizes["rope_theta"])
    # query head h reads K/V head h // (nh / nkv)
    k, v = jnp.repeat(k, nh // nkv, axis=1), jnp.repeat(v, nh // nkv, axis=1)
    rows = min(QUERY_ROWS, T)

    @jax.checkpoint
    def block(start):
        """A block of query rows: its [heads, rows, T] scores never outlive it."""
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / math.sqrt(hd)
        m = allowed(start + jnp.arange(rows), idx, L, B)
        w = jax.nn.softmax(jnp.where(m[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(0, T, rows)).reshape(T, nh * hd)
    return mm(out, p[f"l{l}_wo"], mode)


def moe(p, l, x, sizes, mode, held=None, given=None):
    """`held`: (first, count) of the routed experts whose part is computed;
    the configuration's own range by default. `given`: [T, experts a token]
    ids, the choice S as data (the module's docstring). Tokens are
    independent here, so the layer is computed a block of rows at a time."""
    first, count = held or (sizes.get("experts_held_first", 0), sizes["num_experts"])
    k = sizes["num_experts_per_tok"]

    @jax.checkpoint
    def rows(x, given=None):
        r = jax.nn.softmax(jnp.dot(x, p[f"l{l}_router"], precision=HI), axis=-1)
        if given is None:
            top, chosen = jax.lax.top_k(r, k)
        else:
            top, chosen = jnp.take_along_axis(r, given, axis=-1), given
        if sizes["norm_topk_prob"]:
            top = top / jnp.sum(top, axis=-1, keepdims=True)    # over ALL the chosen

        def add_expert(y, held_expert):
            # DEPARTURE: the held experts only; the rest live on other chips. A
            # rolled loop over the stacked experts (one expert's program, not 16)
            e, wg, wu, wd = held_expert
            w = jnp.sum(jnp.where(chosen == first + e, top, 0.0), axis=-1)
            out = mm(jax.nn.silu(mm(x, wg, mode)) * mm(x, wu, mode), wd, mode)
            return y + w[:, None] * out, None

        return jax.lax.scan(
            add_expert, jnp.zeros_like(x),
            (jnp.arange(count), p[f"l{l}_gate"], p[f"l{l}_up"], p[f"l{l}_down"]))[0]

    n = min(TOKEN_ROWS, x.shape[0])
    if x.shape[0] % n:
        return rows(x, given)
    if given is None:
        return jax.lax.map(rows, x.reshape(-1, n, x.shape[1])).reshape(x.shape)
    return jax.lax.map(lambda xg: rows(*xg), (x.reshape(-1, n, x.shape[1]),
                                              given.reshape(-1, n, k))).reshape(x.shape)


def pairs_held(p, x_by_layer, sizes):
    """How many (token, expert) pairs the held experts get, all layers:
    what the program's counter `moe.pairs_held` must read."""
    first, count = sizes.get("experts_held_first", 0), sizes["num_experts"]
    n = 0
    for l, x in enumerate(x_by_layer):
        r = jax.nn.softmax(jnp.dot(x, p[f"l{l}_router"], precision=HI), axis=-1)
        _, chosen = jax.lax.top_k(r, sizes["num_experts_per_tok"])
        n += int(jnp.sum((chosen >= first) & (chosen < first + count)))
    return n


def hidden(p, tokens, sizes, mode="highest", moe_inputs=None, routing=None):
    """One sequence's [2L] ids -> the stream after the last layer, [2L, H].
    `moe_inputs`: a list that gets each expert layer's input. `routing`:
    [layers, 2L, experts a token], each layer's choices as data."""
    eps = sizes["rms_norm_eps"]
    h = p["embed"][tokens]
    for l in _layers(sizes):
        given = None if routing is None else routing[l]
        layer = lambda h, l=l, given=given: _layer(p, l, h, sizes, mode, eps, moe_inputs, given)
        # the backward pass recomputes a layer from its input; a probe of the
        # expert layers' inputs runs plain (it appends to a Python list)
        h = (jax.checkpoint(layer) if moe_inputs is None else layer)(h)
    return h


def _layer(p, l, h, sizes, mode, eps, moe_inputs, given=None):
    h = h + attention(p, l, rms(h, p[f"l{l}_norm1"][0], eps), sizes, mode)
    x = rms(h, p[f"l{l}_norm2"][0], eps)
    if moe_inputs is not None:
        moe_inputs.append(x)
    return h + moe(p, l, x, sizes, mode, given=given)


@functools.partial(jax.jit, static_argnames=("sizes_key", "mode"))
def _own_routing(p, tokens, sizes_key, mode):
    sizes = dict(sizes_key)
    xs = []
    hidden(p, tokens, sizes, mode, moe_inputs=xs)
    return jnp.stack([jax.lax.top_k(
        jnp.dot(x, p[f"l{l}_router"], precision=HI), sizes["num_experts_per_tok"])[1]
        for l, x in enumerate(xs)])


def own_routing(p, batch, mode="highest"):
    """The choices this side makes by itself for the batch, as a batch's
    `routing` has them: int32 [sequences, layers, 2L, experts a token]
    (the softmax keeps the order, so the largest logits are the largest r)."""
    sizes_key = tuple(sorted(_CONFIG.items()))
    return jnp.stack([_own_routing(p, row, sizes_key, mode) for row in batch["tokens"]])


def sequence_cost(p, tokens, labels, weights, sizes, mode="highest", routing=None):
    """sum_{i<L} weight_i * CE(logits_i, labels_i) of one sequence."""
    L = labels.shape[0]
    h = rms(hidden(p, tokens, sizes, mode, routing=routing), p["final_norm"][0],
            sizes["rms_norm_eps"])[:L]

    @jax.checkpoint
    def rows(block):
        """A block of rows' cost: its [rows, V] scores never outlive it."""
        hb, lab, w = block
        logp = jax.nn.log_softmax(mm(hb, p["head"], mode), axis=-1)
        return -jnp.sum(w * jnp.take_along_axis(logp, lab[:, None], axis=-1)[:, 0])

    n = min(TOKEN_ROWS, L)
    if L % n:
        return rows((h, labels, weights))
    return jnp.sum(jax.lax.map(rows, (h.reshape(-1, n, h.shape[1]),
                                      labels.reshape(-1, n), weights.reshape(-1, n))))


@functools.partial(jax.jit, static_argnames=("sizes_key", "mode"), donate_argnums=(0, 1))
def _add_sequence(loss, grads, p, tokens, labels, weights, routing, inv_b, sizes_key, mode):
    sizes = dict(sizes_key)
    l, g = jax.value_and_grad(
        lambda q: sequence_cost(q, tokens, labels, weights, sizes, mode, routing) * inv_b)(p)
    return loss + l, jax.tree_util.tree_map(jnp.add, grads, g)


def loss_and_grad(p, batch, mode="highest"):
    """The batch's loss (mean over sequences) and its gradient (under the
    batch's `routing` where it brings one), a sequence
    at a time (and attention a block of query rows at a time), so that a
    batch's activations and a sequence's 2L x 2L scores never sit in memory
    at once."""
    sizes_key = tuple(sorted(_CONFIG.items()))
    n = batch["tokens"].shape[0]
    loss = jnp.float32(0.0)
    grads = jax.tree_util.tree_map(jnp.zeros_like, p)
    routing = batch.get("routing")
    for i in range(n):
        loss, grads = _add_sequence(
            loss, grads, p, batch["tokens"][i], batch["labels"][i], batch["weights"][i],
            None if routing is None else routing[i], jnp.float32(1.0 / n), sizes_key, mode)
    return loss, grads


def to_batch(arrays):
    """The traffic generator's named arrays -> this reference's batch."""
    return {"tokens": jnp.asarray(arrays["tokens"]), "labels": jnp.asarray(arrays["labels"]),
            "weights": jnp.asarray(arrays["weights"], jnp.float32)}
