"""Plain reference of Laguna-XS.2 (`model_type: laguna`, poolside): a
decoder whose layers mix full and sliding-window attention with a head
count a layer, a per-head output gate, one dense lead layer and sparse
layers of one shared and 256 routed experts, as ONE CHIP'S SHARE of an
expert-parallel deployment, trained by next-token prediction.

Straightforward `jax.numpy`, float32, matrix products at `highest`
precision, a dense mask, a dense loop over experts, no kernels. It imports
nothing from `paddle_tpu` and takes nothing the program made: the weights
come from `init_params(sizes, seed)` here, and the harness hands the SAME
arrays to the program.

Block l on a sequence's stream h [T, H]; H_l = `num_attention_heads_per_layer[l]`
query heads over `num_key_value_heads` key/value heads of `head_dim`;
N(x; g) = x * rsqrt(mean(x^2) + eps) * g:

- x = N(h; g1); q = x Wq -> [T, H_l, hd], k = x Wk, v = x Wv -> [T, Hkv, hd];
  no bias, no q/k norm (the config has no key for one: `assumed`).
- q, k turned by the layer TYPE's rotary parameters (`rope_parameters`),
  rotate-half over the first rot = partial_rotary_factor * hd lanes of a
  head, the rest unchanged; cos and sin times `attention_factor`; YaRN's
  frequencies where `rope_type` is "yarn" (`frequencies`), fixed, not a
  function of the sequence's length.
- scores q_i . k_j / sqrt(hd) for the pairs the layer's rule allows: full
  layer j <= i; sliding layer j <= i and i - j < `sliding_window`; query
  head n reads key/value head n // (H_l / Hkv); softmax; values.
- the gate: g = sigmoid(x Wgate) [T, H_l], one number a head a position,
  times the head's result before Wo (`assumed`: the head-wise form of
  arXiv:2505.06708); h <- h + (g * a) Wo.
- x2 = N(h; g2). A dense layer (`mlp_layer_types[l]`): h <- h + (silu(x2 Wg) *
  (x2 Wu)) Wd, `intermediate_size` wide. A sparse layer: r =
  `router_scores`(x2 Wr) over ALL the routed experts (softmax: `assumed`);
  S = the `num_experts_per_tok` largest; w_e = r_e / sum_{S} r; h <- h +
  the shared expert's (silu(x2 Sg) * (x2 Su)) Sd + `moe_routed_scaling_factor`
  * sum_{e in S and HELD HERE} w_e (silu(x2 Wg_e) * (x2 Wu_e)) Wd_e.
- Head: logits = N(h; gf) W_head; loss = mean over the batch's positions of
  -log softmax(logits)[label], the label being the next token.

Departures from the published model, each marked DEPARTURE at its line:
the experts held (a range of the routed ones), the vocabulary slice, the
depth (the configuration's `reduced`: the first `num_hidden_layers` entries
of the per-layer lists), and no auxiliary or bias term in the router.

`mode` selects the arithmetic of the linear layers' matrix products:
"highest" is the reference; "fp8" is the control of lower precision;
"bfloat16" rounds both inputs to bfloat16.

The choice S is discrete, so a batch may bring `routing`, the choices
another computation made ([sequences, sparse layers, T, experts a token]
ids): S is then DATA, r and w_e are still computed here from this side's
own x (`compare/train_steps_lean.py` feeds the program's; `own_routing`
gives this side's own).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
QUERY_ROWS = 512        # attention is computed this many query rows at a time
TOKEN_ROWS = 1024       # the feed-forward layers and the head, this many rows at a time


def _layers(sizes):
    return range(int(sizes["num_hidden_layers"]))   # DEPARTURE: depth cut (`reduced`)


def _sparse(sizes, l):
    return sizes["mlp_layer_types"][l] == "sparse"


def sparse_layers(sizes):
    """The sparse layers' numbers, in order: a batch's `routing` has one
    entry for each."""
    return [l for l in _layers(sizes) if _sparse(sizes, l)]


def param_shapes(sizes):
    """Reference name -> shape. A leading 1 marks a norm's gain."""
    h, nkv, hd = sizes["hidden_size"], sizes["num_key_value_heads"], sizes["head_dim"]
    f, routed = sizes["moe_intermediate_size"], sizes["num_experts_routed"]
    fs, fd = sizes["shared_expert_intermediate_size"], sizes["intermediate_size"]
    held = sizes["num_experts"]     # DEPARTURE: the experts held here (`reduced`)
    v = sizes["vocab_size"]         # DEPARTURE: the vocabulary slice (`reduced`)
    shapes = {"embed": (v, h), "head": (h, v), "final_norm": (1, h)}
    for l in _layers(sizes):
        nh = sizes["num_attention_heads_per_layer"][l]
        shapes.update({
            f"l{l}_norm1": (1, h), f"l{l}_wq": (h, nh * hd),
            f"l{l}_wk": (h, nkv * hd), f"l{l}_wv": (h, nkv * hd),
            f"l{l}_wo": (nh * hd, h), f"l{l}_wgate": (h, nh), f"l{l}_norm2": (1, h)})
        if _sparse(sizes, l):
            shapes.update({
                f"l{l}_router": (h, routed), f"l{l}_gate": (held, h, f),
                f"l{l}_up": (held, h, f), f"l{l}_down": (held, f, h),
                f"l{l}_shared_gate": (h, fs), f"l{l}_shared_up": (h, fs),
                f"l{l}_shared_down": (fs, h)})
        else:
            shapes.update({f"l{l}_mlp_gate": (h, fd), f"l{l}_mlp_up": (h, fd),
                           f"l{l}_mlp_down": (fd, h)})
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


_SIZE_KEYS = ("hidden_size", "num_attention_heads_per_layer", "num_key_value_heads",
              "head_dim", "layer_types", "mlp_layer_types", "sliding_window",
              "rope_parameters", "intermediate_size", "moe_intermediate_size",
              "shared_expert_intermediate_size", "num_experts", "num_experts_routed",
              "experts_held_first", "num_experts_per_tok", "moe_routed_scaling_factor",
              "num_hidden_layers", "vocab_size", "rms_norm_eps")

# the configuration `init_params` was last asked for, as a JSON string (it
# holds lists, and is a jitted function's static argument): `loss_and_grad(p,
# batch, mode)` is handed arrays only (perfbench/compare/train_steps.py)
_CONFIG = {"key": "{}"}


def configure(sizes):
    _CONFIG["key"] = json.dumps({k: sizes[k] for k in _SIZE_KEYS if k in sizes},
                                sort_keys=True)


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
    # float32 -> bfloat16 -> float32 pair as excess precision it may keep
    q = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x + jax.lax.stop_gradient(q - x)


def mm(a, b, mode):
    """A linear layer's matrix product (projections, the gate's, the
    feed-forwards, head). "fp8" is the control: both inputs rounded to
    float8_e4m3fn (per-tensor scale), exact products, float32 accumulation.
    The router, the softmaxes and attention's own products stay at `highest`
    in every mode, as an fp8 recipe keeps them."""
    if mode == "fp8":
        a, b = _as_fp8(a), _as_fp8(b)
    elif mode == "bfloat16":
        a, b = _as_bf16(a), _as_bf16(b)
    elif mode != "highest":
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.dot(a, b, precision=HI)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def allowed(q_idx, k_idx, window=0):
    """[len(q_idx), len(k_idx)] bool: j <= i, and under a window i - j < window."""
    i, j = q_idx[:, None], k_idx[None, :]
    return (j <= i) & (i - j < window) if window else (j <= i)


def frequencies(rope, rot):
    """The rot/2 rotary frequencies of one layer type's `rope_parameters`
    entry: base^(-2i/rot), or YaRN's as the `transformers` library computes
    them for `rope_type: yarn` (a frequency that makes more than beta_fast
    turns over the original positions kept, fewer than beta_slow divided by
    `factor`, a linear ramp over the frequency index between, bounds rounded
    outwards)."""
    base = float(rope["rope_theta"])
    i = jnp.arange(rot // 2, dtype=jnp.float32)
    e = base ** (-2.0 * i / rot)
    if rope.get("rope_type", "default") != "yarn":
        return e
    l0 = rope["original_max_position_embeddings"]
    cd = lambda n: rot * math.log(l0 / (2 * math.pi * n)) / (2 * math.log(base))
    low = max(math.floor(cd(rope["beta_fast"])), 0)
    high = min(math.ceil(cd(rope["beta_slow"])), rot - 1)
    ramp = jnp.clip((i - low) / ((high - low) or 1e-3), 0.0, 1.0)
    return e * (1 - ramp) + e / rope["factor"] * ramp


def rotary(x, pos, rope):
    """x [T, heads, hd] turned at positions pos [T]: rotate-half over the
    first rot lanes, cos and sin times the attention factor, the rest of the
    head unchanged."""
    hd = x.shape[-1]
    rot = int(round(rope.get("partial_rotary_factor", 1) * hd))
    ang = pos.astype(jnp.float32)[:, None] * frequencies(rope, rot)[None, :]
    f = rope.get("attention_factor", 1.0)
    c, s = (f * jnp.cos(ang))[:, None, :], (f * jnp.sin(ang))[:, None, :]
    a, b, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest], -1)


def attention(p, l, x, sizes, mode):
    """The block's attention with its gate and output projection; x [T, H]
    is the block's normalised input."""
    T = x.shape[0]
    nh, nkv, hd = (sizes["num_attention_heads_per_layer"][l],
                   sizes["num_key_value_heads"], sizes["head_dim"])
    kind = sizes["layer_types"][l]
    window = sizes["sliding_window"] if kind == "sliding_attention" else 0
    rope = sizes["rope_parameters"][kind]
    group = nh // nkv
    q = mm(x, p[f"l{l}_wq"], mode).reshape(T, nh, hd)
    k = mm(x, p[f"l{l}_wk"], mode).reshape(T, nkv, hd)
    v = mm(x, p[f"l{l}_wv"], mode).reshape(T, nkv, hd)
    idx = jnp.arange(T)
    q, k = rotary(q, idx, rope), rotary(k, idx, rope)
    q = q.reshape(T, nkv, group, hd)        # query head n reads K/V head n // group
    rows = min(QUERY_ROWS, T)
    # under a window a block of query rows reads the keys from window - 1
    # before its first row on: whole blocks of rows, so that the slice has one
    # size; the key blocks before them hold no allowed pair and are left out
    span = -(-(window - 1) // rows) * rows if window else T
    keys = min(span + rows, T)

    @jax.checkpoint
    def block(start):
        """A block of query rows: its [heads, rows, keys] scores never outlive it."""
        first = jnp.maximum(start + rows - keys, 0)
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, 0)
        kb = jax.lax.dynamic_slice_in_dim(k, first, keys, 0)
        vb = jax.lax.dynamic_slice_in_dim(v, first, keys, 0)
        s = jnp.einsum("qhgd,khd->hgqk", qb, kb, precision=HI) / math.sqrt(hd)
        m = allowed(start + jnp.arange(rows), first + jnp.arange(keys), window)
        w = jax.nn.softmax(jnp.where(m[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", w, vb, precision=HI)

    a = jax.lax.map(block, jnp.arange(0, T, rows)).reshape(T, nh, hd)
    gate = jax.nn.sigmoid(mm(x, p[f"l{l}_wgate"], mode))             # [T, nh]
    return mm((gate[:, :, None] * a).reshape(T, nh * hd), p[f"l{l}_wo"], mode)


def gated_mlp(x, wg, wu, wd, mode):
    """(silu(x Wg) * (x Wu)) Wd: the dense layer's feed-forward and the
    shared expert, a block of rows at a time."""
    rows = jax.checkpoint(lambda xb: mm(jax.nn.silu(mm(xb, wg, mode)) * mm(xb, wu, mode), wd, mode))
    n = min(TOKEN_ROWS, x.shape[0])
    if x.shape[0] % n:
        return rows(x)
    return jax.lax.map(rows, x.reshape(-1, n, x.shape[1])).reshape(x.shape)


def router_scores(logits):
    """The router's score function: softmax over all the routed experts
    (`assumed`; a sigmoid router would be this one line)."""
    return jax.nn.softmax(logits, axis=-1)


def moe(p, l, x, sizes, mode, held=None, given=None):
    """The ROUTED part of a sparse layer, scaling factor included. `held`:
    (first, count) of the routed experts whose part is computed; the
    configuration's own range by default. `given`: [T, experts a token]
    ids, the choice S as data. Tokens are independent here, so the layer is
    computed a block of rows at a time."""
    first, count = held or (sizes.get("experts_held_first", 0), sizes["num_experts"])
    k = sizes["num_experts_per_tok"]

    @jax.checkpoint
    def rows(x, given=None):
        r = router_scores(jnp.dot(x, p[f"l{l}_router"], precision=HI))
        if given is None:
            top, chosen = jax.lax.top_k(r, k)
        else:
            top, chosen = jnp.take_along_axis(r, given, axis=-1), given
        top = top / jnp.sum(top, axis=-1, keepdims=True)    # over ALL the chosen

        def add_expert(y, held_expert):
            # DEPARTURE: the held experts only; the rest live on other chips
            e, wg, wu, wd = held_expert
            w = jnp.sum(jnp.where(chosen == first + e, top, 0.0), axis=-1)
            out = mm(jax.nn.silu(mm(x, wg, mode)) * mm(x, wu, mode), wd, mode)
            return y + w[:, None] * out, None

        y = jax.lax.scan(
            add_expert, jnp.zeros_like(x),
            (jnp.arange(count), p[f"l{l}_gate"], p[f"l{l}_up"], p[f"l{l}_down"]))[0]
        return sizes["moe_routed_scaling_factor"] * y       # on the routed sum only

    n = min(TOKEN_ROWS, x.shape[0])
    if x.shape[0] % n:
        return rows(x, given)
    if given is None:
        return jax.lax.map(rows, x.reshape(-1, n, x.shape[1])).reshape(x.shape)
    return jax.lax.map(lambda xg: rows(*xg), (x.reshape(-1, n, x.shape[1]),
                                              given.reshape(-1, n, k))).reshape(x.shape)


def shared_expert(p, l, x, mode):
    return gated_mlp(x, p[f"l{l}_shared_gate"], p[f"l{l}_shared_up"],
                     p[f"l{l}_shared_down"], mode)


def feed_forward(p, l, x, sizes, mode, given=None):
    if not _sparse(sizes, l):
        return gated_mlp(x, p[f"l{l}_mlp_gate"], p[f"l{l}_mlp_up"], p[f"l{l}_mlp_down"], mode)
    return shared_expert(p, l, x, mode) + moe(p, l, x, sizes, mode, given=given)


def hidden(p, tokens, sizes, mode="highest", moe_inputs=None, routing=None):
    """One sequence's [T] ids -> the stream after the last layer, [T, H].
    `moe_inputs`: a list that gets each sparse layer's input. `routing`:
    [sparse layers, T, experts a token], each sparse layer's choices as data."""
    h = p["embed"][tokens]
    sparse = sparse_layers(sizes)
    for l in _layers(sizes):
        given = None if routing is None or l not in sparse else routing[sparse.index(l)]
        layer = lambda h, l=l, given=given: _layer(p, l, h, sizes, mode, moe_inputs, given)
        # the backward pass recomputes a layer from its input; a probe of the
        # sparse layers' inputs runs plain (it appends to a Python list)
        h = (jax.checkpoint(layer) if moe_inputs is None else layer)(h)
    return h


def _layer(p, l, h, sizes, mode, moe_inputs, given=None):
    eps = sizes["rms_norm_eps"]
    h = h + attention(p, l, rms(h, p[f"l{l}_norm1"][0], eps), sizes, mode)
    x = rms(h, p[f"l{l}_norm2"][0], eps)
    if moe_inputs is not None and _sparse(sizes, l):
        moe_inputs.append(x)
    return h + feed_forward(p, l, x, sizes, mode, given)


@functools.partial(jax.jit, static_argnames=("sizes_key", "mode"))
def _own_routing(p, tokens, sizes_key, mode):
    sizes = json.loads(sizes_key)
    xs = []
    hidden(p, tokens, sizes, mode, moe_inputs=xs)
    return jnp.stack([jax.lax.top_k(
        jnp.dot(x, p[f"l{l}_router"], precision=HI), sizes["num_experts_per_tok"])[1]
        for l, x in zip(sparse_layers(sizes), xs)])


def own_routing(p, batch, mode="highest"):
    """The choices this side makes by itself for the batch, as a batch's
    `routing` has them: int32 [sequences, sparse layers, T, experts a token]
    (the score function keeps the order, so the largest logits are the
    largest r)."""
    return jnp.stack([_own_routing(p, row, _CONFIG["key"], mode) for row in batch["tokens"]])


def sequence_cost(p, tokens, labels, sizes, mode="highest", routing=None):
    """sum over the sequence's positions of CE(logits_i, labels_i)."""
    T = labels.shape[0]
    h = rms(hidden(p, tokens, sizes, mode, routing=routing), p["final_norm"][0],
            sizes["rms_norm_eps"])

    @jax.checkpoint
    def rows(block):
        """A block of rows' cost: its [rows, V] scores never outlive it."""
        hb, lab = block
        logp = jax.nn.log_softmax(mm(hb, p["head"], mode), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lab[:, None], axis=-1)[:, 0])

    n = min(TOKEN_ROWS, T)
    if T % n:
        return rows((h, labels))
    return jnp.sum(jax.lax.map(rows, (h.reshape(-1, n, h.shape[1]), labels.reshape(-1, n))))


@functools.partial(jax.jit, static_argnames=("sizes_key", "mode"), donate_argnums=(0, 1))
def _add_sequence(loss, grads, p, tokens, labels, routing, share, sizes_key, mode):
    sizes = json.loads(sizes_key)
    l, g = jax.value_and_grad(
        lambda q: sequence_cost(q, tokens, labels, sizes, mode, routing) * share)(p)
    return loss + l, jax.tree_util.tree_map(jnp.add, grads, g)


def loss_and_grad(p, batch, mode="highest"):
    """The batch's loss (the mean over its positions) and its gradient
    (under the batch's `routing` where it brings one), a sequence at a time
    (and attention a block of query rows at a time), so that a batch's
    activations and a sequence's T x T scores never sit in memory at once."""
    n, T = batch["tokens"].shape
    loss = jnp.float32(0.0)
    grads = jax.tree_util.tree_map(jnp.zeros_like, p)
    routing = batch.get("routing")
    for i in range(n):
        loss, grads = _add_sequence(
            loss, grads, p, batch["tokens"][i], batch["labels"][i],
            None if routing is None else routing[i], jnp.float32(1.0 / (n * T)),
            _CONFIG["key"], mode)
    return loss, grads


def to_batch(arrays):
    """The traffic generator's named arrays -> this reference's batch."""
    return {"tokens": jnp.asarray(arrays["tokens"]), "labels": jnp.asarray(arrays["labels"])}
