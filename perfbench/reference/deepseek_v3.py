"""Plain reference of a `model_type: deepseek_v3` decoder with `q_lora_rank:
null` (kanana-2-30b-a3b-instruct-2601, kakaocorp): latent attention (a
head's scores over `qk_nope_head_dim` lanes of its own and
`qk_rope_head_dim` rotary lanes shared by all heads, its values
`v_head_dim` wide, keys and values projected up from one `kv_lora_rank`-wide
latent), `first_k_dense_replace` dense lead layers, then sparse layers of a
sigmoid router whose CHOICE is steered by a per-expert bias, `n_shared_experts`
shared experts and `n_routed_experts` routed ones, as ONE CHIP'S SHARE of an
expert-parallel deployment, trained by next-token prediction.

Straightforward `jax.numpy`, float32, matrix products at `highest`
precision, a dense mask, a dense loop over experts, no kernels. It imports
nothing from `paddle_tpu` and takes nothing the program made: the weights
come from `init_params(sizes, seed)` here, and the harness hands the SAME
arrays to the program.

Block l on a sequence's stream h [T, D]; H = `num_attention_heads`; dn, dr,
dv = `qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`; N(x; g) = x *
rsqrt(mean(x^2) + eps) * g:

- x = N(h; g1); q = x Wq -> [T, H, dn + dr], a head [q_nope | q_rope]
  (`q_lora_rank` null: a full-rank product, no norm on q).
- a = x Wkv_a -> [T, kv_lora_rank + dr]; c = N(a[:, :kv_lora_rank]; g_kv);
  k_rope = a[:, kv_lora_rank:], ONE head for all H query heads.
- u = c Wkv_b -> [T, H, dn + dv], a head [k_nope | v].
- q_rope and k_rope turned by rotary positions (`rope_theta`, all dr lanes;
  `rope_scaling` null: plain frequencies, no factor), `rope_interleave`:
  the lanes (2i, 2i + 1) are a pair (`rotary`).
- s_ij = (q_nope_i . k_nope_j + q_rope_i . k_rope_j) / sqrt(dn + dr) for
  j <= i; softmax; o_i = sum_j p_ij v_j; h <- h + o Wo. No bias, no output gate.
- x2 = N(h; g2). Layer l < `first_k_dense_replace`: h <- h + (silu(x2 Wg) *
  (x2 Wu)) Wd, `intermediate_size` wide. Else: r = `router_scores`(x2 Wr)
  over ALL the routed experts (sigmoid), S = the `num_experts_per_tok`
  largest of r + b (b the selection bias; `n_group` = `topk_group` = 1: no
  group limit), w_e = r_e / (sum_{S} r + 1e-20) * `routed_scaling_factor`
  (the bias is in the choice and NOT in the weights); h <- h + the shared
  MLP's (silu(x2 Sg) * (x2 Su)) Sd, `n_shared_experts` *
  `moe_intermediate_size` wide, + sum_{e in S and HELD HERE} w_e (silu(x2
  Wg_e) * (x2 Wu_e)) Wd_e.
- Head: logits = N(h; gf) W_head; loss = mean over the batch's positions of
  -log softmax(logits)[label], the label being the next token.

Departures from the published model, each marked DEPARTURE at its line: the
experts held (a range of the routed ones), the vocabulary slice, the depth
(the configuration's `reduced`), the selection bias's values (drawn from
the seed and constant: the rule that moves them from the experts' load is
outside the gradient and not part of a step here), and no auxiliary or
sequence-wise balance term in the loss.

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
    return l >= sizes["first_k_dense_replace"] and l % sizes.get("moe_layer_freq", 1) == 0


def sparse_layers(sizes):
    """The sparse layers' numbers, in order: a batch's `routing` has one
    entry for each."""
    return [l for l in _layers(sizes) if _sparse(sizes, l)]


def static_leaves(sizes):
    """The leaves no gradient and no optimizer touches: the selection biases."""
    return [f"l{l}_router_bias" for l in sparse_layers(sizes)]


def param_shapes(sizes):
    """Reference name -> shape. A leading 1 marks a norm's gain (and a
    router's selection bias, by name)."""
    d, nh = sizes["hidden_size"], sizes["num_attention_heads"]
    dn, dr, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    latent = sizes["kv_lora_rank"]
    f, routed = sizes["moe_intermediate_size"], sizes["n_routed_experts_total"]
    fs, fd = sizes["n_shared_experts"] * f, sizes["intermediate_size"]
    held = sizes["n_routed_experts"]    # DEPARTURE: the experts held here (`reduced`)
    v = sizes["vocab_size"]             # DEPARTURE: the vocabulary slice (`reduced`)
    shapes = {"embed": (v, d), "head": (d, v), "final_norm": (1, d)}
    for l in _layers(sizes):
        shapes.update({
            f"l{l}_norm1": (1, d), f"l{l}_wq": (d, nh * (dn + dr)),
            f"l{l}_wkv_a": (d, latent + dr), f"l{l}_kv_norm": (1, latent),
            f"l{l}_wkv_b": (latent, nh * (dn + dv)), f"l{l}_wo": (nh * dv, d),
            f"l{l}_norm2": (1, d)})
        if _sparse(sizes, l):
            shapes.update({
                f"l{l}_router": (d, routed), f"l{l}_router_bias": (1, routed),
                f"l{l}_gate": (held, d, f), f"l{l}_up": (held, d, f), f"l{l}_down": (held, f, d),
                f"l{l}_shared_gate": (d, fs), f"l{l}_shared_up": (d, fs),
                f"l{l}_shared_down": (fs, d)})
        else:
            shapes.update({f"l{l}_mlp_gate": (d, fd), f"l{l}_mlp_up": (d, fd),
                           f"l{l}_mlp_down": (fd, d)})
    return shapes


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init(shape_items, key, bias_std):
    out = {}
    for i, (name, shape) in enumerate(shape_items):
        k = jax.random.fold_in(key, i)
        if name.endswith("_router_bias"):
            # DEPARTURE: the selection bias's values are drawn from the seed,
            # N(0, selection_bias_std) (`assumed`), and constant over a run
            out[name] = bias_std * jax.random.normal(k, shape, jnp.float32)
        elif shape[0] == 1:                  # a gain: starts at 1
            out[name] = jnp.ones(shape, jnp.float32)
        elif name == "embed":                # unit rows: the stream starts at scale 1
            out[name] = jax.random.normal(k, shape, jnp.float32)
        else:                                # N(0, 1/sqrt(fan-in))
            out[name] = shape[-2] ** -0.5 * jax.random.normal(k, shape, jnp.float32)
    return out


_SIZE_KEYS = ("hidden_size", "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
              "v_head_dim", "kv_lora_rank", "rope_theta", "rope_interleave",
              "first_k_dense_replace", "moe_layer_freq", "intermediate_size",
              "moe_intermediate_size", "n_shared_experts", "n_routed_experts",
              "n_routed_experts_total", "experts_held_first", "num_experts_per_tok",
              "routed_scaling_factor", "scoring_func", "norm_topk_prob",
              "num_hidden_layers", "vocab_size", "rms_norm_eps")

# the configuration `init_params` was last asked for, as a JSON string (a
# jitted function's static argument): `loss_and_grad(p, batch, mode)` is
# handed arrays only (perfbench/compare/train_steps.py)
_CONFIG = {"key": "{}"}


def configure(sizes):
    _CONFIG["key"] = json.dumps({k: sizes[k] for k in _SIZE_KEYS if k in sizes},
                                sort_keys=True)


def init_params(sizes, seed):
    """All weights in one jitted call on the device, float32. Remembers
    `sizes` for `loss_and_grad`."""
    configure(sizes)
    key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    return _init(tuple(sorted(param_shapes(sizes).items())), key,
                 float(sizes.get("selection_bias_std", 0.0)))


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
    """A linear layer's matrix product (projections, the feed-forwards,
    head). "fp8" is the control: both inputs rounded to float8_e4m3fn
    (per-tensor scale), exact products, float32 accumulation. The router,
    the softmaxes and attention's own products stay at `highest` in every
    mode, as an fp8 recipe keeps them."""
    if mode == "fp8":
        a, b = _as_fp8(a), _as_fp8(b)
    elif mode == "bfloat16":
        a, b = _as_bf16(a), _as_bf16(b)
    elif mode != "highest":
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.dot(a, b, precision=HI)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def rotary(x, pos, theta, interleave):
    """x [T, heads, dr] turned at positions pos [T], all dr lanes, angle i
    = pos * theta^(-2i/dr). `interleave`: the lanes (2i, 2i + 1) are pair i
    and stay where they are (the `transformers` library de-interleaves to
    [even lanes | odd lanes] and rotates halves: q and k reordered alike,
    the same scores); else lane i pairs with lane i + dr/2."""
    dr = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * float(theta) ** (
        -2.0 * jnp.arange(dr // 2, dtype=jnp.float32) / dr)[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * c - b * s, b * c + a * s], axis=-1).reshape(x.shape)
    a, b = x[..., :dr // 2], x[..., dr // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def attention(p, l, x, sizes, mode):
    """The block's latent attention with its output projection; x [T, D] is
    the block's normalised input."""
    T = x.shape[0]
    nh, dn, dr, dv = (sizes["num_attention_heads"], sizes["qk_nope_head_dim"],
                      sizes["qk_rope_head_dim"], sizes["v_head_dim"])
    latent = sizes["kv_lora_rank"]
    q = mm(x, p[f"l{l}_wq"], mode).reshape(T, nh, dn + dr)
    a = mm(x, p[f"l{l}_wkv_a"], mode)
    c = rms(a[:, :latent], p[f"l{l}_kv_norm"][0], sizes["rms_norm_eps"])
    u = mm(c, p[f"l{l}_wkv_b"], mode).reshape(T, nh, dn + dv)
    idx = jnp.arange(T)
    turn = lambda y: rotary(y, idx, sizes["rope_theta"], sizes.get("rope_interleave", False))
    q_nope, q_rope = q[..., :dn], turn(q[..., dn:])
    k_nope, v = u[..., :dn], u[..., dn:]
    k_rope = turn(a[:, None, latent:])[:, 0]                 # [T, dr]: one head for all
    rows = min(QUERY_ROWS, T)

    @jax.checkpoint
    def block(start):
        """A block of query rows: its [heads, rows, T] scores never outlive it."""
        qn = jax.lax.dynamic_slice_in_dim(q_nope, start, rows, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, start, rows, 0)
        s = (jnp.einsum("qhd,khd->hqk", qn, k_nope, precision=HI)
             + jnp.einsum("qhd,kd->hqk", qr, k_rope, precision=HI)) / math.sqrt(dn + dr)
        m = idx[None, :] <= (start + jnp.arange(rows))[:, None]          # j <= i
        w = jax.nn.softmax(jnp.where(m[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(0, T, rows)).reshape(T, nh * dv)
    return mm(o, p[f"l{l}_wo"], mode)


def gated_mlp(x, wg, wu, wd, mode):
    """(silu(x Wg) * (x Wu)) Wd: the dense layer's feed-forward and the
    shared experts, a block of rows at a time."""
    rows = jax.checkpoint(lambda xb: mm(jax.nn.silu(mm(xb, wg, mode)) * mm(xb, wu, mode), wd, mode))
    n = min(TOKEN_ROWS, x.shape[0])
    if x.shape[0] % n:
        return rows(x)
    return jax.lax.map(rows, x.reshape(-1, n, x.shape[1])).reshape(x.shape)


def router_scores(logits):
    """The router's score function (`scoring_func: sigmoid`): each expert's
    own sigmoid, float32."""
    return jax.nn.sigmoid(logits)


def choose(r, bias, k):
    """S: the k largest of r + b (`topk_method: noaux_tc` with `n_group` =
    `topk_group` = 1: no group limit). The bias steers the choice alone."""
    return jax.lax.top_k(r + bias, k)[1]


def moe(p, l, x, sizes, mode, held=None, given=None):
    """The ROUTED part of a sparse layer, scaling factor included. `held`:
    (first, count) of the routed experts whose part is computed; the
    configuration's own range by default. `given`: [T, experts a token]
    ids, the choice S as data. Tokens are independent here, so the layer is
    computed a block of rows at a time."""
    first, count = held or (sizes.get("experts_held_first", 0), sizes["n_routed_experts"])
    k = sizes["num_experts_per_tok"]

    @jax.checkpoint
    def rows(x, given=None):
        r = router_scores(jnp.dot(x, p[f"l{l}_router"], precision=HI))
        chosen = choose(r, p[f"l{l}_router_bias"], k) if given is None else given
        top = jnp.take_along_axis(r, chosen, axis=-1)       # the UNBIASED scores
        if sizes.get("norm_topk_prob", True):
            top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)  # over ALL the chosen

        def add_expert(y, held_expert):
            # DEPARTURE: the held experts only; the rest live on other chips
            e, wg, wu, wd = held_expert
            w = jnp.sum(jnp.where(chosen == first + e, top, 0.0), axis=-1)
            out = mm(jax.nn.silu(mm(x, wg, mode)) * mm(x, wu, mode), wd, mode)
            return y + w[:, None] * out, None

        y = jax.lax.scan(
            add_expert, jnp.zeros_like(x),
            (jnp.arange(count), p[f"l{l}_gate"], p[f"l{l}_up"], p[f"l{l}_down"]))[0]
        return sizes["routed_scaling_factor"] * y           # on the routed sum only

    n = min(TOKEN_ROWS, x.shape[0])
    if x.shape[0] % n:
        return rows(x, given)
    if given is None:
        return jax.lax.map(rows, x.reshape(-1, n, x.shape[1])).reshape(x.shape)
    return jax.lax.map(lambda xg: rows(*xg), (x.reshape(-1, n, x.shape[1]),
                                              given.reshape(-1, n, k))).reshape(x.shape)


def shared_expert(p, l, x, mode):
    """The `n_shared_experts` shared experts: ONE gated MLP of their joint
    width, which every token passes."""
    return gated_mlp(x, p[f"l{l}_shared_gate"], p[f"l{l}_shared_up"],
                     p[f"l{l}_shared_down"], mode)


def feed_forward(p, l, x, sizes, mode, given=None):
    if not _sparse(sizes, l):
        return gated_mlp(x, p[f"l{l}_mlp_gate"], p[f"l{l}_mlp_up"], p[f"l{l}_mlp_down"], mode)
    return shared_expert(p, l, x, mode) + moe(p, l, x, sizes, mode, given=given)


def hidden(p, tokens, sizes, mode="highest", moe_inputs=None, routing=None):
    """One sequence's [T] ids -> the stream after the last layer, [T, D].
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
    return jnp.stack([choose(
        router_scores(jnp.dot(x, p[f"l{l}_router"], precision=HI)),
        p[f"l{l}_router_bias"], sizes["num_experts_per_tok"])
        for l, x in zip(sparse_layers(sizes), xs)])


def own_routing(p, batch, mode="highest"):
    """The choices this side makes by itself for the batch, as a batch's
    `routing` has them: int32 [sequences, sparse layers, T, experts a token]."""
    return jnp.stack([_own_routing(p, row, _CONFIG["key"], mode) for row in batch["tokens"]])


def sequence_cost(p, tokens, labels, sizes, mode="highest", routing=None):
    """sum over the sequence's positions of CE(logits_i, labels_i). DEPARTURE:
    no auxiliary and no sequence-wise balance term (`assumed`)."""
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
    activations and a sequence's T x T scores never sit in memory at once.
    The selection biases' gradient is 0: a choice has none."""
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
