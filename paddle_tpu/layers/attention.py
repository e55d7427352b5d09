"""Multi-head self-attention layer with optional context parallelism.

A TPU extension beyond the 2016 reference (whose only attention is the
additive simple_attention inside recurrent groups,
/root/reference/python/paddle/trainer_config_helpers/networks.py:943):
transformer-style scaled-dot-product attention over a padded sequence
[B, T, D], with the context dimension shardable across chips — the layer
dispatches to ring / all-to-all attention (paddle_tpu.parallel.
sequence_parallel) when the active mesh has a "seq" axis.

Parameters: ``_<name>.wqkv`` [D, 3·H·Dh] fused projection, ``_<name>.wo``
[H·Dh, D] output projection.

With ``head_dim`` set the layer is the grouped-query form: ``num_heads``
query heads over ``num_kv_heads`` key/value heads of ``head_dim`` each
(so H·Dh need not be the model width), separate ``_<name>.wq`` [D, H·Dh],
``.wk`` / ``.wv`` [D, Hkv·Dh] and ``.wo`` [H·Dh, size]; optionally an RMS
norm over each head's q and k (``.q_norm`` / ``.k_norm`` [1, Dh]) and a
rotary embedding (rotate-half, ``rope_theta``) at the positions the mask
rule gives each index, over the whole head or its first ``rotary_dim``
lanes, with YaRN's frequencies (``rope_yarn``) and a factor on cos and sin
(``rope_attention_factor``) where the config gives them; and the mask is a
rule over positions (``attention_mask``: full | causal | sliding_window
with ``mask_window`` | block_diffusion, `ops/attention_mask.py`) shared by
the XLA path and the Pallas kernel. ``output_gate``: each head's result is
multiplied, before ``wo``, by one number a head a position, the sigmoid (in
float32) of a projection of the layer's input, ``_<name>.wg`` [D, H] (the
head-wise gate of "Gated Attention for Large Language Models",
arXiv:2505.06708). Device time splits into the scopes ``qkv`` (the three
products, and the head prologue of q and k: norm, turn and the scores'
scale in one pass each way under a hand-written backward,
`ops/pallas_head_prologue.py`, the kernels ``head_prologue_fwd`` /
``head_prologue_bwd`` where a kernel can run), ``core`` (scores, softmax,
values), ``gate`` and ``out``.

With ``kv_latent_dim`` set the layer is the LATENT form (multi-head latent
attention): keys and values come, a head, from one ``kv_latent_dim``-wide
latent a position, and a head's scores are over ``head_dim`` lanes of its
own plus ``rope_head_dim`` rotary lanes that ALL the query heads read from
one shared key head; its values are ``value_head_dim`` wide. x [T, D] the
layer's input, H = ``num_heads``, dn / dr / dv the three widths::

    q = x Wq                         Wq [D, H (dn + dr)]; a head: [q_nope | q_rope]
    a = x Wkv_a                      Wkv_a [D, latent + dr]
    c = RMSNorm(a[:, :latent]; g)    g [latent];  k_rope = a[:, latent:]
    u = c Wkv_b                      Wkv_b [latent, H (dn + dv)]; a head: [k_nope | v]
    s_ij = (q_nope_i . k_nope_j + turn(q_rope)_i . turn(k_rope)_j) / sqrt(dn + dr)
    y = (softmax_j(s) v) Wo          Wo [H dv, size]

Parameters ``_<name>.wq``, ``.wkv_a``, ``.kv_norm`` [1, latent] (float32),
``.wkv_b``, ``.wo``. The two score parts go to the flash kernels as they
are (`ops/pallas_attention.py`: the shared rotary key head is never
broadcast in memory, the values keep their own width); the rotary lanes
are turned by the same prologue, with ``rope_interleave`` as a static
order of the weights' rotary columns (`pallas_head_prologue.
interleaved_order`). Scopes: ``qkv`` (q's product, the turn of q_rope, the
scores' scale), ``latent_down`` (x Wkv_a, the latent's norm, k_rope's
turn), ``latent_up`` (c Wkv_b), ``core``, ``out``. A recomputation block
keeps the kernel's ``out`` and ``lse``; c, k_nope and v are recomputed.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from paddle_tpu.graph.argument import Argument
from paddle_tpu.layers.base import LayerContext, register_layer, finalize_output, with_seq_meta
from paddle_tpu.ops.precision import hp
from paddle_tpu.proto import LayerConfig

Array = jax.Array


@register_layer("multi_head_attention")
def multi_head_attention(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    from paddle_tpu.parallel.sequence_parallel import (
        alltoall_attention,
        full_attention,
        ring_attention,
    )

    arg = inputs[0]
    assert arg.is_seq and arg.value is not None, (
        f"{cfg.name}: multi_head_attention needs a dense sequence input"
    )
    if cfg.kv_latent_dim:
        return _latent_attention(cfg, arg, ctx)
    if cfg.head_dim:
        return _grouped_query_attention(cfg, arg, ctx)
    x = arg.value                                   # [B, T, D]
    B, T, D = x.shape
    H = max(cfg.num_heads, 1)
    model_dim = cfg.size
    Dh = model_dim // H
    assert H * Dh == model_dim, f"{cfg.name}: size {model_dim} not divisible by heads {H}"

    wqkv = ctx.param(f"_{cfg.name}.wqkv")           # [D, 3·H·Dh]
    wo = ctx.param(f"_{cfg.name}.wo")               # [H·Dh, size_out]
    qkv = jnp.einsum("btd,de->bte", x, wqkv).reshape(B, T, 3, H, Dh)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

    lengths = arg.seq_lengths
    causal = cfg.causal_attention
    mesh = ctx.mesh
    mode = cfg.seq_parallel_mode
    if mesh is not None and "seq" in getattr(mesh, "axis_names", ()) and mode != "":
        attn = ring_attention if mode == "ring" else alltoall_attention
        out = attn(q, k, v, mesh, lengths=lengths, causal=causal)
    else:
        out = full_attention(q, k, v, lengths=lengths, causal=causal)
    out = out.reshape(B, T, H * Dh)
    value = jnp.einsum("bte,ed->btd", out, wo)
    value = finalize_output(cfg, value, ctx, mask=arg.seq_mask())
    # zero padded positions so downstream pooling/costs see clean zeros
    # (mask cast keeps bf16 activations bf16)
    value = value * arg.seq_mask(dtype=value.dtype)[..., None]
    return with_seq_meta(arg, value)


def rms_normalize(x: Array, gain: Array, eps: float) -> Array:
    """x / sqrt(mean(x^2) + eps) * gain over the last axis: statistics in
    float32, the result in x's dtype."""
    xf = hp(x)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (xf * inv * hp(gain)).astype(x.dtype)


def _rule_and_positions(cfg: LayerConfig, ctx: LayerContext, T: int):
    """What the forms with a mask rule share before their products: the
    rule, checked against T, and the position the rule gives each index."""
    from paddle_tpu.ops.attention_mask import rule_of

    if ctx.mesh is not None and cfg.seq_parallel_mode:
        raise NotImplementedError(
            f"{cfg.name}: attention with a mask rule does not "
            "run sequence-parallel yet; drop seq_parallel or the mesh's seq axis")
    rule = rule_of(cfg.attention_mask, cfg.mask_block_length, cfg.causal_attention,
                   cfg.mask_window)
    rule.check(T)
    return rule, rule.positions(jnp.arange(T, dtype=jnp.int32), T)


def _project_out(cfg: LayerConfig, arg: Argument, ctx: LayerContext, out: Array) -> Argument:
    """The tail those forms share: heads' results [B, T, H, Dv] -> ``wo``,
    the layer's bias and activation, padded positions zeroed."""
    B, T = out.shape[:2]
    with jax.named_scope("out"):
        value = jnp.einsum("bte,ed->btd", out.reshape(B, T, -1),
                           ctx.param(f"_{cfg.name}.wo"))
    value = finalize_output(cfg, value, ctx, mask=arg.seq_mask())
    value = value * arg.seq_mask(dtype=value.dtype)[..., None]
    return with_seq_meta(arg, value)


def _grouped_query_attention(cfg: LayerConfig, arg: Argument, ctx: LayerContext) -> Argument:
    from paddle_tpu.ops.pallas_attention import gate_heads
    from paddle_tpu.ops.pallas_head_prologue import head_prologue, turn_tables
    from paddle_tpu.parallel.sequence_parallel import rule_attention

    x = arg.value                                   # [B, T, D]
    B, T, _ = x.shape
    H, Dh = cfg.num_heads, cfg.head_dim
    Hkv = cfg.num_kv_heads or H
    assert H % Hkv == 0, f"{cfg.name}: {H} query heads over {Hkv} key/value heads"
    rule, pos = _rule_and_positions(cfg, ctx, T)
    with jax.named_scope("qkv"):
        q = jnp.einsum("btd,de->bte", x, ctx.param(f"_{cfg.name}.wq"))
        k = jnp.einsum("btd,de->bte", x, ctx.param(f"_{cfg.name}.wk"))
        v = jnp.einsum("btd,de->bte", x, ctx.param(f"_{cfg.name}.wv")).reshape(B, T, Hkv, Dh)
        gains = [ctx.param(f"_{cfg.name}.{n}", cast=False)[0] if cfg.qk_norm else None
                 for n in ("q_norm", "k_norm")]
        rot = cfg.rotary_dim or Dh
        turn = turn_tables(pos, cfg.rope_theta, Dh, rot, tuple(cfg.rope_yarn) or None,
                           cfg.rope_attention_factor) if cfg.rope_theta else None
        # the scores' 1/sqrt(Dh) is folded into q: the kernel multiplies no
        # score. The prologue leaves the projection's layout with its heads
        # named, [B, T, H, Dh], and the flash kernels read a head where it
        # lies (`pallas_attention.by_column`): nothing is transposed
        q = head_prologue(q, gains[0], turn, Dh, cfg.norm_epsilon, Dh ** -0.5, rot)
        k = head_prologue(k, gains[1], turn, Dh, cfg.norm_epsilon, 1.0, rot)
    with jax.named_scope("core"):
        out = rule_attention(q, k, v, arg.seq_lengths, rule, scale=1.0)
    if cfg.output_gate:
        with jax.named_scope("gate"):
            g = jax.nn.sigmoid(jnp.einsum("btd,dh->bth", x, ctx.param(f"_{cfg.name}.wg"),
                                          preferred_element_type=jnp.float32))
            out = gate_heads(out, g)
    return _project_out(cfg, arg, ctx, out)


def _latent_attention(cfg: LayerConfig, arg: Argument, ctx: LayerContext) -> Argument:
    """The latent form (module docstring)."""
    from paddle_tpu.ops.pallas_head_prologue import (
        head_prologue, interleaved_order, turn_tables)
    from paddle_tpu.parallel.sequence_parallel import rule_attention

    x = arg.value                                   # [B, T, D]
    B, T, D = x.shape
    H, dn, dr, latent = cfg.num_heads, cfg.head_dim, cfg.rope_head_dim, cfg.kv_latent_dim
    dv = cfg.value_head_dim or dn
    rule, pos = _rule_and_positions(cfg, ctx, T)
    turn = turn_tables(pos, cfg.rope_theta, dr, dr, tuple(cfg.rope_yarn) or None,
                       cfg.rope_attention_factor)
    # the rotary lanes' order, on the weights' columns (a score sums over
    # lanes: q_rope and k_rope reordered alike give the same scores)
    order = interleaved_order(dr) if cfg.rope_interleave else slice(None)
    # a part with rotary lanes goes through the head prologue, which turns it
    # and leaves [B, T, heads, dr]. The other parts need no prologue (no
    # per-head norm, no turn), and nothing of them is kept for a backward.
    # Every part stays as its product leaves it, and the flash kernels read a
    # head where it lies (`pallas_attention.by_column`): q_nope, k_nope and v
    # as 128-lane column blocks, k_rope as its one head; only q_rope, many
    # heads of 64 lanes, is transposed to head-major round the kernels
    turned = lambda y, scale: head_prologue(y, None, turn, dr, cfg.norm_epsilon, scale)
    with jax.named_scope("qkv"):
        wq = ctx.param(f"_{cfg.name}.wq").reshape(D, H, dn + dr)
        # the scores' 1/sqrt(dn + dr) is folded into both parts of q
        scale = (dn + dr) ** -0.5
        q_nope = jnp.einsum("btd,de->bte", x, wq[:, :, :dn].reshape(D, H * dn))
        q_nope = (q_nope.astype(jnp.float32) * scale).astype(x.dtype).reshape(B, T, H, dn)
        q_rope = turned(jnp.einsum("btd,de->bte", x, wq[:, :, dn:][:, :, order].reshape(D, H * dr)),
                        scale)
    with jax.named_scope("latent_down"):
        wkv_a = ctx.param(f"_{cfg.name}.wkv_a")
        c = rms_normalize(jnp.einsum("btd,de->bte", x, wkv_a[:, :latent]),
                          ctx.param(f"_{cfg.name}.kv_norm", cast=False)[0], cfg.norm_epsilon)
        # ONE key head of rotary lanes for all the query heads
        k_rope = turned(jnp.einsum("btd,de->bte", x, wkv_a[:, latent:][:, order]), 1.0)
    with jax.named_scope("latent_up"):
        wkv_b = ctx.param(f"_{cfg.name}.wkv_b").reshape(latent, H, dn + dv)
        k_nope = jnp.einsum("bte,ef->btf", c, wkv_b[:, :, :dn].reshape(latent, H * dn)).reshape(B, T, H, dn)
        v = jnp.einsum("bte,ef->btf", c, wkv_b[:, :, dn:].reshape(latent, H * dv)).reshape(B, T, H, dv)
    with jax.named_scope("core"):
        out = rule_attention((q_nope, q_rope), (k_nope, k_rope), v, arg.seq_lengths, rule,
                             scale=1.0)
    return _project_out(cfg, arg, ctx, out)
