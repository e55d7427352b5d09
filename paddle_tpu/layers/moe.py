"""Sparse-expert (mixture of experts) feed-forward layer.

A TPU extension beyond the 2016 reference. Every token is routed to
``experts_per_token`` of ``experts`` SwiGLU experts:

    r   = softmax(x Wr) over all the experts, in float32
          (score_function "sigmoid": sigmoid(x Wr), an expert by itself)
    S   = the experts_per_token largest of r   (selection_bias: of r + b)
    w_e = r_e / sum_{e' in S} r_e'        (norm_topk_prob; else w_e = r_e)
    y   = f * sum_{e in S, held here} w_e * (silu(x Wg_e) * (x Wu_e)) Wd_e

with ``f`` the ``routed_scaling_factor`` (1 by default). The selection
bias ``b`` (``_<name>.router_bias`` [1, experts], float32) steers the
CHOICE alone: the weights come from the unbiased scores of the chosen. It
is a static parameter: no gradient reaches it (a choice has none), and the
clip and the optimizer pass it by; the rule that moves it from the experts'
load, outside the gradient, is not built. A shared expert,
which every token passes through, is no part of this layer: it is a
``gated_mlp`` layer beside it (`layers/gated_mlp.py`), joined in the config.

The program HOLDS a contiguous range of the experts (`experts_held_first`,
`experts_held_count`; all of them by default): it routes over all of them,
renormalises over all the chosen, and computes its own experts' part; the
sum of every holder's output is the whole layer's. On one chip there is
no exchange. No capacity and no dropped pair (`ops/grouped_matmul.py`).

Parameters: ``_<name>.router`` [D, experts] (kept float32), and the held
experts stacked: ``_<name>.gate`` / ``.up`` [held, D, width], ``.down``
[held, width, D]. Device time splits into the scopes ``router``,
``dispatch`` (sort, gather), ``experts`` (the grouped products) and
``combine`` (scatter-add). The layer counts (`base.publish_counter`)
``moe.pairs_held``, the pairs it computed, ``moe.load_max_over_mean``,
its fullest held expert's pairs over the mean, and, under a selection bias,
``moe.bias_moved_pairs``, the chosen (token, expert) pairs that the unbiased
scores would not have chosen (held or not; one more top-k over the scores),
and publishes the extra
output ``<name>@chosen``, int32 [..., experts_per_token]: the experts it
chose for each token, for a config that wants them
(``get_output_layer(moe, "chosen")``); unread, it costs nothing.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from paddle_tpu.graph.argument import Argument
from paddle_tpu.layers.base import (
    LayerContext,
    finalize_output,
    publish_counter,
    register_layer,
    with_seq_meta,
)
from paddle_tpu.ops.grouped_matmul import expert_ffn, route
from paddle_tpu.proto import LayerConfig


def router_scores(logits, score_function: str):
    """The router's score function, float32 [tokens, experts]: a softmax
    over the experts ("" or "softmax"), or each expert's own sigmoid."""
    if score_function == "sigmoid":
        return jax.nn.sigmoid(logits)
    return jax.nn.softmax(logits, axis=-1)


@register_layer("moe")
def moe_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    if ctx.mesh is not None:
        raise NotImplementedError(
            f"{cfg.name}: the sparse-expert layer does not run under a mesh yet "
            "(experts across chips need the all-to-all exchange): train it on "
            "one chip, each holding its experts_held range")
    arg = inputs[0]
    x = arg.value
    lead, D = x.shape[:-1], x.shape[-1]
    xf = x.reshape(-1, D)
    first = cfg.experts_held_first
    count = cfg.experts_held_count or cfg.experts - first
    k = cfg.experts_per_token
    with jax.named_scope("router"):
        logits = jnp.dot(xf.astype(jnp.float32),
                         ctx.param(f"_{cfg.name}.router", cast=False).astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = router_scores(logits, cfg.score_function)
        if cfg.selection_bias:
            # the bias is in the choice and NOT in the weights
            bias = jax.lax.stop_gradient(ctx.param(f"_{cfg.name}.router_bias", cast=False))
            _, chosen = jax.lax.top_k(probs + bias, k)
            top = jnp.take_along_axis(probs, chosen, axis=-1)
            unbiased = jax.lax.top_k(probs, k)[1]
            moved = jnp.all(chosen[:, :, None] != unbiased[:, None, :], axis=-1)
        else:
            top, chosen = jax.lax.top_k(probs, k)
        weight = top
        if cfg.norm_topk_prob:
            total = jnp.sum(top, axis=-1, keepdims=True)
            # a sigmoid's chosen scores may all be tiny: the published guard
            weight = top / (total + 1e-20 if cfg.score_function == "sigmoid" else total)
        if cfg.routed_scaling_factor != 1.0:
            weight = weight * cfg.routed_scaling_factor
    with jax.named_scope("dispatch"):
        routing = route(chosen.astype(jnp.int32), first, count)
    y = expert_ffn(xf, weight, ctx.param(f"_{cfg.name}.gate"),
                   ctx.param(f"_{cfg.name}.up"), ctx.param(f"_{cfg.name}.down"),
                   routing)
    sizes = routing.group_sizes.astype(jnp.float32)
    publish_counter(cfg, ctx, "moe.pairs_held", jnp.sum(sizes))
    if cfg.selection_bias:
        publish_counter(cfg, ctx, "moe.bias_moved_pairs", jnp.sum(moved.astype(jnp.float32)))
    publish_counter(cfg, ctx, "moe.load_max_over_mean",
                    jnp.max(sizes) * count / jnp.maximum(jnp.sum(sizes), 1.0), how="max")
    ctx.outputs[f"{cfg.name}@chosen"] = with_seq_meta(
        arg, chosen.astype(jnp.int32).reshape(*lead, k))
    value = finalize_output(cfg, y.reshape(*lead, D), ctx)
    if arg.is_seq:
        value = value * arg.seq_mask(dtype=value.dtype)[..., None]
    return with_seq_meta(arg, value)
