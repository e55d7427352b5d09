"""Dense gated feed-forward layer (SwiGLU).

A TPU extension beyond the 2016 reference: ``(silu(x Wg) * (x Wu)) Wd``
over the feature axis, ``expert_width`` wide: a transformer block's dense
feed-forward, or the shared expert beside a sparse-expert layer
(`layers/moe.py`), which every token passes through. The gate's silu and
the product are taken in float32 and rounded once, as the experts' are.

Parameters: ``_<name>.gate`` / ``.up`` [D, width], ``.down`` [width, D];
no bias.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from paddle_tpu.graph.argument import Argument
from paddle_tpu.layers.base import LayerContext, finalize_output, register_layer, with_seq_meta
from paddle_tpu.proto import LayerConfig


@register_layer("gated_mlp")
def gated_mlp_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    arg = inputs[0]
    x = arg.value
    g = jnp.einsum("...d,df->...f", x, ctx.param(f"_{cfg.name}.gate"))
    u = jnp.einsum("...d,df->...f", x, ctx.param(f"_{cfg.name}.up"))
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(x.dtype)
    value = jnp.einsum("...f,fd->...d", h, ctx.param(f"_{cfg.name}.down"))
    value = finalize_output(cfg, value, ctx)
    if arg.is_seq:
        value = value * arg.seq_mask(dtype=value.dtype)[..., None]
    return with_seq_meta(arg, value)
