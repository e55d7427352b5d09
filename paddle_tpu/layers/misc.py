"""Elementwise / miscellaneous layers.

Reference counterparts in /root/reference/paddle/gserver/layers/:
InterpolationLayer, PowerLayer, ScalingLayer, SlopeInterceptLayer,
SumToOneNormLayer, ConvexCombinationLayer, CosSimLayer, CosSimVecMatLayer,
OuterProdLayer, ConvShiftLayer, MultiplexLayer, DataNormLayer,
HierarchicalSigmoidLayer, NCELayer.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from paddle_tpu.graph.argument import Argument
from paddle_tpu.layers.base import (
    LayerContext,
    finalize_output,
    first_seq_meta,
    input_mask,
    register_layer,
    with_seq_meta,
)
from paddle_tpu.proto import LayerConfig

Array = jax.Array
_EPS = 1e-10


@register_layer("interpolation")
def interpolation_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # out = w * x + (1 - w) * y ; w is [B, 1]
    w, x, y = inputs[0].value, inputs[1].value, inputs[2].value
    out = w * x + (1.0 - w) * y
    meta = first_seq_meta(inputs[1:])
    return with_seq_meta(meta, out)


@register_layer("power")
def power_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # out = x ^ w ; w is [B, 1] scalar exponent per sample
    w, x = inputs[0].value, inputs[1].value
    out = jnp.power(jnp.clip(x, _EPS, None), w)
    return with_seq_meta(inputs[1], out)


@register_layer("scaling")
def scaling_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # out = w * x ; w is [B, 1] per-sample scale
    w, x = inputs[0].value, inputs[1].value
    return with_seq_meta(inputs[1], w * x)


@register_layer("slope_intercept")
def slope_intercept_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    out = cfg.slope * inputs[0].value + cfg.intercept
    return with_seq_meta(inputs[0], out)


@register_layer("sum_to_one_norm")
def sum_to_one_norm_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    x = inputs[0].value
    s = jnp.sum(x, axis=-1, keepdims=True)
    return with_seq_meta(inputs[0], x / jnp.where(jnp.abs(s) < _EPS, 1.0, s))


@register_layer("convex_comb")
def convex_comb_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: ConvexCombinationLayer — inputs: (weights [B, M], vectors
    # [B, M*size]); out[b] = sum_m w[b,m] * v[b,m,:].
    w, v = inputs[0].value, inputs[1].value
    M = w.shape[-1]
    vv = v.reshape(v.shape[0], M, cfg.size)
    out = jnp.einsum("bm,bmd->bd", w, vv)
    return Argument(value=out)


@register_layer("cos")
def cos_sim_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    x, y = inputs[0].value, inputs[1].value
    dot = jnp.sum(x * y, axis=-1, keepdims=True)
    nx = jnp.linalg.norm(x, axis=-1, keepdims=True)
    ny = jnp.linalg.norm(y, axis=-1, keepdims=True)
    out = cfg.cos_scale * dot / jnp.clip(nx * ny, _EPS, None)
    meta = first_seq_meta(inputs)
    return with_seq_meta(meta, out)


@register_layer("cos_vm")
def cos_vec_mat_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: CosSimVecMatLayer — cosine of a vector against each row of a
    # matrix input: x [B, D], m [B, K*D] → out [B, K].
    x, m = inputs[0].value, inputs[1].value
    K = cfg.size
    D = x.shape[-1]
    mm = m.reshape(m.shape[0], K, D)
    dot = jnp.einsum("bd,bkd->bk", x, mm)
    nx = jnp.linalg.norm(x, axis=-1, keepdims=True)
    nm = jnp.linalg.norm(mm, axis=-1)
    out = cfg.cos_scale * dot / jnp.clip(nx * nm, _EPS, None)
    return Argument(value=out)


@register_layer("out_prod")
def outer_prod_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    x, y = inputs[0].value, inputs[1].value
    out = jnp.einsum("bi,bj->bij", x, y).reshape(x.shape[0], -1)
    return Argument(value=out)


@register_layer("conv_shift")
def conv_shift_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: ConvShiftLayer — circular convolution (NTM-style shift):
    # out[i] = sum_j a[(i + j - (K-1)/2) mod D] * b[j], b of odd width K.
    a, b = inputs[0].value, inputs[1].value
    D, K = a.shape[-1], b.shape[-1]
    half = (K - 1) // 2
    idx = (jnp.arange(D)[:, None] + jnp.arange(K)[None, :] - half) % D  # [D, K]
    gathered = a[:, idx]  # [B, D, K]
    out = jnp.einsum("bdk,bk->bd", gathered, b)
    return Argument(value=out)


@register_layer("multiplex")
def multiplex_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: MultiplexLayer — first input: index ids choosing which of the
    # remaining inputs supplies each row.
    sel = inputs[0].ids
    stacked = jnp.stack([a.value for a in inputs[1:]], axis=0)  # [N, B, D]
    out = jnp.take_along_axis(stacked, sel[None, :, None], axis=0)[0]
    return Argument(value=out)


@register_layer("data_norm")
def data_norm_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: DataNormLayer — normalize features with precomputed stats held
    # in the (static) input parameter, strategies z-score/min-max/decimal.
    x = inputs[0].value
    stats = ctx.param(cfg.inputs[0].input_parameter_name).reshape(5, cfg.size)
    # rows: min, max, sum, sum_of_squares, count (reference layout)
    mn, mx, sm, ssq, cnt = stats
    cnt = jnp.clip(cnt, 1.0, None)
    mean = sm / cnt
    std = jnp.sqrt(jnp.clip(ssq / cnt - mean * mean, _EPS, None))
    strat = cfg.data_norm_strategy
    if strat == "z-score":
        out = (x - mean) / std
    elif strat == "min-max":
        out = (x - mn) / jnp.clip(mx - mn, _EPS, None)
    else:  # decimal-scaling
        out = x / jnp.clip(jnp.power(10.0, jnp.ceil(jnp.log10(jnp.clip(jnp.abs(mx), 1.0, None)))), 1.0, None)
    return with_seq_meta(inputs[0], out)


@register_layer("hsigmoid")
def hsigmoid_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    """Hierarchical sigmoid cost (ref: HierarchicalSigmoidLayer.cpp +
    math/MatrixBitCode.cpp): binary-code decomposition of a num_classes
    softmax; cost only (generation path not reproduced).

    Code of class c: bits of (c + num_classes) below the MSB, walked from
    the bit under the MSB downward; node index for bit j is
    (c + num_classes) >> (j+1) minus 1... following the reference's
    simplified arithmetic: idx_j = ((c + num_classes) >> (j + 1)) - 1.
    """
    label = inputs[-1]
    feats = inputs[:-1]
    num_classes = cfg.num_classes
    code_len = max(1, (num_classes - 1).bit_length())
    c = (label.ids if label.ids is not None else jnp.argmax(label.value, -1)).astype(jnp.int32)
    code = c + num_classes
    js = jnp.arange(code_len, dtype=jnp.int32)
    node = (code[:, None] >> (js[None, :] + 1)) - 1        # [B, L]
    bit = ((code[:, None] >> js[None, :]) & 1).astype(jnp.float32)
    valid = (node >= 0).astype(jnp.float32)
    node_c = jnp.clip(node, 0, num_classes - 2)
    acc = jnp.zeros(bit.shape, jnp.float32)
    for in_cfg, f in zip(cfg.inputs[:-1], feats):
        # gather the path rows from the master-dtype table (casting the
        # whole [num_classes-1, D] table per step would be an HBM-bound
        # full pass); the cost is an f32 island anyway
        w = ctx.param(in_cfg.input_parameter_name, cast=False)
        acc = acc + jnp.einsum("bd,bld->bl", f.value, w[node_c])
    if cfg.bias_parameter_name:
        b = ctx.param(cfg.bias_parameter_name, cast=False).reshape(-1)
        acc = acc + b[node_c]
    # per-node binary CE: bit=1 ⇒ -log sigmoid(acc) ... reference sums
    # -log(sigmoid) over the path with sign from the bit.
    per_node = jnp.logaddexp(0.0, acc) - bit * acc
    cost = jnp.sum(per_node * valid, axis=1)
    return Argument(value=cost[:, None])


@register_layer("nce")
def nce_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    """Noise-contrastive estimation cost (ref: NCELayer.cpp).

    inputs: feature(s) + label (+ optional per-sample weight). Samples
    num_neg_samples negatives from neg_sampling_dist (or uniform).
    """
    # feature inputs are exactly those with a parameter attached
    # (reference NCELayer.cpp:80-84: label then optional weight follow)
    n_feat = sum(1 for ic in cfg.inputs if ic.input_parameter_name)
    feats = inputs[:n_feat]
    label = inputs[n_feat]
    weight = inputs[n_feat + 1] if len(inputs) > n_feat + 1 else None
    num_classes = cfg.num_classes
    k = cfg.num_neg_samples
    pos = (label.ids if label.ids is not None else jnp.argmax(label.value, -1)).astype(jnp.int32)
    B = pos.shape[0]
    rng = ctx.layer_rng(cfg.name, "nce")
    if cfg.neg_sampling_dist:
        dist = jnp.asarray(cfg.neg_sampling_dist)
        logits = jnp.log(jnp.clip(dist, _EPS, None))
        neg = jax.random.categorical(rng, logits, shape=(B, k)).astype(jnp.int32)
        p_noise = dist
    else:
        neg = jax.random.randint(rng, (B, k), 0, num_classes, jnp.int32)
        p_noise = jnp.full((num_classes,), 1.0 / num_classes)
    samples = jnp.concatenate([pos[:, None], neg], axis=1)  # [B, 1+k]
    acc = jnp.zeros((B, 1 + k), jnp.float32)
    for in_cfg, f in zip(cfg.inputs[: len(feats)], feats):
        # gather sampled rows from the master-dtype table — NCE's whole
        # point is avoiding O(vocab) work, so never cast the full table
        w = ctx.param(in_cfg.input_parameter_name, cast=False)
        acc = acc + jnp.einsum("bd,bkd->bk", f.value, w[samples])
    if cfg.bias_parameter_name:
        b = ctx.param(cfg.bias_parameter_name, cast=False).reshape(-1)
        acc = acc + b[samples]
    log_kp = jnp.log(k * jnp.clip(p_noise[samples], _EPS, None))
    delta = acc - log_kp  # logit of P(data | sample)
    labels01 = jnp.concatenate([jnp.ones((B, 1)), jnp.zeros((B, k))], axis=1)
    per = jnp.logaddexp(0.0, delta) - labels01 * delta
    cost = jnp.sum(per, axis=1)
    if weight is not None and weight.value is not None:
        cost = cost * weight.value.reshape(cost.shape)
    return Argument(value=cost[:, None])


@register_layer("rms_norm")
def rms_norm_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # TPU extension: x / sqrt(mean(x^2) + norm_epsilon) * g over the feature
    # axis, one gain vector `_<name>.w0` [1, D] (kept float32); statistics
    # in float32 whatever the compute dtype
    from paddle_tpu.layers.attention import rms_normalize

    arg = inputs[0]
    gain = ctx.param(cfg.inputs[0].input_parameter_name, cast=False)[0]
    value = rms_normalize(arg.value, gain, cfg.norm_epsilon)
    return with_seq_meta(arg, finalize_output(cfg, value, ctx, mask=input_mask(arg)))
