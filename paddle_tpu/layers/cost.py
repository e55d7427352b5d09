"""Cost layers.

Reference: /root/reference/paddle/gserver/layers/CostLayer.cpp (square
error, multi-class CE, binary/soft CE, self-norm CE, rank cost, huber) and
config_parser's define_cost type strings (config_parser.py:1700-1708).

Each cost layer outputs per-sample cost [B, 1] (sequences: summed over
valid timesteps — the padded-batch equivalent of the reference's ragged
per-row costs), already scaled by ``coeff`` and the optional weight. The
weight is per SAMPLE where it is a [B, 1] value (the reference's
`Argument::weight`: the sample's summed cost is multiplied), and per
POSITION where it is a sequence shaped like the per-step cost ([B, T, 1]
over a [B, T] cost): each step's cost is multiplied before the sum over
time (a diffusion loss weighs each masked position by 1/t and every other
by 0). The gradient machine averages over the batch to form the scalar
loss that jax.grad differentiates.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.graph.argument import Argument
from paddle_tpu.layers.base import LayerContext, TimeMajorLogits, register_layer
from paddle_tpu.proto import LayerConfig

from paddle_tpu.ops.precision import hp as _hp

Array = jax.Array
_EPS = 1e-10
# test hook: force the probability-path cross-entropy (parity tests
# compare the fused-logits formulation against it on the same graph)
_USE_FUSED_CE = True


def _finish_cost(cfg: LayerConfig, per_step: Array, arg: Argument, weight_arg: Optional[Argument]) -> Argument:
    """Reduce per-step cost over time (masked) and apply coeff/weight."""
    w = weight_arg.value if weight_arg is not None else None
    if w is not None and w.size == per_step.size and per_step.ndim > 1:
        # a weight a position: applied before the reduction over time
        per_step = per_step * _hp(w).reshape(per_step.shape)
        w = None
    if arg.is_nested_seq:
        cost = jnp.sum(per_step * arg.sub_seq_mask(), axis=(1, 2))
    elif arg.is_seq:
        cost = jnp.sum(per_step * arg.seq_mask(), axis=1)
    else:
        cost = per_step
    if w is not None:
        cost = cost * w.reshape(cost.shape)
    return Argument(value=(cfg.coeff * cost)[:, None])


def _label_ids(label: Argument) -> Array:
    if label.ids is not None:
        return label.ids
    return jnp.argmax(label.value, axis=-1).astype(jnp.int32)


def _fused_softmax_ce(z: Array, ids: Array) -> Array:
    """-log softmax(z)[ids] from logits, never materializing the
    full-width probabilities in f32: the max is exact in any float dtype,
    exp runs in the logits dtype, and only the reduction accumulates in
    (at least) f32 — XLA fuses the widening convert into the reduce. The
    gradient autodiff derives is softmax(z) - onehot in the logits dtype,
    the standard mixed-precision formulation."""
    m = jax.lax.stop_gradient(jnp.max(z, axis=-1, keepdims=True))
    acc = jnp.promote_types(z.dtype, jnp.float32)
    se = jnp.sum(jnp.exp(z - m), axis=-1, dtype=acc)
    lse = _hp(jnp.squeeze(m, -1)) + jnp.log(se)
    picked = _hp(jnp.take_along_axis(z, ids[..., None], axis=-1)[..., 0])
    return lse - picked


@register_layer("multi-class-cross-entropy")
def multi_class_cross_entropy(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # inputs: [probabilities (post-softmax), label(, weight)]
    out, label = inputs[0], inputs[1]
    weight = inputs[2] if len(inputs) > 2 else None
    ids = _label_ids(label)
    z = (
        ctx.logits.get(cfg.inputs[0].input_layer_name)
        if _USE_FUSED_CE and not cfg.inputs[0].input_layer_argument
        else None
    )
    per_step = _fused_or_plain_ce(z, out, ids)
    return _finish_cost(cfg, per_step, out, weight)


def _fused_or_plain_ce(z, out: Argument, ids: Array) -> Array:
    """Per-step CE: fused from logits when the published view matches,
    else -log(p) from the probabilities. A hoisted recurrent out-link
    publishes TimeMajorLogits (flat [T*B, V]); the CE then runs in that
    native layout and only the [T, B] per-step costs transpose — never
    the V-sized tensor (see layers/base.py TimeMajorLogits)."""
    if isinstance(z, TimeMajorLogits):
        B, T = out.value.shape[0], out.value.shape[1]
        if (
            out.value.ndim == 3
            and (z.T, z.B) == (T, B)
            and z.flat.shape == (T * B, out.value.shape[2])
        ):
            ids_flat = jnp.swapaxes(ids, 0, 1).reshape(-1)      # [T*B], tiny
            per_flat = _fused_softmax_ce(z.flat, ids_flat)
            return jnp.swapaxes(per_flat.reshape(T, B), 0, 1)   # [B, T], tiny
        z = None
    if z is not None and z.shape == out.value.shape:
        return _fused_softmax_ce(z, ids)
    p = jnp.take_along_axis(_hp(out.value), ids[..., None], axis=-1)[..., 0]
    return -jnp.log(jnp.clip(p, _EPS, None))


@register_layer("multi_class_cross_entropy_with_selfnorm")
def selfnorm_cross_entropy(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: CostLayer.cpp MultiClassCrossEntropyWithSelfNorm — CE on
    # unnormalized softmax plus alpha * log(Z)^2 keeping Z near 1.
    out, label = inputs[0], inputs[1]
    ids = _label_ids(label)
    v = _hp(out.value)
    z = jnp.sum(v, axis=-1)
    p = jnp.take_along_axis(v, ids[..., None], axis=-1)[..., 0]
    per_step = -jnp.log(jnp.clip(p / jnp.clip(z, _EPS, None), _EPS, None))
    per_step = per_step + cfg.softmax_selfnorm_alpha * jnp.square(jnp.log(jnp.clip(z, _EPS, None)))
    return _finish_cost(cfg, per_step, out, None)


@register_layer("square_error")
def square_error(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    out, label = inputs[0], inputs[1]
    weight = inputs[2] if len(inputs) > 2 else None
    v = _hp(out.value)
    target = _hp(label.value) if label.value is not None else label.ids.astype(v.dtype)
    if target.ndim < out.value.ndim:
        target = target[..., None]
    per_step = jnp.sum(jnp.square(v - target), axis=-1)
    return _finish_cost(cfg, per_step, out, weight)


@register_layer("multi_binary_label_cross_entropy")
def multi_binary_label_cross_entropy(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    out, label = inputs[0], inputs[1]
    p = jnp.clip(_hp(out.value), _EPS, 1.0 - _EPS)
    y = _hp(label.value)
    per_step = -jnp.sum(y * jnp.log(p) + (1.0 - y) * jnp.log(1.0 - p), axis=-1)
    return _finish_cost(cfg, per_step, out, None)


@register_layer("soft_binary_class_cross_entropy")
def soft_binary_class_cross_entropy(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    return multi_binary_label_cross_entropy(cfg, inputs, ctx)


@register_layer("rank-cost")
def rank_cost(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: RankingCost — inputs: left score, right score, label (1 if left
    # should rank higher, 0.5 for ties), optional weight.
    left, right, label = inputs[0], inputs[1], inputs[2]
    weight = inputs[3] if len(inputs) > 3 else None
    o = (_hp(left.value) - _hp(right.value))[..., 0]
    t = label.value[..., 0] if label.value is not None else label.ids.astype(o.dtype)
    per_step = jnp.logaddexp(0.0, o) - t * o
    return _finish_cost(cfg, per_step, left, weight)


@register_layer("huber")
def huber_two_class(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: HuberTwoClass — labels {0,1} → y in {-1,+1}; quadratic in
    # (-1, 1), linear outside, zero when y*f >= 1.
    out, label = inputs[0], inputs[1]
    f = _hp(out.value)[..., 0]
    y = 2.0 * _label_ids(label).astype(f.dtype) - 1.0
    a = y * f
    per_step = jnp.where(a < -1.0, -4.0 * a, jnp.where(a < 1.0, jnp.square(1.0 - a), 0.0))
    return _finish_cost(cfg, per_step, out, None)


@register_layer("auc-validation", "pnpair-validation")
def validation_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: ValidationLayer family (paddle/gserver/layers/ValidationLayer.h:
    # 52 AucValidation, :84 PnpairValidation; registered cost types in
    # config_parser.py:1703-1704) — metric-only nodes: forward contributes
    # ZERO cost and no gradient; the metric itself accumulates in the
    # evaluator the DSL registers alongside (trainer/evaluators.py
    # AucEvaluator / PnpairEvaluator), reported per log period / pass end.
    out = inputs[0]
    ref = out.value if out.value is not None else out.ids
    per_step = jnp.zeros(ref.shape[:-1] if out.value is not None else ref.shape,
                         jnp.float32)
    return _finish_cost(cfg, per_step, out, None)


@register_layer("classification_error")
def classification_error_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: ClassificationErrorLayer — 1.0 where argmax(output) != label.
    out, label = inputs[0], inputs[1]
    pred = jnp.argmax(out.value, axis=-1)
    err = (pred != _label_ids(label)).astype(jnp.promote_types(out.value.dtype, jnp.float32))
    return _finish_cost(cfg, err, out, inputs[2] if len(inputs) > 2 else None)
