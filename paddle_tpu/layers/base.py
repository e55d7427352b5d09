"""Layer forward framework.

TPU-native replacement for the reference's ``Layer`` base
(/root/reference/paddle/gserver/layers/Layer.h:58): instead of stateful
objects with hand-written forward/backward over Matrix, a layer is a pure
function ``(LayerConfig, [Argument], LayerContext) -> Argument``. Backward
comes from jax.grad of the whole graph; bias/activation/dropout
post-processing is shared here (mirroring Layer::forwardActivation /
backwardActivation semantics, including dropout after activation).
"""

from __future__ import annotations

import functools
import zlib
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.graph.argument import Argument
from paddle_tpu.ops.activations import apply_activation
from paddle_tpu.proto import LayerConfig, ModelConfig
from paddle_tpu.utils.error import layer_scope
from paddle_tpu.utils.registry import Registry

Array = jax.Array

LayerFn = Callable[[LayerConfig, List[Argument], "LayerContext"], Argument]
layer_registry: Registry[LayerFn] = Registry("layer type")


class TimeMajorLogits(NamedTuple):
    """Pre-softmax logits of a HOISTED recurrent out-link, kept in the
    vocab projection's native flat [T*B, V] form instead of the
    transposed [B, T, V] view. The fused-CE consumer reduces over V
    directly on this layout and transposes only the tiny [T, B]
    per-step costs — transposing the V-sized tensor itself forces a
    full-tensor relayout copy inside the train step (between the
    projection's {0,1} layout and the [B,T,V] consumers)."""

    flat: jax.Array   # [T*B, V]
    T: int
    B: int


def register_layer(*type_names: str):
    return layer_registry.register(*type_names)


@dataclass
class LayerContext:
    """Mutable context threaded through one network forward pass.

    Carries everything the reference's Layer pulled from its members:
    parameter store, pass type, rng, sibling outputs, and (for batch-norm
    style layers) read/write running state.
    """

    params: Dict[str, Array]
    model: ModelConfig
    pass_type: str = "train"                    # train | test | gen
    rng: Optional[Array] = None
    states: Dict[str, Any] = field(default_factory=dict)
    state_updates: Dict[str, Any] = field(default_factory=dict)
    outputs: Dict[str, Argument] = field(default_factory=dict)
    dtype: Any = jnp.float32
    # mixed precision (OptimizationConfig.dtype="bfloat16"): master params
    # and optimizer state stay `dtype` (f32); activations and matmul
    # operands are cast to `compute_dtype` so the MXU runs bf16. Softmax,
    # losses, batch-norm statistics and CRF/CTC recursions stay f32
    # (upcast at their entry points). jax.grad of the cast yields f32
    # parameter gradients automatically (convert_element_type transpose).
    compute_dtype: Any = None
    # data layers feeding ONLY cost layers (regression targets, soft
    # labels, per-sample weights) — their dense values must NOT be
    # narrowed, or the f32 loss island would see pre-rounded targets
    # (GradientMachine computes this set from the graph)
    no_cast_inputs: frozenset = frozenset()
    # device mesh for layers that issue explicit collectives (ring
    # attention); None outside meshed execution
    mesh: Any = None
    # lax.scan unroll factor for recurrent layers/groups
    # (OptimizationConfig.scan_unroll; 1 = no unrolling)
    scan_unroll: int = 1
    # OptimizationConfig.pallas_rnn: lstmemory/gated_recurrent layers use
    # the fused Pallas sequence kernels when shapes/activations allow
    pallas_rnn: bool = False
    # OptimizationConfig.pallas_flat: the kernels take the transpose-free
    # batch-major interface (PADDLE_TPU_PALLAS_FLAT=1 still forces it)
    pallas_flat: bool = False
    # OptimizationConfig.conv_s2d: few-channel 7x7/s2 stem convs rewrite
    # to a space-to-depth 4x4/s1 conv (layers/vision.py _stem_s2d_conv)
    conv_s2d: bool = False
    # OptimizationConfig.conv_stats_mode: 1x1/s1 convs publish their
    # output's per-channel (sum, sumsq, rows) into `conv_stats` — via
    # input-side Gram algebra ("gram", pure XLA) or the fused Pallas
    # matmul kernel ("pallas", ops/pallas_conv1x1_bn); "" = off
    conv_stats_mode: str = ""
    # OptimizationConfig.pallas_decoder: attention-GRU decoder groups
    # run as one fused Pallas launch (graph/fused_decoder.py)
    pallas_decoder: bool = False
    # OptimizationConfig.remat="block": runs of layers that share a
    # LayerConfig.remat_block go under one jax.checkpoint (graph/network.py)
    remat_blocks: bool = False
    # recurrent-group prologue hoisting (graph/recurrent_group.py
    # _plan_prologue): mixed layer name -> (skip_input_indices,
    # precomputed [B, out] slice) for scan-input projections computed
    # outside the scan; set only on per-step contexts
    mixed_prologue: Optional[Dict[str, Any]] = None
    # NHWC layout side-table (layer name -> [B, H, W, C] array): the conv
    # family publishes its pre-flatten output here and prefers consuming
    # it, so chains of conv/pool/bn/norm skip the per-layer
    # flat->NCHW->NHWC round-trip (XLA does not reliably cancel it; the
    # flat Argument.value stays authoritative and is DCE'd when every
    # consumer took the NHWC view). Recurrent groups build their own
    # context, so entries never cross a scan boundary.
    nhwc: Dict[str, Array] = field(default_factory=dict)
    # pre-softmax logits side-table (layer name -> pre-activation array,
    # OR a TimeMajorLogits wrapper for hoisted recurrent out-links —
    # check isinstance before assuming an array): finalize_output
    # publishes here when the activation is a plain feature-axis softmax,
    # so a downstream multi-class cross-entropy can compute fused
    # log-softmax CE from the logits instead of re-upcasting the
    # materialized probabilities ([B*T, V] f32 traffic at NMT vocab
    # sizes). The softmax output stays authoritative for every other
    # consumer and is DCE'd when only the loss reads it.
    logits: Dict[str, Any] = field(default_factory=dict)
    # fused conv+BN statistics side-table (producer layer name ->
    # (sum [C] f32, sumsq [C] f32, rows)): a 1x1 conv that ran the
    # pallas_conv_stats kernel publishes its output's per-channel
    # statistics here; a downstream batch_norm consuming that layer in
    # training mode uses them instead of re-reading the activation from
    # HBM. The conv output Argument stays authoritative for every other
    # consumer; both come from one custom_vjp call, so gradients through
    # output and statistics compose in its backward.
    conv_stats: Dict[str, Any] = field(default_factory=dict)
    # sparse-embedding prefetch (GradientMachine::prefetch analog): rows
    # pre-gathered outside autodiff, keyed by (param_name, input_layer);
    # the table projection returns these instead of gathering, so
    # jax.grad yields row gradients, never a dense [V, D] scatter
    table_overrides: Optional[Dict[Any, Array]] = None
    # enclosing scope for recurrent-group steps: group-ENTRY resolution
    # (static links, memory boot layers, nested-group in-links) may walk
    # up this chain; ordinary layer-input lookup deliberately cannot, so
    # referencing an outer sequence without StaticInput stays an error
    parent: Optional["LayerContext"] = None
    # generation-capture sink (graph/decode_step.py): when a dict is
    # supplied, a generator recurrent group stores its prepared decode
    # inputs (static-link Arguments, unexpanded memory boots) here and
    # SKIPS the beam-search loop — the serving engine's prefill path,
    # which scatters the captured state into slot buffers and then
    # drives per-step decode launches itself
    gen_capture: Optional[Dict[str, Any]] = None

    @property
    def is_training(self) -> bool:
        return self.pass_type == "train"

    def param(self, name: str, cast: bool = True) -> Array:
        try:
            v = self.params[name]
        except KeyError:
            known = ", ".join(sorted(self.params))
            raise KeyError(f"parameter {name!r} not found (have: {known})") from None
        if cast and self.compute_dtype is not None and jnp.issubdtype(v.dtype, jnp.floating):
            v = v.astype(self.compute_dtype)
        return v

    def cast_compute(self, x: Optional[Array]) -> Optional[Array]:
        """Cast a float activation to the compute dtype (no-op otherwise)."""
        if (
            x is not None
            and self.compute_dtype is not None
            and jnp.issubdtype(x.dtype, jnp.floating)
            and x.dtype != self.compute_dtype
        ):
            return x.astype(self.compute_dtype)
        return x

    def layer_rng(self, layer_name: str, salt: str = "") -> Array:
        assert self.rng is not None, "LayerContext.rng not set but layer needs randomness"
        return jax.random.fold_in(self.rng, zlib.crc32(f"{layer_name}/{salt}".encode()))


def input_mask(arg: Argument) -> Optional[Array]:
    """[B, T] float validity mask if arg is a sequence, else None."""
    if arg.is_nested_seq:
        return arg.sub_seq_mask()
    if arg.is_seq:
        return arg.seq_mask()
    return None


def finalize_output(
    cfg: LayerConfig,
    value: Array,
    ctx: LayerContext,
    mask: Optional[Array] = None,
) -> Array:
    """Shared bias + activation + dropout tail of a layer forward."""
    if cfg.bias_parameter_name:
        value = value + ctx.param(cfg.bias_parameter_name)
    # dropout after softmax would make the probabilities the only honest
    # source, so the logits view is published only for dropout-free layers
    if cfg.active_type == "softmax" and not cfg.drop_rate:
        ctx.logits[cfg.name] = value
    value = apply_activation(cfg.active_type, value, mask)
    if cfg.drop_rate > 0.0 and ctx.is_training:
        keep = 1.0 - cfg.drop_rate
        rng = ctx.layer_rng(cfg.name, "dropout")
        m = jax.random.bernoulli(rng, keep, value.shape)
        # inverted dropout (scale at train time) — reference scales at train
        # time too (Layer.cpp forwardDropOut divides by (1 - drop_rate)).
        value = jnp.where(m, value / keep, 0.0)
    return value


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _clip_error(x, t):
    """Identity forward; backward clips the cotangent to [-t, t] — the
    reference's per-layer error clipping (Layer.cpp backwardActivation
    errorClip on the output gradient, configured by
    ExtraAttr(error_clipping_threshold))."""
    return x


def _clip_error_fwd(x, t):
    return x, None


def _clip_error_bwd(t, _, g):
    return (jnp.clip(g, -t, t),)


_clip_error.defvjp(_clip_error_fwd, _clip_error_bwd)


def forward_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    fn = layer_registry.get(cfg.type)
    # the named scope is HLO metadata only (`op_name`): device time in a
    # profile splits by `<type>:<name>`, for a layer of the root network, of
    # a recurrent group's step and of its hoisted epilogue alike
    with layer_scope(f"{cfg.name}({cfg.type})"), \
            jax.named_scope(f"{cfg.type}:{cfg.name}"):
        out = fn(cfg, inputs, ctx)
    if cfg.error_clipping_threshold > 0 and out.value is not None:
        out = out.replace(
            value=_clip_error(out.value, float(cfg.error_clipping_threshold))
        )
        # a published NHWC or logits view would bypass the clip wrapper —
        # drop them so every consumer goes through the clipped value
        ctx.nhwc.pop(cfg.name, None)
        ctx.logits.pop(cfg.name, None)
        ctx.conv_stats.pop(cfg.name, None)
    ctx.outputs[cfg.name] = out
    return out


# ------------------------------------------------------- layer counters
# A layer that has a number worth counting a step (pairs computed, rows
# dropped) publishes it with `publish_counter`: an extra output of the layer,
# so that it crosses a recomputation block like any other. The train step
# folds every layer's into one small dict (`step_counters`, in the trace),
# the host reads it back with the loss and `note_counters` puts it into the
# registry that `pass_end` snapshots. The trainer names no layer type.

COUNTER_EXTRA = "counter"
# the key a train step's evaluator states carry `step_counters` under ("@"
# separates a layer's name from its extra's: no evaluator's name holds it)
LAYER_COUNTERS = "@layer_counters"


def publish_counter(cfg: LayerConfig, ctx: "LayerContext", name: str, value,
                    how: str = "sum") -> None:
    """This layer's part of the counter `name` for this step. `how`: "sum"
    adds the layers' parts and counts up over the steps (a registry
    counter); "max" takes the largest layer's and holds the last step's (a
    gauge)."""
    if how not in ("sum", "max"):
        raise ValueError(f"{cfg.name}: counter {name!r}: how={how!r}")
    ctx.outputs[f"{cfg.name}@{COUNTER_EXTRA}.{how}:{name}"] = Argument(
        value=jnp.asarray(value, jnp.float32))


def step_counters(outputs: Dict[str, Argument]) -> Dict[str, Dict[str, Array]]:
    """{"sum" | "max": {counter name: f32[]}} over every layer that
    published one in this step; {} where none did."""
    parts: Dict[tuple, list] = {}
    for key, arg in outputs.items():
        extra = key.partition("@")[2]
        if extra.startswith(COUNTER_EXTRA + "."):
            how, _, name = extra[len(COUNTER_EXTRA) + 1:].partition(":")
            parts.setdefault((how, name), []).append(arg.value)
    out: Dict[str, Dict[str, Array]] = {}
    for (how, name), values in parts.items():
        fold = jnp.sum if how == "sum" else jnp.max
        out.setdefault(how, {})[name] = fold(jnp.stack(values))
    return out


def note_counters(values) -> None:
    """The host's half: one step's `step_counters`, read back."""
    if not values:
        return
    from paddle_tpu.observability import metrics as obs

    for name, v in values.get("sum", {}).items():
        obs.registry().counter(name).inc(float(v))
    for name, v in values.get("max", {}).items():
        obs.registry().gauge(name).set(float(v))


def first_seq_meta(inputs: List[Argument]) -> Argument:
    """Propagate sequence metadata from the first sequence input."""
    for a in inputs:
        if a.is_seq or a.is_nested_seq:
            return a
    return inputs[0] if inputs else Argument()


def with_seq_meta(template: Argument, value: Array) -> Argument:
    return Argument(
        value=value,
        seq_lengths=template.seq_lengths,
        sub_seq_lengths=template.sub_seq_lengths,
    )
