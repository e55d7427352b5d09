"""Vision layers: conv, pool, batch-norm, response norm, block expand.

Reference counterparts: ExpandConvLayer.cpp (im2col conv), CudnnConvLayer,
PoolLayer/CudnnPoolLayer, BatchNormalizationLayer/CudnnBatchNormLayer,
NormProjectionLayer (cross-map LRN), BlockExpandLayer, ResizeLayer,
FeatureMapExpandLayer in /root/reference/paddle/gserver/layers/.

Data contract matches the reference: images flow between layers as
flattened NCHW rows [B, C*H*W]. Internally we reshape to NHWC and use
``lax.conv_general_dilated`` / ``lax.reduce_window`` so XLA tiles the MXU
directly — no im2col materialization.

Weight layout (set by our config_parser): conv filters are stored flat as
[num_filters, filter_channels * fh * fw], reshaped here to HWIO.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddle_tpu.graph.argument import Argument
from paddle_tpu.layers.base import LayerContext, register_layer
from paddle_tpu.ops.activations import apply_activation
from paddle_tpu.ops.precision import hp
from paddle_tpu.proto import ConvConfig, LayerConfig, OperatorConfig

Array = jax.Array


def conv_output_size(img: int, filter_size: int, padding: int, stride: int, caffe_mode: bool) -> int:
    # ref: paddle/math/MathUtils.cpp outputSize
    if caffe_mode:
        return (img - filter_size + 2 * padding) // stride + 1
    return (img - filter_size + 2 * padding + stride - 1) // stride + 1


def _nchw_to_nhwc(x: Array, channels: int, h: int, w: int) -> Array:
    return x.reshape(x.shape[0], channels, h, w).transpose(0, 2, 3, 1)


def _nhwc_to_flat(x: Array) -> Array:
    return x.transpose(0, 3, 1, 2).reshape(x.shape[0], -1)


from paddle_tpu.ops.activations import is_elementwise


def _take_nhwc(ctx: LayerContext, input_layer_name: str, arg, channels: int,
               h: int, w: int) -> Array:
    """The producer's published NHWC view when shapes agree, else convert
    from the flat NCHW value (see LayerContext.nhwc)."""
    x = ctx.nhwc.get(input_layer_name)
    if x is not None and x.shape[1:] == (h, w, channels):
        return x
    return _nchw_to_nhwc(arg.value, channels, h, w)


def _dropout(ctx: LayerContext, cfg: LayerConfig, x: Array) -> Array:
    if cfg.drop_rate > 0.0 and ctx.is_training:
        keep = 1.0 - cfg.drop_rate
        m = jax.random.bernoulli(ctx.layer_rng(cfg.name, "dropout"), keep, x.shape)
        x = jnp.where(m, x / keep, 0.0)
    return x


def _publish_nhwc(ctx: LayerContext, cfg: LayerConfig, y_nhwc: Array) -> Argument:
    """Publish the NHWC view for downstream conv-family layers and return
    the flat Argument (DCE'd by XLA if every consumer took the view)."""
    ctx.nhwc[cfg.name] = y_nhwc
    return Argument(value=_nhwc_to_flat(y_nhwc))


def _conv2d(x_nhwc: Array, w_hwio: Array, stride: Tuple[int, int], padding, groups: int) -> Array:
    # bf16 in/out is safe on TPU: the MXU accumulates partial products in
    # f32 internally regardless of the result dtype, so no explicit
    # preferred_element_type (which this JAX's conv transpose rejects for
    # mixed bf16-operand/f32-cotangent pairs).
    return lax.conv_general_dilated(
        x_nhwc,
        w_hwio,
        window_strides=stride,
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
    )


def _stem_s2d_conv(x: Array, w_hwio: Array) -> Array:
    """Space-to-depth rewrite of the 7x7/stride-2/pad-3 few-channel stem
    conv (the ResNet conv1): C=3 wastes the MXU's 128-deep contraction,
    so re-express the conv EXACTLY as a 4x4/stride-1 VALID conv over a
    2x2 space-to-depth view with 4C input channels (the MLPerf trick).

    Derivation: with x padded (4, 2) per spatial dim and the kernel
    zero-padded 7→8 at the FRONT, y[i,j] = Σ_{u',v'<8} w'[u',v']
    xp[2i+u', 2j+v']; substituting u' = 2α+a turns the sum into a 4x4
    conv over X[i,j,(a,b,c)] = xp[2i+a, 2j+b, c]. Summation order aside,
    this is the same arithmetic (parity pinned in tests/test_s2d.py)."""
    B, H, W, C = x.shape
    O = w_hwio.shape[-1]
    xp = jnp.pad(x, ((0, 0), (4, 2), (4, 2), (0, 0)))
    Hp, Wp = H + 6, W + 6
    X = (
        xp.reshape(B, Hp // 2, 2, Wp // 2, 2, C)
        .transpose(0, 1, 3, 2, 4, 5)
        .reshape(B, Hp // 2, Wp // 2, 4 * C)
    )
    w8 = jnp.pad(w_hwio, ((1, 0), (1, 0), (0, 0), (0, 0)))  # 7→8, zero row/col FIRST
    w4 = (
        w8.reshape(4, 2, 4, 2, C, O)
        .transpose(0, 2, 1, 3, 4, 5)
        .reshape(4, 4, 4 * C, O)
    )
    return _conv2d(X, w4, (1, 1), "VALID", 1)


def _stem_s2d_applies(ctx, cc, fy, sy, py, h, w) -> bool:
    return (
        ctx.conv_s2d
        and cc.channels <= 4
        and fy == cc.filter_size == 7
        and sy == cc.stride == 2
        and py == cc.padding == 3
        and cc.groups == 1
        and h % 2 == 0
        and w % 2 == 0
    )


def _fused_stats_gates(cfg: LayerConfig, ctx: LayerContext,
                       allow_stride: bool = False):
    """Shared eligibility gate for BOTH fused conv+BN-statistics modes:
    single-input 1x1/p0 ungrouped conv whose output is exactly what a
    downstream batch_norm would reduce — identity activation, no
    dropout, shared (or no) bias — in a training pass. Returns the conv
    input config, or None. ``allow_stride`` admits strided 1x1 convs
    (resnet downsample projections): a stride-s 1x1/p0 conv is a matmul
    over the ::s-sliced input, so input-side statistics stay exact —
    but only under caffe-mode output sizing, where the conv output rows
    are exactly the ceil(img/s) slice positions."""
    if not ctx.is_training or len(cfg.inputs) != 1:
        return None
    in_cfg = cfg.inputs[0]
    cc = in_cfg.conv_conf
    fy = cc.filter_size_y or cc.filter_size
    sy = cc.stride_y or cc.stride
    py = cc.padding_y if cc.padding_y >= 0 else cc.padding
    stride_ok = (
        sy == 1 and cc.stride == 1
        or (allow_stride and cc.caffe_mode and sy >= 1 and cc.stride >= 1)
    )
    if not (fy == 1 and cc.filter_size == 1 and stride_ok
            and py == 0 and cc.padding == 0 and cc.groups == 1):
        return None
    if cfg.active_type not in ("", "linear") or cfg.drop_rate > 0.0:
        return None
    if cfg.bias_parameter_name and not cfg.shared_biases:
        return None
    return in_cfg


def _conv1x1_stats_forward(cfg: LayerConfig, inputs: List[Argument],
                           ctx: LayerContext):
    """1x1/s1 conv through the fused matmul + BN-statistics Pallas kernel
    (ops/pallas_conv1x1_bn): publishes per-channel (sum, sumsq, rows) into
    ctx.conv_stats so a downstream batch_norm skips its statistics pass's
    full HBM re-read of this output. Returns None whenever any gate fails
    — the caller falls through to the XLA conv, identical semantics.

    The kernel's row-major [M, K] interface costs layout copies at its
    boundary (XLA lays conv outputs batch-near-minor), which the "gram"
    mode avoids; this is the conv_stats_mode="pallas" knob, off by
    default and not measured on the chip. Gates beyond the shared ones
    mirror the fused-RNN path (layers/recurrent.py): single-device only
    (no GSPMD partitioning rule for the custom call), TPU backend or
    forced interpret mode, and kernel shape/VMEM support.
    """
    from paddle_tpu.utils import device

    def xla(why):
        device.log_selection("conv_stats_pallas", cfg.name, f"XLA conv ({why})")

    if ctx.mesh is not None:
        return xla("under a mesh")
    in_cfg = _fused_stats_gates(cfg, ctx)
    if in_cfg is None:
        return xla("not a 1x1 conv feeding a batch_norm in training")
    cc = in_cfg.conv_conf
    mode = device.pallas_mode()
    if mode is None:
        return xla(device.why_no_pallas())
    from paddle_tpu.ops import pallas_conv1x1_bn as pcb

    h = w = cc.img_size
    x = _take_nhwc(ctx, in_cfg.input_layer_name, inputs[0], cc.channels, h, w)
    B = x.shape[0]
    M, K, N = B * h * w, cc.channels, cfg.num_filters
    if not pcb.supported(M, K, N, x.dtype.itemsize):
        return xla(f"kernel gate refuses M={M} K={K} N={N} {x.dtype}")
    device.log_selection("conv_stats_pallas", cfg.name, f"Pallas kernel, {mode}")
    wf = ctx.param(in_cfg.input_parameter_name).reshape(N, K)
    if cfg.bias_parameter_name:
        b = ctx.param(cfg.bias_parameter_name).reshape(N).astype(x.dtype)
    else:
        b = jnp.zeros((N,), x.dtype)
    y2, s, q = pcb.conv1x1_stats(x.reshape(M, K), wf.T, b,
                                 mode == "interpret")
    ctx.conv_stats[cfg.name] = (s, q, M)
    return _publish_nhwc(ctx, cfg, y2.reshape(B, h, w, N))


def _gram_stats_gates(cfg: LayerConfig, ctx: LayerContext):
    """Gate for input-side Gram statistics: the shared fused-stats gate
    plus a stride-dependent width ratio (N >= 2K at stride 1, N >= 4K
    strided — derivation at the check below). Unlike the pallas path
    this is pure XLA (any backend, works under a mesh — the reduces
    shard like BN's own), and only worthwhile when the output is
    sufficiently wider than the input (resnet expand convs are N = 4K;
    its stride-2 downsample projections are N = 2K and stay on the
    direct path)."""
    in_cfg = _fused_stats_gates(cfg, ctx, allow_stride=True)
    if in_cfg is None:
        return None
    cc = in_cfg.conv_conf
    strided = cc.stride_y > 1 or cc.stride > 1
    # break-even math: the stats-side reads x (or its ::s slice) TWICE
    # vs the saved single read of y. Stride 1: 2*M*K vs M*N -> N >= 2K.
    # Stride s: the slice has the SAME row count as y, so 2*M_out*K vs
    # M_out*N breaks even at N = 2K exactly (resnet downsample
    # projections are all N = 2K, plus strided reads waste cache lines)
    # -> require N >= 4K so strided convs only engage at a clear win.
    need = 4 if strided else 2
    if cfg.num_filters < need * cc.channels:
        return None
    return in_cfg


def _publish_gram_stats(cfg: LayerConfig, ctx: LayerContext, x_nhwc: Array,
                        w2: Array, bias) -> None:
    """Per-channel sum/sumsq of y = x@w + b computed from the INPUT side:

        sum_m(y)   = colsum(x) @ w + M*b
        sum_m(y^2) = diag(w^T (x^T x) w) + 2*b*(colsum(x) @ w) + M*b^2

    exact algebra (associativity aside), so the BN stats pass never has
    to re-read y from HBM — it reads x twice (colsum + Gram) instead,
    a win at the _gram_stats_gates width ratios and FREE when no
    batch_norm consumes the entry
    (XLA dead-code-eliminates the unused reduces). All plain jnp ops:
    autodiff composes the stats' gradient with the conv's naturally, and
    XLA keeps its own conv layouts, which the pallas variant's
    row-major interface cannot.

    Semantics note: these are statistics of the UNROUNDED x@w (the
    activation-dtype path reduces the bf16-rounded y) — a ~1e-3-relative
    difference on the mean, inside BN's own eps regime; the parity test
    pins it (tests/test_conv_stats.py).
    """
    f32 = jnp.float32
    M = x_nhwc.shape[0] * x_nhwc.shape[1] * x_nhwc.shape[2]
    cs = jnp.sum(x_nhwc, axis=(0, 1, 2), dtype=f32)          # [K]
    gram = jnp.einsum("bhwk,bhwl->kl", x_nhwc, x_nhwc,
                      preferred_element_type=f32)            # [K, K]
    w32 = w2.astype(f32)
    csw = cs @ w32                                           # [N]
    s = csw
    q = jnp.einsum("kn,kl,ln->n", w32, gram, w32)
    if bias is not None:
        b32 = bias.astype(f32)
        s = s + M * b32
        q = q + 2.0 * b32 * csw + M * jnp.square(b32)
    ctx.conv_stats[cfg.name] = (s, q, M)


def _conv_forward(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    if ctx.conv_stats_mode == "pallas":
        out = _conv1x1_stats_forward(cfg, inputs, ctx)
        if out is not None:
            return out
    gram_in = (
        _gram_stats_gates(cfg, ctx) if ctx.conv_stats_mode == "gram" else None
    )
    acc = None
    for in_cfg, arg in zip(cfg.inputs, inputs):
        cc = in_cfg.conv_conf
        h = w = cc.img_size
        fy = cc.filter_size_y or cc.filter_size
        sy = cc.stride_y or cc.stride
        py = cc.padding_y if cc.padding_y >= 0 else cc.padding
        x = _take_nhwc(ctx, in_cfg.input_layer_name, arg, cc.channels, h, w)
        wf = ctx.param(in_cfg.input_parameter_name)
        wf = wf.reshape(cfg.num_filters, cc.filter_channels, fy, cc.filter_size)
        w_hwio = wf.transpose(2, 3, 1, 0)  # OIHW → HWIO
        if gram_in is not None:
            # a strided 1x1/p0 conv only ever reads the ::s positions —
            # statistics of the sliced view are exact (the slice fuses
            # into the stats reduces; nothing materializes)
            x_stats = x[:, ::sy, ::cc.stride, :] if (sy > 1 or cc.stride > 1) else x
            gram_operands = (x_stats, w_hwio.reshape(cc.channels, cfg.num_filters))
        if _stem_s2d_applies(ctx, cc, fy, sy, py, h, w):
            y = _stem_s2d_conv(x, w_hwio)
        else:
            y = _conv2d(x, w_hwio, (sy, cc.stride), [(py, py), (cc.padding, cc.padding)], cc.groups)
        acc = y if acc is None else acc + y
    if gram_in is not None:
        bias = (
            ctx.param(cfg.bias_parameter_name)
            if cfg.bias_parameter_name
            else None
        )
        _publish_gram_stats(cfg, ctx, *gram_operands,
                            bias.reshape(cfg.num_filters) if bias is not None else None)
    if cfg.bias_parameter_name:
        b = ctx.param(cfg.bias_parameter_name)
        if cfg.shared_biases:
            acc = acc + b.reshape(1, 1, 1, cfg.num_filters)
        else:
            # flat layout is filter-major [F, H, W] (reference
            # addUnsharedBias over NCHW rows) — transpose into NHWC
            b_hwf = b.reshape(cfg.num_filters, acc.shape[1], acc.shape[2]).transpose(1, 2, 0)
            acc = acc + b_hwf[None]
    if not is_elementwise(cfg.active_type):
        out = apply_activation(cfg.active_type, _nhwc_to_flat(acc))
        out = _dropout(ctx, cfg, out)
        return Argument(value=out)
    acc = _dropout(ctx, cfg, apply_activation(cfg.active_type, acc))
    return _publish_nhwc(ctx, cfg, acc)


register_layer("conv", "exconv", "cudnn_conv")(_conv_forward)


def conv_operator_forward(op: OperatorConfig, inputs: List[Argument]) -> Array:
    """ConvOperator in a mixed layer: conv(image_input, filter_input).

    ref: ConvOperator.cpp — the second input *is* the filter values
    (dynamic filters), used e.g. for spatial attention.
    """
    cc = op.conv_conf
    x = _nchw_to_nhwc(inputs[0].value, cc.channels, cc.img_size, cc.img_size)
    B = x.shape[0]
    wf = inputs[1].value.reshape(B, op.num_filters, cc.filter_channels, cc.filter_size, cc.filter_size)

    def one(xi, wi):
        return _conv2d(
            xi[None],
            wi.transpose(2, 3, 1, 0),
            (cc.stride, cc.stride),
            [(cc.padding, cc.padding), (cc.padding, cc.padding)],
            cc.groups,
        )[0]

    y = jax.vmap(one)(x, wf)
    return _nhwc_to_flat(y)


@register_layer("pool")
def pool_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    pc = cfg.inputs[0].pool_conf
    h = pc.img_size_y or pc.img_size
    w = pc.img_size
    ky = pc.size_y or pc.size_x
    sy = pc.stride_y or pc.stride
    py = pc.padding_y or pc.padding
    x = _take_nhwc(ctx, cfg.inputs[0].input_layer_name, inputs[0], pc.channels, h, w)
    window = (1, ky, pc.size_x, 1)
    strides = (1, sy, pc.stride, 1)
    # the config declares ceil-mode output sizes (reference outputSize with
    # caffeMode=false); extend the high-edge padding so the last window fits
    oy = pc.output_y or pc.output_x
    ox = pc.output_x
    hi_y = max(0, (oy - 1) * sy + ky - h - py)
    hi_x = max(0, (ox - 1) * pc.stride + pc.size_x - w - pc.padding)
    pads = ((0, 0), (py, hi_y), (pc.padding, hi_x), (0, 0))
    kind = pc.pool_type
    # in-image element count per window, computed in numpy at trace time
    # (all static): a full-shape reduce_window over ones compiles to an
    # O(B*C*H*W*window) constant-fold inside XLA — minutes at B=256 — for
    # what is really an [out_y] x [out_x] outer product. A ceil-mode
    # window can land entirely in padding — guard those outputs to 0.
    def _axis_counts(n_out, stride, pad, k, img):
        starts = np.arange(n_out) * stride - pad
        return np.clip(np.minimum(starts + k, img) - np.maximum(starts, 0), 0, None)

    counts = jnp.asarray(
        np.outer(_axis_counts(oy, sy, py, ky, h),
                 _axis_counts(ox, pc.stride, pc.padding, pc.size_x, w))
        [None, :, :, None],
        dtype=x.dtype,
    )
    if "max" in kind:
        y = lax.reduce_window(x, -jnp.inf, lax.max, window, strides, pads)
        y = jnp.where(counts > 0, y, 0.0)
    else:
        # avg pooling divides each window by its *in-image* area (reference
        # avgPoolForward clips hstart/hend to the image before dividing)
        y = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
        y = y / jnp.maximum(counts, 1.0)
    if not is_elementwise(cfg.active_type):
        return Argument(value=apply_activation(cfg.active_type, _nhwc_to_flat(y)))
    return _publish_nhwc(ctx, cfg, apply_activation(cfg.active_type, y))


@register_layer("batch_norm", "cudnn_batch_norm")
def batch_norm_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    """ref: BatchNormalizationLayer.cpp.

    inputs[0] carries the data plus an ImageConfig; per-channel gamma is the
    input parameter, beta the bias parameter; moving mean/var live in params
    as the 2nd/3rd input parameters (is_static) and are updated through
    ``ctx.state_updates`` with moving_average_fraction.
    """
    ic = cfg.inputs[0].image_conf
    a = inputs[0]
    x = a.value
    seq_meta = {}
    if a.is_seq:
        seq_meta = dict(seq_lengths=a.seq_lengths)
        B, T, D = x.shape
        x = x.reshape(B * T, D)
    x_nhwc = None
    if ic is not None and ic.img_size > 0:
        C, hw = ic.channels, ic.img_size * ic.img_size
        if not a.is_seq:
            # NHWC flattens to per-pixel rows of C directly — same row
            # set as the NCHW transpose dance, so identical statistics
            x_nhwc = _take_nhwc(ctx, cfg.inputs[0].input_layer_name, a,
                                C, ic.img_size, ic.img_size)
            xr = x_nhwc.reshape(-1, C)
        else:
            xr = x.reshape(x.shape[0], C, hw).transpose(0, 2, 1).reshape(-1, C)
    else:
        C = cfg.size
        xr = x
    # STATISTICS run in f32 even when activations are bf16 (bf16 mean/var
    # over big batches is too lossy), but the full-size activation is never
    # upcast: the reductions accumulate in f32 directly over the bf16 rows
    # (XLA fuses the widening convert into the reduce) and the NORMALIZATION
    # applies as a per-channel scale/offset in the activation dtype. The
    # previous hp(xr)-then-normalize-in-f32 formulation materialized f32
    # copies/reshapes of every BN input.
    # gamma/beta/running stats are master-dtype params (cast=False).
    gamma = ctx.param(cfg.inputs[0].input_parameter_name, cast=False).reshape(C)
    beta = (
        ctx.param(cfg.bias_parameter_name, cast=False).reshape(C)
        if cfg.bias_parameter_name
        else None
    )
    mean_name = cfg.inputs[1].input_parameter_name
    var_name = cfg.inputs[2].input_parameter_name
    eps = 1e-5
    use_global = cfg.use_global_stats or not ctx.is_training
    if use_global:
        mean = ctx.params[mean_name].reshape(C)
        var = ctx.params[var_name].reshape(C)
        centered = (hp(xr) - mean).astype(xr.dtype)
    else:
        # at-least-f32 accumulation (f64 under the x64 gradient check)
        acc_dt = jnp.promote_types(xr.dtype, jnp.float32)
        # fused statistics (conv_stats_mode): a 1x1 conv feeding this BN
        # already published sum/sumsq — from its matmul epilogue
        # ("pallas", ops/pallas_conv1x1_bn) or from input-side Gram
        # algebra ("gram", _publish_gram_stats) — and consuming them
        # skips this pass's full HBM re-read of the activation. Gated on
        # exact row-count match and f32 accumulation (the x64 gradient
        # check wants f64 stats, which the producers do not make).
        pub = (
            ctx.conv_stats.get(cfg.inputs[0].input_layer_name)
            if (x_nhwc is not None and not a.is_seq and acc_dt == jnp.float32)
            else None
        )
        if pub is not None and pub[2] == xr.shape[0] and pub[0].shape == (C,):
            s_pub, q_pub, rows = pub
            mean = s_pub / rows
            msq = q_pub / rows
        else:
            # one-pass statistics: mean and E[x^2] are independent
            # reductions over the same input, so XLA fuses them into a
            # single traversal (a two-pass centered variance would read
            # the activation twice — the var reduce depends on the mean).
            # The squares are exact (bf16->f32 widening then f32 multiply
            # inside the fusion); the E[x^2]-mean^2 cancellation at f32
            # only bites for channels with |mean|/std >~ 1e3, far beyond
            # post-conv activations.
            mean = jnp.mean(xr, axis=0, dtype=acc_dt)
            msq = jnp.mean(jnp.square(hp(xr)), axis=0, dtype=acc_dt)
        var = jnp.maximum(msq - jnp.square(mean), 0.0)
        # center against the EXACT f32 mean (a bf16-rounded mean would
        # bias every centered value); the convert-sub-convert chain
        # fuses, so no f32 tensor reaches HBM
        centered = (hp(xr) - mean).astype(xr.dtype)
        f = cfg.moving_average_fraction
        ctx.state_updates[mean_name] = (
            f * ctx.params[mean_name].reshape(C) + (1.0 - f) * mean
        ).reshape(ctx.params[mean_name].shape)
        ctx.state_updates[var_name] = (
            f * ctx.params[var_name].reshape(C) + (1.0 - f) * var
        ).reshape(ctx.params[var_name].shape)
    scale = hp(gamma) * lax.rsqrt(hp(var) + eps)  # f32 [C]
    # center-then-scale in the activation dtype (both branches): folding
    # the mean into a bf16 offset would cancel catastrophically for
    # channels whose mean is large relative to their std
    yn = centered * scale.astype(xr.dtype)
    if beta is not None:
        yn = yn + beta.astype(xr.dtype)
    if x_nhwc is not None and is_elementwise(cfg.active_type):
        y_img = apply_activation(cfg.active_type, yn.reshape(x_nhwc.shape))
        return _publish_nhwc(ctx, cfg, y_img)
    if x_nhwc is not None:
        y = _nhwc_to_flat(yn.reshape(x_nhwc.shape))
    elif ic is not None and ic.img_size > 0:
        y = yn.reshape(x.shape[0], hw, C).transpose(0, 2, 1).reshape(x.shape[0], -1)
    else:
        y = yn
    if seq_meta:
        y = y.reshape(a.value.shape)
    y = apply_activation(cfg.active_type, y)
    return Argument(value=y, **seq_meta)


@register_layer("norm", "norm-projection")
def norm_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: NormProjectionLayer (cmrnorm-projection): cross-map local
    # response normalization: y = x / (1 + scale/size * sum_window x^2)^pow
    nc = cfg.inputs[0].norm_conf
    x = _take_nhwc(ctx, cfg.inputs[0].input_layer_name, inputs[0],
                   nc.channels, nc.img_size, nc.img_size)
    half = nc.size // 2
    sq = jnp.square(x)
    acc = lax.reduce_window(
        sq, 0.0, lax.add, (1, 1, 1, nc.size), (1, 1, 1, 1), ((0, 0), (0, 0), (0, 0), (half, nc.size - 1 - half))
    )
    # NormConfig.scale already carries scale/size (the reference's
    # config_parser divides before storing; our DSL does the same)
    denom = jnp.power(1.0 + nc.scale * acc, nc.pow)
    return _publish_nhwc(ctx, cfg, x / denom)


@register_layer("blockexpand")
def block_expand_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: BlockExpandLayer.cpp — extract sliding blocks as a sequence of
    # flattened patches (OCR-style); output is a sequence of length
    # output_x * output_y per image.
    bc = cfg.inputs[0].block_expand_conf
    x = _take_nhwc(ctx, cfg.inputs[0].input_layer_name, inputs[0],
                   bc.channels, bc.img_size_y, bc.img_size_x)
    patches = lax.conv_general_dilated_patches(
        x.transpose(0, 3, 1, 2),  # NCHW
        filter_shape=(bc.block_y, bc.block_x),
        window_strides=(bc.stride_y, bc.stride_x),
        padding=[(bc.padding_y, bc.padding_y), (bc.padding_x, bc.padding_x)],
    )  # [B, C*by*bx, oy, ox]
    B, D, oy, ox = patches.shape
    seq = patches.transpose(0, 2, 3, 1).reshape(B, oy * ox, D)
    lengths = jnp.full((B,), oy * ox, jnp.int32)
    return Argument(value=seq, seq_lengths=lengths)


@register_layer("featmap_expand")
def featmap_expand_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: FeatureMapExpandLayer — tile a sequence input num_filters times.
    a = inputs[0]
    out = jnp.tile(a.value, (1,) * (a.value.ndim - 1) + (cfg.num_filters,))
    return Argument(value=out, seq_lengths=a.seq_lengths)


@register_layer("resize")
def resize_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: ResizeLayer — reinterpret rows with a new feature width.
    x = inputs[0].value
    return Argument(value=x.reshape(-1, cfg.size))
