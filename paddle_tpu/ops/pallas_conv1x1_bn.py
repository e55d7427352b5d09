"""Fused 1x1-conv (matmul) + batch-norm-statistics Pallas TPU kernel.

ResNet-style conv+BN chains pay a full extra HBM read per layer: the
conv writes its output y, then the BN statistics pass re-reads all of y
to reduce per-channel sum/sum-of-squares (XLA's
`convert_reduce_fusion`s). XLA:TPU cannot fuse a reduction into
a convolution's epilogue from lax-level code, but a 1x1 stride-1 conv
IS a matmul over [B*H*W, Cin] x [Cin, Cout] — so this kernel computes
the matmul tile-by-tile and accumulates the per-channel statistics of
each output tile while it is still in VMEM, before it is ever written.
The separate statistics pass (and its HBM read) disappears.

In ResNet-50 bottlenecks the two 1x1 convs produce the reduce (C) and
expand (4C) feature maps — ~80% of the BN-statistics volume — so
covering only 1x1/s1 convs captures most of the win without writing a
general conv kernel (the 3x3 keeps XLA's conv).

Statistics semantics match layers/vision.py batch_norm_layer exactly:
sum and sumsq accumulate in f32 over the *rounded* activation-dtype
output rows (the same values the XLA path's one-pass
``jnp.mean(xr, dtype=f32)`` sees), so downstream mean/var agree with
the unfused path to reduction-order rounding.

Backward is plain XLA (no pallas): with y = x@w + b, s = sum_m(y),
q = sum_m(y^2), the cotangent into the matmul is
    g = dy + ds[None, :] + 2*y*dq[None, :]
and dx = g @ w.T, dw = x.T @ g, db = sum_m(g) — the same two matmuls
the unfused conv backward costs.

ref role: this replaces the reference's ConvProjection +
BatchNormalizationLayer::calMeanAndStd forward pair
(paddle/gserver/layers/BatchNormalizationLayer.cpp) for 1x1 convs;
the reference fuses nothing here (cuDNN conv then column reductions).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

# Per-invocation VMEM budget, shared convention with pallas_lstm.py.
_VMEM_BUDGET_BYTES = (
    int(os.environ.get("PADDLE_TPU_PALLAS_VMEM_BUDGET", 0)) or 14 * 1024 * 1024
)

# Row-block candidates: prefer big blocks (fewer weight re-streams),
# multiples of 128 first (native sublane*lane tiling), 8 minimum.
_BM_CANDIDATES = (1024, 896, 768, 640, 512, 384, 256, 128, 64, 32, 16, 8)


def _pick_bm(M: int) -> int | None:
    for bm in _BM_CANDIDATES:
        if M % bm == 0:
            return bm
    return None


def _pick_bn(N: int) -> int | None:
    # OUTPUT blocks need a full 128 lane dim: N=64 is a measured Mosaic
    # compile rejection on hardware (2026-08-01), unlike sub-128 INPUT
    # k blocks which compile fine (the K=64 expand shape passes). The
    # excluded convs are resnet's stage-2 1x1 reduces — the smallest
    # stats tensors, so the loss is minor.
    for bn in (512, 256, 128):
        if N % bn == 0:
            return bn
    return None


def _pick_bk(K: int) -> int | None:
    if K <= 512:
        return K if (K % 128 == 0 or (K < 128 and K % 8 == 0)) else None
    for bk in (512, 256, 128):
        if K % bk == 0:
            return bk
    return None


def _vmem_bytes(bm: int, bn: int, bk: int, N: int, itemsize: int) -> int:
    x_blk = 2 * bm * bk * itemsize            # double-buffered
    w_blk = 2 * bk * bn * itemsize
    o_blk = 2 * bm * bn * itemsize
    acc = bm * bn * 4
    stats = 2 * 2 * N * 4 + 2 * N * itemsize  # s/q outputs + bias block
    return x_blk + w_blk + o_blk + acc + stats


def blocks_for(M: int, K: int, N: int, itemsize: int):
    """(bm, bn, bk) if the kernel supports this shape, else None."""
    bm, bn, bk = _pick_bm(M), _pick_bn(N), _pick_bk(K)
    if bm is None or bn is None or bk is None:
        return None
    if _vmem_bytes(bm, bn, bk, N, itemsize) >= _VMEM_BUDGET_BYTES:
        return None
    return bm, bn, bk


def supported(M: int, K: int, N: int, itemsize: int = 2) -> bool:
    return blocks_for(M, K, N, itemsize) is not None


def _kernel(x_ref, w_ref, b_ref, o_ref, s_ref, q_ref, acc_scr, *, bn: int, nk: int):
    m = pl.program_id(0)
    n = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((m == 0) & (n == 0) & (k == 0))
    def _zero_stats():
        s_ref[...] = jnp.zeros_like(s_ref)
        q_ref[...] = jnp.zeros_like(q_ref)

    @pl.when(k == 0)
    def _zero_acc():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        y32 = acc_scr[...] + b_ref[...].astype(jnp.float32)
        yb = y32.astype(o_ref.dtype)
        o_ref[...] = yb
        # statistics of the ROUNDED output (what the XLA path reduces),
        # accumulated f32 while the tile is VMEM-resident
        yf = yb.astype(jnp.float32)
        sl = pl.dslice(n * bn, bn)
        s_ref[0, sl] += jnp.sum(yf, axis=0)
        q_ref[0, sl] += jnp.sum(yf * yf, axis=0)


def _run(x: Array, w: Array, b: Array, interpret: bool):
    M, K = x.shape
    _, N = w.shape
    blocks = blocks_for(M, K, N, x.dtype.itemsize)
    assert blocks is not None, (M, K, N)
    bm, bn, bk = blocks
    nm, nn, nk = M // bm, N // bn, K // bk
    kernel = functools.partial(_kernel, bn=bn, nk=nk)
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * 3)
    y, s, q = pl.pallas_call(
        kernel,
        name="conv1x1_bn_fwd",
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
            pl.BlockSpec((1, N), lambda m, n, k: (0, 0)),
            pl.BlockSpec((1, N), lambda m, n, k: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), x.dtype),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compiler_params,
        interpret=interpret,
    )(x, w, b.reshape(1, N))
    return y, s[0], q[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def conv1x1_stats(x: Array, w: Array, b: Array, interpret: bool = False):
    """y = x @ w + b with fused per-channel statistics.

    x: [M, K] rows (B*H*W pixels), w: [K, N], b: [N] (zeros when the
    conv has no bias). Returns (y [M,N] in x.dtype, sum [N] f32,
    sumsq [N] f32) where sum/sumsq reduce the rounded y over rows.
    """
    return _run(x, w, b, interpret)


def _fwd(x, w, b, interpret):
    y, s, q = _run(x, w, b, interpret)
    return (y, s, q), (x, w, b, y)


def _bwd(interpret, res, cts):
    x, w, b, y = res
    dy, ds, dq = cts
    f32 = jnp.float32
    g32 = (
        dy.astype(f32)
        + ds[None, :].astype(f32)
        + 2.0 * y.astype(f32) * dq[None, :].astype(f32)
    )
    g = g32.astype(y.dtype)
    dx = jax.lax.dot_general(
        g, w, (((1,), (1,)), ((), ())), preferred_element_type=f32
    ).astype(x.dtype)
    dw = jax.lax.dot_general(
        x, g, (((0,), (0,)), ((), ())), preferred_element_type=f32
    ).astype(w.dtype)
    db = jnp.sum(g32, axis=0).astype(b.dtype)
    return dx, dw, db


conv1x1_stats.defvjp(_fwd, _bwd)
