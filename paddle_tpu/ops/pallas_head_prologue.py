"""The grouped-query head prologue: per-head RMS norm, rotary turn and
scale of a projection's heads, one pass over the data each way.

What it computes for every head row ``x`` of ``Dh`` (float32 statistics
and arithmetic inside a pass, rounded ONCE to the input's dtype)::

    n = x * rsqrt(mean(x^2) + eps) * gain          (gain None: n = x)
    y = (n * C + roll(n, Dh/2) * S) * scale        (tables None: y = n * scale)

with ``C = [cos, cos]`` and ``S = [-sin, sin]`` at the row's position: the
rotate-half turn as ONE roll of the whole head row and two multiplies, no
half-row slices and no concatenate. A PARTIAL turn (the first ``rot`` of
the ``Dh`` lanes turned, the rest passed through) is the same with two
rolls, each with its own table::

    y = (n * C + roll(n, Dh - rot/2) * S_up + roll(n, rot/2) * S_down) * scale
    C = [cos, cos, 1...], S_up = [-sin, 0, 0...], S_down = [0, sin, 0...]

(`turn_tables` gives two tables for a whole-head turn and three for a
partial one; the tables may carry a factor on cos and sin). The backward
applies the turn's transpose, the same rolls with the sines negated, and
the norm's backward is its closed form; autodiff sees neither::

    dn = dy * scale * C - roll(dy * scale, Dh/2) * S
    u = x * inv,  g = dn * gain
    dx = inv * (g - u * mean(g * u)),   d gain = sum over rows of dn * u

:func:`head_prologue` carries that backward (`jax.custom_vjp`) and keeps
``x`` alone for it. The mathematics is written once (`_forward_rows`,
`_backward_rows`); where a Pallas kernel can run (`device.pallas_mode`)
the two passes are the kernels ``head_prologue_fwd`` / ``head_prologue_bwd``
over row tiles, else the same functions over the whole array under XLA.

Layout: x and dx are the projection as the product leaves it, ``[B, T,
H*Dh]`` (a head is a 128-lane column block, a position a sublane row, so
the tables apply to a head's slab as they are); y and dy are the same
array with its heads named, ``[B, T, H, Dh]``, a free reshape: the flash
kernels of `ops/pallas_attention.py` read a head of q and k, and write its
cotangent, as that column block where it lies, so nothing is transposed
between the product and the scores.
Correctness is tested on the CPU, both ways
(tests/test_block_diffusion_moe.py); what Mosaic accepts, by compiling for
a described v5e (tests/test_chip_compile.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array
F32 = jnp.float32

_LANES = 128
# a tile: at most this many heads (a kernel's body is written out once a
# head, and tracing and lowering 32 of them a call, six calls a layer, cost
# a process 8 s of set-up where 4 cost one) by rows up to this many bytes of
# one operand (double-buffered; the backward has three), and the scoped VMEM
# the kernels may use
_TILE_HEADS = 4
_TILE_BYTES = 2 * 1024 * 1024
_VMEM_LIMIT = 48 * 1024 * 1024


def rotary_frequencies(theta: float, rot: int, yarn=None) -> Array:
    """float32 ``[rot/2]``: the rotate-half frequencies ``theta^(-2i/rot)``
    or, with ``yarn = (factor, original positions, beta_fast, beta_slow)``,
    YaRN's (arXiv:2309.00071): a frequency that makes more than
    ``beta_fast`` turns over the original positions is kept, one that makes
    fewer than ``beta_slow`` is divided by ``factor``, and between the two
    (by frequency index, the bounds rounded outwards) a linear ramp. Fixed,
    not a function of the sequence's length."""
    half = rot // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    if yarn is None:
        return inv_freq
    factor, original, beta_fast, beta_slow = yarn
    index_of = lambda turns: rot * math.log(original / (2 * math.pi * turns)) / (2 * math.log(theta))
    low = max(math.floor(index_of(beta_fast)), 0)
    high = min(math.ceil(index_of(beta_slow)), rot - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=F32) - low) / ((high - low) or 1e-3), 0.0, 1.0)
    return inv_freq * (1.0 - ramp) + inv_freq / factor * ramp


def turn_tables(positions: Array, theta: float, head_dim: int, rot: int = 0,
                yarn=None, factor: float = 1.0) -> Tuple[Array, ...]:
    """The turn's tables at integer ``positions`` [T], float32 ``[T, Dh]``
    each. The whole head turned (``rot`` 0 or ``head_dim``): ``(C, S)`` =
    ``[cos, cos]`` and ``[-sin, sin]`` of the rotate-half angles. The first
    ``rot`` lanes turned: ``(C, S_up, S_down)`` of the module docstring.
    ``yarn``: :func:`rotary_frequencies`; ``factor`` multiplies cos and sin
    (YaRN's attention factor), so the turned lanes only."""
    rot = rot or head_dim
    ang = positions.astype(F32)[:, None] * rotary_frequencies(theta, rot, yarn)[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)                                 # [T, rot/2]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    if rot == head_dim:
        return jnp.concatenate([cos, cos], -1), jnp.concatenate([-sin, sin], -1)
    zero = jnp.zeros_like(sin)
    rest = jnp.zeros((ang.shape[0], head_dim - rot), F32)
    return (jnp.concatenate([cos, cos, rest + 1.0], -1),
            jnp.concatenate([-sin, zero, rest], -1),
            jnp.concatenate([zero, sin, rest], -1))


def interleaved_order(rot: int):
    """The static lane order that makes an INTERLEAVED turn (the lanes (2i,
    2i + 1) are a pair) a rotate-half one: [even lanes | odd lanes]. A
    score is a sum over lanes, so q and k reordered alike give the scores
    of the interleaved turn; a caller applies the order to the projection's
    weight columns (a gather of a weight, not of an activation) and the
    prologue then turns halves, as it does for every other head."""
    return np.concatenate([np.arange(0, rot, 2), np.arange(1, rot, 2)])


def _shifts(head_dim: int, rot: int, tables) -> Tuple[int, ...]:
    """What each sine table's roll turns a head row by (``rot`` 0: the
    whole head)."""
    if tables is None:
        return ()
    if rot in (0, head_dim):
        return (head_dim // 2,)
    return (head_dim - rot // 2, rot // 2)


# ------------------------------------------------- the mathematics, once


def _inv_rms(xf, eps):
    return jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)


def _turned(a, c, s, rolls, sign):
    """``a * c`` plus (``sign`` 1) or minus each sine table times its roll
    of ``a``: the turn, and with the sines negated its transpose."""
    out = a * c
    for roll, table in zip(rolls, s):
        out = out + roll(a) * table if sign > 0 else out - roll(a) * table
    return out


def _forward_rows(xf, gain, c, s, eps, scale, rolls):
    """float32 head rows ``[..., Dh]`` -> float32 y. ``gain``, ``c`` and
    the sine tables ``s`` broadcast against the rows; ``rolls``: a roll
    along the row for each sine table."""
    if gain is not None:
        xf = xf * _inv_rms(xf, eps) * gain
    if c is not None:
        xf = _turned(xf, c, s, rolls, 1)
    return xf if scale == 1.0 else xf * scale


def _backward_rows(xf, d, gain, c, s, eps, scale, rolls):
    """float32 head rows and their cotangent -> (dx, dn * u); the second
    is None without a norm, else what sums over rows to ``d gain``."""
    if scale != 1.0:
        d = d * scale
    if c is not None:
        d = _turned(d, c, s, rolls, -1)
    if gain is None:
        return d, None
    inv = _inv_rms(xf, eps)
    u = xf * inv
    du = d * u
    g_u = du * gain                                                       # g * u
    return inv * (d * gain - u * jnp.mean(g_u, axis=-1, keepdims=True)), du


# ------------------------------------------------------------ the kernels


def _tiling(T: int, width: int, head_dim: int, itemsize: int) -> Tuple[int, int]:
    """(rows, lanes) of a tile of a ``[B, T, width]`` projection: as many
    whole heads as divide the projection's, ``_TILE_HEADS`` at most, by the
    largest power of two of rows from 1024 down to 16 that divides T and
    keeps the tile within ``_TILE_BYTES``; rows 0 where none does."""
    heads = width // head_dim
    lanes = head_dim * next(n for n in range(min(heads, _TILE_HEADS), 0, -1) if heads % n == 0)
    rows = next((rows for rows in (1024, 512, 256, 128, 64, 32, 16)
                 if T % rows == 0 and rows * lanes * itemsize <= _TILE_BYTES), 0)
    return rows, lanes


def supported(T: int, width: int, head_dim: int, itemsize: int) -> bool:
    """Whether the kernels take a ``[B, T, width]`` projection of
    ``head_dim`` heads: a head is whole lane tiles, and T tiles."""
    return (head_dim % _LANES == 0 and width % head_dim == 0
            and _tiling(T, width, head_dim, itemsize)[0] > 0)


def _unpack(refs, has_gain, shifts):
    """(gain, C, the sine tables: values or None; a lane roll a sine table;
    the other refs). A head's whole
    ``[rows, Dh]`` slab is one value: a loop over fewer rows at a time only
    adds its own latency (on a v5e, 32 rows a trip: 1.8 ms a pass of q
    against 0.9)."""
    refs = list(refs)
    gain = refs.pop(0)[...] if has_gain else None                         # [1, Dh]
    tables = [refs.pop(0)[...] for _ in range(len(shifts) + 1)] if shifts else [None]
    rolls = [lambda a, n=n: pltpu.roll(a, n, 1) for n in shifts]
    return gain, tables[0], tables[1:], rolls, refs                       # [rows, Dh] each


def _fwd_kernel(*refs, head_dim, eps, scale, has_gain, shifts):
    gain, c, s, roll, (x_ref, y_ref) = _unpack(refs, has_gain, shifts)
    for h in range(x_ref.shape[2] // head_dim):
        lanes = slice(h * head_dim, (h + 1) * head_dim)
        xf = x_ref[0, :, lanes].astype(F32)
        y_ref[0, :, lanes] = _forward_rows(xf, gain, c, s, eps, scale, roll).astype(y_ref.dtype)


def _bwd_kernel(*refs, head_dim, eps, scale, has_gain, shifts):
    gain, c, s, roll, (x_ref, dy_ref, dx_ref, *d_gain_ref) = _unpack(refs, has_gain, shifts)
    acc = 0.0
    for h in range(x_ref.shape[2] // head_dim):
        lanes = slice(h * head_dim, (h + 1) * head_dim)
        dx, du = _backward_rows(x_ref[0, :, lanes].astype(F32), dy_ref[0, :, lanes].astype(F32),
                                gain, c, s, eps, scale, roll)
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        if has_gain:
            acc = acc + du
    if has_gain:
        d_gain_ref[0][0, 0, 0] = jnp.sum(acc, axis=0, keepdims=True)      # this tile's d gain


def _call(kernel, name, x, dy, gain, tables, head_dim, eps, scale, rot, interpret):
    """One pass in tiles of some rows by a few heads, every array ``[B, T,
    width]``: the forward kernel (``dy`` None) reads the projection ``x``
    and writes y; the backward kernel reads x and ``dy`` and writes dx and,
    under a norm, each tile's ``d gain`` (one ``[1, Dh]`` a tile). The grid
    walks the row tiles outermost, so a tile's tables stay in VMEM for all
    its head groups and sequences."""
    B, T, width = x.shape
    rows, lanes = _tiling(T, width, head_dim, x.dtype.itemsize)
    grid = (T // rows, width // lanes, B)
    tile = pl.BlockSpec((1, rows, lanes), lambda j, g, b: (b, j, g))
    operands, specs = [], []
    if gain is not None:
        operands.append(gain.astype(F32).reshape(1, head_dim))
        specs.append(pl.BlockSpec((1, head_dim), lambda j, g, b: (0, 0)))
    if tables is not None:
        operands += list(tables)
        specs += [pl.BlockSpec((rows, head_dim), lambda j, g, b: (j, 0))] * len(tables)
    tiled = [x] if dy is None else [x, dy]
    outs = [(jax.ShapeDtypeStruct(x.shape, x.dtype), tile)]
    if dy is not None and gain is not None:
        outs.append((jax.ShapeDtypeStruct(grid + (1, head_dim), F32),
                     pl.BlockSpec((1, 1, 1, 1, head_dim), lambda j, g, b: (j, g, b, 0, 0))))
    return pl.pallas_call(
        functools.partial(kernel, head_dim=head_dim, eps=eps, scale=scale,
                          has_gain=gain is not None, shifts=_shifts(head_dim, rot, tables)),
        name=name, grid=grid,
        in_specs=specs + [tile] * len(tiled),
        out_specs=[spec for _, spec in outs], out_shape=[shape for shape, _ in outs],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(*operands, *tiled)


# ------------------------------------------------------------ the function


def _whole(x, gain, tables, head_dim, rot):
    """The XLA path's view of the operands: the rows' shape ``[B, T, H,
    Dh]``, the gain float32, the tables ``[1, T, 1, Dh]``, and the rolls
    along the last axis."""
    B, T, width = x.shape
    c, *s = [None] if tables is None else [t[None, :, None, :] for t in tables]
    rolls = [lambda a, n=n: jnp.roll(a, n, axis=-1) for n in _shifts(head_dim, rot, tables)]
    return ((B, T, width // head_dim, head_dim),
            None if gain is None else gain.astype(F32), c, s, rolls)


def _packed_tables(tables, n: int):
    """A whole-head turn's ``(C, S)`` of heads that lie ``n`` to a lane tile,
    as the three tables of a partial turn of the tile: the two rolls of the
    module docstring bring every lane its partner inside its own head, and
    the sines are zero where a roll crosses into a neighbour."""
    if tables is None:
        return None
    c, s = tables
    half = s.shape[1] // 2
    zero = jnp.zeros_like(s[:, :half])
    up, down = jnp.concatenate([s[:, :half], zero], -1), jnp.concatenate([zero, s[:, half:]], -1)
    return tuple(jnp.tile(t, (1, n)) for t in (c, up, down))


def head_prologue(x: Array, gain: Optional[Array], tables, head_dim: int,
                  eps: float, scale: float, rot: int = 0) -> Array:
    """A projection ``x`` [B, T, H*Dh], as the product leaves it, with its
    heads made ready for the scores (module docstring): ``[B, T, H, Dh]``,
    the same layout with the heads named, in x's dtype. ``gain`` [Dh] or None (no
    norm); ``tables`` from :func:`turn_tables` or None (no turn), ``rot``
    the lanes they turn where not the whole head. The kernels run where
    `device.pallas_mode` has a way to run them and
    :func:`supported` admits the shape. Heads narrower than a lane tile that
    are turned whole and have no norm (latent attention's 32 rotary heads of
    64) go through the kernels several to a tile (`_packed_tables`); XLA
    pads each of their float32 passes to whole lane tiles, twice the bytes
    at 64 lanes and four times for a half head."""
    from paddle_tpu.utils import device

    mode = device.pallas_mode()
    B, T, width = x.shape
    packed = (gain is None and head_dim < _LANES and _LANES % head_dim == 0
              and rot in (0, head_dim))
    if mode and packed and supported(T, width, _LANES, x.dtype.itemsize):
        tables, rot, kernel_dim = _packed_tables(tables, _LANES // head_dim), head_dim, _LANES
    else:
        kernel_dim = head_dim
        if not supported(T, width, head_dim, x.dtype.itemsize):
            mode = None
    device.log_selection(
        "head_prologue", f"T={T} heads={width // head_dim} Dh={head_dim}",
        f"Pallas kernel, {mode}" if mode else "XLA path")
    y = _prologue(x, gain, tables, kernel_dim, eps, scale, mode, rot)
    return y.reshape(B, T, width // head_dim, head_dim)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _prologue(x, gain, tables, head_dim, eps, scale, mode, rot=0):
    """``mode`` "compiled" / "interpret": the kernels; None: the same
    mathematics over the whole array under XLA."""
    if mode is not None:
        y = _call(_fwd_kernel, "head_prologue_fwd", x, None, gain, tables,
                  head_dim, eps, scale, rot, mode == "interpret")[0]
        return y.reshape(*x.shape[:2], -1, head_dim)
    shape, g, c, s, roll = _whole(x, gain, tables, head_dim, rot)
    y = _forward_rows(x.astype(F32).reshape(shape), g, c, s, eps, scale, roll)
    return y.astype(x.dtype)


def _vjp_fwd(x, gain, tables, head_dim, eps, scale, mode, rot):
    return _prologue(x, gain, tables, head_dim, eps, scale, mode, rot), (x, gain, tables)


def _vjp_bwd(head_dim, eps, scale, mode, rot, kept, dy):
    x, gain, tables = kept
    if mode is not None:
        dx, *du = _call(_bwd_kernel, "head_prologue_bwd", x, dy.reshape(x.shape), gain, tables,
                        head_dim, eps, scale, rot, mode == "interpret")
        du = du[0] if du else None
    else:
        shape, g, c, s, roll = _whole(x, gain, tables, head_dim, rot)
        dx, du = _backward_rows(x.astype(F32).reshape(shape), dy.astype(F32),
                                g, c, s, eps, scale, roll)
        dx = dx.reshape(x.shape).astype(x.dtype)
    d_gain = None if gain is None else (
        jnp.sum(du.reshape(-1, head_dim), axis=0).astype(gain.dtype))
    # the tables come from integer positions: nothing to hand back
    d_tables = None if tables is None else tuple(jnp.zeros_like(t) for t in tables)
    return dx, d_gain, d_tables


_prologue.defvjp(_vjp_fwd, _vjp_bwd)
