"""Recurrent layers: simple RNN, LSTM, GRU (full-sequence and step forms).

Reference counterparts: RecurrentLayer.cpp, LstmLayer.cpp (+LstmCompute),
GatedRecurrentLayer.cpp (+GruCompute), LstmStepLayer.cpp, GruStepLayer.cpp
in /root/reference/paddle/gserver/layers/. The reference fuses per-frame
cell math in CUDA and schedules variable-length sequences densely via
SequenceToBatch (SequenceToBatch.h:41); the TPU-native formulation is a
``lax.scan`` over padded [T, B, D] with a carry mask — XLA fuses the cell,
and the MXU sees one [B, D]x[D, kD] matmul per step.

Layout contracts (from config_parser.py LstmLayer/GatedRecurrentLayer):
- lstmemory: input is the 4*size x-projection, recurrent weight
  [size, 4*size], bias 7*size = 4 gate biases + 3 peephole vectors,
  gate order [candidate, input, forget, output].
- gated_recurrent: input is the 3*size x-projection, weight [size, 3*size]
  split [update, reset | candidate], bias 3*size.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.graph.argument import Argument
from paddle_tpu.layers.base import LayerContext, register_layer
from paddle_tpu.ops.activations import apply_activation
from paddle_tpu.proto import LayerConfig

Array = jax.Array


def _scan_time(cell, x_tbd: Array, mask_tb: Array, init_carry, reverse: bool,
               unroll: int = 1):
    """Scan ``cell`` over the time-major sequence with carry masking.

    Padded steps pass the carry through unchanged so that (a) forward scans
    keep the final state at the last valid step and (b) reversed scans stay
    at the init state until the sequence actually starts.
    """

    def step(carry, inp):
        x_t, m_t = inp
        new_carry, y = cell(carry, x_t)
        m = m_t[:, None]
        merged = jax.tree_util.tree_map(lambda n, o: m * n + (1.0 - m) * o, new_carry, carry)
        return merged, y * m

    carry, ys = jax.lax.scan(
        step, init_carry, (x_tbd, mask_tb), reverse=reverse, unroll=unroll
    )
    return carry, ys


def _prep(a: Argument) -> Tuple[Array, Array]:
    x = jnp.swapaxes(a.value, 0, 1)  # [T, B, D]
    mask = jnp.swapaxes(a.seq_mask(dtype=x.dtype), 0, 1)  # [T, B]
    return x, mask


@register_layer("recurrent")
def recurrent_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    a = inputs[0]
    x, mask = _prep(a)
    w = ctx.param(cfg.inputs[0].input_parameter_name).reshape(cfg.size, cfg.size)
    b = ctx.param(cfg.bias_parameter_name).reshape(-1) if cfg.bias_parameter_name else 0.0

    def cell(h, x_t):
        h_new = apply_activation(cfg.active_type, x_t + jnp.dot(h, w) + b)
        return h_new, h_new

    B = x.shape[1]
    h0 = jnp.zeros((B, cfg.size), x.dtype)
    _, ys = _scan_time(cell, x, mask, h0, cfg.reversed, unroll=ctx.scan_unroll)
    return Argument(value=jnp.swapaxes(ys, 0, 1), seq_lengths=a.seq_lengths)


def lstm_cell_step(
    cfg: LayerConfig,
    x4: Array,            # [B, 4*size] x-projection (candidate,i,f,o)
    h_prev: Array,
    c_prev: Array,
    w: Array,             # [size, 4*size]
    bias: Optional[Array],  # [7*size] or None
) -> Tuple[Array, Array]:
    size = h_prev.shape[-1]
    gates = x4 + jnp.dot(h_prev, w)
    if bias is not None:
        gates = gates + bias[: 4 * size]
        peep_i = bias[4 * size : 5 * size]
        peep_f = bias[5 * size : 6 * size]
        peep_o = bias[6 * size : 7 * size]
    else:
        peep_i = peep_f = peep_o = None
    a, gi, gf, go = jnp.split(gates, 4, axis=-1)
    act_gate = lambda v: apply_activation(cfg.active_gate_type or "sigmoid", v)
    act_in = lambda v: apply_activation(cfg.active_type or "tanh", v)
    act_state = lambda v: apply_activation(cfg.active_state_type or "sigmoid", v)
    i = act_gate(gi + (peep_i * c_prev if peep_i is not None else 0.0))
    f = act_gate(gf + (peep_f * c_prev if peep_f is not None else 0.0))
    c = f * c_prev + i * act_in(a)
    o = act_gate(go + (peep_o * c if peep_o is not None else 0.0))
    h = o * act_state(c)
    return h, c


def _pallas_rnn_path(ctx, cfg, a, x, mask, w, bias, usable_fn, fwd_fn):
    """The fused Pallas kernel path shared by lstmemory/gated_recurrent,
    or None to take the scan. Gating: a TPU backend (off it the kernel
    body would run in the Python interpreter — only the parity tests ask
    for that, see utils/device.pallas_mode); shapes/activations/VMEM
    checked by the kernel's usable(). Meshes: single-device, or a purely
    data-parallel mesh — there the kernel runs per-shard under shard_map
    (each shard's batch rows are independent sequences); any non-trivial
    model/seq axis falls back to the scan, whose ops GSPMD can partition.
    Either way the choice is logged once per layer at debug. Callers
    guard on ctx.pallas_rnn BEFORE importing the kernel module, keeping
    the ops import lazy on the default path."""
    import os

    from paddle_tpu.utils import device

    def scan(why):
        device.log_selection("pallas_rnn", cfg.name, f"scan path ({why})")
        return None

    data_extent = None
    T, B = mask.shape
    if ctx.mesh is not None:
        from paddle_tpu.parallel.mesh import data_only_extent

        data_extent = data_only_extent(ctx.mesh)
        if data_extent is None:
            return scan("mesh has a non-data axis")
        if B % data_extent:
            return scan(f"batch {B} not divisible by data={data_extent}")
    mode = device.pallas_mode()
    if mode is None:
        return scan(device.why_no_pallas())
    interpret = mode == "interpret"
    # gate on the PER-SHARD batch the kernel will actually see
    local = jax.ShapeDtypeStruct(
        (T, B // (data_extent or 1), x.shape[2]), x.dtype)
    if not usable_fn(cfg, local):
        return scan(f"kernel gate refuses {local.shape} {local.dtype}")
    device.log_selection(
        "pallas_rnn", cfg.name,
        f"Pallas kernel, {mode}"
        + (f", shard_map over data={data_extent}" if data_extent else ""))
    # transpose-free interface — the kernel reads the projection
    # output's batch-major value through a free [B, T*width] reshape
    # instead of a materialized time-major swap (A/B knob; flip the
    # default only on a measured win). settings(pallas_flat=True) is
    # the config-level switch; the PADDLE_TPU_PALLAS_FLAT=1 env var
    # still forces it for configs that can't be edited.
    flat = ctx.pallas_flat or os.environ.get("PADDLE_TPU_PALLAS_FLAT") == "1"
    x_bt = a.value if flat else None
    if data_extent is None:
        ys = fwd_fn(cfg, x, mask, w, bias, interpret=interpret, x_bt=x_bt)
    else:
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.parallel.mesh import replicated_specs, shard_map_unchecked

        def shard_fn(xin, mask_l, *wb):
            w_l = wb[0]
            bias_l = wb[1] if len(wb) > 1 else None
            return fwd_fn(cfg, xin, mask_l, w_l, bias_l,
                          interpret=interpret,
                          x_bt=xin if flat else None)

        x_spec = P("data") if flat else P(None, "data")
        wb_args = (w,) if bias is None else (w, bias)
        ys = shard_map_unchecked(
            shard_fn, ctx.mesh,
            in_specs=(x_spec, P(None, "data")) + replicated_specs(*wb_args),
            out_specs=x_spec,  # ys shards on batch exactly like x
        )(x_bt if flat else x, mask, *wb_args)
    value = ys if flat else jnp.swapaxes(ys, 0, 1)
    return Argument(value=value, seq_lengths=a.seq_lengths)


@register_layer("lstmemory")
def lstmemory_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    a = inputs[0]
    x, mask = _prep(a)  # [T, B, 4*size]
    size = cfg.size
    w = ctx.param(cfg.inputs[0].input_parameter_name).reshape(size, 4 * size)
    bias = ctx.param(cfg.bias_parameter_name).reshape(-1) if cfg.bias_parameter_name else None

    if ctx.pallas_rnn:
        from paddle_tpu.ops import pallas_lstm as pk

        out = _pallas_rnn_path(
            ctx, cfg, a, x, mask, w, bias, pk.usable, pk.lstm_layer_forward
        )
        if out is not None:
            return out

    def cell(carry, x_t):
        h, c = carry
        h2, c2 = lstm_cell_step(cfg, x_t, h, c, w, bias)
        return (h2, c2), h2

    B = x.shape[1]
    init = (jnp.zeros((B, size), x.dtype), jnp.zeros((B, size), x.dtype))
    _, ys = _scan_time(cell, x, mask, init, cfg.reversed, unroll=ctx.scan_unroll)
    return Argument(value=jnp.swapaxes(ys, 0, 1), seq_lengths=a.seq_lengths)


def gru_cell_step(
    cfg: LayerConfig,
    x3: Array,        # [B, 3*size] x-projection (update,reset,candidate)
    h_prev: Array,
    w: Array,         # [size, 3*size]: [:, :2s]=gates, [:, 2s:]=candidate
    bias: Optional[Array],
) -> Array:
    size = h_prev.shape[-1]
    xg, xc = x3[..., : 2 * size], x3[..., 2 * size :]
    wg, wc = w[:, : 2 * size], w[:, 2 * size :]
    g = xg + jnp.dot(h_prev, wg)
    if bias is not None:
        g = g + bias[: 2 * size]
    act_gate = lambda v: apply_activation(cfg.active_gate_type or "sigmoid", v)
    u, r = jnp.split(act_gate(g), 2, axis=-1)
    cand = xc + jnp.dot(r * h_prev, wc)
    if bias is not None:
        cand = cand + bias[2 * size :]
    c = apply_activation(cfg.active_type or "tanh", cand)
    # ref GruCompute: output = update * prev + (1 - update) * candidate
    return u * h_prev + (1.0 - u) * c


@register_layer("gated_recurrent")
def gated_recurrent_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    a = inputs[0]
    x, mask = _prep(a)
    size = cfg.size
    w = ctx.param(cfg.inputs[0].input_parameter_name).reshape(size, 3 * size)
    bias = ctx.param(cfg.bias_parameter_name).reshape(-1) if cfg.bias_parameter_name else None

    if ctx.pallas_rnn:
        from paddle_tpu.ops import pallas_gru as pg

        out = _pallas_rnn_path(
            ctx, cfg, a, x, mask, w, bias, pg.usable, pg.gru_layer_forward
        )
        if out is not None:
            return out

    def cell(h, x_t):
        h2 = gru_cell_step(cfg, x_t, h, w, bias)
        return h2, h2

    B = x.shape[1]
    h0 = jnp.zeros((B, size), x.dtype)
    _, ys = _scan_time(cell, x, mask, h0, cfg.reversed, unroll=ctx.scan_unroll)
    return Argument(value=jnp.swapaxes(ys, 0, 1), seq_lengths=a.seq_lengths)


@register_layer("lstm_step")
def lstm_step_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: LstmStepLayer.cpp — one LSTM step inside a recurrent_group.
    # inputs: [x-projection 4*size, prev cell state]; primary output is the
    # hidden state; the new cell state is published as "<name>@state" for
    # get_output(..., arg_name='state').
    x4, c_prev = inputs[0].value, inputs[1].value
    size = cfg.size
    w = jnp.zeros((size, 4 * size), x4.dtype)  # step layers have no recurrent weight
    bias = ctx.param(cfg.bias_parameter_name).reshape(-1) if cfg.bias_parameter_name else None
    h_prev = jnp.zeros((x4.shape[0], size), x4.dtype)
    h, c = lstm_cell_step(cfg, x4, h_prev, c_prev, w, bias)
    ctx.outputs[f"{cfg.name}@state"] = Argument(value=c, seq_lengths=inputs[0].seq_lengths)
    return Argument(value=h, seq_lengths=inputs[0].seq_lengths)


@register_layer("gru_step")
def gru_step_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: GruStepLayer.cpp — inputs: [x-projection 3*size, prev output].
    x3, h_prev = inputs[0].value, inputs[1].value
    size = cfg.size
    w = ctx.param(cfg.inputs[0].input_parameter_name).reshape(size, 3 * size)
    bias = ctx.param(cfg.bias_parameter_name).reshape(-1) if cfg.bias_parameter_name else None
    h = gru_cell_step(cfg, x3, h_prev, w, bias)
    return Argument(value=h, seq_lengths=inputs[0].seq_lengths)


@register_layer("mdlstmemory")
def mdlstm_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    """Multi-dimensional LSTM over a 2-D grid (ref: MDLstmLayer.cpp:81-473,
    Graves-style MDLSTM). Input is a NESTED argument [B, H, W, (3+D)*size]
    holding the precomputed x-projections for blocks
    [inputNode, inputGate, forgetGate×D, outputGate]; the recurrent weight
    [size, (3+D)*size] is SHARED across the D predecessor directions and
    the bias packs (3+D) gate biases + checkIg + checkFg×D + checkOg
    (config_parser.py:2608 MDLstmLayer). directions[d]=False scans dim d
    backwards. Per position: each predecessor (top/left) contributes its
    output through W, its state through the peepholes and through an
    independent forget gate — out-of-grid predecessors contribute zeros,
    which reproduces the reference's skip semantics exactly.

    TPU formulation: lax.scan over rows carrying the previous row's
    (out, state) [W, B, size], with an inner lax.scan over columns — the
    cell math vectorizes over the batch. Ragged grids (per-sample
    sub_seq_lengths) are handled by zeroing out-of-grid cells' out/state,
    which makes them behave exactly like the reference's out-of-grid
    skip.
    """
    a = inputs[0]
    x = a.value
    assert x is not None and x.ndim == 4, (
        "mdlstmemory expects a nested [B, H, W, (3+D)*size] input "
        "(dense_vector_sub_sequence grid)"
    )
    dirs = list(cfg.directions) or [True, True]
    D = len(dirs)
    assert D == 2, "mdlstmemory: 2-D grids supported (directions must have 2 entries)"
    nb = cfg.size
    w = ctx.param(cfg.inputs[0].input_parameter_name).reshape(nb, (3 + D) * nb)
    bias = ctx.param(cfg.bias_parameter_name).reshape(-1)
    gate_bias = bias[: (3 + D) * nb]
    check_ig = bias[(3 + D) * nb : (4 + D) * nb]
    check_fg = bias[(4 + D) * nb : (4 + 2 * D) * nb].reshape(D, nb)
    check_og = bias[(4 + 2 * D) * nb : (5 + 2 * D) * nb]

    if a.sub_seq_lengths is not None:
        grid_mask = a.sub_seq_mask(dtype=x.dtype)[..., None]  # [B, H, W, 1]
    else:
        grid_mask = jnp.ones(x.shape[:3] + (1,), x.dtype)
    if not dirs[0]:
        x = jnp.flip(x, 1)
        grid_mask = jnp.flip(grid_mask, 1)
    if not dirs[1]:
        x = jnp.flip(x, 2)
        grid_mask = jnp.flip(grid_mask, 2)
    B, H, W, _ = x.shape
    g_all = jnp.transpose(x + gate_bias, (1, 2, 0, 3))  # [H, W, B, (3+D)nb]
    m_all = jnp.transpose(grid_mask, (1, 2, 0, 3))      # [H, W, B, 1]

    act_gate = lambda v: apply_activation(cfg.active_gate_type or "sigmoid", v)
    act_in = lambda v: apply_activation(cfg.active_type or "tanh", v)
    act_state = lambda v: apply_activation(cfg.active_state_type or "sigmoid", v)

    def col_cell(carry, inp):
        out_l, st_l = carry                        # left neighbor [B, nb]
        g, out_t, st_t, m = inp                    # this col + top neighbor
        g = g + jnp.dot(out_t + out_l, w)          # shared recurrent weight
        in_pre = g[:, :nb]
        ig_pre = g[:, nb : 2 * nb]
        fg_pre = g[:, 2 * nb : (2 + D) * nb]
        og_pre = g[:, (2 + D) * nb : (3 + D) * nb]
        ig = act_gate(ig_pre + (st_t + st_l) * check_ig)
        fg = act_gate(
            fg_pre + jnp.concatenate([st_t * check_fg[0], st_l * check_fg[1]], -1)
        )
        state = fg[:, :nb] * st_t + fg[:, nb:] * st_l + act_in(in_pre) * ig
        og = act_gate(og_pre + state * check_og)
        out = og * act_state(state)
        # out-of-grid cells emit zeros so neighbors treat them as absent
        out = out * m
        state = state * m
        return (out, state), (out, state)

    def row_step(carry, inp):
        g_row, m_row = inp
        out_top, st_top = carry                    # previous row [W, B, nb]
        z = jnp.zeros((B, nb), x.dtype)
        (_, _), (outs, sts) = jax.lax.scan(
            col_cell, (z, z), (g_row, out_top, st_top, m_row)
        )
        return (outs, sts), outs

    zrow = jnp.zeros((W, B, nb), x.dtype)
    _, ys = jax.lax.scan(row_step, (zrow, zrow), (g_all, m_all))  # [H, W, B, nb]
    out = jnp.transpose(ys, (2, 0, 1, 3))                # [B, H, W, nb]
    if not dirs[1]:
        out = jnp.flip(out, 2)
    if not dirs[0]:
        out = jnp.flip(out, 1)
    return Argument(
        value=out, seq_lengths=a.seq_lengths, sub_seq_lengths=a.sub_seq_lengths
    )
