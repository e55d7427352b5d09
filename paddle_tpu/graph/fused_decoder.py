"""Template matcher wiring the fused attention-GRU decoder kernel
(ops/pallas_attention_gru) into the recurrent-group scan.

A training-time recurrent group whose step graph is EXACTLY the
attention-decoder template built by
trainer_config_helpers.networks.simple_attention + gru_step_layer
(the reference's demo/seqToseq decoder, networks.py:943 +
GruStepLayer.cpp) is lowered to one Pallas launch instead of a
lax.scan of ~10 layers per step:

    memory(gru) -> [transform -> expand -> combine -> softmax
                    -> scaling -> pooling] -> mixed(din) -> gru_step

Anything that deviates — extra layers, other activations, dropout,
error clipping, sequence memories, unhoisted in-link consumers, shapes
the kernel gates out — falls back to the scan with identical
semantics. The matcher runs only when OptimizationConfig.pallas_decoder
is set (a separate knob from pallas_rnn: this kernel must not become a
default before a measured A/B win).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

Array = jax.Array

_AGENT_TYPES = ("agent", "sequence_agent", "scatter_agent", "gather_agent")


def _clean(cfg) -> bool:
    """No semantics outside the template on an in-scan layer."""
    return cfg.drop_rate == 0.0 and cfg.error_clipping_threshold == 0


def _single_proj(cfg, want_type: str):
    """The layer's single input if it is a `want_type` projection."""
    if len(cfg.inputs) != 1:
        return None
    ic = cfg.inputs[0]
    if ic.proj_conf is None or ic.proj_conf.type != want_type:
        return None
    return ic


def match_decoder(network, sub, ctx, statics, skip, pro_plan) -> Optional[Dict[str, Any]]:
    """Returns the extraction plan, or None when the group is not the
    attention-GRU decoder template (the scan path is always a correct
    fallback; every bail says why once, at debug)."""
    from paddle_tpu.utils import device

    def no(why):
        device.log_selection("pallas_decoder", sub.name, f"scan path ({why})")

    if not ctx.is_training or sub.reversed:
        return no("not a forward training group")
    if ctx.mesh is not None:
        from paddle_tpu.parallel.mesh import data_only_extent

        # a pallas custom call has no GSPMD partitioning rule; under a
        # purely data-parallel mesh the decoder runs per-shard via
        # shard_map (run_fused_decoder) — anything else takes the scan
        if data_only_extent(ctx.mesh) is None:
            return no("mesh has a non-data axis")
    if device.pallas_mode() is None:
        return no(device.why_no_pallas())
    if len(sub.memories) != 1 or sub.memories[0].is_sequence:
        return no("not exactly one non-sequence memory")
    mem = sub.memories[0]
    lm = network.layer_map
    step_layers = [
        lm[n]
        for n in sub.layer_names
        if n not in skip and lm[n].type not in _AGENT_TYPES
    ]
    by_name = {l.name: l for l in step_layers}
    if len(step_layers) != 8 or not all(_clean(l) for l in step_layers):
        return no("step graph is not 8 clean layers")

    # anchor: the gru_step owning the memory
    gru = next((l for l in step_layers if l.type == "gru_step"), None)
    if gru is None or gru.name != mem.layer_name or len(gru.inputs) != 2:
        return no("no gru_step owning the memory")
    if gru.inputs[1].input_layer_name != mem.link_name:
        return no("gru_step does not read the memory")
    D = gru.size

    din = by_name.get(gru.inputs[0].input_layer_name)
    if din is None or din.type != "mixed" or din.size != 3 * D:
        return no("gru input is not a linear 3D-wide mixed layer")
    if din.active_type not in ("", "linear"):
        return no("gru input has an activation")
    # every din input except the context projection must be hoisted
    hoisted = set(pro_plan.get(din.name, ()))
    ctx_idx = [i for i in range(len(din.inputs)) if i not in hoisted]
    if len(ctx_idx) != 1:
        return no("not exactly one unhoisted (context) input")
    ctx_ic = din.inputs[ctx_idx[0]]
    if ctx_ic.proj_conf is None or ctx_ic.proj_conf.type != "fc":
        return no("context input is not an fc projection")

    pooling = by_name.get(ctx_ic.input_layer_name)
    if (
        pooling is None
        or pooling.type != "average"
        or (pooling.average_strategy or "average") != "sum"
        or pooling.trans_type == "seq"
        or pooling.active_type not in ("", "linear")
        or len(pooling.inputs) != 1
    ):
        return no("context is not a sum pooling")

    scaling = by_name.get(pooling.inputs[0].input_layer_name)
    if scaling is None or scaling.type != "scaling" or len(scaling.inputs) != 2:
        return no("no scaling layer under the pooling")
    sm_name, ev_link = (
        scaling.inputs[0].input_layer_name,
        scaling.inputs[1].input_layer_name,
    )
    if ev_link not in statics:
        return no("encoder values are not a static link")

    sm = by_name.get(sm_name)
    if (
        sm is None
        or sm.type != "fc"
        or sm.size != 1
        or sm.active_type != "sequence_softmax"
        or sm.bias_parameter_name
        or len(sm.inputs) != 1
    ):
        return no("attention weights are not a bias-free sequence_softmax fc")

    combine = by_name.get(sm.inputs[0].input_layer_name)
    if (
        combine is None
        or combine.type != "mixed"
        or combine.active_type != "tanh"
        or combine.size != D
        or len(combine.inputs) != 2
    ):
        return no("no tanh combine of two inputs")
    comb_srcs = []
    for ic in combine.inputs:
        if ic.proj_conf is None or ic.proj_conf.type != "identity":
            return no("combine input is not an identity projection")
        comb_srcs.append(ic.input_layer_name)

    expand = next(
        (by_name[n] for n in comb_srcs if n in by_name and by_name[n].type == "expand"),
        None,
    )
    ep_link = next((n for n in comb_srcs if n in statics), None)
    if expand is None or ep_link is None or ep_link == ev_link:
        return no("no expand + static encoder projection pair")
    if not expand.inputs or expand.inputs[0].input_layer_name not in by_name:
        return no("expand does not read a step layer")

    transform = by_name.get(expand.inputs[0].input_layer_name)
    if (
        transform is None
        or transform.type != "mixed"
        or transform.active_type not in ("", "linear")
        or transform.size != D
    ):
        return no("no linear D-wide attention transform")
    tr_ic = _single_proj(transform, "fc")
    if tr_ic is None or tr_ic.input_layer_name != mem.link_name:
        return no("attention transform does not read the memory")

    # the whole template accounted for?
    template = {gru.name, din.name, pooling.name, scaling.name, sm.name,
                combine.name, expand.name, transform.name}
    if template != set(by_name):
        return no("layers outside the template")
    # in-links may only feed the hoisted din inputs
    in_link_names = {l.link_name for l in sub.in_links}
    for l in step_layers:
        for i, ic in enumerate(l.inputs):
            if ic.input_layer_name in in_link_names and not (
                l.name == din.name and i in hoisted
            ):
                return no("an in-link feeds an unhoisted input")

    gru_acts = (gru.active_type or "tanh", gru.active_gate_type or "sigmoid")
    if gru_acts != ("tanh", "sigmoid"):
        return no("gru activations are not tanh/sigmoid")
    return dict(
        gru=gru, din=din, transform=transform, combine=combine, softmax=sm,
        ctx_ic=ctx_ic, tr_ic=tr_ic, ep_link=ep_link, ev_link=ev_link, D=D,
    )


def run_fused_decoder(network, sub, ctx, statics, plan, pro_feeds,
                      boot_carry, mask_bt) -> Optional[Array]:
    """Build kernel operands from the matched plan and run it. Returns
    the RAW per-step GRU output stream [T, B, D], or None when shapes
    fail the kernel gate (caller falls back to the scan)."""
    from paddle_tpu.ops import pallas_attention_gru as pag
    from paddle_tpu.utils import device

    def no(why):
        device.log_selection("pallas_decoder", sub.name, f"scan path ({why})")

    D = plan["D"]
    gru, din = plan["gru"], plan["din"]
    ep_arg = statics[plan["ep_link"]]
    ev_arg = statics[plan["ev_link"]]
    if ep_arg.value is None or ev_arg.value is None or not ep_arg.is_seq:
        return no("encoder statics are not dense sequences")
    B, Te = ep_arg.value.shape[0], ep_arg.value.shape[1]
    E = ev_arg.value.shape[2]
    xw = pro_feeds.get(din.name)
    if xw is None or ep_arg.value.shape[2] != D:
        return no("no hoisted word-side input, or encoder projection not D wide")
    Td = xw.shape[0]
    dtype = xw.dtype
    if dtype not in (jnp.float32, jnp.bfloat16):
        return no(f"dtype {dtype}")
    mode = device.pallas_mode()
    interpret = mode == "interpret"
    data_extent = None
    if ctx.mesh is not None:
        from paddle_tpu.parallel.mesh import data_only_extent

        data_extent = data_only_extent(ctx.mesh)
        if data_extent is None or B % data_extent:
            return no(f"batch {B} does not split over the mesh's data axis")
    B_local = B // (data_extent or 1)
    # the lane-alignment/VMEM gate is a Mosaic-compile constraint; the
    # interpreter (CPU parity tests) takes any shape
    if not interpret and not pag.supported(B_local, Te, D, E,
                                           jnp.dtype(dtype).itemsize):
        return no(f"kernel gate refuses B={B_local} Te={Te} D={D} E={E} {dtype}")
    device.log_selection(
        "pallas_decoder", sub.name,
        f"Pallas kernel, {mode}"
        + (f", shard_map over data={data_extent}" if data_extent else ""))

    wa = ctx.param(plan["tr_ic"].input_parameter_name).reshape(D, D)
    v = ctx.param(plan["softmax"].inputs[0].input_parameter_name).reshape(D, 1)
    wctx = ctx.param(plan["ctx_ic"].input_parameter_name).reshape(E, 3 * D)
    wg = ctx.param(gru.inputs[0].input_parameter_name).reshape(D, 3 * D)

    f32 = jnp.float32
    ba = jnp.zeros((1, D), dtype)
    if plan["transform"].bias_parameter_name:
        ba = ba + ctx.param(plan["transform"].bias_parameter_name).reshape(1, D)
    if plan["combine"].bias_parameter_name:
        ba = ba + ctx.param(plan["combine"].bias_parameter_name).reshape(1, D)
    if din.bias_parameter_name:
        xw = xw + ctx.param(din.bias_parameter_name).reshape(1, 1, 3 * D).astype(dtype)
    if gru.bias_parameter_name:
        xw = xw + ctx.param(gru.bias_parameter_name).reshape(1, 1, 3 * D).astype(dtype)

    ep = jnp.swapaxes(ep_arg.value, 0, 1)                     # [Te, B, D]
    ev = jnp.swapaxes(ev_arg.value, 0, 1)                     # [Te, B, E]
    em = jnp.swapaxes(ep_arg.seq_mask(), 0, 1)[:, :, None].astype(dtype)
    dmask = jnp.swapaxes(mask_bt, 0, 1)[:, :, None].astype(dtype)
    h0 = boot_carry.astype(dtype)

    operands = (ep, ev, em, xw.astype(dtype), dmask, h0,
                wa, ba.astype(wa.dtype), v.reshape(1, D), wctx, wg)
    if data_extent is None:
        return pag.fused_attention_gru(*operands, ("tanh", "sigmoid"),
                                       interpret)
    # purely data-parallel mesh: per-shard execution (each shard's batch
    # rows are independent decodes); weights replicated, batch dims
    # sharded.
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel.mesh import replicated_specs, shard_map_unchecked

    def shard_fn(ep_l, ev_l, em_l, xw_l, dm_l, h0_l, *ws):
        return pag.fused_attention_gru(ep_l, ev_l, em_l, xw_l, dm_l, h0_l,
                                       *ws, ("tanh", "sigmoid"), interpret)

    seq_spec = P(None, "data")
    in_specs = (seq_spec,) * 5 + (P("data"),) + replicated_specs(*operands[6:])
    return shard_map_unchecked(
        shard_fn, ctx.mesh, in_specs=in_specs, out_specs=seq_spec
    )(*operands)
