"""Recurrent-group executor — the RecurrentGradientMachine analog.

Reference: /root/reference/paddle/gserver/gradientmachines/
RecurrentGradientMachine.cpp (1174 LoC). There, the engine clones the
sub-network per timestep (resizeOrCreateFrames :296), scatters sorted
ragged sequences into frames via Scatter/GatherAgentLayers, walks frames
forward then backward, and implements generation as an imperative beam
search (:717, :1114).

TPU-native formulation:
- training/eval: ONE ``lax.scan`` over the padded time axis. Scatter
  agents become per-step slices of [B, T, D]; memory links become scan
  carries (masked so padding passes state through); gather agents are the
  stacked scan outputs. XLA unrolls nothing — one compiled step reused T
  times, backward derived by jax.grad through the scan.
- generation: a ``lax.while_loop`` bounded by max_num_frames implementing
  batched beam search with static shapes (beam reindexing via
  take_along_axis, finished-beam masking) that exits as soon as every
  beam has finished — the replacement for the pointer-chasing beamSearch
  loop. Groups with real sequence in-links generate one step per input
  frame (per-step conditioning); nested in-links feed one whole
  subsequence per step.
- nested (sub-sequence) groups: the outer scan steps over SUBSEQUENCES
  ([B, S, T, D] in-links feed [B, T, D] sequence frames, ref
  createInFrameInfo hasSubseq branch :564); an inner recurrent group in
  the step body scans the tokens — scan-in-scan, still one compiled step.
- sequence-valued memories (memory(is_seq=True), ref createMemoryFrameInfo
  :622): the carry is a whole padded sequence (value, lengths), booted
  from a sequence layer, so step s can read step s-1's full output
  sequence (hierarchical RNN decoders).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.graph.argument import Argument
from paddle_tpu.layers.base import (LayerContext, TimeMajorLogits, forward_layer,
                                   input_mask, publish_counter, register_layer)
from paddle_tpu.ops.activations import apply_activation
from paddle_tpu.proto import LayerConfig, SubModelConfig

Array = jax.Array


@register_layer(
    "agent",
    "sequence_agent",
    "scatter_agent",
    "sequence_scatter_agent",
    "gather_agent",
    "sequence_gather_agent",
)
def _agent_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    raise RuntimeError(
        f"agent layer {cfg.name!r} executed outside a recurrent group — "
        "agents are fed by the group executor"
    )


def forward_recurrent_group(network, cfg: LayerConfig, ctx: LayerContext) -> None:
    sub = network.submodel_map.get(cfg.name)
    assert sub is not None, f"no sub-model named {cfg.name!r}"
    # the group's scan, prologue and epilogue, and every layer of its step,
    # nest under this scope in the program's HLO metadata
    with jax.named_scope(f"{cfg.type}:{cfg.name}"):
        if sub.generator is not None:
            _generate(network, cfg, sub, ctx)
        else:
            _forward_scan(network, cfg, sub, ctx)


# ------------------------------------------------------------- training


def _is_int_carry(x: Array) -> bool:
    return jnp.issubdtype(x.dtype, jnp.integer)


def _carry_to_arg(carry: Array) -> Argument:
    if _is_int_carry(carry):
        return Argument(ids=carry)
    return Argument(value=carry)


def _resolve_outer(sub: SubModelConfig, name: str) -> str:
    """Map an in-group agent name back to the outer layer feeding it."""
    for link in list(sub.static_links) + list(sub.in_links):
        if link.link_name == name:
            return link.layer_name
    return name


def _scope_lookup(ctx: LayerContext, name: str) -> Argument:
    """Group-entry name resolution: this scope, then enclosing scopes.

    Used ONLY for in-links, static links, and memory boot layers — the
    references a nested group may legitimately make to layers outside its
    enclosing group (reference: agent layers connect across frames).
    """
    c = ctx
    while c is not None:
        if name in c.outputs:
            return c.outputs[name]
        c = c.parent
    raise KeyError(f"layer output {name!r} not found in any enclosing scope")


def _memory_boot(network, mem, ctx: LayerContext, batch: int, dtype, sub: SubModelConfig) -> Array:
    size = network.layer_map[mem.link_name].size
    if mem.boot_layer_name:
        boot = _scope_lookup(ctx, _resolve_outer(sub, mem.boot_layer_name)).value
    elif mem.boot_with_const_id >= 0:
        boot = jnp.full((batch,), mem.boot_with_const_id, jnp.int32)
        return boot
    else:
        boot = jnp.zeros((batch, size), dtype)
    if mem.boot_bias_parameter_name:
        boot = boot + ctx.param(mem.boot_bias_parameter_name).reshape(-1)
        boot = apply_activation(mem.boot_bias_active_type, boot)
    return boot


class _DeferredRead:
    """One step's part in a static read whose gradient is taken after the
    scan (see _plan_static_grad): the scaling multiplies the static with its
    gradient stopped, its weights are kept for the product after the loop,
    and the pooling's result gets this step's zero tap."""

    def __init__(self, scaling: str, pooling: str, x_sg: Array, tap: Array):
        self.scaling, self.pooling, self.x_sg, self.tap = scaling, pooling, x_sg, tap
        self.weights = None


def _run_submodel_step(
    network,
    sub: SubModelConfig,
    ctx: LayerContext,
    fed: Dict[str, Argument],
    rng: Optional[Array],
    skip: frozenset = frozenset(),
    mixed_prologue: Optional[Dict[str, Any]] = None,
    deferred: Tuple[_DeferredRead, ...] = (),
    recompute: Optional[Dict[Tuple[str, ...], int]] = None,
) -> Dict[str, Argument]:
    """Run the sub-model's layers once with pre-fed agent outputs.
    ``skip`` names epilogue layers hoisted out of the scan;
    ``mixed_prologue`` maps a mixed layer to (skip_input_indices,
    precomputed [B, out] slice) for projections hoisted BEFORE the scan
    (see _plan_prologue); ``deferred`` are the static reads whose gradient
    the caller takes after the scan; ``recompute`` maps each span the
    backward remakes (see _plan_static_recompute) to the bytes of wide
    residuals it spares a step, which this run writes in."""
    step_ctx = LayerContext(
        params=ctx.params,
        model=ctx.model,
        pass_type=ctx.pass_type,
        rng=rng,
        states=ctx.states,
        dtype=ctx.dtype,
        mesh=ctx.mesh,
        compute_dtype=ctx.compute_dtype,
        no_cast_inputs=ctx.no_cast_inputs,
        scan_unroll=ctx.scan_unroll,
        mixed_prologue=mixed_prologue,
    )
    # the parent link lets an inner group's ENTRY resolution (static
    # links, boot layers, nested in-links) see outer-scope layers without
    # making them resolvable as ordinary layer inputs — a step referencing
    # an outer sequence without StaticInput still fails loudly
    step_ctx.parent = ctx
    step_ctx.outputs.update(fed)
    starts = {s[0]: s for s in recompute or ()}
    spanned = {n for s in recompute or () for n in s}
    for name in sub.layer_names:
        lcfg = network.layer_map[name]
        if lcfg.name in step_ctx.outputs or lcfg.name in skip:
            continue
        if name in starts:
            recompute[starts[name]] = _run_recomputed(network, starts[name], step_ctx)
        if name in spanned:
            continue
        if lcfg.type == "recurrent_layer_group":
            # nested group: the inner executor scans the tokens of this
            # step's subsequence (scan-in-scan)
            forward_recurrent_group(network, lcfg, step_ctx)
            continue
        ins = [
            network._lookup_input(step_ctx, ic.input_layer_name, ic.input_layer_argument)
            for ic in lcfg.inputs
        ]
        for d in deferred:
            if d.scaling == name:
                w, x = ins
                d.weights = jnp.broadcast_to(w.value, x.value.shape[:-1] + (1,))[..., 0]
                ins = [w, x.replace(value=d.x_sg)]
        out = forward_layer(lcfg, ins, step_ctx)
        for d in deferred:
            if d.pooling == name:
                step_ctx.outputs[name] = out.replace(
                    value=out.value + d.tap.astype(out.value.dtype)
                )
    # NOTE: state updates produced inside the scan body (batch_norm moving
    # stats) would be scan tracers — propagating them out would leak.
    # Running statistics are not updated inside recurrent groups
    # (divergence; the reference shares this limitation in practice since
    # BN inside a step sees per-frame batches).
    return step_ctx.outputs


def _memory_feed_arg(mem, carry) -> Argument:
    """Turn a scan/beam carry back into the Argument fed to the step
    (shared by training and generation)."""
    if mem.is_sequence:
        v, sl = carry
        return (
            Argument(ids=v, seq_lengths=sl)
            if _is_int_carry(v)
            else Argument(value=v, seq_lengths=sl)
        )
    return _carry_to_arg(carry)


def _advance_seq_memory(mem, old, out_arg: Argument, Tm: int, n_rows: int):
    """New (value, lengths) for a sequence memory from the linked layer's
    step output, padded/clamped to the FIXED capacity Tm (the boot
    sequence's padded length — XLA carries need static shapes, so a
    carried sequence cannot grow past the boot's capacity; pad the boot
    to the maximum length the step may produce; see doc/divergences.md).
    Callers apply their own keep-mask (per-sample in training, per-beam
    in generation)."""
    old_v, _ = old
    new_v = out_arg.ids if _is_int_carry(old_v) else out_arg.value
    assert new_v.ndim == old_v.ndim, (
        f"sequence memory {mem.layer_name!r}: linked layer must "
        "produce a sequence frame"
    )
    new_v = _pad_time(new_v, Tm)
    if out_arg.seq_lengths is not None:
        new_l = jnp.minimum(out_arg.seq_lengths, Tm)
    else:
        new_l = jnp.full((n_rows,), Tm, jnp.int32)
    return new_v, new_l


def _pad_time(x: Array, T: int) -> Array:
    """Pad or slice axis 1 to exactly T (static shapes for scan carries)."""
    if x.shape[1] == T:
        return x
    if x.shape[1] > T:
        return jax.lax.slice_in_dim(x, 0, T, axis=1)
    pad = [(0, 0), (0, T - x.shape[1])] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, pad)


def _memory_boot_seq(network, mem, ctx: LayerContext, sub: SubModelConfig):
    """Boot a sequence-valued memory (createMemoryFrameInfo seqFlag branch,
    ref RecurrentGradientMachine.cpp:622): the boot layer MUST be a
    sequence; the carry is its padded (value-or-ids, lengths) pair."""
    assert mem.boot_layer_name, (
        f"sequence memory for {mem.layer_name!r} needs a sequence boot layer "
        "(reference: 'boot layer must be a sequence when is_sequence = true')"
    )
    boot = _scope_lookup(ctx, _resolve_outer(sub, mem.boot_layer_name))
    assert boot.is_seq, (
        f"boot layer {mem.boot_layer_name!r} of sequence memory is not a sequence"
    )
    v = boot.value if boot.value is not None else boot.ids
    return (v, boot.seq_lengths)


# layer types that are pure per-row functions of their inputs (no
# sequence/time semantics, no randomness) — safe to re-apply on stacked
# [T*B, D] rows after the scan instead of per step inside it
_HOISTABLE_TYPES = frozenset({"fc", "mixed", "addto", "slope_intercept", "concat"})


def _plan_epilogue(network, sub: SubModelConfig):
    """Split the step graph into (inside, epilogue) for training scans.

    Layers that only feed the group's out-links — never a memory, never
    another inside layer — and are pure per-row ops can run ONCE on the
    stacked scan outputs instead of once per step. The classic win is an
    NMT decoder's vocab-softmax projection: inside the scan it re-reads
    the [D, V] weight from HBM every step and multiplies [B, D] rows;
    hoisted it is a single [T*B, D] x [D, V] matmul. Returns
    (epilogue: ordered layer names, frontier: inside outputs the epilogue
    reads), or None when nothing can be hoisted.
    """
    layer_map = network.layer_map
    names = [n for n in sub.layer_names if n in layer_map]
    name_set = set(names)
    for n in names:
        if layer_map[n].type == "recurrent_layer_group":
            return None  # nested groups: keep everything inside
    # consumers within the step graph
    consumers: Dict[str, set] = {n: set() for n in names}
    for n in names:
        for ic in layer_map[n].inputs:
            if ic.input_layer_name in consumers:
                consumers[ic.input_layer_name].add(n)
    # everything a memory reads must stay inside (the carry depends on it)
    inside_roots = {m.layer_name for m in sub.memories if m.layer_name in name_set}
    must_inside = set()
    stack = list(inside_roots)
    while stack:
        n = stack.pop()
        if n in must_inside:
            continue
        must_inside.add(n)
        for ic in layer_map[n].inputs:
            if ic.input_layer_name in name_set:
                stack.append(ic.input_layer_name)
    out_names = {l.layer_name for l in sub.out_links}

    def hoistable(n):
        lc = layer_map[n]
        return (
            lc.type in _HOISTABLE_TYPES
            and lc.drop_rate == 0.0
            and n not in must_inside
        )

    # reverse-topological growth: a layer joins the epilogue when every
    # step-graph consumer already did (out-link layers additionally have
    # the implicit "out" consumer, which the epilogue serves)
    epilogue: list = []
    in_epi: set = set()
    for n in reversed(names):
        if not hoistable(n):
            continue
        if not consumers[n] and n not in out_names:
            continue  # dead layer — leave it alone
        if all(c in in_epi for c in consumers[n]):
            in_epi.add(n)
            epilogue.append(n)
    epilogue.reverse()
    if not any(n in out_names for n in epilogue):
        return None  # hoisting pays only when an out-link moves out
    # frontier: non-epilogue values the epilogue reads (inside layers or
    # fed agents)
    frontier: list = []
    for n in epilogue:
        for ic in layer_map[n].inputs:
            src = ic.input_layer_name
            if src not in in_epi and src not in frontier:
                frontier.append(src)
    return epilogue, frontier


def _plan_prologue(network, sub: SubModelConfig, epilogue: frozenset):
    """Projection PROLOGUE hoisting: the input-side dual of the epilogue.

    A mixed layer inside the scan often sums a carry-dependent projection
    (attention context) with projections of plain scan inputs (the NMT
    decoder's target-word projection, reference seqToseq_net.py:120-124).
    The scan-input projections are time-parallel: compute them ONCE
    outside the scan as a single [T, B, D] x [D, out] matmul (full MXU
    tiles, one weight read) and feed the per-step slices in as extra scan
    inputs; the step's mixed layer starts its sum from the precomputed
    slice and skips those projection inputs.

    Returns {mixed_layer_name: (input_index, ...)} naming the
    weight-bearing projections (fc/trans_fc) whose source is a plain
    non-subseq in-link agent. Epilogue layers are excluded (they already
    run outside the scan).
    """
    layer_map = network.layer_map
    in_links = {l.link_name for l in sub.in_links if not l.has_subseq}
    plan = {}
    for n in sub.layer_names:
        lc = layer_map.get(n)
        if lc is None or lc.type != "mixed" or n in epilogue:
            continue
        idxs = tuple(
            idx
            for idx, ic in enumerate(lc.inputs)
            if ic.proj_conf is not None
            and ic.proj_conf.type in ("fc", "trans_fc")
            and ic.input_layer_name in in_links
        )
        if idxs:
            plan[n] = idxs
    return plan


def _plan_static_grad(network, sub: SubModelConfig, ctx: LayerContext,
                      statics: Dict[str, Argument], skip: frozenset,
                      frontier) -> Tuple[Tuple[str, str, str], ...]:
    """The static reads whose gradient is taken ONCE, after the scan.

    An attention read (simple_attention's `_scaling` -> `_pooling`) is a
    scaling of a static sequence x by per-position weights w_t, summed by
    a pooling: ctx_t[b] = sum_s w_t[b, s] x[b, s]. Left to autodiff, the
    scan's transpose adds each step's cotangent of the closed-over x into
    a carry of x's own shape, a read and a write of all of x every
    reverse step. That cotangent is w_t[b, s] * d ctx_t[b] (rank one a
    row), so its sum over the steps is one batched product of the stacked
    weights and the stacked d ctx after the loop (_scan_static_grad).

    Returns ((scaling, pooling, static link), ...): every scaling in a
    training group's step that reads a dense static sequence and whose
    result goes to a linear sum pooling alone; empty where there is none.
    Any other read of the static keeps its own gradient path."""
    from paddle_tpu.utils import device

    def no(why):
        device.log_selection("scan_static_grad", sub.name,
                             f"per-step accumulation ({why})")
        return ()

    if not ctx.is_training or sub.generator is not None:
        return no("not a training group")
    if any(l.has_subseq for l in sub.in_links):
        return no("nested group")
    lm = network.layer_map
    names = [n for n in sub.layer_names if n in lm and n not in skip]
    readers: Dict[str, List[str]] = {}
    for n in names:
        for ic in lm[n].inputs:
            readers.setdefault(ic.input_layer_name, []).append(n)
    # a scaling's result read outside the step graph's own layers keeps
    # the per-step path: a memory, an out-link, the hoisted epilogue
    outside = ({m.layer_name for m in sub.memories}
               | {l.layer_name for l in sub.out_links} | set(frontier))
    reads, why = [], "no scaling of a static sequence"
    for n in names:
        sc = lm[n]
        if sc.type != "scaling" or len(sc.inputs) != 2:
            continue
        link = sc.inputs[1].input_layer_name
        x = statics.get(link)
        if x is None or x.value is None or not x.is_seq or x.is_nested_seq \
                or x.value.ndim != 3 or lm[sc.inputs[0].input_layer_name].size != 1:
            continue
        pool = lm.get(readers[n][0]) if len(readers.get(n, ())) == 1 else None
        if (
            n in outside
            or sc.drop_rate or sc.error_clipping_threshold
            or pool is None or pool.type != "average"
            or (pool.average_strategy or "average") != "sum"
            or pool.trans_type == "seq" or len(pool.inputs) != 1
            or pool.active_type not in ("", "linear") or pool.bias_parameter_name
            or pool.drop_rate or pool.error_clipping_threshold
        ):
            why = f"{n} feeds more than a linear sum pooling"
            continue
        reads.append((n, pool.name, link))
    if not reads:
        return no(why)
    device.log_selection(
        "scan_static_grad", sub.name,
        "after the scan: " + ", ".join(f"{s} -> {p} of {l}" for s, p, l in reads))
    return tuple(reads)


def _plan_static_recompute(network, sub: SubModelConfig, ctx: LayerContext,
                           statics: Dict[str, Argument], skip: frozenset,
                           frontier) -> Tuple[Tuple[str, ...], ...]:
    """The spans of step layers that the backward loop recomputes, and the
    forward loop does not stack.

    An additive attention's scores (simple_attention's `_expand` ->
    `_combine` -> `_softmax`) are worked out over every position of a
    static sequence: the step's [B, D] transform is expanded to [B, S, D],
    added to the projected states and put through the tanh, and a size-1
    fc reduces the width away. Left to autodiff, the scan stacks that
    tanh's residuals (its value and 1 - value), T copies of [B, S, D]
    each, written by the forward loop and read back by the backward.
    Under jax.checkpoint the span keeps only its inputs, the [B, D]
    transform and the loop-invariant statics and parameters, and the
    backward loop remakes the tanh from them (_run_recomputed).

    A span starts at an `expand` of a step value as a static sequence,
    takes every `mixed` / `addto` that reads such wide values and static
    sequences only, and ends at each size-1 fc that reads only wide values:
    what leaves it is [B, S, 1]. A [B, D] value is left alone: it is all
    the span would keep, so recomputing it buys nothing. Returns the spans'
    layer names, each in step order; empty where a wide value is read
    outside its span, or there is none."""
    from paddle_tpu.utils import device

    def no(why):
        device.log_selection("scan_recompute_static", sub.name,
                             f"stacked residuals ({why})")
        return ()

    if not ctx.is_training or sub.generator is not None:
        return no("not a training group")
    if any(l.has_subseq for l in sub.in_links):
        return no("nested group")
    lm = network.layer_map
    names = [n for n in sub.layer_names if n in lm and n not in skip]
    if any(lm[n].type == "recurrent_layer_group" for n in names):
        return no("a group inside the step")
    seqs = {l for l, a in statics.items() if a.value is not None and a.is_seq
            and not a.is_nested_seq and a.value.ndim == 3}
    seq_mems = {m.link_name for m in sub.memories if m.is_sequence}
    wide, groups = set(), []
    for n in names:
        lc = lm[n]
        srcs = {ic.input_layer_name for ic in lc.inputs}
        if lc.type == "expand":
            x, layout = (ic.input_layer_name for ic in lc.inputs)
            joins = layout in seqs and not {x} & (wide | set(statics) | seq_mems)
        elif lc.type in ("mixed", "addto"):
            joins = bool(srcs & wide) and srcs <= wide | seqs
        else:
            # the reduce: its activation runs after the checkpoint, so no
            # dropout or error clip may follow the activation in its layer
            joins = (lc.type == "fc" and lc.size == 1 and srcs and srcs <= wide
                     and not lc.drop_rate and not lc.error_clipping_threshold)
        if not joins:
            continue
        if lc.type != "fc":
            wide.add(n)
        # a span is a connected part of the joined layers
        linked = [g for g in groups if srcs & set(g)]
        groups = [g for g in groups if g not in linked] + [sum(linked, []) + [n]]
    spans = [g for g in groups if any(lm[n].type == "fc" for n in g)]
    if not spans:
        return no("no width reduced over a static sequence's positions")
    outside = ({m.layer_name for m in sub.memories}
               | {l.layer_name for l in sub.out_links} | set(frontier))
    spanned = {n for g in groups for n in g}
    for n in names:
        for ic in lm[n].inputs:
            if ic.input_layer_name in wide and n not in spanned:
                return no(f"{ic.input_layer_name} is read by {n}")
    if wide & outside:
        return no(f"{sorted(wide & outside)[0]} leaves the step")
    # a span runs where its first layer stands: what it reads from outside
    # has to be made before that
    order = {n: i for i, n in enumerate(names)}
    spans = tuple(tuple(sorted(g, key=order.get)) for g in spans)
    for g in spans:
        for n in g:
            for ic in lm[n].inputs:
                if order.get(ic.input_layer_name, -1) > order[g[0]] \
                        and ic.input_layer_name not in g:
                    return no(f"{n} reads {ic.input_layer_name}, made after {g[0]}")
    device.log_selection(
        "scan_recompute_static", sub.name,
        "recomputed in the backward: " + "; ".join(" -> ".join(s) for s in spans))
    return spans


def _run_recomputed(network, span: Tuple[str, ...], ctx: LayerContext) -> int:
    """Run a span of _plan_static_recompute under one jax.checkpoint that
    saves nothing: its inputs (a [B, D] step value, statics, parameters)
    are its only residuals, and the backward remakes the wide values from
    them. The reducing fc's activation runs after the checkpoint, so its
    [B, S] residuals are kept as usual and the backward does not remake
    the scores before it needs the wide values. Puts the span's outputs
    into ``ctx.outputs``; returns the bytes of the wide residuals autodiff
    would have kept of the span, a step's worth."""
    lm = network.layer_map
    inside = set(span)
    reads = {(ic.input_layer_name, ic.input_layer_argument)
             for n in span for ic in lm[n].inputs if ic.input_layer_name not in inside}
    read = {network._key(n, a): network._lookup_input(ctx, n, a) for n, a in sorted(reads)}
    leaves = tuple(n for n in span if lm[n].type == "fc")

    def run(params, read, layers):
        sub = dataclasses.replace(ctx, params=params, outputs=dict(read),
                                  state_updates={}, nhwc={}, logits={}, conv_stats={})
        for c in layers:
            ins = [network._lookup_input(sub, ic.input_layer_name, ic.input_layer_argument)
                   for ic in c.inputs]
            forward_layer(c, ins, sub)
        return {n: sub.outputs[n] for n in leaves}

    def kept(params, read):
        # what jax.vjp of the plain span keeps beyond its own inputs
        given = {id(x) for x in jax.tree.leaves((params, read))}
        plain = functools.partial(run, layers=[lm[n] for n in span])
        res = jax.tree.leaves(jax.vjp(plain, params, read)[1])
        return [r for r in res if id(r) not in given]

    wide = [r for r in jax.eval_shape(kept, ctx.params, read)
            if r.ndim == 3 and r.shape[-1] > 1]
    linear = [dataclasses.replace(lm[n], active_type="") if n in leaves else lm[n]
              for n in span]
    made = jax.checkpoint(functools.partial(run, layers=linear), prevent_cse=False)(
        ctx.params, read)
    for n, out in made.items():
        with jax.named_scope(f"{lm[n].type}:{n}"):
            ctx.outputs[n] = out.replace(value=apply_activation(
                lm[n].active_type, out.value, input_mask(out)))
    return sum(r.size * r.dtype.itemsize for r in wide)


def _scan_static_grad(step, init_carries, xs, statics, reads, reverse, unroll):
    """The group's scan with the static reads' gradient taken after it
    (see _plan_static_grad): (ys, frontier values) as jax.lax.scan's.

    The step reads the static under stop_gradient, and each pooling's
    result gets a zero tap, an input of the scan, so d ctx comes back
    stacked [T, B, D] as a scan input's cotangent does, never in a carry.
    A custom VJP round the scan adds sum_t W[t] * d tap[t] to the static's
    cotangent in one product, in float32, cast once; every other input's
    cotangent is the scan's own."""
    T = xs[3].shape[0]  # the time-major step mask
    links = tuple(dict.fromkeys(l for _, _, l in reads))
    vals = {l: statics[l].value for l in links}

    def zero_taps():
        return tuple(
            jnp.zeros((T, vals[l].shape[0], vals[l].shape[2]), vals[l].dtype)
            for _, _, l in reads
        )

    def scan_fn(vals, taps):
        fed = {**statics, **{l: statics[l].replace(value=vals[l]) for l in links}}
        # the stop sits outside the loop, so x enters the scan with no tangent
        sg = {l: jax.lax.stop_gradient(vals[l]) for l in links}
        body = functools.partial(
            step, statics=fed,
            reads=tuple((s, p, sg[l]) for s, p, l in reads),
        )
        _, (ys, frs, weights) = jax.lax.scan(
            body, init_carries, xs[:-1] + (taps,), reverse=reverse, unroll=unroll
        )
        return (ys, frs), weights

    # every array the scan closes over (parameters, the other links, the
    # lengths, the rng key) becomes an explicit input of the custom VJP:
    # one closed over would be a tracer of this trace, stale wherever the
    # call is replayed (a rematerialised loss replays it)
    closed, out_shapes = jax.make_jaxpr(scan_fn, return_shape=True)(vals, zero_taps())
    consts, out_tree = list(closed.consts), jax.tree.structure(out_shapes)

    def conv(vals, taps, *consts):
        out = jax.core.eval_jaxpr(closed.jaxpr, consts, *jax.tree.leaves((vals, taps)))
        return jax.tree.unflatten(out_tree, out)

    # the pooling's mask of each static, [B, S]: an input too, for the same reason
    masks = {l: statics[l].seq_mask() for l in links}

    @jax.custom_vjp
    def run(vals, consts, masks):
        return conv(vals, zero_taps(), *consts)[0]

    def fwd(vals, consts, masks):
        out, vjp_fn, weights = jax.vjp(
            lambda v, c, t: conv(v, t, *c), vals, consts, zero_taps(), has_aux=True
        )
        return out, (vjp_fn, weights, masks)

    def bwd(res, d_out):
        vjp_fn, weights, masks = res
        d_vals, d_consts, d_taps = vjp_fn(d_out)
        d_vals = dict(d_vals)
        for l in links:
            # a scope with a name of its own, so a profile's table by scope
            # shows the product beside the scan it came out of
            with jax.named_scope(f"static_grad:{l}"):
                g = sum(
                    jnp.einsum("tbs,tbd->bsd", w, d, preferred_element_type=jnp.float32)
                    for (_, _, rl), w, d in zip(reads, weights, d_taps) if rl == l
                )
                g = g * masks[l][..., None]
                d_vals[l] = (d_vals[l].astype(jnp.float32) + g).astype(d_vals[l].dtype)
        return d_vals, d_consts, jax.tree.map(jnp.zeros_like, masks)

    run.defvjp(fwd, bwd)
    return run(vals, consts, masks)


def _forward_scan(network, cfg: LayerConfig, sub: SubModelConfig, ctx: LayerContext) -> None:
    assert sub.in_links, f"recurrent group {cfg.name} has no sequence inputs"
    nested = any(link.has_subseq for link in sub.in_links)
    if nested:
        # outer scan over SUBSEQUENCES: [B, S, T, ...] in-links feed
        # [B, T, ...] sequence frames (createInFrameInfo hasSubseq :564)
        ref_link = next(l for l in sub.in_links if l.has_subseq)
        first = _scope_lookup(ctx, ref_link.layer_name)
        assert first.is_nested_seq, (
            f"in-link {ref_link.layer_name!r} marked has_subseq but is not nested"
        )
    else:
        first = _scope_lookup(ctx, sub.in_links[0].layer_name)
        assert first.is_seq, f"in-link {sub.in_links[0].layer_name!r} is not a sequence"
    lengths = first.seq_lengths          # [B]: valid timesteps / subsequences
    B, T = first.batch_size, first.max_len
    mask_bt = first.seq_mask()           # [B, T] (T = S for nested groups)

    # time-major stacked in-link slices; nested links also stack their
    # per-subsequence lengths so each frame is a real sequence Argument
    xs_vals: Dict[str, Array] = {}
    xs_ids: Dict[str, Array] = {}
    xs_sublens: Dict[str, Array] = {}
    for link in sub.in_links:
        arg = _scope_lookup(ctx, link.layer_name)
        if arg.value is not None:
            xs_vals[link.link_name] = jnp.swapaxes(arg.value, 0, 1)
        if arg.ids is not None:
            xs_ids[link.link_name] = jnp.swapaxes(arg.ids, 0, 1)
        if link.has_subseq:
            assert arg.sub_seq_lengths is not None
            xs_sublens[link.link_name] = jnp.swapaxes(arg.sub_seq_lengths, 0, 1)  # [S, B]

    statics: Dict[str, Argument] = {
        link.link_name: _scope_lookup(ctx, link.layer_name) for link in sub.static_links
    }

    memories = list(sub.memories)
    # carry dtype must match the traced computation (x64 gradient checks
    # promote everything), so follow the data rather than ctx.dtype
    carry_dtype = first.value.dtype if first.value is not None else ctx.dtype
    init_carries = []
    seq_mem_T: Dict[int, int] = {}
    for i, mem in enumerate(memories):
        if mem.is_sequence:
            v, sl = _memory_boot_seq(network, mem, ctx, sub)
            seq_mem_T[i] = v.shape[1]
            init_carries.append((v, sl))
        else:
            init_carries.append(_memory_boot(network, mem, ctx, B, carry_dtype, sub))
    init_carries = tuple(init_carries)
    out_links = list(sub.out_links)
    base_rng = ctx.rng

    # epilogue hoisting: pure per-row suffix layers (e.g. the NMT vocab
    # projection) run ONCE on stacked scan outputs instead of per step —
    # one [T*B, D] x [D, V] matmul instead of T weight re-reads. Only for
    # flat groups whose hoisted layers never read a sequence-valued feed.
    plan = None if nested else _plan_epilogue(network, sub)
    if plan is not None:
        # a hoisted layer must never read a sequence-VALUED feed — its
        # per-step input would be [B, T2, D] with lengths the frontier
        # capture can't carry
        seq_feeds = {m.link_name for m in sub.memories if m.is_sequence}
        seq_feeds |= {l.link_name for l in sub.in_links if l.has_subseq}
        seq_feeds |= {l.link_name for l in sub.static_links if l.has_subseq}
        if any(f in seq_feeds for f in plan[1]):
            plan = None
    epilogue, frontier = plan if plan is not None else ([], [])
    # loop-invariant static feeds are rebuilt outside the scan (tiling a
    # [B, D] static T times as scan output would waste memory)
    dyn_frontier = [f for f in frontier if f not in statics]
    skip = frozenset(epilogue)
    inside_out_links = [l for l in out_links if l.layer_name not in skip]

    # prologue hoisting: time-parallel projections of plain scan inputs
    # computed once outside the scan (see _plan_prologue)
    pro_plan = {} if nested else _plan_prologue(network, sub, skip)
    pro_feeds: Dict[str, Array] = {}
    if pro_plan:
        from paddle_tpu.layers.core import apply_projection

        for lname, idxs in pro_plan.items():
            lc = network.layer_map[lname]
            acc = None
            for idx in idxs:
                ic = lc.inputs[idx]
                # the SAME projection code the in-scan path uses, applied
                # to the [T, B, D] time-major stacked in-link in one matmul
                y = apply_projection(
                    ic.proj_conf, ic, Argument(value=xs_vals[ic.input_layer_name]), ctx
                )
                acc = y if acc is None else acc + y
            pro_feeds[lname] = acc

    # fused attention-GRU decoder (OptimizationConfig.pallas_decoder):
    # when the step graph is exactly the simple_attention + gru_step
    # template, the whole time loop runs as ONE pallas launch with the
    # encoder states VMEM-resident per batch block (ops/
    # pallas_attention_gru); the hoisted epilogue then consumes the
    # same raw frontier stream the scan would have produced
    fused_ys = None
    if not nested and ctx.pallas_decoder:
        from paddle_tpu.graph.fused_decoder import match_decoder, run_fused_decoder

        fplan = match_decoder(network, sub, ctx, statics, skip, pro_plan)
        if fplan is not None:
            gname = fplan["gru"].name
            frontier_ok = all(f == gname for f in dyn_frontier)
            links_ok = all(l.layer_name == gname for l in inside_out_links)
            if frontier_ok and links_ok:
                # no try/except here: Mosaic compiles when the enclosing
                # jit is compiled, not when the kernel is traced, so a
                # handler at this call site never sees a kernel the
                # compiler refuses — that is fixed, or gated by the
                # kernel's supported() (tests/test_chip_compile.py)
                fused_ys = run_fused_decoder(
                    network, sub, ctx, statics, fplan, pro_feeds,
                    init_carries[0], mask_bt,
                )

    # attention reads of a static whose gradient is taken after the scan
    reads = () if fused_ys is not None else _plan_static_grad(
        network, sub, ctx, statics, skip, dyn_frontier)
    # attention scores over a static's positions, remade by the backward
    # loop: each span, and the residual bytes it spares a step
    spans = () if fused_ys is not None else _plan_static_recompute(
        network, sub, ctx, statics, skip, dyn_frontier)
    recomputed = dict.fromkeys(spans, 0)

    def step(carries, inp, statics=statics, reads=()):
        x_v, x_i, x_sl, m_t, t_idx, x_pro, x_taps = inp
        fed: Dict[str, Argument] = {}
        for link in sub.in_links:
            name = link.link_name
            fed[name] = Argument(
                value=x_v.get(name),
                ids=x_i.get(name),
                seq_lengths=x_sl.get(name),
            )
        for name, arg in statics.items():
            fed[name] = arg
        for i, (mem, carry) in enumerate(zip(memories, carries)):
            fed[mem.link_name] = _memory_feed_arg(mem, carry)
        rng = jax.random.fold_in(base_rng, t_idx) if base_rng is not None else None
        mixed_pro = {
            lname: (pro_plan[lname], x_pro[lname]) for lname in x_pro
        }
        deferred = tuple(
            _DeferredRead(s, p, x_sg, tap) for (s, p, x_sg), tap in zip(reads, x_taps)
        )
        outs = _run_submodel_step(
            network, sub, ctx, fed, rng, skip=skip, mixed_prologue=mixed_pro,
            deferred=deferred, recompute=recomputed,
        )
        new_carries = []
        m = m_t[:, None]
        for i, (mem, old) in enumerate(zip(memories, carries)):
            out_arg = outs[mem.layer_name]
            if mem.is_sequence:
                old_v, old_l = old
                new_v, new_l = _advance_seq_memory(mem, old, out_arg, seq_mem_T[i], B)
                keep = m_t > 0
                keep_v = keep.reshape((B,) + (1,) * (new_v.ndim - 1))
                new_carries.append(
                    (jnp.where(keep_v, new_v, old_v), jnp.where(keep, new_l, old_l))
                )
            else:
                new = out_arg.value if not _is_int_carry(old) else out_arg.ids
                keep = m > 0 if new.ndim == 2 else m_t > 0
                new_carries.append(jnp.where(keep, new, old))
        ys = []
        for l in inside_out_links:
            out_arg = outs[l.layer_name]
            if out_arg.value.ndim >= 3 and out_arg.seq_lengths is not None:
                # sequence frame (inner-group output): nested result
                # (mask cast keeps bf16 outputs bf16)
                ys.append(
                    (
                        out_arg.value * m_t[:, None, None].astype(out_arg.value.dtype),
                        (out_arg.seq_lengths * m_t.astype(jnp.int32)),
                    )
                )
            else:
                ys.append((out_arg.value * m.astype(out_arg.value.dtype), None))
        # frontier values for the hoisted epilogue — UNMASKED (the final
        # out-link mask is applied after the epilogue, matching the
        # masked-inside semantics exactly)
        fr = tuple((outs[f].value, outs[f].ids) for f in dyn_frontier)
        return tuple(new_carries), (tuple(ys), fr, tuple(d.weights for d in deferred))

    xs = (
        xs_vals,
        xs_ids,
        xs_sublens,
        jnp.swapaxes(mask_bt, 0, 1),
        jnp.arange(T, dtype=jnp.int32),
        pro_feeds,
        (),
    )
    if fused_ys is not None:
        # same (ys, frs) pytree the scan would produce: masked out-link
        # streams + raw frontier values for the hoisted epilogue
        m3 = jnp.swapaxes(mask_bt, 0, 1)[:, :, None].astype(fused_ys.dtype)
        ys = [(fused_ys * m3, None) for _ in inside_out_links]
        frs = tuple((fused_ys, None) for _ in dyn_frontier)
    elif reads:
        ys, frs = _scan_static_grad(
            step, init_carries, xs, statics, reads, bool(sub.reversed), ctx.scan_unroll
        )
    else:
        _, (ys, frs, _) = jax.lax.scan(
            step, init_carries, xs, reverse=bool(sub.reversed), unroll=ctx.scan_unroll
        )
    if recomputed:
        # a gauge: the bytes the forward loop no longer stacks a training step
        publish_counter(cfg, ctx, "scan.recomputed_bytes",
                        T * sum(recomputed.values()), how="max")
    for link, (y, y_lens) in zip(inside_out_links, ys):
        if y_lens is not None:
            # [S, B, T, D] → nested [B, S, T, D] with per-subseq lengths
            ctx.outputs[link.link_name] = Argument(
                value=jnp.swapaxes(y, 0, 1),
                seq_lengths=lengths,
                sub_seq_lengths=jnp.swapaxes(y_lens, 0, 1),
            )
        else:
            ctx.outputs[link.link_name] = Argument(
                value=jnp.swapaxes(y, 0, 1), seq_lengths=lengths
            )
    if epilogue:
        _run_epilogue(
            network, ctx, epilogue, dyn_frontier, frs, statics, out_links,
            B, T, mask_bt, lengths,
        )
    # the group layer itself exposes the first out-link (logits alias
    # included, so a cost wired to the group name keeps the fused path)
    if out_links:
        ctx.outputs[cfg.name] = ctx.outputs[out_links[0].link_name]
        if out_links[0].link_name in ctx.logits:
            ctx.logits[cfg.name] = ctx.logits[out_links[0].link_name]


def _run_epilogue(network, ctx, epilogue, dyn_frontier, frs, statics,
                  out_links, B, T, mask_bt, lengths):
    """Apply hoisted per-row layers once to the stacked scan outputs."""
    epi_ctx = LayerContext(
        params=ctx.params,
        model=ctx.model,
        pass_type=ctx.pass_type,
        rng=None,  # epilogue layers are rng-free by construction
        states=ctx.states,
        dtype=ctx.dtype,
        mesh=ctx.mesh,
        compute_dtype=ctx.compute_dtype,
        no_cast_inputs=ctx.no_cast_inputs,
        scan_unroll=ctx.scan_unroll,
    )
    for name, (v, ids) in zip(dyn_frontier, frs):
        # [T, B, ...] → rows [T*B, ...]
        flat_v = None if v is None else v.reshape((-1,) + v.shape[2:])
        flat_i = None if ids is None else ids.reshape((-1,) + ids.shape[2:])
        epi_ctx.outputs[name] = Argument(value=flat_v, ids=flat_i)
    for name, arg in statics.items():
        # loop-invariant feeds: tile the [B, ...] value across the T rows

        def tile(x):
            if x is None:
                return None
            return jnp.broadcast_to(x[None], (T,) + x.shape).reshape(
                (-1,) + x.shape[1:]
            )

        if name not in epi_ctx.outputs:
            epi_ctx.outputs[name] = Argument(value=tile(arg.value), ids=tile(arg.ids))
    layer_map = network.layer_map
    for name in epilogue:
        lcfg = layer_map[name]
        ins = [
            network._lookup_input(epi_ctx, ic.input_layer_name, ic.input_layer_argument)
            for ic in lcfg.inputs
        ]
        forward_layer(lcfg, ins, epi_ctx)
    hoisted = {l.layer_name for l in out_links} & set(epilogue)
    mask = mask_bt[..., None]
    for link in out_links:
        if link.layer_name not in hoisted:
            continue
        flat = epi_ctx.outputs[link.layer_name].value          # [T*B, D]
        y = jnp.swapaxes(flat.reshape((T, B) + flat.shape[1:]), 0, 1)
        y = y * mask.astype(y.dtype)
        ctx.outputs[link.link_name] = Argument(value=y, seq_lengths=lengths)
        z = epi_ctx.logits.get(link.layer_name)
        if z is not None:
            # re-publish the hoisted layer's pre-softmax logits under the
            # out-link name so the fused cross-entropy path survives the
            # hoist (the probabilities' transpose is then DCE-able when
            # only the loss consumes this link). Published FLAT in the
            # projection's [T*B, V] layout: transposing the V-sized
            # tensor to [B, T, V] here forced a full relayout copy on
            # TPU (layers/base.py TimeMajorLogits) — the CE consumer
            # transposes only the [T, B] per-step costs instead.
            ctx.logits[link.link_name] = TimeMajorLogits(z, T, B)


# ------------------------------------------------------------ generation


def _expand_beams(arg: Argument, K: int) -> Argument:
    """Tile an Argument's batch dim by the beam width: [B, ...] → [B*K, ...]."""

    def rep(x):
        return None if x is None else jnp.repeat(x, K, axis=0)

    return Argument(
        value=rep(arg.value),
        ids=rep(arg.ids),
        seq_lengths=rep(arg.seq_lengths),
        sub_seq_lengths=rep(arg.sub_seq_lengths),
        weight=rep(arg.weight),
    )


def _generate(network, cfg: LayerConfig, sub: SubModelConfig, ctx: LayerContext) -> None:
    """Batched beam search (ref: RecurrentGradientMachine::beamSearch
    :1114 and oneWaySearch :786 — greedy is beam_size=1)."""
    gen = sub.generator
    K = max(int(cfg.beam_size or gen.beam_size), 1)
    L = int(gen.max_num_frames)
    assert L > 0, "generator needs max_num_frames (beam_search max_length)"
    bos, eos = int(cfg.bos_id), int(cfg.eos_id)

    # batch size from any static link or boot layer
    B = None
    statics: Dict[str, Argument] = {}
    for link in sub.static_links:
        arg = _scope_lookup(ctx, link.layer_name)
        statics[link.link_name] = _expand_beams(arg, K)
        B = arg.batch_size if B is None else B
    # real sequence in-links: generation consumes one input frame per step
    # (per-step conditioning — each generated token sees x_t next to the
    # fed-back embedding; sequence length follows the input). A NESTED
    # in-link ([B, S, T, ...] sub-sequences) feeds one whole subsequence
    # per generated step — the step sub-network sees it as a flat
    # sequence, mirroring training's outer-scan-over-subsequences
    # (createInFrameInfo hasSubseq branch) at generation time.
    in_xs_v: Dict[str, Array] = {}
    in_xs_i: Dict[str, Array] = {}
    in_xs_l: Dict[str, Array] = {}  # nested links: per-step inner lengths
    in_lengths = None
    L_in = None
    for link in sub.in_links:
        arg = _scope_lookup(ctx, link.layer_name)
        if link.has_subseq:
            assert arg.is_nested_seq and arg.is_seq, (
                f"generation in-link {link.layer_name!r} marked has_subseq "
                "needs a nested sequence with OUTER lengths "
                "(seq_lengths = subsequence count per sample)"
            )
        else:
            assert arg.is_seq, (
                f"generation in-link {link.layer_name!r} must be a sequence "
                "(wrap whole-sequence conditions in StaticInput(..., is_seq=True))"
            )
        B = arg.batch_size if B is None else B
        # axis 1 is the generation axis either way: frames (flat) or
        # subsequences (nested)
        L_in = arg.max_len if L_in is None else min(L_in, arg.max_len)
        # generation ends at the SHORTEST in-link per sample — a longer
        # link's frames past that point would be padding, not conditioning
        in_lengths = (
            arg.seq_lengths
            if in_lengths is None
            else jnp.minimum(in_lengths, arg.seq_lengths)
        )
        ex = _expand_beams(arg, K)  # [B*K, T|S, ...]
        if ex.value is not None:
            in_xs_v[link.link_name] = jnp.swapaxes(ex.value, 0, 1)  # [T|S, B*K, ...]
        if ex.ids is not None:
            in_xs_i[link.link_name] = jnp.swapaxes(ex.ids, 0, 1)
        if link.has_subseq:
            in_xs_l[link.link_name] = jnp.swapaxes(ex.sub_seq_lengths, 0, 1)  # [S, B*K]
    if L_in is not None:
        L = min(L, L_in)

    memories = list(sub.memories)
    for mem in memories:
        if mem.boot_layer_name and B is None:
            B = _scope_lookup(ctx, mem.boot_layer_name).batch_size
    assert B is not None, f"generation group {cfg.name}: cannot infer batch size"
    gen_dtype = ctx.dtype
    for arg in statics.values():
        if arg.value is not None:
            gen_dtype = arg.value.dtype
            break
    if gen_dtype == ctx.dtype:
        for v in in_xs_v.values():
            gen_dtype = v.dtype
            break
    # boot memories (unexpanded [B, ...] first — the decode-step capture
    # below wants them per SAMPLE, not per beam), then expand across
    # beams: [B, ...] → [B*K, ...]. Sequence-valued memories (seqFlag
    # branch of createMemoryFrameInfo, ref RecurrentGradientMachine.cpp:
    # 740-744) carry a (padded sequence, lengths) pair so step s reads
    # step s-1's FULL output sequence — hierarchical decoders at
    # generation time.
    boots = []
    seq_mem_T: Dict[int, int] = {}
    for i, mem in enumerate(memories):
        if mem.is_sequence:
            v, sl = _memory_boot_seq(network, mem, ctx, sub)
            seq_mem_T[i] = v.shape[1]
            boots.append((v, sl))
        else:
            boots.append(_memory_boot(network, mem, ctx, B, gen_dtype, sub))

    # the feed agent for previously generated ids (created by beam_search())
    predict_agent = f"__generated_id@{cfg.name}"
    assert predict_agent in network.layer_map, "generation group missing the generated-id agent"
    score_layer = sub.out_links[0].layer_name

    if ctx.gen_capture is not None:
        # per-step decoder seam (graph/decode_step.py): the serving
        # engine's prefill runs the graph up to here — encoder outputs
        # (static links) and memory boots, per sample — and takes over
        # the decode loop itself, one slot-batched step per launch.
        # Outputs are zero placeholders: a capture forward exists only
        # for its captured side channel.
        ctx.gen_capture.update(
            group=cfg.name,
            statics={link.link_name: _scope_lookup(ctx, link.layer_name)
                     for link in sub.static_links},
            boots=list(boots),
            batch=B,
            dtype=gen_dtype,
        )
        zeros = Argument(ids=jnp.zeros((B, L), jnp.int32),
                         seq_lengths=jnp.zeros((B,), jnp.int32))
        ctx.outputs[cfg.name] = zeros
        ctx.outputs[f"{cfg.name}@beams"] = Argument(
            ids=jnp.zeros((B, K, L), jnp.int32),
            value=jnp.zeros((B, K), gen_dtype),
            seq_lengths=jnp.full((B,), K, jnp.int32),
            sub_seq_lengths=jnp.zeros((B, K), jnp.int32),
        )
        ctx.outputs[score_layer] = ctx.outputs[cfg.name]
        return

    carries0 = []
    for mem, boot in zip(memories, boots):
        if mem.is_sequence:
            v, sl = boot
            carries0.append((jnp.repeat(v, K, axis=0), jnp.repeat(sl, K, axis=0)))
        else:
            carries0.append(jnp.repeat(boot, K, axis=0))
    carries0 = tuple(carries0)

    neg_inf = jnp.asarray(-1e30, gen_dtype)
    init_state = (
        carries0,
        jnp.full((B * K,), bos, jnp.int32),                  # prev token per beam
        jnp.concatenate(                                      # cum log prob [B, K]
            [jnp.zeros((B, 1), gen_dtype), jnp.full((B, K - 1), neg_inf, gen_dtype)], axis=1
        )
        if K > 1
        else jnp.zeros((B, 1), gen_dtype),
        # an empty in-link sequence is finished before step 0 (no frame to
        # condition on) — generates length 0, not one garbage token
        (
            jnp.zeros((B, K), bool)
            if in_lengths is None
            else jnp.broadcast_to((in_lengths <= 0)[:, None], (B, K))
        ),
        jnp.zeros((B, K, L), jnp.int32),                      # token history
        jnp.zeros((B, K), jnp.int32),                         # lengths
    )
    base_rng = ctx.rng

    def step(state, inp):
        t_idx, x_v, x_i, x_l = inp
        carries, prev_tok, cum, finished, history, lens = state
        fed: Dict[str, Argument] = {predict_agent: Argument(ids=prev_tok)}
        for link in sub.in_links:
            fed[link.link_name] = Argument(
                value=x_v.get(link.link_name),
                ids=x_i.get(link.link_name),
                # nested links feed one whole subsequence per step
                seq_lengths=x_l.get(link.link_name),
            )
        for name, arg in statics.items():
            fed[name] = arg
        for mem, carry in zip(memories, carries):
            fed[mem.link_name] = _memory_feed_arg(mem, carry)
        rng = jax.random.fold_in(base_rng, t_idx) if base_rng is not None else None
        outs = _run_submodel_step(network, sub, ctx, fed, rng)
        probs = outs[score_layer].value  # [B*K, V]
        V = probs.shape[-1]
        logp = jnp.log(jnp.clip(probs, 1e-20, None)).reshape(B, K, V)
        fin = finished[:, :, None]
        # finished beams may only "emit" eos with no score change; every
        # other candidate is dead (-inf, not the clip floor, else a
        # finished beam's V-1 ghosts can outrank live continuations)
        eos_onehot = jax.nn.one_hot(eos, V, dtype=logp.dtype)
        logp = jnp.where(fin, jnp.where(eos_onehot[None, None, :] > 0, 0.0, neg_inf), logp)
        total = cum[:, :, None] + logp  # [B, K, V]
        flat = total.reshape(B, K * V)
        top_scores, top_idx = jax.lax.top_k(flat, K)  # [B, K]
        beam_idx = top_idx // V                        # [B, K]
        token = (top_idx % V).astype(jnp.int32)        # [B, K]
        # advance memories with this step's outputs (finished beams freeze
        # their state), then reindex by the selected beams
        flat_sel = (jnp.arange(B)[:, None] * K + beam_idx).reshape(-1)  # [B*K]
        fin_flat = finished.reshape(-1)

        def freeze(old, new):
            keep = fin_flat.reshape((-1,) + (1,) * (new.ndim - 1))
            return jnp.where(keep if new.ndim > 1 else fin_flat, old, new)

        new_carries = []
        for i, (mem, old) in enumerate(zip(memories, carries)):
            out_arg = outs[mem.layer_name]
            if mem.is_sequence:
                old_v, old_l = old
                new_v, new_l = _advance_seq_memory(mem, old, out_arg, seq_mem_T[i], B * K)
                new_carries.append(
                    (freeze(old_v, new_v)[flat_sel], freeze(old_l, new_l)[flat_sel])
                )
            else:
                new = out_arg.ids if _is_int_carry(old) else out_arg.value
                new_carries.append(freeze(old, new)[flat_sel])
        new_carries = tuple(new_carries)
        finished = jnp.take_along_axis(finished, beam_idx, axis=1)
        lens = jnp.take_along_axis(lens, beam_idx, axis=1)
        history = jnp.take_along_axis(history, beam_idx[:, :, None], axis=1)
        history = history.at[:, :, t_idx].set(jnp.where(finished, eos, token))
        lens = jnp.where(finished, lens, lens + 1)
        finished = finished | (token == eos)
        if in_lengths is not None:
            # real in-links bound the generation: a sequence ends with its
            # last input frame even without eos
            finished = finished | ((t_idx + 1) >= in_lengths[:, None])
        return (
            new_carries,
            token.reshape(-1),
            top_scores,
            finished,
            history,
            lens,
        ), None

    # while_loop instead of a fixed-L scan: generation stops as soon as
    # every beam of every sample has finished (eos / in-link exhausted) —
    # with the default max_length=500 and typical outputs of tens of
    # tokens this is the difference between L steps and ~longest-output
    # steps per batch. Generation is never differentiated, so while_loop's
    # no-reverse-AD limitation does not bite.
    in_v = {k: v[:L] for k, v in in_xs_v.items()}
    in_i = {k: v[:L] for k, v in in_xs_i.items()}
    in_l = {k: v[:L] for k, v in in_xs_l.items()}

    def cond(carry):
        t, state = carry
        return (t < L) & ~jnp.all(state[3])  # state[3] = finished [B, K]

    def body(carry):
        t, state = carry
        inp = (
            t,
            {k: v[t] for k, v in in_v.items()},
            {k: v[t] for k, v in in_i.items()},
            {k: v[t] for k, v in in_l.items()},
        )
        state, _ = step(state, inp)
        return t + 1, state

    _, state = jax.lax.while_loop(cond, body, (jnp.asarray(0, jnp.int32), init_state))
    _, _, scores, finished, history, lens = state
    # best beam per sample (beams are kept sorted by top_k, but normalize
    # defensively by picking argmax score)
    best = jnp.argmax(scores, axis=1)  # [B]
    best_tokens = jnp.take_along_axis(history, best[:, None, None], axis=1)[:, 0]  # [B, L]
    best_lens = jnp.take_along_axis(lens, best[:, None], axis=1)[:, 0]
    ctx.outputs[cfg.name] = Argument(ids=best_tokens, seq_lengths=best_lens)
    ctx.outputs[f"{cfg.name}@beams"] = Argument(
        ids=history, value=scores, seq_lengths=jnp.full((B,), K, jnp.int32),
        sub_seq_lengths=lens,
    )
    ctx.outputs[score_layer] = ctx.outputs[cfg.name]
