"""Sequence manipulation layers.

Reference counterparts: MaxLayer/AverageLayer (SequencePool subtypes),
SequenceLastInstanceLayer, ExpandLayer, SequenceConcatLayer,
SequenceReshapeLayer, SubSequenceLayer
(/root/reference/paddle/gserver/layers/). The reference walks ragged rows
via sequenceStartPositions; here everything is masked reductions/gathers on
padded [B, T, D] — XLA turns these into fused reduce/gather kernels.

``trans_type`` ("non-seq" | "seq") mirrors the reference's pooling levels
(AggregateLevel): "non-seq" (default) aggregates the WHOLE outer
sequence — a nested input flattens to one row per sample; "seq"
aggregates each SUBSEQUENCE (nested input required) → output is a plain
sequence over subsequences.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from paddle_tpu.graph.argument import Argument
from paddle_tpu.layers.base import LayerContext, finalize_output, register_layer
from paddle_tpu.proto import LayerConfig

Array = jax.Array


def _pool(cfg: LayerConfig, a: Argument, mode: str) -> Argument:
    """Masked pooling at the configured trans_type level (see the
    module docstring for the AggregateLevel semantics)."""
    per_subseq = cfg.trans_type == "seq"
    if per_subseq:
        assert a.is_nested_seq, (
            f"{cfg.name}: trans_type='seq' needs a nested (sub-sequence) "
            "input (reference: 'input must hasSubseq')"
        )
    if a.is_nested_seq and per_subseq:
        mask = a.sub_seq_mask()  # [B, S, T]
        x = a.value  # [B, S, T, D]
        axis = 2
        lengths = a.sub_seq_lengths
        out_meta = dict(seq_lengths=a.seq_lengths)
    elif a.is_nested_seq:
        # "non-seq" over a nested input: one row per SAMPLE, all valid
        # tokens of all subsequences participate
        mask = a.sub_seq_mask()  # [B, S, T]
        x = a.value  # [B, S, T, D]
        axis = (1, 2)
        lengths = jnp.sum(a.sub_seq_lengths, axis=1)  # total tokens [B]
        out_meta = {}
    else:
        assert a.is_seq, f"{cfg.name}: pooling a non-sequence input"
        mask = a.seq_mask()  # [B, T]
        x = a.value  # [B, T, D]
        axis = 1
        lengths = a.seq_lengths
        out_meta = {}
    m = mask[..., None].astype(x.dtype)  # keep bf16 activations bf16
    if mode == "max":
        neg = jnp.finfo(x.dtype).min
        out = jnp.max(jnp.where(m > 0, x, neg), axis=axis)
        out = jnp.where(lengths[..., None] > 0, out, 0.0)
    else:
        s = jnp.sum(x * m, axis=axis)
        n = jnp.clip(lengths[..., None].astype(x.dtype), 1.0, None)
        if mode == "sum":
            out = s
        elif mode == "squarerootn":
            out = s / jnp.sqrt(n)
        else:  # average
            out = s / n
    return Argument(value=out, **out_meta)


@register_layer("max")
def max_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    out = _pool(cfg, inputs[0], "max")
    if cfg.output_max_index:
        # ref: MaxLayer with output_max_index — emit argmax positions.
        a = inputs[0]
        if a.is_nested_seq and cfg.trans_type != "seq":
            raise NotImplementedError(
                f"{cfg.name}: output_max_index over a whole nested sequence "
                "(trans_type='non-seq') is unsupported — use trans_type='seq' "
                "for per-subsequence indices"
            )
        mask = a.sub_seq_mask() if a.is_nested_seq else a.seq_mask()
        neg = jnp.finfo(a.value.dtype).min
        axis = 2 if a.is_nested_seq else 1
        idx = jnp.argmax(jnp.where(mask[..., None] > 0, a.value, neg), axis=axis)
        return Argument(ids=idx.astype(jnp.int32), seq_lengths=out.seq_lengths)
    v = finalize_output(cfg, out.value, ctx)
    return Argument(value=v, seq_lengths=out.seq_lengths)


@register_layer("average")
def average_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    mode = cfg.average_strategy or "average"
    out = _pool(cfg, inputs[0], mode)
    v = finalize_output(cfg, out.value, ctx)
    return Argument(value=v, seq_lengths=out.seq_lengths)


def _select_instance(cfg: LayerConfig, a: Argument, first: bool) -> Argument:
    """First/last instance at the configured trans_type level (see the
    module docstring for the AggregateLevel semantics)."""
    per_subseq = cfg.trans_type == "seq"
    if per_subseq:
        assert a.is_nested_seq, (
            f"{cfg.name}: trans_type='seq' needs a nested (sub-sequence) "
            "input (reference: 'input must hasSubseq')"
        )
        x, lengths = a.value, a.sub_seq_lengths  # [B,S,T,D], [B,S]
        idx = jnp.zeros_like(lengths) if first else jnp.clip(lengths - 1, 0, None)
        out = jnp.take_along_axis(x, idx[..., None, None], axis=2)[:, :, 0]
        return Argument(value=out, seq_lengths=a.seq_lengths)
    if a.is_nested_seq:
        # whole-sequence instance over a nested input: first token of the
        # first NON-EMPTY subsequence / last token of the last non-empty
        # one (empty subsequences hold only padding)
        B, S = a.value.shape[:2]
        n_subs = (
            a.seq_lengths
            if a.seq_lengths is not None
            else jnp.full((B,), S, jnp.int32)
        )
        s_iota = jnp.arange(S, dtype=jnp.int32)[None, :]
        valid = (s_iota < n_subs[:, None]) & (a.sub_seq_lengths > 0)
        if first:
            s_idx = jnp.min(jnp.where(valid, s_iota, S), axis=1)
        else:
            s_idx = jnp.max(jnp.where(valid, s_iota, -1), axis=1)
        s_idx = jnp.clip(s_idx, 0, S - 1)
        sub = jnp.take_along_axis(a.value, s_idx[:, None, None, None], axis=1)[:, 0]
        sub_len = jnp.take_along_axis(a.sub_seq_lengths, s_idx[:, None], axis=1)[:, 0]
        t_idx = jnp.zeros_like(sub_len) if first else jnp.clip(sub_len - 1, 0, None)
        out = jnp.take_along_axis(sub, t_idx[:, None, None], axis=1)[:, 0]
        return Argument(value=out)
    assert a.is_seq
    x, lengths = a.value, a.seq_lengths
    idx = jnp.zeros_like(lengths) if first else jnp.clip(lengths - 1, 0, None)
    out = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    return Argument(value=out)


@register_layer("seqlastins")
def seq_last_ins_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    out = _select_instance(cfg, inputs[0], first=cfg.select_first)
    return Argument(value=finalize_output(cfg, out.value, ctx), seq_lengths=out.seq_lengths)


@register_layer("seqfirstins")
def seq_first_ins_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    out = _select_instance(cfg, inputs[0], first=True)
    return Argument(value=finalize_output(cfg, out.value, ctx), seq_lengths=out.seq_lengths)


@register_layer("expand")
def expand_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: ExpandLayer — broadcast a dense (or seq-level) input along the
    # sequence layout of the second input.
    src, layout = inputs[0], inputs[1]
    if layout.is_nested_seq and src.is_seq:
        # seq over subseqs → nested: broadcast each subsequence value over T
        out = jnp.broadcast_to(
            src.value[:, :, None, :], layout.value.shape[:3] + (src.value.shape[-1],)
        )
        return Argument(value=out, seq_lengths=layout.seq_lengths, sub_seq_lengths=layout.sub_seq_lengths)
    T = layout.max_len
    out = jnp.broadcast_to(src.value[:, None, :], (src.value.shape[0], T, src.value.shape[-1]))
    return Argument(value=out, seq_lengths=layout.seq_lengths)


@register_layer("seqconcat")
def seq_concat_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: SequenceConcatLayer — concatenate two sequences in time per
    # sample. Padded impl: place b after a's valid region via gather.
    a, b = inputs[0], inputs[1]
    Ta, Tb = a.max_len, b.max_len
    T = Ta + Tb
    la, lb = a.seq_lengths, b.seq_lengths
    pos = jnp.arange(T, dtype=jnp.int32)[None, :]  # [1, T]
    from_a = pos < la[:, None]
    idx_a = jnp.clip(pos, 0, Ta - 1)
    idx_b = jnp.clip(pos - la[:, None], 0, Tb - 1)
    ga = jnp.take_along_axis(a.value, idx_a[..., None], axis=1)
    gb = jnp.take_along_axis(b.value, idx_b[..., None], axis=1)
    out = jnp.where(from_a[..., None], ga, gb)
    lengths = la + lb
    valid = pos < lengths[:, None]
    out = jnp.where(valid[..., None], out, 0.0)
    return Argument(value=finalize_output(cfg, out, ctx), seq_lengths=lengths)


@register_layer("seqreshape")
def seq_reshape_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: SequenceReshapeLayer — reinterpret [B, T, D] as [B, T*D/size,
    # size]; only exact multiples are meaningful with padding.
    a = inputs[0]
    B, T, D = a.value.shape
    new_T = T * D // cfg.size
    out = a.value.reshape(B, new_T, cfg.size)
    lengths = (a.seq_lengths * D) // cfg.size
    return Argument(value=finalize_output(cfg, out, ctx), seq_lengths=lengths)


@register_layer("subseq")
def sub_seq_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # ref: SubSequenceLayer — inputs: (sequence, offsets, sizes); output is
    # the slice [offset, offset+size) of each sequence.
    a, offs, sizes = inputs[0], inputs[1], inputs[2]
    o = (offs.ids if offs.ids is not None else offs.value[..., 0].astype(jnp.int32)).reshape(-1)
    s = (sizes.ids if sizes.ids is not None else sizes.value[..., 0].astype(jnp.int32)).reshape(-1)
    T = a.max_len
    pos = jnp.arange(T, dtype=jnp.int32)[None, :]
    idx = jnp.clip(pos + o[:, None], 0, T - 1)
    out = jnp.take_along_axis(a.value, idx[..., None], axis=1)
    valid = pos < s[:, None]
    out = jnp.where(valid[..., None], out, 0.0)
    return Argument(value=finalize_output(cfg, out, ctx), seq_lengths=s)


@register_layer("seq_slice")
def seq_slice_layer(cfg: LayerConfig, inputs: List[Argument], ctx: LayerContext) -> Argument:
    # TPU extension: the (padded) time axis cut into `seq_parts` equal parts,
    # part `seq_part` kept: [B, T, D] -> [B, T / parts, D]. A sequence's
    # length becomes what of it lies inside the part. (Block-diffusion
    # training feeds the noised and the clean copy as one 2L-long sequence
    # and takes its loss over the first L.)
    arg = inputs[0]
    ref = arg.value if arg.value is not None else arg.ids
    assert arg.is_seq and not arg.is_nested_seq, f"{cfg.name}: seq_slice needs a plain sequence"
    T = ref.shape[1]
    assert T % cfg.seq_parts == 0, f"{cfg.name}: {T} positions do not cut into {cfg.seq_parts} parts"
    n = T // cfg.seq_parts
    lo = cfg.seq_part * n
    cut = lambda x: None if x is None else x[:, lo:lo + n]
    return Argument(value=cut(arg.value), ids=cut(arg.ids),
                    seq_lengths=jnp.clip(arg.seq_lengths - lo, 0, n))
