"""Sequence/context parallelism: ring attention + all-to-all attention.

Long-context support has no 2016 reference counterpart (SURVEY.md §5 "new
design territory"): the reference's longest-sequence machinery is
host-side batching (SequenceToBatch). Here a sequence is *sharded across
chips* on the mesh's "seq" axis and attention runs over the full context
via ICI collectives:

- ``ring_attention``: blockwise attention with the K/V shards rotating
  around the ring (`lax.ppermute`), combined with a streaming (online
  softmax) accumulator — memory per chip stays O(T/n), comms overlap with
  the next block's compute. The TPU analog of Ring Attention
  (Liu et al. '23) on ICI neighbors.
- ``alltoall_attention``: Ulysses-style — `lax.all_to_all` resharding from
  sequence-sharded to head-sharded, full-context attention locally per
  head group, reshard back. Cheaper comms for moderate contexts; requires
  heads % seq_shards == 0.

Both are differentiable (jax autodiff through the collective), masked for
padded positions, optionally causal, and numerically match the reference
``full_attention`` below — see tests/test_sequence_parallel.py, which runs
them on an 8-device CPU mesh exactly like the reference tests distributed
code on loopback pservers (SURVEY.md §4).

Layout convention: q/k/v are [B, T_local, H, D] under shard_map (T sharded
over "seq"); lengths is the *global* valid-length vector [B], replicated.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array

_NEG = -1e30  # big-negative instead of -inf: keeps fully-masked rows NaN-free

# rings up to this size unroll (XLA overlaps each ppermute with the next
# block's matmuls); larger rings roll with lax.scan so program size stays
# O(1) in n. Module-level so tests can force the scan path on small meshes
# (the 64-chip branch must not be dead untested code).
RING_UNROLL_MAX = 8


def full_attention(
    q: Array, k: Array, v: Array,
    lengths: Optional[Array] = None,
    causal: bool = False,
    q_offset: int = 0,
    kv_offset: int = 0,
) -> Array:
    """Single-device attention over [B, T, H, D] tensors.

    On TPU, self-attention shapes the flash kernel supports dispatch to
    paddle_tpu.ops.pallas_attention (O(T) activation memory); everything
    else takes the XLA path below (which materializes [B, H, T, T]).

    Outputs at padded query rows (positions >= lengths) are unspecified
    and differ between the flash and XLA paths — callers must mask them
    (the mha layer does)."""
    from paddle_tpu.utils import device

    # one debug line per shape: which attention ran and why
    site = f"T={q.shape[1]} D={q.shape[3]}"
    if q_offset or kv_offset or q.shape != k.shape:
        why = "not plain self-attention"
    elif jax.default_backend() != "tpu":
        why = device.why_no_pallas()
    else:
        from paddle_tpu.ops import pallas_attention

        if pallas_attention.supported(q.shape[1], q.shape[3], q.dtype.itemsize):
            device.log_selection("flash_attention", site,
                                 "Pallas kernel, compiled")
            return pallas_attention.flash_attention(
                q, k, v, lengths=lengths, causal=causal
            )
        why = "kernel gate refuses the shape"
    device.log_selection("flash_attention", site, f"XLA path ({why})")
    D = q.shape[-1]
    # scores and softmax in f32 even for bf16 q/k/v: the QK matmul takes
    # bf16 operands with an f32 result; p stays f32 through the PV matmul
    # (matching the ring path's f32 online-softmax state — narrowing p
    # would diverge from it)
    acc_t = jnp.promote_types(q.dtype, jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=acc_t) / math.sqrt(D)
    Tq, Tk = q.shape[1], k.shape[1]
    q_pos = q_offset + jnp.arange(Tq)
    kv_pos = kv_offset + jnp.arange(Tk)
    mask = jnp.ones((Tq, Tk), bool)
    if causal:
        mask &= kv_pos[None, :] <= q_pos[:, None]
    mask = jnp.broadcast_to(mask, (q.shape[0], 1, Tq, Tk))
    if lengths is not None:
        mask &= (kv_pos[None, None, None, :] < lengths[:, None, None, None])
    s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v, preferred_element_type=acc_t)
    return out.astype(q.dtype)


def rule_attention(q, k, v: Array, lengths: Optional[Array], rule,
                   scale: Optional[float] = None) -> Array:
    """Single-device attention of [B, T, H, D] queries over [B, T, Hkv, D]
    keys and values (query head h reads K/V head ``h // (H / Hkv)``) under
    a mask RULE over positions (`ops/attention_mask.MaskRule`).

    ``q`` and ``k`` may be tuples of score PARTS: part i is [B, T, H, D_i]
    against [B, T, Hk_i, D_i], a key head count of its own, and the scores
    are the parts' sum (latent attention: the lanes that differ by head,
    and the rotary lanes all heads read from one key head); ``v`` [B, T,
    Hkv, Dv] has a width of its own, which is the result's.

    Where a Pallas kernel can run (a TPU backend, or interpret mode off
    it) and its gate admits the shape, the flash kernel of
    `ops/pallas_attention` does it, walking only the tiles the rule
    leaves; else the XLA path below, which materializes [B, H, T, T]
    scores and is for small T only. Padded query rows are unspecified, as
    in :func:`full_attention`. ``scale`` multiplies the scores (1/sqrt(D)
    by default, D the parts' sum; 1 where the caller folded it into q)."""
    from paddle_tpu.ops import pallas_attention
    from paddle_tpu.utils import device

    qs, ks = pallas_attention.as_parts(q), pallas_attention.as_parts(k)
    B, T, H, _ = qs[0].shape
    widths = tuple(x.shape[3] for x in qs)
    D, Dv = sum(widths), v.shape[3]
    # "128" for one part of the values' width, "128+64/128" for latent heads
    site = f"T={T} D={'+'.join(map(str, widths))}{'' if Dv == D else f'/{Dv}'} {rule.kind}"
    mode = device.pallas_mode()
    if mode is None:
        why = device.why_no_pallas()
    elif not pallas_attention.supported(T, widths, qs[0].dtype.itemsize, Dv):
        why = "kernel gate refuses the shape"
    else:
        device.log_selection("rule_attention", site, f"Pallas kernel, {mode}")
        device.log_selection("rule_attention", site,
                             f"tile walk: {pallas_attention.walk_census(rule, T)}")
        return pallas_attention.flash_attention(
            q, k, v, lengths=lengths, rule=rule, interpret=mode == "interpret",
            scale=scale)
    device.log_selection("rule_attention", site, f"XLA path ({why})")
    acc_t = jnp.promote_types(qs[0].dtype, jnp.float32)

    def part_scores(qp, kp):                                   # -> [B, H, T, T]
        heads = kp.shape[2]
        qg = qp.reshape(B, T, heads, H // heads, qp.shape[3])
        return jnp.einsum("bqhgd,bkhd->bhgqk", qg, kp,
                          preferred_element_type=acc_t).reshape(B, H, T, T)

    s = sum(map(part_scores, qs[1:], ks[1:]), part_scores(qs[0], ks[0]))
    s = s * (1.0 / math.sqrt(D) if scale is None else scale)
    idx = jnp.arange(T)
    mask = jnp.broadcast_to(rule.allowed(idx, idx, T), (T, T))[None, None]
    if lengths is not None:
        mask = mask & (idx[None, None, None, :] < lengths[:, None, None, None])
    p = jax.nn.softmax(jnp.where(mask, s, _NEG), axis=-1)
    Hkv = v.shape[2]
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.reshape(B, Hkv, H // Hkv, T, T), v,
                     preferred_element_type=acc_t)
    return out.reshape(B, T, H, Dv).astype(qs[0].dtype)


def _ring_attention_local(q, k, v, lengths, causal, axis_name):
    """Per-shard body: stream the K/V ring through an online-softmax
    accumulator. q/k/v: [B, T_loc, H, D] (this shard's block)."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, T_loc, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    q_pos = idx * T_loc + jnp.arange(T_loc)                      # global positions

    # accumulate in f32 regardless of q.dtype: bf16 online-softmax state
    # drifts across ring steps (matches the f32-accumulating flash kernel)
    acc_t = jnp.float32
    o0 = jnp.zeros((B, H, T_loc, D), acc_t)
    m0 = jnp.full((B, H, T_loc), _NEG, acc_t)
    l0 = jnp.zeros((B, H, T_loc), acc_t)
    # under the new shard_map type system fresh constants are unvarying;
    # the loop carry must already vary over the ring axis like q does
    if hasattr(jax.lax, "pcast"):
        o0, m0, l0 = (
            jax.lax.pcast(x, (axis_name,), to="varying") for x in (o0, m0, l0)
        )
    elif hasattr(jax.lax, "pvary"):
        o0, m0, l0 = (jax.lax.pvary(x, (axis_name,)) for x in (o0, m0, l0))
    perm = [(j, (j + 1) % n) for j in range(n)]

    def block(r, o, m, l, k_blk, v_blk):
        src = (idx - r) % n                                      # block owner
        kv_pos = src * T_loc + jnp.arange(T_loc)
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_blk, preferred_element_type=acc_t
        ) * scale
        mask = jnp.ones((T_loc, T_loc), bool)
        if causal:
            mask = kv_pos[None, :] <= q_pos[:, None]
        mask = jnp.broadcast_to(mask, (B, 1, T_loc, T_loc))
        if lengths is not None:
            mask = mask & (kv_pos[None, None, None, :] < lengths[:, None, None, None])
        s = jnp.where(mask, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask, p, 0.0)                              # kill _NEG rows exactly
        l = l * alpha + jnp.sum(p, axis=-1)
        o = o * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk, preferred_element_type=acc_t
        )
        return o, m_new, l

    if n <= RING_UNROLL_MAX:
        # unrolled ring (n is static under shard_map): no permute after the
        # last block, and XLA can overlap each ppermute with the next matmul
        o, m, l = o0, m0, l0
        k_blk, v_blk = k, v
        for r in range(n):
            o, m, l = block(r, o, m, l, k_blk, v_blk)
            if r != n - 1:
                k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
                v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
    else:
        # large rings (e.g. 64-chip seq axis): roll the ring with lax.scan
        # so compile time and program size stay O(1) in n; the last block
        # runs outside the loop so no wasted trailing ppermute
        def body(carry, r):
            o, m, l, k_blk, v_blk = carry
            o, m, l = block(r, o, m, l, k_blk, v_blk)
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            return (o, m, l, k_blk, v_blk), None
        (o, m, l, k_blk, v_blk), _ = jax.lax.scan(
            body, (o0, m0, l0, k, v), jnp.arange(n - 1)
        )
        o, m, l = block(n - 1, o, m, l, k_blk, v_blk)
    o = o / jnp.maximum(l[..., None], 1e-20)
    o = o.astype(q.dtype)
    return jnp.transpose(o, (0, 2, 1, 3))                        # [B, T_loc, H, D]


def _alltoall_attention_local(q, k, v, lengths, causal, axis_name):
    """Per-shard body: reshard seq→heads, full local attention, reshard
    back. Requires H % n == 0."""
    n = jax.lax.psum(1, axis_name)
    B, T_loc, H, D = q.shape

    def seq_to_heads(x):  # [B, T_loc, H, D] -> [B, T_glob, H/n, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):  # inverse
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = full_attention(qg, kg, vg, lengths=lengths, causal=causal)
    return heads_to_seq(out)


def _sharded_attention(q, k, v, lengths, mesh: Mesh, *, causal: bool, axis: str, local_fn):
    if axis not in mesh.axis_names or mesh.shape[axis] == 1:
        return full_attention(q, k, v, lengths=lengths, causal=causal)
    n = mesh.shape[axis]
    assert q.shape[1] % n == 0, (
        f"global seq len {q.shape[1]} must divide the {axis}={n} mesh axis "
        "(pad to a multiple; lengths masking keeps numerics exact)"
    )
    # co-shard the batch over any data axes so composing with data
    # parallelism doesn't all-gather q/k/v across the data dimension
    data_axes = tuple(
        n for n in mesh.axis_names if n in ("data", "expert") and mesh.shape[n] > 1
    )
    b_spec = data_axes if data_axes else None
    seq_spec = P(b_spec, axis, None, None)
    len_spec = P(b_spec)
    shard_fn = functools.partial(local_fn, causal=causal, axis_name=axis)

    mapped = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, len_spec),
        out_specs=seq_spec,
    )
    return mapped(q, k, v, lengths)


def ring_attention(
    q: Array, k: Array, v: Array,
    mesh: Mesh,
    lengths: Optional[Array] = None,
    causal: bool = False,
    axis: str = "seq",
) -> Array:
    """Attention over sequence-sharded q/k/v [B, T_global, H, D]; T_global
    is sharded over ``axis`` by the caller's in_shardings (or replicated
    inputs get partitioned here). Returns the same layout."""
    if lengths is None:
        lengths = jnp.full((q.shape[0],), q.shape[1], jnp.int32)
    return _sharded_attention(
        q, k, v, lengths, mesh, causal=causal, axis=axis, local_fn=_ring_attention_local
    )


def alltoall_attention(
    q: Array, k: Array, v: Array,
    mesh: Mesh,
    lengths: Optional[Array] = None,
    causal: bool = False,
    axis: str = "seq",
) -> Array:
    if lengths is None:
        lengths = jnp.full((q.shape[0],), q.shape[1], jnp.int32)
    if axis in mesh.axis_names:
        assert q.shape[2] % mesh.shape[axis] == 0, (
            f"heads {q.shape[2]} must divide {axis}={mesh.shape[axis]} "
            "(use ring_attention otherwise)"
        )
    return _sharded_attention(
        q, k, v, lengths, mesh, causal=causal, axis=axis,
        local_fn=_alltoall_attention_local,
    )
