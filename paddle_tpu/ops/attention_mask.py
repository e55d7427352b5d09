"""Attention masks as ONE rule over positions.

A rule says which (query, key) index pairs of a T-long axis may attend,
and which position each index stands for (what a rotary embedding
turns by). The XLA path (`parallel/sequence_parallel.py`), the Pallas
kernel (`ops/pallas_attention.py`) and the tile tables the kernel skips
by all read the same two functions, so a new mask is one more `kind`
here and nothing else:

- ``attrs(idx, T)``: a tuple of int arrays shaped like ``idx``, what the
  rule needs to know of an index (computed outside the kernels, where
  ``//`` and ``%`` are cheap);
- ``allowed_from(q_attrs, k_attrs)``: comparisons only, broadcast over
  whatever shapes the two sides have. Works on numpy and jax arrays.

Kinds:

- ``full``: every pair.
- ``causal``: ``k <= q``.
- ``sliding_window`` (with ``window``): ``k <= q and q - k < window``: a
  query sees itself and the ``window - 1`` positions before it.
- ``block_diffusion`` (Block Diffusion, arXiv:2503.09573, the training
  mask): the axis holds two copies of an L = T/2 long sequence, the
  noised copy at 0..L-1 and the clean copy at L..2L-1; index i stands
  for position ``i mod L``, in block ``(i mod L) // block_length``. A
  noised query sees the noised keys of its own block and the clean keys
  of EARLIER blocks; a clean query sees the clean keys of its own and
  earlier blocks; nothing sees a later block, and a clean query never a
  noised key. The axis is the padded one: every sequence of the batch
  must fill it (both copies L long).
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

KINDS = ("full", "causal", "sliding_window", "block_diffusion")


@dataclasses.dataclass(frozen=True)
class MaskRule:
    kind: str = "full"
    block_length: int = 0
    window: int = 0

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"attention_mask must be one of {KINDS}, got {self.kind!r}")
        if self.kind == "block_diffusion" and self.block_length < 1:
            raise ValueError("block_diffusion needs block_length >= 1")
        if self.kind == "sliding_window" and self.window < 1:
            raise ValueError("sliding_window needs window >= 1")

    # ------------------------------------------------------------ the rule

    def positions(self, idx, T):
        """The position index ``idx`` stands for (rotary angle)."""
        if self.kind == "block_diffusion":
            return idx % (T // 2)
        return idx

    def attrs(self, idx, T):
        if self.kind == "block_diffusion":
            L = T // 2
            return (idx // L, (idx % L) // self.block_length)
        return (idx,)

    def allowed_from(self, q, k):
        if self.kind == "full":
            return (q[0] >= 0) & (k[0] >= 0)
        if self.kind == "causal":
            return k[0] <= q[0]
        if self.kind == "sliding_window":
            return (k[0] <= q[0]) & (q[0] - k[0] < self.window)
        q_clean, q_blk = q
        k_clean, k_blk = k
        noised_q = (q_clean == 0) & (
            ((k_clean == 0) & (k_blk == q_blk)) | ((k_clean == 1) & (k_blk < q_blk)))
        clean_q = (q_clean == 1) & (k_clean == 1) & (k_blk <= q_blk)
        return noised_q | clean_q

    def allowed(self, q_idx, k_idx, T):
        """[len(q_idx), len(k_idx)] bool: may query index attend key index."""
        qa = tuple(a[:, None] for a in self.attrs(q_idx, T))
        ka = tuple(a[None, :] for a in self.attrs(k_idx, T))
        return self.allowed_from(qa, ka)

    def check(self, T):
        if self.kind == "block_diffusion" and (
                T % 2 or (T // 2) % self.block_length):
            raise ValueError(
                f"block_diffusion({self.block_length}) needs an axis of two "
                f"copies of whole blocks, got T={T}")


def rule_of(kind: str = "", block_length: int = 0, causal: bool = False,
            window: int = 0) -> MaskRule:
    """The rule a layer's config names (``causal`` is the old flag)."""
    return MaskRule(kind or ("causal" if causal else "full"), int(block_length), int(window))


# ------------------------------------------------------------- tile tables


@functools.lru_cache(maxsize=64)
def tile_occupancy(rule: MaskRule, T: int, bq: int, bk: int) -> np.ndarray:
    """int8 [T/bq, T/bk]: 0 where the rule empties the tile, 1 where it
    allows every pair of it, 2 where it allows some. Evaluated a row of
    tiles at a time (never the whole T x T)."""
    nq, nk = T // bq, T // bk
    k_idx = np.arange(T)
    out = np.zeros((nq, nk), np.int8)
    for i in range(nq):
        m = rule.allowed(np.arange(i * bq, (i + 1) * bq), k_idx, T)
        m = np.broadcast_to(m, (bq, T)).reshape(bq, nk, bk)
        some, every = m.any(axis=(0, 2)), m.all(axis=(0, 2))
        out[i] = np.where(every, 1, np.where(some, 2, 0))
    out.setflags(write=False)       # cached: every caller gets this array
    return out


def tile_lists(occ: np.ndarray):
    """Row r's non-empty tiles, as the kernels read them: a flat int32
    table [rows * width] of ``2 * tile + partial`` (padded with the row's
    last entry) and the int32 count a row."""
    rows = occ.shape[0]
    counts = (occ > 0).sum(axis=1).astype(np.int32)
    width = max(int(counts.max()), 1)
    table = np.zeros((rows, width), np.int32)
    for r in range(rows):
        ids = np.flatnonzero(occ[r])
        codes = 2 * ids + (occ[r, ids] == 2)
        if len(codes):
            table[r, : len(codes)] = codes
            table[r, len(codes):] = codes[-1]
    return table.reshape(-1), counts, width
