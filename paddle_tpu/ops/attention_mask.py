"""Attention masks as ONE rule over positions.

A rule says which (query, key) index pairs of a T-long axis may attend,
and which position each index stands for (what a rotary embedding
turns by). The XLA path (`parallel/sequence_parallel.py`), the Pallas
kernel (`ops/pallas_attention.py`) and the tile tables the kernel skips
by (`tile_occupancy`) and pairs partial tiles by (`tile_walk`) all read
the same two functions, so a new mask is one more `kind` here and
nothing else:

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
from typing import NamedTuple

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


class TileWalk(NamedTuple):
    """How the kernels walk a row of tiles (a query tile's key tiles, or,
    transposed, a key tile's query tiles). Every non-empty tile of
    `tile_occupancy` is in it exactly once: as a single (the row's whole
    tiles first, whose step needs no mask, then its partial ones), or as
    one of a PAIR of partial tiles whose allowed sets, in tile-local
    coordinates, are disjoint, so that the kernels merge the two score
    tiles element by element and pay one pass of softmax vector work for
    both."""

    table: np.ndarray        # int32 [rows * width]: a row's single tiles, whole then partial
    pairs: np.ndarray        # int32 [rows * pair_width * 2]: tile A, tile B
    counts: np.ndarray       # int32 [rows * 3]: whole singles, all singles, pairs
    width: int
    pair_width: int          # 0 where no row has a pair
    union_whole: bool        # every pair's two sets together are the whole tile
    census: str              # whole / partial / paired tiles, for the log


def _flat(rows, per):
    """Lists of ints a row -> (flat int32 table, entries of the longest
    row); an entry is ``per`` ints, a short row is padded with zeros."""
    width = max(len(row) for row in rows) // per
    table = np.zeros((len(rows), max(width, 1) * per), np.int32)
    for r, row in enumerate(rows):
        table[r, : len(row)] = row
    table.setflags(write=False)         # cached: every caller gets this array
    return table.reshape(-1), width


@functools.lru_cache(maxsize=64)
def tile_walk(rule: MaskRule, T: int, bq: int, bk: int, transpose: bool = False) -> TileWalk:
    """The walk of `tile_occupancy(rule, T, bq, bk)` (of its transpose: the
    backward kernel's), found from the rule's own masks and from nothing
    else: in a row, a partial tile takes the first later partial tile it
    shares no allowed local (row, column) with; a tile is in at most one
    pair. A causal row has one partial tile and no pair; a window's far
    and near edge tiles pair (two triangles where the window is a multiple
    of the tile: `union_whole`); a noised block-diffusion query tile's own
    noised key tile pairs with its last clean one, and by key tile nothing
    does (the clean key tile's two partial query tiles overlap). Tiles
    ascend within a row's whole and within its partial singles."""
    occ = tile_occupancy(rule, T, bq, bk)
    occ = occ.T if transpose else occ

    def local_mask(r, c):
        i, j = (c, r) if transpose else (r, c)
        m = rule.allowed(np.arange(i * bq, (i + 1) * bq), np.arange(j * bk, (j + 1) * bk), T)
        return np.broadcast_to(m, (bq, bk))

    singles, pairs, counts, union_whole = [], [], [], True
    for r, row in enumerate(occ):
        free = [int(c) for c in np.flatnonzero(row == 2)]
        masks = {c: local_mask(r, c) for c in free} if len(free) > 1 else {}
        paired = []
        while len(free) > 1:
            a = free.pop(0)
            b = next((c for c in free if not (masks[a] & masks[c]).any()), None)
            if b is not None:
                free.remove(b)
                paired += [a, b]
                union_whole &= bool((masks[a] | masks[b]).all())
        whole = [int(c) for c in np.flatnonzero(row == 1)]
        partial = [int(c) for c in np.flatnonzero(row == 2) if c not in paired]
        singles.append(whole + partial)
        pairs.append(paired)
        counts += [len(whole), len(whole) + len(partial), len(paired) // 2]

    table, width = _flat(singles, 1)
    pair_table, pair_width = _flat(pairs, 2)
    counts = np.asarray(counts, np.int32)
    counts.setflags(write=False)
    n_paired = 2 * int(counts[2::3].sum())
    census = (f"{int((occ == 1).sum())} whole + {int((occ == 2).sum()) - n_paired} partial + "
              f"{n_paired} paired tiles in {len(occ)} rows")
    return TileWalk(table, pair_table, counts, max(width, 1), pair_width,
                    union_whole and pair_width > 0, census)
