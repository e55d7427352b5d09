"""The one general traffic generator. A mix is a data file
(`perfbench/traffic/<mix>.json`) of parameters; this reads it.

A mix describes ITEMS (a training sample, a request) as named fields:

- `sequence`: ids uniform over [low, high) with a length drawn from a named
  length distribution; `high` (and `low`) may be a number or a key of the
  configuration's sizes (`"target_dict_dim"`);
- `shifted`: another sequence field moved right by one with a first id put
  in front (the decoder's input for a next-word target).

and how they ARRIVE: `{"kind": "batches", "batch": B, "cycle": N}`, N
batches of B items, fed to a trainer round and round.

Lengths are stratified: a group (one batch) gets the SAME multiset of
lengths for every seed, namely the group-size quantiles of the distribution,
and the seed only permutes them and draws the ids. So every seed offers the
same work in another order, and every batch has the same shape and the same
number of real tokens.
"""

from __future__ import annotations

from statistics import NormalDist

import numpy as np


def _quantile_lengths(spec, n):
    """n lengths at the (i + 0.5) / n quantiles of the distribution."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(x)) for x in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + q * (spec["max"] + 1 - spec["min"]) - 0.5
    elif spec["dist"] == "fixed":
        v = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", 1 << 30)
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def bucket(n, multiple=8):
    """The padded length the trainer's assembler gives a batch whose longest
    sequence is n (a copy of `paddle_tpu/data/feeder.py::bucket_length`):
    the next multiple of 8 up to 64, then the next power of two."""
    n = max(int(n), 1)
    if n <= 64:
        return -(-n // multiple) * multiple
    p = 64
    while p < n:
        p *= 2
    return p


def _bound(x, sizes):
    return int(sizes[x]) if isinstance(x, str) else int(x)


class Items:
    """`groups` groups of `group` items; field -> (flat ids, lengths).
    Item i of group g is row g * group + i."""

    def __init__(self, group, groups):
        self.group, self.groups = group, groups
        self.seq = {}      # name -> (flat int32, lens int32[n], offsets)

    def __len__(self):
        return self.group * self.groups

    def sequence(self, name, row):
        flat, lens, offs = self.seq[name]
        return flat[offs[row]: offs[row] + lens[row]]

    def group_rows(self, g):
        return range(g * self.group, (g + 1) * self.group)

    def lengths(self, g):
        """Group g's lengths: sequence field -> int array."""
        return {name: lens[g * self.group: (g + 1) * self.group]
                for name, (_flat, lens, _offs) in self.seq.items()}

    def shapes(self, g):
        """Group g's padded shapes as the trainer pads: field -> (T, B)."""
        return {name: (bucket(int(lens.max())), self.group)
                for name, lens in self.lengths(g).items()}

    def padded(self, name, rows):
        """[len(rows), T] ids, zero past each length, and the lengths: T
        as the trainer pads the longest."""
        lens = np.asarray([self.seq[name][1][r] for r in rows], np.int32)
        t = bucket(int(lens.max()))
        out = np.zeros((len(lens), t), np.int32)
        for i, r in enumerate(rows):
            out[i, : lens[i]] = self.sequence(name, r)
        return out, lens

    def real_tokens(self, name, g):
        lens = self.seq[name][1]
        return int(lens[g * self.group: (g + 1) * self.group].sum())


def generate(mix, sizes, seed):
    """The mix's items for this seed. Same seed, same items."""
    arrival = mix["arrival"]
    if arrival["kind"] != "batches":
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    group, groups = int(arrival["batch"]), int(arrival["cycle"])
    rng = np.random.default_rng([int(seed), 0x70657266])
    items = Items(group, groups)
    for f in mix["fields"]:
        name, kind = f["name"], f["kind"]
        if kind == "sequence":
            base = _quantile_lengths(mix["lengths"][f["length"]], group)
            lens = np.concatenate(
                [rng.permutation(base) for _ in range(groups)]).astype(np.int32)
            offs = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int64)
            flat = rng.integers(_bound(f["low"], sizes), _bound(f["high"], sizes),
                                size=int(lens.sum()), dtype=np.int32)
            items.seq[name] = (flat, lens, offs)
        elif kind == "shifted":
            flat0, lens, offs = items.seq[f["of"]]
            flat = np.empty_like(flat0)
            flat[1:] = flat0[:-1]
            flat[offs] = _bound(f["first"], sizes)
            items.seq[name] = (flat, lens, offs)
        else:
            raise ValueError(f"unknown field kind {kind!r}")
    return items


def samples_of(items, g):
    """Group g as the list of dicts a @provider yields (python lists: the
    trainer's assembler walks them)."""
    rows = list(items.group_rows(g))
    cols = {}
    for name, (flat, lens, offs) in items.seq.items():
        lo = int(offs[rows[0]])
        lst = flat[lo: int(offs[rows[-1]] + lens[rows[-1]])].tolist()
        cols[name] = [lst[offs[r] - lo: offs[r] - lo + lens[r]] for r in rows]
    names = list(cols)
    return [{k: cols[k][i] for k in names} for i in range(len(rows))]


def arrays_of(items, g):
    """Group g as padded arrays for the plain reference: name -> [B, T] ids,
    `name.len` -> [B] lengths."""
    rows = list(items.group_rows(g))
    out = {}
    for name in items.seq:
        out[name], out[name + ".len"] = items.padded(name, rows)
    return out
