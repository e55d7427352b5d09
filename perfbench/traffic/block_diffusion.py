"""The traffic generator of block-diffusion training. A mix is a data file
(`perfbench/traffic/<mix>.json`) of parameters; this reads it.

An item is one training sequence of L corpus tokens, fed as the model
reads it: `tokens`, 2L ids, the noised copy x_t then the clean copy x_0;
`labels`, the L clean ids; `weights`, L floats. The noise is DATA: for each
block of `block_length` tokens a level t is drawn uniformly from
[`t_min`, `t_max`], each token of the block is replaced by the mask id with
probability t (the linear schedule), and a masked position weighs 1/t in
the loss, every other 0 (Block Diffusion, arXiv:2503.09573: a masked
position's logits predict that position's own token, no shift). Ids are
uniform over [0, mask id). The program and the plain reference get the
same arrays and neither draws anything.

Arrival: `{"kind": "batches", "batch": B, "cycle": N}`, N batches of B
sequences, fed to a trainer round and round. Every sequence is exactly L
long (no padding), so every seed offers the same work.

The interface is `traffic/general.py`'s, as `entries/train.py` uses it:
`generate`, `samples_of`, `arrays_of`, and on the items `groups`, `group`,
`real_tokens`, `lengths`, `shapes`.
"""

from __future__ import annotations

import numpy as np


def _size(x, sizes):
    return int(sizes[x]) if isinstance(x, str) else int(x)


class Items:
    """`groups` groups of `group` sequences; row r of group g is row
    g * group + r of each array."""

    def __init__(self, group, groups, tokens, labels, weights):
        self.group, self.groups = group, groups
        self.arrays = {"tokens": tokens, "labels": labels, "weights": weights}

    def __len__(self):
        return self.group * self.groups

    def rows(self, g):
        return slice(g * self.group, (g + 1) * self.group)

    def lengths(self, g):
        """Group g's lengths: field -> int array (all alike: no padding)."""
        return {k: np.full(self.group, a.shape[1], np.int64)
                for k, a in self.arrays.items()}

    def shapes(self, g):
        """Group g's shapes as the trainer sees them: field -> (T, B)."""
        return {k: (a.shape[1], self.group) for k, a in self.arrays.items()}

    def real_tokens(self, name, g):
        return int(self.group * self.arrays[name].shape[1])


def generate(mix, sizes, seed):
    """The mix's items for this seed. Same seed, same items."""
    arrival = mix["arrival"]
    if arrival["kind"] != "batches":
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    group, groups = int(arrival["batch"]), int(arrival["cycle"])
    n, length = group * groups, int(mix["length"])
    block = _size(mix["block_length"], sizes)
    mask_id = _size(mix["mask_id"], sizes)
    if length % block:
        raise ValueError(f"{length} tokens do not cut into blocks of {block}")
    noise = mix["noise"]
    rng = np.random.default_rng([int(seed), 0x62646966])
    clean = rng.integers(0, mask_id, size=(n, length), dtype=np.int32)
    # t in (t_min, t_max]: 1 - U[0, 1) is (0, 1]. The range is the mix's: a
    # level near 0 gives a weight 1/t in the hundreds to the odd token
    t = noise["t_min"] + (noise["t_max"] - noise["t_min"]) * (
        1.0 - rng.random((n, length // block)))
    t = np.repeat(t, block, axis=1)
    masked = rng.random((n, length)) < t
    noised = np.where(masked, np.int32(mask_id), clean)
    weights = np.where(masked, 1.0 / t, 0.0).astype(np.float32)
    return Items(group, groups, np.concatenate([noised, clean], axis=1), clean, weights)


def samples_of(items, g):
    """Group g as the list of dicts a @provider yields (python lists: the
    trainer's assembler walks them)."""
    cols = {k: a[items.rows(g)].tolist() for k, a in items.arrays.items()}
    return [{k: cols[k][i] for k in cols} for i in range(items.group)]


def arrays_of(items, g):
    """Group g as arrays for the plain reference: name -> [B, T], `name.len`
    -> [B] lengths."""
    out = {}
    for k, a in items.arrays.items():
        out[k] = a[items.rows(g)]
        out[k + ".len"] = np.full(items.group, a.shape[1], np.int32)
    return out
