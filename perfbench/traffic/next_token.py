"""Next-token training batches: `traffic/general.py`'s items (its
`sequence` and `shifted` fields, its lengths, its arrival) with one thing
more for the plain reference's side: `arrays_of` adds `weights`, float32
[B, T], 1 on every real position of `labels` and 0 on padding. Every
position weighs 1 in such a mix, so the program is fed no weight; the
comparison `compare/train_steps_lean.py` reads the first batch's `weights`
when it says how much of the loss sits on positions whose expert choices
differ, and `general.arrays_of` gives a mix's sequence fields only.
"""

from __future__ import annotations

import os

import numpy as np

from perfbench.harness import load_module

_general = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)), "general.py"))
generate, samples_of = _general.generate, _general.samples_of


def arrays_of(items, g):
    """`general.arrays_of`, and `weights`: 1.0 where `labels` is real."""
    out = _general.arrays_of(items, g)
    t = out["labels"].shape[1]
    out["weights"] = (np.arange(t)[None, :] < out["labels.len"][:, None]).astype(np.float32)
    return out
