"""The @provider of train cells whose fields are not all integer sequences.

`perfbench/provider.py` types every field `integer_value_sequence`; a mix
with float fields (block diffusion's per-position loss weights) names this
module in its configuration's DSL file instead. The `Feed` and the registry
are `perfbench.provider`'s own, so `entries/train.py` registers and drives
the feed exactly as for the other cells; only the field types differ, each
by the `kind` the mix gives it.
"""

from __future__ import annotations

from paddle.trainer.PyDataProvider2 import (
    dense_vector_sequence,
    integer_value_sequence,
    provider,
)

from perfbench.provider import FEEDS

TYPES = {"sequence": integer_value_sequence, "dense_sequence": dense_vector_sequence}


def _hook(settings, feed, **kwargs):
    f = FEEDS[feed]
    settings.feed = f
    settings.input_types = {
        name: TYPES[kind](dim) for name, (kind, dim) in f.input_types.items()}
    # one batch to a pool, as `perfbench.provider` does
    settings.pool_size = len(f.batches[0])
    settings.should_shuffle = False


@provider(init_hook=_hook, should_shuffle=False)
def process(settings, file_name):
    yield from settings.feed.samples()
