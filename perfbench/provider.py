"""The @provider the train cells feed `Trainer.train()` through.

The trainer finds a provider by module and object name (the reference's
`define_py_data_sources2` contract), and its arguments travel as JSON, so
the harness cannot hand it an object: it registers a `Feed` here under a
name, and the configuration's DSL file passes that name on. One `Feed`
serves every pass of a run; before each `train()` call the harness says
which batches the next pass is (the cycle's batches round and round).
"""

from __future__ import annotations

import jax
from paddle.trainer.PyDataProvider2 import integer_value_sequence, provider

FEEDS = {}


class Feed:
    def __init__(self, batches, input_types, tokens_per_batch):
        """`batches`: list of sample lists (one list of dicts a batch);
        `input_types`: slot name -> ("sequence", dim);
        `tokens_per_batch`: the real tokens of each batch."""
        self.batches = batches
        self.input_types = input_types
        self.tokens_per_batch = tokens_per_batch
        self._plan = []
        self.served = []          # batch indices the last pass was given

    def next_pass(self, batch_ids):
        self._plan = list(batch_ids)
        self.served = []

    def samples(self):
        for b in self._plan:
            with jax.profiler.TraceAnnotation("perfbench.provider_batch"):
                self.served.append(b)
                yield from self.batches[b % len(self.batches)]

    def tokens_served(self):
        n = len(self.tokens_per_batch)
        return sum(self.tokens_per_batch[b % n] for b in self.served)


def _hook(settings, feed, **kwargs):
    f = FEEDS[feed]
    settings.feed = f
    settings.input_types = {
        name: integer_value_sequence(dim)
        for name, (_kind, dim) in f.input_types.items()}
    # one batch to a pool: the trainer's default pool gathers tens of
    # thousands of samples before it cuts the first batch
    settings.pool_size = len(f.batches[0])
    settings.should_shuffle = False


@provider(init_hook=_hook, should_shuffle=False)
def process(settings, file_name):
    yield from settings.feed.samples()
