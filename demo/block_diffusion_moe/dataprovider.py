"""Synthetic block-diffusion corpus: a planted bigram language (each token
constrains its successor to a small window), noised a block at a time. The
noise is made HERE, as data: per block a level t ~ U[0.45, 0.95] (Block
Diffusion's clipped schedule for block length 4: a level near 0 would give
the odd masked token a weight in the hundreds), each token
of the block replaced by the mask id (the vocabulary's last id) with
probability t, and the loss weight of a masked position is 1/t."""

import random

from paddle.trainer.PyDataProvider2 import *


def hook(settings, vocab=97, seq_len=32, block_length=4, **kwargs):
    settings.vocab, settings.seq_len, settings.block = vocab, seq_len, block_length
    settings.input_types = {
        "tokens": integer_value_sequence(vocab),
        "labels": integer_value_sequence(vocab),
        "weights": dense_vector_sequence(1),
    }


@provider(init_hook=hook, sort_by_length=False)
def process(settings, file_name):
    V, L, B = settings.vocab, settings.seq_len, settings.block
    mask_id = V - 1
    rng = random.Random(file_name)
    for _ in range(64):
        clean = [rng.randrange(mask_id)]
        while len(clean) < L:
            clean.append(((clean[-1] * 7) % mask_id + rng.randrange(4)) % mask_id)
        noised, weights = [], []
        for start in range(0, L, B):
            t = 0.45 + 0.5 * (1.0 - rng.random())
            for tok in clean[start:start + B]:
                hit = rng.random() < t
                noised.append(mask_id if hit else tok)
                weights.append(1.0 / t if hit else 0.0)
        yield {"tokens": noised + clean, "labels": clean, "weights": weights}
