"""Closed-form operation counts of the seqToseq attention GRU
encoder-decoder (the equations of `perfbench/reference/seqtoseq.py`), for
REAL tokens only: a padded position costs the chip time and counts nothing
here, so padding shows as a lower `step_mfu`.

Per real source token (forward): the two encoder GRUs' x-projections and
recurrences, and the projection of the encoded vector. Per real target
token: the attention transform, the scores and the context over the pair's
real source positions, the decoder GRU's two input projections and its
recurrence, and the output layer. Per pair: the decoder's boot state.
Element-wise work, the softmaxes and the embedding lookups are not
counted. Forward plus backward is three times the forward.
"""

from __future__ import annotations

import numpy as np

from perfbench.flops import recurrence_call


def _per_source_token(s):
    e, h, d = s["word_vector_dim"], s["encoder_size"], s["decoder_size"]
    return 2 * (2.0 * e * 3 * h + 2.0 * h * 3 * h) + 2.0 * (2 * h) * d


def _per_target_token(s, src_len):
    e, h, d = s["word_vector_dim"], s["encoder_size"], s["decoder_size"]
    v = s["target_dict_dim"]
    attention = 2.0 * d * d + src_len * (2.0 * d + 2.0 * 2 * h)
    gru = 2.0 * (2 * h) * 3 * d + 2.0 * e * 3 * d + 2.0 * d * 3 * d
    return attention + gru + 2.0 * d * v


def forward_flops(s, src_len, trg_len):
    src_len = np.asarray(src_len, np.float64)
    trg_len = np.asarray(trg_len, np.float64)
    boot = 2.0 * s["encoder_size"] * s["decoder_size"]
    return float(np.sum(src_len * _per_source_token(s)
                        + trg_len * _per_target_token(s, src_len) + boot))


def train_step_flops(s, lengths):
    return 3.0 * forward_flops(s, lengths["source_language_word"],
                               lengths["target_language_next_word"])


def train_kernel_calls(s, shapes, dtype_bytes=2):
    """The recurrent kernel calls of one train step at the step's padded
    shapes: two encoder GRUs, forward and backward."""
    t, b = shapes["source_language_word"]
    h = s["encoder_size"]
    return [recurrence_call("gru", t, b, h, 3, dtype_bytes, bwd)
            for bwd in (False, True) for _ in range(2)]
