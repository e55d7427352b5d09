"""Closed-form operation counts of one chip's share of a block-diffusion
mixture-of-experts transformer (the equations of
`perfbench/reference/sdar_moe.py`), and the operations and bytes of its two
kernels: attention under the block-diffusion mask, and the grouped matrix
products of the experts held.

A sequence of L corpus tokens is 2L input positions (the noised and the
clean copy). Per position and layer (forward): the q, k, v and output
projections, the router, and the experts' share: a position chooses
`num_experts_per_tok` of `num_experts_routed` experts, of which
`num_experts` are held here, so on average k * held / routed (token,
expert) pairs a position are computed here, each three products of
hidden x width (what the data really routed here is the program's counter
`moe.pairs_held`, which the grouped product's roofline reads). Attention
counts the ALLOWED (query, key) pairs of the mask only: L * B + L * L a
sequence and head (B the block length), two products of head_dim a pair;
a tile the kernel computes and the mask then empties costs the chip time
and counts nothing here. The head is over the first L positions and the
vocabulary held. Element-wise work, norms, softmaxes, rotary turns and the
embedding lookup are not counted. Forward plus backward is three times the
forward; recomputation counts nothing.
"""

from __future__ import annotations

import numpy as np


def allowed_pairs(L, block):
    """(query, key) pairs the block-diffusion mask allows on one 2L-long
    sequence: noised-noised L * B, noised-clean B^2 nb (nb - 1) / 2,
    clean-clean B^2 nb (nb + 1) / 2, with nb = L / B blocks."""
    return float(L) * block + float(L) * L


def _layer_forward(s, L):
    """One layer's forward operations on one sequence of L corpus tokens."""
    h, nh, nkv, hd = (s["hidden_size"], s["num_attention_heads"],
                      s["num_key_value_heads"], s["head_dim"])
    t = 2.0 * L
    projections = 2.0 * t * h * (nh * hd + 2 * nkv * hd) + 2.0 * t * nh * hd * h
    attention = 4.0 * allowed_pairs(L, s["block_length"]) * nh * hd
    router = 2.0 * t * h * s["num_experts_routed"]
    pairs = t * s["num_experts_per_tok"] * s["num_experts"] / s["num_experts_routed"]
    experts = pairs * 3 * 2.0 * h * s["moe_intermediate_size"]
    return projections + attention + router + experts


def forward_flops(s, corpus_lengths):
    L = np.asarray(corpus_lengths, np.float64)
    per_seq = [s["num_hidden_layers"] * _layer_forward(s, l)
               + 2.0 * l * s["hidden_size"] * s["vocab_size"] for l in L]
    return float(np.sum(per_seq))


def train_step_flops(s, lengths):
    return 3.0 * forward_flops(s, lengths["labels"])


def attention_call(s, L, sequences, itemsize=2):
    """Attention's own products (scores and values) of one train step, all
    layers, forward + backward, for the allowed pairs; bytes: q, k, v read
    and the output written forward; backward reads them, the output and its
    gradient and writes the three gradients."""
    nh, nkv, hd = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
    n = s["num_hidden_layers"] * sequences
    fwd = 4.0 * allowed_pairs(L, s["block_length"]) * nh * hd
    q = 2.0 * L * nh * hd * itemsize
    kv = 2.0 * L * nkv * hd * itemsize
    return {"kind": "bd_attention", "flops": n * 3.0 * fwd,
            "bytes": n * ((2 * q + 2 * kv) + (4 * q + 4 * kv))}


def grouped_mm_call(s, pairs, itemsize=2):
    """The experts' three grouped products for `pairs` (token, expert)
    pairs (all layers, as the program's counter counts them), forward +
    backward; bytes: each pair's rows read and written, and the held
    experts' weights read forward and backward and their float32 gradient
    written, a layer."""
    h, f = s["hidden_size"], s["moe_intermediate_size"]
    weights = s["num_hidden_layers"] * s["num_experts"] * 3 * h * f
    rows = pairs * (2 * h + 3 * f) * itemsize
    return {"kind": "grouped_mm", "flops": 3.0 * pairs * 3 * 2.0 * h * f,
            "bytes": 3 * rows + 2 * weights * itemsize + weights * 4}


def train_kernel_calls(s, shapes, dtype_bytes=2):
    """The kernel calls of one train step whose size the shapes fix:
    attention's. (The grouped product's size is data: `grouped_mm_call`
    from the counted pairs.)"""
    t, b = shapes["labels"]
    return [attention_call(s, t, b, dtype_bytes)]
