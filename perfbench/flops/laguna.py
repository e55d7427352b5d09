"""Closed-form operation counts of one chip's share of a decoder that mixes
full and sliding-window attention with a head count a layer, a per-head
output gate, a dense lead layer and sparse layers of a shared and routed
experts (the equations of `perfbench/reference/laguna.py`), and the
operations and bytes of its kernels: attention under the window rule and
under the causal rule, and the grouped matrix products of the experts held.

Per position and layer (forward): the q, k, v, gate and output
projections; attention's two products of head_dim a pair for the ALLOWED
(query, key) pairs only (causal: T (T + 1) / 2 a sequence and head; a
window W: W T - W (W - 1) / 2): a tile a kernel computes and the rule then
empties costs the chip time and counts nothing here; a dense layer's three
products of hidden x intermediate_size; a sparse layer's router, its shared
expert's three products, and the held experts' share: a position chooses
`num_experts_per_tok` of `num_experts_routed` experts, of which
`num_experts` are held here, so on average k * held / routed (token,
expert) pairs a position, each three products of hidden x width (what the
data really routed here is the program's counter `moe.pairs_held`, which
the grouped product's roofline reads). The head is over the vocabulary
held. Element-wise work, norms, softmaxes, rotary turns and the embedding
lookup are not counted. Forward plus backward is three times the forward;
recomputation counts nothing.
"""

from __future__ import annotations

import numpy as np


def allowed_pairs(t, window=0):
    """(query, key) pairs of one t-long sequence and head: j <= i, and
    under a window i - j < window."""
    t = float(t)
    if not window or window >= t:
        return t * (t + 1) / 2
    return window * t - window * (window - 1) / 2.0


def _window(s, l):
    return s["sliding_window"] if s["layer_types"][l] == "sliding_attention" else 0


def _sparse_layers(s):
    return [l for l in range(s["num_hidden_layers"]) if s["mlp_layer_types"][l] == "sparse"]


def layer_forward(s, l, t):
    """Layer l's forward operations on one sequence of t tokens, by part."""
    h, nkv, hd = s["hidden_size"], s["num_key_value_heads"], s["head_dim"]
    nh = s["num_attention_heads_per_layer"][l]
    t = float(t)
    parts = {
        "projections": 2.0 * t * h * (nh * hd + 2 * nkv * hd) + 2.0 * t * nh * hd * h,
        "gate": 2.0 * t * h * nh,
        "attention": 4.0 * allowed_pairs(t, _window(s, l)) * nh * hd,
    }
    if s["mlp_layer_types"][l] == "sparse":
        pairs = t * s["num_experts_per_tok"] * s["num_experts"] / s["num_experts_routed"]
        parts.update(
            router=2.0 * t * h * s["num_experts_routed"],
            shared=3 * 2.0 * t * h * s["shared_expert_intermediate_size"],
            experts=pairs * 3 * 2.0 * h * s["moe_intermediate_size"])
    else:
        parts["dense"] = 3 * 2.0 * t * h * s["intermediate_size"]
    return parts


def forward_flops(s, lengths):
    per_seq = [sum(sum(layer_forward(s, l, t).values()) for l in range(s["num_hidden_layers"]))
               + 2.0 * t * s["hidden_size"] * s["vocab_size"]
               for t in np.asarray(lengths, np.float64)]
    return float(np.sum(per_seq))


def train_step_flops(s, lengths):
    return 3.0 * forward_flops(s, lengths["labels"])


def attention_call(s, kind, t, sequences, itemsize=2):
    """Attention's own products (scores and values) of one train step,
    forward + backward, for the allowed pairs of the layers of one KIND
    (`layer_types`); bytes: q, k, v read and the output written forward;
    backward reads them, the output and its gradient and writes the three
    gradients."""
    nkv, hd = s["num_key_value_heads"], s["head_dim"]
    flops = bytes_ = 0.0
    for l in range(s["num_hidden_layers"]):
        if s["layer_types"][l] != kind:
            continue
        nh = s["num_attention_heads_per_layer"][l]
        flops += 3.0 * 4.0 * allowed_pairs(t, _window(s, l)) * nh * hd
        q, kv = t * nh * hd * itemsize, t * nkv * hd * itemsize
        bytes_ += (2 * q + 2 * kv) + (4 * q + 4 * kv)
    name = "window_attention" if kind == "sliding_attention" else "causal_attention"
    return {"kind": name, "flops": sequences * flops, "bytes": sequences * bytes_}


def grouped_mm_call(s, pairs, itemsize=2):
    """The experts' three grouped products for `pairs` (token, expert)
    pairs (all sparse layers, as the program's counter counts them), forward
    + backward; bytes: each pair's rows read and written, and the held
    experts' weights read forward and backward and their float32 gradient
    written, a layer."""
    h, f = s["hidden_size"], s["moe_intermediate_size"]
    weights = len(_sparse_layers(s)) * s["num_experts"] * 3 * h * f
    rows = pairs * (2 * h + 3 * f) * itemsize
    return {"kind": "grouped_mm", "flops": 3.0 * pairs * 3 * 2.0 * h * f,
            "bytes": 3 * rows + 2 * weights * itemsize + weights * 4}


def train_kernel_calls(s, shapes, dtype_bytes=2):
    """The kernel calls of one train step whose size the shapes fix: one
    entry a rule. (The grouped product's size is data: `grouped_mm_call`
    from the counted pairs.)"""
    t, b = shapes["labels"]
    kinds = sorted(set(s["layer_types"][: s["num_hidden_layers"]]))
    return [attention_call(s, kind, t, b, dtype_bytes) for kind in kinds]
