"""Closed-form operation counts of one chip's share of a `deepseek_v3`
decoder with `q_lora_rank: null` (latent attention, dense lead layers, then
sparse layers of a sigmoid router, shared experts and routed experts: the
equations of `perfbench/reference/deepseek_v3.py`), and the operations and
bytes of its kernels: latent attention under the causal rule and the
grouped matrix products of the experts held.

Per position and layer (forward): the five projections (q: hidden x H (dn +
dr); down: hidden x (latent + dr); up: latent x H (dn + dv); out: H dv x
hidden); attention's two products for the ALLOWED (query, key) pairs only
(T (T + 1) / 2 a sequence and head): a score costs 2 (dn + dr) operations
a pair (both score parts) and the values 2 dv; a dense layer's three
products of hidden x intermediate_size; a sparse layer's router, its shared
experts' three products (n_shared_experts x moe_intermediate_size wide),
and the held experts' share: a position chooses `num_experts_per_tok` of
`n_routed_experts_total` experts, of which `n_routed_experts` are held
here, so on average k * held / routed (token, expert) pairs a position,
each three products of hidden x width (what the data really routed here is
the program's counter `moe.pairs_held`, which the grouped product's
roofline reads). The head is over the vocabulary held. Element-wise work,
norms, softmaxes, the router's sigmoid and top-k, rotary turns and the
embedding lookup are not counted. Forward plus backward is three times the
forward; recomputation counts nothing.
"""

from __future__ import annotations

import numpy as np


def allowed_pairs(t):
    """(query, key) pairs of one t-long sequence and head: j <= i."""
    t = float(t)
    return t * (t + 1) / 2


def _sparse(s, l):
    return l >= s["first_k_dense_replace"] and l % s.get("moe_layer_freq", 1) == 0


def _sparse_layers(s):
    return [l for l in range(s["num_hidden_layers"]) if _sparse(s, l)]


def _widths(s):
    return s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]


def layer_forward(s, l, t):
    """Layer l's forward operations on one sequence of t tokens, by part."""
    h, nh, latent = s["hidden_size"], s["num_attention_heads"], s["kv_lora_rank"]
    dn, dr, dv = _widths(s)
    t = float(t)
    parts = {
        "projections": 2.0 * t * (h * nh * (dn + dr) + nh * dv * h),
        "latent": 2.0 * t * (h * (latent + dr) + latent * nh * (dn + dv)),
        "attention": 2.0 * allowed_pairs(t) * nh * (dn + dr + dv),
    }
    if _sparse(s, l):
        pairs = t * s["num_experts_per_tok"] * s["n_routed_experts"] / s["n_routed_experts_total"]
        parts.update(
            router=2.0 * t * h * s["n_routed_experts_total"],
            shared=3 * 2.0 * t * h * s["n_shared_experts"] * s["moe_intermediate_size"],
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


def attention_call(s, t, sequences, itemsize=2):
    """Latent attention's own products (both score parts and the values) of
    one train step, forward + backward, for the allowed pairs of every
    layer: 2 (dn + dr + dv) operations a pair a head forward, three times
    that with the backward. Bytes: forward reads q_nope, q_rope, k_nope, v
    (H heads each) and k_rope (ONE head) and writes the output; backward
    reads them, the output and its gradient and writes the five gradients
    (k_rope's once: its query heads' parts are summed)."""
    nh = s["num_attention_heads"]
    dn, dr, dv = _widths(s)
    layers = s["num_hidden_layers"]
    flops = layers * 3.0 * 2.0 * allowed_pairs(t) * nh * (dn + dr + dv)
    by_head = t * nh * (dn + dr + dn + dv) * itemsize        # q_nope, q_rope, k_nope, v
    shared = t * dr * itemsize                               # k_rope
    out = t * nh * dv * itemsize
    bytes_ = layers * ((by_head + shared + out) + (2 * by_head + 2 * shared + 2 * out))
    return {"kind": "causal_attention", "flops": sequences * flops, "bytes": sequences * bytes_}


def grouped_mm_call(s, pairs, itemsize=2):
    """The experts' three grouped products for `pairs` (token, expert)
    pairs (all sparse layers, as the program's counter counts them), forward
    + backward; bytes: each pair's rows read and written, and the held
    experts' weights read forward and backward and their float32 gradient
    written, a layer."""
    h, f = s["hidden_size"], s["moe_intermediate_size"]
    weights = len(_sparse_layers(s)) * s["n_routed_experts"] * 3 * h * f
    rows = pairs * (2 * h + 3 * f) * itemsize
    return {"kind": "grouped_mm", "flops": 3.0 * pairs * 3 * 2.0 * h * f,
            "bytes": 3 * rows + 2 * weights * itemsize + weights * 4}


def train_kernel_calls(s, shapes, dtype_bytes=2):
    """The kernel calls of one train step whose size the shapes fix. (The
    grouped product's size is data: `grouped_mm_call` from the counted
    pairs.)"""
    t, b = shapes["labels"]
    return [attention_call(s, t, b, dtype_bytes)]
