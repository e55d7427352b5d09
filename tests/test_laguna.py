"""A decoder that mixes full and sliding-window attention with a head count
a layer, a partial YaRN rotary turn, a per-head output gate, a dense gated
MLP and a shared expert beside the routed ones: the window rule and its
kernel, the head prologue's partial turn, the turn tables, the gate,
`gated_mlp`, the routed scaling factor and the shares, the new scopes,
against the plain reference (`perfbench/reference/
laguna.py`), and the trainer's first three steps against it through the
benchmark's own harness, at a small size on the CPU.

Small size: hidden 64, 2 key/value heads of 16 under 6 (full layers) or 8
(sliding layers) query heads, window 8 of 32 positions, a dense layer 128
wide, 16 routed experts of width 32 with 2 a token and a shared expert of
32, 5 layers (dense + full, three sliding, full), vocabulary 97.
"""

import io
import json
import os
import re
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.graph.argument import Argument
from paddle_tpu.layers.base import LayerContext
from paddle_tpu.ops import grouped_matmul
from paddle_tpu.ops import pallas_attention
from paddle_tpu.ops.attention_mask import MaskRule, TileWalk, tile_occupancy, tile_walk
from paddle_tpu.ops.pallas_attention import flash_attention
from paddle_tpu.parallel.sequence_parallel import rule_attention
from paddle_tpu.proto import LayerConfig, ModelConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "perfbench", "configs", "laguna-xs.2-ep16")
T = 32
SMALL = {
    "hidden_size": 64, "head_dim": 16, "num_key_value_heads": 2,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 16, "num_experts_routed": 16,
    "num_experts_per_tok": 2, "vocab_size": 97, "target_dict_dim": 97,
    "sliding_window": 8, "trained_positions": T,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6] + [8] * 35,
    # the published structure at a small scale: the full layers turn half of a
    # head by YaRN's frequencies (low 0, high 3: a ramp over all 4) times an
    # attention factor, the sliding layers the whole head by the plain ones
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 100, "rope_type": "yarn", "factor": 4,
            "original_max_position_embeddings": 64, "beta_slow": 1, "beta_fast": 4,
            "attention_factor": 1.2, "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}},
}


def _load(path):
    sys.path.insert(0, REPO)
    from perfbench.harness import load_module

    return load_module(path)


def _reference():
    return _load(os.path.join(REPO, "perfbench", "reference", "laguna.py"))


def _sizes(**over):
    with open(CONFIG + ".json") as f:
        real = json.load(f)
    cfg = dict(real, **SMALL)
    cfg["settings"] = dict(real["settings"], dtype="float32")
    cfg.update(over)
    return cfg


# ------------------------------------------------- the window rule, the tiles


def _qkv(T, H, Hkv, D, seed):
    rng = np.random.RandomState(seed)
    mk = lambda h: jnp.asarray(rng.randn(2, T, h, D).astype(np.float32))
    return mk(H), mk(Hkv), mk(Hkv)


def _dense(q, k, v, window):
    """j <= i and i - j < window, written out; bypasses the rule and every kernel."""
    T, g = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    ok = (j <= i) & (i - j < window)
    p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("D,kv_heads", [(16, 2), (128, 8)],
                         ids=["head-major-16", "column-block-128-over-8"])
@pytest.mark.parametrize("group", [6, 8])
def test_window_rule_and_its_kernel_match_the_dense_rule(group, D, kv_heads):
    """The rule against the written-out mask, and the kernel (interpreted,
    small tiles so that tiles are skipped on both sides of the band)
    against the rule, for 6 and for 8 query heads a key/value head: forward
    and the three gradients; heads of 16 lanes (a head-major copy) and the
    cell's 48 and 64 heads of 128 over 8 (column blocks of [B, T, H*D])."""
    rule = MaskRule("sliding_window", window=24)
    idx = np.arange(64)
    want = (idx[None, :] <= idx[:, None]) & (idx[:, None] - idx[None, :] < 24)
    np.testing.assert_array_equal(rule.allowed(idx, idx, 64), want)
    q, k, v = _qkv(64, kv_heads * group, kv_heads, D, group)
    w = jnp.asarray(np.random.RandomState(4).randn(*q.shape).astype(np.float32))
    flash = lambda q, k, v: flash_attention(q, k, v, rule=rule, interpret=True, block=16)
    np.testing.assert_allclose(flash(q, k, v), _dense(q, k, v, 24), atol=2e-5)
    g_k = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(lambda *a: jnp.sum(_dense(*a, 24) * w), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_k, g_d):
        np.testing.assert_allclose(a, b, atol=1e-4)
    np.testing.assert_allclose(rule_attention(q, k, v, None, rule), _dense(q, k, v, 24), atol=2e-5)


@pytest.mark.parametrize("edge,tiles,whole", [(512, 31, 0), (256, 93, 31), (128, 310, 186)])
def test_window_tiles_at_the_cell_size(edge, tiles, whole):
    """8,192 positions, window 512, each tile edge the kernels have (they
    walk 8,192 positions by 512): a row of tiles keeps the diagonal tile,
    the tile the window's far edge cuts and the whole tiles between them;
    the pairs the rule allows are the closed form of
    `perfbench/flops/laguna.py`."""
    rule = MaskRule("sliding_window", window=512)
    occ = tile_occupancy(rule, 8192, edge, edge)
    assert (occ > 0).sum() == tiles and (occ == 1).sum() == whole
    assert not np.triu(occ, 1).any()                          # nothing above the diagonal
    allowed = _load(os.path.join(REPO, "perfbench", "flops", "laguna.py")).allowed_pairs(8192, 512)
    assert allowed == 512 * 8192 - 512 * 511 // 2
    idx = np.arange(8192)
    assert sum(int(rule.allowed(idx[i:i + edge], idx, 8192).sum())
               for i in range(0, 8192, edge)) == allowed


# ------------------------------------------------------- paired partial tiles

RULES = {
    "full": MaskRule("full"),
    "causal": MaskRule("causal"),
    "window": MaskRule("sliding_window", window=512),
    "block_diffusion": MaskRule("block_diffusion", 4),
}


def _walked(walk):
    """A walk's tiles a row: its whole singles, its partial singles, [(A, B)] of its pairs."""
    counts = walk.counts.reshape(-1, 3)
    table = walk.table.reshape(len(counts), -1)
    pairs = walk.pairs.reshape(len(counts), -1, 2)
    return [(list(table[r, :w]), list(table[r, w:n]), [tuple(ab) for ab in pairs[r, :p]])
            for r, (w, n, p) in enumerate(counts)]


@pytest.mark.parametrize("transpose", [False, True], ids=["by_query_tile", "by_key_tile"])
@pytest.mark.parametrize("kind", sorted(RULES))
def test_the_walk_holds_every_tile_once_and_pairs_only_disjoint_ones(kind, transpose):
    """The host's table at the cells' size (8,192 positions, 512-wide
    tiles), by query tile (the forward kernel) and by key tile (the backward one): every
    non-empty tile of `tile_occupancy` is walked exactly once, as a single
    (a row's whole tiles before its partial ones) or in a pair; a pair is two PARTIAL tiles whose
    allowed sets, in tile-local coordinates, share nothing; `union_whole`
    says whether together they are the whole tile. The window gives 15
    pairs and 1 single a direction, block diffusion a pair a noised query
    tile and none by key tile, causal and full none."""
    rule, T, edge = RULES[kind], 8192, 512
    occ = tile_occupancy(rule, T, edge, edge)
    occ = occ.T if transpose else occ
    walk = tile_walk(rule, T, edge, edge, transpose)
    idx = lambda t: np.arange(t * edge, (t + 1) * edge)
    local = lambda r, c: rule.allowed(*((idx(c), idx(r)) if transpose else (idx(r), idx(c))), T)
    whole_unions = []
    for r, (whole, partial, pairs) in enumerate(_walked(walk)):
        assert whole == list(np.flatnonzero(occ[r] == 1))
        assert sorted(partial + [t for ab in pairs for t in ab]) == list(np.flatnonzero(occ[r] == 2))
        assert partial == sorted(partial)
        for a, b in pairs:
            assert a != b and not (local(r, a) & local(r, b)).any()
            whole_unions.append(bool((local(r, a) | local(r, b)).all()))
    n_pairs = len(whole_unions)
    want = {"window": 15, "block_diffusion": 0 if transpose else 8, "causal": 0, "full": 0}
    assert n_pairs == want[kind] and (walk.pair_width > 0) == (n_pairs > 0)
    assert walk.union_whole == (n_pairs > 0 and all(whole_unions)) == (kind == "window")
    if kind == "window":                        # the first row (the last, by key tile)
        assert walk.counts.reshape(-1, 3)[:, 1].sum() == 1
    assert f"{2 * n_pairs} paired" in walk.census


def _unpaired(rule, T, bq, bk, transpose=False):
    """The walk with no tile paired: every partial tile a single."""
    occ = tile_occupancy(rule, T, bq, bk)
    occ = occ.T if transpose else occ
    rows = [list(np.flatnonzero(row == 1)) + list(np.flatnonzero(row == 2)) for row in occ]
    table = np.zeros((len(occ), max(map(len, rows))), np.int32)
    for r, row in enumerate(rows):
        table[r, : len(row)] = row
    counts = np.asarray([[(row == 1).sum(), (row > 0).sum(), 0] for row in occ], np.int32)
    return TileWalk(table.reshape(-1), np.zeros(2 * len(occ), np.int32), counts.reshape(-1),
                    table.shape[1], 0, False, "unpaired")


PAIRED = {
    # id: (rule, positions, query heads a key/value head, lengths, pairs by query tile)
    "window_is_the_tile": (MaskRule("sliding_window", window=16), 64, 6, None, 3),
    "window_two_tiles": (MaskRule("sliding_window", window=32), 64, 8, None, 2),
    "window_half_a_tile": (MaskRule("sliding_window", window=8), 64, 6, None, 3),
    "window_no_multiple": (MaskRule("sliding_window", window=40), 96, 8, None, 3),
    "block_diffusion": (MaskRule("block_diffusion", 4), 128, 8, None, 4),
    "causal": (MaskRule("causal"), 64, 6, None, 0),
    # the first sequence ends inside the second tile of its last row's pair,
    # the second inside the second tile of row 1's, with whole rows past it
    "short_in_the_second_tile": (MaskRule("sliding_window", window=16), 64, 8, (59, 23), 3),
    "short_window_half_a_tile": (MaskRule("sliding_window", window=8), 64, 6, (64, 37), 3),
    # the one backward kernel under the rules with no pair by key tile: a head's dq
    # summed over 4 or 8 key tiles, and a sequence's end inside a tile
    "full": (MaskRule("full"), 64, 6, None, 0),
    "short_full": (MaskRule("full"), 64, 8, (64, 41), 0),
    "short_causal": (MaskRule("causal"), 64, 6, (50, 64), 0),
    "short_block_diffusion": (MaskRule("block_diffusion", 4), 128, 8, (128, 77), 4),
}


@pytest.mark.parametrize("case", sorted(PAIRED))
def test_paired_tiles_match_the_xla_path(case, monkeypatch):
    """The two kernels (interpreted, 16-wide tiles) with the pairs the
    host finds against the XLA path of `rule_attention`: the output and
    the gradients of q, k and v. Against the same kernels walking every
    tile as a single: close where tiles are paired, and bit for bit where
    the rule has no pair (causal), whose kernels are the unpaired ones."""
    rule, T, group, lengths, n_pairs = PAIRED[case]
    assert int(tile_walk(rule, T, 16, 16).counts[2::3].sum()) == n_pairs
    q, k, v = _qkv(T, 2 * group, 2, 16, len(case))
    w = jnp.asarray(np.random.RandomState(5).randn(*q.shape).astype(np.float32))
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
        # padded query rows are unspecified on both paths: out of the comparison
        w = w * (jnp.arange(T)[None, :] < lengths[:, None])[:, :, None, None]
    flash = lambda q, k, v: flash_attention(q, k, v, lengths=lengths, rule=rule,
                                            interpret=True, block=16)
    xla = lambda q, k, v: rule_attention(q, k, v, lengths, rule)
    both = lambda f: (f(q, k, v) * (w != 0),) + jax.grad(
        lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2))(q, k, v)
    paired = both(flash)
    for got, want, tol in zip(paired, both(xla), (2e-5, 1e-4, 1e-4, 1e-4)):
        np.testing.assert_allclose(got, want, atol=tol)
    monkeypatch.setattr(pallas_attention, "tile_walk", _unpaired)
    for got, want in zip(paired, both(flash)):
        if n_pairs:
            np.testing.assert_allclose(got, want, atol=1e-5)
        else:
            np.testing.assert_array_equal(got, want)


def test_the_walks_census_goes_out_once_beside_the_kernels_selection(monkeypatch, caplog):
    """`rule_attention` says once a site what its kernels walk (whole,
    partial and paired tiles, by query tile and by key tile): how one sees
    that pairing engaged; a constant of the program and no metric."""
    from paddle_tpu.utils import device

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(device, "_said", set())
    rule = MaskRule("sliding_window", window=512)
    q, k, v = _qkv(1024, 2, 1, 8, 3)
    device.logger.addHandler(caplog.handler)        # the package's logger does not propagate
    try:
        with caplog.at_level("DEBUG", logger=device.logger.name):
            for _ in range(2):
                rule_attention(q, k, v, None, rule)
    finally:
        device.logger.removeHandler(caplog.handler)
    said = [r.getMessage() for r in caplog.records if "tile walk" in r.getMessage()]
    assert said == ["rule_attention T=1024 D=8 sliding_window: tile walk: "
                    "fwd 0 whole + 1 partial + 2 paired tiles in 2 rows; "
                    "bwd 0 whole + 1 partial + 2 paired tiles in 2 rows"]


# --------------------------------------------- the head prologue, a partial turn


def _written_out_turn(x, gain, positions, freqs, factor, rot, scale, eps=1e-6):
    """The rule of the issue written out for autodiff: float32 RMS norm over
    each head, the first `rot` lanes turned rotate-half by half-row slices
    and a concatenate (cos and sin times `factor`), the rest passed through,
    the scale, one rounding to x's dtype. x [B, T, H, Dh]."""
    xf = x.astype(jnp.float32)
    if gain is not None:
        xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps) * gain
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    c, s = (factor * jnp.cos(ang))[None, :, None, :], (factor * jnp.sin(ang))[None, :, None, :]
    a, b, rest = xf[..., :rot // 2], xf[..., rot // 2:rot], xf[..., rot:]
    xf = jnp.concatenate([a * c - b * s, b * c + a * s, rest], axis=-1)
    return (xf * scale).astype(x.dtype)


@pytest.mark.parametrize("path", ["xla", "kernel"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("norm", [False, True], ids=["turn", "norm+turn"])
def test_head_prologue_with_a_partial_turn(norm, dtype, path, monkeypatch):
    """rot 64 of 128 lanes, an attention factor on cos and sin: the value,
    dx and d gain against the written-out rule under `jax.grad`, through
    the XLA path and the kernels (interpreted). Tolerances as for the
    whole-head turn (tests/test_block_diffusion_moe.py)."""
    from paddle_tpu.ops.pallas_head_prologue import head_prologue, rotary_frequencies, turn_tables

    if path == "kernel":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    B, Tn, H, Dh, rot, factor = 2, 64, 3, 128, 64, 1.4158883083359672
    rng = np.random.RandomState(32)
    x = jnp.asarray(3.0 * rng.randn(B, Tn, H, Dh), dtype)
    w = jnp.asarray(rng.randn(B, Tn, H, Dh), jnp.float32)
    gain = jnp.asarray(1.0 + 0.1 * rng.randn(Dh), jnp.float32) if norm else None
    positions = jnp.arange(Tn, dtype=jnp.int32)
    yarn, scale = (64.0, 4096.0, 64.0, 1.0), Dh ** -0.5

    def new(x, gain):
        tables = turn_tables(positions, 5e5, Dh, rot, yarn, factor)
        assert len(tables) == 3
        y = head_prologue(x.reshape(B, Tn, H * Dh), gain, tables, Dh, 1e-6, scale, rot)
        assert y.shape == (B, Tn, H, Dh) and y.dtype == x.dtype
        return y

    freqs = rotary_frequencies(5e5, rot, yarn)
    old = lambda x, gain: _written_out_turn(x, gain, positions, freqs, factor, rot, scale)
    loss = lambda f: (lambda x, gain: jnp.sum(f(x, gain).astype(jnp.float32) * w))
    wrt = (0, 1) if norm else (0,)
    want = (old(x, gain), *jax.grad(loss(old), argnums=wrt)(x, gain))
    got = (new(x, gain), *jax.grad(loss(new), argnums=wrt)(x, gain))
    np.testing.assert_array_equal(np.asarray(got[0][..., rot:], np.float32),
                                  np.asarray(want[0][..., rot:], np.float32))   # passed through
    for name, a, b in zip(("y", "dx", "d gain"), got, want):
        assert a.dtype == b.dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if dtype == "bfloat16" and name != "d gain":
            np.testing.assert_array_less(np.abs(a - b), 2.0 ** -7 * np.abs(b) + 1e-30, err_msg=name)
        else:
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * np.abs(b).max(), err_msg=name)


def test_yarn_frequencies_and_tables_against_the_formula():
    """`turn_tables` with the published full-attention parameters against
    the issue's formula evaluated in numpy (float64): the frequencies to
    float32's rounding, the tables to the rounding of an angle of up to
    8,191 radians (1e-3 absolute: half a float32 step of the angle)."""
    from paddle_tpu.ops.pallas_head_prologue import rotary_frequencies, turn_tables

    d, base, factor, l0, fast, slow, f = 64, 500000.0, 64.0, 4096.0, 64.0, 1.0, 1.4158883083359672
    i = np.arange(d // 2, dtype=np.float64)
    e = base ** (-2 * i / d)
    cd = lambda n: d * np.log(l0 / (2 * np.pi * n)) / (2 * np.log(base))
    low, high = max(np.floor(cd(fast)), 0), min(np.ceil(cd(slow)), d - 1)
    assert (low, high) == (5, 16)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    w = e * (1 - ramp) + e / factor * ramp
    np.testing.assert_allclose(rotary_frequencies(base, d, (factor, l0, fast, slow)), w, rtol=2e-6)
    pos = np.arange(0, 8192, 37)
    c, s_up, s_down = (np.asarray(t) for t in turn_tables(
        jnp.asarray(pos, jnp.int32), base, 128, d, (factor, l0, fast, slow), f))
    ang = pos[:, None] * w[None, :]
    np.testing.assert_allclose(c[:, :32], f * np.cos(ang), atol=1e-3)
    np.testing.assert_allclose(c[:, 32:64], f * np.cos(ang), atol=1e-3)
    np.testing.assert_allclose(s_up[:, :32], -f * np.sin(ang), atol=1e-3)
    np.testing.assert_allclose(s_down[:, 32:64], f * np.sin(ang), atol=1e-3)
    assert (c[:, 64:] == 1).all() and not s_up[:, 32:].any()
    assert not s_down[:, :32].any() and not s_down[:, 64:].any()


def test_the_whole_head_tables_are_what_they_were():
    """The default (no `rot`, no YaRN, factor 1) to the last bit: the
    expression `turn_tables` had before it took them."""
    from paddle_tpu.ops.pallas_head_prologue import turn_tables

    pos = jnp.tile(jnp.arange(4096, dtype=jnp.int32), 2)
    for theta, hd in ((1e6, 128), (1e4, 16)):
        inv_freq = theta ** (-jnp.arange(hd // 2, dtype=jnp.float32) / (hd // 2))
        ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        for got in (turn_tables(pos, theta, hd), turn_tables(pos, theta, hd, hd, None, 1.0)):
            assert len(got) == 2
            np.testing.assert_array_equal(got[0], jnp.concatenate([cos, cos], -1))
            np.testing.assert_array_equal(got[1], jnp.concatenate([-sin, sin], -1))


# ------------------------------------- the layers against the reference's layers


def _attention_cfg(sizes, l):
    kind = sizes["layer_types"][l]
    rope = sizes["rope_parameters"][kind]
    yarn = rope.get("rope_type") == "yarn"
    return dict(
        num_heads=sizes["num_attention_heads_per_layer"][l],
        num_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        rope_theta=float(rope["rope_theta"]),
        rotary_dim=int(rope["partial_rotary_factor"] * sizes["head_dim"]) % sizes["head_dim"],
        rope_yarn=[float(rope[k]) for k in ("factor", "original_max_position_embeddings",
                                            "beta_fast", "beta_slow")] if yarn else [],
        rope_attention_factor=float(rope.get("attention_factor", 1.0)),
        attention_mask="sliding_window" if kind == "sliding_attention" else "causal",
        mask_window=sizes["sliding_window"] if kind == "sliding_attention" else 0,
        output_gate=True)


def _attention_layer(params, x, **kw):
    from paddle_tpu.layers.attention import multi_head_attention

    cfg = LayerConfig(name="att", type="multi_head_attention", size=x.shape[-1], **kw)
    ctx = LayerContext(params=params, model=ModelConfig())
    arg = Argument(value=x, seq_lengths=jnp.full((x.shape[0],), x.shape[1], jnp.int32))
    return multi_head_attention(cfg, [arg], ctx).value, ctx


@pytest.mark.parametrize("l", [0, 1], ids=["full-6-a-group", "sliding-8-a-group"])
def test_gated_attention_matches_the_reference(l):
    """One attention layer of each kind (partial YaRN turn under the causal
    rule and 3 query heads a key/value head; whole-head turn under the
    window and 4), the gate included: the value and every parameter's and
    the input's gradient against the reference's layer. float32 on both
    sides: 2e-5 of rounding."""
    ref, sizes, rng = _reference(), _sizes(), np.random.RandomState(20 + l)
    shapes = ref.param_shapes(sizes)
    p = {n: jnp.asarray(rng.randn(*shapes[f"l{l}_{n}"]).astype(np.float32) / np.sqrt(shapes[f"l{l}_{n}"][0]))
         for n in ("wq", "wk", "wv", "wo", "wgate")}
    x = jnp.asarray(rng.randn(T, 64).astype(np.float32))
    w = jnp.asarray(rng.randn(T, 64).astype(np.float32))
    mine = lambda p, x: _attention_layer(
        {"_att." + {"wgate": "wg"}.get(n, n): v for n, v in p.items()}, x[None],
        **_attention_cfg(sizes, l))[0][0]
    theirs = lambda p, x: ref.attention({f"l{l}_{n}": v for n, v in p.items()}, l, x, sizes, "highest")
    np.testing.assert_allclose(mine(p, x), theirs(p, x), atol=2e-5)
    got = jax.grad(lambda p, x: jnp.sum(mine(p, x) * w), argnums=(0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(theirs(p, x) * w), argnums=(0, 1))(p, x)
    np.testing.assert_allclose(got[1], want[1], atol=2e-5)
    for n in p:
        np.testing.assert_allclose(got[0][n], want[0][n], atol=2e-5, err_msg=n)
    # the gate does something: without it the layer is another function
    no_gate = _attention_layer({"_att." + n: v for n, v in p.items()}, x[None],
                               **dict(_attention_cfg(sizes, l), output_gate=False))[0][0]
    assert float(jnp.max(jnp.abs(no_gate - mine(p, x)))) > 1e-3


def _gated_mlp(params, x, width):
    from paddle_tpu.layers.gated_mlp import gated_mlp_layer

    cfg = LayerConfig(name="mlp", type="gated_mlp", size=x.shape[-1], expert_width=width)
    return gated_mlp_layer(cfg, [Argument(value=x)], LayerContext(params=params, model=ModelConfig())).value


def test_gated_mlp_matches_the_reference():
    ref, rng = _reference(), np.random.RandomState(22)
    mk = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32) / np.sqrt(s[0]))
    p = {"gate": mk(64, 128), "up": mk(64, 128), "down": mk(128, 64)}
    x, w = mk(48, 64) * 8.0, mk(48, 64)
    mine = lambda p, x: _gated_mlp({"_mlp." + n: v for n, v in p.items()}, x, 128)
    theirs = lambda p, x: ref.gated_mlp(x, p["gate"], p["up"], p["down"], "highest")
    np.testing.assert_allclose(mine(p, x), theirs(p, x), atol=2e-5)
    got = jax.grad(lambda p, x: jnp.sum(mine(p, x) * w), argnums=(0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(theirs(p, x) * w), argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5)


def _moe_share(params, x, first, count, factor):
    from paddle_tpu.layers.moe import moe_layer

    cfg = LayerConfig(name="moe", type="moe", size=x.shape[-1], experts=16, experts_per_token=2,
                      expert_width=32, experts_held_first=first, experts_held_count=count,
                      routed_scaling_factor=factor)
    held = {n: (v if n.endswith("router") else v[first:first + count]) for n, v in params.items()}
    return moe_layer(cfg, [Argument(value=x)], LayerContext(params=held, model=ModelConfig())).value


def test_the_sixteen_shares_add_up_to_the_whole_layer(monkeypatch):
    """16 shares of one expert each: their routed parts, scaling factor
    included, summed, plus the shared expert ONCE (every chip computes it
    alike), give what the uncut reference gives for the whole sparse
    layer."""
    monkeypatch.setattr(grouped_matmul, "CHUNK_ROWS", 32)
    ref, sizes, rng = _reference(), _sizes(), np.random.RandomState(23)
    mk = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32) / np.sqrt(s[-2]))
    routed = {"router": mk(64, 16), "gate": mk(16, 64, 32), "up": mk(16, 64, 32), "down": mk(16, 32, 64)}
    shared = {"gate": mk(64, 32), "up": mk(64, 32), "down": mk(32, 64)}
    x = jnp.asarray(rng.randn(96, 64).astype(np.float32))
    p = {**{f"l1_{n}": v for n, v in routed.items()}, **{f"l1_shared_{n}": v for n, v in shared.items()}}
    whole = ref.feed_forward(p, 1, x, sizes, "highest")
    factor = sizes["moe_routed_scaling_factor"]
    assert factor == 2.5
    prog = {"_moe." + n: v for n, v in routed.items()}
    shares = sum(_moe_share(prog, x, first, 1, factor) for first in range(16))
    once = _gated_mlp({"_mlp." + n: v for n, v in shared.items()}, x, 32)
    np.testing.assert_allclose(shares + once, whole, atol=5e-5)
    # the factor is on the routed sum only, and 1 leaves the layer what it was
    np.testing.assert_allclose(_moe_share(prog, x, 0, 16, 1.0) * factor, shares, atol=5e-5)
    np.testing.assert_allclose(whole - once, ref.moe(p, 1, x, sizes, "highest", held=(0, 16)), atol=5e-5)


# ----------------------------------------------------------- the whole model


def _machine(tmp_path, **over):
    from paddle_tpu.config import parse_config
    from paddle_tpu.graph.machine import GradientMachine

    sizes = _sizes(**over)
    path = os.path.join(str(tmp_path), "small.json")
    with open(path, "w") as f:
        json.dump(sizes, f)
    conf = parse_config(CONFIG + ".py", f"config_json={path},feed=x,feed_list=y,batch=2")
    return GradientMachine(conf.model_config), sizes


def _batch(rng, n=2):
    labels = rng.randint(0, 97, (n, T)).astype(np.int32)
    tokens = np.concatenate([np.zeros((n, 1), np.int32), labels[:, :-1]], 1)
    lens = jnp.full((n,), T, jnp.int32)
    return tokens, labels, {"tokens": Argument(ids=jnp.asarray(tokens), seq_lengths=lens),
                            "labels": Argument(ids=jnp.asarray(labels), seq_lengths=lens)}


def test_the_small_model_matches_the_reference(tmp_path):
    """All three block kinds through the configuration's own DSL file at the
    small size, under recomputation blocks: the loss and EVERY leaf's
    gradient against the reference computed under the program's expert
    choices (which are the reference's own here: float32 on both sides).
    1e-6 absolute on gradients of 0.01 to 0.3: float32 rounding."""
    gm, sizes = _machine(tmp_path)
    ref = _reference()
    p_ref = ref.init_params(sizes, 5)
    shapes = gm.init_params(seed=1)
    assert set(shapes) == set(sizes["param_map"])
    params = {k: jnp.asarray(p_ref[v]).reshape(shapes[k].shape) for k, v in sizes["param_map"].items()}
    tokens, labels, batch = _batch(np.random.RandomState(0))
    loss, grads, outs, _ = jax.jit(gm.grad_fn("block", sparse=False))(params, batch, None)
    names = sorted(sizes["routing_map"], key=sizes["routing_map"].get)
    assert names == ["l1_chosen", "l2_chosen", "l3_chosen", "l4_chosen"]
    routing = jnp.stack([outs[n].value for n in names], axis=1)
    fed = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels), "routing": routing}
    want_loss, want = ref.loss_and_grad(p_ref, fed)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    assert 3.0 < float(loss) < 7.0                  # a mean over positions, near ln(97)
    for k, v in sizes["param_map"].items():
        np.testing.assert_allclose(np.asarray(grads[k]).reshape(want[v].shape), want[v],
                                   atol=1e-6, err_msg=k)
    own = ref.own_routing(p_ref, fed)
    np.testing.assert_array_equal(np.sort(own, -1), np.sort(np.asarray(routing), -1))


def test_the_new_scopes_are_in_the_steps_program(tmp_path):
    """Where the readers of `gated_mlp_ms.train` and the by-part table
    look: the optimized program of a gradient step carries `op_name`s under
    `multi_head_attention:<name>/gate` and under `gated_mlp:<name>` (the
    dense layer's and each shared expert's), forward and backward."""
    gm, _ = _machine(tmp_path)
    _, _, batch = _batch(np.random.RandomState(2))
    hlo = jax.jit(gm.grad_fn("block", sparse=False)).lower(
        gm.init_params(seed=3), batch, None).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in [f"multi_head_attention:l{l}_attn" + r"\)*/gate/" for l in range(5)] + [
            r"gated_mlp:l0_mlp", r"gated_mlp:l1_shared", r"gated_mlp:l4_shared"]:
        hits = [n for n in names if re.search(scope, n)]
        assert hits and any("transpose(" in n for n in hits), scope


def test_the_new_arguments_leave_the_other_configuration_alone(tmp_path):
    """`sdar-30b-a3b-ep8`'s DSL file at a test size builds what it built:
    the same parameters, a whole-head turn, no window, no gate, no scaling
    factor; and the DSL refuses the new arguments without `head_dim`."""
    from paddle_tpu.config import parse_config
    from paddle_tpu.trainer_config_helpers import layers as dsl

    other = os.path.join(REPO, "perfbench", "configs", "sdar-30b-a3b-ep8")
    with open(other + ".json") as f:
        sizes = json.load(f)
    sizes.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 moe_intermediate_size=32, num_experts=8, num_experts_routed=8,
                 num_hidden_layers=1, vocab_size=97, mask_id=96)
    path = os.path.join(str(tmp_path), "other.json")
    with open(path, "w") as f:
        json.dump(sizes, f)
    conf = parse_config(other + ".py", f"config_json={path},feed=x,feed_list=y,batch=2")
    by_type = {l.type: l for l in conf.model_config.layers}
    att, moe = by_type["multi_head_attention"], by_type["moe"]
    assert (att.rotary_dim, att.rope_yarn, att.rope_attention_factor, att.mask_window,
            att.output_gate) == (0, [], 1.0, 0, False)
    assert moe.routed_scaling_factor == 1.0 and "gated_mlp" not in by_type
    assert sorted(p.name for p in conf.model_config.parameters if "l0_" in p.name) == sorted(
        f"_l0_{n}" for n in ("attn.wq", "attn.wk", "attn.wv", "attn.wo", "attn.q_norm", "attn.k_norm",
                             "moe.router", "moe.gate", "moe.up", "moe.down", "norm1.w0", "norm2.w0"))
    assert dsl.multi_head_attention_layer.__defaults__ is not None
    with pytest.raises(ValueError, match="window"):
        MaskRule("sliding_window")


# ------------------------------- the trainer's three steps and the reference


def _tiny_root(tmp, dtype):
    """A temporary copy of the benchmark with the small configuration and a
    cell beside it, as NEW files (the harness finds them by name)."""
    root = os.path.join(str(tmp), "root")
    shutil.copytree(os.path.join(REPO, "perfbench"), os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = _sizes()
    cfg["settings"] = dict(cfg["settings"], dtype=dtype, learning_rate=1e-3)
    with open(os.path.join(root, "perfbench", "configs", "tiny-lm.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(REPO, "perfbench", "traffic", "lm_4x8192.json")) as f:
        mix = json.load(f)
    mix.update(lengths={"file": {"dist": "fixed", "value": T}},
               arrival={"kind": "batches", "batch": 4, "cycle": 6})
    with open(os.path.join(root, "perfbench", "traffic", "lm_tiny.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(REPO, "perfbench", "workloads", "laguna.train.json")) as f:
        wl = json.load(f)
    wl.update(config="tiny-lm", traffic="lm_tiny", limits=LIMITS[dtype])
    with open(os.path.join(root, "perfbench", "workloads", "tiny.lm.json"), "w") as f:
        json.dump(wl, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny-lm", "source": "test", "reduced": [], "why": "test",
                         "file": "perfbench/configs/tiny-lm.json"}]
    bench["workloads"] = [{"name": "tiny.lm", "config": "tiny-lm", "traffic": "lm_tiny",
                           "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.lm"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


# set between what the program reads at this size (float32: rounding, and in
# `change_gap` a router choice that flips on that rounding in steps two and
# three; bfloat16: the program's bfloat16 activations) and what the fp8
# control reads (an unmoved state reads 1)
LIMITS = {
    "float32": {"loss_gap": 1e-5, "grad_gap": 2e-3, "grad_diff": 2e-3, "change_gap": 5e-2,
                "routing_gap": 0.01},
    "bfloat16": {"loss_gap": 1.5e-3, "grad_gap": 0.15, "grad_diff": 0.06, "change_gap": 0.08,
                 "routing_gap": 0.2},
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_steps_match_the_reference_and_the_fp8_control_does_not(dtype, tmp_path):
    """The configuration's DSL file at the small size through `cli._setup`
    -> `parse_config` -> `Trainer.train()`, as `paddle train` builds it (the
    benchmark's entry `train_routed`), against the reference's three steps."""
    sys.path.insert(0, REPO)
    from perfbench import harness

    root = _tiny_root(tmp_path, dtype)
    out = io.StringIO()
    harness.run_cell(["--workload", "tiny.lm", "--seed", "2147483659", "--seconds", "0.2"],
                     root=root, require_chip=False, out=out)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert set(line["compared"]) == set(LIMITS[dtype])

    cell = harness.load_cell(root, "tiny.lm")
    gen = cell.module("traffic", cell.mix["generator"])
    ref = cell.module("reference", "laguna")
    cmp = cell.module("compare", cell.workload["compare"])
    items = gen.generate(cell.mix, cell.config, 2147483659)
    batches = [gen.arrays_of(items, g) for g in range(3)]
    assert batches[0]["tokens"].shape == (4, T) and (batches[0]["tokens"][:, 0] == 0).all()
    np.testing.assert_array_equal(batches[0]["tokens"][:, 1:], batches[0]["labels"][:, :-1])
    base = cmp.reference_steps(ref, cell.config, 2147483659, batches)
    assert base["routing"][0].shape == (4, 4, T, 2)             # the four sparse layers
    control = cmp.checks(cmp.reference_steps(ref, cell.config, 2147483659, batches, mode="fp8"),
                         base, cell.workload["limits"])
    assert not all(c.ok for c in control), control


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [6, 16], ids=["6-heads-one-tile", "16-heads-8-a-tile"])
def test_the_gates_kernels_match_its_written_out_form(heads, dtype, monkeypatch):
    """`gate_heads` through its kernels (interpreted: `attention_gate` each
    way and `attention_delta` for d g, on [B, T, H*128] where it lies)
    against the product written out under `jax.grad`: the value, dx and
    d g, bit for bit (one float32 product a number, rounded once; d g a
    float32 sum over a head's 128 lanes). Heads narrower than a lane tile
    take the written-out form."""
    from paddle_tpu.ops.pallas_attention import gate_heads

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(heads)
    x = jnp.asarray(rng.randn(2, 256, heads, 128), dtype)
    g = jnp.asarray(rng.rand(2, 256, heads), jnp.float32)
    w = jnp.asarray(rng.randn(2, 256, heads, 128), jnp.float32)
    written_out = lambda x, g: (x.astype(jnp.float32) * g[..., None]).astype(x.dtype)
    kernels = gate_heads
    both = lambda f: (f(x, g), *jax.grad(lambda x, g: jnp.sum(f(x, g).astype(jnp.float32) * w),
                                         argnums=(0, 1))(x, g))
    assert "attention_gate" in str(jax.make_jaxpr(kernels)(x, g))
    for name, a, b in zip(("y", "dx", "d g"), both(kernels), both(written_out)):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        if name == "d g":
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * float(jnp.abs(b).max()), err_msg=name)
        else:
            np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32), err_msg=name)
    narrow = x[..., :16]
    assert "pallas_call" not in str(jax.make_jaxpr(kernels)(narrow, g))
