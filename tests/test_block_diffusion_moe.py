"""Block-diffusion mixture-of-experts training, the layers: the mask rule
and its kernel, grouped-query attention, the sparse-expert layer and its
shares against the plain reference's layer (`perfbench/reference/
sdar_moe.py`), the per-position cost weight, the time-axis slice and block
recomputation, at a small size on the CPU. The trainer's steps against the
reference are tests/test_block_diffusion_train.py.

Small size: hidden 64, 4 query / 2 key-value heads of 16, 8 experts of
width 32 with 2 a token, 2 layers, L = 32, block 4, vocabulary 97.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.graph.argument import Argument
from paddle_tpu.layers.base import LayerContext, step_counters
from paddle_tpu.ops import grouped_matmul
from paddle_tpu.ops.attention_mask import MaskRule, tile_occupancy
from paddle_tpu.ops.pallas_attention import flash_attention
from paddle_tpu.parallel.sequence_parallel import full_attention, rule_attention
from paddle_tpu.proto import LayerConfig, ModelConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SIZES = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_routed": 8, "experts_held_first": 0, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "vocab_size": 97, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "norm_topk_prob": True, "block_length": 4, "mask_id": 96,
}
L = 32


def _reference():
    sys.path.insert(0, REPO)
    from perfbench.harness import load_module

    return load_module(os.path.join(REPO, "perfbench", "reference", "sdar_moe.py"))


# ------------------------------------------------------------ the mask rule


def test_block_diffusion_tiles_at_the_cell_size():
    """L = 4096, B = 4, 128-wide tiles: the noised-noised quadrant keeps its
    32 diagonal tiles, noised-clean and clean-clean their lower triangles,
    clean-noised nothing."""
    rule = MaskRule("block_diffusion", 4)
    occ = tile_occupancy(rule, 8192, 128, 128)
    assert occ.shape == (64, 64)
    assert (occ[:32, :32] > 0).sum() == 32 and (occ[32:, :32] > 0).sum() == 0
    assert (occ > 0).sum() == 32 + 2 * (32 * 33 // 2)      # 1088 of 4096
    allowed = sum(int(rule.allowed(np.arange(i, i + 512), np.arange(8192), 8192).sum())
                  for i in range(0, 8192, 512))
    assert allowed == 4096 * 4 + 4096 * 4096      # perfbench/flops/sdar_moe.py's closed form
    ref = _reference()
    idx = np.arange(64)
    np.testing.assert_array_equal(rule.allowed(idx, idx, 64),
                                  np.asarray(ref.allowed(idx, idx, 32, 4)))


def _qkv(T, H, Hkv, D, seed):
    rng = np.random.RandomState(seed)
    mk = lambda h: jnp.asarray(rng.randn(2, T, h, D).astype(np.float32))
    return mk(H), mk(Hkv), mk(Hkv)


def _dense(q, k, v, rule):
    """The dense rule, bypassing every kernel."""
    T, g = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    idx = jnp.arange(T)
    p = jax.nn.softmax(jnp.where(rule.allowed(idx, idx, T)[None, None], s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("heads", [(4, 2, 16), (32, 4, 128)],
                         ids=["head-major-4-over-2x16", "column-block-32-over-4x128"])
@pytest.mark.parametrize("kind", ["full", "causal", "block_diffusion"])
def test_kernel_matches_the_dense_rule(kind, heads):
    """Interpret mode, small tiles (so that tiles are skipped), grouped
    heads: forward and the three gradients; with heads of 16 lanes, which
    the kernels read from a head-major copy, and with the cell's 32 heads of
    128 over 4, which they read and write as column blocks of [B, T, H*D]
    (dK and dV folded over each group of 8 lane blocks)."""
    rule = MaskRule(kind, 4 if kind == "block_diffusion" else 0)
    H, Hkv, D = heads
    q, k, v = _qkv(64, H, Hkv, D, 3)
    w = jnp.asarray(np.random.RandomState(4).randn(2, 64, H, D).astype(np.float32))
    flash = lambda q, k, v: flash_attention(q, k, v, rule=rule, interpret=True, block=16)
    np.testing.assert_allclose(flash(q, k, v), _dense(q, k, v, rule), atol=2e-5)
    g_k = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(lambda *a: jnp.sum(_dense(*a, rule) * w), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_k, g_d):
        np.testing.assert_allclose(a, b, atol=1e-4)
    np.testing.assert_allclose(rule_attention(q, k, v, None, rule),
                               _dense(q, k, v, rule), atol=2e-5)


def _attention_layer(params, x, **kw):
    from paddle_tpu.layers.attention import multi_head_attention

    cfg = LayerConfig(name="att", type="multi_head_attention", size=x.shape[-1], **kw)
    ctx = LayerContext(params=params, model=ModelConfig())
    arg = Argument(value=x, seq_lengths=jnp.full((x.shape[0],), x.shape[1], jnp.int32))
    return multi_head_attention(cfg, [arg], ctx).value


def _gqa_params(rng, d=64, h=4, hkv=2, hd=16):
    mk = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32) / np.sqrt(s[0]))
    return {"_att.wq": mk(d, h * hd), "_att.wk": mk(d, hkv * hd), "_att.wv": mk(d, hkv * hd),
            "_att.wo": mk(h * hd, d), "_att.q_norm": jnp.ones((1, hd)),
            "_att.k_norm": jnp.ones((1, hd))}


def test_mask_by_perturbation():
    """A noised position's output does not move when a noised token of
    another block, or a clean token of its own or a later block, changes;
    it moves for a clean token of an earlier block."""
    rng = np.random.RandomState(5)
    params = _gqa_params(rng)
    kw = dict(num_heads=4, num_kv_heads=2, head_dim=16, qk_norm=True, rope_theta=1e4,
              attention_mask="block_diffusion", mask_block_length=4)
    x = jnp.asarray(rng.randn(1, 2 * L, 64).astype(np.float32))
    base = _attention_layer(params, x, **kw)
    probe = 9                                   # noised, block 2

    def moved(i):
        out = _attention_layer(params, x.at[0, i].add(1.0), **kw)
        return float(jnp.max(jnp.abs(out[0, probe] - base[0, probe])))

    assert moved(2) == 0.0 and moved(20) == 0.0          # noised, other blocks
    assert moved(L + 8) == 0.0 and moved(L + 11) == 0.0  # clean, its own block
    assert moved(L + 12) == 0.0                          # clean, a later block
    assert moved(L + 7) > 1e-4 and moved(L + 0) > 1e-4   # clean, earlier blocks
    assert moved(10) > 1e-4                              # noised, its own block
    # a clean position never sees a noised one
    out = _attention_layer(params, x.at[0, 3].add(1.0), **kw)
    assert float(jnp.max(jnp.abs(out[0, L:] - base[0, L:]))) == 0.0


def test_old_multi_head_attention_unchanged():
    """The fused-QKV form is what it was: the layer is its own equations
    around `full_attention`, to the bit."""
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(2, 24, 32).astype(np.float32))
    wqkv = jnp.asarray(rng.randn(32, 96).astype(np.float32) * 0.1)
    wo = jnp.asarray(rng.randn(32, 32).astype(np.float32) * 0.1)
    got = _attention_layer({"_att.wqkv": wqkv, "_att.wo": wo}, x, num_heads=4,
                           causal_attention=True)
    qkv = jnp.einsum("btd,de->bte", x, wqkv).reshape(2, 24, 3, 4, 8)
    out = full_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                         lengths=jnp.full((2,), 24, jnp.int32), causal=True)
    want = jnp.einsum("bte,ed->btd", out.reshape(2, 24, 32), wo)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------- the head prologue


def _written_out_prologue(x, gain, positions, theta, scale, eps=1e-6):
    """What the layer computed before `ops/pallas_head_prologue.py`, in
    plain `jax.numpy` for autodiff: float32 RMS norm over each head,
    rotate-half rotary turn by half-row slices and a concatenate, the
    scale, one rounding to x's dtype. x [B, T, H, Dh]."""
    xf = x.astype(jnp.float32)
    if gain is not None:
        xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps) * gain
    if theta:
        half = xf.shape[-1] // 2
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
        cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
        x1, x2 = xf[..., :half], xf[..., half:]
        xf = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return (xf * scale).astype(x.dtype)


@pytest.mark.parametrize("path", ["xla", "kernel"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("norm,turn", [(True, True), (True, False), (False, True), (False, False)],
                         ids=["norm+turn", "norm", "turn", "scale"])
def test_head_prologue_matches_the_written_out_rule(norm, turn, dtype, path, monkeypatch):
    """`head_prologue` (one pass each way, a hand-written backward) against
    the rule written out under `jax.grad`: the value, dx and d gain, with
    and without the norm and the turn, through the XLA path and through the
    kernels (interpreted), at positions that repeat as the block-diffusion
    rule's do. float32 agrees to 1e-5 of the largest entry, bfloat16 to one
    rounding of the result."""
    from paddle_tpu.ops.pallas_head_prologue import head_prologue, turn_tables

    if path == "kernel":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    B, T, H, Dh = 2, 64, 4, 128
    rng = np.random.RandomState(31)
    x = jnp.asarray(3.0 * rng.randn(B, T, H, Dh), dtype)
    w = jnp.asarray(rng.randn(B, T, H, Dh), jnp.float32)
    gain = jnp.asarray(1.0 + 0.1 * rng.randn(Dh), jnp.float32) if norm else None
    positions = jnp.tile(jnp.arange(T // 2, dtype=jnp.int32), 2)
    theta, scale = (1e6 if turn else 0.0), Dh ** -0.5

    def new(x, gain):
        tables = turn_tables(positions, theta, Dh) if theta else None
        y = head_prologue(x.reshape(B, T, H * Dh), gain, tables, Dh, 1e-6, scale)
        assert y.shape == (B, T, H, Dh) and y.dtype == x.dtype
        return y

    old = lambda x, gain: _written_out_prologue(x, gain, positions, theta, scale)
    loss = lambda f: (lambda x, gain: jnp.sum(f(x, gain).astype(jnp.float32) * w))
    wrt = (0, 1) if norm else (0,)
    want = (old(x, gain), *jax.grad(loss(old), argnums=wrt)(x, gain))
    got = (new(x, gain), *jax.grad(loss(new), argnums=wrt)(x, gain))
    for name, a, b in zip(("y", "dx", "d gain"), got, want):
        assert a.dtype == b.dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if dtype == "bfloat16" and name != "d gain":
            # both round a float32 result once: a bfloat16 step apart at most
            np.testing.assert_array_less(np.abs(a - b), 2.0 ** -7 * np.abs(b) + 1e-30, err_msg=name)
        else:
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * np.abs(b).max(), err_msg=name)


def test_the_prologue_runs_under_the_layers_qkv_scope(monkeypatch):
    """Where the reader of `attention_ms.train` and the by-scope table
    looks: in the optimized program of a gradient step (the demo's two
    blocks at a head of 128, the kernels interpreted), every instruction of
    the prologue's forward, its recomputation and its hand-written backward
    carries an `op_name` under `multi_head_attention:<name>/qkv` (a
    `custom_vjp`'s backward takes its scopes from the call site)."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    gm = _demo_machine("seq_len=16,heads=2,kv_heads=1,head_dim=128")
    params = gm.init_params(seed=3)
    rng = np.random.RandomState(12)
    lens = lambda t: jnp.full((2,), t, jnp.int32)
    batch = {
        "tokens": Argument(ids=jnp.asarray(rng.randint(0, 97, (2, 32)), jnp.int32), seq_lengths=lens(32)),
        "labels": Argument(ids=jnp.asarray(rng.randint(0, 96, (2, 16)), jnp.int32), seq_lengths=lens(16)),
        "weights": Argument(value=jnp.asarray(rng.rand(2, 16, 1).astype(np.float32)), seq_lengths=lens(16)),
    }
    hlo = jax.jit(gm.grad_fn("block")).lower(params, batch, None).compile().as_text()
    names = re.findall(r'op_name="([^"]*head_prologue_[a-z]+[^"]*)"', hlo)
    under = re.compile(r"multi_head_attention:l[01]_attn\)*/qkv/head_prologue_(fwd|bwd)/")
    assert names and all(under.search(n) for n in names), [n for n in names if not under.search(n)][:3]
    seen = {(under.search(n).group(1), "rematted_computation" in n, "transpose(" in n) for n in names}
    # the forward, its recomputation inside the backward pass, the backward
    assert {("fwd", False, False), ("fwd", True, True), ("bwd", False, True)} <= seen, seen


# ------------------------------------------------------- the expert layer


def _moe_layer(params, x, first, count, experts=8, k=2, width=32):
    from paddle_tpu.layers.moe import moe_layer

    cfg = LayerConfig(name="moe", type="moe", size=x.shape[-1], experts=experts,
                      experts_per_token=k, expert_width=width,
                      experts_held_first=first, experts_held_count=count)
    held = {n: (v if n.endswith("router") else v[first:first + count])
            for n, v in params.items()}
    ctx = LayerContext(params=held, model=ModelConfig())
    out = moe_layer(cfg, [Argument(value=x)], ctx)
    counted = step_counters(ctx.outputs)
    return out.value, (counted["sum"]["moe.pairs_held"], counted["max"]["moe.load_max_over_mean"],
                       ctx.outputs["moe@chosen"].value)


def _moe_params(rng, d=64, experts=8, width=32):
    mk = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32) / np.sqrt(s[-2]))
    return {"_moe.router": mk(d, experts), "_moe.gate": mk(experts, d, width),
            "_moe.up": mk(experts, d, width), "_moe.down": mk(experts, width, d)}


def _ref_moe(ref, params, x, held):
    """The reference layer given the same share: the router whole, the held
    experts' stacks."""
    first, count = held
    p = {"l0_" + n.split(".")[1]: (v if n.endswith("router") else v[first:first + count])
         for n, v in params.items()}
    return ref.moe(p, 0, x, SIZES, "highest", held=held)


def test_the_shares_add_up_to_the_whole_layer(monkeypatch):
    """Four shares of 2 experts each: every share's output summed equals
    the uncut reference layer's (attention, which every chip computes
    alike, is outside this layer and counted once)."""
    monkeypatch.setattr(grouped_matmul, "CHUNK_ROWS", 32)    # several trips
    ref, rng = _reference(), np.random.RandomState(7)
    params, x = _moe_params(rng), jnp.asarray(rng.randn(96, 64).astype(np.float32))
    whole = _ref_moe(ref, params, x, (0, 8))
    shares = [_moe_layer(params, x, first, 2) for first in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(y for y, _ in shares), whole, atol=2e-5)
    assert sum(float(c[0]) for _, c in shares) == 96 * 2      # every pair, once
    for first, (y, _) in zip((0, 2, 4, 6), shares):
        np.testing.assert_allclose(y, _ref_moe(ref, params, x, (first, 2)), atol=2e-5)


def test_no_pair_is_dropped_when_every_token_goes_to_one_expert(monkeypatch):
    monkeypatch.setattr(grouped_matmul, "CHUNK_ROWS", 32)
    ref, rng = _reference(), np.random.RandomState(8)
    params = _moe_params(rng)
    x = jnp.asarray(rng.randn(128, 64).astype(np.float32)).at[:, 0].set(4.0)
    params["_moe.router"] = params["_moe.router"].at[0, 5].set(20.0)   # all to expert 5
    y, counters = _moe_layer(params, x, 4, 2)                          # holds 4 and 5
    np.testing.assert_allclose(y, _ref_moe(ref, params, x, (4, 2)), atol=2e-5)
    r = jax.nn.softmax(x @ params["_moe.router"], axis=-1)
    chosen = jax.lax.top_k(r, 2)[1]
    assert int(jnp.sum(chosen == 5)) == 128
    assert float(counters[0]) == float(jnp.sum((chosen >= 4) & (chosen < 6)))
    assert float(counters[1]) > 1.5                                    # max over mean
    # the choices it publishes are the reference's, and the reference given
    # them as data computes the same layer
    np.testing.assert_array_equal(np.sort(counters[2], -1), np.sort(chosen, -1))
    p = {"l0_" + n.split(".")[1]: (v if n.endswith("router") else v[4:6])
         for n, v in params.items()}
    np.testing.assert_allclose(ref.moe(p, 0, x, SIZES, "highest", held=(4, 2), given=counters[2]),
                               y, atol=2e-5)


def test_layer_counters_fold_by_name_whatever_layer_published_them():
    """`publish_counter` / `step_counters` / `note_counters` name no layer
    type: sums add up over layers and steps, a max holds the last step's."""
    from paddle_tpu.layers.base import note_counters, publish_counter
    from paddle_tpu.observability import metrics as obs

    ctx = LayerContext(params={}, model=ModelConfig())
    for name, rows, peak in (("a", 3.0, 1.5), ("b", 4.0, 2.5)):
        cfg = LayerConfig(name=name, type="anything", size=1)
        publish_counter(cfg, ctx, "test.rows", rows)
        publish_counter(cfg, ctx, "test.peak", peak, how="max")
    ctx.outputs["a"] = Argument(value=jnp.zeros((1,)))          # an ordinary output
    got = jax.device_get(step_counters(ctx.outputs))
    assert got == {"sum": {"test.rows": 7.0}, "max": {"test.peak": 2.5}}
    assert step_counters({"a": ctx.outputs["a"]}) == {}
    before = obs.registry().snapshot().get("test.rows", 0.0)
    note_counters(got)
    note_counters(got)
    snap = obs.registry().snapshot()
    assert snap["test.rows"] - before == 14.0 and snap["test.peak"] == 2.5
    with pytest.raises(ValueError):
        publish_counter(cfg, ctx, "test.rows", 1.0, how="mean")


def test_expert_layer_gradients_match_the_reference(monkeypatch):
    monkeypatch.setattr(grouped_matmul, "CHUNK_ROWS", 32)
    ref, rng = _reference(), np.random.RandomState(9)
    params, x = _moe_params(rng), jnp.asarray(rng.randn(64, 64).astype(np.float32))
    w = jnp.asarray(rng.randn(64, 64).astype(np.float32))
    mine = jax.grad(lambda p, x: jnp.sum(_moe_layer(p, x, 2, 4)[0] * w), argnums=(0, 1))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(_ref_moe(ref, p, x, (2, 4)) * w), argnums=(0, 1))(params, x)
    np.testing.assert_allclose(mine[1], want[1], atol=1e-4)
    for n in params:
        np.testing.assert_allclose(mine[0][n], want[0][n], atol=1e-4, err_msg=n)


def test_the_expert_layer_refuses_a_mesh():
    from paddle_tpu.layers.moe import moe_layer

    ctx = LayerContext(params={}, model=ModelConfig(), mesh=object())
    with pytest.raises(NotImplementedError, match="mesh"):
        moe_layer(LayerConfig(name="moe", type="moe"), [Argument(value=jnp.zeros((4, 8)))], ctx)


# ------------------------------------------- cost weight, slice, recomputation


def test_cost_weight_a_position_and_a_sample():
    from paddle_tpu.layers.cost import multi_class_cross_entropy

    rng = np.random.RandomState(10)
    p = jax.nn.softmax(jnp.asarray(rng.randn(3, 5, 7).astype(np.float32)), -1)
    ids = jnp.asarray(rng.randint(0, 7, (3, 5)), jnp.int32)
    lens = jnp.asarray([5, 3, 4], jnp.int32)
    out, lab = Argument(value=p, seq_lengths=lens), Argument(ids=ids, seq_lengths=lens)
    cfg = LayerConfig(name="cost", type="multi-class-cross-entropy", coeff=1.0)
    cfg.inputs = [type("I", (), {"input_layer_name": "x", "input_layer_argument": ""})()] * 3
    ctx = LayerContext(params={}, model=ModelConfig())
    ce = -jnp.log(jnp.take_along_axis(p, ids[..., None], -1)[..., 0])
    mask = jnp.arange(5)[None] < lens[:, None]
    w_pos = jnp.asarray(rng.rand(3, 5, 1).astype(np.float32))
    got = multi_class_cross_entropy(cfg, [out, lab, Argument(value=w_pos, seq_lengths=lens)], ctx)
    np.testing.assert_allclose(got.value[:, 0], jnp.sum(ce * w_pos[..., 0] * mask, 1), rtol=1e-6)
    w_seq = jnp.asarray(rng.rand(3, 1).astype(np.float32))
    got = multi_class_cross_entropy(cfg, [out, lab, Argument(value=w_seq)], ctx)
    np.testing.assert_allclose(got.value[:, 0], jnp.sum(ce * mask, 1) * w_seq[:, 0], rtol=1e-6)


def test_seq_slice_takes_a_part_of_the_time_axis():
    from paddle_tpu.layers.sequence import seq_slice_layer

    x = jnp.arange(2 * 8 * 3, dtype=jnp.float32).reshape(2, 8, 3)
    arg = Argument(value=x, seq_lengths=jnp.asarray([8, 6], jnp.int32))
    ctx = LayerContext(params={}, model=ModelConfig())
    for part, lens in ((0, [4, 4]), (1, [4, 2])):
        cfg = LayerConfig(name="s", type="seq_slice", seq_parts=2, seq_part=part)
        out = seq_slice_layer(cfg, [arg], ctx)
        np.testing.assert_array_equal(out.value, x[:, part * 4:(part + 1) * 4])
        assert out.seq_lengths.tolist() == lens


DEMO = os.path.join(REPO, "demo", "block_diffusion_moe")


def _demo_machine(args=""):
    from paddle_tpu.config import parse_config
    from paddle_tpu.graph.machine import GradientMachine

    cwd = os.getcwd()
    os.chdir(DEMO)
    sys.path.insert(0, DEMO)
    try:
        conf = parse_config("trainer_config.py", args)
    finally:
        os.chdir(cwd)
        sys.path.remove(DEMO)
    return GradientMachine(conf.model_config)


def _pallas_calls(jaxpr, into=None):
    """The names of every `pallas_call` of a jaxpr, sub-jaxprs included."""
    into = [] if into is None else into
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            into.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, into)
    return into


@pytest.mark.parametrize("rule,seq_len", [
    ("block_diffusion", 64), ("causal", 64),     # 2L = 128: the flash kernel, interpreted
    ("block_diffusion", 32),                     # the kernel's gate refuses 64: the XLA path
], ids=["block_diffusion", "causal", "xla_path"])
def test_block_recomputation_changes_no_number(rule, seq_len, monkeypatch):
    """`remat="block"` against `remat="none"`: the same loss and gradients,
    and the flash forward ONCE a layer (the block keeps the kernel's named
    `out` and `lse`; a bare checkpoint ran it twice); a block with no such
    kernel keeps nothing."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    gm = _demo_machine(f"seq_len={seq_len}")
    assert {l.remat_block for l in gm.model.layers} == {"", "block0", "block1"}
    for l in gm.model.layers:
        if l.type == "multi_head_attention":
            l.attention_mask = rule
    n = seq_len                                  # corpus tokens; 2n positions fed
    params = gm.init_params(seed=3)
    rng = np.random.RandomState(11)
    lens = lambda t: jnp.full((2,), t, jnp.int32)
    batch = {
        "tokens": Argument(ids=jnp.asarray(rng.randint(0, 97, (2, 2 * n)), jnp.int32),
                           seq_lengths=lens(2 * n)),
        "labels": Argument(ids=jnp.asarray(rng.randint(0, 96, (2, n)), jnp.int32),
                           seq_lengths=lens(n)),
        "weights": Argument(value=jnp.asarray(rng.rand(2, n, 1).astype(np.float32)),
                            seq_lengths=lens(n)),
    }
    plain = jax.jit(gm.grad_fn("none"))(params, batch, None)
    blocks = jax.jit(gm.grad_fn("block"))(params, batch, None)
    np.testing.assert_allclose(plain[0], blocks[0], rtol=1e-6)
    for name in params:
        np.testing.assert_allclose(plain[1][name], blocks[1][name], atol=1e-5, err_msg=name)
    # a block's extras cross its edge
    assert {"l1_moe@chosen", "l1_moe@counter.sum:moe.pairs_held"} <= set(blocks[2])
    # the kernels of the gradient's program: a layer's forward once, with
    # or without blocks (2 layers); `attention_delta` reads the kept `out`
    # and its cotangent for the backward
    a_layer = ["attention_bwd", "attention_delta", "attention_fwd"] if seq_len == 64 else []
    for remat in ("none", "block"):
        jaxpr = jax.make_jaxpr(gm.grad_fn(remat))(params, batch, None).jaxpr
        assert sorted(_pallas_calls(jaxpr)) == sorted(2 * a_layer), remat
