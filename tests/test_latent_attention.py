"""Latent attention (a head's scores in two parts, 'nope' lanes of its own
and rotary lanes all heads share from one key head, values of another
width, keys and values projected up from one latent) and a sigmoid router
with a selection bias: the flash kernels with split score parts, the
interleaved turn, the layer, the router, the shares, the new scopes and the
counter, against the plain reference (`perfbench/reference/deepseek_v3.py`),
and the trainer's first three steps against it through the benchmark's own
harness, at a small size on the CPU.

Small size: hidden 64, 4 heads of 16 + 8 score lanes and 16 value lanes
over a latent of 32, a dense layer 128 wide, 16 routed experts of width 32
with 3 a token and 2 shared experts, 5 layers (dense, four sparse),
vocabulary 97, 32 positions.
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
from paddle_tpu.layers.base import LayerContext, step_counters
from paddle_tpu.ops import grouped_matmul
from paddle_tpu.ops.attention_mask import MaskRule
from paddle_tpu.ops.pallas_attention import flash_attention, supported
from paddle_tpu.parallel.sequence_parallel import rule_attention
from paddle_tpu.proto import LayerConfig, ModelConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "perfbench", "configs")
CONFIG = os.path.join(CONFIGS, "kanana-2-30b-a3b-ep8")
T = 32
SMALL = {
    "hidden_size": 64, "num_attention_heads": 4, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32, "rope_theta": 100,
    "intermediate_size": 128, "moe_intermediate_size": 32, "n_routed_experts": 16,
    "n_routed_experts_total": 16, "num_experts_per_tok": 3, "vocab_size": 97,
    "target_dict_dim": 97, "trained_positions": T, "selection_bias_std": 0.05,
}


def _load(path):
    sys.path.insert(0, REPO)
    from perfbench.harness import load_module

    return load_module(path)


def _reference():
    return _load(os.path.join(REPO, "perfbench", "reference", "deepseek_v3.py"))


def _sizes(**over):
    with open(CONFIG + ".json") as f:
        real = json.load(f)
    cfg = dict(real, **SMALL)
    cfg["settings"] = dict(real["settings"], dtype="float32")
    cfg.update(over)
    return cfg


# ------------------------------------------------------------- the kernels


def _parts(seed, B=2, T=256, H=4, widths=(128, 64), key_heads=(4, 1), Dv=128, Hv=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2 * len(widths) + 2)
    q = tuple(jax.random.normal(ks[i], (B, T, H, d)) for i, d in enumerate(widths))
    k = tuple(jax.random.normal(ks[len(widths) + i], (B, T, h, d))
              for i, (d, h) in enumerate(zip(widths, key_heads)))
    return q, k, jax.random.normal(ks[-2], (B, T, Hv, Dv)), jax.random.normal(ks[-1], (B, T, H, Dv))


KERNEL_CASES = {
    # the latent cell's form: 128 lanes a head + 64 shared from ONE key head, values of 128
    "causal-128+64-shared-over-128": (MaskRule("causal"), dict()),
    # one operand of 192 against values of 128: the single-operand form
    "causal-192-over-128": (MaskRule("causal"), dict(widths=(192,), key_heads=(4,))),
    # two parts under the rules the other cells run: a window's pairs add to two query
    # tiles' dq a part, and a full rule walks every key tile
    "sliding_window-128+64-shared-over-128": (MaskRule("sliding_window", 0, 96), dict()),
    "full-128+64-shared-over-128": (MaskRule("full"), dict()),
    # the shapes of the other two cells: ONE part, grouped heads, under their rules
    "sliding_window-one-part": (MaskRule("sliding_window", 0, 96),
                                dict(widths=(128,), key_heads=(2,), Hv=2)),
    "block_diffusion-one-part": (MaskRule("block_diffusion", 4),
                                 dict(widths=(128,), key_heads=(2,), Hv=2)),
    # the cells' grouped heads at their counts, every operand a column block of
    # [B, T, heads*128]: dK and dV fold 8 (or 6) lane blocks onto one
    "block_diffusion-32-over-4": (MaskRule("block_diffusion", 4),
                                  dict(B=1, H=32, widths=(128,), key_heads=(4,), Hv=4)),
    "sliding_window-64-over-8": (MaskRule("sliding_window", 0, 96),
                                 dict(B=1, H=64, widths=(128,), key_heads=(8,), Hv=8)),
    "causal-48-over-8": (MaskRule("causal"), dict(B=1, H=48, widths=(128,), key_heads=(8,), Hv=8)),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernels_with_score_parts_match_the_xla_path(case):
    """The two flash kernels in interpret mode against `rule_attention`'s
    XLA path: the result and all the gradients (five with two score parts),
    a padded sequence among them; a shared key head's gradient is the sum
    over its query heads."""
    rule, kw = KERNEL_CASES[case]
    q, k, v, w = _parts(7, **kw)
    lengths = jnp.array([256, 256 - 37][-v.shape[0]:], jnp.int32)
    inside = (jnp.arange(256)[None, :] < lengths[:, None])[:, :, None, None]
    loss = lambda fn: (lambda q, k, v: jnp.sum(jnp.where(inside, fn(q, k, v), 0.0) * w))
    kernel = lambda q, k, v: flash_attention(q, k, v, lengths=lengths, rule=rule,
                                             interpret=True, block=128)
    xla = lambda q, k, v: rule_attention(q, k, v, lengths, rule)
    got = jax.value_and_grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(loss(xla), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]), jax.tree_util.tree_leaves(want[1])):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-5)


def _transposes(jaxpr):
    """The `transpose` equations of whole operands ([B, ., ., D]: a kernel's
    own turn of a tile's statistics is rank 2) in a jaxpr and in every
    jaxpr inside it, by the shape each writes."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "transpose" and len(eqn.outvars[0].aval.shape) == 4:
            found.append(eqn.outvars[0].aval.shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _transposes(sub)
    return found


def test_only_the_narrow_many_head_part_is_transposed():
    """Which layout a part takes is its shape's alone (`by_column`): in the
    traced forward and backward of the latent cell's form the only
    transposes are q_rope's (4 heads of 64: Mosaic cannot cut a 64-lane
    block out of a wider row), in and again for the backward and its dq
    out; q_nope, k_nope, v, the ONE k_rope head, the result, its cotangent
    and their gradients are read and written where they lie. A form with no
    such part traces no transpose at all."""
    from paddle_tpu.ops.pallas_attention import by_column

    assert by_column(32, 128) and by_column(1, 64) and by_column(4, 256)
    assert not by_column(32, 64) and not by_column(4, 16)
    rule = MaskRule("causal")
    q, k, v, w = _parts(5, B=1)
    loss = lambda q, k, v: jnp.sum(flash_attention(q, k, v, rule=rule, interpret=True, block=128) * w)
    both = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    assert sorted(_transposes(both(q, k, v).jaxpr)) == [(1, 4, 256, 64)] * 2 + [(1, 256, 4, 64)]
    one = lambda q, k, v: loss((q,), (k,), v)
    assert _transposes(jax.make_jaxpr(jax.value_and_grad(one, argnums=(0, 1, 2)))(q[0], k[0], v).jaxpr) == []


def test_one_part_is_the_kernel_as_it_was():
    """A 1-tuple of parts and the bare arrays trace to the same program:
    the other cells' kernels are what they were."""
    q, k, v, _ = _parts(3, widths=(128,), key_heads=(2,), Hv=2)
    rule = MaskRule("sliding_window", 0, 96)
    bare = jax.make_jaxpr(lambda q, k, v: flash_attention(q, k, v, rule=rule, interpret=True,
                                                          block=128))(q[0], k[0], v)
    tupled = jax.make_jaxpr(lambda q, k, v: flash_attention((q,), (k,), v, rule=rule,
                                                            interpret=True, block=128))(q[0], k[0], v)
    assert str(bare) == str(tupled)


def test_the_gate_takes_the_widths():
    """`supported` admits the latent cell's widths at its length and keeps
    its answers for one width: a part narrower than a lane tile is planned
    as a whole one."""
    assert supported(8192, (128, 64), 2, 128) and supported(8192, 192, 2, 128)
    assert supported(8192, 128) and supported(17408, 128) and not supported(17920, 128)
    assert not supported(8192, (128, 60), 2, 128)          # no multiple of 8
    assert not supported(8192, (128, 64), 2, 384)          # a value wider than 256
    # three lane tiles of keys and one of values against two and one: a shorter axis
    assert supported(17408, (128, 128), 2, 128) != supported(17408, 128, 2, 128)


# ---------------------------------------------------------- the interleaved turn


def test_the_interleaved_turn_against_the_formula():
    """The prologue's rotate-half turn over columns in `interleaved_order`
    gives, for q and k alike, the scores of the turn that pairs the lanes
    (2i, 2i + 1), written out here: y[2i] = x[2i] cos - x[2i+1] sin, y[2i+1]
    = x[2i+1] cos + x[2i] sin at angle pos * theta^(-2i/d)."""
    from paddle_tpu.ops.pallas_head_prologue import head_prologue, interleaved_order, turn_tables

    rng, d, heads, theta = np.random.RandomState(1), 8, 3, 100.0
    q = rng.randn(2, T, heads * d).astype(np.float32)
    k = rng.randn(2, T, d).astype(np.float32)
    order = interleaved_order(d)
    assert order.tolist() == [0, 2, 4, 6, 1, 3, 5, 7]
    tables = turn_tables(jnp.arange(T), theta, d, d)

    def turned(x, n):
        cols = np.concatenate([h * d + order for h in range(n)])
        y = head_prologue(jnp.asarray(x[..., cols]), None, tables, d, 1e-6, 1.0)
        return np.asarray(y).transpose(0, 2, 1, 3)                                    # [B, n, T, d]

    ang = np.arange(T)[:, None] * theta ** (-2.0 * np.arange(d // 2) / d)[None, :]

    def written_out(x, n):
        x = x.reshape(2, T, n, d)
        c, s = np.cos(ang)[None, :, None, :], np.sin(ang)[None, :, None, :]
        y = np.empty_like(x)
        y[..., 0::2] = x[..., 0::2] * c - x[..., 1::2] * s
        y[..., 1::2] = x[..., 1::2] * c + x[..., 0::2] * s
        return y.transpose(0, 2, 1, 3)

    got = np.einsum("bhqd,bkd->bhqk", turned(q, heads), turned(k, 1)[:, 0])
    want = np.einsum("bhqd,bkd->bhqk", written_out(q, heads), written_out(k, 1)[:, 0])
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and the reference's own turn is the written-out one
    ref = _reference()
    theirs = ref.rotary(jnp.asarray(q[0].reshape(T, heads, d)), jnp.arange(T), theta, True)
    np.testing.assert_allclose(theirs.transpose(1, 0, 2), written_out(q, heads)[0], atol=2e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("turn", [True, False], ids=["turn", "scale"])
def test_narrow_heads_go_through_the_prologue_kernels_two_to_a_lane_tile(turn, dtype, monkeypatch):
    """The latent cell's rotary heads (64 lanes, no norm, turned whole):
    the kernels (interpreted) take them two to a 128-lane tile with the
    turn's tables tiled (`_packed_tables`), and give what the XLA path
    gives, value and dx: float32 to a rounding of the arithmetic, bfloat16
    to one rounding of the result. A norm, or ONE head of 64 (k_rope), keeps
    the XLA path."""
    from paddle_tpu.ops import pallas_head_prologue as hp

    B, Tn, H, d = 2, 64, 4, 64
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(B, Tn, H * d), dtype)
    w = jnp.asarray(rng.randn(B, Tn, H, d), jnp.float32)
    tables = hp.turn_tables(jnp.arange(Tn), 1e4, d, d) if turn else None
    run = lambda x: hp.head_prologue(x, None, tables, d, 1e-6, 0.3)
    both = lambda: (run(x), jax.grad(lambda x: jnp.sum(run(x).astype(jnp.float32) * w))(x))
    calls = []
    real = hp._call
    monkeypatch.setattr(hp, "_call", lambda *a, **kw: calls.append(a[1]) or real(*a, **kw))
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    want = both()
    assert not calls
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    got = both()
    assert sorted(set(calls)) == ["head_prologue_bwd", "head_prologue_fwd"]
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        tol = 2.0 ** -7 * np.abs(b) + 1e-30 if dtype == "bfloat16" else 1e-6 * np.abs(b).max()
        np.testing.assert_array_less(np.abs(a - b), tol + 1e-38)
    del calls[:]
    hp.head_prologue(x[..., :d], None, tables, d, 1e-6, 1.0)                    # one head of 64
    hp.head_prologue(x, jnp.ones((d,)), tables, d, 1e-6, 1.0)                   # a norm a head
    assert not calls


# -------------------------------------------------------------- the layers


def _attention_layer(params, x, **kw):
    from paddle_tpu.layers.attention import multi_head_attention

    cfg = LayerConfig(name="att", type="multi_head_attention", size=x.shape[-1], **kw)
    ctx = LayerContext(params=params, model=ModelConfig())
    arg = Argument(value=x, seq_lengths=jnp.full((x.shape[0],), x.shape[1], jnp.int32))
    return multi_head_attention(cfg, [arg], ctx).value


@pytest.mark.parametrize("interleave", [True, False], ids=["interleaved", "rotate-half"])
def test_latent_attention_matches_the_reference(interleave):
    """The latent layer's value and every parameter's and the input's
    gradient against the reference's layer, with the published pairing of
    the rotary lanes and with rotate-half. float32 on both sides: 2e-5."""
    ref, sizes, rng = _reference(), _sizes(rope_interleave=interleave), np.random.RandomState(30)
    shapes = ref.param_shapes(sizes)
    names = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")
    p = {n: jnp.asarray(rng.randn(*shapes[f"l1_{n}"]).astype(np.float32)
                        / np.sqrt(shapes[f"l1_{n}"][0])) for n in names}
    p["kv_norm"] = 1.0 + 0.1 * p["kv_norm"]
    x = jnp.asarray(rng.randn(T, 64).astype(np.float32))
    w = jnp.asarray(rng.randn(T, 64).astype(np.float32))
    kw = dict(num_heads=4, head_dim=16, kv_latent_dim=32, rope_head_dim=8, rope_theta=100.0,
              rope_interleave=interleave, attention_mask="causal")
    mine = lambda p, x: _attention_layer({"_att." + n: v for n, v in p.items()}, x[None], **kw)[0]
    theirs = lambda p, x: ref.attention({f"l1_{n}": v for n, v in p.items()}, 1, x, sizes, "highest")
    np.testing.assert_allclose(mine(p, x), theirs(p, x), atol=2e-5)
    got = jax.grad(lambda p, x: jnp.sum(mine(p, x) * w), argnums=(0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(theirs(p, x) * w), argnums=(0, 1))(p, x)
    np.testing.assert_allclose(got[1], want[1], atol=2e-5)
    for n in p:
        np.testing.assert_allclose(got[0][n], want[0][n], atol=2e-5, err_msg=n)
    # the pairing matters: the other one is another function
    other = _attention_layer({"_att." + n: v for n, v in p.items()}, x[None],
                             **dict(kw, rope_interleave=not interleave))[0]
    assert float(jnp.max(jnp.abs(other - mine(p, x)))) > 1e-3


def _moe(params, x, first=0, count=16, k=3, factor=2.448, **kw):
    from paddle_tpu.layers.moe import moe_layer

    cfg = LayerConfig(name="moe", type="moe", size=x.shape[-1], experts=16, experts_per_token=k,
                      expert_width=32, experts_held_first=first, experts_held_count=count,
                      routed_scaling_factor=factor, **kw)
    held = {n: (v if "router" in n else v[first:first + count]) for n, v in params.items()}
    ctx = LayerContext(params=held, model=ModelConfig())
    return moe_layer(cfg, [Argument(value=x)], ctx).value, ctx


def _layer_weights(rng):
    mk = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32) / np.sqrt(s[-2]))
    routed = {"router": mk(64, 16), "router_bias": 0.05 * mk(1, 16), "gate": mk(16, 64, 32),
              "up": mk(16, 64, 32), "down": mk(16, 32, 64)}
    shared = {"gate": mk(64, 64), "up": mk(64, 64), "down": mk(64, 64)}
    return routed, shared


def test_sigmoid_router_with_a_selection_bias_matches_the_reference(monkeypatch):
    """The routed part (sigmoid scores, the bias in the CHOICE and not in
    the weights, the guard, the factor) and every gradient against the
    reference; the bias gets none; the counter counts the pairs the bias
    moved; and without the bias the choice is another."""
    monkeypatch.setattr(grouped_matmul, "CHUNK_ROWS", 32)
    ref, sizes, rng = _reference(), _sizes(), np.random.RandomState(31)
    routed, _ = _layer_weights(rng)
    x = jnp.asarray(rng.randn(96, 64).astype(np.float32))
    w = jnp.asarray(rng.randn(96, 64).astype(np.float32))
    kw = dict(score_function="sigmoid", selection_bias=True)
    mine = lambda p, x: _moe({"_moe." + n: v for n, v in p.items()}, x, **kw)[0]
    theirs = lambda p, x: ref.moe({f"l1_{n}": v for n, v in p.items()}, 1, x, sizes, "highest")
    np.testing.assert_allclose(mine(routed, x), theirs(routed, x), atol=5e-5)
    got = jax.grad(lambda p, x: jnp.sum(mine(p, x) * w), argnums=(0, 1))(routed, x)
    want = jax.grad(lambda p, x: jnp.sum(theirs(p, x) * w), argnums=(0, 1))(routed, x)
    np.testing.assert_allclose(got[1], want[1], atol=5e-5)
    for n in routed:
        np.testing.assert_allclose(got[0][n], want[0][n], atol=5e-5, err_msg=n)
    assert not np.any(np.asarray(got[0]["router_bias"]))
    # the counter against a count by hand
    _, ctx = _moe({"_moe." + n: v for n, v in routed.items()}, x, **kw)
    r = jax.nn.sigmoid(jnp.dot(x, routed["router"], precision="highest"))
    biased = np.asarray(jax.lax.top_k(r + routed["router_bias"], 3)[1])
    plain = np.asarray(jax.lax.top_k(r, 3)[1])
    moved = sum(len(set(a) - set(b)) for a, b in zip(biased, plain))
    assert 0 < moved < 0.5 * biased.size
    assert float(step_counters(ctx.outputs)["sum"]["moe.bias_moved_pairs"]) == moved
    np.testing.assert_array_equal(np.sort(np.asarray(ctx.outputs["moe@chosen"].value), -1),
                                  np.sort(biased, -1))
    # no bias configured: no parameter read, no counter, the unbiased choice
    _, ctx = _moe({"_moe." + n: v for n, v in routed.items() if n != "router_bias"}, x,
                  score_function="sigmoid")
    assert "moe.bias_moved_pairs" not in step_counters(ctx.outputs)["sum"]
    np.testing.assert_array_equal(np.sort(np.asarray(ctx.outputs["moe@chosen"].value), -1),
                                  np.sort(plain, -1))


def test_the_eight_shares_add_up_to_the_whole_layer(monkeypatch):
    """8 shares of two experts each: their routed parts, scaling factor
    included, summed, plus the shared MLP ONCE (every chip computes it
    alike), give what the uncut reference gives for the whole sparse
    layer."""
    from paddle_tpu.layers.gated_mlp import gated_mlp_layer

    monkeypatch.setattr(grouped_matmul, "CHUNK_ROWS", 32)
    ref, sizes, rng = _reference(), _sizes(), np.random.RandomState(32)
    routed, shared = _layer_weights(rng)
    x = jnp.asarray(rng.randn(96, 64).astype(np.float32))
    p = {**{f"l1_{n}": v for n, v in routed.items()},
         **{f"l1_shared_{n}": v for n, v in shared.items()}}
    whole = ref.feed_forward(p, 1, x, sizes, "highest")
    assert sizes["routed_scaling_factor"] == 2.448 and sizes["n_shared_experts"] == 2
    prog = {"_moe." + n: v for n, v in routed.items()}
    kw = dict(score_function="sigmoid", selection_bias=True)
    shares = sum(_moe(prog, x, first, 2, **kw)[0] for first in range(0, 16, 2))
    cfg = LayerConfig(name="mlp", type="gated_mlp", size=64, expert_width=64)
    once = gated_mlp_layer(cfg, [Argument(value=x)], LayerContext(
        params={"_mlp." + n: v for n, v in shared.items()}, model=ModelConfig())).value
    np.testing.assert_allclose(shares + once, whole, atol=5e-5)
    np.testing.assert_allclose(_moe(prog, x, 0, 16, **kw)[0], shares, atol=5e-5)
    # a share alone is the reference's for the same range
    part = ref.moe({**p, **{f"l1_{n}": routed[n][4:6] for n in ("gate", "up", "down")}},
                   1, x, sizes, "highest", held=(4, 2))
    np.testing.assert_allclose(_moe(prog, x, 4, 2, **kw)[0], part, atol=5e-5)


def test_the_dsl_refuses_what_is_not_built():
    from paddle_tpu.config import parse_config
    from paddle_tpu.trainer_config_helpers import (
        data_layer, moe_layer, multi_head_attention_layer, outputs, settings)

    def built(make):
        def config():
            settings(batch_size=2, learning_rate=1e-3)
            outputs(make(data_layer(name="x", size=8)))
        return lambda: parse_config(config)

    with pytest.raises(NotImplementedError, match="n_group"):
        built(lambda x: moe_layer(x, 8, 2, 4, n_group=2))()
    with pytest.raises(AssertionError, match="score_function"):
        built(lambda x: moe_layer(x, 8, 2, 4, score_function="tanh"))()
    with pytest.raises(AssertionError, match="latent form"):
        built(lambda x: multi_head_attention_layer(x, 2, head_dim=4, rope_head_dim=2))()
    conf = built(lambda x: moe_layer(x, 8, 2, 4, score_function="sigmoid", selection_bias=True,
                                     name="m"))()
    bias = {p.name: p for p in conf.model_config.parameters}["_m.router_bias"]
    assert bias.is_static and bias.dims == [1, 8] and bias.initial_std == 0.0


# ----------------------------------------------------------- the whole model


def _machine(tmp_path, **over):
    from paddle_tpu.config import parse_config
    from paddle_tpu.graph.machine import GradientMachine

    sizes = _sizes(**over)
    path = os.path.join(str(tmp_path), "small.json")
    with open(path, "w") as f:
        json.dump(sizes, f)
    conf = parse_config(CONFIG + ".py", f"config_json={path},feed=x,feed_list=y,batch=2")
    return GradientMachine(conf.model_config), sizes, conf


def _batch(rng, n=2):
    labels = rng.randint(0, 97, (n, T)).astype(np.int32)
    tokens = np.concatenate([np.zeros((n, 1), np.int32), labels[:, :-1]], 1)
    lens = jnp.full((n,), T, jnp.int32)
    return tokens, labels, {"tokens": Argument(ids=jnp.asarray(tokens), seq_lengths=lens),
                            "labels": Argument(ids=jnp.asarray(labels), seq_lengths=lens)}


def test_the_small_model_matches_the_reference(tmp_path):
    """The dense and the sparse block through the configuration's own DSL
    file at the small size, under recomputation blocks: the loss and EVERY
    leaf's gradient against the reference computed under the program's
    expert choices (which are the reference's own here: float32 on both
    sides); the selection biases are static and get no gradient. 1e-6
    absolute on gradients of 0.01 to 0.1: float32 rounding."""
    gm, sizes, conf = _machine(tmp_path)
    ref = _reference()
    p_ref = ref.init_params(sizes, 5)
    shapes = gm.init_params(seed=1)
    assert set(shapes) == set(sizes["param_map"])
    static = sorted(p.name for p in conf.model_config.parameters if p.is_static)
    assert static == [f"_l{l}_moe.router_bias" for l in range(1, 5)]
    assert sorted(sizes["param_map"][n] for n in static) == ref.static_leaves(sizes)
    params = {k: jnp.asarray(p_ref[v]).reshape(shapes[k].shape)
              for k, v in sizes["param_map"].items()}
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
    # the bias engaged: some pairs moved, in every sparse layer
    counted = step_counters(outs)["sum"]
    assert 0 < float(counted["moe.bias_moved_pairs"]) < 0.5 * float(counted["moe.pairs_held"])


def test_the_new_scopes_and_the_counter_are_in_the_steps_program(tmp_path):
    """Where the reader of `latent_proj_ms.train` looks: the optimized
    program of a gradient step carries `op_name`s under
    `multi_head_attention:<name>/latent_down` and `/latent_up`, forward and
    backward, beside `qkv`, `core` and `out`; and the step's outputs hold
    the counter `moe.bias_moved_pairs` of every sparse layer."""
    gm, _, _ = _machine(tmp_path)
    _, _, batch = _batch(np.random.RandomState(2))
    fn = jax.jit(gm.grad_fn("block", sparse=False))
    params = gm.init_params(seed=3)
    hlo = fn.lower(params, batch, None).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for l in range(5):
        for part in ("qkv", "latent_down", "latent_up", "core", "out"):
            scope = f"multi_head_attention:l{l}_attn" + rf"\)*/{part}/"
            hits = [n for n in names if re.search(scope, n)]
            assert hits and any("transpose(" in n for n in hits), scope
    outs = fn(params, batch, None)[2]
    assert sorted(k for k in outs if "moe.bias_moved_pairs" in k) == [
        f"l{l}_moe@counter.sum:moe.bias_moved_pairs" for l in range(1, 5)]


@pytest.mark.parametrize("other", ["sdar-30b-a3b-ep8", "laguna-xs.2-ep16", "seqtoseq-wmt14"])
def test_the_new_arguments_leave_the_other_configurations_protos_alone(other):
    """The three configurations the benchmark had serialize as they did: no
    layer of theirs carries a new field (a default is left out of the
    serialized proto), and no parameter is new."""
    from paddle_tpu.config import parse_config

    conf = parse_config(os.path.join(CONFIGS, other + ".py"), "feed=x,feed_list=y")
    text = str(conf.model_config.to_dict())
    for field in ("kv_latent_dim", "rope_head_dim", "value_head_dim", "rope_interleave",
                  "score_function", "selection_bias"):
        assert field not in text, field
    assert not [p.name for p in conf.model_config.parameters
                if p.name.endswith((".router_bias", ".wkv_a", ".wkv_b", ".kv_norm"))]
    assert not any(p.is_static for p in conf.model_config.parameters)


# ------------------------------- the trainer's three steps and the reference


def _tiny_root(tmp, dtype):
    """A temporary copy of the benchmark with the small configuration and a
    cell beside it, as NEW files (the harness finds them by name)."""
    root = os.path.join(str(tmp), "root")
    shutil.copytree(os.path.join(REPO, "perfbench"), os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = _sizes()
    cfg["settings"] = dict(cfg["settings"], dtype=dtype, learning_rate=1e-3)
    cfg["dsl"] = "kanana-2-30b-a3b-ep8.py"
    with open(os.path.join(root, "perfbench", "configs", "tiny-latent.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(REPO, "perfbench", "traffic", "lm_4x8192.json")) as f:
        mix = json.load(f)
    mix.update(lengths={"file": {"dist": "fixed", "value": T}},
               arrival={"kind": "batches", "batch": 4, "cycle": 6})
    with open(os.path.join(root, "perfbench", "traffic", "lm_tiny.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(REPO, "perfbench", "workloads", "kanana.train.json")) as f:
        wl = json.load(f)
    assert (wl["entry"], wl["compare"]) == ("train_routed", "train_steps_static")
    wl.update(config="tiny-latent", traffic="lm_tiny", limits=LIMITS[dtype])
    with open(os.path.join(root, "perfbench", "workloads", "tiny.latent.json"), "w") as f:
        json.dump(wl, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny-latent", "source": "test", "reduced": [], "why": "test",
                         "file": "perfbench/configs/tiny-latent.json"}]
    bench["workloads"] = [{"name": "tiny.latent", "config": "tiny-latent", "traffic": "lm_tiny",
                           "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.latent"]
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
    benchmark's entry `train_routed`, the comparison `train_steps_static`),
    against the reference's three steps; the static biases do not move."""
    sys.path.insert(0, REPO)
    from perfbench import harness

    root = _tiny_root(tmp_path, dtype)
    out = io.StringIO()
    harness.run_cell(["--workload", "tiny.latent", "--seed", "2147483659", "--seconds", "0.2"],
                     root=root, require_chip=False, out=out)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert set(line["compared"]) == set(LIMITS[dtype])

    cell = harness.load_cell(root, "tiny.latent")
    gen = cell.module("traffic", cell.mix["generator"])
    ref = cell.module("reference", "deepseek_v3")
    cmp = cell.module("compare", cell.workload["compare"])
    items = gen.generate(cell.mix, cell.config, 2147483659)
    batches = [gen.arrays_of(items, g) for g in range(3)]
    base = cmp.reference_steps(ref, cell.config, 2147483659, batches)
    assert base["routing"][0].shape == (4, 4, T, 3)             # the four sparse layers
    for leaf in ref.static_leaves(cell.config):
        assert base["change_norm"][leaf] == 0.0 and not np.any(base["grad"][leaf])
    control = cmp.checks(cmp.reference_steps(ref, cell.config, 2147483659, batches, mode="fp8"),
                         base, cell.workload["limits"])
    assert not all(c.ok for c in control), control
