"""The attention scores over a static sequence's positions, remade by a
training scan's backward loop and not stacked by its forward loop
(graph/recurrent_group.py: _plan_static_recompute, _run_recomputed).

- the loss and the gradients equal plain autodiff through the scan (the
  planner patched to find nothing) in float32, with ragged source and
  target lengths, for the demo's decoder and for variants of its step,
  with the static reads' gradient after the scan and without it;
- the planner takes expand -> mixed -> size-1 fc spans of a flat training
  group, and nothing where a wide value is read outside its span;
- the compiled forward loop stacks no array of the static's positions and
  width;
- the counter `scan.recomputed_bytes` says how many bytes a step the
  forward loop no longer stacks, and is absent where nothing is remade.
"""

import copy
import os
import re
import tempfile
import textwrap
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.graph.recurrent_group as rg
from paddle_tpu.config import parse_config
from paddle_tpu.graph import GradientMachine, make_seq
from paddle_tpu.graph.argument import Argument
from paddle_tpu.layers.base import LayerContext, step_counters

DECODER = """
from paddle_tpu.trainer_config_helpers import *
settings(batch_size=4, learning_rate=1e-3)
src = data_layer(name="src", size=20)
src_emb = embedding_layer(input=src, size=8, param_attr=ParamAttr(name="src_emb"))
enc = simple_gru(input=src_emb, size=12, name="encoder")
enc_proj = mixed_layer(name="enc_proj", size=8, input=[full_matrix_projection(enc)])
trg = data_layer(name="trg", size=20)
trg_emb = embedding_layer(input=trg, size=8, param_attr=ParamAttr(name="trg_emb"))

def decoder_step(enc_seq, enc_p, cur_emb):
    mem = memory(name="dec_state", size=8)
    reads = []
    if VARIANT in ("sigmoid_weights", "wide_read"):
        e = expand_layer(input=mixed_layer(size=8, input=[full_matrix_projection(mem)]),
                         expand_as=enc_seq, name="e")
        comb = mixed_layer(size=8, act=TanhActivation(), name="comb",
                           input=[identity_projection(e), identity_projection(enc_p)])
        w = fc_layer(input=comb, size=1, act=SigmoidActivation(), bias_attr=False,
                     name="w")
        reads.append(full_matrix_projection(pooling_layer(
            input=scaling_layer(weight=w, input=enc_seq), pooling_type=SumPooling())))
        if VARIANT == "wide_read":
            # the tanh's value read again, outside the scores
            reads.append(full_matrix_projection(pooling_layer(
                input=comb, pooling_type=SumPooling(), name="comb_sum")))
    elif VARIANT != "no_static_read":
        reads.append(full_matrix_projection(simple_attention(
            encoded_sequence=enc_seq, encoded_proj=enc_p, decoder_state=mem,
            name="att")))
    if VARIANT == "two_reads":
        # a second attention over the same encoder states
        reads.append(full_matrix_projection(simple_attention(
            encoded_sequence=enc_seq, encoded_proj=enc_p, decoder_state=mem,
            name="att2")))
    if VARIANT == "second_consumer":
        # the states read again, differentiably, outside the attention
        reads.append(full_matrix_projection(pooling_layer(
            input=enc_seq, pooling_type=AvgPooling(), name="enc_mean")))
    inputs = mixed_layer(size=24, input=reads + [full_matrix_projection(cur_emb)])
    return gru_step_layer(input=inputs, output_mem=mem, size=8, name="dec_state")

dec = recurrent_group(step=decoder_step,
                      input=[StaticInput(enc, is_seq=True),
                             StaticInput(enc_proj, is_seq=True), trg_emb],
                      name="decoder_group", reverse=REVERSE)
out = fc_layer(input=dec, size=20, act=SoftmaxActivation(), name="out")
label = data_layer(name="label", size=20)
outputs(classification_cost(input=out, label=label))
"""

ATT = ("att_expand", "att_combine", "att_softmax")
# the decoder of demo/seqToseq, its attention named by the DSL
DEMO = ("__attention_0___expand", "__attention_0___combine", "__attention_0___softmax")
SPANS = {
    "demo": (DEMO,),
    "plain": (ATT,),
    "two_reads": (ATT, ("att2_expand", "att2_combine", "att2_softmax")),
    "second_consumer": (ATT,),
    "sigmoid_weights": (("e", "comb", "w"),),
    "wide_read": (),
    "no_static_read": (),
}
T, B, S, D = 5, 3, 7, 8  # target steps, batch, source positions, scores' width


def _model(variant="plain", reverse=False):
    if variant == "demo":
        from paddle_tpu.flagship import nmt_config

        return nmt_config(vocab=20, dim=D).model_config
    src = DECODER.replace("VARIANT", repr(variant)).replace("REVERSE", str(reverse))
    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(textwrap.dedent(src))
        path = f.name
    try:
        return parse_config(path).model_config
    finally:
        os.unlink(path)


def _batch(variant, seed=5):
    """Source and target of different padded lengths, one of each full and
    one of length 1, so padding lies on both sides of the scores."""
    rng = np.random.RandomState(seed)
    src_len = np.array([S, 1, 4], np.int32)
    trg_len = np.array([2, T, 1], np.int32)
    trg = rng.randint(0, 20, (B, T)).astype(np.int32)
    names = (("source_language_word", "target_language_word", "target_language_next_word")
             if variant == "demo" else ("src", "trg", "label"))
    return dict(zip(names, (
        make_seq(None, src_len, ids=rng.randint(0, 20, (B, S)).astype(np.int32)),
        make_seq(None, trg_len, ids=trg),
        make_seq(None, trg_len, ids=np.roll(trg, -1, axis=1)),
    )))


def _run(gm, params, batch):
    loss, grads, outputs = jax.jit(gm.grad_fn())(params, batch, None)[:3]
    return loss, grads, step_counters(outputs)


# ------------------------------------------------------------ the gradients


@pytest.mark.parametrize("static_grad", [True, False], ids=["static_grad", "per_step"])
@pytest.mark.parametrize("variant,reverse", [
    ("demo", False),
    ("plain", False),
    ("plain", True),              # a reversed group
    ("two_reads", False),         # two spans over one static
    ("second_consumer", False),   # the states read again outside the scores
    ("sigmoid_weights", False),   # weights that padding does not zero
])
def test_gradients_equal_autodiff_through_the_scan(variant, reverse, static_grad,
                                                  monkeypatch):
    gm = GradientMachine(_model(variant, reverse))
    params = gm.init_params(seed=4)
    batch = _batch(variant)
    if not static_grad:
        monkeypatch.setattr(rg, "_plan_static_grad", lambda *a: ())
    found = []
    plan = rg._plan_static_recompute
    monkeypatch.setattr(rg, "_plan_static_recompute",
                        lambda *a: found.append(plan(*a)) or found[-1])
    l_on, g_on, _ = _run(gm, params, batch)
    assert found == [SPANS[variant]]
    monkeypatch.setattr(rg, "_plan_static_recompute", lambda *a: ())
    l_off, g_off, _ = _run(gm, params, batch)
    np.testing.assert_allclose(float(l_on), float(l_off), rtol=1e-6)
    assert set(g_on) == set(g_off)
    for k in g_off:
        ref = np.asarray(g_off[k])
        np.testing.assert_allclose(np.asarray(g_on[k]), ref, rtol=1e-5,
                                   atol=1e-6 * float(np.abs(ref).max()), err_msg=k)


# ------------------------------------------------------------- the planner


def _plan(variant, pass_type="train"):
    """The planner alone on a variant's step graph, statics of B x S."""
    if variant == "generation":
        from paddle_tpu.flagship import nmt_config

        model = nmt_config(vocab=20, dim=D, is_generating=True).model_config
    else:
        model = _model(variant)
    net = GradientMachine(model).network
    sub = copy.deepcopy(net.submodel_map["decoder_group"])
    statics = {
        link.link_name: Argument(value=jnp.zeros((B, S, net.layer_map[link.layer_name].size)),
                                 seq_lengths=jnp.array([S, 1, 4], jnp.int32))
        for link in sub.static_links
    }
    ctx = LayerContext(params={}, model=model, pass_type=pass_type)
    return rg._plan_static_recompute(types.SimpleNamespace(layer_map=net.layer_map),
                                     sub, ctx, statics, frozenset(), ())


@pytest.mark.parametrize("variant", sorted(SPANS))
def test_the_planners_spans(variant):
    assert _plan(variant) == SPANS[variant]


@pytest.mark.parametrize("variant,pass_type", [
    ("plain", "test"),       # an eval pass takes no backward
    ("generation", "gen"),   # a generator group is never planned
])
def test_no_plan_outside_a_training_scan(variant, pass_type):
    assert _plan(variant, pass_type) == ()


def test_an_eval_pass_runs_the_step_as_it_was(monkeypatch):
    gm = GradientMachine(_model())
    params = gm.init_params(seed=4)
    called = []
    monkeypatch.setattr(rg, "_run_recomputed",
                        lambda *a: called.append(a) or pytest.fail("recomputed in eval"))
    out, _ = gm.forward(params, _batch("plain"), "test")
    assert np.isfinite(np.asarray(out["out"].value)).all()
    assert not called


# ------------------------------------------------------------ the structure


def _forward_loop_types(gm, params, batch):
    """The array types the compiled forward loops of the decoder group (CPU)
    carry: what they stack, among the rest."""
    text = jax.jit(lambda p, b: gm.grad_fn()(p, b, None)[:2]).lower(
        params, batch).compile().as_text()
    found = []
    for m in re.finditer(r"^\s*%\S+ = \((.*?)\) while\(.*op_name=\"([^\"]*)\"", text, re.M):
        if "recurrent_layer_group:decoder_group" in m.group(2) and \
                "transpose" not in m.group(2):
            found += re.findall(r"\w+\[[\d,]*\]", m.group(1))
    assert found, "no forward loop of the decoder group"
    return found


def test_the_forward_loop_stacks_no_array_of_the_statics_positions_and_width(monkeypatch):
    """T = 5 steps over B = 3 sources of S = 7 positions: the tanh of the
    demo's attention is [3, 7, 8] a step. Remade by the backward, no
    forward loop stacks a [5, 3, 7, 8] array; with the planner patched off
    the loop stacks its residuals."""
    gm = GradientMachine(_model("demo"))
    params = gm.init_params(seed=3)
    batch = _batch("demo")
    assert f"f32[{T},{B},{S},{D}]" not in _forward_loop_types(gm, params, batch)
    monkeypatch.setattr(rg, "_plan_static_recompute", lambda *a: ())
    assert f"f32[{T},{B},{S},{D}]" in _forward_loop_types(gm, params, batch)


@pytest.mark.parametrize("variant,arrays", [
    ("demo", 2),         # the tanh's value and 1 - value
    ("two_reads", 4),    # two spans
    ("sigmoid_weights", 2),
])
def test_the_counter_gives_the_bytes_no_longer_stacked(variant, arrays):
    gm = GradientMachine(_model(variant))
    _, _, counters = _run(gm, gm.init_params(seed=4), _batch(variant))
    assert float(counters["max"]["scan.recomputed_bytes"]) == T * B * S * D * 4 * arrays


@pytest.mark.parametrize("variant", ["wide_read", "no_static_read"])
def test_a_group_with_no_span_publishes_no_counter(variant):
    gm = GradientMachine(_model(variant))
    _, _, counters = _run(gm, gm.init_params(seed=4), _batch(variant))
    assert "scan.recomputed_bytes" not in counters.get("max", {})
