"""The encoder states' gradient through a decoder's attention read, taken
ONCE after the group's scan (graph/recurrent_group.py: _plan_static_grad,
_scan_static_grad) and not accumulated in a carry of the states' shape in
every reverse step.

- the gradients equal plain autodiff through the scan (the matcher patched
  to find nothing) in float32, with ragged source and target lengths, for
  the demo's decoder and for variants of its step;
- the matcher takes only a scaling of a static sequence whose result goes
  to a linear sum pooling alone, in a flat training group;
- the compiled backward loop carries no array of the static's shape.
"""

import copy
import dataclasses
import re
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
from paddle_tpu.layers.base import LayerContext

DECODER = """
from paddle_tpu.trainer_config_helpers import *
settings(batch_size=4, learning_rate=1e-3)
src = data_layer(name="src", size=20)
src_emb = embedding_layer(input=src, size=8, param_attr=ParamAttr(name="src_emb"))
if VARIANT == "sigmoid_weights":
    # no recurrence masks these states: what padding contributes to the
    # read reaches the embedding's rows unless the product drops it
    enc = mixed_layer(name="encoder", size=12, input=[full_matrix_projection(src_emb)])
else:
    enc = simple_gru(input=src_emb, size=12, name="encoder")
enc_proj = mixed_layer(name="enc_proj", size=8, input=[full_matrix_projection(enc)])
trg = data_layer(name="trg", size=20)
trg_emb = embedding_layer(input=trg, size=8, param_attr=ParamAttr(name="trg_emb"))

def decoder_step(enc_seq, enc_p, cur_emb):
    mem = memory(name="dec_state", size=8)
    if VARIANT == "sigmoid_weights":
        # weights that padding does not zero: the pooling's mask must
        e = expand_layer(input=mixed_layer(size=8, input=[full_matrix_projection(mem)]),
                         expand_as=enc_seq)
        comb = mixed_layer(size=8, act=TanhActivation(),
                           input=[identity_projection(e), identity_projection(enc_p)])
        w = fc_layer(input=comb, size=1, act=SigmoidActivation(), bias_attr=False)
        context = pooling_layer(input=scaling_layer(weight=w, input=enc_seq),
                                pooling_type=SumPooling())
    else:
        context = simple_attention(encoded_sequence=enc_seq, encoded_proj=enc_p,
                                   decoder_state=mem, name="att")
    reads = [full_matrix_projection(context)]
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


def _parse(src):
    import os
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(textwrap.dedent(src))
        path = f.name
    try:
        return parse_config(path)
    finally:
        os.unlink(path)


def _decoder(variant="plain", reverse=False):
    src = DECODER.replace("VARIANT", repr(variant)).replace("REVERSE", str(reverse))
    return _parse(src)


def _ragged_batch(B=3, S=7, T=5, seed=0):
    """Source and target of different padded lengths, one of each full and
    one of length 1, so padding lies on both sides of the product."""
    rng = np.random.RandomState(seed)
    src_len = np.array([S, 1, 4], np.int32)[:B]
    trg_len = np.array([2, T, 1], np.int32)[:B]
    trg = rng.randint(0, 20, (B, T)).astype(np.int32)
    return {
        "src": make_seq(None, src_len, ids=rng.randint(0, 20, (B, S)).astype(np.int32)),
        "trg": make_seq(None, trg_len, ids=trg),
        "label": make_seq(None, trg_len, ids=np.roll(trg, -1, axis=1)),
    }


def _grads_both_ways(gm, params, batch, monkeypatch):
    """(loss, grads) with the deferred gradient, then with the matcher
    patched to find nothing (today's scan), and what the matcher found."""
    found = []
    plan = rg._plan_static_grad

    def spy(*a):
        found.append(plan(*a))
        return found[-1]

    monkeypatch.setattr(rg, "_plan_static_grad", spy)
    on = jax.jit(gm.grad_fn())(params, batch, None)[:2]
    monkeypatch.setattr(rg, "_plan_static_grad", lambda *a: ())
    off = jax.jit(gm.grad_fn())(params, batch, None)[:2]
    return on, off, found


def _assert_same(on, off):
    (l_on, g_on), (l_off, g_off) = on, off
    np.testing.assert_allclose(float(l_on), float(l_off), rtol=1e-6)
    assert set(g_on) == set(g_off)
    for k in g_off:
        ref = np.asarray(g_off[k])
        np.testing.assert_allclose(np.asarray(g_on[k]), ref, rtol=1e-5,
                                   atol=1e-6 * float(np.abs(ref).max()), err_msg=k)


def test_the_demo_decoders_gradients_equal_autodiff_through_the_scan(monkeypatch):
    """demo/seqToseq's own gru_encoder_decoder: the bidirectional encoder's
    concatenated states are the static the attention scales."""
    from paddle_tpu.flagship import nmt_config

    tc = nmt_config(vocab=20, dim=8)
    gm = GradientMachine(tc.model_config)
    params = gm.init_params(seed=3)
    b = _ragged_batch()
    batch = {"source_language_word": b["src"], "target_language_word": b["trg"],
             "target_language_next_word": b["label"]}
    on, off, found = _grads_both_ways(gm, params, batch, monkeypatch)
    assert [[(s, p) for s, p, _ in f] for f in found] == [
        [("__attention_0___scaling", "__attention_0___pooling")]]
    _assert_same(on, off)


@pytest.mark.parametrize("variant,reverse,reads", [
    ("plain", False, 1),
    ("plain", True, 1),           # a reversed group: the product is over all t
    ("two_reads", False, 2),      # two attentions of one static: one product each
    ("second_consumer", False, 1),  # the mean keeps its own per-step path
    ("sigmoid_weights", False, 1),  # padded positions weigh; the mask drops them
])
def test_variants_gradients_equal_autodiff_through_the_scan(variant, reverse, reads,
                                                           monkeypatch):
    tc = _decoder(variant, reverse)
    gm = GradientMachine(tc.model_config)
    params = gm.init_params(seed=4)
    on, off, found = _grads_both_ways(gm, params, _ragged_batch(seed=5), monkeypatch)
    assert len(found) == 1 and len(found[0]) == reads
    assert {link for _, _, link in found[0]} == {"encoder@decoder_group"}
    _assert_same(on, off)


def test_an_eval_pass_runs_the_scan_as_it_was(monkeypatch):
    """Only a training pass defers: the test pass's forward is unchanged."""
    tc = _decoder()
    gm = GradientMachine(tc.model_config)
    params = gm.init_params(seed=4)
    called = []
    monkeypatch.setattr(rg, "_scan_static_grad",
                        lambda *a: called.append(a) or pytest.fail("deferred in eval"))
    out, _ = gm.forward(params, _ragged_batch(), "test")
    assert np.isfinite(np.asarray(out["out"].value)).all()
    assert not called


# ------------------------------------------------------------- the matcher


def _matcher_inputs(case):
    """(network, sub, ctx, statics) for the matcher alone: the demo's
    decoder at a small width, changed as `case` says."""
    from paddle_tpu.flagship import nmt_config

    tc = nmt_config(vocab=20, dim=8, is_generating=case == "generation")
    gm = GradientMachine(tc.model_config)
    net = gm.network
    sub = copy.deepcopy(net.submodel_map["decoder_group"])
    lm = dict(net.layer_map)
    pool = "__attention_0___pooling"
    if case == "average_pooling":
        lm[pool] = dataclasses.replace(lm[pool], average_strategy="average")
    elif case == "pooling_bias":
        lm[pool] = dataclasses.replace(lm[pool], bias_parameter_name="b")
    elif case == "pooling_activation":
        lm[pool] = dataclasses.replace(lm[pool], active_type="tanh")
    elif case == "scaling_read_twice":
        sub.out_links.append(dataclasses.replace(
            sub.out_links[0], layer_name="__attention_0___scaling",
            link_name="__attention_0___scaling"))
    elif case == "nested":
        sub.in_links = [dataclasses.replace(l, has_subseq=True) for l in sub.in_links]
    elif case == "reversed":
        sub.reversed = True
    statics = {}
    for link in sub.static_links:
        size = lm[link.layer_name].size
        statics[link.link_name] = Argument(value=jnp.zeros((2, 5, size)),
                                           seq_lengths=jnp.array([5, 3], jnp.int32))
    ctx = LayerContext(params={}, model=tc.model_config,
                       pass_type="test" if case == "test_pass" else "train")
    return types.SimpleNamespace(layer_map=lm), sub, ctx, statics


@pytest.mark.parametrize("case,takes", [
    ("training", True),
    ("reversed", True),
    ("average_pooling", False),     # the pooling divides by the length
    ("pooling_bias", False),
    ("pooling_activation", False),
    ("scaling_read_twice", False),  # the scaling's result is also an out-link
    ("nested", False),
    ("generation", False),
    ("test_pass", False),
])
def test_the_matcher_takes_only_a_scaling_under_a_linear_sum_pooling(case, takes):
    network, sub, ctx, statics = _matcher_inputs(case)
    reads = rg._plan_static_grad(network, sub, ctx, statics, frozenset(), ())
    if takes:
        assert [(s, p) for s, p, _ in reads] == [
            ("__attention_0___scaling", "__attention_0___pooling")]
    else:
        assert reads == ()


# ------------------------------------------------------------ the structure


def _backward_loop_carries(gm, params, batch):
    """The arrays the compiled backward loops of the decoder group (CPU)
    rewrite in every trip: the tuple elements whose body result is not the
    element it was handed. Returns (their types, the optimized HLO text)."""
    text = jax.jit(lambda p, b: gm.grad_fn()(p, b, None)[:2]).lower(
        params, batch).compile().as_text()
    bodies = {m.group(1): m.group(2) for m in re.finditer(
        r"^%(\S+) [^\n]*\{\n(.*?)\n\}", text, re.M | re.S)}
    carried = []
    for m in re.finditer(
            r"^\s*%\S+ = \((.*?)\) while\(.*?body=%([\w.\-]+).*op_name=\"[^\"]*"
            r"transpose\(jvp\(recurrent_layer_group:decoder_group\)\)", text, re.M):
        types_ = re.findall(r"\w+\[[\d,]*\]", m.group(1))
        body = bodies[m.group(2)]
        root = re.search(r"ROOT \S+ = .*? tuple\((.*)\)", body).group(1)
        outs = re.findall(r"%[\w.\-]+", root)
        same = dict(re.findall(
            r"^\s*(%[\w.\-]+) = \S+ get-tuple-element\(%[\w.\-]+\), index=(\d+)",
            body, re.M))
        carried += [t for i, (t, o) in enumerate(zip(types_, outs))
                    if same.get(o) != str(i)]
    return carried, text


def test_the_backward_loop_carries_no_array_of_the_statics_shape(monkeypatch):
    """B = 3, S = 7: the encoder's states are f32[3,7,12], the projected
    states f32[3,7,8]. With the product after the loop, no backward loop of
    the decoder rewrites a [3,7,12] array in its trips (the states may still
    be handed through unchanged: the attention weights' gradient reads
    them); the projected states' full-rank read through the tanh keeps its
    own carry. The same step with the matcher patched off rewrites one: the
    carry this removes."""
    tc = _decoder()
    gm = GradientMachine(tc.model_config)
    params = gm.init_params(seed=4)
    batch = _ragged_batch()
    carried, text = _backward_loop_carries(gm, params, batch)
    assert "f32[3,7,8]" in carried, carried
    assert "f32[3,7,12]" not in carried, carried
    assert re.search(r'op_name="[^"]*recurrent_layer_group:decoder_group\)*/'
                     r'static_grad:encoder', text)
    monkeypatch.setattr(rg, "_plan_static_grad", lambda *a: ())
    carried, text = _backward_loop_carries(gm, params, batch)
    assert "f32[3,7,12]" in carried, carried
    assert not re.search(r'op_name="[^"]*static_grad:', text)
