"""The per-batch evaluators' statistics inside the train step.

An evaluator whose statistic is a masked reduction (classification_error,
seq_classification_error, sum, last-column-sum) has ONE definition,
`batch_state(args)`: the train step calls it on its layer outputs and
returns {evaluator name: f32[k]} beside the loss, and `eval_batch` is the
same function on whatever arrays it is handed. Held here against a plain
numpy statement of each metric, row by row, and end to end through every
variant of the step (single, fused launch, accumulation, skip policy).
"""

import os
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.config import parse_config
from paddle_tpu.graph.argument import Argument
from paddle_tpu.layers.base import LAYER_COUNTERS
from paddle_tpu.observability import metrics as obs
from paddle_tpu.observability import spans as obs_spans
from paddle_tpu.proto import EvaluatorConfig, ModelConfig
from paddle_tpu.resilience import faultinject
from paddle_tpu.trainer import Trainer
from paddle_tpu.trainer.evaluators import EvaluatorChain, evaluator_registry
from paddle_tpu.utils.flags import FLAGS

PROVIDER_DIR = os.path.join(os.path.dirname(__file__), "providers")


# ----------------------------------------------- the plain numpy statement


def _real_rows(x, arg):
    """The real rows of ``x`` (laid out as ``arg``), one by one."""
    x = np.asarray(x)
    if arg.sub_seq_lengths is not None:
        sub = np.asarray(arg.sub_seq_lengths)
        return [x[b, s, t] for b in range(x.shape[0])
                for s in range(x.shape[1]) for t in range(sub[b, s])]
    if arg.seq_lengths is not None:
        lens = np.asarray(arg.seq_lengths)
        return [x[b, t] for b in range(x.shape[0]) for t in range(lens[b])]
    return list(x)


def _f32(a):
    return np.asarray(a).astype(np.float32)      # exact from bfloat16


def _np_classification(out, label, threshold=0.5):
    wrong = 0
    rows = _real_rows(_f32(out.value), out)
    for row, lab in zip(rows, _real_rows(label.ids, out)):
        if len(row) == 1:
            pred = int(threshold > 0 and row[0] > np.float32(threshold))
        else:
            pred = int(np.argmax(row))           # first index on ties
        wrong += pred != int(lab)
    return [wrong, len(rows)]


def _np_seq_classification(out, label):
    ids = np.asarray(label.ids)
    wrong = 0
    lens = (out.seq_lengths if out.seq_lengths is not None   # else all real
            else np.full(out.value.shape[0], out.value.shape[1]))
    for b in range(out.value.shape[0]):
        one = Argument(
            value=out.value[b:b + 1], seq_lengths=lens[b:b + 1],
            sub_seq_lengths=(None if out.sub_seq_lengths is None
                             else out.sub_seq_lengths[b:b + 1]))
        frames = _real_rows(_f32(one.value), one)
        labs = (_real_rows(ids[b:b + 1], one) if ids.ndim == out.value.ndim - 1
                else [ids[b]] * len(frames))
        wrong += any(int(np.argmax(f)) != int(l) for f, l in zip(frames, labs))
    return [wrong, out.value.shape[0]]


def _np_sum(arg):
    rows = _real_rows(_f32(arg.value), arg)
    return [float(np.sum(np.asarray(rows, np.float64))), len(rows)]


def _np_column_sum(arg):
    rows = _real_rows(_f32(arg.value), arg)
    return [float(np.sum(np.asarray([r[-1] for r in rows], np.float64))),
            len(rows)]


# --------------------------------------------------------- test Arguments

B, S, T, C = 5, 3, 7, 6


def _layout(layout, rng):
    """(rows' shape, lengths of that layout); lengths 0 included."""
    if layout == "flat":
        return (B,), {}
    if layout == "seq":
        lens = rng.integers(0, T + 1, B).astype(np.int32)
        lens[0] = T
        return (B, T), {"seq_lengths": lens}
    sub = rng.integers(0, T + 1, (B, S)).astype(np.int32)
    sub[0, 0] = T
    return (B, S, T), {"seq_lengths": (sub > 0).sum(1).astype(np.int32),
                       "sub_seq_lengths": sub}


def _values(rng, shape, dtype):
    # a few levels only, all exact in bfloat16: rows full of ties, and the
    # padding holds values that would move every result if it were read
    return jnp.asarray(rng.integers(0, 4, shape) / 4.0, dtype)


def _evaluator(type_, **fields):
    return evaluator_registry.get(type_)(EvaluatorConfig(
        name=f"e_{type_}", type=type_, input_layers=["out", "label"][
            :1 if "sum" in type_ else 2], **fields))


CASES = {
    "classification_error": lambda a: _np_classification(*a),
    "seq_classification_error": lambda a: _np_seq_classification(*a),
    "sum": lambda a: _np_sum(a[0]),
    "last-column-sum": lambda a: _np_column_sum(a[0]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("type_,layout", [
    (t, l) for t in CASES for l in ("flat", "seq", "nested")
    # a per-sequence error has no non-sequence form
    if (t, l) != ("seq_classification_error", "flat")])
def test_batch_state_is_the_plain_numpy_metric(type_, layout, dtype):
    rng = np.random.default_rng(len(type_) * 31 + len(layout))
    rows, lens = _layout(layout, rng)
    out = Argument(value=_values(rng, rows + (C,), dtype), **lens)
    label = Argument(ids=jnp.asarray(rng.integers(0, C, rows), jnp.int32),
                     **lens)
    ev = _evaluator(type_)
    args = [out, label][:len(ev.cfg.input_layers)]
    state = ev.batch_state(args)
    assert state.shape == (2,) and state.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(state), CASES[type_](args),
                               rtol=1e-6)
    # traced (as the train step calls it) and through eval_batch: one number
    np.testing.assert_array_equal(jax.jit(ev.batch_state)(args), state)
    ev.eval_batch(args)
    ev.eval_batch(args)
    np.testing.assert_array_equal(ev.merge_state(), 2 * np.asarray(state, np.float64))


@pytest.mark.parametrize("layout", ["flat", "seq", "nested"])
@pytest.mark.parametrize("threshold", [0.5, 0.25, 0.0])
def test_classification_threshold_form(layout, threshold):
    rng = np.random.default_rng(7)
    rows, lens = _layout(layout, rng)
    out = Argument(value=_values(rng, rows + (1,), jnp.bfloat16), **lens)
    label = Argument(ids=jnp.asarray(rng.integers(0, 2, rows), jnp.int32), **lens)
    ev = _evaluator("classification_error", classification_threshold=threshold)
    np.testing.assert_array_equal(
        ev.batch_state([out, label]), _np_classification(out, label, threshold))


@pytest.mark.parametrize("per_frame", [False, True], ids=["label_a_sequence", "label_a_frame"])
def test_seq_classification_labels_and_unmasked_output(per_frame):
    rng = np.random.default_rng(3)
    out = Argument(value=_values(rng, (B, T, C), jnp.float32))     # no lengths
    label = Argument(ids=jnp.asarray(
        rng.integers(0, C, (B, T) if per_frame else (B,)), jnp.int32))
    ev = _evaluator("seq_classification_error")
    np.testing.assert_array_equal(
        ev.batch_state([out, label]), _np_seq_classification(out, label))
    with pytest.raises(ValueError, match="seq_classification_error needs"):
        ev.batch_state([Argument(value=out.value[:, 0]), label])


def test_layouts_that_differ_are_paired_by_position_on_the_host():
    """An output laid out as sequences and a label that is one flat column
    have no common mask: `batch_state` says so (None: at trace time the
    evaluator stays on the host), and `eval_batch` gathers the real rows
    and pairs them by position, cut to the shorter side."""
    rng = np.random.default_rng(11)
    rows, lens = _layout("seq", rng)
    out = Argument(value=_values(rng, rows + (C,), jnp.bfloat16), **lens)
    n = int(lens["seq_lengths"].sum())
    flat_ids = rng.integers(0, C, n - 2).astype(np.int32)          # two short
    label = Argument(ids=jnp.asarray(flat_ids))
    ev = _evaluator("classification_error")
    assert ev.batch_state([out, label]) is None
    ev.eval_batch([out, label])
    preds = [int(np.argmax(r)) for r in _real_rows(_f32(out.value), out)]
    wrong = sum(p != l for p, l in zip(preds, flat_ids))
    np.testing.assert_array_equal(ev.merge_state(), [wrong, n - 2])
    assert ev.result() == {"classification_error": wrong / (n - 2)}


def test_states_of_the_step_are_not_merged_across_processes_again():
    """Point 4's trap: a state computed inside a jitted step over a mesh is
    global already; only evaluators fed local rows merge at read time."""
    model = ModelConfig(evaluators=[
        EvaluatorConfig(name="in_step", type="sum", input_layers=["out"]),
        EvaluatorConfig(name="on_host", type="sum", input_layers=["out"])])
    chain = EvaluatorChain(model)
    chain.merge_fn = lambda vec: 2 * vec            # two processes
    out = {"out": Argument(value=jnp.ones((4, 3)))}
    rest = chain.add_states({"in_step": np.array([[12.0, 4.0], [12.0, 4.0]])})
    assert [e.cfg.name for e in rest] == ["on_host"]
    chain.eval_batch(out, only=rest)
    res = chain.results()
    assert res["in_step.sum"] == 24.0               # two stacked batches, once
    assert res["on_host.sum"] == 24.0               # local rows, two processes


# ------------------------------------------------ through the train step


@pytest.fixture
def provider_path():
    sys.path.insert(0, PROVIDER_DIR)
    yield
    sys.path.remove(PROVIDER_DIR)


FLAT_NET = """
    data = data_layer(name="word", size=100)
    output = fc_layer(input=data, size=2, act=SoftmaxActivation(), name="output")
    label = data_layer(name="label", size=2)
    sum_evaluator(output, name="total")
    column_sum_evaluator(output, name="last")
    {extra}
    outputs(classification_cost(input=output, label=label, name="cost"))
"""
SEQ_NET = """
    data = data_layer(name="word", size=100)
    emb = embedding_layer(input=data, size=8)
    frames = fc_layer(input=emb, size=2, act=SoftmaxActivation(), name="frames")
    output = pooling_layer(input=frames, pooling_type=AvgPooling(), name="output")
    label = data_layer(name="label", size=2)
    sum_evaluator(frames, name="total")
    column_sum_evaluator(frames, name="last")
    seq_classification_error_evaluator(input=frames, label=label, name="seqerr")
    {extra}
    outputs(classification_cost(input=output, label=label, name="cost"))
"""


def _config(tmp, net, obj, settings="", extra=""):
    (tmp / "train.list").write_text("1\n2\n")
    path = tmp / f"conf_{abs(hash((net, settings, extra)))}.py"
    path.write_text(textwrap.dedent(f"""
    from paddle_tpu.trainer_config_helpers import *

    define_py_data_sources2(train_list={str(tmp / 'train.list')!r},
                            test_list=None, module="synthetic_bow", obj={obj!r})
    settings(batch_size=64, learning_rate=0.0,
             learning_method=AdamOptimizer(){settings})
    """) + textwrap.dedent(net.format(extra=extra)))
    return parse_config(str(path))


def _pass_end(cfg, tmp, **flags):
    """One pass of `Trainer.train()`; its `pass_end` record and the trainer."""
    flags = dict(save_dir=tempfile.mkdtemp(dir=tmp),
                 metrics_path=str(tmp / "metrics"), num_passes=1,
                 start_pass=0, log_period=0, init_model_path="",
                 trace_events_path="", seed=7, **flags)
    for k, v in flags.items():
        setattr(FLAGS, k, v)
    try:
        obs.registry().reset()
        trainer = Trainer(cfg)
        trainer.train(num_passes=1)
        records = list(obs.read_records(str(tmp / "metrics" / "metrics.jsonl")))
    finally:
        obs.configure("")
        obs_spans.configure("")
    return [r for r in records if r["kind"] == "pass_end"][-1], trainer


def _expected(trainer, net):
    """The pass's evaluator results by the plain numpy statements, from the
    forward pass over the same samples (the learning rate is 0)."""
    frames = "frames" if net is SEQ_NET else "output"
    tot = {k: np.zeros(2) for k in ("cost", "total", "last", "seqerr")}
    for batch in trainer._provider(for_test=False).batches():
        outs = trainer.test_fwd(trainer.params, batch)
        tot["cost"] += _np_classification(outs["output"], outs["label"])
        tot["total"] += _np_sum(outs[frames])
        tot["last"] += _np_column_sum(outs[frames])
        if net is SEQ_NET:
            tot["seqerr"] += _np_seq_classification(outs[frames], outs["label"])
    return tot


MODES = {"single": "", "fused": ", batches_per_launch=3",
         "accumulated": ", num_batches_per_send_parameter=2"}


@pytest.mark.parametrize("net,obj", [(FLAT_NET, "process"), (SEQ_NET, "process_seq")],
                         ids=["flat", "sequences"])
def test_pass_end_results_equal_in_every_variant_of_the_step(
        net, obj, tmp_path, provider_path):
    ends = {}
    for mode, settings in MODES.items():
        tmp = tmp_path / mode
        tmp.mkdir()
        ends[mode], trainer = _pass_end(_config(tmp, net, obj, settings), tmp)
    want = _expected(trainer, net)
    steps = ends["single"]["launches_single"]
    assert ends["fused"]["launches_fused"] > 0
    n_evals = 4 if net is SEQ_NET else 3
    for mode, end in ends.items():
        # exact for the counts: the same integers divided
        # (`classification_cost` names its evaluator after itself)
        assert end["cost.classification_error.classification_error"] == (
            want["cost"][0] / want["cost"][1]), mode
        if net is SEQ_NET:
            assert end["seqerr.seq_classification_error"] == (
                want["seqerr"][0] / want["seqerr"][1]), mode
        np.testing.assert_allclose(end["total.sum"], want["total"][0], rtol=1e-5)
        np.testing.assert_allclose(end["last.column_sum"], want["last"][0], rtol=1e-5)
        np.testing.assert_allclose(end["total.mean"], want["total"][0] / want["total"][1], rtol=1e-5)
        # every evaluator of these nets is computed inside the step
        assert end["counters"]["eval.device_batches"] == n_evals * steps, mode
        assert end["counters"]["eval.host_batches"] == 0, mode
        assert not [s for s in end["spans"] if s.startswith("eval/")], mode
    assert want["cost"][1] == ends["single"]["samples"]


@pytest.mark.parametrize("mode,dropped", [("single", 64), ("fused", 3 * 64)])
def test_a_batch_the_skip_policy_discards_adds_nothing(
        mode, dropped, tmp_path, provider_path):
    clean, _ = _pass_end(_config(tmp_path, FLAT_NET, "process", MODES[mode]), tmp_path)
    faultinject.configure("trainer.nonfinite=raise@2")
    try:
        end, _ = _pass_end(_config(tmp_path, FLAT_NET, "process", MODES[mode]),
                           tmp_path, nonfinite_policy="skip")
    finally:
        faultinject.configure("")
    # softmax rows add up to 1, so `sum` counts the rows that were added
    assert end["samples"] == clean["samples"] - dropped
    np.testing.assert_allclose(end["total.sum"], end["samples"], rtol=1e-5)
    np.testing.assert_allclose(clean["total.sum"], clean["samples"], rtol=1e-5)
    assert end["counters"]["eval.device_batches"] == 3 * (end["samples"] // 64 + 1)


def _step_out_info(trainer, batch):
    n = float(next(iter(batch.values())).batch_size)
    return trainer.train_step.lower(
        trainer.params, trainer.opt_state, batch, jax.random.PRNGKey(0),
        jnp.asarray(n)).out_info


def test_the_step_of_a_classification_cost_net_returns_no_softmax_output():
    from paddle_tpu.flagship import nmt_batch, nmt_config

    vocab = 300
    trainer = Trainer(nmt_config(vocab=vocab, dim=32, batch_size=4))
    _, _, loss, keep, states = _step_out_info(
        trainer, nmt_batch(vocab=vocab, B=4, T=6))
    (ev,) = trainer.config.model_config.evaluators
    assert ev.type == "classification_error"
    assert not set(ev.input_layers) & set(keep)
    assert set(keep) == set(trainer.gm.network.output_layer_names)
    assert not [x for x in jax.tree_util.tree_leaves(keep) if vocab in x.shape]
    # beside the evaluator's statistic, the decoder group's one layer
    # counter (graph/recurrent_group.py: scan.recomputed_bytes)
    assert jax.tree_util.tree_map(lambda v: (v.shape, v.dtype), states) == {
        ev.name: ((2,), jnp.float32),
        LAYER_COUNTERS: {"max": {"scan.recomputed_bytes": ((), jnp.float32)}}}
    assert loss.shape == ()


@pytest.mark.parametrize("extra,type_", [
    ("value_printer_evaluator(input=output, name='host')", "value_printer"),
    ("chunk_evaluator(input=output, label=label, chunk_scheme='IOB',"
     " num_chunk_types=1, name='host')", "chunk")])
def test_a_host_only_evaluator_still_gets_its_layers(
        extra, type_, tmp_path, provider_path):
    cfg = _config(tmp_path, FLAT_NET, "process", extra=extra)
    trainer = Trainer(cfg)
    batch = next(iter(trainer._provider(for_test=False).batches()))
    keep, states = _step_out_info(trainer, batch)[3:5]
    host = next(e for e in cfg.model_config.evaluators if e.name == "host")
    assert set(host.input_layers) <= set(keep)
    assert set(states) == {"cost.classification_error", "total", "last"}
    end, _ = _pass_end(cfg, tmp_path)
    steps = end["launches_single"]
    assert end["spans"][f"eval/{type_}"][0] == steps
    assert end["spans"]["eval/readback"][0] >= steps
    assert end["counters"]["eval.host_batches"] == steps
    assert end["counters"]["eval.device_batches"] == 3 * steps
