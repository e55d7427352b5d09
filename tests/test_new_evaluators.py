"""rank-auc and per-sequence classification-error evaluators, config-wired."""

import os

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from paddle_tpu.graph.argument import Argument
from paddle_tpu.proto import EvaluatorConfig
from paddle_tpu.trainer import evaluators as ev


def test_rank_auc_exact():
    cfg = EvaluatorConfig(name="r", type="rank-auc", input_layers=["s", "c"])
    e = ev.evaluator_registry.get("rank-auc")(cfg)
    e.start()
    # pos scores {0.1, 0.8}, neg {0.9, 0.2}: only (0.8, 0.2) of the four
    # pos/neg pairs is correctly ranked → AUC = 1/4
    scores = np.asarray([[0.1], [0.9], [0.2], [0.8]], np.float32)
    clicks = np.asarray([[1.0], [0.0], [0.0], [1.0]], np.float32)
    e.eval_batch([Argument(value=scores), Argument(value=clicks)])
    assert abs(e.result()["rank_auc"] - 0.25) < 1e-6

    e.start()
    order = np.linspace(0, 1, 20)[:, None].astype(np.float32)
    lab = (order[:, 0] > 0.6).astype(np.float32)[:, None]
    e.eval_batch([Argument(value=order), Argument(value=lab)])
    assert e.result()["rank_auc"] == 1.0


def test_seq_classification_error_masks_padding():
    cfg = EvaluatorConfig(name="s", type="seq_classification_error",
                          input_layers=["o", "l"])
    e = ev.evaluator_registry.get("seq_classification_error")(cfg)
    e.start()
    v = np.zeros((2, 4, 2), np.float32)
    v[0, :, 1] = 1.0           # predicts 1 everywhere
    v[1, :, 0] = 1.0           # predicts 0 everywhere
    lens = np.asarray([2, 4], np.int32)
    labels = np.asarray([[1, 1, 0, 0],      # wrong only in padding → correct
                         [0, 0, 0, 1]],     # wrong at a valid frame → wrong
                        np.int32)
    e.eval_batch([
        Argument(value=v, seq_lengths=lens),
        Argument(ids=labels, seq_lengths=lens),
    ])
    assert e.result()["seq_classification_error"] == 0.5


def test_dsl_wrappers_emit_configs():
    from paddle_tpu.config.builder import fresh_context
    from paddle_tpu.trainer_config_helpers import (
        classification_cost,
        data_layer,
        fc_layer,
        outputs,
        rank_auc_evaluator,
        seq_classification_error_evaluator,
        settings,
        SoftmaxActivation,
    )

    with fresh_context() as ctx:
        settings(batch_size=8, learning_rate=0.1)
        d = data_layer("x", size=4)
        out = fc_layer(input=d, size=2, act=SoftmaxActivation())
        label = data_layer("label", size=2)
        rank_auc_evaluator(input=out, click=label)
        seq_classification_error_evaluator(input=out, label=label)
        outputs(classification_cost(input=out, label=label))
        tc = ctx.finalize()
    types = [e.type for e in tc.model_config.evaluators]
    assert "rank-auc" in types and "seq_classification_error" in types


def test_validation_layers_parse_train_and_report(tmp_path):
    """auc-validation / pnpair-validation compat (ref: ValidationLayer.h:
    52,84; config_parser.py:1703-1704): a reference-style config using
    both parses, trains, and reports the metrics through test()."""
    import textwrap

    train_list = tmp_path / "train.list"
    train_list.write_text("1\n")
    cfg_src = textwrap.dedent(f"""
    from paddle_tpu.trainer_config_helpers import *
    define_py_data_sources2(train_list={str(train_list)!r},
                            test_list={str(train_list)!r},
                            module="synthetic_bow", obj="process")
    settings(batch_size=32, learning_rate=0.3)
    data = data_layer(name="word", size=100)
    output = fc_layer(input=data, size=2, act=SoftmaxActivation(), name="output")
    label = data_layer(name="label", size=2)
    av = auc_validation(input=output, label=label)
    # info: one query group for every row (single-column layer -> qid 0)
    qid = fc_layer(input=data, size=1, act=LinearActivation(), name="qid")
    pv = pnpair_validation(input=output, label=label, info=qid)
    outputs(classification_cost(input=output, label=label), av, pv)
    """)
    cfg_path = tmp_path / "cfg.py"
    cfg_path.write_text(cfg_src)

    import sys as _sys

    from paddle_tpu.config import parse_config
    from paddle_tpu.trainer import Trainer
    from paddle_tpu.utils.flags import FLAGS

    providers = os.path.join(REPO, "tests", "providers")
    _sys.path.insert(0, providers)
    FLAGS.save_dir = str(tmp_path / "model")
    FLAGS.log_period = 0
    try:
        cfg = parse_config(str(cfg_path))
        types = {l.type for l in cfg.model_config.layers}
        assert {"auc-validation", "pnpair-validation"} <= types, types
        trainer = Trainer(cfg)
        trainer.train(num_passes=2)
        metrics = trainer.test()
    finally:
        _sys.path.remove(providers)
    # the separable synthetic data trains to a strong ranking
    # (results keys are '<evaluator name>.<metric>')
    auc = [v for k, v in metrics.items() if k.endswith(".auc")]
    pnp = [v for k, v in metrics.items() if k.endswith(".pnpair_accuracy")]
    assert auc and auc[0] > 0.9, metrics
    assert pnp and pnp[0] > 0.9, metrics
    # validation layers contribute zero cost (the real cost dominates)
    assert np.isfinite(metrics["cost"])


def test_pnpair_vectorized_matches_reference_loop():
    """The vectorized pair walk must agree with the reference's O(n^2)
    loop semantics (PnpairEvaluator::stat: pair weight = mean of sample
    weights, ties 0.5) on randomized grouped data."""
    rng = np.random.RandomState(0)
    e = ev.evaluator_registry.get("pnpair")(EvaluatorConfig(name="p", type="pnpair"))
    n = 120
    qids = rng.randint(0, 5, n)
    labels = rng.randint(0, 3, n)
    scores = np.round(rng.rand(n), 2)  # rounding forces ties
    weights = rng.rand(n) + 0.5
    e.records = list(zip(qids.tolist(), labels.tolist(),
                         scores.tolist(), weights.tolist()))
    got = e.result()["pnpair_accuracy"]
    # sub-unit total pair weight must not deflate the metric
    e2 = ev.evaluator_registry.get("pnpair")(EvaluatorConfig(name="p2", type="pnpair"))
    e2.records = [(0, 1, 0.9, 0.5), (0, 0, 0.1, 0.5)]  # one pair, weight 0.5
    assert e2.result()["pnpair_accuracy"] == 1.0

    # reference loop
    from collections import defaultdict

    by_q = defaultdict(list)
    for q, l, s, w in e.records:
        by_q[q].append((l, s, w))
    pos, total = 0.0, 0.0
    for items in by_q.values():
        for i in range(len(items)):
            for j in range(i + 1, len(items)):
                li, si, wi = items[i]
                lj, sj, wj = items[j]
                if li == lj:
                    continue
                w = (wi + wj) / 2.0
                total += w
                hi, lo = (si, sj) if li > lj else (sj, si)
                if hi > lo:
                    pos += w
                elif hi == lo:
                    pos += 0.5 * w
    expected = pos / total
    np.testing.assert_allclose(got, expected, rtol=1e-12)
