"""batches_per_launch (fused device launches): k consecutive same-shape
batches train in ONE dispatch via lax.scan, each with its own optimizer
update — numerics match the unfused loop (the TPU-native answer to
per-step dispatch latency; no reference counterpart, see
doc/performance.md).
"""

import os
import sys
import textwrap

import numpy as np
import pytest

from paddle_tpu.config import parse_config
from paddle_tpu.trainer import Trainer
from paddle_tpu.utils.flags import FLAGS

PROVIDER_DIR = os.path.join(os.path.dirname(__file__), "providers")


@pytest.fixture(autouse=True)
def _provider_path():
    sys.path.insert(0, PROVIDER_DIR)
    yield
    sys.path.remove(PROVIDER_DIR)


DEFAULT_BODY = """
    data = data_layer(name="word", size=100)
    output = fc_layer(input=data, size=2, act=SoftmaxActivation(), name="output")
"""

DROPOUT_BODY = """
    data = data_layer(name="word", size=100)
    hid = fc_layer(input=data, size=32, act=ReluActivation())
    drop = dropout_layer(input=hid, dropout_rate=0.5)
    output = fc_layer(input=drop, size=2, act=SoftmaxActivation(), name="output")
"""


def _config(tmp_path, extra_settings="", body=DEFAULT_BODY, with_test=True):
    train_list = tmp_path / "train.list"
    train_list.write_text("1\n2\n3\n")
    if with_test:
        test_list = tmp_path / "test.list"
        test_list.write_text("99\n")
        test_ref = str(test_list)
    else:
        test_ref = None
    src = textwrap.dedent(f"""
    from paddle_tpu.trainer_config_helpers import *

    define_py_data_sources2(train_list={str(train_list)!r},
                            test_list={test_ref!r},
                            module="synthetic_bow", obj="process")
    settings(batch_size=64, learning_rate=0.02,
             learning_method=AdamOptimizer(){extra_settings})
{body}
    label = data_layer(name="label", size=2)
    outputs(classification_cost(input=output, label=label))
    """)
    cfg_path = tmp_path / f"cfg{abs(hash(extra_settings + body)) % 997}.py"
    cfg_path.write_text(src)
    return parse_config(str(cfg_path))


def _fresh_flags(tmp_path, name):
    FLAGS.save_dir = str(tmp_path / name)
    FLAGS.num_passes = 2
    FLAGS.log_period = 0
    FLAGS.start_pass = 0
    FLAGS.init_model_path = ""
    FLAGS.seed = 7


def test_fused_matches_unfused(tmp_path):
    _fresh_flags(tmp_path, "out1")
    t1 = Trainer(_config(tmp_path))
    t1.train(num_passes=2)
    r1 = t1.test()

    _fresh_flags(tmp_path, "out3")
    cfg3 = _config(tmp_path, extra_settings=", batches_per_launch=3")
    assert cfg3.opt_config.batches_per_launch == 3  # settings() plumbing
    t3 = Trainer(cfg3)
    assert t3._fuse_k == 3
    t3.train(num_passes=2)
    r3 = t3.test()

    # same batches in the same order, one optimizer update per batch either
    # way — parameters agree to float tolerance (fusion only changes how
    # XLA schedules the same math) and the optimizer stepped once per batch
    assert int(t1.opt_state.step) == int(t3.opt_state.step)
    for k in t1.params:
        np.testing.assert_allclose(
            np.asarray(t1.params[k]), np.asarray(t3.params[k]),
            rtol=2e-5, atol=2e-6, err_msg=k,
        )
    for k, v in r1.items():
        assert abs(v - r3[k]) < 1e-4, (k, v, r3[k])


def test_fused_remainder_runs_single(tmp_path):
    # 1200 samples / batch 64 = 18 full batches + one 48-sample remainder:
    # with k=4 the remainder (and the flushed tail of full batches) must
    # run through the single-step path, never dropping a batch
    _fresh_flags(tmp_path, "out4")
    cfg = _config(tmp_path, extra_settings=", batches_per_launch=4")
    t = Trainer(cfg)
    t.train(num_passes=1)
    assert int(t.opt_state.step) == 19  # every batch updated exactly once


def test_launch_groups_grouping(tmp_path):
    _fresh_flags(tmp_path, "out5")
    cfg = _config(tmp_path, extra_settings=", batches_per_launch=2")
    t = Trainer(cfg)

    def item(n, shape):
        return (n, None, {"x": np.zeros(shape, np.float32)})

    stream = [
        item(4, (4, 3)),  # a
        item(4, (4, 3)),  # b -> fused(a,b)
        item(4, (4, 3)),  # c
        item(4, (4, 5)),  # shape change: c flushes single
        item(4, (4, 5)),  # -> fused(d,e)
        item(2, (2, 5)),  # tail -> single
    ]
    got = [(kind, g) for kind, g in t._launch_groups(iter(stream))]
    kinds = [k for k, _ in got]
    assert kinds == ["fused", "single", "fused", "single"]
    assert [len(g) for k, g in got if k == "fused"] == [2, 2]
    # order preserved overall
    flat = []
    for k, g in got:
        flat.extend(g if k == "fused" else [g])
    assert [f[0] for f in flat] == [4, 4, 4, 4, 4, 2]
    assert [f[2]["x"].shape for f in flat] == [
        (4, 3), (4, 3), (4, 3), (4, 5), (4, 5), (2, 5)
    ]


def test_fused_sequence_model_trains(tmp_path):
    # sequence batches (Argument with ids + seq_lengths) stack through the
    # fused scan when the padded T agrees; differing-T batches fall back
    # to single dispatches via the shape signature — either way every
    # batch gets exactly one optimizer update
    train_list = tmp_path / "train.list"
    train_list.write_text("1\n2\n")
    test_list = tmp_path / "test.list"
    test_list.write_text("99\n")
    src = textwrap.dedent(f"""
    from paddle_tpu.trainer_config_helpers import *

    define_py_data_sources2(train_list={str(train_list)!r},
                            test_list={str(test_list)!r},
                            module="synthetic_bow", obj="process_seq")
    settings(batch_size=25, learning_rate=0.01,
             learning_method=AdamOptimizer(), batches_per_launch=2)
    words = data_layer(name="words", size=100)
    emb = embedding_layer(input=words, size=16)
    lstm = simple_lstm(input=emb, size=16)
    pool = pooling_layer(input=lstm, pooling_type=MaxPooling())
    output = fc_layer(input=pool, size=2, act=SoftmaxActivation(), name="output")
    label = data_layer(name="label", size=2)
    outputs(classification_cost(input=output, label=label))
    """)
    cfg_path = tmp_path / "lstm_fused.py"
    cfg_path.write_text(src)
    _fresh_flags(tmp_path, "out_seq")
    t = Trainer(parse_config(str(cfg_path)))
    t.train(num_passes=1)
    # 2 files x 200 samples / 25 = 16 batches
    assert int(t.opt_state.step) == 16
    err = [v for k, v in t.test().items() if "classification_error" in k][0]
    assert err < 0.2


def test_fused_launch_composes_with_pallas_rnn(tmp_path, monkeypatch):
    # both knobs at once: the pallas sequence kernel runs inside the
    # fused-launch lax.scan body (a custom call in the scan is fine) and
    # the trained parameters match the plain (unfused, scan-path) loop.
    # B must satisfy the kernel's B % 8 gate or the pallas path silently
    # declines (tests/test_pallas_lstm.py pins that rejection).
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    train_list = tmp_path / "train.list"
    train_list.write_text("1\n")
    test_list = tmp_path / "test.list"
    test_list.write_text("99\n")

    def cfg_src(extra):
        return textwrap.dedent(f"""
        from paddle_tpu.trainer_config_helpers import *

        define_py_data_sources2(train_list={str(train_list)!r},
                                test_list={str(test_list)!r},
                                module="synthetic_bow", obj="process_seq")
        settings(batch_size=40, learning_rate=0.01,
                 learning_method=AdamOptimizer(){extra})
        words = data_layer(name="words", size=100)
        emb = embedding_layer(input=words, size=16)
        lstm = simple_lstm(input=emb, size=128)
        pool = pooling_layer(input=lstm, pooling_type=MaxPooling())
        output = fc_layer(input=pool, size=2, act=SoftmaxActivation(), name="output")
        label = data_layer(name="label", size=2)
        outputs(classification_cost(input=output, label=label))
        """)

    from paddle_tpu.ops import pallas_lstm as pk

    calls = []
    orig = pk.lstm_layer_forward
    monkeypatch.setattr(
        pk, "lstm_layer_forward",
        lambda *a, **k: (calls.append(1), orig(*a, **k))[1],
    )

    p_base = tmp_path / "base.py"
    p_base.write_text(cfg_src(""))
    _fresh_flags(tmp_path, "out_base")
    t_base = Trainer(parse_config(str(p_base)))
    t_base.train(num_passes=1)
    assert not calls  # baseline: plain loop, scan path

    p_both = tmp_path / "both.py"
    p_both.write_text(cfg_src(", batches_per_launch=2, pallas_rnn=True"))
    _fresh_flags(tmp_path, "out_both")
    t_both = Trainer(parse_config(str(p_both)))
    t_both.train(num_passes=1)
    assert calls  # the kernel ran inside the fused-launch scan

    assert int(t_both.opt_state.step) == int(t_base.opt_state.step) == 5
    for k in t_base.params:
        np.testing.assert_allclose(
            np.asarray(t_both.params[k]), np.asarray(t_base.params[k]),
            rtol=5e-4, atol=5e-5, err_msg=k,
        )


def test_fused_nan_gate_fires_before_housekeeping(tmp_path):
    # a non-finite loss inside a fused launch must abort with the launch
    # batch index BEFORE any periodic housekeeping can observe (and e.g.
    # checkpoint) the poisoned params
    _fresh_flags(tmp_path, "out_nan")
    cfg = _config(tmp_path, extra_settings=", batches_per_launch=4")
    # Adam normalizes updates, so a large-but-finite lr keeps the loss
    # finite; an inf lr poisons the params after the first update and the
    # SECOND batch of the first launch sees a non-finite loss
    cfg.opt_config.learning_rate = float("inf")
    FLAGS.saving_period_by_batches = 1  # housekeeping WOULD save each batch
    t = Trainer(cfg)
    with pytest.raises(FloatingPointError, match="launch of"):
        t.train(num_passes=1)
    # the gate fired before per-batch housekeeping: despite a save period
    # of one batch, no checkpoint of the poisoned params was written
    # (telemetry artifacts — metrics.jsonl — are fine; pass dirs are not)
    save_dir = str(tmp_path / "out_nan")
    assert not os.path.exists(save_dir) or not [
        d for d in os.listdir(save_dir) if d.startswith("pass-")
    ]


def test_fused_rejects_accumulation(tmp_path):
    _fresh_flags(tmp_path, "out6")
    cfg = _config(
        tmp_path,
        extra_settings=(
            ", batches_per_launch=2, num_batches_per_send_parameter=2"
        ),
    )
    with pytest.raises(ValueError, match="batches_per_launch"):
        Trainer(cfg)


def test_fused_matches_unfused_with_dropout(tmp_path):
    """rng-using models too: the fused path consumes one split of the
    pass rng chain PER BATCH exactly like the unfused loop, so dropout
    masks are identical and k>1 reproduces k=1 numerics bitwise (up to
    float scheduling tolerance)."""

    _fresh_flags(tmp_path, "outd1")
    t1 = Trainer(_config(tmp_path, body=DROPOUT_BODY, with_test=False))
    t1.train(num_passes=1)

    _fresh_flags(tmp_path, "outd3")
    t3 = Trainer(_config(tmp_path, ", batches_per_launch=3",
                         body=DROPOUT_BODY, with_test=False))
    t3.train(num_passes=1)

    assert int(t1.opt_state.step) == int(t3.opt_state.step)
    for k in t1.params:
        np.testing.assert_allclose(
            np.asarray(t1.params[k], dtype=np.float32),
            np.asarray(t3.params[k], dtype=np.float32),
            rtol=2e-5, atol=2e-6, err_msg=k,
        )
