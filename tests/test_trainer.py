"""End-to-end Trainer tests — the Milestone A slice (SURVEY.md §7 stage 3):
a quick_start-style config trains through provider → Trainer → checkpoint,
and quality reaches the expected range.
"""

import os
import sys
import textwrap

import numpy as np
import pytest

from paddle_tpu.config import parse_config
from paddle_tpu.trainer import Trainer, checkpoint
from paddle_tpu.utils.flags import FLAGS

PROVIDER_DIR = os.path.join(os.path.dirname(__file__), "providers")


@pytest.fixture(autouse=True)
def _provider_path():
    sys.path.insert(0, PROVIDER_DIR)
    yield
    sys.path.remove(PROVIDER_DIR)


def write_lists(tmp_path):
    train_list = tmp_path / "train.list"
    train_list.write_text("1\n2\n3\n")
    test_list = tmp_path / "test.list"
    test_list.write_text("99\n")
    return str(train_list), str(test_list)


def lr_config(tmp_path):
    train_list, test_list = write_lists(tmp_path)
    src = textwrap.dedent(f"""
    from paddle_tpu.trainer_config_helpers import *

    define_py_data_sources2(train_list={train_list!r}, test_list={test_list!r},
                            module="synthetic_bow", obj="process")
    settings(batch_size=64, learning_rate=0.02, learning_method=AdamOptimizer())
    data = data_layer(name="word", size=100)
    output = fc_layer(input=data, size=2, act=SoftmaxActivation(), name="output")
    label = data_layer(name="label", size=2)
    cls = classification_cost(input=output, label=label)
    outputs(cls)
    """)
    cfg_path = tmp_path / "lr_config.py"
    cfg_path.write_text(src)
    return str(cfg_path)


def test_lr_trains_end_to_end(tmp_path):
    cfg = parse_config(lr_config(tmp_path))
    FLAGS.save_dir = str(tmp_path / "out")
    FLAGS.num_passes = 3
    FLAGS.log_period = 0
    FLAGS.start_pass = 0
    FLAGS.init_model_path = ""
    trainer = Trainer(cfg)
    trainer.train(num_passes=3)
    results = trainer.test()
    err = [v for k, v in results.items() if "classification_error" in k][0]
    assert err < 0.1, f"LR failed to learn: error={err}"
    # checkpoints exist and load back
    last = checkpoint.latest_pass(str(tmp_path / "out"))
    assert last == 2
    params, opt_state, meta = checkpoint.load_checkpoint(
        os.path.join(str(tmp_path / "out"), checkpoint.PASS_FMT % last),
        trainer.opt_state,
    )
    assert set(params) == set(trainer.params)
    assert opt_state is not None and int(opt_state.step) > 0


def test_resume_from_checkpoint(tmp_path):
    cfg = parse_config(lr_config(tmp_path))
    FLAGS.save_dir = str(tmp_path / "out")
    FLAGS.log_period = 0
    FLAGS.start_pass = 0
    FLAGS.init_model_path = ""
    t1 = Trainer(cfg)
    t1.train(num_passes=1)
    FLAGS.start_pass = 1
    t2 = Trainer(cfg)
    np.testing.assert_allclose(
        np.asarray(t1.params["_output.w0"]), np.asarray(t2.params["_output.w0"])
    )
    assert int(t2.opt_state.step) == int(t1.opt_state.step)
    t2.train(num_passes=2)
    FLAGS.start_pass = 0


def test_checkgrad_job(tmp_path):
    cfg = parse_config(lr_config(tmp_path))
    FLAGS.save_dir = str(tmp_path / "model")
    FLAGS.start_pass = 0
    FLAGS.init_model_path = ""
    trainer = Trainer(cfg)
    assert trainer.check_gradient(max_entries=5)


def test_lstm_sequence_trains(tmp_path):
    train_list, test_list = write_lists(tmp_path)
    src = textwrap.dedent(f"""
    from paddle_tpu.trainer_config_helpers import *

    define_py_data_sources2(train_list={train_list!r}, test_list={test_list!r},
                            module="synthetic_bow", obj="process_seq")
    settings(batch_size=32, learning_rate=0.01, learning_method=AdamOptimizer())
    words = data_layer(name="words", size=100)
    emb = embedding_layer(input=words, size=16)
    lstm = simple_lstm(input=emb, size=16)
    pool = pooling_layer(input=lstm, pooling_type=MaxPooling())
    output = fc_layer(input=pool, size=2, act=SoftmaxActivation(), name="output")
    label = data_layer(name="label", size=2)
    outputs(classification_cost(input=output, label=label))
    """)
    cfg_path = tmp_path / "lstm_config.py"
    cfg_path.write_text(src)
    cfg = parse_config(str(cfg_path))
    FLAGS.save_dir = str(tmp_path / "model")
    FLAGS.log_period = 0
    FLAGS.start_pass = 0
    trainer = Trainer(cfg)
    trainer.train(num_passes=3)
    results = trainer.test()
    err = [v for k, v in results.items() if "classification_error" in k][0]
    assert err < 0.15, f"LSTM failed to learn: error={err}"


def test_remat_full_matches_plain_gradients():
    """settings(remat="full") wraps the loss in jax.checkpoint — backward
    recomputes the forward; gradients must match the stored-activation
    path exactly (same math, different schedule)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.flagship import example_batch, flagship_config
    from paddle_tpu.graph import GradientMachine

    tc = flagship_config()
    gm = GradientMachine(tc.model_config)
    params = gm.init_params(seed=1)
    batch = example_batch(B=4, T=8)
    rng = jax.random.PRNGKey(0)
    loss_a, grads_a, _, _ = jax.jit(gm.grad_fn(remat="none"))(params, batch, rng)
    loss_b, grads_b, _, _ = jax.jit(gm.grad_fn(remat="full"))(params, batch, rng)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-6)
    for k in grads_a:
        np.testing.assert_allclose(
            np.asarray(grads_a[k]), np.asarray(grads_b[k]), rtol=1e-6, atol=1e-7,
            err_msg=k,
        )
    import pytest

    with pytest.raises(ValueError):
        gm.grad_fn(remat="bogus")


def test_multi_pass_test_job(tmp_path, caplog):
    """--job=test --test_pass=0 evaluates every saved checkpoint in
    sequence (the reference Tester's pass-by-pass mode)."""
    import logging

    from paddle_tpu import cli

    cfg_path = lr_config(tmp_path)
    FLAGS.save_dir = str(tmp_path / "out")
    FLAGS.num_passes = 3
    FLAGS.log_period = 0
    FLAGS.start_pass = 0
    FLAGS.init_model_path = ""
    Trainer(parse_config(cfg_path)).train(num_passes=3)

    # the paddle_tpu logger doesn't propagate (own stderr handler) —
    # attach caplog's handler directly to count per-pass evaluations
    from paddle_tpu.utils.logging import logger as ptu_logger

    ptu_logger.addHandler(caplog.handler)
    FLAGS.test_pass = 0
    try:
        with caplog.at_level(logging.INFO, logger="paddle_tpu"):
            rc = cli.main(["test", f"--config={cfg_path}",
                           f"--save_dir={tmp_path / 'out'}",
                           "--num_passes=3", "--test_pass=0"])
    finally:
        ptu_logger.removeHandler(caplog.handler)
    assert rc == 0
    # all three saved passes actually evaluated
    evaluated = [r for r in caplog.records if "Test (pass" in r.getMessage()]
    assert len(evaluated) == 3, [r.getMessage() for r in caplog.records][-10:]
