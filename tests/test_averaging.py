"""Sliding-window parameter averaging + the non-finite-loss trap.

Reference: AverageOptimizer (/root/reference/paddle/parameter/
AverageOptimizer.h:24,99) keeps a bounded window — average = (SUM1+SUM2+
SUM3)/(numAccumulates+oldNumAccumulates), shifting the window once it
holds min(max_average_window, numUpdates*average_window) batches. The
FP trap mirrors TrainerMain.cpp:96 (feenableexcept): NaN/Inf aborts.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.optimizer import Updater
from paddle_tpu.proto import ModelConfig, OptimizationConfig, ParameterConfig


def _updater(average_window=1.0, max_average_window=3):
    m = ModelConfig()
    m.parameters.append(ParameterConfig(name="w", size=4, dims=[4]))
    opt = OptimizationConfig(
        learning_rate=0.1, learning_method="sgd",
        learning_rate_schedule="constant", batch_size=2,
        average_window=average_window, max_average_window=max_average_window,
    )
    return Updater(opt, m)


def test_window_average_matches_reference_semantics():
    upd = _updater(average_window=1.0, max_average_window=3)
    params = {"w": jnp.zeros((4,), jnp.float32)}
    state = upd.init_state(params)
    history = []
    for i in range(5):
        g = jnp.full((4,), float(i + 1), jnp.float32)
        params, state = upd(params, {"w": g}, state, 2.0)
        history.append(np.asarray(params["w"]).copy())
    # steps 1..3 fill the window (limit = min(3, t*1.0) with min_window=3),
    # so at t=3 it shifts: old = w1+w2+w3, count 3; t=4,5 accumulate anew.
    want = (history[0] + history[1] + history[2] + history[3] + history[4]) / 5.0
    got = np.asarray(upd.averaged_params(params, state)["w"])
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert float(state.avg_old_count) == 3.0
    assert float(state.avg_count) == 2.0
    np.testing.assert_allclose(
        np.asarray(state.avg_old_sum["w"]),
        history[0] + history[1] + history[2],
        rtol=1e-6,
    )


def test_cumulative_before_first_shift():
    """Until the window first closes, the average is the plain cumulative
    mean (old bucket empty)."""
    upd = _updater(average_window=1.0, max_average_window=100)
    params = {"w": jnp.zeros((4,), jnp.float32)}
    state = upd.init_state(params)
    history = []
    for i in range(4):
        params, state = upd(params, {"w": jnp.ones((4,), jnp.float32)}, state, 2.0)
        history.append(np.asarray(params["w"]).copy())
    got = np.asarray(upd.averaged_params(params, state)["w"])
    np.testing.assert_allclose(got, np.mean(history, axis=0), rtol=1e-6)


def test_nan_loss_aborts_training(tmp_path, monkeypatch):
    import os
    import sys
    import textwrap

    from paddle_tpu.config import parse_config
    from paddle_tpu.trainer import Trainer
    from paddle_tpu.utils.flags import FLAGS

    provider_dir = os.path.join(os.path.dirname(__file__), "providers")
    sys.path.insert(0, provider_dir)
    monkeypatch.setattr(FLAGS, "save_dir", "")
    monkeypatch.setattr(FLAGS, "mesh_shape", "")
    try:
        train_list = tmp_path / "train.list"
        train_list.write_text("1\n")
        src = textwrap.dedent(f"""
        from paddle_tpu.trainer_config_helpers import *
        define_py_data_sources2(train_list={str(train_list)!r}, test_list=None,
                                module="synthetic_bow", obj="process")
        settings(batch_size=32, learning_rate=0.05)
        data = data_layer(name="word", size=100)
        output = fc_layer(input=data, size=2, act=SoftmaxActivation(), name="output")
        label = data_layer(name="label", size=2)
        outputs(classification_cost(input=output, label=label))
        """)
        cfg_path = tmp_path / "cfg.py"
        cfg_path.write_text(src)
        trainer = Trainer(parse_config(str(cfg_path)))
        # force a poisoned step: the trap must abort, not train through it
        trainer._train_step_fn = lambda p, o, b, r, n: (p, o, jnp.nan, {}, {})
        with pytest.raises(FloatingPointError, match="non-finite loss"):
            trainer.train(num_passes=1)
    finally:
        sys.path.remove(provider_dir)
