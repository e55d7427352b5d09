"""Async SGD analog (algorithm='async_sgd' → local SGD over the data
mesh axis, paddle_tpu/parallel/local_sgd.py).

Semantics pinned here:
- merge period 1 with a linear-in-gradient method (momentum) reproduces
  sync SGD exactly (averaging after linear local updates == updating
  with the mean gradient);
- longer merge periods still converge on a separable problem;
- the drift gate (async_lagged_grad_discard_ratio analog of the
  reference's stale-gradient discard, TrainerConfig.proto.m4:124-129)
  excludes a diverged replica from the merge and reports it;
- the DSL surface (settings(is_async=True, ...)) reaches
  OptimizationConfig.
"""

import sys
import tempfile
import textwrap

import numpy as np
import pytest

from paddle_tpu.config import parse_config
from paddle_tpu.trainer import Trainer
from paddle_tpu.utils.flags import FLAGS


PROVIDER = """
import numpy as np
from paddle_tpu.data import provider, dense_vector, integer_value

@provider(input_types=[dense_vector(20), integer_value(3)],
          should_shuffle=False)
def process(settings, filename):
    rng = np.random.RandomState(11)
    for _ in range(192):
        y = rng.randint(0, 3)
        x = (rng.randn(20) * 0.4 + y).astype(np.float32)
        yield x.tolist(), int(y)
"""


def _config(tmp_path, is_async, period=1, ratio=None):
    train_list = tmp_path / "train.list"
    train_list.write_text("a\n")
    extra = f", async_lagged_grad_discard_ratio={ratio}" if ratio is not None else ""
    src = textwrap.dedent(f"""
    from paddle_tpu.trainer_config_helpers import *

    define_py_data_sources2(train_list={str(train_list)!r}, test_list=None,
                            module="lsgdprov", obj="process")
    settings(batch_size=16, learning_rate=0.05,
             learning_method=MomentumOptimizer(momentum=0.9),
             is_async={is_async},
             num_batches_per_send_parameter={period}{extra})
    data = data_layer(name="x", size=20)
    h = fc_layer(input=data, size=8, act=TanhActivation(), name="h")
    output = fc_layer(input=h, size=3, act=SoftmaxActivation(), name="output")
    label = data_layer(name="label", size=3)
    outputs(classification_cost(input=output, label=label))
    """)
    p = tmp_path / f"cfg_async{int(is_async)}_{period}_{ratio}.py"
    p.write_text(src)
    return str(p)


@pytest.fixture()
def ws(tmp_path):
    (tmp_path / "lsgdprov.py").write_text(PROVIDER)
    sys.path.insert(0, str(tmp_path))
    yield tmp_path
    sys.path.remove(str(tmp_path))


def _train(tmp_path, is_async, period=1, ratio=None, passes=2, stats_period=0):
    FLAGS.save_dir = tempfile.mkdtemp(dir=tmp_path)
    FLAGS.log_period = 0
    FLAGS.start_pass = 0
    FLAGS.init_model_path = ""
    FLAGS.mesh_shape = "data=8"
    FLAGS.show_parameter_stats_period = stats_period
    cfg = parse_config(_config(tmp_path, is_async, period, ratio))
    tr = Trainer(cfg)
    tr.train(num_passes=passes)
    return tr, {k: np.asarray(v) for k, v in tr.params.items()}


def test_async_period1_matches_sync_momentum(ws):
    """Merge period 1 + momentum == sync SGD: local updates are linear in
    the gradient, so post-update averaging equals the mean-gradient
    update, bit-for-bit up to float reassociation."""
    _, p_sync = _train(ws, is_async=False)
    tr, p_async = _train(ws, is_async=True, period=1)
    assert tr._async, "async mode should be active under data=8"
    # the staleness gate must NOT fire on healthy stochastic variation
    assert tr._lsgd_discarded == 0
    assert set(p_sync) == set(p_async)
    for k in p_sync:
        np.testing.assert_allclose(p_async[k], p_sync[k], rtol=2e-4,
                                   atol=2e-5, err_msg=k)


def test_async_period4_converges(ws):
    """Merge every 4 batches: replicas diverge between merges but the
    averaged model still learns the (separable) problem."""
    tr, p_async = _train(ws, is_async=True, period=4, passes=3)
    # canonical params materialized (flushed) after training
    for k, v in p_async.items():
        assert v.ndim <= 2, f"{k} left stacked: {v.shape}"
    # train cost on a fresh sweep must beat the ~log(3) random baseline
    provider = tr._provider(for_test=False)
    cost, _, _ = tr._full_data_sweep(tr.params, provider, want_grad=False)
    assert cost < 0.7, f"local SGD failed to learn: cost {cost}"


def test_observability_does_not_perturb_async_numerics(ws):
    """Mid-pass stats/test hooks read a PASSIVE merged view: turning on
    show_parameter_stats_period must reproduce the exact parameters of a
    run without it — a logging flag must not cut the merge period short
    (the reference pserver's test path read merged params without
    collapsing trainers' local progress)."""
    _, plain = _train(ws, is_async=True, period=4)
    _, with_stats = _train(ws, is_async=True, period=4, stats_period=3)
    for k in plain:
        np.testing.assert_array_equal(plain[k], with_stats[k], err_msg=k)


def test_drift_gate_discards_outlier():
    """One replica pushed far from the rest is excluded by the gate and
    counted; with the gate disabled (ratio<=0) it contaminates the mean."""
    from paddle_tpu.parallel.local_sgd import LocalSgd
    from paddle_tpu.parallel.mesh import make_mesh

    mesh = make_mesh("data=8")
    base = np.tile(np.arange(4, dtype=np.float32), (8, 1))  # identical
    noise = np.linspace(-0.01, 0.01, 8, dtype=np.float32)[:, None]
    stacked = base + noise
    outlier = stacked.copy()
    outlier[5] += 100.0

    gated = LocalSgd.__new__(LocalSgd)
    gated.mesh, gated.R, gated.ratio = mesh, 8, 1.5
    from jax.sharding import NamedSharding, PartitionSpec as P

    gated._stacked = NamedSharding(mesh, P("data"))
    gated._repl = NamedSharding(mesh, P())
    gated._merge_fn = None
    new_p, _, discarded = gated.merge({"w": outlier.copy()}, {})
    assert int(discarded) == 1
    merged = np.asarray(new_p["w"])
    # all replicas identical after merge, equal to the mean of the 7 kept
    expect = np.delete(outlier, 5, axis=0).mean(0)
    np.testing.assert_allclose(merged[0], expect, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(merged[5], expect, rtol=1e-5, atol=1e-6)

    ungated = LocalSgd.__new__(LocalSgd)
    ungated.mesh, ungated.R, ungated.ratio = mesh, 8, 0.0
    ungated._stacked = gated._stacked
    ungated._repl = gated._repl
    ungated._merge_fn = None
    new_p2, _, discarded2 = ungated.merge({"w": outlier.copy()}, {})
    assert int(discarded2) == 0
    np.testing.assert_allclose(
        np.asarray(new_p2["w"])[0], outlier.mean(0), rtol=1e-5, atol=1e-6
    )


def test_drift_gate_discards_nan_replica():
    """A replica with a non-finite parameter must be discarded and must
    NOT poison the merge (a plain-median anchor would turn every
    replica's drift NaN, reject everyone, and average the NaN in through
    the keep-everyone fallback)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.parallel.local_sgd import LocalSgd
    from paddle_tpu.parallel.mesh import make_mesh

    mesh = make_mesh("data=8")
    base = np.tile(np.arange(4, dtype=np.float32), (8, 1))
    base += np.linspace(-0.01, 0.01, 8, dtype=np.float32)[:, None]
    poisoned = base.copy()
    poisoned[3, 2] = np.nan

    lsgd = LocalSgd.__new__(LocalSgd)
    lsgd.mesh, lsgd.R, lsgd.ratio = mesh, 8, 1.5
    lsgd._stacked = NamedSharding(mesh, P("data"))
    lsgd._repl = NamedSharding(mesh, P())
    lsgd._merge_fn = None
    new_p, _, discarded = lsgd.merge({"w": poisoned.copy()}, {})
    assert int(discarded) == 1
    merged = np.asarray(new_p["w"])
    assert np.isfinite(merged).all(), "NaN replica poisoned the merge"
    expect = np.delete(base, 3, axis=0).mean(0)
    np.testing.assert_allclose(merged[3], expect, rtol=1e-5, atol=1e-6)


def test_is_async_reaches_opt_config(ws):
    cfg = parse_config(_config(ws, is_async=True, period=3, ratio=2.0))
    assert cfg.opt_config.algorithm == "async_sgd"
    assert cfg.opt_config.num_batches_per_send_parameter == 3
    assert cfg.opt_config.async_lagged_grad_discard_ratio == 2.0


def test_async_merge_period_not_rejected_as_accumulation(ws):
    """In async mode num_batches_per_send_parameter is the merge period
    (its reference meaning), so combining it with batches_per_launch
    must not trip the accumulation/fuse conflict check — fuse is simply
    ignored (mesh + async are not single-chip dispatch paths)."""
    FLAGS.save_dir = str(ws / "model")
    FLAGS.mesh_shape = "data=8"
    cfg = parse_config(_config(ws, is_async=True, period=4))
    cfg.opt_config.batches_per_launch = 8
    tr = Trainer(cfg)
    assert tr._async and tr._sync_n == 4
    assert tr._accum_n == 1 and tr._fuse_k == 1
