"""SPMD data parallelism on a virtual 8-device CPU mesh.

The analog of the reference's loopback-pserver distributed tests
(/root/reference/paddle/trainer/tests/test_TrainerOnePass.cpp:120-296
checkRemoteUpdater*): a sharded trainer must produce the same parameters as
the single-device trainer on the same data.
"""

import os
import sys
import textwrap

import jax
import numpy as np
import pytest

from paddle_tpu.config import parse_config
from paddle_tpu.parallel import make_mesh
from paddle_tpu.trainer import Trainer
from paddle_tpu.utils.flags import FLAGS

PROVIDER_DIR = os.path.join(os.path.dirname(__file__), "providers")


@pytest.fixture(autouse=True)
def _provider_path(tmp_path):
    sys.path.insert(0, PROVIDER_DIR)
    FLAGS.save_dir = str(tmp_path / "model")
    FLAGS.mesh_shape = ""
    FLAGS.start_pass = 0
    FLAGS.init_model_path = ""
    FLAGS.log_period = 0
    yield
    sys.path.remove(PROVIDER_DIR)
    FLAGS.mesh_shape = ""


def _lr_config(tmp_path, batch_size=64):
    train_list = tmp_path / "train.list"
    train_list.write_text("1\n2\n")
    test_list = tmp_path / "test.list"
    test_list.write_text("99\n")
    src = textwrap.dedent(f"""
    from paddle_tpu.trainer_config_helpers import *

    define_py_data_sources2(train_list={str(train_list)!r}, test_list={str(test_list)!r},
                            module="synthetic_bow", obj="process")
    settings(batch_size={batch_size}, learning_rate=0.05)
    data = data_layer(name="word", size=100)
    output = fc_layer(input=data, size=2, act=SoftmaxActivation(), name="output")
    label = data_layer(name="label", size=2)
    outputs(classification_cost(input=output, label=label))
    """)
    cfg_path = tmp_path / "lr_config.py"
    cfg_path.write_text(src)
    return parse_config(str(cfg_path))


def test_mesh_construction():
    assert len(jax.devices()) == 8, "conftest should provide 8 virtual devices"
    mesh = make_mesh("data=8")
    assert mesh.shape == {"data": 8}
    mesh2 = make_mesh("data=4,model=2")
    assert mesh2.shape == {"data": 4, "model": 2}


def test_sharded_matches_single_device(tmp_path):
    cfg = _lr_config(tmp_path)
    t_single = Trainer(cfg)
    t_single.train(num_passes=1)

    FLAGS.mesh_shape = "data=8"
    t_sharded = Trainer(cfg)
    assert t_sharded._mesh is not None
    t_sharded.train(num_passes=1)
    FLAGS.mesh_shape = ""

    w1 = np.asarray(t_single.params["_output.w0"])
    w2 = np.asarray(t_sharded.params["_output.w0"])
    np.testing.assert_allclose(w1, w2, rtol=2e-4, atol=1e-5)

    r1 = t_single.test()
    r2 = t_sharded.test()
    err1 = [v for k, v in r1.items() if "classification_error" in k][0]
    err2 = [v for k, v in r2.items() if "classification_error" in k][0]
    assert abs(err1 - err2) < 0.02


def test_tensor_parallel_param_sharding(tmp_path):
    """Model-parallel parameter sharding via ParamAttr(sharding=...)."""
    train_list = tmp_path / "train.list"
    train_list.write_text("1\n")
    src = textwrap.dedent(f"""
    from paddle_tpu.trainer_config_helpers import *

    define_py_data_sources2(train_list={str(train_list)!r}, test_list=None,
                            module="synthetic_bow", obj="process")
    settings(batch_size=32, learning_rate=0.05, mesh_shape="data=4,model=2")
    data = data_layer(name="word", size=100)
    hidden = fc_layer(input=data, size=64, name="hidden",
                      param_attr=ParamAttr(sharding=[None, "model"]))
    output = fc_layer(input=hidden, size=2, act=SoftmaxActivation(), name="output",
                      param_attr=ParamAttr(sharding=["model", None]))
    label = data_layer(name="label", size=2)
    outputs(classification_cost(input=output, label=label))
    """)
    cfg_path = tmp_path / "tp_config.py"
    cfg_path.write_text(src)
    cfg = parse_config(str(cfg_path))
    trainer = Trainer(cfg)
    assert trainer._mesh is not None
    trainer.train(num_passes=1)
    # the hidden weight should actually be sharded over the model axis
    w = trainer.params["_hidden.w0"]
    sh = w.sharding
    spec = getattr(sh, "spec", None)
    assert spec is not None and tuple(spec) == (None, "model"), spec


def test_three_axis_mesh_composed_sharding():
    """data=2 × model=2 × seq=2 in ONE train step: batch sharded over
    data, embedding + softmax weight over model, attention context over
    seq (ring) — the composed 64-chip layout at virtual scale, with bf16
    and remat on (the production stack)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.config.builder import fresh_context
    from paddle_tpu.flagship import example_batch
    from paddle_tpu.graph import GradientMachine
    from paddle_tpu.graph.machine import compute_dtype_of
    from paddle_tpu.optimizer import Updater
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.spmd import shard_train_step
    from paddle_tpu.trainer_config_helpers import (
        MaxPooling,
        ParamAttr,
        SoftmaxActivation,
        classification_cost,
        data_layer,
        embedding_layer,
        fc_layer,
        multi_head_attention_layer,
        outputs,
        pooling_layer,
        settings,
    )

    def build(dtype="bfloat16", remat="full", mesh_shape="data=2,model=2,seq=2"):
        with fresh_context() as ctx:
            settings(batch_size=8, learning_rate=1e-3, dtype=dtype,
                     remat=remat, mesh_shape=mesh_shape)
            words = data_layer(name="words", size=300)
            emb = embedding_layer(
                input=words, size=32,
                param_attr=ParamAttr(name="emb", sharding=(None, "model")),
            )
            att = multi_head_attention_layer(
                input=emb, num_heads=4, causal=True, seq_parallel="ring", name="att"
            )
            pool = pooling_layer(input=att, pooling_type=MaxPooling())
            out = fc_layer(
                input=pool, size=4, act=SoftmaxActivation(), name="output",
                param_attr=ParamAttr(name="w_out", sharding=("model", None)),
            )
            label = data_layer(name="label", size=4)
            outputs(classification_cost(input=out, label=label))
            return ctx.finalize()

    losses = {}
    for key, (dtype, remat, mesh_shape) in {
        "plain": ("float32", "none", None),
        "3axis": ("bfloat16", "full", "data=2,model=2,seq=2"),
    }.items():
        tc = build(dtype, remat, mesh_shape or "")
        gm = GradientMachine(tc.model_config,
                             compute_dtype=compute_dtype_of(tc.opt_config))
        up = Updater(tc.opt_config, tc.model_config)
        params = gm.init_params(seed=6)
        opt_state = up.init_state(params)
        grad_fn = gm.grad_fn(remat=tc.opt_config.remat)

        def step(params, opt_state, batch, rng, bs):
            loss, grads, outs, su = grad_fn(params, batch, rng)
            new_params, new_opt = up(params, grads, opt_state, bs)
            for k, v in su.items():
                new_params[k] = v
            return new_params, new_opt, loss, outs["output"].value

        batch = example_batch(dict_dim=300, B=8, T=16, classes=4, seed=2)
        rng = jax.random.PRNGKey(3)
        if mesh_shape:
            mesh = make_mesh(mesh_shape)
            gm.mesh = mesh
            sharded = shard_train_step(step, mesh, gm)
            new_p, _, loss, out = sharded(params, opt_state, batch, rng, jnp.asarray(8.0))
            # parameters keep their declared layouts through the update
            assert "model" in str(new_p["emb"].sharding.spec)
            assert "model" in str(new_p["w_out"].sharding.spec)
        else:
            _, _, loss, out = jax.jit(step)(params, opt_state, batch, rng, jnp.asarray(8.0))
        losses[key] = float(loss)
    assert np.isfinite(losses["3axis"])
    np.testing.assert_allclose(losses["plain"], losses["3axis"], rtol=0.03, atol=0.02)
