"""Mixed-precision (bf16) tests.

OptimizationConfig.dtype="bfloat16" runs activations and matmuls in bf16
with f32 master weights, optimizer state, and loss math (the TPU
mixed-precision recipe; no reference counterpart — the reference is
float-or-double only, /root/reference/proto/CMakeLists.txt:15-16
WITH_DOUBLE). Parity tests compare bf16 training against f32 with loose
tolerance, per-layer dtype checks pin the f32 islands (softmax, loss,
batch-norm statistics), and checkgrad proves mixed precision does not
leak into the finite-difference path.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.flagship import example_batch, flagship_config
from paddle_tpu.graph import GradientMachine, make_dense, make_ids
from paddle_tpu.graph.machine import compute_dtype_of
from paddle_tpu.optimizer import Updater


def _jit_step(tc, seed=1):
    """The train step of `tc` as the tests of this file drive it:
    (jitted step, params, optimizer state, machine)."""
    gm = GradientMachine(tc.model_config, compute_dtype=compute_dtype_of(tc.opt_config))
    up = Updater(tc.opt_config, tc.model_config)
    params = gm.init_params(seed=seed)
    st = up.init_state(params)
    grad_fn = gm.grad_fn()

    @jax.jit
    def step(params, st, batch, rng):
        loss, grads, outputs, su = grad_fn(params, batch, rng)
        new_params, new_st = up(params, grads, st, jnp.asarray(float(_bs(batch))))
        for k, v in su.items():
            new_params[k] = v
        return new_params, new_st, loss, grads

    return step, params, st, gm


def _train(tc, batch, steps=5, seed=1):
    step, params, st, gm = _jit_step(tc, seed)
    losses = []
    rng = jax.random.PRNGKey(7)
    grads = None
    for _ in range(steps):
        rng, sub = jax.random.split(rng)
        params, st, loss, grads = step(params, st, batch, sub)
        losses.append(float(loss))
    return losses, params, grads, gm


def _bs(batch):
    for a in batch.values():
        return a.batch_size


def test_compute_dtype_of():
    tc = flagship_config()
    assert compute_dtype_of(tc.opt_config) is None
    tc.opt_config.dtype = "bfloat16"
    assert compute_dtype_of(tc.opt_config) == jnp.bfloat16
    tc.opt_config.dtype = "int8"
    with pytest.raises(ValueError):
        compute_dtype_of(tc.opt_config)


def test_lstm_classifier_bf16_parity():
    batch = example_batch(B=8, T=16)
    tc = flagship_config()
    l32, p32, g32, _ = _train(tc, batch)
    tc.opt_config.dtype = "bfloat16"
    l16, p16, g16, _ = _train(tc, batch)
    # losses track within bf16 tolerance and training makes progress
    np.testing.assert_allclose(l16, l32, rtol=0.05, atol=0.02)
    assert l32[-1] < l32[0] and l16[-1] < l16[0]
    # master params and their gradients stay f32
    assert all(v.dtype == jnp.float32 for v in p16.values())
    assert all(getattr(v, "dtype", jnp.float32) == jnp.float32 for v in jax.tree_util.tree_leaves(g16))


def test_bf16_activation_islands():
    """Activations bf16; softmax output bf16 but normalized; cost f32."""
    tc = flagship_config()
    tc.opt_config.dtype = "bfloat16"
    gm = GradientMachine(tc.model_config, compute_dtype=jnp.bfloat16)
    batch = example_batch(B=4, T=8)
    outputs, _ = gm.forward(gm.init_params(seed=1), batch, "train", jax.random.PRNGKey(0))
    assert outputs["__embedding_0__"].value.dtype == jnp.bfloat16
    assert outputs["output"].value.dtype == jnp.bfloat16
    assert outputs["__cost_0__"].value.dtype == jnp.float32
    # softmax computed in f32 internally: rows sum to 1 within bf16 eps
    s = np.asarray(outputs["output"].value.astype(jnp.float32)).sum(-1)
    np.testing.assert_allclose(s, 1.0, atol=2e-2)


def _vgg_cifar_config(dtype):
    from paddle_tpu.config.builder import fresh_context
    from paddle_tpu.trainer_config_helpers import (
        MomentumOptimizer,
        SoftmaxActivation,
        classification_cost,
        data_layer,
        fc_layer,
        img_conv_layer,
        img_pool_layer,
        batch_norm_layer,
        outputs,
        settings,
    )

    with fresh_context() as ctx:
        settings(batch_size=8, learning_rate=0.01,
                 learning_method=MomentumOptimizer(0.9), dtype=dtype)
        img = data_layer(name="image", size=3 * 8 * 8)
        conv = img_conv_layer(input=img, filter_size=3, num_filters=8,
                              num_channels=3, stride=1, padding=1, name="conv")
        bn = batch_norm_layer(input=conv, name="bn")
        pool = img_pool_layer(input=bn, pool_size=2, stride=2, num_channels=8)
        out = fc_layer(input=pool, size=4, act=SoftmaxActivation(), name="out")
        label = data_layer(name="label", size=4)
        outputs(classification_cost(input=out, label=label))
        return ctx.finalize()


def _image_batch(B=8):
    rng = np.random.RandomState(3)
    return {
        "image": make_dense(rng.randn(B, 3 * 8 * 8).astype(np.float32)),
        "label": make_ids(rng.randint(0, 4, (B,)).astype(np.int32)),
    }


def test_conv_bn_bf16_parity_and_f32_stats():
    batch = _image_batch()
    l32, p32, _, _ = _train(_vgg_cifar_config("float32"), batch, steps=4)
    l16, p16, _, gm16 = _train(_vgg_cifar_config("bfloat16"), batch, steps=4)
    np.testing.assert_allclose(l16, l32, rtol=0.08, atol=0.05)
    # batch-norm running stats are master-dtype f32 and track the f32 run
    stats = [n for n in p16 if "moving" in n or "mean" in n or "var" in n]
    assert gm16.compute_dtype == jnp.bfloat16
    for n in p16:
        assert p16[n].dtype == jnp.float32, n
    for n in stats:
        np.testing.assert_allclose(
            np.asarray(p16[n]), np.asarray(p32[n]), rtol=0.05, atol=0.05
        )


def test_checkgrad_ignores_compute_dtype():
    tc = flagship_config()
    tc.opt_config.dtype = "bfloat16"
    gm = GradientMachine(tc.model_config, compute_dtype=jnp.bfloat16)
    params = gm.init_params(seed=1)
    report = gm.check_gradient(params, example_batch(B=4, T=8), max_entries=3)
    assert gm.compute_dtype == jnp.bfloat16  # restored after the check
    assert report and all(d < 5e-2 for d in report.values()), report


def test_cost_only_data_layers_not_narrowed():
    """Regression targets / weights feed only cost layers — their dense
    values must reach the f32 loss island un-rounded."""
    from paddle_tpu.config.builder import fresh_context
    from paddle_tpu.trainer_config_helpers import (
        LinearActivation,
        data_layer,
        fc_layer,
        outputs,
        regression_cost,
        settings,
    )

    with fresh_context() as ctx:
        settings(batch_size=4, learning_rate=0.1, dtype="bfloat16")
        x = data_layer(name="x", size=8)
        y = data_layer(name="y", size=1)
        pred = fc_layer(input=x, size=1, act=LinearActivation(), name="pred")
        outputs(regression_cost(input=pred, label=y))
        tc = ctx.finalize()

    gm = GradientMachine(tc.model_config, compute_dtype=jnp.bfloat16)
    assert gm.no_cast_inputs == frozenset({"y"})
    rng = np.random.RandomState(5)
    batch = {
        "x": make_dense(rng.randn(4, 8).astype(np.float32)),
        "y": make_dense(np.full((4, 1), 0.123456, np.float32)),
    }
    outs, _ = gm.forward(gm.init_params(seed=1), batch, "train", None)
    assert outs["x"].value.dtype == jnp.bfloat16   # feature narrowed
    assert outs["y"].value.dtype == jnp.float32    # target untouched
    np.testing.assert_array_equal(np.asarray(outs["y"].value), batch["y"].value)


def test_sparse_table_grads_stay_f32_under_bf16():
    """sparse_update embedding: prefetched rows cast to bf16 in compute,
    RowSparseGrad rows come back f32 for the master update."""
    from paddle_tpu.config.builder import fresh_context
    from paddle_tpu.trainer_config_helpers import (
        ParamAttr,
        SoftmaxActivation,
        classification_cost,
        data_layer,
        embedding_layer,
        fc_layer,
        outputs,
        pooling_layer,
        settings,
    )
    from paddle_tpu.optimizer.sparse import RowSparseGrad

    with fresh_context() as ctx:
        settings(batch_size=4, learning_rate=0.1, dtype="bfloat16")
        words = data_layer(name="words", size=100)
        emb = embedding_layer(
            input=words, size=8,
            param_attr=ParamAttr(name="emb", sparse_update=True),
        )
        pool = pooling_layer(input=emb)
        out = fc_layer(input=pool, size=2, act=SoftmaxActivation(), name="out")
        label = data_layer(name="label", size=2)
        outputs(classification_cost(input=out, label=label))
        tc = ctx.finalize()

    gm = GradientMachine(tc.model_config, compute_dtype=compute_dtype_of(tc.opt_config))
    params = gm.init_params(seed=1)
    batch = example_batch(dict_dim=100, B=4, T=8)
    loss, grads, _, _ = gm.grad_fn()(params, batch, jax.random.PRNGKey(0))
    g = grads["emb"]
    assert isinstance(g, RowSparseGrad)
    assert g.rows.dtype == jnp.float32
    assert np.isfinite(float(loss))


def test_resnet_bf16_reaches_every_convolution():
    """Under dtype='bfloat16', EVERY convolution (forward and backward)
    in the lowered ResNet train step takes/produces bf16 — what the TPU
    backend maps onto the MXU's bf16 path. Checked on the pre-backend
    StableHLO (XLA:CPU would legalize bf16 convs to f32, hiding a
    regression)."""
    from paddle_tpu.flagship import make_image_batch, resnet_config

    tc = resnet_config(50, 32, 16)
    tc.opt_config.batch_size = 4
    tc.opt_config.dtype = "bfloat16"
    step, params, opt_state, _gm = _jit_step(tc)
    batch = make_image_batch(4, 32, 16)
    txt = step.lower(params, opt_state, batch, jax.random.PRNGKey(0)).as_text()
    convs = [l for l in txt.splitlines() if "stablehlo.convolution" in l]
    assert len(convs) > 100, f"expected ResNet-50 fwd+bwd convs, got {len(convs)}"
    f32_convs = [l for l in convs if "xbf16>" not in l.split("->")[-1]]
    assert not f32_convs, f"{len(f32_convs)} convolutions fell back to f32:\n" + \
        "\n".join(c.strip()[:160] for c in f32_convs[:5])
