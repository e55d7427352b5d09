"""Analytic FLOP accounting for the fused Pallas kernels (ops/kernel_flops).

The analytic formulas must agree with XLA's own count of the equivalent
scan-path computation (fully unrolled so every step is visible to
HloCostAnalysis — a rolled while body is counted once regardless of trip
count), and the trace-time capture must collect exactly one fwd + one bwd
record when a train-shaped jit containing a fused kernel is lowered —
that sum is what a caller adds to cost_analysis()['flops'] so the pallas
and XLA paths report comparable-basis MFU.
"""

import types

import jax
import jax.numpy as jnp

import paddle_tpu.graph  # noqa: F401  (break the layers<->graph import cycle)
from paddle_tpu.layers.recurrent import (
    _scan_time,
    gru_cell_step,
    lstm_cell_step,
)
from paddle_tpu.ops import kernel_flops as kf


def _flops_of(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"])


def _lstm_cfg(H):
    return types.SimpleNamespace(
        size=H, reversed=False, active_type="tanh",
        active_gate_type="sigmoid", active_state_type="sigmoid",
    )


def _gru_cfg(H):
    return types.SimpleNamespace(
        size=H, reversed=False, active_type="tanh", active_gate_type="sigmoid",
    )


def test_lstm_analytic_matches_unrolled_scan_cost_analysis():
    T, B, H = 4, 16, 128
    cfg = _lstm_cfg(H)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (T, B, 4 * H))
    w = jax.random.normal(ks[1], (H, 4 * H)) * 0.05
    bias = jax.random.normal(ks[2], (7 * H,)) * 0.1
    mask = jnp.ones((T, B))

    def loss(x, w, bias):
        def cell(carry, x_t):
            h, c = carry
            h2, c2 = lstm_cell_step(cfg, x_t, h, c, w, bias)
            return (h2, c2), h2

        init = (jnp.zeros((B, H)), jnp.zeros((B, H)))
        _, ys = _scan_time(cell, x, mask, init, False, unroll=T)
        return jnp.sum(ys)

    measured = _flops_of(jax.value_and_grad(loss, argnums=(0, 1, 2)), x, w, bias)
    analytic = kf.lstm_fwd_flops(T, B, H) + kf.lstm_bwd_flops(T, B, H)
    # the scan path's HLO carries extra bookkeeping the kernel doesn't
    # (mask tree_map merges in the grad, bias adds, sum-reduction), and
    # the kernel's elementwise coefficients are approximate — but the
    # matmul terms dominate and must pin the two counts together
    assert 0.75 < analytic / measured < 1.25, (analytic, measured)


def test_gru_analytic_matches_unrolled_scan_cost_analysis():
    T, B, H = 4, 16, 128
    cfg = _gru_cfg(H)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (T, B, 3 * H))
    w = jax.random.normal(ks[1], (H, 3 * H)) * 0.05
    bias = jax.random.normal(ks[2], (3 * H,)) * 0.1
    mask = jnp.ones((T, B))

    def loss(x, w, bias):
        def cell(h, x_t):
            h2 = gru_cell_step(cfg, x_t, h, w, bias)
            return h2, h2

        _, ys = _scan_time(cell, x, mask, jnp.zeros((B, H)), False, unroll=T)
        return jnp.sum(ys)

    measured = _flops_of(jax.value_and_grad(loss, argnums=(0, 1, 2)), x, w, bias)
    analytic = kf.gru_fwd_flops(T, B, H) + kf.gru_bwd_flops(T, B, H)
    assert 0.75 < analytic / measured < 1.25, (analytic, measured)


def test_capture_collects_fwd_and_bwd_records_at_lower_time():
    """Lowering a value_and_grad jit over the fused LSTM must record
    exactly one fwd + one bwd analytic count; outside capture()
    recording is a no-op."""
    from paddle_tpu.ops import pallas_lstm as pk

    T, B, H = 3, 8, 128
    cfg = _lstm_cfg(H)
    x = jax.random.normal(jax.random.PRNGKey(2), (T, B, 4 * H))
    w = jax.random.normal(jax.random.PRNGKey(3), (H, 4 * H)) * 0.05
    mask = jnp.ones((T, B))

    def loss(x, w):
        ys = pk.lstm_layer_forward(cfg, x, mask, w, None, interpret=True)
        return jnp.sum(ys)

    with kf.capture() as log:
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(x, w)
    assert sorted(log) == sorted(
        [kf.lstm_fwd_flops(T, B, H), kf.lstm_bwd_flops(T, B, H)]
    ), log
    # forward-only trace records only the primal's fwd count
    with kf.capture() as log2:
        jax.jit(loss).lower(x, w)
    assert log2 == [kf.lstm_fwd_flops(T, B, H)], log2
    # no capture active: record() must be a no-op (no stale global list)
    kf.record(123.0)
    with kf.capture() as log3:
        pass
    assert log3 == []


def test_capture_gru_records():
    from paddle_tpu.ops import pallas_gru as pg

    T, B, H = 3, 8, 128
    cfg = _gru_cfg(H)
    x = jax.random.normal(jax.random.PRNGKey(4), (T, B, 3 * H))
    w = jax.random.normal(jax.random.PRNGKey(5), (H, 3 * H)) * 0.05
    mask = jnp.ones((T, B))

    def loss(x, w):
        ys = pg.gru_layer_forward(cfg, x, mask, w, None, interpret=True)
        return jnp.sum(ys)

    with kf.capture() as log:
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(x, w)
    assert sorted(log) == sorted(
        [kf.gru_fwd_flops(T, B, H), kf.gru_bwd_flops(T, B, H)]
    ), log


def test_capture_is_reentrant():
    with kf.capture() as outer:
        kf.record(1.0)
        with kf.capture() as inner:
            kf.record(2.0)
        kf.record(3.0)
    assert outer == [1.0, 3.0] and inner == [2.0]


# ---------------------------------------------------- jaxpr matmul counter


def test_jaxpr_flops_matches_cost_analysis_on_scan_free_graph():
    """On a scan-free matmul graph the jaxpr counter and XLA's cost
    analysis must agree (both count 2·M·N·K per dot; the counter skips
    elementwise, which is negligible here)."""
    a = jnp.zeros((64, 128))
    b = jnp.zeros((128, 256))
    c = jnp.zeros((256, 32))

    def f(a, b, c):
        return jnp.sum((a @ b) @ c)

    measured = _flops_of(jax.value_and_grad(f, argnums=(0, 1, 2)), a, b, c)
    analytic = kf.train_step_flops(jax.value_and_grad(f, argnums=(0, 1, 2)), a, b, c)
    assert 0.9 < analytic / measured < 1.1, (analytic, measured)


def test_jaxpr_flops_counts_conv():
    x = jnp.zeros((4, 16, 16, 8))   # NHWC
    k = jnp.zeros((3, 3, 8, 32))    # HWIO

    def f(x, k):
        return jnp.sum(jax.lax.conv_general_dilated(
            x, k, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")))

    analytic = kf.train_step_flops(f, x, k)
    # out [4,16,16,32]; 2 * out_elems * (3*3*8)
    expected = 2.0 * 4 * 16 * 16 * 32 * (3 * 3 * 8)
    assert analytic == expected, (analytic, expected)


def test_jaxpr_flops_scales_with_scan_length_where_cost_analysis_does_not():
    """The whole point: HloCostAnalysis counts a scan body once regardless
    of trip count; the jaxpr counter multiplies by `length`."""
    w = jnp.zeros((128, 128))

    def f(x, w):
        def body(h, xt):
            h2 = jnp.tanh(xt + h @ w)
            return h2, h2

        _, ys = jax.lax.scan(body, jnp.zeros((16, 128)), x)
        return jnp.sum(ys)

    f1 = kf.train_step_flops(f, jnp.zeros((1, 16, 128)), w)
    f8 = kf.train_step_flops(f, jnp.zeros((8, 16, 128)), w)
    assert abs(f8 / f1 - 8.0) < 1e-6, (f1, f8)
    c1 = _flops_of(f, jnp.zeros((1, 16, 128)), w)
    c8 = _flops_of(f, jnp.zeros((8, 16, 128)), w)
    assert c8 / c1 < 2.0  # cost analysis: body counted once (the bug)


def test_jaxpr_flops_counts_pallas_grid():
    """pallas_call bodies are counted per grid step, so the counter's
    total for the fused LSTM matches the analytic formulas' matmul term."""
    from paddle_tpu.ops import pallas_lstm as pk

    T, B, H = 3, 8, 128
    cfg = _lstm_cfg(H)
    x = jnp.zeros((T, B, 4 * H))
    w = jnp.zeros((H, 4 * H))
    mask = jnp.ones((T, B))

    def loss(x, w):
        return jnp.sum(pk.lstm_layer_forward(cfg, x, mask, w, None, interpret=True))

    analytic = kf.train_step_flops(jax.value_and_grad(loss, argnums=(0, 1)), x, w)
    matmul_terms = T * (8.0 * B * H * H + 16.0 * B * H * H)
    # counter sees only dots (inside the kernel + none outside here)
    assert abs(analytic - matmul_terms) / matmul_terms < 1e-6, (
        analytic, matmul_terms)
