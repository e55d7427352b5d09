import os

import pytest

from perfbench import trace_reduce as tr
from perfbench.tests.helpers import HERE

TINY = os.path.join(HERE, "data", "tiny.xplane.pb")


def _trace():
    ops = [("%fusion.1 = f32[] fusion()", 1.0, 1.0),
           ("%while.2", 3.0, 4.0),                 # encloses the next two
           ("%dot.3", 3.5, 1.0), ("%all-reduce.4", 5.0, 1.2),
           ("%gru_kernel.5 = bf16[8] custom-call(bf16[8] %x), custom_call_target=\"tpu_custom_call\"", 8.0, 1.0)]
    modules = [("jit_train_step(123)", 1.0, 1.0), ("jit_train_step(123)", 3.0, 4.0),
               ("jit_other", 8.0, 1.0)]
    dev = tr.Device("/device:TPU:0", ops, modules)
    spans = [("perfbench.window", 0.0, 10.0), ("perfbench.train_call", 0.5, 8.9),
             ("perfbench.provider_batch", 2.0, 0.9)]
    return tr.Trace([dev], spans, (0.0, 10.0))


def test_busy_union_and_idle_share():
    t = _trace()
    assert t.busy(t.devices[0]) == [[1.0, 2.0], [3.0, 7.0], [8.0, 9.0]]
    assert t.busy_s() == pytest.approx(6.0)
    assert t.idle_pct() == pytest.approx(40.0)
    assert t.gaps(t.devices[0]) == [(0.0, 1.0), (2.0, 3.0), (7.0, 8.0), (9.0, 10.0)]


def test_gap_attribution_prefers_the_shortest_span():
    got = dict(_trace().gaps_by_host_span())
    assert got["perfbench.provider_batch"] == pytest.approx(1.0)    # 2..3
    assert got["perfbench.train_call"] == pytest.approx(2.0)        # 0.5..1 is cut: 0..1 mid .5 ; 7..8
    assert got["no_perfbench_span"] == pytest.approx(1.0)           # 9..10, mid 9.5


def test_self_times_kernel_matching_and_modules():
    t = _trace()
    top = dict(t.top_ops())
    assert top["while"] == pytest.approx(4.0 - 1.0 - 1.2)
    assert top["dot"] == pytest.approx(1.0)
    assert t.op_seconds(r" custom-call\(") == (1.0, 1)
    assert t.module_count("train_step") == 2
    assert t.module_gaps("train_step") == [pytest.approx(1.0)]
    b = t.breakdown()
    assert len(b["device_ops"]) <= 10 and b["device_ops"][0][0] == "while"


def test_interval_arithmetic():
    assert tr.union([(3, 4), (1, 2), (1.5, 3.5)]) == [[1, 4]]
    assert tr.subtract([(0, 10)], [[1, 2], [2, 3], [9, 12]]) == [(0, 1), (3, 9)]
    assert tr.clip([(0, 5), (6, 9)], 4, 7) == [(4, 5), (6, 7)]
    assert tr.op_kind("%fusion.26.remat = bf16[8] fusion(%x.1)") == "fusion"


@pytest.mark.skipif(not os.path.exists(TINY), reason="no recorded trace")
def test_recorded_trace_from_the_chip():
    t = tr.from_xplane(TINY, chips=1)
    assert len(t.devices) == 1 and t.devices[0].ops
    assert any(s[0] == tr.WINDOW_SPAN for s in t.host_spans)
    assert 0 < t.busy_s() < t.window_s
    # three sleeps of 2 ms inside the window: the device idles through each
    gaps = dict(t.gaps_by_host_span())
    assert gaps.get("perfbench.sleep", 0) >= 3 * 0.0015
    # the device's clock runs about a millisecond ahead of the host's in
    # this file, so the first program starts before `perfbench.window` does
    assert t.module_count("tiny_matmul") in (2, 3) and t.module_count("tiny_loop") == 3
    assert len(t.module_gaps("tiny_loop")) == 2
    assert dict(t.top_ops())["sine_multiply_fusion"] > dict(t.top_ops())["while"]
    assert 0 < t.idle_pct() < 100
