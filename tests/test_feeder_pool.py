"""Parallel batch packing (doc/performance.md "Zero-stall host"):
the --data_packer_threads pool must preserve batch order and shuffle
semantics exactly, keep the stall watchdog / fault-site / bad-sample
budget contracts of the single-thread prefetch path, respect the
--prefetch_depth bound, and publish the pack_threads_busy telemetry."""

import threading
import time

import numpy as np
import pytest

from paddle_tpu.data.feeder import DataProvider, MultiDataProvider
from paddle_tpu.data.provider import (
    dense_vector,
    dense_vector_sequence,
    integer_value,
    provider,
)
from paddle_tpu.observability import metrics as obs
from paddle_tpu.resilience import BadSampleError, DataStallError, faultinject
from paddle_tpu.utils.retry import RetryPolicy

@pytest.fixture(autouse=True)
def _fresh():
    obs.registry().reset()
    yield
    faultinject.configure("")


def _dense_provider(n=64, bad_every=0, shuffle=None):
    @provider(input_types=[dense_vector(4), integer_value(2)],
              should_shuffle=shuffle)
    def process(settings, file_name):
        for i in range(n):
            if bad_every and i % bad_every == 3:
                yield ["not", "a", "float", "!"], 0
            else:
                yield [float(i)] * 4, i % 2

    return process


def _mk_dp(p, **kw):
    kw.setdefault("stall_timeout", 0)
    kw.setdefault("max_bad_samples", 0)
    kw.setdefault(
        "retry",
        RetryPolicy(max_attempts=3, base_delay=0.01, max_delay=0.02, jitter=0.0),
    )
    return DataProvider(p, ["f1"], 8, ["x", "y"], **kw)


def _values(batches):
    return [
        [float(v) for v in np.asarray(b["x"].value)[:, 0]] for b in batches
    ]


def test_pool_matches_sync_order_and_content_exactly():
    """Same seed, same provider: the 2-thread pool must deliver the
    SAME batches in the SAME order as the synchronous path — the
    sequential shuffle half runs on one dispatcher regardless of the
    packer count, and the queue is order-preserving."""
    ref = _values(_mk_dp(_dense_provider(n=64), async_prefetch=False,
                         seed=7).batches())
    pooled = _values(_mk_dp(_dense_provider(n=64), packer_threads=2,
                            seed=7).batches())
    assert pooled == ref
    four = _values(_mk_dp(_dense_provider(n=64), packer_threads=4,
                          seed=7, prefetch_depth=2).batches())
    assert four == ref


def test_single_thread_path_also_matches_sync():
    ref = _values(_mk_dp(_dense_provider(n=40), async_prefetch=False,
                         seed=3).batches())
    one = _values(_mk_dp(_dense_provider(n=40), packer_threads=1,
                         seed=3).batches())
    assert one == ref


@pytest.mark.chaos
def test_pool_stall_watchdog_fires():
    faultinject.configure("provider.stall=sleep:20@2")
    dp = _mk_dp(_dense_provider(), stall_timeout=1.0, packer_threads=2)
    t0 = time.monotonic()
    with pytest.raises(DataStallError) as ei:
        list(dp.batches())
    assert time.monotonic() - t0 < 10
    msg = str(ei.value)
    assert "data_stall_timeout" in msg and "alive" in msg, msg


def test_pool_propagates_provider_error():
    @provider(input_types=[dense_vector(4), integer_value(2)])
    def boom(settings, file_name):
        for i in range(20):
            yield [float(i)] * 4, i % 2
        raise ValueError("provider exploded")

    dp = _mk_dp(boom, packer_threads=2,
                retry=RetryPolicy(max_attempts=1, base_delay=0.0, jitter=0.0))
    with pytest.raises(ValueError, match="provider exploded"):
        list(dp.batches())


def test_pool_bad_sample_budget_semantics():
    dp = _mk_dp(_dense_provider(n=40, bad_every=10), max_bad_samples=5,
                packer_threads=2)
    total = sum(len(np.asarray(b["y"].ids)) for b in dp.batches())
    assert total == 36  # 4 malformed samples skipped, all others kept
    dp2 = _mk_dp(_dense_provider(n=40, bad_every=10), max_bad_samples=3,
                 packer_threads=2)
    with pytest.raises(BadSampleError, match="max_bad_samples"):
        list(dp2.batches())


def test_prefetch_depth_bounds_runahead():
    """With the consumer paused, the dispatcher may run at most
    prefetch_depth queued + packer_threads executing + 1 blocked-in-put
    batches ahead — the bounded queue is the backpressure."""
    produced = []

    @provider(input_types=[dense_vector(4), integer_value(2)],
              should_shuffle=False, pool_size=8)
    def counted(settings, file_name):
        for i in range(400):
            produced.append(i)
            yield [float(i)] * 4, i % 2

    dp = _mk_dp(counted, packer_threads=2, prefetch_depth=2)
    it = dp.batches()
    next(it)
    time.sleep(0.5)  # dispatcher free-runs against the bound
    # batches of 8 from a pool of 8: consumed 1 batch; bound =
    # depth(2) + threads(2) + 1 in-put + 1 delivered (+ pool slack of
    # one 8-sample refill in flight)
    assert len(produced) <= 8 * 8, len(produced)
    it.close()


def test_pool_busy_histogram_published():
    list(_mk_dp(_dense_provider(n=64), packer_threads=2).batches())
    snap = obs.registry().snapshot().get("data.pack_threads_busy")
    assert snap and snap["count"] > 0 and 1.0 <= snap["max"] <= 2.0, snap


def test_multi_provider_rides_the_pool():
    from paddle_tpu.proto import DataConfig

    subs = [_mk_dp(_dense_provider(n=32), async_prefetch=False, seed=i)
            for i in range(2)]
    mp = MultiDataProvider(subs, [1, 1], async_prefetch=True)
    total = sum(len(np.asarray(b["y"].ids)) for b in mp.batches())
    assert total == 64


def test_sort_by_length_unchanged_through_pool():
    @provider(input_types={"x": dense_vector_sequence(4),
                           "y": integer_value(2)},
              pool_size=32, should_shuffle=True)
    def seqs(settings, file_name):
        rng = np.random.RandomState(0)
        for i in range(64):
            t = int(rng.randint(1, 30))
            yield {"x": [[float(i)] * 4] * t, "y": i % 2}

    seqs.sort_by_length = True  # the @provider extension flag

    ref = _values_seq(_mk_dp_seq(seqs, async_prefetch=False, seed=5).batches())
    pooled = _values_seq(_mk_dp_seq(seqs, packer_threads=3, seed=5).batches())
    assert pooled == ref


def _mk_dp_seq(p, **kw):
    kw.setdefault("stall_timeout", 0)
    kw.setdefault("max_bad_samples", 0)
    return DataProvider(p, ["f1"], 8, ["x", "y"], **kw)


def _values_seq(batches):
    return [np.asarray(b["x"].seq_lengths).tolist() for b in batches]
