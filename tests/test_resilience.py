"""Fault-tolerance layer (doc/resilience.md): atomic checksummed
checkpoints with fallback restore, the data-pipeline watchdog, the
bad-sample budget, the shared RetryPolicy, and the deterministic
fault-injection harness that drives the chaos tests.

The chaos tests are fast and deterministic (seeded injection at named
sites), so they ride along with tier-1 under the ``chaos`` marker.
"""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.data.feeder import DataProvider
from paddle_tpu.data.provider import dense_vector, integer_value, provider
from paddle_tpu.resilience import (
    BadSampleError,
    CheckpointCorruptError,
    DataStallError,
    faultinject,
)
from paddle_tpu.resilience import manifest as mf
from paddle_tpu.trainer import checkpoint as ckpt
from paddle_tpu.utils.retry import RetryPolicy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clear_faults():
    """Fault plans are process-global; never leak one across tests."""
    yield
    faultinject.configure("")


def _params(offset=0.0):
    return {
        "w": jnp.arange(12.0).reshape(3, 4) + offset,
        "b": jnp.ones((4,)) + offset,
    }


def _truncate(path, keep_ratio=0.5):
    data = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(data[: int(len(data) * keep_ratio)])


# --------------------------------------------------------------- manifest


def test_manifest_roundtrip_and_detection(tmp_path):
    d = str(tmp_path)
    (tmp_path / "a.bin").write_bytes(b"hello world" * 100)
    (tmp_path / "b.json").write_text('{"k": 1}')
    mf.write_manifest(d)
    assert mf.verify_dir(d) == []
    # size mismatch (truncation)
    _truncate(os.path.join(d, "a.bin"))
    problems = mf.verify_dir(d)
    assert len(problems) == 1 and "size" in problems[0], problems
    # crc mismatch (same-size corruption)
    mf.write_manifest(d)
    data = bytearray((tmp_path / "a.bin").read_bytes())
    data[10] ^= 0xFF
    (tmp_path / "a.bin").write_bytes(bytes(data))
    problems = mf.verify_dir(d)
    assert len(problems) == 1 and "crc32" in problems[0], problems
    # missing file
    os.remove(os.path.join(d, "b.json"))
    assert any("missing" in p for p in mf.verify_dir(d))
    # a dir with no manifest verifies clean (pre-resilience checkpoints)
    assert mf.verify_dir(str(tmp_path / "nodir_yet")) == [] or True
    other = tmp_path / "legacy"
    other.mkdir()
    (other / "params.npz").write_bytes(b"x")
    assert mf.verify_dir(str(other)) == []


def test_partial_manifest_merge(tmp_path):
    d = str(tmp_path)
    (tmp_path / "t.shard00000.npz").write_bytes(b"p0" * 50)
    (tmp_path / "t.shard00001.npz").write_bytes(b"p1" * 70)
    (tmp_path / "meta.json").write_text("{}")
    mf.write_partial_manifest(d, 0, ["t.shard00000.npz"])
    mf.write_partial_manifest(d, 1, ["t.shard00001.npz"])
    merged = mf.merge_partial_manifests(d)
    # partials merged + process-0-local leftovers (meta.json) digested
    assert set(merged["files"]) == {
        "t.shard00000.npz", "t.shard00001.npz", "meta.json",
    }
    assert not [n for n in os.listdir(d) if n.startswith("MANIFEST.partial")]
    assert mf.verify_dir(d) == []


# ------------------------------------------------------------ RetryPolicy


def test_retry_policy_retries_then_succeeds():
    sleeps = []
    policy = RetryPolicy(
        max_attempts=4, base_delay=0.1, jitter=0.0, sleep=sleeps.append
    )
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert policy.call(flaky) == "ok"
    assert len(calls) == 3
    assert sleeps == [0.1, 0.2]  # exponential, no jitter


def test_retry_policy_exhausts_attempts():
    policy = RetryPolicy(max_attempts=3, base_delay=0.0, jitter=0.0, sleep=lambda s: None)
    calls = []

    def always_fails():
        calls.append(1)
        raise OSError("still broken")

    with pytest.raises(OSError, match="still broken"):
        policy.call(always_fails)
    assert len(calls) == 3


def test_retry_policy_nonretryable_passes_through():
    policy = RetryPolicy(max_attempts=5, sleep=lambda s: None)
    with pytest.raises(ValueError):
        policy.call(lambda: (_ for _ in ()).throw(ValueError("logic bug")))


def test_retry_policy_jitter_and_cap():
    import random

    policy = RetryPolicy(base_delay=1.0, max_delay=4.0, multiplier=2.0, jitter=0.25)
    rng = random.Random(0)
    for attempt, cap in [(1, 1.0), (2, 2.0), (3, 4.0), (10, 4.0)]:
        for _ in range(50):
            d = policy.delay_for(attempt, rng)
            assert cap * 0.75 <= d <= cap * 1.25, (attempt, d)


def test_retry_policy_deadline():
    policy = RetryPolicy(max_attempts=1000, base_delay=0.02, jitter=0.0, deadline=0.08)
    calls = []

    def always_fails():
        calls.append(1)
        raise OSError("nope")

    with pytest.raises(OSError):
        policy.call(always_fails)
    assert 1 < len(calls) < 100  # deadline stopped it long before max_attempts


# ------------------------------------------------------------ faultinject


def test_fault_spec_parsing_and_triggers():
    inj = faultinject.FaultInjector("a.b=raise@2;c.d=raise@3+")
    # nth: fires on exactly the 2nd hit
    inj.fire("a.b")
    with pytest.raises(faultinject.FaultInjected):
        inj.fire("a.b")
    inj.fire("a.b")  # 3rd hit: silent again
    # from: every hit >= 3
    inj.fire("c.d")
    inj.fire("c.d")
    for _ in range(3):
        with pytest.raises(faultinject.FaultInjected):
            inj.fire("c.d")
    # unknown sites are free
    inj.fire("nobody.home")
    assert inj.hits("a.b") == 3


def test_fault_spec_rejects_garbage():
    with pytest.raises(ValueError):
        faultinject.FaultInjector("not a spec")
    with pytest.raises(ValueError):
        faultinject.FaultInjector("site=raise@p1.5")


def test_fault_probability_is_seed_deterministic():
    def pattern(seed):
        inj = faultinject.FaultInjector("x=raise@p0.5", seed)
        out = []
        for _ in range(40):
            try:
                inj.fire("x")
                out.append(0)
            except faultinject.FaultInjected:
                out.append(1)
        return out

    p7 = pattern(7)
    assert p7 == pattern(7)  # pure function of (seed, site)
    assert 0 < sum(p7) < 40  # actually probabilistic
    assert p7 != pattern(8)


def test_fault_oserror_is_retryable():
    faultinject.configure("x.y=oserror@1")
    policy = RetryPolicy(max_attempts=3, base_delay=0.0, jitter=0.0, sleep=lambda s: None)
    calls = []

    def op():
        calls.append(1)
        faultinject.fault_point("x.y")
        return "ok"

    assert policy.call(op) == "ok"
    assert len(calls) == 2  # one injected EIO, one clean retry


# ---------------------------------------------- atomic checkpoint + chaos


@pytest.mark.chaos
def test_midwrite_fault_preserves_previous_checkpoint(tmp_path):
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 0, _params())
    faultinject.configure("checkpoint.write=raise@1")
    with pytest.raises(faultinject.FaultInjected):
        ckpt.save_checkpoint(d, 1, _params(offset=100.0))
    # the aborted save never touched the published namespace
    assert not os.path.exists(os.path.join(d, "pass-00001"))
    assert ckpt.verify_checkpoint(os.path.join(d, "pass-00000")) == []
    params, _, meta = ckpt.load_checkpoint(os.path.join(d, "pass-00000"))
    np.testing.assert_array_equal(np.asarray(params["w"]), np.asarray(_params()["w"]))
    # a later clean save of the same pass succeeds and sweeps the stale tmp
    faultinject.configure("")
    ckpt.save_checkpoint(d, 1, _params(offset=100.0))
    names = sorted(os.listdir(d))
    assert names == ["pass-00000", "pass-00001"], names


@pytest.mark.chaos
def test_torn_rename_leaves_both_old_checkpoint_and_tmp(tmp_path):
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 0, _params())
    faultinject.configure("checkpoint.rename=raise@1")
    with pytest.raises(faultinject.FaultInjected):
        ckpt.save_checkpoint(d, 1, _params(offset=1.0))
    # torn exactly between write and rename: tmp fully written, final absent
    assert os.path.exists(os.path.join(d, "pass-00001.tmp", "MANIFEST.json"))
    assert not os.path.exists(os.path.join(d, "pass-00001"))
    assert ckpt.find_restorable_checkpoint(d) == os.path.join(d, "pass-00000")


CRASH_CHILD = """
import sys
sys.path.insert(0, {repo!r})
from paddle_tpu.utils.backend_guard import ensure_cpu_mesh
ensure_cpu_mesh(1)
import jax.numpy as jnp
from paddle_tpu.resilience import faultinject
from paddle_tpu.trainer import checkpoint as ckpt

d = sys.argv[1]
params = {{"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones((4,))}}
ckpt.save_checkpoint(d, 0, params)
faultinject.configure("checkpoint.rename=exit@1")  # os._exit: a real kill
ckpt.save_checkpoint(d, 1, {{"w": params["w"] + 100.0, "b": params["b"]}})
print("UNREACHABLE")
"""


@pytest.mark.chaos
def test_hard_kill_between_write_and_rename_subprocess(tmp_path):
    """The acceptance scenario end-to-end, with a REAL process death
    (os._exit — no finally blocks, no atexit): the previous pass dir
    stays intact and restorable, and the next save heals the litter."""
    d = str(tmp_path / "out")
    r = subprocess.run(
        [sys.executable, "-c", CRASH_CHILD.format(repo=REPO), d],
        capture_output=True, text=True, timeout=240,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode == 3, (r.returncode, r.stderr[-2000:])
    assert "UNREACHABLE" not in r.stdout
    # killed between write and rename: tmp complete, final never appeared
    assert os.path.isdir(os.path.join(d, "pass-00001.tmp"))
    assert not os.path.exists(os.path.join(d, "pass-00001"))
    # the previous checkpoint is intact, verified, and restorable
    prev = os.path.join(d, "pass-00000")
    assert ckpt.verify_checkpoint(prev) == []
    assert ckpt.find_restorable_checkpoint(d) == prev
    params, _, meta = ckpt.load_checkpoint(prev)
    np.testing.assert_array_equal(
        np.asarray(params["w"]), np.arange(12.0).reshape(3, 4)
    )
    assert meta["pass_id"] == 0
    # recovery save sweeps the stale tmp
    ckpt.save_checkpoint(d, 1, _params(offset=100.0))
    assert sorted(os.listdir(d)) == ["pass-00000", "pass-00001"]


@pytest.mark.chaos
def test_corrupt_latest_quarantined_and_falls_back(tmp_path):
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 0, _params())
    ckpt.save_checkpoint(d, 1, _params(offset=50.0))
    _truncate(os.path.join(d, "pass-00001", "params.npz"))
    params, _, meta = ckpt.load_checkpoint(os.path.join(d, "pass-00001"))
    # fell back to the prior pass and quarantined the bad dir
    assert meta["pass_id"] == 0
    np.testing.assert_array_equal(np.asarray(params["w"]), np.asarray(_params()["w"]))
    names = sorted(os.listdir(d))
    assert names == ["pass-00000", "pass-00001.corrupt"], names


@pytest.mark.chaos
def test_legacy_checkpoint_without_manifest_still_falls_back(tmp_path):
    """Pre-manifest checkpoints can't be caught by verification — a
    truncated legacy npz surfaces as BadZipFile at deserialization time
    and must still enter the quarantine+fallback chain."""
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 0, _params())
    ckpt.save_checkpoint(d, 1, _params(offset=5.0))
    os.remove(os.path.join(d, "pass-00001", "MANIFEST.json"))  # legacy dir
    _truncate(os.path.join(d, "pass-00001", "params.npz"))
    params, _, meta = ckpt.load_checkpoint(os.path.join(d, "pass-00001"))
    assert meta["pass_id"] == 0
    assert os.path.isdir(os.path.join(d, "pass-00001.corrupt"))


@pytest.mark.chaos
def test_protected_old_dir_survives_rotation_sweep(tmp_path):
    """Torn-commit recovery: the pass-N.old a run restored from is the
    only known-good state — rotation must not sweep it until protection
    is lifted."""
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 0, _params())
    os.rename(os.path.join(d, "pass-00000"), os.path.join(d, "pass-00000.old"))
    ckpt.save_checkpoint(d, 1, _params(offset=1.0), protect_pass=0)
    assert sorted(os.listdir(d)) == ["pass-00000.old", "pass-00001"]
    # protection lifted (a newer save proved durable): litter is swept
    ckpt.save_checkpoint(d, 2, _params(offset=2.0))
    assert sorted(os.listdir(d)) == ["pass-00001", "pass-00002"]


def test_nonexistent_path_raises_filenotfound(tmp_path):
    """A never-existed path (wrong --start_pass, typo'd init_model_path)
    is a caller error: fail fast, never silently substitute an older
    checkpoint, never mutate the save_dir."""
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 3, _params())
    with pytest.raises(FileNotFoundError):
        ckpt.load_checkpoint(os.path.join(d, "pass-00009"))
    assert sorted(os.listdir(d)) == ["pass-00003"]


def test_fallback_candidates_verified_even_when_first_preverified(tmp_path):
    """verify=False covers only the first (caller-verified) candidate —
    anything the fallback chain reaches is unvetted and must pass
    verification before being deserialized."""
    d = str(tmp_path)
    for p in range(3):
        ckpt.save_checkpoint(d, p, _params(offset=float(p)))
    # pass-2: params tree gone (load fails after the skipped verify);
    # pass-1: truncated (only verification catches it); pass-0: clean
    os.remove(os.path.join(d, "pass-00002", "params.npz"))
    _truncate(os.path.join(d, "pass-00001", "params.npz"))
    params, _, meta = ckpt.load_checkpoint(
        os.path.join(d, "pass-00002"), verify=False
    )
    assert meta["pass_id"] == 0
    np.testing.assert_array_equal(np.asarray(params["w"]), np.asarray(_params()["w"]))


def test_no_fallback_candidate_raises(tmp_path):
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 0, _params())
    _truncate(os.path.join(d, "pass-00000", "params.npz"))
    with pytest.raises(CheckpointCorruptError) as ei:
        ckpt.load_checkpoint(os.path.join(d, "pass-00000"))
    assert "pass-00000" in str(ei.value)
    assert os.path.isdir(os.path.join(d, "pass-00000.corrupt"))


@pytest.mark.chaos
def test_torn_commit_old_dir_is_last_resort_restorable(tmp_path):
    """Crash exactly between _commit's two renames (re-save of the same
    pass): pass-N.old holds the previous durable checkpoint and the
    restore scan recovers it when nothing else exists."""
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 0, _params())
    os.rename(os.path.join(d, "pass-00000"), os.path.join(d, "pass-00000.old"))
    got = ckpt.find_restorable_checkpoint(d)
    assert got == os.path.join(d, "pass-00000.old")
    params, _, _ = ckpt.load_checkpoint(got)
    np.testing.assert_array_equal(np.asarray(params["w"]), np.asarray(_params()["w"]))
    # once a newer save completes, the leftover is swept
    ckpt.save_checkpoint(d, 1, _params(offset=1.0))
    assert sorted(os.listdir(d)) == ["pass-00001"]


def test_resave_same_pass_is_atomic(tmp_path):
    """Periodic save then pass-end save hit the same pass id: the second
    replaces the first without a window where neither exists."""
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 0, _params())
    ckpt.save_checkpoint(d, 0, _params(offset=9.0))
    assert sorted(os.listdir(d)) == ["pass-00000"]
    params, _, _ = ckpt.load_checkpoint(os.path.join(d, "pass-00000"))
    np.testing.assert_array_equal(
        np.asarray(params["w"]), np.asarray(_params(offset=9.0)["w"])
    )


def test_write_fault_retried_by_io_policy(tmp_path, monkeypatch):
    from paddle_tpu.utils.flags import FLAGS

    monkeypatch.setattr(FLAGS, "io_retry_base_delay", 0.01)
    faultinject.configure("checkpoint.write=oserror@1")
    path = ckpt.save_checkpoint(str(tmp_path), 0, _params())
    assert ckpt.verify_checkpoint(path) == []
    assert faultinject.current().hits("checkpoint.write") >= 2  # retried


def test_rotation_budget_and_protection(tmp_path):
    d = str(tmp_path)
    for p in range(3):
        ckpt.save_checkpoint(d, p, _params(), keep=2)
    assert sorted(os.listdir(d)) == ["pass-00001", "pass-00002"]
    # tmp/corrupt dirs never count toward the keep budget; stale tmp is
    # swept, quarantine is kept
    os.makedirs(os.path.join(d, "pass-00007.tmp"))
    os.makedirs(os.path.join(d, "pass-00006.corrupt"))
    ckpt.save_checkpoint(d, 3, _params(), keep=2)
    names = sorted(os.listdir(d))
    assert names == ["pass-00002", "pass-00003", "pass-00006.corrupt"], names
    # the restored-from pass is never rolled away
    for p in range(4, 7):
        ckpt.save_checkpoint(d, p, _params(), keep=2, protect_pass=2)
    names = sorted(n for n in os.listdir(d) if ckpt._is_pass_dir_name(n))
    assert names == ["pass-00002", "pass-00005", "pass-00006"], names


def test_latest_pass_ignores_tmp_and_corrupt(tmp_path):
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 1, _params())
    os.makedirs(os.path.join(d, "pass-00009.tmp"))
    os.makedirs(os.path.join(d, "pass-00008.corrupt"))
    assert ckpt.latest_pass(d) == 1


# ------------------------------------------------------- data pipeline


def _dense_provider(n=64, bad_every=0):
    @provider(input_types=[dense_vector(4), integer_value(2)])
    def process(settings, file_name):
        for i in range(n):
            if bad_every and i % bad_every == 3:
                yield ["not", "a", "float", "!"], 0  # malformed dense row
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


@pytest.mark.chaos
def test_stalled_provider_raises_datastallerror_within_timeout():
    import time

    faultinject.configure("provider.stall=sleep:20@2")
    dp = _mk_dp(_dense_provider(), stall_timeout=1.0)
    t0 = time.monotonic()
    with pytest.raises(DataStallError) as ei:
        list(dp.batches())
    elapsed = time.monotonic() - t0
    assert elapsed < 10, elapsed  # raised within the timeout, not after 20s
    # the error is diagnosable: liveness + stall age + the knob to turn
    msg = str(ei.value)
    assert "data_stall_timeout" in msg and "alive" in msg, msg


@pytest.mark.chaos
def test_flaky_provider_succeeds_under_retry_exactly_once():
    faultinject.configure("provider.yield=oserror@5")
    dp = _mk_dp(_dense_provider(n=40), async_prefetch=False)
    batches = list(dp.batches())
    xs = sorted(
        float(v)
        for b in batches
        for v in np.asarray(b["x"].value)[:, 0]
    )
    # every sample delivered exactly once despite the mid-file EIO
    assert xs == [float(i) for i in range(40)], xs[:10]
    assert faultinject.current().hits("provider.yield") > 40  # retried


@pytest.mark.chaos
def test_retry_budget_resets_after_progress():
    """Two isolated transient errors far apart in one file must not add
    up to 'retries exhausted' — successful progress earns a fresh
    budget."""
    faultinject.configure("provider.yield=oserror@3;provider.yield=oserror@30")
    dp = _mk_dp(
        _dense_provider(n=40), async_prefetch=False,
        retry=RetryPolicy(max_attempts=2, base_delay=0.0, jitter=0.0,
                          sleep=lambda s: None),
    )
    xs = sorted(
        float(v) for b in dp.batches() for v in np.asarray(b["x"].value)[:, 0]
    )
    assert xs == [float(i) for i in range(40)]  # both hiccups survived


@pytest.mark.chaos
def test_flaky_provider_fails_when_retries_exhausted():
    faultinject.configure("provider.yield=oserror@5+")  # every hit >= 5
    dp = _mk_dp(_dense_provider(n=40), async_prefetch=False)
    with pytest.raises(OSError):
        list(dp.batches())


def test_bad_sample_budget_skips_then_fails():
    # 40 samples, malformed at i = 3, 13, 23, 33 → 4 bad
    dp = _mk_dp(_dense_provider(n=40, bad_every=10), max_bad_samples=5,
                async_prefetch=False)
    total = sum(len(np.asarray(b["y"].ids)) for b in dp.batches())
    assert total == 36  # the 4 bad samples were skipped, all others kept
    # budget exceeded → loud typed failure
    dp2 = _mk_dp(_dense_provider(n=40, bad_every=10), max_bad_samples=3,
                 async_prefetch=False)
    with pytest.raises(BadSampleError, match="max_bad_samples"):
        list(dp2.batches())


def test_bad_sample_budget_disabled_is_failfast():
    dp = _mk_dp(_dense_provider(n=20, bad_every=10), max_bad_samples=0,
                async_prefetch=False)
    with pytest.raises(Exception):
        list(dp.batches())


# ----------------------------------------------------- trainer wiring


def test_trainer_auto_restore_skips_corrupt_and_resumes(tmp_path):
    import textwrap

    from paddle_tpu.config import parse_config
    from paddle_tpu.trainer import Trainer
    from paddle_tpu.utils.flags import _Flags

    providers = os.path.join(REPO, "tests", "providers")
    sys.path.insert(0, providers)
    try:
        (tmp_path / "train.list").write_text("1\n")
        cfg_src = textwrap.dedent(f"""
        from paddle_tpu.trainer_config_helpers import *
        define_py_data_sources2(train_list={str(tmp_path / 'train.list')!r},
                                test_list=None,
                                module="synthetic_bow", obj="process")
        settings(batch_size=64, learning_rate=0.02,
                 learning_method=AdamOptimizer())
        data = data_layer(name="word", size=100)
        output = fc_layer(input=data, size=2, act=SoftmaxActivation(), name="output")
        label = data_layer(name="label", size=2)
        outputs(classification_cost(input=output, label=label))
        """)
        (tmp_path / "cfg.py").write_text(cfg_src)
        cfg = parse_config(str(tmp_path / "cfg.py"))
        save_dir = str(tmp_path / "out")
        t1 = Trainer(cfg, _Flags(save_dir=save_dir, log_period=0))
        t1.train(num_passes=2)
        assert ckpt.latest_pass(save_dir) == 1
        step_after = int(t1.opt_state.step)

        # corrupt the newest checkpoint; auto-restore must skip it,
        # resume from pass 0, and protect pass 0 from rotation
        _truncate(os.path.join(save_dir, "pass-00001", "params.npz"))
        t2 = Trainer(
            cfg, _Flags(save_dir=save_dir, init_model_path="auto", log_period=0)
        )
        assert t2._restored_pass == 0
        assert t2.start_pass == 1  # resumes after the restored pass
        assert 0 < int(t2.opt_state.step) < step_after

        # nothing restorable → fresh start, not a crash
        t3 = Trainer(
            cfg,
            _Flags(save_dir=str(tmp_path / "empty"), init_model_path="auto",
                   log_period=0),
        )
        assert t3._restored_pass is None and t3.start_pass == 0
    finally:
        sys.path.remove(providers)


# ------------------------------------------------------------- tooling


def test_check_checkpoint_cli(tmp_path, capsys):
    from paddle_tpu import cli

    d = str(tmp_path)
    ckpt.save_checkpoint(d, 0, _params())
    ckpt.save_checkpoint(d, 1, _params())
    assert cli.main(["check-checkpoint", d]) == 0
    out = capsys.readouterr().out
    assert out.count("OK") == 2 and "CORRUPT" not in out
    # single pass dir form
    assert cli.main(["check-checkpoint", os.path.join(d, "pass-00001")]) == 0
    # corruption detected offline
    _truncate(os.path.join(d, "pass-00001", "params.npz"))
    assert cli.main(["check-checkpoint", d]) == 1
    out = capsys.readouterr().out
    assert "CORRUPT" in out and "truncated" in out
    # usage errors
    assert cli.main(["check-checkpoint"]) == 2
    assert cli.main(["check-checkpoint", str(tmp_path / "nope")]) == 2
