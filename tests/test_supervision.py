"""Run supervision (doc/resilience.md "Supervision & divergence
recovery"): the crash-loop-aware auto-restart supervisor behind `paddle
supervise`, the trainer's --nonfinite_policy divergence recovery
(skip/rollback), the unified NonFiniteLossError type, and the barrier
skew-summary guard the supervisor's crash report consumes.

The chaos tests are fast and deterministic (seeded injection at the new
``trainer.crash`` / ``trainer.nonfinite`` sites), so they ride along
with tier-1 under the ``chaos`` marker.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from paddle_tpu.resilience import NonFiniteLossError, faultinject
from paddle_tpu.resilience.supervisor import (
    CRASH_REPORT,
    EXIT_CRASH_LOOP,
    Supervisor,
    probe_restorable,
)
from paddle_tpu.trainer import checkpoint as ckpt
from paddle_tpu.utils.flags import _Flags

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROVIDERS = os.path.join(REPO, "tests", "providers")

SUBPROC_ENV = dict(
    os.environ,
    JAX_PLATFORMS="cpu",
    PYTHONPATH=f"{REPO}:{os.path.join(REPO, 'compat')}:{PROVIDERS}",
)


@pytest.fixture(autouse=True)
def _clear_faults():
    """Fault plans are process-global; never leak one across tests."""
    yield
    faultinject.configure("")


def _no_sleep(_s):
    pass


# ---------------------------------------------------------- supervisor


def _stub_supervisor(tmp_path, script, flags=None, **kw):
    flags = flags or _Flags(
        supervise_dir=str(tmp_path / "sup"),
        restart_budget=5,
        crash_loop_threshold=3,
    )
    return Supervisor(
        ["--config=unused.py"], flags,
        child_cmd=[sys.executable, "-c", script, str(tmp_path / "counter")],
        sleep=_no_sleep, **kw,
    )


def test_supervisor_restarts_with_backoff_until_success(tmp_path):
    # child fails twice, then succeeds — the supervisor must restart it
    # (bounded) and report overall success
    script = textwrap.dedent("""
        import os, sys
        c = sys.argv[1]
        n = int(open(c).read()) if os.path.exists(c) else 0
        open(c, "w").write(str(n + 1))
        print("attempt", n)
        sys.exit(0 if n >= 2 else 1)
    """)
    sup = _stub_supervisor(tmp_path, script)
    assert sup.run() == 0
    assert [a["exit_code"] for a in sup.attempts] == [1, 1, 0]
    # per-attempt child output was captured
    for a in sup.attempts:
        assert os.path.exists(a["log"])
    assert "attempt 0" in open(sup.attempts[0]["log"]).read()
    # no crash report on a run that eventually succeeded
    assert not os.path.exists(os.path.join(sup.dir, CRASH_REPORT))


def test_supervisor_crash_loop_stops_with_report(tmp_path):
    # a child that dies identically every launch with zero checkpoint
    # progress is poison: restarting replays it, so the supervisor must
    # stop at the threshold and write a diagnosable JSON crash report
    script = (
        "import sys\n"
        "print('BarrierStat: step mean/host=[...] slowest=host1')\n"
        "print('boom: poisoned batch')\n"
        "sys.exit(5)\n"
    )
    sup = _stub_supervisor(tmp_path, script)
    assert sup.run() == EXIT_CRASH_LOOP
    assert len(sup.attempts) == 3  # crash_loop_threshold
    report_path = os.path.join(sup.dir, CRASH_REPORT)
    report = json.load(open(report_path))
    assert report["reason"] == "crash_loop"
    assert [a["exit_code"] for a in report["attempts"]] == [5, 5, 5]
    assert "boom: poisoned batch" in report["log_tail"]
    # slowest-host attribution (utils/barrier skew line) is surfaced
    assert "slowest=host1" in report["step_time_skew"]


def test_supervisor_budget_exhausted_when_progressing(tmp_path):
    # the child keeps making checkpoint progress (the probe sees a new
    # restorable pass each launch) so it is NOT a crash loop — but the
    # restart budget still bounds the supervisor
    progress = iter(range(100))
    script = "import sys; sys.exit(4)"
    flags = _Flags(
        supervise_dir=str(tmp_path / "sup"),
        restart_budget=2,
        crash_loop_threshold=3,
    )
    sup = _stub_supervisor(
        tmp_path, script, flags=flags,
        probe=lambda: f"pass-{next(progress):05d}",
    )
    assert sup.run() == 4
    assert len(sup.attempts) == 3  # initial + 2 restarts
    report = json.load(open(os.path.join(sup.dir, CRASH_REPORT)))
    assert report["reason"] == "restart_budget_exhausted"


def test_supervisor_forwards_sigterm_and_stops(tmp_path):
    # preemption: SIGTERM to the supervisor reaches the child and the
    # supervisor does NOT restart it
    script = "import time; time.sleep(60)"
    sup = _stub_supervisor(tmp_path, script)
    threading.Timer(
        1.0, lambda: os.kill(os.getpid(), signal.SIGTERM)
    ).start()
    t0 = time.monotonic()
    rc = sup.run()
    assert time.monotonic() - t0 < 30  # child died at the signal, not 60s
    assert rc != 0
    assert len(sup.attempts) == 1  # no restart after a forwarded SIGTERM


def test_supervisor_dry_run_prints_plan(tmp_path, capsys):
    flags = _Flags(dry_run=True, restart_budget=2,
                   supervise_dir=str(tmp_path / "sup"))
    sup = Supervisor(["--config=cfg.py", "--save_dir=out"], flags)
    assert sup.run() == 0
    out = capsys.readouterr().out
    assert "--init_model_path=auto" in out       # the restart injection
    assert "restart_budget=2" in out
    assert CRASH_REPORT in out
    assert not os.path.exists(sup.dir)           # nothing was launched
    assert sup.attempts == []


def test_restart_cmd_replaces_user_init_model_path():
    sup = Supervisor(
        ["--config=c.py", "--init_model_path=/pretrained", "--seed=7"],
        _Flags(),
    )
    first = sup.child_cmd(restart=False)
    again = sup.child_cmd(restart=True)
    assert "--init_model_path=/pretrained" in first
    assert "--init_model_path=/pretrained" not in again
    assert again[-1] == "--init_model_path=auto"
    assert "--seed=7" in again
    # space-separated value form is stripped as a pair
    sup2 = Supervisor(["--init_model_path", "/x", "--seed=7"], _Flags())
    again2 = sup2.child_cmd(restart=True)
    assert "/x" not in again2 and "--seed=7" in again2


def test_supervisor_import_is_jax_free():
    """The supervisor must stay usable when the accelerator runtime is
    exactly what keeps crashing the child — importing it (and the probe
    it uses) may never pull in jax."""
    code = (
        "import sys\n"
        "from paddle_tpu.resilience.supervisor import probe_restorable\n"
        "sys.exit(1 if 'jax' in sys.modules else 0)\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], env=SUBPROC_ENV, capture_output=True,
        text=True, timeout=60,
    )
    assert r.returncode == 0, r.stderr


def test_probe_restorable_is_manifest_aware(tmp_path):
    import jax.numpy as jnp

    d = str(tmp_path)
    assert probe_restorable(d) is None
    params = {"w": jnp.ones((2, 2))}
    ckpt.save_checkpoint(d, 0, params)
    ckpt.save_checkpoint(d, 1, params)
    assert probe_restorable(d) == os.path.join(d, "pass-00001")
    # a torn newest checkpoint must not count as progress
    data = open(os.path.join(d, "pass-00001", "params.npz"), "rb").read()
    open(os.path.join(d, "pass-00001", "params.npz"), "wb").write(data[:10])
    assert probe_restorable(d) == os.path.join(d, "pass-00000")
    # a bare tmp dir is not restorable state
    os.makedirs(os.path.join(d, "pass-00009.tmp"))
    assert probe_restorable(d) == os.path.join(d, "pass-00000")


# --------------------------------------------------- barrier skew guard


def test_summarize_host_stats_guards_idle_hosts():
    """A host with no recorded steps joins the allgather with NaN
    sentinels; attribution must exclude it (not let zeros fake the
    fastest host) while still calling it out."""
    from paddle_tpu.utils.barrier import summarize_host_stats

    stats = np.array([[0.010, 0.012], [np.nan, np.nan], [0.030, 0.040]])
    line = summarize_host_stats(stats)
    assert "slowest=host2" in line
    assert "skew=20.0ms" in line
    assert "no steps recorded on host(s) 1" in line
    assert summarize_host_stats(np.full((3, 2), np.nan)) is None


def test_skew_summary_single_process_returns_none():
    from paddle_tpu.utils.barrier import step_time_skew_summary

    assert step_time_skew_summary([]) is None
    assert step_time_skew_summary([0.01, 0.02]) is None


# -------------------------------------------- divergence policy (unit)


@pytest.fixture
def bow_cfg(tmp_path):
    """Fresh parsed config per call (rollback mutates opt_config)."""
    sys.path.insert(0, PROVIDERS)
    (tmp_path / "train.list").write_text("1\n")
    src = textwrap.dedent(f"""
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
    (tmp_path / "cfg.py").write_text(src)

    def make():
        from paddle_tpu.config import parse_config

        return parse_config(str(tmp_path / "cfg.py"))

    yield make
    sys.path.remove(PROVIDERS)


@pytest.mark.chaos
def test_nonfinite_skip_finishes_where_abort_dies(tmp_path, bow_cfg):
    """The acceptance scenario: the same injected divergence kills an
    abort run and is survived by --nonfinite_policy=skip."""
    from paddle_tpu.trainer import Trainer

    faultinject.configure("trainer.nonfinite=raise@3")
    t = Trainer(bow_cfg(), _Flags(log_period=0))
    with pytest.raises(NonFiniteLossError) as ei:
        t.train(num_passes=1)
    assert isinstance(ei.value, FloatingPointError)  # back-compat contract
    assert ei.value.pass_id == 0 and ei.value.batch_id == 2

    faultinject.configure("trainer.nonfinite=raise@3")
    t2 = Trainer(
        bow_cfg(),
        _Flags(log_period=0, nonfinite_policy="skip", max_nonfinite_steps=2,
               save_dir=str(tmp_path / "model")),
    )
    t2.train(num_passes=1)  # completes
    assert t2._nf_count == 1
    # 400 samples / batch 64 = 7 batches; the poisoned one was discarded
    assert int(t2.opt_state.step) == 6


@pytest.mark.chaos
def test_nonfinite_skip_budget_exhausts_loudly(tmp_path, bow_cfg):
    from paddle_tpu.trainer import Trainer

    faultinject.configure("trainer.nonfinite=raise@3+")  # every batch >= 3
    t = Trainer(
        bow_cfg(),
        _Flags(log_period=0, nonfinite_policy="skip", max_nonfinite_steps=2),
    )
    with pytest.raises(NonFiniteLossError, match="max_nonfinite_steps"):
        t.train(num_passes=1)
    assert t._nf_count == 3  # two discarded, the third raised


@pytest.mark.chaos
def test_nonfinite_rollback_restores_and_tempers_lr(tmp_path, bow_cfg):
    """rollback: restore the newest verified checkpoint, scale the lr,
    fast-forward the re-run pass past the poison region, finish."""
    from paddle_tpu.trainer import Trainer

    save_dir = str(tmp_path / "out_rb")
    cfg = bow_cfg()
    # hit 10 = pass 1, batch 2 (7 batches per pass)
    faultinject.configure("trainer.nonfinite=raise@10")
    t = Trainer(
        cfg,
        _Flags(save_dir=save_dir, log_period=0,
               nonfinite_policy="rollback", rollback_lr_scale=0.5),
    )
    t.train(num_passes=2)
    assert t.rollbacks == 1
    assert cfg.opt_config.learning_rate == pytest.approx(0.02 * 0.5)
    assert ckpt.latest_pass(save_dir) == 1
    # pass 0: 7 steps; pass 1 diverged at batch 2 (2 steps, then rolled
    # back to the pass-0 state); re-run pass 1 fast-forwarded past
    # batches 0..2 and trained the remaining 4
    assert int(t.opt_state.step) == 7 + 4


@pytest.mark.chaos
def test_rollback_without_checkpoint_raises_typed(tmp_path, bow_cfg):
    from paddle_tpu.trainer import Trainer

    faultinject.configure("trainer.nonfinite=raise@2")
    t = Trainer(
        bow_cfg(),
        _Flags(save_dir=str(tmp_path / "empty_rb"), log_period=0,
               nonfinite_policy="rollback"),
    )
    with pytest.raises(NonFiniteLossError, match="no restorable checkpoint"):
        t.train(num_passes=1)


def test_whole_data_cost_raises_same_type(tmp_path, bow_cfg, monkeypatch):
    """Satellite: the whole-data cost check and the per-step check raise
    the SAME typed error, so supervisors classify divergence uniformly."""
    from paddle_tpu.trainer import Trainer

    cfg = bow_cfg()
    cfg.opt_config.algorithm = "owlqn"
    cfg.opt_config.learning_method = "lbfgs"
    t = Trainer(cfg, _Flags(log_period=0))
    monkeypatch.setattr(
        t, "_full_data_sweep", lambda *a, **k: (float("nan"), {}, 1)
    )
    with pytest.raises(NonFiniteLossError, match="whole-data"):
        t.train(num_passes=1)


def test_bad_policy_value_rejected(tmp_path, bow_cfg):
    from paddle_tpu.trainer import Trainer

    with pytest.raises(ValueError, match="nonfinite_policy"):
        Trainer(bow_cfg(), _Flags(nonfinite_policy="explode"))


# --------------------------------------------- end-to-end (subprocess)


def _write_train_cfg(tmp_path):
    (tmp_path / "train.list").write_text("1\n")
    src = textwrap.dedent(f"""
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
    cfg = tmp_path / "cfg.py"
    cfg.write_text(src)
    return str(cfg)


@pytest.mark.chaos
def test_supervise_e2e_restart_resumes_and_completes(tmp_path):
    """The acceptance scenario end-to-end with REAL process deaths:
    `paddle supervise` survives an injected `trainer.crash` (os._exit
    mid-pass-2), restarts with backoff, resumes from the PR 1
    manifest-verified checkpoint, and the run completes."""
    cfg = _write_train_cfg(tmp_path)
    save_dir = str(tmp_path / "out")
    sup_dir = str(tmp_path / "sup")
    # 7 batches/pass: hit 18 = pass 2, batch 3. Run 1 saves passes 0-1
    # then dies; run 2 resumes at pass 2 (hits restart at 1) and finishes.
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "supervise",
         f"--config={cfg}", f"--save_dir={save_dir}",
         f"--supervise_dir={sup_dir}", "--num_passes=3", "--log_period=0",
         "--restart_base_delay=0.01",
         "--fault_spec=trainer.crash=exit:9@18"],
        capture_output=True, text=True, timeout=420, env=SUBPROC_ENV,
        cwd=str(tmp_path),
    )
    assert r.returncode == 0, (r.returncode, r.stderr[-3000:])
    # the run got all the way to the end across the restart
    assert os.path.isdir(os.path.join(save_dir, "pass-00002"))
    logs = sorted(
        n for n in os.listdir(sup_dir) if n.startswith("attempt-")
    )
    assert logs == ["attempt-000.log", "attempt-001.log"]
    # the restart actually resumed from the verified checkpoint
    assert "resumed pass 1" in open(os.path.join(sup_dir, logs[1])).read()
    assert not os.path.exists(os.path.join(sup_dir, CRASH_REPORT))


@pytest.mark.chaos
def test_supervise_e2e_crash_loop_report(tmp_path):
    """Deterministic crash loop: the child dies at batch 3 of pass 0
    every launch, never checkpointing — the supervisor must stop within
    the threshold and emit the JSON crash report."""
    cfg = _write_train_cfg(tmp_path)
    save_dir = str(tmp_path / "out")
    sup_dir = str(tmp_path / "sup")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "supervise",
         f"--config={cfg}", f"--save_dir={save_dir}",
         f"--supervise_dir={sup_dir}", "--num_passes=3", "--log_period=0",
         "--restart_base_delay=0.01", "--crash_loop_threshold=2",
         "--fault_spec=trainer.crash=exit:9@3"],
        capture_output=True, text=True, timeout=420, env=SUBPROC_ENV,
        cwd=str(tmp_path),
    )
    assert r.returncode == EXIT_CRASH_LOOP, (r.returncode, r.stderr[-3000:])
    report = json.load(open(os.path.join(sup_dir, CRASH_REPORT)))
    assert report["reason"] == "crash_loop"
    assert [a["exit_code"] for a in report["attempts"]] == [9, 9]
    assert all(a["restored_from"] is None for a in report["attempts"])
    assert report["log_tail"]  # the child log tail is attached
    # the report carries the child's structured telemetry tail (last N
    # metrics.jsonl records per host), not just grepped log text: the
    # trainer wrote run_start into <save_dir>/metrics.jsonl and the
    # fault-injection layer flushed its own firing before os._exit
    tail = report["metrics_tail"]["0"]
    kinds = [rec["kind"] for rec in tail]
    assert "run_start" in kinds and "fault" in kinds, kinds
    fault = next(rec for rec in tail if rec["kind"] == "fault")
    assert fault["site"] == "trainer.crash" and fault["action"] == "exit"
