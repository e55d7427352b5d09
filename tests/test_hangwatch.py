"""Hang defense (doc/resilience.md "Hang detection & elastic relaunch"):
the in-process step-progress hangwatch behind ``--step_hang_timeout``,
the heartbeat liveness layer, the 17/18/19 exit-code discipline, and
the supervisor's preemption/hang handling.

Unit tests drive the watchdog and the staleness logic with fake clocks
(no sleeping); the chaos e2e proves the acceptance scenario with a REAL
wedged trainer: an injected ``trainer.stall`` is detected within
``--step_hang_timeout``, leaves a ``hang_report.json`` with all thread
stacks, exits 19, and ``paddle supervise`` restarts the run to
completion.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

from paddle_tpu.resilience import (
    EXIT_CRASH_LOOP,
    EXIT_HANG,
    EXIT_PREEMPTED,
    faultinject,
    heartbeat as hb,
)
from paddle_tpu.resilience.hangwatch import HANG_REPORT, HangWatch
from paddle_tpu.resilience.supervisor import CRASH_REPORT, Supervisor
from paddle_tpu.utils.flags import _Flags, flag_value

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROVIDERS = os.path.join(REPO, "tests", "providers")

SUBPROC_ENV = dict(
    os.environ,
    JAX_PLATFORMS="cpu",
    PYTHONPATH=f"{REPO}:{os.path.join(REPO, 'compat')}:{PROVIDERS}",
)


@pytest.fixture(autouse=True)
def _clear_faults():
    yield
    faultinject.configure("")


# ----------------------------------------------------------- exit codes


def test_exit_codes_are_distinct_and_stable():
    """Wrappers dispatch on these; they may never collide or drift."""
    assert (EXIT_CRASH_LOOP, EXIT_PREEMPTED, EXIT_HANG) == (17, 18, 19)
    assert len({EXIT_CRASH_LOOP, EXIT_PREEMPTED, EXIT_HANG}) == 3
    # the supervisor re-exports the crash-loop code for old importers
    from paddle_tpu.resilience import supervisor

    assert supervisor.EXIT_CRASH_LOOP == EXIT_CRASH_LOOP


# ------------------------------------------------------------ hangwatch


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _watch(tmp_path, timeout=10.0):
    clock = _FakeClock()
    fired = []
    hw = HangWatch(
        timeout, report_dir=str(tmp_path),
        clock=clock, exit_fn=fired.append,
    )
    return hw, clock, fired


def test_hangwatch_fires_only_past_timeout(tmp_path):
    hw, clock, fired = _watch(tmp_path)
    hw.ping(0, 3)
    clock.t = 9.0
    assert hw.check() == pytest.approx(9.0)
    assert fired == []
    # a ping resets the age — a progressing loop never fires
    hw.ping(0, 4)
    clock.t = 18.0
    hw.check()
    assert fired == []
    clock.t = 30.0
    hw.check()
    assert fired == [EXIT_HANG]
    # the report landed atomically (no .tmp left behind)
    assert os.path.exists(tmp_path / HANG_REPORT)
    assert not os.path.exists(str(tmp_path / HANG_REPORT) + ".tmp")


def test_hangwatch_report_carries_stacks_and_context(tmp_path):
    hw, clock, fired = _watch(tmp_path, timeout=5.0)
    # give the report a metrics tail to pick up
    from paddle_tpu.observability import metrics as obs

    obs.configure(str(tmp_path))
    obs.emit("pass_end", pass_id=1, step=7)
    obs.flush()
    try:
        hw.ping(1, 7)
        clock.t = 6.0
        hw.check()
    finally:
        obs.configure("")
    assert fired == [EXIT_HANG]
    report = json.load(open(tmp_path / HANG_REPORT))
    assert report["reason"] == "step_hang"
    assert report["timeout_s"] == 5.0
    assert report["last_progress"] == {"pass": 1, "step": 7}
    # every thread's stack, with file:line frames — this test's own
    # frame must be visible in the main thread's stack
    assert report["threads"], report
    all_frames = "\n".join(
        f for t in report["threads"].values() for f in t["frames"]
    )
    assert "test_hangwatch.py" in all_frames
    # telemetry tail rode along
    kinds = [r["kind"] for r in report["metrics_tail"]["0"]]
    assert "pass_end" in kinds


def test_hangwatch_gauge_and_max_age(tmp_path):
    from paddle_tpu.observability import metrics as obs

    hw, clock, _fired = _watch(tmp_path, timeout=100.0)
    hw.ping()
    clock.t = 7.0
    hw.check()
    assert obs.registry().gauge("trainer.progress_age_s").value == 7.0
    clock.t = 9.0
    hw.check()
    hw.ping()
    clock.t = 10.0
    hw.check()
    # max since construction, then reset (the trainer reads this once
    # per pass into the pass_end record)
    assert hw.take_max_age() == pytest.approx(9.0)
    assert hw.take_max_age() == pytest.approx(0.0)
    # a stall SHORTER than the monitor poll period still registers:
    # ping() folds the age it just ended into the max, so a near-miss
    # the monitor thread never sampled reaches progress_age_max_s
    clock.t = 14.0
    hw.ping()  # 5s since the ping at t=9, never sampled by check()
    assert hw.take_max_age() == pytest.approx(5.0)


def test_hangwatch_thread_detects_real_stall(tmp_path):
    """The actual monitor thread (real clock, tiny timeout): no pings →
    fires within a few poll periods; exit_fn is captured, not os._exit."""
    fired = []
    hw = HangWatch(0.2, report_dir=str(tmp_path), exit_fn=fired.append)
    hw.start()
    try:
        deadline = time.monotonic() + 10
        while not fired and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        hw.stop()
    assert fired == [EXIT_HANG]
    assert os.path.exists(tmp_path / HANG_REPORT)


# ------------------------------------------------------------ heartbeat


def test_heartbeat_staleness_with_fake_clock(tmp_path):
    d = str(tmp_path)
    hb.write_beat(d, 0, clock=lambda: 100.0)
    hb.write_beat(d, 1, clock=lambda: 107.0)
    assert hb.stale_hosts(d, 2, 10.0, now=108.0) == []
    # only host 0 has gone silent past the threshold
    assert hb.stale_hosts(d, 2, 10.0, now=112.0) == [(0, 12.0)]
    stale = dict(hb.stale_hosts(d, 2, 10.0, now=150.0))
    assert stale == {0: 50.0, 1: 43.0}


def test_heartbeat_never_started_host_aged_from_epoch(tmp_path):
    d = str(tmp_path)
    hb.write_beat(d, 0, clock=lambda: 100.0)
    # host 1 never wrote a beat: judged from the observation epoch, so a
    # trainer wedged before its FIRST beat is still caught — but only
    # after the startup grace (since + stale_after)
    assert hb.stale_hosts(d, 2, 10.0, now=105.0, since=100.0) == []
    assert (1, 20.0) in hb.stale_hosts(d, 2, 10.0, now=120.0, since=100.0)
    # without an epoch a missing beat is unjudgeable
    assert hb.stale_hosts(d, 2, 10.0, now=120.0) == [(0, 20.0)]


def test_heartbeat_epoch_clamps_previous_round(tmp_path):
    """Beats from before a relaunch must not instantly re-flag a host:
    ages are clamped to the new round's start."""
    d = str(tmp_path)
    hb.write_beat(d, 0, clock=lambda: 100.0)
    assert hb.stale_hosts(d, 1, 10.0, now=200.0, since=195.0) == []
    assert hb.stale_hosts(d, 1, 10.0, now=210.0, since=195.0) == [(0, 15.0)]


def test_heartbeat_writer_renews_and_marks_stop(tmp_path):
    d = str(tmp_path)
    w = hb.HeartbeatWriter(d, 3, 0.05)
    w.start()
    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            beat = hb.read_beats(d).get(3)
            if beat and beat["seq"] >= 3:
                break
            time.sleep(0.02)
    finally:
        w.stop()
    final = hb.read_beats(d)[3]
    assert final["seq"] >= 3
    assert final.get("stopped") is True  # clean exit is distinguishable
    assert final["interval_s"] == 0.05
    # torn beats are impossible (atomic replace): no tmp litter
    assert not [n for n in os.listdir(d) if ".tmp." in n]


def test_heartbeat_ignores_garbage_files(tmp_path):
    d = str(tmp_path)
    (tmp_path / "host-9.json").write_text("{not json")
    (tmp_path / "unrelated.txt").write_text("x")
    hb.write_beat(d, 0, clock=lambda: 50.0)
    assert set(hb.read_beats(d)) == {0}


def test_resolve_dir_precedence(tmp_path):
    assert hb.resolve_dir("/explicit", "/save") == "/explicit"
    assert hb.resolve_dir("", "/save") == os.path.join("/save", "heartbeats")
    assert hb.resolve_dir("", "") == ""


def test_run_dir_of_handles_jsonl_metrics_path():
    """--metrics_path may be an explicit *.jsonl stream file (a shape
    metrics.py supports); the hang report and the supervisor looking
    for it must both land on the containing directory."""
    from paddle_tpu.resilience.hangwatch import run_dir_of

    assert run_dir_of("/runs/a") == "/runs/a"
    assert run_dir_of("/runs/a/metrics.jsonl") == "/runs/a"
    assert run_dir_of("metrics.jsonl") == "."


# --------------------------------------------------- flag_value helper


def test_flag_value_reads_both_forms_last_wins():
    argv = ["--a=1", "--heartbeat_interval", "2", "--b",
            "--heartbeat_interval=5"]
    assert flag_value(argv, "heartbeat_interval") == "5"
    assert flag_value(argv, "missing", "dflt") == "dflt"
    # prefix must not match a longer flag name
    assert flag_value(["--heartbeat_interval_x=9"], "heartbeat_interval") == ""


# -------------------------------------------------------- paddle faults


def test_paddle_faults_lists_every_site(capsys):
    from paddle_tpu import cli
    from paddle_tpu.resilience.faultinject import KNOWN_SITES, SITE_DOCS

    assert cli.main(["faults"]) == 0
    out = capsys.readouterr().out
    for site in KNOWN_SITES:
        assert site in out, site
    assert "trainer.stall" in SITE_DOCS
    # the doc page points at the same table
    doc = open(os.path.join(REPO, "doc", "resilience.md")).read()
    assert "paddle faults" in doc
    for site in KNOWN_SITES:
        assert site in doc, f"{site} undocumented in doc/resilience.md"


# ------------------------------------------- supervisor exit-code rules


def _stub_supervisor(tmp_path, script, flags=None, **kw):
    flags = flags or _Flags(
        supervise_dir=str(tmp_path / "sup"),
        restart_budget=5,
        crash_loop_threshold=3,
    )
    return Supervisor(
        ["--config=unused.py"], flags,
        child_cmd=[sys.executable, "-c", script, str(tmp_path / "counter")],
        sleep=lambda _s: None, **kw,
    )


def test_supervisor_preemption_exit_is_a_free_restart(tmp_path):
    """A child exiting EXIT_PREEMPTED is restarted even with ZERO
    restart budget — preemption is the scheduler's decision, not a
    failure — and the preempted attempt never feeds crash-loop
    accounting."""
    script = textwrap.dedent(f"""
        import os, sys
        c = sys.argv[1]
        n = int(open(c).read()) if os.path.exists(c) else 0
        open(c, "w").write(str(n + 1))
        sys.exit({EXIT_PREEMPTED} if n < 2 else 0)
    """)
    flags = _Flags(
        supervise_dir=str(tmp_path / "sup"),
        restart_budget=0,           # no budget at all
        crash_loop_threshold=2,     # two same-state deaths would stop it
    )
    sup = _stub_supervisor(tmp_path, script, flags=flags)
    assert sup.run() == 0
    codes = [a["exit_code"] for a in sup.attempts]
    assert codes == [EXIT_PREEMPTED, EXIT_PREEMPTED, 0]
    assert not os.path.exists(os.path.join(sup.dir, CRASH_REPORT))


def test_supervisor_hang_exit_consumes_budget_and_attaches_report(tmp_path):
    """EXIT_HANG is a real failure: it consumes budget, and the crash
    report embeds the child's hang_report.json forensics."""
    metrics_dir = tmp_path / "run"
    metrics_dir.mkdir()
    hang = {"reason": "step_hang", "age_s": 42.0,
            "threads": {"MainThread": {"daemon": False, "frames": ["f:1"]}}}
    flags = _Flags(
        supervise_dir=str(tmp_path / "sup"),
        metrics_path=str(metrics_dir),
        restart_budget=1,
        crash_loop_threshold=10,
    )
    # keeps "progressing" so this is budget exhaustion, not a crash loop
    progress = iter(range(100))
    sup = _stub_supervisor(
        tmp_path, f"import sys; sys.exit({EXIT_HANG})", flags=flags,
        probe=lambda: f"pass-{next(progress):05d}",
    )
    # written AFTER the supervisor was born, as the real hangwatch would
    (metrics_dir / HANG_REPORT).write_text(json.dumps(hang))
    assert sup.run() == EXIT_HANG
    assert [a["exit_code"] for a in sup.attempts] == [EXIT_HANG, EXIT_HANG]
    report = json.load(open(os.path.join(sup.dir, CRASH_REPORT)))
    assert report["reason"] == "restart_budget_exhausted"
    assert report["hang_report"]["age_s"] == 42.0
    assert report["hang_report"]["threads"]

    # a hang_report.json predating the supervise run (leftover from an
    # earlier incident in the same save_dir) must NOT be embedded as
    # this run's forensics
    old = time.time() - 3600
    os.utime(metrics_dir / HANG_REPORT, (old, old))
    sup2 = _stub_supervisor(
        tmp_path, f"import sys; sys.exit({EXIT_HANG})", flags=flags,
        probe=lambda: f"pass-{next(progress):05d}",
    )
    assert sup2.run() == EXIT_HANG
    report2 = json.load(open(os.path.join(sup2.dir, CRASH_REPORT)))
    assert report2["hang_report"] is None


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
def test_supervise_e2e_hang_detected_reported_and_recovered(tmp_path):
    """The acceptance scenario end-to-end: a deliberately stalled
    trainer (`trainer.stall` sleep at launch 18 = pass 2, batch 3) is
    detected within --step_hang_timeout, leaves hang_report.json with
    thread stacks, exits 19, and `paddle supervise` restarts it from
    the pass-1 checkpoint to completion."""
    cfg = _write_train_cfg(tmp_path)
    save_dir = str(tmp_path / "out")
    sup_dir = str(tmp_path / "sup")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "supervise",
         f"--config={cfg}", f"--save_dir={save_dir}",
         f"--supervise_dir={sup_dir}", "--num_passes=3", "--log_period=0",
         # timeout sized for a LOADED 2-CPU container: jit compile of the
         # first launch can legitimately take several seconds, and a
         # false positive here turns the drill into a crash loop
         "--restart_base_delay=0.01", "--step_hang_timeout=10",
         "--fault_spec=trainer.stall=sleep:600@18"],
        capture_output=True, text=True, timeout=420, env=SUBPROC_ENV,
        cwd=str(tmp_path),
    )
    assert r.returncode == 0, (r.returncode, r.stderr[-3000:])
    # the run completed across the hang restart
    assert os.path.isdir(os.path.join(save_dir, "pass-00002"))
    # the hung attempt exited with the distinct hang code and the
    # supervisor named it
    assert f"rc={EXIT_HANG}" in r.stderr and "hang" in r.stderr
    # forensics: all thread stacks, with the stall site on the main one
    report = json.load(open(os.path.join(save_dir, HANG_REPORT)))
    assert report["reason"] == "step_hang"
    all_frames = "\n".join(
        f for t in report["threads"].values() for f in t["frames"]
    )
    assert "faultinject" in all_frames  # the injected sleep is visible
    # the hang record was flushed to telemetry BEFORE the death
    from paddle_tpu.observability import metrics as obs_mod

    kinds = [rec["kind"]
             for recs in obs_mod.read_tail(save_dir, n=200).values()
             for rec in recs]
    assert "hang" in kinds
    # ... and `paddle metrics` warns about it
    r2 = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "metrics", save_dir],
        capture_output=True, text=True, timeout=120, env=SUBPROC_ENV,
    )
    assert r2.returncode == 0, r2.stderr
    assert "hang detected" in r2.stdout
    assert "age s" in r2.stdout  # the per-pass max progress-age column


@pytest.mark.chaos
def test_train_preemption_exits_18(tmp_path):
    """SIGTERM to a bare `paddle train` checkpoints at the launch
    boundary and exits EXIT_PREEMPTED — the distinct code wrappers
    treat as budget-free."""
    cfg = _write_train_cfg(tmp_path)
    save_dir = str(tmp_path / "out")
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.cli", "train",
         f"--config={cfg}", f"--save_dir={save_dir}",
         "--num_passes=500", "--log_period=0"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        env=SUBPROC_ENV, cwd=str(tmp_path),
    )
    try:
        # wait until training is demonstrably under way (a pass saved —
        # ANY pass: a pass takes ~25 ms here and rolling deletion removes
        # pass-00000 three passes later, inside one poll interval; a
        # missed pass-00000 cost this test its whole 300 s deadline)
        import glob

        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if glob.glob(os.path.join(save_dir, "pass-*", "meta.json")):
                break
            if proc.poll() is not None:
                break
            time.sleep(0.2)
        assert proc.poll() is None, proc.stdout.read().decode()[-3000:]
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=300)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert proc.returncode == EXIT_PREEMPTED, (
        proc.returncode, out.decode()[-3000:]
    )
    assert b"preemption" in out
