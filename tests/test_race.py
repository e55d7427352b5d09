"""`paddle race` — the deterministic schedule explorer (dynamic half of
the analysis stack; doc/static_analysis.md "Dynamic analysis").

Coverage:

- detector fixtures: the three PR-9 PTL005 bugs (unlocked async-writer
  `completed`, heartbeat `_seq`, hangwatch `_fired`), reintroduced as
  subclass twins of the REAL classes, are each detected as torn reads
  within the default schedule budget; lock-order inversion and lost
  wakeup fixtures for the other detectors;
- the drain progress-signal regression this PR fixed (a concurrent
  save's queue motion credited as writer progress): the legacy logic
  fails its invariant under exploration, the shipped code is clean;
- replay: the whole run is a pure function of (seed, schedules) —
  identical findings, fingerprints, and traces across runs;
- the repo-wide gate: every spec under tests/race_specs passes with
  the checked-in ZERO-entry baseline, jax-free, in well under 60 s;
- --json records validate against the schema; `paddle compare` judges
  race artifacts direction-aware (growth ⇒ REGRESSION exit 1).

Everything here is jax-free and fast, like test_lint.py.
"""

import json
import os
import re
import sys
import time

import pytest

from paddle_tpu.analysis.dynamic.cli import (
    DEFAULT_SCHEDULES,
    RACE_BASELINE_NAME,
    main as race_main,
)
from paddle_tpu.analysis.dynamic.explore import Explorer, load_specs
from paddle_tpu.observability import metrics as obs
from paddle_tpu.resilience.hangwatch import HangWatch
from paddle_tpu.resilience.heartbeat import HeartbeatWriter, write_beat
from paddle_tpu.trainer.async_ckpt import AsyncCheckpointer
from paddle_tpu.utils import concurrency as cc

pytestmark = pytest.mark.race

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS_DIR = os.path.join(REPO, "tests", "race_specs")


def explore(spec, schedules=DEFAULT_SCHEDULES, seed=0):
    return Explorer(seed=seed, schedules=schedules).run_spec(spec)


def rules_of(result):
    return sorted({f.rule for f in result.findings})


# ----------------------------------------- PR-9 PTL005 bugs, reintroduced


class _BuggyWriter(AsyncCheckpointer):
    """PR-9 bug #1 reintroduced: the background writer's `completed`
    increment without the cv — drain's progress signal can tear."""

    def _write(self, job):
        (self._write_fn or self._default_write_fn())(
            self.save_dir, job.pass_id, job.params, job.opt_state,
            extra_meta=job.extra_meta, keep=job.keep,
            protect_pass=job.protect_pass,
        )
        self.completed += 1  # the pre-PR-9 unlocked write


class _SpecBuggyCompleted:
    NAME = "twin_completed"

    @staticmethod
    def run(ctx):
        ac = _BuggyWriter("", inflight_limit=2,
                          write_fn=lambda *a, **k: "p",
                          snapshot_fn=lambda tree: tree)
        ctx.watch(ac, "completed")
        ac.save(0, {"w": 0})
        ac.save(1, {"w": 1})
        ac.drain()


class _BuggyBeat(HeartbeatWriter):
    """PR-9 bug #2 reintroduced: `_seq += 1` outside `_seq_lock` —
    stop()'s final beat overlaps a daemon renewal wedged in slow
    shared-fs I/O past the bounded join, and the counter tears. The
    virtual sleep IS that slow write (3 s > stop's 1 s join timeout)."""

    def beat(self, **extra):
        seq = self._seq + 1
        cc.sleep(3.0)  # the slow-fs window the real class's lock covers
        self._seq = seq
        write_beat(self.dir, self.host, seq=seq, clock=self.clock,
                   extra=extra)


class _SpecBuggySeq:
    NAME = "twin_seq"

    @staticmethod
    def run(ctx):
        hb = _BuggyBeat(ctx.tmpdir, host=0, interval_s=1.0,
                        clock=lambda: 1e9)
        ctx.watch(hb, "_seq")
        hb.start()
        cc.sleep(2.5)
        hb.stop()


class _BuggyHangWatch(HangWatch):
    """PR-9 bug #3 reintroduced: the `_fired` test-and-set claimed
    WITHOUT the lock — two concurrent check() calls double-report."""

    def check(self):
        age = self.clock() - self._last
        if age > self.timeout_s and not self._fired:
            self._fired = True
            self.exit_fn(19)
        return age


class _SpecBuggyFired:
    NAME = "twin_fired"

    @staticmethod
    def run(ctx):
        exits = []
        hw = _BuggyHangWatch(timeout_s=2.0, report_dir=ctx.tmpdir,
                             exit_fn=exits.append, poll_s=1.0)
        ctx.watch(hw, "_fired")
        hw.start()                 # monitor thread drives check()
        cc.sleep(3.0)              # past the timeout, no pings
        hw.check()                 # caller-side check races the monitor
        hw.stop()


@pytest.mark.parametrize("spec", [
    _SpecBuggyCompleted, _SpecBuggySeq, _SpecBuggyFired,
], ids=lambda s: s.NAME)
def test_ptl005_bugs_detected_as_torn_reads(spec):
    """Acceptance: each PR-9 statically-found bug, reintroduced against
    the real class, is DYNAMICALLY proven racy within the default
    budget — static finds the fields, dynamic proves the race."""
    result = explore(spec)
    torn = [f for f in result.findings if f.rule == "torn_read"]
    assert torn, (
        f"{spec.NAME}: no torn_read within {DEFAULT_SCHEDULES} schedules:\n"
        + "\n".join(f.render() for f in result.findings)
    )
    attr = {"twin_completed": "completed", "twin_seq": "_seq",
            "twin_fired": "_fired"}[spec.NAME]
    assert any(f".{attr}`" in f.message for f in torn), torn[0].message


def test_fixed_classes_are_clean():
    """The same scenarios against the SHIPPED classes: no findings —
    the locks PR 9 added satisfy the happens-before detector."""
    specs = load_specs(SPECS_DIR)
    ex = Explorer(seed=0, schedules=DEFAULT_SCHEDULES)
    for spec in specs:
        result = ex.run_spec(spec)
        assert result.findings == [], (
            f"{spec.NAME}:\n" + "\n".join(
                f.render() for f in result.findings
            )
        )


# ------------------------------------ the drain progress-signal regression


class _LegacyDrainCheckpointer(AsyncCheckpointer):
    """The pre-PR `_wait_idle` progress signal: (completed,
    len(pending), id(active)) — trainer-side queue motion (a concurrent
    save / drop-oldest) and id() reuse both read as writer progress."""

    def _wait_idle(self, timeout=None):
        from paddle_tpu.resilience import CheckpointError

        deadline = None if timeout is None else cc.monotonic() + timeout
        self._ensure_thread()
        with self._cv:
            last_state = None
            while self._pending or self._active is not None:
                state = (self.completed, len(self._pending),
                         id(self._active))
                if (self.hangwatch is not None
                        and self._active is not None
                        and state != last_state):
                    self.hangwatch.ping(self._active.pass_id)
                last_state = state
                self._cv.wait(timeout=0.2)
                if deadline is not None and cc.monotonic() > deadline:
                    raise CheckpointError("drain timeout")


def _drain_signal_spec(cls):
    class _Spec:
        NAME = f"drain_signal_{cls.__name__}"

        @staticmethod
        def run(ctx):
            gate = cc.Event()
            pings = []

            class _Hw:
                def ping(self, pass_id=None, step=None):
                    import threading

                    if "writer" in threading.current_thread().name:
                        return
                    active = ac._active
                    pings.append((ac.completed,
                                  active.seq if active else None))

            def write_fn(save_dir, pass_id, params, opt_state=None, **kw):
                if pass_id == 0:
                    gate.wait()
                return "p"

            ac = cls("", inflight_limit=2, hangwatch=_Hw(),
                     write_fn=write_fn, snapshot_fn=lambda tree: tree)

            def late_saver():
                # wait until the main thread is demonstrably inside
                # drain (its first ping landed), then enqueue while the
                # writer is still wedged — queue motion, NOT progress
                while not pings:
                    cc.sleep(0.05)
                ac.save(1, {"w": 1})
                gate.set()

            ac.save(0, {"w": 0})
            while ac._active is None:  # ensure claimed, not droppable
                cc.sleep(0.01)
            t = cc.Thread(target=late_saver, name="saver2", daemon=False)
            t.start()
            ac.drain()
            t.join()
            # at most one ping per distinct WRITER state — a duplicate
            # means queue motion was credited as progress (the masked-
            # wedged-writer bug)
            assert len(pings) == len(set(pings)), (
                f"drain credited non-writer motion as progress: {pings}"
            )

    return _Spec


def test_legacy_drain_signal_bug_is_surfaced():
    """The explorer surfaces the concrete interleaving bug this PR
    fixed: under the legacy signal, a concurrent save during drain
    produces a duplicate-state ping (⇒ a wedged writer could never trip
    the hangwatch); the shipped signal is clean on the same spec."""
    legacy = explore(_drain_signal_spec(_LegacyDrainCheckpointer))
    assert any(
        f.rule == "spec_error" and "non-writer motion" in f.message
        for f in legacy.findings
    ), "\n".join(f.render() for f in legacy.findings) or "no findings"
    fixed = explore(_drain_signal_spec(AsyncCheckpointer))
    assert fixed.findings == [], "\n".join(
        f.render() for f in fixed.findings
    )


# -------------------------------------------- other detector fixture pairs


class _SpecLockOrder:
    NAME = "lock_order_pair"

    @staticmethod
    def run(ctx):
        a, b = cc.Lock(), cc.Lock()

        def ab():
            with a:
                with b:
                    pass

        def ba():
            with b:
                with a:
                    pass

        t = cc.Thread(target=ba, daemon=False)
        t.start()
        ab()
        t.join()


def test_lock_order_cycle_detected_without_deadlocking():
    """The union graph catches the inversion even in schedules where
    the deadlock never actually fires."""
    result = explore(_SpecLockOrder)
    assert any(f.rule == "lock_order" for f in result.findings), (
        "\n".join(f.render() for f in result.findings) or "no findings"
    )
    lo = [f for f in result.findings if f.rule == "lock_order"][0]
    assert "cycle" in lo.message


class _SpecLostWakeup:
    NAME = "lost_wakeup_pair"

    @staticmethod
    def run(ctx):
        ev = cc.Event()

        def waiter():
            ev.wait()  # no timeout, and nothing will ever set it

        t = cc.Thread(target=waiter, daemon=False)
        t.start()
        t.join()


def test_lost_wakeup_detected():
    result = explore(_SpecLostWakeup)
    dets = rules_of(result)
    assert "lost_wakeup" in dets, dets
    assert any("no possible future wake" in f.message
               for f in result.findings)


# ----------------------------------------------------------------- replay


def test_run_is_a_pure_function_of_seed_and_budget():
    a = explore(_SpecBuggyCompleted, schedules=16, seed=7)
    b = explore(_SpecBuggyCompleted, schedules=16, seed=7)
    assert [(f.rule, f.fingerprint, f.schedule, f.trace)
            for f in a.findings] == \
           [(f.rule, f.fingerprint, f.schedule, f.trace)
            for f in b.findings]
    assert a.schedules_run == b.schedules_run and a.steps == b.steps


def test_finding_fingerprints_are_line_shift_stable():
    """Fingerprints key on (file, function, attr), not line numbers —
    the same rule lint's baseline follows."""
    a = explore(_SpecBuggyCompleted)
    fps = {f.fingerprint for f in a.findings}
    assert fps and all(re.fullmatch(r"[0-9a-f]{16}", fp) for fp in fps)


# ------------------------------------------------------------- CLI / gate


def test_repo_wide_race_gate_zero_findings_fast_and_jax_free():
    """THE gate (mirrors test_lint's): every shipped spec passes with
    the checked-in ZERO-entry baseline. What the gate costs is printed,
    not asserted: beside five other xdist workers its seconds are the
    machine's load, not the gate's (26 s alone here, 74 s in the run that
    failed it)."""
    bl_path = os.path.join(REPO, RACE_BASELINE_NAME)
    assert os.path.isfile(bl_path), "checked-in race baseline missing"
    with open(bl_path) as f:
        doc = json.load(f)
    assert doc["findings"] == [], (
        "the race baseline must stay EMPTY — fix races, don't "
        "grandfather them"
    )
    jax_loaded_before = "jax" in sys.modules  # other suites may have
    t0, c0 = time.monotonic(), time.process_time()
    rc = race_main(["--specs", SPECS_DIR, "--baseline", bl_path])
    print(f"race gate: {time.monotonic() - t0:.1f}s wall, "
          f"{time.process_time() - c0:.1f}s of this process's CPU")
    assert rc == 0
    assert ("jax" in sys.modules) == jax_loaded_before, (
        "the race gate must stay jax-free (a spec imported the "
        "accelerator runtime)"
    )


def test_cli_json_records_validate(tmp_path, capsys):
    rc = race_main(["--specs", SPECS_DIR, "--spec", "heartbeat",
                    "--no-baseline", "--json", "--schedules", "6"])
    out = capsys.readouterr().out
    recs = [json.loads(line) for line in out.splitlines() if line.strip()]
    assert rc == 0
    assert recs[-1]["kind"] == "race_summary"
    for rec in recs:
        assert obs.validate_record(rec) == [], rec
    assert recs[-1]["findings"] == 0
    assert set(recs[-1]["counts"]) <= set(recs[-1]["detectors"])
    assert recs[-1]["specs"] == ["heartbeat"]


def test_cli_list_and_unknown_spec(capsys):
    assert race_main(["--specs", SPECS_DIR, "--list"]) == 0
    out = capsys.readouterr().out
    for name in ("async_ckpt", "sharded_commit", "hangwatch",
                 "heartbeat", "feeder_pool"):
        assert name in out
    assert race_main(["--specs", SPECS_DIR, "--spec", "nope"]) == 2


def _buggy_spec_dir(tmp_path):
    d = tmp_path / "specs"
    d.mkdir()
    (d / "spec_bug.py").write_text(
        "from paddle_tpu.utils import concurrency as cc\n"
        "NAME = 'bugfix'\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self.n = 0\n"
        "    def work(self):\n"
        "        self.n += 1\n"
        "def run(ctx):\n"
        "    c = C()\n"
        "    ctx.watch(c, 'n')\n"
        "    t = cc.Thread(target=c.work, daemon=True)\n"
        "    t.start()\n"
        "    c.n += 1\n"
        "    t.join()\n"
    )
    return str(d)


def test_cli_exit_1_on_new_findings_and_baseline_grandfathers(tmp_path,
                                                              capsys):
    d = _buggy_spec_dir(tmp_path)
    bl = str(tmp_path / RACE_BASELINE_NAME)
    assert race_main(["--specs", d, "--no-baseline"]) == 1
    capsys.readouterr()
    # grandfather, then the same run is clean — and the findings stay
    # visible as [baselined]
    assert race_main(["--specs", d, "--write-baseline",
                      "--baseline", bl]) == 0
    capsys.readouterr()
    assert race_main(["--specs", d, "--baseline", bl]) == 0
    out = capsys.readouterr().out
    assert "[baselined]" in out


def test_compare_diffs_race_runs(tmp_path, capsys):
    """`paddle compare` on two race artifacts: detector-count growth is
    a REGRESSION (exit 1), shrinkage an improvement."""
    from paddle_tpu.observability.compare import main as compare_main

    clean_dir = SPECS_DIR
    race_main(["--specs", clean_dir, "--spec", "heartbeat",
               "--no-baseline", "--json", "--schedules", "4"])
    a = tmp_path / "a.jsonl"
    a.write_text(capsys.readouterr().out)
    race_main(["--specs", _buggy_spec_dir(tmp_path), "--no-baseline",
               "--json", "--schedules", "4"])
    b = tmp_path / "b.jsonl"
    b.write_text(capsys.readouterr().out)

    assert compare_main([str(a), str(b)]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "race.torn_read" in out
    assert compare_main([str(a), str(a)]) == 0
    assert "NO CHANGE" in capsys.readouterr().out
    assert compare_main([str(b), str(a)]) == 0
    assert "IMPROVED" in capsys.readouterr().out


def test_check_analysis_script_is_the_combined_gate():
    """bin/check_analysis.sh runs lint + race against both checked-in
    baselines — a PR introducing a lock-order inversion (or any new
    finding) fails it before review. Run here end-to-end, jax-free."""
    import subprocess

    script = os.path.join(REPO, "bin", "check_analysis.sh")
    assert os.path.isfile(script) and os.access(script, os.X_OK), (
        "bin/check_analysis.sh missing or not executable"
    )
    r = subprocess.run(
        ["bash", script, "--schedules", "8"],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "PYTHON": sys.executable, "JAX_PLATFORMS": "cpu"},
        cwd=REPO,
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "analysis gate clean" in r.stdout


def test_race_marker_registered():
    with open(os.path.join(REPO, "pyproject.toml"), encoding="utf-8") as f:
        assert re.search(r'^\s*"race:', f.read(), re.MULTILINE), (
            "race pytest marker missing from pyproject.toml"
        )
