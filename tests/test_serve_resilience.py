"""Serving resilience (doc/resilience.md "Serving resilience"): the
launch-failure circuit breaker (open/half-open/close on the injectable
clock), deadline-aware admission shedding and brownout degradation, the
durable at-least-once request journal, the --status_path health probe +
`paddle serve-status`, `paddle supervise --supervise_job=serve`, the
shed/breaker telemetry + `paddle compare` rates, and the serve.* chaos
e2e drills: an injected `serve.stall` under supervision produces
serve_hang_report.json + exit 19, the server restarts, and every
journaled request is answered (deduped by id, zero stranded futures);
an injected `serve.oom` dies with oom_report.json + exit 20."""

import json
import os
import subprocess
import sys

import pytest

from paddle_tpu.observability import metrics as obs
from paddle_tpu.observability.analyze import load_run
from paddle_tpu.resilience import EXIT_HANG, EXIT_OOM, faultinject
from paddle_tpu.resilience.supervisor import CRASH_REPORT, Supervisor
from paddle_tpu.serving import Engine, FakeBackend
from paddle_tpu.serving.resilience import (
    SERVE_HANG_REPORT,
    CircuitBreaker,
    RequestJournal,
    StatusWriter,
    journal_progress,
    status_main,
)
from paddle_tpu.utils.flags import _Flags

pytestmark = pytest.mark.serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    obs.registry().reset()
    yield
    obs.configure("")
    faultinject.configure("")


def _validated(run_dir):
    recs = [r for rs in load_run(run_dir).values() for r in rs]
    for rec in recs:
        assert not obs.validate_record(rec), rec
    return recs


# ------------------------------------------------------ circuit breaker


def test_breaker_open_half_open_close_on_injectable_clock():
    """The full state machine, deterministically: threshold faults open
    the breaker, the cooldown's expiry reads half_open (one probe may
    launch), success closes, a half-open fault reopens with a FRESH
    cooldown."""
    t = [100.0]
    br = CircuitBreaker(threshold=2, cooldown_s=10.0, clock=lambda: t[0])
    assert br.state == "closed"
    assert br.allow_submit() and br.allow_launch()
    assert br.retry_after_s() == 0.0

    assert br.record_fault() is False          # 1 of 2: still closed
    assert br.state == "closed"
    assert br.record_fault() is True           # 2nd consecutive: OPENS
    assert br.state == "open"
    assert not br.allow_submit() and not br.allow_launch()
    assert br.opened_total == 1
    t[0] += 4.0
    assert abs(br.retry_after_s() - 6.0) < 1e-9

    t[0] += 6.0                                # cooldown elapsed
    assert br.state == "half_open"
    assert br.allow_launch() and br.allow_submit()  # the probe window
    br.note_probe()                            # engine launched the probe
    # EXACTLY one probe cohort: until its collect resolves the state,
    # further boundaries must not burn cohorts against the device (the
    # pipelined loop runs boundaries faster than collects resolve)
    assert not br.allow_launch()
    assert br.allow_submit()                   # arrivals queue behind it
    assert br.record_fault() is True           # probe faulted: REOPENS
    assert br.state == "open" and br.opened_total == 2
    assert abs(br.retry_after_s() - 10.0) < 1e-9   # fresh cooldown

    t[0] += 10.0
    assert br.state == "half_open"
    br.note_probe()
    br.record_success()                        # probe succeeded: CLOSES
    assert br.state == "closed" and br.retry_after_s() == 0.0
    assert br.allow_launch()                   # the probe latch cleared
    # and the consecutive count reset with it: one fault stays closed
    assert br.record_fault() is False
    assert br.state == "closed"


def test_engine_sheds_fast_while_breaker_open(tmp_path):
    """A collect fault with threshold=1 opens the breaker; the next
    submit is answered outcome=shed with the cooldown remainder as its
    retry-after hint — within one boundary, no slot burned — and the
    breaker_open count lands in the serve_window."""
    obs.configure(str(tmp_path))
    be = FakeBackend(slots=1, max_length=4, fail_at_launch=1)
    eng = Engine(be, request_timeout_s=30.0, idle_poll_s=0.01,
                 breaker=CircuitBreaker(1, 600.0)).start()
    try:
        r0 = eng.submit([2], max_new_tokens=2, rid="f0").result(timeout=60.0)
        assert r0.outcome == "error", r0
        # the fault both errored the cohort AND opened the breaker (same
        # lock block) — this submit observes the open state
        r1 = eng.submit([2], max_new_tokens=2, rid="f1").result(timeout=60.0)
        assert r1.outcome == "shed", r1
        assert r1.retry_after_s is not None and 0.0 < r1.retry_after_s <= 600.0
        assert eng.status()["breaker"] == "open"
    finally:
        assert eng.drain(timeout=60.0)
    eng.window_roll()
    recs = _validated(str(tmp_path))
    (w,) = [r for r in recs if r["kind"] == "serve_window"]
    assert w["shed"] == 1 and w["breaker_open"] == 1, w
    (shed_rec,) = [r for r in recs if r["kind"] == "request"
                   and r["outcome"] == "shed"]
    assert shed_rec["id"] == "f1" and shed_rec["retry_after_s"] > 0.0


def test_engine_breaker_half_open_probe_recovers():
    """After the cooldown the half-open probe cohort goes through: the
    first non-faulting launch closes the breaker and service resumes."""
    be = FakeBackend(slots=1, max_length=4, fail_at_launch=1)
    eng = Engine(be, request_timeout_s=30.0, idle_poll_s=0.01,
                 breaker=CircuitBreaker(1, 0.05)).start()
    try:
        assert eng.submit([2], max_new_tokens=2,
                          rid="g0").result(timeout=60.0).outcome == "error"
        # sheds during the cooldown answer fast; once half-open, a probe
        # completes and closes the breaker — poll until service resumes
        import time as _time

        deadline = _time.time() + 60.0
        outcome, i = None, 0
        while _time.time() < deadline:
            i += 1
            outcome = eng.submit([2], max_new_tokens=1,
                                 rid=f"g{i}").result(timeout=60.0).outcome
            if outcome == "ok":
                break
            _time.sleep(0.01)
        assert outcome == "ok", outcome
        assert eng.status()["breaker"] == "closed"
    finally:
        assert eng.drain(timeout=60.0)


# ------------------------------------------------------ shed policies


def test_deadline_shed_at_admission_with_measured_etas():
    """shed_policy=deadline: a queued request whose remaining deadline
    the measured prefill+decode estimate can't cover is answered
    outcome=shed AT ADMISSION (no slot wasted, no retry hint — more
    time would not fit the budget either)."""
    be = FakeBackend(slots=2, max_length=16)
    eng = Engine(be, request_timeout_s=0.5, idle_poll_s=0.01,
                 shed_policy="deadline")
    # prime the EMAs as a warmed engine would have measured them:
    # 1s/micro-step makes an 8-token budget a provable 8s > 0.5s miss
    eng._step_ema = 1.0
    eng._prefill_ema = 0.0
    eng.start()
    try:
        res = eng.submit([2], max_new_tokens=8, rid="d0").result(timeout=60.0)
        assert res.outcome == "shed", res
        assert res.retry_after_s is None
        assert res.tokens == []
    finally:
        assert eng.drain(timeout=60.0)


def test_deadline_policy_never_guesses_unmeasured():
    """Before any launch has been measured (step EMA 0) the deadline
    policy must admit normally — shedding on a guess would refuse the
    very first requests of every run."""
    be = FakeBackend(slots=2, max_length=16)
    eng = Engine(be, request_timeout_s=0.5, idle_poll_s=0.01,
                 shed_policy="deadline").start()
    try:
        res = eng.submit([2], max_new_tokens=2, rid="u0").result(timeout=60.0)
        assert res.outcome == "ok", res
    finally:
        assert eng.drain(timeout=60.0)


def test_brownout_caps_budgets_and_sheds_excess_arrivals():
    """Engaged brownout degrades instead of dying: admissions get their
    token budget capped to the brownout share of max_length, and
    arrivals past one full slot wave are shed with a drain-ETA hint."""
    from paddle_tpu.serving.engine import BROWNOUT_BUDGET_SHARE

    be = FakeBackend(slots=1, max_length=8, step_delay_s=0.05)
    eng = Engine(be, request_timeout_s=30.0, idle_poll_s=0.01,
                 shed_policy="brownout")
    # engage the degraded mode directly (the EMA needs sustained
    # boundaries); give the drain-ETA estimator a measured rate
    eng._brownout = True
    eng._pressure_ema = 5.0
    eng._step_ema = 0.05
    eng.start()
    try:
        cap = max(1, int(8 * BROWNOUT_BUDGET_SHARE))
        f0 = eng.submit([2], max_new_tokens=8, rid="b0")   # occupies the slot
        # wait for b0's admission — a brownout shed is queue-depth-based,
        # so the next two submits must observe a settled queue
        import time as _time

        deadline = _time.time() + 30.0
        while eng.status().get("occupancy") != 1:
            assert _time.time() < deadline, eng.status()
            _time.sleep(0.005)
        f1 = eng.submit([2], max_new_tokens=8, rid="b1")   # fills the wave
        f2 = eng.submit([2], max_new_tokens=8, rid="b2")   # past it: shed
        r2 = f2.result(timeout=60.0)
        assert r2.outcome == "shed", r2
        assert r2.retry_after_s is not None and r2.retry_after_s > 0.0
        r0, r1 = f0.result(timeout=60.0), f1.result(timeout=60.0)
        # both admitted requests completed — with the capped budget, not
        # the 8 tokens they asked for (degrade, don't die)
        assert r0.outcome == "ok" and r1.outcome == "ok", (r0, r1)
        assert len(r0.tokens) <= cap and len(r1.tokens) <= cap, (r0, r1)
    finally:
        assert eng.drain(timeout=60.0)


def test_unmeasured_drain_eta_is_a_real_backoff():
    """A brownout shed BEFORE the first collect boundary (step EMA
    unmeasured) must hint a conservative retry-after, not echo the
    20 ms idle poll — a near-zero hint invites the burst right back."""
    from paddle_tpu.serving.engine import UNMEASURED_RETRY_S

    eng = Engine(FakeBackend(slots=1, max_length=8), idle_poll_s=0.02,
                 shed_policy="brownout")
    with eng._lock:
        assert eng._step_ema == 0.0
        assert eng._drain_eta_locked() == UNMEASURED_RETRY_S
        eng._step_ema = 0.05
        eng._prefill_ema = 0.1
        assert eng._drain_eta_locked() > eng.idle_poll_s


def test_journal_replay_bypasses_queue_cap():
    """queue_cap governs NEW arrivals. A restarted server's journal
    replay (submit(replay=True)) re-offers an already-accepted backlog
    that can legitimately exceed the cap (cap + in-flight at the
    crash); capping it would reject-and-done-mark the tail —
    permanently truncating the queue the journal exists to preserve."""
    be = FakeBackend(slots=1, max_length=8, step_delay_s=0.01)
    eng = Engine(be, queue_cap=2, request_timeout_s=30.0,
                 idle_poll_s=0.01)
    eng.start()
    try:
        futs = [eng.submit([2], max_new_tokens=1, rid=f"jr{i}",
                           replay=True)
                for i in range(5)]
        outs = [f.result(timeout=60.0).outcome for f in futs]
        assert outs == ["ok"] * 5, outs
        # the cap still binds fresh arrivals — flood past it
        fresh = [eng.submit([2], max_new_tokens=4, rid=f"nw{i}")
                 for i in range(8)]
        fresh_outs = [f.result(timeout=60.0).outcome for f in fresh]
        assert all(o in ("ok", "rejected") for o in fresh_outs), fresh_outs
        assert fresh_outs.count("rejected") >= 1, fresh_outs
    finally:
        assert eng.drain(timeout=60.0)


def test_unknown_shed_policy_refused_loudly():
    with pytest.raises(ValueError, match="shed policy"):
        Engine(FakeBackend(slots=1), shed_policy="sometimes")


# ------------------------------------------------------------- journal


def test_auto_request_ids_are_incarnation_salted():
    """Id-less stdin lines get pid-salted auto ids: the line counter
    restarts at 0 every incarnation, and a journaled `req-0` from a
    previous run must not make a FRESH id-less request look like a
    duplicate (silently dropped) after a supervised restart."""
    from paddle_tpu.serving.frontend import _parse_line

    doc, err, rid = _parse_line("[1, 2]", 0)
    assert err == "" and doc["id"] == rid == f"req-{os.getpid()}-0", doc
    doc2, _, rid2 = _parse_line('{"prompt": [3], "id": "mine"}', 1)
    assert doc2["id"] == rid2 == "mine"
    # a validation error still answers under the CLIENT's id when one
    # was parseable — a synthetic id is uncorrelatable
    doc3, err3, rid3 = _parse_line('{"prompt": "oops", "id": "bad1"}', 2)
    assert doc3 is None and err3 and rid3 == "bad1"
    doc4, err4, rid4 = _parse_line("{not json", 3)
    assert doc4 is None and err4 and rid4 == f"req-{os.getpid()}-3"


def test_request_journal_at_least_once_contract(tmp_path):
    """Accept is durable and deduping, done-marks clear pending, a
    reloaded journal re-offers exactly the accepted-but-unanswered set
    in acceptance order, and a torn tail line (the crash the journal
    exists for) is tolerated."""
    path = str(tmp_path / "j.jsonl")
    j = RequestJournal(path)
    assert j.accept({"id": "a", "prompt": [1, 2], "max_new_tokens": 3})
    assert j.accept({"id": "b", "prompt": [4], "max_new_tokens": 1})
    assert not j.accept({"id": "a", "prompt": [9]})  # replayed stdin line
    j.answer("a", "ok")
    assert j.is_done("a") and not j.is_done("b")
    assert [d["id"] for d in j.pending()] == ["b"]
    j.close()

    # crash mid-append: the torn tail must not poison the reload
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"op": "acce')
    j2 = RequestJournal(path)
    assert [d["id"] for d in j2.pending()] == ["b"]
    assert j2.pending()[0]["prompt"] == [4]
    assert j2.pending()[0]["max_new_tokens"] == 1
    j2.close()

    # the supervisor's progress fingerprint moves with answered count —
    # and ONLY with it: fresh accepts must not disguise a crash loop
    # that answers nothing as progress
    fp1 = journal_progress(path)
    assert fp1 == "answered:1"
    j3 = RequestJournal(path)
    assert j3.accept({"id": "c", "prompt": [5], "max_new_tokens": 1})
    j3.close()
    assert journal_progress(path) == fp1
    j4 = RequestJournal(path)
    j4.answer("b", "ok")
    j4.close()
    assert journal_progress(path) != fp1
    assert journal_progress(str(tmp_path / "missing.jsonl")) is None


# ------------------------------------------------------- status probe


def test_status_writer_and_serve_status_renderer(tmp_path, capsys):
    """--status_path: the periodic snapshot is atomic and honest (queue
    depth, occupancy, totals, draining), the final stop() snapshot
    carries the draining flag, and `paddle serve-status` renders it
    jax-free (both table and --json)."""
    be = FakeBackend(slots=2, max_length=4)
    eng = Engine(be, request_timeout_s=30.0, idle_poll_s=0.01).start()
    path = str(tmp_path / "health" / "status.json")
    writer = StatusWriter(path, eng, interval_s=0.02)
    writer.write_now()
    doc = json.load(open(path))
    assert doc["started"] and not doc["draining"]
    assert doc["queue_depth"] == 0 and doc["slots"] == 2
    assert doc["breaker"] == "disabled" and doc["shed_policy"] == "off"
    assert eng.submit([2], max_new_tokens=1,
                      rid="s0").result(timeout=60.0).outcome == "ok"
    assert eng.drain(timeout=60.0)
    writer.stop()          # final snapshot after the drain
    doc = json.load(open(path))
    assert doc["draining"] is True
    assert doc["totals"]["ok"] == 1

    assert status_main([path]) == 0
    out = capsys.readouterr().out
    assert "draining" in out and "queue depth" in out
    assert status_main([path, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["totals"]["ok"] == 1
    assert status_main([str(tmp_path / "nope.json")]) == 1

    # degraded snapshots render LOUDLY, not as a blank 'not started'
    # table: stale = the engine's bounded-lock timeout fired (scheduler
    # busy or wedged), error = the probe itself failed
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps({"stale": True, "detail": "lock busy"}))
    assert status_main([str(stale)]) == 0
    out = capsys.readouterr().out
    assert "STALE" in out and "lock busy" in out
    assert "not started" not in out
    broken = tmp_path / "broken.json"
    broken.write_text(json.dumps({"error": "probe exploded"}))
    assert status_main([str(broken)]) == 1
    assert "probe exploded" in capsys.readouterr().out


# ------------------------------------- supervise --supervise_job=serve


def _no_sleep(_s):
    pass


def test_supervisor_serve_child_cmd_keeps_args_verbatim():
    """A serve child's restart command is `paddle serve` with the user
    args kept verbatim: no --init_model_path=auto injection (the
    journal, not a checkpoint, is the resume state) and the
    supervisor-only --supervise_job stripped."""
    flags = _Flags(supervise_job="serve", serve_journal_path="/tmp/j.jsonl")
    sup = Supervisor(
        ["--config=c.py", "--supervise_job=serve",
         "--serve_journal_path=/tmp/j.jsonl"], flags,
    )
    first = sup.child_cmd(restart=False)
    again = sup.child_cmd(restart=True)
    assert first[-3:] == ["serve", "--config=c.py",
                          "--serve_journal_path=/tmp/j.jsonl"], first
    assert again == first, (first, again)
    assert not any("supervise_job" in a for a in first)
    assert not any("init_model_path" in a for a in again)


def test_supervisor_serve_probe_reads_journal_progress(tmp_path):
    """The serve child's crash-loop probe fingerprints the journal's
    answered count — None without a journal (every death then looks
    loop-like, which errs toward stopping)."""
    jpath = str(tmp_path / "j.jsonl")
    j = RequestJournal(jpath)
    j.accept({"id": "x", "prompt": [1], "max_new_tokens": 1})
    j.close()
    flags = _Flags(supervise_job="serve", serve_journal_path=jpath,
                   supervise_dir=str(tmp_path / "sup"))
    sup = Supervisor(["--config=c.py"], flags)
    assert sup.job == "serve"
    assert sup._probe() == "answered:0"
    flags2 = _Flags(supervise_job="serve",
                    supervise_dir=str(tmp_path / "sup2"))
    assert Supervisor(["--config=c.py"], flags2)._probe() is None


def test_supervisor_serve_exit20_consumes_budget_then_recovers(tmp_path):
    """An OOM death (exit 20) of a serve child is charged to the
    restart budget — never free — but within budget the child restarts
    and a clean second run ends the supervision with rc 0."""
    jpath = str(tmp_path / "j.jsonl")
    script = (
        "import json, os, sys\n"
        "counter, journal = sys.argv[1], sys.argv[2]\n"
        "n = int(open(counter).read()) if os.path.exists(counter) else 0\n"
        "open(counter, 'w').write(str(n + 1))\n"
        "with open(journal, 'a') as f:\n"
        "    f.write(json.dumps({'op': 'accept', 'id': f'r{n}'}) + '\\n')\n"
        "    f.write(json.dumps({'op': 'done', 'id': f'r{n}',\n"
        "                        'outcome': 'ok'}) + '\\n')\n"
        "sys.exit(20 if n == 0 else 0)\n"
    )
    flags = _Flags(supervise_job="serve", serve_journal_path=jpath,
                   supervise_dir=str(tmp_path / "sup"),
                   restart_budget=1, crash_loop_threshold=3)
    sup = Supervisor(
        ["--config=unused.py"], flags,
        child_cmd=[sys.executable, "-c", script,
                   str(tmp_path / "counter"), jpath],
        sleep=_no_sleep,
    )
    assert sup.run() == 0
    assert [a["exit_code"] for a in sup.attempts] == [EXIT_OOM, 0]
    assert not os.path.exists(os.path.join(str(tmp_path / "sup"),
                                           CRASH_REPORT))

    # the same death with ZERO budget is terminal: OOM never rides free
    flags0 = _Flags(supervise_job="serve",
                    supervise_dir=str(tmp_path / "sup0"),
                    restart_budget=0, crash_loop_threshold=3)
    sup0 = Supervisor(
        ["--config=unused.py"], flags0,
        child_cmd=[sys.executable, "-c", "import sys; sys.exit(20)"],
        sleep=_no_sleep,
    )
    assert sup0.run() == EXIT_OOM
    report = json.load(open(os.path.join(str(tmp_path / "sup0"),
                                         CRASH_REPORT)))
    assert report["reason"] == "restart_budget_exhausted"


# ----------------------------------------------------- compare rates


def test_compare_shed_and_error_rates_lower_is_better(tmp_path):
    """Per-rung shed_rate/error_rate growth is a serving REGRESSION —
    and an artifact that PREDATES the fields (no shed_rate key) still
    joins: the old side zero-fills, so 0 -> N growth is judged instead
    of landing invisibly in only_b."""
    from paddle_tpu.observability.compare import compare, load_side

    def artifact(name, rung_extra):
        p = tmp_path / name
        rung = {"offered_rps": 50.0, "p50_ms": 2.0, "p99_ms": 4.0,
                "goodput_tok_s": 5000.0}
        rung.update(rung_extra)
        p.write_text(json.dumps({
            "metric": "serve_cpu_smoke_goodput_tokens_per_sec",
            "value": 5000.0, "unit": "tokens/s",
            "rungs": [rung],
        }))
        return str(p)

    old = artifact("old.json", {})                       # pre-PR-15 shape
    new = artifact("new.json", {"shed_rate": 0.25, "error_rate": 0.1})
    doc = compare(load_side(old), load_side(new))
    by = {m["metric"]: m["verdict"] for m in doc["metrics"]}
    assert by["serve.50rps.shed_rate"] == "REGRESSION", by
    assert by["serve.50rps.error_rate"] == "REGRESSION", by
    assert doc["verdict"] == "REGRESSION"
    strays = [k for k in list(doc.get("only_a") or []) +
              list(doc.get("only_b") or []) if "rate" in str(k)]
    assert not strays, strays
    # and shrinking rates read as improvement, not regression
    doc2 = compare(load_side(new), load_side(old))
    by2 = {m["metric"]: m["verdict"] for m in doc2["metrics"]}
    assert by2["serve.50rps.shed_rate"] == "IMPROVED", by2


# ------------------------------------------------------ overload A/B


def test_ab_overload_shed_on_vs_off(tmp_path, monkeypatch):
    """THE overload A/B (ISSUE 15 acceptance): the serve ladder at
    3x/6x measured capacity with a deadline that bites, shedding on vs
    off. The STABLE mechanical wins are asserted from the live run —
    deep-overload timeouts convert to sheds (a doomed request is
    answered outcome=shed well before its deadline instead of wasting
    a slot and timing out), the completed-request tail does not get
    worse, and the live artifacts' 0 -> N shed_rate growth is flagged
    by the like-for-like compare. The verdict-IMPROVED compare contract
    itself is pinned deterministically in
    test_compare_shed_ab_verdict_improved_with_abs_floor: at CPU smoke
    scale the sub-100ms percentiles jitter across containers by more
    than the policy's real latency win, so asserting the live verdict
    would pin a coin flip, not the contract."""
    from paddle_tpu.observability import compare

    monkeypatch.delenv("PADDLE_TPU_BENCH_METRICS_DIR", raising=False)
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_REQUESTS", "64")
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_MIXED_LEN", "1")
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_SEED", "0")
    # the serial loop, like the static-vs-continuous knee A/B: the
    # overload signal should measure the SHED POLICY, not pipelined
    # scheduler jitter in a 64-sample tail
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_PIPELINE", "off")
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    # the deadline must BITE at overload (it is what the off side burns
    # and the deadline policy defends) — 80ms against a ~4ms/req service
    kw = dict(B=4, T=8, vocab=1000, dim=128, beam_size=1, max_length=64,
              dtype="float32", timeout_s=0.08)
    # quick calibration pass, then pin the overload ladder off it
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_DIR", str(tmp_path / "cal"))
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_RATES", "1.0")
    _, cal = bench.bench_serve(engine="continuous", n_requests=1, **kw)
    cap = cal["capacity_rps"]
    rates = ",".join(str(round(f * cap, 4)) for f in (3.0, 6.0))
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_RATES", rates)

    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_DIR", str(tmp_path / "off"))
    v_off, e_off = bench.bench_serve(engine="continuous", **kw)
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_SHED", "deadline")
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_DIR", str(tmp_path / "on"))
    v_on, e_on = bench.bench_serve(engine="continuous", **kw)
    obs.configure("")

    assert e_on["shed_policy"] == "deadline"
    assert "shed_policy" not in e_off
    assert sum(r["shed"] for r in e_on["rungs"]) > 0, e_on["rungs"]
    assert all(r["shed"] == 0 for r in e_off["rungs"]), e_off["rungs"]

    # the conversion: at 6x the off side burns its deadline on doomed
    # requests; the deadline policy sheds them at admission instead
    off6, on6 = e_off["rungs"][-1], e_on["rungs"][-1]
    assert off6["timeouts"] > 0, off6
    assert on6["shed"] > 0, on6
    assert on6["timeouts"] <= off6["timeouts"] // 2, (off6, on6)
    # and the completed-request tail did not get worse for it
    assert on6["p99_ms"] <= off6["p99_ms"] * 1.25, (off6, on6)

    # every shed was ANSWERED well before the deadline it could not
    # have met — the client hears "shed, don't wait" instead of
    # burning its own 80ms
    recs = _validated(str(tmp_path / "on"))
    sheds = [r for r in recs if r["kind"] == "request"
             and r["outcome"] == "shed"]
    assert sheds
    assert all(r["t_shed"] - r["t_enqueue"] < 0.08 for r in sheds), sheds

    # the live artifacts join, and WITHOUT an abs-floor the deliberate
    # shed growth is flagged — the like-for-like guard (satellite:
    # growth => REGRESSION) bites on real sweeps
    a, b = tmp_path / "A.json", tmp_path / "B.json"
    metric = "serve_cpu_smoke_goodput_tokens_per_sec"
    a.write_text(json.dumps(dict(metric=metric, value=round(v_off, 1),
                                 **e_off)))
    b.write_text(json.dumps(dict(metric=metric, value=round(v_on, 1),
                                 **e_on)))
    doc = compare.compare(compare.load_side(str(a)),
                          compare.load_side(str(b)), threshold=0.2)
    assert any("shed_rate" in m for m in doc["regressions"]), doc
    strays = [k for k in list(doc["only_a"]) + list(doc["only_b"])
              if "shed_rate" in str(k) or "error_rate" in str(k)]
    assert not strays, strays


def test_compare_shed_ab_verdict_improved_with_abs_floor(tmp_path):
    """The compare half of the overload A/B contract, pinned
    deterministically: a shed-on sweep whose completed-request p99
    improved lands verdict IMPROVED when the deliberate 0 -> N
    shed_rate is absorbed via --abs-floor (which only applies to
    zero-baseline metrics — the latency rows are judged normally), and
    REGRESSION without the floor (the like-for-like guard)."""
    from paddle_tpu.observability import compare

    def artifact(name, p99, shed_rate):
        p = tmp_path / name
        p.write_text(json.dumps({
            "metric": "serve_cpu_smoke_goodput_tokens_per_sec",
            "value": 5000.0, "unit": "tokens/s",
            "rungs": [{"offered_rps": 300.0, "p50_ms": 20.0, "p99_ms": p99,
                       "goodput_tok_s": 5000.0, "shed_rate": shed_rate,
                       "error_rate": 0.0}],
        }))
        return str(p)

    off = artifact("off.json", 120.0, 0.0)
    on = artifact("on.json", 40.0, 0.3)
    doc = compare.compare(compare.load_side(off), compare.load_side(on),
                          threshold=0.2, abs_floor=1.0)
    assert doc["verdict"] == "IMPROVED", doc
    assert "serve.300rps.p99_ms" in doc["improvements"], doc
    doc2 = compare.compare(compare.load_side(off), compare.load_side(on),
                           threshold=0.2)
    assert doc2["verdict"] == "REGRESSION", doc2
    assert "serve.300rps.shed_rate" in doc2["regressions"], doc2


# ------------------------------------------------------- chaos e2e


SERVE_CONFIG = """
import sys
sys.path.insert(0, {demo!r})
from paddle.trainer_config_helpers import *
from seqToseq_net import gru_encoder_decoder

settings(batch_size=2, learning_rate=1e-3, learning_method=AdamOptimizer())
gru_encoder_decoder(source_dict_dim=50, target_dict_dim=50,
                    is_generating=True, word_vector_dim=16,
                    encoder_size=16, decoder_size=16, beam_size=1,
                    max_length=6)
"""

SUBPROC_ENV = dict(
    os.environ, JAX_PLATFORMS="cpu",
    PYTHONPATH=f"{REPO}:{os.path.join(REPO, 'compat')}",
)


def _serve_cfg(tmp_path):
    cfg = tmp_path / "serve_conf.py"
    cfg.write_text(SERVE_CONFIG.format(
        demo=os.path.join(REPO, "demo", "seqToseq")))
    return cfg


@pytest.mark.chaos
def test_chaos_serve_oom_premortem_exit20(tmp_path):
    """An injected serve.oom (synthetic RESOURCE_EXHAUSTED at the 2nd
    collect boundary) gets the trainer's treatment: oom_report.json in
    the run dir and exit EXIT_OOM=20 — not a raw crash."""
    cfg = _serve_cfg(tmp_path)
    run_dir = tmp_path / "run"
    reqs = "\n".join(json.dumps(
        {"id": f"o{i}", "prompt": [4 + i, 7], "max_new_tokens": 4}
    ) for i in range(2))
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "serve",
         f"--config={cfg}", "--use_tpu=0", "--serve_slots=2",
         "--serve_prompt_tokens=4", "--serve_decode_block=1",
         f"--metrics_path={run_dir}",
         "--fault_spec=serve.oom=raise@2"],
        input=reqs + "\n", capture_output=True, text=True, timeout=300,
        env=SUBPROC_ENV,
    )
    assert out.returncode == EXIT_OOM, (out.returncode, out.stderr[-3000:])
    report = json.load(open(run_dir / "oom_report.json"))
    assert "RESOURCE_EXHAUSTED" in report["error"], report["error"]


@pytest.mark.chaos
def test_chaos_serve_stall_hangwatch_exit19_with_forensics(tmp_path):
    """An injected serve.stall wedges the 2nd decode collect; the
    --serve_hang_timeout hangwatch dumps serve_hang_report.json — with
    thread stacks AND the in-flight cohort snapshot — and exits 19.
    The --status_path probe file exists and parses."""
    cfg = _serve_cfg(tmp_path)
    run_dir = tmp_path / "run"
    status = tmp_path / "status.json"
    reqs = "\n".join(json.dumps(
        {"id": f"h{i}", "prompt": [4 + i, 7], "max_new_tokens": 4}
    ) for i in range(2))
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "serve",
         f"--config={cfg}", "--use_tpu=0", "--serve_slots=2",
         "--serve_prompt_tokens=4", "--serve_decode_block=1",
         f"--metrics_path={run_dir}", f"--status_path={status}",
         "--serve_hang_timeout=2",
         "--fault_spec=serve.stall=sleep:3600@2"],
        input=reqs + "\n", capture_output=True, text=True, timeout=300,
        env=SUBPROC_ENV,
    )
    assert out.returncode == EXIT_HANG, (out.returncode, out.stderr[-3000:])
    # the wedged cohort's outcome=error answers were FLUSHED to stdout
    # before the exit (the hangwatch's answer_flush hook) — without a
    # journal these lines are the only answer the client will ever get
    answers = {d["id"]: d for d in
               (json.loads(l) for l in out.stdout.splitlines()
                if l.strip().startswith("{")) if "outcome" in d}
    assert set(answers) == {"h0", "h1"}, (answers, out.stderr[-2000:])
    assert all(d["outcome"] == "error" and "hang" in d.get("error", "")
               for d in answers.values()), answers
    report = json.load(open(run_dir / SERVE_HANG_REPORT))
    assert report["reason"] == "serve_hang"
    assert report["threads"], "no thread stacks in the forensics"
    # the in-flight cohort snapshot: the wedged requests are NAMED
    inflight = report["inflight"]
    slotted = [s["rid"] for s in inflight["slots"] if s]
    assert slotted, inflight
    assert set(slotted) <= {"h0", "h1"}, inflight
    assert json.load(open(status))["started"] is True


@pytest.mark.chaos
def test_chaos_serve_stall_supervised_restart_answers_journal(tmp_path):
    """THE acceptance scenario (ISSUE 15): under an injected
    serve.stall, `paddle supervise --supervise_job=serve` sees the
    child's hangwatch produce serve_hang_report.json + exit 19,
    restarts the server, and the request journal re-offers every
    accepted-but-unanswered request — every request id is answered
    (at-least-once, deduped by id), none twice within an incarnation,
    zero stranded futures, and the supervision ends rc 0.

    8 requests x 2 slots x budget 2 = 8 collect boundaries in run 1;
    the stall at boundary 7 wedges the last cohort. Run 2 replays only
    the unanswered tail (at most one wave short of 7 boundaries even if
    every done-mark was lost), so the same fault spec never re-fires."""
    cfg = _serve_cfg(tmp_path)
    save_dir = tmp_path / "out"
    sup_dir = tmp_path / "sup"
    jpath = tmp_path / "journal.jsonl"
    ids = [f"j{i}" for i in range(8)]
    reqs = "\n".join(json.dumps(
        {"id": rid, "prompt": [4 + i, 7], "max_new_tokens": 2}
    ) for i, rid in enumerate(ids))
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "supervise",
         "--supervise_job=serve",
         f"--config={cfg}", "--use_tpu=0", "--serve_slots=2",
         "--serve_prompt_tokens=4", "--serve_decode_block=1",
         f"--save_dir={save_dir}", f"--supervise_dir={sup_dir}",
         f"--serve_journal_path={jpath}",
         f"--compile_cache_dir={tmp_path / 'ccache'}",
         "--serve_hang_timeout=3", "--restart_base_delay=0.01",
         "--fault_spec=serve.stall=sleep:3600@7"],
        input=reqs + "\n", capture_output=True, text=True, timeout=600,
        env=SUBPROC_ENV, cwd=str(tmp_path),
    )
    assert out.returncode == 0, (out.returncode, out.stderr[-3000:])
    # the hang was diagnosed, not silent: forensics + exactly 2 attempts
    report = json.load(open(save_dir / SERVE_HANG_REPORT))
    assert report["reason"] == "serve_hang"
    logs = sorted(n for n in os.listdir(sup_dir)
                  if n.startswith("attempt-"))
    assert logs == ["attempt-000.log", "attempt-001.log"], logs

    def results(log_name):
        out = {}
        for line in open(sup_dir / log_name):
            line = line.strip()
            if not line.startswith("{"):
                continue
            doc = json.loads(line)
            if "outcome" in doc and doc.get("id") in ids:
                assert doc["id"] not in out, (
                    f"{doc['id']} answered twice in {log_name}")
                out[doc["id"]] = doc["outcome"]
        return out

    first, second = results(logs[0]), results(logs[1])
    # every journaled request is answered across the incarnations —
    # dedupe by id is the at-least-once contract; zero stranded futures
    assert set(first) | set(second) == set(ids), (first, second)
    # the wedged cohort either heard "the server hung" (outcome=error
    # answered by the hangwatch just before exit 19) or was re-offered
    # by the journal and answered ok by the restarted server; requests
    # the first incarnation never answered MUST all come back ok
    unanswered = set(ids) - set(first)
    assert unanswered <= set(second), (unanswered, second)
    assert all(second[rid] == "ok" for rid in unanswered), second
    assert all(o == "ok" for o in first.values()
               if o not in ("error",)), first
    if unanswered:
        # the restart reported the replay it performed
        assert any("re-offering" in open(sup_dir / l).read()
                   for l in logs) or "re-offering" in out.stderr, (
            "restart did not report the journal replay")
    # and the journal itself holds every accept
    accepted = {json.loads(l)["id"] for l in open(jpath)
                if l.strip() and json.loads(l).get("op") == "accept"}
    assert accepted == set(ids)
    assert not os.path.exists(sup_dir / CRASH_REPORT)
