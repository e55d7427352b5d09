"""Serving telemetry (doc/observability.md "Serving telemetry"):
request/serve_window schema + driver determinism, saturation behavior,
the `paddle serve-report` analyzer with its roofline join, `--follow`
on serve streams, `paddle compare` serve-artifact semantics, the
embedding API's request records, and the CPU `bench.py serve` e2e
smoke (the acceptance path: a run dir serve-report can render with
recompiles=0 after warmup)."""

import json
import os
import sys

import numpy as np
import pytest

from paddle_tpu.observability import metrics as obs
from paddle_tpu.observability import serving
from paddle_tpu.observability.analyze import analyze, follow, load_run

pytestmark = pytest.mark.serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    obs.registry().reset()
    yield
    obs.configure("")


def _fixed_launch(service_s=0.05, tokens=5):
    """Deterministic injected service time: the rung becomes a pure
    function of the seed (the determinism contract under test)."""

    def launch(requests):
        return [tokens] * len(requests), service_s

    return launch


def _validated_records(run_dir):
    recs = [r for recs in load_run(run_dir).values() for r in recs]
    assert recs, f"no records under {run_dir}"
    for rec in recs:
        assert not obs.validate_record(rec), (rec, obs.validate_record(rec))
    return recs


# ------------------------------------------------------------- schedule


def test_arrival_schedule_deterministic_and_rate_shaped():
    a = serving.arrival_offsets(500, 20.0, seed=3)
    b = serving.arrival_offsets(500, 20.0, seed=3)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, serving.arrival_offsets(500, 20.0, seed=4))
    # offsets are cumulative (sorted) and the mean inter-arrival matches
    # the offered rate to sampling noise
    assert np.all(np.diff(a) >= 0)
    assert abs(np.diff(a, prepend=0.0).mean() - 1 / 20.0) < 0.01


def test_same_seed_same_cohort_assignment():
    def run():
        _, reqs = serving.run_rung(
            _fixed_launch(0.03), rate_rps=100.0, n_requests=60, seed=11,
            max_batch=4, timeout_s=10.0,
        )
        return [(r.rid, r.cohort, r.cohort_size, r.outcome,
                 round(r.t_enqueue, 9), round(r.t_admit, 9)) for r in reqs]

    first, second = run(), run()
    assert first == second
    # the load is high enough that cohorts actually batch (the test
    # would pass vacuously if every cohort had one request)
    assert any(c[2] > 1 for c in first)


def test_saturation_rejects_timeouts_and_queue_wait_dominate(tmp_path):
    obs.configure(str(tmp_path))
    summary, reqs = serving.run_rung(
        _fixed_launch(0.5), rate_rps=1000.0, n_requests=40, seed=5,
        max_batch=4, timeout_s=2.0, queue_cap=20, beam_size=2,
    )
    outcomes = {r.outcome for r in reqs}
    assert "rejected" in outcomes and "timeout" in outcomes
    assert summary["rejected"] > 0 and summary["timeouts"] > 0
    # offered load >> capacity: completed requests spent most of their
    # end-to-end time waiting in the queue
    assert summary["queue_wait_share"] > 0.5
    recs = _validated_records(str(tmp_path))
    by_kind = {}
    for r in recs:
        by_kind.setdefault(r["kind"], []).append(r)
    assert len(by_kind["request"]) == 40  # every arrival leaves evidence
    assert {r["outcome"] for r in by_kind["request"]} == {
        "ok", "rejected", "timeout"
    }
    ok = [r for r in by_kind["request"] if r["outcome"] == "ok"]
    for r in ok:
        assert r["ttft_s"] == pytest.approx(r["queue_wait_s"] + r["decode_s"])
        assert r["cohort_size"] >= 1 and r["beam_size"] == 2
    (w,) = by_kind["serve_window"]
    assert w["arrived"] == 40
    assert w["completed"] == len(ok)
    assert w["latency"]["count"] == len(ok)
    # admitted = joined a cohort: rejected/timed-out requests never were
    assert w["admitted"] == len(ok)
    assert w["admitted"] < w["arrived"]


def test_expired_queue_entries_free_capped_slots():
    """An entry that expired before a later arrival must not occupy a
    capped queue slot: queue_cap=1, timeout 2s, 10s launches — request B
    (t=1) expires at t=3, so D (t=8) gets B's slot instead of a
    spurious rejection."""
    arrivals = iter([0.0, 1.0, 8.0])

    def sched(n, rate, seed):
        return np.array([next(arrivals) for _ in range(n)])

    real = serving.arrival_offsets
    serving.arrival_offsets = sched
    try:
        summary, reqs = serving.run_rung(
            _fixed_launch(10.0), rate_rps=1.0, n_requests=3, seed=0,
            max_batch=1, timeout_s=2.0, queue_cap=1,
        )
    finally:
        serving.arrival_offsets = real
    by = {r.rid: r.outcome for r in reqs}
    assert by["r0-0"] == "ok"       # admitted immediately
    assert by["r0-1"] == "timeout"  # expired at t=3 waiting out launch 1
    assert by["r0-2"] == "ok"       # took the freed slot — NOT rejected
    assert summary["rejected"] == 0 and summary["timeouts"] == 1
    assert summary["admitted"] == 2


def test_request_and_serve_window_schema_registration():
    assert "request" in obs.FLUSH_KINDS and "serve_window" in obs.FLUSH_KINDS
    base = {"v": obs.SCHEMA_VERSION, "host": 0, "t": 0.0}
    assert obs.validate_record(dict(base, kind="request", id="r0", outcome="ok")) == []
    missing = obs.validate_record(dict(base, kind="request"))
    assert any("id" in p for p in missing) and any("outcome" in p for p in missing)
    assert obs.validate_record(
        dict(base, kind="serve_window", rung=0, offered_rps=1.0,
             engine="static")
    ) == []
    # the engine stamp became REQUIRED with the continuous engine: two
    # engines' rungs in one stream must never be mistaken for one ladder
    missing = obs.validate_record(
        dict(base, kind="serve_window", rung=0, offered_rps=1.0))
    assert any("engine" in p for p in missing)
    assert obs.validate_record(dict(base, kind="serve_window", rung=0))
    # a non-int rung is junk the analyzers must be able to SKIP (the
    # sort keys mix rungs across hosts), not crash on
    assert obs.validate_record(
        dict(base, kind="serve_window", rung="2", offered_rps=1.0)
    )


def test_saturation_knee_is_contiguous():
    """A rung that passes ABOVE a demonstrated failure (sampling luck)
    must not overstate capacity: the knee scan stops at the first
    saturated rung."""
    def rung(rate, completed, p99):
        return {"offered_rps": rate, "arrived": 100, "completed": completed,
                "latency": {"p99": p99}}

    assert serving.saturation_knee(
        [rung(10, 100, 0.01), rung(20, 100, 0.02), rung(40, 50, 0.5)]
    ) == 20
    # 20 req/s fails the completion bar; 40 passing cannot revive it
    assert serving.saturation_knee(
        [rung(10, 100, 0.01), rung(20, 98, 0.02), rung(40, 100, 0.02)]
    ) == 10
    assert serving.saturation_knee([rung(10, 50, 0.5)]) is None


# --------------------------------------------------------- serve-report


def _write_serve_fixture(run_dir, *, recompiles=0, host_share=0.1,
                         exec_per_launch=0.05):
    """A 3-rung serve run with compile/roofline joins; every record is
    validate_record-checked before it lands (the golden fixtures must
    obey the same schema the live driver does)."""
    w = obs.MetricsWriter(run_dir, host=0)
    real_emit = w.emit

    def emit(kind, **fields):
        real_emit(kind, **fields)
        rec = {"v": obs.SCHEMA_VERSION, "kind": kind, "host": 0, "t": 0.0,
               **fields}
        assert not obs.validate_record(rec), obs.validate_record(rec)

    emit("compile", group=serving.SERVE_GROUP, sig="cafe01",
         recompiles=recompiles, trace_s=0.1, compile_s=0.4,
         flops=8.0e6, bytes_accessed=1.0e5)
    for rung, (rate, p50, p99, wait_share, occ, goodput) in enumerate([
        (10.0, 0.010, 0.020, 0.05, 2.0, 900.0),
        (40.0, 0.020, 0.050, 0.30, 3.5, 3200.0),
        (160.0, 0.200, 0.800, 0.85, 4.0, 3900.0),
    ]):
        snap = lambda v: {"count": 30, "mean": v, "p50": p50, "p99": p99,
                          "max": p99}
        emit("serve_window", rung=rung, offered_rps=rate, engine="static",
             window_s=3.0,
             arrived=30, admitted=30 if rung < 2 else 24,
             completed=30 if rung < 2 else 24,
             rejected=0 if rung < 2 else 4, timeouts=0 if rung < 2 else 2,
             errors=0, launches=10, exec_s=exec_per_launch * 10,
             gen_tokens=int(goodput * 3), goodput_tok_s=goodput,
             completed_rps=10.0, queue_wait_share=wait_share,
             host_share=host_share, latency=snap(p50), ttft=snap(p50),
             queue_wait=snap(p50 * wait_share),
             queue_depth={"count": 10, "mean": 2.0, "p50": 2, "p99": 6,
                          "max": 8},
             occupancy={"count": 10, "mean": occ, "p50": occ, "p99": occ,
                        "max": occ})
        for i in range(3):  # a few request records per rung
            emit("request", id=f"r{rung}-{i}", rung=rung, outcome="ok",
                 cohort=i, cohort_size=4, beam_size=3, prompt_tokens=8,
                 gen_tokens=12, t_enqueue=0.0, t_admit=0.01,
                 t_first_token=0.02, t_finish=0.02, queue_wait_s=0.01,
                 ttft_s=0.02, decode_s=0.01, e2e_s=0.02)
    emit("roofline", group=serving.SERVE_GROUP, sig="cafe01", launches=30,
         batches=30, exec_s=exec_per_launch * 30, flops_per_launch=8.0e6,
         bytes_per_launch=1.0e5, device_kind="TPU v4")
    emit("run_end", status="completed")
    w.flush()


def test_serve_report_golden_table(tmp_path, capsys):
    _write_serve_fixture(str(tmp_path))
    assert serving.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    # >= 3 rungs with latency/ttft/queue-wait/occupancy/goodput columns
    for frag in ("rung", "offered r/s", "p50 ms", "p99 ms", "ttft p50",
                 "q-wait", "occ", "goodput tok/s", "bound"):
        assert frag in out
    rows = [ln for ln in out.splitlines()
            if ln.strip().startswith(("0 ", "1 ", "2 "))]
    assert len(rows) == 3
    assert "  10.00" in rows[0] and " 160.00" in rows[2]
    assert "85.0%" in rows[2]  # queue-wait share of the saturated rung
    assert "recompiles after warmup: 0" in out
    # TPU v4 intensity 80 FLOP/B < ridge -> memory-bound via the
    # roofline join (host_share low, launches above the dispatch floor)
    assert "memory-bound" in out
    # rung 2 drops completions and blows past 5x p99: knee is rung 1
    assert "saturation knee: 40.00 req/s" in out


def test_serve_report_flags_recompiles_and_bound_overrides(tmp_path, capsys):
    _write_serve_fixture(str(tmp_path), recompiles=2, host_share=0.9,
                         exec_per_launch=0.001)
    assert serving.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "recompiles after warmup: 2" in out
    assert "signature instability" in out
    assert "host-bound" in out  # host_share > 0.5 beats everything

    # dispatch floor: host share low, launches at ~1ms -> dispatch-bound
    assert serving.classify_rung(
        {"host_share": 0.1, "launches": 10, "exec_s": 0.01},
        {"intensity": 80.0, "device_kind": "TPU v4"},
    ) == "dispatch-bound"


def test_serve_report_rejects_non_serve_dir(tmp_path, capsys):
    w = obs.MetricsWriter(str(tmp_path), host=0)
    w.emit("pass_end", pass_id=0, samples=8)
    w.flush()
    assert serving.main([str(tmp_path)]) == 1
    assert "no serve_window records" in capsys.readouterr().err


def test_metrics_analyzer_recognizes_serve_runs(tmp_path):
    _write_serve_fixture(str(tmp_path))
    doc = analyze(load_run(str(tmp_path)))
    assert doc["serve"] == {"requests": 9, "windows": 3, "rungs": 3}
    assert len(doc["serve_windows"]) == 3
    # a rerun appending to the same run dir re-emits the same request
    # ids and rungs: counts stay latest-wins, never 2x
    _write_serve_fixture(str(tmp_path))
    doc = analyze(load_run(str(tmp_path)))
    assert doc["serve"] == {"requests": 9, "windows": 3, "rungs": 3}
    from paddle_tpu.observability.analyze import _fmt_table

    table = _fmt_table(doc)
    assert "serve telemetry: 9 request record(s)" in table
    assert "paddle serve-report" in table


def test_rerun_with_shorter_ladder_leaves_no_ghost_rungs(tmp_path):
    """A new run_start supersedes the host's earlier serve telemetry
    wholesale — a previous 3-rung sweep must not leak rung 2 into a
    later 1-rung sweep's report/knee/compare."""
    _write_serve_fixture(str(tmp_path))  # 3 rungs
    w = obs.MetricsWriter(str(tmp_path), host=0)  # new epoch: run_start
    w.emit("serve_window", rung=0, offered_rps=5.0, engine="static",
           window_s=1.0,
           arrived=4, admitted=4, completed=4, rejected=0, timeouts=0,
           errors=0, launches=2, exec_s=0.1, gen_tokens=40,
           goodput_tok_s=40.0,
           latency={"count": 4, "mean": 0.01, "p50": 0.01, "p99": 0.02,
                    "max": 0.02})
    w.emit("run_end", status="completed")
    w.flush()
    doc = analyze(load_run(str(tmp_path)))
    assert doc["serve"]["windows"] == 1 and doc["serve"]["rungs"] == 1
    assert doc["serve_windows"][0]["offered_rps"] == 5.0


def test_epoch_reset_covers_run_end_and_compile_joins(tmp_path):
    """The run_start epoch reset is wholesale: a crashed rerun is NOT
    reported completed on the strength of the previous epoch's run_end,
    and a previous sweep's recompile does not flag signature
    instability on a clean rerun."""
    _write_serve_fixture(str(tmp_path), recompiles=2)  # epoch 1: dirty
    w = obs.MetricsWriter(str(tmp_path), host=0)  # epoch 2 begins
    w.emit("compile", group=serving.SERVE_GROUP, sig="beef02",
           recompiles=0, trace_s=0.1, compile_s=0.2)
    w.emit("request", id="e2-0", rung=0, outcome="ok")
    w.flush()  # killed mid-rung: no serve_window, no run_end
    doc = analyze(load_run(str(tmp_path)))
    assert not doc["run_ended"]
    assert any("run_end" in warning for warning in doc["warnings"])
    sdoc = serving.serve_doc(load_run(str(tmp_path)))
    assert sdoc["compiles"] == 1 and sdoc["recompiles"] == 0
    # epoch 3 is oneshot-only (rung -1): the crashed epoch-2 driver is
    # superseded and this stream owes no run_end — no crash warning
    w3 = obs.MetricsWriter(str(tmp_path), host=0)
    w3.emit("request", id="e3-0", rung=-1, outcome="ok")
    w3.flush()
    doc = analyze(load_run(str(tmp_path)))
    assert not any("run_end" in warning for warning in doc["warnings"])


def test_failed_launch_leaves_error_records_and_partial_window(tmp_path):
    """A raising launch_fn must not take its cohort's evidence with it:
    terminal outcome=error records (with the failing launch's measured
    seconds) and the partial serve_window land before the re-raise."""
    obs.configure(str(tmp_path))
    calls = []

    def flaky(requests):
        calls.append(len(requests))
        if len(calls) >= 2:
            raise RuntimeError("device fell over")
        return [3] * len(requests), 0.01

    with pytest.raises(RuntimeError):
        serving.run_rung(flaky, rate_rps=500.0, n_requests=12, seed=2,
                         max_batch=4, timeout_s=10.0)
    recs = _validated_records(str(tmp_path))
    reqs = [r for r in recs if r["kind"] == "request"]
    errs = [r for r in reqs if r["outcome"] == "error"]
    assert errs and all(r["service_s"] >= 0 for r in errs)
    assert all("cohort" in r for r in errs)
    (w,) = [r for r in recs if r["kind"] == "serve_window"]
    assert w["errors"] == len(errs)
    assert w["completed"] == len(reqs) - len(errs)


# --------------------------------------------------------------- follow


def test_metrics_follow_tails_serve_stream_until_run_end(tmp_path):
    """Mirror of the PR-7 follow test for serve runs: request and
    serve_window records stream live, torn tails stay buffered, and the
    serve driver's run_end ends the tail."""
    run_dir = str(tmp_path)
    w = obs.MetricsWriter(run_dir, host=0)
    w.emit("request", id="r0-0", rung=0, outcome="ok")
    w.flush()
    path = os.path.join(run_dir, "metrics.jsonl")
    g = follow(run_dir, poll_s=0.01, max_polls=200)
    assert next(g)["kind"] == "run_start"
    rec = next(g)
    assert rec["kind"] == "request" and rec["id"] == "r0-0"
    # a complete serve_window plus a TORN request tail: the window is
    # yielded, the torn half stays buffered until its newline lands
    with open(path, "a") as f:
        f.write('{"v": 1, "kind": "serve_window", "host": 0, "t": 1.0, '
                '"rung": 0, "offered_rps": 8.0}\n'
                '{"v": 1, "kind": "requ')
    rec = next(g)
    assert rec["kind"] == "serve_window" and rec["offered_rps"] == 8.0
    with open(path, "a") as f:
        f.write('est", "host": 0, "t": 2.0, "id": "r0-1", "outcome": "ok"}\n'
                '{"v": 1, "kind": "run_end", "host": 0, "t": 3.0, '
                '"status": "completed"}\n')
    assert next(g)["id"] == "r0-1"
    assert next(g)["kind"] == "run_end"
    # the CLI stop rule: every observed host completed
    assert list(follow(run_dir, poll_s=0, max_polls=2))[-1]["kind"] == "run_end"


# -------------------------------------------------------------- compare


def test_compare_serve_artifacts_direction_aware(tmp_path):
    from paddle_tpu.observability.compare import compare, load_side

    a, b = tmp_path / "a", tmp_path / "b"
    _write_serve_fixture(str(a))
    _write_serve_fixture(str(b))
    # degrade B's rung-1 latency 3x and raise its goodput: latency is
    # lower-is-better (REGRESSION), goodput higher-is-better (IMPROVED)
    path = os.path.join(str(b), "metrics.jsonl")
    lines = open(path).read().splitlines()
    out = []
    for ln in lines:
        rec = json.loads(ln)
        if rec.get("kind") == "serve_window" and rec.get("rung") == 1:
            rec["latency"] = dict(rec["latency"], p50=0.060, p99=0.150)
            rec["ttft"] = dict(rec["ttft"], p50=0.060, p99=0.150)
            rec["goodput_tok_s"] = 4800.0
        out.append(json.dumps(rec))
    open(path, "w").write("\n".join(out) + "\n")
    doc = compare(load_side(str(a)), load_side(str(b)))
    by = {m["metric"]: m["verdict"] for m in doc["metrics"]}
    # rungs join on OFFERED LOAD (40 req/s), not index — two sweeps with
    # different auto-calibrated ladders must never cross-compare
    assert by["serve.40rps.p99_ms"] == "REGRESSION"
    assert by["serve.40rps.ttft_p99_ms"] == "REGRESSION"
    assert by["serve.40rps.goodput_tok_s"] == "IMPROVED"
    assert by["serve.10rps.p99_ms"] == "SAME"
    assert doc["verdict"] == "REGRESSION"  # exit-1 semantics upstream


def test_compare_mismatched_rate_ladders_never_cross_join(tmp_path):
    """Auto-calibrated sweeps on different machines land different
    ladders: the serve metrics must fall into only_a/only_b instead of
    judging rung k of one ladder against rung k of another."""
    from paddle_tpu.observability.compare import compare, load_side

    a, b = tmp_path / "a", tmp_path / "b"
    _write_serve_fixture(str(a))
    _write_serve_fixture(str(b))
    path = os.path.join(str(b), "metrics.jsonl")
    lines = open(path).read().splitlines()
    out = []
    for ln in lines:
        rec = json.loads(ln)
        if rec.get("kind") == "serve_window":
            rec["offered_rps"] = rec["offered_rps"] * 2  # other ladder
            rec["latency"] = dict(rec["latency"], p50=9.0, p99=9.0)
        out.append(json.dumps(rec))
    open(path, "w").write("\n".join(out) + "\n")
    doc = compare(load_side(str(a)), load_side(str(b)))
    assert not any(m["metric"].startswith("serve.") and "rps." in m["metric"]
                   for m in doc["metrics"])
    assert any(n.startswith("serve.10rps.") for n in doc["only_a"])
    assert any(n.startswith("serve.20rps.") for n in doc["only_b"])


def test_compare_serve_bench_artifacts(tmp_path):
    """The archived BENCH_*.json serve line is comparable on its own:
    per-rung latency/goodput + knee, offered-load-keyed like the
    run-dir side — a latency regression with a flat headline must not
    read NO CHANGE."""
    from paddle_tpu.observability.compare import compare, load_side

    def artifact(name, p99, knee):
        p = tmp_path / name
        p.write_text(json.dumps({
            "metric": "serve_cpu_smoke_goodput_tokens_per_sec",
            "value": 5000.0, "unit": "tokens/s",
            "knee_rps": knee,
            "rungs": [{"offered_rps": 50.0, "p50_ms": 2.0, "p99_ms": p99,
                       "ttft_p50_ms": 2.0, "ttft_p99_ms": p99,
                       "goodput_tok_s": 5000.0, "queue_wait_share": 0.2}],
        }))
        return str(p)

    doc = compare(load_side(artifact("a.json", 4.0, 200.0)),
                  load_side(artifact("b.json", 12.0, 100.0)))
    by = {m["metric"]: m["verdict"] for m in doc["metrics"]}
    assert by["serve.50rps.p99_ms"] == "REGRESSION"
    assert by["serve_knee_rps"] == "REGRESSION"
    assert by["serve.50rps.goodput_tok_s"] == "SAME"
    assert doc["verdict"] == "REGRESSION"


def test_compare_pipeline_modes_never_cross_join(tmp_path):
    """The rung join is (engine, pipeline, offered load): one artifact
    carrying BOTH a blocking and a pipelined sweep of the same rate
    ladder keeps the modes apart (pipeline-qualified keys, never a
    blocking-vs-pipelined rung diffed against itself), and two such
    artifacts join mode-to-mode regardless of sweep order."""
    from paddle_tpu.observability.compare import compare, load_side

    def artifact(name, order):
        rungs = []
        for mode in order:
            rungs.append({
                "offered_rps": 50.0, "p50_ms": 2.0, "p99_ms": 5.0,
                "goodput_tok_s": 4000.0 if mode == "off" else 5000.0,
                "engine": "continuous", "pipeline": mode,
            })
        p = tmp_path / name
        p.write_text(json.dumps({
            "metric": "serve_cpu_smoke_goodput_tokens_per_sec",
            "value": 5000.0, "unit": "tokens/s",
            "rungs": rungs,
        }))
        return str(p)

    # sweep order differs between the artifacts — the deterministic
    # (engine, pipeline)-sorted key assignment must still join
    # off-to-off and on-to-on
    doc = compare(load_side(artifact("a.json", ("off", "on"))),
                  load_side(artifact("b.json", ("on", "off"))))
    by = {m["metric"]: m["verdict"] for m in doc["metrics"]}
    joined = [k for k in by if k.startswith("serve.") and "rps." in k]
    assert len(joined) >= 4, by
    # identical values mode-to-mode: every joined rung metric is SAME —
    # a crosswise join would read the structural off-vs-on goodput gap
    # (4000 vs 5000, 25%) as a verdict
    assert all(by[k] == "SAME" for k in joined), by
    assert not doc["only_a"] and not doc["only_b"], doc


# ------------------------------------------------------- embedding API


def test_sequence_generator_emits_request_records(tmp_path):
    from paddle_tpu import api
    from paddle_tpu.flagship import nmt_gen_batch, nmt_gen_config

    obs.configure(str(tmp_path))
    tc = nmt_gen_config(vocab=50, dim=16, beam_size=2, max_length=4,
                        batch_size=2)
    machine = api.GradientMachine(tc.model_config)
    gen = machine.asSequenceGenerator()
    batch = nmt_gen_batch(vocab=50, B=2, T=4)
    results = gen.generate(batch)
    obs.flush()
    assert len(results) == 2
    reqs = [r for r in _validated_records(str(tmp_path))
            if r["kind"] == "request"]
    assert len(reqs) == 2
    for r in reqs:
        assert r["outcome"] == "ok"
        assert r["cohort_size"] == 2
        assert r["beam_size"] == 2
        assert r["prompt_tokens"] >= 1
        assert r["gen_tokens"] >= 1
        assert r["e2e_s"] > 0
        # the first call paid the jit trace+compile: flagged, so
        # aggregations can split compile cost from steady-state latency
        assert r["cold_start"] is True
    # both samples share the call's cohort; a second call gets a new one
    assert len({r["cohort"] for r in reqs}) == 1
    gen.generate(batch)
    obs.flush()
    reqs2 = [r for r in _validated_records(str(tmp_path))
             if r["kind"] == "request"]
    assert len({r["cohort"] for r in reqs2}) == 2
    warm = [r for r in reqs2 if r["id"] not in {x["id"] for x in reqs}]
    assert all("cold_start" not in r for r in warm)

    # a raising forward still leaves per-sample error evidence
    gen._fwd = lambda *a: (_ for _ in ()).throw(RuntimeError("boom"))
    with pytest.raises(RuntimeError):
        gen.generate(batch)
    obs.flush()
    errs = [r for r in _validated_records(str(tmp_path))
            if r["kind"] == "request" and r["outcome"] == "error"]
    assert len(errs) == 2

    # dense-only feeds (no seq_lengths) still emit: n sizes the cohort
    serving.log_oneshot([], [], 0.1, outcome="error", n=3)
    obs.flush()
    errs = [r for r in _validated_records(str(tmp_path))
            if r["kind"] == "request" and r["outcome"] == "error"]
    assert len(errs) == 5

    # an oneshot-only stream owes no run_end: `paddle metrics` must not
    # claim the run crashed nor point at serve-report (which would exit
    # 1 with zero serve_window records)
    doc = analyze(load_run(str(tmp_path)))
    assert not any("run_end" in w for w in doc["warnings"])
    from paddle_tpu.observability.analyze import _fmt_table

    table = _fmt_table(doc)
    assert "serve telemetry" in table
    assert "serve-report" not in table


# ------------------------------------------------------------ bench e2e


def test_bench_serve_e2e_cpu_acceptance(tmp_path, monkeypatch, capsys):
    """The acceptance path: `bench.py serve` on the CPU backend produces
    a run dir where serve-report renders >= 3 offered-load rungs, every
    record passes validate_record, and the serve launch group shows
    recompiles=0 after warmup (signature-stable padding)."""
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_DIR", str(tmp_path))
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_REQUESTS", "10")
    monkeypatch.delenv("PADDLE_TPU_BENCH_METRICS_DIR", raising=False)
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)

    value, extras = bench.bench_serve(B=2, T=4, vocab=50, dim=16,
                                      beam_size=2, max_length=4,
                                      dtype="float32")
    # with no explicit mirror dir, bench.main() mirrors the headline
    # into the serve stream and THEN closes it — replay that here
    obs.emit("bench", metric="serve_cpu_smoke_goodput_tokens_per_sec",
             value=round(value, 1))
    obs.emit("run_end", status="completed")
    obs.flush()
    assert value > 0
    assert len(extras["rungs"]) >= 3
    assert extras["run_dir"] == str(tmp_path)

    recs = _validated_records(str(tmp_path))
    kinds = {r["kind"] for r in recs}
    assert {"request", "serve_window", "compile", "roofline",
            "run_end"} <= kinds
    compiles = [r for r in recs if r["kind"] == "compile"
                and r["group"] == serving.SERVE_GROUP]
    assert compiles and all(c["recompiles"] == 0 for c in compiles)
    assert len(compiles) == 1  # ONE signature across warmup + all rungs

    assert serving.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    rows = [ln for ln in out.splitlines()
            if ln.strip() and ln.strip().split()[0].isdigit()]
    assert len(rows) >= 3
    assert "recompiles after warmup: 0" in out
    assert "stream ends without run_end" not in out
