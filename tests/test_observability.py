"""Unified run telemetry (doc/observability.md): metrics.jsonl schema +
writer semantics, trace-event spans, hot-path instrumentation through a
real smoke train run, the `paddle metrics` analyzer, plotcurve's
metrics-first path, the supervisor's metrics-tail crash report, and
bench.py's shared-schema record."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from paddle_tpu.config import parse_config
from paddle_tpu.observability import metrics as obs
from paddle_tpu.observability import spans as obs_spans
from paddle_tpu.observability.analyze import analyze, load_run
from paddle_tpu.trainer import Trainer
from paddle_tpu.utils.flags import FLAGS

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROVIDER_DIR = os.path.join(os.path.dirname(__file__), "providers")
SUBPROC_ENV = {
    **os.environ,
    "PYTHONPATH": f"{REPO}:{REPO}/compat:{PROVIDER_DIR}",
    "JAX_PLATFORMS": "cpu",
}


@pytest.fixture(autouse=True)
def _provider_path():
    sys.path.insert(0, PROVIDER_DIR)
    yield
    sys.path.remove(PROVIDER_DIR)


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Telemetry state is process-global: isolate each test."""
    obs.registry().reset()
    yield
    obs.configure("")
    obs_spans.configure("")
    FLAGS.metrics_path = ""
    FLAGS.trace_events_path = ""


def _lr_config(tmp_path):
    train_list = tmp_path / "train.list"
    train_list.write_text("1\n2\n")
    test_list = tmp_path / "test.list"
    test_list.write_text("99\n")
    src = textwrap.dedent(f"""
    from paddle_tpu.trainer_config_helpers import *

    define_py_data_sources2(train_list={str(train_list)!r},
                            test_list={str(test_list)!r},
                            module="synthetic_bow", obj="process")
    settings(batch_size=64, learning_rate=0.02, learning_method=AdamOptimizer())
    data = data_layer(name="word", size=100)
    output = fc_layer(input=data, size=2, act=SoftmaxActivation(), name="output")
    label = data_layer(name="label", size=2)
    outputs(classification_cost(input=output, label=label))
    """)
    cfg_path = tmp_path / "lr_config.py"
    cfg_path.write_text(src)
    return str(cfg_path)


def _fresh_flags(tmp_path, name="out"):
    FLAGS.save_dir = str(tmp_path / name)
    FLAGS.num_passes = 2
    FLAGS.log_period = 0
    FLAGS.start_pass = 0
    FLAGS.init_model_path = ""
    FLAGS.seed = 7
    FLAGS.metrics_path = ""
    FLAGS.trace_events_path = ""
    return FLAGS.save_dir


# ----------------------------------------------------- writer + registry


def test_registry_counter_gauge_histogram_snapshot():
    r = obs.MetricsRegistry()
    r.counter("c").inc()
    r.counter("c").inc(2.5)
    r.gauge("g").set(4.0)
    h = r.histogram("h")
    h.observe(1.0)
    snap = r.snapshot()
    assert snap["c"] == pytest.approx(3.5)
    assert snap["g"] == 4.0
    assert snap["h"]["count"] == 1
    with pytest.raises(AssertionError):
        r.gauge("c")  # name reuse across kinds is a bug, not a silent cast


def test_writer_schema_buffering_and_torn_tail(tmp_path):
    w = obs.MetricsWriter(str(tmp_path), host=0, buffer_limit=100)
    path = os.path.join(str(tmp_path), "metrics.jsonl")
    # run_start is a flush kind: already on disk
    assert os.path.exists(path)
    n0 = len(open(path).read().splitlines())
    w.emit("train_window", pass_id=0, step=10, AvgCost=0.5)
    # buffered: nothing new on disk until a boundary kind or the limit
    assert len(open(path).read().splitlines()) == n0
    w.emit("pass_end", pass_id=0, step=20, samples=128, AvgCost=0.4,
           loss=float("nan"))
    records = [json.loads(l) for l in open(path).read().splitlines()]
    assert [r["kind"] for r in records] == ["run_start", "train_window", "pass_end"]
    for rec in records:
        assert obs.validate_record(rec) == [], rec
    # non-finite floats serialize as strings, keeping strict JSON
    assert records[-1]["loss"] == "nan"
    # t is a wall-time OFFSET: monotone nondecreasing
    ts = [r["t"] for r in records]
    assert ts == sorted(ts)
    # torn tail (crash mid-write) must not break readers
    with open(path, "a") as f:
        f.write('{"v": 1, "kind": "pass_end", "hos')
    got = list(obs.read_records(path))
    assert len(got) == 3
    # validate_record flags garbage
    assert obs.validate_record({"kind": 3}) != []


def test_writer_host_naming_and_reconfigure(tmp_path):
    w0 = obs.configure(str(tmp_path), host=0)
    assert os.path.basename(w0.path) == "metrics.jsonl"
    # same path reconfigure reuses the writer (no duplicate run_start)
    assert obs.configure(str(tmp_path), host=0) is w0
    w1 = obs.MetricsWriter(str(tmp_path), host=2)
    assert os.path.basename(w1.path) == "metrics.host2.jsonl"
    w1.flush()
    assert sorted(os.path.basename(p) for p in obs.metrics_files(str(tmp_path))) == [
        "metrics.host2.jsonl", "metrics.jsonl",
    ]


# ------------------------------------------------------- smoke train run


def _train_smoke(tmp_path, **flag_overrides):
    cfg = parse_config(_lr_config(tmp_path))
    run_dir = _fresh_flags(tmp_path)
    for k, v in flag_overrides.items():
        setattr(FLAGS, k, v)
    trainer = Trainer(cfg)
    trainer.train(num_passes=2)
    return trainer, run_dir


def test_smoke_train_emits_valid_metrics_stream(tmp_path):
    trainer, run_dir = _train_smoke(tmp_path)
    path = os.path.join(run_dir, "metrics.jsonl")
    assert os.path.exists(path), os.listdir(run_dir)
    records = list(obs.read_records(path))
    for rec in records:
        assert obs.validate_record(rec) == [], rec
    kinds = [r["kind"] for r in records]
    assert kinds[0] == "run_start"
    assert kinds[-1] == "run_end" and records[-1]["status"] == "completed"
    pass_ends = [r for r in records if r["kind"] == "pass_end"]
    assert [r["pass"] for r in pass_ends] == [0, 1]
    for pe in pass_ends:
        # the shared summary dict + step-time quantiles + counters
        for key in ("samples", "AvgCost", "CurrentCost", "samples_per_sec",
                    "pass_time_s", "step_time_p50_s", "step_time_p99_s",
                    "launches_single", "counters", "step"):
            assert key in pe, (key, sorted(pe))
        assert pe["step_time_p99_s"] >= pe["step_time_p50_s"] > 0
        assert pe["samples"] > 0
    # checkpoint telemetry: one save per pass, with duration and bytes
    saves = [r for r in records if r["kind"] == "checkpoint" and r["op"] == "save"]
    assert [s["pass"] for s in saves] == [0, 1]
    assert all(s["bytes"] > 0 and s["duration_s"] > 0 for s in saves)
    # test records ride along (test at pass end, with a test list set)
    assert any(r["kind"] == "test" and "cost" in r for r in records)
    # the quality curve in telemetry matches the in-process history
    hist = {p: res["cost"] for p, res in trainer.test_history}
    tests = {r["pass"]: r["cost"] for r in records if r["kind"] == "test"
             if "pass" in r}
    for p, c in hist.items():
        assert tests[p] == pytest.approx(c)


def test_pass_end_record_matches_logged_line(tmp_path, caplog):
    """Satellite: the 'Pass N done' log text and the pass_end record
    render from ONE shared dict — same keys, same values."""
    import logging
    import re

    # the paddle_tpu logger doesn't propagate (own stderr handler) —
    # attach caplog's handler directly
    from paddle_tpu.utils.logging import logger as ptu_logger

    ptu_logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger="paddle_tpu"):
            _, run_dir = _train_smoke(tmp_path)
    finally:
        ptu_logger.removeHandler(caplog.handler)
    logged = {}
    for m in re.finditer(r"Pass (\d+) done: (.*)", caplog.text):
        kv = dict(re.findall(r"([A-Za-z_][\w.]*)=([-+0-9.eE]+)", m.group(2)))
        logged[int(m.group(1))] = kv
    records = list(obs.read_records(os.path.join(run_dir, "metrics.jsonl")))
    for rec in records:
        if rec["kind"] != "pass_end":
            continue
        kv = logged[rec["pass"]]
        assert int(kv["samples"]) == rec["samples"]
        assert float(kv["AvgCost"]) == pytest.approx(rec["AvgCost"], rel=1e-5)
        assert float(kv["CurrentCost"]) == pytest.approx(
            rec["CurrentCost"], rel=1e-5
        )


def test_trace_events_export_loads_and_nests(tmp_path):
    _, run_dir = _train_smoke(
        tmp_path, trace_events_path=str(tmp_path / "trace.json")
    )
    doc = json.load(open(tmp_path / "trace.json"))  # valid JSON by parse
    events = doc["traceEvents"]
    by_name = {}
    for ev in events:
        assert ev["ph"] in ("X", "i")
        by_name.setdefault(ev["name"], []).append(ev)
    # trainer / data / checkpoint spans all present
    assert "trainer/pass" in by_name
    assert "trainer/launch" in by_name
    assert "checkpoint/save" in by_name
    # one name a phase: the old flat names are gone
    assert not {"train_step", "onePass", "test"} & set(by_name)
    # nesting: every launch lies inside some trainer/pass span
    passes = [(e["ts"], e["ts"] + e["dur"]) for e in by_name["trainer/pass"]]
    for step in by_name["trainer/launch"]:
        s0, s1 = step["ts"], step["ts"] + step["dur"]
        assert any(p0 <= s0 and s1 <= p1 + 1 for p0, p1 in passes), (
            (s0, s1), passes
        )


def test_nonfinite_events_recorded(tmp_path):
    from paddle_tpu.resilience import faultinject

    cfg = parse_config(_lr_config(tmp_path))
    run_dir = _fresh_flags(tmp_path)
    FLAGS.nonfinite_policy = "skip"
    faultinject.configure("trainer.nonfinite=raise@2")
    try:
        Trainer(cfg).train(num_passes=1)
    finally:
        faultinject.configure("")
    records = list(obs.read_records(os.path.join(run_dir, "metrics.jsonl")))
    nf = [r for r in records if r["kind"] == "nonfinite"]
    assert len(nf) == 1 and nf[0]["policy"] == "skip"
    assert nf[0]["value"] == "nan"
    faults = [r for r in records if r["kind"] == "fault"]
    assert faults and faults[0]["site"] == "trainer.nonfinite"
    pe = [r for r in records if r["kind"] == "pass_end"][-1]
    assert pe["counters"]["nonfinite.events"] == 1
    assert pe["counters"]["faults.fired"] >= 1


# --------------------------------------------------------------- analyzer


def test_analyzer_aggregates_run(tmp_path):
    _, run_dir = _train_smoke(tmp_path)
    doc = analyze(load_run(run_dir))
    assert doc["hosts"] == [0]
    assert [p["pass"] for p in doc["passes"]] == [0, 1]
    row = doc["passes"][0]
    assert row["samples"] > 0 and "AvgCost" in row
    assert "data_wait_share" in row and 0.0 <= row["data_wait_share"] <= 1.0
    assert {c["op"] for c in doc["checkpoints"]} == {"save"}
    assert doc["run_ended"] is True
    assert doc["invalid_records"] == 0


def test_analyzer_flags_missing_run_end_and_straggler(tmp_path):
    # hand-written two-host streams: host 1 is the straggler, no run_end
    w0 = obs.MetricsWriter(str(tmp_path), host=0)
    w1 = obs.MetricsWriter(str(tmp_path), host=1)
    for host, w, mean in ((0, w0, 0.01), (1, w1, 0.05)):
        w.emit("pass_end", pass_id=0, step=10, samples=64, AvgCost=0.5,
               pass_time_s=1.0, step_time_mean_s=mean,
               step_time_p50_s=mean, step_time_p99_s=mean * 2)
        w.flush()
    doc = analyze(load_run(str(tmp_path)))
    assert doc["hosts"] == [0, 1]
    assert doc["passes"][0]["hosts"] == 2
    assert doc["straggler"] and "slowest=host1" in doc["straggler"]["line"]
    assert any("run_end" in w for w in doc["warnings"])


def test_analyzer_dedupes_rerun_passes_latest_wins(tmp_path):
    """A supervised restart (or rollback) re-runs a pass and appends a
    SECOND pass_end for the same (host, pass) to the same stream — the
    analyzer must keep the latest, not double-count samples or inflate
    the hosts divisor."""
    w = obs.MetricsWriter(str(tmp_path), host=0)
    w.emit("pass_end", pass_id=0, step=10, samples=64, AvgCost=0.9,
           pass_time_s=1.0)
    # crash + restart: the re-run pass lands with different numbers
    w.emit("pass_end", pass_id=0, step=10, samples=64, AvgCost=0.7,
           pass_time_s=2.0)
    w.emit("pass_end", pass_id=1, step=20, samples=64, AvgCost=0.5,
           pass_time_s=1.0)
    w.flush()
    doc = analyze(load_run(str(tmp_path)))
    assert [p["pass"] for p in doc["passes"]] == [0, 1]
    row = doc["passes"][0]
    assert row["hosts"] == 1           # one host, despite two records
    assert row["samples"] == 64        # not doubled
    assert row["AvgCost"] == 0.7       # latest wins
    assert row["pass_time_s"] == 2.0


def test_paddle_metrics_cli_table_and_json(tmp_path):
    _, run_dir = _train_smoke(tmp_path)
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "metrics", run_dir],
        capture_output=True, text=True, env=SUBPROC_ENV, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    assert "AvgCost" in r.stdout and "p99 ms" in r.stdout
    assert "checkpoint" in r.stdout
    r2 = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "metrics", run_dir, "--json"],
        capture_output=True, text=True, env=SUBPROC_ENV, timeout=120,
    )
    assert r2.returncode == 0, r2.stderr
    doc = json.loads(r2.stdout)
    assert [p["pass"] for p in doc["passes"]] == [0, 1]
    # an empty dir is a clean, jax-free error
    r3 = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "metrics", str(tmp_path)],
        capture_output=True, text=True, env=SUBPROC_ENV, timeout=120,
    )
    assert r3.returncode == 1
    assert "no metrics" in r3.stderr


# -------------------------------------------------------------- plotcurve


def test_plotcurve_prefers_metrics_for_run_dirs(tmp_path, capsys):
    from paddle_tpu.utils import plotcurve

    _, run_dir = _train_smoke(tmp_path)
    series = plotcurve.parse_metrics(run_dir)
    assert len(series["AvgCost"]) == 2
    assert series["AvgCost"][1] < series["AvgCost"][0]  # it learned
    # main() routes a run dir through the metrics path
    assert plotcurve.main([ "-i", run_dir, "AvgCost"]) == 0
    out = capsys.readouterr().out
    assert "AvgCost" in out and "*" in out
    # legacy path intact: log text still parses (old runs keep plotting)
    log = tmp_path / "train.log"
    log.write_text("Pass 0 done: samples=10 AvgCost=0.9 CurrentCost=0.9\n")
    assert plotcurve.main(["-i", str(log), "AvgCost"]) == 0


def test_plotcurve_metrics_series_stay_pass_aligned(tmp_path):
    """A field present in only SOME pass_end records (mfu when FLOP
    accounting failed) must leave a NaN gap at its pass, not shift later
    points left onto the wrong pass."""
    from paddle_tpu.utils import plotcurve

    w = obs.MetricsWriter(str(tmp_path), host=0)
    w.emit("pass_end", pass_id=0, step=5, samples=64, AvgCost=0.9)
    w.emit("pass_end", pass_id=1, step=10, samples=64, AvgCost=0.5, mfu=0.3)
    w.flush()
    series = plotcurve.parse_metrics(str(tmp_path))
    assert series["AvgCost"] == [0.9, 0.5]
    assert len(series["mfu"]) == 2
    assert series["mfu"][0] != series["mfu"][0]  # NaN gap at pass 0
    assert series["mfu"][1] == 0.3
    # the ascii plot skips the gap instead of crashing on NaN min/max
    art = plotcurve.ascii_plot(series["mfu"])
    assert "*" in art


# ------------------------------------------------------------- supervisor


def test_crash_report_carries_metrics_tail(tmp_path):
    from paddle_tpu.resilience.supervisor import Supervisor

    run_dir = str(tmp_path / "out")
    w = obs.MetricsWriter(run_dir, host=0)
    w.emit("pass_end", pass_id=0, step=10, samples=64, AvgCost=0.5)
    w.emit("barrier_skew", pass_id=0, mean_s=[0.01, 0.05], skew_s=0.04,
           slowest_host=1, line="BarrierStat: ... slowest=host1")
    w.flush()

    class Flags:
        save_dir = run_dir
        supervise_dir = str(tmp_path / "sup")
        restart_budget = 1
        crash_loop_threshold = 2
        restart_base_delay = 0.0
        metrics_path = ""
        dry_run = False

    sup = Supervisor(["--config=c.py"], Flags(), child_cmd=["true"])
    os.makedirs(sup.dir, exist_ok=True)
    log = tmp_path / "sup" / "attempt-000.log"
    log.write_text("some child output\n")
    sup._crash_report("crash_loop", str(log), "test detail")
    report = json.load(open(tmp_path / "sup" / "crash_report.json"))
    tail = report["metrics_tail"]["0"]
    assert [r["kind"] for r in tail] == ["run_start", "pass_end", "barrier_skew"]
    # straggler attribution now comes from the STRUCTURED record
    assert report["step_time_skew"]["kind"] == "barrier_skew"
    assert report["step_time_skew"]["slowest_host"] == 1


def test_crash_report_falls_back_to_log_grep_without_metrics(tmp_path):
    from paddle_tpu.resilience.supervisor import Supervisor

    class Flags:
        save_dir = ""
        supervise_dir = str(tmp_path / "sup")
        restart_budget = 1
        crash_loop_threshold = 2
        restart_base_delay = 0.0
        metrics_path = ""
        dry_run = False

    sup = Supervisor([], Flags(), child_cmd=["true"])
    os.makedirs(sup.dir, exist_ok=True)
    log = tmp_path / "sup" / "attempt-000.log"
    log.write_text("noise\nBarrierStat: step mean/host=[...] slowest=host0\n")
    sup._crash_report("crash_loop", str(log), "d")
    report = json.load(open(tmp_path / "sup" / "crash_report.json"))
    assert report["metrics_tail"] == {}
    assert "BarrierStat" in report["step_time_skew"]


# ------------------------------------------------------------------ bench


def test_bench_emit_mirrors_metrics_schema(tmp_path, monkeypatch, capsys):
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    monkeypatch.setenv("PADDLE_TPU_BENCH_METRICS_DIR", str(tmp_path / "bm"))
    bench._emit("serve_goodput_tokens_per_sec", 123.4, "tokens/s",
                engine="static")
    capsys.readouterr()  # swallow the stdout JSON line
    recs = list(obs.read_records(str(tmp_path / "bm" / "metrics.jsonl")))
    bench_recs = [r for r in recs if r["kind"] == "bench"]
    assert len(bench_recs) == 1
    rec = bench_recs[0]
    assert obs.validate_record(rec) == []
    assert rec["metric"] == "serve_goodput_tokens_per_sec"
    assert rec["value"] == 123.4 and rec["unit"] == "tokens/s"
