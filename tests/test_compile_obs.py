"""Compile & cost attribution (doc/observability.md "Compile telemetry",
doc/performance.md "Roofline methodology"): kind=compile/roofline record
schema through a real smoke train run, the persistent compilation cache
e2e (two `paddle train` runs sharing --compile_cache_dir: the second
run's compile records show cache hits and a measured drop in
time_to_first_step_s), the cost_analysis-unavailable fallback, `paddle
roofline`, `paddle compare` (incl. the regression verdict), `paddle
metrics --follow`, and the warm-resume verification skip."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from paddle_tpu.config import parse_config
from paddle_tpu.observability import compile_log
from paddle_tpu.observability import metrics as obs
from paddle_tpu.observability import spans as obs_spans
from paddle_tpu.observability.analyze import analyze, follow, load_run
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
    obs.registry().reset()
    yield
    obs.configure("")
    obs_spans.configure("")
    FLAGS.metrics_path = ""
    FLAGS.trace_events_path = ""
    FLAGS.compile_cache_dir = ""


def _lr_config(tmp_path, hidden=0):
    train_list = tmp_path / "train.list"
    train_list.write_text("1\n2\n")
    test_list = tmp_path / "test.list"
    test_list.write_text("99\n")
    mid = (
        f'h = fc_layer(input=data, size={hidden}, act=ReluActivation())'
        if hidden else "h = data"
    )
    src = textwrap.dedent(f"""
    from paddle_tpu.trainer_config_helpers import *

    define_py_data_sources2(train_list={str(train_list)!r},
                            test_list={str(test_list)!r},
                            module="synthetic_bow", obj="process")
    settings(batch_size=64, learning_rate=0.02, learning_method=AdamOptimizer())
    data = data_layer(name="word", size=100)
    {mid}
    output = fc_layer(input=h, size=2, act=SoftmaxActivation(), name="output")
    label = data_layer(name="label", size=2)
    outputs(classification_cost(input=output, label=label))
    """)
    cfg_path = tmp_path / "lr_config.py"
    cfg_path.write_text(src)
    return str(cfg_path)


def _train_smoke(tmp_path, **flag_overrides):
    cfg = parse_config(_lr_config(tmp_path))
    FLAGS.save_dir = str(tmp_path / "out")
    FLAGS.num_passes = 2
    FLAGS.log_period = 0
    FLAGS.start_pass = 0
    FLAGS.init_model_path = ""
    FLAGS.seed = 7
    for k, v in flag_overrides.items():
        setattr(FLAGS, k, v)
    trainer = Trainer(cfg)
    trainer.train(num_passes=2)
    return trainer, FLAGS.save_dir


# ------------------------------------------------- records through a run


def test_smoke_train_emits_compile_and_roofline_records(tmp_path):
    _, run_dir = _train_smoke(tmp_path)
    records = list(obs.read_records(os.path.join(run_dir, "metrics.jsonl")))
    compiles = [r for r in records if r["kind"] == "compile"]
    rooflines = [r for r in records if r["kind"] == "roofline"]
    assert compiles and rooflines
    for rec in compiles + rooflines:
        assert obs.validate_record(rec) == [], rec
    # one compile per (group, batch-shape signature): the full batch and
    # the end-of-pass remainder each compile the train step once, and
    # NOT again on pass 2
    groups = {c["group"] for c in compiles}
    assert "train_step" in groups and "test_fwd" in groups
    by_group_sig = {(c["group"], c["sig"]) for c in compiles}
    assert len(by_group_sig) == len(compiles), "recompiled a cached signature"
    for c in compiles:
        assert c["trace_s"] >= 0 and c["compile_s"] > 0
        assert isinstance(c["recompiles"], int)
        # CPU backend provides cost analysis: FLOPs/bytes captured
        assert c.get("flops", 0) > 0 and c.get("bytes_accessed", 0) > 0
    # train_step compiles carry the analytic cross-check fields
    ts = [c for c in compiles if c["group"] == "train_step"]
    assert all("flops_analytic" in c and "flops_disagreement" in c for c in ts)
    # roofline records: cumulative exec totals per group+sig — the
    # test forward is timed too (standalone `paddle test`/`paddle gen`
    # get the same roofline discipline as training)
    roof_groups = {r["group"] for r in rooflines}
    assert "train_step" in roof_groups and "test_fwd" in roof_groups
    for r in rooflines:
        assert r["launches"] > 0 and r["exec_s"] >= 0
        assert r.get("flops_per_launch", 0) > 0
        assert r["device_kind"]
    # counters snapshot carries the compile tallies
    pe = [r for r in records if r["kind"] == "pass_end"][-1]
    assert pe["counters"]["compile.count"] == len(compiles)


def test_paddle_metrics_shows_compile_table(tmp_path):
    _, run_dir = _train_smoke(tmp_path)
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "metrics", run_dir],
        capture_output=True, text=True, env=SUBPROC_ENV, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    assert "compile totals:" in r.stdout
    assert "train_step" in r.stdout and "trace s" in r.stdout
    doc = analyze(load_run(run_dir))
    t = doc["compile_totals"]
    assert t["count"] == len(doc["compiles"]) > 0
    assert t["compile_s"] > 0


def test_roofline_cli_prints_group_table(tmp_path):
    _, run_dir = _train_smoke(tmp_path)
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "roofline", run_dir],
        capture_output=True, text=True, env=SUBPROC_ENV, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    # per-launch-group table with the documented columns
    for col in ("group", "launches", "GFLOP/launch", "MB/launch",
                "GFLOP/s", "FLOP/B", "bucket", "train_step"):
        assert col in r.stdout, (col, r.stdout)
    r2 = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "roofline", run_dir, "--json"],
        capture_output=True, text=True, env=SUBPROC_ENV, timeout=120,
    )
    doc = json.loads(r2.stdout)
    assert doc["groups"] and doc["compile_totals"]["count"] > 0
    row = doc["groups"][0]
    assert row["bucket"] in ("compute-bound", "memory-bound", "host-bound",
                             "unknown")
    assert row.get("achieved_flops_per_s", 0) > 0
    assert row.get("intensity", 0) > 0
    # an empty dir is a clean, jax-free error
    r3 = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "roofline", str(tmp_path)],
        capture_output=True, text=True, env=SUBPROC_ENV, timeout=120,
    )
    assert r3.returncode == 1


def test_roofline_bucket_classification():
    from paddle_tpu.observability.costs import classify

    # v4: 275 TFLOP/s / 1228 GB/s → ridge ~224 FLOP/B
    assert classify(500.0, "TPU v4") == "compute-bound"
    assert classify(10.0, "TPU v4") == "memory-bound"
    # data-wait dominance trumps the ridge position
    assert classify(500.0, "TPU v4", data_wait_share=0.8) == "host-bound"
    # unknown chips / missing analysis are never guessed
    assert classify(10.0, "cpu") == "unknown"
    assert classify(None, "TPU v4") == "unknown"


# ------------------------------------------------ persistent cache e2e


@pytest.fixture(scope="module")
def cache_runs(tmp_path_factory):
    """Two `paddle train` processes sharing --compile_cache_dir, the first
    on an empty directory: ((compile records, restart record, last
    pass_end record) of the cold run, the same of the warm one)."""
    tmp_path = tmp_path_factory.mktemp("cache_runs")
    sys.path.insert(0, PROVIDER_DIR)
    try:
        cfg = _lr_config(tmp_path, hidden=256)  # big enough that compile dominates
    finally:
        sys.path.remove(PROVIDER_DIR)
    cache = str(tmp_path / "cache")

    def run(name):
        out = str(tmp_path / name)
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.cli", "train",
             f"--config={cfg}", f"--save_dir={out}", "--num_passes=1",
             "--log_period=0", "--use_tpu=0",
             f"--compile_cache_dir={cache}"],
            capture_output=True, text=True, env=SUBPROC_ENV, timeout=300,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        recs = list(obs.read_records(os.path.join(out, "metrics.jsonl")))
        compiles = [x for x in recs if x["kind"] == "compile"]
        restart = [x for x in recs if x["kind"] == "restart"]
        assert compiles and len(restart) == 1
        return compiles, restart[0], [x for x in recs if x["kind"] == "pass_end"][-1]

    return run("runA"), run("runB")


def test_compile_cache_two_runs_hit_and_faster_ttfs(cache_runs):
    """Acceptance: two `paddle train` runs sharing --compile_cache_dir —
    the second run's compile records show cache hits and a measured
    drop in time_to_first_step_s."""
    (c_cold, r_cold, _), (c_warm, r_warm, _) = cache_runs
    # cold run: all misses (cache dir was empty); warm run: all hits
    assert all(c.get("cache_hit") is False for c in c_cold), c_cold
    assert all(c.get("cache_hit") is True for c in c_warm), c_warm
    # the warm run's XLA compile time collapses...
    cold_s = sum(c["compile_s"] for c in c_cold)
    warm_s = sum(c["compile_s"] for c in c_warm)
    assert warm_s < cold_s
    # ...and time_to_first_step_s drops measurably (restore + trace
    # still run; the XLA half is what the cache absorbs)
    assert r_warm["time_to_first_step_s"] < r_cold["time_to_first_step_s"]


def test_jax_counters_tell_a_cold_process_from_a_warm_one(cache_runs):
    """The listener's counters see every jit of the process, the
    registry's launch groups and the rest: both processes trace, lower
    and build executables; the warm one loads from the cache what the cold
    one compiled, and the seconds move from the one counter to the other."""
    (c_cold, _, cold), (_, _, warm) = cache_runs
    # the pass's record is written before the pass-end test compiles
    steps = [r for r in c_cold if r["group"] == "train_step"]
    for end in (cold, warm):
        c = end["counters"]
        assert c["jax.trace_s"] > 0 and c["jax.lower_s"] > 0
        assert c["jax.backend_compile_s"] > 0
        assert c["jax.compiles"] >= c["compile.count"] == len(steps)
        assert c.get("compile.aot_fallbacks", 0) == 0
        assert "compile.total_s" not in c
        # through `cli._setup`: the DSL file's parse is a span of the run
        assert end["spans_total"]["config/parse"][0] == 1
        assert end["spans_total"]["trainer/init"][0] == 1
    # (a cold process hits too, where two of its jits lower to one module)
    cold, warm = cold["counters"], warm["counters"]
    assert warm["jax.cache_load_s"] > cold.get("jax.cache_load_s", 0)
    assert warm["jax.backend_compile_s"] < cold["jax.backend_compile_s"]
    assert warm["jax.compiles"] == cold["jax.compiles"]


def test_jax_counters_grow_on_a_first_compile_only():
    import jax
    import jax.numpy as jnp

    names = ("jax.trace_s", "jax.lower_s", "jax.backend_compile_s",
             "jax.compiles")
    x = jnp.ones((3, 5))                     # its own fill compiles here
    fn = jax.jit(lambda a: jnp.tanh(a) @ a.T + 36.0)
    before = obs.registry().snapshot()
    jax.block_until_ready(fn(x))
    first = obs.registry().snapshot()
    for n in names:
        assert first[n] > before.get(n, 0), n
    assert first["jax.compiles"] == before.get("jax.compiles", 0) + 1
    jax.block_until_ready(fn(x))             # the same shape: nothing new
    assert obs.registry().snapshot() == first


def test_jax_event_seconds_are_self_time():
    """An event that closed inside another's seconds (a jit traced inside
    a trace, a cache load inside a backend compile) is taken out of the
    outer one's: the counters are disjoint."""
    import time

    on = compile_log._on_jax_event
    trace = "/jax/core/compile/jaxpr_trace_duration"
    build = "/jax/core/compile/backend_compile_duration"
    load = "/jax/compilation_cache/cache_retrieval_time_sec"
    time.sleep(0.05)
    on(trace, 0.01)       # closed 0.01 s ago at the earliest ...
    on(trace, 0.04)       # ... so inside this one
    on(load, 0.5e-3)
    on(build, 2e-3, fun_name="f")            # holds the load, not the traces
    on("/jax/some/other_event", 7.0)
    c = obs.registry().snapshot()
    assert c["jax.trace_s"] == pytest.approx(0.04)
    assert c["jax.cache_load_s"] == pytest.approx(0.5e-3)
    assert c["jax.backend_compile_s"] == pytest.approx(1.5e-3)
    assert c["jax.compiles"] == 1 and "jax.lower_s" not in c


# ------------------------------------------------------ fallback paths


def test_cost_analysis_of_graceful_on_unavailable_backends():
    from paddle_tpu.observability.costs import cost_analysis_of

    class Raises:
        def cost_analysis(self):
            raise NotImplementedError("backend says no")

    class Dicty:
        def cost_analysis(self):
            return {"flops": 8.0, "bytes accessed": 4.0}

    class Empty:
        def cost_analysis(self):
            return {"transcendentals": 3.0}

    class Scalarless:
        def cost_analysis(self):
            return "not a dict"

    assert cost_analysis_of(Raises()) is None
    assert cost_analysis_of(Empty()) is None
    assert cost_analysis_of(Scalarless()) is None
    assert cost_analysis_of(Dicty()) == {"flops": 8.0, "bytes_accessed": 4.0}


def test_registry_inline_fallback_without_lower(tmp_path):
    """Callables without .lower (mesh-sharded closures, plain python)
    still get a compile record — mode=inline, combined timing, no cost
    analysis — and the launch result is returned unchanged."""
    obs.configure(str(tmp_path), host=0)
    reg = compile_log.CompileRegistry(device_kind="cpu")
    calls = []

    def step(x):
        calls.append(x)
        return x * 2

    assert reg.call("train_step", ("sig", 1), step, 21) == 42
    assert reg.call("train_step", ("sig", 1), step, 4) == 8
    obs.flush()
    recs = [r for r in obs.read_records(os.path.join(str(tmp_path), "metrics.jsonl"))
            if r["kind"] == "compile"]
    assert len(recs) == 1  # second call hit the registry cache
    rec = recs[0]
    assert obs.validate_record(rec) == []
    assert rec["mode"] == "inline"
    assert rec["compile_s"] > 0 and "trace_s" not in rec
    assert "flops" not in rec  # no executable to cost-analyze
    assert calls == [21, 4]


def test_registry_counts_an_aot_executable_given_up_for_jit_dispatch(tmp_path, caplog):
    """An AOT executable that rejects its inputs before dispatch is given
    up for jit dispatch, which compiles the step AGAIN: one increment of
    `compile.aot_fallbacks`, once an entry (the entry keeps the jit path
    after), and the second compile shows in `jax.compiles`."""
    import logging

    import jax
    import jax.numpy as jnp

    from paddle_tpu.utils.logging import logger as ptu_logger

    obs.configure(str(tmp_path), host=0)
    reg = compile_log.CompileRegistry(device_kind="cpu")
    fn = jax.jit(lambda x: x * 2.0 + 1.0)
    small, wide = jnp.ones((3,)), jnp.ones((4,))
    assert float(reg.call("train_step", ("sig",), fn, small)[0]) == 3.0
    c = obs.registry().snapshot()
    assert c.get("compile.aot_fallbacks", 0) == 0
    # the registry's key says "same signature", the executable disagrees
    ptu_logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.WARNING, logger="paddle_tpu"):
            out = reg.call("train_step", ("sig",), fn, wide)
    finally:
        ptu_logger.removeHandler(caplog.handler)
    after = obs.registry().snapshot()
    assert out.shape == (4,) and float(out[0]) == 3.0
    assert "falling back to jit dispatch" in caplog.text
    assert after["compile.aot_fallbacks"] == 1
    assert after["jax.compiles"] == c["jax.compiles"] + 1
    assert after["compile.count"] == c["compile.count"] == 1
    reg.call("train_step", ("sig",), fn, wide)
    assert obs.registry().snapshot()["compile.aot_fallbacks"] == 1


def test_registry_cost_analysis_raise_keeps_compile_record(tmp_path, monkeypatch):
    """A backend whose compiled.cost_analysis() raises still yields the
    timed compile record — just without FLOPs/bytes."""
    import jax

    from paddle_tpu.observability import costs

    obs.configure(str(tmp_path), host=0)
    monkeypatch.setattr(
        costs, "cost_analysis_of",
        lambda compiled: (_ for _ in ()).throw(RuntimeError("unreachable")),
    )
    # the registry must swallow even a raising helper (graceful contract)
    reg = compile_log.CompileRegistry()
    fn = jax.jit(lambda x: x + 1)
    try:
        out = reg.call("train_step", ("s",), fn, 1.0)
    except RuntimeError:
        pytest.fail("cost-analysis failure leaked out of the registry")
    assert float(out) == 2.0
    obs.flush()
    recs = [r for r in obs.read_records(os.path.join(str(tmp_path), "metrics.jsonl"))
            if r["kind"] == "compile"]
    assert len(recs) == 1 and recs[0]["compile_s"] > 0


def test_flops_cross_check_warns_once_per_signature(tmp_path, caplog):
    import logging

    import jax

    from paddle_tpu.utils.logging import logger as ptu_logger

    obs.configure(str(tmp_path), host=0)
    reg = compile_log.CompileRegistry()
    fn = jax.jit(lambda x: x @ x)
    x = np.eye(8, dtype=np.float32)
    ptu_logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.WARNING, logger="paddle_tpu"):
            reg.call("train_step", ("s",), fn, x, analytic_flops=1e12)
    finally:
        ptu_logger.removeHandler(caplog.handler)
    assert "FLOPs accounting disagreement" in caplog.text
    assert "scan/while bodies once" in caplog.text
    obs.flush()
    rec = [r for r in obs.read_records(os.path.join(str(tmp_path), "metrics.jsonl"))
           if r["kind"] == "compile"][0]
    assert rec["flops_analytic"] == 1e12
    assert rec["flops_disagreement"] > 0.10


# --------------------------------------------------------------- compare


def _fake_run(tmp_path, name, sps, p99, compile_s):
    d = str(tmp_path / name)
    w = obs.MetricsWriter(d, host=0)
    w.emit("compile", group="train_step", sig="aaaa", recompiles=0,
           trace_s=0.01, compile_s=compile_s, cache_hit=False)
    w.emit("pass_end", pass_id=0, step=10, samples=640, AvgCost=0.5,
           pass_time_s=1.0, samples_per_sec=sps, mfu=0.30,
           step_time_mean_s=p99 / 2, step_time_p50_s=p99 / 2,
           step_time_p99_s=p99)
    w.emit("run_end", status="completed")
    w.close()
    return d


def test_compare_regression_verdict_and_exit_code(tmp_path):
    a = _fake_run(tmp_path, "a", sps=1000.0, p99=0.010, compile_s=1.0)
    b = _fake_run(tmp_path, "b", sps=800.0, p99=0.020, compile_s=1.0)
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "compare", a, b],
        capture_output=True, text=True, env=SUBPROC_ENV, timeout=120,
    )
    # golden shape: per-metric rows with direction-aware verdicts, then
    # the overall verdict naming the regressed metrics; exit code 1
    assert r.returncode == 1, r.stdout + r.stderr
    assert "samples_per_sec" in r.stdout and "step_p99_ms" in r.stdout
    assert "verdict: REGRESSION" in r.stdout
    assert "samples_per_sec" in r.stdout.splitlines()[-1]
    # within-noise comparison: NO CHANGE, exit 0
    c = _fake_run(tmp_path, "c", sps=1010.0, p99=0.0101, compile_s=1.0)
    r2 = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "compare", a, c],
        capture_output=True, text=True, env=SUBPROC_ENV, timeout=120,
    )
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "verdict: NO CHANGE" in r2.stdout
    # --json carries the full document
    r3 = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "compare", a, b, "--json"],
        capture_output=True, text=True, env=SUBPROC_ENV, timeout=120,
    )
    doc = json.loads(r3.stdout)
    assert doc["verdict"] == "REGRESSION"
    assert "samples_per_sec" in doc["regressions"]
    assert "mfu" not in doc["regressions"]  # unchanged metric


def test_compare_bench_artifacts(tmp_path):
    from paddle_tpu.observability.compare import compare, load_side

    a = tmp_path / "BENCH_a.json"
    a.write_text(json.dumps({
        "n": 1, "cmd": "bench", "rc": 0,
        "tail": 'noise\n' + json.dumps({
            "metric": "resnet50_train_imgs_per_sec_per_chip", "value": 2000.0,
            "unit": "imgs/s", "vs_baseline": 1.0, "mfu": 0.30,
            "compile_s": 10.0,
            "legs": {"nmt_train_tokens_per_sec": {"value": 500000.0,
                                                  "unit": "tokens/s"}},
        }) + "\n",
    }))
    b = tmp_path / "BENCH_b.json"  # raw result-line file also accepted
    b.write_text(json.dumps({
        "metric": "resnet50_train_imgs_per_sec_per_chip", "value": 2400.0,
        "unit": "imgs/s", "vs_baseline": 1.2, "mfu": 0.36, "compile_s": 2.0,
        "legs": {"nmt_train_tokens_per_sec": {"value": 430000.0,
                                              "unit": "tokens/s"}},
    }))
    doc = compare(load_side(str(a)), load_side(str(b)))
    by = {m["metric"]: m["verdict"] for m in doc["metrics"]}
    assert by["resnet50_train_imgs_per_sec_per_chip"] == "IMPROVED"
    assert by["compile_total_s"] == "IMPROVED"          # lower is better
    assert by["nmt_train_tokens_per_sec"] == "REGRESSION"  # -14% throughput
    assert doc["verdict"] == "REGRESSION"  # any regression wins overall


# ---------------------------------------------------------------- follow


def test_metrics_follow_tails_live_stream(tmp_path):
    run_dir = str(tmp_path)
    w = obs.MetricsWriter(run_dir, host=0)
    w.emit("pass_end", pass_id=0, step=10, samples=64, AvgCost=0.5)
    w.flush()
    path = os.path.join(run_dir, "metrics.jsonl")
    g = follow(run_dir, poll_s=0.01, max_polls=200)
    assert next(g)["kind"] == "run_start"
    assert next(g)["kind"] == "pass_end"
    # live append while following: a complete record plus a TORN tail —
    # the record is yielded, the torn half stays buffered
    with open(path, "a") as f:
        f.write('{"v": 1, "kind": "checkpoint", "host": 0, "t": 1.0}\n'
                '{"v": 1, "kind": "run_')
    assert next(g)["kind"] == "checkpoint"
    with open(path, "a") as f:
        f.write('end", "host": 0, "t": 2.0, "status": "completed"}\n')
    rec = next(g)
    assert rec["kind"] == "run_end" and rec["status"] == "completed"
    # max_polls bounds the wait when nothing more arrives
    assert list(follow(run_dir, poll_s=0, max_polls=2))[-1]["kind"] == "run_end"


# ----------------------------------------------------------- warm resume


def _small_params():
    import jax.numpy as jnp

    return {"w": jnp.arange(8, dtype=jnp.float32)}


def test_warm_resume_skips_reverify_of_self_written_checkpoints(
        tmp_path, monkeypatch):
    from paddle_tpu.trainer import checkpoint as ckpt

    d = str(tmp_path)
    verified = []
    real = ckpt.verify_checkpoint
    monkeypatch.setattr(
        ckpt, "verify_checkpoint", lambda p: (verified.append(p), real(p))[1]
    )
    ckpt.save_checkpoint(d, 0, _small_params())
    path = os.path.join(d, "pass-00000")
    assert ckpt.written_this_process(path)

    # rollback-path lookups trust this process's own commits: no CRC walk
    verified.clear()
    assert ckpt.find_restorable_checkpoint(d, trust_own_writes=True) == path
    assert verified == []
    ckpt.load_checkpoint(path, trust_own_writes=True)
    assert verified == []

    # the default (cold-restore contract) still verifies in full
    verified.clear()
    assert ckpt.find_restorable_checkpoint(d) == path
    assert verified == [path]
    verified.clear()
    ckpt.load_checkpoint(path)
    assert verified == [path]

    # fresh process ⇒ empty write log ⇒ trust is inert (full verify)
    monkeypatch.setattr(ckpt, "_written_this_process", set())
    verified.clear()
    assert ckpt.find_restorable_checkpoint(d, trust_own_writes=True) == path
    assert verified == [path]


def test_quarantine_revokes_self_written_trust(tmp_path):
    from paddle_tpu.trainer import checkpoint as ckpt

    d = str(tmp_path)
    ckpt.save_checkpoint(d, 0, _small_params())
    path = os.path.join(d, "pass-00000")
    assert ckpt.written_this_process(path)
    assert ckpt._quarantine(path) is not None
    assert not ckpt.written_this_process(path)


def test_corrupt_trusted_checkpoint_falls_back_not_config_error(tmp_path):
    """A TRUSTED (verify-skipped) checkpoint whose bytes are torn on
    disk must enter the fallback chain, not re-raise as a config error
    — nothing CRC-verified it on the trusted path."""
    from paddle_tpu.trainer import checkpoint as ckpt

    d = str(tmp_path)
    ckpt.save_checkpoint(d, 0, _small_params())
    ckpt.save_checkpoint(d, 1, _small_params())
    newest = os.path.join(d, "pass-00001")
    assert ckpt.written_this_process(newest)
    # torn npz AFTER the manifest was recorded (fsync'd then damaged):
    # trust skips the CRC, so only deserialization can catch it
    npz = [os.path.join(newest, f) for f in os.listdir(newest)
           if f.endswith(".npz")][0]
    with open(npz, "r+b") as f:
        f.truncate(10)
    params, _, meta = ckpt.load_checkpoint(
        newest, trust_own_writes=True, fallback=True
    )
    # fell back to pass 0 instead of dying on BadZipFile
    assert meta["pass_id"] == 0
    assert os.path.isdir(newest + ".corrupt")
