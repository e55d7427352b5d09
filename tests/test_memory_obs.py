"""Memory observability (doc/observability.md "Memory & numerics
telemetry"): static per-launch-group plans on compile records,
pass-boundary live sampling (host-RSS-only degradation on the CPU
backend), the `paddle memory` analyzer, the OOM pre-mortem
(oom_report.json + EXIT_OOM=20) driven by the `trainer.oom` fault site,
and the supervisor's budget-consuming treatment of OOM deaths."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from paddle_tpu.observability import memory as obs_mem
from paddle_tpu.observability import metrics as obs
from paddle_tpu.resilience import EXIT_OOM

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROVIDER_DIR = os.path.join(os.path.dirname(__file__), "providers")
SUBPROC_ENV = {
    **os.environ,
    "PYTHONPATH": f"{REPO}:{REPO}/compat:{PROVIDER_DIR}",
    "JAX_PLATFORMS": "cpu",
}


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    obs.registry().reset()
    yield
    obs.configure("")


def _write_config(tmp_path):
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
    cfg_path = tmp_path / "cfg.py"
    cfg_path.write_text(src)
    return str(cfg_path)


def _records(run_dir):
    out = []
    for path in obs.metrics_files(str(run_dir)):
        out.extend(obs.read_records(path))
    return out


# ------------------------------------------------------------------ units


def test_is_oom_error_narrow():
    assert obs_mem.is_oom_error(RuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert obs_mem.is_oom_error(RuntimeError("Resource exhausted: ..."))
    assert obs_mem.is_oom_error(MemoryError("out of memory"))
    assert obs_mem.is_oom_error(RuntimeError("failed to allocate 2GB"))
    assert obs_mem.is_oom_error(obs_mem.SyntheticOomError("drill"))
    # a shape bug must crash loudly, never classify as OOM
    assert not obs_mem.is_oom_error(ValueError("shape mismatch [3] vs [4]"))
    assert not obs_mem.is_oom_error(RuntimeError("kaboom"))


def test_memory_analysis_of_graceful():
    class Plan:
        argument_size_in_bytes = 100
        output_size_in_bytes = 40
        temp_size_in_bytes = 60
        alias_size_in_bytes = 30
        generated_code_size_in_bytes = 10

    class Ok:
        def memory_analysis(self):
            return Plan()

    out = obs_mem.memory_analysis_of(Ok())
    assert out["mem_arg_bytes"] == 100
    # arg + out + temp + code - alias
    assert out["mem_total_bytes"] == 100 + 40 + 60 + 10 - 30

    class Raising:
        def memory_analysis(self):
            raise RuntimeError("unimplemented on this backend")

    assert obs_mem.memory_analysis_of(Raising()) is None

    class Empty:
        def memory_analysis(self):
            return object()  # no size attributes at all

    assert obs_mem.memory_analysis_of(Empty()) is None


def test_device_stats_none_degrades_to_host_only(monkeypatch):
    """The CPU backend's memory_stats() is None — and any backend may
    raise; both degrade to a host-RSS-only snapshot that still
    validates (tier-1 runs entirely on this path)."""

    class NoneDev:
        def memory_stats(self):
            return None

    class RaisingDev:
        def memory_stats(self):
            raise RuntimeError("no allocator stats")

    import jax

    for dev in (NoneDev(), RaisingDev()):
        monkeypatch.setattr(jax, "local_devices", lambda d=dev: [d])
        assert obs_mem.device_memory_stats() is None
        snap = obs_mem.sample_memory()
        assert snap["host_rss_bytes"] > 0
        assert "hbm_peak_bytes" not in snap


def test_device_stats_summed_over_devices(monkeypatch):
    class Dev:
        def __init__(self, n):
            self.n = n

        def memory_stats(self):
            return {"bytes_in_use": 10 * self.n,
                    "peak_bytes_in_use": 20 * self.n,
                    "bytes_limit": 100}

    import jax

    monkeypatch.setattr(jax, "local_devices", lambda: [Dev(1), Dev(2)])
    stats = obs_mem.device_memory_stats()
    assert stats == {"bytes_in_use": 30, "peak_bytes_in_use": 60,
                     "devices": 2, "bytes_limit": 200}
    snap = obs_mem.sample_memory()
    assert snap["hbm_peak_bytes"] == 60 and snap["hbm_limit_bytes"] == 200


def test_sample_and_emit_record_validates(tmp_path):
    obs.configure(str(tmp_path))
    snap = obs_mem.sample_and_emit(pass_id=3, step=7)
    assert snap["host_rss_bytes"] > 0
    obs.flush()
    recs = [r for r in _records(tmp_path) if r["kind"] == "memory"]
    assert len(recs) == 1
    assert obs.validate_record(recs[0]) == []
    assert recs[0]["pass"] == 3 and recs[0]["step"] == 7
    # the gauges ride the registry for the next pass_end snapshot
    assert obs.registry().snapshot()["mem.host_rss_bytes"] > 0


def test_trigger_oom_report_backstop_not_fired(tmp_path):
    """Healthy path: report written + kind=oom record flushed, and the
    forensics backstop timer is cancelled (exit_fn never called)."""
    obs.configure(str(tmp_path))
    exits = []
    err = obs_mem.SyntheticOomError("unit")
    path = obs_mem.trigger_oom_report(
        str(tmp_path), err,
        groups=[{"group": "train_step", "sig": "ab", "mem_total_bytes": 512},
                {"group": "test_fwd", "sig": "cd", "mem_total_bytes": 1024}],
        live={"host_rss_bytes": 123},
        where={"pass": 1, "step": 5},
        exit_fn=exits.append,
    )
    assert exits == []  # backstop cancelled on the normal path
    report = json.load(open(path))
    assert report["reason"] == "oom"
    # ranked: the biggest plan leads
    assert [g["group"] for g in report["groups"]] == ["test_fwd", "train_step"]
    assert report["static_total_bytes"] == 1536
    assert report["where"] == {"pass": 1, "step": 5}
    assert "metrics_tail" in report
    oom_recs = [r for r in _records(tmp_path) if r["kind"] == "oom"]
    assert len(oom_recs) == 1 and obs.validate_record(oom_recs[0]) == []
    assert oom_recs[0]["report"] == path


# --------------------------------------------------- smoke train (shared)


@pytest.fixture(scope="module")
def smoke_run(tmp_path_factory):
    """One in-process 2-pass smoke train with telemetry on — shared by
    the record-shape tests below (training twice would double the suite
    cost for identical evidence)."""
    tmp_path = tmp_path_factory.mktemp("mem_smoke")
    cfg = _write_config(tmp_path)
    sys.path.insert(0, PROVIDER_DIR)
    from paddle_tpu.config import parse_config
    from paddle_tpu.trainer import Trainer
    from paddle_tpu.utils.flags import _Flags

    # flags of its own: a module's shared run leaves nothing in FLAGS
    save_dir = str(tmp_path / "out")
    flags = _Flags(config=cfg, save_dir=save_dir, num_passes=2, log_period=0,
                   seed=7)
    obs.registry().reset()
    try:
        trainer = Trainer(parse_config(cfg, ""), flags)
        trainer.train()
    finally:
        obs.configure("")
        sys.path.remove(PROVIDER_DIR)
    return save_dir, _records(save_dir)


def test_smoke_compile_records_carry_static_plan(smoke_run):
    _save_dir, recs = smoke_run
    compiles = [r for r in recs if r["kind"] == "compile"]
    assert compiles, "no compile records in the smoke run"
    with_mem = [c for c in compiles if "mem_total_bytes" in c]
    assert with_mem, "no compile record carries the static memory plan"
    for c in with_mem:
        assert obs.validate_record(c) == []
        assert c["mem_total_bytes"] >= 0
        assert c["mem_arg_bytes"] > 0  # params alone are nonzero


def test_smoke_memory_records_per_pass(smoke_run):
    _save_dir, recs = smoke_run
    mems = [r for r in recs if r["kind"] == "memory"]
    assert {m["pass"] for m in mems} == {0, 1}  # one per pass boundary
    for m in mems:
        assert obs.validate_record(m) == []
        assert m["host_rss_bytes"] > 0
        # CPU backend: allocator stats unavailable — degraded, not broken
        assert "hbm_peak_bytes" not in m
    # the gauges rode the pass_end counters snapshot
    pass_ends = [r for r in recs if r["kind"] == "pass_end"]
    assert pass_ends and all(
        (p.get("counters") or {}).get("mem.host_rss_bytes", 0) > 0
        for p in pass_ends
    )


def test_paddle_memory_renders_smoke_run(smoke_run):
    """`paddle memory <run_dir>` is jax-free: run it in a subprocess
    with jax import poisoned to prove it."""
    save_dir, _recs = smoke_run
    code = (
        "import sys; sys.modules['jax'] = None\n"
        "from paddle_tpu.observability.memory import main\n"
        f"sys.exit(main([{save_dir!r}]))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=SUBPROC_ENV,
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    assert "static footprint per launch group" in out.stdout
    assert "train_step" in out.stdout
    assert "device stats unavailable" in out.stdout  # CPU degradation
    # and --json round-trips
    doc = json.loads(subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "memory", save_dir,
         "--json"],
        env=SUBPROC_ENV, capture_output=True, text=True, timeout=60,
    ).stdout)
    assert doc["groups"] and doc["static_total_bytes"] > 0
    assert "0" in doc["live"] or 0 in doc["live"]


def test_paddle_memory_golden_headroom_table(tmp_path, capsys):
    """Synthetic TPU-shaped stream: static rows ranked, live peak with
    headroom computed against the capacity table for the device kind
    the roofline records name (no allocator limit in the records)."""
    w = obs.MetricsWriter(str(tmp_path), host=0)
    w.emit("compile", group="train_step", sig="aaaa", mem_arg_bytes=10 ** 9,
           mem_out_bytes=10 ** 9, mem_temp_bytes=2 * 10 ** 9,
           mem_total_bytes=4 * 10 ** 9)
    w.emit("compile", group="test_fwd", sig="bbbb", mem_arg_bytes=10 ** 8,
           mem_out_bytes=10 ** 8, mem_temp_bytes=0,
           mem_total_bytes=2 * 10 ** 8)
    w.emit("roofline", group="train_step", sig="aaaa", launches=3,
           exec_s=1.0, device_kind="TPU v4")
    w.emit("memory", host_rss_bytes=10 ** 9, hbm_in_use_bytes=5 * 10 ** 9,
           hbm_peak_bytes=8 * 10 ** 9, devices=1)
    w.close()
    assert obs_mem.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    lines = out.splitlines()
    # ranked: train_step (4 GB) before test_fwd (0.2 GB)
    assert lines.index(
        next(l for l in lines if l.startswith("train_step"))
    ) < lines.index(next(l for l in lines if l.startswith("test_fwd")))
    assert "static total: 4200.00 MB over 2 group(s)" in out
    # v4 capacity 32 GB, peak 8 GB -> 25.0%, headroom 24 GB
    assert "hbm peak 8.00 GB" in out
    assert "capacity 32.00 GB" in out and "peak 25.0%" in out
    assert "headroom 24.00 GB" in out


def test_paddle_memory_capacity_scales_by_device_count(tmp_path, capsys):
    """The live records sum peak over local devices, so the capacity
    table fallback must scale by the recorded device count — a 4-chip
    v4 host is 4 x 32 GB, not 32 (which would read >100% used)."""
    w = obs.MetricsWriter(str(tmp_path), host=0)
    w.emit("roofline", group="train_step", sig="aaaa", launches=1,
           exec_s=1.0, device_kind="TPU v4")
    w.emit("memory", host_rss_bytes=10 ** 9, hbm_in_use_bytes=40 * 10 ** 9,
           hbm_peak_bytes=64 * 10 ** 9, devices=4)
    w.close()
    assert obs_mem.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "capacity 128.00 GB" in out  # 4 devices x 32 GB
    assert "peak 50.0%" in out and "headroom 64.00 GB" in out


def test_paddle_memory_no_data(tmp_path):
    assert obs_mem.main([str(tmp_path / "nowhere")]) == 1


# ------------------------------------------------------------- chaos e2e


def test_chaos_oom_exit20_and_premortem(tmp_path):
    """Injected trainer.oom at a launch boundary: `paddle train` exits
    EXIT_OOM=20, oom_report.json carries the ranked static groups + the
    metrics tail, and the flushed kind=oom record survives the death."""
    cfg = _write_config(tmp_path)
    save_dir = str(tmp_path / "out")
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "train",
         f"--config={cfg}", f"--save_dir={save_dir}", "--num_passes=1",
         "--fault_spec=trainer.oom=raise@3"],
        env=SUBPROC_ENV, capture_output=True, text=True, timeout=240,
    )
    assert out.returncode == EXIT_OOM, (out.returncode, out.stderr[-2000:])
    report = json.load(open(os.path.join(save_dir, obs_mem.OOM_REPORT)))
    assert report["reason"] == "oom"
    assert "RESOURCE_EXHAUSTED" in report["error"]
    assert any(g["group"] == "train_step" for g in report["groups"])
    assert report["static_total_bytes"] > 0
    assert report["metrics_tail"], "telemetry tail missing from pre-mortem"
    recs = _records(save_dir)
    oom_recs = [r for r in recs if r["kind"] == "oom"]
    assert len(oom_recs) == 1 and obs.validate_record(oom_recs[0]) == []
    # the analyzer warns about it
    from paddle_tpu.observability.analyze import analyze, load_run

    doc = analyze(load_run(save_dir))
    assert any("OOM" in w for w in doc["warnings"])
    # and `paddle memory` renders the pre-mortem jax-free
    mem_out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "memory", save_dir],
        env=SUBPROC_ENV, capture_output=True, text=True, timeout=60,
    )
    assert mem_out.returncode == 0
    assert "OOM pre-mortem" in mem_out.stdout


def test_supervise_oom_consumes_budget_and_embeds_premortem(tmp_path):
    """An OOM loop is deterministic poison: `paddle supervise` charges
    each exit-20 death to --restart_budget (never free like exit 18)
    and its final crash report embeds the child's oom_report.json."""
    cfg = _write_config(tmp_path)
    save_dir = str(tmp_path / "out")
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "supervise",
         f"--config={cfg}", f"--save_dir={save_dir}", "--num_passes=1",
         "--restart_budget=1", "--restart_base_delay=0.01",
         "--fault_spec=trainer.oom=raise@2"],
        env=SUBPROC_ENV, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == EXIT_OOM, (out.returncode, out.stderr[-2000:])
    report = json.load(
        open(os.path.join(save_dir, "supervise", "crash_report.json"))
    )
    # budget consumed: exactly budget+1 attempts, every death an OOM
    assert report["reason"] == "restart_budget_exhausted"
    assert [a["exit_code"] for a in report["attempts"]] == [EXIT_OOM] * 2
    assert report.get("oom_report", {}).get("reason") == "oom"
