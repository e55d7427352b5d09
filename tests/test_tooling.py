"""Ops tooling: plotcurve parsing, model diagram, cluster launch dry run."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_plotcurve_parses_and_plots():
    from paddle_tpu.utils.plotcurve import ascii_plot, parse_log

    log = [
        "[x I paddle_tpu] Pass 0 done: samples=100 AvgCost=0.9 CurrentCost=0.9  e.classification_error: classification_error=0.5  (10 samples/s)",
        "[x I paddle_tpu] Pass 1 done: samples=100 AvgCost=0.7 CurrentCost=0.6  e.classification_error: classification_error=0.3  (10 samples/s)",
        "noise line",
        "[x I paddle_tpu] Pass 2 done: samples=100 AvgCost=0.5 CurrentCost=0.4  e.classification_error: classification_error=0.2  (10 samples/s)",
    ]
    series = parse_log(log)
    assert series["AvgCost"] == [0.9, 0.7, 0.5]
    assert series["classification_error"] == [0.5, 0.3, 0.2]
    art = ascii_plot(series["AvgCost"])
    assert "*" in art and "0.9" in art


def test_make_model_diagram(tmp_path):
    from paddle_tpu.config import parse_config
    from paddle_tpu.utils.make_model_diagram import make_diagram

    cfg_file = tmp_path / "conf.py"
    cfg_file.write_text(
        "from paddle.trainer_config_helpers import *\n"
        "settings(batch_size=4, learning_rate=0.1)\n"
        "d = data_layer('x', size=4)\n"
        "o = fc_layer(input=d, size=2, act=SoftmaxActivation(), name='out')\n"
        "outputs(classification_cost(input=o, label=data_layer('label', size=2)))\n"
    )
    cfg = parse_config(str(cfg_file))
    dot = make_diagram(cfg.model_config)
    assert dot.startswith("digraph") and '"x" -> "out"' in dot


def test_cluster_launch_dry_run(tmp_path):
    conf = tmp_path / "conf.py"
    conf.write_text("HOSTS = ['u@h0', 'u@h1']\n")
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.utils.cluster_launch",
         "--conf", str(conf), "--workdir", "/job", "--dry_run",
         "--", "--config=train.conf", "--mesh_shape=data=16"],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "PYTHONPATH": f"{REPO}:{REPO}/compat"},
    )
    assert out.returncode == 0, out.stderr
    assert "--process_id=0" in out.stdout and "--process_id=1" in out.stdout
    assert "--coordinator_address=h0:8476" in out.stdout
    assert "u@h1" in out.stdout


def _write_fake_ssh(bin_dir, body):
    """A stub `ssh` on PATH: argv is [-o, BatchMode=yes, host, remote] —
    $3 is the host, $4 the remote command (cluster_launch's call shape)."""
    ssh = bin_dir / "ssh"
    ssh.write_text("#!/bin/sh\nhost=$3\nremote=$4\n" + body)
    ssh.chmod(0o755)
    return {**os.environ, "PATH": f"{bin_dir}:{os.environ['PATH']}",
            "PYTHONPATH": f"{REPO}:{REPO}/compat"}


def _await_lines(path, n):
    """Shell for a stub host that is about to fail: wait until ``n``
    hosts have logged their call. The launcher kills the survivors the
    moment it sees a failure, so a host that fails at once can take a
    neighbour down before that neighbour's `echo` has run."""
    return f"while [ \"$(wc -l < {path})\" -lt {n} ]; do sleep 0.05; done;"


def test_cluster_launch_tears_down_on_first_host_failure(tmp_path):
    """One dead host must fail the whole launch promptly (and kill the
    surviving hosts) instead of leaving the launcher blocked in a serial
    wait while the others hang in collectives."""
    import time

    conf = tmp_path / "conf.py"
    conf.write_text("HOSTS = ['u@h_fail', 'u@h_hang']\n")
    env = _write_fake_ssh(tmp_path, (
        "case \"$host\" in\n"
        "  *fail*) sleep 0.3; exit 3;;\n"
        "  *) sleep 120;;\n"
        "esac\n"
    ))
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.utils.cluster_launch",
         "--conf", str(conf), "--workdir", "/job",
         "--poll_interval", "0.1", "--grace", "2",
         "--", "--config=train.conf"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=60,
    )
    elapsed = time.monotonic() - t0
    assert out.returncode == 3, (out.returncode, out.stderr)
    assert elapsed < 30, elapsed  # did not wait out the 120s survivor
    # the failing rank is named in the exit message
    assert "rank 0" in out.stderr and "u@h_fail" in out.stderr


def test_cluster_launch_relaunches_with_auto_resume(tmp_path):
    """--max_restarts: after a host failure the whole job relaunches
    with --init_model_path=auto appended (resume from the newest
    verified checkpoint), and a clean second round exits 0."""
    conf = tmp_path / "conf.py"
    conf.write_text("HOSTS = ['u@h_once', 'u@h_ok']\n")
    calls = tmp_path / "calls.log"
    marker = tmp_path / "round2"
    env = _write_fake_ssh(tmp_path, (
        f"echo \"$remote\" >> {calls}\n"
        "case \"$host\" in\n"
        f"  *once*) if [ ! -f {marker} ]; then touch {marker};"
        f" {_await_lines(calls, 2)} exit 2; fi; exit 0;;\n"
        "  *) exit 0;;\n"
        "esac\n"
    ))
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.utils.cluster_launch",
         "--conf", str(conf), "--workdir", "/job",
         "--poll_interval", "0.1", "--grace", "2",
         "--max_restarts", "1", "--restart_delay", "0.1",
         "--", "--config=train.conf"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=60,
    )
    assert out.returncode == 0, (out.returncode, out.stderr)
    assert "relaunching" in out.stderr
    lines = calls.read_text().splitlines()
    assert len(lines) == 4  # 2 hosts x 2 rounds
    assert all("--init_model_path=auto" not in l for l in lines[:2])
    assert all("--init_model_path=auto" in l for l in lines[2:])


def test_cluster_launch_names_signal_deaths(tmp_path):
    """Satellite (doc/resilience.md): a host killed by a signal is
    reported by signal NAME (rc=-15 → SIGTERM), and the launcher's own
    exit status follows the 128+signum shell convention."""
    conf = tmp_path / "conf.py"
    conf.write_text("HOSTS = ['u@h_sig', 'u@h_ok']\n")
    env = _write_fake_ssh(tmp_path, (
        "case \"$host\" in\n"
        "  *sig*) sleep 0.3; kill -TERM $$;;\n"
        "  *) sleep 120;;\n"
        "esac\n"
    ))
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.utils.cluster_launch",
         "--conf", str(conf), "--workdir", "/job",
         "--poll_interval", "0.1", "--grace", "2",
         "--", "--config=train.conf"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=60,
    )
    assert out.returncode == 143, (out.returncode, out.stderr)
    assert "SIGTERM" in out.stderr and "rc=-15" in out.stderr


def test_cluster_launch_preemption_exit_is_budget_free(tmp_path):
    """A host exiting EXIT_PREEMPTED (18 — clean preemption save) must
    trigger an auto-resume relaunch that consumes NO restart budget:
    even --max_restarts=0 (fail fast) relaunches."""
    conf = tmp_path / "conf.py"
    conf.write_text("HOSTS = ['u@h_pre', 'u@h_ok']\n")
    calls = tmp_path / "calls.log"
    marker = tmp_path / "round2"
    env = _write_fake_ssh(tmp_path, (
        f"echo \"$remote\" >> {calls}\n"
        "case \"$host\" in\n"
        f"  *pre*) if [ ! -f {marker} ]; then touch {marker};"
        f" {_await_lines(calls, 2)} exit 18; fi; exit 0;;\n"
        "  *) exit 0;;\n"
        "esac\n"
    ))
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.utils.cluster_launch",
         "--conf", str(conf), "--workdir", "/job",
         "--poll_interval", "0.1", "--grace", "2",
         "--max_restarts", "0", "--restart_delay", "0.1",
         "--", "--config=train.conf"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=60,
    )
    assert out.returncode == 0, (out.returncode, out.stderr)
    assert "preempt" in out.stderr
    assert "no restart budget" in out.stderr
    lines = calls.read_text().splitlines()
    assert len(lines) == 4  # 2 hosts x 2 rounds despite max_restarts=0
    assert all("--init_model_path=auto" in l for l in lines[2:])


def test_cluster_launch_elastic_drops_repeat_offender(tmp_path):
    """--elastic_min_hosts: a host that caused two job failures is
    dropped from the next relaunch; the survivors get recomputed ranks
    and --num_processes, and the job completes without it."""
    conf = tmp_path / "conf.py"
    conf.write_text("HOSTS = ['u@h_bad', 'u@h_ok']\n")
    calls = tmp_path / "calls.log"
    # h_ok hangs while h_bad is around (it would be torn down anyway)
    # and exits 0 once it is the only host (--num_processes=1): round 3
    # — after the drop — is the clean single-host completion
    env = _write_fake_ssh(tmp_path, (
        f"echo \"$host $remote\" >> {calls}\n"
        "case \"$host\" in\n"
        "  *bad*) sleep 0.2; exit 2;;\n"
        "  *) case \"$remote\" in\n"
        "       *--num_processes=1*) exit 0;;\n"
        "       *) sleep 120;;\n"
        "     esac;;\n"
        "esac\n"
    ))
    # budget of ONE: round 1 consumes it; round 2's failure triggers the
    # drop, whose relaunch must be budget-free (the drop IS the fix) —
    # with budget accounting on the drop round the job would give up here
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.utils.cluster_launch",
         "--conf", str(conf), "--workdir", "/job",
         "--poll_interval", "0.1", "--grace", "2",
         "--max_restarts", "1", "--restart_delay", "0.1",
         "--elastic_min_hosts", "1",
         "--", "--config=train.conf"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=60,
    )
    assert out.returncode == 0, (out.returncode, out.stderr)
    assert "dropping host u@h_bad" in out.stderr, out.stderr
    assert "no restart budget consumed" in out.stderr
    lines = calls.read_text().splitlines()
    rounds3 = [l for l in lines if "--num_processes=1" in l]
    assert rounds3 and all("h_ok" in l.split()[0] for l in rounds3)
    assert all("--process_id=0" in l for l in rounds3)


def test_cluster_launch_heartbeat_staleness_names_wedged_rank(tmp_path):
    """Tentpole: a wedged-but-alive rank (process running, heartbeat
    stale) is detected by the launcher's staleness poll, named, and the
    job torn down with the hang exit code — the failure process
    liveness alone can never see."""
    import time

    conf = tmp_path / "conf.py"
    conf.write_text("HOSTS = ['u@h_beat', 'u@h_wedge']\n")
    hb_dir = tmp_path / "hb"
    hb_dir.mkdir()
    # the stub hosts write the heartbeat files themselves: h_beat renews
    # every 0.2s, h_wedge writes ONE beat then goes silent while staying
    # alive — exactly a wedged collective
    env = _write_fake_ssh(tmp_path, (
        "case \"$host\" in\n"
        "  *beat*)\n"
        "    i=0\n"
        "    while [ $i -lt 300 ]; do\n"
        f"      echo '{{\"host\": 0, \"t\": '$(date +%s)'}}' > {hb_dir}/host-0.json\n"
        "      sleep 0.2; i=$((i+1))\n"
        "    done;;\n"
        "  *wedge*)\n"
        f"    echo '{{\"host\": 1, \"t\": '$(date +%s)'}}' > {hb_dir}/host-1.json\n"
        "    sleep 120;;\n"
        "esac\n"
    ))
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.utils.cluster_launch",
         "--conf", str(conf), "--workdir", "/job",
         "--poll_interval", "0.1", "--grace", "2",
         "--heartbeat_startup_grace", "0",  # stubs beat instantly
         "--", "--config=train.conf",
         "--heartbeat_interval=0.2", "--heartbeat_stale_after=3",
         f"--heartbeat_dir={hb_dir}"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120,
    )
    elapsed = time.monotonic() - t0
    from paddle_tpu.resilience import EXIT_HANG

    assert out.returncode == EXIT_HANG, (out.returncode, out.stderr)
    assert elapsed < 60, elapsed  # did not wait out the 120s wedge
    assert "rank 1" in out.stderr and "heartbeat stale" in out.stderr
    assert "wedged" in out.stderr


def test_cluster_launch_relative_heartbeat_dir_disables_monitoring(capsys):
    """A relative heartbeat dir resolves differently on the launcher
    and the hosts — monitoring must refuse it loudly instead of watching
    an empty local directory and tearing down healthy jobs."""
    from paddle_tpu.utils.cluster_launch import _heartbeat_config

    assert _heartbeat_config(
        ["--heartbeat_interval=5", "--save_dir=ckpts"]
    ) is None
    assert "relative" in capsys.readouterr().err
    dir_, stale = _heartbeat_config(
        ["--heartbeat_interval=5", "--heartbeat_dir=/shared/hb"]
    )
    assert dir_ == "/shared/hb" and stale == 15.0  # 3x interval default
    assert _heartbeat_config(["--config=c.py"]) is None  # hb off


def test_teardown_escalates_on_one_shared_deadline(monkeypatch):
    """Satellite: _teardown must not serially wait ≥0.1s per
    already-expired host — once the shared grace deadline has passed,
    the remaining hosts skip straight to SIGKILL."""
    import signal as _signal
    import time

    from paddle_tpu.utils import cluster_launch as cl

    class FakeProc:
        """A host that ignores SIGTERM for the whole grace window."""

        def __init__(self):
            self.signals = []
            self.wait_timeouts = []

        def got(self, sig):
            self.signals.append(sig)

        def poll(self):
            return None

        def wait(self, timeout=None):
            if timeout is None:
                return -9  # SIGKILL always lands
            self.wait_timeouts.append(timeout)
            time.sleep(timeout)  # stubborn: rides out the full grace
            raise subprocess.TimeoutExpired("ssh", timeout)

    monkeypatch.setattr(cl, "_signal_group", lambda p, sig: p.got(sig))
    procs = [FakeProc() for _ in range(20)]
    t0 = time.monotonic()
    cl._teardown(procs, grace_s=0.2)
    elapsed = time.monotonic() - t0
    # old behavior: 19 extra clamped 0.1s waits ≈ 2.1s total
    assert elapsed < 1.0, elapsed
    # only the host(s) inside the grace window got a timed wait; the
    # rest were killed outright
    assert sum(len(p.wait_timeouts) for p in procs) == 1
    for p in procs:
        assert p.signals == [_signal.SIGTERM, _signal.SIGKILL]


def test_cmd_arguments_doc_flags_exist():
    """Every `--flag` referenced in a doc/cmd_arguments.md table row must
    exist in utils/flags.py, so the flag reference can't silently rot —
    and (the reverse direction) every flag the code defines must appear
    in the doc, so a new flag can't land undocumented."""
    import dataclasses
    import re

    from paddle_tpu.utils.flags import _Flags

    known = {f.name for f in dataclasses.fields(_Flags)}
    doc = open(os.path.join(REPO, "doc", "cmd_arguments.md")).read()
    referenced = set()
    for line in doc.splitlines():
        if line.lstrip().startswith("|"):
            referenced.update(re.findall(r"`--([A-Za-z0-9_]+)", line))
    assert len(referenced) > 20, "doc table parsing broke"
    missing = referenced - known
    assert not missing, (
        f"doc/cmd_arguments.md references flags missing from "
        f"utils/flags.py: {sorted(missing)}"
    )
    # anywhere in the doc counts for the reverse check (a few flags are
    # described in prose rather than a table row)
    documented = set(re.findall(r"`--([A-Za-z0-9_]+)", doc))
    undocumented = known - documented
    assert not undocumented, (
        f"utils/flags.py defines flags doc/cmd_arguments.md never "
        f"mentions: {sorted(undocumented)}"
    )


def test_supervise_dry_run_prints_plan_without_launching(tmp_path):
    """`paddle supervise --dry_run` prints the child command and restart
    policy, launches nothing, and needs no jax/accelerator."""
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "supervise",
         "--dry_run=1", "--config=cfg.py", "--restart_budget=2",
         f"--supervise_dir={tmp_path / 'sup'}"],
        capture_output=True, text=True, cwd=REPO, timeout=60,
        env={**os.environ, "PYTHONPATH": f"{REPO}:{REPO}/compat",
             "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr
    assert "--config=cfg.py" in out.stdout
    assert "--init_model_path=auto" in out.stdout
    assert "restart_budget=2" in out.stdout
    assert not (tmp_path / "sup").exists()


def test_show_pb_inspects_shard_and_checkpoint(tmp_path, capsys):
    """show_pb analog (ref python/paddle/utils/show_pb.py): dumps binary
    shards, checkpoint trees, and merged models."""
    import numpy as np

    from paddle_tpu.data.binary import write_shard
    from paddle_tpu.data.provider import dense_vector, integer_value
    from paddle_tpu.utils import show_pb

    shard = tmp_path / "shard.npz"
    write_shard(str(shard), [[[0.5, 1.0], 1], [[2.0, 3.0], 0]],
                [dense_vector(2), integer_value(2)])
    assert show_pb.show(str(shard)) == 0
    out = capsys.readouterr().out
    assert "samples: 2" in out and "dense" in out and "index" in out

    from paddle_tpu.trainer.checkpoint import save_checkpoint

    save_checkpoint(str(tmp_path / "model"), 0,
                    {"_fc.w0": np.ones((3, 2), np.float32)})
    assert show_pb.show(str(tmp_path / "model" / "pass-00000")) == 0
    out = capsys.readouterr().out
    assert "_fc.w0" in out and "(3, 2)" in out and "total parameters: 6" in out


def test_torch2paddle_converts_and_trains(tmp_path):
    """torch2paddle analog (ref python/paddle/utils/torch2paddle.py):
    torch Linear weights convert (transposed) into a checkpoint that
    initializes our fc layers and reproduces torch's forward."""
    import subprocess

    import numpy as np
    import torch

    from paddle_tpu.utils.torch2paddle import convert, convert_tensor

    # layout rules
    w = np.arange(6, dtype=np.float32).reshape(2, 3)  # torch [out=2, in=3]
    assert convert_tensor("x", w).shape == (3, 2)
    c = np.zeros((4, 3, 2, 2), np.float32)  # conv OIHW
    assert convert_tensor("c", c).shape == (4, 12)

    lin = torch.nn.Linear(4, 2)
    sd = lin.state_dict()
    model_path = tmp_path / "m.pth"
    torch.save(sd, str(model_path))
    layers = tmp_path / "layers.txt"
    layers.write_text("out\n")

    env = {**os.environ, "PYTHONPATH": f"{REPO}:{REPO}/compat",
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.utils.torch2paddle",
         "-i", str(model_path), "-l", str(layers), "-o", str(tmp_path / "ckpt")],
        capture_output=True, text=True, cwd=REPO, env=env,
    )
    assert r.returncode == 0, r.stderr
    assert (tmp_path / "ckpt" / "pass-00000" / "params.npz").exists()

    # the converted fc reproduces torch's forward: x @ w0 + wbias
    with np.load(tmp_path / "ckpt" / "pass-00000" / "params.npz") as z:
        w0, wb = z["_out.w0"], z["_out.wbias"]
    x = np.random.RandomState(0).rand(5, 4).astype(np.float32)
    ours = x @ w0 + wb
    theirs = lin(torch.from_numpy(x)).detach().numpy()
    np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-6)


def _run_bench_with(patch, *argv):
    """`python bench.py <argv>` in a child, on the CPU, with `patch`
    applied to the module first."""
    code = (f"import sys; sys.argv = ['bench.py', *{list(argv)!r}]\n"
            f"import bench\n{patch}\nsys.exit(bench.main())\n")
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})


def test_bench_exits_nonzero_when_a_leg_raises():
    """No catch-all: a leg that raises ends the run with its traceback
    and a non-zero exit code — never a `bench_failed` line and exit 0,
    never a retry on another backend."""
    r = _run_bench_with(
        "def boom(**kw):\n    raise RuntimeError('leg exploded')\n"
        "bench.bench_serve = boom", "serve")
    assert r.returncode == 1, (r.returncode, r.stderr[-2000:])
    assert "RuntimeError: leg exploded" in r.stderr
    assert not [l for l in r.stdout.splitlines() if l.startswith("{")]


def test_bench_line_names_the_device_it_ran_on(tmp_path):
    """Every result line carries platform / device_kind / device_count as
    jax reports them, and no field from an earlier run."""
    r = _run_bench_with(
        "bench.bench_serve = lambda **kw: "
        f"(123.0, {{'dtype': kw['dtype'], 'run_dir': {str(tmp_path)!r}}})",
        "serve")
    assert r.returncode == 0, r.stderr[-2000:]
    (line,) = [json.loads(l) for l in r.stdout.splitlines()
               if l.startswith("{")]
    assert line["metric"] == "serve_cpu_smoke_goodput_tokens_per_sec"
    assert line["value"] == 123.0 and line["dtype"] == "float32"
    assert line["unit"] == "tokens/s"
    assert (line["platform"], line["device_kind"]) == ("cpu", "cpu")
    assert line["device_count"] >= 1
    assert "last_measured" not in line and "backend" not in line


@pytest.mark.parametrize("argv", [("nmt",), ()], ids=["nmt", "no-argument"])
def test_bench_takes_only_the_serve_leg(argv):
    """`serve` is the one leg left: anything else, or nothing, exits 2
    with no result line and names the benchmark for training speed."""
    r = _run_bench_with("bench.bench_serve = None", *argv)
    assert r.returncode == 2, (r.returncode, r.stderr[-2000:])
    assert '{"metric"' not in r.stdout
    assert "perfbench.run" in r.stderr


def test_bench_has_no_harness_that_hides_the_device():
    """The pieces PR 21 removed stay removed: no subprocess probe, no
    supervisor, no forced-CPU retry, no embedded stale rows."""
    sys.path.insert(0, REPO)
    import bench

    for gone in ("_supervise", "_good_json_line", "_load_last_measured",
                 "PROBE_TIMEOUT_S"):
        assert not hasattr(bench, gone), gone
    import paddle_tpu.utils.backend_guard as bg

    assert [n for n in vars(bg) if not n.startswith("_")
            and callable(getattr(bg, n))] == ["ensure_cpu_mesh"]
