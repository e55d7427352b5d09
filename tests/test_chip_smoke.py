"""chip_smoke.py rehearsed on the CPU, and the two start-up contracts it
leans on: one place for the compile cache, and `--use_tpu` as a
requirement. The rehearsal is the script's own ``--rehearse`` argument:
toy widths, every phase, kernels interpreted — and still a failure,
because the platform is not a TPU.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(tmp_path, devices=1, **extra):
    """The caller's environment with an explicit CPU platform, ``devices``
    virtual devices and a compile cache of this test's own (hit detection
    counts entries in the directory, so a cache shared with concurrently
    running tests would blur it)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PADDLE_TPU_PALLAS_INTERPRET")}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.update(extra)
    return env


def _run(args, env, cwd=REPO, script=SMOKE):
    r = subprocess.run([sys.executable, script] + args, env=env, cwd=cwd,
                       capture_output=True, text=True, timeout=600)
    lines = [l for l in r.stdout.splitlines() if l.strip()]
    phases = [json.loads(l) for l in lines if l.startswith('{"phase"')]
    return r, lines, {p["phase"]: p for p in phases}


def test_rehearsal_runs_every_phase_and_fails_off_the_tpu(tmp_path):
    r, lines, phases = _run(["--rehearse"], _env(tmp_path))
    assert r.returncode == 1, r.stdout + r.stderr
    # the contract's last line, and nothing else in it
    assert json.loads(lines[-1]) == {
        "ok": False, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert list(phases) == [
        "device", "train bf16+pallas_rnn", "check-checkpoint",
        "train library defaults",
        "train library defaults again (compile cache)",
        "serve", "static generator"]
    assert all(p["ok"] for p in phases.values()), phases
    prod = phases["train bf16+pallas_rnn"]
    assert len(prod["losses"]) == 8 and prod["parameters"] > 0
    assert prod["native_datapath"] in ("loaded", "NumPy fallback")
    # interpreted kernels are plain HLO: no Mosaic call off the TPU
    assert prod["train_step"]["mosaic_calls"] == 0
    # one cache, found again by the next process
    assert phases["train library defaults"]["train_step"]["cache_hit"] is False
    again = phases["train library defaults again (compile cache)"]
    assert again["train_step"]["cache_hit"] is True
    assert phases["serve"]["compiles"] == {"serve_prefill": [0],
                                           "serve_decode": [0]}
    assert phases["serve"]["answered"] == 12
    # f32 on the CPU: the engine's greedy tokens ARE the generator's
    assert phases["static generator"]["requests_token_for_token"] == "12/12"
    # a smoke test prints seconds, never a rate
    assert "per_sec" not in r.stdout and "/s" not in r.stdout


def test_four_chip_option_runs_only_the_mesh_and_its_reference(tmp_path):
    r, lines, phases = _run(["--rehearse", "--four-chips"],
                            _env(tmp_path, devices=4))
    assert r.returncode == 1, r.stdout + r.stderr
    assert json.loads(lines[-1]) == {
        "ok": False, "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
    assert list(phases) == ["train data=4", "train one device (reference)"]
    assert all(p["ok"] for p in phases.values()), phases
    step = phases["train data=4"]["train_step"]
    # shard device sets, not mesh.shape: the executable's inputs live on
    # four distinct devices, the batch is split, the gradients all-reduced
    assert step["devices"] == 4 and step["sharded_inputs"] >= 3
    assert step["collectives"]["all-reduce"] >= 1
    ref = phases["train one device (reference)"]
    assert ref["train_step"]["devices"] == 1
    assert max(ref["loss_rel_diff_per_step"]) <= ref["tolerance"]


def test_refuses_to_run_with_interpreted_kernels(tmp_path):
    r, lines, _ = _run([], _env(tmp_path, PADDLE_TPU_PALLAS_INTERPRET="1"))
    assert r.returncode == 2 and not lines
    assert "PADDLE_TPU_PALLAS_INTERPRET" in r.stderr


def test_fails_with_no_result_outside_a_checkout(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SMOKE, alone / "chip_smoke.py")
    r, lines, _ = _run([], _env(tmp_path), cwd=str(alone),
                       script=str(alone / "chip_smoke.py"))
    assert r.returncode != 0 and not lines


def test_default_run_stops_at_the_device_phase_off_the_tpu(tmp_path):
    """No accelerator: fail at once — never train the 512-wide model on
    the CPU to find out."""
    r, lines, phases = _run([], _env(tmp_path))
    assert r.returncode == 1
    assert list(phases) == ["device"]
    assert json.loads(lines[-1])["ok"] is False


# ------------------------------------------------------- the compile cache

_RESOLVE = ("from paddle_tpu.observability.compile_log import "
            "enable_compile_cache, resolve_cache_dir; import jax;"
            "print(resolve_cache_dir({flag!r}));"
            "enable_compile_cache({flag!r});"
            "print(jax.config.jax_compilation_cache_dir)")


def _resolved(flag, env):
    r = subprocess.run([sys.executable, "-c", _RESOLVE.format(flag=flag)],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


def test_cache_dir_env_wins_and_nothing_else_is_set(tmp_path):
    placed = str(tmp_path / "placed")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=placed)
    flag = str(tmp_path / "from_flag")
    assert _resolved(flag, env) == [placed, placed]
    assert not os.path.exists(flag)


def test_cache_dir_default_is_fixed_inside_the_checkout(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS")}
    fixed = os.path.join(REPO, ".jax_cache")
    # identical across two processes (and two temp directories); enabled
    # without being asked wherever the platform is not an explicit CPU
    assert _resolved("", env) == [fixed, fixed]
    assert _resolved("", dict(env, TMPDIR=str(tmp_path))) == [fixed, fixed]
    # an explicit CPU run: the same place, but only when a directory is
    # named (XLA:CPU's cache loader floods stderr — compile_log.py)
    cpu = dict(env, JAX_PLATFORMS="cpu")
    assert _resolved("", cpu) == [fixed, "None"]
    # --compile_cache_dir keeps working when the variable is unset
    flag = str(tmp_path / "from_flag")
    assert _resolved(flag, cpu) == [flag, flag]


# --------------------------------------------------- --use_tpu is a demand


def test_use_tpu_without_a_tpu_fails_at_start_up(tmp_path):
    """`paddle train --use_tpu=1` with JAX_PLATFORMS unset ends up on a
    TPU or fails at start-up naming the reason; it never trains on the
    CPU instead."""
    cfg = tmp_path / "conf.py"
    cfg.write_text(
        "from paddle.trainer_config_helpers import *\n"
        "settings(batch_size=4, learning_rate=0.1)\n"
        "x = data_layer(name='x', size=4)\n"
        "y = data_layer(name='y', size=2)\n"
        "outputs(classification_cost(input=fc_layer(input=x, size=2, "
        "act=SoftmaxActivation()), label=y))\n")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["TPU_LOG_DIR"] = "disabled"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "paddle"), "train",
         f"--config={cfg}", "--use_tpu=1", "--num_passes=1"],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=300)
    if "platform=tpu" in r.stderr:
        pytest.skip("a TPU is attached here")
    assert r.returncode != 0
    assert "no usable tpu backend" in r.stderr, r.stderr[-2000:]
    assert "platform=cpu" not in r.stderr
