"""Preemption-aware checkpointing: SIGTERM during train() saves a
consistent checkpoint at the next launch boundary and exits cleanly —
the TPU-pod recovery story SURVEY §5 flags as the reference's gap (its
design is fail-fast restart-from-last-pass only)."""

import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = """
import sys
sys.path.insert(0, {repo!r})
import os
os.chdir({ws!r})
from paddle_tpu.utils.backend_guard import ensure_cpu_mesh
ensure_cpu_mesh(1)
from paddle_tpu.config import parse_config
from paddle_tpu.trainer import Trainer
from paddle_tpu.utils.flags import _Flags

open("cfg.py", "w").write('''
from paddle_tpu.trainer_config_helpers import *
define_py_data_sources2(train_list="train.list", test_list=None,
                        module="slow_provider", obj="process")
settings(batch_size=16, learning_rate=0.1, learning_method=MomentumOptimizer())
data = data_layer(name="x", size=8)
out = fc_layer(input=data, size=2, act=SoftmaxActivation(), name="output")
label = data_layer(name="y", size=2)
outputs(classification_cost(input=out, label=label))
''')
open("train.list", "w").write("s1\\n")
open("slow_provider.py", "w").write('''
from paddle_tpu.data.provider import *
import os, time

@provider(input_types=[dense_vector(8), integer_value(2)])
def process(settings, file_name):
    for i in range(100000):
        time.sleep(0.002)  # slow stream: many launch boundaries
        if i == 200:       # the loop is demonstrably live
            open("started.flag", "w").write("x")
        yield [0.1] * 8, i % 2
''')
cfg = parse_config("cfg.py")
flags = _Flags(config="cfg.py", num_passes=1, log_period=0, save_dir="out")
t = Trainer(cfg, flags)
t.train()
print("TRAIN_RETURNED_CLEANLY", flush=True)
"""


def test_sigterm_saves_checkpoint_and_exits(tmp_path):
    proc = subprocess.Popen(
        [sys.executable, "-c", CHILD.format(repo=REPO, ws=str(tmp_path))],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    try:
        flag = tmp_path / "started.flag"
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not flag.exists():
            if proc.poll() is not None:
                out, _ = proc.communicate()
                raise AssertionError(f"child exited early:\n{out[-2500:]}")
            time.sleep(0.25)
        assert flag.exists(), "training loop never became live"
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:  # never leak the slow-provider child
            proc.kill()
            proc.wait()
    assert proc.returncode == 0, out[-2500:]
    assert "TRAIN_RETURNED_CLEANLY" in out, out[-2500:]
    assert "preemption: checkpoint saved" in out, out[-2500:]
    assert (tmp_path / "out" / "pass-00000").exists(), out[-1500:]


RESUME_CHILD = """
import sys
sys.path.insert(0, {repo!r})
import os
os.chdir({ws!r})
from paddle_tpu.utils.backend_guard import ensure_cpu_mesh
ensure_cpu_mesh(1)
from paddle_tpu.config import parse_config
from paddle_tpu.trainer import Trainer
from paddle_tpu.utils.flags import _Flags

cfg = parse_config("cfg.py")
flags = _Flags(config="cfg.py", num_passes=1, log_period=0,
               init_model_path=os.path.join("out", "pass-00000"))
t = Trainer(cfg, flags)
# the preemption checkpoint carries the optimizer state: the step
# counter must resume from where the SIGTERM landed, not zero
step = int(t.opt_state.step)
print(f"RESUMED_STEP={{step}}", flush=True)
assert step > 0, step
"""


def test_resume_from_preemption_checkpoint(tmp_path):
    """The documented resume path: --init_model_path on the preemption
    checkpoint restores parameters AND optimizer state."""
    # first leg: train, preempt, save (same flow as the test above)
    proc = subprocess.Popen(
        [sys.executable, "-c", CHILD.format(repo=REPO, ws=str(tmp_path))],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    try:
        flag = tmp_path / "started.flag"
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not flag.exists():
            assert proc.poll() is None, proc.communicate()[0][-2000:]
            time.sleep(0.25)
        assert flag.exists()
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert proc.returncode == 0, out[-2000:]
    # second leg: resume and verify the optimizer step counter carried over
    r = subprocess.run(
        [sys.executable, "-c", RESUME_CHILD.format(repo=REPO, ws=str(tmp_path))],
        capture_output=True, text=True, timeout=180, cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    assert "RESUMED_STEP=" in r.stdout
