"""Multi-process async SGD (local SGD) — the loopback-pserver analog for
the async path (reference async tests ran loopback pservers too,
test_TrainerOnePass.cpp:120-296).

Two OS processes form one 8-device CPU mesh (4 virtual devices each) and
train an is_async=True config; the replica-stacked step, the drift-gated
merge, and the collapse are all cross-process collectives here. The
final parameters must match the single-process 8-device async run — the
mode is SPMD-deterministic, so process count cannot change numerics
beyond float reassociation.
"""

import os
import sys
import textwrap

import numpy as np

import mp_harness

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROVIDERS = os.path.join(REPO, "tests", "providers")

WORKER = mp_harness.WORKER_PREAMBLE + """
from paddle_tpu.config import parse_config
from paddle_tpu.trainer import Trainer
from paddle_tpu.utils.flags import FLAGS

FLAGS.save_dir = os.path.join(ws, "mp_model")
FLAGS.mesh_shape = "data=8"
FLAGS.log_period = 0
FLAGS.seed = 7
trainer = Trainer(parse_config(os.path.join(ws, "cfg.py")))
assert trainer._async, "async mode must be active on the 8-way data mesh"
trainer.train(num_passes=1)

if jax.process_index() == 0:
    import numpy as np
    np.savez(os.path.join(ws, "mp_async_params.npz"),
             **{{k: np.asarray(v) for k, v in trainer.params.items()}})
print("WORKER_OK", pid, flush=True)
"""


def _write_config(ws):
    train_list = os.path.join(ws, "train.list")
    with open(train_list, "w") as f:
        f.write("1\n2\n")
    src = textwrap.dedent(f"""
    from paddle_tpu.trainer_config_helpers import *
    define_py_data_sources2(train_list={train_list!r}, test_list=None,
                            module="synthetic_bow", obj="process")
    settings(batch_size=64, learning_rate=0.05,
             learning_method=MomentumOptimizer(momentum=0.9),
             is_async=True, num_batches_per_send_parameter=3)
    data = data_layer(name="word", size=100)
    output = fc_layer(input=data, size=2, act=SoftmaxActivation(), name="output")
    label = data_layer(name="label", size=2)
    outputs(classification_cost(input=output, label=label))
    """)
    path = os.path.join(ws, "cfg.py")
    with open(path, "w") as f:
        f.write(src)
    return path


def test_two_process_async_matches_single(tmp_path):
    mp_harness.skip_unless_cross_process_computations()
    ws = str(tmp_path)
    cfg_path = _write_config(ws)
    sys.path.insert(0, PROVIDERS)

    from paddle_tpu.config import parse_config
    from paddle_tpu.trainer import Trainer
    from paddle_tpu.utils.flags import FLAGS

    FLAGS.save_dir = os.path.join(ws, "ref_model")
    FLAGS.mesh_shape = "data=8"
    FLAGS.log_period = 0
    FLAGS.seed = 7
    try:
        ref = Trainer(parse_config(cfg_path))
        assert ref._async
        ref.train(num_passes=1)
    finally:
        sys.path.remove(PROVIDERS)

    mp_harness.run_two_workers(WORKER.format(repo=REPO, providers=PROVIDERS), ws)

    with np.load(os.path.join(ws, "mp_async_params.npz")) as z:
        mp_params = {k: z[k] for k in z.files}
    for name, ref_v in ref.params.items():
        np.testing.assert_allclose(
            np.asarray(ref_v), mp_params[name], rtol=2e-4, atol=1e-5,
            err_msg=name,
        )
