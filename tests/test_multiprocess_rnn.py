"""Multi-process training of a recurrent-group model — the loopback
cluster analog (reference test_TrainerOnePass.cpp checkRemoteUpdater) for
the RGM path: two processes form one 8-device mesh, train an embedding →
recurrent_group (lax.scan) → pool → softmax classifier, and must match
the single-process 8-device run. Round-2 coverage gap: multi-process runs
only ever trained a bag-of-words fc model.
"""

import os
import sys
import textwrap

import mp_harness

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROVIDERS = os.path.join(REPO, "tests", "providers")

WORKER = mp_harness.WORKER_PREAMBLE + """

from paddle_tpu.config import parse_config
from paddle_tpu.trainer import Trainer
from paddle_tpu.utils.flags import FLAGS

FLAGS.save_dir = os.path.join(ws, "mp_model")
FLAGS.mesh_shape = "data=8"
FLAGS.log_period = 0
FLAGS.seed = 13
trainer = Trainer(parse_config(os.path.join(ws, "cfg.py")))
trainer.train(num_passes=1)
if jax.process_index() == 0:
    import numpy as np
    np.savez(os.path.join(ws, "mp_params.npz"),
             **{{k: np.asarray(v) for k, v in trainer.params.items()}})
print("WORKER_OK", pid, flush=True)
"""

CONFIG = """
from paddle_tpu.trainer_config_helpers import *
define_py_data_sources2(train_list={train_list!r}, test_list=None,
                        module="synthetic_bow", obj="process_seq")
settings(batch_size=64, learning_rate=0.05)
word = data_layer(name="word", size=100)
emb = embedding_layer(input=word, size=12)
def step(x_t):
    mem = memory(name="rnn", size=12)
    return fc_layer(input=[x_t, mem], size=12, act=TanhActivation(), name="rnn")
rnn = recurrent_group(step=step, input=emb, name="rg")
pool = pooling_layer(input=rnn, pooling_type=MaxPooling())
output = fc_layer(input=pool, size=2, act=SoftmaxActivation(), name="output")
label = data_layer(name="label", size=2)
outputs(classification_cost(input=output, label=label))
"""


def test_two_process_recurrent_group_matches_single(tmp_path):
    mp_harness.skip_unless_cross_process_computations()
    ws = str(tmp_path)
    train_list = os.path.join(ws, "train.list")
    with open(train_list, "w") as f:
        f.write("1\n2\n")
    with open(os.path.join(ws, "cfg.py"), "w") as f:
        f.write(textwrap.dedent(CONFIG.format(train_list=train_list)))

    sys.path.insert(0, PROVIDERS)
    from paddle_tpu.config import parse_config
    from paddle_tpu.trainer import Trainer
    from paddle_tpu.utils.flags import FLAGS

    FLAGS.save_dir = os.path.join(ws, "ref_model")
    FLAGS.mesh_shape = "data=8"
    FLAGS.log_period = 0
    FLAGS.seed = 13
    try:
        ref = Trainer(parse_config(os.path.join(ws, "cfg.py")))
        ref.train(num_passes=1)
    finally:
        sys.path.remove(PROVIDERS)

    outs = mp_harness.run_two_workers(
        WORKER.format(repo=REPO, providers=PROVIDERS), ws)

    with np.load(os.path.join(ws, "mp_params.npz")) as z:
        mp_params = {k: z[k] for k in z.files}
    assert any("rnn" in k for k in mp_params), mp_params.keys()
    for name, ref_v in ref.params.items():
        np.testing.assert_allclose(
            np.asarray(ref_v), mp_params[name], rtol=3e-4, atol=2e-5,
            err_msg=name,
        )
