"""Multi-process distributed training — the loopback-pserver analog.

The reference tests distribution without a cluster by spinning loopback
pservers in-process and asserting the remote updater matches local
training (/root/reference/paddle/trainer/tests/test_TrainerOnePass.cpp:
120-296). Here: two OS processes join a jax.distributed coordination
service over localhost, form one 8-device CPU mesh (4 virtual devices
each), train the same config, and the result must match the
single-process 8-device run. Also asserts the BarrierStat-style per-host
step-time skew summary appears in the pass log.
"""

import os
import sys
import textwrap

import mp_harness

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROVIDERS = os.path.join(REPO, "tests", "providers")

WORKER = mp_harness.WORKER_PREAMBLE + """

from paddle_tpu.config import parse_config
from paddle_tpu.trainer import Trainer
from paddle_tpu.utils.flags import FLAGS

FLAGS.save_dir = os.path.join(ws, "mp_model")
FLAGS.metrics_path = os.path.join(ws, "mp_metrics")
FLAGS.mesh_shape = "data=8"
FLAGS.log_period = 0
FLAGS.seed = 7
trainer = Trainer(parse_config(os.path.join(ws, "cfg.py")))
trainer.train(num_passes=1)

# distributeEval analog, sufficient-statistics form: evaluators
# accumulate over LOCAL row blocks and merge small state vectors at read
# time — no per-batch activation gather (asserted: gather_outputs never
# fires for this all-mergeable chain). Results must be identical across
# processes and match the single-process run.
import json
from paddle_tpu.parallel import spmd
from paddle_tpu.parallel.spmd import globalize_batch
from paddle_tpu.trainer.evaluators import EvaluatorChain

gather_calls = [0]
_orig_gather = spmd.gather_outputs
def _counting_gather(*a, **k):
    gather_calls[0] += 1
    return _orig_gather(*a, **k)
spmd.gather_outputs = _counting_gather

chain = EvaluatorChain(trainer.config.model_config)
chain.start()
provider = trainer._provider(for_test=False)
for batch in provider.batches():
    b = globalize_batch(batch, trainer._mesh)
    if b is None:
        continue
    outputs = trainer.test_fwd(trainer.params, b)
    trainer._eval_outputs(chain, outputs)
res = chain.results()
res["_gather_calls"] = gather_calls[0]
spmd.gather_outputs = _orig_gather
with open(os.path.join(ws, "eval_p%d.json" % pid), "w") as f:
    json.dump(res, f)

if jax.process_index() == 0:
    import numpy as np
    np.savez(os.path.join(ws, "mp_params.npz"),
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
    settings(batch_size=64, learning_rate=0.05)
    data = data_layer(name="word", size=100)
    output = fc_layer(input=data, size=2, act=SoftmaxActivation(), name="output")
    label = data_layer(name="label", size=2)
    sum_evaluator(output, name="rows")
    auc_evaluator(input=output, label=label, name="auc")
    outputs(classification_cost(input=output, label=label))
    """)
    path = os.path.join(ws, "cfg.py")
    with open(path, "w") as f:
        f.write(src)
    return path


def test_two_process_training_matches_single(tmp_path):
    mp_harness.skip_unless_cross_process_computations()
    ws = str(tmp_path)
    cfg_path = _write_config(ws)
    sys.path.insert(0, PROVIDERS)

    # single-process reference on the same 8-device mesh (this pytest
    # process already has 8 virtual devices via conftest)
    from paddle_tpu.config import parse_config
    from paddle_tpu.trainer import Trainer
    from paddle_tpu.utils.flags import FLAGS

    from paddle_tpu.observability import metrics as obs

    FLAGS.save_dir = os.path.join(ws, "ref_model")
    FLAGS.metrics_path = os.path.join(ws, "ref_metrics")
    FLAGS.mesh_shape = "data=8"
    FLAGS.log_period = 0
    FLAGS.seed = 7
    try:
        ref = Trainer(parse_config(cfg_path))
        ref.train(num_passes=1)
    finally:
        obs.configure("")
        sys.path.remove(PROVIDERS)

    outs = mp_harness.run_two_workers(
        WORKER.format(repo=REPO, providers=PROVIDERS), ws)
    # BarrierStat skew line logged at pass end on every host
    assert any("BarrierStat" in err for _, _, err in outs), outs[0][2][-2000:]

    with np.load(os.path.join(ws, "mp_params.npz")) as z:
        mp_params = {k: z[k] for k in z.files}
    for name, ref_v in ref.params.items():
        np.testing.assert_allclose(
            np.asarray(ref_v), mp_params[name], rtol=2e-4, atol=1e-5,
            err_msg=name,
        )

    # the train pass's evaluators are computed inside the jitted step over
    # the mesh, where the reduction is global already: every process reports
    # the single-process numbers, and none counts a row process_count times
    # (`rows.sum` adds up softmax rows, so it counts the samples) though the
    # chain does merge `auc`, which is fed local rows on the host
    def pass_end(run, name="metrics.jsonl"):
        recs = obs.read_records(os.path.join(ws, run, name))
        return [r for r in recs if r["kind"] == "pass_end"][-1]

    ref_end = pass_end("ref_metrics")
    assert abs(ref_end["rows.sum"] - ref_end["samples"]) < 1e-2
    for name in ("metrics.jsonl", "metrics.host1.jsonl"):
        end = pass_end("mp_metrics", name)
        assert end["samples"] == ref_end["samples"]
        assert abs(end["rows.sum"] - ref_end["rows.sum"]) < 1e-2, name
        assert abs(end["rows.mean"] - 1.0) < 1e-5, name
        err = "__cost_0__.classification_error.classification_error"
        assert abs(end[err] - ref_end[err]) <= 5e-3, name
        assert abs(end["auc.auc"] - ref_end["auc.auc"]) <= 5e-3, name
        assert end["counters"]["eval.host_batches"] == (
            end["counters"]["eval.device_batches"] / 2) > 0

    # merged evaluator metrics: identical on every process, and the
    # classification error matches the single-process run over the same
    # data with the (numerically near-identical) final parameters
    import json
    from paddle_tpu.trainer.evaluators import EvaluatorChain

    with open(os.path.join(ws, "eval_p0.json")) as f:
        eval_p0 = json.load(f)
    with open(os.path.join(ws, "eval_p1.json")) as f:
        eval_p1 = json.load(f)
    assert eval_p0 == eval_p1, (eval_p0, eval_p1)
    assert eval_p0, "no evaluator results produced"
    # the chain is all-mergeable (classification_error): local rows +
    # state merge, never a per-batch activation gather
    assert eval_p0.pop("_gather_calls") == 0
    eval_p1.pop("_gather_calls")

    sys.path.insert(0, PROVIDERS)
    try:
        chain = EvaluatorChain(ref.config.model_config)
        chain.start()
        provider = ref._provider(for_test=False)
        for batch in provider.batches():
            chain.eval_batch(ref.test_fwd(ref.params, batch))
        ref_results = chain.results()
    finally:
        sys.path.remove(PROVIDERS)
    for k, v in ref_results.items():
        assert abs(eval_p0[k] - v) <= 5e-3, (k, eval_p0[k], v)
