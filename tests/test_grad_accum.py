"""Gradient accumulation (num_batches_per_send_parameter = N): N batches
of size b accumulated must produce EXACTLY the updates of batch size N*b
(reference TrainerInternal: N forwardBackwards per parameter send — the
sample-weighted mean gradient is identical).
"""

import os
import sys
import tempfile
import textwrap

import numpy as np
import pytest

from paddle_tpu.config import parse_config
from paddle_tpu.trainer import Trainer
from paddle_tpu.utils.flags import FLAGS


PROVIDER = """
import numpy as np
from paddle_tpu.data import provider, dense_vector, integer_value

@provider(input_types=[dense_vector(20), integer_value(3)],
          should_shuffle=False)
def process(settings, filename):
    rng = np.random.RandomState(7)
    for _ in range(192):
        y = rng.randint(0, 3)
        x = (rng.randn(20) * 0.4 + y).astype(np.float32)
        yield x.tolist(), int(y)
"""


def _config(tmp_path, batch_size, accum):
    train_list = tmp_path / "train.list"
    train_list.write_text("a\n")
    src = textwrap.dedent(f"""
    from paddle_tpu.trainer_config_helpers import *

    define_py_data_sources2(train_list={str(train_list)!r}, test_list=None,
                            module="accprov", obj="process")
    settings(batch_size={batch_size}, learning_rate=0.05,
             learning_method=AdamOptimizer(),
             num_batches_per_send_parameter={accum})
    data = data_layer(name="x", size=20)
    h = fc_layer(input=data, size=8, act=TanhActivation(), name="h")
    output = fc_layer(input=h, size=3, act=SoftmaxActivation(), name="output")
    label = data_layer(name="label", size=3)
    outputs(classification_cost(input=output, label=label))
    """)
    p = tmp_path / f"cfg_{batch_size}_{accum}.py"
    p.write_text(src)
    return str(p)


@pytest.fixture()
def ws(tmp_path):
    (tmp_path / "accprov.py").write_text(PROVIDER)
    sys.path.insert(0, str(tmp_path))
    yield tmp_path
    sys.path.remove(str(tmp_path))


def _train(tmp_path, batch_size, accum, mesh_shape=""):
    FLAGS.save_dir = tempfile.mkdtemp(dir=tmp_path)
    FLAGS.log_period = 0
    FLAGS.start_pass = 0
    FLAGS.init_model_path = ""
    FLAGS.mesh_shape = mesh_shape
    cfg = parse_config(_config(tmp_path, batch_size, accum))
    tr = Trainer(cfg)
    tr.train(num_passes=2)
    return {k: np.asarray(v) for k, v in tr.params.items()}


def test_accum_matches_large_batch(ws):
    """4 batches of 16 with accum=4 == 1 batch of 64 (unshuffled data):
    identical update sequence, near-identical parameters."""
    p_accum = _train(ws, 16, 4)
    p_big = _train(ws, 64, 1)
    assert set(p_accum) == set(p_big)
    for k in p_big:
        np.testing.assert_allclose(p_accum[k], p_big[k], rtol=2e-4, atol=2e-5,
                                   err_msg=k)
    # and accumulation actually changed something vs. no training
    assert any(np.abs(p_big[k]).sum() > 0 for k in p_big)


def test_accum_under_mesh(ws):
    """Accumulation composes with a data-parallel mesh (sharded astep and
    ustep) and matches the unmeshed result."""
    p_mesh = _train(ws, 16, 4, mesh_shape="data=8")
    p_flat = _train(ws, 16, 4)
    for k in p_flat:
        np.testing.assert_allclose(p_mesh[k], p_flat[k], rtol=2e-4, atol=2e-5,
                                   err_msg=k)


def test_accum_with_sparse_table_falls_back_dense(ws):
    """A sparse_update embedding under accumulation uses dense gradients
    (RowSparseGrad shapes vary per batch and cannot be accumulated);
    training still converges."""
    train_list = ws / "train.list"
    train_list.write_text("a\n")
    src = textwrap.dedent(f"""
    from paddle_tpu.trainer_config_helpers import *

    define_py_data_sources2(train_list={str(train_list)!r}, test_list=None,
                            module="seqprov", obj="process")
    settings(batch_size=16, learning_rate=0.1,
             learning_method=AdamOptimizer(),
             num_batches_per_send_parameter=3)
    words = data_layer(name="words", size=50)
    emb = embedding_layer(input=words, size=8,
                          param_attr=ParamAttr(name="emb", sparse_update=True))
    pool = pooling_layer(input=emb, pooling_type=AvgPooling())
    output = fc_layer(input=pool, size=2, act=SoftmaxActivation(), name="output")
    label = data_layer(name="label", size=2)
    outputs(classification_cost(input=output, label=label))
    """)
    p = ws / "cfg_sparse_accum.py"
    p.write_text(src)
    (ws / "seqprov.py").write_text(textwrap.dedent("""
    import numpy as np
    from paddle_tpu.data import provider, integer_value_sequence, integer_value

    @provider(input_types=[integer_value_sequence(50), integer_value(2)],
              should_shuffle=False)
    def process(settings, filename):
        rng = np.random.RandomState(3)
        for _ in range(96):
            y = rng.randint(0, 2)
            toks = rng.randint(25 * y, 25 * y + 25, rng.randint(3, 8))
            yield [int(t) for t in toks], int(y)
    """))
    FLAGS.save_dir = str(ws / "model")
    FLAGS.log_period = 0
    FLAGS.start_pass = 0
    FLAGS.init_model_path = ""
    cfg = parse_config(str(p))
    tr = Trainer(cfg)
    assert tr._accum_n == 3
    batch = next(tr._provider(for_test=False).batches())
    loss0 = float(tr.gm.loss_fn(tr.params, batch, None)[0])
    tr.train(num_passes=4)
    loss1 = float(tr.gm.loss_fn(tr.params, batch, None)[0])
    assert np.isfinite(np.asarray(tr.params["emb"])).all()
    # the separable classes must be learned through the accumulated path
    assert loss1 < 0.5 * loss0, (loss0, loss1)
