"""FLAGS is one process-wide object, and an xdist worker runs many test
files: what one test sets must not reach the next (tests/conftest.py puts
every field back). The two tests below are one experiment and run in file
order: the first leaves fields set, as `tests/test_numerics.py` does; the
second is the test that used to fail after it on the same worker
(`test_averaging.py::test_nan_loss_aborts_training`: a step built for
`--numerics_log_period` returns one output more than the test's stub).
"""

import os
import textwrap
from dataclasses import asdict

from paddle_tpu.utils.flags import FLAGS, _Flags

PROVIDER_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "providers")


def test_a_test_sets_flags_and_does_not_put_them_back():
    FLAGS.numerics_log_period = 7
    FLAGS.mesh_shape = "data=8"
    assert (FLAGS.numerics_log_period, FLAGS.mesh_shape) == (7, "data=8")


def test_the_next_test_finds_every_flag_at_its_default(tmp_path, monkeypatch):
    assert asdict(FLAGS) == asdict(_Flags())

    from paddle_tpu.config import parse_config
    from paddle_tpu.trainer import Trainer

    monkeypatch.syspath_prepend(PROVIDER_DIR)
    (tmp_path / "train.list").write_text("1\n")
    cfg = tmp_path / "cfg.py"
    cfg.write_text(textwrap.dedent(f"""
        from paddle_tpu.trainer_config_helpers import *
        define_py_data_sources2(train_list={str(tmp_path / 'train.list')!r},
                                test_list=None, module="synthetic_bow", obj="process")
        settings(batch_size=32, learning_rate=0.05)
        data = data_layer(name="word", size=100)
        output = fc_layer(input=data, size=2, act=SoftmaxActivation(), name="output")
        outputs(classification_cost(input=output, label=data_layer(name="label", size=2)))
        """))
    assert Trainer(parse_config(str(cfg)))._numerics_groups is None
