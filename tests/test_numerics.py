"""Numerics observability (doc/observability.md "Memory & numerics
telemetry"): per-layer health aux inside the jitted step
(--numerics_log_period), zero recompiles after warmup with the aux
enabled, the nonfinite blame re-run naming the poisoned layer
(`trainer.nonfinite_layer` fault site), no false blame when only the
loss was faked, and `paddle compare`'s direction-awareness for the new
metrics."""

import json
import math
import os
import sys
import textwrap

import numpy as np
import pytest

from paddle_tpu.observability import metrics as obs
from paddle_tpu.observability import numerics as obs_num
from paddle_tpu.resilience import NonFiniteLossError, faultinject

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROVIDER_DIR = os.path.join(os.path.dirname(__file__), "providers")


@pytest.fixture(autouse=True)
def _provider_path():
    sys.path.insert(0, PROVIDER_DIR)
    yield
    sys.path.remove(PROVIDER_DIR)


@pytest.fixture(autouse=True)
def _fresh_state():
    obs.registry().reset()
    yield
    obs.configure("")
    faultinject.configure("")


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
    hid = fc_layer(input=data, size=8, act=TanhActivation(), name="hid")
    output = fc_layer(input=hid, size=2, act=SoftmaxActivation(), name="output")
    label = data_layer(name="label", size=2)
    outputs(classification_cost(input=output, label=label))
    """)
    cfg_path = tmp_path / "cfg.py"
    cfg_path.write_text(src)
    return str(cfg_path)


def _trainer(cfg, save_dir, **flag_overrides):
    """A trainer on flags of its own: the module's shared run below
    leaves nothing in the process-wide FLAGS."""
    from paddle_tpu.config import parse_config
    from paddle_tpu.trainer import Trainer
    from paddle_tpu.utils.flags import _Flags

    defaults = dict(config=cfg, save_dir=save_dir, num_passes=2,
                    log_period=0, seed=7)
    flags = _Flags(**{**defaults, **flag_overrides})
    return Trainer(parse_config(cfg, ""), flags)


def _records(run_dir):
    out = []
    for path in obs.metrics_files(str(run_dir)):
        out.extend(obs.read_records(path))
    return out


# ------------------------------------------------------------------ units


def test_layer_groups_maps_params_to_layers(tmp_path):
    from paddle_tpu.config import parse_config

    cfg = _write_config(tmp_path)
    config = parse_config(cfg, "")
    pnames = [p.name for p in config.model_config.parameters]
    groups = obs_num.layer_groups(config.model_config, pnames)
    assert set(groups["output"]) == {"_output.w0", "_output.wbias"}
    assert set(groups["hid"]) == {"_hid.w0", "_hid.wbias"}
    # every param lands in exactly one group
    assert sorted(p for ps in groups.values() for p in ps) == sorted(pnames)


def test_step_health_and_derive_roundtrip():
    import jax.numpy as jnp

    params = {"w": jnp.asarray([3.0, 4.0]), "b": jnp.asarray([1.0])}
    new_params = {"w": jnp.asarray([3.0, 4.2]), "b": jnp.asarray([1.0])}
    grads = {"w": jnp.asarray([0.6, 0.8]), "b": jnp.asarray([float("nan")])}
    groups = {"fc": ["w", "b"]}
    health = obs_num.step_health(params, new_params, grads, groups)
    layers, nf_layers, gnorm = obs_num.derive(
        {k: np.asarray(v) for k, v in health.items()}
    )
    fc = layers["fc"]
    # param norm sqrt(9+16+1); update norm 0.2 over it
    assert fc["param_norm"] == pytest.approx(math.sqrt(26.0), rel=1e-5)
    assert fc["update_ratio"] == pytest.approx(0.2 / math.sqrt(26.0), rel=1e-4)
    assert fc["nonfinite"] == 1
    assert nf_layers == ["fc"]
    # the NaN grad poisons the norm sums — reported as nonfinite, and
    # the global norm skips the poisoned (non-finite) contribution
    assert not math.isfinite(fc["grad_norm"]) or fc["grad_norm"] >= 1.0
    assert math.isfinite(gnorm)


def test_derive_takes_last_batch_of_fused_stack():
    stacked = {"fc": np.asarray([[1.0, 1.0, 0.0, 0.0],
                                 [4.0, 9.0, 1.0, 2.0]])}
    layers, nf_layers, _ = obs_num.derive(stacked)
    assert layers["fc"]["grad_norm"] == pytest.approx(2.0)
    assert layers["fc"]["param_norm"] == pytest.approx(3.0)
    assert layers["fc"]["nonfinite"] == 2
    assert nf_layers == ["fc"]


def test_record_kinds_registered():
    """Satellite: memory/numerics/oom are first-class schema citizens —
    registered (validate_record enforces their fields); memory/oom are
    flush kinds (an oom must reach disk before the death), numerics is
    BUFFERED like its analog train_window (a per-record flush at
    --numerics_log_period=1 would put file I/O on the hot step loop)."""
    for kind in ("memory", "numerics", "oom"):
        assert kind in obs.KIND_REQUIRED
    assert "memory" in obs.FLUSH_KINDS and "oom" in obs.FLUSH_KINDS
    assert "numerics" not in obs.FLUSH_KINDS
    assert obs.validate_record(
        {"v": 1, "kind": "numerics", "host": 0, "t": 0.0}
    ) == ["numerics record missing required key 'layers'"]


# --------------------------------------------------------- smoke + blame


@pytest.fixture(scope="module")
def numerics_run(tmp_path_factory):
    """One 2-pass smoke train with --numerics_log_period=2 — shared by
    the record/recompile tests below."""
    tmp_path = tmp_path_factory.mktemp("numerics_smoke")
    cfg = _write_config(tmp_path)
    sys.path.insert(0, PROVIDER_DIR)
    obs.registry().reset()
    save_dir = str(tmp_path / "out")
    try:
        trainer = _trainer(cfg, save_dir, numerics_log_period=2)
        trainer.train()
    finally:
        obs.configure("")
        sys.path.remove(PROVIDER_DIR)
    return save_dir, _records(save_dir)


def test_numerics_records_validate_and_carry_layers(numerics_run):
    _save_dir, recs = numerics_run
    nums = [r for r in recs if r["kind"] == "numerics"]
    assert nums, "no numerics records from the smoke run"
    for r in nums:
        assert obs.validate_record(r) == []
        for layer in ("hid", "output"):
            row = r["layers"][layer]
            assert row["grad_norm"] >= 0
            assert row["param_norm"] > 0
            assert row["update_ratio"] > 0  # Adam moves every step
            assert row["nonfinite"] == 0
        assert r["nonfinite_layers"] == []
        assert r["global_grad_norm"] > 0
    # pass-end emission: every pass has at least one numerics record
    assert {r["pass"] for r in nums} == {0, 1}


def test_numerics_zero_recompiles_after_warmup(numerics_run):
    """Acceptance: enabling --numerics_log_period causes zero
    recompiles after warmup — every compile record lands in pass 0 (or
    unscoped), and no launch group compiles the same signature twice."""
    _save_dir, recs = numerics_run
    compiles = [r for r in recs if r["kind"] == "compile"]
    assert compiles
    assert all(c.get("pass", 0) <= 0 for c in compiles), (
        "a compile happened after warmup with numerics enabled: "
        + json.dumps([{k: c.get(k) for k in ("group", "sig", "pass")}
                      for c in compiles])
    )
    sigs = [(c["group"], c["sig"]) for c in compiles]
    assert len(sigs) == len(set(sigs)), "a (group, sig) compiled twice"


def test_numerics_table_column_and_analyzer_doc(numerics_run):
    save_dir, _recs = numerics_run
    from paddle_tpu.observability.analyze import (
        _fmt_table,
        analyze,
        load_run,
    )

    doc = analyze(load_run(save_dir))
    assert doc["numerics"] == {"records": doc["numerics"]["records"],
                               "nonfinite_layers": []}
    assert doc["numerics"]["records"] >= 2
    for row in doc["passes"]:
        assert row["nf_layers"] == 0
    table = _fmt_table(doc)
    assert "nf lyr" in table
    assert "numerics telemetry:" in table


def test_blame_names_poisoned_layer_e2e(tmp_path):
    """trainer.nonfinite_layer=raise:hid plants a real NaN in layer
    `hid`'s parameters; the loss goes NaN, the policy trips, and the
    blame re-run must name `hid` (phase `params`) — on the nonfinite
    record AND in the raised error. No shortcut: blame never consults
    the injector."""
    cfg = _write_config(tmp_path)
    save_dir = str(tmp_path / "out")
    trainer = _trainer(
        cfg, save_dir, numerics_log_period=2, nonfinite_policy="skip",
        max_nonfinite_steps=1, fault_spec="trainer.nonfinite_layer=raise:hid@3",
    )
    faultinject.configure("trainer.nonfinite_layer=raise:hid@3")
    with pytest.raises(NonFiniteLossError) as ei:
        trainer.train()
    assert "layer 'hid'" in str(ei.value)
    obs.flush()
    nf_recs = [r for r in _records(save_dir) if r["kind"] == "nonfinite"]
    assert nf_recs
    for r in nf_recs:
        assert obs.validate_record(r) == []
        assert r["blame_layer"] == "hid"
        assert r["blame_phase"] == "params"
    # the numerics aux saw the nonfinite gradients too (the NaN weight
    # poisons hid's grads through the chain rule)
    nums = [r for r in _records(save_dir) if r["kind"] == "numerics"]
    assert any(r["nonfinite_layers"] for r in nums)


def test_no_false_blame_on_faked_loss(tmp_path):
    """trainer.nonfinite only FAKES the loss value host-side — the
    model itself is healthy, so the blame re-run must find nothing and
    the record must carry no blame fields (a wrong blame is worse than
    none)."""
    cfg = _write_config(tmp_path)
    save_dir = str(tmp_path / "out")
    trainer = _trainer(
        cfg, save_dir, nonfinite_policy="skip", max_nonfinite_steps=3,
    )
    faultinject.configure("trainer.nonfinite=raise@3")
    trainer.train()
    nf_recs = [r for r in _records(save_dir) if r["kind"] == "nonfinite"]
    assert len(nf_recs) == 1
    assert "blame_layer" not in nf_recs[0]


def test_numerics_under_mesh(tmp_path):
    """The sharded train step carries the aux through its explicit
    out_shardings (spmd.shard_train_step extra_outs) — a data=1 mesh on
    the CPU backend exercises exactly that wrapper."""
    cfg = _write_config(tmp_path)
    save_dir = str(tmp_path / "out")
    trainer = _trainer(
        cfg, save_dir, numerics_log_period=2, mesh_shape="data=1",
        num_passes=1,
    )
    trainer.train()
    nums = [r for r in _records(save_dir) if r["kind"] == "numerics"]
    assert nums and all(obs.validate_record(r) == [] for r in nums)
    assert all(r["layers"]["output"]["param_norm"] > 0 for r in nums)


def test_numerics_disabled_under_accumulation(tmp_path, caplog):
    """Honest degradation: gradient accumulation applies updates
    outside the one-batch step, so the aux would misattribute — the
    flag is refused with a warning, not silently mis-measured."""
    import logging

    from paddle_tpu.utils.logging import logger as ptu_logger

    cfg = _write_config(tmp_path)
    src = open(cfg).read().replace(
        "settings(batch_size=64, learning_rate=0.02, "
        "learning_method=AdamOptimizer())",
        "settings(batch_size=64, learning_rate=0.02, "
        "learning_method=AdamOptimizer(), "
        "num_batches_per_send_parameter=2)",
    )
    cfg2 = tmp_path / "cfg_accum.py"
    cfg2.write_text(src)
    ptu_logger.addHandler(caplog.handler)  # propagate=False on this logger
    try:
        with caplog.at_level(logging.WARNING, logger="paddle_tpu"):
            trainer = _trainer(str(cfg2), str(tmp_path / "out"),
                               numerics_log_period=2)
    finally:
        ptu_logger.removeHandler(caplog.handler)
    assert trainer._numerics_groups is None
    assert trainer._numerics_period == 0
    assert any("--numerics_log_period is not supported" in m
               for m in caplog.messages)


# ------------------------------------------------------------- compare


def test_compare_direction_awareness(tmp_path):
    """Peak-bytes growth and a layer newly producing nonfinite
    gradients are REGRESSIONs (exit 1); shrinkage/cleanup improves."""
    from paddle_tpu.observability.compare import compare, load_side

    def run_dir(name, peak, nf_layers):
        d = tmp_path / name
        w = obs.MetricsWriter(str(d), host=0)
        w.emit("numerics", pass_id=0, step=2,
               layers={"output": {"grad_norm": 1.0, "param_norm": 1.0,
                                  "update_ratio": 0.1,
                                  "nonfinite": 1 if nf_layers else 0}},
               nonfinite_layers=nf_layers, global_grad_norm=1.0)
        w.emit("memory", pass_id=0, step=9, host_rss_bytes=10 ** 9,
               hbm_in_use_bytes=peak // 2, hbm_peak_bytes=peak, devices=1)
        w.emit("run_end", status="completed")
        w.close()
        return str(d)

    a = run_dir("a", peak=4 * 10 ** 9, nf_layers=[])
    b = run_dir("b", peak=6 * 10 ** 9, nf_layers=["output"])
    doc = compare(load_side(a), load_side(b))
    assert doc["verdict"] == "REGRESSION"
    assert "hbm_peak_bytes" in doc["regressions"]
    assert "nonfinite_layers" in doc["regressions"]
    # reverse direction improves (footprint shrank, layer went clean)
    doc = compare(load_side(b), load_side(a))
    assert doc["verdict"] == "IMPROVED"
    assert "hbm_peak_bytes" in doc["improvements"]
    # identical sides: no change
    doc = compare(load_side(a), load_side(a))
    assert doc["verdict"] == "NO CHANGE"
