"""`correct` is a comparison that has been shown to fail: the control (the
reference in the nearest lower precision, put in the program's place) fails
a cell's limits, and a run whose timed path is broken underneath comes out
with `correct` false. Toy size, on the CPU; the chip-size readings are
`control_on_chip.py`'s, in PERF.md."""


import pytest

from perfbench import harness
from perfbench.tests import helpers


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return helpers.make_root(tmp_path_factory.mktemp("bench"))


def test_train_control_and_planted_faults_fail_the_limits(root):
    cell = harness.load_cell(root, "toy.train")
    gen = cell.module("traffic", "general")
    ref = cell.module("reference", "seqtoseq")
    cmp = cell.module("compare", "train_steps")
    items = gen.generate(cell.mix, cell.config, 11)
    batches = [gen.arrays_of(items, g) for g in range(3)]
    base = cmp.reference_steps(ref, cell.config, 11, batches)
    limits = cell.workload["limits"]
    sound, _ = cmp.gaps(base, cmp.reference_steps(ref, cell.config, 11, batches))
    assert all(sound[k] <= limits[k] for k in limits)
    for kw in ({"mode": cell.config["precision"]["control_mode"]},
               {"fault": "half_batch"}, {"fault": "state_unchanged"}):
        got = cmp.checks(cmp.reference_steps(ref, cell.config, 11, batches, **kw),
                         base, limits)
        assert not all(c.ok for c in got), (kw, got)
        if "mode" in kw:
            # the number that has to see a lower precision, whose rounding
            # has no bias and so hides from gaps of norms
            assert not next(c for c in got if c.name == "grad_diff").ok
    unchanged, _ = cmp.gaps(cmp.reference_steps(
        ref, cell.config, 11, batches, fault="state_unchanged"), base)
    assert unchanged["change_gap"] == pytest.approx(1.0)
    assert unchanged["grad_gap"] == pytest.approx(1.0)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(root, monkeypatch):
    from paddle_tpu.optimizer.updater import Updater

    monkeypatch.setattr(Updater, "__call__",
                        lambda self, params, grads, state, n: (params, state))
    line = helpers.run_toy(root, "toy.train", seconds=0.3)
    assert line["correct"] is False
    assert line["compared"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(root, monkeypatch):
    cell = harness.load_cell(root, "toy.train")
    gen = cell.module("traffic", "general")
    whole = gen.samples_of
    monkeypatch.setattr(gen, "samples_of",
                        lambda items, g: whole(items, g)[: items.group // 2])
    line = helpers.run_toy(root, "toy.train", seconds=0.3)
    assert line["correct"] is False
    assert line["compared"]["loss_gap"]["value"] > line["compared"]["loss_gap"]["limit"]
