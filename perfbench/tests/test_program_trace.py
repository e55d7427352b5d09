"""`program_trace.py` and the seven readers over it: on a hand-made trace
whose numbers can be reckoned by hand, on the pair recorded on a v5e by
`record_tiny_step_trace.py`, and on a trace of a program that has no span
and no scope (the parent of the PR that added them): nothing, no error."""

import os
import shutil
import types

import pytest

from perfbench import harness, program_trace as pt, trace_reduce as tr
from perfbench.tests.helpers import HERE, REPO

DATA = os.path.join(HERE, "data")
READERS = ("data_wait_ms.train", "host_eval_ms.train", "host_other_ms.train",
           "idle_unexplained_pct.train", "decoder_scan_ms.train",
           "vocab_cost_ms.train", "unscoped_device_pct.train")

HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (p: f32[8,30]) -> f32[8,30] {
  %p = f32[8,30]{1,0} parameter(0)
  ROOT %e = f32[8,30]{1,0} exponential(%p), metadata={op_name="jit(step)/jvp(recurrent_layer_group:dec)/fc:out/exp"}
}

ENTRY %main {
  %emb = f32[30,4]{1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(step)/jvp(mixed:emb)/gather"}
  %while.1 = (s32[], f32[8,4]{1,0:T(8,128)}) while(%t), metadata={op_name="jit(step)/jvp(recurrent_layer_group:dec)/while"}
  %gru.2 = f32[8,4]{1,0} fusion(%b), kind=kLoop, metadata={op_name="jit(step)/jvp(recurrent_layer_group:dec)/while/body/closed_call/gru_step:g/mul"}
  %out.3 = f32[8,30]{1,0:T(8,128)} fusion(%c), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(recurrent_layer_group:dec)/fc:out/dot_general"}
  %ce.4 = f32[8,30]{1,0} fusion(%d), kind=kLoop, metadata={op_name="jit(step)/jvp(multi-class-cross-entropy:ce)/log"}
  %mean.5 = f32[] fusion(%e), kind=kLoop, metadata={op_name="jit(step)/jvp(cost)/reduce_sum"}
  %bwd.6 = f32[8,4]{1,0} fusion(%f), kind=kLoop, metadata={op_name="jit(step)/transpose(jvp(recurrent_layer_group:dec))/while/body/closed_call/gru_step:g/mul"}
  %adam.7 = f32[4,30]{1,0} fusion(%g), kind=kLoop, metadata={op_name="jit(step)/optimizer/mul"}
  ROOT %copy.8 = f32[4,30]{1,0} copy(%adam.7)
}
"""


def test_hlo_text_gives_scopes_shapes_and_the_vocabulary_layers():
    module, instrs = pt.parse_hlo(HLO)
    assert module == "jit_step"
    assert instrs["out.3"] == ("jit(step)/jvp(recurrent_layer_group:dec)/fc:out/dot_general",
                               frozenset({30}))
    assert instrs["while.1"][1] == frozenset({4}) and instrs["copy.8"][0] == ""
    assert pt.scope_path(instrs["bwd.6"][0]) == (
        ("recurrent_layer_group:dec", "gru_step:g"), True)
    assert pt.scope_path(instrs["adam.7"][0]) == (("optimizer",), False)
    assert pt.scope_path("jit(step)/jit(clip)/min") == ((), False)
    assert pt.scope_path("") == ((), False)
    # the projection and the cost layer hold the dictionary's size LAST; the
    # embedding holds it first and the optimizer is no layer
    assert pt.vocabulary_scopes(instrs, 30) == {"fc:out", "multi-class-cross-entropy:ce"}


def _made():
    """Two steps of 10 s (numbers 3 and 4) and the pull that ends the pass."""
    line = []
    for k, t in enumerate((0.0, 10.0)):
        line += [("trainer/step", t, 10.0, 3 + k),
                 ("trainer/data_wait", t, 1.0 + k, None),
                 ("data/prefetch_wait", t + 0.1, 0.5, None),
                 ("trainer/flops_count", t + 2.0, 0.5, None),
                 ("trainer/launch", t + 3.0, 0.5, None),
                 ("trainer/loss_sync", t + 4.0, 1.0, None),
                 ("trainer/eval_outputs", t + 5.0, 4.0, None),
                 ("eval/classification_error", t + 5.0, 4.0, None),
                 ("eval/readback", t + 5.0, 3.0, None),
                 ("trainer/housekeeping", t + 9.5, 0.25, None)]
    line += [("trainer/step", 20.0, 0.5, 5), ("trainer/data_wait", 20.0, 0.5, None)]
    lines = {"/host:CPU/python3#0": line,
             "/host:CPU/python3#1": [("data/pack", 1.0, 0.5, None)]}
    ops, modules = [], []
    for t in (3.5, 13.5):
        modules.append(("jit_step(77)", t, 1.5))
        ops += [("%emb = f32[30,4] fusion(f32[] %a)", t, 0.1),
                ("%while.1 = (s32[]) while(%t)", t + 0.1, 0.6),
                ("%gru.2 = f32[8,4] fusion(%b)", t + 0.2, 0.2),
                ("%bwd.6 = f32[8,4] fusion(%f)", t + 0.4, 0.2),
                ("%out.3 = f32[8,30] fusion(%c)", t + 0.7, 0.3),
                ("%ce.4 = f32[8,30] fusion(%d)", t + 1.0, 0.1),
                ("%mean.5 = f32[] fusion(%e)", t + 1.1, 0.1),
                ("%adam.7 = f32[4,30] fusion(%g)", t + 1.2, 0.2),
                ("%copy.8 = f32[4,30] copy(%adam.7)", t + 1.4, 0.1)]
    modules.append(("jit_convert(5)", 9.6, 0.1))
    ops.append(("%copy.8 = f32[] copy(%x)", 9.6, 0.1))     # another program's
    trace = tr.Trace([tr.Device("/device:TPU:0", ops, modules)], [], (0.0, 21.0))
    return pt.ProgramTrace(lines, trace, dict([pt.parse_hlo(HLO)]))


def test_steps_and_their_phases():
    p = _made()
    steps = p.steps()
    assert [s.num for s in steps] == [3, 4]            # the closing pull is no step
    assert p.per_step_ms(totals=("trainer/data_wait",)) == [1000.0, 2000.0]
    assert p.per_step_ms(totals=("trainer/eval_outputs",)) == [4000.0, 4000.0]
    assert steps[0].self_s["eval/classification_error"] == pytest.approx(1.0)
    # the step less data wait, loss sync and evaluators: 10 - 1 - 1 - 4
    other = p.per_step_ms(totals=("trainer/flops_count", "trainer/launch",
                                  "trainer/housekeeping"), selfs=("trainer/step",))
    assert other == [pytest.approx(4000.0), pytest.approx(3000.0)]
    assert "data/pack" in pt.host_table(p) and "trainer/loss_sync" in pt.host_table(p)


def test_idle_gaps_are_cut_at_span_boundaries_and_given_to_the_deepest_span():
    idle = _made().idle_by_span()
    # the gap 5.0 .. 9.6 of step 3: read-back 3 s, the arg-max 1 s, then the
    # step's own 0.5 and housekeeping's 0.1 before the other program runs
    assert idle["eval/readback"] == pytest.approx(6.0)
    assert idle["eval/classification_error"] == pytest.approx(2.0)
    assert idle["data/prefetch_wait"] == pytest.approx(1.0)
    assert idle["trainer/launch"] == pytest.approx(1.0)
    assert idle["trainer/step"] == pytest.approx(3.5)   # between its children
    assert sum(idle.values()) == pytest.approx(21.0 - 3.1)
    assert idle["(no span)"] == pytest.approx(0.5)      # after the last pull


def test_device_rows_by_scope_forward_backward_and_other_programs():
    rows = {r.instr: r for r in _made().device_rows("jit_step(")}
    assert rows["while.1"].seconds == pytest.approx(2 * (0.6 - 0.4))   # self time
    assert rows["bwd.6"].backward and not rows["gru.2"].backward
    assert rows["out.3"].scopes == ("recurrent_layer_group:dec", "fc:out")
    assert rows["copy.8"].scopes == (pt.UNSCOPED,)
    assert rows[""].scopes == (pt.OTHER_PROGRAMS,) and rows[""].seconds == pytest.approx(0.1)
    assert _made().device_rows("jit_other(") is None


def _view(p, monkeypatch):
    monkeypatch.setattr(pt, "of", lambda view: p)
    return tr.View(trace=p.trace, cell=types.SimpleNamespace(config={"target_dict_dim": 30}),
                   run=types.SimpleNamespace(facts={"step_program": "jit_step("}, trace_dir="x"),
                   peaks=None, chips=1)


@pytest.mark.parametrize("name,expected", [
    ("data_wait_ms.train", 1500.0),
    ("host_eval_ms.train", 4000.0),
    ("host_other_ms.train", 3500.0),
    # of 17.9 idle seconds, 3.5 under `trainer/step` alone (between its
    # children) and 0.5 under no span at all
    ("idle_unexplained_pct.train", 100.0 * 4.0 / 17.9),
    ("decoder_scan_ms.train", 600.0),                  # while 0.2 + gru 0.2 + bwd 0.2
    ("vocab_cost_ms.train", 500.0),                    # out 0.3 + ce 0.1 + cost 0.1
    ("unscoped_device_pct.train", 100.0 * 0.3 / 3.1),  # two copies and the other program
])
def test_reader_on_the_made_trace(name, expected, monkeypatch):
    reader = harness.load_module(os.path.join(REPO, "perfbench", "layer_metrics", name + ".py"))
    assert reader.read(_view(_made(), monkeypatch)) == pytest.approx(expected)


def _recorded_view(tmp_path, xplane, hlo):
    """The run directory a traced run leaves: `trace/` and `hlo/` side by side."""
    os.makedirs(tmp_path / "trace")
    shutil.copy(os.path.join(DATA, xplane), tmp_path / "trace" / xplane)
    if hlo:
        os.makedirs(tmp_path / "hlo")
        shutil.copy(os.path.join(DATA, hlo), tmp_path / "hlo" / "train_step-0.hlo.txt")
    trace = tr.load(str(tmp_path / "trace"), chips=1)
    return tr.View(trace=trace, cell=types.SimpleNamespace(config={"target_dict_dim": 200}),
                   run=types.SimpleNamespace(facts={"step_program": "jit_step("},
                                             trace_dir=str(tmp_path / "trace")),
                   peaks=None, chips=1)


recorded = pytest.mark.skipif(
    not os.path.exists(os.path.join(DATA, "tiny_step.xplane.pb")),
    reason="no recorded step trace")


@recorded
@pytest.mark.parametrize("name", READERS)
def test_reader_on_the_recorded_step(name, tmp_path):
    view = _recorded_view(tmp_path, "tiny_step.xplane.pb", "tiny_step.hlo.txt")
    reader = harness.load_module(os.path.join(REPO, "perfbench", "layer_metrics", name + ".py"))
    value = reader.read(view)
    assert value is not None and value >= 0
    if name.endswith("_pct.train"):
        assert value <= 100


@recorded
def test_recorded_step_has_four_steps_whose_phases_tile_them(tmp_path):
    p = pt.of(_recorded_view(tmp_path, "tiny_step.xplane.pb", "tiny_step.hlo.txt"))
    steps = p.steps()
    assert len(steps) == 4
    assert [s.num for s in steps] == list(range(steps[0].num, steps[0].num + 4))
    for s in steps:
        parts = sum(s.total[n] for n in (
            "trainer/data_wait", "trainer/flops_count", "trainer/launch",
            "trainer/loss_sync", "trainer/eval_outputs", "trainer/housekeeping"))
        assert parts + s.self_s["trainer/step"] == pytest.approx(s.end - s.start)
        assert s.total["eval/readback"] <= s.total["eval/classification_error"]
    rows = p.device_rows("jit_step(")
    scopes = {s for r in rows for s in r.scopes}
    assert "recurrent_layer_group:decoder_group" in scopes and "optimizer" in scopes
    assert any(r.backward for r in rows) and any(not r.backward for r in rows)
    # the toy's 200-word projection and its cost layer, found by shape
    vocab = pt.vocabulary_scopes(p.step_hlo("jit_step("), 200)
    assert any(v.startswith("multi-class-cross-entropy:") for v in vocab)
    assert any(v.startswith(("mixed:", "fc:")) for v in vocab)


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_in_a_program_without_spans_or_scopes(name, tmp_path):
    view = _recorded_view(tmp_path, "tiny.xplane.pb", None)
    view.run.facts["step_program"] = "jit_tiny_loop("
    reader = harness.load_module(os.path.join(REPO, "perfbench", "layer_metrics", name + ".py"))
    assert reader.read(view) is None
    assert reader.read(tr.View(None, view.run, view.cell, None, 1)) is None
