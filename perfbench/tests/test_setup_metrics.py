"""The four readers of set-up (`setup_phases.py`, `layer_metrics/setup_*.py`):
hand-made records whose numbers can be reckoned by hand, records without the
new keys (a program before the spans: nothing to read, never an error), and
the toy cell traced with the four entries added to its `BENCHMARK.json`."""

import json
import os
import types

import pytest

from perfbench import harness, setup_phases
from perfbench.tests import helpers

METRICS = ("setup_trainer_init_s", "setup_trace_lower_s", "setup_compile_s",
           "setup_outside_program_pct")
B = json.load(open(os.path.join(helpers.REPO, "BENCHMARK.json")))


def _read_all(view):
    return {m: harness.load_module(os.path.join(
        helpers.REPO, "perfbench", "layer_metrics", m + ".py")).read(view)
        for m in METRICS}


def _view(tmp_path, records, setup_s=40.0):
    out = tmp_path / "cell"
    out.mkdir()
    with open(out / "metrics.jsonl", "w") as f:
        f.write("not a record\n")
        for r in records:
            f.write(json.dumps(r) + "\n")
    return types.SimpleNamespace(run=types.SimpleNamespace(
        trace_dir=str(out / "trace"), values={"setup_s": setup_s}))


def _records(new=True):
    """Three set-up passes (a `train()` call each) and the window's."""
    recs = [{"kind": "run_start", "pass": 0}]
    for p in range(4):
        window = p == 3
        rec = {"kind": "pass_end", "pass": p,
               "spans": {"trainer/step": [1 if not window else 5, 2.0],
                         "trainer/launch": [1 if not window else 5, 1.5]},
               "counters": {"compile.count": 1.0}}
        if new:
            done = p          # `train()` calls closed before this pass's end
            rec["spans_total"] = {
                "config/parse": [1, 0.5], "trainer/init": [1, 6.0],
                "trainer/init_params": [1, 4.0],
                "trainer/train": [done, 7.0 * done],
                "trainer/pass": [done, 6.5 * done],
                "data/provider_start": [p + 1, 0.1 * (p + 1)],
                "trainer/step": [p + 1 if not window else 8, 2.0 * (p + 1)],
                "trainer/launch": [p + 1 if not window else 8, 1.5 * (p + 1)],
                "compile/trace_lower": [1, 3.0], "compile/backend": [1, 2.0],
                "compile/report": [1, 0.25]}
            rec["counters"].update({
                "jax.trace_s": 5.0 + p, "jax.lower_s": 2.0,
                "jax.backend_compile_s": 1.0, "jax.cache_load_s": 3.0 + p,
                "jax.compiles": 40.0})
        recs.append(rec)
    return recs


def test_hand_made_records_give_the_four_values(tmp_path, capsys):
    got = _read_all(_view(tmp_path, _records()))
    assert got["setup_trainer_init_s"] == 6.0
    # the counters of the last pass before the window (pass 2)
    assert got["setup_trace_lower_s"] == 7.0 + 2.0
    assert got["setup_compile_s"] == 1.0 + 5.0 + 0.25
    # 40 s less the parse, the construction and three 7 s calls
    assert got["setup_outside_program_pct"] == pytest.approx(
        100 * (40.0 - 0.5 - 6.0 - 21.0) / 40.0)
    # the table, once a run: parents before children, then the counters
    err = capsys.readouterr().err
    assert err.count("set-up by span") == 1
    lines = err.splitlines()
    at = {n: next(i for i, l in enumerate(lines) if l.split()[:1] == [n])
          for n in ("trainer/init", "trainer/init_params", "trainer/train",
                    "trainer/pass", "trainer/step", "compile/backend")}
    assert at["trainer/init"] < at["trainer/init_params"] < at["trainer/train"]
    assert at["trainer/train"] < at["trainer/pass"] < at["trainer/step"]
    # count, total, self: three calls of 7 s, 6.5 s of each in its pass,
    # and 0.1 s a provider (the window's own is in: one call more)
    assert lines[at["trainer/train"]].split()[1:4] == ["3", "21.000", "1.100"]
    assert "jax.trace_s 7.000" in err and "jax.compiles 40" in err


def test_spans_before_the_window_leave_the_window_out(tmp_path):
    window = _records()[-1]
    spans = setup_phases.spans_before_window(window)
    assert spans["trainer/step"] == (3, 6.0)          # 8 - 5 launches
    assert spans["trainer/train"] == (3, 21.0)
    assert spans["compile/report"] == (1, 0.25)


def test_records_of_a_program_without_the_spans_give_none(tmp_path):
    assert _read_all(_view(tmp_path, _records(new=False))) == dict.fromkeys(METRICS)
    # nor with one pass only, no trace directory, or no records at all
    empty = types.SimpleNamespace(run=types.SimpleNamespace(
        trace_dir=None, values={}))
    assert _read_all(empty) == dict.fromkeys(METRICS)
    (tmp_path / "one").mkdir()
    assert _read_all(_view(tmp_path / "one", _records()[:2])) == dict.fromkeys(METRICS)


def test_benchmark_json_lists_the_four_in_every_cell():
    cells = [w["name"] for w in B["workloads"]]
    mine = {m["name"]: m for m in B["per_layer"] if m["name"] in METRICS}
    assert list(mine) == list(METRICS)
    assert [m["name"] for m in B["per_layer"]][-4:] == list(METRICS)
    for m in mine.values():
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["workloads"] == cells
        assert m["layer"] in ("trainer set-up", "compile + cache")


def test_toy_cell_traced_reports_the_four(tmp_path):
    root = helpers.make_root(tmp_path)
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    for m in B["per_layer"]:
        if m["name"] in METRICS:
            bench["per_layer"].append(dict(m, workloads=["toy.train"]))
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    line = helpers.run_toy(root, "toy.train", seed=2 ** 31 + 11, seconds=0.5,
                           trace=1)
    assert line["correct"] is True, line["compared"]
    got = {m: line["metrics"][m]["value"] for m in METRICS}
    assert all(v > 0 for v in got.values()), got
    assert got["setup_outside_program_pct"] < 100
