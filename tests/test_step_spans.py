"""The train step split in place (doc/observability.md "Spans"): every
phase of a launch is a `stat_timer` span under one `trainer/step` root, on
the profiler trace's clock and in the `pass_end` record's `spans`; every
layer, the cost and the optimizer run under a `jax.named_scope`; and each
compile's optimized HLO text, which maps a profile's device events to those
scopes, is kept beside its record. Round the steps, set-up is spans too
(`config/parse`, `trainer/init` and its children, `trainer/train`,
`data/provider_start`, `compile/*`), in the `pass_end` record's
`spans_total` and the `restart` record's, which the benchmark's set-up
readers read. No assertion here is on a duration."""

import glob
import os
import re
import subprocess
import sys
import textwrap
import time
import types

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.config import parse_config
from paddle_tpu.observability import metrics as obs
from paddle_tpu.observability import spans as obs_spans
from paddle_tpu.trainer import Trainer
from paddle_tpu.utils.flags import _Flags
from paddle_tpu.utils.stats import global_stats, stat_timer

pytestmark = pytest.mark.obs

PROVIDER_DIR = os.path.join(os.path.dirname(__file__), "providers")
STEPS = 3
# the spans of one launch, all on the trainer's thread and inside its step
PHASES = ("trainer/data_wait", "trainer/flops_count", "trainer/launch",
          "trainer/loss_sync", "trainer/eval_outputs", "trainer/housekeeping")
# the rest of the table of doc/observability.md that this run must show
# (`eval/classification_error` from `trainer/test` alone: in a train step
# the statistic is computed inside the step)
OTHERS = ("trainer/step", "trainer/pass", "trainer/test", "data/prefetch_wait",
          "data/h2d", "data/provider_next", "data/pack",
          "eval/classification_error", "checkpoint/save")
# an evaluator with no in-step form: its layer stays a program output
HOST_EVALUATOR = "value_printer_evaluator(input=output)"
# set-up by phase: the children of `Trainer.__init__`'s span, and the
# compile registry's three
INIT_PARTS = ("trainer/init_devices", "trainer/init_graph",
              "trainer/init_params", "trainer/init_opt_state")
COMPILE_PARTS = ("compile/trace_lower", "compile/backend", "compile/report")
SETUP_METRICS = ("setup_trainer_init_s", "setup_trace_lower_s",
                 "setup_compile_s", "setup_outside_program_pct")


def _config(tmp, evaluator=""):
    (tmp / "train.list").write_text("1\n")
    (tmp / "test.list").write_text("99\n")
    # synthetic_bow yields 400 samples a file: 160 + 160 + 80 = three steps
    (tmp / "conf.py").write_text(textwrap.dedent(f"""
    from paddle_tpu.trainer_config_helpers import *

    define_py_data_sources2(train_list={str(tmp / 'train.list')!r},
                            test_list={str(tmp / 'test.list')!r},
                            module="synthetic_bow", obj="process")
    settings(batch_size=160, learning_rate=0.02, learning_method=AdamOptimizer())
    data = data_layer(name="word", size=100)
    output = fc_layer(input=data, size=2, act=SoftmaxActivation(), name="output")
    label = data_layer(name="label", size=2)
    {evaluator}
    outputs(classification_cost(input=output, label=label))
    """))
    return str(tmp / "conf.py")


def _train(tmp, metrics_path, evaluator=""):
    """One pass of three steps; returns the run's records."""
    # flags of its own: the module's shared runs leave nothing in FLAGS
    flags = _Flags(save_dir=str(tmp / "out"), metrics_path=metrics_path,
                   num_passes=1, log_period=0, seed=7)
    sys.path.insert(0, PROVIDER_DIR)
    try:
        cfg = parse_config(_config(tmp, evaluator))
        obs.registry().reset()
        Trainer(cfg, flags).train(num_passes=1)
        path = os.path.join(metrics_path or flags.save_dir, "metrics.jsonl")
        return list(obs.read_records(path))
    finally:
        sys.path.remove(PROVIDER_DIR)
        obs.configure("")
        obs_spans.configure("")


def _program_spans(trace_dir):
    """{thread line: [(name, start, end, step number)]} of the
    `<layer>/<what>` events of a profiler trace."""
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats).get("step_num"))
                     for e in line.events
                     if re.fullmatch(r"[a-z_]+/[a-z0-9_]+", e.name)]
            if spans:
                lines[(plane.name, line.name, i)] = spans
    return lines


def _traced(tmp, evaluator=""):
    trace_dir = str(tmp / "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        records = _train(tmp, str(tmp / "metrics"), evaluator)
    finally:
        jax.profiler.stop_trace()
    return _program_spans(trace_dir), records, tmp


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Every evaluator of the run is computed inside the train step."""
    return _traced(tmp_path_factory.mktemp("step_spans"))


@pytest.fixture(scope="module")
def traced_host(tmp_path_factory):
    """The same run with one evaluator more that stays on the host."""
    return _traced(tmp_path_factory.mktemp("step_spans_host"), HOST_EVALUATOR)


def _trainer_line(lines):
    return next(sp for sp in lines.values()
                if any(s[0] == "trainer/step" for s in sp))


def test_every_span_of_the_table_is_in_the_trace(traced):
    lines, _, _ = traced
    seen = {s[0] for sp in lines.values() for s in sp}
    assert seen >= set(PHASES) | set(OTHERS), sorted(set(PHASES + OTHERS) - seen)
    # the feeder's two halves run on threads of their own, so on other
    # lines of the trace than the trainer's
    trainer = {s[0] for s in _trainer_line(lines)}
    assert "data/pack" not in trainer and "data/provider_next" not in trainer
    # one name, one rule
    assert not seen & {"train_step", "onePass", "test"}


def test_each_phase_lies_inside_a_step_and_steps_tile_the_pass(traced):
    line = _trainer_line(traced[0])
    steps = [s for s in line if s[0] == "trainer/step"]
    for name, start, end, _ in line:
        if name in PHASES:
            assert any(a <= start and end <= b for _, a, b, _ in steps), name
    # the steps that hold a launch carry the batch numbers 0, 1, 2; the one
    # more is the pull that found the pass's end
    launches = [s for s in line if s[0] == "trainer/launch"]
    full = [s for s in steps
            if any(s[1] <= l[1] and l[2] <= s[2] for l in launches)]
    assert [s[3] for s in full] == list(range(STEPS))
    assert len(steps) == STEPS + 1
    (_, p0, p1, _), = [s for s in line if s[0] == "trainer/pass"]
    assert all(p0 <= a and b <= p1 for _, a, b, _ in steps)
    assert sum(b - a for _, a, b, _ in steps) >= 0.95 * (p1 - p0)
    # no evaluator runs and nothing is read back inside a step whose
    # evaluators are all computed in the step: `eval/*` is `test()`'s
    tests = [s for s in line if s[0] == "trainer/test"]
    assert not any(s[0] == "eval/readback" for s in line)
    for name, start, end, _ in line:
        if name.startswith("eval/"):
            assert any(a <= start and end <= b for _, a, b, _ in tests), name


def test_host_evaluator_reads_back_inside_its_span_inside_eval_outputs(
        traced_host):
    line = _trainer_line(traced_host[0])
    steps = [s for s in line if s[0] == "trainer/step"]

    def in_steps(name):          # `test()` runs the evaluators as well
        return [s for s in line if s[0] == name
                and any(a <= s[1] < b for _, a, b, _ in steps)]

    evs = in_steps("trainer/eval_outputs")
    printers = in_steps("eval/value_printer")
    reads = in_steps("eval/readback")
    assert len(evs) == len(printers) == len(reads) == STEPS
    for inner, outer in ((reads, printers), (printers, evs)):
        for _, start, end, _ in inner:
            assert any(a <= start and end <= b for _, a, b, _ in outer)
    # the other evaluator of the run is still computed in the step
    assert not in_steps("eval/classification_error")


def test_pass_end_record_counts_the_same_spans(traced, traced_host):
    (end,) = [r for r in traced[1] if r["kind"] == "pass_end"]
    spans = end["spans"]
    for name in PHASES + ("trainer/step", "data/h2d"):
        assert spans[name][0] == STEPS, (name, spans[name])
        assert spans[name][1] >= 0
    assert spans["data/prefetch_wait"][0] >= STEPS
    # the pass's own span closes after its record is written
    assert "trainer/pass" not in spans
    # one increment an evaluator a batch, and no `eval/*` span in a pass
    # whose evaluators are all in the step
    assert not [n for n in spans if n.startswith("eval/")]
    assert end["counters"]["eval.device_batches"] == STEPS
    assert end["counters"]["eval.host_batches"] == 0
    (end,) = [r for r in traced_host[1] if r["kind"] == "pass_end"]
    assert end["spans"]["eval/value_printer"][0] == STEPS
    assert end["spans"]["eval/readback"][0] == STEPS
    assert "eval/classification_error" not in end["spans"]
    assert end["counters"]["eval.device_batches"] == STEPS
    assert end["counters"]["eval.host_batches"] == STEPS


def test_compile_record_names_the_kept_hlo_text(traced):
    _, records, tmp = traced
    compiles = [r for r in records if r["kind"] == "compile"]
    assert {r["group"] for r in compiles} >= {"train_step", "test_fwd"}
    for r in compiles:
        assert os.path.dirname(r["hlo_path"]) == str(tmp / "metrics" / "hlo")
        with open(r["hlo_path"]) as f:
            text = f.read()
        assert text.startswith("HloModule ")
    step = next(r for r in compiles if r["group"] == "train_step")
    with open(step["hlo_path"]) as f:
        assert 'op_name="jit(step)/optimizer/' in f.read()


def test_no_hlo_is_kept_without_metrics_path(tmp_path):
    records = _train(tmp_path, "")          # records go to --save_dir
    compiles = [r for r in records if r["kind"] == "compile"]
    assert compiles and not any("hlo_path" in r for r in compiles)
    assert not glob.glob(str(tmp_path / "**" / "hlo"), recursive=True)


def test_step_hlo_holds_a_scope_for_every_layer_cost_and_optimizer():
    from paddle_tpu.flagship import nmt_batch, nmt_config

    tc = nmt_config(vocab=300, dim=32, batch_size=4)
    trainer = Trainer(tc)
    text = trainer.train_step.lower(
        trainer.params, trainer.opt_state, nmt_batch(vocab=300, B=4, T=6),
        jax.random.PRNGKey(0), jnp.asarray(4.0)).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    fed = ("data", "agent", "sequence_agent", "scatter_agent")   # no compute
    for layer in tc.model_config.layers:
        if layer.type not in fed:
            scope = f"{layer.type}:{layer.name}"
            assert any(scope in n for n in op_names), scope
    assert any("/jvp(cost)/" in n for n in op_names)
    assert any(n.startswith("jit(step)/optimizer/") for n in op_names)
    (ev,) = tc.model_config.evaluators        # its statistic is in the step
    assert any(n.startswith(f"jit(step)/{ev.type}:{ev.name}/") for n in op_names)
    # a layer of the group's step nests under the group, backward included
    group = "recurrent_layer_group:decoder_group"
    assert any(f"jvp({group})/" in n and "gru_step:gru_decoder" in n
               for n in op_names)
    assert any(f"transpose(jvp({group}))/" in n and "gru_step:gru_decoder" in n
               for n in op_names)


def test_stat_timer_alone_adds_only_the_statset_entry(monkeypatch):
    obs_spans.configure("")
    before = global_stats.snapshot()
    with stat_timer("test/scope"):
        pass
    with stat_timer("test/step", step_num=7) as root:
        assert root.step_num == 7
    assert global_stats.growth_since(before).keys() == {"test/scope", "test/step"}
    assert global_stats.get("test/scope").count == before.get(
        "test/scope", (0, 0.0))[0] + 1
    # a dropped scope and one left by an exception reach the trace only
    before = global_stats.snapshot()
    with stat_timer("test/scope") as sp:
        sp.drop()
    with pytest.raises(KeyError):
        with stat_timer("test/scope"):
            raise KeyError("x")
    assert global_stats.growth_since(before) == {}
    # with a collector the same scope is also a Chrome trace event
    events = []
    monkeypatch.setattr(obs_spans, "_collector", type(
        "C", (), {"_t0": 0.0, "record": lambda self, *a: events.append(a)})())
    with stat_timer("test/scope"):
        pass
    assert [e[0] for e in events] == ["test/scope"]


# ------------------------------------------------------ set-up by phase


@pytest.fixture(scope="module")
def cli_run(tmp_path_factory):
    """`paddle train` as the CLI builds it (`cli._setup` -> `Trainer`), two
    `train()` calls of one pass each, in a StatSet and a registry emptied
    first as a new process has them: the first call stands for a
    benchmark's set-up, the second for its window."""
    from paddle_tpu import cli
    from paddle_tpu.utils.flags import FLAGS

    tmp = tmp_path_factory.mktemp("cli_run")
    saved = dict(vars(FLAGS))
    sys.path.insert(0, PROVIDER_DIR)
    try:
        global_stats.reset()
        obs.registry().reset()
        t_start = time.monotonic()
        flags, cfg = cli._setup([
            f"--config={_config(tmp)}", f"--save_dir={tmp / 'out'}",
            f"--metrics_path={tmp / 'metrics'}", "--num_passes=1",
            "--log_period=0", "--seed=7", "--use_tpu=0"])
        trainer = Trainer(cfg, flags)
        trainer.train(num_passes=1)
        trainer.start_pass = 1
        setup_s = time.monotonic() - t_start
        trainer.train(num_passes=2)
        records = list(obs.read_records(str(tmp / "metrics" / "metrics.jsonl")))
        return types.SimpleNamespace(
            records=records, setup_s=setup_s, stats=global_stats.snapshot(),
            metrics_dir=str(tmp / "metrics"))
    finally:
        sys.path.remove(PROVIDER_DIR)
        obs.configure("")
        obs_spans.configure("")
        vars(FLAGS).clear()
        vars(FLAGS).update(saved)


def test_cli_run_shows_every_setup_span_with_the_counts_it_implies(cli_run):
    stats = cli_run.stats
    compiles = [r for r in cli_run.records if r["kind"] == "compile"]
    assert compiles and not any(r.get("mode") == "inline" for r in compiles)
    want = {"config/parse": 1, "trainer/init": 1, "trainer/train": 2,
            "data/provider_start": 2, "trainer/pass": 2,
            **{n: 1 for n in INIT_PARTS},
            **{n: len(compiles) for n in COMPILE_PARTS}}
    assert {n: stats.get(n, (0, 0.0))[0] for n in want} == want
    # the compile record's two durations are the spans' own
    for part, key in (("compile/trace_lower", "trace_s"),
                      ("compile/backend", "compile_s")):
        assert stats[part][1] == pytest.approx(
            sum(r[key] for r in compiles), abs=1e-5 * len(compiles))


def test_children_of_a_setup_span_lie_inside_it(cli_run):
    def total(*names):
        return sum(cli_run.stats[n][1] for n in names)

    assert total(*INIT_PARTS) <= total("trainer/init")
    assert (total("data/provider_start", "trainer/pass", "trainer/test",
                  "checkpoint/save") <= total("trainer/train"))
    # the train step compiles under its launch, the test forward under
    # the pass-end test
    assert total(*COMPILE_PARTS) <= total("trainer/launch", "trainer/test")


def test_pass_end_totals_are_the_totals_before_plus_the_pass(cli_run):
    first, second = [r for r in cli_run.records if r["kind"] == "pass_end"]
    for name in PHASES + ("trainer/step", "data/h2d"):
        c0, s0 = first["spans_total"][name]
        dc, ds = second["spans"][name]
        c1, s1 = second["spans_total"][name]
        assert c1 == c0 + dc, name
        assert s1 == pytest.approx(s0 + ds, abs=1e-5), name
    # a span still open at the pass's end is not in its record yet: the
    # first call's `trainer/train` and pass are closed by the second's
    assert "trainer/train" not in first["spans_total"]
    assert second["spans_total"]["trainer/train"][0] == 1
    assert second["spans_total"]["trainer/pass"][0] == 1
    assert first["spans_total"]["trainer/init"][0] == 1
    # cumulative like the counters beside them
    for name in ("jax.trace_s", "jax.lower_s", "jax.backend_compile_s"):
        assert second["counters"][name] >= first["counters"][name] > 0
    assert first["counters"]["jax.compiles"] >= first["counters"]["compile.count"]


def test_restart_record_carries_time_to_first_step_by_phase(cli_run):
    (restart,) = [r for r in cli_run.records if r["kind"] == "restart"]
    assert obs.validate_record(restart) == []
    spans = restart["spans_total"]
    for name in ("config/parse", "trainer/init", "data/provider_start",
                 "trainer/data_wait", "trainer/flops_count",
                 "trainer/launch", "trainer/loss_sync") + INIT_PARTS + COMPILE_PARTS:
        assert spans[name][0] == 1, name
    # the launch's step, its pass and its call are still open
    assert not {"trainer/step", "trainer/pass", "trainer/train"} & set(spans)
    # and `paddle metrics` prints the phases beside the two numbers
    from paddle_tpu.observability.analyze import (RESTART_PHASES, _fmt_table,
                                                  analyze, load_run)

    table = _fmt_table(analyze(load_run(cli_run.metrics_dir)))
    (head,) = [l for l in table.splitlines() if l.startswith("restart  ")]
    assert all(h in head for h, _ in RESTART_PHASES)


def test_setup_readers_over_the_cli_run(cli_run):
    """The benchmark's four readers of set-up, over this run's records:
    the first `train()` call is the set-up, the second the window."""
    from perfbench import harness

    pb = os.path.join(os.path.dirname(os.path.dirname(__file__)), "perfbench")
    view = types.SimpleNamespace(run=types.SimpleNamespace(
        trace_dir=os.path.join(cli_run.metrics_dir, "trace"),
        values={"setup_s": cli_run.setup_s}))
    got = {m: harness.load_module(
        os.path.join(pb, "layer_metrics", m + ".py")).read(view)
        for m in SETUP_METRICS}
    assert all(isinstance(v, float) for v in got.values()), got
    first, second = [r for r in cli_run.records if r["kind"] == "pass_end"]
    assert got["setup_trainer_init_s"] == first["spans_total"]["trainer/init"][1]
    assert got["setup_trace_lower_s"] == (
        first["counters"]["jax.trace_s"] + first["counters"]["jax.lower_s"])
    assert sum(got[m] for m in SETUP_METRICS[:3]) < cli_run.setup_s
    assert 0 < got["setup_outside_program_pct"] < 100


def test_dump_config_opens_no_span_and_imports_no_jax(tmp_path):
    """`config/parse` is the device jobs': a span's first scope imports
    jax, which `paddle dump_config` must stay usable without."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import sys; sys.modules['jax'] = None\n"
            "from paddle_tpu import cli\n"
            f"rc = cli.main(['dump_config', '--config={_config(tmp_path)}'])\n"
            "from paddle_tpu.utils.stats import global_stats\n"
            "assert global_stats.snapshot() == {}, global_stats.snapshot()\n"
            "sys.exit(rc)\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu",
                          "PYTHONPATH": f"{repo}:{repo}/compat:{PROVIDER_DIR}"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert '"model_config"' in out.stdout
