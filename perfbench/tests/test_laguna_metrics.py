"""What `laguna.train` adds to the yardstick: its traffic mix's determinism,
`flops/laguna.py` against a count by brute force at a small size, and the
five new per-layer readers on a hand-made trace whose numbers can be
reckoned by hand (and on a program without the scopes: nothing, no error)."""

import json
import os
import types

import numpy as np
import pytest

from perfbench import harness, program_trace as pt, trace_reduce as tr
from perfbench.tests.helpers import REPO

fl = harness.load_module(os.path.join(REPO, "perfbench/flops/laguna.py"))
ref = harness.load_module(os.path.join(REPO, "perfbench/reference/laguna.py"))
gen = harness.load_module(os.path.join(REPO, "perfbench/traffic/next_token.py"))
SIZES = json.load(open(os.path.join(REPO, "perfbench/configs/laguna-xs.2-ep16.json")))
MIX = json.load(open(os.path.join(REPO, "perfbench/traffic/lm_4x8192.json")))
SMALL = dict(SIZES, hidden_size=8, head_dim=4, num_key_value_heads=2, intermediate_size=12,
             moe_intermediate_size=6, shared_expert_intermediate_size=5, num_experts=4,
             num_experts_routed=8, num_experts_per_tok=2, vocab_size=11, sliding_window=3,
             num_attention_heads_per_layer=[4, 6, 6, 6, 4] + [6] * 35)


def test_the_mix_is_a_function_of_the_seed_and_every_token_is_real():
    a = gen.generate(MIX, SIZES, 2 ** 31 + 12345)
    b = gen.generate(MIX, SIZES, 2 ** 31 + 12345)
    c = gen.generate(MIX, SIZES, 7)
    for f in ("labels", "tokens"):
        assert np.array_equal(a.seq[f][0], b.seq[f][0])
        assert not np.array_equal(a.seq[f][0][:1000], c.seq[f][0][:1000])
        assert (a.seq[f][1] == 8192).all()
    assert (a.group, a.groups) == (4, 24) and a.shapes(0)["labels"] == (8192, 4)
    assert a.real_tokens("labels", 5) == 32768
    assert 0 <= a.seq["labels"][0].min() and a.seq["labels"][0].max() == SIZES["vocab_size"] - 1
    rows = gen.samples_of(a, 2)
    assert set(rows[0]) == {"labels", "tokens"}          # the program is fed no weight
    assert rows[1]["tokens"] == [0] + rows[1]["labels"][:-1]
    arrays = gen.arrays_of(a, 2)
    assert arrays["weights"].shape == (4, 8192) and (arrays["weights"] == 1).all()
    assert arrays["labels"].tolist()[1] == rows[1]["labels"]


def test_operations_against_a_count_by_brute_force():
    """Every weight matrix a token passes through costs two operations an
    element (the held experts by the share of pairs routed here), attention
    two products of head_dim a pair the rule allows, counted pair by pair."""
    t = 7
    shapes = ref.param_shapes(SMALL)
    size = lambda name: int(np.prod(shapes[name]))
    total = 0.0
    for l in range(5):
        nh, hd = SMALL["num_attention_heads_per_layer"][l], SMALL["head_dim"]
        window = 3 if SMALL["layer_types"][l] == "sliding_attention" else 0
        pairs = sum(1 for i in range(t) for j in range(t)
                    if j <= i and (not window or i - j < window))
        assert fl.allowed_pairs(t, window) == pairs
        dense = sum(size(f"l{l}_{n}") for n in ("wq", "wk", "wv", "wo", "wgate"))
        if SMALL["mlp_layer_types"][l] == "sparse":
            dense += sum(size(f"l{l}_{n}") for n in ("router", "shared_gate", "shared_up", "shared_down"))
            one_expert = sum(size(f"l{l}_{n}") for n in ("gate", "up", "down")) / 4
            dense += one_expert * 2 * 4 / 8               # k pairs a token, 4 of 8 held
        else:
            dense += sum(size(f"l{l}_{n}") for n in ("mlp_gate", "mlp_up", "mlp_down"))
        want = 2.0 * t * dense + 4.0 * pairs * nh * hd
        assert sum(fl.layer_forward(SMALL, l, t).values()) == pytest.approx(want)
        total += want
    total += 2.0 * t * size("head")
    assert fl.forward_flops(SMALL, [t, t]) == pytest.approx(2 * total)
    assert fl.train_step_flops(SMALL, {"labels": [t, t]}) == pytest.approx(6 * total)
    calls = {c["kind"]: c for c in fl.train_kernel_calls(SMALL, {"labels": (t, 2)})}
    assert set(calls) == {"window_attention", "causal_attention"}
    # sequences x layers x heads x allowed pairs, forward + backward, head_dim 4
    assert calls["causal_attention"]["flops"] == 3 * 4 * (2 * 2 * 4 * (t * (t + 1) // 2)) * 4
    assert calls["window_attention"]["flops"] == 3 * 4 * (2 * 3 * 6 * (3 * t - 3)) * 4
    mm = fl.grouped_mm_call(SMALL, 10)
    assert mm["flops"] == 3.0 * 10 * 3 * 2 * 8 * 6


def test_the_cell_counts_what_the_issue_reckoned():
    step = fl.train_step_flops(SIZES, {"labels": [8192] * 4})
    assert step == pytest.approx(77.4e12, rel=0.005)
    by_layer = [4 * sum(fl.layer_forward(SIZES, l, 8192).values()) for l in range(5)]
    assert by_layer[0] == pytest.approx(8.5e12, rel=0.01)
    assert by_layer[1] == by_layer[2] == by_layer[3] == pytest.approx(3.35e12, rel=0.01)
    assert by_layer[4] == pytest.approx(5.6e12, rel=0.02)
    assert sum(int(np.prod(s)) for s in ref.param_shapes(SIZES).values()) == 490297344


# ------------------------------------------------------------------ the readers

HLO = """HloModule jit_step, is_scheduled=true

ENTRY %main {
  %q.1 = f32[8,4]{1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(step)/jvp(remat_block:block0)/multi_head_attention:l0_attn/qkv/dot_general"}
  %core.2 = f32[8,4]{1,0} custom-call(%b), metadata={op_name="jit(step)/jvp(remat_block:block0)/multi_head_attention:l0_attn/core/attention_fwd"}
  %core.3 = f32[8,4]{1,0} custom-call(%c), metadata={op_name="jit(step)/jvp(remat_block:block1)/multi_head_attention:l1_attn/core/attention_fwd"}
  %core.4 = f32[8,4]{1,0} custom-call(%d), metadata={op_name="jit(step)/transpose(jvp(remat_block:block1))/multi_head_attention:l1_attn/core/attention_dq"}
  %gate.5 = f32[8,4]{1,0} fusion(%e), kind=kLoop, metadata={op_name="jit(step)/jvp(remat_block:block1)/multi_head_attention:l1_attn/gate/logistic"}
  %mlp.6 = f32[8,4]{1,0} fusion(%f), kind=kLoop, metadata={op_name="jit(step)/jvp(remat_block:block0)/gated_mlp:l0_mlp/dot_general"}
  %mlp.7 = f32[8,4]{1,0} fusion(%g), kind=kLoop, metadata={op_name="jit(step)/transpose(jvp(remat_block:block1))/gated_mlp:l1_shared/dot_general"}
  ROOT %adam.8 = f32[4,30]{1,0} fusion(%h), kind=kLoop, metadata={op_name="jit(step)/optimizer/mul"}
}
"""
CONFIG = {"num_hidden_layers": 2, "layer_types": ["full_attention", "sliding_attention"],
          "param_map": {"_l0_attn.wq": "l0_wq", "_l1_attn.wq": "l1_wq", "_l1_attn.wk": "l1_wk"},
          "reference": "laguna"}
PEAKS = {"bf16_tflops": 100.0, "hbm_gbps": 1000.0}


def _view(tmp_path, monkeypatch, hlo=HLO, config=CONFIG):
    """Two steps of the program above; each op runs once a step."""
    durations = {"q.1": 0.10, "core.2": 0.40, "core.3": 0.20, "core.4": 0.30, "gate.5": 0.05,
                 "mlp.6": 0.25, "mlp.7": 0.15, "adam.8": 0.05}
    ops, modules = [], []
    for start in (1.0, 4.0):
        modules.append(("jit_step(7)", start, 2.0))
        t = start
        for name, d in durations.items():
            ops.append((f"%{name} = f32[8,4] fusion(%x)", t, d))
            t += d
    trace = tr.Trace([tr.Device("/device:TPU:0", ops, modules)], [], (0.0, 7.0))
    p = pt.ProgramTrace({}, trace, dict([pt.parse_hlo(hlo)]))
    monkeypatch.setattr(pt, "of", lambda view: p)
    os.makedirs(tmp_path / "trace", exist_ok=True)
    calls = [{"kind": "causal_attention", "flops": 2e12, "bytes": 1e6},
             {"kind": "window_attention", "flops": 1e12, "bytes": 1e6}]
    cell = types.SimpleNamespace(config=config)
    run = types.SimpleNamespace(facts={"step_program": "jit_step(", "kernel_calls": calls,
                                       "steps": 2}, trace_dir=str(tmp_path / "trace"))
    return tr.View(trace=trace, cell=cell, run=run, peaks=PEAKS, chips=1)


@pytest.mark.parametrize("name,expected", [
    ("window_attention_ms.train", 550.0),             # l1: core 0.2 + 0.3, gate 0.05
    ("window_attention_roofline", 100.0 * 0.01 / 0.5),   # 1e12 / 100e12 s over l1's core
    ("causal_attention_roofline", 100.0 * 0.02 / 0.4),   # l0's core
    ("gated_mlp_ms.train", 400.0),                    # 0.25 + 0.15
    ("attention_ms.train", 1050.0),                   # every attention layer
])
def test_reader_on_the_made_trace(name, expected, tmp_path, monkeypatch):
    reader = harness.load_module(os.path.join(REPO, "perfbench", "layer_metrics", name + ".py"))
    assert reader.read(_view(tmp_path, monkeypatch)) == pytest.approx(expected)


@pytest.mark.parametrize("name", ["window_attention_ms.train", "window_attention_roofline",
                                  "causal_attention_roofline", "gated_mlp_ms.train"])
def test_reader_finds_nothing_where_the_program_has_no_such_scope(name, tmp_path, monkeypatch):
    """Another configuration's program (no `layer_types`, no `gated_mlp`),
    and no trace at all: None, never an error."""
    reader = harness.load_module(os.path.join(REPO, "perfbench", "layer_metrics", name + ".py"))
    plain = HLO.replace("gated_mlp:", "fc:")
    view = _view(tmp_path, monkeypatch, hlo=plain, config={"num_hidden_layers": 2, "reference": "x"})
    view.run.facts["kernel_calls"] = [{"kind": "bd_attention", "flops": 1.0, "bytes": 1.0}]
    assert reader.read(view) is None
    monkeypatch.setattr(pt, "of", lambda view: None)          # nothing was traced
    assert reader.read(tr.View(None, types.SimpleNamespace(facts={}, trace_dir=None),
                               view.cell, None, 1)) is None
