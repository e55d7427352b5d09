"""What `kanana.train` adds to the yardstick: `flops/deepseek_v3.py` against
a count by brute force at a small size and against the issue's reckoning at
the cell's, the new reader `latent_proj_ms.train` on a hand-made trace whose
numbers can be reckoned by hand (and on a program without the scopes:
nothing, no error), the accepted readers the cell lists on the same trace,
and the comparison's handling of a static leaf."""

import json
import os
import types

import numpy as np
import pytest

from perfbench import harness, program_trace as pt, trace_reduce as tr
from perfbench.tests.helpers import REPO

fl = harness.load_module(os.path.join(REPO, "perfbench/flops/deepseek_v3.py"))
ref = harness.load_module(os.path.join(REPO, "perfbench/reference/deepseek_v3.py"))
SIZES = json.load(open(os.path.join(REPO, "perfbench/configs/kanana-2-30b-a3b-ep8.json")))
SMALL = dict(SIZES, hidden_size=8, num_attention_heads=3, qk_nope_head_dim=4, qk_rope_head_dim=2,
             v_head_dim=5, kv_lora_rank=6, intermediate_size=12, moe_intermediate_size=6,
             n_routed_experts=4, n_routed_experts_total=8, num_experts_per_tok=2, vocab_size=11)


def test_operations_against_a_count_by_brute_force():
    """Every weight matrix a token passes through costs two operations an
    element (the held experts by the share of pairs routed here; the bias
    and the gains none), attention 2 (dn + dr) operations a score and 2 dv a
    value for each pair j <= i, counted pair by pair."""
    t = 7
    shapes = ref.param_shapes(SMALL)
    size = lambda name: int(np.prod(shapes[name]))
    pairs = sum(1 for i in range(t) for j in range(t) if j <= i)
    assert fl.allowed_pairs(t) == pairs
    total = 0.0
    for l in range(5):
        dense = sum(size(f"l{l}_{n}") for n in ("wq", "wkv_a", "wkv_b", "wo"))
        if l >= 1:
            dense += sum(size(f"l{l}_{n}") for n in ("router", "shared_gate", "shared_up", "shared_down"))
            one_expert = sum(size(f"l{l}_{n}") for n in ("gate", "up", "down")) / 4
            dense += one_expert * 2 * 4 / 8               # k pairs a token, 4 of 8 held
        else:
            dense += sum(size(f"l{l}_{n}") for n in ("mlp_gate", "mlp_up", "mlp_down"))
        want = 2.0 * t * dense + 2.0 * pairs * 3 * (4 + 2 + 5)
        assert sum(fl.layer_forward(SMALL, l, t).values()) == pytest.approx(want)
        total += want
    total += 2.0 * t * size("head")
    assert fl.forward_flops(SMALL, [t, t]) == pytest.approx(2 * total)
    assert fl.train_step_flops(SMALL, {"labels": [t, t]}) == pytest.approx(6 * total)
    (call,) = fl.train_kernel_calls(SMALL, {"labels": (t, 2)})
    # sequences x layers x heads x allowed pairs x (score 4 + 2, value 5) lanes, forward + backward
    assert call["kind"] == "causal_attention"
    assert call["flops"] == 3 * 2 * (2 * 5 * 3 * pairs) * (4 + 2 + 5)
    # by head: q_nope 4, q_rope 2, k_nope 4, v 5, out 5; k_rope 2 ONCE; three passes of them
    assert call["bytes"] == 2 * 5 * 3 * t * (3 * (4 + 2 + 4 + 5 + 5) + 2) * 2
    mm = fl.grouped_mm_call(SMALL, 10)
    assert mm["flops"] == 3.0 * 10 * 3 * 2 * 8 * 6


def test_the_cell_counts_what_the_issue_reckoned():
    """ISSUE 34's numbers: 575,955,968 parameters, 91 TFLOP a step, the
    kernels' 41.2 TFLOP (3 x 2 x 33.56 M pairs x 320 lanes x 32 heads x 4
    sequences x 5 layers), the five projections' 26 TFLOP."""
    shapes = ref.param_shapes(SIZES)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 575955968
    assert sum(int(np.prod(s)) for n, s in shapes.items() if n.startswith("l2_w") or n == "l2_kv_norm") == 26345984
    assert fl.train_step_flops(SIZES, {"labels": [8192] * 4}) == pytest.approx(91e12, rel=0.01)
    (call,) = fl.train_kernel_calls(SIZES, {"labels": (8192, 4)})
    assert call["flops"] == pytest.approx(41.2e12, rel=0.005)
    parts = fl.layer_forward(SIZES, 1, 8192)
    assert 3 * 4 * 5 * (parts["projections"] + parts["latent"]) == pytest.approx(26e12, rel=0.02)
    assert ref.static_leaves(SIZES) == [f"l{l}_router_bias" for l in range(1, 5)]


# ------------------------------------------------------------------ the readers

HLO = """HloModule jit_step, is_scheduled=true

ENTRY %main {
  %q.1 = f32[8,4]{1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(step)/jvp(remat_block:block0)/multi_head_attention:l0_attn/qkv/dot_general"}
  %down.2 = f32[8,4]{1,0} fusion(%b), kind=kLoop, metadata={op_name="jit(step)/jvp(remat_block:block0)/multi_head_attention:l0_attn/latent_down/dot_general"}
  %up.3 = f32[8,4]{1,0} fusion(%c), kind=kLoop, metadata={op_name="jit(step)/transpose(jvp(remat_block:block1))/multi_head_attention:l1_attn/latent_up/dot_general"}
  %core.4 = f32[8,4]{1,0} custom-call(%d), metadata={op_name="jit(step)/jvp(remat_block:block1)/multi_head_attention:l1_attn/core/attention_fwd"}
  %core.5 = f32[8,4]{1,0} custom-call(%e), metadata={op_name="jit(step)/transpose(jvp(remat_block:block0))/multi_head_attention:l0_attn/core/attention_dkv"}
  %mlp.6 = f32[8,4]{1,0} fusion(%f), kind=kLoop, metadata={op_name="jit(step)/jvp(remat_block:block1)/gated_mlp:l1_shared/dot_general"}
  %norm.7 = f32[8,4]{1,0} fusion(%g), kind=kLoop, metadata={op_name="jit(step)/jvp(remat_block:block1)/rms_norm:l1_latent_down/mul"}
  ROOT %adam.8 = f32[4,30]{1,0} fusion(%h), kind=kLoop, metadata={op_name="jit(step)/optimizer/mul"}
}
"""
CONFIG = {"num_hidden_layers": 2, "layer_types": ["full_attention", "full_attention"],
          "param_map": {"_l0_attn.wq": "l0_wq", "_l1_attn.wq": "l1_wq"}, "reference": "deepseek_v3"}
PEAKS = {"bf16_tflops": 100.0, "hbm_gbps": 1000.0}


def _view(tmp_path, monkeypatch, hlo=HLO, config=CONFIG):
    """Two steps of the program above; each op runs once a step."""
    durations = {"q.1": 0.10, "down.2": 0.07, "up.3": 0.13, "core.4": 0.20, "core.5": 0.30,
                 "mlp.6": 0.25, "norm.7": 0.05, "adam.8": 0.05}
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
    calls = [{"kind": "causal_attention", "flops": 2e12, "bytes": 1e6}]
    cell = types.SimpleNamespace(config=config)
    run = types.SimpleNamespace(facts={"step_program": "jit_step(", "kernel_calls": calls,
                                       "steps": 2}, trace_dir=str(tmp_path / "trace"))
    return tr.View(trace=trace, cell=cell, run=run, peaks=PEAKS, chips=1)


@pytest.mark.parametrize("name,expected", [
    ("latent_proj_ms.train", 200.0),                  # latent_down 0.07 + latent_up 0.13; no rms_norm:l1_latent_down
    ("attention_ms.train", 800.0),                    # qkv, the latent's two, both cores
    ("causal_attention_roofline", 100.0 * 0.02 / 0.5),   # 2e12 / 100e12 s over both layers' cores
    ("gated_mlp_ms.train", 250.0),
])
def test_reader_on_the_made_trace(name, expected, tmp_path, monkeypatch):
    reader = harness.load_module(os.path.join(REPO, "perfbench", "layer_metrics", name + ".py"))
    assert reader.read(_view(tmp_path, monkeypatch)) == pytest.approx(expected)


def test_the_new_reader_finds_nothing_where_the_program_has_no_such_scope(tmp_path, monkeypatch):
    """The parent's program (no latent scopes), and no trace at all: None,
    never an error."""
    reader = harness.load_module(os.path.join(REPO, "perfbench", "layer_metrics",
                                              "latent_proj_ms.train.py"))
    plain = HLO.replace("/latent_down/", "/qkv/").replace("/latent_up/", "/qkv/")
    view = _view(tmp_path, monkeypatch, hlo=plain)
    assert reader.read(view) is None
    monkeypatch.setattr(pt, "of", lambda view: None)          # nothing was traced
    assert reader.read(tr.View(None, types.SimpleNamespace(facts={}, trace_dir=None),
                               view.cell, None, 1)) is None


def test_a_static_leaf_is_compared_as_a_zero_gradient(capsys):
    """`compare/train_steps_static.py`: the program reports no first moment
    for a static leaf; the comparison fills zeros in and is then
    `train_steps_lean`'s own."""
    static = harness.load_module(os.path.join(REPO, "perfbench/compare/train_steps_static.py"))
    lean = harness.load_module(os.path.join(REPO, "perfbench/compare/train_steps_lean.py"))
    assert static.checks is lean.checks and static.reference_steps is lean.reference_steps
    seen = {}

    def fake_compare(ref_, sizes, seed, batches, program, limits):
        seen.update(program["grad"])
        return ["checked"]

    ref_ = types.SimpleNamespace(param_shapes=lambda s: {"a": (2, 3), "bias": (1, 4)},
                                 static_leaves=lambda s: ["bias"])
    program = {"grad": {"a": np.ones((2, 3))}, "change_norm": {"a": 1.0, "bias": 0.0}}
    old, static._lean.compare = static._lean.compare, fake_compare
    try:
        assert static.compare(ref_, {}, 1, [], program, {}) == ["checked"]
    finally:
        static._lean.compare = old
    assert seen["bias"].shape == (1, 4) and not seen["bias"].any() and seen["a"].all()
    assert "'bias': 0.0" in capsys.readouterr().err
