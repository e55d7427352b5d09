"""Block-diffusion mixture-of-experts training through the normal path:
`paddle train` on the demo config, and the trainer's first three steps
against the plain reference (`perfbench/reference/sdar_moe.py`) through the
benchmark's own harness, entry (`entries/train_routed.py`: the program's
expert choices go to the reference as data) and comparison
(`perfbench/compare/train_steps_lean.py`: `train_steps.py`'s numbers),
float32 and bfloat16, with the fp8 control coming out NOT correct; small
size (tests/test_block_diffusion_moe.py's), on the CPU.
"""

import io
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEMO = os.path.join(REPO, "demo", "block_diffusion_moe")
L = 32
SIZES = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_routed": 8, "experts_held_first": 0, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "vocab_size": 97, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "norm_topk_prob": True, "block_length": 4, "mask_id": 96,
}


def test_the_demo_trains_under_paddle_train(tmp_path):
    for f in ("trainer_config.py", "dataprovider.py"):
        shutil.copy(os.path.join(DEMO, f), tmp_path)
    (tmp_path / "train.list").write_text("seed-1\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(tmp_path), REPO, os.path.join(REPO, "compat")]))
    run = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "paddle"), "train",
         "--config=trainer_config.py", "--num_passes=1", f"--save_dir={tmp_path}/out",
         "--log_period=4", f"--metrics_path={tmp_path}/metrics"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert run.returncode == 0, run.stderr[-2000:]
    ends = [json.loads(l) for p in (tmp_path / "metrics").rglob("*.jsonl")
            for l in p.read_text().splitlines() if '"pass_end"' in l]
    assert ends and np.isfinite(ends[-1]["AvgCost"])
    # 8 batches of 8 sequences of 64 positions, 2 layers, 2 choices a token, all held
    assert ends[-1]["counters"]["moe.pairs_held"] == 8 * 8 * 64 * 2 * 2
    assert ends[-1]["counters"]["moe.load_max_over_mean"] >= 1.0


# ------------------------------- the trainer's three steps and the reference


def _tiny_root(tmp, dtype):
    """A temporary copy of the benchmark with a small configuration and
    cell beside it, as NEW files (the harness finds them by name)."""
    root = os.path.join(str(tmp), "root")
    shutil.copytree(os.path.join(REPO, "perfbench"), os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "perfbench", "configs", "sdar-30b-a3b-ep8.json")) as f:
        real = json.load(f)
    layers = SIZES["num_hidden_layers"]
    cfg = dict(real, **SIZES)
    cfg["settings"] = dict(real["settings"], dtype=dtype, learning_rate=1e-3)
    cfg["param_map"] = {k: v for k, v in real["param_map"].items()
                        if not v.startswith("l") or int(v[1:v.index("_")]) < layers}
    cfg["routing_map"] = {k: l for k, l in real["routing_map"].items() if l < layers}
    with open(os.path.join(root, "perfbench", "configs", "tiny-bd.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(REPO, "perfbench", "traffic", "bd_4x4096.json")) as f:
        mix = json.load(f)
    mix.update(length=L, arrival={"kind": "batches", "batch": 4, "cycle": 6})
    with open(os.path.join(root, "perfbench", "traffic", "bd_tiny.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(REPO, "perfbench", "workloads", "sdar.train.json")) as f:
        wl = json.load(f)
    wl.update(config="tiny-bd", traffic="bd_tiny", limits=LIMITS[dtype])
    with open(os.path.join(root, "perfbench", "workloads", "tiny.bd.json"), "w") as f:
        json.dump(wl, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny-bd", "source": "test", "reduced": [], "why": "test",
                         "file": "perfbench/configs/tiny-bd.json"}]
    bench["workloads"] = [{"name": "tiny.bd", "config": "tiny-bd", "traffic": "bd_tiny",
                           "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.bd"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


# set between what the program reads at this size (float32: rounding, and in
# `change_gap` a router choice that flips on that rounding in steps two and
# three, 0.002 to 0.012; bfloat16: the program's bfloat16 activations) and what
# the fp8 control reads (an unmoved state reads 1)
LIMITS = {
    "float32": {"loss_gap": 1e-5, "grad_gap": 2e-3, "grad_diff": 2e-3, "change_gap": 5e-2,
                "routing_gap": 0.01},
    "bfloat16": {"loss_gap": 1.5e-3, "grad_gap": 0.15, "grad_diff": 0.06, "change_gap": 0.08,
                 "routing_gap": 0.2},
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_steps_match_the_reference_and_the_fp8_control_does_not(dtype, tmp_path):
    sys.path.insert(0, REPO)
    from perfbench import harness

    root = _tiny_root(tmp_path, dtype)
    out = io.StringIO()
    harness.run_cell(["--workload", "tiny.bd", "--seed", "2147483659", "--seconds", "0.2"],
                     root=root, require_chip=False, out=out)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert set(line["compared"]) == set(LIMITS[dtype])      # all four numbers and the choices

    cell = harness.load_cell(root, "tiny.bd")
    gen = cell.module("traffic", "block_diffusion")
    ref = cell.module("reference", "sdar_moe")
    cmp = cell.module("compare", cell.workload["compare"])
    items = gen.generate(cell.mix, cell.config, 2147483659)
    batches = [gen.arrays_of(items, g) for g in range(3)]
    base = cmp.reference_steps(ref, cell.config, 2147483659, batches)
    # the lean comparison is `train_steps`' own with fewer copies held
    plain = cell.module("compare", "train_steps").reference_steps(
        ref, cell.config, 2147483659, batches)
    assert plain["loss"] == base["loss"] and plain["change_norm"] == base["change_norm"]
    for k in plain["grad"]:
        np.testing.assert_array_equal(np.asarray(plain["grad"][k]), base["grad"][k])
    control = cmp.checks(cmp.reference_steps(ref, cell.config, 2147483659, batches, mode="fp8"),
                         base, cell.workload["limits"])
    assert not all(c.ok for c in control), control
    # routing as data: the reference given ITS OWN choices is the reference,
    # and choices that are no top-k read near 1 in `routing_gap`
    own = base["routing"][0]
    assert own.shape == (4, SIZES["num_hidden_layers"], 2 * L, SIZES["num_experts_per_tok"])
    p = ref.init_params(cell.config, 2147483659)
    batch = ref.to_batch(batches[0])
    loss, grad = ref.loss_and_grad(p, batch)
    loss_fed, grad_fed = ref.loss_and_grad(p, dict(batch, routing=jnp.asarray(own)))
    assert float(loss) == float(loss_fed)
    for k in grad:
        np.testing.assert_allclose(grad_fed[k], grad[k], rtol=1e-6, atol=1e-9, err_msg=k)
    assert cmp.routing_gap(own, own[:2]) == 0.0
    assert cmp.routing_gap((own + 1) % SIZES["num_experts_routed"], own) > 0.5
    # the program's count of pairs is the reference's: first check step, all layers
    ends = sorted((r for r in harness.Context(cell, 0, 0, 0, False).records()
                   if r.get("kind") == "pass_end"), key=lambda r: r["pass"])
    want = 0
    for row in np.asarray(batches[0]["tokens"]):
        xs = []
        ref.hidden(p, jnp.asarray(row), cell.config, moe_inputs=xs)
        want += ref.pairs_held(p, xs, cell.config)
    if dtype == "float32":
        assert ends[0]["counters"]["moe.pairs_held"] == want


# ------------------------------------------- the cell's traffic and its counts


def _bench_file(*parts):
    sys.path.insert(0, REPO)
    from perfbench.harness import load_json, load_module

    path = os.path.join(REPO, "perfbench", *parts)
    return load_json(path) if path.endswith(".json") else load_module(path)


def test_block_diffusion_traffic_is_seeded_noise_as_data():
    gen = _bench_file("traffic", "block_diffusion.py")
    mix = dict(_bench_file("traffic", "bd_4x4096.json"), length=64,
               arrival={"kind": "batches", "batch": 4, "cycle": 3})
    sizes = {"block_length": 4, "mask_id": 96, "vocab_size": 97}
    a, b = gen.generate(mix, sizes, 2 ** 31 + 11), gen.generate(mix, sizes, 2 ** 31 + 11)
    other = gen.generate(mix, sizes, 12)
    for k in a.arrays:
        np.testing.assert_array_equal(a.arrays[k], b.arrays[k])
    assert not np.array_equal(a.arrays["tokens"], other.arrays["tokens"])
    tokens, labels, weights = (a.arrays[k] for k in ("tokens", "labels", "weights"))
    assert tokens.shape == (12, 128) and labels.shape == weights.shape == (12, 64)
    np.testing.assert_array_equal(tokens[:, 64:], labels)          # the clean copy
    masked = tokens[:, :64] == 96
    assert labels.max() < 96 and 0.2 < masked.mean() < 0.8
    np.testing.assert_array_equal(np.where(masked, labels, tokens[:, :64]), labels)
    assert ((weights > 0) == masked).all() and weights[masked].min() >= 1.0
    # one level a block: the masked positions of a block share their weight
    blocks = weights.reshape(12, 16, 4)
    assert all(len(set(b[b > 0].tolist())) <= 1 for row in blocks for b in row)
    assert a.real_tokens("labels", 0) == 4 * 64 and a.shapes(0)["tokens"] == (128, 4)
    sample = gen.samples_of(a, 1)[2]
    assert sample["tokens"] == tokens[6].tolist() and sample["weights"] == weights[6].tolist()
    np.testing.assert_array_equal(gen.arrays_of(a, 1)["labels"], labels[4:8])


def test_closed_form_counts_at_the_cell_size():
    fl = _bench_file("flops", "sdar_moe.py")
    cfg = _bench_file("configs", "sdar-30b-a3b-ep8.json")
    assert fl.allowed_pairs(4096, 4) == 4096 * 4 + 4096 * 4096   # held to the rule in test_block_diffusion_moe
    lengths = {"labels": np.full(4, 4096)}
    step = fl.train_step_flops(cfg, lengths)
    assert 34e12 < step < 38e12                     # 3 x (4 layers x 2.65 + the head 1.27) TFLOP
    call = fl.train_kernel_calls(cfg, {"labels": (4096, 4)})[0]
    assert call["kind"] == "bd_attention" and abs(call["flops"] / (3 * 4 * 1.1e12) - 1) < 0.01
    pairs = 4 * 32768 * 8 * 16 / 128                # what an even router would hold, 4 layers
    mm = fl.grouped_mm_call(cfg, pairs)
    assert abs(mm["flops"] / (3 * 4 * 0.309e12) - 1) < 0.01
    # the configuration's file: published widths, the cut, every leaf mapped
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["moe_intermediate_size"], cfg["num_experts_routed"],
            cfg["num_experts_per_tok"], cfg["rope_theta"], cfg["rms_norm_eps"]) == (
        2048, 32, 4, 128, 768, 128, 8, 1000000, 1e-6)
    ref = _bench_file("reference", "sdar_moe.py")
    assert set(cfg["param_map"].values()) == set(ref.param_shapes(cfg))
    assert sum(int(np.prod(s)) for s in ref.param_shapes(cfg).values()) == 456346624
