#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls — the
`paddle` CLI — at the reference width of the seqToseq NMT model
(demo/seqToseq/seqToseq_net.py at its own defaults: word vectors, encoder
and decoder 512 wide, 30,000-word source and target dictionaries). Depth
is what the demo builds; batch and step counts are small; weights are
random from ``--seed``; data is a synthetic @provider drawing ids over
the full vocabulary from the same seed.

    python chip_smoke.py                one chip (what the driver runs)
    python chip_smoke.py --four-chips   data-parallel SGD over 4 chips
                                        against the same run on one
                                        device, and nothing else
    python chip_smoke.py --rehearse     toy widths, every phase, on
                                        whatever platform jax has (the
                                        CPU rehearsal; still fails, as
                                        the platform is not a TPU)

One process per chip: this parent never imports jax; each phase is a
sequential `bin/paddle` child that holds the chip and releases it on
exit. Phases (one chip): device → train (bf16 + pallas_rnn, two passes,
checkpoints) → check-checkpoint → train (library defaults) → the same
again (must hit the compile cache) → serve from the checkpoint → the
static generator on the same prompts (printed agreement).

Everything it learns goes to stdout as one JSON object per phase; the
LAST line is ``{"ok": ..., "device": {"platform", "kind", "count"}}``
with the device as jax reports it. Exit code 0 only when every phase
passed AND the platform is ``tpu``. It is a smoke test: it prints
seconds spent, never a rate.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
PADDLE = os.path.join(REPO, "bin", "paddle")
INTERPRET_ENV = "PADDLE_TPU_PALLAS_INTERPRET"

# the reference width (demo/seqToseq/seqToseq_net.py:14-19) and the toy
# the CPU rehearsal uses — 128 is the narrowest the GRU kernel's gate takes
# (loss_tol: how far the first batch's loss per token may sit from
# ln(vocab) — a batch of 8 short samples has a noisy mean length)
REAL = dict(width=512, vocab=30000, batch=256, t_max=32, slots=8, loss_tol=0.05)
TOY = dict(width=128, vocab=120, batch=8, t_max=8, slots=4, loss_tol=0.25)
BATCHES_PER_PASS = 4
N_REQUESTS = 12
PHASE_TIMEOUT_S = 900

PROVIDER = '''
import random

from paddle.trainer.PyDataProvider2 import *


def _lengths(rng, t_max):
    return rng.randint(t_max // 2, t_max)


def hook(settings, vocab, t_max, n_samples, **kwargs):
    settings.vocab, settings.t_max, settings.n = vocab, t_max, n_samples
    seq = integer_value_sequence(vocab)
    settings.input_types = {"source_language_word": seq,
                            "target_language_word": seq,
                            "target_language_next_word": seq}


def gen_hook(settings, vocab, t_max, n_samples, **kwargs):
    settings.vocab, settings.t_max, settings.n = vocab, t_max, n_samples
    settings.input_types = {
        "source_language_word": integer_value_sequence(vocab)}


@provider(init_hook=hook)
def process(settings, file_name):
    """file names are seeds; ids are uniform over the FULL vocabulary
    (0/1 are <s>/<e>), lengths over [t_max/2, t_max]. No target ends in
    <e>: one token 1,000 times likelier than any other is all eight
    steps would learn, and every served request would stop at once"""
    rng = random.Random(int(file_name))
    v, t = settings.vocab, settings.t_max
    for _ in range(settings.n):
        src = [rng.randrange(2, v) for _ in range(_lengths(rng, t))]
        trg = [rng.randrange(2, v) for _ in range(_lengths(rng, t))]
        yield {"source_language_word": src,
               "target_language_word": [0] + trg[:-1],
               "target_language_next_word": trg}


@provider(init_hook=gen_hook, should_shuffle=False)
def prompts(settings, file_name):
    """the serve phase's prompts, in order, for the static generator"""
    import json
    with open(file_name) as f:
        for line in f:
            yield {"source_language_word": json.loads(line)["prompt"]}
'''

TRAIN_CONF = '''
import sys
sys.path.insert(0, {demo!r})
from paddle.trainer_config_helpers import *
from seqToseq_net import gru_encoder_decoder

WIDTH = get_config_arg("width", int, 512)
VOCAB = get_config_arg("vocab", int, 30000)
# production=1 is what the bench legs call production: bf16 compute over
# f32 master weights + the fused Pallas GRU. production=0 passes nothing:
# library defaults, what a user's own `paddle train` gets
PRODUCTION = get_config_arg("production", int, 0)

define_py_data_sources2(
    train_list="train.list", test_list=None,
    module="smoke_provider", obj="process",
    args={{"vocab": VOCAB, "t_max": get_config_arg("t_max", int, 32),
          "n_samples": get_config_arg("n_samples", int, 1024)}})

settings(batch_size=get_config_arg("batch", int, 256), learning_rate=1e-3,
         learning_method=AdamOptimizer(),
         **(dict(dtype="bfloat16", pallas_rnn=True) if PRODUCTION else {{}}))

gru_encoder_decoder(source_dict_dim=VOCAB, target_dict_dim=VOCAB,
                    is_generating=False, word_vector_dim=WIDTH,
                    encoder_size=WIDTH, decoder_size=WIDTH)
'''

GEN_CONF = '''
import sys
sys.path.insert(0, {demo!r})
from paddle.trainer_config_helpers import *
from seqToseq_net import gru_encoder_decoder

WIDTH = get_config_arg("width", int, 512)
VOCAB = get_config_arg("vocab", int, 30000)
T_MAX = get_config_arg("t_max", int, 32)

define_py_data_sources2(
    train_list=None, test_list="prompts.list",
    module="smoke_provider", obj="prompts",
    args={{"vocab": VOCAB, "t_max": T_MAX, "n_samples": 0}})

settings(batch_size=get_config_arg("batch", int, 12), learning_rate=0.0)

gru_encoder_decoder(source_dict_dim=VOCAB, target_dict_dim=VOCAB,
                    is_generating=True, word_vector_dim=WIDTH,
                    encoder_size=WIDTH, decoder_size=WIDTH,
                    beam_size=1, max_length=T_MAX,
                    gen_result="gen_result.txt")
'''


class Failure(Exception):
    pass


# ----------------------------------------------------------------- helpers


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def read_records(run_dir):
    path = os.path.join(run_dir, "metrics.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def paddle(args, ws, env, stdin=None):
    """One `bin/paddle` child, run to its end. Returns (rc, stdout,
    stderr, wall seconds). A child past its limit is killed: nothing
    this script starts outlives it."""
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, PADDLE] + args, cwd=ws, env=env, text=True,
        stdin=subprocess.PIPE if stdin is not None else subprocess.DEVNULL,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    try:
        out, err = proc.communicate(stdin, timeout=PHASE_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, err = proc.communicate()
        err += f"\n[chip_smoke] killed after {PHASE_TIMEOUT_S}s"
    return proc.returncode, out, err, time.monotonic() - t0


def compile_split(records, wall_s):
    """Wall time of a phase split into compile (trace + XLA compile of
    every launch group, from the kind=compile records) and everything
    else (process start, data, steps, checkpoint I/O)."""
    compiles = [r for r in records if r["kind"] == "compile"]
    c = sum(r.get("trace_s", 0.0) + r.get("compile_s", 0.0) for r in compiles)
    return {"wall_s": round(wall_s, 1), "compile_s": round(c, 1),
            "run_s": round(wall_s - c, 1)}, compiles


def peak_hbm(records):
    peaks = [r["hbm_peak_bytes"] for r in records
             if r["kind"] == "memory" and "hbm_peak_bytes" in r]
    return max(peaks) if peaks else None


def tail(text, n=1500):
    return text[-n:]


class Smoke:
    def __init__(self, args):
        self.size = dict(TOY if args.rehearse else REAL)
        self.rehearse = args.rehearse
        self.seed = args.seed
        self.keep = args.keep
        self.ws = tempfile.mkdtemp(prefix="chip_smoke_")
        self.device = None
        self.failed = []
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = os.pathsep.join(
            [self.ws, REPO, os.path.join(REPO, "compat")])
        if self.rehearse:
            # the only way a Pallas kernel body runs off the TPU; on a TPU
            # backend the program ignores it (utils/device.pallas_mode)
            self.env[INTERPRET_ENV] = "1"
            # an explicit CPU run has no compile cache unless one is
            # named: name the place every other run uses by default
            self.env.setdefault("JAX_COMPILATION_CACHE_DIR",
                                os.path.join(REPO, ".jax_cache"))
        self.n_samples = self.size["batch"] * BATCHES_PER_PASS
        self.config_args = (
            "width={width},vocab={vocab},t_max={t_max},batch={batch}"
            .format(**self.size) + f",n_samples={self.n_samples}")

    # -------------------------------------------------------- workspace

    def write_workspace(self):
        demo = os.path.join(REPO, "demo", "seqToseq")
        files = {
            "smoke_provider.py": PROVIDER,
            "train_conf.py": TRAIN_CONF.format(demo=demo),
            "gen_conf.py": GEN_CONF.format(demo=demo),
            "train.list": f"{self.seed}\n",
            "prompts.list": "requests.jsonl\n",
        }
        for name, text in files.items():
            with open(os.path.join(self.ws, name), "w") as f:
                f.write(text)

    # ----------------------------------------------------------- phases

    def phase(self, name, fn):
        """Run one phase; print its JSON line; never raises."""
        t0 = time.monotonic()
        info = {"phase": name}
        try:
            info.update(fn() or {})
            info["ok"] = True
        except Failure as e:
            info.update(ok=False, error=str(e))
            self.failed.append(name)
        info.setdefault("wall_s", round(time.monotonic() - t0, 1))
        emit(info)
        return info["ok"]

    def device_phase(self):
        rc, out, err, wall = paddle(["version"], self.ws, self.env)
        if rc != 0:
            raise Failure(f"`paddle version` exit {rc}: {tail(err)}")
        m = re.search(r"^device: (\{.*\})$", out, re.M)
        if not m:
            raise Failure(f"no device line in `paddle version`: {tail(out)}")
        dev = json.loads(m.group(1))
        self.device = {k: dev[k] for k in ("platform", "kind", "count")}
        info = dict(dev, wall_s=round(wall, 1))
        if dev["platform"] == "tpu":
            # a kind the peaks table does not know is a failure HERE, not
            # a None carried into every utilization number downstream
            missing = [k for k in ("peak_tflops", "peak_gbps", "peak_hbm_gb")
                       if not dev.get(k)]
            if missing:
                raise Failure(f"ops/kernel_flops has no {missing} for "
                              f"device_kind {dev['kind']!r}")
        return info

    def train(self, name, production, passes, mesh=""):
        """One `paddle train` child; returns (phase info, per-batch
        losses). Checks every claim the phase makes about itself."""
        run = os.path.join(self.ws, name)
        args = ["train", "--config=train_conf.py",
                f"--config_args={self.config_args},production={production}",
                f"--num_passes={passes}", f"--save_dir={run}",
                "--log_period=1", "--dot_period=0", f"--seed={self.seed}"]
        if mesh:
            args.append(f"--mesh_shape={mesh}")
        rc, _out, err, wall = paddle(args, self.ws, self.env)
        recs = read_records(run)
        info, compiles = compile_split(recs, wall)
        if rc != 0:
            raise Failure(f"`paddle train` exit {rc}: {tail(err)}")
        losses = [r["CurrentCost"] for r in recs if r["kind"] == "train_window"]
        want = passes * BATCHES_PER_PASS
        if len(losses) != want:
            raise Failure(f"{len(losses)} batches logged, expected {want}")
        if not all(math.isfinite(x) for x in losses):
            raise Failure(f"non-finite loss: {losses}")
        # cost is summed over a sample's target tokens and averaged over
        # the batch: an untrained softmax over V words costs ln(V) a token
        mean_len = self.mean_target_len()
        per_token = losses[0] / mean_len
        info["first_loss_per_token"] = round(per_token, 4)
        info["ln_vocab"] = round(math.log(self.size["vocab"]), 4)
        info["losses"] = [round(x, 5) for x in losses]
        tol = self.size["loss_tol"]
        if abs(per_token / math.log(self.size["vocab"]) - 1.0) > tol:
            raise Failure(
                f"first-batch loss {per_token:.4f}/token is not within "
                f"{tol:.0%} of ln({self.size['vocab']}) = {info['ln_vocab']}")
        steps = [c for c in compiles if c["group"] == "train_step"]
        if len(steps) != 1:
            raise Failure(f"train_step compiled {len(steps)} times, expected "
                          "once (one batch shape)")
        step = steps[0]
        info["train_step"] = {
            k: step.get(k) for k in (
                "trace_s", "compile_s", "cache_hit", "mosaic_calls",
                "mosaic_shapes", "collectives", "devices", "sharded_inputs",
                "mode") if k in step}
        info["native_datapath"] = (
            "loaded" if "native datapath: loaded" in err else
            "NumPy fallback" if "native datapath: NumPy" in err else "unknown")
        info["peak_hbm_bytes"] = peak_hbm(recs)
        self.device = self.device or {
            "platform": step["platform"], "kind": step["device_kind"],
            "count": step["device_count"]}
        return info, losses, step

    def mean_target_len(self):
        """Mean target length of the training set, regenerated from the
        seed by the provider's own rule (pure python — no jax here)."""
        import random

        rng = random.Random(self.seed)
        t, v = self.size["t_max"], self.size["vocab"]
        total = 0
        for _ in range(self.n_samples):
            for _ in range(rng.randint(t // 2, t)):
                rng.randrange(2, v)
            n = rng.randint(t // 2, t)
            for _ in range(n):
                rng.randrange(2, v)
            total += n
        return total / self.n_samples

    def train_production(self):
        info, _losses, step = self.train("prod", production=1, passes=2)
        on_tpu = self.device["platform"] == "tpu"
        # no hidden fallback: every kernel selection upstream is silent,
        # so the compiled HLO is the only proof the Pallas GRU ran — two
        # encoder GRUs, forward and backward
        if on_tpu and step.get("mosaic_calls", 0) < 4:
            raise Failure(
                "pallas_rnn=True but the compiled train step holds "
                f"{step.get('mosaic_calls')} Mosaic custom calls (expected "
                ">= 4: two encoder GRUs, forward + backward)")
        self.params_changed(info)
        return info

    def params_changed(self, info):
        """pass-00000 and pass-00001 both exist and every parameter moved
        between them (numpy only: the parent stays off jax)."""
        import numpy as np

        trees = []
        for p in ("pass-00000", "pass-00001"):
            path = os.path.join(self.ws, "prod", p, "params.npz")
            if not os.path.exists(path):
                raise Failure(f"checkpoint {p} was not written ({path})")
            with np.load(path) as z:
                trees.append({k: z[k] for k in z.files})
        a, b = trees
        same = [k for k in a if np.array_equal(a[k], b[k])]
        info["parameters"] = len(a)
        info["parameter_elements"] = int(sum(v.size for v in a.values()))
        if same:
            raise Failure(f"parameters did not change between passes: {same}")
        bad = [k for k, v in b.items() if not np.isfinite(v).all()]
        if bad:
            raise Failure(f"non-finite parameters in pass-00001: {bad}")

    def check_checkpoint(self):
        rc, out, err, wall = paddle(
            ["check-checkpoint", os.path.join(self.ws, "prod")],
            self.ws, self.env)
        if rc != 0 or "pass-00000" not in out:
            raise Failure(f"`paddle check-checkpoint` exit {rc}: "
                          f"{tail(out)} {tail(err)}")
        return {"wall_s": round(wall, 1),
                "verdicts": [l.split()[0] for l in out.splitlines() if l]}

    def train_default(self, name, expect_cache_hit):
        info, _losses, step = self.train(name, production=0, passes=1)
        if step.get("mosaic_calls", 0):
            raise Failure("library defaults selected a Pallas kernel: "
                          f"{step.get('mosaic_shapes')}")
        if expect_cache_hit:
            recs = read_records(os.path.join(self.ws, name))
            misses = [c["group"] for c in recs
                      if c["kind"] == "compile" and c.get("cache_hit") is not True]
            if misses:
                raise Failure(
                    "a second process compiling what an earlier phase "
                    f"compiled missed the persistent cache: {misses}")
        return info

    def write_requests(self):
        import random

        rng = random.Random(self.seed + 1)
        t, v = self.size["t_max"], self.size["vocab"]
        # prompts of mixed length, 1 token to the full prompt window, and
        # mixed output budgets; more requests than slots, so slots turn over
        lens = [1, t, t // 2, 3, t - 1, t // 4] * 2
        self.requests = [
            {"id": f"r{i}", "prompt": [rng.randrange(2, v) for _ in range(n)],
             "max_new_tokens": rng.randint(2, t)}
            for i, n in enumerate(lens[:N_REQUESTS])]
        text = "".join(json.dumps(r) + "\n" for r in self.requests)
        with open(os.path.join(self.ws, "requests.jsonl"), "w") as f:
            f.write(text)          # the static generator reads them back
        return text

    def serve(self):
        stdin = self.write_requests()
        run = os.path.join(self.ws, "serve")
        ckpt = os.path.join(self.ws, "prod", "pass-00001")
        rc, out, err, wall = paddle(
            ["serve", "--config=gen_conf.py",
             f"--config_args={self.config_args}",
             f"--init_model_path={ckpt}", f"--metrics_path={run}",
             f"--serve_slots={self.size['slots']}",
             f"--serve_prompt_tokens={self.size['t_max']}",
             f"--seed={self.seed}"],
            self.ws, self.env, stdin=stdin)
        recs = read_records(run)
        info, compiles = compile_split(recs, wall)
        if rc != 0:
            raise Failure(f"`paddle serve` exit {rc} on stdin EOF: {tail(err)}")
        answers = {}
        for line in out.splitlines():
            if line.startswith("{"):
                a = json.loads(line)
                answers[a["id"]] = a
        self.answers = answers
        v = self.size["vocab"]
        for r in self.requests:
            a = answers.get(r["id"])
            if a is None:
                raise Failure(f"request {r['id']} was never answered")
            toks = a.get("tokens") or []
            if a["outcome"] != "ok" or not toks:
                raise Failure(f"request {r['id']}: {a}")
            if not all(isinstance(x, int) and 0 <= x < v for x in toks):
                raise Failure(f"request {r['id']}: token outside the "
                              f"vocabulary: {toks}")
            if len(toks) > r["max_new_tokens"]:
                raise Failure(f"request {r['id']}: {len(toks)} tokens over a "
                              f"budget of {r['max_new_tokens']}")
        groups = {}
        for c in compiles:
            groups.setdefault(c["group"], []).append(c["recompiles"])
        info["compiles"] = groups
        # each launch group compiled exactly once — at warm-up — and
        # never again while requests of mixed length flowed through
        for g in ("serve_prefill", "serve_decode"):
            if groups.get(g) != [0]:
                raise Failure(f"{g} compiled {groups.get(g)} (expected once, "
                              "recompiles=0 after warm-up)")
        info["answered"] = len(answers)
        info["tokens"] = sum(len(a["tokens"]) for a in answers.values())
        info["peak_hbm_bytes"] = peak_hbm(recs)
        return info

    def generator(self):
        """The static generator (`paddle gen`, the graph SequenceGenerator
        wraps) at beam_size=1 on the same prompts from the same
        checkpoint. GATED: it runs, answers every prompt, stays inside
        the vocabulary. PRINTED, not gated: token agreement with the
        engine — exact on the CPU in f32 (tests/test_engine.py pins it);
        on the chip near-uniform logits after eight steps can tie."""
        ckpt = os.path.join(self.ws, "prod", "pass-00001")
        run = os.path.join(self.ws, "gen")
        rc, _out, err, wall = paddle(
            ["gen", "--config=gen_conf.py",
             f"--config_args={self.config_args},batch={N_REQUESTS}",
             f"--init_model_path={ckpt}", f"--metrics_path={run}",
             f"--seed={self.seed}"],
            self.ws, self.env)
        info, _ = compile_split(read_records(run), wall)
        if rc != 0:
            raise Failure(f"`paddle gen` exit {rc}: {tail(err)}")
        with open(os.path.join(self.ws, "gen_result.txt")) as f:
            lines = f.read().splitlines()
        # per sample: an index line, then "score\ttok tok ..." per beam
        golden = [[int(t) for t in l.split("\t")[1].split()]
                  for l in lines if "\t" in l]
        if len(golden) != len(self.requests):
            raise Failure(f"{len(golden)} generator results for "
                          f"{len(self.requests)} prompts")
        v = self.size["vocab"]
        if not all(0 <= t < v for g in golden for t in g):
            raise Failure("generator token outside the vocabulary")
        exact, agree, total = 0, 0, 0
        for r, g in zip(self.requests, golden):
            toks = self.answers[r["id"]]["tokens"]
            ref = g[: len(toks)]          # the engine stops at its budget
            exact += toks == ref
            n = next((i for i, (x, y) in enumerate(zip(toks, ref)) if x != y),
                     min(len(toks), len(ref)))
            agree += n
            total += len(toks)
        info["requests_token_for_token"] = f"{exact}/{len(golden)}"
        info["agreeing_prefix_tokens"] = f"{agree}/{total}"
        return info

    # --------------------------------------------------------- four chips

    def four_chips(self):
        """Data-parallel SGD over four chips against the same seed and
        global batch on one device — and nothing else."""
        mesh = {}

        def run_mesh():
            info, losses, step = self.train(
                "mesh4", production=1, passes=1, mesh="data=4")
            mesh.update(losses=losses, step=step)
            B = self.size["batch"]
            if step.get("devices") != 4:
                raise Failure(f"the step's inputs live on {step.get('devices')} "
                              "distinct devices, expected 4")
            if step.get("sharded_inputs", 0) < 3:
                raise Failure("the batch is not split over the devices: "
                              f"{step.get('sharded_inputs')} sharded inputs")
            if not step.get("collectives", {}).get("all-reduce"):
                raise Failure("no gradient all-reduce in the partitioned HLO: "
                              f"{step.get('collectives')}")
            if self.device["platform"] == "tpu":
                # the kernel's PER-DEVICE shape shows its shard_map: it
                # works on a quarter of the batch, on each of four chips
                shapes = " ".join(step.get("mosaic_shapes", []))
                if (step.get("mosaic_calls", 0) < 4
                        or f",{B // 4}," not in shapes or f",{B}," in shapes):
                    raise Failure(
                        "the Pallas GRU is not running per shard: "
                        f"{step.get('mosaic_calls')} kernels, shapes {shapes}")
            return info

        def run_one():
            info, losses, _step = self.train("one", production=1, passes=1)
            # same seed, same global batch: the four-way run only sums the
            # per-shard losses and (f32-accumulated) gradients in another
            # order. Measured: <= 2.5e-7 on the v5e, <= 2.5e-5 on the CPU
            # rehearsal; 1e-3 of the loss per step is the bound
            tol = 1e-3
            rel = [abs(a - b) / abs(b) for a, b in zip(mesh["losses"], losses)]
            info["loss_rel_diff_per_step"] = [float(f"{r:.3g}") for r in rel]
            info["tolerance"] = tol
            if max(rel) > tol:
                raise Failure(f"data=4 diverges from one device: per-step "
                              f"relative loss difference {rel} > {tol}")
            return info

        if self.phase("train data=4", run_mesh):
            self.phase("train one device (reference)", run_one)
        else:
            self.failed.append("train one device (reference): not run")

    # --------------------------------------------------------------- main

    def one_chip(self):
        if not self.phase("device", self.device_phase):
            return
        if self.device["platform"] != "tpu" and not self.rehearse:
            # no accelerator: stop here — never carry on on the CPU
            self.failed.append("platform")
            return
        if not self.phase("train bf16+pallas_rnn", self.train_production):
            return
        self.phase("check-checkpoint", self.check_checkpoint)
        if self.phase("train library defaults",
                      lambda: self.train_default("default", False)):
            self.phase("train library defaults again (compile cache)",
                       lambda: self.train_default("default2", True))
        if self.phase("serve", self.serve):
            self.phase("static generator", self.generator)

    def finish(self):
        if not self.keep:
            shutil.rmtree(self.ws, ignore_errors=True)
        dev = self.device or {"platform": None, "kind": None, "count": 0}
        ok = not self.failed and dev["platform"] == "tpu"
        if self.failed:
            print("failed: " + "; ".join(self.failed), flush=True)
        elif not ok:
            print(f"every phase ran, but the platform is {dev['platform']!r}, "
                  "not 'tpu'", flush=True)
        emit({"ok": ok, "device": dev})
        return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only: --mesh_shape=data=4 against one device")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths on whatever platform jax has")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--keep", action="store_true",
                    help="keep the scratch workspace")
    args = ap.parse_args(argv)
    if os.environ.get(INTERPRET_ENV):
        print(f"refusing to run with {INTERPRET_ENV} set: a smoke of "
              "interpreted kernels proves nothing about the chip",
              file=sys.stderr)
        return 2
    if not os.path.exists(PADDLE):
        print(f"no {PADDLE}: chip_smoke.py runs from the root of a checkout",
              file=sys.stderr)
        return 2
    smoke = Smoke(args)
    try:
        smoke.write_workspace()
        if args.four_chips:
            smoke.four_chips()
        else:
            smoke.one_chip()
    finally:
        rc = smoke.finish()
    return rc


if __name__ == "__main__":
    sys.exit(main())
