"""`train_steps`' comparison for a configuration whose parameters are a
large part of the chip: the same numbers (`gaps`, `checks` and their limits
are `train_steps.py`'s own, imported), the same three reference steps with
the same Adam, holding fewer copies of the parameters on the device.

`train_steps.reference_steps` keeps p0, p, m, v, the first gradient, the
last gradient and, in its undonated Adam, three new trees: nine copies at
the peak. At 456 M float32 parameters that is 16.4 GB of a 16.9 GB chip (my
chip run, PR 28: 12.78 GB live before the second step's gradient, whose
program then could not reserve its 6.2 GB). Here the Adam step donates p,
m and v, the first gradient goes to the host once it is taken, the gradient
is dropped once Adam has used it, and the parameters' change is taken
against weights made again from the seed (`init_params` is a function of
the seed): three copies and a gradient at the peak.

**Routing as data.** Where the program reports the experts it chose in each
of the three steps (`program["routing"]`, entry `train_routed`), the
reference's steps are computed under those choices (`reference/sdar_moe.py`
says how). Why: top-8 of 128 is discrete, the bfloat16 program's router
inputs differ from the float32 reference's in the third digit, so a few
percent of the tokens choose another eighth expert, and the mix's loss
weight 1/t (t down to 0.001) lets ONE token carry a third of a batch's
squared weight. Compared each under its own choices, two sound computations
then read `grad_gap` 0.02 on one seed and 0.89 on the next (my chip runs, PR
28; PERF.md section 2 has the witness). Under the same choices both sides
compute the same smooth function, and the four numbers mean what they mean
for a dense model. The choices themselves are held to the reference's own by
`routing_gap`: the share of (sequence, layer, position) whose chosen SET
differs from what the reference chooses by itself in the first step; a few
percent for rounding, near 1 for a wrong top-k.
"""

from __future__ import annotations

import json
import os
import sys

from perfbench.harness import Check, load_module

_base = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "train_steps.py"))


def routing_gap(a, b):
    """Share of (sequence, layer, position) whose chosen set differs; the
    sequences both sides have (a planted fault leaves half out)."""
    import numpy as np

    n = min(len(a), len(b))
    a, b = np.sort(np.asarray(a[:n]), axis=-1), np.sort(np.asarray(b[:n]), axis=-1)
    return float(np.mean(np.any(a != b, axis=-1)))


def gaps(program, reference):
    """`train_steps.gaps`, and `routing_gap` where both sides report their
    first step's choices."""
    numbers, notes = _base.gaps(program, reference)
    if program.get("routing") is not None and reference.get("routing") is not None:
        numbers["routing_gap"] = routing_gap(program["routing"][0], reference["routing"][0])
    return numbers, notes


def checks(program, reference, limits):
    """One `Check` a limit, as `train_steps.checks`."""
    numbers, notes = gaps(program, reference)
    per_leaf = {k: [v if v is None else float(f"{v:.3g}") for v in row]
                for k, row in notes["per_leaf"].items()}
    print(f"perfbench: per leaf [grad_gap, grad_diff, change_gap, the "
          f"reference's gradient norm over the median leaf's]: "
          f"{json.dumps(per_leaf)}; worst: {notes['worst_leaf']}; "
          f"left out of change_gap: {notes['left_out']}", file=sys.stderr)
    return [Check(name, float(numbers[name]), float(limit))
            for name, limit in limits.items()]


def reference_steps(ref, sizes, seed, batches, mode="highest", fault=None, routing=None):
    """Three steps of the reference, as `train_steps.reference_steps`
    (the same optimizer arithmetic, the same planted faults). `routing`: a
    step's choices to compute it under, one array a step; by default each
    step chooses for itself. The result's `routing` holds what this side
    chooses by itself in the first step."""
    import functools

    import jax
    import jax.numpy as jnp

    s = sizes["settings"]
    b1, b2, eps = s["adam_beta1"], s["adam_beta2"], s["adam_epsilon"]
    lr, clip = s["learning_rate"], s.get("gradient_clipping_threshold", 0.0)
    decay = s.get("l2_decay", 0.0)
    lr_scale = s.get("param_learning_rate", {})

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def adam(p, g, m, v, t):
        out_p, out_m, out_v = {}, {}, {}
        for k in p:
            gk = jnp.clip(g[k], -clip, clip) if clip else g[k]
            if decay and k not in s.get("no_decay", ()):
                gk = gk + decay * p[k]
            out_m[k] = b1 * m[k] + (1 - b1) * gk
            out_v[k] = b2 * v[k] + (1 - b2) * gk * gk
            mhat = out_m[k] / (1 - b1 ** t)
            vhat = out_v[k] / (1 - b2 ** t)
            out_p[k] = p[k] - lr * lr_scale.get(k, 1.0) * mhat / (jnp.sqrt(vhat) + eps)
        return out_p, out_m, out_v

    p = ref.init_params(sizes, seed)
    m = {k: jnp.zeros_like(x) for k, x in p.items()}
    v = {k: jnp.zeros_like(x) for k, x in p.items()}
    losses, grad, own = [], None, None
    for t, arrays in enumerate(batches, start=1):
        batch = ref.to_batch(arrays)
        if routing is not None:
            batch["routing"] = jnp.asarray(routing[t - 1])
        if fault == "half_batch":
            keep = len(next(iter(batch.values()))) // 2
            batch = {k: x[:keep] for k, x in batch.items()}
        if t == 1 and hasattr(ref, "own_routing"):
            own = [jax.device_get(ref.own_routing(p, batch, mode))]
        loss, g = ref.loss_and_grad(p, batch, mode)
        losses.append(float(loss))
        if fault == "state_unchanged":
            del g
            continue
        p, m, v = adam(p, g, m, v, jnp.float32(t))
        del g
        if t == 1:
            # to the host: it is read once more, leaf by leaf, at the end
            grad = {k: x / (1 - b1) for k, x in jax.device_get(m).items()}
    if grad is None:
        grad = {k: jax.device_get(jnp.zeros_like(x)) for k, x in p.items()}
    p0 = ref.init_params(sizes, seed)
    change = _base._leaf_norms({k: p[k] - p0[k] for k in p})
    return {"loss": losses, "grad": grad, "change_norm": change, "routing": own}


def compare(ref, sizes, seed, batches, program, limits):
    routing = program.get("routing")
    reference = reference_steps(ref, sizes, seed, batches, routing=routing)
    if routing is not None and reference["routing"] is not None:
        _say_flips(routing[0], reference["routing"][0], batches[0]["weights"])
    return checks(program, reference, limits)


def _say_flips(a, b, weights):
    """On standard error: by layer, the share of positions whose chosen set
    differs, and how much of the batch's squared loss weight sits on
    positions that differ in some layer."""
    import numpy as np

    differs = np.any(np.sort(np.asarray(a), -1) != np.sort(np.asarray(b), -1), axis=-1)
    w2 = np.square(np.asarray(weights, np.float64))          # [sequences, L]
    hit = differs[:, :, :w2.shape[1]].any(axis=1)            # the noised half carries the weight
    print(f"perfbench: positions whose chosen experts differ from the reference's own, "
          f"by layer: {[float(f'{x:.3g}') for x in differs.mean(axis=(0, 2))]}; they carry "
          f"{float(np.sum(w2 * hit) / np.sum(w2)):.3g} of the first batch's squared loss "
          f"weight, its heaviest position {float(w2.max() / np.sum(w2)):.3g}", file=sys.stderr)
