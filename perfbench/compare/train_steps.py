"""The comparison that decides `correct` for a train cell: the trainer's
first three steps against the plain reference's.

Numbers compared, each with a limit of its own in the cell's file:

- `loss_gap`: the widest |program - reference| / reference over the three
  steps' losses;
- `grad_gap`: the first gradient as the optimizer got it (the program's is
  Adam's first moment after one step over 1 - beta1; both after the
  element-wise clip), by the worst leaf: the gap between the two NORMS of a
  leaf over the reference's norm of that leaf or of the median leaf,
  whichever is larger;
- `grad_diff`: the same gradients, by the MEDIAN leaf: the norm of the
  DIFFERENCE of the two over the same measure. A gap of norms cannot see
  rounding that has no bias (a lower precision reads no higher than the
  program there); the difference does, and this is the number the control
  fails. The median, because the leaves behind the recurrences amplify any
  rounding to the same few percent in the program and in the control, so
  the worst leaf parts nothing (PERF.md section 2);
- `change_gap`: the gap of norms of the parameters' change over the three
  steps, leaving out leaves whose reference gradient is under a thousandth
  of the median leaf's (they move under Adam by round-off alone).

`reference_steps(..., mode=...)` is also the control: the reference in a
lower precision, put in the program's place.
"""

from __future__ import annotations

import json
import statistics
import sys

from perfbench.harness import Check


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    return {k: float(v) for k, v in jax.device_get(
        jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v)))
                           for k, v in t.items()})(tree)).items()}


def reference_steps(ref, sizes, seed, batches, mode="highest", fault=None):
    """Three steps of the reference: its own weights from the seed, its loss
    and gradient, the configuration's optimizer in plain float32.
    `fault` plants one of the faults the tests read (see tests)."""
    import jax
    import jax.numpy as jnp

    s = sizes["settings"]
    b1, b2, eps = s["adam_beta1"], s["adam_beta2"], s["adam_epsilon"]
    lr, clip = s["learning_rate"], s.get("gradient_clipping_threshold", 0.0)
    decay = s.get("l2_decay", 0.0)
    lr_scale = s.get("param_learning_rate", {})

    @jax.jit
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

    p0 = ref.init_params(sizes, seed)
    p = p0
    m = {k: jnp.zeros_like(x) for k, x in p.items()}
    v = {k: jnp.zeros_like(x) for k, x in p.items()}
    losses, grad = [], None
    for t, arrays in enumerate(batches, start=1):
        batch = ref.to_batch(arrays)
        if fault == "half_batch":
            # half of the batch left out and the mean taken over the rest
            keep = len(next(iter(batch.values()))) // 2
            batch = {k: x[:keep] for k, x in batch.items()}
        loss, g = ref.loss_and_grad(p, batch, mode)
        losses.append(float(loss))
        if fault == "state_unchanged":
            continue
        p, m, v = adam(p, g, m, v, jnp.float32(t))
        if t == 1:
            grad = {k: x / (1 - b1) for k, x in m.items()}
    if grad is None:
        grad = {k: jnp.zeros_like(x) for k, x in p.items()}
    change = _leaf_norms({k: p[k] - p0[k] for k in p})
    return {"loss": losses, "grad": grad, "change_norm": change}


def _grad_norms(program, reference):
    """Leaf -> (program's norm, reference's norm, norm of the difference)."""
    import jax
    import jax.numpy as jnp

    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    one = jax.jit(lambda a, b: (norm(a), norm(b), norm(a - b)))
    return {k: tuple(float(x) for x in one(
        jnp.asarray(program[k]).reshape(r.shape), r))
        for k, r in reference.items()}


def gaps(program, reference):
    """The numbers compared, from the two sides' readings, and which leaf
    gave each."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(program["loss"], reference["loss"]))
    g = _grad_norms(program["grad"], reference["grad"])
    med_g = statistics.median(nr for _, nr, _ in g.values())
    grad_gap = {k: abs(np_ - nr) / max(nr, med_g) for k, (np_, nr, _) in g.items()}
    grad_diff = {k: nd / max(nr, med_g) for k, (_, nr, nd) in g.items()}
    rc = reference["change_norm"]
    moved = [k for k in rc if g[k][1] >= 1e-3 * med_g]
    med_c = statistics.median(rc[k] for k in moved)
    change_gap = {k: abs(program["change_norm"][k] - rc[k]) / max(rc[k], med_c)
                  for k in moved}
    worst = {"grad_gap": max(grad_gap, key=grad_gap.get),
             "change_gap": max(change_gap, key=change_gap.get)}
    return ({"loss_gap": loss_gap,
             "grad_gap": grad_gap[worst["grad_gap"]],
             "grad_diff": statistics.median(grad_diff.values()),
             "change_gap": change_gap[worst["change_gap"]]},
            {"worst_leaf": worst, "left_out": sorted(set(rc) - set(moved)),
             "per_leaf": {k: [grad_gap[k], grad_diff[k], change_gap.get(k),
                              g[k][1] / med_g] for k in g}})


def checks(program, reference, limits):
    """One `Check` a limit; `correct` is all of them within their limits."""
    numbers, notes = gaps(program, reference)
    per_leaf = {k: [v if v is None else float(f"{v:.3g}") for v in row]
                for k, row in notes["per_leaf"].items()}
    print(f"perfbench: per leaf [grad_gap, grad_diff, change_gap, the "
          f"reference's gradient norm over the median leaf's]: "
          f"{json.dumps(per_leaf)}; worst: {notes['worst_leaf']}; "
          f"left out of change_gap: {notes['left_out']}", file=sys.stderr)
    return [Check(name, float(numbers[name]), float(limit))
            for name, limit in limits.items()]


def compare(ref, sizes, seed, batches, program, limits):
    return checks(program, reference_steps(ref, sizes, seed, batches), limits)
