"""The second witness for a cell whose model routes discretely, read on the
chip at the cell's own size:
`python3 -m perfbench.tests.routing_witness <cell> <seed> [<seed> ...]`
(the benchmark's own runs never run this).

The question it answers: when the program's first gradient is far from the
plain reference's on some seed, is the program at fault, or do two sound
computations of different precision simply choose different experts for a
token that carries much of the batch's loss weight? The witness is the plain
reference itself with every linear layer's inputs rounded to the precision
the configuration states (`mode="bfloat16"`): a second, independent writing
of what the program computes, with none of its code. For each seed's first
batch it reads the witness's first gradient against the reference's twice:

- `own`: each side under the experts it chose by itself;
- `fed`: the reference under the WITNESS's choices (routing as data, as
  `compare/train_steps_lean.py` feeds the program's).

Where `own` reads high and `fed` low on the same seed, the choices are what
differed, not the arithmetic. One JSON line a seed, also appended to
`chiprun_out/<cell>.witness.jsonl`.
"""

import json
import os
import statistics
import sys

from perfbench import harness


def _numbers(ours, theirs, base):
    """(`grad_gap` and its leaf, `grad_diff`) as `train_steps.gaps` has them."""
    g = base._grad_norms(ours, theirs)
    med = statistics.median(nr for _, nr, _ in g.values())
    gap = {k: abs(a - nr) / max(nr, med) for k, (a, nr, _) in g.items()}
    worst = max(gap, key=gap.get)
    diff = statistics.median(nd / max(nr, med) for _, nr, nd in g.values())
    return {"grad_gap": gap[worst], "worst_leaf": worst, "grad_diff": diff}


def main(argv):
    import jax
    import jax.numpy as jnp
    import numpy as np

    cell = harness.load_cell(os.getcwd(), argv[0])
    from paddle_tpu.observability.compile_log import enable_compile_cache
    enable_compile_cache("")
    gen = cell.module("traffic", cell.mix["generator"])
    ref = cell.module("reference", cell.config["reference"])
    compare = cell.module("compare", cell.workload["compare"])
    base = cell.module("compare", "train_steps")
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/{cell.name}.witness.jsonl", "a") as out:
        for seed in (int(s) for s in argv[1:]):
            items = gen.generate(cell.mix, cell.config, seed)
            arrays = gen.arrays_of(items, 0)
            batch = ref.to_batch(arrays)
            p = ref.init_params(cell.config, seed)
            host = lambda tree: jax.device_get(tree)
            own_ref = host(ref.own_routing(p, batch))
            own_wit = host(ref.own_routing(p, batch, "bfloat16"))
            g_ref = host(ref.loss_and_grad(p, batch)[1])
            g_wit = host(ref.loss_and_grad(p, batch, "bfloat16")[1])
            g_fed = host(ref.loss_and_grad(p, dict(batch, routing=jnp.asarray(own_wit)))[1])
            differs = np.any(np.sort(own_wit, -1) != np.sort(own_ref, -1), axis=-1)
            w2 = np.square(np.asarray(arrays["weights"], np.float64))
            hit = differs[:, :, :w2.shape[1]].any(axis=1)
            line = {"cell": cell.name, "seed": seed,
                    "own": _numbers(g_wit, g_ref, base), "fed": _numbers(g_wit, g_fed, base),
                    "routing_gap": compare.routing_gap(own_wit, own_ref),
                    "differ_by_layer": [float(x) for x in differs.mean(axis=(0, 2))],
                    "squared_weight_on_differing": float(np.sum(w2 * hit) / np.sum(w2)),
                    "squared_weight_of_heaviest": float(w2.max() / np.sum(w2)),
                    "heaviest_weight": float(np.max(arrays["weights"]))}
            print(json.dumps(line), flush=True)
            print(json.dumps(line), file=out, flush=True)
            del g_ref, g_wit, g_fed, p
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
