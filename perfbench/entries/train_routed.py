"""Entry `train_routed`: `entries/train.py` for a configuration with
discrete routing. The same set-up, steps, window and facts (the code below
is `train.py`'s, whose helpers it imports); one thing more: after each of
the three compared steps it reads the experts the program chose
(`Trainer.forward_output`, the layers the configuration's `routing_map`
names, which its DSL file declares as outputs) and hands them to the
comparison as `program["routing"]`, so that the plain reference is computed
under the same choices (`compare/train_steps_lean.py` says why). What
`train.py` would need changed for it: `run`'s loop over `CHECK_STEPS` (one
read a step) and the `program` dict (one key).

The window drives `paddle_tpu.trainer.Trainer.train()`,
built as `paddle train` builds it (`cli._setup` -> `parse_config` ->
`Trainer`), fed by perfbench's own @provider.

Set-up builds ONE trainer, gives it weights made here from the seed (the
same the plain reference gets), and drives it through its first steps with
the window's own call and feed, one pass a step so that the state can be
read between them; then warms up and hands that same trainer to the window.
`correct` compares those first three steps with the plain reference once
the window has closed (see `perfbench/README.md`).
"""

from __future__ import annotations

import gc
import os
import sys
import time

import numpy as np

from perfbench.harness import Check, Run, load_module, memory_peak

_train = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)), "train.py"))
CHECK_STEPS = _train.CHECK_STEPS
_flag_args, _input_types, install_weights = (
    _train._flag_args, _train._input_types, _train.install_weights)
_pass_end, _norms = _train._pass_end, _train._norms


def _choices(trainer, routing_map):
    """The last step's choices as the reference's batch has them: int32
    [sequences, layers, positions, experts a token]. `routing_map`: the
    program's output layer -> the reference's layer number."""
    import jax

    kept = trainer.forward_output
    missing = set(routing_map) - set(kept)
    if missing:
        raise SystemExit(f"the step kept no {sorted(missing)}: the "
                         "configuration's DSL file declares them as outputs")
    names = sorted(routing_map, key=routing_map.get)
    got = jax.device_get([kept[n].value for n in names])
    return np.stack([np.asarray(g, np.int32) for g in got], axis=1)


def run(ctx):
    import jax

    cell, sizes, wl = ctx.cell, ctx.cell.config, ctx.cell.workload
    ctx.fresh_out_dir()
    from paddle_tpu import cli

    flags, config = cli._setup(_flag_args(ctx, cell))
    from paddle_tpu.observability import metrics as obs
    from paddle_tpu.trainer import Trainer

    from perfbench import provider

    gen = cell.module("traffic", cell.mix["generator"])
    items = gen.generate(cell.mix, sizes, ctx.seed)
    token_field = cell.mix["token_field"]
    feed = provider.Feed(
        batches=[gen.samples_of(items, g) for g in range(items.groups)],
        input_types=_input_types(cell.mix, sizes),
        tokens_per_batch=[items.real_tokens(token_field, g)
                          for g in range(items.groups)])
    provider.FEEDS[cell.name] = feed

    # no checkpoint inside the window: the benchmark's passes are not a
    # user's passes, and `Trainer.train()` saves at the end of each call
    config.save_dir = ""
    trainer = Trainer(config, flags)
    ref = cell.module("reference", sizes["reference"])
    inv_map = sizes["param_map"]
    install_weights(trainer, ref.init_params(sizes, ctx.seed), inv_map)
    p0 = {inv_map[k]: np.asarray(v) for k, v in
          jax.device_get(trainer.params).items()}

    # --- the first steps, through the window's own call and feed
    losses, m1, pass_id, routing = [], None, 0, []

    def one_pass(batch_ids):
        """One `train()` call over these batches; returns its seconds."""
        nonlocal pass_id
        feed.next_pass(batch_ids)
        trainer.start_pass = pass_id
        t = time.monotonic()
        with jax.profiler.TraceAnnotation("perfbench.train_call"):
            trainer.train(num_passes=pass_id + 1)
        pass_id += 1
        return time.monotonic() - t

    for step in range(CHECK_STEPS):
        per_step = one_pass([step])
        losses.append(float(_pass_end(ctx, pass_id - 1)["AvgCost"]))
        routing.append(_choices(trainer, sizes["routing_map"]))
        if step == 0:
            m1 = {inv_map[k]: np.asarray(v) for k, v in jax.device_get(
                {k: s["m"] for k, s in trainer.opt_state.slots.items()
                 if "m" in s}).items()}
    p3 = {inv_map[k]: np.asarray(v) for k, v in
          jax.device_get(trainer.params).items()}
    program = {
        "loss": losses,
        "grad": {k: v / (1.0 - sizes["settings"]["adam_beta1"])
                 for k, v in m1.items()},
        "change_norm": _norms({k: p3[k].reshape(p0[k].shape) - p0[k]
                               for k in p0}),
        "routing": routing,
    }
    del p3, p0

    # --- how many batches fill `--seconds`: fixed before the window opens,
    # from the last check step's time or, where a step is short, from a
    # warm-up pass of about a second (the trainer's feeder runs several
    # batches ahead of the step, so a deadline in the provider would let the
    # window overrun by that many steps)
    next_batch = CHECK_STEPS
    if per_step < 0.5:
        n = int(np.ceil(1.0 / max(per_step, 1e-3)))
        per_step = one_pass(range(next_batch, next_batch + n)) / n
        next_batch += n
    obs.flush()
    compiles_before = [r for r in ctx.records() if r.get("kind") == "compile"]

    # --- the window: a traced run traces a shorter one, of a few steps at
    # the least, so that the trace stays small and still shows step gaps
    n_window = max(1, int(round(ctx.seconds / per_step)))
    trace_dir = None
    if ctx.trace:
        n_window = max(int(wl.get("trace_min_steps", 4)),
                       int(round(float(wl.get("trace_seconds", 3.0)) / per_step)))
        trace_dir = os.path.join(ctx.out_dir, "trace")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # perfbench's own spans only
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.monotonic() - ctx.t_start
    with jax.profiler.TraceAnnotation("perfbench.window"):
        t0 = time.monotonic()
        one_pass(range(next_batch, next_batch + n_window))
        jax.block_until_ready(trainer.params)
        t1 = time.monotonic()
    if ctx.trace:
        jax.profiler.stop_trace()
    window_s = t1 - t0
    steps = len(feed.served)
    tokens = feed.tokens_served()
    obs.flush()
    records = ctx.records()
    end = _pass_end(ctx, pass_id - 1)
    compiles = [r for r in records if r.get("kind") == "compile"]
    in_window = compiles[len(compiles_before):]
    step_compiles = [r for r in compiles if r.get("group") == "train_step"]
    mosaic = max((int(r.get("mosaic_calls") or 0) for r in step_compiles),
                 default=0)
    faults = []
    if in_window:
        faults.append(f"{len(in_window)} compile(s) inside the window")
    if ctx.require_chip and sizes["settings"].get("pallas_rnn") and mosaic < 1:
        faults.append("pallas_rnn asked for, no Mosaic call in the timed step")
    if int(end["samples"]) != steps * items.group:
        faults.append(f"trainer counted {end['samples']} samples, fed "
                      f"{steps * items.group}")
    if not np.isfinite(float(end["AvgCost"])):
        faults.append("non-finite loss in the window")
    for f in faults:
        print(f"perfbench: FAILED window: {f}", flush=True, file=sys.stderr)
    peak = memory_peak(ctx.devices)

    # --- free the program's state, then the plain reference
    trainer.params = trainer.opt_state = None
    provider.FEEDS.pop(cell.name, None)
    del trainer
    gc.collect()
    check_batches = [gen.arrays_of(items, g) for g in range(CHECK_STEPS)]
    compare = cell.module("compare", wl["compare"])
    checks = compare.compare(ref, sizes, ctx.seed, check_batches, program,
                             wl["limits"])

    fl = cell.module("flops", sizes["reference"])
    step_flops = [fl.train_step_flops(sizes, items.lengths(g))
                  for g in range(items.groups)]
    facts = {
        "window_s": window_s, "steps": steps, "tokens": tokens,
        "mosaic_calls": mosaic, "faults": faults,
        "flops": sum(step_flops[b % items.groups] for b in feed.served),
        "kernel_calls": fl.train_kernel_calls(sizes, items.shapes(0)),
        "step_program": wl.get("step_program", "train_step"),
    }
    return Run(
        attempted=steps, failed=steps if faults else 0,
        values={"train_tokens_per_s": tokens / window_s, "setup_s": setup_s},
        facts=facts, checks=checks if not faults else
        checks + [Check("window_faults", float(len(faults)), 0.0)],
        memory_peak_bytes=peak, trace_dir=trace_dir)
