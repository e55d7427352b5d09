"""Offered-load driver for `paddle serve`'s two engines.

`python bench.py serve [--engine=static|continuous]` drives a seeded
open-loop arrival process at a ladder of offered loads over the seqToseq
generator and writes what the serving stack records about it: per-request
`kind=request` records, per-rung `kind=serve_window` rollups and one
`kind=bench` line (doc/observability.md "Serving telemetry",
doc/serving.md). The serving tests reach the engines' acceptance
behaviour through `bench_serve`, and it is the only caller of the static
engine.

It is a load generator, not a speed instrument. On the CPU it shrinks to
toy shapes and renames its metric `serve_cpu_smoke_*`: counts of
requests, launches, recompiles and tokens, never a device number. No cell
of BENCHMARK.json measures serving yet; training speed is measured by
`python3 -m perfbench.run --workload <cell>` (perfbench/README.md) and
recorded in PERF_LEDGER.jsonl and PERF.md.

Every result line is stamped with the device it ran on (`platform`,
`device_kind`, `device_count`, as jax reports them). Nothing here looks
for another backend when the one it was given fails, and a leg that
raises ends the process with a traceback and a non-zero exit code.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

BENCH_DTYPE = os.environ.get("PADDLE_TPU_BENCH_DTYPE", "bfloat16")


def _serve_sweep_static(gm, params, registry, *, group, rates, B, T,
                        n_requests, seed, timeout_s, queue_cap, beam_size,
                        prompt_fn, budget_fn, make_seq):
    """The PR-8 static engine: run-to-completion micro-batch cohorts
    over the jitted full-generation launch, virtual-clock driver.
    Returns (sweep doc, measured capacity req/s)."""
    import jax
    import numpy as np

    from paddle_tpu.observability import serving

    def fwd(params, batch):
        outputs, _ = gm.forward(params, batch, pass_type="gen", rng=None)
        best = outputs[group]
        return best.ids, best.seq_lengths

    fwd = jax.jit(fwd)
    sig_key = (B, T)  # ONE signature: every cohort pads to it

    serving_now = [False]  # warmup/calibration launches stay out of the
    # roofline totals: they serve no requests, and the rung windows'
    # launches/exec_s must reconcile with the serve_gen roofline row

    def launch_fn(requests):
        # pad-to-signature: a fixed [B, T] int32 batch regardless of
        # cohort size or prompt lengths — empty slots replay a 1-token
        # dummy prompt whose output is discarded. The signature never
        # changes, so CompileRegistry reuse keeps recompiles at 0.
        ids = np.full((B, T), 2, dtype=np.int32)
        lengths = np.ones((B,), dtype=np.int32)
        for i, r in enumerate(requests):
            p = np.asarray(r.prompt, dtype=np.int32)[:T]
            ids[i, : len(p)] = p
            lengths[i] = max(len(p), 1)
        batch = {"source_language_word": make_seq(None, lengths, ids=ids)}
        t0 = time.perf_counter()
        _out_ids, out_lens = registry.call(
            serving.SERVE_GROUP, sig_key, fwd, params, batch
        )
        lens_np = np.asarray(out_lens)  # device sync via readback
        dt = time.perf_counter() - t0
        if serving_now[0]:
            registry.note_exec(serving.SERVE_GROUP, sig_key, dt)
        # delivered tokens cap at the request's output budget (mixed-
        # length workloads) — run-to-completion still PAID max_length
        # decode steps for the whole cohort, which is the A/B's point
        return [
            int(lens_np[i]) if r.max_new is None
            else min(int(lens_np[i]), r.max_new)
            for i, r in enumerate(requests)
        ], dt

    # warmup: the ONE compile (kind=compile record, recompiles=0), then
    # a clean measured launch to calibrate capacity for the rate ladder
    prng = np.random.RandomState(seed)
    warm = [serving.Request(rid=f"warm-{i}", t_enqueue=0.0,
                            prompt=prompt_fn(prng, i))
            for i in range(B)]
    launch_fn(warm)
    # the warmup launch paid the compile but isn't roofline-counted:
    # discard the pending compile-cost deduction so it can't zero the
    # first RUNG launch's exec time instead
    registry.drop_pending(serving.SERVE_GROUP, sig_key)
    # median of 3: one descheduled calibration launch would otherwise
    # halve the whole auto-rate ladder (A/B runs pin rates anyway)
    service_s = sorted(launch_fn(warm)[1] for _ in range(3))[1]
    capacity_rps = B / max(service_s, 1e-6)
    serving_now[0] = True
    if not rates:
        rates = [round(f * capacity_rps, 4) for f in (0.25, 0.5, 1.0, 2.0)]

    doc = serving.run_sweep(
        launch_fn, rates, n_requests=n_requests, seed=seed, max_batch=B,
        timeout_s=timeout_s, queue_cap=queue_cap, beam_size=beam_size,
        prompt_fn=prompt_fn, budget_fn=budget_fn, engine="static",
    )
    return doc, capacity_rps


def _serve_sweep_continuous(gm, params, registry, *, rates, B, T,
                            max_length, n_requests, seed, timeout_s,
                            queue_cap, decode_block, prompt_fn, budget_fn,
                            pipeline=True, fused_step=False,
                            shed_policy="off", replicas=(1,),
                            transport="pipe", spec_tokens="0",
                            slot_dtype="f32"):
    """The continuous-batching engine (paddle_tpu/serving/) on the SAME
    seeded workload, driven open-loop in wall-clock time. ``pipeline``
    selects the overlapped dispatch/collect loop vs the serial PR-12
    loop (PADDLE_TPU_BENCH_SERVE_PIPELINE — the overlap A/B's subject).
    ``replicas`` is the fleet-size LADDER
    (PADDLE_TPU_BENCH_SERVE_REPLICAS): each size N > 1 runs the whole
    rate sweep through ``drive_fleet_rung`` — N engines behind the
    router's own least-loaded scoring — so the scaling curve (goodput
    vs replicas, router overhead share) is measured, not assumed.

    ``transport`` (PADDLE_TPU_BENCH_SERVE_TRANSPORT=pipe|tcp) selects
    the submit path: ``pipe`` is the direct in-process call; ``tcp``
    fronts every engine with an :class:`EngineSocketServer` on a
    loopback ephemeral port and drives it through a framed
    :class:`SocketEngineClient`, so JSON serialization + the socket
    round trip land in the measured ``router_share`` — the
    pipe-vs-tcp A/B `paddle compare` judges. tcp routes EVERY rung
    (n == 1 included) through the fleet driver: the single-engine
    drive_rung path has no client seam.

    ``spec_tokens`` (PADDLE_TPU_BENCH_SERVE_SPEC, "0" = off) is the
    speculative draft-length ladder and ``slot_dtype``
    (PADDLE_TPU_BENCH_SERVE_SLOT_DTYPE) the slot-state storage dtype —
    doc/serving.md "Speculative decode" / "Reduced-precision slot
    state". With speculation on, the calibration pass's emitted
    sequences seed every engine's draft table before rung 0 (the
    calibration launches are already excluded from rung telemetry via
    ``backend.serving``), so the first measured rung isn't penalized
    by draft-table cold start — the same discipline that keeps warmup
    compiles out of the measurement. Returns (sweep doc, measured
    capacity req/s of ONE replica)."""
    import numpy as np

    from paddle_tpu.observability import serving
    from paddle_tpu.serving import Engine, drive_rung
    from paddle_tpu.serving.fleet import drive_fleet_rung
    from paddle_tpu.serving.jax_backend import JaxDecodeBackend

    replicas = tuple(replicas) or (1,)
    n_max = max(replicas)
    backend = JaxDecodeBackend(
        gm, params, slots=B, prompt_tokens=T, max_length=max_length,
        decode_block=decode_block, registry=registry, pipeline=pipeline,
        fused_step=fused_step, spec_tokens=spec_tokens,
        slot_dtype=slot_dtype,
    )
    backend.warmup()  # compiles land now; Engine.start()'s call re-runs
    # two cheap no-slot launches (idempotent semantically)
    # capacity calibration without request records OR roofline exec
    # (the static leg's serving_now rule): drive the backend directly —
    # B full-length sequences back to back, like the static leg's
    # full-batch launch
    backend.serving = False
    prng = np.random.RandomState(seed)
    warm = [serving.Request(rid=f"warm-{i}", t_enqueue=0.0,
                            prompt=prompt_fn(prng, i))
            for i in range(B)]
    t0 = time.perf_counter()
    backend.admit(list(range(B)), warm, [max_length] * B)
    # calibration emits real greedy tokens — keep them: with
    # speculation on they seed the draft tables below, so rung 0 sees
    # a warm table (the launches themselves stay out of rung telemetry
    # via backend.serving)
    cal_seqs = [[] for _ in range(B)]
    done = False
    while not done:
        out = backend.step()
        toks = np.asarray(out.tokens)
        lives = np.asarray(out.live)
        for u in range(toks.shape[0]):
            for b in range(B):
                if lives[u, b]:
                    cal_seqs[b].append(int(toks[u, b]))
        done = bool(out.finished.all())
    capacity_rps = B / max(time.perf_counter() - t0, 1e-6)
    backend.serving = True
    if not rates:
        rates = [round(f * capacity_rps, 4) for f in (0.25, 0.5, 1.0, 2.0)]

    # replica 0 owns the shared CompileRegistry; the extra fleet
    # backends compile identical signatures and would only double-count
    # the compile/roofline telemetry
    backends = [backend] + [
        JaxDecodeBackend(
            gm, params, slots=B, prompt_tokens=T, max_length=max_length,
            decode_block=decode_block, registry=None, pipeline=pipeline,
            fused_step=fused_step, spec_tokens=spec_tokens,
            slot_dtype=slot_dtype,
        )
        for _ in range(1, n_max)
    ]
    engines = [
        Engine(b, queue_cap=queue_cap, request_timeout_s=timeout_s,
               pipeline=pipeline, shed_policy=shed_policy,
               replica=(f"replica-{i}" if n_max > 1 else "")).start()
        for i, b in enumerate(backends)
    ]
    draft_seeded = 0
    if backend.spec_blocks:
        for e in engines:
            draft_seeded = e.seed_draft(cal_seqs)
    servers, clients = [], []
    if transport == "tcp":
        # the real wire, loopback: every engine behind a framed socket
        # server, driven by a framed client — serialization + syscall
        # cost lands inside the router_s stopwatch
        from paddle_tpu.serving.transport import (EngineSocketServer,
                                                  SocketEngineClient)

        for e in engines:
            srv = EngineSocketServer(e, "127.0.0.1:0")
            srv.start()
            servers.append(srv)
        for srv in servers:
            c = SocketEngineClient(srv.address)
            c.start()
            clients.append(c)
    try:
        windows = []
        rung = 0
        for n in replicas:
            for rate in rates:
                reqs = serving.schedule_requests(
                    float(rate), n_requests, seed + rung, rung=rung,
                    prompt_fn=prompt_fn, budget_fn=budget_fn,
                )
                if n_max <= 1 and transport != "tcp":
                    # no fleet anywhere in the ladder: the PR-13 single-
                    # engine path, byte-identical records
                    w = drive_rung(engines[0], reqs, rate_rps=float(rate),
                                   rung=rung)
                else:
                    # n == 1 rungs also go through the fleet driver so
                    # the baseline carries replicas=1 (and pays the
                    # same routing overhead) — the scaling curve's x=1
                    # point must be measured under the same discipline
                    w = drive_fleet_rung(
                        engines[:n], reqs, rate_rps=float(rate), rung=rung,
                        clients=clients[:n] if clients else None)
                windows.append(w)
                rung += 1
    finally:
        for c in clients:
            c.close()
        for srv in servers:
            srv.close()
        for e in engines:
            e.drain(timeout=600.0)
    # the knee belongs to ONE ladder: with a fleet-size sweep, report
    # the LARGEST fleet's (its capacity is the headline the sweep asks
    # about); mixed-size windows would fake an early knee
    knee_windows = [w for w in windows
                    if int(w.get("replicas") or 1) == n_max]
    return ({"rungs": windows,
             "knee_rps": serving.saturation_knee(knee_windows),
             # per-slot device state bytes (weights excluded) — the
             # honest bf16-vs-f32 footprint stamp `paddle compare`
             # judges as slot_bytes
             "slot_bytes": backend.slot_state_bytes(),
             "draft_seeded": draft_seeded},
            capacity_rps)


def bench_serve(B=None, T=None, vocab=None, dim=None, beam_size=None,
                max_length=None, n_requests=None, rates=None, seed=None,
                run_dir=None, timeout_s=None, queue_cap=None, dtype=None,
                engine=None, mixed_len=None, decode_block=None,
                pipeline=None, fused_step=None, replicas=None):
    """Offered-load serving leg (doc/observability.md "Serving
    telemetry"): a deterministic seeded open-loop arrival process at a
    sweep of offered loads drives one of TWO engines over the seqToseq
    generator (``--engine`` / PADDLE_TPU_BENCH_SERVE_ENGINE):

    - ``static`` (default, the PR-8 path): a dynamic micro-batch
      aggregator over the jitted full beam-search generation launch —
      run-to-completion cohorts of up to B, padded to ONE signature so
      the ``serve_gen`` launch group never recompiles after warmup.
    - ``continuous``: the slot-based continuous-batching engine
      (paddle_tpu/serving/, doc/serving.md) on the SAME seeded arrival
      schedule, prompts and budgets — ``serve_prefill``/``serve_decode``
      launch groups, one signature each, driven in wall-clock time.

    Emits per-request ``kind=request`` records and per-rung
    ``kind=serve_window`` rollups (``engine`` stamped on both) into
    ``run_dir`` (PADDLE_TPU_BENCH_SERVE_DIR), the run dir `paddle
    serve-report` renders. Headline: best goodput (generated tok/s)
    across rungs; extras carry per-rung p50/p99 latency and TTFT vs
    offered load plus the saturation knee. With
    PADDLE_TPU_BENCH_SERVE_MIXED_LEN=1 every request draws a seeded
    heavy-tailed output budget (most short, a tail at max_length) — the
    mixed-length workload where run-to-completion batching pays
    max_length for every cohort and iteration-level scheduling shows
    its goodput win; `paddle compare` of a static vs a continuous run
    on pinned PADDLE_TPU_BENCH_SERVE_RATES is the A/B.

    Without PADDLE_TPU_BENCH_SERVE_RATES (comma-separated req/s), the
    rungs are calibrated from a measured full-batch, full-length
    serving pass: 0.25x / 0.5x / 1x / 2x the back-to-back capacity, so
    the sweep brackets the knee on any backend."""
    import jax
    import numpy as np

    from paddle_tpu.flagship import nmt_gen_config
    from paddle_tpu.graph import GradientMachine, make_seq
    from paddle_tpu.graph.machine import compute_dtype_of
    from paddle_tpu.observability import metrics as obsm
    from paddle_tpu.observability import serving
    from paddle_tpu.observability.compile_log import CompileRegistry

    on_cpu = jax.default_backend() == "cpu"
    env = os.environ.get
    engine = engine or env("PADDLE_TPU_BENCH_SERVE_ENGINE", "static")
    if engine not in ("static", "continuous"):
        raise ValueError(f"unknown serve engine {engine!r}: expected "
                         "'static' or 'continuous'")
    B = int(env("PADDLE_TPU_BENCH_SERVE_B", 0)) or B or (4 if on_cpu else 64)
    T = T or (8 if on_cpu else 32)
    vocab = vocab or (200 if on_cpu else 30000)
    dim = dim or (32 if on_cpu else 512)
    beam_size = beam_size or (2 if on_cpu else 3)
    max_length = max_length or (8 if on_cpu else 32)
    n_requests = (int(env("PADDLE_TPU_BENCH_SERVE_REQUESTS", 0))
                  or n_requests or (32 if on_cpu else 256))
    seed = int(env("PADDLE_TPU_BENCH_SERVE_SEED", "0")) if seed is None else seed
    if mixed_len is None:
        mixed_len = env("PADDLE_TPU_BENCH_SERVE_MIXED_LEN", "0") == "1"
    if decode_block is None:
        # the decode-block LADDER (an int or "1,2,4,8"): one compiled
        # serve_decode signature covers every rung, the engine's
        # adaptive policy picks per iteration (doc/serving.md)
        decode_block = (env("PADDLE_TPU_BENCH_SERVE_BLOCK", "")
                        or ("1,2,4,8" if on_cpu else "1,2,4"))
    if pipeline is None:
        pip_env = env("PADDLE_TPU_BENCH_SERVE_PIPELINE", "")
        if pip_env:
            pipeline = pip_env != "off"
        else:
            # overlap needs somewhere to overlap INTO: on a TPU the
            # device runs beside the host; on a CPU backend "device"
            # work shares the host's cores, so a 1-core box can only
            # lose to speculation+context-switching (doc/serving.md
            # "Pipelined decode"). Count the cores this process may
            # actually USE — a cgroup/affinity-limited container on a
            # big host is still 1-core
            try:
                cores = len(os.sched_getaffinity(0))
            except (AttributeError, OSError):
                cores = os.cpu_count() or 1
            pipeline = (not on_cpu) or cores > 1
    if fused_step is None:
        fused_step = env("PADDLE_TPU_BENCH_SERVE_FUSED", "0") == "1"
    # overload defense for the shed-on-vs-off A/B
    # (PADDLE_TPU_BENCH_SERVE_SHED=off|deadline|brownout, continuous
    # engine only — the static driver has no admission estimator)
    shed_policy = env("PADDLE_TPU_BENCH_SERVE_SHED", "off")
    # 0 is a LEGAL deadline (drop everything not admitted immediately)
    # — None, not falsiness, is the unset sentinel
    if timeout_s is None:
        t_env = env("PADDLE_TPU_BENCH_SERVE_TIMEOUT")
        timeout_s = float(t_env) if t_env is not None else 60.0
    queue_cap = (int(env("PADDLE_TPU_BENCH_SERVE_QUEUE_CAP", 0))
                 if queue_cap is None else queue_cap)
    run_dir = run_dir or env("PADDLE_TPU_BENCH_SERVE_DIR",
                             os.path.join(REPO, "output", "bench_serve"))
    obsm.configure(run_dir)

    tc = nmt_gen_config(vocab=vocab, dim=dim, beam_size=beam_size,
                        max_length=max_length, dtype=dtype or BENCH_DTYPE,
                        batch_size=B)
    gm = GradientMachine(tc.model_config,
                         compute_dtype=compute_dtype_of(tc.opt_config))
    params = gm.init_params(seed=1)
    group = next(s.name for s in tc.model_config.sub_models
                 if s.generator is not None)
    registry = CompileRegistry(device_kind=jax.devices()[0].device_kind)

    def prompt_fn(rng, i):
        return rng.randint(2, vocab, size=int(rng.randint(1, T + 1))).tolist()

    budget_fn = None
    if mixed_len:
        # heavy-tailed output budgets (real serving is mostly-short with
        # a long tail): ~90% draw 1..max(L/8, 1) tokens, ~10% the full
        # max_length — run-to-completion pays max_length for EVERY
        # cohort regardless, which is exactly the A/B's subject
        short = max(max_length // 8, 1)

        def budget_fn(rng, i):
            if rng.rand() < 0.1:
                return max_length
            return 1 + int(rng.randint(0, short))

    rates_env = env("PADDLE_TPU_BENCH_SERVE_RATES", "")
    if rates_env:
        rates = [float(r) for r in rates_env.split(",") if r.strip()]
    # the fleet-size ladder (--replicas=N or "1,2,4"): each size runs
    # the whole rate sweep through the in-process fleet driver
    # (serving/fleet.drive_fleet_rung), continuous engine only — the
    # static driver has no router seam to measure
    if replicas is None:
        rep_env = env("PADDLE_TPU_BENCH_SERVE_REPLICAS", "")
        replicas = ([int(r) for r in rep_env.split(",") if r.strip()]
                    if rep_env else [1])
    elif isinstance(replicas, int):
        replicas = [replicas]
    replicas = [max(int(n), 1) for n in replicas] or [1]
    if max(replicas) > 1 and engine != "continuous":
        raise ValueError(
            "PADDLE_TPU_BENCH_SERVE_REPLICAS needs "
            "PADDLE_TPU_BENCH_SERVE_ENGINE=continuous (the static "
            "driver has no fleet)")
    # the submit path A/B (doc/serving.md "Cross-host fleet"): pipe is
    # the in-process call, tcp fronts every engine with a loopback
    # framed-socket server so the wire cost is measured
    transport = env("PADDLE_TPU_BENCH_SERVE_TRANSPORT", "pipe")
    if transport not in ("pipe", "tcp"):
        raise ValueError(f"unknown serve transport {transport!r}: "
                         "expected 'pipe' or 'tcp'")
    if transport == "tcp" and engine != "continuous":
        raise ValueError(
            "PADDLE_TPU_BENCH_SERVE_TRANSPORT=tcp needs "
            "PADDLE_TPU_BENCH_SERVE_ENGINE=continuous (the static "
            "driver has no socket seam)")
    # speculative decode + slot-state precision (doc/serving.md): the
    # spec-on-vs-off and bf16-vs-f32 A/Bs, continuous engine only
    from paddle_tpu.serving.backend import (parse_slot_dtype,
                                            parse_spec_tokens)

    spec_tokens = env("PADDLE_TPU_BENCH_SERVE_SPEC", "0")
    slot_dtype = parse_slot_dtype(
        env("PADDLE_TPU_BENCH_SERVE_SLOT_DTYPE", "f32"))
    if parse_spec_tokens(spec_tokens) and engine != "continuous":
        raise ValueError(
            "PADDLE_TPU_BENCH_SERVE_SPEC needs "
            "PADDLE_TPU_BENCH_SERVE_ENGINE=continuous (the static "
            "driver has no draft seam)")
    if slot_dtype != "f32" and engine != "continuous":
        raise ValueError(
            "PADDLE_TPU_BENCH_SERVE_SLOT_DTYPE needs "
            "PADDLE_TPU_BENCH_SERVE_ENGINE=continuous (slot state is "
            "the continuous engine's)")

    if engine == "continuous":
        doc, capacity_rps = _serve_sweep_continuous(
            gm, params, registry, rates=rates, B=B, T=T,
            max_length=max_length, n_requests=n_requests, seed=seed,
            timeout_s=timeout_s, queue_cap=queue_cap,
            decode_block=decode_block, prompt_fn=prompt_fn,
            budget_fn=budget_fn, pipeline=bool(pipeline),
            fused_step=bool(fused_step), shed_policy=shed_policy,
            replicas=tuple(replicas), transport=transport,
            spec_tokens=spec_tokens, slot_dtype=slot_dtype,
        )
        beam_size = 1  # the engine decodes greedily (doc/serving.md)
    else:
        doc, capacity_rps = _serve_sweep_static(
            gm, params, registry, group=group, rates=rates, B=B, T=T,
            n_requests=n_requests, seed=seed, timeout_s=timeout_s,
            queue_cap=queue_cap, beam_size=beam_size, prompt_fn=prompt_fn,
            budget_fn=budget_fn, make_seq=make_seq,
        )
    registry.emit_roofline()
    # run_end must be the serve stream's LAST record (after the
    # kind=bench headline — doc/observability.md). When the bench-record
    # mirror will land in THIS stream (PADDLE_TPU_BENCH_METRICS_DIR
    # unset → main() defaults it to run_dir, or explicitly equal), the
    # caller emits run_end after the mirror through the same reused
    # writer; when the mirror goes elsewhere, close the stream here
    # while this leg's writer is still installed (re-opening later
    # would append a second run_start with a re-anchored `t`)
    mdir = env("PADDLE_TPU_BENCH_METRICS_DIR", "")
    if mdir and os.path.abspath(mdir) != os.path.abspath(run_dir):
        obsm.emit("run_end", status="completed")
    obsm.flush()

    rungs = [
        {
            "offered_rps": w.get("offered_rps"),
            "arrived": w.get("arrived"),
            "completed": w.get("completed"),
            "rejected": w.get("rejected"),
            "timeouts": w.get("timeouts"),
            "shed": w.get("shed", 0),
            "errors": w.get("errors", 0),
            # overload-defense rates ride the archived artifact so
            # `paddle compare` can judge shed/error growth without the
            # telemetry run dir (zero-filled there for older artifacts)
            "shed_rate": (round((w.get("shed", 0) or 0)
                                / float(w["arrived"]), 6)
                          if w.get("arrived") else 0.0),
            "error_rate": (round((w.get("errors", 0) or 0)
                                 / float(w["arrived"]), 6)
                           if w.get("arrived") else 0.0),
            "p50_ms": round((w.get("latency") or {}).get("p50", 0.0) * 1e3, 3),
            "p99_ms": round((w.get("latency") or {}).get("p99", 0.0) * 1e3, 3),
            "ttft_p50_ms": round((w.get("ttft") or {}).get("p50", 0.0) * 1e3, 3),
            "ttft_p99_ms": round((w.get("ttft") or {}).get("p99", 0.0) * 1e3, 3),
            "queue_wait_share": w.get("queue_wait_share"),
            "occupancy_mean": round((w.get("occupancy") or {}).get("mean", 0.0), 3),
            "goodput_tok_s": w.get("goodput_tok_s"),
            "engine": w.get("engine", engine),
            # pipeline mode rides every rung record (continuous engine
            # only): `paddle compare` joins on (engine, pipeline,
            # offered load), so a pipelined-vs-blocking A/B compares
            # mode-to-mode instead of landing in only_a/only_b
            **({"pipeline": w["pipeline"]}
               if isinstance(w.get("pipeline"), str) else {}),
            # fleet rungs: the size joins the compare key ((engine,
            # pipeline, replicas, offered load)) and the measured
            # router overhead share rides the artifact
            **({"replicas": int(w["replicas"])}
               if isinstance(w.get("replicas"), int) else {}),
            **({"router_share": w["router_share"]}
               if isinstance(w.get("router_share"), (int, float)) else {}),
            # pipe|tcp — compare joins pipe-vs-tcp rungs on offered
            # load and judges router_share across the wire
            **({"transport": w["transport"]}
               if isinstance(w.get("transport"), str) else {}),
            # speculation config + per-rung draft acceptance: spec
            # ("4"/"2,4"/"off") and slot_dtype join the compare key;
            # accept_rate rides so an archived artifact carries the
            # spec A/B's explanatory variable (zero when no verify
            # launch ran — compare zero-fills old artifacts the same)
            **({"spec": w["spec"]}
               if isinstance(w.get("spec"), str) else {}),
            **({"slot_dtype": w["slot_dtype"]}
               if isinstance(w.get("slot_dtype"), str) else {}),
            **({"accept_rate": w["accept_rate"]}
               if isinstance(w.get("accept_rate"), (int, float)) else {}),
        }
        for w in doc["rungs"]
    ]
    best = max((w.get("goodput_tok_s", 0.0) for w in doc["rungs"]), default=0.0)
    extras = dict(
        batch=B, beam_size=beam_size, max_length=max_length,
        dtype=tc.opt_config.dtype, n_requests=n_requests, engine=engine,
        mixed_len=bool(mixed_len), capacity_rps=round(capacity_rps, 3),
        knee_rps=doc.get("knee_rps"), rungs=rungs, run_dir=run_dir,
        tokens=("greedy generated" if engine == "continuous"
                else "best-beam generated"),
    )
    if engine == "continuous":
        # the headline stamps the pipeline mode + ladder so an archived
        # line says WHAT was measured (and compare joins on it)
        extras["pipeline"] = "on" if pipeline else "off"
        extras["decode_blocks"] = str(decode_block)
        extras["transport"] = transport
        # speculation + slot-dtype headline stamps: spec=K|off and the
        # storage dtype say WHAT was measured; slot_bytes is the
        # memory_analysis-honest per-slot footprint compare judges
        spec_ladder = parse_spec_tokens(spec_tokens)
        extras["spec"] = (",".join(str(k) for k in spec_ladder)
                          if spec_ladder else "off")
        extras["slot_dtype"] = slot_dtype
        if isinstance(doc.get("slot_bytes"), int):
            extras["slot_bytes"] = doc["slot_bytes"]
        if doc.get("draft_seeded"):
            extras["draft_seeded"] = doc["draft_seeded"]
        if max(replicas) > 1:
            extras["replicas"] = ",".join(str(n) for n in replicas)
        if fused_step:
            extras["fused_step"] = True
        if shed_policy != "off":
            extras["shed_policy"] = shed_policy
    # memory trajectory for the serve leg too: the sweep's live HBM
    # peak (absent on stat-less backends) and the serve_gen group's
    # static plan from its one compile
    from paddle_tpu.observability.memory import device_memory_stats

    stats = device_memory_stats()
    if stats and stats.get("peak_bytes_in_use"):
        extras["peak_hbm_bytes"] = stats["peak_bytes_in_use"]
    static_rows = registry.static_memory_rows()
    if static_rows:
        extras["static_mem_bytes"] = static_rows[0]["mem_total_bytes"]
    return best, extras


def _emit(metric, value, unit, **extra):
    from paddle_tpu.utils.device import device_stamp

    line = {
        "metric": metric,
        "value": round(float(value), 1),
        "unit": unit,
        # the device every number of this run came from, as jax reports it
        **device_stamp(),
    }
    line.update({k: v for k, v in extra.items() if v is not None})
    print(json.dumps(line))
    _emit_metrics_record(line)


def _emit_metrics_record(line):
    """Mirror each result line into a run-telemetry stream
    (PADDLE_TPU_BENCH_METRICS_DIR): the printed line and live run
    telemetry then share ONE schema — `paddle metrics --tail` and
    any jsonl tooling read bench sessions unchanged
    (doc/observability.md, kind="bench")."""
    path = os.environ.get("PADDLE_TPU_BENCH_METRICS_DIR", "")
    if not path:
        return
    try:
        from paddle_tpu.observability import metrics as obs

        obs.configure(path)
        obs.emit("bench", **line)
        obs.flush()
    except Exception as e:  # telemetry must never fail the bench
        print(f"# bench metrics record failed: {e}", file=sys.stderr)


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else ""
    if which != "serve":
        print(
            f"bench.py takes one leg, 'serve' (got {which!r}). Training "
            "speed: python3 -m perfbench.run --workload <cell> "
            "(perfbench/README.md)",
            file=sys.stderr,
        )
        return 2

    # persistent compilation cache, at the one place every entry point
    # uses (compile_log.resolve_cache_dir)
    from paddle_tpu.observability.compile_log import enable_compile_cache

    enable_compile_cache()

    import jax

    # the platform jax was given decides the shapes: real ones on an
    # accelerator, bench_serve's toy defaults (and a renamed metric, f32:
    # bf16 on XLA CPU is emulated) on the CPU backend.
    # `bench.py serve --engine={static,continuous}` picks the engine
    # (PADDLE_TPU_BENCH_SERVE_ENGINE also works) — run one of each on
    # pinned PADDLE_TPU_BENCH_SERVE_RATES and `paddle compare` the two
    # artifacts for the A/B (doc/serving.md)
    on_tpu = jax.default_backend() != "cpu"
    eng = None
    for a in sys.argv[2:]:
        if a.startswith("--engine="):
            eng = a.split("=", 1)[1]
    value, extras = bench_serve(dtype=None if on_tpu else "float32",
                                engine=eng)
    metric = ("serve_goodput_tokens_per_sec" if on_tpu
              else "serve_cpu_smoke_goodput_tokens_per_sec")
    # one schema, one stream: unless the caller already points the
    # bench-record mirror somewhere, land the kind=bench headline in
    # the serve run dir next to its request/serve_window records
    os.environ.setdefault("PADDLE_TPU_BENCH_METRICS_DIR", extras["run_dir"])
    _emit(metric, value, "tokens/s", **extras)
    sys.stdout.flush()
    # the mirror above landed in the serve stream (same resolved
    # writer — no reconfigure, no second run_start): NOW close it,
    # run_end last, so `paddle metrics --follow` shows the headline
    # before it stops. The other-dir case already closed in
    # bench_serve.
    mdir = os.environ.get("PADDLE_TPU_BENCH_METRICS_DIR", "")
    if mdir and os.path.abspath(mdir) == os.path.abspath(extras["run_dir"]):
        from paddle_tpu.observability import metrics as obsm

        obsm.emit("run_end", status="completed")
        obsm.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
