"""Benchmark harness — one process, on the platform jax was given.

Headline metric: ResNet-50 bf16 training throughput (imgs/sec/chip), the
north-star workload from BASELINE.md. The default run ("all") also times
the two sequence flagships — the stacked-LSTM classifier and the seqToseq
NMT attention encoder-decoder (demo/seqToseq, reference
demo/seqToseq/seqToseq_net.py:65-181) — and reports them in the same JSON
line under "legs", plus an MFU figure (see benchmarks/mfu.py: analytic
model matmul FLOPs from a jaxpr walk of the step / wall-clock / chip
peak).
`python bench.py resnet|lstm|nmt` runs a single leg. vs_baseline is
measured against benchmarks/targets.json when present (the reference
publishes no numbers — BASELINE.md; targets are clearly-labeled estimates,
and the JSON carries `baseline_kind` so an estimate can never masquerade
as a measured reference ratio).

On TPU all legs train in bf16 mixed precision (f32 master weights) —
the production configuration; `PADDLE_TPU_BENCH_DTYPE=float32` forces
full precision for A/B runs. Set PADDLE_TPU_BENCH_TRACE_DIR to capture an
xplane trace of the headline timed window.

Every result line is stamped with the device it ran on (`platform`,
`device_kind`, `device_count`, as jax reports them). Nothing here looks
for another backend when the one it was given fails, and a leg that
raises ends the process with a traceback and a non-zero exit code: a
number that is not there is better than one from somewhere else. On a
CPU backend the legs shrink to smoke shapes and their metrics are
renamed `*_cpu_smoke_*` — a check that the legs run, never a device
number.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

BENCH_DTYPE = os.environ.get("PADDLE_TPU_BENCH_DTYPE", "bfloat16")
TRACE_DIR = os.environ.get("PADDLE_TPU_BENCH_TRACE_DIR", "")
# which leg's trace window to trace when TRACE_DIR is set: the resnet
# headline always traces; "lstm"/"nmt" trace that leg instead (one trace
# per run keeps the xplane dirs unambiguous)
TRACE_LEG = os.environ.get("PADDLE_TPU_BENCH_TRACE_LEG", "")
# fuse k optimizer steps into one device launch (lax.fori_loop over the
# jitted step) — amortizes per-launch dispatch latency on the small
# recurrent legs. Throughput semantics are unchanged: the same batch is
# consumed per step either way, and the JSON reports the knob.
STEPS_PER_LAUNCH = int(os.environ.get("PADDLE_TPU_BENCH_STEPS_PER_LAUNCH", "1"))
_SPL_ENV_SET = "PADDLE_TPU_BENCH_STEPS_PER_LAUNCH" in os.environ


def _leg_spl(default: int = 1) -> int:
    """Per-leg fused-launch factor: an explicit env value wins (A/B
    control); otherwise the leg's measured-best default applies."""
    return STEPS_PER_LAUNCH if _SPL_ENV_SET else default


def _leg_extras(spl=1, rnn_leg=False, **kw):
    """Per-leg JSON extras; tags the knobs that are active. The
    pallas_rnn tag only goes on legs that HAVE recurrent layers —
    default-on _pallas_on() would otherwise stamp conv-only legs
    (resnet) with a knob that cannot affect them."""
    if spl > 1:
        kw["steps_per_launch"] = spl
    if rnn_leg and _pallas_on():
        kw["pallas_rnn"] = True
    if os.environ.get("PADDLE_TPU_BENCH_S2D") == "1":
        kw["conv_s2d"] = True
    if rnn_leg and _pallas_decoder_on():
        kw["pallas_decoder"] = True
    if rnn_leg and os.environ.get("PADDLE_TPU_PALLAS_FLAT") == "1":
        kw["pallas_flat"] = True
    return kw


def _jit_train_step(tc, spl=1):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.graph import GradientMachine
    from paddle_tpu.graph.machine import compute_dtype_of
    from paddle_tpu.optimizer import Updater

    # A/B knobs for the recurrent legs (no-op for ResNet: no scans)
    env_unroll = os.environ.get("PADDLE_TPU_BENCH_UNROLL")
    if env_unroll:
        tc.opt_config.scan_unroll = int(env_unroll)
    if _pallas_on():
        tc.opt_config.pallas_rnn = True
    if os.environ.get("PADDLE_TPU_BENCH_S2D") == "1":
        tc.opt_config.conv_s2d = True
    if _conv_stats_mode():
        tc.opt_config.conv_stats_mode = _conv_stats_mode()
    if _pallas_decoder_on():
        tc.opt_config.pallas_decoder = True

    gm = GradientMachine(tc.model_config, compute_dtype=compute_dtype_of(tc.opt_config),
                         scan_unroll=tc.opt_config.scan_unroll,
                         pallas_rnn=tc.opt_config.pallas_rnn,
                         conv_s2d=tc.opt_config.conv_s2d,
                         conv_stats_mode=tc.opt_config.conv_stats_mode,
                         pallas_decoder=tc.opt_config.pallas_decoder)
    updater = Updater(tc.opt_config, tc.model_config)
    params = gm.init_params(seed=1)
    opt_state = updater.init_state(params)
    grad_fn = gm.grad_fn(remat=tc.opt_config.remat)

    def one_step(params, opt_state, batch, bs):
        loss, grads, outputs, state_updates = grad_fn(params, batch, None)
        new_params, new_opt = updater(params, grads, opt_state, bs)
        for k, v in state_updates.items():
            new_params[k] = v
        return new_params, new_opt, loss

    if spl > 1:

        def multi(params, opt_state, batch, bs):
            def body(_, carry):
                p, o, _loss = carry
                p2, o2, loss = one_step(p, o, batch, bs)
                return p2, o2, loss.astype(jnp.float32)

            init = (params, opt_state, jnp.zeros((), jnp.float32))
            return jax.lax.fori_loop(0, spl, body, init)

        step = jax.jit(multi, donate_argnums=(0, 1))
    else:
        step = jax.jit(one_step, donate_argnums=(0, 1))
    # one_step is returned for FLOP counting: always the per-step
    # computation, so _time_steps' explicit ×spl stays correct however
    # the fused fori lowers
    return step, params, opt_state, one_step


def _time_steps(step, params, opt_state, batch, bs, steps, warmup, trace=False, spl=1,
                count_fn=None):
    """Returns (elapsed seconds, flops-per-LAUNCH or None, compile-info
    dict) — a launch is ``spl`` fused optimizer steps, and the elapsed
    time likewise covers ``steps`` launches, so callers must treat both
    as per-launch. The compile info (``trace_s``/``compile_s``/
    ``compile_cache_hit``) rides each leg's JSON extras into the
    ``kind=bench`` record, so BENCH_*.json carries compile cost and the
    persistent cache's effect is measured run over run.

    FLOPs are analytic MODEL matmul FLOPs from a jaxpr walk of
    ``count_fn`` (the per-step function) — NOT XLA's cost analysis, which
    counts scan/while bodies once regardless of trip count and so
    understated the recurrent legs' MFU several-fold in round 4 (and
    cannot see inside pallas_call custom calls at all). See
    paddle_tpu/ops/kernel_flops.py. Cost analysis remains the fallback
    when no count_fn is given."""
    import jax

    from benchmarks.mfu import flops_of_compiled
    from paddle_tpu.observability.compile_log import cache_probe
    from paddle_tpu.ops.kernel_flops import capture as kernel_flops_capture
    from paddle_tpu.ops.kernel_flops import train_step_flops

    flops = None
    compile_info = {}
    if count_fn is not None:
        try:
            flops = train_step_flops(count_fn, params, opt_state, batch, bs)
        except Exception:
            flops = None
    # AOT-compile ONCE and drive the loop with the same executable the
    # cost analysis describes (jit dispatch would compile a second time).
    # The capture collects analytic FLOP counts recorded by any fused
    # Pallas kernels traced inside the step — the cost-analysis fallback
    # cannot see into a pallas_call custom call
    try:
        hit_probe = cache_probe()
        t0 = time.perf_counter()
        with kernel_flops_capture() as kernel_log:
            lowered = step.lower(params, opt_state, batch, bs)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        compile_info["trace_s"] = round(t1 - t0, 4)
        compile_info["compile_s"] = round(time.perf_counter() - t1, 4)
        hit = hit_probe()
        if hit is not None:
            compile_info["compile_cache_hit"] = hit
        # static memory plan of this leg's one launch group — BENCH_*.json
        # carries a memory trajectory alongside throughput, and `paddle
        # compare` judges footprint growth (doc/observability.md)
        from paddle_tpu.observability.memory import memory_analysis_of

        mem = memory_analysis_of(compiled)
        if mem:
            compile_info["static_mem_bytes"] = mem["mem_total_bytes"]
        if flops is None:
            flops = flops_of_compiled(compiled)
            if flops is not None and kernel_log:
                flops += sum(kernel_log)
        # per-launch basis: count_fn counts ONE step, and XLA's cost
        # analysis counts a fori body once (verified empirically), so
        # both bases scale by the fused-launch factor
        if flops is not None:
            flops *= spl
        step = compiled
    except Exception:
        if flops is not None:
            flops *= spl  # still per-launch on the jit dispatch path
    # sync: reading the last loss back waits for every step before it
    import contextlib

    loss = None
    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state, batch, bs)
    float(loss)
    tracer = (
        jax.profiler.trace(TRACE_DIR) if trace and TRACE_DIR else contextlib.nullcontext()
    )
    with tracer:  # exception-safe: a failing step still finalizes the trace
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, batch, bs)
        float(loss)
        dt = time.perf_counter() - t0
    # live HBM peak over the timed run (allocator cumulative peak —
    # host-side C call, no device sync); absent on backends without
    # allocator stats (CPU), same degradation as the kind=memory records
    from paddle_tpu.observability.memory import device_memory_stats

    stats = device_memory_stats()
    if stats and stats.get("peak_bytes_in_use"):
        compile_info["peak_hbm_bytes"] = stats["peak_bytes_in_use"]
    return dt, flops, compile_info


def _mfu_of(flops, dt, steps):
    import jax

    from benchmarks.mfu import mfu

    kind = jax.devices()[0].device_kind
    m = mfu(flops, dt / steps, kind)
    return (round(m, 4) if m is not None else None), kind


def _is_oom(e) -> bool:
    """True only for memory-exhaustion failures. Anything else (a shape
    bug, a bad rewrite, a lowering error) must FAIL the leg loudly rather
    than silently stepping the ladder down and reporting a healthy-looking
    number for a different configuration.

    The base classifier is the ONE shared OOM matcher
    (observability/memory.py — what routes a training death to the
    oom_report.json pre-mortem and EXIT_OOM); the bench ladder adds the
    looser bare-'oom' token on top, acceptable only HERE because this
    predicate runs inside a leg where memory exhaustion is the expected
    failure mode — the trainer-wide catch must not inherit it."""
    from paddle_tpu.observability.memory import is_oom_error

    return is_oom_error(e) or "oom" in f"{type(e).__name__}: {e}".lower()


def _pallas_on() -> bool:
    """Tri-state PADDLE_TPU_BENCH_PALLAS_RNN: '1' forces the fused
    kernels, '0' forces the scan path, unset defaults to ON for
    accelerator runs and OFF for CPU smoke — measured default
    (2026-08-01 03:27Z follow-up session): pallas lstm 10.57M vs 5.67M
    tok/s at k=8 (1.86x, MFU 0.507), decision-table flip."""
    v = os.environ.get("PADDLE_TPU_BENCH_PALLAS_RNN")
    if v is not None:
        return v == "1"
    import jax

    return jax.default_backend() != "cpu"


def _pallas_decoder_on() -> bool:
    """Tri-state PADDLE_TPU_BENCH_PALLAS_DECODER: '1' runs matching
    attention-GRU decoder groups as one fused Pallas launch
    (ops/pallas_attention_gru), '0'/unset keeps the lax.scan — off
    pending a measured A/B win on hardware (first compile ever)."""
    return os.environ.get("PADDLE_TPU_BENCH_PALLAS_DECODER") == "1"


def _conv_stats_mode() -> str:
    """PADDLE_TPU_BENCH_CONV_STATS: 'gram' computes BN statistics from
    the 1x1 conv's input side (pure XLA — colsum + Gram algebra),
    'pallas' uses the fused matmul kernel (measured end-to-end loser:
    layout-boundary copies, see doc/performance.md), '1' aliases gram,
    '0'/'' force off. Unset = off pending a measured A/B win."""
    v = os.environ.get("PADDLE_TPU_BENCH_CONV_STATS", "")
    if v == "1":
        return "gram"
    if v in ("gram", "pallas"):
        return v
    return ""


def _knob_fallback(is_on, env_var, tag_key, fallback_label):
    """Decorator factory for optional-kernel legs: if the leg fails with
    the knob on — a Mosaic rejection, a VMEM miss in the real compiler,
    anything — rerun it with the knob forced off instead of forfeiting
    the A/B leg's budget, and tag the JSON so the fallback can never
    masquerade as a win for the kernel."""

    def deco(leg_fn):
        @functools.wraps(leg_fn)
        def wrapped(*args, **kwargs):
            if not is_on():
                return leg_fn(*args, **kwargs)
            try:
                return leg_fn(*args, **kwargs)
            except Exception as e:
                err = f"{type(e).__name__}: {str(e)[:300]}"
                sys.stderr.write(f"{tag_key} leg failed, retrying on "
                                 f"{fallback_label}: {err}\n")
                orig = os.environ.get(env_var)
                os.environ[env_var] = "0"
                try:
                    value, extras = leg_fn(*args, **kwargs)
                except Exception as e2:
                    # keep the original diagnosis in the parseable record,
                    # not just stderr — the rerun's error alone would
                    # lose it
                    raise RuntimeError(
                        f"{type(e2).__name__}: {str(e2)[:300]} "
                        f"(rerun on {fallback_label} after {tag_key} "
                        f"failure: {err})"
                    ) from e2
                finally:
                    if orig is None:
                        del os.environ[env_var]
                    else:
                        os.environ[env_var] = orig
                extras = dict(extras or {})
                extras[tag_key] = f"FELL BACK to {fallback_label} ({err})"
                return value, extras

        return wrapped

    return deco


_pallas_fallback = _knob_fallback(
    lambda: _pallas_on(), "PADDLE_TPU_BENCH_PALLAS_RNN",
    "pallas_rnn", "the scan path")
_conv_stats_fallback = _knob_fallback(
    lambda: bool(_conv_stats_mode()), "PADDLE_TPU_BENCH_CONV_STATS",
    "conv_stats", "the XLA path")
_pallas_decoder_fallback = _knob_fallback(
    _pallas_decoder_on, "PADDLE_TPU_BENCH_PALLAS_DECODER",
    "pallas_decoder", "the scan path")


def _try_ladder(configs, run_one):
    """Run the first ladder configuration that survives an OOM-class
    failure; any other error re-raises immediately. The successful rung's
    extras gain a "skipped_rungs" list recording each rung stepped past
    and why, so the JSON never hides that a smaller configuration ran.

    Rungs are (batch, remat) tuples; once a rung OOMs, later rungs with
    the same remat mode and an equal-or-larger batch are skipped without
    compiling — they strictly dominate the failed rung's memory, and the
    ladder is no longer monotonically descending (256 leads on measured
    throughput), so a guaranteed-OOM 512 could otherwise burn a full
    compile after 256 already failed."""
    skipped = []
    oomed = []  # (batch, ...) rungs that hit OOM
    for i, cfg in enumerate(configs):
        # rung = (batch,) or (batch, remat, ...): dominate = same
        # non-batch knobs with an equal-or-larger batch
        dom = next((o for o in oomed if o[1:] == cfg[1:] and cfg[0] >= o[0]), None)
        if dom is not None and i < len(configs) - 1:
            skipped.append({"rung": list(cfg),
                            "error": f"skipped: memory-dominates OOMed rung {list(dom)}"})
            continue
        try:
            value, extras = run_one(*cfg)
        except Exception as e:
            if i == len(configs) - 1 or not _is_oom(e):
                raise
            oomed.append(cfg)
            skipped.append({"rung": list(cfg), "error": f"{type(e).__name__}: {str(e)[:200]}"})
            continue
        if skipped:
            extras = dict(extras or {}, skipped_rungs=skipped)
        return value, extras
    raise AssertionError("empty ladder")


@_conv_stats_fallback
def bench_resnet50(B=None, img_size=224, classes=1000, steps=20, warmup=3, trace=True,
                   dtype=None):
    """Headline leg. Without an explicit B, tries a (batch, remat)
    ladder led by the measured-fastest rung (B=256 — past it the BN-stat
    and residual bandwidth grows faster than MXU fill; 2026-08-01 batch
    A/B in benchmarks/RESULTS.md), stepping to other plain sizes on OOM
    and only then to remat rungs (the +33% recompute FLOPs often beats
    halving B), keeping the first configuration that runs.
    PADDLE_TPU_BENCH_RESNET_B pins a size."""
    import jax.numpy as jnp

    from paddle_tpu.flagship import make_image_batch, resnet_config

    env_b = os.environ.get("PADDLE_TPU_BENCH_RESNET_B")
    env_remat = os.environ.get("PADDLE_TPU_BENCH_RESNET_REMAT", "none")
    if env_b:
        ladder = [(int(env_b), env_remat)]
    elif B:
        ladder = [(B, "none")]
    else:
        # 256 leads — measured (2026-08-01 03:43Z batch A/B): 2201 imgs/s
        # at B=256 vs 2082 at 512 and 1957 at 768; past 256 the BN-stat
        # and residual bandwidth grows faster than MXU fill. ALL plain
        # rungs come before ANY remat rung — if a plain rung OOMs a
        # smaller plain rung must win, not a remat one whose +33%
        # recompute would silently replace the mfu headline with
        # hw_flops_util
        sizes = (256, 512, 128, 64)
        ladder = [(b, "none") for b in sizes] + [(b, "full") for b in sizes]

    def run_one(b, remat):
        tc = resnet_config(50, img_size, classes)
        tc.opt_config.batch_size = b
        tc.opt_config.dtype = dtype or BENCH_DTYPE
        tc.opt_config.remat = remat
        spl = _leg_spl(1)  # long compute-bound steps: fusing launches is noise
        step, params, opt_state, one_step = _jit_train_step(tc, spl)
        batch = make_image_batch(b, img_size, classes)
        dt, flops, cinfo = _time_steps(
            step, params, opt_state, batch, jnp.asarray(float(b)), steps, warmup,
            trace=trace and TRACE_LEG in ("", "resnet"), spl=spl, count_fn=one_step,
        )
        m, kind = _mfu_of(flops, dt, steps)
        extras = _leg_extras(spl=spl, device_kind=kind, dtype=tc.opt_config.dtype, batch=b,
                             **cinfo)
        if _conv_stats_mode():
            extras["conv_stats"] = _conv_stats_mode()
        if remat == "none":
            extras["mfu"] = m
        else:
            # remat recompute FLOPs are in the executed count, so this
            # is hardware-FLOPs utilization, NOT model-FLOPs (MFU would
            # be overstated ~33%) — different key, never comparable
            extras["remat"] = remat
            extras["hw_flops_util"] = m
        return b * steps * spl / dt, extras

    return _try_ladder(ladder, run_one)


@_pallas_fallback
def bench_lstm_classifier(B=256, T=64, steps=20, warmup=3, dtype=None):
    import jax.numpy as jnp

    from paddle_tpu.flagship import example_batch, flagship_config

    import jax

    B = int(os.environ.get("PADDLE_TPU_BENCH_LSTM_B", 0)) or B
    tc = flagship_config(dict_dim=10000, emb_dim=256, hidden=512, classes=2)
    tc.opt_config.batch_size = B
    tc.opt_config.dtype = dtype or BENCH_DTYPE
    # measured-best default: k=8 fused launches on the accelerator (5.55M
    # vs 4.31M tok/s at k=1 — this leg is dispatch-latency-bound); plain
    # single launches on the CPU smoke path
    spl = _leg_spl(8 if jax.default_backend() != "cpu" else 1)
    step, params, opt_state, one_step = _jit_train_step(tc, spl)
    batch = example_batch(dict_dim=10000, B=B, T=T)
    dt, flops, cinfo = _time_steps(
        step, params, opt_state, batch, jnp.asarray(float(B)), steps, warmup,
        trace=TRACE_LEG == "lstm", spl=spl, count_fn=one_step,
    )
    m, _ = _mfu_of(flops, dt, steps)
    extras = _leg_extras(spl=spl, rnn_leg=True, mfu=m, dtype=tc.opt_config.dtype,
                         **cinfo)
    return B * T * steps * spl / dt, extras


@_pallas_fallback
@_pallas_decoder_fallback
def bench_nmt(B=None, T=32, vocab=30000, dim=512, steps=10, warmup=2, dtype=None):
    """seqToseq NMT attention encoder-decoder train step; tokens/sec counts
    target (decoder) tokens — BASELINE.md north-star workload #2. Without
    an explicit B, walks a 448/384/256/128/64 batch ladder on OOM (448
    measured fastest 2026-08-01: 599.6k tok/s MFU 0.4102; 512 breaks
    the fused GRU kernel's hardware compile); an
    explicit B or PADDLE_TPU_BENCH_NMT_B pins a size, matching
    bench_resnet50's PADDLE_TPU_BENCH_RESNET_B."""
    import jax.numpy as jnp

    from paddle_tpu.flagship import nmt_batch, nmt_config

    def run_one(b):
        import jax

        tc = nmt_config(vocab=vocab, dim=dim, dtype=dtype or BENCH_DTYPE)
        tc.opt_config.batch_size = b
        # measured default (2026-08-01 03:26Z session): k=8 419.9k tok/s
        # vs k=1 373.3k = 1.125x — decision-table flip; CPU smoke stays k=1
        spl = _leg_spl(8 if jax.default_backend() != "cpu" else 1)
        step, params, opt_state, one_step = _jit_train_step(tc, spl)
        batch = nmt_batch(vocab=vocab, B=b, T=T)
        dt, flops, cinfo = _time_steps(
            step, params, opt_state, batch, jnp.asarray(float(b)), steps, warmup,
            trace=TRACE_LEG == "nmt", spl=spl, count_fn=one_step,
        )
        m, _ = _mfu_of(flops, dt, steps)
        extras = _leg_extras(spl=spl, rnn_leg=True, mfu=m, dtype=tc.opt_config.dtype,
                             tokens="target", batch=b, **cinfo)
        return b * T * steps * spl / dt, extras

    env_b = os.environ.get("PADDLE_TPU_BENCH_NMT_B")
    if env_b:
        ladder = [(int(env_b),)]
    else:
        # 448 leads — measured (2026-08-01 06:08Z, post flat-logits):
        # 599.6k tok/s MFU 0.4102 vs 587.4k at 384 and 554.6k at 256;
        # at 512 the fused GRU kernel's hardware compile fails (falls
        # back to scan), so 448 is the largest kernel-clean batch
        ladder = [(B,)] if B else [(448,), (384,), (256,), (128,), (64,)]
    return _try_ladder(ladder, run_one)


def bench_nmt_gen(B=None, T=32, vocab=30000, dim=512, beam_size=3,
                  max_length=32, steps=10, warmup=2, dtype=None):
    """seqToseq beam-search generation throughput: generated (best-beam)
    tokens/sec — the reference's gen.conf workload (SURVEY hard part #1's
    beam search under XLA's static-shape regime). Forward-only; no MFU
    (the decode while-loop is dispatch/latency-bound, not matmul-bound,
    and its trip count is data-dependent)."""
    import jax
    import numpy as np

    from paddle_tpu.flagship import nmt_gen_batch, nmt_gen_config
    from paddle_tpu.graph import GradientMachine
    from paddle_tpu.graph.machine import compute_dtype_of

    def run_one(b):
        tc = nmt_gen_config(vocab=vocab, dim=dim, beam_size=beam_size,
                            max_length=max_length, dtype=dtype or BENCH_DTYPE,
                            batch_size=b)
        gm = GradientMachine(tc.model_config,
                             compute_dtype=compute_dtype_of(tc.opt_config))
        params = gm.init_params(seed=1)
        batch = nmt_gen_batch(vocab=vocab, B=b, T=T)
        group = next(s.name for s in tc.model_config.sub_models
                     if s.generator is not None)

        def fwd(params, batch):
            outputs, _ = gm.forward(params, batch, pass_type="gen", rng=None)
            best = outputs[group]
            return best.ids, best.seq_lengths

        fwd = jax.jit(fwd)
        ids, lens = fwd(params, batch)
        jax.block_until_ready((ids, lens))
        for _ in range(warmup - 1):
            ids, lens = fwd(params, batch)
        jax.block_until_ready((ids, lens))
        tracing = TRACE_DIR and TRACE_LEG == "gen"
        if tracing:
            jax.profiler.start_trace(TRACE_DIR)
        # count generated tokens EVERY timed step (the lens readback is
        # also the per-step device sync): the old once-after-loop
        # `tokens * steps / dt` assumed every step produced identical
        # trip counts — data-dependent decode lengths (early-EOS beams)
        # would silently skew the headline
        t0 = time.perf_counter()
        tokens = 0.0
        for _ in range(steps):
            ids, lens = fwd(params, batch)
            tokens += float(np.asarray(lens).sum())  # sync via readback
        dt = time.perf_counter() - t0
        if tracing:
            jax.profiler.stop_trace()
        extras = _leg_extras(beam_size=beam_size, max_length=max_length,
                             dtype=tc.opt_config.dtype, batch=b,
                             tokens="best-beam generated")
        return tokens / dt, extras

    env_b = os.environ.get("PADDLE_TPU_BENCH_GEN_B")
    if env_b:
        ladder = [(int(env_b),)]
    else:
        # 512 leads — measured (2026-08-01 07:08Z batch sweep): decode is
        # dispatch-bound per step, so tokens/s scales with batch until
        # the MXU fills: 800.6 (64) / 1557.6 (128) / 2450.3 (256) /
        # 3114.4 (512) tok/s at beam=3
        ladder = [(B,)] if B else [(512,), (256,), (128,), (64,)]
    return _try_ladder(ladder, run_one)


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
    ``replicas`` is the fleet-size LADDER (PADDLE_TPU_BENCH_SERVE_
    REPLICAS): each size N > 1 runs the whole rate sweep through
    ``drive_fleet_rung`` — N engines behind the router's own
    least-loaded scoring — so the scaling curve (goodput vs replicas,
    router overhead share) is measured, not assumed.

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
            # lose to speculation+context-switching (measured −10..−27%
            # goodput — doc/performance.md "Pipelined decode"). Count
            # the cores this process may actually USE — a cgroup/
            # affinity-limited container on a big host is still 1-core
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
    extras = _leg_extras(
        batch=B, beam_size=beam_size, max_length=max_length,
        dtype=tc.opt_config.dtype, n_requests=n_requests, engine=engine,
        mixed_len=bool(mixed_len), capacity_rps=round(capacity_rps, 3),
        knee_rps=doc.get("knee_rps"), rungs=rungs, run_dir=run_dir,
        tokens=("greedy generated" if engine == "continuous"
                else "best-beam generated"),
    )
    if engine == "continuous":
        # the headline stamps the pipeline mode + ladder so an archived
        # BENCH_*.json says WHAT was measured (and compare joins on it)
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


def bench_feeder(B=128, dim=512, n_batches=40, max_threads=None,
                 repeats=3):
    """Input-pipeline microbenchmark (no train step): packed samples/s
    and bytes/s through ``BatchAssembler`` + the prefetch pipeline, with
    1 vs N packer threads (``--data_packer_threads``). Device-free by
    construction — it measures exactly the host packing stage the
    zero-stall work parallelized, so regressions in the feeder can't
    hide behind device time. Samples are pre-built numpy sequences
    (varied lengths, so bucketing and padding run for real) and the
    shuffle pool is active, matching the training-path shape of the
    work. Emitted through the same ``kind=bench`` metrics schema as
    every other leg, so ``BENCH_*.json`` tracks input-pipeline
    throughput run over run."""
    import numpy as np

    from paddle_tpu.data.feeder import DataProvider
    from paddle_tpu.native import get_lib
    from paddle_tpu.data.provider import (
        dense_vector_sequence, integer_value, provider,
    )

    B = int(os.environ.get("PADDLE_TPU_BENCH_FEEDER_B", 0)) or B
    n = max_threads or int(os.environ.get("PADDLE_TPU_BENCH_FEEDER_THREADS", "2"))
    rng = np.random.default_rng(0)
    # lengths 100-128 all bucket to T=128: realistic padding work with a
    # high C-packer share (the measured sweet spot for exposing packing
    # parallelism — shorter/raggeder mixes shift time into GIL-held
    # Python prep and understate the pool). Only B*4 UNIQUE samples,
    # cycled: assemble re-packs them identically each time, and holding
    # every sample of every batch resident (~1.2 GB at the defaults)
    # would OOM-risk small CI containers for no extra signal
    uniq = B * 4
    samples = [
        (rng.standard_normal((int(rng.integers(100, 129)), dim)).astype(np.float32),
         int(i % 2))
        for i in range(uniq)
    ]

    @provider(input_types={"x": dense_vector_sequence(dim),
                           "y": integer_value(2)},
              pool_size=B * 8)
    def synth(settings, file_name):
        for i in range(B * n_batches):
            yield samples[i % uniq]

    def one_pass(threads):
        dp = DataProvider(
            synth, ["mem"], B, ["x", "y"],
            packer_threads=threads, prefetch_depth=4,
            stall_timeout=300.0, seed=1,
        )
        t0 = time.perf_counter()
        n_samples = n_bytes = 0
        for batch in dp.batches():
            n_samples += int(np.asarray(batch["y"].ids).shape[0])
            n_bytes += sum(
                getattr(f, "nbytes", 0)
                for a in batch.values()
                for f in (a.value, a.ids, a.seq_lengths)
                if f is not None
            )
        return n_samples, n_bytes, time.perf_counter() - t0

    one_pass(1)  # warm the native lib + allocator
    results = {}
    for threads in sorted({1, n}):
        best = min((one_pass(threads) for _ in range(repeats)),
                   key=lambda r: r[2])
        results[threads] = best
    ns, nb, dt = results[n]
    rate = ns / dt
    rate1 = results[1][0] / results[1][2]
    return rate, {
        "packer_threads": n,
        "batch": B,
        "dim": dim,
        "bytes_per_sec": round(nb / dt, 1),
        "samples_per_sec_1thread": round(rate1, 1),
        "speedup_vs_1thread": round(rate / rate1, 3) if n > 1 else 1.0,
        "native_datapath": get_lib() is not None,
    }


def bench_sparse(V=100_000, D=64, B=4096, steps=20, warmup=3, dtype=None):
    """Row-sharded sparse-embedding step microbenchmark (doc/sparse.md):
    touched-rows/s through one gather → per-row adagrad → scatter-drop
    update step — the exact kernel sequence the ``sparse_update`` table
    path runs, built from the same ``optimizer.sparse.dedupe`` the
    updater uses. Ids are a hot-set-skewed mix (80 % of occurrences
    from 1 % of rows, the CTR-shaped distribution), so the dedupe and
    the unique-row rate measure something real. Alongside the headline
    it measures the gather's own share of the step (a second
    gather-only jit over the same ids) and stamps ``static_mem_bytes``
    + the roofline bucket — gather-dominated steps must classify
    memory-bound on any known chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.observability import costs
    from paddle_tpu.optimizer.sparse import dedupe

    dt = jnp.dtype(dtype or "float32")
    rng = np.random.default_rng(0)
    hot = max(V // 100, 1)
    n_hot = int(B * 0.8)
    ids_batches = [
        jnp.asarray(np.concatenate([
            rng.integers(0, hot, size=n_hot),
            rng.integers(0, V, size=B - n_hot),
        ]).astype(np.int32))
        for _ in range(4)
    ]
    table = jnp.asarray(rng.standard_normal((V, D)), dtype=dt)
    acc = jnp.zeros((V, D), dtype=dt)  # per-row adagrad accumulator

    def step(table, acc, ids):
        rows = jnp.take(table, ids, axis=0)
        loss = 0.5 * jnp.mean(rows * rows)
        grads = rows / (ids.shape[0] * D)
        uid, g_rows, _valid = dedupe(ids, grads, V)
        safe = jnp.clip(uid, 0, V - 1)
        acc_rows = jnp.take(acc, safe, axis=0) + g_rows * g_rows
        update = g_rows / (jnp.sqrt(acc_rows) + 1e-6)
        table = table.at[uid].add(-0.1 * update, mode="drop")
        acc = acc.at[uid].max(acc_rows, mode="drop")
        return table, acc, loss

    def gather_only(table, ids):
        return jnp.take(table, ids, axis=0).sum()

    jstep = jax.jit(step, donate_argnums=(0, 1))
    jgather = jax.jit(gather_only)
    extras = {"vocab": V, "dim": D, "batch": B, "steps": steps}
    step_fn = jstep
    try:
        # AOT-compile once and TIME the same executable, so the
        # static-memory/roofline analysis does not pay a second compile
        # of an identical step graph (jit's own cache would)
        compiled = jstep.lower(table, acc, ids_batches[0]).compile()
        step_fn = compiled
        ma = compiled.memory_analysis()
        if ma is not None:
            extras["static_mem_bytes"] = int(
                getattr(ma, "temp_size_in_bytes", 0)
                + getattr(ma, "argument_size_in_bytes", 0)
                + getattr(ma, "output_size_in_bytes", 0)
            )
        ca = costs.cost_analysis_of(compiled)
        if ca and ca.get("bytes_accessed"):
            intensity = ca.get("flops", 0.0) / ca["bytes_accessed"]
            extras["roofline_class"] = costs.classify(
                intensity, jax.devices()[0].device_kind
            )
    except Exception:
        pass  # AOT-less backends: headline still measured below

    def time_fn(fn, *state):
        # every fn returns (carried_state..., last_result): the carry
        # threads donated buffers, the tail is only blocked on at the end
        for i in range(warmup):
            state = fn(*state, ids_batches[i % len(ids_batches)])[:-1]
        t0 = time.perf_counter()
        out = state
        for i in range(steps):
            out = fn(*out[: len(state)], ids_batches[i % len(ids_batches)])
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    t_step = time_fn(step_fn, table, acc)
    t_gather = time_fn(lambda t, ids: (t, jgather(t, ids)),
                       jnp.asarray(rng.standard_normal((V, D)), dtype=dt))
    rows_per_sec = B * steps / max(t_step, 1e-9)
    uniq = np.mean([
        np.unique(np.asarray(ids)).size / B for ids in ids_batches
    ])
    extras.update({
        "sparse_gather_share": round(min(t_gather / max(t_step, 1e-9), 1.0), 4),
        "unique_row_rate": round(float(uniq), 4),
        "step_ms": round(t_step / steps * 1e3, 3),
    })
    return rows_per_sec, extras


def _emit(metric, value, unit, vs_baseline, **extra):
    from paddle_tpu.utils.device import device_stamp

    line = {
        "metric": metric,
        "value": round(float(value), 1),
        "unit": unit,
        "vs_baseline": round(float(vs_baseline), 3),
        # the device every number of this run came from, as jax reports it
        **device_stamp(),
    }
    line.update({k: v for k, v in extra.items() if v is not None})
    print(json.dumps(line))
    _emit_metrics_record(line)


def _emit_metrics_record(line):
    """Mirror each result line into a run-telemetry stream
    (PADDLE_TPU_BENCH_METRICS_DIR): the BENCH_*.json payload and live
    run telemetry then share ONE schema — `paddle metrics --tail` and
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
    if STEPS_PER_LAUNCH < 1:
        raise ValueError(
            "PADDLE_TPU_BENCH_STEPS_PER_LAUNCH must be an integer >= 1, "
            f"got {STEPS_PER_LAUNCH}"
        )
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which not in ("all", "resnet", "lstm", "nmt", "gen", "serve", "feeder",
                     "sparse"):
        print(
            f"unknown benchmark {which!r}: expected 'all', 'resnet', 'lstm', "
            "'nmt', 'gen', 'serve', 'feeder' or 'sparse'",
            file=sys.stderr,
        )
        return 2

    # persistent compilation cache, at the one place every entry point
    # uses (compile_log.resolve_cache_dir): repeat runs skip recompiling
    # unchanged steps; a cold cache is merely the old speed. The helper
    # also drops jax's min-compile-time gate so cache hits are measurable
    # (and measured — _time_steps stamps trace_s/compile_s/
    # compile_cache_hit into every leg's record)
    from paddle_tpu.observability.compile_log import enable_compile_cache

    enable_compile_cache()

    if which == "feeder":
        # host-only leg (no train step): packing throughput
        value, extras = bench_feeder()
        _emit("feeder_pack_samples_per_sec", value, "samples/s", 1.0,
              baseline_kind="none", **extras)
        return 0

    targets_path = os.path.join(REPO, "benchmarks", "targets.json")
    targets = {}
    if os.path.exists(targets_path):
        with open(targets_path) as f:
            targets = json.load(f)

    import jax

    # the platform jax was given decides the shapes: real ones on an
    # accelerator, smoke shapes (renamed metrics) on the CPU backend
    on_tpu = jax.default_backend() != "cpu"

    # bf16 on XLA CPU is emulated and slow — CPU fallbacks run f32 so
    # their numbers stay comparable run-to-run
    leg_dtype = None if on_tpu else "float32"
    if which == "lstm":
        value, extras = bench_lstm_classifier(dtype=leg_dtype)
        metric, unit, tkey = (
            "lstm_classifier_train_tokens_per_sec",
            "tokens/s",
            "lstm_classifier_tokens_per_sec",
        )
    elif which == "nmt":
        # CPU has nothing to OOM the ladder down: pin the pre-ladder B=64
        value, extras = bench_nmt(dtype=leg_dtype, **({} if on_tpu else {"B": 64}))
        metric, unit, tkey = ("nmt_train_tokens_per_sec", "tokens/s", "nmt_tokens_per_sec")
    elif which == "gen":
        if on_tpu:
            value, extras = bench_nmt_gen()
            metric = "nmt_gen_tokens_per_sec"
        else:
            value, extras = bench_nmt_gen(
                B=4, T=8, vocab=200, dim=32, max_length=8, steps=2, warmup=1,
                dtype="float32")
            metric = "nmt_gen_cpu_smoke_tokens_per_sec"
        unit, tkey = "tokens/s", None
    elif which == "sparse":
        # sparse-embedding leg (doc/sparse.md): touched-rows/s headline,
        # gather share + static_mem_bytes + roofline bucket in extras —
        # `paddle compare` judges rows/s higher-better and gather share
        # lower-better (_HIGHER_BETTER entries). CPU smoke shrinks the
        # table and renames the metric, same contract as the other legs
        if on_tpu:
            value, extras = bench_sparse()
            metric = "sparse_rows_per_sec"
        else:
            value, extras = bench_sparse(
                V=20_000, D=32, B=1024, steps=8, warmup=2, dtype="float32"
            )
            metric = "sparse_cpu_smoke_rows_per_sec"
        unit, tkey = "rows/s", None
    elif which == "serve":
        # offered-load serving leg: CPU smoke shapes are bench_serve's
        # backend-aware defaults (tiny model, named so a toy run never
        # masquerades as the flagship serving number).
        # `bench.py serve --engine={static,continuous}` picks the
        # engine (PADDLE_TPU_BENCH_SERVE_ENGINE also works) — run one
        # of each on pinned PADDLE_TPU_BENCH_SERVE_RATES and `paddle
        # compare` the two artifacts for the A/B (doc/serving.md)
        eng = None
        for a in sys.argv[2:]:
            if a.startswith("--engine="):
                eng = a.split("=", 1)[1]
        value, extras = bench_serve(dtype=None if on_tpu else "float32",
                                    engine=eng)
        metric = ("serve_goodput_tokens_per_sec" if on_tpu
                  else "serve_cpu_smoke_goodput_tokens_per_sec")
        unit, tkey = "tokens/s", None
        # one schema, one stream: unless the driver already points the
        # bench-record mirror somewhere, land the kind=bench headline in
        # the serve run dir next to its request/serve_window records
        os.environ.setdefault("PADDLE_TPU_BENCH_METRICS_DIR",
                              extras["run_dir"])
    elif on_tpu:
        # headline: bf16 ResNet-50; "all" additionally runs the two
        # sequence flagships (emitted incrementally below)
        value, extras = bench_resnet50()
        metric, unit, tkey = (
            "resnet50_train_imgs_per_sec_per_chip",
            "imgs/s",
            "resnet50_imgs_per_sec",
        )
    else:
        # CPU smoke runs can't push 224px ResNet: shrink AND rename the
        # metric so a toy run can never masquerade as the flagship number
        value, extras = bench_resnet50(B=16, img_size=32, classes=16, steps=5, warmup=2,
                                       trace=False, dtype="float32")
        metric, unit, tkey = ("resnet50_cpu_smoke_imgs_per_sec", "imgs/s", None)

    target = targets.get(tkey) if tkey else None
    vs_baseline = value / target if target else 1.0
    common = dict(baseline_kind="estimated" if target else "none")
    # emit the headline IMMEDIATELY — if a later leg fails, the measured
    # number is already on stdout
    _emit(metric, value, unit, vs_baseline, **common, **extras)
    sys.stdout.flush()
    if which == "serve":
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
    if which == "all":
        if on_tpu:
            leg_specs = [
                ("lstm_classifier_train_tokens_per_sec", bench_lstm_classifier, {}),
                ("nmt_train_tokens_per_sec", bench_nmt, {}),
            ]
        else:
            # tiny lstm/nmt smoke legs: worthless as perf numbers (and
            # named so) but they prove all three flagship train steps
            # compile and run
            leg_specs = [
                ("lstm_cpu_smoke_tokens_per_sec", bench_lstm_classifier,
                 dict(B=8, T=16, steps=3, warmup=1, dtype="float32")),
                ("nmt_cpu_smoke_tokens_per_sec", bench_nmt,
                 dict(B=4, T=8, vocab=200, dim=32, steps=2, warmup=1,
                      dtype="float32")),
            ]
        legs = {}
        for key, fn, kw in leg_specs:
            # a leg that raises ends the run (non-zero exit, traceback);
            # the lines already emitted stay on stdout
            v, e = fn(**kw)
            legs[key] = {"value": round(v, 1), "unit": "tokens/s",
                         **{k: x for k, x in (e or {}).items() if x is not None}}
            # cumulative re-emit after each leg: always a complete line
            _emit(metric, value, unit, vs_baseline, **common, legs=legs, **extras)
            sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
