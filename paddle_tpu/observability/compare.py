"""``paddle compare <run_a> <run_b>`` — diff two runs, with a verdict.

Bench trajectory has been eyeballed across ``BENCH_*.json`` files and
run dirs since round 1; this makes the comparison mechanical. Each side
may be:

- a **run dir** (or one ``metrics*.jsonl``): compared on the analyzer's
  steady-state numbers — last-pass step p50/p99, samples/s, MFU,
  data-wait share, total checkpoint-blocked seconds, compile totals
  (count / seconds / cache hits), and worst time-to-first-step;
- a **bench artifact**: a ``BENCH_*.json`` driver record (the last
  parseable result line inside its ``tail``), or a raw bench JSON line
  file — compared on the headline value plus every numeric leg;
- a **lint artifact** (``paddle lint --json`` output): compared on the
  total and per-rule NEW-finding counts from the ``lint_summary``
  record — all lower-is-better, zero-filled from the summary's rule
  list so a rule going 0 → N is judged (REGRESSION, exit 1) instead of
  falling into ``only_b``;
- a **race artifact** (``paddle race --json`` output): same shape as
  the lint diff — total and per-detector NEW-finding counts from the
  ``race_summary`` record, zero-filled from its detector list, all
  lower-is-better (a PR introducing a lock-order inversion regresses).

Every shared metric gets a relative delta and a per-metric verdict
against a noise threshold (``--threshold``, default 5%): metrics where
higher is better (throughput, MFU) regress when B is lower; latency-like
metrics (step quantiles, data-wait, compile seconds, ttfs) regress when
B is higher. The overall verdict is REGRESSION if any metric regressed,
IMPROVED if any improved (and none regressed), else NO CHANGE — and the
exit code is 1 on REGRESSION so scripts can gate on it.

jax-free, like the other analyzers.

Usage::

    paddle compare <run_a> <run_b> [--threshold 0.05] [--abs-floor 0.05]
                   [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

from paddle_tpu.observability import metrics as obs

# metric name -> True when higher is better (throughput-like); absent
# names are matched by _higher_is_better's suffix rules
_HIGHER_BETTER = {
    "samples_per_sec": True,
    "mfu": True,
    "step_p50_ms": False,
    "step_p99_ms": False,
    "data_wait_share": False,
    "ckpt_blocked_s": False,
    "compile_count": False,
    "compile_total_s": False,
    "time_to_first_step_s": False,
    "restore_s": False,
    "cache_hits": True,
    # memory plane: footprint growth is a regression (the headroom the
    # next batch-size bump needs); numerics: a layer newly producing
    # nonfinite gradients is a regression even when throughput held
    "hbm_peak_bytes": False,
    "static_mem_bytes": False,
    "nonfinite_layers": False,
    # sparse plane (doc/sparse.md): rows/s is throughput; gather share
    # growing means the step is spending more of itself fetching rows
    "sparse_rows_per_sec": True,
    "sparse_gather_share": False,
}


def _serve_key(offered_rps, qualifier, seen_pre: set,
               engine: Optional[str] = None,
               pipeline: Optional[str] = None,
               replicas: Any = None,
               transport: Optional[str] = None,
               spec: Optional[str] = None,
               slot_dtype: Optional[str] = None) -> str:
    """The ONE serve rung key format, shared by the run-dir and bench-
    artifact sides (a divergence would silently break their
    comparability): 6 significant digits of offered load — a slow
    backend's sub-1 req/s ladder must not collapse rungs into one key —
    with later duplicates engine-qualified first (a both-engines
    artifact repeats every rate once per engine; joining them as one
    key would diff an engine against itself), then PIPELINE-qualified
    (a one-artifact pipelined-vs-blocking sweep repeats every (engine,
    rate) once per mode), and finally rung-qualified (variance-gauging
    repeated rates) instead of silently overwritten.

    The rung join is therefore (engine, pipeline, replicas, offered
    load): two sweeps of the SAME configuration join on offered load
    alone; mismatched ladders land in only_a/only_b (visible, never a
    bogus verdict); and a pure A/B — one engine (or one pipeline mode)
    per artifact, pinned PADDLE_TPU_BENCH_SERVE_RATES — joins on
    offered load, which is exactly the static-vs-continuous (or
    pipelined-vs-blocking) comparison being asked for.

    Fleet rungs (``--replicas=N``, N > 1) carry an unconditional
    ``xN`` qualifier: a replicas ladder repeats every (engine, rate)
    once per fleet size IN ONE artifact, and the scaling curve
    (goodput vs replicas, router overhead share) is read by joining
    same-x rungs across artifacts — an x2 rung must never diff against
    an x4 one.

    Transport (``pipe`` vs ``tcp``, the socket-fleet sweep) qualifies
    only on collision, AFTER pipeline: a one-transport-per-artifact
    A/B (pipe baseline vs tcp candidate, pinned rates) joins on
    offered load alone — which is exactly the cross-transport
    router_share comparison being asked for — while a both-transports
    artifact repeats every (engine, pipeline, rate) once per wire and
    must not diff a transport against itself.

    Speculation config (``spec``, the draft-length ladder spelling or
    "off") and slot-state dtype qualify the same way, after pipeline:
    the intended spec-on-vs-spec-off (or bf16-vs-f32) A/B is one
    config per artifact with pinned rates — joining on offered load
    alone — while a both-configs sweep in ONE artifact repeats every
    (engine, pipeline, rate) once per config and must not diff a
    config against itself."""
    rate = format(float(offered_rps or 0.0), ".6g")
    x = f"x{int(replicas)}." if replicas and int(replicas) > 1 else ""
    pre = f"serve.{x}{rate}rps."
    if pre in seen_pre and engine:
        pre = f"serve.{engine}.{x}{rate}rps."
    if pre in seen_pre and engine and pipeline:
        pre = f"serve.{engine}.pipe-{pipeline}.{x}{rate}rps."
    if pre in seen_pre and engine and pipeline and spec:
        pre = f"serve.{engine}.pipe-{pipeline}.spec-{spec}.{x}{rate}rps."
    if pre in seen_pre and engine and pipeline and spec and slot_dtype:
        pre = (f"serve.{engine}.pipe-{pipeline}.spec-{spec}"
               f".dt-{slot_dtype}.{x}{rate}rps.")
    if pre in seen_pre and engine and pipeline and transport:
        pre = f"serve.{engine}.pipe-{pipeline}.net-{transport}.{x}{rate}rps."
    if pre in seen_pre:
        pre = f"{pre[:-1]}.r{qualifier}."
    seen_pre.add(pre)
    return pre


def _engine_scoped(pre: str, engine: Optional[str], key: str) -> str:
    """Key for SHARE-type rung metrics (queue_wait_share): a share of
    e2e is only comparable when the latency regime is shared, so these
    are engine-qualified unconditionally — same-engine A/Bs still join,
    while a cross-engine join (where the denominator shrank with the
    engine change) lands in only_a/only_b instead of minting a phantom
    verdict."""
    if not engine:
        return pre + key
    if pre.startswith(f"serve.{engine}."):
        return pre + key  # already engine-qualified (both-engines side)
    return f"serve.{engine}.{pre[len('serve.'):]}{key}"


def _higher_is_better(name: str) -> bool:
    if name in _HIGHER_BETTER:
        return _HIGHER_BETTER[name]
    n = name.lower()
    # per-rung overload-defense rates (shed = policy refusals, error =
    # failed launches): growth is a serving regression. Checked before
    # the generic suffix rules — neither matches "_s"/"latency", and
    # the throughput default would judge them backwards
    if n.endswith(("shed_rate", "error_rate")):
        return False
    # speculative-decode draft acceptance (doc/serving.md "Speculative
    # decode"): a higher share of draft tokens surviving verification
    # is more free tokens per launch — explicit because the generic
    # rules below would only cover it by the fall-through default
    if n.endswith("accept_rate"):
        return True
    # lint/race metrics are finding counts: fewer is always better (and
    # the bare rule/detector ids would otherwise fall through to the
    # throughput default below)
    if n.startswith(("lint", "race")):
        return False
    # tail-attribution shares (doc/observability.md "Distributed
    # tracing"): an overhead bucket growing its slice of the p99 cohort
    # is a regression — EXCEPT decode, whose share growing means the
    # tail spends its time on useful token work instead of waiting (a
    # decode-dominated p99 is the healthy end state)
    if ".p99_share." in n:
        return n.endswith(".decode")
    # serving metrics (doc/observability.md "Serving telemetry"):
    # goodput and the saturation knee are throughput-like; latency/TTFT/
    # queue-wait fall through to the lower-is-better suffixes below
    if any(s in n for s in ("per_sec", "per_chip", "samples", "tokens",
                            "imgs", "speedup", "mfu", "hits", "goodput",
                            "knee")):
        return True
    if any(s in n for s in ("_s", "_ms", "latency", "wait", "blocked",
                            "compile", "p50", "p99", "_bytes")):
        return False
    return True  # bench values are throughput by convention


# ------------------------------------------------------------- run sides


def _run_side(path: str) -> Dict[str, float]:
    """Comparable scalars of one run dir / metrics stream."""
    from paddle_tpu.observability.analyze import analyze, load_run

    streams = load_run(path)
    doc = analyze(streams)
    out: Dict[str, float] = {}
    # steady state: the LAST pass row carries the converged step shape
    if doc["passes"]:
        last = doc["passes"][-1]
        for src, dst, scale in (
            ("samples_per_sec", "samples_per_sec", 1.0),
            ("mfu", "mfu", 1.0),
            ("step_time_p50_s", "step_p50_ms", 1e3),
            ("step_time_p99_s", "step_p99_ms", 1e3),
            ("data_wait_share", "data_wait_share", 1.0),
        ):
            if src in last:
                out[dst] = float(last[src]) * scale
        # 0.0 is a real measurement (async saves block nothing) and must
        # stay comparable — omitting it would hide a 0 → nonzero
        # regression from the verdict
        out["ckpt_blocked_s"] = sum(
            float(r.get("ckpt_blocked_s", 0.0)) for r in doc["passes"]
        )
    t = doc.get("compile_totals") or {}
    if t.get("count"):
        out["compile_count"] = float(t["count"])
        out["compile_total_s"] = t["trace_s"] + t["compile_s"]
        out["cache_hits"] = float(t["cache_hits"])
    lat = doc.get("restart_latency") or {}
    if lat:
        out["time_to_first_step_s"] = float(lat["time_to_first_step_s_max"])
        out["restore_s"] = float(lat["restore_s_max"])
    # memory plane: worst last-snapshot HBM peak across hosts (lower is
    # better — footprint growth is the regression the OOM pre-mortem
    # exists for). Host RSS deliberately stays OUT of the verdict
    # surface: it moves a few percent between identical runs (allocator
    # noise), and a flaky REGRESSION teaches people to ignore the tool.
    # Numerics plane: distinct layers that produced a nonfinite
    # gradient, zero-filled whenever numerics ran so 0 -> N gets a
    # REGRESSION verdict instead of landing in only_b
    mem_last = (doc.get("memory") or {}).get("last") or {}
    peaks = [
        float(r["hbm_peak_bytes"]) for r in mem_last.values()
        if isinstance(r.get("hbm_peak_bytes"), (int, float))
    ]
    if peaks:
        out["hbm_peak_bytes"] = max(peaks)
    num = doc.get("numerics")
    if num is not None:
        out["nonfinite_layers"] = float(len(num.get("nonfinite_layers") or ()))
    # serve runs (doc/observability.md "Serving telemetry"): per-rung
    # latency/TTFT (lower is better) and goodput (higher), keyed by the
    # rung's OFFERED LOAD — not its index: two auto-calibrated sweeps
    # can land different rate ladders, and joining rung 3 of a 20 req/s
    # ladder against rung 3 of a 10 req/s ladder would judge a 2x-load
    # latency gap as a perf regression. Mismatched ladders instead fall
    # into only_a/only_b (visible, never a bogus verdict); pin
    # PADDLE_TPU_BENCH_SERVE_RATES for A/B runs. The knee rides as one
    # headline number either way. A run dir can carry both training and
    # serve telemetry — the key namespaces never collide.
    # per-replica fleet windows (carrying `replica`) are diagnostics,
    # not comparison units: N of them share one (engine, pipeline,
    # rate) per rung, and the MERGED replicas=N rollup is the record
    # the scaling curve joins on — keying the parts would mint
    # nondeterministic .rN qualifiers and bogus cross-replica diffs
    windows = [w for w in (doc.get("serve_windows") or [])
               if not w.get("replica")]
    seen_pre: set = set()
    # p99 tail-latency attribution (doc/observability.md "Distributed
    # tracing"): per-rate bucket shares reconstructed from the run's
    # span streams, ZERO-FILLED below so pre-tracing artifacts (no span
    # records) still share the keys — a 0 -> N queue-wait share then
    # gets a REGRESSION verdict instead of landing invisibly in only_b.
    # Joined on the same ".6g" offered-load format as the rung keys.
    from paddle_tpu.observability.tracing import (BUCKETS,
                                                  p99_shares_by_rate)

    # training-only dirs skip the trace pass (it would re-read every
    # stream just to find zero rungs)
    shares_by_rate = ({format(rate, ".6g"): s
                       for rate, s in p99_shares_by_rate(path).items()}
                      if windows else {})
    # deterministic key assignment: iterate (engine, rung)-sorted so a
    # both-engines stream always hands the SAME engine the unqualified
    # keys regardless of which sweep was recorded first — two such
    # artifacts then join engine-to-engine, never crosswise
    for w in sorted(windows,
                    key=lambda w: (str(w.get("engine") or ""),
                                   str(w.get("pipeline") or ""),
                                   str(w.get("spec") or ""),
                                   str(w.get("slot_dtype") or ""),
                                   int(w.get("replicas") or 0),
                                   str(w.get("transport") or ""),
                                   w.get("rung") if isinstance(
                                       w.get("rung"), int) else 0)):
        engine = w.get("engine") if isinstance(w.get("engine"), str) else None
        pipe = w.get("pipeline") if isinstance(w.get("pipeline"), str) else None
        tran = (w.get("transport")
                if isinstance(w.get("transport"), str) else None)
        pre = _serve_key(w.get("offered_rps"), w.get("rung", 0), seen_pre,
                         engine=engine, pipeline=pipe,
                         replicas=w.get("replicas"), transport=tran,
                         spec=(w.get("spec")
                               if isinstance(w.get("spec"), str) else None),
                         slot_dtype=(w.get("slot_dtype")
                                     if isinstance(w.get("slot_dtype"), str)
                                     else None))
        for snap_key, dst, scale in (
            ("latency", "p50_ms", 1e3), ("latency", "p99_ms", 1e3),
            ("ttft", "ttft_p50_ms", 1e3), ("ttft", "ttft_p99_ms", 1e3),
        ):
            q = "p99" if "p99" in dst else "p50"
            v = (w.get(snap_key) or {}).get(q)
            if isinstance(v, (int, float)):
                out[pre + dst] = float(v) * scale
        if isinstance(w.get("goodput_tok_s"), (int, float)):
            out[pre + "goodput_tok_s"] = float(w["goodput_tok_s"])
        if isinstance(w.get("queue_wait_share"), (int, float)):
            out[_engine_scoped(pre, engine, "queue_wait_share")] = float(
                w["queue_wait_share"])
        if isinstance(w.get("router_share"), (int, float)):
            # fleet rungs: the router's measured host-seconds share of
            # the window — the scaling curve's overhead axis
            out[_engine_scoped(pre, engine, "router_share")] = float(
                w["router_share"])
        # overload-defense rates, ZERO-FILLED when the window predates
        # them (pre-shed artifacts carry no `shed` field): both sides
        # then share the keys, and 0 -> N shed/error growth gets a
        # REGRESSION verdict instead of landing invisibly in only_b
        arrived = w.get("arrived")
        if isinstance(arrived, (int, float)) and arrived > 0:
            out[pre + "shed_rate"] = round(
                float(w.get("shed", 0) or 0) / float(arrived), 6)
            out[pre + "error_rate"] = round(
                float(w.get("errors", 0) or 0) / float(arrived), 6)
        # speculative-decode acceptance, ZERO-FILLED like shed_rate:
        # pre-speculation artifacts (no accept_rate field) still share
        # the key, and 0 -> N acceptance shows up as IMPROVED instead
        # of landing invisibly in only_b. Only the continuous engine
        # speculates — static windows stay 0 == 0 (SAME).
        if engine == "continuous":
            out[pre + "accept_rate"] = round(
                float(w.get("accept_rate", 0.0) or 0.0), 6)
        # engine-scoped like the other share metrics: a share of e2e is
        # only comparable within one latency regime
        shares = shares_by_rate.get(
            format(float(w.get("offered_rps") or 0.0), ".6g")) or {}
        for bucket in BUCKETS:
            out[_engine_scoped(pre, engine, f"p99_share.{bucket}")] = round(
                float(shares.get(bucket, 0.0)), 6)
    if windows:
        from paddle_tpu.observability.serving import saturation_knee

        knee = saturation_knee(windows)
        if knee is not None:
            out["serve_knee_rps"] = float(knee)
    return out


# ----------------------------------------------------------- bench sides


def _bench_lines(text: str) -> List[Dict[str, Any]]:
    """Bench result lines: the shared tolerant JSONL policy, narrowed
    to records carrying a ``metric`` key (driver tails mix result lines
    with free-form log output)."""
    return [rec for rec in obs.parse_record_lines(text) if "metric" in rec]


def _bench_side(path: str, raw: str) -> Dict[str, float]:
    """Comparable scalars of one bench artifact: the headline value plus
    every numeric leg/extras field (compile_s, cache-hit counts included
    — bench records carry them since the compile-telemetry PR)."""
    try:
        doc = json.loads(raw)
    except ValueError:
        doc = None
    if isinstance(doc, dict) and "tail" in doc and "metric" not in doc:
        # BENCH_*.json driver artifact: result lines live in the tail
        lines = _bench_lines(doc["tail"])
    elif isinstance(doc, dict) and "metric" in doc:
        lines = [doc]
    else:
        lines = _bench_lines(raw)
    good = [
        l for l in lines
        if l.get("metric") != "bench_failed"
        and isinstance(l.get("value"), (int, float))
    ]
    if not good:
        raise ValueError(f"no bench result line in {path!r}")
    line = good[-1]  # cumulative re-emits: the last line is most complete
    out: Dict[str, float] = {line["metric"]: float(line["value"])}
    if isinstance(line.get("mfu"), (int, float)):
        out["mfu"] = float(line["mfu"])
    # same quantity under the same name as the run-dir side: trace +
    # XLA compile together (a bench-vs-run comparison must not diff
    # two different definitions of "compile_total_s")
    if isinstance(line.get("compile_s"), (int, float)):
        out["compile_total_s"] = float(line["compile_s"]) + float(
            line.get("trace_s") or 0.0
        )
    # memory trajectory: a bench line stamps static_mem_bytes (the
    # compiled plan — deterministic, comparable) AND peak_hbm_bytes
    # (allocator peak). Only the static plan joins the verdict surface:
    # the allocator peak is cumulative over the PROCESS, so whatever
    # ran before the measured part (a larger attempt, a calibration
    # pass) is in it — diffing it would manufacture a phantom
    # footprint regression.
    v = line.get("static_mem_bytes")
    if isinstance(v, (int, float)) and not isinstance(v, bool):
        out["static_mem_bytes"] = float(v)
    # serve-leg artifacts (doc/observability.md "Serving telemetry"):
    # the archived BENCH_*.json carries per-rung latency/TTFT/goodput
    # and the knee — comparable WITHOUT the telemetry run dir, under
    # the same offered-load-keyed join as the run-dir side
    seen_pre: set = set()
    rungs = [(i, r) for i, r in enumerate(line.get("rungs") or [])
             if isinstance(r, dict)]
    # (engine, pipeline, replicas, transport, index)-sorted for the same
    # deterministic key assignment as the run-dir side (see _run_side)
    rungs.sort(key=lambda p: (str(p[1].get("engine") or ""),
                              str(p[1].get("pipeline") or ""),
                              str(p[1].get("spec") or ""),
                              str(p[1].get("slot_dtype") or ""),
                              int(p[1].get("replicas") or 0),
                              str(p[1].get("transport") or ""), p[0]))
    for i, r in rungs:
        engine = r.get("engine") if isinstance(r.get("engine"), str) else None
        pipe = r.get("pipeline") if isinstance(r.get("pipeline"), str) else None
        tran = (r.get("transport")
                if isinstance(r.get("transport"), str) else None)
        pre = _serve_key(r.get("offered_rps"), i, seen_pre, engine=engine,
                         pipeline=pipe, replicas=r.get("replicas"),
                         transport=tran,
                         spec=(r.get("spec")
                               if isinstance(r.get("spec"), str) else None),
                         slot_dtype=(r.get("slot_dtype")
                                     if isinstance(r.get("slot_dtype"), str)
                                     else None))
        for key in ("p50_ms", "p99_ms", "ttft_p50_ms", "ttft_p99_ms",
                    "goodput_tok_s"):
            v = r.get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[pre + key] = float(v)
        v = r.get("queue_wait_share")
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[_engine_scoped(pre, engine, "queue_wait_share")] = float(v)
        v = r.get("router_share")
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            # fleet rungs: measured router overhead share of the window
            out[_engine_scoped(pre, engine, "router_share")] = float(v)
        # zero-filled like the run-dir side: pre-shed bench artifacts
        # (no shed_rate field) still join, with 0 -> N judged
        for key in ("shed_rate", "error_rate"):
            v = r.get(key)
            out[pre + key] = (
                float(v)
                if isinstance(v, (int, float)) and not isinstance(v, bool)
                else 0.0
            )
        # draft acceptance, zero-filled on continuous rungs like the
        # run-dir side (0 -> N = IMPROVED, never only_b); per-slot
        # state bytes (memory_analysis stamp, the bf16 proof surface)
        # ride conditionally — zero-filling them would mint a phantom
        # "bytes went to 0" IMPROVED verdict against pre-stamp artifacts
        if engine == "continuous":
            v = r.get("accept_rate")
            out[pre + "accept_rate"] = (
                float(v)
                if isinstance(v, (int, float)) and not isinstance(v, bool)
                else 0.0
            )
        v = r.get("slot_bytes")
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[pre + "slot_bytes"] = float(v)
    if isinstance(line.get("knee_rps"), (int, float)):
        out["serve_knee_rps"] = float(line["knee_rps"])
    # headline per-slot state bytes (bf16 slot-state A/B): lower is
    # better via the "_bytes" suffix rule
    v = line.get("slot_bytes")
    if isinstance(v, (int, float)) and not isinstance(v, bool):
        out["slot_bytes"] = float(v)
    for leg, payload in (line.get("legs") or {}).items():
        if isinstance(payload, dict) and isinstance(
            payload.get("value"), (int, float)
        ):
            out[leg] = float(payload["value"])
            # peak_hbm_bytes deliberately NOT copied — see the
            # process-cumulative note above
            for key in ("mfu", "compile_s", "trace_s", "static_mem_bytes"):
                v = payload.get(key)
                # bool is an int subclass — exclude it explicitly
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    out[f"{leg}.{key}"] = float(v)
            hit = payload.get("compile_cache_hit")
            if isinstance(hit, bool):
                out[f"{leg}.cache_hits"] = 1.0 if hit else 0.0
    return out


# ------------------------------------------------------------ lint sides


def _lint_side(raw: str) -> Optional[Dict[str, float]]:
    """Comparable scalars of a ``paddle lint --json`` artifact, or None
    when the text carries no lint records (so bench/run detection can
    proceed). Counts are NEW (non-baselined) findings; per-rule keys
    are zero-filled from the summary's rule list so both sides share
    every rule key and 0 -> N drift gets a verdict (new-findings
    regression => exit 1) instead of landing in only_b."""
    recs = list(obs.parse_record_lines(raw))
    summaries = [r for r in recs if r.get("kind") == "lint_summary"]
    if summaries:
        s = summaries[-1]  # re-run appended to the same file: last wins
        counts = s.get("counts") or {}
        out = {"lint_findings": float(s.get("findings") or 0)}
        for rid in (s.get("rules") or sorted(counts)):
            out[f"lint.{rid}"] = float(counts.get(rid, 0))
        return out
    findings = [r for r in recs if r.get("kind") == "lint_finding"]
    if findings:
        # summary-less stream (filtered/truncated): count what's there
        out = {"lint_findings": 0.0}
        for r in findings:
            if r.get("baselined"):
                continue
            out["lint_findings"] += 1.0
            key = f"lint.{r.get('rule', '?')}"
            out[key] = out.get(key, 0.0) + 1.0
        return out
    return None


def _race_side(raw: str) -> Optional[Dict[str, float]]:
    """Comparable scalars of a ``paddle race --json`` artifact (None
    when the text carries no race records): total + per-detector NEW
    finding counts, zero-filled from the summary's detector list so
    both sides share every key and 0 -> N drift gets a REGRESSION
    verdict instead of landing in only_b — the exact shape of the lint
    diff above, for the dynamic analyzer."""
    recs = list(obs.parse_record_lines(raw))
    summaries = [r for r in recs if r.get("kind") == "race_summary"]
    if summaries:
        s = summaries[-1]  # re-run appended to the same file: last wins
        counts = s.get("counts") or {}
        out = {"race_findings": float(s.get("findings") or 0)}
        for det in (s.get("detectors") or sorted(counts)):
            out[f"race.{det}"] = float(counts.get(det, 0))
        return out
    findings = [r for r in recs if r.get("kind") == "race_finding"]
    if findings:
        out = {"race_findings": 0.0}
        for r in findings:
            if r.get("baselined"):
                continue
            out["race_findings"] += 1.0
            key = f"race.{r.get('detector', '?')}"
            out[key] = out.get(key, 0.0) + 1.0
        return out
    return None


def _probe_lint(path: str) -> bool:
    """O(1) probe for a lint/race artifact — a multi-hundred-MB run
    stream must NOT be read (let alone JSON-parsed) just to learn it is
    not one (read_records streams it later). `paddle lint --json` and
    `paddle race --json` write their record kinds in the very first
    line, so the first 64 KB decide."""
    try:
        with open(path) as f:
            head = f.read(65536)
    except OSError:
        return False
    return any(marker in head for marker in (
        '"lint_summary"', '"lint_finding"',
        '"race_summary"', '"race_finding"',
    ))


def load_side(path: str) -> Dict[str, float]:
    if os.path.isfile(path):
        if path.endswith(".jsonl") and not _probe_lint(path):
            pass  # run stream: fall through to the streaming analyzer
        else:
            # ONE read serves all file-artifact detectors (lint, race,
            # bench)
            with open(path) as f:
                raw = f.read()
            lint = _lint_side(raw)
            if lint is not None:
                return lint
            race = _race_side(raw)
            if race is not None:
                return race
            if not path.endswith(".jsonl"):
                return _bench_side(path, raw)
    if not obs.metrics_files(path):
        raise ValueError(
            f"{path!r} is neither a bench artifact nor a run dir with "
            "metrics*.jsonl"
        )
    return _run_side(path)


# --------------------------------------------------------------- compare


def compare(a: Dict[str, float], b: Dict[str, float],
            threshold: float = 0.05,
            abs_floor: float = 0.05) -> Dict[str, Any]:
    rows = []
    regressions, improvements = [], []
    for name in sorted(set(a) & set(b)):
        va, vb = a[name], b[name]
        delta = (vb - va) / abs(va) if va else (0.0 if vb == va else float("inf"))
        hb = _higher_is_better(name)
        # a zero baseline makes every nonzero delta infinite — the
        # relative threshold can never absorb it, so sub-`abs_floor`
        # absolute movement (metric units) stays noise instead of an
        # automatic verdict (0 -> 0.002 s of ckpt block is not a
        # regression; 0 -> 4 cache hits still registers)
        if abs(delta) <= threshold or (va == 0 and abs(vb) <= abs_floor):
            verdict = "SAME"
        elif (delta > 0) == hb:
            verdict = "IMPROVED"
            improvements.append((name, delta))
        else:
            verdict = "REGRESSION"
            regressions.append((name, delta))
        rows.append({
            "metric": name, "a": va, "b": vb,
            "delta": None if delta == float("inf") else round(delta, 4),
            "higher_is_better": hb, "verdict": verdict,
        })
    if regressions:
        verdict = "REGRESSION"
    elif improvements:
        verdict = "IMPROVED"
    else:
        verdict = "NO CHANGE"
    return {
        "threshold": threshold,
        "metrics": rows,
        "only_a": sorted(set(a) - set(b)),
        "only_b": sorted(set(b) - set(a)),
        "regressions": [n for n, _ in regressions],
        "improvements": [n for n, _ in improvements],
        "verdict": verdict,
    }


def format_comparison(doc: Dict[str, Any], label_a: str, label_b: str) -> str:
    lines = [
        f"# compare: A={label_a}  B={label_b}  "
        f"(noise threshold {doc['threshold'] * 100:.1f}%)",
        f"{'metric':<36} {'A':>12} {'B':>12} {'delta':>8} {'verdict':>11}",
    ]
    for row in doc["metrics"]:
        d = row["delta"]
        lines.append(
            f"{row['metric']:<36} {row['a']:>12.4g} {row['b']:>12.4g} "
            f"{'inf' if d is None else format(d * 100, '+.1f') + '%':>8} "
            f"{row['verdict']:>11}"
        )
    for side, names in (("A", doc["only_a"]), ("B", doc["only_b"])):
        if names:
            lines.append(f"only in {side}: {', '.join(names)}")
    detail = ""
    if doc["regressions"]:
        detail = f" ({', '.join(doc['regressions'])})"
    elif doc["improvements"]:
        detail = f" ({', '.join(doc['improvements'])})"
    lines.append(f"verdict: {doc['verdict']}{detail}")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="paddle compare",
        description="diff two run dirs or bench artifacts with a "
                    "noise-thresholded regression verdict",
    )
    p.add_argument("run_a", help="baseline: run dir, metrics*.jsonl, or "
                                 "BENCH_*.json")
    p.add_argument("run_b", help="candidate: same shapes as run_a")
    p.add_argument("--threshold", type=float, default=0.05,
                   help="relative noise threshold (default 0.05 = 5%%)")
    p.add_argument("--abs-floor", type=float, default=0.05, dest="abs_floor",
                   help="absolute noise floor (metric units) for "
                        "zero-baseline metrics, where every nonzero "
                        "delta is infinite (default 0.05)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit the comparison as JSON")
    args = p.parse_args(argv)

    try:
        a, b = load_side(args.run_a), load_side(args.run_b)
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if not set(a) & set(b):
        print("error: the two sides share no comparable metrics "
              f"(A has {sorted(a)}, B has {sorted(b)})", file=sys.stderr)
        return 2
    doc = compare(a, b, threshold=args.threshold, abs_floor=args.abs_floor)
    if args.as_json:
        print(json.dumps(doc, indent=2))
    else:
        print(format_comparison(doc, args.run_a, args.run_b))
    return 1 if doc["verdict"] == "REGRESSION" else 0


if __name__ == "__main__":
    sys.exit(main())
