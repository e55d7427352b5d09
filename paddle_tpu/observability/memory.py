"""HBM accounting + OOM pre-mortem forensics (`paddle memory`).

The signature workloads — high-dimensional sparse embeddings, variable
length sequence batches — live and die by device memory, yet until this
module the telemetry stack could see time (spans), compute cost
(compile/roofline records), and requests, but not a single byte of HBM:
an OOM was a raw ``XlaRuntimeError`` with no forensics, and "will this
batch size fit" was answered by trying it. Three planes close the gap:

- **static** — every launch-group compilation's
  ``compiled.memory_analysis()`` (argument/output/temp/generated-code
  bytes) is joined onto its ``kind=compile`` record by the
  CompileRegistry (:func:`memory_analysis_of`), so the per-group
  footprint XLA *planned* is on disk before the first step runs;
- **live** — :func:`sample_and_emit` reads ``device.memory_stats()``
  (in-use / cumulative-peak / limit, summed over local devices) plus
  the host RSS at pass boundaries into ``kind=memory`` records and the
  ``mem.hbm_peak_bytes`` / ``mem.hbm_in_use_bytes`` /
  ``mem.host_rss_bytes`` gauges. Backends without allocator stats (the
  CPU backend returns None) degrade to host-RSS-only records with a
  one-time log line — never a crash, never a schema-invalid record;
- **post-mortem** — :func:`trigger_oom_report` writes
  ``oom_report.json`` (static footprint ranked per group, the last
  live snapshot, the telemetry tail + last barrier skew) when a launch
  dies of RESOURCE_EXHAUSTED, mirroring the hang_report flow including
  its write-failure backstop: the report write itself may need memory
  or a wedged fs, so a backstop timer guarantees ``EXIT_OOM`` (20)
  regardless. Supervisors treat 20 as budget-consuming — an OOM loop
  is deterministic poison, not scheduling, and must not restart for
  free.

``paddle memory <run_dir>`` reads it all back jax-free (like `paddle
metrics`): the per-launch-group static table, live peak/headroom vs the
measured allocator limit (or the chip capacity table in
``ops/kernel_flops.py`` when the allocator reported none), and a
rendering of any ``oom_report.json`` found in the run dir.

Usage::

    paddle memory <run_dir | metrics.jsonl> [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

from paddle_tpu.observability import metrics as obs
from paddle_tpu.resilience import EXIT_OOM
from paddle_tpu.utils import concurrency as cc
from paddle_tpu.utils.logging import logger

OOM_REPORT = "oom_report.json"

# same hard deadline as hangwatch.FORENSICS_DEADLINE_S, same reason: an
# OOM'd process may fail ITS OWN forensics (the report write can need
# memory; the run dir can live on the fs that is part of the problem),
# so a backstop timer guarantees the distinct exit code regardless
FORENSICS_DEADLINE_S = 30.0

__all__ = [
    "OOM_REPORT", "EXIT_OOM", "SyntheticOomError", "is_oom_error",
    "memory_analysis_of", "device_memory_stats", "host_rss_bytes",
    "sample_memory", "sample_and_emit", "build_oom_report",
    "trigger_oom_report", "main",
]


# ------------------------------------------------------------ OOM typing


class SyntheticOomError(RuntimeError):
    """The `trainer.oom` fault site's deterministic stand-in for a real
    device OOM: the message carries the canonical RESOURCE_EXHAUSTED
    marker so :func:`is_oom_error` (and any operator tooling grepping
    logs) classifies it exactly like the XlaRuntimeError it simulates."""

    def __init__(self, info: str = ""):
        detail = f" ({info})" if info else ""
        super().__init__(
            "RESOURCE_EXHAUSTED: out of memory "
            f"[synthetic — injected at trainer.oom{detail}]"
        )


def is_oom_error(e: BaseException) -> bool:
    """True only for device-memory exhaustion. The match is message-based
    (XlaRuntimeError carries no typed subclass for it) and deliberately
    narrow: a shape bug must crash loudly, not masquerade as an OOM
    pre-mortem."""
    msg = f"{type(e).__name__}: {e}".lower()
    return any(
        s in msg
        for s in ("resource_exhausted", "resource exhausted",
                  "out of memory", "failed to allocate")
    )


# --------------------------------------------------------- static plane


def memory_analysis_of(compiled) -> Optional[Dict[str, int]]:
    """Static memory plan of one compiled executable as ``mem_*_bytes``
    fields, or None. Graceful by the cost_analysis_of covenant: backends
    without memory analysis, raising calls, and missing attributes all
    collapse to None/absent keys — accounting must never be able to
    break training."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    out: Dict[str, int] = {}
    for attr, key in (
        ("argument_size_in_bytes", "mem_arg_bytes"),
        ("output_size_in_bytes", "mem_out_bytes"),
        ("temp_size_in_bytes", "mem_temp_bytes"),
        ("alias_size_in_bytes", "mem_alias_bytes"),
        ("generated_code_size_in_bytes", "mem_code_bytes"),
    ):
        v = getattr(ma, attr, None)
        if isinstance(v, (int, float)) and v >= 0:
            out[key] = int(v)
    if not out:
        return None
    # aliased buffers (donated inputs reused as outputs) are counted on
    # both sides of the plan — subtract them once so the total is the
    # planner's actual footprint, clamped at 0 for odd backends
    out["mem_total_bytes"] = max(
        out.get("mem_arg_bytes", 0)
        + out.get("mem_out_bytes", 0)
        + out.get("mem_temp_bytes", 0)
        + out.get("mem_code_bytes", 0)
        - out.get("mem_alias_bytes", 0),
        0,
    )
    return out


# ----------------------------------------------------------- live plane

_warned_no_device_stats = False


def device_memory_stats() -> Optional[Dict[str, int]]:
    """Live allocator stats summed over the local devices:
    ``{bytes_in_use, peak_bytes_in_use, bytes_limit?, devices}``, or
    None when the backend reports none (the CPU backend's
    ``memory_stats()`` is None) or jax is absent entirely. The one-time
    degradation log keeps the silence diagnosable without spamming
    every pass boundary."""
    global _warned_no_device_stats
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return None
    in_use = peak = limit = 0
    seen = 0
    for d in devices:
        try:
            s = d.memory_stats()
        except Exception:
            s = None
        if not s:
            continue
        seen += 1
        in_use += int(s.get("bytes_in_use", 0) or 0)
        peak += int(s.get("peak_bytes_in_use", 0) or 0)
        limit += int(s.get("bytes_limit", 0) or 0)
    if not seen:
        if not _warned_no_device_stats:
            _warned_no_device_stats = True
            logger.info(
                "device memory stats unavailable on this backend "
                "(memory_stats() is empty — CPU?) — kind=memory records "
                "carry host RSS only"
            )
        return None
    out = {"bytes_in_use": in_use, "peak_bytes_in_use": peak,
           "devices": seen}
    if limit:
        out["bytes_limit"] = limit
    return out


def host_rss_bytes() -> int:
    """Current resident set size of this process. /proc when available
    (live value); ru_maxrss (the PEAK, linux kB) as the portable
    fallback — a number is always returned, so the host half of a
    memory record can never be absent."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource

        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:
        return 0


def sample_memory() -> Dict[str, Any]:
    """One live snapshot: host RSS always; HBM fields only when the
    backend reports them (validate_record requires only the host
    field, so a CPU run's records stay schema-clean)."""
    snap: Dict[str, Any] = {"host_rss_bytes": host_rss_bytes()}
    dev = device_memory_stats()
    if dev is not None:
        snap["hbm_in_use_bytes"] = dev["bytes_in_use"]
        snap["hbm_peak_bytes"] = dev["peak_bytes_in_use"]
        if "bytes_limit" in dev:
            snap["hbm_limit_bytes"] = dev["bytes_limit"]
        snap["devices"] = dev["devices"]
    return snap


def sample_and_emit(pass_id: Optional[int] = None,
                    step: Optional[int] = None) -> Dict[str, Any]:
    """Sample + publish: the gauges ride the next ``pass_end`` counters
    snapshot, the ``kind=memory`` record is the per-boundary trajectory
    `paddle memory`/`compare` read. Called synchronously at pass
    boundaries (allocator stats are a host-side C call — no device
    sync, no daemon thread to race)."""
    snap = sample_memory()
    r = obs.registry()
    r.gauge("mem.host_rss_bytes").set(snap["host_rss_bytes"])
    if "hbm_peak_bytes" in snap:
        r.gauge("mem.hbm_peak_bytes").set(snap["hbm_peak_bytes"])
        r.gauge("mem.hbm_in_use_bytes").set(snap["hbm_in_use_bytes"])
    obs.emit("memory", pass_id=pass_id, step=step, **snap)
    return snap


# ---------------------------------------------------------- pre-mortem


def build_oom_report(
    report_dir: str,
    error: BaseException,
    groups: Optional[List[Dict[str, Any]]] = None,
    live: Optional[Dict[str, Any]] = None,
    where: Optional[Dict[str, Any]] = None,
    device_kind: str = "",
) -> Dict[str, Any]:
    """The pre-mortem document: which launch groups XLA planned to be
    big (ranked), what the allocator looked like at the last boundary,
    and the telemetry tail — everything "why did this rank die of OOM"
    needs, from the run dir alone."""
    groups = sorted(
        groups or [],
        key=lambda g: -int(g.get("mem_total_bytes", 0) or 0),
    )
    report: Dict[str, Any] = {
        "reason": "oom",
        "error": str(error)[:4000],
        "error_type": type(error).__name__,
        "where": where or {},
        "device_kind": device_kind,
        "groups": groups,
        "static_total_bytes": sum(
            int(g.get("mem_total_bytes", 0) or 0) for g in groups
        ),
        "live": live,
        "pid": os.getpid(),
        "written_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    }
    # same post-mortem evidence shape as hang_report.json / the
    # supervisor's crash report — one shared helper, no drift
    try:
        tails, skew = obs.tail_with_last_skew(report_dir, n=25)
        report["metrics_tail"] = tails
        report["barrier_skew"] = skew
    except Exception as e:  # forensics best-effort, never masks the OOM
        report["metrics_tail_error"] = str(e)
    return report


def write_oom_report(report_dir: str, report: Dict[str, Any]) -> str:
    path = os.path.join(report_dir or ".", OOM_REPORT)
    try:
        os.makedirs(report_dir or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(report, f, indent=2, default=str)
        os.replace(tmp, path)  # readers never see a torn report
    except OSError as e:
        logger.error("oom pre-mortem: could not write %s: %s", path, e)
    return path


def trigger_oom_report(
    report_dir: str,
    error: BaseException,
    groups: Optional[List[Dict[str, Any]]] = None,
    live: Optional[Dict[str, Any]] = None,
    where: Optional[Dict[str, Any]] = None,
    device_kind: str = "",
    exit_fn: Optional[Callable[[int], None]] = None,
) -> str:
    """Write the pre-mortem with the hang-report discipline: announce,
    arm the backstop, write, flush the evidence record, disarm.

    Unlike hangwatch (a daemon thread whose only exit is ``os._exit``),
    the caller here is the step loop itself — on the normal path the
    report lands, the ``kind=oom`` record flushes, and the original
    error is re-raised by the caller (the CLI maps it to
    :data:`EXIT_OOM`). ``exit_fn`` (``os._exit`` in production) backs
    that path up: if the forensics themselves wedge — the report write
    blocking on a dead fs, the tail scan thrashing a memory-starved
    host — the timer still exits 20 within FORENSICS_DEADLINE_S, so the
    supervisor sees a *classified* death either way."""
    path = os.path.join(report_dir or ".", OOM_REPORT)
    logger.error(
        "device OOM (%s) — writing pre-mortem %s, then exiting %d: %s",
        type(error).__name__, path, EXIT_OOM, str(error)[:500],
    )
    backstop = None
    if exit_fn is not None:
        backstop = cc.Timer(FORENSICS_DEADLINE_S, exit_fn, args=(EXIT_OOM,))
        backstop.daemon = True
        backstop.start()
    report = build_oom_report(
        report_dir, error, groups=groups, live=live, where=where,
        device_kind=device_kind,
    )
    path = write_oom_report(report_dir, report)
    obs.registry().counter("ooms.detected").inc()
    obs.emit(
        "oom",
        pass_id=(where or {}).get("pass"),
        step=(where or {}).get("step"),
        error=str(error)[:500],
        report=path,
        static_total_bytes=report["static_total_bytes"],
    )
    obs.flush()  # the caller is about to die — same discipline as faults
    if backstop is not None:
        backstop.cancel()
    return path


# ------------------------------------------------------ jax-free reader


def collect(streams: Dict[int, List[Dict[str, Any]]]) -> Dict[str, Any]:
    """Memory view of merged metrics streams: the static per-group table
    (from ``kind=compile`` records carrying memory analysis, latest-wins
    per (host, group, sig) like the roofline dedupe) and the last live
    ``kind=memory`` snapshot per host."""
    latest_static: Dict[tuple, Dict[str, Any]] = {}
    live_by_host: Dict[int, Dict[str, Any]] = {}
    device_kind = ""
    for host in sorted(streams):
        for rec in streams[host]:
            kind = rec.get("kind")
            if kind == "compile" and "mem_total_bytes" in rec:
                latest_static[(host, rec.get("group"), rec.get("sig"))] = rec
            elif kind == "memory":
                live_by_host[int(rec.get("host", host))] = rec
            elif kind == "roofline" and rec.get("device_kind"):
                device_kind = rec["device_kind"]
    groups: Dict[tuple, Dict[str, Any]] = {}
    for (_h, group, sig), rec in latest_static.items():
        # one host's plan is authoritative (SPMD compiles identically);
        # keep the largest if hosts ever disagree
        key = (group, sig)
        if key not in groups or rec.get("mem_total_bytes", 0) > groups[key].get(
            "mem_total_bytes", 0
        ):
            groups[key] = {
                "group": group,
                "sig": sig,
                **{k: rec[k] for k in rec if k.startswith("mem_")},
            }
    rows = sorted(
        groups.values(), key=lambda r: -int(r.get("mem_total_bytes", 0))
    )
    return {
        "groups": rows,
        "static_total_bytes": sum(
            int(r.get("mem_total_bytes", 0)) for r in rows
        ),
        "live": {h: live_by_host[h] for h in sorted(live_by_host)},
        "device_kind": device_kind,
    }


def _capacity_bytes(doc: Dict[str, Any]) -> Optional[int]:
    """Device HBM capacity for headroom math: the measured allocator
    limit when any host reported one, else the chip capacity table
    (never guessed for unknown device kinds). Both sides of the
    peak-vs-capacity ratio are PER HOST: the records sum peak over
    local devices, so the table fallback must scale by the recorded
    device count or a 4-chip host would read >100% utilization."""
    limits = [
        int(rec["hbm_limit_bytes"])
        for rec in doc["live"].values()
        if isinstance(rec.get("hbm_limit_bytes"), int)
    ]
    if limits:
        return max(limits)
    from paddle_tpu.ops.kernel_flops import peak_hbm_gb

    cap = peak_hbm_gb(doc.get("device_kind", ""))
    if not cap:
        return None
    devices = max(
        (int(rec.get("devices", 1) or 1) for rec in doc["live"].values()),
        default=1,
    )
    return int(cap * 1e9) * devices


def read_oom_report(run_dir: str) -> Optional[Dict[str, Any]]:
    from paddle_tpu.resilience.hangwatch import run_dir_of

    path = os.path.join(run_dir_of(run_dir), OOM_REPORT)
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _mb(v) -> str:
    return f"{v / 1e6:.2f}" if isinstance(v, (int, float)) else "-"


def _gb(v) -> str:
    return f"{v / 1e9:.2f} GB" if isinstance(v, (int, float)) else "-"


def format_report(doc: Dict[str, Any],
                  oom: Optional[Dict[str, Any]] = None) -> str:
    lines: List[str] = []
    if doc["groups"]:
        lines.append(
            "static footprint per launch group (XLA memory analysis at "
            "compile time):"
        )
        lines.append(
            f"{'group':<12} {'sig':<10} {'args MB':>9} {'out MB':>9} "
            f"{'temp MB':>9} {'total MB':>9}"
        )
        for r in doc["groups"]:
            lines.append(
                f"{str(r.get('group', '?')):<12} {str(r.get('sig', '?')):<10} "
                f"{_mb(r.get('mem_arg_bytes')):>9} "
                f"{_mb(r.get('mem_out_bytes')):>9} "
                f"{_mb(r.get('mem_temp_bytes')):>9} "
                f"{_mb(r.get('mem_total_bytes')):>9}"
            )
        lines.append(
            f"static total: {_mb(doc['static_total_bytes'])} MB over "
            f"{len(doc['groups'])} group(s)"
        )
    else:
        lines.append(
            "no static memory analysis in this run's compile records "
            "(pre-memory-telemetry run, or the backend provides none)"
        )
    if doc["live"]:
        lines.append("")
        lines.append("live memory (last sample per host):")
        cap = _capacity_bytes(doc)
        for h, rec in doc["live"].items():
            peak = rec.get("hbm_peak_bytes")
            if isinstance(peak, int):
                line = (
                    f"host {h}: hbm peak {_gb(peak)}, in use "
                    f"{_gb(rec.get('hbm_in_use_bytes'))}"
                )
                if cap:
                    line += (
                        f", capacity {_gb(cap)} (peak {peak / cap * 100:.1f}%"
                        f", headroom {_gb(max(cap - peak, 0))})"
                    )
                line += f"; host RSS {_gb(rec.get('host_rss_bytes'))}"
            else:
                line = (
                    f"host {h}: host RSS {_gb(rec.get('host_rss_bytes'))} "
                    "(device stats unavailable on this backend)"
                )
            lines.append(line)
    if oom is not None:
        lines.append("")
        err = str(oom.get("error", "")).splitlines()
        top = (oom.get("groups") or [{}])[0]
        lines.append(
            f"! OOM pre-mortem ({OOM_REPORT}, written {oom.get('written_at', '?')}): "
            f"{err[0] if err else '?'}"
        )
        if top.get("group"):
            lines.append(
                f"  largest static group: {top['group']} "
                f"({_mb(top.get('mem_total_bytes'))} MB planned)"
            )
        live = oom.get("live") or {}
        if isinstance(live.get("hbm_peak_bytes"), int):
            lines.append(
                f"  last live snapshot: hbm peak {_gb(live['hbm_peak_bytes'])}, "
                f"in use {_gb(live.get('hbm_in_use_bytes'))}"
            )
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="paddle memory",
        description="per-launch-group HBM accounting + live memory "
                    "trajectory + OOM pre-mortem rendering from a run's "
                    "telemetry",
    )
    p.add_argument("run_dir", help="run dir (or one metrics*.jsonl file)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit the analysis as JSON")
    args = p.parse_args(argv)

    from paddle_tpu.observability.analyze import load_run

    files = obs.metrics_files(args.run_dir)
    oom = read_oom_report(args.run_dir)
    if not files and oom is None:
        print(f"no metrics*.jsonl (or {OOM_REPORT}) under {args.run_dir!r} "
              "(was the run started with --metrics_path / --save_dir?)",
              file=sys.stderr)
        return 1
    doc = collect(load_run(args.run_dir)) if files else {
        "groups": [], "static_total_bytes": 0, "live": {}, "device_kind": "",
    }
    if not doc["groups"] and not doc["live"] and oom is None:
        print("no memory telemetry in this run's streams "
              "(pre-memory-telemetry run, or it never finished a pass)",
              file=sys.stderr)
        return 1
    if args.as_json:
        doc["oom_report"] = oom
        print(json.dumps(doc, indent=2, default=str))
    else:
        print(f"# memory: {', '.join(files) if files else args.run_dir}")
        print(format_report(doc, oom))
    return 0


if __name__ == "__main__":
    sys.exit(main())
