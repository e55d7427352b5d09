"""Per-launch-group compile telemetry + the persistent compilation cache.

Every distinct launch group the trainer dispatches (fused step, single
step, test forward, generator — one per batch-shape signature) costs a
trace + an XLA compile the first time it runs, and costs it AGAIN on
every process restart: the elastic/preemption machinery made restarts
frequent, which made recompilation a first-order throughput tax nobody
could see (ROADMAP item 5). This module makes every compile a schema
record and makes the cache persistent:

- :class:`CompileRegistry` AOT-compiles each (group, signature) once
  via ``fn.lower(...).compile()`` — timing the trace and the compile
  separately — pulls XLA's cost analysis off the executable
  (``observability/costs.py``), and emits a ``kind=compile`` record
  (trace_s, compile_s, recompile count, cache hit/miss, FLOPs, bytes).
  Callables without ``.lower`` (the mesh-sharded step closures, plain
  python) degrade to timing the first dispatch as one combined number
  (``mode="inline"``) — the telemetry never loses a compile, it just
  reports it coarser.
- :func:`enable_compile_cache` wires jax's persistent compilation cache
  to the ONE directory :func:`resolve_cache_dir` names
  (``JAX_COMPILATION_CACHE_DIR``, else ``--compile_cache_dir``, else a
  fixed path in the checkout): warm restarts skip the XLA backend
  compile, and the compile records prove it (``cache_hit=true``, lower
  ``time_to_first_step_s`` in the PR-6 ``restart`` record).
- The registry also accumulates per-group execution time
  (:meth:`CompileRegistry.note_exec`) and emits ``kind=roofline``
  records at pass end — the raw material of ``paddle roofline``.

- One listener on jax's own compile events (:func:`_on_jax_event`) keeps
  the process-wide ``jax.*`` counters: the registry's spans see its
  launch groups, the counters see EVERY jit of the process.

Cache-hit detection is host-side and observational: a compile that
consults the persistent cache writes a new ``*-cache`` entry on a miss
and writes nothing on a hit, so counting entries around the compile
classifies it without reaching into jax internals. Single-process
precise; on a pod several hosts may race the same entry — the records
stay per-host honest ("this host's compile did not add an entry").
"""

from __future__ import annotations

import collections
import hashlib
import os
import re
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from paddle_tpu.observability import metrics as obs
from paddle_tpu.utils.device import device_stamp
from paddle_tpu.utils.logging import logger
from paddle_tpu.utils.stats import stat_timer

# the enabled persistent-cache dir ("" = off) — module state, one per
# process, matching jax's own process-global cache config
_cache_dir: str = ""

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# the cache's path is part of its key, so the default never moves: one
# directory inside the checkout (git-ignored), the same for every entry
# point and every process — never a temp dir, a pid or a timestamp
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def resolve_cache_dir(flag_dir: str = "") -> str:
    """THE place the persistent compilation cache lives, for every entry
    point (`paddle train|serve`, bench.py, chip_smoke.py, the tests):
    where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory and no
    other — the machine's owner placed the cache, and no flag overrides
    it; otherwise the caller's ``--compile_cache_dir``; otherwise the
    fixed in-checkout :data:`DEFAULT_CACHE_DIR`."""
    return os.environ.get(CACHE_DIR_ENV) or flag_dir or DEFAULT_CACHE_DIR


def enable_compile_cache(flag_dir: str = "") -> bool:
    """Point jax's persistent compilation cache at
    :func:`resolve_cache_dir` (created if missing) — call before the
    first compile. On by default everywhere but on an explicit
    ``JAX_PLATFORMS=cpu`` run, which gets it only by naming a directory
    (variable or flag). Also drops the min-compile-time/entry-size gates so
    even fast CPU-backend compiles populate the cache — without that,
    smoke-scale steps would never cache and a warm restart would measure
    nothing. Returns True when the cache is active; never raises
    (telemetry must not take down the run it observes)."""
    global _cache_dir
    named = os.environ.get(CACHE_DIR_ENV) or flag_dir
    if not named and os.environ.get("JAX_PLATFORMS") == "cpu":
        # an explicit CPU run (the tests, debugging) gets no cache it did
        # not ask for by name: XLA:CPU in jax 0.9.0 logs ~8 KB of
        # target-feature complaints for EVERY cached executable it loads
        # — a flood on stderr that stalls a child whose parent does not
        # drain the pipe — and test-sized CPU compiles are fast anyway
        return False
    cache_dir = named or DEFAULT_CACHE_DIR
    try:
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _cache_dir = cache_dir
        logger.info("persistent compilation cache: %s", cache_dir)
        return True
    except Exception as e:
        logger.warning(
            "persistent compilation cache unavailable (%s): %s", cache_dir, e
        )
        return False


def cache_dir() -> str:
    return _cache_dir


def _cache_entries() -> Optional[int]:
    """Number of compiled-executable entries in the persistent cache
    (None = cache off/unreadable)."""
    if not _cache_dir:
        return None
    try:
        return sum(1 for f in os.listdir(_cache_dir) if f.endswith("-cache"))
    except OSError:
        return None


def cache_probe() -> Callable[[], Optional[bool]]:
    """Snapshot for hit detection: call BEFORE a compile, call the
    returned closure after — True = hit (no new cache entry written),
    False = miss, None = cache disabled/unreadable."""
    before = _cache_entries()

    def hit() -> Optional[bool]:
        after = _cache_entries()
        if before is None or after is None:
            return None
        return after == before

    return hit


# jax's own events -> the registry's cumulative counters (seconds). They
# fire for EVERY jit of the process: the parameter initialisers, a
# caller's own jitted helpers, the flops count's `make_jaxpr`, the second
# compile of an AOT fallback — none of which passes `_first_call`
_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower_s",
    "/jax/core/compile/backend_compile_duration": "jax.backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_load_s",
}


class _ClosedEvents(threading.local):
    """This thread's closed events that no later event has enclosed yet,
    oldest first, as (start, seconds)."""

    KEPT = 4096

    def __init__(self):
        self.events: list = []


_closed = _ClosedEvents()


def _on_jax_event(event: str, duration: float, **_kwargs) -> None:
    """THE listener of jax's compile events. jax's events nest — a jit
    traced inside another's trace fires inside it, an eager op on a
    constant compiles inside a trace, a load from the persistent cache is
    inside ``backend_compile_duration`` — and an event arrives when it
    closes, with its seconds only. Each counter gets its events' SELF
    time (the seconds less those of the events that closed inside it, on
    this thread), so the four are disjoint and their sum is the process's
    seconds under any of them, none twice. ``jax.compiles`` counts the
    executables built or loaded."""
    name = _JAX_EVENTS.get(event)
    if name is None:
        return
    start = time.perf_counter() - duration
    closed = _closed.events
    inside = 0.0
    while closed and closed[-1][0] >= start:
        inside += closed.pop()[1]
    closed.append((start, duration))
    if len(closed) > _closed.KEPT:
        del closed[:_closed.KEPT // 2]
    r = obs.registry()
    r.counter(name).inc(max(duration - inside, 0.0))
    if name == "jax.backend_compile_s":
        r.counter("jax.compiles").inc()


jax.monitoring.register_event_duration_secs_listener(_on_jax_event)


_MOSAIC_RE = re.compile(
    r"= (.*?) custom-call\(.*custom_call_target=\"tpu_custom_call\"")
_COLLECTIVE_RE = re.compile(
    r" (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def hlo_census(compiled, text: str) -> Dict[str, Any]:
    """What the optimized (and, on a mesh, partitioned) program of one
    AOT executable contains (``text`` is its ``as_text()``) — the only
    proof a kernel or a collective is really in the step, since every
    kernel selection upstream is silent:

    - ``mosaic_calls``: Pallas/Mosaic kernels (``tpu_custom_call``), and
      ``mosaic_shapes`` their distinct result shapes — PER-DEVICE shapes,
      so a kernel under ``shard_map`` shows its shard's batch;
    - ``collectives``: count per collective op (absent when none);
    - ``devices``: distinct devices the executable's inputs live on, and
      ``sharded_inputs``: how many inputs are split (not replicated)
      over them.

    Empty when the backend offers no HLO text (never raises)."""
    if not text:
        return {}
    try:
        shardings = jax.tree_util.tree_leaves(compiled.input_shardings)
    except Exception:
        return {}
    shapes = [re.sub(r"\{[^}]*\}", "", m) for m in _MOSAIC_RE.findall(text)]
    out: Dict[str, Any] = {"mosaic_calls": len(shapes)}
    if shapes:
        out["mosaic_shapes"] = sorted(set(shapes))
    ops = collections.Counter(_COLLECTIVE_RE.findall(text))
    if ops:
        out["collectives"] = dict(sorted(ops.items()))
    out["devices"] = len({d.id for sh in shardings for d in sh.device_set}) or 1
    out["sharded_inputs"] = sum(
        1 for sh in shardings if not sh.is_fully_replicated)
    return out


def _hlo_text(compiled) -> str:
    """The executable's optimized HLO as text; "" where the backend
    offers none (never raises)."""
    try:
        return compiled.as_text() or ""
    except Exception:
        return ""


def _keep_hlo(hlo_dir: str, rec: Dict[str, Any], text: str) -> None:
    """Write one compile's HLO text to ``<hlo_dir>/<group>-<sig>.hlo.txt``
    (once per compile, in set-up) and name it in the compile record
    (``hlo_path``). A failed write is logged and leaves the record
    without it — telemetry must not take down the run it observes."""
    path = os.path.join(hlo_dir, f"{rec['group']}-{rec['sig']}.hlo.txt")
    try:
        os.makedirs(hlo_dir, exist_ok=True)
        with open(path, "w") as f:
            f.write(text)
    except OSError as e:
        logger.warning("keeping the HLO text at %s failed: %s", path, e)
        return
    rec["hlo_path"] = path


def sig_hash(key: Any) -> str:
    """Short stable id of a launch-group signature key for records
    (the full key is a nested shape/dtype tuple — too long to log)."""
    return hashlib.md5(repr(key).encode()).hexdigest()[:10]


class _Entry:
    __slots__ = (
        "sig", "callable", "fallback_fn", "flops", "bytes_accessed",
        "flops_analytic", "exec_s", "calls", "batches", "compile_s_pending",
        "degraded", "mem",
    )

    def __init__(self, sig: str, callable_, fallback_fn):
        self.sig = sig
        self.callable = callable_
        self.fallback_fn = fallback_fn
        self.flops: Optional[float] = None
        self.bytes_accessed: Optional[float] = None
        self.flops_analytic: Optional[float] = None
        # static memory plan (mem_*_bytes, observability/memory.py) —
        # the OOM pre-mortem ranks launch groups from these
        self.mem: Optional[Dict[str, int]] = None
        self.exec_s = 0.0
        self.calls = 0
        self.batches = 0
        # trace+compile seconds paid INSIDE the first timed launch —
        # note_exec subtracts it once so roofline exec time measures
        # execution, not compilation
        self.compile_s_pending = 0.0
        self.degraded = False


class CompileRegistry:
    """Per-trainer compile/cost bookkeeping for launch groups.

    ``call(group, key, fn, *args)`` routes a launch through the cached
    AOT executable for its (group, signature); the first call per
    signature is the instrumented compile. ``note_exec`` accumulates the
    caller-measured wall time (the caller's timing includes the
    device sync the registry cannot see), and ``emit_roofline`` turns
    the accumulated totals into ``kind=roofline`` records.
    """

    def __init__(self, device_kind: Optional[str] = None, hlo_dir: str = ""):
        # where each compile's optimized HLO text is kept ("" = nowhere):
        # a profile's device events carry an instruction's name and no
        # scope, and this text is the map from the one to the other
        # (`metadata={op_name="..."}`)
        self._hlo_dir = hlo_dir
        self._entries: Dict[Tuple[str, Any], _Entry] = {}
        self._warned_flops: set = set()
        self._warned_degraded: set = set()
        self._device_kind = device_kind
        # compiles per group over the registry's LIFETIME — survives
        # invalidate(), so a rollback re-jit records recompiles>0
        self._group_compiles: Dict[str, int] = {}
        # exec totals of invalidated entries, re-seeded into the
        # recompiled entry: roofline records are cumulative per
        # (group, sig) and the analyzers keep latest-wins, so losing
        # the pre-rollback totals would skew achieved FLOP/s upward
        self._carryover: Dict[Tuple[str, Any], Tuple[float, int, int]] = {}

    @property
    def device_kind(self) -> Optional[str]:
        return self._device_kind

    # ------------------------------------------------------------- call

    def call(self, group: str, key: Any, fn, *args,
             analytic_flops: Optional[float] = None,
             pass_id: Optional[int] = None, step: Optional[int] = None):
        ent = self._entries.get((group, key))
        if ent is not None:
            return self._run(group, ent, args)
        return self._first_call(group, key, fn, args, analytic_flops,
                                pass_id, step)

    def _run(self, group: str, ent: _Entry, args):
        if ent.callable is not ent.fallback_fn:
            try:
                return ent.callable(*args)
            except (TypeError, ValueError) as e:
                # an AOT executable is stricter than jit dispatch about
                # input avals/shardings; a rejection is raised BEFORE
                # dispatch (TypeError/ValueError), so re-running via the
                # jit path is safe even with donated buffers. Runtime
                # failures (OOM etc.) propagate — after dispatch the
                # donated args are gone and a retry would only mask the
                # real error with "Array has been deleted".
                obs.registry().counter("compile.aot_fallbacks").inc()
                if group not in self._warned_degraded:
                    self._warned_degraded.add(group)
                    logger.warning(
                        "AOT executable for launch group %r rejected its "
                        "inputs (%s: %s) — falling back to jit dispatch",
                        group, type(e).__name__, e,
                    )
                ent.callable = ent.fallback_fn
                ent.degraded = True
        return ent.fallback_fn(*args)

    def _first_call(self, group, key, fn, args, analytic_flops,
                    pass_id, step):
        rec: Dict[str, Any] = {
            "group": group,
            "sig": sig_hash(key),
            # compiles of this group BEFORE this one: >0 means the group
            # recompiled (new batch signature / rollback invalidation —
            # lifetime count, so invalidate() cannot reset it to 0)
            "recompiles": self._group_compiles.get(group, 0),
            # the device this compile was FOR, as jax reports it — a
            # compile record read off another machine says where it ran
            **device_stamp(),
        }
        self._group_compiles[group] = self._group_compiles.get(group, 0) + 1
        hit_probe = cache_probe()
        out = None
        callable_ = fn
        cost = None
        mem = None
        lower = getattr(fn, "lower", None)
        if lower is not None:
            try:
                # the compile's halves are spans like the step's (trace_s
                # and compile_s are read off them), and what the telemetry
                # itself costs in set-up is a third
                with stat_timer("compile/trace_lower") as traced:
                    lowered = lower(*args)
                with stat_timer("compile/backend") as built:
                    compiled = lowered.compile()
                rec["trace_s"] = round(traced.elapsed_s, 6)
                rec["compile_s"] = round(built.elapsed_s, 6)
                from paddle_tpu.observability.costs import cost_analysis_of
                from paddle_tpu.observability.memory import memory_analysis_of

                with stat_timer("compile/report"):
                    cost = cost_analysis_of(compiled)
                    # static HBM plan (argument/output/temp/generated
                    # bytes): joined onto the SAME compile record, so every
                    # launch group's planned footprint is on disk before
                    # the first step runs — the raw material of
                    # `paddle memory` and the OOM pre-mortem
                    mem = memory_analysis_of(compiled)
                    text = _hlo_text(compiled)
                    rec.update(hlo_census(compiled, text))
                    if text and self._hlo_dir:
                        _keep_hlo(self._hlo_dir, rec, text)
                callable_ = compiled
            except Exception as e:
                logger.debug(
                    "AOT compile of launch group %r failed (%s) — timing "
                    "the first dispatch instead", group, e, exc_info=True,
                )
                lower = None
        if lower is None:
            # no .lower (mesh-sharded closures, plain python) or AOT
            # refused: the first dispatch pays trace+compile together —
            # still measured, just not separable
            with stat_timer("compile/backend") as built:
                out = fn(*args)
            rec["compile_s"] = round(built.elapsed_s, 6)
            rec["mode"] = "inline"
        hit = hit_probe()
        if hit is not None:
            rec["cache_hit"] = hit
        if cost is not None:
            rec.update(cost)  # flops / bytes_accessed, whichever exist
        if mem is not None:
            rec.update(mem)  # mem_*_bytes static footprint, when known
        if analytic_flops:
            rec["flops_analytic"] = float(analytic_flops)
        self._cross_check(group, rec)
        r = obs.registry()
        r.counter("compile.count").inc()
        if hit is True:
            r.counter("compile.cache_hits").inc()
        elif hit is False:
            r.counter("compile.cache_misses").inc()
        obs.emit("compile", pass_id=pass_id, step=step, **rec)
        ent = _Entry(rec["sig"], callable_, fn)
        ent.flops = rec.get("flops")
        ent.bytes_accessed = rec.get("bytes_accessed")
        ent.flops_analytic = rec.get("flops_analytic")
        ent.mem = mem
        ent.compile_s_pending = rec.get("compile_s", 0.0) + rec.get("trace_s", 0.0)
        carried = self._carryover.pop((group, key), None)
        if carried is not None:
            ent.exec_s, ent.calls, ent.batches = carried
        self._entries[(group, key)] = ent
        if out is None:
            out = self._run(group, ent, args)
        return out

    def _cross_check(self, group: str, rec: Dict[str, Any]) -> None:
        """Satellite: the analytic matmul count (the MFU basis) vs XLA's
        cost analysis, once per signature — >10% disagreement becomes a
        logged warning instead of folklore (kernel_flops.py documents
        that XLA counts scan/while bodies once regardless of trip count,
        so scanned models are understated there)."""
        af, xf = rec.get("flops_analytic"), rec.get("flops")
        if not af or not xf:
            return
        ratio = abs(af - xf) / max(abs(af), abs(xf))
        rec["flops_disagreement"] = round(ratio, 4)
        mark = (group, rec["sig"])
        if ratio > 0.10 and mark not in self._warned_flops:
            self._warned_flops.add(mark)
            logger.warning(
                "FLOPs accounting disagreement for launch group %r (sig "
                "%s): analytic %.4g vs XLA cost analysis %.4g (%.0f%% "
                "apart). XLA counts scan/while bodies once regardless of "
                "trip count (ops/kernel_flops.py), so scanned models are "
                "understated there; MFU and the roofline use the analytic "
                "count when present.",
                group, rec["sig"], af, xf, ratio * 100,
            )

    # ------------------------------------------------------ exec/roofline

    def note_exec(self, group: str, key: Any, seconds: float,
                  batches: int = 1) -> None:
        """Attribute one launch's measured wall time (caller-timed, sync
        included) to its group. The first launch's time has the compile
        cost deducted — roofline positions measure execution."""
        ent = self._entries.get((group, key))
        if ent is None:
            return
        s = float(seconds)
        if ent.compile_s_pending:
            s = max(s - ent.compile_s_pending, 0.0)
            ent.compile_s_pending = 0.0
        ent.exec_s += s
        ent.calls += 1
        ent.batches += int(batches)

    def drop_pending(self, group: str, key: Any) -> None:
        """Discard the pending compile-cost deduction of a group whose
        first launch was thrown away (non-finite skip): the launch that
        paid the compile never reaches note_exec, and the deduction
        must not zero a later clean launch's exec time instead."""
        ent = self._entries.get((group, key))
        if ent is not None:
            ent.compile_s_pending = 0.0

    def emit_roofline(self, pass_id: Optional[int] = None) -> None:
        """One ``kind=roofline`` record per launch group with execution
        data — cumulative totals (the analyzer keeps latest-wins per
        (host, group, sig), so restarts/re-runs never double-count)."""
        for (group, _key), ent in self._entries.items():
            if not ent.calls:
                continue
            rec: Dict[str, Any] = {
                "group": group,
                "sig": ent.sig,
                "launches": ent.calls,
                "batches": ent.batches,
                "exec_s": round(ent.exec_s, 6),
            }
            if ent.flops:
                rec["flops_per_launch"] = ent.flops
            if ent.flops_analytic:
                rec["flops_analytic_per_launch"] = ent.flops_analytic
            if ent.bytes_accessed:
                rec["bytes_per_launch"] = ent.bytes_accessed
            if self._device_kind:
                rec["device_kind"] = self._device_kind
            obs.emit("roofline", pass_id=pass_id, **rec)

    def static_memory_rows(self) -> list:
        """Per-launch-group static memory plan (mem_*_bytes), ranked by
        total footprint — the OOM pre-mortem's group ranking. Groups
        whose backend reported no memory analysis are absent (omitted,
        never guessed)."""
        rows = []
        for (group, _key), ent in self._entries.items():
            if not ent.mem:
                continue
            rows.append({
                "group": group, "sig": ent.sig, "launches": ent.calls,
                **ent.mem,
            })
        rows.sort(key=lambda r: -int(r.get("mem_total_bytes", 0)))
        return rows

    def invalidate(self, *groups: str) -> None:
        """Drop the cached executables of the named groups (rollback
        retunes the learning rate — the baked constants are stale). The
        groups' cumulative exec totals are carried over to the
        recompiled entries: the roofline records share the (group, sig)
        identity across the recompile, and the analyzers keep
        latest-wins, so a reset here would silently shed the
        pre-rollback execution time."""
        for k in [k for k in self._entries if k[0] in groups]:
            ent = self._entries.pop(k)
            if ent.calls:
                self._carryover[k] = (ent.exec_s, ent.calls, ent.batches)
