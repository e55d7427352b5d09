"""Scoped timers aggregated into a global stat set.

TPU-native analog of the reference's ``REGISTER_TIMER`` / ``StatSet``
(/root/reference/paddle/utils/Stat.h:70,127,244): named scopes accumulate
wall-time and call counts, dumped periodically by the trainer. On TPU the
device work is async, so a timer around a jitted call measures its dispatch;
the wait for the device is a scope of its own where the host reads a result
back (``trainer/loss_sync``). Scopes also emit
``jax.profiler.TraceAnnotation`` so they show up in xplane traces, on the
device trace's clock. Names follow one rule, ``<layer>/<what>``
(doc/observability.md lists them).
"""

from __future__ import annotations

from paddle_tpu.utils import concurrency as cc
import time
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple


@dataclass
class Stat:
    name: str
    total_s: float = 0.0
    count: int = 0
    max_s: float = 0.0
    _lock: object = field(default_factory=cc.Lock, repr=False)

    def add(self, dt: float) -> None:
        with self._lock:
            self.total_s += dt
            self.count += 1
            if dt > self.max_s:
                self.max_s = dt

    @property
    def avg_s(self) -> float:
        return self.total_s / self.count if self.count else 0.0


class StatSet:
    def __init__(self, name: str = "global"):
        self.name = name
        self._stats: Dict[str, Stat] = {}
        self._lock = cc.Lock()

    def get(self, name: str) -> Stat:
        st = self._stats.get(name)  # a hit needs no lock: dict reads are atomic
        if st is None:
            with self._lock:
                st = self._stats.setdefault(name, Stat(name))
        return st

    def snapshot(self) -> Dict[str, Tuple[int, float]]:
        """name -> (count, total_s) now; two snapshots bracket a pass and
        their difference is what the pass spent in each scope."""
        with self._lock:
            stats = list(self._stats.values())
        return {s.name: (s.count, s.total_s) for s in stats}

    def growth_since(self, before: Dict[str, Tuple[int, float]],
                     now: Optional[Dict[str, Tuple[int, float]]] = None) -> Dict[str, list]:
        """name -> [count, total_s] added since the ``before`` snapshot
        (scopes that did not run are left out), up to the ``now`` snapshot
        where the caller took one already."""
        out = {}
        for name, (count, total_s) in (now or self.snapshot()).items():
            c0, t0 = before.get(name, (0, 0.0))
            if count > c0:
                out[name] = [count - c0, round(total_s - t0, 6)]
        return out

    def totals(self, now: Optional[Dict[str, Tuple[int, float]]] = None) -> Dict[str, list]:
        """name -> [count, total_s] since process start, in a record's
        form: what ``pass_end`` and ``restart`` carry as ``spans_total``.
        A scope still open (the pass's own, the ``train()`` call's) is not
        in it yet."""
        return self.growth_since({}, now)

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()

    def summary(self) -> str:
        with self._lock:
            stats = sorted(self._stats.values(), key=lambda s: -s.total_s)
        if not stats:
            return f"=== StatSet {self.name}: empty ==="
        lines = [f"=== StatSet {self.name} ==="]
        for s in stats:
            lines.append(
                f"  {s.name:<40s} total={s.total_s * 1e3:10.2f}ms "
                f"avg={s.avg_s * 1e3:8.3f}ms max={s.max_s * 1e3:8.3f}ms n={s.count}"
            )
        return "\n".join(lines)


global_stats = StatSet()

# (TraceAnnotation, StepTraceAnnotation, record_perf), looked up on the
# first scope and kept: importing this module must not pull in jax — the
# supervisor CLI (`paddle supervise`) imports the utils package and has to
# stay usable when the accelerator runtime is exactly what keeps crashing —
# and a scope on the step's path must not pay two import statements each
# time it opens
_hooks: Optional[tuple] = None


def _lookup_hooks() -> tuple:
    global _hooks
    import jax

    from paddle_tpu.observability import spans

    _hooks = (jax.profiler.TraceAnnotation, jax.profiler.StepTraceAnnotation,
              spans.record_perf)
    return _hooks


class stat_timer:
    """THE span primitive: ``with stat_timer("<layer>/<what>"):`` times a
    scope into ``global_stats`` (total and count: what the ``pass_end``
    record's ``spans`` and the pass-end log dump read), the jax profiler
    trace (a ``TraceAnnotation``, so the span lies on the device trace's
    clock) and — when ``--trace_events_path`` configured a collector —
    the span layer (observability/spans.py), where the same named scopes
    export as nested Chrome trace events. With no trace running and no
    collector a scope costs two clock reads, one TraceMe enter/exit and
    one locked add.

    ``step_num``: the scope is the root of one step — a
    ``StepTraceAnnotation`` carrying the number, so that the spans of a
    step share an identifier in the trace.

    A scope left by an exception, or ``drop()``ped, reaches the profiler
    trace only: it is no completed unit of the work it names.

    ``elapsed_s``: the scope's seconds once it has closed, for a caller
    that writes them into a record of its own (the compile record's
    ``trace_s`` / ``compile_s``): one clock, one primitive.
    """

    __slots__ = ("name", "step_num", "elapsed_s", "_t0", "_ann", "_dropped")

    def __init__(self, name: str, step_num: Optional[int] = None):
        self.name = name
        self.step_num = step_num
        self._dropped = False

    def __enter__(self) -> "stat_timer":
        hooks = _hooks or _lookup_hooks()
        self._ann = (hooks[0](self.name) if self.step_num is None
                     else hooks[1](self.name, step_num=self.step_num))
        self._t0 = time.perf_counter()
        self._ann.__enter__()
        return self

    def drop(self) -> None:
        """Leave this scope out of the StatSet and the collector (a pull
        that found the pass's end is no step)."""
        self._dropped = True

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._ann.__exit__(exc_type, exc, tb)
        self.elapsed_s = dt = time.perf_counter() - self._t0
        if exc_type is None and not self._dropped:
            global_stats.get(self.name).add(dt)
            _hooks[2](self.name, self._t0, dt)
        return False
