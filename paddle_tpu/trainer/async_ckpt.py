"""Async checkpointing — the step loop never waits on checkpoint I/O.

The synchronous ``Trainer.save()`` serializes + fsyncs the whole model
inline at pass end: on a big model over a shared filesystem that is the
single largest stall left in the hot path (PR 3's ``paddle metrics``
measures it as the ``checkpoint`` row durations). The reference hid
host work behind device compute everywhere it could (DoubleBuffer
prefetch threads, async pserver pushes) but its ParamUtil save was just
as synchronous — this module closes the gap for the TPU port.

Behind ``--async_checkpoint`` a save becomes two halves:

1. **Snapshot** (the step loop's only cost): every device array's
   host copy is *dispatched* asynchronously (``copy_to_host_async``),
   then collected — the one unavoidable device→host wait. The wall
   time of this half is the ``ckpt.blocked_s`` counter and the
   ``op="snapshot"`` checkpoint record.
2. **Write** (background): a daemon writer thread runs the *unchanged*
   PR-1 durability protocol over the host trees —
   ``pass-N.tmp`` → fsync → ``MANIFEST.json`` → rename, rotation with
   ``protect_pass`` — via ``checkpoint.save_checkpoint``. Its wall time
   is the ``ckpt.write_s`` counter (and the usual ``op="save"`` record,
   now emitted from the writer thread).

Contracts that make this safe, not just fast:

- **Bounded in-flight saves** (``--ckpt_inflight_limit``, default 1):
  at most one save is actively writing and at most ``limit`` more may
  queue behind it; enqueueing past the bound drops the OLDEST pending
  (never the active, never the newest — the newest state is the one
  worth making durable), counted by ``ckpt.async_dropped`` and logged.
- **drain()** blocks until everything enqueued is durable. The trainer
  drains at every pass-end test/eval (so a writer failure surfaces at
  most one pass late), on preemption (the SIGTERM save must be durable
  before exit ``EXIT_PREEMPTED``), before a rollback-restore (the
  newest save must be on disk before ``find_restorable_checkpoint``
  scans), and at the end of ``train()``.
- **Writer failures are never silent**: an exception in the background
  write is stored and re-raised as :class:`CheckpointError` from the
  NEXT ``save()`` or ``drain()``. A crash before either loses only the
  in-flight write — the PR-1 protocol guarantees the previous
  checkpoint is still durable and restorable.
- **Hangwatch**: the writer pings the step-progress watchdog at the
  start and end of every background write, and ``drain()`` pings it
  while an active write is still making the queue shrink — a long
  (but live) write at a drain barrier is not misdiagnosed as a trainer
  hang. A writer wedged forever on a dead shared fs still trips the
  watchdog once pings stop, exactly like a wedged synchronous save.

Multi-process runs use :class:`ShardedAsyncCheckpointer` — the elastic
sharded twin (doc/resilience.md "Elastic sharded checkpointing"):

- ``save()`` snapshots only the shards THIS process uniquely owns
  (``checkpoint.snapshot_owned_trees`` — every owned shard's
  device→host copy dispatched before the first collect blocks) and
  enqueues them on the same bounded queue.
- The per-host background writer runs the PR-1 durable discipline over
  its own files only: shard npz + partial index + partial manifest into
  ``pass-N.tmp`` (``checkpoint.write_sharded_host_trees``). No
  cross-process coordination happens on the write path at all.
- The ONLY collective is ``drain()``'s cheap pass-end agreement, and it
  is a HOST protocol (the jax distributed runtime's KV store + barrier
  — no device collectives): every process publishes which passes its
  writer made locally durable (or its writer error), all rendezvous,
  and the commit set is the INTERSECTION (writer speeds differ, so the
  drop-oldest policy can drop different passes per host — a pass is
  durable only where EVERY host's shards landed). Process 0 then merges
  partial indexes + manifests and renames each agreed pass into place;
  a second agreement round carries process 0's commit verdict to every
  host (and keeps the round counters aligned even when the commit
  itself fails).
- **Writer failures propagate to every host**: a failed write surfaces
  as :class:`CheckpointError` from drain() on ALL processes (the
  agreement carries the error), so the job tears down together instead
  of one rank dying while the rest block in a barrier. This is the
  sharded analog of the single-process "next save/drain" contract —
  made symmetric, which is why sharded ``save()`` does NOT re-raise a
  pending local error early.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from paddle_tpu.observability import metrics as obs
from paddle_tpu.resilience import CheckpointError
from paddle_tpu.sparse import runtime as sparse_rt
from paddle_tpu.utils import concurrency as cc
from paddle_tpu.utils.logging import logger
from paddle_tpu.utils.stats import stat_timer

__all__ = [
    "AsyncCheckpointer", "ShardedAsyncCheckpointer", "snapshot_to_host",
]


class _LazyModule:
    """Import-on-first-attribute proxy. The concurrency machinery here
    (queues, writer threads, the drain protocol) is jax-free by design
    — `paddle race` drives it with injected write/snapshot/finalize
    seams and must never pay (or depend on) the jax import — while the
    production paths still reach the real checkpoint module the moment
    they touch it. Attribute assignment works normally (tests
    monkeypatch ``ac_mod.ckpt.finalize_sharded_pass``)."""

    def __init__(self, name: str):
        self._name = name

    def __getattr__(self, attr):
        if attr.startswith("__"):  # dunder probes (copy/pickle) stay cheap
            raise AttributeError(attr)
        return getattr(importlib.import_module(self._name), attr)


#: the durable-protocol module (PR 1), resolved lazily — see _LazyModule
ckpt: Any = _LazyModule("paddle_tpu.trainer.checkpoint")


def snapshot_to_host(tree):
    """Device→host copy of a pytree: dispatch EVERY leaf's async copy
    first, then collect — the collection blocks only until the last DMA
    lands, not once per leaf. Host leaves (numpy scalars in a restored
    opt_state) pass through."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    for leaf in leaves:
        copy_async = getattr(leaf, "copy_to_host_async", None)
        if copy_async is not None:
            try:
                copy_async()
            except Exception:
                pass  # backends without async copies fall back to the
                # blocking np.asarray below — correct, just slower
    return jax.tree_util.tree_unflatten(
        treedef, [np.asarray(leaf) for leaf in leaves]
    )


class _Job:
    __slots__ = ("pass_id", "params", "opt_state", "extra_meta", "keep",
                 "protect_pass", "on_durable", "snapshot", "meta", "seq")

    def __init__(self, pass_id, params, opt_state, extra_meta, keep,
                 protect_pass, on_durable, snapshot=None, meta=None):
        # seq: per-checkpointer monotonically increasing id, assigned at
        # enqueue under the cv. drain()'s writer-progress signal keys on
        # it — NOT on id(job), which the allocator can recycle
        self.seq = -1
        self.pass_id = pass_id
        self.params = params
        self.opt_state = opt_state
        self.extra_meta = extra_meta
        self.keep = keep
        self.protect_pass = protect_pass
        self.on_durable = on_durable
        # sharded-mode payload: {base: (pieces, partial_index)} host
        # snapshot + the pass meta dict (built at save time — the live
        # state keeps training while the write is in flight)
        self.snapshot = snapshot
        self.meta = meta


class AsyncCheckpointer:
    """Background checkpoint writer (see module docstring).

    ``write_fn`` is an injectable seam (fake-clock/gated unit tests);
    production uses :func:`checkpoint.save_checkpoint` — the unchanged
    durable protocol."""

    def __init__(
        self,
        save_dir: str,
        inflight_limit: int = 1,
        hangwatch=None,
        *,
        write_fn: Optional[Callable[..., str]] = None,
        snapshot_fn: Optional[Callable[[Any], Any]] = None,
    ):
        self.save_dir = save_dir
        self.inflight_limit = max(1, int(inflight_limit))
        self.hangwatch = hangwatch
        # injectable seams: production uses the PR-1 durable protocol
        # and the async device→host snapshot; unit tests and the race
        # explorer substitute gated/jax-free fakes
        self._write_fn = write_fn  # None -> ckpt.save_checkpoint, lazily
        self._snapshot_fn = snapshot_fn or snapshot_to_host
        self._cv = cc.Condition()
        self._pending: List[_Job] = []     # queued, oldest first
        self._active: Optional[_Job] = None
        self._error: Optional[BaseException] = None
        self._thread = None
        self._job_seq = 0                  # next _Job.seq, under the cv
        self.dropped = 0
        self.completed = 0

    # -------------------------------------------------------- trainer side

    def save(
        self,
        pass_id: int,
        params: Dict[str, jax.Array],
        opt_state=None,
        extra_meta: Optional[Dict[str, Any]] = None,
        keep: int = 3,
        protect_pass: Optional[int] = None,
        on_durable: Optional[Callable[[int, str], None]] = None,
    ) -> float:
        """Snapshot device→host and enqueue the background write.
        Returns the seconds the caller was blocked (the snapshot — what
        ``ckpt.blocked_s`` accounts). Raises :class:`CheckpointError`
        first if a PREVIOUS background write failed."""
        self._raise_pending_error()
        t0 = cc.perf_counter()
        # ONE pytree so every leaf's async copy (params AND opt_state)
        # is dispatched before the first collection blocks — collecting
        # params first would serialize the two DMA trees
        host_params, host_opt = self._snapshot_fn((params, opt_state))
        blocked = cc.perf_counter() - t0
        job = _Job(pass_id, host_params, host_opt, dict(extra_meta or {}),
                   keep, protect_pass, on_durable)
        self._enqueue(job, blocked)
        return blocked

    def _enqueue(self, job: _Job, blocked: float) -> None:
        """Queue one snapshotted job on the bounded writer queue (the
        shared half of sync-tree and sharded saves): drop-oldest-pending
        beyond the limit, wake the writer, account the snapshot cost."""
        with self._cv:
            job.seq = self._job_seq
            self._job_seq += 1
            self._pending.append(job)
            # drop-oldest-pending: the active write cannot be revoked
            # mid-protocol and the newest state is the one worth keeping
            while len(self._pending) > self.inflight_limit:
                old = self._pending.pop(0)
                self.dropped += 1
                obs.registry().counter("ckpt.async_dropped").inc()
                logger.warning(
                    "async checkpoint: dropping queued save of pass %d "
                    "(superseded by pass %d; --ckpt_inflight_limit=%d)",
                    old.pass_id, job.pass_id, self.inflight_limit,
                )
            self._set_inflight_gauge_locked()
            self._cv.notify_all()
        self._ensure_thread()
        obs.registry().counter("ckpt.blocked_s").inc(blocked)
        obs.emit(
            "checkpoint", op="snapshot", pass_id=job.pass_id,
            step=job.extra_meta.get("batch_id"),
            path=ckpt.PASS_FMT % job.pass_id if self.save_dir else "",
            duration_s=round(blocked, 6),
        )

    def _wait_idle(self, timeout: Optional[float] = None) -> None:
        """Block until the local writer queue is empty (or ``timeout``
        seconds passed — then :class:`CheckpointError`). Pings the
        hangwatch while the writer is demonstrably live so a long write
        at a drain barrier is not misdiagnosed as a trainer hang."""
        deadline = None if timeout is None else cc.monotonic() + timeout
        # a dead/never-started writer would leave the queue stuck: make
        # sure one is running before waiting on it
        self._ensure_thread()
        with self._cv:
            last_state = None
            while self._pending or self._active is not None:
                # ping only when the WRITER demonstrably progressed (a
                # write completed / a new job was claimed) since the
                # last poll: an unconditional ping would keep a writer
                # wedged forever on a dead fs from ever tripping the
                # watchdog — the exact failure hangwatch exists for.
                # Keyed on the claimed job's enqueue seq, NOT on queue
                # shape or id(): a concurrent save()'s drop-oldest
                # rearranging `_pending` is trainer-side motion (the
                # wedged writer would look live and never trip the
                # watchdog), and a recycled id() after a completed job
                # would hide a real claim (a live writer tripping it) —
                # both surfaced by the `paddle race` drain spec
                state = (self.completed,
                         self._active.seq if self._active is not None
                         else None)
                if (self.hangwatch is not None
                        and self._active is not None
                        and state != last_state):
                    self.hangwatch.ping(self._active.pass_id)
                last_state = state
                self._cv.wait(timeout=0.2)
                if deadline is not None and cc.monotonic() > deadline:
                    raise CheckpointError(
                        f"async checkpoint drain timed out after {timeout}s "
                        f"({len(self._pending)} pending, active="
                        f"{self._active.pass_id if self._active else None})"
                    )

    def drain(self, timeout: Optional[float] = None) -> None:
        """Block until every enqueued save is durable (or ``timeout``
        seconds passed — then :class:`CheckpointError`). Re-raises a
        stored writer failure."""
        self._wait_idle(timeout)
        self._raise_pending_error()

    def inflight(self) -> int:
        with self._cv:
            return len(self._pending) + (1 if self._active is not None else 0)

    # --------------------------------------------------------- writer side

    def _ensure_thread(self) -> None:
        with self._cv:
            if self._thread is not None and self._thread.is_alive():
                return
            self._thread = cc.Thread(
                target=self._run, name="pt-ckpt-writer", daemon=True
            )
            self._thread.start()

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._pending:
                    # BOUNDED idle wait (lint rule PTL008): a daemon
                    # thread parked forever on an uninstrumented
                    # primitive cannot be reported forensically by the
                    # hang-defense stack; waking to re-check the
                    # predicate once a minute is free
                    self._cv.wait(timeout=60.0)
                self._active = self._pending.pop(0)
                self._set_inflight_gauge_locked()
                job = self._active
            try:
                self._write(job)
            finally:
                # drop BOTH references to the host snapshot before the
                # idle wait — holding it would keep a full extra host
                # copy of model+optimizer state resident between saves
                job = None
                with self._cv:
                    self._active = None
                    self._set_inflight_gauge_locked()
                    self._cv.notify_all()

    def _default_write_fn(self):
        return ckpt.save_checkpoint

    def _write(self, job: _Job) -> None:
        if self.hangwatch is not None:
            self.hangwatch.ping(job.pass_id)
        t0 = cc.perf_counter()
        try:
            path = (self._write_fn or self._default_write_fn())(
                self.save_dir,
                job.pass_id,
                job.params,
                job.opt_state,
                extra_meta=job.extra_meta,
                keep=job.keep,
                protect_pass=job.protect_pass,
            )
        except BaseException as e:
            with self._cv:
                self._error = e
            logger.error(
                "async checkpoint: background write of pass %d failed: %s "
                "(will re-raise as CheckpointError on the next save/drain)",
                job.pass_id, e,
            )
            return
        finally:
            if self.hangwatch is not None:
                self.hangwatch.ping(job.pass_id)
        dt = cc.perf_counter() - t0
        # under the cv: drain() reads `completed` (from the step-loop
        # thread) as its writer-progress signal — a torn increment would
        # read as "no progress" and misdiagnose a live drain as a hang
        with self._cv:
            self.completed += 1
        obs.registry().counter("ckpt.write_s").inc(dt)
        if job.on_durable is not None:
            try:
                job.on_durable(job.pass_id, path)
            except Exception:
                logger.warning(
                    "async checkpoint: on_durable callback failed for "
                    "pass %d", job.pass_id, exc_info=True,
                )

    # ------------------------------------------------------------- plumbing

    def _set_inflight_gauge_locked(self) -> None:
        obs.registry().gauge("ckpt.async_inflight").set(
            len(self._pending) + (1 if self._active is not None else 0)
        )

    def _take_error(self) -> Optional[BaseException]:
        with self._cv:
            err, self._error = self._error, None
        return err

    def _raise_pending_error(self) -> None:
        err = self._take_error()
        if err is not None:
            raise CheckpointError(
                f"async checkpoint write failed: {err}"
            ) from err


class _KvAgreement:
    """The pass-end agreement channel: publish a small payload, wait for
    every process, read everyone's payloads back — over the jax
    distributed runtime's KV store + host barrier. No device collectives
    (the agreement must work even when the backend cannot run
    cross-process computations, and must not occupy the accelerator).
    Single-process (or no distributed client): degenerates to returning
    only the local payload. Rounds are numbered locally; the agreement
    is only ever called from collective call sites (drain), so every
    process's round counter stays aligned."""

    def __init__(self, timeout_s: float = 600.0):
        import jax

        from paddle_tpu.utils.barrier import distributed_client

        self.timeout_s = float(timeout_s)
        self.client = distributed_client()
        self.pid = jax.process_index()
        self.count = jax.process_count()
        self._round = 0
        self._prev_key: Optional[str] = None

    def agree(self, payload: str) -> List[str]:
        """Everyone's payloads, pid-ordered. Raises on rendezvous
        failure (a peer died mid-protocol)."""
        r = self._round
        self._round += 1
        if self.client is None or self.count == 1:
            return [payload]
        timeout_ms = int(self.timeout_s * 1000)
        key = f"ckpt_agree/{r}/{self.pid:05d}"
        if self._prev_key is not None:
            # bound KV-store growth by one round, deleting only NOW:
            # deleting right after our own dir read would race a slower
            # peer still reading that round's directory (the barrier
            # orders the sets before any read, but nothing orders one
            # process's delete after ANOTHER's read — except the next
            # round's barrier, which is where we are)
            try:
                self.client.key_value_delete(self._prev_key)
            except Exception:
                pass
        self._prev_key = key
        self.client.key_value_set(key, payload)
        self.client.wait_at_barrier(f"ckpt_agree_{r}", timeout_ms)
        items = self.client.key_value_dir_get(f"ckpt_agree/{r}/")
        return [v for _k, v in sorted(items)]



class ShardedAsyncCheckpointer(AsyncCheckpointer):
    """Per-host async shard writer + pass-end commit agreement — the
    multi-process elastic twin of :class:`AsyncCheckpointer` (see the
    module docstring for the protocol and its failure contract)."""

    def __init__(
        self,
        save_dir: str,
        inflight_limit: int = 1,
        hangwatch=None,
        *,
        process_index: Optional[int] = None,
        process_count: Optional[int] = None,
        agreement=None,
        agree_timeout: float = 600.0,
        write_fn: Optional[Callable[..., None]] = None,
        snapshot_fn: Optional[Callable[..., Any]] = None,
        finalize_fn: Optional[Callable[..., str]] = None,
    ):
        super().__init__(save_dir, inflight_limit, hangwatch,
                         write_fn=write_fn)
        # sharded snapshot contract differs from the base's one-tree
        # copy: (pass_id, params, opt_state, extra_meta) -> (snapshot,
        # meta); finalize_fn(pass_id, job, rotate) -> final path runs
        # process 0's commit merge. Both injectable (race specs drive
        # the REAL queue/commit protocol jax-free)
        self._snapshot_fn = snapshot_fn or self._default_shard_snapshot
        self._finalize_fn = finalize_fn or self._default_finalize
        if process_index is None or process_count is None:
            import jax
        self.pid = (jax.process_index() if process_index is None
                    else int(process_index))
        self.count = (jax.process_count() if process_count is None
                      else int(process_count))
        self.agreement = agreement or _KvAgreement(agree_timeout)
        # locally durable jobs awaiting the commit agreement
        self._durable: List[_Job] = []
        # save() calls since the last drain: when zero on every process
        # (deterministic — saves are collective call sites), drain skips
        # the agreement round entirely, so saving_period > 1 does not
        # pay per-pass KV chatter
        self._saves_since_drain = 0

    # -------------------------------------------------------- trainer side

    def save(
        self,
        pass_id: int,
        params: Dict[str, jax.Array],
        opt_state=None,
        extra_meta: Optional[Dict[str, Any]] = None,
        keep: int = 3,
        protect_pass: Optional[int] = None,
        on_durable: Optional[Callable[[int, str], None]] = None,
    ) -> float:
        """Snapshot this process's owned shards device→host and enqueue
        the background shard write. Unlike the single-process save, a
        pending LOCAL writer error is NOT raised here — it travels
        through the next drain's agreement so every host fails together
        instead of this one desyncing the collective call sites."""
        t0 = cc.perf_counter()
        snapshot, meta = self._snapshot_fn(
            pass_id, params, opt_state, extra_meta
        )
        blocked = cc.perf_counter() - t0
        job = _Job(pass_id, None, None, dict(extra_meta or {}), keep,
                   protect_pass, on_durable, snapshot=snapshot, meta=meta)
        self._saves_since_drain += 1
        self._enqueue(job, blocked)
        return blocked

    def drain(self, timeout: Optional[float] = None) -> None:
        """Local writer barrier + the pass-end commit agreement.

        1. Wait for THIS host's writer queue to empty.
        2. Publish ``{ok, passes}`` (locally durable pass ids, or the
           writer error) and rendezvous with every process.
        3. Any host not ok → :class:`CheckpointError` on EVERY host.
        4. Process 0 finalizes the agreed (intersection) passes: merge
           indexes + manifests, meta, rename, one rotation at the end.
        5. A second agreement round carries process 0's commit verdict
           (a barrier alone could not say WHY it was released): a failed
           finalize raises :class:`CheckpointError` on every host with
           the rounds still aligned, instead of process 0 dying raw
           while the peers stall out a bare barrier.
        6. Per-process ``on_durable`` callbacks for the committed set.
        """
        self._wait_idle(timeout)
        err = self._take_error()
        with self._cv:
            durable, self._durable = self._durable, []
        saves, self._saves_since_drain = self._saves_since_drain, 0
        if not saves and err is None and not durable:
            return  # nothing enqueued anywhere since the last agreement
        local: Dict[int, _Job] = {}
        for job in durable:  # latest-wins per pass (periodic + pass-end)
            local[job.pass_id] = job
        payload = json.dumps({
            "pid": self.pid,
            "ok": err is None,
            "passes": sorted(local),
            "error": "" if err is None else f"{type(err).__name__}: {err}",
        })
        if self.hangwatch is not None and local:
            # entering a blocking rendezvous that lasts as long as the
            # slowest peer's write: one ping so the wait is measured
            # from here, exactly like the sync sharded save's barrier
            self.hangwatch.ping(max(local))
        try:
            replies = [json.loads(r) for r in self.agreement.agree(payload)]
        except Exception as e:
            raise CheckpointError(
                f"sharded checkpoint agreement failed (peer died "
                f"mid-protocol?): {e}"
            ) from e
        bad = [d for d in replies if not d.get("ok")]
        if bad or err is not None:
            detail = "; ".join(
                f"host {d.get('pid')}: {d.get('error') or 'failed'}" for d in bad
            ) or f"host {self.pid}: {err}"
            raise CheckpointError(
                f"sharded async checkpoint write failed — {detail} "
                "(no pass from this round was committed)"
            ) from err
        commit = set(local)
        for d in replies:
            commit &= set(d.get("passes", []))
        ordered = sorted(commit)
        finals: Dict[int, str] = {}
        commit_err: Optional[BaseException] = None
        if self.pid == 0:
            try:
                for i, p in enumerate(ordered):
                    # ONE rotation after the last commit: rotating
                    # mid-batch would sweep the .tmp of the next pass
                    # awaiting its own commit
                    finals[p] = self._finalize_fn(
                        p, local[p], i == len(ordered) - 1
                    )
            except BaseException as e:
                # captured, not raised: the commit round below must still
                # run so the peers learn the verdict and every process's
                # agreement round counter stays aligned
                commit_err = e
        try:
            verdicts = self.agreement.agree(json.dumps({
                "pid": self.pid, "committed": commit_err is None,
            }))
        except Exception as e:
            raise CheckpointError(
                f"sharded checkpoint commit rendezvous failed: {e}"
            ) from e
        # pid-ordered replies: the head is process 0's commit verdict
        head = json.loads(verdicts[0])
        if not head.get("committed", False):
            raise CheckpointError(
                "sharded checkpoint commit failed on host 0: "
                f"{commit_err if commit_err is not None else 'see host 0 log'}"
            ) from commit_err
        for p in ordered:
            job = local[p]
            if job.on_durable is not None:
                final = finals.get(p)
                if final is None:
                    # non-zero pids never ran finalize; reconstruct the
                    # path (this is the one place a peer host touches
                    # the checkpoint module, and only lazily)
                    final = os.path.join(self.save_dir, ckpt.PASS_FMT % p)
                try:
                    job.on_durable(p, final)
                except Exception:
                    logger.warning(
                        "async checkpoint: on_durable callback failed for "
                        "pass %d", p, exc_info=True,
                    )

    # --------------------------------------------------------- writer side

    def _default_shard_snapshot(self, pass_id, params, opt_state, extra_meta):
        trees, meta = ckpt.build_save_trees(
            pass_id, params, opt_state, extra_meta, multihost=True
        )
        # sparse-table meta: which params are row-sharded tables and
        # how many hosts wrote this pass — restore compares the host
        # count against its own to detect (and count) a reshard
        tables = sparse_rt.registered_tables()
        if tables:
            meta.setdefault("sparse_tables", tables)
            meta.setdefault("sparse_hosts", self.count)
        return ckpt.snapshot_owned_trees(trees, self.pid), meta

    def _default_finalize(self, pass_id: int, job: _Job, rotate: bool) -> str:
        t0 = cc.perf_counter()
        with stat_timer("checkpoint/save"):
            final = ckpt.finalize_sharded_pass(
                self.save_dir, pass_id, job.snapshot.keys(), job.meta,
                keep=job.keep, protect_pass=job.protect_pass,
                expected_pids=range(self.count), rotate=rotate,
            )
        logger.info("saved checkpoint %s", final)
        ckpt._ckpt_record(
            "save", final, t0, pass_id=pass_id, measure_bytes=True,
            step=job.extra_meta.get("batch_id"),
        )
        return final

    def _default_write_fn(self):
        return ckpt.write_sharded_host_trees

    def _write(self, job: _Job) -> None:
        if self.hangwatch is not None:
            self.hangwatch.ping(job.pass_id)
        t0 = cc.perf_counter()
        try:
            (self._write_fn or self._default_write_fn())(
                self.save_dir, job.pass_id, job.snapshot, self.pid
            )
        except BaseException as e:
            with self._cv:
                self._error = e
            logger.error(
                "async checkpoint: background shard write of pass %d failed "
                "on host %d: %s (will surface as CheckpointError on every "
                "host at the next drain agreement)",
                job.pass_id, self.pid, e,
            )
            return
        finally:
            if self.hangwatch is not None:
                self.hangwatch.ping(job.pass_id)
        dt = cc.perf_counter() - t0
        obs.registry().counter("ckpt.write_s").inc(dt)
        # the written pieces are on disk now — keep only the tree bases
        # (what the commit merge needs), so a pass awaiting its
        # agreement does not pin a full host copy of this host's shards
        job.snapshot = dict.fromkeys(job.snapshot)
        # `completed` under the cv with the durable list: drain() reads
        # both from the step-loop thread as its writer-progress signal
        with self._cv:
            self.completed += 1
            self._durable.append(job)
