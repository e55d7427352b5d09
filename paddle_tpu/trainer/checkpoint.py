"""Checkpointing — pass-%05d directories with params + optimizer state.

Reference: ParameterUtil (/root/reference/paddle/trainer/ParamUtil.cpp:
53-103) wrote one binary file per parameter with a versioned header and
rolled old pass dirs; the reference did NOT checkpoint optimizer state — we
do (SURVEY.md §5 flags this as a required upgrade).

Single-host format: one .npz for params, one per optimizer tree,
meta.json for step counters + config snapshot.

Multi-host SHARDED format (the pserver-side save/load analog,
ParameterServer2::loadValueVector/saveValueVector,
/root/reference/paddle/pserver/ParameterServer2.cpp:1150-1213): every
process writes the addressable shards it uniquely owns (replica_id == 0)
to ``<tree>.shard<pid>.npz`` plus a partial index; after a cross-process
barrier, process 0 merges the partials into ``<tree>.index.json``. The
save_dir must be a shared filesystem (the standard TPU-pod setup; same
assumption orbax/GCS makes). Restore assembles each parameter from its
shard records and re-shards onto the CURRENT mesh via
``jax.make_array_from_callback`` — a checkpoint written on one mesh
layout loads onto any other, including single-host ↔ multi-host moves.

DURABILITY (doc/resilience.md): a save writes into ``pass-%05d.tmp``,
fsyncs every file, records a per-file CRC32/size manifest
(``MANIFEST.json``), and only then renames the directory into place —
the previous checkpoint (including an earlier save of the SAME pass) is
never removed until the new one is durable, so a crash at any point
leaves at least one restorable checkpoint. ``load_checkpoint`` verifies
the manifest first and, on corruption or incompleteness, quarantines the
bad directory (``*.corrupt``) and falls back to the newest earlier pass.
File I/O retries transient OSErrors through the shared RetryPolicy
(``--io_retry_*``). The reference's ParamUtil rewrote pass dirs in
place, destroying the previous checkpoint on a mid-save crash — the
exact gap SURVEY §5 flags.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import time
import zipfile
import zlib
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.observability import metrics as obs
from paddle_tpu.optimizer.updater import UpdaterState
from paddle_tpu.resilience import CheckpointCorruptError
from paddle_tpu.resilience import manifest as ckpt_manifest
from paddle_tpu.resilience.faultinject import FaultInjected, fault_point
from paddle_tpu.sparse import runtime as sparse_rt
from paddle_tpu.utils.flags import FLAGS
from paddle_tpu.utils.logging import logger
from paddle_tpu.utils.retry import RetryPolicy
from paddle_tpu.utils.stats import stat_timer

PASS_FMT = "pass-%05d"
TMP_SUFFIX = ".tmp"
CORRUPT_SUFFIX = ".corrupt"

# pass dirs COMMITTED (written, fsynced, manifested, renamed into place)
# by THIS process. An in-run restore of one of them — the rollback path,
# where the trainer reloads a checkpoint it saved minutes earlier — may
# skip re-CRCing the bytes (callers opt in via ``trust_own_writes``);
# verification cost belongs to cold restores, and a fresh process
# starts with an empty set, so those always verify in full.
_written_this_process: set = set()


def written_this_process(path: str) -> bool:
    """True when this process committed ``path`` (and it has not been
    quarantined since)."""
    return os.path.abspath(os.path.normpath(path)) in _written_this_process


def _is_pass_dir_name(d: str) -> bool:
    return d.startswith("pass-") and d[5:].isdigit()


def _dir_bytes(path: str) -> int:
    """On-disk size of one checkpoint dir (telemetry only: best-effort)."""
    total = 0
    try:
        for root, _dirs, files in os.walk(path):
            for f in files:
                try:
                    total += os.path.getsize(os.path.join(root, f))
                except OSError:
                    pass
    except OSError:
        pass
    return total


def _ckpt_record(op: str, path: str, t0: float, pass_id: Optional[int] = None,
                 measure_bytes: bool = False, **fields) -> None:
    """One structured ``checkpoint`` record (save/load/verify durations
    and bytes — doc/observability.md) for the operation the caller ran
    under its ``checkpoint/<op>`` span. The dir walk for
    ``measure_bytes`` only runs when telemetry is actually on — a
    telemetry-less tool (merge_model, tests) must not pay thousands of
    stat() calls for a field a no-op emit would discard. Multi-host
    saves/loads are collective: only process 0 records (and walks), so a
    pod save costs ONE shared-FS directory walk, not N, and `paddle
    metrics` shows one checkpoint row per operation. The spans are
    per-host (host-side timing is cheap and genuinely per process)."""
    dur = time.perf_counter() - t0
    if not obs.enabled():
        return
    if jax.process_count() > 1 and jax.process_index() != 0:
        return
    if measure_bytes:
        fields["bytes"] = _dir_bytes(path)
    obs.emit("checkpoint", op=op, path=path, pass_id=pass_id,
             duration_s=round(dur, 6), **fields)


def _io_policy() -> RetryPolicy:
    """Shared-FS writes/reads see transient errors at pod scale; all
    checkpoint file I/O funnels through this one policy.

    Deliberately built from the process-global FLAGS (not a trainer's
    _Flags instance): this module also serves flag-less tools
    (check-checkpoint, merge_model, torch2paddle) and deep helpers that
    have no trainer in scope. Per-trainer ``--io_retry_*`` overrides DO
    reach the data-provider retry (trainer._provider); a trainer wanting
    different checkpoint-I/O retries sets the global FLAGS."""
    return RetryPolicy.from_flags(FLAGS, name="checkpoint-io")


def _fsync_dir(path: str) -> None:
    """Make a directory entry durable (rename atomicity needs the parent
    synced). Best-effort: not every filesystem supports dir fsync."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _write_file(path: str, writer: Callable, mode: str = "wb") -> None:
    """One durable checkpoint file: fault site → write → flush → fsync,
    the whole unit retried on transient OSError (a retry reopens the
    file, so a partial first attempt is truncated away)."""

    def once():
        fault_point("checkpoint.write", info=os.path.basename(path))
        with open(path, mode) as f:
            writer(f)
            f.flush()
            os.fsync(f.fileno())

    _io_policy().call(once, name=f"write {os.path.basename(path)}")


def _durable_manifest(fn, *args, label: str):
    """Manifest writes get the same treatment as every other checkpoint
    file: the checkpoint.write fault site + the shared retry policy
    (the fsync discipline lives inside manifest.py itself)."""

    def once():
        fault_point("checkpoint.write", info=label)
        return fn(*args)

    return _io_policy().call(once, name=f"write {label}")


def _flatten(tree: Dict, prefix: str = "") -> Dict[str, Any]:
    """Flatten nested dicts to 'a/b' keys. Values are NOT materialized —
    np.savez coerces at write time (single-host), and the sharded writer
    must see live jax.Arrays to read their addressable shards."""
    out = {}
    for k, v in tree.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten(v, key + "/"))
        elif v is not None:
            out[key] = v
    return out


def _unflatten(flat: Dict[str, np.ndarray]) -> Dict:
    out: Dict = {}
    for k, v in flat.items():
        parts = k.split("/")
        d = out
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = jnp.asarray(v)
    return out


def snapshot_owned_trees(
    trees: Dict[str, Dict[str, Any]], pid: Optional[int] = None
) -> Dict[str, Tuple[Dict[str, np.ndarray], Dict[str, Any]]]:
    """Device→host snapshot of the shards THIS process uniquely owns
    (replica_id == 0), across ALL trees at once: every owned shard's
    async copy is dispatched before the first collection blocks (the
    sharded twin of async_ckpt.snapshot_to_host), so the caller pays one
    DMA wait, not one per shard. Returns ``{base: (pieces, partial)}``
    where ``pieces`` are the npz members to write and ``partial`` is the
    per-process index fragment (shard filenames already stamped)."""
    pid = jax.process_index() if pid is None else int(pid)
    staged: Dict[str, List[Tuple[str, Any, str, Any]]] = {}
    for base, flat in trees.items():
        owned: List[Tuple[str, Any, str, Any]] = []
        for name, arr in flat.items():
            arr = jnp.asarray(arr) if not isinstance(arr, jax.Array) else arr
            for i, sh in enumerate(arr.addressable_shards):
                if sh.replica_id != 0:
                    continue  # exactly one process owns each distinct slice
                copy_async = getattr(sh.data, "copy_to_host_async", None)
                if copy_async is not None:
                    try:
                        copy_async()
                    except Exception:
                        pass  # backends without async copies: the
                        # np.asarray below blocks — correct, just slower
                owned.append((name, arr, f"{name}::{i}", sh))
        staged[base] = owned
    out: Dict[str, Tuple[Dict[str, np.ndarray], Dict[str, Any]]] = {}
    for base, owned in staged.items():
        shard_file = f"{base}.shard{pid:05d}.npz"
        pieces: Dict[str, np.ndarray] = {}
        partial: Dict[str, Any] = {}
        for name, arr, key, sh in owned:
            data = np.asarray(sh.data)
            pieces[key] = data
            entry = partial.get(name)
            if entry is None:
                # the GLOBAL parameter shape/dtype, not the shard's
                entry = partial[name] = {
                    "shape": list(arr.shape),
                    "dtype": str(arr.dtype),
                    "shards": [],
                }
            rec = {
                "file": shard_file,
                "key": key,
                "start": [int(sl.start or 0) for sl in sh.index],
                # record extent up front so restore can skip
                # non-overlapping records without reading them
                "shape": list(data.shape),
            }
            # row-sharded sparse tables (and their per-row optimizer
            # slots) carry an EXPLICIT row interval: check-checkpoint
            # proves exact row coverage from these, and a relaunch
            # reshard reads only overlapping records (doc/sparse.md)
            nrows = sparse_rt.registered_tables().get(name.split("/", 1)[0])
            if (nrows is not None and data.ndim >= 1
                    and int(arr.shape[0]) == int(nrows)):
                lo = rec["start"][0] if rec["start"] else 0
                rec["row_range"] = [lo, lo + int(data.shape[0])]
            entry["shards"].append(rec)
        out[base] = (pieces, partial)
    return out


def write_owned_shards(
    path: str, base: str, pid: int,
    pieces: Dict[str, np.ndarray], partial: Dict[str, Any],
) -> str:
    """Durably write one process's shard file + partial index for one
    tree (the write half of ``_save_tree_sharded``). Returns the shard
    filename (the caller manifests the files it wrote)."""
    shard_file = f"{base}.shard{pid:05d}.npz"
    _write_file(os.path.join(path, shard_file), lambda f: np.savez(f, **pieces))
    # the partial index is transient (merged then deleted): durable write,
    # but never manifested
    _write_file(
        os.path.join(path, f"{base}.index.{pid:05d}.json"),
        lambda f: json.dump(partial, f),
        mode="w",
    )
    return shard_file


def _save_tree_sharded(path: str, base: str, flat: Dict[str, jax.Array]) -> str:
    """Write this process's uniquely-owned shards of one tree + a partial
    index. Called by EVERY process (snapshot + write in one step — the
    synchronous path; the async path stages the two halves)."""
    pid = jax.process_index()
    pieces, partial = snapshot_owned_trees({base: flat}, pid)[base]
    return write_owned_shards(path, base, pid, pieces, partial)


def write_sharded_host_trees(
    save_dir: str, pass_id: int,
    snapshot: Dict[str, Tuple[Dict[str, np.ndarray], Dict[str, Any]]],
    pid: int,
) -> None:
    """Background-writer half of a sharded ASYNC save: write this
    process's shard files + partial indexes + partial manifest into the
    pass's tmp dir. Every process's writer calls this independently
    (``exist_ok``: no cross-process ordering before the pass-end
    agreement); the commit half is :func:`finalize_sharded_pass`."""
    tmp = os.path.join(save_dir, PASS_FMT % pass_id) + TMP_SUFFIX
    os.makedirs(tmp, exist_ok=True)
    # chaos site: this host's row shards never land — the pass cannot
    # commit, and check-checkpoint must name the missing row interval
    fault_point("sparse.shard_lost", info=f"pass={pass_id} pid={pid}")
    own_files = [
        write_owned_shards(tmp, base, pid, pieces, partial)
        for base, (pieces, partial) in snapshot.items()
    ]
    _durable_manifest(
        ckpt_manifest.write_partial_manifest, tmp, pid, own_files,
        label=f"MANIFEST.partial.{pid:05d}.json",
    )
    # chaos site: poison a row AFTER the manifest digested the healthy
    # bytes — the CRC verify must catch it and quarantine/fall back
    try:
        fault_point("sparse.row_corrupt", info=f"pass={pass_id} pid={pid}")
    except FaultInjected:
        for fn in own_files:
            full = os.path.join(tmp, fn)
            try:
                size = os.path.getsize(full)
                with open(full, "r+b") as f:
                    f.seek(size // 2)
                    b = f.read(1) or b"\x00"
                    f.seek(size // 2)
                    f.write(bytes([b[0] ^ 0xFF]))
                    f.flush()
                    os.fsync(f.fileno())
            except OSError:
                pass
            break


_SHARD_FILE_RE = re.compile(r"^(?P<base>.+)\.shard(?P<pid>\d{5})\.npz$")
_PARTIAL_IDX_RE = re.compile(r"^(?P<base>.+)\.index\.(?P<pid>\d{5})\.json$")
_MERGED_IDX_RE = re.compile(r"^(?P<base>.+)\.index\.json$")
_PARTIAL_MANIFEST_RE = re.compile(r"^MANIFEST\.partial\.(?P<pid>\d{5})\.json$")


def _sweep_stale_sharded_files(
    tmp: str, tree_bases: Iterable[str], expected_pids: Iterable[int]
) -> None:
    """Drop litter from a CRASHED earlier attempt at this pass out of the
    tmp dir before merging: shard/index/partial-manifest files from a pid
    outside the current process set, or from a tree the current save does
    not write (e.g. an optimizer tree that existed before). Without this,
    the manifest merge would digest a dead process's stale shard into the
    checkpoint and the index merge would resurrect its slices. Only
    recognized checkpoint file patterns are touched."""
    bases = set(tree_bases)
    pids = {int(p) for p in expected_pids}
    for fn in os.listdir(tmp):
        m = _SHARD_FILE_RE.match(fn)
        if m:
            if m.group("base") in bases and int(m.group("pid")) in pids:
                continue
        else:
            m = _PARTIAL_IDX_RE.match(fn)
            if m:
                if m.group("base") in bases and int(m.group("pid")) in pids:
                    continue
            else:
                m = _PARTIAL_MANIFEST_RE.match(fn)
                if m:
                    if int(m.group("pid")) in pids:
                        continue
                else:
                    m = _MERGED_IDX_RE.match(fn)
                    if not m or m.group("base") in bases:
                        continue  # unknown files and live merged indexes stay
        logger.warning("sharded save: sweeping stale file %s from %s", fn, tmp)
        try:
            os.remove(os.path.join(tmp, fn))
        except OSError:
            pass


def finalize_sharded_pass(
    save_dir: str,
    pass_id: int,
    tree_bases: Iterable[str],
    meta: Dict[str, Any],
    keep: int = 3,
    protect_pass: Optional[int] = None,
    expected_pids: Optional[Iterable[int]] = None,
    rotate: bool = True,
) -> str:
    """Process-0 commit half of a sharded save: merge the partial indexes
    and partial manifests every process left in ``pass-N.tmp``, write
    meta.json, and atomically publish the dir (``_commit``). Must only
    run once every process's shards + partial manifest are known durable
    (the sync path's barrier / the async path's pass-end agreement).
    ``expected_pids`` turns on the stale-file sweep (async saves reuse a
    tmp dir a crashed run may have littered); ``rotate=False`` lets a
    caller committing SEVERAL passes in one drain defer rotation until
    the last one (rotation sweeps ``*.tmp`` dirs — including, otherwise,
    the tmp of the next pass awaiting its own commit)."""
    final = os.path.join(save_dir, PASS_FMT % pass_id)
    tmp = final + TMP_SUFFIX
    tree_bases = list(tree_bases)
    if expected_pids is not None:
        _sweep_stale_sharded_files(tmp, tree_bases, expected_pids)
    for base in tree_bases:
        _merge_tree_indexes(tmp, base)
    _write_file(
        os.path.join(tmp, "meta.json"),
        lambda f: json.dump(meta, f, indent=2),
        mode="w",
    )
    _durable_manifest(
        ckpt_manifest.merge_partial_manifests, tmp, label="MANIFEST.json"
    )
    # peers' shards arrived over the shared fs — process 0 cannot vouch
    # for their bytes, so the merged pass never rides the verify skip
    _commit(tmp, final, self_written=False)
    if rotate:
        _rotate(save_dir, keep, protect=protect_pass)
    return final


def _merge_tree_indexes(path: str, base: str) -> None:
    """Process 0, after the barrier: merge partial indexes into
    ``<base>.index.json`` and drop the partials."""
    merged: Dict[str, Any] = {}
    for fn in sorted(os.listdir(path)):
        if not (fn.startswith(f"{base}.index.") and fn.endswith(".json")):
            continue
        if fn == f"{base}.index.json":
            continue
        with open(os.path.join(path, fn)) as f:
            partial = json.load(f)
        for name, entry in partial.items():
            if name in merged:
                assert merged[name]["shape"] == entry["shape"], name
                merged[name]["shards"].extend(entry["shards"])
            else:
                merged[name] = entry
        os.remove(os.path.join(path, fn))
    _write_file(
        os.path.join(path, f"{base}.index.json"),
        lambda f: json.dump(merged, f),
        mode="w",
    )


def _optimizer_trees(opt_state: UpdaterState) -> Dict[str, Dict]:
    trees = {"optimizer_slots": _flatten(opt_state.slots)}
    if opt_state.avg_sum is not None:
        trees["optimizer_avg"] = _flatten(opt_state.avg_sum)
    if opt_state.avg_old_sum is not None:
        trees["optimizer_avg_old"] = _flatten(opt_state.avg_old_sum)
    return trees


def build_save_trees(
    pass_id: int,
    params: Dict[str, jax.Array],
    opt_state: Optional[UpdaterState],
    extra_meta: Optional[Dict[str, Any]],
    multihost: bool,
) -> Tuple[Dict[str, Dict], Dict[str, Any]]:
    """(trees, meta) of one save — the single source both the sync
    ``save_checkpoint`` and the async sharded snapshot build from, so
    the two paths cannot diverge on format."""
    trees: Dict[str, Dict] = {"params": _flatten(params)}
    meta: Dict[str, Any] = {"pass_id": pass_id, "format_version": 2 if multihost else 1}
    if opt_state is not None:
        trees.update(_optimizer_trees(opt_state))
        meta["optimizer"] = {
            "step": int(opt_state.step),
            "num_samples": float(opt_state.num_samples),
            "avg_count": float(opt_state.avg_count),
            "avg_old_count": (
                float(opt_state.avg_old_count)
                if opt_state.avg_old_count is not None
                else 0.0
            ),
        }
    if extra_meta:
        meta.update(extra_meta)
    return trees, meta


def save_checkpoint(
    save_dir: str,
    pass_id: int,
    params: Dict[str, jax.Array],
    opt_state: Optional[UpdaterState] = None,
    extra_meta: Optional[Dict[str, Any]] = None,
    keep: int = 3,
    protect_pass: Optional[int] = None,
) -> str:
    """Save one pass directory, atomically. In multi-process runs every
    process must call this (collective); shards are written where they
    live instead of materializing cross-host arrays on process 0.

    Protocol: everything is written into ``pass-%05d.tmp`` (fsynced),
    a CRC32/size ``MANIFEST.json`` is recorded, then the tmp dir is
    renamed into place. A pre-existing final dir for the same pass (a
    periodic save followed by the pass-end save) is moved aside and
    removed only AFTER the rename — at every instant at least one
    complete checkpoint of this pass exists on disk. ``protect_pass``
    exempts one pass (the one this run restored from) from rolling
    deletion."""
    final = os.path.join(save_dir, PASS_FMT % pass_id)
    tmp = final + TMP_SUFFIX
    t0 = time.perf_counter()
    with stat_timer("checkpoint/save"):
        multihost = jax.process_count() > 1
        if jax.process_index() == 0:
            os.makedirs(save_dir, exist_ok=True)
            # a stale .tmp here is a crashed previous attempt at this pass —
            # garbage by definition (it never renamed); the FINAL dir stays
            # untouched until the fresh write is durable
            shutil.rmtree(tmp, ignore_errors=True)
            os.makedirs(tmp)
        trees, meta = build_save_trees(pass_id, params, opt_state, extra_meta, multihost)
        if multihost:
            from paddle_tpu.utils.barrier import host_barrier

            # everyone waits for mkdir, writes its shards + its slice of the
            # manifest, then process 0 merges partial indexes and manifests,
            # finalizes meta, and commits the rename. The barriers are HOST
            # barriers (distributed-runtime rendezvous): this is a pure
            # filesystem protocol and must not depend on the backend being
            # able to run cross-process device computations.
            host_barrier("ckpt_dir:" + os.path.basename(tmp))
            own_files = [_save_tree_sharded(tmp, base, flat) for base, flat in trees.items()]
            pid = jax.process_index()
            _durable_manifest(
                ckpt_manifest.write_partial_manifest, tmp, pid, own_files,
                label=f"MANIFEST.partial.{pid:05d}.json",
            )
            host_barrier("ckpt_shards:" + os.path.basename(tmp))
            if jax.process_index() == 0:
                finalize_sharded_pass(
                    save_dir, pass_id, trees, meta, keep=keep,
                    protect_pass=protect_pass,
                )
            host_barrier("ckpt_done:" + os.path.basename(final))
        else:
            for base, flat in trees.items():
                _write_file(
                    os.path.join(tmp, f"{base}.npz"),
                    lambda f, _flat=flat: np.savez(f, **_flat),
                )
            _write_file(
                os.path.join(tmp, "meta.json"),
                lambda f: json.dump(meta, f, indent=2),
                mode="w",
            )
            _durable_manifest(ckpt_manifest.write_manifest, tmp, label="MANIFEST.json")
            _commit(tmp, final)
            _rotate(save_dir, keep, protect=protect_pass)
    logger.info("saved checkpoint %s", final)
    _ckpt_record("save", final, t0, pass_id=pass_id, measure_bytes=True,
                 # mid-pass periodic saves (--saving_period_by_batches)
                 # of one pass are distinct stalls: the batch id keys
                 # them apart in `paddle metrics` dedupe
                 step=(extra_meta or {}).get("batch_id"))
    return final


def _commit(tmp: str, final: str, self_written: bool = True) -> None:
    """Atomically publish a complete tmp dir as the final pass dir. A
    crash before the rename leaves the old checkpoint untouched (plus a
    stale .tmp that the next save's rotation sweeps); a crash after it
    leaves the new checkpoint complete — there is no window in which
    neither is restorable.

    ``self_written=False`` (the sharded-pass merge commit): the dir
    holds shards PEER processes wrote over the shared fs, so it must
    not enter the trust-own-writes verify skip — this process can only
    vouch for bytes it wrote and fsynced itself."""
    _fsync_dir(tmp)
    fault_point("checkpoint.rename", info=os.path.basename(final))
    old = None
    if os.path.lexists(final):
        # re-save of the same pass id: POSIX cannot rename onto a
        # non-empty dir, so move the old one aside and drop it only
        # after the new dir is in place
        old = final + ".old"
        shutil.rmtree(old, ignore_errors=True)
        os.rename(final, old)
    os.rename(tmp, final)
    _fsync_dir(os.path.dirname(final) or ".")
    if self_written:
        _written_this_process.add(os.path.abspath(final))
    if old is not None:
        shutil.rmtree(old, ignore_errors=True)


def _rotate(save_dir: str, keep: int, protect: Optional[int] = None) -> None:
    """Rolling deletion of old pass dirs (ParamUtil::deleteOldestPass).

    Only completed ``pass-NNNNN`` dirs count toward the keep budget:
    ``*.tmp`` and ``*.corrupt`` dirs are not restorable state, and
    counting them would silently shrink the number of real checkpoints
    retained. Stale ``*.tmp`` dirs (crashed writes — ours already
    renamed) are swept outright; quarantined ``*.corrupt`` dirs are kept
    for post-mortem. ``protect`` (the pass this run restored from) is
    never rolled away: until a newer checkpoint proves itself loadable,
    it is the only state known-good."""
    names = os.listdir(save_dir)
    for d in names:
        # .tmp = crashed write; .old = crash inside _commit's two-rename
        # window — both are litter once a newer save completed. The one
        # exception: the .old of the protected pass, which may be the
        # very dir this run restored from (torn-commit recovery).
        if d.startswith("pass-") and (d.endswith(TMP_SUFFIX) or d.endswith(".old")):
            if protect is not None and d == (PASS_FMT % protect) + ".old":
                continue
            shutil.rmtree(os.path.join(save_dir, d), ignore_errors=True)
    if keep <= 0:
        return
    passes = sorted(int(d[5:]) for d in names if _is_pass_dir_name(d))
    for p in passes[:-keep]:
        if protect is not None and p == protect:
            continue
        shutil.rmtree(os.path.join(save_dir, PASS_FMT % p), ignore_errors=True)


def has_params_tree(path: str) -> bool:
    """True if a pass dir contains a params tree in either format."""
    return os.path.exists(os.path.join(path, "params.npz")) or os.path.exists(
        os.path.join(path, "params.index.json")
    )


def latest_pass(save_dir: str) -> Optional[int]:
    if not os.path.isdir(save_dir):
        return None
    passes = [
        int(d[5:]) for d in os.listdir(save_dir) if _is_pass_dir_name(d)
    ]
    return max(passes) if passes else None


def verify_checkpoint(path: str) -> List[str]:
    """Problems with one pass directory; empty list = restorable.

    Checks completeness (a params tree is present — meta.json stays
    optional, as in the loader) and, when a ``MANIFEST.json`` exists,
    every manifested file's size and CRC32. Pre-manifest checkpoints
    verify on completeness alone — old checkpoints must keep loading."""
    if not os.path.isdir(path):
        return [f"{path}: not a directory"]
    t0 = time.perf_counter()
    with stat_timer("checkpoint/verify"):
        problems: List[str] = []
        if not has_params_tree(path):
            problems.append("no params tree (params.npz / params.index.json)")
        # the CRC pass reads every manifested byte — transient shared-FS read
        # errors retry through the shared policy rather than condemning a
        # good checkpoint
        problems.extend(
            _io_policy().call(ckpt_manifest.verify_dir, path, name=f"verify {path}")
        )
    _ckpt_record("verify", path, t0, ok=not problems)
    return problems


def _shard_host(fname: str) -> Optional[int]:
    m = _SHARD_FILE_RE.match(fname)
    return int(m.group("pid")) if m else None


def verify_sharded_shards(path: str) -> List[str]:
    """Structural verification of the SHARDED trees in one pass dir —
    what the byte-level manifest check cannot see: every shard record in
    each merged index must resolve (its file present, its key in the npz
    archive), and the records of each parameter must cover its full
    extent exactly once (a bad merge that silently lost one host's
    partial index leaves a hole the manifest never notices, because the
    manifest only covers files that EXIST). Problems name the owning
    host parsed from the shard filename. Cheap: only zip directories are
    read, never shard data (CRC content checks are the manifest's job).
    Empty list = clean; non-sharded (format-1) dirs verify trivially."""
    problems: List[str] = []
    if not os.path.isdir(path):
        return [f"{path}: not a directory"]
    members: Dict[str, Optional[set]] = {}  # shard file -> npz keys (None=unreadable)

    def keys_of(fname: str) -> Optional[set]:
        if fname not in members:
            full = os.path.join(path, fname)
            if not os.path.exists(full):
                members[fname] = None
            else:
                try:
                    with zipfile.ZipFile(full) as z:
                        members[fname] = {
                            n[:-4] if n.endswith(".npy") else n
                            for n in z.namelist()
                        }
                except (OSError, zipfile.BadZipFile):
                    members[fname] = None
        return members[fname]

    for fn in sorted(os.listdir(path)):
        m = _MERGED_IDX_RE.match(fn)
        if not m or _PARTIAL_IDX_RE.match(fn):
            continue
        base = m.group("base")
        try:
            with open(os.path.join(path, fn)) as f:
                index = json.load(f)
        except (OSError, ValueError) as e:
            problems.append(f"{fn}: unreadable index ({e})")
            continue
        for name, entry in sorted(index.items()):
            total = 1
            for d in entry.get("shape", []):
                total *= int(d)
            covered = 0
            coverage_known = True
            for rec in entry.get("shards", []):
                fname = rec.get("file", "")
                host = _shard_host(fname)
                who = f"host {host}" if host is not None else fname
                keys = keys_of(fname)
                if keys is None:
                    word = ("missing" if not os.path.exists(os.path.join(path, fname))
                            else "unreadable")
                    problems.append(
                        f"{base}/{name}: shard file {fname} {word} ({who})"
                    )
                    coverage_known = False
                    continue
                if rec.get("key") not in keys:
                    problems.append(
                        f"{base}/{name}: record {rec.get('key')!r} absent "
                        f"from {fname} ({who})"
                    )
                    coverage_known = False
                    continue
                rshape = rec.get("shape")
                if rshape is None:
                    coverage_known = False  # pre-'shape' checkpoints
                    continue
                vol = 1
                for d in rshape:
                    vol *= int(d)
                covered += vol
            if coverage_known and covered != total:
                problems.append(
                    f"{base}/{name}: shard records cover {covered} of "
                    f"{total} elements (lost or duplicated host shards?)"
                )
            # row-sharded entries additionally prove EXACT row
            # coverage: a missing or overlapping row_range is a named
            # hole (check-checkpoint classifies these as PARTIAL),
            # never a silent zero-init on restore
            row_recs = [
                (rec["row_range"][0], rec["row_range"][1],
                 _shard_host(rec.get("file", "")))
                for rec in entry.get("shards", [])
                if rec.get("row_range")
            ]
            if row_recs and entry.get("shape"):
                from paddle_tpu.sparse import rowshard

                for msg in rowshard.coverage_problems(
                        int(entry["shape"][0]), row_recs):
                    problems.append(f"{base}/{name}: row coverage: {msg}")
    return problems


def partial_pass_report(save_dir: str) -> List[Tuple[str, int]]:
    """Uncommitted sharded saves under ``save_dir``: ``pass-N.tmp`` dirs
    a crashed run left behind, with how many per-process partial
    manifests each holds. These are NOT restorable (the pass never
    reached its commit agreement) — `paddle check-checkpoint` surfaces
    them so an operator can tell 'that save never landed' from 'all
    good'."""
    out: List[Tuple[str, int]] = []
    if not os.path.isdir(save_dir):
        return out
    for d in sorted(os.listdir(save_dir)):
        if not (d.endswith(TMP_SUFFIX)
                and _is_pass_dir_name(d[: -len(TMP_SUFFIX)])):
            continue
        full = os.path.join(save_dir, d)
        try:
            partials = sum(
                1 for fn in os.listdir(full) if _PARTIAL_MANIFEST_RE.match(fn)
            )
        except OSError:
            continue
        out.append((full, partials))
    return out


def find_restorable_checkpoint(
    save_dir: str, trust_own_writes: bool = False
) -> Optional[str]:
    """Newest pass dir under ``save_dir`` that verifies clean, or None.

    Read-only (corrupt candidates are logged and skipped, never
    quarantined here — that is load_checkpoint's job); backs
    ``--init_model_path=auto``.

    ``trust_own_writes``: skip the CRC walk for pass dirs this process
    committed itself (the trainer's in-run rollback path — re-reading a
    multi-GB checkpoint just to re-hash bytes this process wrote and
    fsynced minutes earlier is restart latency for nothing). Fresh
    processes have committed nothing, so cold restores always verify."""
    if not os.path.isdir(save_dir):
        return None
    passes = sorted(
        (int(d[5:]) for d in os.listdir(save_dir) if _is_pass_dir_name(d)),
        reverse=True,
    )
    for p in passes:
        path = os.path.join(save_dir, PASS_FMT % p)
        if trust_own_writes and written_this_process(path):
            logger.info(
                "find_restorable_checkpoint: %s was committed by this "
                "process — skipping re-verification", path,
            )
            return path
        problems = verify_checkpoint(path)
        if not problems:
            return path
        logger.warning(
            "find_restorable_checkpoint: skipping %s: %s", path, "; ".join(problems)
        )
    # last resort: a crash exactly between _commit's two renames leaves
    # the previous (fully durable, once-published) checkpoint as
    # pass-NNNNN.old — restorable even though unpublished. Never .tmp:
    # a tmp dir was never known complete+published as a whole.
    olds = sorted(
        (
            d for d in os.listdir(save_dir)
            if d.endswith(".old") and _is_pass_dir_name(d[: -len(".old")])
        ),
        reverse=True,
    )
    for d in olds:
        path = os.path.join(save_dir, d)
        if not verify_checkpoint(path):
            logger.warning(
                "find_restorable_checkpoint: recovering from torn commit "
                "leftover %s", path,
            )
            return path
    return None


def _quarantine(path: str) -> Optional[str]:
    """Rename a corrupt pass dir to ``*.corrupt`` (kept for post-mortem,
    excluded from rotation budgets and restore scans). Returns the new
    path, or None when quarantine was skipped (not a pass dir, already
    gone, or a non-0 process in a multi-host run — one renamer only)."""
    if not _is_pass_dir_name(os.path.basename(path)):
        return None
    if jax.process_count() > 1 and jax.process_index() != 0:
        return None
    dest = path + CORRUPT_SUFFIX
    n = 1
    while os.path.lexists(dest):
        dest = f"{path}{CORRUPT_SUFFIX}{n}"
        n += 1
    try:
        os.rename(path, dest)
    except OSError as e:
        logger.warning("could not quarantine %s: %s", path, e)
        return None
    # proven bad: it must never ride the trust-own-writes verify skip
    _written_this_process.discard(os.path.abspath(os.path.normpath(path)))
    logger.warning("quarantined corrupt checkpoint %s -> %s", path, dest)
    return dest


def _fallback_candidate(path: str) -> Optional[str]:
    """The newest pass dir older than ``path`` in the same save_dir, or
    None when ``path`` is not a pass dir / nothing older exists."""
    base = os.path.basename(path)
    if not _is_pass_dir_name(base):
        return None
    save_dir = os.path.dirname(path) or "."
    bad_id = int(base[5:])
    if not os.path.isdir(save_dir):
        return None
    older = [
        int(d[5:])
        for d in os.listdir(save_dir)
        if _is_pass_dir_name(d) and int(d[5:]) < bad_id
    ]
    if not older:
        return None
    return os.path.join(save_dir, PASS_FMT % max(older))


class _ShardedTreeReader:
    """Lazy reader over one sharded-format tree: `read_slice` loads ONLY
    the shard records overlapping the requested slice, so restoring onto a
    sharded layout costs O(local shard bytes) host memory per parameter —
    never O(full parameter) on every host (the reference streams blocks
    the same way, ParameterServer2.cpp:1150-1213). `bytes_read` counts the
    record bytes actually pulled off disk (tests pin the streaming claim
    on it)."""

    def __init__(self, path: str, index: Dict[str, Any]):
        self.path = path
        self.index = index
        self._files: Dict[str, Any] = {}
        self.bytes_read = 0

    def names(self):
        return self.index.keys()

    def spec(self, name: str) -> Tuple[Tuple[int, ...], np.dtype]:
        e = self.index[name]
        return tuple(e["shape"]), np.dtype(e["dtype"])

    def _record(self, rec) -> np.ndarray:
        z = self._files.get(rec["file"])
        if z is None:
            z = self._files[rec["file"]] = np.load(os.path.join(self.path, rec["file"]))
        data = z[rec["key"]]  # decompresses this member only
        self.bytes_read += data.nbytes
        return data

    def read_slice(self, name: str, idx, shape, dtype) -> np.ndarray:
        """Assemble the sub-array covering `idx` (a tuple of slices as
        handed out by jax.make_array_from_callback; None bounds mean the
        full axis)."""
        want = tuple(
            slice(s.start or 0, dim if s.stop is None else s.stop)
            for s, dim in zip(idx, shape)
        )
        out = np.zeros([w.stop - w.start for w in want], dtype)
        for rec in self.index[name]["shards"]:
            starts = rec["start"]
            data = None
            rec_shape = rec.get("shape")
            if rec_shape is None:  # pre-'shape' checkpoints: the probe
                data = self._record(rec)  # read doubles as the data read
                rec_shape = data.shape
            lo = [max(w.start, st) for w, st in zip(want, starts)]
            hi = [min(w.stop, st + d) for w, st, d in zip(want, starts, rec_shape)]
            if any(l >= h for l, h in zip(lo, hi)):
                continue  # no overlap: record never read (when indexed)
            if data is None:
                data = self._record(rec)
            src = tuple(slice(l - st, h - st) for l, h, st in zip(lo, hi, starts))
            dst = tuple(slice(l - w.start, h - w.start) for l, h, w in zip(lo, hi, want))
            out[dst] = data[src]
        return out

    def close(self):
        for z in self._files.values():
            z.close()


def _tree_index(path: str, base: str) -> Optional[Dict[str, Any]]:
    idx_path = os.path.join(path, f"{base}.index.json")
    if os.path.exists(idx_path):
        with open(idx_path) as f:
            return json.load(f)
    return None


def _load_tree_numpy(path: str, base: str) -> Optional[Dict[str, np.ndarray]]:
    """Read one tree as full host numpy arrays from either format, or
    None if the tree is absent (merge_model and single-process restores —
    the streaming path is load_checkpoint's sharding_for branch)."""
    index = _tree_index(path, base)
    if index is not None:
        reader = _ShardedTreeReader(path, index)
        try:
            return {
                name: reader.read_slice(
                    name, (slice(None),) * len(shape), shape, dtype
                )
                for name, (shape, dtype) in ((n, reader.spec(n)) for n in reader.names())
            }
        finally:
            reader.close()
    npz_path = os.path.join(path, f"{base}.npz")
    if os.path.exists(npz_path):
        with np.load(npz_path) as z:
            return {k: z[k] for k in z.files}
    return None


def load_checkpoint(
    path: str,
    opt_template: Optional[UpdaterState] = None,
    missing: str = "fail",
    expected_params: Optional[Dict[str, jax.Array]] = None,
    sharding_for: Optional[Callable[[str, str, Any], Any]] = None,
    io_stats: Optional[Dict[str, int]] = None,
    verify: bool = True,
    fallback: bool = True,
    trust_own_writes: bool = False,
) -> Tuple[Dict[str, jax.Array], Optional[UpdaterState], Dict[str, Any]]:
    """Load params (+ optimizer state rebuilt onto ``opt_template``),
    with verification and a fallback restore chain.

    ``verify``: check completeness + the CRC32/size manifest before
    deserializing anything. ``trust_own_writes``: also skip that check
    when ``path`` is a checkpoint THIS process committed earlier in the
    run (rollback/in-run restart) — verification cost belongs to cold
    restores, and a fresh process has committed nothing, so those keep
    the full verify. Only the first candidate is ever trusted; anything
    the fallback chain reaches is verified regardless. ``fallback``:
    when ``path`` is a ``pass-NNNNN`` dir that fails verification,
    quarantine it (``*.corrupt``) and retry with the newest earlier pass
    dir in the same save_dir, logging exactly what was skipped and why;
    raises CheckpointCorruptError only when no candidate survives. A
    mismatched model (``missing='fail'`` KeyError) is a config error,
    not corruption — it never triggers fallback.

    A path that does not exist at all is a caller error (wrong
    ``--start_pass``, a typo'd ``--init_model_path``) and raises
    FileNotFoundError up front — fallback is for checkpoints that went
    bad, never a license to silently substitute state the caller did
    not ask for.

    Multi-host: every process verifies the FULL manifest (an
    N_hosts × checkpoint-size read amplification on restore — the known
    cost of keeping verification collective-free; the optimization path
    is verify-on-process-0 + broadcast) and walks the fallback chain
    independently; only process 0 quarantines. Verification outcomes
    depend on per-process I/O, so under concurrent corruption hosts CAN
    diverge on the candidate — corrupt-restore on a pod is best-effort;
    when a pod-wide restore reports corruption, run
    ``paddle check-checkpoint`` and restart cleanly rather than relying
    on per-host fallback. See the remaining parameters on
    ``_load_checkpoint_once``."""
    tried: List[str] = []
    cur = os.path.normpath(path)
    if not os.path.isdir(cur):
        raise FileNotFoundError(f"checkpoint {cur} does not exist")
    t0 = time.perf_counter()
    first = True
    with stat_timer("checkpoint/load"):
        while True:
            # verify=False / trust_own_writes cover only the FIRST candidate
            # (the caller just CRC'd it, e.g. find_restorable_checkpoint, or
            # this process wrote it); anything the fallback chain reaches is
            # unvetted and must be verified here
            trusted = trust_own_writes and written_this_process(cur)
            if first and trusted and verify:
                logger.info(
                    "load_checkpoint: %s was committed by this process — "
                    "skipping re-verification", cur,
                )
            skip_crc = first and (not verify or trusted)
            problems = [] if skip_crc else verify_checkpoint(cur)
            # the corruption-vs-config disambiguation below may assume
            # clean bytes only when a CRC actually ran — here, or by the
            # caller (the verify=False contract). A trusted self-written
            # skip verified NOTHING: its deserialization failures must
            # enter the fallback chain, not re-raise as config errors.
            bytes_vetted = not (skip_crc and trusted)
            first = False
            if not problems:
                try:
                    result = _load_checkpoint_once(
                        cur, opt_template, missing, expected_params, sharding_for,
                        io_stats,
                    )
                    _ckpt_record(
                        "load", cur, t0,
                        pass_id=result[2].get("pass_id")
                        if isinstance(result[2].get("pass_id"), int) else None,
                        measure_bytes=True,
                        fallbacks=len(tried),
                    )
                    return result
                except (
                    FileNotFoundError,
                    EOFError,
                    ValueError,
                    zipfile.BadZipFile,
                    zlib.error,
                ) as e:
                    # corruption-shaped deserialization failures: no params
                    # tree, a file vanished between verify and read, or a
                    # torn/truncated archive in a PRE-MANIFEST checkpoint
                    # (np.load raises BadZipFile on truncation, zlib.error on
                    # corrupt members, ValueError/EOFError on garbage). But a
                    # checkpoint whose manifest just CRC-verified clean cannot
                    # be torn on disk — a ValueError there is a model/config
                    # mismatch (wrong shapes for this net), and quarantining
                    # good checkpoints over it would walk the whole chain into
                    # *.corrupt. Config errors propagate; only manifest-less
                    # dirs (and vanished files) enter the fallback chain here.
                    if (
                        bytes_vetted
                        and not isinstance(e, FileNotFoundError)
                        and ckpt_manifest.read_manifest(cur) is not None
                    ):
                        raise
                    problems = [f"load failed: {e}"]
            detail = f"{cur}: {'; '.join(problems)}"
            tried.append(detail)
            logger.error("checkpoint failed verification: %s", detail)
            nxt = _fallback_candidate(cur) if fallback else None
            if fallback:
                _quarantine(cur)
            if nxt is None:
                raise CheckpointCorruptError(
                    "no restorable checkpoint: " + " | ".join(tried), problems=tried
                )
            logger.warning("falling back to earlier checkpoint %s", nxt)
            cur = nxt


def _load_checkpoint_once(
    path: str,
    opt_template: Optional[UpdaterState] = None,
    missing: str = "fail",
    expected_params: Optional[Dict[str, jax.Array]] = None,
    sharding_for: Optional[Callable[[str, str, Any], Any]] = None,
    io_stats: Optional[Dict[str, int]] = None,
) -> Tuple[Dict[str, jax.Array], Optional[UpdaterState], Dict[str, Any]]:
    """Deserialize one (pre-verified) pass directory.

    ``missing``: fail | rand | zero — the reference's
    --load_missing_parameter_strategy; ``expected_params`` supplies shapes
    (and values, for 'rand') for parameters absent from the file.

    ``sharding_for(tree_base, flat_key, shape)`` (multi-process restore):
    returns the NamedSharding each value must live on; values are built with
    ``jax.make_array_from_callback`` so the restore re-shards onto the
    CURRENT mesh regardless of the layout the checkpoint was written
    with. Without it values load as host-local arrays (single process).

    Sharded-format trees restore STREAMING: each device slice is assembled
    from only the shard records overlapping it, so peak host memory is
    O(local shard bytes) per parameter, not O(parameter bytes) — the
    ParameterServer2 block-wise semantics. ``io_stats`` (optional dict)
    receives per-tree bytes actually read from shard files.
    """

    def put(base: str, key: str, full):
        if sharding_for is None:
            return jnp.asarray(full)
        full = np.asarray(full)
        sh = sharding_for(base, key, full.shape)
        return jax.make_array_from_callback(full.shape, sh, lambda idx, _f=full: _f[idx])

    def load_tree(base: str) -> Optional[Dict[str, jax.Array]]:
        index = _tree_index(path, base)
        if index is not None:
            reader = _ShardedTreeReader(path, index)
            try:
                out = {}
                for name in reader.names():
                    shape, dtype = reader.spec(name)
                    if sharding_for is None:
                        out[name] = jnp.asarray(
                            reader.read_slice(name, (slice(None),) * len(shape), shape, dtype)
                        )
                    else:
                        sh = sharding_for(base, name, shape)
                        # several local devices may ask for the same slice
                        # (replication): memoize per parameter so each
                        # record is decompressed at most once, holding at
                        # most this parameter's process-local bytes
                        memo: Dict[Any, np.ndarray] = {}

                        def cb(idx, n=name, s=shape, d=dtype, m=memo):
                            key = tuple((x.start, x.stop) for x in idx)
                            if key not in m:
                                m[key] = reader.read_slice(n, idx, s, d)
                            return m[key]

                        out[name] = jax.make_array_from_callback(shape, sh, cb)
                return out
            finally:
                if io_stats is not None:
                    io_stats[base] = reader.bytes_read
                reader.close()
        npz_path = os.path.join(path, f"{base}.npz")
        if not os.path.exists(npz_path):
            return None
        with np.load(npz_path) as z:
            return {k: put(base, k, z[k]) for k in z.files}

    params = load_tree("params")
    if params is None:
        raise FileNotFoundError(f"no params tree in checkpoint {path}")
    if expected_params is not None:
        for name, val in expected_params.items():
            if name not in params:
                if missing == "fail":
                    raise KeyError(f"parameter {name!r} missing from checkpoint {path}")
                params[name] = jnp.zeros_like(val) if missing == "zero" else val
    meta = {}
    meta_path = os.path.join(path, "meta.json")
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            meta = json.load(f)
    opt_state = None
    slot_vals = load_tree("optimizer_slots") if opt_template is not None else None
    if opt_template is not None and slot_vals is not None:
        slots = _unflatten(slot_vals)
        om = meta.get("optimizer", {})
        avg_sum = opt_template.avg_sum
        if avg_sum is not None:
            avg_sum = load_tree("optimizer_avg") or avg_sum
        avg_old_sum = opt_template.avg_old_sum
        if avg_old_sum is not None:
            avg_old_sum = load_tree("optimizer_avg_old") or avg_old_sum

        def scalar(v, dtype):
            # multi-process: keep host numpy — jit treats it as replicated
            # input; a committed single-device jnp array would fail to
            # reshard across processes
            return np.asarray(v, dtype) if sharding_for is not None else jnp.asarray(v, dtype)

        opt_state = UpdaterState(
            step=scalar(om.get("step", 0), jnp.int32),
            num_samples=scalar(om.get("num_samples", 0.0), jnp.float32),
            slots=slots,
            avg_sum=avg_sum,
            avg_count=scalar(om.get("avg_count", 0.0), jnp.float32),
            avg_old_sum=avg_old_sum,
            avg_old_count=scalar(om.get("avg_old_count", 0.0), jnp.float32),
        )
    logger.info("loaded checkpoint %s", path)
    return params, opt_state, meta


def merge_model(save_dir: str, pass_id: int, config_json: str, out_path: str) -> None:
    """MergeModel analog (/root/reference/paddle/trainer/MergeModel.cpp):
    bundle config + parameters into one deployable .npz."""
    path = os.path.join(save_dir, PASS_FMT % pass_id)
    arrays = _load_tree_numpy(path, "params")
    if arrays is None:
        raise FileNotFoundError(f"no params tree in checkpoint {path}")
    arrays["__config_json__"] = np.frombuffer(config_json.encode(), dtype=np.uint8)
    np.savez(out_path, **arrays)
