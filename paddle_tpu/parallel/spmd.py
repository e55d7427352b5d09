"""SPMD sharding of the train/test step over a mesh.

This replaces the reference's gradient ring (MultiGradientMachine.h:62-80)
and pserver sync-SGD (ParameterServer2::addGradient/op_SGD,
/root/reference/paddle/pserver/ParameterServer2.cpp:352,1035): instead of
shipping gradients over threads/sockets, the ONE jitted step is compiled
with sharded inputs — XLA partitions the computation and inserts
psum/all-gather over ICI where the math requires it. Sync-SGD semantics
(num_batches_per_send_parameter == 1) fall out exactly: the optimizer
update sees the full-batch mean gradient every step. The async/stale path
is deliberately not reproduced (doc/divergences.md).

Sharding rules:
- batch Arguments: leading axis over the "data" mesh axis
- parameters: replicated, unless ParameterConfig.sharding names mesh axes
  (tensor parallelism), e.g. sharding=["model", null] shards dim 0
- optimizer slots follow their parameter's sharding
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.optimizer.updater import UpdaterState


def batch_sharding(mesh: Mesh) -> NamedSharding:
    if "data" in mesh.axis_names:
        return NamedSharding(mesh, P("data"))
    return NamedSharding(mesh, P())


def param_sharding(mesh: Mesh, param_cfg) -> NamedSharding:
    if param_cfg is not None and param_cfg.sharding:
        axes = [a if (a and a in mesh.axis_names) else None for a in param_cfg.sharding]
        return NamedSharding(mesh, P(*axes))
    return NamedSharding(mesh, P())


def _param_shardings(mesh: Mesh, gm) -> Dict[str, NamedSharding]:
    return {name: param_sharding(mesh, cfg) for name, cfg in gm.param_configs.items()}


def _slot_sharding(mesh: Mesh, param_sh: NamedSharding, ndim: Optional[int]) -> NamedSharding:
    """Optimizer-slot sharding policy (single source of truth, used by the
    train-step in_shardings AND checkpoint restore): row-wise slots (e.g.
    sparse t_last, [V]) take the leading axes of the parameter's spec;
    full-shape slots take it whole."""
    spec = tuple(param_sh.spec)
    if ndim is not None:
        spec = spec[:ndim]
    return NamedSharding(mesh, P(*spec))


def _opt_state_sharding(mesh: Mesh, param_shards: Dict[str, NamedSharding], opt_state: UpdaterState):
    repl = NamedSharding(mesh, P())

    def slot_shard(name, arr):
        ps = param_shards.get(name, repl)
        return _slot_sharding(mesh, ps, arr.ndim if hasattr(arr, "ndim") else None)

    slots = {
        name: {slot: slot_shard(name, arr) for slot, arr in d.items()}
        for name, d in opt_state.slots.items()
    }
    avg = (
        {name: param_shards.get(name, repl) for name in opt_state.avg_sum}
        if opt_state.avg_sum is not None
        else None
    )
    avg_old = (
        {name: param_shards.get(name, repl) for name in opt_state.avg_old_sum}
        if opt_state.avg_old_sum is not None
        else None
    )
    return UpdaterState(
        step=repl, num_samples=repl, slots=slots, avg_sum=avg, avg_count=repl,
        avg_old_sum=avg_old,
        avg_old_count=repl if opt_state.avg_old_count is not None else None,
    )


@functools.lru_cache(maxsize=8)
def _replicate_fn(mesh: Mesh):
    # one cached PjitFunction per mesh so per-batch gathers hit the jit
    # cache instead of retracing every call
    return jax.jit(lambda a: a, out_shardings=NamedSharding(mesh, P()))


def replicate_to_host(x, mesh: Mesh):
    """All-gather a (possibly cross-host sharded) array and return the
    FULL value as host numpy on every process. The jit identity with a
    replicated out_sharding compiles to one all-gather over ICI."""
    import numpy as np

    return np.asarray(_replicate_fn(mesh)(x).addressable_data(0))


def gather_outputs(outputs, mesh: Mesh, names=None):
    """Materialize (selected) layer outputs as full host values on every
    process — the distributeEval analog (reference Evaluator::
    distributeEval merges per-trainer evaluator state over the pserver,
    /root/reference/paddle/gserver/evaluators/Evaluator.h:81-82; here
    each host instead sees the full small output batch and computes
    identical merged metrics). ``names`` limits the gather to the layers
    the evaluator chain actually reads. The whole picked tree goes through
    ONE jitted all-gather (one collective, one host sync per batch)."""
    import numpy as np

    picked = outputs if names is None else {k: outputs[k] for k in names if k in outputs}
    rep = _replicate_fn(mesh)(picked)
    return jax.tree_util.tree_map(lambda x: np.asarray(x.addressable_data(0)), rep)


class NotRowLocal(Exception):
    """An output's process-local rows cannot be assembled on this host
    (non-batch axes sharded across devices, or an exotic layout) — the
    caller falls back to the full per-batch gather."""


def rows_locally_assemblable(outputs, names=None) -> bool:
    """Decide from GLOBAL sharding metadata whether every selected leaf's
    rows can be assembled process-locally. The decision must be identical
    on every process (it gates which collective runs next — a per-process
    disagreement would deadlock), so it only consults sharding specs,
    never this process's addressable shards."""
    picked = outputs if names is None else {k: outputs[k] for k in names if k in outputs}

    def ok(x) -> bool:
        if not isinstance(x, jax.Array) or x.ndim == 0:
            return True
        spec = getattr(x.sharding, "spec", None)
        if spec is None:
            return False  # not a NamedSharding: no portable metadata
        # axes beyond the batch axis must be unsharded (a PartitionSpec
        # shorter than ndim leaves trailing axes unsharded)
        return all(p is None for p in tuple(spec)[1:])

    return all(
        ok(leaf) for leaf in jax.tree_util.tree_leaves(picked)
    )


def local_row_block(outputs, names=None):
    """Each process's contiguous row block of (selected) batch-leading
    outputs as host numpy — the input side of sufficient-statistics
    evaluator merging (reference Evaluator::getState/distributeEval,
    Evaluator.h:81-82): processes accumulate metrics over disjoint row
    blocks locally and SUM small state vectors once per period, instead
    of all-gathering raw [B, V] activations every batch.

    Process p takes rows [B*p/pc, B*(p+1)/pc) of every leaf: replicated
    leaves are sliced on the host; batch-sharded leaves are assembled from
    the replica-0 addressable shards, which must tile exactly that block
    (the standard data-axis layout built by globalize_batch). Check
    rows_locally_assemblable first; an unexpected layout here raises
    NotRowLocal, which the caller must treat as fatal (the decision
    already committed every process to this path).
    """
    import numpy as np

    pid, pc = jax.process_index(), jax.process_count()
    picked = outputs if names is None else {k: outputs[k] for k in names if k in outputs}

    def loc(x):
        if not isinstance(x, jax.Array) or x.ndim == 0:
            return np.asarray(x)
        B = x.shape[0]
        lo, hi = B * pid // pc, B * (pid + 1) // pc
        if x.is_fully_addressable:
            return np.asarray(x)[lo:hi]
        # replicated across processes: some addressable shard holds the
        # full batch axis — slice this process's block from it
        for sh in x.addressable_shards:
            row_sl = sh.index[0] if sh.index else slice(None)
            if (row_sl.start or 0) == 0 and row_sl.stop in (None, B):
                return np.asarray(sh.data)[lo:hi]
        rows = sorted(
            ((s.index[0].start or 0, np.asarray(s.data))
             for s in x.addressable_shards if s.replica_id == 0),
            key=lambda t: t[0],
        )
        expect = lo
        for start, data in rows:
            if start != expect:
                raise NotRowLocal(f"non-contiguous rows at {start} (shape {x.shape})")
            expect += data.shape[0]
        if not rows or rows[0][0] != lo or expect != hi:
            raise NotRowLocal(f"rows {[r[0] for r in rows]} != [{lo}:{hi}] (shape {x.shape})")
        return np.concatenate([d for _, d in rows], axis=0)

    return jax.tree_util.tree_map(loc, picked)


def merge_eval_states(vec):
    """SUM a small per-process evaluator state vector across processes
    (one host allgather per read period — the distributeEval merge)."""
    import numpy as np

    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(np.asarray(vec))).sum(axis=0)


def checkpoint_sharding_fn(mesh: Mesh, gm):
    """(tree_base, flat_key, shape) → NamedSharding for checkpoint restore:
    params and averaging sums take the parameter's sharding; optimizer
    slots take the leading axes of their parameter's spec (row-wise slots
    like sparse t_last are [V]-shaped); everything else replicates."""
    param_shards = _param_shardings(mesh, gm)
    repl = NamedSharding(mesh, P())

    def fn(base: str, key: str, shape) -> NamedSharding:
        if base in ("params", "optimizer_avg", "optimizer_avg_old"):
            return param_shards.get(key, repl)
        if base == "optimizer_slots":
            pname = key.split("/", 1)[0]
            return _slot_sharding(mesh, param_shards.get(pname, repl), len(shape))
        return repl

    return fn


def owned_row_range(arr) -> "tuple[int, int]":
    """The contiguous ``[lo, hi)`` row interval of a dim-0-sharded
    array whose rows THIS process uniquely owns (replica_id == 0) —
    the live-array twin of the ``row_range`` stamped into sparse shard
    records (doc/sparse.md).  A replicated array owns every row on
    process 0 and nothing elsewhere; a process owning non-contiguous
    row blocks is a layout this framework never produces, and raises.
    """
    rows = []
    for sh in arr.addressable_shards:
        if sh.replica_id != 0:
            continue
        sl = sh.index[0] if sh.index else slice(0, int(arr.shape[0]))
        lo = int(sl.start or 0)
        hi = int(sl.stop) if sl.stop is not None else int(arr.shape[0])
        rows.append((lo, hi))
    if not rows:
        return (0, 0)
    rows.sort()
    merged = [list(rows[0])]
    for lo, hi in rows[1:]:
        if lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    if len(merged) != 1:
        raise ValueError(
            f"non-contiguous owned row blocks {merged} — not a "
            "row-sharded table layout"
        )
    return (merged[0][0], merged[0][1])


def _batch_tree_sharding(mesh: Mesh, batch) -> Any:
    bs = batch_sharding(mesh)
    return jax.tree_util.tree_map(lambda _: bs, batch)


def globalize_batch(batch, mesh: Mesh):
    """Assemble per-process batch slices into global sharded arrays.

    Multi-host analog of the reference's per-trainer data partitions
    (each pserver trainer reads its own split): every process builds the
    same host-level batch (providers are seeded identically), takes its
    contiguous row block, and jax.make_array_from_process_local_data
    glues the blocks into one global array sharded over the 'data' axis.
    No-op in single-process mode. Returns None for a remainder batch
    whose size is not divisible by the process count (the end-of-pass
    partial batch) — the caller skips it; sync-SGD needs every host to
    contribute an identical batch structure.
    """
    import numpy as np

    pc = jax.process_count()
    if pc == 1:
        return batch
    bs = batch_sharding(mesh)
    pid = jax.process_index()
    first = next(
        v
        for v in jax.tree_util.tree_leaves(batch)
        if hasattr(v, "shape") and v.shape
    )
    if first.shape[0] % pc != 0:
        return None

    def put(x):
        if x is None:
            return None
        x = np.asarray(x)
        n = x.shape[0] // pc
        local = x[pid * n : (pid + 1) * n]
        return jax.make_array_from_process_local_data(bs, local, x.shape)

    return jax.tree_util.tree_map(put, batch)


def shard_train_step(step, mesh: Mesh, gm, donate: bool = True,
                     extra_outs: int = 0):
    """Wrap a (params, opt_state, batch, rng, batch_size) step with mesh
    shardings. Shardings for the batch depend on its treedef, so the jit is
    built lazily per batch structure and cached. ``donate=False`` keeps the
    input buffers valid after the call (the trainer's skip/rollback
    divergence policies must be able to discard a poisoned update).
    ``extra_outs``: trailing aux outputs beyond the canonical
    (params, opt_state, loss, keep) — the evaluators' per-batch states
    and the numerics health pytree ride this way, replicated: a few
    numbers each, which every process reads back whole."""
    param_shards = _param_shardings(mesh, gm)
    repl = NamedSharding(mesh, P())
    bs = batch_sharding(mesh)
    cache: Dict[Any, Any] = {}

    def jitted(params, opt_state, batch):
        treedef = jax.tree_util.tree_structure((opt_state, batch))
        fn = cache.get(treedef)
        if fn is None:
            p_spec = {k: param_shards.get(k, repl) for k in params}
            o_spec = _opt_state_sharding(mesh, param_shards, opt_state)
            b_spec = jax.tree_util.tree_map(lambda _: bs, batch)
            # pin param/opt-state outputs to the same shardings as the
            # inputs so step N's outputs are valid step N+1 inputs
            fn = jax.jit(
                step,
                in_shardings=(p_spec, o_spec, b_spec, repl, repl),
                out_shardings=(p_spec, o_spec, None, None)
                + (repl,) * extra_outs,
                donate_argnums=(0, 1) if donate else (),
            )
            cache[treedef] = fn
        return fn

    def call(params, opt_state, batch, rng, batch_size):
        return jitted(params, opt_state, batch)(
            params, opt_state, batch, rng, batch_size)

    # the AOT seam CompileRegistry compiles through: the mesh step gets
    # the same split trace/compile timing, cost and memory analysis and
    # HLO census (kernels, collectives, devices) as the one-device step
    call.lower = lambda params, opt_state, batch, rng, batch_size: jitted(
        params, opt_state, batch).lower(
            params, opt_state, batch, rng, batch_size)
    return call


def shard_accum_steps(astep, ustep, mesh: Mesh, gm, donate: bool = True):
    """Mesh-shard the gradient-accumulation pair
    (num_batches_per_send_parameter > 1): ``astep(params, acc, batch,
    rng, n) -> (params, acc, loss, keep, eval_states)`` accumulates one
    batch's gradients; ``ustep(params,
    opt_state, acc, total_n)`` applies one optimizer update. The
    accumulator tree mirrors the parameter tree, so it takes the
    parameter shardings. ``donate=False``: see shard_train_step."""
    param_shards = _param_shardings(mesh, gm)
    repl = NamedSharding(mesh, P())
    bs = batch_sharding(mesh)
    a_cache: Dict[Any, Any] = {}
    u_fn = None

    def p_spec(params):
        return {k: param_shards.get(k, repl) for k in params}

    def a_call(params, acc, batch, rng, n):
        treedef = jax.tree_util.tree_structure(batch)
        fn = a_cache.get(treedef)
        if fn is None:
            ps = p_spec(params)
            b_spec = jax.tree_util.tree_map(lambda _: bs, batch)
            fn = jax.jit(
                astep,
                in_shardings=(ps, ps, b_spec, repl, repl),
                out_shardings=(ps, ps, None, None, repl),
                donate_argnums=(0, 1) if donate else (),
            )
            a_cache[treedef] = fn
        return fn(params, acc, batch, rng, n)

    def u_call(params, opt_state, acc, total_n):
        # the opt-state structure is fixed for a run: one jit, built lazily
        nonlocal u_fn
        if u_fn is None:
            ps = p_spec(params)
            o_spec = _opt_state_sharding(mesh, param_shards, opt_state)
            u_fn = jax.jit(
                ustep,
                in_shardings=(ps, o_spec, ps, repl),
                out_shardings=(ps, o_spec, ps),
                donate_argnums=(0, 1, 2) if donate else (),
            )
        return u_fn(params, opt_state, acc, total_n)

    return a_call, u_call


def shard_test_fwd(fwd, mesh: Mesh, gm):
    param_shards = _param_shardings(mesh, gm)
    repl = NamedSharding(mesh, P())
    bs = batch_sharding(mesh)
    cache: Dict[Any, Any] = {}

    def call(params, batch):
        treedef = jax.tree_util.tree_structure(batch)
        fn = cache.get(treedef)
        if fn is None:
            p_spec = {k: param_shards.get(k, repl) for k in params}
            b_spec = jax.tree_util.tree_map(lambda _: bs, batch)
            fn = jax.jit(fwd, in_shardings=(p_spec, b_spec))
            cache[treedef] = fn
        return fn(params, batch)

    return call
