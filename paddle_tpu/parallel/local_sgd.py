"""Local SGD — the TPU-native analog of the reference's async SGD.

Reference semantics (``settings(is_async=True)`` → ``algorithm=
'async_sgd'``, proto default ``TrainerConfig.proto.m4:22``): the pserver
applies each trainer's gradient the moment it arrives instead of waiting
for a synchronized batch (`ParameterServer2.cpp:572` op dispatch without
the sync barriers), and discards gradients that lag more than
``async_lagged_grad_discard_ratio`` behind the current update count
(`TrainerConfig.proto.m4:124-129`, `config_parser.py:2929-2930`).

An SPMD step is lock-step by construction, so apply-on-arrival is
re-designed rather than translated (doc/divergences.md):

- Every data-parallel replica keeps its OWN parameter + optimizer-state
  copy and applies its local gradient immediately each batch — the
  analog of a trainer not waiting for the others. The per-batch step has
  ZERO cross-replica collectives: it is one ``jax.vmap`` over the
  replica axis, which XLA maps 1:1 onto the ``data`` mesh axis.
- Every ``num_batches_per_send_parameter`` batches the replicas merge by
  parameter averaging (one weighted all-reduce of params + slots) — the
  "send parameter" analog.
- The staleness discard maps to a drift gate at the merge: replicas
  whose distance from the element-wise median model exceeds
  ``async_lagged_grad_discard_ratio × R ×`` the median replica drift
  are excluded from the average (their divergent work is discarded,
  exactly what the pserver did to lagged gradients) and snapped to the
  merged values. The R-scaled median statistic is calibrated so
  ordinary stochastic replica spread (≲2-3× the median) never triggers
  while genuine divergence (NaN, exploding replicas) always does —
  mirroring the reference gate, which never fired in healthy runs.
  ``ratio <= 0`` disables the gate.

Determinism note: unlike the reference's wall-clock-dependent async
path, this mode is bit-reproducible — "staleness" is measured in
parameter space, not arrival time, so runs are identical across
repeats. Each replica draws its own rng stream (``jax.random.split`` of
the step key), mirroring per-trainer dropout streams.

Constraints (same reasons as gradient accumulation,
trainer.py::_build_accum_steps): dense gradients only (row-sparse shapes
vary per batch and cannot ride the fixed-shape replica stack), and the
mesh must be data-parallel only — tensor-parallel params have no
per-replica copy to diverge.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def data_axis_size(mesh: Mesh) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get("data", 1)


def check_data_only(mesh: Mesh) -> None:
    for ax, size in zip(mesh.axis_names, mesh.devices.shape):
        if ax != "data" and size > 1:
            raise ValueError(
                "async_sgd (local SGD) is data-parallel only; mesh axis "
                f"{ax!r} has size {size} — drop it or use sync SGD"
            )


def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)


class LocalSgd:
    """Jitted machinery for one local-SGD run: ``stack`` canonical trees
    into per-replica stacks, per-batch ``step``, periodic ``merge``, and
    ``collapse`` back to canonical (replica-0) trees.

    Stacked trees carry a leading replica axis of size R sharded over the
    ``data`` mesh axis, so each device holds exactly its own replica —
    the same per-device memory as the replicated sync path.
    """

    def __init__(self, step_body, mesh: Mesh, ratio: float):
        """``step_body(params, opt_state, batch, rng, batch_size) ->
        (new_params, new_opt, loss, kept_outputs, *sums)`` is the SAME
        one-batch closure the sync path jits (Trainer._one_batch_step /
        __graft_entry__._train_step) — taken whole, not rebuilt from
        grad_fn + updater, so the sync and local-SGD per-batch semantics
        cannot diverge. ``sums`` (the trainer's: the evaluators'
        per-batch states) are statistics of a replica's sub-batch that
        add up to the global batch's."""
        check_data_only(mesh)
        self.mesh = mesh
        self.R = data_axis_size(mesh)
        self.ratio = float(ratio)
        self._step_body = step_body
        self._stacked = NamedSharding(mesh, P("data"))
        self._repl = NamedSharding(mesh, P())
        self._step_cache: Dict[Any, Any] = {}
        self._merge_fn = None
        self._view_fn = None
        self._stack_fn = None
        self._collapse_fn = None

    # ------------------------------------------------------------- stack

    def stack(self, params, opt_state):
        """Broadcast canonical trees to [R, ...] replica stacks (all
        replicas start identical, like trainers pulling the same initial
        model from the pserver)."""
        if self._stack_fn is None:
            R = self.R

            def bcast(tree):
                return jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x, (R,) + jnp.shape(x)), tree
                )

            self._stack_fn = jax.jit(bcast, out_shardings=self._stacked)
        return self._stack_fn(params), self._stack_fn(opt_state)

    def collapse(self, params_r, opt_r):
        """Replica 0 of each stacked tree as canonical replicated values.
        Call only after a merge — replicas must be identical, or work
        from replicas 1..R-1 would be dropped silently."""
        if self._collapse_fn is None:
            self._collapse_fn = jax.jit(
                lambda tree: jax.tree_util.tree_map(lambda x: x[0], tree),
                out_shardings=self._repl,
            )
        return self._collapse_fn(params_r), self._collapse_fn(opt_r)

    # -------------------------------------------------------------- step

    def step(self, params_r, opt_r, batch, rng, n):
        """One local update on every replica: the global batch [B, ...]
        splits into R contiguous sub-batches (a local reshape — the batch
        is already sharded over ``data``), each replica applies its own
        gradient to its own copy. ``n`` (global sample count) advances
        every replica's schedule counter — replicas move in lockstep
        through the global data stream, matching the reference pserver's
        global ``num_samples_processed``."""
        treedef = jax.tree_util.tree_structure(batch)
        fn = self._step_cache.get(treedef)
        if fn is None:
            fn = self._build_step(batch)
            self._step_cache[treedef] = fn
        return fn(params_r, opt_r, batch, rng, n)

    def _build_step(self, batch_example):
        R = self.R
        body = self._step_body

        def lstep(params_r, opt_r, batch, rng, n):
            batch_r = jax.tree_util.tree_map(
                lambda x: x.reshape((R, x.shape[0] // R) + x.shape[1:]), batch
            )
            rngs = jax.random.split(rng, R)
            # n (the GLOBAL sample count) broadcasts unmapped: every
            # replica advances its schedule counter by the global batch
            new_pr, new_or, losses, keeps, *sums = jax.vmap(
                body, in_axes=(0, 0, 0, 0, None)
            )(params_r, opt_r, batch_r, rngs, n)
            # kept outputs back to global batch order [B, ...] for the
            # evaluator chain (replica blocks are contiguous row blocks)
            keep_flat = jax.tree_util.tree_map(
                lambda x: x.reshape((-1,) + x.shape[2:]) if x.ndim >= 2 else x,
                keeps,
            )
            # summed over the replicas beside the loss's mean: a few
            # numbers, the same on every process
            sums = jax.tree_util.tree_map(lambda x: x.sum(axis=0), sums)
            return (new_pr, new_or), (jnp.mean(losses), keep_flat, *sums)

        b_spec = jax.tree_util.tree_map(lambda _: self._stacked, batch_example)
        jitted = jax.jit(
            lstep,
            in_shardings=(self._stacked, self._stacked, b_spec, self._repl, self._repl),
            # the second group is as long as the body's own outputs
            out_shardings=((self._stacked, self._stacked), None),
            donate_argnums=(0, 1),
        )

        def step(*args):
            stacks, rest = jitted(*args)
            return (*stacks, *rest)

        return step

    # ------------------------------------------------------------- merge

    def merge(self, params_r, opt_r):
        """Drift-gated parameter averaging across replicas. Returns the
        merged stacks (all replicas identical afterwards) and the number
        of replicas whose work was discarded by the staleness gate."""
        if self._merge_fn is None:
            self._merge_fn = self._build_merge()
        return self._merge_fn(params_r, opt_r)

    def merged_view(self, params_r, opt_r):
        """Read-only merged snapshot as canonical (replicated) trees —
        the same drift-gated weighted average as ``merge`` but WITHOUT
        touching the replica stacks. Mid-pass observability (periodic
        test/stats/checkpoint) reads this, exactly as the reference's
        test path read the pserver's merged parameters without
        collapsing the trainers' local progress — a logging flag must
        not perturb the optimization trajectory or the merge schedule."""
        if self._view_fn is None:
            self._view_fn = self._build_view()
        return self._view_fn(params_r, opt_r)

    def _gate_weights(self, params_r):
        """Drift-gate weights [R] + discard count.

        Per-replica drift ||p_i - median(p)|| is measured from the
        element-wise MEDIAN model: a diverged replica cannot drag the
        anchor toward itself (a mean anchor caps any outlier's relative
        drift at (R-1)x and gets ordinary stochastic variation discarded
        instead). Gate at ratio*R*median(drift): benign replica spread
        stays within ~2-3x of the median, a genuinely broken replica
        (exploding, NaN) is orders of magnitude out, so the margin is
        wide on both sides. Non-finite replicas are handled OUTSIDE the
        drift statistic: a single NaN element would make the plain
        median (and then every replica's drift) NaN, rejecting everyone
        and letting the keep-everyone insurance average the NaN in — so
        the anchor is the nanmedian and a replica with any non-finite
        parameter is discarded by its own finiteness mask."""
        R, ratio = self.R, self.ratio
        leaves = [
            x.astype(jnp.float32)
            for x in jax.tree_util.tree_leaves(params_r)
            if _is_float(x)
        ]
        finite = jnp.ones((R,), bool)
        sq = []
        for xf in leaves:
            finite &= jnp.isfinite(xf).reshape(R, -1).all(axis=1)
            med = jnp.nanmedian(xf, axis=0, keepdims=True)
            d = ((xf - med) ** 2).reshape(R, -1)
            sq.append(jnp.where(jnp.isfinite(d), d, 0.0).sum(axis=1))
        drift = jnp.sqrt(sum(sq)) if sq else jnp.zeros((R,), jnp.float32)
        if ratio > 0:
            med_drift = jnp.nanmedian(jnp.where(finite, drift, jnp.nan))
            # median 0 = at least half the replicas sit exactly on the
            # median model (e.g. just-stacked identical replicas):
            # anything that moved off it is divergent by definition
            keep = finite & (
                drift <= jnp.where(med_drift > 0, ratio * R * med_drift, 0.0)
            )
        else:
            keep = jnp.ones((R,), bool)
        w = keep.astype(jnp.float32)
        wsum = w.sum()
        # a gate that rejects everyone keeps everyone (mirrors the
        # reference never discarding ALL gradients of an update);
        # unreachable with the median gate but cheap insurance
        w = jnp.where(wsum > 0, w / jnp.maximum(wsum, 1.0), jnp.full((R,), 1.0 / R))
        discarded = (R - keep.sum()).astype(jnp.int32)
        return w, discarded

    def _wmean(self, w, x):
        """Gate-weighted mean of one stacked leaf → canonical [..] value."""
        R = self.R
        if not _is_float(x):
            return x[0]  # int counters are replica-identical (lockstep)
        wx = w.reshape((R,) + (1,) * (x.ndim - 1))
        # zero the discarded replicas' values BEFORE the weighted sum —
        # 0 * NaN is NaN, so a NaN replica would otherwise poison the
        # merge through its zero weight
        xf = jnp.where(wx > 0, x.astype(jnp.float32), 0.0)
        return (xf * wx).sum(0).astype(x.dtype)

    def _build_merge(self):
        def merge(params_r, opt_r):
            w, discarded = self._gate_weights(params_r)

            def wmean_bcast(x):
                if not _is_float(x):
                    return x
                return jnp.broadcast_to(self._wmean(w, x), x.shape)

            new_pr = jax.tree_util.tree_map(wmean_bcast, params_r)
            new_or = jax.tree_util.tree_map(wmean_bcast, opt_r)
            return new_pr, new_or, discarded

        return jax.jit(
            merge,
            in_shardings=(self._stacked, self._stacked),
            out_shardings=(self._stacked, self._stacked, None),
            donate_argnums=(0, 1),
        )

    def _build_view(self):
        def view(params_r, opt_r):
            w, _ = self._gate_weights(params_r)
            wm = lambda x: self._wmean(w, x)
            return (
                jax.tree_util.tree_map(wm, params_r),
                jax.tree_util.tree_map(wm, opt_r),
            )

        # NOT donated: the stacks stay live for the next local step
        return jax.jit(
            view,
            in_shardings=(self._stacked, self._stacked),
            out_shardings=(self._repl, self._repl),
        )
