"""Trainer — the pass/batch training driver.

TPU-native replacement for the reference's Trainer/TrainerInternal
(/root/reference/paddle/trainer/Trainer.cpp:266-477,
TrainerInternal.cpp:64-170): the per-batch
startBatch → forwardBackward(updateCallback) → finishBatch pipeline
becomes ONE jit-compiled train_step (forward + grad + optimizer update
fused by XLA, buffers donated); the pass loop, periodic test, stats and
checkpointing stay on the host. So do the evaluators' accumulators, but
not their per-batch arithmetic where it is a masked reduction of layer
outputs (classification_error, seq_classification_error, sum,
last-column-sum): the step computes those statistics where the layers'
values already are and returns {evaluator name: f32[k]} beside the loss,
read in the same transfer; only the layers of the other evaluators
(AUC, chunk, CTC, printers, ...) are still program outputs
(``_keep_and_eval_states``).

When a mesh is configured (opt_config.mesh_shape / FLAGS.mesh_shape) the
step is sharded over devices — see paddle_tpu.parallel.spmd — which is the
replacement for MultiGradientMachine's thread ring and the pserver's dense
sync path.
"""

from __future__ import annotations

import functools
import math
import os
import sys
import time
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.data.feeder import DataProvider, create_data_provider
from paddle_tpu.graph.argument import Argument
from paddle_tpu.resilience import NonFiniteLossError, faultinject
from paddle_tpu.graph.machine import GradientMachine
from paddle_tpu.layers.base import LAYER_COUNTERS, note_counters, step_counters
from paddle_tpu.optimizer import Updater
from paddle_tpu.proto import TrainerConfig
from paddle_tpu.trainer import checkpoint as ckpt
from paddle_tpu.trainer.evaluators import EvaluatorChain
from paddle_tpu.observability import compile_log
from paddle_tpu.observability import memory as obs_mem
from paddle_tpu.observability import metrics as obs
from paddle_tpu.observability import numerics as obs_num
from paddle_tpu.observability import spans as obs_spans
from paddle_tpu.sparse import rowshard as sparse_rows
from paddle_tpu.sparse import runtime as sparse_rt
from paddle_tpu.utils import concurrency as cc
from paddle_tpu.utils.flags import FLAGS
from paddle_tpu.utils.logging import logger
from paddle_tpu.utils.stats import global_stats, stat_timer


class TrainerStats:
    """Windowed cost averages (ref: TrainerInternal.h TrainerStats)."""

    def __init__(self):
        self.total_cost = 0.0
        self.total_samples = 0
        self.window_cost = 0.0
        self.window_samples = 0

    def add(self, cost_sum: float, n: int) -> None:
        self.total_cost += cost_sum
        self.total_samples += n
        self.window_cost += cost_sum
        self.window_samples += n

    def reset_window(self) -> None:
        self.window_cost = 0.0
        self.window_samples = 0

    def summary_dict(self) -> Dict[str, Any]:
        """The pass/window stats as one dict — the SINGLE source both the
        human log line (``summary()``) and the metrics.jsonl record are
        rendered from, so log text and telemetry can never drift."""
        return {
            "samples": self.total_samples,
            "AvgCost": self.total_cost / max(self.total_samples, 1),
            "CurrentCost": self.window_cost / max(self.window_samples, 1),
        }

    def summary(self) -> str:
        return " ".join(
            f"{k}={v:d}" if isinstance(v, int) else f"{k}={v:.6g}"
            for k, v in self.summary_dict().items()
        )


class PreemptionExit(Exception):
    """Raised inside the pass loop after a preemption-triggered save."""

    def __init__(self, pass_id: int, saved_path: str):
        super().__init__(f"preempted at pass {pass_id}")
        self.pass_id = pass_id
        self.saved_path = saved_path


class _RollbackRequest(Exception):
    """Internal control flow: train_one_pass asks train() to restore the
    newest verified checkpoint (``--nonfinite_policy=rollback``)."""

    def __init__(self, pass_id: int, batch_id: int):
        super().__init__(f"rollback requested at pass {pass_id} batch {batch_id}")
        self.pass_id = pass_id
        self.batch_id = batch_id


class Trainer:
    def __init__(self, config: TrainerConfig, flags=FLAGS):
        # restart-latency anchor: time_to_first_step_s (the `restart`
        # telemetry record) is measured from here to the first completed
        # launch — the number ROADMAP item 5 tightens heartbeat-grace
        # and crash-loop windows from
        self._t_construct = time.perf_counter()
        # set-up by phase (doc/observability.md "Spans"): the root of the
        # construction, its children opened where the work happens
        with stat_timer("trainer/init"):
            self._init(config, flags)

    def _init(self, config: TrainerConfig, flags) -> None:
        self.config = config
        self.flags = flags
        from paddle_tpu.utils.device import describe_devices

        with stat_timer("trainer/init_devices"):
            # fails here, naming the reason, when the platform the run asked
            # for (--use_tpu) is not there — never carries on elsewhere
            device = describe_devices("trainer")
            from paddle_tpu.native import get_lib

            # builds datapath.cc on first use; a missing toolchain degrades
            # to NumPy packing — say which this run got, not only when it
            # failed
            logger.info("native datapath: %s",
                        "loaded" if get_lib() is not None else "NumPy fallback")
        dtype = jnp.float32
        if flags.use_double:
            # the reference's WITH_DOUBLE build; mostly for gradient checks
            jax.config.update("jax_enable_x64", True)
            dtype = jnp.float64
        from paddle_tpu.graph.machine import compute_dtype_of

        # OptimizationConfig.dtype="bfloat16" → bf16 activations/matmuls
        # with f32 master weights + optimizer state (x64 builds stay full)
        compute_dtype = None if flags.use_double else compute_dtype_of(config.opt_config)
        with stat_timer("trainer/init_graph"):
            self.gm = GradientMachine(
                config.model_config, dtype=dtype, compute_dtype=compute_dtype,
                scan_unroll=config.opt_config.scan_unroll,
                pallas_rnn=config.opt_config.pallas_rnn,
                pallas_flat=config.opt_config.pallas_flat,
                conv_s2d=config.opt_config.conv_s2d,
                conv_stats_mode=config.opt_config.conv_stats_mode,
                pallas_decoder=config.opt_config.pallas_decoder,
            )
            self.updater = Updater(
                config.opt_config, config.model_config,
                init_model_path=flags.init_model_path or config.init_model_path,
            )
        with stat_timer("trainer/init_params"):
            self.params = self.gm.init_params(seed=flags.seed)
        with stat_timer("trainer/init_opt_state"):
            self.opt_state = self.updater.init_state(self.params)
        self.start_pass = flags.start_pass or config.start_pass
        self.save_dir = flags.save_dir or config.save_dir
        self._train_step_fn = None
        self._test_fwd_fn = None
        self._mesh = None
        mesh_shape = flags.mesh_shape or config.opt_config.mesh_shape
        if not mesh_shape and flags.trainer_count > 1:
            # reference -trainer_count: N-way data parallelism
            mesh_shape = f"data={flags.trainer_count}"
        if mesh_shape:
            from paddle_tpu.parallel.mesh import make_mesh

            self._mesh = make_mesh(mesh_shape)
            self.gm.mesh = self._mesh  # layers with explicit collectives
        elif device["device_count"] > 1:
            logger.info(
                "no --mesh_shape: training on device 0 of %d (pass "
                "--mesh_shape=data=%d for data-parallel SGD over all of them)",
                device["device_count"], device["device_count"])
        # sync-SGD over a data-parallel mesh needs every device to get an
        # identical batch slice: batches whose size is not divisible by
        # the data axis (the end-of-pass remainder) are skipped, matching
        # globalize_batch's multi-host policy (doc/divergences.md)
        self._batch_divisor = 1
        if self._mesh is not None:
            self._batch_divisor = dict(
                zip(self._mesh.axis_names, self._mesh.devices.shape)
            ).get("data", 1)
        self._multiproc = jax.process_count() > 1
        if self._multiproc and self._mesh is None:
            raise ValueError(
                "multi-process training needs a mesh "
                "(--mesh_shape or --trainer_count)"
            )
        # gradient accumulation: N forward/backwards per optimizer update
        # (reference num_batches_per_send_parameter, TrainerInternal.cpp)
        self._accum_n = max(1, int(config.opt_config.num_batches_per_send_parameter))
        # async SGD analog (settings(is_async=True) → algorithm='async_sgd'):
        # per-replica local updates with periodic drift-gated parameter
        # averaging (paddle_tpu/parallel/local_sgd.py). In this mode
        # num_batches_per_send_parameter is the MERGE PERIOD (its
        # reference meaning: batches between parameter sends), not a
        # gradient-accumulation count — reinterpreted HERE, before the
        # fuse/accumulation conflict check below, so an async config with
        # a merge period is never rejected as "accumulation".
        self._async = config.opt_config.algorithm == "async_sgd"
        self._local_sgd = None
        self._lsgd_state = None      # (params_r, opt_r) replica stacks
        self._lsgd_dirty = False     # stacks hold updates self.params lacks
        self._lsgd_batches = 0       # local batches since the last merge
        self._lsgd_discarded = 0     # replicas drift-discarded this pass
        self._sync_n = 1
        if self._async:
            self._sync_n = self._accum_n
            self._accum_n = 1
            if self._mesh is None or self._batch_divisor <= 1:
                logger.warning(
                    "async_sgd with a single data-parallel replica is "
                    "exactly sync SGD — running the ordinary sync step "
                    "(add --mesh_shape=data=N for local-SGD replicas)"
                )
                self._async = False
            else:
                from paddle_tpu.parallel.local_sgd import check_data_only

                check_data_only(self._mesh)
        # fused launches: k consecutive same-shape batches per device
        # dispatch (lax.scan over stacked batches); each batch keeps its
        # own optimizer update, so numerics match the unfused loop
        self._fuse_k = max(1, int(config.opt_config.batches_per_launch))
        if self._fuse_k > 1 and self._accum_n > 1:
            raise ValueError(
                "batches_per_launch > 1 cannot combine with "
                "num_batches_per_send_parameter > 1 — fuse launches of "
                "accumulation micro-batches are not supported; pick one"
            )
        if self._fuse_k > 1 and (self._mesh is not None or self._async):
            logger.warning(
                "batches_per_launch > 1 is a single-chip dispatch-latency "
                "optimization; ignored under a mesh"
            )
            self._fuse_k = 1
        self._fused_step_fn = None
        # per-pass held-out results appended by train(): [(pass_id, {...})]
        # — programmatic convergence-curve access (quality tracking tests,
        # plotcurve's structured counterpart)
        self.test_history: list = []
        # model-FLOP accounting for the pass-end MFU log line: analytic
        # matmul FLOPs per distinct batch-shape signature (one jaxpr
        # trace each — ops/kernel_flops.py; XLA cost analysis undercounts
        # scans so it cannot be the basis)
        self._flops_cache: dict = {}
        self._pass_flops = 0.0
        self._pass_train_s = 0.0
        self._pass_flops_incomplete = False
        # preemption-aware checkpointing: set by the SIGTERM handler that
        # _preemption_guard installs around train(); checked at launch
        # boundaries so the saved checkpoint is always consistent
        self._preempt_requested = False
        self._accum_fns = None
        self._acc = None
        self._acc_batches = 0
        self._acc_samples = 0
        # whole-data batch algorithms (reference Trainer::trainOnePassBatch,
        # Trainer.cpp:492, selected by algorithm=owlqn): one quasi-Newton
        # update per pass, driven host-side between jitted data sweeps
        self._batch_method = None
        self._bm_grad_fn = None
        self._bm_cost_fn = None
        if config.opt_config.algorithm == "owlqn":
            if self._multiproc:
                raise ValueError(
                    "whole-data batch methods (algorithm=owlqn) run "
                    "single-process; drop --mesh_shape/multi-host"
                )
            if self._accum_n > 1:
                raise ValueError(
                    "num_batches_per_send_parameter > 1 (gradient "
                    "accumulation) has no effect under whole-data batch "
                    "methods — each pass already uses the full dataset"
                )
            from paddle_tpu.optimizer.batch_methods import BatchMethod

            # the line search compares full-data objectives, so the
            # objective must be deterministic: dropout and batch-statistics
            # layers are incompatible with whole-data batch methods
            stochastic = [
                f"{l.name} ({l.type})"
                for l in config.model_config.layers
                if getattr(l, "drop_rate", 0) > 0 or "batch_norm" in l.type
            ]
            if stochastic:
                raise ValueError(
                    "whole-data batch methods (algorithm=owlqn) need a "
                    "deterministic objective; remove dropout/batch_norm "
                    "layers: " + ", ".join(stochastic)
                )
            oc = config.opt_config
            self._batch_method = BatchMethod(
                method=oc.learning_method if oc.learning_method in ("lbfgs", "owlqn") else "lbfgs",
                history=oc.owlqn_steps,
                c1=oc.c1,
                backoff=oc.backoff,
                max_backoff=oc.max_backoff,
                l1weight=oc.l1weight,
                l2weight=oc.l2weight,
                learning_rate=oc.learning_rate,
            )
        # divergence policy (--nonfinite_policy, doc/resilience.md): what
        # a NaN/Inf loss does. abort keeps the reference's FP-trap role;
        # skip discards the poisoned update (pre-step buffers stay valid
        # because donation is disabled below); rollback restores the
        # newest verified checkpoint, scales the lr, and fast-forwards
        # past the poison region. Both are bounded by max_nonfinite_steps.
        self._nf_policy = str(getattr(flags, "nonfinite_policy", "abort") or "abort")
        if self._nf_policy not in ("abort", "skip", "rollback"):
            raise ValueError(
                f"--nonfinite_policy={self._nf_policy!r} "
                "(want abort, skip, or rollback)"
            )
        self._nf_budget = max(0, int(getattr(flags, "max_nonfinite_steps", 3)))
        self._nf_count = 0
        self.rollbacks = 0
        # (pass_id, first clean batch): re-run of the rolled-back pass
        # skips batches before this index — the poison region
        self._ff_target: Optional[Tuple[int, int]] = None
        if self._nf_policy != "abort" and (
            self._async or self._batch_method is not None
        ):
            logger.warning(
                "--nonfinite_policy=%s is not supported under %s — a "
                "non-finite loss still aborts (with NonFiniteLossError)",
                self._nf_policy,
                "async_sgd (replica stacks hold no single pre-step state)"
                if self._async else "whole-data batch methods",
            )
            self._nf_policy = "abort"
        if self._nf_policy == "rollback" and not self.save_dir:
            logger.warning(
                "--nonfinite_policy=rollback without --save_dir: there "
                "will be no checkpoint to roll back to — the first "
                "non-finite loss raises NonFiniteLossError"
            )
        # per-layer model-health telemetry (--numerics_log_period,
        # doc/observability.md "Numerics telemetry"): the jitted step
        # grows one aux output — per-layer grad/param/update norms and
        # nonfinite counts, computed on device where the grads already
        # live. The launch signature is fixed at build time by the flag
        # (never per step), so recompiles stay 0 after warmup; the host
        # reads the tiny health tree back only at log-period boundaries.
        self._numerics_period = max(
            0, int(getattr(flags, "numerics_log_period", 0) or 0)
        )
        self._numerics_groups = None
        self._numerics_last = None  # newest launch's device health tree
        # the last train batch's kept layers, {layer name: Argument}, as the
        # step returned them (the network's declared outputs and what host
        # evaluators read): the reference API's Trainer::getForwardOutput.
        # Device arrays; nothing here reads them
        self.forward_output: Dict[str, Argument] = {}
        if self._numerics_period:
            if (self._accum_n > 1 or self._async
                    or self._batch_method is not None):
                # honest degradation (the hangwatch precedent): these
                # paths apply updates outside _one_batch_step, so the
                # aux would misattribute — better absent than wrong
                logger.warning(
                    "--numerics_log_period is not supported under "
                    "gradient accumulation / async_sgd / whole-data "
                    "batch methods — numerics telemetry disabled for "
                    "this run"
                )
                self._numerics_period = 0
            else:
                self._numerics_groups = obs_num.layer_groups(
                    config.model_config, list(self.params)
                )
        # row-sharded sparse-parameter training (paddle_tpu/sparse/,
        # doc/sparse.md): register each sparse_update table's row count
        # so the durable shard protocol stamps row_range into its shard
        # records; refuse loudly (before any training) when the current
        # host set cannot hold a table within --sparse_row_budget; and
        # account touched rows per pass for the kind=sparse record
        self._sparse_plan = self.gm.sparse_prefetch_plan()
        self._sparse_stats = None
        if self._sparse_plan:
            tables = {
                pn: int(self.params[pn].shape[0])
                for pn, _ in self._sparse_plan
                if pn in self.params
            }
            err = sparse_rows.row_budget_error(
                tables, jax.process_count(),
                int(getattr(flags, "sparse_row_budget", 0) or 0),
            )
            if err:
                raise ValueError(err)
            sparse_rt.register_tables(tables)
            self._sparse_stats = sparse_rt.SparseStats({
                pn: int(np.prod(self.params[pn].shape[1:]) or 1)
                * self.params[pn].dtype.itemsize
                for pn in tables
            })
        # last live memory snapshot (pass-boundary sampling) — the OOM
        # pre-mortem's "what did the allocator look like" fallback when
        # sampling after the OOM itself fails — and the last launch
        # position, so the pre-mortem can say WHERE the run died
        self._mem_last = None
        self._last_launch: Optional[Tuple[int, int]] = None
        # telemetry (doc/observability.md): per-host metrics.jsonl stream
        # (--metrics_path, defaulting to save_dir) + Chrome trace-event
        # spans (--trace_events_path). No-ops when neither is configured.
        obs.configure_from_flags(flags, host=jax.process_index())
        obs_spans.configure_from_flags(flags, host=jax.process_index())
        # compile & cost attribution (doc/observability.md "Compile
        # telemetry"): every launch-group compilation becomes a
        # kind=compile record (trace/compile seconds, cache hit/miss,
        # XLA cost analysis), and the persistent cache keeps compiled
        # executables across processes so elastic relaunches stop
        # re-paying the full trace+compile. `paddle train` (cli._setup)
        # enabled it already; a program that builds a Trainer itself
        # asks through flags.compile_cache_dir — either way the place is
        # compile_log.resolve_cache_dir's
        if getattr(flags, "compile_cache_dir", ""):
            compile_log.enable_compile_cache(flags.compile_cache_dir)
        # with --metrics_path, each compile's optimized HLO text is kept
        # beside its record (`hlo_path`): what maps a profile's device
        # events to the layers' named scopes
        metrics_path = getattr(flags, "metrics_path", "")
        self._compiles = compile_log.CompileRegistry(
            device_kind=device["device_kind"],
            hlo_dir=os.path.join(metrics_path, "hlo") if metrics_path else "")
        # hang defense (doc/resilience.md "Hang detection"): the step
        # loop pings the watchdog at every launch boundary; a stall
        # beyond --step_hang_timeout dumps forensics (hang_report.json
        # in the run dir — where the supervisor's crash report looks)
        # and exits EXIT_HANG. On a multi-host pod every host runs one:
        # a rank wedged inside a collective because ANOTHER rank died
        # still produces a named, stack-carrying report.
        self._hangwatch = None
        hang_timeout = float(getattr(flags, "step_hang_timeout", 0) or 0)
        if hang_timeout > 0:
            from paddle_tpu.resilience.hangwatch import HangWatch, run_dir_of

            self._hangwatch = HangWatch(
                hang_timeout,
                report_dir=run_dir_of(
                    getattr(flags, "metrics_path", "")
                    or self.save_dir or "."
                ),
            )
        # cluster liveness: renew this host's heartbeat file so
        # cluster_launch can tell a wedged-but-alive rank from a slow one
        self._heartbeat = None
        hb_interval = float(getattr(flags, "heartbeat_interval", 0) or 0)
        if hb_interval > 0:
            from paddle_tpu.resilience import heartbeat as hb

            hb_dir = hb.resolve_dir(
                getattr(flags, "heartbeat_dir", ""), self.save_dir
            )
            if hb_dir:
                self._heartbeat = hb.HeartbeatWriter(
                    hb_dir, jax.process_index(), hb_interval
                )
                # first beat NOW, before the (possibly multi-GB, shared-
                # fs) checkpoint restore below: a monitor must see "this
                # rank is alive and initializing", not silence it could
                # mistake for a wedge
                self._heartbeat.beat(phase="init")
            else:
                logger.warning(
                    "--heartbeat_interval=%g but neither --heartbeat_dir "
                    "nor --save_dir is set — heartbeats disabled",
                    hb_interval,
                )
        # set by the PreemptionExit path: the CLI turns it into the
        # distinct EXIT_PREEMPTED process code so supervisors/launchers
        # can restart preempted runs without consuming restart budget
        self.preempted = False
        # async checkpointing (--async_checkpoint, doc/performance.md +
        # doc/resilience.md "Elastic sharded checkpointing"): save() pays
        # only the device→host snapshot; the durable-protocol write runs
        # on a background thread. Multi-process runs use the SHARDED
        # async checkpointer: each host's writer persists only the
        # shards it owns, and the one remaining collective is drain()'s
        # cheap pass-end commit agreement over the distributed runtime's
        # host KV store (no device collectives on the save path at all).
        self._async_ckpt = None
        if getattr(flags, "async_checkpoint", False) and self.save_dir:
            inflight = int(getattr(flags, "ckpt_inflight_limit", 1) or 1)
            if self._multiproc:
                from paddle_tpu.utils.barrier import distributed_client

                if distributed_client() is None:
                    logger.warning(
                        "--async_checkpoint multi-process needs the jax "
                        "distributed runtime's KV client for the pass-end "
                        "commit agreement — unavailable here; saving "
                        "synchronously"
                    )
                else:
                    from paddle_tpu.trainer.async_ckpt import (
                        ShardedAsyncCheckpointer,
                    )

                    self._async_ckpt = ShardedAsyncCheckpointer(
                        self.save_dir,
                        inflight_limit=inflight,
                        hangwatch=self._hangwatch,
                        agree_timeout=float(
                            getattr(flags, "ckpt_agree_timeout", 600.0) or 600.0
                        ),
                    )
            else:
                from paddle_tpu.trainer.async_ckpt import AsyncCheckpointer

                self._async_ckpt = AsyncCheckpointer(
                    self.save_dir,
                    inflight_limit=inflight,
                    hangwatch=self._hangwatch,
                )
        # restart telemetry: restore cost is captured by _maybe_restore,
        # the `restart` record is emitted at the first completed launch
        self._restore_s = 0.0
        self._restart_pending = True
        self._maybe_restore()
        # StaticPruningHook init semantics: mask values once at startup
        self.params = self.updater.apply_init_hooks(self.params)

    # ------------------------------------------------------------ restore

    def ckpt_sharding_for(self):
        """Multi-process restore must rebuild every value as a global
        array sharded onto the CURRENT mesh (a host-local jnp array could
        not be resharded across processes by jit). None single-process."""
        if self._mesh is None or not self._multiproc:
            return None
        from paddle_tpu.parallel.spmd import checkpoint_sharding_fn

        return checkpoint_sharding_fn(self._mesh, self.gm)

    def _maybe_restore(self) -> None:
        self._restored_pass: Optional[int] = None
        init_path = self.flags.init_model_path or self.config.init_model_path
        sharding_for = self.ckpt_sharding_for()
        pre_verified = False
        if init_path == "auto":
            # newest checkpoint under save_dir that passes manifest
            # verification; a fresh run (nothing restorable) starts clean
            init_path = (
                ckpt.find_restorable_checkpoint(self.save_dir)
                if self.save_dir else None
            )
            if init_path is None:
                logger.info(
                    "--init_model_path=auto: no restorable checkpoint under "
                    "%r — starting fresh", self.save_dir,
                )
                return
            pre_verified = True  # find_restorable just CRC'd this dir
        if init_path:
            # fallback (quarantine + walk to an earlier pass) only within
            # OUR OWN save_dir: an explicit init_model_path pointing at a
            # foreign/pretrained model dir must fail loudly, never rename
            # a shared directory or substitute weights the user did not
            # ask for (same contract as api.py loadParameters)
            own = bool(self.save_dir) and os.path.abspath(
                os.path.dirname(os.path.normpath(init_path))
            ) == os.path.abspath(self.save_dir)
            t_restore = time.perf_counter()
            self.params, opt_state, meta = ckpt.load_checkpoint(
                init_path,
                self.opt_state,
                missing=self.flags.load_missing_parameter_strategy,
                expected_params=self.params,
                sharding_for=sharding_for,
                # don't re-CRC a multi-GB checkpoint the auto scan just
                # verified moments ago (fallback candidates, if the load
                # has to walk to one, are still verified)
                verify=not pre_verified,
                fallback=pre_verified or own,
            )
            self._restore_s = time.perf_counter() - t_restore
            if opt_state is not None:
                self.opt_state = opt_state
            restored = self._note_restored(init_path, meta)
            if pre_verified and restored is not None and self.start_pass == 0:
                # auto-resume: continue pass numbering past the pass the
                # load ACTUALLY restored (meta pass_id — the chain may
                # have fallen back below the scanned candidate), the
                # reference's restart-from-last-pass minus the "hope the
                # files are intact" part
                self.start_pass = restored + 1
                logger.info(
                    "--init_model_path=auto: resumed pass %d from %s "
                    "(start_pass=%d)", restored, init_path, self.start_pass,
                )
            return
        if self.start_pass > 0:
            path = os.path.join(self.save_dir, ckpt.PASS_FMT % (self.start_pass - 1))
            t_restore = time.perf_counter()
            self.params, opt_state, meta = ckpt.load_checkpoint(
                path, self.opt_state, expected_params=self.params,
                sharding_for=sharding_for,
            )
            self._restore_s = time.perf_counter() - t_restore
            if opt_state is not None:
                self.opt_state = opt_state
            self._note_restored(path, meta)

    def _note_restored(self, path: str, meta: Optional[Dict] = None) -> Optional[int]:
        """Record which pass in OUR save_dir this run restored from, so
        rolling deletion never removes the only known-good state (the
        load may also have FALLEN BACK to an earlier pass than the path
        asked for — trust meta['pass_id'] when present)."""
        if (self._sparse_stats is not None and meta is not None
                and isinstance(meta.get("sparse_hosts"), int)
                and meta["sparse_hosts"] != jax.process_count()):
            # the checkpoint was written by a different host set: the
            # sharded restore just re-sliced every table's row ranges
            # onto the current mesh — count it as a reshard event
            self._sparse_stats.note_reshard(
                meta["sparse_hosts"], jax.process_count()
            )
            logger.info(
                "sparse tables resharded across relaunch: %d -> %d host(s)",
                meta["sparse_hosts"], jax.process_count(),
            )
        if meta is not None and isinstance(meta.get("pass_id"), int):
            pass_id = meta["pass_id"]
        else:
            base = os.path.basename(os.path.normpath(path))
            if base.endswith(".old"):
                # torn-commit leftover (see checkpoint._commit): the pass
                # id still applies, so resume numbering stays correct
                base = base[: -len(".old")]
            if not (base.startswith("pass-") and base[5:].isdigit()):
                return None
            pass_id = int(base[5:])
        # abspath both sides: a relative --save_dir must still match an
        # absolute init path to the same directory (and vice versa)
        if self.save_dir and os.path.abspath(
            os.path.dirname(os.path.normpath(path))
        ) == os.path.abspath(self.save_dir):
            self._restored_pass = pass_id
        return pass_id

    # ------------------------------------------------------------- steps

    def _keep_and_eval_states(self):
        """What a step body hands the host of its layer outputs, as
        ``select(outputs) -> (keep, eval_states)``, called inside the
        step's trace: the per-batch statistics of the evaluators that have
        a traceable form for these shapes ({evaluator name: f32[k]}), and
        the layers the step must still return: the network's outputs plus
        the input layers of the evaluators that stay on the host."""
        chain = EvaluatorChain(self.config.model_config)
        net_outputs = set(self.gm.network.output_layer_names)

        def select(outputs):
            states = chain.batch_states(outputs)
            host = [e for e in chain.evaluators if e.cfg.name not in states]
            kept = net_outputs | set(chain.layers_for(host))
            # what the layers counted this step (`base.publish_counter`)
            # rides with the evaluator states: one read in
            # `trainer/loss_sync`, then `note_counters` on the host
            counters = step_counters(outputs)
            if counters:
                if LAYER_COUNTERS in states:
                    raise ValueError(f"an evaluator may not be named {LAYER_COUNTERS!r}")
                states[LAYER_COUNTERS] = counters
            return {k: v for k, v in outputs.items() if k in kept}, states

        return select

    def _one_batch_step(self, sparse: bool = True):
        """The single-batch grad→update→state→keep body shared by the
        ordinary train step and the fused-launch scan, so the two paths
        cannot diverge. Returns (params, opt_state, loss, kept layers,
        evaluator states) and, with numerics telemetry, the health aux."""
        grad_fn = self.gm.grad_fn(
            remat=self.config.opt_config.remat, sparse=sparse
        )
        updater = self.updater
        select = self._keep_and_eval_states()
        nm_groups = self._numerics_groups

        def step(params, opt_state, in_args, rng, batch_size):
            # the scopes are HLO metadata: a profile's device time splits
            # into the layers' own scopes (layers/base.py forward_layer),
            # `cost`, `optimizer` and `numerics`
            loss, grads, outputs, state_updates = grad_fn(params, in_args, rng)
            with jax.named_scope("optimizer"):
                new_params, new_opt = updater(params, grads, opt_state, batch_size)
            for k, v in state_updates.items():
                new_params[k] = v
            keep, eval_states = select(outputs)
            if nm_groups is None:
                return new_params, new_opt, loss, keep, eval_states
            # numerics aux: fused into THIS launch (grads and both
            # parameter trees are already live on device) — one extra
            # [4]-vector per layer in the outputs, zero extra launches
            with jax.named_scope("numerics"):
                health = obs_num.step_health(params, new_params, grads, nm_groups)
            return new_params, new_opt, loss, keep, eval_states, health

        return step

    @property
    def _donate_steps(self) -> bool:
        """skip/rollback must be able to hand back the pre-step state of
        a poisoned update, so the train steps may not donate their input
        buffers (the documented ~2x parameter-memory cost of those
        policies); abort keeps the donating fast path."""
        return self._nf_policy == "abort"

    def _build_train_step(self):
        step = self._one_batch_step()

        if self._mesh is not None:
            from paddle_tpu.parallel.spmd import shard_train_step

            return shard_train_step(
                step, self._mesh, self.gm, donate=self._donate_steps,
                extra_outs=2 if self._numerics_groups is not None else 1,
            )
        return jax.jit(
            step, donate_argnums=(0, 1) if self._donate_steps else ()
        )

    def _build_accum_steps(self):
        """Gradient accumulation (num_batches_per_send_parameter = N > 1,
        reference TrainerInternal: N forwardBackwards per parameter send):
        ``astep`` folds one batch's sample-weighted gradients into an
        on-device accumulator; ``ustep`` applies ONE optimizer update from
        the accumulated mean. Dense gradients only — RowSparseGrad shapes
        vary per batch and cannot live in a fixed-shape accumulator."""
        grad_fn = self.gm.grad_fn(remat=self.config.opt_config.remat, sparse=False)
        updater = self.updater
        select = self._keep_and_eval_states()

        def astep(params, acc, in_args, rng, n):
            loss, grads, outputs, state_updates = grad_fn(params, in_args, rng)
            new_acc = jax.tree_util.tree_map(lambda a, g: a + g * n, acc, grads)
            new_params = dict(params)
            for k, v in state_updates.items():  # BN stats advance per batch
                new_params[k] = v
            keep, eval_states = select(outputs)
            return new_params, new_acc, loss, keep, eval_states

        def ustep(params, opt_state, acc, total_n):
            mean = jax.tree_util.tree_map(lambda a: a / total_n, acc)
            new_params, new_opt = updater(params, mean, opt_state, total_n)
            zero = jax.tree_util.tree_map(jnp.zeros_like, acc)
            return new_params, new_opt, zero

        if self._mesh is not None:
            from paddle_tpu.parallel.spmd import shard_accum_steps

            return shard_accum_steps(
                astep, ustep, self._mesh, self.gm, donate=self._donate_steps
            )
        if not self._donate_steps:
            return jax.jit(astep), jax.jit(ustep)
        return (
            jax.jit(astep, donate_argnums=(0, 1)),
            jax.jit(ustep, donate_argnums=(0, 1, 2)),
        )

    def _build_fused_step(self):
        """k optimizer steps over k stacked batches in ONE device launch
        (``batches_per_launch``): a lax.scan whose carry is (params,
        opt_state) and whose xs are the stacked inputs + per-batch rngs +
        sample counts. Dense gradients only (same constraint and reason as
        gradient accumulation: sparse row sets vary per batch and cannot
        ride a fixed-shape scan input)."""
        one = self._one_batch_step(sparse=False)

        def fstep(params, opt_state, stacked, rngs, ns):
            def body(carry, xs):
                p, o = carry
                in_args, rng, n = xs
                # loss, kept layers, evaluator states, and the numerics
                # health aux where it is on — the scan stacks whatever ys
                # the body returns, so both shapes ride the same machinery
                out = one(p, o, in_args, rng, n)
                return (out[0], out[1]), tuple(out[2:])

            (p, o), ys = jax.lax.scan(
                body, (params, opt_state), (stacked, rngs, ns)
            )
            return (p, o) + tuple(ys)

        return jax.jit(
            fstep, donate_argnums=(0, 1) if self._donate_steps else ()
        )

    @property
    def fused_step(self):
        if self._fused_step_fn is None:
            self._fused_step_fn = self._build_fused_step()
        return self._fused_step_fn

    def _launch_groups(self, gen):
        """Group the (n, host, device) batch stream for fused launches.

        Yields ("fused", [k items]) for runs of k consecutive batches with
        identical tree structure/shapes/sample count, and ("single", item)
        otherwise (shape changes, end-of-pass remainders) — partial groups
        run through the ordinary one-batch step rather than compiling a
        scan variant per remainder length."""
        if self._fuse_k <= 1:
            for item in gen:
                yield "single", item
            return

        def sig_of(item):
            # signature from the HOST-side Argument dict: the packed
            # device tree is a deterministic function of the host batch,
            # so identical host field shapes/dtypes imply an identical
            # device tree — and reading ``.shape`` off O(slots) numpy
            # fields costs nothing, where the old jax tree_flatten of
            # the device tree walked O(leaves) registered pytree nodes
            # per batch, every step, on the hot path
            n, host, dev = item
            sig = [n]
            if host is None:
                # no host-side view (direct device trees — tests, future
                # host-less providers): fall back to the device-tree
                # signature the grouping originally used
                leaves, treedef = jax.tree_util.tree_flatten(dev)
                sig.append((
                    treedef,
                    tuple((l.shape, str(l.dtype)) for l in leaves),
                ))
                return tuple(sig)
            for name, arg in host.items():  # dict order is stable per provider
                sig.append((
                    name,
                    tuple(
                        None if f is None else (f.shape, str(f.dtype))
                        for f in (arg.value, arg.ids, arg.seq_lengths,
                                  arg.sub_seq_lengths, arg.weight)
                    ),
                ))
            return tuple(sig)

        buf, sig = [], None
        for item in gen:
            s = sig_of(item)
            if buf and s != sig:
                for it in buf:
                    yield "single", it
                buf = []
            sig = s
            buf.append(item)
            if len(buf) == self._fuse_k:
                yield "fused", buf
                buf, sig = [], None
        for it in buf:
            yield "single", it

    def _build_test_fwd(self):
        gm = self.gm

        def fwd(params, in_args):
            outputs, _ = gm.forward(params, in_args, pass_type="test", rng=None)
            return outputs

        if self._mesh is not None:
            from paddle_tpu.parallel.spmd import shard_test_fwd

            return shard_test_fwd(fwd, self._mesh, self.gm)
        return jax.jit(fwd)

    @property
    def train_step(self):
        if self._train_step_fn is None:
            self._train_step_fn = self._build_train_step()
        return self._train_step_fn

    @property
    def test_fwd(self):
        if self._test_fwd_fn is None:
            self._test_fwd_fn = self._build_test_fwd()
        return self._test_fwd_fn

    # ------------------------------------------------------------- data

    def _provider(self, for_test: bool, ordered: Optional[bool] = None) -> Optional[DataProvider]:
        dc = self.config.test_data_config if for_test else self.config.data_config
        if dc is None:
            return None
        slot_names = self.config.model_config.input_layer_names
        from paddle_tpu.utils.retry import RetryPolicy

        return create_data_provider(
            dc,
            self.config.opt_config.batch_size,
            slot_names,
            seed=self.flags.seed,
            for_test=for_test if ordered is None else ordered,
            # resilience knobs come from THIS trainer's flags object, not
            # the process-global FLAGS (programmatic embeddings pass
            # their own _Flags instance)
            stall_timeout=self.flags.data_stall_timeout,
            max_bad_samples=self.flags.max_bad_samples,
            retry=RetryPolicy.from_flags(self.flags, name="data-provider"),
            packer_threads=getattr(self.flags, "data_packer_threads", None),
            prefetch_depth=getattr(self.flags, "prefetch_depth", None),
        )

    # ------------------------------------------------------------- train

    def _preemption_guard(self):
        """Context manager active for the duration of train(): installs a
        SIGTERM handler that requests a checkpoint-and-exit at the next
        launch boundary (TPU preemption notices arrive as SIGTERM). Only
        installable from the main thread — elsewhere (library embedding,
        test runners) it degrades to a no-op. The previous handler is
        restored on exit, and a SECOND SIGTERM falls through to it, so a
        stuck save can still be killed the ordinary way. Gate:
        flags.save_on_preempt (default on; the handler itself is cheap)."""
        import contextlib
        import signal

        # Gates: flag off; non-main thread (signal API unavailable);
        # multi-process (the flag would be per-host and unsynchronized —
        # hosts at different launch boundaries would issue mismatched
        # collectives and deadlock the save; multi-host preemption relies
        # on the deterministic periodic saves instead, doc/divergences.md)
        if (not getattr(self.flags, "save_on_preempt", True)
                or self._multiproc
                or cc.current_thread() is not cc.main_thread()):
            return contextlib.nullcontext()

        @contextlib.contextmanager
        def guard():
            prev = signal.getsignal(signal.SIGTERM)
            # None = installed by non-Python code; fall through to default
            fallback = prev if prev is not None else signal.SIG_DFL

            def on_sigterm(signum, frame):
                # flag-only: logging (or any IO) from a signal handler can
                # re-enter a buffered stream mid-write and raise; the
                # message is logged at the launch-boundary check instead
                self._preempt_requested = True
                signal.signal(signal.SIGTERM, fallback)  # 2nd signal: old path

            signal.signal(signal.SIGTERM, on_sigterm)
            try:
                yield
            finally:
                self._preempt_requested = False
                if signal.getsignal(signal.SIGTERM) is on_sigterm:
                    signal.signal(signal.SIGTERM, fallback)

        return guard()

    def train(self, num_passes: Optional[int] = None) -> None:
        # the root of one call: the provider, the liveness plumbing, the
        # passes, the closing save
        with stat_timer("trainer/train"):
            self._train(num_passes)

    def _train(self, num_passes: Optional[int]) -> None:
        num_passes = num_passes or self.flags.num_passes
        with stat_timer("data/provider_start"):
            train_provider = self._provider(for_test=False)
        assert train_provider is not None, "no train data configured"
        if self._batch_method is not None:
            if self._hangwatch is not None:
                # honest degradation, not a silent one: the operator who
                # set the flag must not believe the hangwatch is armed
                logger.warning(
                    "--step_hang_timeout is not supported under "
                    "whole-data batch methods (the pass is one long "
                    "sweep with no launch boundary to ping) — hangwatch "
                    "disabled for this run"
                )
            # the heartbeat is a wall-clock daemon, no launch boundary
            # needed — it MUST run here, or a cluster_launch monitoring
            # the same flags would tear down a healthy batch-mode job
            # as silent
            if self._heartbeat is not None:
                self._heartbeat.start()
            try:
                return self._train_batch_mode(num_passes, train_provider)
            finally:
                if self._heartbeat is not None:
                    self._heartbeat.stop()
        rng = jax.random.PRNGKey(self.flags.seed)
        saved_pass = -1
        # liveness plumbing runs for the whole loop INCLUDING the final
        # save: a save wedged on a dead shared fs is still a hang, and
        # the heartbeat must outlive the last step so cluster_launch
        # never mistakes "finishing up" for "went silent"
        if self._hangwatch is not None:
            self._hangwatch.start()
        if self._heartbeat is not None:
            self._heartbeat.start()
        try:
            with self._preemption_guard():
                try:
                    # while-loop (not range): a rollback rewinds pass_id to
                    # just after the restored checkpoint. Per-pass keys are
                    # folded from the base key, so a re-run pass replays the
                    # same rng stream it saw the first time.
                    pass_id = self.start_pass
                    while pass_id < num_passes:
                        pass_rng = jax.random.fold_in(rng, pass_id)
                        try:
                            with stat_timer("trainer/pass"):
                                self.train_one_pass(pass_id, train_provider, pass_rng)
                        except _RollbackRequest as rb:
                            pass_id = self._apply_rollback(rb)
                            continue
                        with stat_timer("trainer/test"):
                            pass_results = self.test(pass_id=pass_id)
                        if pass_results:
                            self.test_history.append((pass_id, pass_results))
                        if self.save_dir and (pass_id + 1) % max(self.flags.saving_period, 1) == 0:
                            self.save(pass_id)
                            saved_pass = pass_id
                        logger.info(global_stats.summary())
                        if self._hangwatch is not None:
                            self._hangwatch.ping(pass_id)
                        pass_id += 1
                except PreemptionExit as e:
                    # the SIGTERM save must be DURABLE before the clean
                    # exit-18 return: a preempted pod may be reclaimed
                    # the instant the process dies
                    self._drain_async_ckpt()
                    if e.saved_path:
                        logger.info(
                            "preemption: checkpoint saved at %s — exiting the "
                            "train loop cleanly (resume with --init_model_path "
                            "on that pass dir and --start_pass=%d)",
                            e.saved_path, e.pass_id,
                        )
                    else:
                        logger.info(
                            "preemption: exiting the train loop cleanly "
                            "(no --save_dir configured, nothing was saved)"
                        )
                    # the CLI maps this to EXIT_PREEMPTED (18): restart
                    # machinery treats the death as the scheduler's call,
                    # not the run's, and charges no restart budget
                    self.preempted = True
                    obs.emit("run_end", status="preempted")
                    obs.flush()
                    return
            if (
                self.save_dir
                and saved_pass != num_passes - 1
                and num_passes > self.start_pass  # at least one pass actually ran
            ):
                self.save(num_passes - 1, final=True)
            # process-exit barrier: everything enqueued must be durable
            # (and any background-write failure must surface) before the
            # run may claim it completed
            self._drain_async_ckpt()
            # the on-purpose end of the run: a stream WITHOUT this record
            # ended in a crash/kill (what `paddle metrics` flags and the
            # supervisor's crash report captures)
            obs.emit("run_end", status="completed")
            obs.flush()
            obs_spans.export()
        except Exception as e:
            # OOM pre-mortem (doc/resilience.md "OOM forensics"): a
            # RESOURCE_EXHAUSTED death leaves oom_report.json — the
            # per-group static footprint ranked, the last live memory
            # snapshot, the telemetry tail — then re-raises; the CLI
            # maps it to the distinct EXIT_OOM so supervisors classify
            # the death (and charge budget — an OOM loop is
            # deterministic poison, not scheduling)
            if obs_mem.is_oom_error(e):
                self._oom_premortem(e)
            raise
        finally:
            if self._hangwatch is not None:
                self._hangwatch.stop()
            if self._heartbeat is not None:
                self._heartbeat.stop()

    # --------------------------------------------- whole-data batch mode

    def _bm_fns(self):
        if self._bm_grad_fn is None:
            gm = self.gm
            # pass_type="test": the line search needs a deterministic
            # objective (the dropout/batch_norm guard in __init__ rejects
            # models where train and test objectives differ)
            loss = functools.partial(gm.loss_fn, pass_type="test")
            self._bm_grad_fn = jax.jit(jax.value_and_grad(loss, has_aux=True))
            self._bm_cost_fn = jax.jit(lambda p, b: loss(p, b, None)[0])
        return self._bm_grad_fn, self._bm_cost_fn

    def _full_data_sweep(self, params, provider, want_grad: bool):
        """Stream the whole dataset once; returns (mean cost, mean grads
        over trainable params as numpy or None, total samples). The
        jitted per-batch step is the 'one forwardBackward over all data'
        of reference trainOnePassBatch, streamed to bound device memory."""
        grad_fn, cost_fn = self._bm_fns()
        trainable = {k for k, t in self.gm.trainable_mask().items() if t}
        total_c, total_n, total_g = 0.0, 0, None
        for batch in provider.batches():
            n = _batch_num_samples(batch)
            w = float(n)
            if want_grad:
                (loss, _aux), grads = grad_fn(params, batch, None)
                gw = {k: grads[k] * w for k in trainable}
                total_g = gw if total_g is None else {
                    k: total_g[k] + gw[k] for k in trainable
                }
            else:
                loss = cost_fn(params, batch)
            total_c += float(loss) * w
            total_n += n
        assert total_n, "empty training data"
        # host-side quasi-Newton math runs in float64 regardless of the
        # device dtype — curvature dot products are precision-sensitive
        mean_g = (
            {k: np.asarray(v, np.float64) / total_n for k, v in total_g.items()}
            if want_grad
            else None
        )
        return total_c / total_n, mean_g, total_n

    def _train_batch_mode(self, num_passes: int, provider: DataProvider) -> None:
        """One quasi-Newton update per pass (Trainer::trainOnePassBatch,
        reference Trainer.cpp:492): full-data gradient → L-BFGS/OWL-QN
        direction → backtracking line search → accept/reject."""
        bm = self._batch_method
        static = {
            k: v for k, v in self.params.items()
            if not self.gm.trainable_mask().get(k, True)
        }
        dtypes = {k: v.dtype for k, v in self.params.items()}

        def merge(xt):
            # host math is float64; devices keep their configured dtype
            full = {k: jnp.asarray(v, dtypes[k]) for k, v in xt.items()}
            full.update(static)
            return full

        def eval_cost(xt):
            c, _, _ = self._full_data_sweep(merge(xt), provider, want_grad=False)
            return c

        cached = None  # (cost, grads, n) from a rejected pass: params did
        # not move and the objective is deterministic, so the sweep would
        # recompute identical values — reuse instead of re-sweeping
        saved_pass = -1
        last_pass = self.start_pass - 1
        for pass_id in range(self.start_pass, num_passes):
            last_pass = pass_id
            with stat_timer("trainer/pass"):
                if cached is not None:
                    cost, grads, n = cached
                    cached = None
                else:
                    cost, grads, n = self._full_data_sweep(
                        self.params, provider, want_grad=True
                    )
                if not np.isfinite(cost):
                    # same typed failure as the per-step trap so
                    # supervisors/tests classify divergence vs. crash
                    # uniformly (subclasses FloatingPointError)
                    raise NonFiniteLossError(
                        f"non-finite whole-data cost ({cost}) at pass {pass_id}",
                        value=float(cost), pass_id=pass_id,
                    )
                bm.record_grad(grads)  # completes the previous pass's (s, y)
                xt = {
                    k: np.asarray(v, np.float64)
                    for k, v in self.params.items()
                    if k not in static
                }
                direction = bm.direction(xt, grads)
                accepted, x_new, f_new = bm.line_search(
                    xt, cost, grads, direction, eval_cost
                )
            if accepted:
                self.params = merge(x_new)
            logger.info(
                "Pass=%d AcceptedPass=%d samples=%d Cost=%g (objective %g%s)",
                pass_id,
                bm.n_accepted - 1 if accepted else -1,
                n,
                cost,
                f_new,
                "" if accepted else ", line search rejected",
            )
            with stat_timer("trainer/test"):
                self.test(pass_id=pass_id)
            if (
                self.flags.show_parameter_stats_period
                and (pass_id + 1) % self.flags.show_parameter_stats_period == 0
            ):
                self.show_parameter_stats()
            if (
                accepted
                and self.save_dir
                and (bm.n_accepted - 1) % max(self.flags.saving_period, 1) == 0
            ):
                self.save(pass_id)
                saved_pass = pass_id
            logger.info(global_stats.summary())
            if not accepted:
                cached = (cost, grads, n)
                if not bm.on_reject():
                    # a tempered steepest-descent step already failed; the
                    # deterministic objective would reject identically forever
                    logger.info(
                        "Pass=%d: line search cannot improve the objective — "
                        "converged, stopping batch-mode training", pass_id,
                    )
                    break
        if self.save_dir and saved_pass != last_pass and last_pass >= self.start_pass:
            self.save(last_pass, final=True)
        self._drain_async_ckpt()

    def _count_model_flops(self, key, fn, *args) -> float:
        """Analytic model matmul FLOPs of one ``fn(*args)`` call, cached
        by batch-shape signature (one jaxpr trace per distinct shape —
        the same granularity jit compiles at). Never raises: accounting
        must not be able to break training."""
        if key in self._flops_cache:
            f = self._flops_cache[key]
        else:
            try:
                from paddle_tpu.ops.kernel_flops import train_step_flops

                f = train_step_flops(fn, *args)
            except Exception as e:
                # cached failure: don't re-trace every batch — but leave a
                # trace, once per shape, so broken FLOPs accounting is
                # diagnosable instead of silently zeroing the MFU line
                logger.debug(
                    "FLOPs accounting disabled for batch signature %r: %s",
                    key, e, exc_info=True,
                )
                f = None
            self._flops_cache[key] = f
        if f is None:
            # a partially-counted pass must not log a confident number
            # ("omitted, never guessed")
            self._pass_flops_incomplete = True
            return 0.0
        return f

    @staticmethod
    def _shape_sig(tree):
        return tuple(
            (str(getattr(l, "shape", ())), str(getattr(l, "dtype", "")))
            for l in jax.tree_util.tree_leaves(tree)
        )

    def _mfu_fields(self) -> Dict[str, float]:
        """Model-FLOP throughput of the finished pass as structured
        fields, over TRAINING time only (the summed step windows —
        in-pass test/save/stats time would understate it). Empty on the
        accumulation path and whenever any batch's counting failed; MFU
        only when the chip's peak is known — never guessed. Both the
        human log note (``_mfu_note``) and the pass_end metrics record
        render from THIS dict."""
        if (self._pass_flops <= 0 or self._pass_train_s <= 0
                or self._pass_flops_incomplete):
            return {}
        from paddle_tpu.ops.kernel_flops import peak_tflops

        tfps = self._pass_flops / self._pass_train_s / 1e12
        fields = {"model_tflops_per_sec": tfps}
        peak = peak_tflops(jax.devices()[0].device_kind)
        if peak:
            fields["mfu"] = tfps / (peak * jax.device_count())
        return fields

    def _mfu_note(self, fields: Optional[Dict[str, float]] = None) -> str:
        """', model X TFLOP/s, MFU Y' rendered from ``_mfu_fields``."""
        if fields is None:
            fields = self._mfu_fields()
        if not fields:
            return ""
        note = f", model {fields['model_tflops_per_sec']:.3g} TFLOP/s"
        if "mfu" in fields:
            note += f", MFU {fields['mfu']:.3f}"
        return note

    def train_one_pass(self, pass_id: int, provider: DataProvider, rng) -> None:
        stats = TrainerStats()
        evaluators = EvaluatorChain(self.config.model_config)
        evaluators.start()
        log_period = self.flags.log_period
        profiling = False
        self._pass_flops = 0.0
        self._pass_train_s = 0.0
        self._pass_flops_incomplete = False
        self._lsgd_discarded = 0
        t0 = time.monotonic()  # rate clock: immune to NTP steps mid-pass
        pass_t0 = time.perf_counter()  # pass_time_s clock
        spans_before = global_stats.snapshot()
        batch_id = 0
        step_times: list = []
        launch_counts = {"single": 0, "fused": 0}
        profiled = False
        # rollback fast-forward: when re-running the pass that diverged,
        # consume (without training) the batches up to and past the
        # poison region, so the same poisoned update is not re-applied
        ff_until = 0
        if self._ff_target is not None:
            tgt_pass, tgt_batch = self._ff_target
            if pass_id == tgt_pass:
                ff_until = tgt_batch
                logger.info(
                    "Pass %d: fast-forwarding past the poison region "
                    "(skipping batches < %d)", pass_id, tgt_batch,
                )
            if pass_id >= tgt_pass:
                self._ff_target = None
        groups = self._launch_groups(
            self._device_prefetch(self._global_batches(provider))
        )
        while True:
            # one launch, end to end, is one `trainer/step` span, opened
            # BEFORE the pull of its input so that every phase of the step
            # (the wait for data included) is its child and shares its
            # step number in the trace
            with stat_timer("trainer/step", step_num=batch_id) as step_span:
                with stat_timer("trainer/data_wait") as wait_span:
                    launch = next(groups, None)
                    if launch is None:
                        # the pull that finds the pass's end is no step
                        wait_span.drop()
                        step_span.drop()
                if launch is None:
                    break
                kind, group = launch
                # launch boundary: the hangwatch ping that proves the step
                # loop is alive — everything below (stall site included)
                # counts against --step_hang_timeout. BEFORE the
                # fast-forward skip: replaying the data pipeline past a
                # rollback's poison region IS progress (same rationale as
                # the feeder watchdog's fast-forward heartbeat), and a long
                # replay must not be misdiagnosed as a hang mid-recovery.
                if self._hangwatch is not None:
                    self._hangwatch.ping(pass_id, batch_id)
                self._last_launch = (pass_id, batch_id)
                if ff_until and batch_id < ff_until:
                    batch_id += len(group) if kind == "fused" else 1
                    continue
                # chaos sites (one hit per trained launch):
                # `trainer.crash=exit@N` is a deterministic mid-run process
                # death — what `paddle supervise` drills recover from;
                # `trainer.stall=sleep:S@N` wedges the step loop — what the
                # hangwatch (--step_hang_timeout) drills detect
                faultinject.fault_point(
                    "trainer.crash", info=f"pass={pass_id} batch={batch_id}"
                )
                faultinject.fault_point(
                    "trainer.stall", info=f"pass={pass_id} batch={batch_id}"
                )
                # `trainer.oom=raise@N` is a deterministic device OOM at the
                # launch boundary — what the oom_report.json pre-mortem +
                # exit-20 drills recover from (the synthetic error carries
                # the canonical RESOURCE_EXHAUSTED marker, so the catch in
                # train() classifies it exactly like the real thing)
                try:
                    faultinject.fault_point(
                        "trainer.oom", info=f"pass={pass_id} batch={batch_id}"
                    )
                except faultinject.FaultInjected as e:
                    raise obs_mem.SyntheticOomError(
                        f"pass={pass_id} batch={batch_id}"
                    ) from e
                # `trainer.nonfinite_layer=raise:LAYER@N` poisons the named
                # layer's parameters with NaN — the effect a nonfinite
                # gradient applied by the optimizer has — so the next loss
                # goes NaN and the per-layer blame re-run must name LAYER
                try:
                    faultinject.fault_point(
                        "trainer.nonfinite_layer",
                        info=f"pass={pass_id} batch={batch_id}",
                    )
                except faultinject.FaultInjected as e:
                    self._poison_layer(e.arg, pass_id, batch_id)
                # sparse tables: `sparse.gather_fault=raise@N` aborts the
                # launch whose touched-row prefetch is about to run (loud
                # failure, never training on stale rows), and the host
                # batch ids feed the kind=sparse per-pass accounting —
                # BEFORE the fused path drops its per-batch host args
                if self._sparse_stats is not None:
                    faultinject.fault_point(
                        "sparse.gather_fault",
                        info=f"pass={pass_id} batch={batch_id}",
                    )
                    for hb in ([it[1] for it in group] if kind == "fused"
                               else [group[1]]):
                        self._sparse_stats.note_batch(self._sparse_plan, hb)
                launch_counts[kind] += 1
                if (
                    self.flags.profile_dir
                    and pass_id == self.start_pass
                    and not profiling
                    and not profiled
                    and batch_id >= self.flags.profile_start_batch
                ):
                    # fused launches advance batch_id by k: trigger at launch
                    # granularity (the window covers whole launches)
                    jax.profiler.start_trace(self.flags.profile_dir)
                    profiling = True
                    logger.info("profiler trace started → %s", self.flags.profile_dir)
                if kind == "fused":
                    t_prep = time.perf_counter()
                    items = group
                    kf = len(items)
                    ns = [it[0] for it in items]
                    stacked = jax.tree_util.tree_map(
                        lambda *xs: jnp.stack(xs), *[it[2] for it in items]
                    )
                    # the stacked copy is what the launch consumes — drop the
                    # per-batch device arrays now instead of holding ~2x the
                    # launch's input data in HBM across the step. Cleared IN
                    # PLACE: _launch_groups' suspended frame still aliases
                    # this list (its buf rebind only runs on the next resume)
                    group.clear()
                    items = group = None
                    # consume one split of the pass chain PER BATCH, exactly
                    # as the unfused loop does, so batches_per_launch=k
                    # reproduces k=1 numerics for rng-using models (dropout)
                    step_keys = []
                    for _ in range(kf):
                        rng, sr = jax.random.split(rng)
                        step_keys.append(sr)
                    rngs = jnp.stack(step_keys)
                    ns_arr = jnp.asarray([float(x) for x in ns])
                    prep_s = time.perf_counter() - t_prep
                    # launch FLOPs counted exactly: the walker multiplies the
                    # fused scan body by its length k. Counted OUTSIDE the
                    # step window (a cache-miss jaxpr trace must not inflate
                    # step timing), while host-side stacking/rng prep stays
                    # INSIDE it, preserving the window's original semantics
                    launch_key = ("fused", kf, self._shape_sig(stacked))
                    with stat_timer("trainer/flops_count"):
                        self._pass_flops += self._count_model_flops(
                            launch_key,
                            self.fused_step, self.params, self.opt_state,
                            stacked, rngs, ns_arr,
                        )
                    t_step = time.perf_counter() - prep_s
                    snap = self._nf_snapshot()
                    with stat_timer("trainer/launch"):
                        fused_out = self._compiles.call(
                            "fused_step", launch_key, self.fused_step,
                            self.params, self.opt_state, stacked, rngs, ns_arr,
                            analytic_flops=self._flops_cache.get(launch_key),
                            pass_id=pass_id, step=batch_id,
                        )
                    self.params, self.opt_state, losses, keeps, states = fused_out[:5]
                    if self._numerics_groups is not None:
                        # stays on device: read back only at the log period
                        self._numerics_last = fused_out[5]
                    # ONE device→host transfer per launch (losses, evaluator
                    # states and kept outputs together); numpy slicing below
                    # adds no further device dispatches
                    with stat_timer("trainer/loss_sync"):
                        losses_host, keeps_host, states_host = jax.device_get((losses, keeps, states))  # lint: disable=PTL002 -- the one designed sync: amortized over the k-batch launch, feeds the nonfinite gate
                    losses_host = np.asarray(losses_host)
                    if faultinject.is_active():
                        losses_host = np.asarray([
                            self._poisoned_loss(float(l), pass_id, batch_id + i)
                            for i, l in enumerate(losses_host)
                        ])
                    if not np.isfinite(losses_host).all():
                        # gate BEFORE any per-batch housekeeping: params already
                        # contain all k updates, so a periodic save fired for an
                        # earlier batch of this launch would checkpoint
                        # NaN-poisoned weights as if they were pre-NaN
                        bad = int(np.flatnonzero(~np.isfinite(losses_host))[0])
                        if self._handle_nonfinite(
                            pass_id, batch_id + bad, float(losses_host[bad]),
                            snap, f"(launch of {kf}) ",
                            # the poisoned batch, sliced out of the stacked
                            # launch for the per-layer blame re-run (cold
                            # path: this only ever runs on a NaN loss)
                            batch=jax.tree_util.tree_map(
                                lambda x, i=bad: x[i], stacked
                            ),
                            rng=rngs[bad],
                        ):
                            # poisoned launch discarded whole (skip policy):
                            # pre-launch params/opt_state are back in place.
                            # If this was the group's FIRST launch, nobody
                            # consumed its compile-cost deduction — drop it,
                            # or the next clean launch's exec time would be
                            # zeroed by a compile it never paid
                            self._compiles.drop_pending("fused_step", launch_key)
                            batch_id += kf
                            continue
                    launch_s = time.perf_counter() - t_step
                    self._pass_train_s += launch_s
                    self._compiles.note_exec(
                        "fused_step", launch_key, launch_s, batches=kf
                    )
                    step_dt = launch_s / kf
                    def batch_of(tree, i):
                        return jax.tree_util.tree_map(lambda x: x[i], tree)

                    results = [
                        (float(losses_host[i]), batch_of(keeps_host, i),
                         batch_of(states_host, i), ns[i])
                        for i in range(kf)
                    ]
                else:
                    rng, step_rng = jax.random.split(rng)
                    n, _host_batch, batch = group
                    launch_key = None
                    if self._accum_n <= 1 and not self._async:
                        launch_key = ("single", self._shape_sig(batch))
                        with stat_timer("trainer/flops_count"):
                            self._pass_flops += self._count_model_flops(
                                launch_key,
                                self.train_step, self.params, self.opt_state,
                                batch, step_rng, jnp.asarray(float(n)),
                            )
                    t_step = time.perf_counter()
                    snap = self._nf_snapshot()
                    with stat_timer("trainer/launch"):
                        if self._accum_n > 1:
                            loss, outputs, states = self._accum_step(batch, step_rng, n)
                        elif self._async:
                            loss, outputs, states = self._async_step(batch, step_rng, n)
                        else:
                            step_out = self._compiles.call(
                                "train_step", launch_key, self.train_step,
                                self.params, self.opt_state, batch, step_rng,
                                jnp.asarray(float(n)),
                                analytic_flops=self._flops_cache.get(launch_key),
                                pass_id=pass_id, step=batch_id,
                            )
                            self.params, self.opt_state, loss, outputs, states = step_out[:5]
                            if self._numerics_groups is not None:
                                self._numerics_last = step_out[5]
                    # the host blocked on the device: the only phase in
                    # which an idle device is not the host's doing. The
                    # evaluators' states (a few floats) ride the loss's read
                    with stat_timer("trainer/loss_sync"):
                        loss_f, states = jax.device_get((loss, states))  # lint: disable=PTL002 -- single-step path: the per-launch loss read IS the nonfinite gate
                    loss_f = self._poisoned_loss(float(loss_f), pass_id, batch_id)
                    step_dt = time.perf_counter() - t_step
                    self._pass_train_s += step_dt
                    if launch_key is not None:
                        self._compiles.note_exec("train_step", launch_key, step_dt)
                    results = [(loss_f, outputs, states, n)]
                if self._restart_pending:
                    # the run's first completed launch: restart latency is
                    # now fully paid (restore + trace + compile + step 1) —
                    # the structured number heartbeat-grace and crash-loop
                    # windows are tuned from (`paddle metrics` "restore s" /
                    # "ttfs s" columns)
                    self._restart_pending = False
                    obs.emit(
                        "restart", pass_id=pass_id, step=batch_id,
                        restore_s=round(self._restore_s, 6),
                        time_to_first_step_s=round(
                            time.perf_counter() - self._t_construct, 6
                        ),
                        resumed=self._restored_pass is not None,
                        # that time by phase: every span closed since
                        # process start (`trainer/init` and its children,
                        # `data/provider_start`, this launch's wait, flops
                        # count, `compile/*` and loss sync)
                        spans_total=global_stats.totals(),
                    )
                batch_id_start = batch_id
                for loss_f, outputs, states, n in results:
                    self.forward_output = outputs
                    step_times.append(step_dt)
                    if not np.isfinite(loss_f):
                        # FP trap role (ref: feenableexcept(FE_INVALID|FE_DIVBYZERO|
                        # FE_OVERFLOW), TrainerMain.cpp:96), now policy-driven:
                        # abort raises, skip discards the update, rollback
                        # restores a checkpoint. Fused launches were gated
                        # above; reaching here is the single-batch path. loss
                        # is already read back each batch, so the check is free.
                        # (`batch` is only bound on the non-fused path —
                        # fused launches were gated above and never get here)
                        if self._handle_nonfinite(
                            pass_id, batch_id, loss_f, snap,
                            batch=batch if kind == "single" else None,
                            rng=step_rng if kind == "single" else None,
                        ):
                            batch_id += 1
                            continue
                    stats.add(loss_f * n, n)
                    with stat_timer("trainer/eval_outputs"):
                        self._eval_outputs(evaluators, outputs, states)
                    batch_id += 1
                    if self.flags.dot_period and batch_id % self.flags.dot_period == 0:
                        print(".", end="", flush=True, file=sys.stderr)
                        self._dots_pending = True

                # periodic housekeeping fires at LAUNCH boundaries: params hold
                # every update of the launch, so a save labeled with a
                # mid-launch batch_id would contain later batches' updates and
                # a resume from it would double-apply them. ``crossed`` is the
                # plain modulo check when a launch is one batch.
                def crossed(period):
                    return period and batch_id // period > batch_id_start // period

                with stat_timer("trainer/housekeeping"):
                    if crossed(self.flags.test_period):
                        self._end_dot_line()
                        with stat_timer("trainer/test"):
                            self.test(pass_id=pass_id)
                    if crossed(self.flags.show_parameter_stats_period):
                        self._end_dot_line()
                        self.show_parameter_stats()
                    if crossed(log_period):
                        self._end_dot_line()
                        logger.info(
                            "Pass %d batch %d  %s  %s",
                            pass_id,
                            batch_id,
                            stats.summary(),
                            evaluators.summary(),
                        )
                        # the window record carries the SAME key=value pairs the
                        # log line just printed (one shared dict, satellite of
                        # doc/observability.md)
                        obs.emit("train_window", pass_id=pass_id, step=batch_id,
                                 **stats.summary_dict())
                        stats.reset_window()
                    if crossed(self._numerics_period) and self._numerics_last is not None:
                        # the ONLY host readback of the health aux: a tiny
                        # [n_layers, 4] transfer at the numerics log period,
                        # inside a helper so the per-step loop stays sync-free
                        self._emit_numerics(pass_id, batch_id)
                    # preemption (SIGTERM flag) saves through the SAME block as the
                    # periodic save — one flush, one save, even when both fire on
                    # this boundary (TPU pods preempt with a SIGTERM notice; the
                    # reference is restart-from-last-pass only — SURVEY §5 names
                    # this the recovery gap). Snapshot the flag ONCE: a signal
                    # landing between two reads must not make the raise claim a
                    # save that never ran.
                    preempted = self._preempt_requested
                    want_save = crossed(self.flags.saving_period_by_batches) or preempted
                    if want_save and self.save_dir:
                        if self._accum_n > 1:
                            # apply pending gradients first or the checkpoint
                            # would silently drop up to N-1 batches' worth
                            self._accum_flush()
                        self.save(pass_id, batch_id=batch_id)
                if preempted:
                    self._end_dot_line()
                    logger.info("SIGTERM received — checkpointed at the launch "
                                "boundary" if self.save_dir else
                                "SIGTERM received — no save_dir, nothing saved")
                    if profiling:
                        # the open trace would otherwise be abandoned mid-write
                        jax.block_until_ready(self.params)  # lint: disable=PTL002 -- preemption exit: runs AT MOST ONCE per process (SIGTERM teardown), and the profiler trace must see the last launch land before stop_trace abandons it
                        jax.profiler.stop_trace()
                        logger.info("profiler trace written to %s",
                                    self.flags.profile_dir)
                    saved_path = (
                        os.path.join(self.save_dir, ckpt.PASS_FMT % pass_id)
                        if self.save_dir else ""
                    )
                    # SIGTERM-driven flush: the preemption window must not
                    # cost the buffered telemetry of this partial pass
                    obs.emit("preempt", pass_id=pass_id, step=batch_id,
                             saved_path=saved_path)
                    obs.flush()
                    obs_spans.export()
                    raise PreemptionExit(pass_id, saved_path)
                if profiling and batch_id >= (
                    self.flags.profile_start_batch + self.flags.profile_num_batches
                ):
                    jax.block_until_ready(self.params)  # lint: disable=PTL002 -- profiler window close: runs ONCE per run (profiling flips false right below), and the trace must include the final profiled launch before stop_trace
                    jax.profiler.stop_trace()
                    profiling = False
                    profiled = True
                    logger.info("profiler trace written to %s", self.flags.profile_dir)
        if self._accum_n > 1:
            # end-of-pass remainder: apply whatever is accumulated so no
            # sample's gradient is dropped (reference flushes on finishPass)
            self._accum_flush()
        self._async_flush(final=True)  # pass end: real merge + collapse
        if self._lsgd_discarded:
            logger.info(
                "Pass %d: drift gate discarded %d replica update block(s) "
                "(async_lagged_grad_discard_ratio=%g)",
                pass_id, self._lsgd_discarded,
                self.config.opt_config.async_lagged_grad_discard_ratio,
            )
        if profiling:
            jax.block_until_ready(self.params)
            jax.profiler.stop_trace()
            logger.info("profiler trace written to %s", self.flags.profile_dir)
        self._end_dot_line()
        # pass-boundary telemetry for the two new planes: the last
        # launch's numerics health (so every pass has at least one
        # numerics record even when the period exceeds the pass), and a
        # live memory snapshot (kind=memory record + mem.* gauges — the
        # gauges land in the counters snapshot of the pass_end below)
        if self._numerics_last is not None:
            self._emit_numerics(pass_id, batch_id)
        if obs.enabled():
            self._mem_last = obs_mem.sample_and_emit(
                pass_id=pass_id, step=batch_id
            )
        dt = time.monotonic() - t0
        rate = stats.total_samples / max(dt, 1e-9)
        mfu_fields = self._mfu_fields()
        logger.info(
            "Pass %d done: %s  %s  (%.1f samples/s%s)",
            pass_id,
            stats.summary(),
            evaluators.summary(),
            rate,
            self._mfu_note(mfu_fields),
        )
        # the structured twin of the "Pass N done" line: same shared
        # dict (summary_dict / mfu_fields) plus step-time quantiles,
        # launch-group counts, and the cumulative counters snapshot —
        # flushed here, so a crash loses at most one pass window
        record: Dict[str, Any] = dict(stats.summary_dict())
        record.update(evaluators.results())
        record.update(mfu_fields)
        record["samples_per_sec"] = rate
        record["pass_time_s"] = time.perf_counter() - pass_t0
        if step_times:
            record["step_time_mean_s"] = float(np.mean(step_times))
            record["step_time_p50_s"] = float(np.percentile(step_times, 50))
            record["step_time_p99_s"] = float(np.percentile(step_times, 99))
        record["launches_single"] = launch_counts["single"]
        record["launches_fused"] = launch_counts["fused"]
        if self._hangwatch is not None:
            # worst step-progress age this pass (the hangwatch gauge's
            # max-since-last-read) — `paddle metrics` surfaces it, so a
            # near-miss stall is visible before the one that kills a run
            record["progress_age_max_s"] = round(
                self._hangwatch.take_max_age(), 3
            )
        # the step's phases without a profiler: {span: [count, total_s]}
        # over this pass, the same scopes the trace shows; and the same
        # since process start, by the convention of `counters`, so that
        # what ran between passes or before the first (set-up) is in a
        # record too
        spans_now = global_stats.snapshot()
        record["spans"] = global_stats.growth_since(spans_before, spans_now)
        record["spans_total"] = global_stats.totals(spans_now)
        if obs.enabled():
            record["counters"] = obs.registry().snapshot()
        obs.emit("pass_end", pass_id=pass_id, step=batch_id, **record)
        # sparse-table plane (doc/sparse.md): touched/unique rows,
        # gather/scatter bytes, reshard events — one kind=sparse
        # record per pass, the raw material of `paddle metrics`' rows/s
        # column and `paddle compare`'s sparse verdicts
        if self._sparse_stats is not None:
            obs.emit(
                "sparse", pass_id=pass_id, step=batch_id,
                **self._sparse_stats.pass_record(duration_s=dt),
            )
        # per-launch-group cost attribution (cumulative totals —
        # `paddle roofline` keeps latest-wins per group, so re-run
        # passes never double-count)
        self._compiles.emit_roofline(pass_id=pass_id)
        from paddle_tpu.utils.barrier import step_time_skew_summary

        step_time_skew_summary(step_times, pass_id=pass_id)

    # --------------------------------------------- divergence recovery

    def _nf_snapshot(self):
        """Pre-step state the skip policy can hand back: plain references
        — valid after the step because _donate_steps disabled buffer
        donation for every non-abort policy. None under abort (the
        handler will raise, nothing to restore)."""
        if self._nf_policy == "abort":
            return None
        return (
            self.params, self.opt_state,
            self._acc, self._acc_batches, self._acc_samples,
        )

    def _poisoned_loss(self, loss_f: float, pass_id: int, batch_id: int) -> float:
        """`trainer.nonfinite` injection site — one hit per batch; a
        firing `raise` rule turns this batch's loss into NaN, the
        deterministic divergence the chaos tests drive policies with."""
        if faultinject.is_active():
            try:
                faultinject.fault_point(
                    "trainer.nonfinite", info=f"pass={pass_id} batch={batch_id}"
                )
            except faultinject.FaultInjected:
                logger.warning(
                    "injected non-finite loss at pass %d batch %d",
                    pass_id, batch_id,
                )
                return float("nan")
        return loss_f

    def _handle_nonfinite(self, pass_id, batch_id, value, snap,
                          launch_note="", batch=None, rng=None):
        """Apply --nonfinite_policy to one non-finite loss. Returns True
        when the poisoned update was discarded (skip) and the caller
        should move on; raises NonFiniteLossError (abort / exhausted
        budget) or _RollbackRequest (rollback) otherwise.

        When the poisoned ``batch`` is available it is re-run in the
        per-layer checking mode (observability/numerics.py) and the
        first layer producing a nonfinite value rides the ``nonfinite``
        record (``blame_layer``/``blame_phase``) and the abort message —
        recovery that names its culprit instead of just surviving it."""
        base = (
            f"non-finite loss ({value}) at pass {pass_id} "
            f"batch {batch_id} {launch_note}"
        )
        blame = None
        if batch is not None:
            # skip/rollback kept the pre-step state (donation disabled):
            # blame re-runs the exact poisoned step. Abort donated the
            # pre-step buffers, so the post-update params stand in —
            # approximate, but a NaN born in the forward/backward still
            # reproduces there.
            params_src = snap[0] if snap is not None else self.params
            blame = obs_num.blame_nonfinite(
                self.gm, self.config.model_config, params_src, batch, rng
            )
        blame_fields = {}
        blame_note = ""
        if blame is not None:
            blame_fields = {"blame_layer": blame["layer"],
                            "blame_phase": blame["phase"]}
            blame_note = (
                f" [first nonfinite at layer {blame['layer']!r}, "
                f"{blame['phase']} phase, {blame['nonfinite']} value(s)]"
            )
            logger.warning(
                "nonfinite blame: first nonfinite value at layer %r "
                "(%s phase, %d nonfinite value(s)%s)",
                blame["layer"], blame["phase"], blame["nonfinite"],
                f", param {blame['param']}" if blame.get("param") else "",
            )
        if self._numerics_last is not None:
            # flush the poisoned launch's health table alongside the
            # event: an abort must not die with the per-layer evidence
            # still sitting on device awaiting the next log period
            self._emit_numerics(pass_id, batch_id)
        obs.registry().counter("nonfinite.events").inc()
        obs.emit("nonfinite", pass_id=pass_id, step=batch_id,
                 value=value, policy=self._nf_policy, **blame_fields)
        if self._nf_policy == "abort" or snap is None:
            raise NonFiniteLossError(
                base + blame_note
                + "— aborting. Try --job=checkgrad, a lower learning "
                "rate, or gradient clipping to locate the cause "
                "(or --nonfinite_policy=skip/rollback to recover).",
                value=value, pass_id=pass_id, batch_id=batch_id,
            )
        self._nf_count += 1
        if self._nf_count > self._nf_budget:
            raise NonFiniteLossError(
                base + blame_note + f"— non-finite budget exhausted "
                f"(--max_nonfinite_steps={self._nf_budget}, "
                f"{self._nf_count - 1} poisoned event(s) already recovered)",
                value=value, pass_id=pass_id, batch_id=batch_id,
            )
        (self.params, self.opt_state, self._acc,
         self._acc_batches, self._acc_samples) = snap
        if self._nf_policy == "skip":
            logger.warning(
                "%s— update discarded (%d/%d non-finite budget used)",
                base, self._nf_count, self._nf_budget,
            )
            return True
        raise _RollbackRequest(pass_id, batch_id)

    def _emit_numerics(self, pass_id: int, batch_id: int) -> None:
        """Read the newest launch's health aux back (the tiny
        [n_layers, 4] tree — the ONLY readback the numerics plane ever
        does, at --numerics_log_period boundaries and pass ends) and
        emit the ``kind=numerics`` record."""
        health = jax.device_get(self._numerics_last)
        layers, nf_layers, grad_norm = obs_num.derive(health)
        obs.emit(
            "numerics", pass_id=pass_id, step=batch_id,
            layers=layers, nonfinite_layers=nf_layers,
            global_grad_norm=grad_norm,
        )
        r = obs.registry()
        r.gauge("numerics.global_grad_norm").set(
            grad_norm if math.isfinite(grad_norm) else -1.0
        )
        if nf_layers:
            r.counter("numerics.nonfinite_layer_events").inc(len(nf_layers))

    def _poison_layer(self, layer: Optional[str], pass_id: int,
                      batch_id: int) -> None:
        """`trainer.nonfinite_layer` injection: write one NaN into each
        of the named layer's parameters — exactly what applying a
        nonfinite gradient through the optimizer would leave behind —
        so the next launch's loss goes NaN and the blame re-run has a
        real poisoned layer to find (no shortcut: blame never consults
        the injector)."""
        groups = self._numerics_groups or obs_num.layer_groups(
            self.config.model_config, list(self.params)
        )
        pnames = groups.get(layer or "")
        if not pnames:
            logger.warning(
                "trainer.nonfinite_layer: no parameters belong to layer "
                "%r (known: %s) — nothing poisoned",
                layer, ", ".join(sorted(groups)),
            )
            return
        for pn in pnames:
            v = np.array(jax.device_get(self.params[pn]))
            v.reshape(-1)[0] = float("nan")
            self.params[pn] = jnp.asarray(v)
        logger.warning(
            "injected NaN into layer %r parameter(s) %s at pass %d "
            "batch %d (trainer.nonfinite_layer)",
            layer, pnames, pass_id, batch_id,
        )

    def _oom_premortem(self, err: BaseException) -> None:
        """Write oom_report.json into the run dir before the OOM death
        propagates: per-group static footprint (XLA's memory plans,
        ranked), the freshest live snapshot the allocator will still
        give us, and the telemetry tail. The backstop timer inside
        trigger_oom_report guarantees exit EXIT_OOM even when the
        forensics themselves wedge — same discipline as hangwatch."""
        from paddle_tpu.resilience.hangwatch import run_dir_of

        report_dir = run_dir_of(
            getattr(self.flags, "metrics_path", "") or self.save_dir or "."
        )
        try:
            # post-OOM sampling usually still works (the allocator is
            # full, not gone) and is the most truthful evidence; the
            # last pass-boundary snapshot is the fallback
            live = obs_mem.sample_memory()
        except Exception:
            live = self._mem_last
        obs_mem.trigger_oom_report(
            report_dir, err,
            groups=self._compiles.static_memory_rows(),
            live=live or self._mem_last,
            where=(
                {"pass": self._last_launch[0], "step": self._last_launch[1]}
                if self._last_launch is not None else None
            ),
            device_kind=self._compiles.device_kind or "",
            exit_fn=os._exit,
        )

    def _apply_rollback(self, rb: _RollbackRequest) -> int:
        """--nonfinite_policy=rollback: restore the newest verified
        checkpoint, temper the learning rate, and arrange to fast-forward
        past the poison region. Returns the pass id to resume from."""
        # settle the background writer first: the newest enqueued save
        # must be on disk before the restore scan, and a FAILED async
        # write must not abort the rollback (older checkpoints remain) —
        # log it and restore from what is actually durable
        if self._async_ckpt is not None:
            try:
                self._async_ckpt.drain()
            except Exception as e:
                logger.warning(
                    "rollback: async checkpoint writer reported %s — "
                    "restoring from the newest durable checkpoint", e,
                )
        # warm-resume: a checkpoint THIS process committed earlier in
        # the run needs no re-CRC before the rollback restore —
        # verification cost belongs to cold restores (fresh processes
        # have written nothing, so they still verify in full)
        path = (
            ckpt.find_restorable_checkpoint(self.save_dir, trust_own_writes=True)
            if self.save_dir else None
        )
        if path is None:
            raise NonFiniteLossError(
                f"non-finite loss at pass {rb.pass_id} batch {rb.batch_id} "
                "— --nonfinite_policy=rollback found no restorable "
                "checkpoint under --save_dir to roll back to",
                pass_id=rb.pass_id, batch_id=rb.batch_id,
            )
        # the restore below (multi-GB on a slow shared fs, then a full
        # re-jit at the next launch) is recovery progress, not a hang —
        # ping around it so an armed hangwatch does not kill a healthy
        # rollback mid-flight (the fast-forward replay after it pings
        # per launch for the same reason)
        if self._hangwatch is not None:
            self._hangwatch.ping(rb.pass_id, rb.batch_id)
        # find_restorable either CRC'd the candidate or trusted this
        # process's own write — verify=False skips the redundant re-CRC
        # in both cases, and trust_own_writes tells load_checkpoint
        # which case it is (a corrupt TRUSTED checkpoint must fall back
        # to an earlier pass, not re-raise as a config error)
        self.params, opt_state, meta = ckpt.load_checkpoint(
            path, self.opt_state, expected_params=self.params,
            sharding_for=self.ckpt_sharding_for(),
            verify=False, fallback=True, trust_own_writes=True,
        )
        if self._hangwatch is not None:
            self._hangwatch.ping(rb.pass_id, rb.batch_id)
        if opt_state is not None:
            self.opt_state = opt_state
        restored = self._note_restored(path, meta)
        scale = float(getattr(self.flags, "rollback_lr_scale", 0.5) or 1.0)
        oc = self.config.opt_config
        old_lr = oc.learning_rate
        oc.learning_rate = old_lr * scale
        # the jitted steps baked the old schedule constants at trace
        # time — drop them so the tempered lr actually takes effect
        # (including the compile registry's AOT executables; the re-jit
        # shows up in the compile telemetry as recompiles>0)
        self._train_step_fn = None
        self._fused_step_fn = None
        self._accum_fns = None
        self._compiles.invalidate("train_step", "fused_step")
        self._acc = None
        self._acc_batches = 0
        self._acc_samples = 0
        self.rollbacks += 1
        self._ff_target = (rb.pass_id, rb.batch_id + 1)
        resume = (restored + 1) if restored is not None else rb.pass_id
        logger.warning(
            "rollback: non-finite loss at pass %d batch %d — restored %s, "
            "learning_rate %g -> %g (x%g), resuming at pass %d "
            "(will fast-forward past batch %d of pass %d)",
            rb.pass_id, rb.batch_id, path, old_lr, oc.learning_rate, scale,
            resume, rb.batch_id, rb.pass_id,
        )
        return resume

    def _accum_step(self, batch, step_rng, n: int):
        """One gradient-accumulation batch; applies the optimizer update
        every N-th call."""
        if self._accum_fns is None:
            self._accum_fns = self._build_accum_steps()
        astep, ustep = self._accum_fns
        if self._acc is None:
            self._acc = jax.tree_util.tree_map(jnp.zeros_like, dict(self.params))
        self.params, self._acc, loss, outputs, states = astep(
            self.params, self._acc, batch, step_rng, jnp.asarray(float(n))
        )
        self._acc_batches += 1
        self._acc_samples += n
        if self._acc_batches >= self._accum_n:
            self._accum_flush()
        return loss, outputs, states

    def _accum_flush(self) -> None:
        if self._acc_batches == 0 or self._acc is None:
            return
        astep, ustep = self._accum_fns
        self.params, self.opt_state, self._acc = ustep(
            self.params, self.opt_state, self._acc,
            jnp.asarray(float(self._acc_samples)),
        )
        self._acc_batches = 0
        self._acc_samples = 0

    # ----------------------------------------------- async SGD (local SGD)

    def _async_step(self, batch, step_rng, n: int):
        """One local-SGD batch: every replica applies its own gradient to
        its own parameter copy (no cross-replica collective); merges every
        ``num_batches_per_send_parameter``-th call."""
        if self._local_sgd is None:
            from paddle_tpu.parallel.local_sgd import LocalSgd

            # the SAME one-batch body the sync path jits (dense grads:
            # sparse row sets vary per batch and cannot ride the stack)
            self._local_sgd = LocalSgd(
                self._one_batch_step(sparse=False),
                self._mesh,
                self.config.opt_config.async_lagged_grad_discard_ratio,
            )
        if self._lsgd_state is None:
            self._lsgd_state = self._local_sgd.stack(self.params, self.opt_state)
        pr, po = self._lsgd_state
        pr, po, loss, outputs, states = self._local_sgd.step(
            pr, po, batch, step_rng, jnp.asarray(float(n))
        )
        self._lsgd_state = (pr, po)
        self._lsgd_dirty = True
        self._lsgd_batches += 1
        if self._lsgd_batches >= self._sync_n:
            self._lsgd_merge()
        return loss, outputs, states

    def _lsgd_merge(self) -> None:
        pr, po = self._lsgd_state
        pr, po, discarded = self._local_sgd.merge(pr, po)
        self._lsgd_state = (pr, po)
        self._lsgd_batches = 0
        self._lsgd_discarded += int(discarded)

    def _async_flush(self, final: bool = False) -> None:
        """Materialize canonical params/opt_state from the replica stacks
        — called before any consumer of self.params (test/save/stats).

        Mid-pass (``final=False``) this reads a PASSIVE merged snapshot
        (`LocalSgd.merged_view`): the replica stacks and the merge
        schedule are untouched, so observability flags (test_period,
        show_parameter_stats_period, periodic saves) never perturb the
        optimization trajectory — the reference's test path likewise
        read the pserver's merged parameters without collapsing the
        trainers' local progress. At pass end (``final=True``) a real
        merge runs and the stacks collapse, so the pass boundary is a
        true synchronization point (reference waitPassFinish)."""
        if not self._async or not self._lsgd_dirty:
            return
        if not final:
            self.params, self.opt_state = self._local_sgd.merged_view(
                *self._lsgd_state
            )
            return  # stacks still ahead of params: stays dirty
        if self._lsgd_batches:
            self._lsgd_merge()
        self.params, self.opt_state = self._local_sgd.collapse(*self._lsgd_state)
        self._lsgd_dirty = False

    @property
    def _is_writer(self) -> bool:
        """Exactly one process writes result/prediction files."""
        return not self._multiproc or jax.process_index() == 0

    def _global_batches(self, provider: DataProvider, pad: bool = False):
        """Yield (n_samples, host batch, mesh-ready batch).

        Batches that cannot be evenly sharded (data-axis divisor ×
        multi-host process count): training SKIPS them with a one-time
        warning (sync-SGD needs identical per-device slices;
        doc/divergences.md), inference jobs (``pad=True``) PAD them by
        repeating the last sample and the caller trims outputs back to n
        — every sample is processed exactly once."""
        div = self._batch_divisor
        if self._multiproc:
            div = div * jax.process_count() // math.gcd(div, jax.process_count())
        for batch in provider.batches():
            n = _batch_num_samples(batch)
            if div > 1 and n % div:
                if not pad:
                    self._warn_remainder(n)
                    continue
                batch = _pad_batch(batch, n + (div - n % div))
            if self._multiproc:
                from paddle_tpu.parallel.spmd import globalize_batch

                g = globalize_batch(batch, self._mesh)
                assert g is not None  # padded/skipped to divisibility above
                yield n, batch, g
            else:
                yield n, batch, batch

    def _gather_host(self, outputs, names):
        """All-gather selected (small) outputs to full host values on
        every process — see spmd.gather_outputs (distributeEval role)."""
        from paddle_tpu.parallel.spmd import gather_outputs

        return gather_outputs(outputs, self._mesh, names)

    def _device_prefetch(self, gen):
        """One-step-lookahead device transfer: the NEXT batch's host→device
        copy is dispatched (async) while the current step computes — the
        device-side half of the reference's DoubleBuffer
        (DataProvider.h:245; the host half is the feeder's prefetch
        thread). Multi-process batches are already device-resident global
        arrays (globalize_batch), so they pass through."""
        if self._multiproc:
            yield from gen
            return
        if self._mesh is not None:
            from paddle_tpu.parallel.spmd import batch_sharding

            sharding = batch_sharding(self._mesh)
            to_device = lambda b: jax.device_put(b, sharding)
        else:
            to_device = jax.device_put

        def put(b):
            with stat_timer("data/h2d"):
                return to_device(b)

        it = iter(gen)
        try:
            n, host, dev = next(it)
        except StopIteration:
            return
        cur = (n, host, put(dev))
        for n2, host2, dev2 in it:
            nxt = (n2, host2, put(dev2))  # dispatches the copy immediately
            yield cur
            cur = nxt
        yield cur

    def _eval_outputs(self, evaluators: EvaluatorChain, outputs,
                      states=None, gathered=False) -> None:
        """Feed one batch to the evaluator chain: ``states``, the per-batch
        statistics the train step computed (already global over a mesh),
        are added as they are; the evaluators that were not in the step
        read ``outputs``, the layers the step kept for them.

        Multi-process, for those: evaluators with summable state
        accumulate over this process's LOCAL row block and merge their
        small state vectors once per read period (the reference's
        getState/distributeEval split, Evaluator.h:81-82) — no per-batch
        [B, V] activation gather. Evaluators without mergeable state
        (raw-record, printers) still get their layers gathered per batch.
        The local/gather split is decided ONCE per chain from global
        sharding metadata so every process runs the same collectives.
        ``gathered``: outputs are already full host values."""
        note_counters((states or {}).get(LAYER_COUNTERS))
        if not evaluators:
            return
        host_evs = evaluators.add_states(states or {})
        if not host_evs:
            return
        if self._multiproc and not gathered:
            from paddle_tpu.parallel import spmd

            plan = getattr(evaluators, "_dist_plan", None)
            if plan is None:
                merge_evs, gather_evs = evaluators.partition(host_evs)
                local_layers = evaluators.layers_for(merge_evs)
                if merge_evs and spmd.rows_locally_assemblable(outputs, local_layers):
                    evaluators.merge_fn = spmd.merge_eval_states
                else:
                    # e.g. a vocab-sharded output: local rows are partial —
                    # fall back to gathering for everything
                    gather_evs = host_evs
                    merge_evs, local_layers = [], []
                plan = evaluators._dist_plan = (
                    merge_evs, local_layers, gather_evs,
                    evaluators.layers_for(gather_evs),
                )
            merge_evs, local_layers, gather_evs, gather_layers = plan
            if merge_evs:
                evaluators.eval_batch(
                    spmd.local_row_block(outputs, local_layers), only=merge_evs
                )
            if gather_evs:
                evaluators.eval_batch(
                    self._gather_host(outputs, gather_layers), only=gather_evs
                )
            return
        evaluators.eval_batch(outputs, only=host_evs)

    def _warn_remainder(self, n: int) -> None:
        if not getattr(self, "_remainder_warned", False):
            self._remainder_warned = True
            logger.warning(
                "skipping remainder batch of %d samples (not divisible by "
                "the %d-way data axis); pad the dataset or pick a batch "
                "size multiple of the mesh to use every sample", n,
                self._batch_divisor,
            )

    def _end_dot_line(self) -> None:
        """Terminate a run of progress dots before a log line (the
        reference printed the newline in TrainerInternal too)."""
        if getattr(self, "_dots_pending", False):
            print("", flush=True, file=sys.stderr)
            self._dots_pending = False

    def show_parameter_stats(self) -> None:
        """Per-parameter value stats (ref: TrainerInternal::showParameterStats,
        TrainerInternal.cpp:184-213)."""
        self._async_flush()
        for name in sorted(self.params):
            v = np.asarray(self.params[name])
            logger.info(
                "Param %-40s mean=%.5g absmax=%.5g std=%.5g shape=%s",
                name, float(v.mean()), float(np.abs(v).max()), float(v.std()),
                tuple(v.shape),
            )

    # -------------------------------------------------------------- test

    def test(self, pass_id: int = -1) -> Dict[str, float]:
        # pass-end eval doubles as the async-checkpoint barrier: the
        # previous pass's background write had a whole pass of training
        # to overlap with, and a writer failure surfaces here at most
        # one pass late instead of at process exit
        self._drain_async_ckpt()
        provider = self._provider(for_test=True)
        if provider is None:
            return {}
        self._async_flush()
        params = self.updater.averaged_params(self.params, self.opt_state)
        if not self.gm.has_cost():
            return self.predict(provider, params)
        stats = TrainerStats()
        evaluators = EvaluatorChain(self.config.model_config)
        evaluators.start()
        for n, _host_batch, batch in self._global_batches(provider, pad=True):
            launch_key = ("test", self._shape_sig(batch))
            t_launch = time.perf_counter()
            outputs = jax.block_until_ready(self._compiles.call(
                "test_fwd", launch_key, self.test_fwd,
                params, batch, pass_id=pass_id,
            ))
            # the block makes exec_s measure execution, not dispatch —
            # the registry's roofline contract (the train paths sync via
            # their loss transfer instead)
            self._compiles.note_exec(
                "test_fwd", launch_key, time.perf_counter() - t_launch
            )
            if self._multiproc:
                # gather only what cost + evaluators read, then slice the
                # padding off host-side
                keep = list(
                    dict.fromkeys(
                        self.gm.cost_layer_names() + evaluators.needed_layers
                    )
                )
                outputs = self._gather_host(outputs, keep)
            outputs = self._trim_outputs(outputs, n)
            cost = float(self.gm.total_cost(outputs))
            stats.add(cost * n, n)
            self._eval_outputs(evaluators, outputs, gathered=True)
        results = {"cost": stats.total_cost / max(stats.total_samples, 1)}
        results.update(evaluators.results())
        logger.info("Test (pass %d): %s  %s", pass_id, stats.summary(),
                    evaluators.summary())
        obs.emit("test", pass_id=pass_id, **results)
        # standalone `paddle test` never reaches a train pass_end —
        # emit the roofline totals here (cumulative + latest-wins, so
        # the in-train duplicate emission is harmless)
        self._compiles.emit_roofline(pass_id=pass_id)
        return results

    def predict(self, provider: DataProvider, params=None) -> Dict[str, float]:
        """Cost-less test job: forward the net and dump output-layer values.

        The role of the reference Tester's prediction path
        (/root/reference/paddle/trainer/Tester.cpp, --predict_output_dir):
        when the config has no cost layer (is_predict configs ending in
        maxid/softmax outputs), write one text file per output layer —
        ids for id outputs, rows of values otherwise.
        """
        if params is None:
            params = self.updater.averaged_params(self.params, self.opt_state)
        out_dir = self.flags.predict_output_dir
        write = self._is_writer
        if out_dir and write:
            os.makedirs(out_dir, exist_ok=True)
        files = {}
        n_total = 0
        try:
            for n, _host_batch, batch in self._global_batches(provider, pad=True):
                launch_key = ("test", self._shape_sig(batch))
                t_launch = time.perf_counter()
                outputs = jax.block_until_ready(self._compiles.call(
                    "test_fwd", launch_key, self.test_fwd, params, batch,
                ))
                self._compiles.note_exec(
                    "test_fwd", launch_key, time.perf_counter() - t_launch
                )
                if self._multiproc:
                    # collective: every host gathers, only process 0 writes
                    outputs = self._gather_host(
                        outputs, self.gm.network.output_layer_names
                    )
                outputs = self._trim_outputs(outputs, n)
                n_total += n
                for name in self.gm.network.output_layer_names:
                    arg = outputs[name]
                    if out_dir and write:
                        f = files.get(name)
                        if f is None:
                            f = files[name] = open(
                                os.path.join(out_dir, f"predict_{name}.txt"), "w"
                            )
                    else:
                        f = None
                    lengths = (
                        np.asarray(arg.seq_lengths) if arg.seq_lengths is not None else None
                    )
                    if arg.ids is not None:
                        data = np.asarray(arg.ids)
                        if data.ndim == 1:
                            data = data[:, None]
                    else:
                        data = np.asarray(arg.value)
                    # one line per sample; sequence outputs print only the
                    # valid (unpadded) timesteps, space-joined
                    if not write:
                        continue
                    for b in range(data.shape[0]):
                        row = data[b]
                        if lengths is not None and row.ndim >= 1 and row.shape[0] >= lengths[b]:
                            row = row[: lengths[b]]
                        line = " ".join(f"{v:.6g}" for v in np.ravel(row))
                        if f is not None:
                            f.write(line + "\n")
                        else:
                            logger.info("predict %s: %s", name, line)
        finally:
            for f in files.values():
                f.close()
        logger.info(
            "Predict done: %d samples%s",
            n_total,
            f" → {out_dir}" if out_dir else "",
        )
        # predict jobs have no pass_end either — flush roofline totals
        self._compiles.emit_roofline()
        return {"samples": float(n_total)}

    # --------------------------------------------------------------- gen

    def generate(self, result_file: Optional[str] = None):
        """Sequence-generation job (ref: RecurrentGradientMachine
        generateSequence + demo/seqToseq gen.conf; the reference drives it
        as `paddle train --job=test` over a generating config).

        Runs the generator sub-model over the test (or train) data and
        writes, per sample, an index line followed by
        ``score\\ttok tok ...`` per kept beam. Returns the list of
        (best_ids, beam_ids, beam_scores, beam_lens) batches."""
        gen_sub = next(
            (s for s in self.config.model_config.sub_models if s.generator is not None),
            None,
        )
        assert gen_sub is not None, "config has no generator (use beam_search in the config)"
        gen = gen_sub.generator
        group = gen_sub.name
        result_file = result_file or self.flags.gen_result or gen.result_file
        words = None
        if gen.dict_file and os.path.exists(gen.dict_file):
            with open(gen.dict_file) as f:
                words = [line.rstrip("\n") for line in f]

        gm = self.gm

        def gen_fwd_fn(params, in_args):
            outputs, _ = gm.forward(params, in_args, pass_type="gen", rng=None)
            return outputs

        if self._mesh is not None:
            from paddle_tpu.parallel.spmd import shard_test_fwd

            gen_fwd = shard_test_fwd(gen_fwd_fn, self._mesh, self.gm)
        else:
            gen_fwd = jax.jit(gen_fwd_fn)

        # generation must consume samples in order (result indices map to
        # data order), even when falling back to the train data source
        provider = self._provider(for_test=True) or self._provider(
            for_test=False, ordered=True
        )
        assert provider is not None, "no data configured for generation"
        params = self.updater.averaged_params(self.params, self.opt_state)
        n_keep = max(int(gen.num_results_per_sample), 1)
        results = []
        sample_idx = 0
        out_f = open(result_file, "w") if result_file and self._is_writer else None
        try:
            for n, host_batch, batch in self._global_batches(provider, pad=True):
                # sample ids come from the HOST batch (pre-globalize), so
                # every process sees the full index column
                id_arg = (
                    host_batch.get(gen.id_input_layer) if gen.id_input_layer else None
                )
                sample_ids = (
                    np.asarray(id_arg.ids).reshape(-1) if id_arg is not None else None
                )
                launch_key = ("gen", self._shape_sig(batch))
                t_launch = time.perf_counter()
                outputs = jax.block_until_ready(self._compiles.call(
                    "generator", launch_key, gen_fwd, params, batch,
                ))
                self._compiles.note_exec(
                    "generator", launch_key, time.perf_counter() - t_launch
                )
                if self._multiproc:
                    outputs = self._gather_host(outputs, [group, f"{group}@beams"])
                outputs = self._trim_outputs(outputs, n)
                best = outputs[group]
                beams = outputs.get(f"{group}@beams")
                ids = np.asarray(best.ids)
                beam_ids = np.asarray(beams.ids) if beams is not None else ids[:, None]
                scores = (
                    np.asarray(beams.value)
                    if beams is not None
                    else np.zeros(beam_ids.shape[:2], np.float32)
                )
                lens = (
                    np.asarray(beams.sub_seq_lengths)
                    if beams is not None
                    else np.asarray(best.seq_lengths)[:, None]
                )
                results.append((ids, beam_ids, scores, lens))
                if out_f is not None:
                    for b in range(ids.shape[0]):
                        tag = sample_ids[b] if sample_ids is not None else sample_idx
                        out_f.write(f"{tag}\n")
                        for k in range(min(n_keep, beam_ids.shape[1])):
                            toks = beam_ids[b, k, : lens[b, k]].tolist()
                            text = " ".join(
                                words[t] if words and t < len(words) else str(t)
                                for t in toks
                            )
                            out_f.write(f"{scores[b, k]:.6f}\t{text}\n")
                        sample_idx += 1
        finally:
            if out_f is not None:
                out_f.close()
                logger.info("generation results written to %s", result_file)
        # `paddle gen` has no pass_end — the ROADMAP-2 ask ("give
        # generation the same roofline discipline training got") needs
        # the totals flushed here
        self._compiles.emit_roofline()
        return results

    # -------------------------------------------------------------- save

    def save(self, pass_id: int, batch_id: Optional[int] = None, final: bool = False) -> None:
        # collective in multi-process runs: each host writes the shards it
        # owns (ckpt.save_checkpoint handles the barrier + index merge) —
        # a cross-host model-sharded parameter is never materialized on
        # one process
        self._async_flush()
        extra = {"config_json": self.config.to_json()}
        if batch_id is not None:
            extra["batch_id"] = batch_id
        if self._sparse_stats is not None:
            # which params are row-sharded tables + how many hosts
            # wrote this pass: a relaunch on a different host set reads
            # these to detect (and count) the reshard it just performed
            extra["sparse_tables"] = sparse_rt.registered_tables()
            extra["sparse_hosts"] = jax.process_count()
        keep = 0 if final else 3
        if self._async_ckpt is not None:
            # step-loop cost: device→host snapshot only; the durable
            # write (and the protect-clearing below) happens when the
            # background writer reports the checkpoint landed
            self._async_ckpt.save(
                pass_id,
                self.params,
                self.opt_state,
                extra_meta=extra,
                keep=keep,
                protect_pass=self._restored_pass,
                on_durable=self._on_ckpt_durable,
            )
            return
        ckpt.save_checkpoint(
            self.save_dir,
            pass_id,
            self.params,
            self.opt_state,
            extra_meta=extra,
            keep=keep,
            # rolling deletion must never remove the checkpoint this run
            # restored from — until a newer save proves restorable it is
            # the only known-good state
            protect_pass=self._restored_pass,
        )
        self._on_ckpt_durable(pass_id, "")

    def _on_ckpt_durable(self, pass_id: int, _path: str) -> None:
        """A checkpoint for ``pass_id`` is durable on disk (manifested +
        renamed). Sync saves call this inline; async saves from the
        writer thread once the background protocol finished — only THEN
        may the restored-from pass rejoin the normal rotation budget."""
        if self._restored_pass is not None and pass_id != self._restored_pass:
            self._restored_pass = None

    def _drain_async_ckpt(self) -> None:
        """Barrier on the background checkpoint writer (no-op when sync).
        Raises CheckpointError if a background write failed — an async
        save failure must never be silent (doc/performance.md)."""
        if self._async_ckpt is not None:
            self._async_ckpt.drain()

    # ---------------------------------------------------------- checkgrad

    def check_gradient(self, epsilon: float = 1e-4, max_entries: int = 10) -> bool:
        """--job=checkgrad (ref: Trainer.cpp:313-387)."""
        provider = self._provider(for_test=False) or self._provider(for_test=True)
        assert provider is not None, "checkgrad needs data"
        batch = next(iter(provider.batches()))
        report = self.gm.check_gradient(self.params, batch, epsilon, max_entries)
        ok = True
        for name, diff in sorted(report.items()):
            status = "OK" if diff < 5e-2 else "FAIL"
            if diff >= 5e-2:
                ok = False
            logger.info("checkgrad %-40s max_rel_diff=%.3e %s", name, diff, status)
        return ok


    def _trim_outputs(self, outputs, n: int):
        """Slice every output's batch dim back to the true sample count
        (inverse of _global_batches' inference padding). Multi-process
        callers must gather to host first (host values slice freely)."""
        first = next(
            (
                v
                for v in jax.tree_util.tree_leaves(outputs)
                if hasattr(v, "shape") and v.shape
            ),
            None,
        )
        if first is None or first.shape[0] == n:
            return outputs
        return jax.tree_util.tree_map(lambda x: x[:n], outputs)


def _pad_batch(batch: Dict[str, Argument], m: int) -> Dict[str, Argument]:
    """Pad every leaf's batch dim to m rows by repeating the last sample
    (host-side; all processes see the same padded batch)."""

    def pad(x):
        x = np.asarray(x)
        if x.shape[0] >= m:
            return x
        reps = np.repeat(x[-1:], m - x.shape[0], axis=0)
        return np.concatenate([x, reps], axis=0)

    return jax.tree_util.tree_map(pad, batch)


def _batch_num_samples(batch: Dict[str, Argument]) -> int:
    for arg in batch.values():
        return arg.batch_size
    return 0
