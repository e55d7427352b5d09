"""`paddle` CLI — train / supervise / test / checkgrad / dump_config /
merge_model / metrics / memory / roofline / compare / serve-report /
version.

Role of the reference's TrainerMain + `paddle` shell dispatcher
(/root/reference/paddle/trainer/TrainerMain.cpp:35-110,
paddle/scripts/submit_local.sh.in:46-69). The pserver subcommand has no TPU
meaning (SPMD replaces it); multi-host launch is `paddle train
--coordinator_address=... --num_processes=N --process_id=k` per host.
`paddle supervise` wraps `paddle train` in the crash-loop-aware
auto-restart supervisor (doc/resilience.md).
"""

from __future__ import annotations

import os
import sys


def main(argv=None) -> int:
    # die quietly when stdout is a closed pipe (`paddle dump_config | head`)
    import signal

    if hasattr(signal, "SIGPIPE"):
        signal.signal(signal.SIGPIPE, signal.SIG_DFL)
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help", "help"):
        print(__doc__)
        print("usage: paddle <train|supervise|test|gen|serve|serve-fleet|"
              "checkgrad|dump_config|merge_model|check-checkpoint|metrics|"
              "memory|roofline|compare|trace|serve-report|serve-status|lint|race|"
              "faults|version> [--flags]")
        return 0
    cmd, rest = argv[0], argv[1:]
    if cmd == "version":
        from paddle_tpu.version import __version__
        import jax

        print(f"paddle_tpu {__version__} (jax {jax.__version__})")
        print(f"devices: {jax.devices()}")
        # the device as jax reports it, with the peaks the roofline and
        # MFU accounting will use for it (null = not in the table, and
        # every utilization number would be omitted)
        import json

        from paddle_tpu.ops.kernel_flops import (peak_gbps, peak_hbm_gb,
                                                 peak_tflops)
        from paddle_tpu.utils.device import device_stamp

        d = device_stamp()
        kind = d["device_kind"]
        print("device: " + json.dumps({
            "platform": d["platform"], "kind": kind,
            "count": d["device_count"],
            "peak_tflops": peak_tflops(kind), "peak_gbps": peak_gbps(kind),
            "peak_hbm_gb": peak_hbm_gb(kind),
        }))
        return 0
    if cmd in ("train", "test", "checkgrad", "gen"):
        return _run_trainer_job(cmd, rest)
    if cmd == "supervise":
        return _supervise(rest)
    if cmd == "dump_config":
        return _dump_config(rest)
    if cmd == "merge_model":
        return _merge_model(rest)
    if cmd in ("check-checkpoint", "check_checkpoint"):
        return _check_checkpoint(rest)
    if cmd == "metrics":
        # telemetry analyzer (doc/observability.md) — jax-free like
        # `supervise`: it must summarize a run dir copied off a pod
        from paddle_tpu.observability.analyze import main as metrics_main

        return metrics_main(rest)
    if cmd == "memory":
        # HBM accounting: per-launch-group static footprint, live
        # peak/headroom, OOM pre-mortem rendering (doc/observability.md
        # "Memory telemetry") — jax-free like `metrics`
        from paddle_tpu.observability.memory import main as memory_main

        return memory_main(rest)
    if cmd == "roofline":
        # per-launch-group cost attribution (doc/performance.md
        # "Roofline methodology") — jax-free like `metrics`
        from paddle_tpu.observability.costs import main as roofline_main

        return roofline_main(rest)
    if cmd == "compare":
        # run/bench diff with a regression verdict — jax-free
        from paddle_tpu.observability.compare import main as compare_main

        return compare_main(rest)

    if cmd == "trace":
        # cross-process request timelines + tail attribution — jax-free
        from paddle_tpu.observability.tracing import main as trace_main

        return trace_main(rest)
    if cmd == "serve":
        # continuous-batching generation server (doc/serving.md):
        # stdin-JSONL requests through the slot-based decode engine,
        # SIGTERM = graceful drain
        from paddle_tpu.serving.frontend import main as serve_main

        return serve_main(rest)
    if cmd in ("serve-fleet", "serve_fleet"):
        # multi-replica serving: a jax-free router supervises
        # --fleet_replicas `paddle serve` children, balances on their
        # health JSON, fails over via journal replay, restarts on
        # budget (doc/serving.md "Serving fleet")
        from paddle_tpu.serving.fleet import main as fleet_main

        return fleet_main(rest)
    if cmd in ("serve-status", "serve_status"):
        # render a `paddle serve --status_path` health snapshot
        # (queue depth, occupancy, last-collect age, shed/error totals,
        # draining flag) — jax-free: the probe side runs anywhere
        from paddle_tpu.serving.resilience import status_main

        return status_main(rest)
    if cmd in ("serve-report", "serve_report"):
        # per-offered-load serving report (request/serve_window records
        # from `bench.py serve`, doc/observability.md) — jax-free
        from paddle_tpu.observability.serving import main as serve_report_main

        return serve_report_main(rest)
    if cmd == "lint":
        # static analysis over the package's own invariants
        # (doc/static_analysis.md) — jax-free: this is the CI gate and
        # runs before the accelerator runtime exists
        from paddle_tpu.analysis.cli import main as lint_main

        return lint_main(rest)
    if cmd == "race":
        # dynamic analysis: deterministic schedule explorer over the
        # daemon-thread paths (doc/static_analysis.md "Dynamic
        # analysis") — jax-free like lint, and gated the same way
        from paddle_tpu.analysis.dynamic.cli import main as race_main

        return race_main(rest)
    if cmd == "faults":
        return _faults()
    print(f"unknown command {cmd!r}", file=sys.stderr)
    return 2


def _faults() -> int:
    """`paddle faults` — list the fault-injection sites with their
    one-line descriptions, so `--fault_spec` chaos specs are written
    from documentation instead of guessed from source. jax-free."""
    from paddle_tpu.resilience.faultinject import SITE_DOCS

    print("fault-injection sites (--fault_spec='site=action[:arg][@trigger]"
          "[;...]', actions: raise | oserror | exit[:code] | sleep[:secs];"
          " see doc/resilience.md):")
    width = max(len(s) for s in SITE_DOCS)
    for site, desc in SITE_DOCS.items():
        print(f"  {site:<{width}}  {desc}")
    return 0


def _setup(rest, device_job=True):
    """Parse flags + config. ``device_job`` = the command will run on a
    device (train/test/checkgrad/gen); dump_config and merge_model stay
    jax-free."""
    from paddle_tpu.utils.flags import FLAGS

    leftover = FLAGS.parse(rest)
    if leftover:
        print(f"warning: unrecognized flags {leftover}", file=sys.stderr)
    if FLAGS.fault_spec:
        # chaos drills: deterministic fault injection at the named sites
        from paddle_tpu.resilience import faultinject

        faultinject.configure(FLAGS.fault_spec, FLAGS.fault_seed)
    # before ANYTHING imports jax (it reads JAX_PLATFORMS once at import):
    # --use_tpu is a requirement, not a hint — utils/device.py
    from paddle_tpu.utils.device import select_platform

    select_platform(FLAGS.use_tpu)
    if device_job:
        # before any jax compile: `paddle train` has a persistent
        # compilation cache by default, at the one place
        # compile_log.resolve_cache_dir names — warm restarts skip the XLA
        # backend compile and the compile telemetry records the hits
        from paddle_tpu.observability.compile_log import enable_compile_cache

        enable_compile_cache(FLAGS.compile_cache_dir)
    if FLAGS.coordinator_address:
        import jax

        jax.distributed.initialize(
            coordinator_address=FLAGS.coordinator_address,
            num_processes=FLAGS.num_processes,
            process_id=FLAGS.process_id,
        )
    from paddle_tpu.config import parse_config

    if not FLAGS.config:
        print("error: --config is required", file=sys.stderr)
        raise SystemExit(2)
    if not os.path.exists(FLAGS.config):
        print(f"error: config file {FLAGS.config!r} not found", file=sys.stderr)
        raise SystemExit(2)
    if not device_job:
        return FLAGS, parse_config(FLAGS.config, FLAGS.config_args)
    # set-up by phase: the DSL file run to the proto is a span. On the
    # device-job path only — a span's first scope imports jax, which
    # dump_config and merge_model never do
    from paddle_tpu.utils.stats import stat_timer

    with stat_timer("config/parse"):
        config = parse_config(FLAGS.config, FLAGS.config_args)
    return FLAGS, config


def _run_trainer_job(cmd, rest) -> int:
    flags, config = _setup(rest)
    from paddle_tpu.trainer import Trainer

    trainer = Trainer(config, flags)
    if cmd == "train":
        try:
            trainer.train()
        except Exception as e:
            from paddle_tpu.observability.memory import OOM_REPORT, is_oom_error

            if is_oom_error(e):
                # the trainer already wrote oom_report.json and flushed
                # the kind=oom record; the distinct code tells
                # supervisors the death is classified (and budgeted —
                # an OOM loop is poison, not scheduling)
                from paddle_tpu.resilience import EXIT_OOM

                print(f"OOM: {e} (forensics: {OOM_REPORT} in the run "
                      "dir; `paddle memory <run_dir>` renders them)",
                      file=sys.stderr)
                return EXIT_OOM
            raise
        if getattr(trainer, "preempted", False):
            # distinct exit code: supervisors/launchers restart a
            # preempted run without consuming restart budget
            from paddle_tpu.resilience import EXIT_PREEMPTED

            return EXIT_PREEMPTED
        return 0
    if cmd == "test":
        if flags.test_pass >= 0:
            _test_saved_passes(trainer, flags)
        else:
            trainer.test()
        return 0
    if cmd == "gen":
        trainer.generate()
        return 0
    ok = trainer.check_gradient()
    return 0 if ok else 1


def _supervise(rest) -> int:
    """`paddle supervise <train flags>` — run `paddle train` (or, with
    `--supervise_job=serve`, `paddle serve`) as a supervised child:
    restart with backoff on nonzero exit (bounded by --restart_budget;
    train children resume via `--init_model_path=auto`, serve children
    re-offer their `--serve_journal_path` queue themselves), stop with
    a JSON crash report on a crash loop, forward SIGTERM so preemption
    still checkpoints/drains. `--dry_run` prints the child command and
    policy.

    The supervisor itself never initializes jax (a dead child must be
    restartable even when the accelerator runtime is what killed it), so
    this parses flags without `_setup` and forwards `rest` verbatim —
    the child re-parses the same flags and validates --config."""
    from paddle_tpu.utils.flags import FLAGS

    leftover = FLAGS.parse(list(rest))
    if leftover:
        print(f"warning: unrecognized flags {leftover}", file=sys.stderr)
    if FLAGS.supervise_job not in ("train", "serve"):
        print(f"error: --supervise_job={FLAGS.supervise_job!r} (expected "
              "train or serve)", file=sys.stderr)
        return 2
    from paddle_tpu.resilience.supervisor import Supervisor

    return Supervisor(rest, FLAGS).run()


def _test_saved_passes(trainer, flags) -> None:
    """Evaluate saved checkpoints pass by pass (ref: Tester; --test_pass
    with --test_wait polls for passes still being written by a concurrent
    trainer)."""
    import time

    from paddle_tpu.trainer import checkpoint as ckpt

    from paddle_tpu.utils.logging import logger

    save_dir = flags.save_dir or trainer.config.save_dir
    pass_id = flags.test_pass
    while pass_id < flags.num_passes:
        path = os.path.join(save_dir, ckpt.PASS_FMT % pass_id)
        # a checkpoint is complete once meta.json exists (written last by
        # save_checkpoint) — guards against racing a concurrent trainer
        if not os.path.exists(os.path.join(path, "meta.json")):
            newest = ckpt.latest_pass(save_dir)
            if newest is not None and newest > pass_id:
                # rotated away by rolling deletion: skip forward
                logger.warning(
                    "pass %d checkpoint rotated away; skipping to %d",
                    pass_id, newest,
                )
                pass_id = newest
                continue
            if flags.test_wait:
                time.sleep(5)
                continue
            break
        # fallback=False: this is a READ-side job, possibly polling a live
        # trainer's save_dir — it must never quarantine (mutate) that dir
        # or silently report pass-N metrics computed from pass-(N-1) params
        trainer.params, opt_state, _ = ckpt.load_checkpoint(
            path, trainer.opt_state, expected_params=trainer.params,
            sharding_for=trainer.ckpt_sharding_for(), fallback=False,
        )
        if opt_state is not None:
            trainer.opt_state = opt_state
        trainer.test(pass_id=pass_id)
        pass_id += 1


def _dump_config(rest) -> int:
    flags, config = _setup(rest, device_job=False)
    print(config.to_json(indent=2))
    return 0


def _check_checkpoint(rest) -> int:
    """`paddle check-checkpoint <dir>` — offline checkpoint verification.

    <dir> is one pass directory, or a save_dir whose pass-NNNNN children
    are each verified. Each dir gets BOTH checks: the byte-level manifest
    verify (CRC/size of every manifested file) and the sharded-structure
    verify (every shard record in each merged index resolves to its file
    and key, coverage is exact — problems name the owning host). In
    save-dir mode, uncommitted sharded saves (`pass-N.tmp` left by a
    crashed run — the pass never reached its commit agreement) are
    reported as PARTIAL. Exit 0 = everything restorable and no partial
    passes, 1 = problems. Never mutates anything (quarantine is
    load_checkpoint's job)."""
    from paddle_tpu.resilience.manifest import read_manifest
    from paddle_tpu.trainer import checkpoint as ckpt

    targets = [a for a in rest if not a.startswith("-")]
    if len(targets) != 1:
        print("usage: paddle check-checkpoint <pass-dir | save-dir>", file=sys.stderr)
        return 2
    root = targets[0]
    if not os.path.isdir(root):
        print(f"error: {root!r} is not a directory", file=sys.stderr)
        return 2
    if ckpt.has_params_tree(root):
        dirs, partials = [root], []
    else:
        dirs = sorted(
            os.path.join(root, d)
            for d in os.listdir(root)
            if ckpt._is_pass_dir_name(d)
        )
        partials = ckpt.partial_pass_report(root)
        if not dirs and not partials:
            print(f"error: no pass dirs (or params tree) under {root!r}", file=sys.stderr)
            return 2
    bad = 0
    for d in dirs:
        problems = ckpt.verify_checkpoint(d) + ckpt.verify_sharded_shards(d)
        manifest = read_manifest(d)
        # row-coverage holes in a committed dir are PARTIAL, not
        # CORRUPT: the bytes that exist are sound, but a row-sharded
        # table has a gap/overlap (a lost host's rows) — the messages
        # name the missing interval and the responsible host(s)
        row_probs = [p for p in problems if "row coverage:" in p
                     or "rows [" in p]
        if problems and len(row_probs) == len(problems):
            bad += 1
            print(f"PARTIAL  {d} (row-sharded coverage holes — not restorable)")
            for p in problems:
                print(f"         - {p}")
        elif problems:
            bad += 1
            print(f"CORRUPT  {d}")
            for p in problems:
                print(f"         - {p}")
        elif manifest is None:
            print(f"OK?      {d} (no MANIFEST.json — pre-resilience save, contents unverified)")
        else:
            print(f"OK       {d} ({len(manifest.get('files', {}))} files verified)")
    if not ckpt.has_params_tree(root):
        for q in sorted(
            d for d in os.listdir(root) if ckpt.CORRUPT_SUFFIX in d
        ):
            print(f"QUARANTINED  {os.path.join(root, q)} (previously failed restore)")
        for tmp, n_manifests in partials:
            bad += 1
            print(
                f"PARTIAL  {tmp} ({n_manifests} per-host partial manifest(s) "
                "— the save never reached its commit agreement; not "
                "restorable)"
            )
            # name the exact row intervals a torn ROW-SHARDED pass is
            # missing (and which hosts did land their partial index)
            from paddle_tpu.sparse import ckpt as sparse_ckpt

            for hole in sparse_ckpt.partial_row_holes(tmp):
                print(f"         - {hole}")
    return 1 if bad else 0


def _merge_model(rest) -> int:
    flags, config = _setup(rest, device_job=False)
    from paddle_tpu.trainer import checkpoint
    from paddle_tpu.trainer.checkpoint import latest_pass

    save_dir = flags.save_dir or config.save_dir
    pass_id = latest_pass(save_dir)
    assert pass_id is not None, f"no checkpoints under {save_dir}"
    out = os.path.join(save_dir, "merged_model.npz")
    checkpoint.merge_model(save_dir, pass_id, config.to_json(), out)
    print(f"merged model written to {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
