"""Command-line driver — the ``caffe train --solver=...`` counterpart.

The reference is launched as ``caffe train --solver=usage/solver.prototxt``
(SURVEY.md §3.1) under mpirun.  Here the same entrypoint is

    python -m npairloss_tpu train --solver usage/solver.prototxt

which parses the solver + net prototxts through the config front-end,
builds the embedding model and identity-balanced data iterators, and runs
the Solver loop on whatever accelerator JAX sees — multi-chip via
``--mesh`` (all devices by default) with the negative pool all-gathered
across the mesh in-graph.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
from typing import Optional

log = logging.getLogger("npairloss_tpu.cli")

# The --precision vocabulary, hardcoded rather than imported: argparse
# construction must stay jax-free (the jax-free entry points —
# staticcheck, watch, timeline — share this parser, and a jax-free
# parent like chip_smoke.py must be able to hold the chip for its
# children).  Pinned == models.precision.available_policies() by
# tests/test_precision_policy.py, so drift is a test failure.
_PRECISION_CHOICES = ("bf16", "fp32_parity", "mxu")

# The staticcheck pass vocabulary, hardcoded for the same reason
# (analysis itself is stdlib-only, but the parser stays literal).
# Pinned == analysis.runner.PASS_NAMES by tests/test_staticcheck.py.
_STATICCHECK_PASSES = ("purity", "scopes", "locks", "contracts",
                       "vocab", "markers")

# The --probe-impl vocabulary, hardcoded for the same jax-free-parser
# reason.  Pinned == ops.pallas_ivf.PROBE_IMPLS by the staticcheck
# vocab pass AND tests/test_pallas_ivf.py, so drift is a lint failure.
_PROBE_IMPL_CHOICES = ("scan", "fused", "auto")


def _identity_batch_geometry(d):
    """(identities, images-per-identity) per batch from a MultibatchData
    layer cfg; the flagship 60x2 geometry (def.prototxt:25-27) when the
    layer is absent."""
    if d is None:
        return 60, 2
    ids = d.identity_num_per_batch or max(2, (d.batch_size or 8) // 2)
    imgs = d.img_num_per_identity or 2
    return ids, imgs


def _build_data(net_cfg, phase: str, input_shape, seed: int = 0,
                synthetic: bool = False, native: str = "auto"):
    """Batches for a phase: the real MultibatchData pipeline from the
    net's source list file, or synthetic identity-balanced clusters when
    ``--synthetic`` was passed explicitly.

    A missing/unreadable source is a hard error unless --synthetic: a
    typo'd path must never silently "train" on random clusters.
    """
    d = net_cfg.data.get(phase)
    if d is None:
        return None, None
    if not synthetic:
        if not d.source:
            raise SystemExit(
                f"{phase} data layer has no `source` list file; pass "
                "--synthetic to train on synthetic identity clusters"
            )
        if not os.path.exists(d.source):
            raise SystemExit(
                f"{phase} data source {d.source!r} does not exist; fix the "
                "net prototxt or pass --synthetic for synthetic data"
            )
        from npairloss_tpu.data import multibatch_loader

        return (
            multibatch_loader(d, net_cfg.transformer, seed=seed,
                              native=native),
            d,
        )
    from npairloss_tpu.data import synthetic_identity_batches

    ids, imgs = _identity_batch_geometry(d)
    return (
        synthetic_identity_batches(
            ids * 4, ids, imgs, input_shape, seed=seed
        ),
        d,
    )


def _pos_topk_arg(v: str):
    """argparse type for --pos-topk: 'auto' or a non-negative int."""
    if v == "auto":
        return "auto"
    try:
        k = int(v)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected 'auto' or a non-negative integer, got {v!r}")
    if k < 0:
        raise argparse.ArgumentTypeError(
            f"buffer slots must be >= 0, got {k}")
    return k


def _build_solver(args):
    """Shared setup for train/test/extract: parse the solver + net
    prototxts, build the model and (optional) mesh, restore a snapshot.
    Returns (solver, net_cfg, input_shape) or an int error code."""
    import jax

    from npairloss_tpu.config import load_net, load_solver
    from npairloss_tpu.models import get_model
    from npairloss_tpu.train import Solver, SolverConfig

    if getattr(args, "solver", None):
        solver_cfg, net_path = load_solver(args.solver)
    else:
        # ``time`` needs only a net, like ``caffe time -model X``; solver
        # hyperparameters are irrelevant to a timing run.
        solver_cfg, net_path = SolverConfig(), None
    if args.net:
        net_path = args.net
    elif net_path and not os.path.isabs(net_path):
        # Caffe resolves the net path relative to the CWD; fall back to
        # solver-relative when that misses (the shipped solver points at
        # a machine-specific ./conf_same_veri/ path).
        if not os.path.exists(net_path):
            cand = os.path.join(os.path.dirname(args.solver), net_path)
            net_path = cand if os.path.exists(cand) else net_path
    if not net_path or not os.path.exists(net_path):
        log.error("net prototxt not found (tried %r); pass --net", net_path)
        return 2
    net_cfg = load_net(net_path)

    if getattr(args, "max_iter", None) is not None:
        import dataclasses

        solver_cfg = dataclasses.replace(solver_cfg, max_iter=args.max_iter)
    if getattr(args, "snapshot_prefix", None):
        import dataclasses

        solver_cfg = dataclasses.replace(
            solver_cfg, snapshot_prefix=args.snapshot_prefix
        )
    if getattr(args, "snapshot_keep", None) is not None:
        import dataclasses

        solver_cfg = dataclasses.replace(
            solver_cfg, snapshot_max_keep=args.snapshot_keep
        )
    if getattr(args, "pipeline", False):
        import dataclasses

        solver_cfg = dataclasses.replace(
            solver_cfg,
            pipeline=True,
            pipeline_depth=getattr(args, "pipeline_depth", 2) or 2,
            pipeline_window=getattr(args, "pipeline_window", 0) or 0,
        )
    crop = 0
    # Shape from the TRAIN layer, else the TEST layer (a net may define
    # only one; test/extract against a TEST-only net must not default).
    for phase in ("TRAIN", "TEST"):
        d = net_cfg.data.get(phase)
        if d is not None and d.transform.crop_size:
            crop = d.transform.crop_size
            break
    side = crop or 224
    input_shape = (side, side, 3)

    loss_cfg = net_cfg.loss.loss if net_cfg.loss else None
    if loss_cfg is None:
        from npairloss_tpu.ops.npair_loss import NPairLossConfig

        loss_cfg = NPairLossConfig()

    mesh = None
    n_dev = len(jax.devices())
    engine = getattr(args, "engine", None)
    want = args.mesh if args.mesh is not None else (n_dev if n_dev > 1 else 1)
    if engine == "blockwise" and args.mesh is None:
        # The Pallas blockwise engine is the single-device streaming
        # path; don't auto-build a mesh around it.  An EXPLICIT --mesh
        # still reaches the Solver's blockwise+mesh contradiction error.
        want = 1
    mp = int(getattr(args, "mp", 1) or 1)
    if want > 1 or engine == "ring" or mp > 1:
        # Ring streams over a mesh axis; a 1-device mesh is valid, so
        # honor --engine ring even single-device.
        # --mp > 1 folds the same devices into a 2-D dp x mp mesh for
        # partition rules that shard parameters (docs/DISTRIBUTED.md).
        from npairloss_tpu.parallel import build_mesh

        mesh = build_mesh(jax.devices()[:max(want, 1)], mp=mp)
    elif engine == "auto":
        # Nothing to exchange on a single shard: auto degrades to the
        # default engine without wrapping a 1-device shard_map mesh
        # around the step.
        engine = None

    partition_rules = None
    if getattr(args, "partition_rules", None):
        from npairloss_tpu.parallel import load_partition_rules
        from npairloss_tpu.parallel.partition import PartitionRuleError

        try:
            partition_rules = load_partition_rules(args.partition_rules)
        except (OSError, ValueError, PartitionRuleError) as e:
            log.error("--partition-rules %s: %s", args.partition_rules, e)
            return 2
        if mesh is None:
            # The module's loud-by-design contract extends to the CLI:
            # a sharding table on a mesh-less run would silently never
            # apply — exactly the no-op shape the table exists to kill.
            log.error("--partition-rules given but no mesh was built "
                      "(single device, no --mesh/--mp): the table "
                      "would silently not apply")
            return 2

    model_name = args.model or _model_for_net(net_cfg)
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    model_kw = {}
    if getattr(args, "remat", False):
        model_kw["remat"] = True  # GoogLeNet trunks; others raise loudly
    if getattr(args, "caffe_pad", False):
        model_kw["caffe_pad"] = True  # GoogLeNet trunks
    precision = getattr(args, "precision", None)
    if precision:
        # Declarative mixed-precision policy (models.precision):
        # resolves the trunk's dtypes AND the loss engines' gemm
        # precision (below) from one named recipe; --bf16 is the
        # legacy spelling of what --precision bf16 now names.
        model = get_model(model_name, policy=precision, **model_kw)
    else:
        model = get_model(model_name, dtype=dtype, **model_kw)

    engine_plan = None
    if mesh is not None and engine != "blockwise":
        # DCN-aware engine selection (parallel.plan): consult the
        # roofline interconnect peaks + the mesh's host topology;
        # --engine auto takes the plan's choice, an explicit engine is
        # honored but the plan (with what auto would have said) is
        # still stamped into the run manifest as provenance.
        from npairloss_tpu.parallel import plan_for_mesh

        d_any = net_cfg.data.get("TRAIN") or net_cfg.data.get("TEST")
        ids, imgs = _identity_batch_geometry(d_any)
        emb_dim = int(getattr(model, "embedding_dim", 0) or 512)
        from npairloss_tpu.obs.fleet.stamp import resolved_process

        engine_plan = plan_for_mesh(
            mesh, ids * imgs, emb_dim,
            requested=engine if engine else "dense",
            process_count=resolved_process()[1],
        )
        if engine == "auto":
            engine = engine_plan.engine
            log.info("engine auto -> %s over %s (%s)",
                     engine, engine_plan.link, engine_plan.reason)

    sim_cache = getattr(args, "sim_cache", None)
    pos_topk = getattr(args, "pos_topk", None)
    solver = Solver(
        model, loss_cfg, solver_cfg, mesh=mesh, input_shape=input_shape,
        engine=engine,
        partition_rules=partition_rules,
        sim_cache={"auto": None, "on": True, "off": False}[sim_cache or "auto"],
        pos_topk=None if pos_topk in (None, "auto") else int(pos_topk),
        matmul_precision=getattr(args, "matmul_precision", None),
        precision=precision or None,
        param_mults=net_cfg.param_mults,
        loss_weight=(net_cfg.loss.loss_weights[0]
                     if net_cfg.loss and net_cfg.loss.loss_weights
                     else 1.0),
    )
    solver.engine_plan = engine_plan
    if getattr(args, "resume", None):
        if args.resume == "auto":
            # Auto-resume (docs/RESILIENCE.md): newest manifest-valid
            # snapshot under snapshot_prefix, torn/corrupt ones skipped
            # with a logged reason; none found = fresh start (the
            # supervisor-relaunch contract — first launch and relaunch
            # run the same command line).
            restored = solver.restore_auto()
            if restored:
                log.info("auto-resume: %s (iteration %d)",
                         restored, solver.iteration)
        else:
            solver.restore_snapshot(args.resume)
    elif getattr(args, "weights", None):
        _load_weights_into(solver, args.weights)
    return solver, net_cfg, input_shape


def cmd_train(args) -> int:
    if getattr(args, "metrics_port", None) and \
            not getattr(args, "live_obs", False):
        # The exporter serves the live registry; without --live-obs
        # there is none — refuse up front rather than train for hours
        # while the scraper gets connection-refused.
        log.error("--metrics-port needs --live-obs (there is no "
                  "metric registry to export without it)")
        return 2
    if getattr(args, "remediation_config", None):
        # Parse NOW: a typo'd policy table must not cost a solver
        # build + restore first (it re-loads cheaply at wiring time).
        from npairloss_tpu.resilience.remediate import load_policies

        try:
            load_policies(args.remediation_config)
        except (OSError, ValueError) as e:
            log.error("--remediation-config %s: %s",
                      args.remediation_config, e)
            return 2
    # The MPI_COMM_WORLD replacement: must run before the first backend
    # query (exactly as MPI_Init precedes any communicator use).
    from npairloss_tpu.parallel import initialize_distributed

    initialize_distributed(
        args.coordinator, args.num_processes, args.process_id
    )

    if getattr(args, "caffe_solverstate", None):
        # Checked BEFORE _build_solver, which eagerly restores --resume.
        if getattr(args, "resume", None):
            log.error("--caffe-solverstate conflicts with --resume "
                      "(pick the Caffe snapshot or the Orbax one)")
            return 2
        if not getattr(args, "weights", None):
            # `caffe train --snapshot` restores the paired .caffemodel
            # automatically; here the weights arrive separately — a
            # solverstate on top of RANDOM init would be a silently
            # corrupt resume (50k-step momentum, fresh weights).
            log.error(
                "--caffe-solverstate needs --weights (the paired "
                ".caffemodel, converted via import-caffemodel) — "
                "resuming momentum over random-init weights would be "
                "a corrupt trajectory")
            return 2

    built = _build_solver(args)
    if isinstance(built, int):
        return built
    solver, net_cfg, input_shape = built

    if net_cfg.param_mults_conflict:
        # Parse records (rather than raises) conflicting per-layer
        # param recipes so inference-only commands can still load the
        # net; training would silently apply NO multipliers, so it is
        # the one path that must refuse.
        log.error("%s", net_cfg.param_mults_conflict)
        return 2

    if getattr(args, "caffe_solverstate", None):
        # The `caffe train --snapshot X.solverstate` semantics: resume
        # the optimizer (momentum + iteration) from a Caffe snapshot;
        # weights come from the paired .caffemodel via --weights.
        try:
            it = solver.load_caffe_solverstate(
                args.caffe_solverstate,
                args.model or _model_for_net(net_cfg),
            )
        except NotImplementedError as e:
            log.error("%s", e)
            return 2
        log.info("resumed optimizer from %s at iteration %d",
                 args.caffe_solverstate, it)

    if getattr(args, "dump_partitions", False):
        # Preflight visibility (docs/DISTRIBUTED.md): the resolved
        # rule -> PartitionSpec table per state leaf, with per-rule
        # match counts — a silent no-op rule (0 matches) is visible
        # BEFORE a multi-hour run.  Pair with --max_iter 0 for a
        # check-only invocation.  Mesh-less runs have no placement to
        # resolve, so the flag demands one.
        if solver.mesh is None:
            log.error("--dump-partitions needs a mesh "
                      "(--mesh/--mp): single-device runs have no "
                      "placement to resolve")
            return 2
        from npairloss_tpu.parallel import render_partition_table

        print(render_partition_table(solver.partition_table()),
              flush=True)

    train_iter, _ = _build_data(
        net_cfg, "TRAIN", input_shape, seed=0, synthetic=args.synthetic,
        native=args.native,
    )
    test_iter, _ = _build_data(
        net_cfg, "TEST", input_shape, seed=1, synthetic=args.synthetic,
        native=args.native,
    )
    if train_iter is None:
        log.error(
            "net %s has no TRAIN MultibatchData layer",
            args.net or args.solver,
        )
        return 2

    import jax as _jax

    if _jax.process_count() > 1:
        # Multi-controller data model (docs/DISTRIBUTED.md): every
        # controller builds the same deterministic loader; each takes
        # its process-disjoint row shard of every global batch, and
        # Solver._put_batch reassembles them in process order into the
        # pod-global array — the mpirun per-rank MultibatchData shape,
        # with global batch = sum of the local batches.
        from npairloss_tpu.data import shard_batches

        train_iter = shard_batches(
            train_iter, _jax.process_index(), _jax.process_count())
        if test_iter is not None:
            test_iter = shard_batches(
                test_iter, _jax.process_index(), _jax.process_count())

    # Configure logging only when the embedder has not.  basicConfig is
    # already a no-op when the ROOT logger has handlers; the extra check
    # covers embedders that configured the package logger directly
    # (handlers beyond our NullHandler) without touching root — adding a
    # root handler there would double their output.
    _pkg_handlers = [
        h for h in logging.getLogger("npairloss_tpu").handlers
        if not isinstance(h, logging.NullHandler)
    ]
    if not logging.getLogger().handlers and not _pkg_handlers:
        logging.basicConfig(level=logging.INFO, format="%(message)s")

    if getattr(args, "debug_checks", False):
        from npairloss_tpu.utils.debug import enable_debug_checks

        enable_debug_checks(True)
    if getattr(args, "health_metrics", False) or \
            getattr(args, "mining_health", False):
        from npairloss_tpu.obs import HealthConfig

        # --mining-health implies the health rows it extends: the
        # AP/AN margin + saturation stats ride the same loss aux.
        solver.health = HealthConfig(
            mining_health=bool(getattr(args, "mining_health", False)))
    if getattr(args, "perf_metrics", False):
        # Continuous phase="perf" rows (ms_per_step / emb_per_sec /
        # MFU) at display cadence — docs/OBSERVABILITY.md §Perf.
        solver.perf_metrics = True

    from npairloss_tpu.resilience import (
        EXIT_PREEMPTED,
        DivergenceConfig,
        DivergenceError,
        PreemptionSignal,
        TrainingPreempted,
    )

    if getattr(args, "divergence_patience", 0):
        solver.divergence = DivergenceConfig(
            patience=args.divergence_patience,
            action=args.divergence_action,
            lr_scale=args.divergence_lr_scale,
            max_rollbacks=args.divergence_max_rollbacks,
        )

    # Graceful preemption (docs/RESILIENCE.md): SIGTERM/SIGINT finish
    # the in-flight step, commit an emergency snapshot, flush telemetry,
    # and exit EXIT_PREEMPTED so a supervisor relaunches with
    # ``--resume auto``.  install() no-ops off the main thread.
    preempt = None
    if not getattr(args, "no_preempt_handler", False):
        preempt = PreemptionSignal().install()
        solver.preempt = preempt

    telemetry = None
    live = None
    exporter = None
    tel_dir = getattr(args, "telemetry_dir", None)
    trace_dir = getattr(args, "trace_dir", None)
    record_fn, log_file = None, None
    try:
        if getattr(args, "live_obs", False):
            # Live observatory (docs/OBSERVABILITY.md §Live): watchdog
            # SLOs over the run's own telemetry rows, alerts.jsonl in
            # the run dir, optional /metrics on --metrics-port.
            if not tel_dir:
                log.error("--live-obs needs --telemetry-dir (the "
                          "registry is fed by the run's metric rows)")
                return 2
            from npairloss_tpu.obs.live import (
                LiveObservatory,
                default_watchdogs,
                load_slo_config,
            )

            if getattr(args, "slo_config", None):
                specs = load_slo_config(args.slo_config)
            else:
                specs = default_watchdogs("train")
            live = LiveObservatory(specs, out_dir=tel_dir)

            def _snapshot_age_probe():
                # Newest committed snapshot's manifest age — state the
                # process already has on disk, polled per tick.
                from npairloss_tpu.resilience.snapshot import (
                    list_snapshots,
                )
                from npairloss_tpu.train import snapshot_info

                snaps = list_snapshots(solver.cfg.snapshot_prefix)
                if not snaps:
                    return
                created = snapshot_info(snaps[-1][1])["created"]
                if created is not None:
                    import time as _time

                    live.registry.set("train_snapshot_age_s",
                                      max(_time.time() - created, 0.0))

            live.add_probe(_snapshot_age_probe)
            if getattr(args, "remediate_dry_run", False):
                args.remediate = True  # a dry-run IS a remediation run
            if getattr(args, "remediate", False):
                # Alert→actuation for training (docs/RESILIENCE.md
                # §Remediation): a health-signal alert (embedding
                # collapse) requests a rollback the train loop executes
                # at its next safe point — resilience/guard.py's
                # divergence recovery generalized beyond non-finite
                # streaks.
                from npairloss_tpu.resilience.guard import (
                    RollbackRequest,
                )
                from npairloss_tpu.resilience.remediate import (
                    RemediationEngine,
                    default_policies,
                    load_policies,
                )

                def _rollback_action(alert):
                    solver.request_rollback(RollbackRequest(
                        reason=(f"{alert.get('slo')} alert "
                                f"{alert.get('alert_id')}"),
                        before_wall_time=alert.get("fired_at"),
                    ))
                    return {"requested": True}

                policies = (
                    load_policies(args.remediation_config)
                    if getattr(args, "remediation_config", None)
                    else default_policies("train"))
                try:
                    remediation = RemediationEngine(
                        policies,
                        {"trainer_rollback": _rollback_action},
                        log_path=os.path.join(tel_dir,
                                              "remediation.jsonl"),
                        dry_run=getattr(args, "remediate_dry_run",
                                        False),
                    )
                except ValueError as e:
                    # A config naming an action training cannot perform
                    # is a config error, not a crash.
                    log.error("--remediation-config %s: %s",
                              args.remediation_config, e)
                    return 2
                live.set_remediation(remediation)
                log.info(
                    "remediation armed: %s%s",
                    ", ".join(f"{p.name}({p.slo}->{p.action})"
                              for p in policies),
                    " [DRY-RUN]" if remediation.dry_run else "")
        elif getattr(args, "remediate", False) or \
                getattr(args, "remediate_dry_run", False):
            log.error("--remediate needs --live-obs (remediation is "
                      "driven by the alert engine)")
            return 2
        if tel_dir or trace_dir:
            import dataclasses

            import jax

            from npairloss_tpu.obs.fleet import fleet_stamp

            # Fleet stamping (docs/OBSERVABILITY.md §Fleet): automatic
            # for multi-process runs (EVERY rank writes its own
            # telemetry.r<k>.jsonl — the old rank-0 gate threw away
            # exactly the streams straggler analysis needs), forceable
            # with --fleet on a single-host mesh.  Off (the byte-
            # identical legacy layout, rank 0 only) otherwise.
            stamp = fleet_stamp()
            fleet_on = bool(getattr(args, "fleet", False)) or (
                stamp is not None and stamp.process_count > 1
            )
            if fleet_on or jax.process_index() == 0:
                from npairloss_tpu.obs import RunTelemetry

                # --telemetry-dir = the full run directory (manifest +
                # metrics.jsonl + trace.json); --trace-dir alone = span
                # tracing only (trace.json, no metric rows).  argparse
                # makes them mutually exclusive.
                telemetry = RunTelemetry(
                    tel_dir or trace_dir, metrics=bool(tel_dir),
                    fleet=fleet_on,
                    extra_sinks=(live.sink,) if live is not None else (),
                )
                if tel_dir:
                    from npairloss_tpu.parallel import mesh_topology

                    telemetry.write_manifest(
                        config={
                            "solver": dataclasses.asdict(solver.cfg),
                            "loss": dataclasses.asdict(solver.loss_cfg),
                            "model": args.model or _model_for_net(net_cfg),
                            "net": args.net,
                            "engine": solver.engine,
                            "synthetic": bool(args.synthetic),
                            "health_metrics":
                                bool(getattr(args, "health_metrics", False)),
                            # Pod-scale provenance (docs/DISTRIBUTED.md):
                            # WHY this engine (DCN-aware plan) and WHERE
                            # every state leaf lives (rule digest, with
                            # zero-match rules flagged).
                            "engine_plan": (
                                solver.engine_plan.to_dict()
                                if solver.engine_plan is not None else None
                            ),
                            "partition": (
                                solver.partition_summary()
                                if solver.mesh is not None else None
                            ),
                        },
                        mesh=(
                            mesh_topology(solver.mesh, solver.axis)
                            if solver.mesh is not None else None
                        ),
                    )
                solver.telemetry = telemetry

        if getattr(args, "log_json", None):
            import jax

            # Rank-gate: in a multi-process run, N hosts appending to one
            # shared path would duplicate every event N times.
            if jax.process_index() == 0:
                from npairloss_tpu.obs import JsonlSink

                # The obs sink IS this format (append JSONL, line
                # buffered) — one implementation to maintain.  Records
                # pass through verbatim: --log-json predates the
                # run-telemetry envelope and its consumers key on the
                # solver's {"event", "iteration"} fields.
                log_file = JsonlSink(args.log_json)
                record_fn = log_file.log

        if live is not None:
            live.start(period_s=args.slo_tick)
            if getattr(args, "metrics_port", None):
                from npairloss_tpu.obs.live import start_http_exporter

                # Train has no HTTP surface of its own — an opt-in
                # localhost exporter serves /metrics (+ /healthz with
                # SLO status) for scrapers.
                exporter = start_http_exporter(
                    live.registry, args.metrics_port,
                    health_fn=lambda: {"ok": True, **live.health()},
                )

        # max_iter override was already baked into solver.cfg by
        # _build_solver; train() falls back to it — one source of truth.
        preempted = None
        try:
            final = solver.train(
                train_iter,
                test_batches=test_iter,
                log_fn=lambda s: print(s, flush=True),
                record_fn=record_fn,
            )
        except TrainingPreempted as e:
            # The emergency snapshot already landed (Solver.train commits
            # it before raising); exit the supervisor-relaunch code.
            preempted = e
        except DivergenceError as e:
            log.error("%s", e)
            return 1
    finally:
        # Telemetry closes on EVERY exit path so a crashed run still
        # leaves metrics.jsonl/trace.json on disk (the diagnosable-from-
        # artifacts contract, docs/OBSERVABILITY.md).  Both closes are
        # guarded: a disk-full close failure is reported but must
        # neither skip the other close nor mask the train outcome
        # propagating past this finally.
        if preempt is not None:
            preempt.uninstall()
        if exporter is not None:
            try:
                exporter.shutdown()
                exporter.server_close()
            except Exception as e:
                log.error("metrics exporter shutdown failed: %s", e)
        if live is not None:
            try:
                live.stop()  # final tick lands pending alert transitions
            except Exception as e:
                log.error("live-obs stop failed: %s", e)
        if log_file is not None:
            try:
                log_file.close()
            except Exception as e:
                log.error("--log-json close failed: %s", e)
        if telemetry is not None:
            try:
                telemetry.close()
            except Exception as e:
                log.error("telemetry close failed: %s", e)
    if preempted is not None:
        print(json.dumps({
            "preempted": True,
            "iteration": preempted.step,
            "snapshot": preempted.snapshot_path,
            "resume": "--resume auto",
        }))
        return EXIT_PREEMPTED
    print(json.dumps({k: float(v) for k, v in final.items()}))
    return 0


def _model_for_net(net_cfg) -> str:
    name = (net_cfg.name or "").lower().replace(" ", "")
    if "resnet" in name:
        return "resnet50"
    if "vit" in name:
        return "vit_b16"
    if "mlp" in name:
        return "mlp"
    return "googlenet"  # the reference's flagship trunk (def.prototxt:1)


def cmd_test(args) -> int:
    """The ``caffe test`` counterpart: restore a snapshot and run the
    TEST phase (same loss+metrics forward as training — the reference
    has no separate eval path, SURVEY.md §3.4) for ``test_iter`` batches."""
    built = _build_solver(args)
    if isinstance(built, int):
        return built
    solver, net_cfg, input_shape = built
    test_iter, _ = _build_data(
        net_cfg, "TEST", input_shape, seed=1, synthetic=args.synthetic,
        native=args.native,
    )
    if test_iter is None:
        log.error("net has no TEST MultibatchData layer")
        return 2
    iters = (solver.cfg.test_iter if args.iterations is None
             else args.iterations)
    if iters <= 0:
        log.error(
            "nothing to evaluate: %s",
            f"--iterations {iters} requests no batches" if args.iterations
            is not None else "solver test_iter is 0 and --iterations was "
            "not given",
        )
        return 2
    m = solver.evaluate(test_iter, iters)
    print(json.dumps({k: float(v) for k, v in sorted(m.items())}))
    return 0


def cmd_extract(args) -> int:
    """Embedding extraction — the metric-learning deployment product
    (the reference's pool5/L2Normalize feature is what retrieval systems
    consume; Caffe's `extract_features` workflow).  Runs the trunk in
    eval mode over the TEST (or TRAIN) source and writes embeddings +
    labels as .npy."""
    import numpy as np

    built = _build_solver(args)
    if isinstance(built, int):
        return built
    solver, net_cfg, input_shape = built
    phase = args.phase.upper()
    batches, _ = _build_data(
        net_cfg, phase, input_shape, seed=1, synthetic=args.synthetic,
        native=args.native,
    )
    if batches is None:
        log.error("net has no %s MultibatchData layer", phase)
        return 2

    import jax
    import jax.numpy as jnp

    def embed_fn(state, x):
        variables = {"params": state["params"]}
        if state["batch_stats"]:
            variables["batch_stats"] = state["batch_stats"]
        return solver.model.apply(variables, x, train=False)

    n_mesh = (len(solver.mesh.devices.flatten())
              if solver.mesh is not None else 1)
    embed_sharded = None
    if solver.mesh is not None:
        # Split the batch over the mesh like train/test steps do (their
        # sharding comes from in_shardings on the jitted step, not from
        # the device_put — a bare jit would run replicated).  Embedding
        # extraction is per-row, so this is pure data parallelism.
        from jax.sharding import NamedSharding, PartitionSpec as P

        embed_sharded = jax.jit(
            embed_fn,
            in_shardings=(None, NamedSharding(solver.mesh, P(solver.axis))),
        )
    embed_replicated = jax.jit(embed_fn)

    embs, labs = [], []
    for _ in range(args.batches):
        x, lab = next(batches)
        # Non-divisible batches (e.g. TEST batch 30 on a 4-mesh) fall
        # back to replicated execution rather than erroring.
        embed = (embed_sharded
                 if embed_sharded is not None and len(x) % n_mesh == 0
                 else embed_replicated)
        if solver.state is None:
            # Init from the actual batch shape (like Solver.step does):
            # the net's TRAIN and TEST layers may crop differently.
            solver.init(np.asarray(x)[:2])
        embs.append(np.asarray(embed(solver.state, jnp.asarray(x))))
        labs.append(np.asarray(lab))
    emb = np.concatenate(embs, axis=0)
    lab = np.concatenate(labs, axis=0)
    np.save(args.out + ".emb.npy", emb)
    np.save(args.out + ".labels.npy", lab)
    print(json.dumps({
        "embeddings": args.out + ".emb.npy",
        "labels": args.out + ".labels.npy",
        "shape": list(emb.shape),
        "mean_norm": float(np.linalg.norm(emb, axis=1).mean()),
    }))
    return 0


def _load_weights_into(solver, path: str):
    """Load a msgpack weights file into a solver, auto-converting to the
    model's MXU-variant layout when needed (s2d stem / fused 1x1s).

    Accepts the wrapped {"params", "batch_stats"} form written by
    import-caffemodel, or a bare params tree."""
    import flax.serialization

    with open(path, "rb") as f:
        tree = flax.serialization.msgpack_restore(f.read())
    batch_stats = None
    if isinstance(tree, dict) and set(tree) <= {"params", "batch_stats"}:
        params = tree["params"]
        batch_stats = tree.get("batch_stats") or None
    else:
        params = tree
    model = solver.model
    if getattr(model, "stem_s2d", False):
        from npairloss_tpu.models.layers import conv1_kernel_to_s2d
        import numpy as np

        k7 = np.asarray(params["conv1"]["Conv_0"]["kernel"])
        if k7.shape[0] == 7:  # plain-layout file -> s2d layout
            params["conv1"]["Conv_0"]["kernel"] = conv1_kernel_to_s2d(k7)
    if getattr(model, "fuse_1x1", False) and any(
        "b1x1" in v for v in params.values() if isinstance(v, dict)
    ):
        from npairloss_tpu.models import fuse_inception_1x1_params

        params, batch_stats = fuse_inception_1x1_params(params, batch_stats)
    solver.load_params(params, batch_stats)
    log.info("loaded pretrained params from %s", path)


def cmd_import_caffemodel(args) -> int:
    """Migrate a reference user's trained .caffemodel trunk: binary
    NetParameter blobs -> GoogLeNetEmbedding params -> msgpack file
    (consumed by ``train --weights``)."""
    import flax.serialization
    import jax
    import numpy as np

    from npairloss_tpu.config.caffemodel import parse_caffemodel
    from npairloss_tpu.models import get_model
    from npairloss_tpu.models.caffe_import import (
        caffe_layer_map,
        googlenet_params_from_caffemodel,
        resnet50_params_from_caffemodel,
    )

    with open(args.weights, "rb") as f:
        blobs = parse_caffemodel(f.read())
    log.info("caffemodel: %d layers with blobs", len(blobs))
    import jax.numpy as jnp

    model = get_model(args.model, dtype=jnp.float32)
    variables = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 224, 224, 3), jnp.float32),
            train=False,
        )
    )
    zeros = lambda tree: jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, np.float32), tree
    )
    if "resnet" in args.model.lower():
        params, batch_stats = resnet50_params_from_caffemodel(
            blobs, zeros(variables["params"]),
            zeros(variables["batch_stats"]),
        )
        mapped = len(jax.tree_util.tree_leaves(params))
    else:
        params = googlenet_params_from_caffemodel(
            blobs, zeros(variables["params"])
        )
        batch_stats = {}
        mapped = len(caffe_layer_map())
    with open(args.out, "wb") as f:
        f.write(flax.serialization.msgpack_serialize(
            {"params": params, "batch_stats": batch_stats}
        ))
    print(json.dumps({
        "out": args.out,
        "caffemodel_layers": len(blobs),
        "mapped_convs": mapped,
    }))
    return 0


def cmd_export_caffemodel(args) -> int:
    """The reverse migration: a trunk trained here -> .caffemodel bytes
    a Caffe deployment stack can consume."""
    import flax.serialization

    if not args.weights and not args.snapshot:
        log.error("pass --weights (msgpack) or --snapshot (.ckpt dir)")
        return 2
    if getattr(args, "solverstate_out", None):
        # Mirror load_caffe_solverstate's gate, and do it before even
        # restoring the tree: the variant trunks (googlenet_bn/s2d/
        # fused/mxu) have momentum trees the unnamed positional history
        # cannot map onto, and letting them past this point would raise
        # from googlenet_history_from_momentum only AFTER the
        # .caffemodel is written — defeating the validate-before-any-
        # write rule below.
        if args.model.lower() != "googlenet":
            log.error("--solverstate-out supports the plain 'googlenet' "
                      "trunk only (history blob order is pinned by the "
                      "plain-trunk layer map)")
            return 2

    from npairloss_tpu.config.caffemodel import write_caffemodel
    from npairloss_tpu.models.caffe_import import (
        caffemodel_layers_from_googlenet_params,
        caffemodel_layers_from_resnet50_params,
    )

    if args.snapshot:
        # Straight from a training snapshot: restore the raw Orbax tree
        # (params / batch_stats / opt) without needing a Solver.
        import orbax.checkpoint as ocp

        tree = ocp.StandardCheckpointer().restore(
            os.path.abspath(args.snapshot)
        )
    else:
        with open(args.weights, "rb") as f:
            tree = flax.serialization.msgpack_restore(f.read())
    batch_stats = {}
    if isinstance(tree, dict) and "params" in tree:
        params = tree["params"]
        batch_stats = tree.get("batch_stats") or {}
    else:
        params = tree
    # Validate --solverstate-out preconditions BEFORE any file is
    # written: failing halfway would leave a .caffemodel on disk next
    # to an error exit.
    opt = None
    if getattr(args, "solverstate_out", None):
        opt = tree.get("opt") if isinstance(tree, dict) else None
        if not opt:
            log.error("--solverstate-out needs a training snapshot "
                      "(--snapshot) carrying optimizer state; "
                      "--weights files hold parameters only")
            return 2

    if "resnet" in args.model.lower():
        layers = caffemodel_layers_from_resnet50_params(params, batch_stats)
    else:
        layers = caffemodel_layers_from_googlenet_params(params)
    blob = write_caffemodel(layers)
    with open(args.out, "wb") as f:
        f.write(blob)
    rec = {"out": args.out, "layers": len(layers), "bytes": len(blob)}
    if opt is not None:
        # Optimizer-state migration: momentum history + iteration as a
        # .solverstate next to the .caffemodel, so a Caffe stack can
        # `caffe train --snapshot` the run trained here.
        from npairloss_tpu.config.caffemodel import write_solverstate
        from npairloss_tpu.models.caffe_import import (
            googlenet_history_from_momentum,
        )

        if isinstance(opt, dict):
            momentum, step = opt["momentum_buf"], opt["step"]
        else:  # NamedTuple survived serialization
            momentum, step = opt.momentum_buf, opt.step
        ss = write_solverstate(
            int(step), googlenet_history_from_momentum(momentum),
            learned_net=os.path.basename(args.out),
        )
        with open(args.solverstate_out, "wb") as f:
            f.write(ss)
        rec["solverstate_out"] = args.solverstate_out
        rec["solverstate_iter"] = int(step)
    print(json.dumps(rec))
    return 0


def cmd_eval(args) -> int:
    """Full-gallery retrieval evaluation over extracted embeddings — the
    protocol papers report for the reference's datasets (every test
    image queries the whole test set), computed on-device in streamed
    query blocks.  Consumes the ``extract`` subcommand's .npy pair."""
    import numpy as np

    from npairloss_tpu.ops.eval_retrieval import evaluate_embeddings

    prefix = args.prefix
    emb_path = args.emb or prefix + ".emb.npy"
    lab_path = args.labels or prefix + ".labels.npy"
    for p in (emb_path, lab_path):
        if not os.path.exists(p):
            log.error("missing %s (run the extract subcommand first)", p)
            return 2
    emb = np.load(emb_path)
    lab = np.load(lab_path)
    if emb.shape[0] != lab.shape[0]:
        log.error(
            "embeddings/labels row mismatch: %s vs %s",
            emb.shape, lab.shape,
        )
        return 2
    m = evaluate_embeddings(
        emb, lab, ks=tuple(args.ks), query_block=args.query_block
    )
    rec = {
        "gallery_size": int(emb.shape[0]),
        "dim": int(emb.shape[1]),
        "classes": int(np.unique(lab).shape[0]),
        **{k: round(v, 4) for k, v in m.items()},
    }
    if args.nmi:
        from npairloss_tpu.ops.eval_retrieval import clustering_nmi

        rec["nmi"] = round(
            clustering_nmi(emb, lab, iters=args.kmeans_iters), 4
        )
    print(json.dumps(rec))
    return 0


def cmd_index(args) -> int:
    """Build (or inspect) a committed gallery index from the ``extract``
    subcommand's .npy pair — the offline half of the serving path
    (docs/SERVING.md).  ``--kind ivf`` clusters the gallery (shared
    k-means, ops/kmeans.py) and commits the IVF index; ``--add-to``
    appends to an existing index of EITHER kind (an IVF add re-assigns
    the new rows into the existing clusters); commits are atomic
    either way."""
    import numpy as np

    from npairloss_tpu.serve.index import index_info, load_index
    from npairloss_tpu.serve.ivf import IVFIndex

    if args.info:
        print(json.dumps(index_info(args.info)))
        return 0
    prefix = args.prefix
    emb_path = args.emb or prefix + ".emb.npy"
    lab_path = args.labels or prefix + ".labels.npy"
    for p in (emb_path, lab_path):
        if not os.path.exists(p):
            log.error("missing %s (run the extract subcommand first)", p)
            return 2
    emb = np.load(emb_path)
    lab = np.load(lab_path)
    if emb.shape[0] != lab.shape[0]:
        log.error("embeddings/labels row mismatch: %s vs %s",
                  emb.shape, lab.shape)
        return 2
    if args.add_to:
        idx = load_index(args.add_to)
        idx.add(emb, lab, normalize=not args.no_normalize)
    elif args.kind == "ivf":
        idx = IVFIndex.build_ivf(
            emb, lab, normalize=not args.no_normalize,
            clusters=args.clusters, iters=args.kmeans_iters,
            train_size=args.train_sample,
        )
        if args.parity_sample:
            # The recall birth certificate (docs/OBSERVABILITY.md
            # §Quality observatory): offline topk_recall parity per
            # scoring mode, stamped into the commit manifest so the
            # live shadow-recall gauge has a committed baseline.
            from npairloss_tpu.serve.ivf import measure_parity

            idx.parity = measure_parity(
                idx, probes=args.parity_probes,
                sample=args.parity_sample)
            log.info("ivf parity stamped: %s", idx.parity["recall"])
    else:
        from npairloss_tpu.serve.index import GalleryIndex

        idx = GalleryIndex.build(
            emb, lab, normalize=not args.no_normalize
        )
    out = idx.save(args.out or (args.add_to or prefix + ".gidx"))
    summary = {
        "out": out,
        "kind": idx.KIND,
        "rows": idx.size,
        "dim": idx.dim,
        "classes": int(np.unique(idx._host_labels).shape[0]),
    }
    if isinstance(idx, IVFIndex):
        summary["clusters"] = idx.n_clusters
        summary["cap"] = idx.layout.cap
        if idx.parity is not None:
            summary["parity"] = idx.parity
    print(json.dumps(summary))
    return 0


def cmd_serve(args) -> int:
    """The online path: load a committed gallery index (and optionally a
    training snapshot for raw-input queries), warm every padding bucket,
    and answer top-K queries over stdin/JSONL or localhost HTTP until
    EOF or a graceful SIGTERM drain (exit 75) — docs/SERVING.md."""
    import sys as _sys

    import jax

    from npairloss_tpu.resilience import (
        EXIT_PREEMPTED,
        PreemptionSignal,
        failpoints,
    )
    from npairloss_tpu.serve import (
        BatcherConfig,
        EngineConfig,
        GalleryIndex,
        IVFIndex,
        QueryEngine,
        RetrievalServer,
        ServerConfig,
    )
    from npairloss_tpu.ops.pallas_ivf import resolve_probe_impl
    from npairloss_tpu.serve.index import load_index, load_newest

    # Arg-only validations FIRST — a misconfigured invocation must fail
    # in milliseconds, not after the index loads and the buckets warm.
    if getattr(args, "remediate_dry_run", False):
        args.remediate = True  # a dry-run IS a remediation run
    if getattr(args, "watch_snapshots", None) and not args.snapshot:
        log.error("--watch-snapshots needs --snapshot/--model (the "
                  "hot-swap restores new params INTO the served model; "
                  "embedding-only serving can only watch --index-prefix)")
        return 2
    if getattr(args, "remediate", False) and \
            not getattr(args, "live_obs", False):
        log.error("--remediate needs --live-obs (remediation is driven "
                  "by the alert engine)")
        return 2
    if getattr(args, "remediation_config", None):
        # Parse NOW (it re-loads cheaply at wiring time): a typo'd
        # policy table must not cost an index load + warmup first.
        from npairloss_tpu.resilience.remediate import load_policies

        try:
            load_policies(args.remediation_config)
        except (OSError, ValueError) as e:
            log.error("--remediation-config %s: %s",
                      args.remediation_config, e)
            return 2
    tenant_registry = None
    if getattr(args, "tenant_config", None):
        # Parse + validate the tenants manifest NOW (jax-free): a typo'd
        # tenant table must fail before any index loads or bucket warms.
        from npairloss_tpu.serve.tenants import TenantRegistry

        try:
            tenant_registry = TenantRegistry.load(args.tenant_config)
        except (OSError, ValueError) as e:
            log.error("--tenant-config %s: %s", args.tenant_config, e)
            return 2
        if args.snapshot or getattr(args, "watch_snapshots", None):
            log.error("--tenant-config serves embedding queries only "
                      "(per-tenant model snapshots are not a thing yet) "
                      "— drop --snapshot/--watch-snapshots")
            return 2
        if getattr(args, "remediate", False):
            log.error("--tenant-config does not compose with "
                      "--remediate: per-tenant hot-swap is armed "
                      "automatically and per-tenant admission replaces "
                      "load_shed (docs/SERVING.md §Multi-tenant)")
            return 2
    if getattr(args, "wal_dir", None) and not args.index_prefix \
            and tenant_registry is None:
        log.error("--wal-dir needs --index-prefix (ingest checkpoints "
                  "publish under the prefix, and cold restart reloads "
                  "the newest one — docs/RESILIENCE.md §Durability); "
                  "in tenant mode each tenant's index_prefix plays "
                  "that role")
        return 2
    shadow_rate = float(getattr(args, "shadow_rate", 0.0) or 0.0)
    if not (0.0 <= shadow_rate <= 1.0):
        log.error("--shadow-rate must be in [0, 1], got %g", shadow_rate)
        return 2
    if shadow_rate > 0 and not getattr(args, "telemetry_dir", None):
        log.error("--shadow-rate needs --telemetry-dir (the recall "
                  "gauges ride the telemetry rows, and quality.jsonl "
                  "lands there — docs/OBSERVABILITY.md §Quality)")
        return 2
    if getattr(args, "qtrace", False) and \
            not getattr(args, "telemetry_dir", None):
        log.error("--qtrace needs --telemetry-dir (the exemplar "
                  "artifact qtrace.json lands there — "
                  "docs/OBSERVABILITY.md §Query tracing)")
        return 2

    _pkg_handlers = [
        h for h in logging.getLogger("npairloss_tpu").handlers
        if not isinstance(h, logging.NullHandler)
    ]
    if not logging.getLogger().handlers and not _pkg_handlers:
        # Serving answers ride stdout; logs go to stderr so a JSONL
        # consumer never has to parse around them.
        logging.basicConfig(level=logging.INFO, format="%(message)s",
                            stream=_sys.stderr)

    mesh = None
    n_dev = len(jax.devices())
    want = args.mesh if args.mesh is not None else (n_dev if n_dev > 1 else 1)
    if want > 1:
        from npairloss_tpu.parallel import data_parallel_mesh

        mesh = data_parallel_mesh(jax.devices()[:want])

    index = index_path = None
    if args.index_prefix:
        found = load_newest(args.index_prefix, mesh=mesh)
        if found is None:
            log.error("no valid index under prefix %r", args.index_prefix)
            return 2
        index_path, index = found
        log.info("serving index %s", index_path)
    elif args.index:
        index_path = os.path.abspath(args.index)
        index = load_index(args.index, mesh=mesh)
    # Reconcile the committed structure with the requested serving
    # structure (docs/SERVING.md §Approximate index): a flat commit can
    # serve through the IVF probe path (clustered in-memory at startup)
    # and an IVF commit can serve flat (the exact-scan recall oracle) —
    # the committed artifact never dictates the serving posture.  ONE
    # closure, because the hot-swap remediation must apply the same
    # reconciliation to every swapped-in index (a flat commit must not
    # demote an IVF tier at the first swap).
    def _reconcile_index(idx):
        if args.index_kind == "ivf" and not isinstance(idx, IVFIndex):
            log.info("clustering flat index into IVF (%s clusters)...",
                     args.ivf_clusters or "auto")
            return IVFIndex.from_gallery(idx, clusters=args.ivf_clusters)
        if args.index_kind == "flat" and isinstance(idx, IVFIndex):
            log.info("serving ivf commit through the flat exact scan")
            return GalleryIndex.build(
                idx._host_emb, idx._host_labels, ids=idx.ids,
                mesh=mesh, normalize=False)
        return idx

    if index is not None:
        index = _reconcile_index(index)

    # Tenant mode loads one index PER TENANT, each reconciled to its
    # own declared kind (a mixed flat/IVF tier behind one front end).
    tenant_indexes = {}
    if tenant_registry is not None:
        from npairloss_tpu.serve.tenants import reconcile_index_kind

        for spec_t in tenant_registry:
            found = load_newest(spec_t.index_prefix, mesh=mesh)
            if found is None:
                log.error("tenant %r: no valid index under prefix %r",
                          spec_t.tenant_id, spec_t.index_prefix)
                return 2
            tpath, tidx = found
            tidx = reconcile_index_kind(
                tidx, spec_t.index_kind,
                clusters=args.ivf_clusters, mesh=mesh)
            tenant_indexes[spec_t.tenant_id] = (tpath, tidx)
            log.info("tenant %r: serving index %s (%s)",
                     spec_t.tenant_id, tpath, spec_t.index_kind)

    # Durable-ingest arm (docs/RESILIENCE.md §Durability): open the WAL
    # (recovery truncates any torn tail loudly), then replay every
    # record ABOVE the loaded artifact's watermark into the pending
    # buffer — exactly-once: records the snapshot already contains are
    # skipped.  Pending records reach a SERVED index only through
    # checkpoint publication + hot-swap; an in-place add to the live
    # gallery would recompile on the serving path.
    wal = None
    if getattr(args, "wal_dir", None) and tenant_registry is None:
        import numpy as np

        from npairloss_tpu.resilience.wal import (
            WalCorruptionError,
            WriteAheadLog,
        )
        from npairloss_tpu.serve.index import INDEX_SUFFIX
        from npairloss_tpu.serve.server import decode_ingest_payload

        base_watermark = int(getattr(index, "ingest_watermark", 0))
        _ingest = {"base": index_path, "pending": []}

        def _apply_ingest(payload):
            _ingest["pending"].append(
                (int(payload["seq"]), decode_ingest_payload(payload)))

        def _publish_checkpoint(wm: int):
            pending = [p for p in _ingest["pending"] if p[0] <= wm]
            if not pending:
                return None
            base = load_index(_ingest["base"], mesh=mesh)
            emb = np.concatenate([d[0] for _, d in pending])
            labels = np.concatenate([d[1] for _, d in pending])
            ids = np.concatenate([d[2] for _, d in pending])
            base.add(emb, labels, ids=ids)
            base.ingest_watermark = wm
            # 'w' sorts after every digit, so checkpoints always win
            # load_newest over the plain numbered commits they grew
            # from, and among themselves by watermark.
            path = base.save(
                f"{args.index_prefix}w{wm:012d}{INDEX_SUFFIX}")
            _ingest["base"] = path
            _ingest["pending"] = [p for p in _ingest["pending"]
                                  if p[0] > wm]
            log.info("ingest checkpoint: %s (watermark %d, +%d row(s))",
                     path, wm, int(emb.shape[0]))
            return path

        try:
            wal = WriteAheadLog(
                args.wal_dir,
                flush_interval_s=max(args.wal_flush_ms, 0.0) / 1e3)
            replayed = 0
            for payload in wal.replay(after_seq=base_watermark):
                _apply_ingest(payload)
                replayed += 1
        except WalCorruptionError as e:
            log.error("--wal-dir %s refused: %s", args.wal_dir, e)
            return 2
        _wal_st = wal.stats()
        log.info("wal: recovered %s — last_seq %d, replayed %d "
                 "record(s) above watermark %d, torn_records %d",
                 args.wal_dir, _wal_st["last_seq"], replayed,
                 base_watermark, _wal_st["torn_records"])

    # Per-tenant durable ingest: the same WAL discipline, one log per
    # tenant under --wal-dir/<tenant_id>, each checkpointing under its
    # own index_prefix — one tenant's ingest volume never advances (or
    # corrupts) a neighbor's watermark.
    tenant_wals = []
    tenant_ingests = {}
    if getattr(args, "wal_dir", None) and tenant_registry is not None:
        import numpy as np

        from npairloss_tpu.resilience.wal import (
            WalCorruptionError,
            WriteAheadLog,
        )
        from npairloss_tpu.serve.index import INDEX_SUFFIX
        from npairloss_tpu.serve.server import decode_ingest_payload
        from npairloss_tpu.serve.tenants import TenantIngest

        for spec_t in tenant_registry:
            tid = spec_t.tenant_id
            tpath, tidx = tenant_indexes[tid]
            t_watermark = int(getattr(tidx, "ingest_watermark", 0))
            t_state = {"base": tpath, "pending": []}

            def _t_apply(payload, _st=t_state):
                _st["pending"].append(
                    (int(payload["seq"]), decode_ingest_payload(payload)))

            def _t_publish(wm, _st=t_state, _spec=spec_t):
                pending = [p for p in _st["pending"] if p[0] <= wm]
                if not pending:
                    return None
                base = load_index(_st["base"], mesh=mesh)
                emb = np.concatenate([d[0] for _, d in pending])
                labels = np.concatenate([d[1] for _, d in pending])
                ids = np.concatenate([d[2] for _, d in pending])
                base.add(emb, labels, ids=ids)
                base.ingest_watermark = wm
                path = base.save(
                    f"{_spec.index_prefix}w{wm:012d}{INDEX_SUFFIX}")
                _st["base"] = path
                _st["pending"] = [p for p in _st["pending"]
                                  if p[0] > wm]
                log.info("tenant %r ingest checkpoint: %s (watermark "
                         "%d, +%d row(s))", _spec.tenant_id, path, wm,
                         int(emb.shape[0]))
                return path

            t_wal_dir = os.path.join(args.wal_dir, tid)
            try:
                t_wal = WriteAheadLog(
                    t_wal_dir,
                    flush_interval_s=max(args.wal_flush_ms, 0.0) / 1e3)
                replayed = 0
                for payload in t_wal.replay(after_seq=t_watermark):
                    _t_apply(payload)
                    replayed += 1
            except WalCorruptionError as e:
                log.error("--wal-dir %s (tenant %r) refused: %s",
                          t_wal_dir, tid, e)
                for w in tenant_wals:
                    w.close()
                return 2
            tenant_wals.append(t_wal)
            tenant_ingests[tid] = TenantIngest(
                t_wal, _t_apply, checkpoint_fn=_t_publish,
                checkpoint_every=args.wal_checkpoint_every,
                watermark=max(t_watermark, t_wal.last_seq),
                checkpoint_watermark=t_watermark)
            log.info("tenant %r durable ingest armed: wal %s, replayed "
                     "%d record(s) above watermark %d", tid, t_wal_dir,
                     replayed, t_watermark)

    model = state = None
    input_shape = None
    if args.snapshot:
        from npairloss_tpu.models import get_model
        from npairloss_tpu.train import restore_for_inference

        model = get_model(args.model or "googlenet")
        state = restore_for_inference(args.snapshot)
        side = args.input_size
        input_shape = (side, side, 3)

    buckets = tuple(int(b) for b in args.buckets.split(","))
    telemetry = None
    live = None
    tel_dir = getattr(args, "telemetry_dir", None)
    trace_dir = getattr(args, "trace_dir", None)
    if getattr(args, "live_obs", False):
        # Live observatory (docs/OBSERVABILITY.md §Live): the registry
        # is FED by the telemetry rows, so live obs without a metrics
        # stream would silently watch nothing — refuse loudly.
        if not tel_dir:
            log.error("--live-obs needs --telemetry-dir (the registry "
                      "is fed by the run's metric rows)")
            return 2
        from npairloss_tpu.obs.live import (
            LiveObservatory,
            default_watchdogs,
            load_slo_config,
        )

        if getattr(args, "slo_config", None):
            specs = load_slo_config(args.slo_config)
        else:
            # The queue-depth gauge reports the TIER-WIDE sum across
            # replica batchers, so the saturation bound must scale the
            # same way — or an N-replica tier pages (and sheds) at 1/N
            # of its real capacity.
            specs = default_watchdogs(
                "serve", max_queue=args.max_queue * args.replicas)
        if tenant_registry is not None:
            # Per-tenant SLOs over the labeled metric streams
            # (serve_p99_ms{tenant=...}) — one evaluator, one alert
            # engine, tenant-scoped tenant_*@<id> alert names.
            from npairloss_tpu.serve.tenants import tenant_slo_specs

            specs = list(specs)
            for spec_t in tenant_registry:
                specs.extend(tenant_slo_specs(spec_t))
        live = LiveObservatory(specs, out_dir=tel_dir)
    if tel_dir or trace_dir:
        from npairloss_tpu.obs import RunTelemetry

        telemetry = RunTelemetry(
            tel_dir or trace_dir, metrics=bool(tel_dir),
            extra_sinks=(live.sink,) if live is not None else (),
        )
        if tel_dir:
            telemetry.write_manifest(config={
                "serve": True,
                "index": args.index or args.index_prefix,
                "index_kind": args.index_kind,
                "probes": args.probes,
                "scoring": args.scoring,
                "probe_impl": args.probe_impl,
                # What "auto" came to on THIS backend (None on a flat
                # tier, where the probe path does not exist).
                "probe_impl_resolved": (
                    resolve_probe_impl(args.probe_impl)
                    if args.index_kind == "ivf" else None),
                "replicas": args.replicas,
                "admission": args.admission,
                "top_k": args.top_k,
                "buckets": list(buckets),
                "deadline_ms": args.deadline_ms,
                "max_queue": args.max_queue,
                "live_obs": live is not None,
                "slo_config": getattr(args, "slo_config", None),
                "remediate": bool(getattr(args, "remediate", False)
                                  or getattr(args, "remediate_dry_run",
                                             False)),
                "shadow_rate": shadow_rate,
                "qtrace": bool(getattr(args, "qtrace", False)),
                **({"tenants": tenant_registry.ids()}
                   if tenant_registry is not None else {}),
            })

    if args.admission != "off" and live is None:
        log.error("--admission %s needs --live-obs (admission is driven "
                  "by the SLO burn-rate engine)", args.admission)
        return 2
    if args.replicas < 1:
        log.error("--replicas must be >= 1, got %d", args.replicas)
        return 2

    preempt = PreemptionSignal().install()
    shadow = None
    tenant_shadows = []
    tenant_swapper = None
    try:
        from npairloss_tpu.serve import Freshness

        tenant_entries = {}
        programs = None
        if tenant_registry is None:
            engine_cfg = EngineConfig(
                top_k=args.top_k, buckets=buckets,
                gallery_block=args.gallery_block,
                probes=args.probes, scoring=args.scoring,
                probe_impl=args.probe_impl,
            )
            engine = QueryEngine(
                index, engine_cfg,
                model=model, state=state, telemetry=telemetry,
            )
            # Replicas share the primary's compiled programs: one
            # warmup warms the whole tier, and a restarted process
            # deserializes them from the persistent compile cache.
            engines = [engine] + [
                QueryEngine(index, engine_cfg, model=model, state=state,
                            telemetry=telemetry,
                            share_compiled_with=engine)
                for _ in range(args.replicas - 1)
            ]
            if not args.no_warmup:
                engine.warmup(input_shape)
                for e in engines[1:]:
                    e.warmed = True
            freshness = Freshness.collect(
                index=index, index_path=index_path,
                snapshot_path=args.snapshot or None,
            )
        else:
            # Tenant mode: one engine set PER TENANT through the shared
            # ProgramCache — bucketed shapes make the jitted programs
            # tenant-agnostic, so tenants at the same geometry share
            # one program family and tenant count never multiplies
            # compiles (the test_tenants.py assertion).
            from npairloss_tpu.serve.tenants import (
                ProgramCache,
                QuotaGate,
                TenantEntry,
                TenantTelemetry,
                tenant_slo_specs,
            )

            programs = ProgramCache()
            for spec_t in tenant_registry:
                tid = spec_t.tenant_id
                tpath, tidx = tenant_indexes[tid]
                t_cfg = EngineConfig(
                    top_k=args.top_k, buckets=buckets,
                    gallery_block=args.gallery_block,
                    probes=args.probes, scoring=args.scoring,
                    probe_impl=spec_t.probe_impl or args.probe_impl,
                )
                t_tel = (TenantTelemetry(telemetry, tid)
                         if telemetry is not None else None)
                primary = programs.engine_for(tidx, t_cfg,
                                              telemetry=t_tel)
                if not args.no_warmup:
                    primary.warmup(None)
                t_engines = [primary] + [
                    QueryEngine(tidx, t_cfg, telemetry=t_tel,
                                share_compiled_with=primary)
                    for _ in range(args.replicas - 1)
                ]
                for e in t_engines[1:]:
                    e.warmed = primary.warmed
                quota = None
                if spec_t.quota_qps > 0:
                    quota = QuotaGate(
                        spec_t.quota_qps,
                        burst_s=spec_t.quota_burst_s,
                        registry=(live.registry.view(tenant=tid)
                                  if live is not None else None))
                t_adm = None
                t_slos = tenant_slo_specs(spec_t)
                if spec_t.admission and live is not None and t_slos:
                    from npairloss_tpu.serve.admission import (
                        AdmissionConfig,
                        AdmissionController,
                    )

                    t_adm = AdmissionController(
                        AdmissionConfig(
                            slo_names=tuple(s.name for s in t_slos),
                            probe_every=spec_t.probe_every),
                        registry=live.registry.view(tenant=tid))
                    live.add_listener(t_adm.on_statuses)
                tenant_entries[tid] = TenantEntry(
                    spec_t, t_engines,
                    freshness=Freshness.collect(index=tidx,
                                                index_path=tpath),
                    quota=quota, admission=t_adm,
                    ingest=tenant_ingests.get(tid))
            first_entry = next(iter(tenant_entries.values()))
            engines = first_entry.engines
            # The server-level freshness stays None: in tenant mode
            # every freshness fact is per-entry (the healthz contract).
            freshness = None
        admission = None
        if args.admission == "slo":
            from npairloss_tpu.serve.admission import controller_from_args

            admission = controller_from_args(
                args.admission_slos, registry=live.registry)
            live.add_listener(admission.on_statuses)
        qtracer = None
        if getattr(args, "qtrace", False):
            from npairloss_tpu.obs.qtrace import QTraceConfig, QueryTracer

            slo_ms = float(getattr(args, "qtrace_slo_ms", 0.0) or 0.0)
            if slo_ms <= 0 and live is not None:
                # Default the per-query SLO to the armed p99 watchdog's
                # target: one latency bar, two enforcement points (the
                # pager on the aggregate, the exemplar on the query).
                for spec in specs:
                    if spec.metric == "serve_p99_ms" and spec.op == "<=":
                        slo_ms = float(spec.target)
                        break
            if slo_ms <= 0:
                slo_ms = 250.0
            qtracer = QueryTracer(
                QTraceConfig(
                    exemplars=args.qtrace_exemplars, slo_ms=slo_ms),
                registry=live.registry if live is not None else None,
                out_path=os.path.join(tel_dir, "qtrace.json"),
            )
            log.info("query tracing armed: slo %.1f ms, %d exemplars",
                     slo_ms, args.qtrace_exemplars)
        server = RetrievalServer(
            engines,
            BatcherConfig(max_batch=buckets[-1],
                          max_delay_ms=args.deadline_ms,
                          max_queue=args.max_queue),
            ServerConfig(metrics_window=args.metrics_window,
                         explicit_drops=getattr(args, "explicit_drops",
                                                False),
                         poll_s=args.poll_s),
            telemetry=telemetry, preempt=preempt,
            freshness=freshness, live=live, admission=admission,
            input_shape=input_shape, qtrace=qtracer,
        )
        if tenant_registry is not None:
            from npairloss_tpu.serve.tenants import TenantSwapper

            server.enable_tenants(tenant_entries)
            # Per-tenant hot-swap watch, always on in tenant mode: the
            # "nothing newer" sweep costs a listdir per tenant, and a
            # published checkpoint/commit under any tenant's prefix
            # swaps THAT tenant in place while its neighbors keep
            # answering.
            tenant_swapper = TenantSwapper(
                server, programs=programs, mesh=mesh,
                telemetry=telemetry, ivf_clusters=args.ivf_clusters)
            tenant_swapper.start(period_s=2.0)
            log.info("multi-tenant serving: %d tenant(s) %s; hot-swap "
                     "sweep every 2.0s", len(tenant_entries),
                     sorted(tenant_entries))
        if wal is not None:
            server.attach_wal(
                wal, _apply_ingest,
                checkpoint_fn=_publish_checkpoint,
                checkpoint_every=args.wal_checkpoint_every,
                watermark=max(base_watermark, wal.last_seq),
                checkpoint_watermark=base_watermark)
            log.info("durable ingest armed: wal %s, flush %.1f ms, "
                     "checkpoint every %d batch(es)", args.wal_dir,
                     args.wal_flush_ms, args.wal_checkpoint_every)
        if shadow_rate > 0 and tenant_registry is not None:
            # Per-tenant quality observatories: each tenant gets its
            # own deterministic sampler, oracle, floor and
            # quality.<tenant>.jsonl — a recall regression in one
            # gallery can never hide inside a healthy aggregate.  The
            # TenantTelemetry facade stamps the tenant into every
            # quality row, so the recall gauges land labeled
            # (serve_recall_at_K{tenant=...}) where the tenant's
            # recall SLO reads them.
            from npairloss_tpu.obs.quality.shadow import (
                ShadowConfig,
                ShadowScorer,
            )
            from npairloss_tpu.serve.tenants import TenantTelemetry

            shadow_ks = tuple(k for k in (1, 5, 10) if k <= args.top_k)
            for t_i, tid in enumerate(tenant_entries):
                entry = tenant_entries[tid]
                spec_t = entry.spec
                baseline = None
                try:
                    from npairloss_tpu.resilience.snapshot import (
                        read_manifest,
                    )

                    raw = read_manifest(
                        tenant_indexes[tid][0]).get("parity")
                    baseline = raw if isinstance(raw, dict) else None
                except Exception:  # noqa: BLE001 — baseline is optional evidence
                    baseline = None
                floor = floor_metric = None
                if spec_t.recall_floor is not None:
                    if spec_t.recall_k in shadow_ks:
                        floor = spec_t.recall_floor
                        floor_metric = (
                            f"serve_recall_at_{spec_t.recall_k}")
                    else:
                        log.warning(
                            "tenant %r recall floor targets recall@%d "
                            "but --top-k %d samples only recall@{%s} — "
                            "that floor can never see a sample", tid,
                            spec_t.recall_k, args.top_k,
                            ",".join(str(k) for k in shadow_ks))
                entry.shadow = ShadowScorer(
                    (lambda e=entry: e.engines[0].index),
                    ShadowConfig(rate=shadow_rate, ks=shadow_ks,
                                 window=args.shadow_window,
                                 seed=args.shadow_seed + t_i),
                    telemetry=TenantTelemetry(telemetry, tid),
                    out_path=os.path.join(tel_dir,
                                          f"quality.{tid}.jsonl"),
                    baseline=baseline,
                    recall_floor=floor, floor_metric=floor_metric,
                ).start()
                tenant_shadows.append(entry.shadow)
            log.info("per-tenant shadow scoring armed: rate %g, "
                     "window %d, %d scorer(s)", shadow_rate,
                     args.shadow_window, len(tenant_shadows))
        elif shadow_rate > 0:
            # Quality observatory (docs/OBSERVABILITY.md §Quality):
            # shadow-score a deterministic sample of live queries
            # against the flat oracle, off the hot path.  The floor the
            # quality log declares is whatever recall SLO this run
            # armed; the baseline is the served IVF commit's parity
            # birth certificate (absent for flat/in-memory indexes).
            from npairloss_tpu.obs.quality.shadow import (
                ShadowConfig,
                ShadowScorer,
            )

            baseline = None
            try:
                from npairloss_tpu.resilience.snapshot import (
                    read_manifest,
                )

                raw = read_manifest(index_path).get("parity")
                baseline = raw if isinstance(raw, dict) else None
            except Exception:  # noqa: BLE001 — baseline is optional evidence
                baseline = None
            shadow_ks = tuple(k for k in (1, 5, 10) if k <= args.top_k)
            floor = floor_metric = None
            if live is not None:
                for spec in specs:
                    if not (spec.metric.startswith("serve_recall_at_")
                            and spec.op == ">="):
                        continue
                    tail = spec.metric.rsplit("_", 1)[-1]
                    if tail.isdigit() and int(tail) in shadow_ks:
                        floor, floor_metric = spec.target, spec.metric
                        break
                    # A floor on a K the shadow can never sample
                    # (--top-k below it) would be silently inert —
                    # SLO, breach detection, and the gate would all
                    # sleep through a real regression.  Say so loudly.
                    log.warning(
                        "recall SLO %s targets %s but --top-k %d "
                        "samples only recall@{%s} — that floor can "
                        "never see a sample (raise --top-k or lower "
                        "the SLO's K)", spec.name, spec.metric,
                        args.top_k,
                        ",".join(str(k) for k in shadow_ks))
            shadow = ShadowScorer(
                lambda: server.engine.index,
                ShadowConfig(rate=shadow_rate,
                             ks=shadow_ks,
                             window=args.shadow_window,
                             seed=args.shadow_seed),
                telemetry=telemetry,
                out_path=os.path.join(tel_dir, "quality.jsonl"),
                baseline=baseline,
                recall_floor=floor, floor_metric=floor_metric,
            ).start()
            server.shadow = shadow
            log.info("shadow scoring armed: rate %g, window %d%s",
                     shadow_rate, args.shadow_window,
                     f", floor {floor} on {floor_metric}"
                     if floor is not None else "")
        if getattr(args, "remediate", False):
            # Alert→actuation (docs/RESILIENCE.md §Remediation): bind
            # the live alerts to the serve-side actions this run can
            # actually perform, audited to remediation.jsonl.
            # (--live-obs presence was validated before the preempt
            # handler went in.)
            from npairloss_tpu.resilience.remediate import (
                RemediationEngine,
                default_policies,
                load_policies,
            )

            explicit = bool(getattr(args, "remediation_config", None))
            policies = (load_policies(args.remediation_config)
                        if explicit else default_policies("serve"))
            actions = {}
            if args.index_prefix or getattr(args, "watch_snapshots",
                                            None):
                from npairloss_tpu.serve.hotswap import SnapshotSwapper

                swapper = SnapshotSwapper(
                    server, mesh=mesh,
                    index_prefix=args.index_prefix,
                    snapshot_prefix=getattr(args, "watch_snapshots",
                                            None),
                    model=model, input_shape=input_shape,
                    telemetry=telemetry,
                    index_transform=_reconcile_index,
                )
                actions["snapshot_hotswap"] = swapper.swap
            actions["rewarm"] = lambda alert: server.rewarm()
            if isinstance(index, IVFIndex):
                # Recall-burn actuation (docs/OBSERVABILITY.md
                # §Quality): widen the probe set, flat-fallback past
                # it.  Only an IVF tier has the knob — the default
                # policy table filters itself out elsewhere.
                from npairloss_tpu.obs.quality.escalate import (
                    ProbeEscalator,
                )

                escalator = ProbeEscalator(server, telemetry=telemetry)
                actions["escalate_probes"] = escalator.escalate
            if admission is None and any(p.action == "load_shed"
                                         for p in policies):
                # Remediation-driven shedding needs the throttle in the
                # submit path: a forced-only controller (NO burn
                # listener — it sheds only while the load_shed policy
                # holds it engaged).
                from npairloss_tpu.serve.admission import (
                    AdmissionConfig,
                    AdmissionController,
                )

                admission = AdmissionController(
                    AdmissionConfig(), registry=live.registry)
                server.admission = admission
            if admission is not None:
                actions["load_shed"] = (admission.engage,
                                        admission.release)
            if not explicit:
                # The default table ships every policy; keep the ones
                # this invocation registered an actuator for.  An
                # EXPLICIT config is never filtered — a policy without
                # its action is a loud config error.
                policies = [p for p in policies if p.action in actions]
            try:
                remediation = RemediationEngine(
                    policies, actions,
                    log_path=os.path.join(tel_dir, "remediation.jsonl"),
                    dry_run=getattr(args, "remediate_dry_run", False),
                )
            except ValueError as e:
                # An explicit config naming an action this invocation
                # has no actuator for (snapshot_hotswap without a
                # watched prefix) — a config error, not a crash.
                log.error("--remediation-config %s: %s",
                          args.remediation_config, e)
                return 2
            server.remediation = remediation
            live.set_remediation(remediation)
            log.info("remediation armed: %s%s",
                     ", ".join(f"{p.name}({p.slo}->{p.action})"
                               for p in policies) or "no policies",
                     " [DRY-RUN]" if remediation.dry_run else "")
        if live is not None:
            # Freshness probe: ages are server state, not metric rows —
            # each evaluator tick republishes them so the staleness
            # watchdogs see a continuous stream.  Reads the SERVER's
            # freshness (not a construction-time snapshot): a hot-swap
            # republishes identity + ages, and the probe must see the
            # drop.  The serve.stale_model failpoint poisons the
            # published model age so the staleness→hot-swap loop is
            # deterministically drivable.
            import time as _time

            _qtrace_last = [0.0]

            def _freshness_probe():
                if qtracer is not None:
                    # Crash-consistent exemplar artifact: checkpoint
                    # qtrace.json on the probe cadence (atomic
                    # tmp+rename), so a host crash loses at most a
                    # couple of seconds of markers instead of the whole
                    # artifact — the drain write stays the final word.
                    now = _time.monotonic()
                    if now - _qtrace_last[0] >= 2.0:
                        _qtrace_last[0] = now
                        try:
                            qtracer.write()
                        except OSError as e:
                            log.error("qtrace checkpoint failed: %s", e)
                if wal is not None:
                    # Ingest-durability gauges (/metrics + the SLO
                    # registry): what the tier has acked vs published,
                    # and the torn-tail evidence recovery counted.
                    st = wal.stats()
                    live.registry.set("serve_ingest_watermark",
                                      float(server.ingest_watermark))
                    live.registry.set("serve_wal_durable_seq",
                                      float(st["durable_seq"]))
                    live.registry.set("serve_wal_torn_records",
                                      float(st["torn_records"]))
                if server.tenants:
                    # Per-tenant freshness/ingest gauges, labeled —
                    # each tenant's staleness and durability watermark
                    # is its own metric stream.
                    for tid in sorted(server.tenants):
                        entry = server.tenants[tid]
                        view = live.registry.view(tenant=tid)
                        if entry.ingest is not None:
                            ist = entry.ingest.stats()
                            view.set("serve_ingest_watermark",
                                     float(ist["watermark"]))
                            wst = ist.get("wal") or {}
                            if "durable_seq" in wst:
                                view.set("serve_wal_durable_seq",
                                         float(wst["durable_seq"]))
                        f_t = entry.freshness
                        if f_t is None:
                            continue
                        for key, v in f_t.ages().items():
                            view.set(f"serve_{key}", v)
                f = server.freshness
                if f is None:
                    return
                ages = f.ages()
                if failpoints.should_fire("serve.stale_model"):
                    ages["model_age_s"] = (
                        ages.get("model_age_s", 0.0)
                        + failpoints.STALE_AGE_FAULT_S)
                for key, v in ages.items():
                    live.registry.set(f"serve_{key}", v)

            live.add_probe(_freshness_probe)
            # Started AFTER warmup: the first windows must reflect
            # serving, not seconds-long XLA compiles.
            live.start(period_s=args.slo_tick)
        if args.http is not None:
            return server.run_http(args.http)
        return server.run_jsonl(_sys.stdin, _sys.stdout)
    finally:
        preempt.uninstall()
        if tenant_swapper is not None:
            try:
                tenant_swapper.stop()
            except Exception as e:  # noqa: BLE001
                log.error("tenant swapper stop failed: %s", e)
        if wal is not None:
            try:
                # Drain-time checkpoint already ran inside the server's
                # drain; this is the final fsync + flusher join.
                wal.close()
            except Exception as e:  # noqa: BLE001
                log.error("wal close failed: %s", e)
        for t_wal in tenant_wals:
            try:
                t_wal.close()
            except Exception as e:  # noqa: BLE001
                log.error("tenant wal close failed: %s", e)
        for t_sh in tenant_shadows:
            try:
                t_sh.close()
            except Exception as e:  # noqa: BLE001
                log.error("tenant shadow scorer close failed: %s", e)
        if shadow is not None:
            try:
                # Drain the shadow queue (every accepted sample
                # scored), flush the final window + summary record —
                # BEFORE the live stop, so the last recall rows reach
                # the final tick, and before telemetry closes.
                shadow.close()
            except Exception as e:  # noqa: BLE001
                log.error("shadow scorer close failed: %s", e)
        if live is not None:
            try:
                # Final tick inside: an alert state that changed right
                # before the drain still reaches alerts.jsonl.
                live.stop()
            except Exception as e:  # noqa: BLE001
                log.error("live-obs stop failed: %s", e)
        if telemetry is not None:
            try:
                telemetry.close()
            except Exception as e:  # noqa: BLE001
                log.error("telemetry close failed: %s", e)


def cmd_timeline(args) -> int:
    """``timeline RUNDIR`` — merge every timeline source under a run
    directory (trainer rank traces, the serve host trace, qtrace
    exemplar span trees, alert/remediation/chaos instants) into one
    Perfetto-loadable ``timeline.json`` (docs/OBSERVABILITY.md §Query
    tracing).  Stdlib-only: runs on any box that can read the
    artifacts."""
    from npairloss_tpu.obs.fleet.merge_traces import merge_timeline
    from npairloss_tpu.obs.tracing import validate_chrome_trace

    run_dir = os.path.abspath(args.run_dir)
    if not os.path.isdir(run_dir):
        log.error("timeline: %s is not a directory", run_dir)
        return 2
    path, merged = merge_timeline(run_dir, out_path=args.out)
    if path is None:
        log.error(
            "timeline: no mergeable source under %s (looked for rank "
            "traces, serve_tel/trace.json, qtrace.json, alerts.jsonl, "
            "remediation.jsonl, gameday.json)", run_dir)
        return 1
    err = validate_chrome_trace(merged)
    if err is not None:
        log.error("merged timeline failed trace validation: %s", err)
        return 1
    sources = merged["otherData"]["sources"]
    log.info("timeline: %d event(s) from %s", len(merged["traceEvents"]),
             ", ".join(k for k, v in sources.items() if v))
    print(json.dumps({"timeline": path,
                      "events": len(merged["traceEvents"]),
                      "sources": sources}))
    return 0


def cmd_watch(args) -> int:
    """``watch RUNDIR`` — the live observatory's OFFLINE feed
    (docs/OBSERVABILITY.md §Live): tail a run directory's telemetry
    streams (legacy metrics.jsonl and the fleet per-rank
    telemetry.r<k>.jsonl alike) through the SAME SLO engine the
    in-process path runs, each record evaluated at its own wall_time —
    one evaluator, two feeds.  Backend-free: no jax object is ever
    built, so it runs on any box that can read the artifacts."""
    from npairloss_tpu.obs.live import (
        default_watchdogs,
        load_slo_config,
        watch_run_dir,
    )

    if args.slo_config:
        specs = load_slo_config(args.slo_config)
    else:
        specs = []
        seen = set()
        for kind in args.watchdogs.split(","):
            kind = kind.strip()
            if not kind:
                continue
            for spec in default_watchdogs(kind):
                if spec.name not in seen:
                    seen.add(spec.name)
                    specs.append(spec)
        if not specs:
            log.error("--watchdogs %r names no presets", args.watchdogs)
            return 2

    def emit(event) -> None:
        print(json.dumps(event), flush=True)

    try:
        summary = watch_run_dir(
            args.run_dir, specs,
            follow=args.follow, poll_s=args.poll_s,
            out_path=args.out, emit=emit,
            stop_after_s=getattr(args, "for_s", None),
        )
    except FileNotFoundError as e:
        log.error("%s", e)
        return 2
    except KeyboardInterrupt:
        print("", file=sys.stderr)
        return 0
    print(json.dumps(summary, default=str))
    # Exit code mirrors the bench_check --alerts gate: an SLO still
    # burning when the watch ends is an actionable state for scripts.
    return 1 if any(a["severity"] == "critical"
                    for a in summary["active"].values()) else 0


def _add_staticcheck_options(sc) -> None:
    """The staticcheck option vocabulary, restated here so argparse
    construction stays import-free (the jax-free-parent contract, like
    _PRECISION_CHOICES).  Option strings, choices, and defaults are
    pinned equal to analysis.runner's own parser by
    tests/test_staticcheck.py — both front doors feed one
    ``run_from_args``, so drift is a test failure."""
    sc.add_argument("root", nargs="?", default=None,
                    help="tree to scan (default: this repo)")
    sc.add_argument("--pass", dest="passes", action="append",
                    choices=list(_STATICCHECK_PASSES), metavar="NAME",
                    help="run only the named pass(es); repeatable "
                    f"(default: all of {list(_STATICCHECK_PASSES)})")
    sc.add_argument("--diff", metavar="BASE",
                    help="restrict findings to files changed since the "
                    "git ref (the fast incremental ci.sh hook)")
    sc.add_argument("--allowlist", metavar="PATH",
                    help="allowlist JSON (default: "
                    "<root>/scripts/staticcheck_allow.json)")
    sc.add_argument("--out", metavar="PATH",
                    default="staticcheck_report.json",
                    help="where the npairloss-staticcheck-v1 report "
                    "lands (default %(default)s; '-' disables)")
    sc.add_argument("--update-timings", dest="update_timings",
                    metavar="PYTEST_LOG",
                    help="regenerate tests/timing_history.json from a "
                    "pytest --durations=0 log, then exit")
    sc.add_argument("--threshold-s", dest="threshold_s", type=float,
                    default=10.0,
                    help="slow-marker threshold recorded by "
                    "--update-timings (default %(default)s)")


def cmd_gameday(args) -> int:
    """``gameday --out DIR`` — the production gameday
    (docs/RESILIENCE.md §Gameday): drive the composed system — trainer
    snapshotting under ``--resume auto``, replicated serving tier with
    live-obs + remediation + snapshot/index watching, the watch
    evaluator — through one deterministic compressed day of traffic
    while the chaos schedule injects every scripted fault, then write
    the ``npairloss-gameday-v1`` verdict to ``<out>/gameday.json``.
    Exit 0 iff the verdict passes (the jax-free twin:
    ``scripts/bench_check.py --gameday``)."""
    if args.duration <= 0:
        log.error("--duration must be > 0, got %s", args.duration)
        return 1
    scenario = getattr(args, "scenario", "day")
    if scenario == "day" and args.replicas < 2:
        log.error("--replicas must be >= 2 (the replica-crash entry "
                  "needs a survivor to reroute to), got %s",
                  args.replicas)
        return 1
    if args.schedule and scenario != "day":
        log.error("--schedule is the day scenario's knob; tenant_skew "
                  "ships its own schedule (the hot-tenant burst)")
        return 1
    if args.schedule and not os.path.exists(args.schedule):
        log.error("--schedule not found: %s", args.schedule)
        return 1

    from npairloss_tpu.gameday.runner import (GamedayError, run_gameday,
                                              run_tenant_skew)

    try:
        if scenario == "tenant_skew":
            report = run_tenant_skew(
                args.out, seed=args.seed, duration_s=args.duration,
                replicas=args.replicas)
        else:
            report = run_gameday(
                args.out, seed=args.seed, duration_s=args.duration,
                schedule_path=args.schedule, replicas=args.replicas)
    except GamedayError as e:
        log.error("gameday run broke: %s", e)
        return 1
    print(json.dumps({
        "verdict": report["verdict"],
        "failures": report["failures"],
        "faults": len(report["faults"]),
        "hot_swaps": report["zero_drop"]["hot_swaps"],
        "queries_dropped": report["zero_drop"]["queries_dropped"],
        "answered": report["traffic"]["answered"],
        "report": os.path.join(os.path.abspath(args.out),
                               "gameday.json"),
    }))
    return 0 if report["verdict"] == "pass" else 1


def cmd_staticcheck(args) -> int:
    """``staticcheck [ROOT]`` — the repo-wide invariant linter
    (docs/STATICCHECK.md): jax-free purity proofs for the contract
    modules, collective comm-scope coverage, guarded-by lock
    discipline, versioned-contract drift, vocabulary drift, and
    tier-1 marker discipline — failing in milliseconds at lint time
    what the runtime gates can only catch after the fact.  Jax-free
    end to end: runnable in a venv with no accelerator stack (the
    package import is lazy; this function imports only
    ``npairloss_tpu.analysis``)."""
    from npairloss_tpu.analysis.runner import run_from_args

    return run_from_args(args, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def cmd_parse(args) -> int:
    from npairloss_tpu.config import dumps, parse_file

    msg = parse_file(args.file)
    if args.json:
        print(json.dumps(msg.to_dict(), indent=2, default=str))
    else:
        print(dumps(msg))
    return 0


def _time_stage_bodies(solver, images, labels):
    """Scan bodies for the three timed stages of ``cmd_time`` plus the
    shared carry, built on the Solver's own apply_model/compute_loss
    plumbing (mutable batch stats threaded through the carry), so the
    differenced loss/backward shares compare like with like and the
    benchmarked graph IS the trained graph.  Two timing-integrity rules
    shape the bodies (regression-pinned by a FLOPs-ratio test):
      * every stage output is anchored by a WHOLE-tensor reduction
        (sum of emb / loss AND metrics / sum over ALL grad leaves) —
        anchoring a single element would let XLA dead-code-eliminate
        most of the work it claims to time (slice-through-dot narrows
        the final matmul; unconsumed grad leaves drop their weight-grad
        gemms; unconsumed metrics drop the retrieval subgraph);
      * params/images/labels ride the scan carry, not the closure —
        jit bakes captured arrays into each program as constants
        (three private copies of a ~72 MB flagship batch otherwise).
    Solver state must be initialized.  Returns
    ``(trunk_body, forward_body, fb_body, init_carry)``.
    """
    import jax
    import jax.numpy as jnp

    state = solver.state
    params, bstats = state["params"], state["batch_stats"]

    def _f32sum(x):
        return jnp.sum(x.astype(jnp.float32))

    def _anchor_all(loss, metrics):
        return jax.tree_util.tree_reduce(
            lambda a, v: a + _f32sum(v), metrics, loss.astype(jnp.float32)
        )

    def trunk_body(carry, s):
        acc, pp, bs, im, lb = carry
        emb, bs = solver.apply_model(
            pp, bs, im * (1.0 + s * 1e-6), train=True
        )
        return (acc + _f32sum(emb), pp, bs, im, lb)

    def forward_body(carry, s):
        acc, pp, bs, im, lb = carry
        emb, bs = solver.apply_model(
            pp, bs, im * (1.0 + s * 1e-6), train=True
        )
        loss, metrics = solver.compute_loss(emb, lb)
        return (acc + _anchor_all(loss, metrics) + _f32sum(emb),
                pp, bs, im, lb)

    def fb_body(carry, s):
        acc, pp, bs, im, lb = carry

        def loss_fn(p):
            emb, new_bs = solver.apply_model(
                p, bs, im * (1.0 + s * 1e-6), train=True
            )
            loss, metrics = solver.compute_loss(emb, lb)
            return loss, (metrics, new_bs)

        (loss, (metrics, new_bs)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(pp)
        gsum = jax.tree_util.tree_reduce(
            lambda a, g: a + _f32sum(g), grads, jnp.float32(0.0)
        )
        return (acc + _anchor_all(loss, metrics) + gsum, pp, new_bs, im, lb)

    init = (jnp.float32(0.0), params, bstats,
            jnp.asarray(images), jnp.asarray(labels))
    return trunk_body, forward_body, fb_body, init


def cmd_time(args) -> int:
    """The ``caffe time`` counterpart (the reference's implied Caffe fork
    is driven by the stock Caffe CLI, whose ``time`` action benchmarks a
    net's forward/backward from ``-model`` + ``-iterations`` alone —
    SURVEY.md §1 L1).  Caffe reports per-layer wall-clock; under jit the
    step is ONE fused XLA program, so the honest analog is per-STAGE
    attribution by differential timing: trunk forward, full forward
    (trunk + loss + metrics), and forward+backward, each timed as one
    scanned program around ``block_until_ready``
    (``utils.profiling.time_scan``) and differenced for the
    loss/backward shares."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from npairloss_tpu.data import synthetic_identity_batches
    from npairloss_tpu.utils.profiling import mfu_from_timing, time_scan

    if _refuse_cpu(args, "time"):
        return 2
    built = _build_solver(args)
    if isinstance(built, int):
        return built
    solver, net_cfg, input_shape = built

    # Batch geometry from the net's data layer (either phase), exactly
    # what `caffe time` would allocate; --batch/--ids override.
    for flag in ("ids", "batch"):
        v = getattr(args, flag, None)
        if v is not None and v < 1:
            log.error("--%s must be >= 1, got %d", flag, v)
            return 2
    d = net_cfg.data.get("TRAIN") or net_cfg.data.get("TEST")
    ids, imgs = _identity_batch_geometry(d)
    if args.ids:
        ids = args.ids
    elif args.batch:
        ids = max(args.batch // imgs, 1)
        if ids * imgs != args.batch:
            log.warning(
                "--batch %d is not a multiple of %d images/identity; "
                "timing batch %d", args.batch, imgs, ids * imgs,
            )
    images, labels = next(
        synthetic_identity_batches(ids * 4, ids, imgs, input_shape, seed=0)
    )
    images = jnp.asarray(images)
    labels = jnp.asarray(labels)
    batch = int(images.shape[0])

    if solver.state is None:
        solver.init(np.asarray(images[:2]))
    steps = int(args.iterations)
    if steps < 1:
        log.error("--iterations must be >= 1, got %d", steps)
        return 2
    dev = jax.devices()[0]
    log.info("timing on %s (%s), batch %d, %d iterations",
             dev.platform, dev.device_kind, batch, steps)

    trunk_body, forward_body, fb_body, init = _time_stage_bodies(
        solver, images, labels
    )
    trunk_ms = time_scan(trunk_body, init, steps=steps)
    forward_ms = time_scan(forward_body, init, steps=steps)
    fb_ms = (None if args.forward_only else
             time_scan(fb_body, init, steps=steps))

    rec = {
        "device": f"{dev.platform}:{dev.device_kind}",
        "device_count": len(jax.devices()),
        "engine": solver.engine or "dense",
        "mesh_devices": solver.mesh.size if solver.mesh is not None else 1,
        "batch": batch,
        "iterations": steps,
        "trunk_forward_ms": round(trunk_ms, 3),
        "forward_ms": round(forward_ms, 3),
        "loss_forward_ms": round(max(forward_ms - trunk_ms, 0.0), 3),
    }
    if fb_ms is not None:
        rec["forward_backward_ms"] = round(fb_ms, 3)
        rec["backward_ms"] = round(max(fb_ms - forward_ms, 0.0), 3)
        rec["emb_per_sec"] = round(batch / fb_ms * 1e3, 1)
        # XLA's analytic FLOPs for one step, from the LOWERED program
        # (client-side; never asks the backend to compile a second
        # executable), plus MFU when the device's peak is known — both
        # via THE shared helper (obs.perf.costs.mfu_from_timing).
        try:
            lowered = jax.jit(
                lambda c: fb_body(c, jnp.float32(0.0))
            ).lower(init)
            est = mfu_from_timing(lowered, seconds=fb_ms * 1e-3,
                                  device_kind=dev.device_kind)
        except Exception as e:
            log.info("step_flops estimate unavailable: %s", e)
            est = {"step_flops": None, "mfu": None}
        if est["step_flops"]:
            rec["step_flops"] = est["step_flops"]
            if est["mfu"] is not None:
                rec["mfu"] = round(est["mfu"], 4)
    print(json.dumps(rec))
    return 0


def cmd_device_query(args) -> int:
    """The ``caffe device_query`` counterpart: enumerate the
    accelerator(s) the way ``caffe device_query -gpu N`` prints CUDA
    device properties (stock-Caffe CLI surface of the implied fork,
    SURVEY.md §1 L1) — platform, device kind, per-device memory
    stats, and the process/mesh topology that replaces
    ``Caffe::NUM_GPU``/``RANK`` (reference:
    npair_multi_class_loss.cpp:44)."""
    import jax

    devices = []
    for dv in jax.devices():
        mem = {}
        try:
            mem = dv.memory_stats() or {}
        except Exception:  # backends without memory introspection
            mem = {}
        devices.append({
            "id": dv.id,
            "platform": dv.platform,
            "device_kind": dv.device_kind,
            "process_index": dv.process_index,
            "bytes_in_use": mem.get("bytes_in_use"),
            "bytes_limit": mem.get("bytes_limit"),
        })
    print(json.dumps({
        "device_count": jax.device_count(),
        "local_device_count": jax.local_device_count(),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "default_backend": jax.default_backend(),
        "devices": devices,
    }, indent=2))
    return 0


def cmd_prof(args) -> int:
    """Perf observatory (docs/OBSERVABILITY.md §Perf): one on-disk
    report per run — static per-``named_scope``-region FLOPs / bytes /
    arithmetic-intensity / roofline bound-class attribution of the
    jitted step, plus the span-derived step-time decomposition
    reconciled against wall time.  This is the STATIC half of
    attribution (what a step costs, where the host's wall clock goes):
    everything comes from compiled-HLO metadata and the host span
    streams, no device trace is taken.  The live modes measure, so
    they refuse a CPU unless ``--platform cpu`` names it (the roofline
    then uses the flagged CPU reference spec).

    ``--fleet RUNDIR`` is the OFFLINE mode (docs/OBSERVABILITY.md
    §Fleet observatory): aggregate a fleet run directory's per-rank
    telemetry streams into the ``npairloss-fleet-report-v1``
    straggler/skew/comms report plus one merged Perfetto timeline —
    no backend is touched.  ``--quality RUNDIR`` is its quality-
    observatory sibling: validate and render the run's
    ``npairloss-quality-v1`` shadow-recall log against its committed
    baseline (§Quality observatory; backend-free too)."""
    if getattr(args, "fleet", None):
        return _prof_fleet(args)
    if getattr(args, "quality", None):
        return _prof_quality(args)

    import jax
    import numpy as np

    from npairloss_tpu.obs import RunTelemetry
    from npairloss_tpu.obs import perf as obsperf

    if _refuse_cpu(args, "prof"):
        return 2
    steps = max(int(args.steps), 1)
    out_dir = args.out if args.out is not None else "perf_reports"
    dev = jax.devices()[0]
    tel = RunTelemetry(os.path.join(out_dir, "run"), metrics=True,
                       trace=True)
    try:
        if args.step == "train":
            report = _prof_train(args, jax, np, dev, tel, steps, obsperf)
        else:
            report = _prof_serve(args, jax, np, dev, tel, steps, obsperf)
    finally:
        tel.close()
    err = obsperf.validate_report(report)
    if err is not None:
        log.error("perf report failed its own schema check: %s", err)
        return 1
    paths = obsperf.write_report(report, out_dir)
    print(obsperf.render_table(report))
    print(json.dumps({"report": paths["json"], "table": paths["txt"],
                      "telemetry": tel.run_dir}))
    return 0


def _prof_quality(args) -> int:
    """``prof --quality RUNDIR``: offline quality-observatory report
    (docs/OBSERVABILITY.md §Quality observatory).  Validates the run's
    ``quality.jsonl`` against the ``npairloss-quality-v1`` contract,
    prints the per-window recall trend with the committed parity
    baseline alongside, and exits non-zero on a schema-invalid log —
    the validator is the contract, exactly like the perf/fleet paths.
    Stdlib-only: no backend is touched."""
    from npairloss_tpu.obs.quality import (
        load_quality_report,
        quality_breaches,
        quality_summary,
        stale_shadow,
        validate_quality_report,
    )

    run_dir = os.path.abspath(args.quality)
    path = (run_dir if run_dir.endswith(".jsonl")
            else os.path.join(run_dir, "quality.jsonl"))
    if not os.path.exists(path):
        log.error("prof --quality: no quality log at %s (serve with "
                  "--shadow-rate > 0 to produce one)", path)
        return 2
    records = load_quality_report(path)
    err = validate_quality_report(records)
    if err is not None:
        log.error("quality log failed its own schema check: %s", err)
        return 1
    summary = quality_summary(records)
    lines = [f"quality observatory — {path}",
             f"  windows {summary['windows']}, samples "
             f"{summary['sampled_total']}, shadow rate "
             f"{summary['shadow_rate']:g}"]
    for key, row in sorted(summary.get("recall", {}).items()):
        lines.append(
            f"  recall@{key[3:]}: min {row['min']:.4f}  mean "
            f"{row['mean']:.4f}  last {row['last']:.4f}")
    base = summary.get("baseline")
    if base:
        lines.append(f"  committed baseline (probes {base.get('probes')},"
                     f" sample {base.get('sample')}): "
                     + json.dumps(base.get("recall", {})))
    if "recall_floor" in summary:
        lines.append(f"  declared floor: {summary['recall_floor']:g} on "
                     f"{summary['floor_metric']} — "
                     f"{summary['breaches']} breaching window(s)")
    for i, metric, r, floor in quality_breaches(records):
        lines.append(f"    breach: record {i} {metric} {r:.4f} < "
                     f"{floor:g}")
    stale = stale_shadow(records)
    if stale:
        lines.append(f"  WARNING: {stale}")
    print("\n".join(lines))
    print(json.dumps({"log": path, **summary,
                      **({"stale": stale} if stale else {})}))
    return 0


def _prof_fleet(args) -> int:
    """``prof --fleet RUNDIR``: offline fleet aggregation (stdlib-only
    — never touches a backend; the streams on disk are the input).
    Writes ``fleet_report.json``/``.txt`` and the merged
    ``fleet_trace.json`` to --out (default: the run dir itself), prints
    the table, and fails on a schema-invalid report — the validator is
    the contract, exactly like the perf report path."""
    from npairloss_tpu.obs.fleet import (
        build_fleet_report,
        merge_run_traces,
        render_fleet_table,
        validate_fleet_report,
        write_fleet_report,
    )
    from npairloss_tpu.obs.tracing import validate_chrome_trace

    run_dir = os.path.abspath(args.fleet)
    if not os.path.isdir(run_dir):
        log.error("prof --fleet: %s is not a directory", run_dir)
        return 2
    # --out default is None (a sentinel, not the literal "perf_reports"
    # string) so an EXPLICIT --out perf_reports is honored here too.
    out_dir = args.out if args.out is not None else run_dir
    os.makedirs(out_dir, exist_ok=True)
    report = build_fleet_report(run_dir)
    trace_path, merged = merge_run_traces(
        run_dir, os.path.join(out_dir, "fleet_trace.json")
        if os.path.abspath(out_dir) != run_dir else None)
    if trace_path is not None:
        terr = validate_chrome_trace(merged)
        if terr is not None:
            # The report itself is independent evidence — land it
            # before failing, same as the schema-failure branch below.
            write_fleet_report(report, out_dir)
            log.error("merged fleet trace failed validation: %s", terr)
            return 1
        report.setdefault("notes", []).append(
            f"merged timeline: {trace_path} "
            f"({len(merged['traceEvents'])} events, "
            f"{len(merged['otherData']['merged_ranks'])} rank lane(s))")
    err = validate_fleet_report(report)
    if err is not None:
        # The report (with its failure) still lands on disk — a bad
        # fleet state must be diagnosable from artifacts too.
        write_fleet_report(report, out_dir)
        log.error("fleet report failed its own schema check: %s", err)
        return 1
    paths = write_fleet_report(report, out_dir)
    print(render_fleet_table(report))
    print(json.dumps({"report": paths["json"], "table": paths["txt"],
                      "trace": trace_path}))
    return 0


def _prof_train(args, jax, np, dev, tel, steps, obsperf):
    """Train-step profile: N real solver steps (device-wait spanned so
    device compute is attributed, not absorbed), then one extra AOT
    compile of the same program for its HLO text."""
    import time as _time

    import jax.numpy as jnp

    from npairloss_tpu import REFERENCE_CONFIG
    from npairloss_tpu.models import get_model
    from npairloss_tpu.train import Solver, SolverConfig

    batch = int(args.batch)
    side = int(args.image)
    policy = getattr(args, "precision", None)
    if policy:
        model = get_model(args.model, policy=policy)
    else:
        model = get_model(
            args.model, dtype=jnp.bfloat16 if args.bf16 else jnp.float32)
    mesh = None
    if args.mesh and args.mesh > 1:
        from npairloss_tpu.parallel import data_parallel_mesh

        mesh = data_parallel_mesh(jax.devices()[:args.mesh])
    input_shape = (side, side, 3) if args.model != "mlp" else (side,)
    solver = Solver(
        model, REFERENCE_CONFIG,
        SolverConfig(base_lr=0.001, lr_policy="step", stepsize=10000,
                     gamma=0.5, momentum=0.9, weight_decay=2e-5,
                     display=0, snapshot=0),
        # perf_metrics stays OFF: with display=0 the continuous rows
        # never emit, so the flops capture would only pay an extra
        # client-side re-lowering (~1/3 of a small prof run's wall)
        # that the report doesn't consume — build_report reads the
        # compiled stage directly.
        mesh=mesh, engine=args.engine, input_shape=input_shape,
        precision=policy or None,
        telemetry=tel,
    )
    # The shared synthetic generator, not a hand-rolled batch — the
    # identity-pair layout contract lives in data.synthetic only.
    from npairloss_tpu.data import synthetic_identity_batches

    ids = max((batch + 1) // 2, 1)
    x, lab = next(iter(synthetic_identity_batches(
        ids, ids, 2, input_shape, seed=0)))
    x, lab = x[:batch], lab[:batch]
    log.info("prof train: model=%s batch=%d steps=%d device=%s",
             args.model, batch, steps, dev.device_kind)
    solver.init(x[:2])
    t0_us = tel.tracer.now_us()
    step_walls = []
    t0 = _time.perf_counter()
    for i in range(steps):
        s0 = _time.perf_counter()
        metrics = solver.step(x, lab)
        # The dispatch is async: without this span the device compute
        # would land in "unattributed"; with it, the wait IS the
        # device-compute share of the loop wall clock.
        with tel.span("step/device_wait", step=i):
            jax.block_until_ready(metrics)
        step_walls.append(_time.perf_counter() - s0)
    wall_ms = (_time.perf_counter() - t0) * 1e3
    # Post-compile per-step time: the first step paid the XLA compile.
    warm = step_walls[1:] or step_walls
    ms_per_step = min(warm) * 1e3
    log.info("prof train: %d steps in %.1f ms (%.2f ms/step warm); "
             "extracting HLO (one extra AOT compile)...",
             steps, wall_ms, ms_per_step)
    x_sds = jax.ShapeDtypeStruct((batch, *input_shape), jnp.float32)
    lab_sds = jax.ShapeDtypeStruct((batch,), jnp.int32)
    compiled = solver._step_fn.lower(solver.state, x_sds, lab_sds).compile()
    events = [e for e in tel.tracer.to_chrome_trace()["traceEvents"]
              if e.get("ts", 0) >= t0_us]
    return obsperf.build_report(
        step="train", device_kind=dev.device_kind, batch=batch,
        stage=compiled, span_events=events, wall_ms=wall_ms,
        ms_per_step=ms_per_step, steps=steps,
        region_depth=int(args.region_depth),
        extra={"model": args.model, "engine": solver.engine,
               "policy": policy or None,
               # The satellite of --dump-partitions: a prof'd mesh run
               # stamps the same rule digest, so a silent no-op rule is
               # visible in the perf artifact too.
               **({"partition": solver.partition_summary()}
                  if mesh is not None else {})},
    )


def _prof_serve(args, jax, np, dev, tel, steps, obsperf):
    """Serve-query profile: synthetic gallery + warmed QueryEngine, N
    per-bucket query dispatches, static attribution of the largest
    bucket's top-k program, serve/* span latency split."""
    import time as _time

    import jax.numpy as jnp

    from npairloss_tpu.serve import EngineConfig, GalleryIndex, QueryEngine

    rng = np.random.default_rng(0)
    gallery = int(args.gallery)
    dim = int(args.dim)
    emb = rng.standard_normal((gallery, dim)).astype(np.float32)
    index = GalleryIndex.build(
        emb, (np.arange(gallery) % max(gallery // 8, 1)).astype(np.int32))
    buckets = tuple(int(b) for b in args.buckets.split(","))
    engine = QueryEngine(
        index, EngineConfig(top_k=int(args.top_k), buckets=buckets),
        telemetry=tel,
    )
    log.info("prof serve: gallery=%d dim=%d buckets=%s steps=%d",
             gallery, dim, buckets, steps)
    engine.warmup()
    t0_us = tel.tracer.now_us()
    t0 = _time.perf_counter()
    q = rng.standard_normal((buckets[-1], dim)).astype(np.float32)
    # Cycle largest-bucket-first so every bucket contributes spans to
    # the latency split, but time ONLY the largest bucket's own
    # dispatches: the MFU/emb_per_sec line prices the largest bucket's
    # compiled program, and dividing its FLOPs by a wall averaged over
    # smaller batches would inflate both by the bucket-size spread.
    big_walls = []
    for i in range(steps):
        b = buckets[-1 - (i % len(buckets))]
        s0 = _time.perf_counter()
        engine.query(q[:b])
        if b == buckets[-1]:
            big_walls.append(_time.perf_counter() - s0)
    wall_ms = (_time.perf_counter() - t0) * 1e3
    bucket = buckets[-1]
    qpad = jnp.zeros((bucket, dim), jnp.float32)
    compiled = engine._topk_fn.lower(
        qpad, index.emb, index.labels, index.valid).compile()
    events = [e for e in tel.tracer.to_chrome_trace()["traceEvents"]
              if e.get("ts", 0) >= t0_us]
    return obsperf.build_report(
        step="serve", device_kind=dev.device_kind, batch=bucket,
        stage=compiled, span_events=events, wall_ms=wall_ms,
        ms_per_step=min(big_walls) * 1e3, steps=len(big_walls),
        serve_spans=True,
        region_depth=int(args.region_depth),
        extra={"gallery": gallery, "dim": dim,
               "compile_stats": engine.compile_stats()},
    )


def main(argv: Optional[list] = None) -> int:
    p = argparse.ArgumentParser(
        prog="npairloss_tpu", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument(
        "--platform", choices=["default", "cpu", "tpu"],
        default="default",
        help="pin the jax platform before backend init: 'tpu' fails at "
        "start-up when no chip is found instead of running on whatever "
        "JAX falls back to; 'cpu' is an explicit CPU run (the "
        "measuring commands time/prof refuse a CPU without it); "
        "default: JAX's own choice",
    )
    sub = p.add_subparsers(dest="cmd", required=True)

    t = sub.add_parser("train", help="train from a solver prototxt")
    t.add_argument("--solver", required=True)
    t.add_argument("--net", help="override the solver's net path")
    t.add_argument("--model", help="model registry name (default: from net)")
    t.add_argument("--max_iter", type=int, help="override solver max_iter")
    t.add_argument("--mesh", type=int, help="devices in the dp mesh")
    t.add_argument(
        "--engine", choices=["auto", "dense", "ring", "blockwise"],
        help="loss engine (default: dense; ring streams the pool over a "
        "mesh, blockwise streams Pallas tiles on one device; auto picks "
        "dense vs ring from the mesh's host topology and the roofline "
        "ICI/DCN peaks — the plan lands in the run manifest)",
    )
    t.add_argument(
        "--mp", type=int, default=1, metavar="M",
        help="model-parallel axis size: the mesh becomes 2-D (dp x mp) "
        "with mp groups on adjacent (same-host) chips, for partition "
        "rules that shard parameters over 'mp' (docs/DISTRIBUTED.md)",
    )
    t.add_argument(
        "--partition-rules", dest="partition_rules", metavar="FILE",
        help="JSON partition-rule table: ordered [regex, spec] pairs "
        "over the flattened state-tree path, first match wins, "
        "unmatched leaves are a loud error (default: everything "
        "replicated) — docs/DISTRIBUTED.md cookbook",
    )
    t.add_argument(
        "--dump-partitions", dest="dump_partitions", action="store_true",
        help="print the resolved rule->PartitionSpec table per state "
        "leaf (zero-match rules flagged) before training; pair with "
        "--max_iter 0 as a preflight check",
    )
    t.add_argument(
        "--pos-topk", dest="pos_topk", default="auto", metavar="K",
        type=_pos_topk_arg,
        help="streaming engines' sparse-positive buffer slots for "
        "RELATIVE AP mining (auto = 8; 0 forces radix selection)")
    t.add_argument(
        "--sim-cache", dest="sim_cache", choices=["auto", "on", "off"],
        default="auto",
        help="streaming engines' fp32 similarity cache (auto = by size)",
    )
    t.add_argument(
        "--matmul-precision", dest="matmul_precision",
        choices=["highest", "default"], default=None,
        help="loss-engine gemm precision: highest = oracle bit-parity "
        "(default), default = ~6x single-pass bf16 MXU throughput mode",
    )
    t.add_argument("--bf16", action="store_true", help="bfloat16 trunk")
    t.add_argument(
        "--precision", choices=_PRECISION_CHOICES, default=None,
        help="declarative mixed-precision policy (models.precision): "
        "mxu = the flagship default (bf16 compute over fp32 params, "
        "single-pass bf16 MXU gemms incl. the loss engines), bf16 = "
        "the legacy --bf16 recipe as a named policy, fp32_parity = the "
        "prototxt-parity fp32 fallback; overrides --bf16 and supplies "
        "--matmul-precision's default",
    )
    t.add_argument(
        "--remat", action="store_true",
        help="rematerialize inception blocks in the backward (GoogLeNet "
        "trunks): ~25%% more trunk FLOPs for much lower activation HBM "
        "— lifts the per-chip batch ceiling; numerically identical",
    )
    t.add_argument(
        "--resume",
        help="snapshot path to restore, or 'auto' to scan snapshot_prefix "
        "for the newest valid snapshot (torn/corrupt ones skipped with a "
        "logged reason; none found = fresh start) — the supervisor-"
        "relaunch contract, docs/RESILIENCE.md",
    )
    t.add_argument(
        "--weights",
        help="pretrained params (.msgpack from import-caffemodel) to "
        "finetune from — fresh optimizer state, iteration 0 (use "
        "--resume for mid-training snapshots instead)",
    )
    t.add_argument(
        "--caffe-solverstate", dest="caffe_solverstate", metavar="PATH",
        help="resume the optimizer (momentum + iteration) from a Caffe "
        ".solverstate — the `caffe train --snapshot` semantics; pair "
        "with --weights for the matching .caffemodel parameters",
    )
    t.add_argument("--snapshot_prefix", help="override snapshot prefix")
    t.add_argument(
        "--snapshot-keep", dest="snapshot_keep", type=int, metavar="N",
        help="retention GC: keep only the newest N committed snapshots "
        "(default: solver snapshot_max_keep; 0 keeps all)",
    )
    t.add_argument(
        "--divergence-patience", dest="divergence_patience", type=int,
        default=0, metavar="N",
        help="arm the divergence guard: N consecutive non-finite losses "
        "trigger --divergence-action (0 = off; costs one host sync per "
        "step when armed)",
    )
    t.add_argument(
        "--divergence-action", dest="divergence_action",
        choices=["rollback", "halt"], default="rollback",
        help="guard action: rollback restores the newest valid snapshot "
        "(bounded by --divergence-max-rollbacks), halt stops with a "
        "diagnosis",
    )
    t.add_argument(
        "--divergence-lr-scale", dest="divergence_lr_scale", type=float,
        default=1.0, metavar="S",
        help="multiply base_lr by S on each rollback (e.g. 0.5 halves "
        "the lr so the trajectory doesn't re-diverge)",
    )
    t.add_argument(
        "--divergence-max-rollbacks", dest="divergence_max_rollbacks",
        type=int, default=2, metavar="N",
        help="rollbacks allowed before the guard halts anyway",
    )
    t.add_argument(
        "--pipeline", action="store_true",
        help="sync-free stepping (docs/PIPELINE.md): device-resident "
        "double-buffered batch prefetch, per-step scalars accumulated "
        "in a device-side ring and read back only at display/test/"
        "snapshot window boundaries, dispatch depth bounded — the "
        "device never waits on the host in steady state; parity-pinned "
        "bit-identical to the default loop",
    )
    t.add_argument(
        "--pipeline-depth", dest="pipeline_depth", type=int, default=2,
        metavar="K",
        help="prefetch depth AND max in-flight dispatched steps "
        "(default 2 — double buffering)",
    )
    t.add_argument(
        "--pipeline-window", dest="pipeline_window", type=int, default=0,
        metavar="W",
        help="cap on steps between host syncs (0 = auto: the smallest "
        "active display/test/snapshot cadence, else 64); bounds the "
        "divergence guard's detection staleness",
    )
    t.add_argument(
        "--no-preempt-handler", dest="no_preempt_handler",
        action="store_true",
        help="do not install the SIGTERM/SIGINT graceful-preemption "
        "handler (emergency snapshot + exit 75)",
    )
    t.add_argument(
        "--synthetic", action="store_true",
        help="train on synthetic identity-balanced clusters instead of the "
        "net's data source (required opt-in; a missing source is an error)",
    )
    t.add_argument(
        "--native", choices=["auto", "never", "require"], default="auto",
        help="C++ data runtime routing: auto (by source suffixes), never "
        "(Python/PIL pipeline), require (error if the native runtime "
        "cannot serve this source)",
    )
    t.add_argument(
        "--caffe-pad", dest="caffe_pad", action="store_true",
        help="evaluate conv1 at Caffe's exact pad-3 geometry (GoogLeNet "
        "trunks; use with imported .caffemodel weights — SAME samples a "
        "phase-shifted grid at stride 2)",
    )
    t.add_argument(
        "--coordinator",
        help="multi-process coordinator HOST:PORT (the mpirun counterpart); "
        "omit on TPU pods for autodetect",
    )
    t.add_argument(
        "--log-json", dest="log_json", metavar="PATH",
        help="append one JSON record per display/test/snapshot event "
        "(machine-readable counterpart of the Caffe-style text log)",
    )
    t_tel = t.add_mutually_exclusive_group()
    t_tel.add_argument(
        "--telemetry-dir", dest="telemetry_dir", metavar="DIR",
        help="full run-telemetry directory: manifest.json (config/topology/"
        "git-sha snapshot) + metrics.jsonl (one structured row per train "
        "step and eval) + trace.json (host span timeline, Perfetto-"
        "viewable) — see docs/OBSERVABILITY.md",
    )
    t_tel.add_argument(
        "--trace-dir", dest="trace_dir", metavar="DIR",
        help="host-side span tracing only: write DIR/trace.json "
        "(Chrome-trace JSON) without per-step metric rows (and without "
        "their per-step host sync); mutually exclusive with "
        "--telemetry-dir, whose run dir already includes the trace",
    )
    t.add_argument(
        "--fleet", action="store_true",
        help="force rank-stamped fleet telemetry (telemetry.r<k>.jsonl "
        "per rank, comm accounting, step-numbered spans) even on a "
        "single process; multi-process runs stamp automatically — "
        "docs/OBSERVABILITY.md §Fleet observatory",
    )
    t.add_argument(
        "--health-metrics", dest="health_metrics", action="store_true",
        help="fold in-graph training-health signals into every step's "
        "metrics (grad/param/update norms, update/param ratio, embedding "
        "magnitude, mined-pair hardness) — obs.health.HealthConfig",
    )
    t.add_argument(
        "--mining-health", dest="mining_health", action="store_true",
        help="extend the health rows with mining-quality trend stats "
        "(AP-AN margin mean/p10, hard-negative saturation) from the "
        "same loss aux — embedding collapse as a quality trend "
        "(docs/OBSERVABILITY.md §Quality observatory); implies "
        "--health-metrics",
    )
    t.add_argument(
        "--perf-metrics", dest="perf_metrics", action="store_true",
        help="emit one phase=\"perf\" telemetry row per display window "
        "(ms_per_step, emb_per_sec, MFU from XLA's analytic step FLOPs) "
        "— needs --telemetry-dir; docs/OBSERVABILITY.md §Perf",
    )
    t.add_argument(
        "--live-obs", dest="live_obs", action="store_true",
        help="live observatory (docs/OBSERVABILITY.md §Live): feed this "
        "run's telemetry rows into the in-process metric registry, "
        "evaluate SLO watchdogs continuously, and append firing/resolved "
        "alerts to <telemetry-dir>/alerts.jsonl (npairloss-alerts-v1); "
        "needs --telemetry-dir; the telemetry streams on disk stay "
        "byte-identical",
    )
    t.add_argument(
        "--slo-config", dest="slo_config", metavar="PATH",
        help="SLO config (JSON; TOML on tomllib-equipped interpreters): "
        "watchdog presets by name plus explicit SLO entries — default: "
        "the standard train watchdogs",
    )
    t.add_argument(
        "--slo-tick", dest="slo_tick", type=float, default=1.0,
        metavar="S",
        help="live-obs evaluation period in seconds (default 1.0)",
    )
    t.add_argument(
        "--metrics-port", dest="metrics_port", type=int, metavar="PORT",
        help="with --live-obs: serve Prometheus /metrics (+ /healthz "
        "with SLO status) on this localhost port",
    )
    t.add_argument(
        "--remediate", action="store_true",
        help="alert→actuation (docs/RESILIENCE.md §Remediation): a "
        "health-signal alert (embedding collapse) requests a rollback "
        "to a pre-incident snapshot, executed at the loop's next safe "
        "point and audited to <telemetry-dir>/remediation.jsonl; "
        "needs --live-obs",
    )
    t.add_argument(
        "--remediation-config", dest="remediation_config",
        metavar="PATH",
        help="remediation policy table (JSON; default: the shipped "
        "train policies)",
    )
    t.add_argument(
        "--remediate-dry-run", dest="remediate_dry_run",
        action="store_true",
        help="log every remediation the policies WOULD run without "
        "acting — implies --remediate",
    )
    t.add_argument(
        "--debug-checks", dest="debug_checks", action="store_true",
        help="validate every step's loss/metric scalars are finite on "
        "host (utils.debug.enable_debug_checks; also settable via "
        "NPAIRLOSS_DEBUG_CHECKS=1)",
    )
    t.add_argument("--num-processes", type=int, help="total host processes")
    t.add_argument("--process-id", type=int, help="this process's rank")
    t.set_defaults(fn=cmd_train)

    def _common(sp):
        sp.add_argument("--solver", required=True)
        sp.add_argument("--net", help="override the solver's net path")
        sp.add_argument("--model", help="model registry name")
        sp.add_argument("--mesh", type=int, help="devices in the dp mesh")
        sp.add_argument(
            "--engine", choices=["dense", "ring", "blockwise"],
            help="loss engine (see train --engine)",
        )
        sp.add_argument(
            "--sim-cache", dest="sim_cache", choices=["auto", "on", "off"],
            default="auto", help="see train --sim-cache",
        )
        sp.add_argument("--bf16", action="store_true")
        sp.add_argument(
            "--precision", choices=_PRECISION_CHOICES, default=None,
            help="mixed-precision policy (see train --precision)",
        )
        sp.add_argument(
            "--resume",
            help="snapshot path to restore, or 'auto' for the newest "
            "valid one under snapshot_prefix (see train --resume)",
        )
        sp.add_argument("--synthetic", action="store_true")
        sp.add_argument(
            "--native", choices=["auto", "never", "require"],
            default="auto", help="see train --native",
        )
        sp.add_argument(
            "--caffe-pad", dest="caffe_pad", action="store_true",
            help="see train --caffe-pad",
        )

    tt = sub.add_parser(
        "test", help="TEST phase only from a snapshot (caffe test)"
    )
    _common(tt)
    tt.add_argument(
        "--iterations", type=int,
        help="TEST batches to average (default: solver test_iter)",
    )
    tt.set_defaults(fn=cmd_test)

    ex = sub.add_parser(
        "extract", help="dump embeddings + labels to .npy (eval mode)"
    )
    _common(ex)
    ex.add_argument("--phase", default="TEST", choices=["TEST", "TRAIN", "test", "train"])
    ex.add_argument("--batches", type=int, default=16)
    ex.add_argument("--out", default="./features")
    ex.set_defaults(fn=cmd_extract)

    ev = sub.add_parser(
        "eval",
        help="full-gallery Recall@K over extracted embeddings (.npy)",
    )
    ev.add_argument(
        "--prefix", default="./features",
        help="extract output prefix (reads PREFIX.emb.npy + "
        "PREFIX.labels.npy)",
    )
    ev.add_argument("--emb", help="explicit embeddings .npy path")
    ev.add_argument("--labels", help="explicit labels .npy path")
    ev.add_argument(
        "--ks", type=int, nargs="+", default=[1, 2, 4, 8, 16, 32],
        help="Recall@K cutoffs (CUB reports 1 2 4 8; SOP 1 10 100 1000)",
    )
    ev.add_argument(
        "--query-block", type=int, default=1024,
        help="queries per streamed block (the N x N matrix is never "
        "materialized)",
    )
    ev.add_argument(
        "--nmi", action="store_true",
        help="also report clustering NMI (on-device k-means with "
        "k = #classes — the CUB/SOP paper protocol's second number)",
    )
    ev.add_argument("--kmeans-iters", type=int, default=20)
    ev.set_defaults(fn=cmd_eval)

    ix = sub.add_parser(
        "index",
        help="build a committed gallery index from extracted embeddings",
    )
    ix.add_argument(
        "--prefix", default="./features",
        help="extract output prefix (reads PREFIX.emb.npy + "
        "PREFIX.labels.npy; default index path PREFIX.gidx)",
    )
    ix.add_argument("--emb", help="explicit embeddings .npy path")
    ix.add_argument("--labels", help="explicit labels .npy path")
    ix.add_argument("--out", help="index directory to commit (.gidx)")
    ix.add_argument(
        "--add-to", dest="add_to", metavar="INDEX",
        help="append rows to an existing index (incremental add) and "
        "re-commit it instead of building fresh",
    )
    ix.add_argument(
        "--no-normalize", dest="no_normalize", action="store_true",
        help="trust the rows are already unit-norm (extract output is)",
    )
    ix.add_argument(
        "--info", metavar="INDEX",
        help="print an existing index's manifest summary and exit",
    )
    ix.add_argument(
        "--kind", choices=["flat", "ivf"], default="flat",
        help="index structure: flat (exact brute-force scan — the "
        "recall oracle) or ivf (k-means clustered, probe-top-C "
        "approximate search; docs/SERVING.md §Approximate index)",
    )
    ix.add_argument(
        "--clusters", type=int, default=0,
        help="ivf cluster count (0 = ~sqrt(N), the classical balance "
        "point)",
    )
    ix.add_argument(
        "--kmeans-iters", dest="kmeans_iters", type=int, default=10,
        help="ivf k-means Lloyd iterations (default 10)",
    )
    ix.add_argument(
        "--train-sample", dest="train_sample", type=int, default=131072,
        help="ivf k-means training subsample bound (full assignment "
        "always streams the whole gallery; default 131072)",
    )
    ix.add_argument(
        "--parity-sample", dest="parity_sample", type=int, default=256,
        help="queries sampled for the build-time recall parity stamp "
        "in the ivf commit manifest (0 disables; default 256) — the "
        "live shadow-recall baseline (docs/OBSERVABILITY.md §Quality)",
    )
    ix.add_argument(
        "--parity-probes", dest="parity_probes", type=int, default=8,
        help="probe count the parity stamp measures at (match the "
        "serving --probes; default 8)",
    )
    ix.set_defaults(fn=cmd_index)

    sv = sub.add_parser(
        "serve",
        help="serve top-K retrieval queries against a gallery index "
        "(stdin/JSONL, or localhost HTTP with --http)",
    )
    sv_idx = sv.add_mutually_exclusive_group(required=True)
    sv_idx.add_argument("--index", help="committed index dir (.gidx)")
    sv_idx.add_argument(
        "--index-prefix", dest="index_prefix",
        help="scan PREFIX*.gidx newest-first and serve the first valid "
        "one (torn/corrupt indexes skipped with a logged reason)",
    )
    sv_idx.add_argument(
        "--tenant-config", dest="tenant_config", metavar="PATH",
        help="multi-tenant serving (docs/SERVING.md §Multi-tenant): a "
        "npairloss-tenants-v1 JSON manifest mapping tenant ids to "
        "index prefixes, per-tenant index kind/probe impl, qps quota, "
        "recall floor and admission params; every query/ingest record "
        "must carry a registered 'tenant' id, and freshness, quotas, "
        "SLOs and shadow scoring split per tenant behind one front "
        "end and one replica tier (replaces --index/--index-prefix)",
    )
    sv.add_argument(
        "--snapshot",
        help="training snapshot to restore for raw-'input' queries "
        "(embedding queries need no model)",
    )
    sv.add_argument("--model", help="model registry name for --snapshot")
    sv.add_argument(
        "--input-size", dest="input_size", type=int, default=224,
        help="input side length for the encode path (default 224)",
    )
    sv.add_argument(
        "--index-kind", dest="index_kind", choices=["flat", "ivf"],
        default="flat",
        help="serve the gallery flat (exact scan — the recall oracle) "
        "or through the IVF probe path; a flat commit served with ivf "
        "is clustered in-memory at startup (--ivf-clusters), an ivf "
        "commit served flat falls back to the exact scan",
    )
    sv.add_argument(
        "--ivf-clusters", dest="ivf_clusters", type=int, default=0,
        help="cluster count when building IVF at startup from a flat "
        "commit (0 = ~sqrt(N))",
    )
    sv.add_argument(
        "--probes", type=int, default=8,
        help="IVF clusters scored per query (recall-vs-latency knob; "
        "clamped to the cluster count; default 8)",
    )
    sv.add_argument(
        "--scoring", choices=["fp32", "bf16", "int8"], default="fp32",
        help="similarity-matmul dtype: fp32 (oracle precision), bf16 "
        "(half the scan bandwidth/MXU cost), int8 (IVF only: "
        "per-cluster-scale quantized slab) — gate reduced modes with "
        "the recall-parity harness (docs/SERVING.md)",
    )
    sv.add_argument(
        "--probe-impl", dest="probe_impl",
        choices=list(_PROBE_IMPL_CHOICES), default="scan",
        help="IVF probe-path implementation: 'scan' (the lax.scan "
        "gather+score baseline), 'fused' (single-pass Pallas kernel: "
        "gather + score + running top-k in one VMEM pass, in-kernel "
        "int8 dequant), 'auto' (fused on TPU, scan elsewhere); the "
        "resolved choice is stamped into the run manifest and /healthz "
        "(ignored by a flat index)",
    )
    sv.add_argument(
        "--replicas", type=int, default=1,
        help="QueryEngine replicas behind this front end (shared "
        "compiled programs; least-loaded routing; per-replica drain)",
    )
    sv.add_argument(
        "--admission", choices=["off", "slo"], default="off",
        help="admission control: 'slo' sheds load (fast-reject, "
        "counted in rejected) while a watched SLO burns and admits "
        "again on clear — needs --live-obs (docs/SERVING.md "
        "§Admission-control runbook)",
    )
    sv.add_argument(
        "--admission-slos", dest="admission_slos", metavar="NAMES",
        help="comma-separated SLO names driving admission (default "
        "serve_p99,serve_queue_saturation)",
    )
    sv.add_argument("--top-k", dest="top_k", type=int, default=10)
    sv.add_argument(
        "--buckets", default="1,8,32",
        help="ascending query padding buckets; steady state serves "
        "exactly these program shapes (default 1,8,32)",
    )
    sv.add_argument(
        "--deadline-ms", dest="deadline_ms", type=float, default=5.0,
        help="max added latency a query may wait for micro-batch "
        "co-riders (default 5)",
    )
    sv.add_argument(
        "--max-queue", dest="max_queue", type=int, default=256,
        help="admission queue bound; submits beyond it are rejected "
        "with backpressure (default 256)",
    )
    sv.add_argument(
        "--metrics-window", dest="metrics_window", type=int, default=100,
        help="queries per emitted latency/QPS/queue-depth metrics row "
        "(0 = none)",
    )
    sv.add_argument(
        "--poll-s", dest="poll_s", type=float, default=0.1,
        help="front-end wakeup period: how long an answer may sit "
        "ready before the idle flush emits it, and the drain-signal "
        "reaction bound while idle — lower it when measured latency "
        "at low qps matters more than wakeup overhead (default 0.1)",
    )
    sv.add_argument(
        "--gallery-block", dest="gallery_block", type=int, default=4096,
        help="gallery rows streamed per block inside a shard",
    )
    sv.add_argument("--mesh", type=int, help="devices in the dp mesh")
    sv.add_argument(
        "--http", type=int, metavar="PORT",
        help="serve localhost HTTP on PORT instead of stdin/JSONL",
    )
    sv.add_argument(
        "--no-warmup", dest="no_warmup", action="store_true",
        help="skip the per-bucket warmup (first queries then pay "
        "the compiles the warmup would have)",
    )
    sv.add_argument(
        "--live-obs", dest="live_obs", action="store_true",
        help="live observatory (docs/OBSERVABILITY.md §Live): SLO "
        "watchdogs over the serve window rows, alerts.jsonl in the "
        "telemetry dir, /metrics + SLO-enriched /healthz on the --http "
        "front end; needs --telemetry-dir",
    )
    sv.add_argument(
        "--slo-config", dest="slo_config", metavar="PATH",
        help="SLO config (JSON/TOML) — default: the standard serve "
        "watchdogs (p99, queue saturation, post-warmup compiles, "
        "index/model staleness)",
    )
    sv.add_argument(
        "--slo-tick", dest="slo_tick", type=float, default=1.0,
        metavar="S",
        help="live-obs evaluation period in seconds (default 1.0)",
    )
    sv.add_argument(
        "--remediate", action="store_true",
        help="alert→actuation (docs/RESILIENCE.md §Remediation): bind "
        "the live alerts to guarded actions — snapshot/index hot-swap "
        "on staleness (needs --watch-snapshots/--index-prefix), "
        "load-shed on queue saturation, re-warm on a post-warmup "
        "compile storm — audited to remediation.jsonl; needs "
        "--live-obs",
    )
    sv.add_argument(
        "--remediation-config", dest="remediation_config",
        metavar="PATH",
        help="remediation policy table (JSON; default: the shipped "
        "serve policies filtered to the actions this invocation can "
        "perform)",
    )
    sv.add_argument(
        "--remediate-dry-run", dest="remediate_dry_run",
        action="store_true",
        help="log every remediation the policies WOULD run (budgets "
        "included) without acting — implies --remediate",
    )
    sv.add_argument(
        "--shadow-rate", dest="shadow_rate", type=float, default=0.0,
        metavar="FRAC",
        help="fraction of live queries shadow-scored off the hot path "
        "against the flat exact oracle (deterministic by query id) — "
        "emits live serve_recall_at_{1,5,10} + score-gap rows and the "
        "npairloss-quality-v1 log; 0 (default) disables and keeps "
        "every stream byte-identical; needs --telemetry-dir "
        "(docs/OBSERVABILITY.md §Quality observatory)",
    )
    sv.add_argument(
        "--shadow-window", dest="shadow_window", type=int, default=32,
        help="shadow samples per emitted quality window row "
        "(default 32)",
    )
    sv.add_argument(
        "--shadow-seed", dest="shadow_seed", type=int, default=0,
        help="shadow sampling seed (same seed = same shadow set)",
    )
    sv.add_argument(
        "--watch-snapshots", dest="watch_snapshots", metavar="PREFIX",
        help="training snapshot_prefix the hot-swap remediation "
        "watches for newer committed snapshots (the train→serve "
        "freshness loop's actuation half; pair with --snapshot for "
        "the initial model)",
    )
    sv.add_argument(
        "--explicit-drops", dest="explicit_drops", action="store_true",
        help="write queries_dropped into the drain summary and "
        "/healthz even at 0 (the gameday zero-drop posture: zero is "
        "evidence, not a default — docs/RESILIENCE.md §Gameday); off, "
        "the key appears only when nonzero",
    )
    sv.add_argument(
        "--qtrace", action="store_true",
        help="per-query tracing (docs/OBSERVABILITY.md §Query "
        "tracing): per-stage spans from admission to answer, always-on "
        "stage histograms + p99 budget decomposition, and the "
        "npairloss-qtrace-v1 exemplar artifact (qtrace.json in the "
        "telemetry dir; SLO-violating and slowest-tail queries keep "
        "full span trees) — needs --telemetry-dir; off (default) "
        "keeps every stream byte-identical",
    )
    sv.add_argument(
        "--qtrace-exemplars", dest="qtrace_exemplars", type=int,
        default=64, metavar="N",
        help="exemplar ring capacity — full span trees retained for "
        "the worst queries (default 64; evicts the fastest retained "
        "exemplar when full)",
    )
    sv.add_argument(
        "--qtrace-slo-ms", dest="qtrace_slo_ms", type=float,
        default=0.0, metavar="MS",
        help="per-query latency SLO for exemplar retention + the "
        "violations counter (default 0 = the armed serve_p99 "
        "watchdog's target when --live-obs is on, else 250)",
    )
    sv.add_argument(
        "--wal-dir", dest="wal_dir", metavar="DIR",
        help="durable-ingest write-ahead log directory "
        "(npairloss-wal-v1 — docs/RESILIENCE.md §Durability): every "
        "stdin ingest record is WAL-appended + fsynced BEFORE its ack, "
        "cold restart replays records above the newest index "
        "snapshot's watermark, and checkpoints publish under "
        "--index-prefix (required with this flag); off (default) "
        "rejects ingest records",
    )
    sv.add_argument(
        "--wal-flush-ms", dest="wal_flush_ms", type=float, default=0.0,
        metavar="MS",
        help="group-commit fsync interval: acks wait for the covering "
        "flush (amortizes fsyncs across concurrent ingests); 0 "
        "(default) fsyncs inline on every append",
    )
    sv.add_argument(
        "--wal-checkpoint-every", dest="wal_checkpoint_every",
        type=int, default=8, metavar="N",
        help="publish an ingest checkpoint (and GC covered WAL "
        "segments) every N acked ingest batches; a final checkpoint "
        "always lands at drain (default 8; 0 = drain-only)",
    )
    sv_tel = sv.add_mutually_exclusive_group()
    sv_tel.add_argument(
        "--telemetry-dir", dest="telemetry_dir", metavar="DIR",
        help="run-telemetry directory (manifest + per-window serve "
        "metric rows + span trace) — see docs/OBSERVABILITY.md",
    )
    sv_tel.add_argument(
        "--trace-dir", dest="trace_dir", metavar="DIR",
        help="span tracing only (serve/admit|batch|dispatch|topk)",
    )
    sv.set_defaults(fn=cmd_serve)

    tl = sub.add_parser(
        "timeline",
        help="merge a run directory's timeline sources (trainer rank "
        "traces, serve host spans, qtrace exemplar span trees, "
        "alert/remediation/chaos instants) into one Perfetto-loadable "
        "timeline.json — docs/OBSERVABILITY.md §Query tracing",
    )
    tl.add_argument("run_dir", metavar="RUNDIR",
                    help="run/telemetry directory (gameday out dirs "
                    "with serve_tel/ + train_tel/ work as-is)")
    tl.add_argument("--out", default=None, metavar="PATH",
                    help="output path (default: RUNDIR/timeline.json)")
    tl.set_defaults(fn=cmd_timeline)

    im = sub.add_parser(
        "import-caffemodel",
        help="migrate a trained .caffemodel trunk to a --weights file",
    )
    im.add_argument("--weights", required=True, help=".caffemodel path")
    im.add_argument(
        "--model", default="googlenet",
        help="target model (plain googlenet; train --weights converts "
        "to s2d/fused layouts automatically)",
    )
    im.add_argument("--out", default="./pretrained.msgpack")
    im.set_defaults(fn=cmd_import_caffemodel)

    exp = sub.add_parser(
        "export-caffemodel",
        help="write a trunk trained here back out as .caffemodel",
    )
    exp.add_argument(
        "--weights",
        help="params .msgpack (from import-caffemodel)",
    )
    exp.add_argument(
        "--snapshot",
        help="export straight from a training snapshot (.ckpt dir) "
        "instead of --weights",
    )
    exp.add_argument(
        "--model", default="googlenet",
        help="trunk family the weights belong to (googlenet | resnet50)",
    )
    exp.add_argument("--out", default="./model.caffemodel")
    exp.add_argument(
        "--solverstate-out", dest="solverstate_out", metavar="PATH",
        help="also write the optimizer state (momentum + iteration) as "
        "a Caffe .solverstate (GoogLeNet trunks; needs --snapshot)",
    )
    exp.set_defaults(fn=cmd_export_caffemodel)

    tm = sub.add_parser(
        "time",
        help="benchmark a net's forward/backward (the caffe time action)",
    )
    tm.add_argument(
        "--net", help="net prototxt to time (like caffe time -model)"
    )
    tm.add_argument(
        "--solver",
        help="optional solver prototxt (only its net path is used)",
    )
    tm.add_argument("--model", help="model registry name (default: from net)")
    tm.add_argument(
        "--iterations", type=int, default=10,
        help="scan length per timed stage (caffe time -iterations)",
    )
    tm_geom = tm.add_mutually_exclusive_group()
    tm_geom.add_argument(
        "--batch", type=int,
        help="override total batch size (rounded down to a multiple of "
        "the net's images/identity)",
    )
    tm_geom.add_argument(
        "--ids", type=int, help="override identities per batch",
    )
    tm.add_argument(
        "--forward-only", dest="forward_only", action="store_true",
        help="skip the forward+backward stage",
    )
    tm.add_argument("--mesh", type=int, help="devices in the dp mesh")
    tm.add_argument(
        "--engine", choices=["dense", "ring", "blockwise"],
        help="loss engine (see train --engine)",
    )
    tm.add_argument("--bf16", action="store_true", help="bfloat16 trunk")
    tm.add_argument(
        "--precision", choices=_PRECISION_CHOICES, default=None,
        help="mixed-precision policy (see train --precision)",
    )
    tm.add_argument(
        "--sim-cache", dest="sim_cache", choices=["auto", "on", "off"],
        default="auto", help="see train --sim-cache",
    )
    tm.add_argument(
        "--pos-topk", dest="pos_topk", type=_pos_topk_arg, default="auto",
        help="see train --pos-topk",
    )
    tm.add_argument(
        "--matmul-precision", dest="matmul_precision",
        choices=["highest", "default"],
        help="see train --matmul-precision",
    )
    tm.add_argument(
        "--remat", action="store_true",
        help="block-remat GoogLeNet trunks (see train --remat)",
    )
    tm.add_argument(
        "--caffe-pad", dest="caffe_pad", action="store_true",
        help="see train --caffe-pad",
    )
    tm.add_argument("--resume", help="snapshot to time (restored weights)")
    tm.set_defaults(fn=cmd_time)

    dq = sub.add_parser(
        "device-query",
        help="enumerate accelerators (the caffe device_query action)",
    )
    dq.set_defaults(fn=cmd_device_query)

    pr = sub.add_parser(
        "prof",
        help="perf observatory: per-region HLO cost attribution + "
        "roofline bound-class + step-time decomposition report "
        "(docs/OBSERVABILITY.md §Perf)",
    )
    pr.add_argument(
        "--step", choices=["train", "serve"], default="train",
        help="which jitted program to profile",
    )
    pr.add_argument(
        "--fleet", metavar="RUNDIR",
        help="offline fleet aggregation: read a fleet run directory's "
        "per-rank telemetry (telemetry.r<k>.jsonl + trace.r<k>.json), "
        "emit the npairloss-fleet-report-v1 straggler/skew/comms "
        "report and a merged Perfetto timeline (ignores the live-"
        "profiling flags; no backend touched)",
    )
    pr.add_argument(
        "--quality", metavar="RUNDIR",
        help="offline quality report: validate a serving run's "
        "npairloss-quality-v1 shadow-recall log (quality.jsonl) and "
        "render the recall trend vs the committed parity baseline "
        "(docs/OBSERVABILITY.md §Quality observatory; no backend "
        "touched)",
    )
    pr.add_argument("--model", default="googlenet",
                    help="model registry name (train)")
    pr.add_argument("--batch", type=int, default=8,
                    help="train batch size (identity pairs)")
    pr.add_argument("--image", type=int, default=224,
                    help="input side (or flat dim for --model mlp)")
    pr.add_argument("--steps", type=int, default=4,
                    help="measured steps/queries for the dynamic layer")
    pr.add_argument("--engine", choices=["dense", "ring", "blockwise"],
                    help="loss engine (train)")
    pr.add_argument("--mesh", type=int, default=0,
                    help="devices in the dp mesh (train; 0 = single)")
    pr.add_argument("--bf16", action="store_true",
                    help="bf16 trunk activations (train)")
    pr.add_argument("--precision", choices=_PRECISION_CHOICES,
                    default=None,
                    help="mixed-precision policy for the profiled trunk "
                    "(see train --precision); the before/after roofline "
                    "recipe is fp32_parity vs mxu")
    pr.add_argument("--gallery", type=int, default=2048,
                    help="synthetic gallery rows (serve)")
    pr.add_argument("--dim", type=int, default=64,
                    help="embedding dim (serve)")
    pr.add_argument("--top-k", dest="top_k", type=int, default=10)
    pr.add_argument("--buckets", default="1,8,32",
                    help="query padding buckets (serve)")
    pr.add_argument("--region-depth", dest="region_depth", type=int,
                    default=2,
                    help="named-scope path depth to aggregate regions at")
    pr.add_argument("--out", default=None,
                    help="report output directory (default: perf_reports "
                    "for live profiles, the run dir itself for --fleet)")
    pr.set_defaults(fn=cmd_prof)

    w = sub.add_parser(
        "watch",
        help="evaluate SLO watchdogs over a run directory's telemetry "
        "offline (the live observatory's second feed; no backend)",
    )
    w.add_argument("run_dir", metavar="RUNDIR",
                   help="run directory holding metrics.jsonl or "
                   "per-rank telemetry.r<k>.jsonl streams")
    w.add_argument(
        "--slo-config", dest="slo_config", metavar="PATH",
        help="SLO config (JSON/TOML); default: the --watchdogs presets",
    )
    w.add_argument(
        "--watchdogs", default="train,serve",
        help="comma-separated watchdog preset kinds when no --slo-config "
        "(default train,serve — a kind whose metrics never appear "
        "just stays ok)",
    )
    w.add_argument(
        "--follow", action="store_true",
        help="keep tailing the streams instead of one replay pass",
    )
    w.add_argument(
        "--poll-s", dest="poll_s", type=float, default=1.0,
        help="--follow poll period (default 1.0)",
    )
    w.add_argument(
        "--for", dest="for_s", type=float, default=None, metavar="S",
        help="stop --follow after S seconds (default: until interrupted)",
    )
    w.add_argument(
        "--out", metavar="PATH",
        help="alert JSONL output (default RUNDIR/alerts.watch.jsonl — "
        "never the in-process engine's alerts.jsonl)",
    )
    w.set_defaults(fn=cmd_watch)

    gd = sub.add_parser(
        "gameday",
        help="production gameday (docs/RESILIENCE.md §Gameday): "
        "deterministic traffic + scripted chaos over the composed "
        "trainer/server/watch group, verdict-gated "
        "(npairloss-gameday-v1)",
    )
    gd.add_argument("--out", required=True, metavar="DIR",
                    help="run directory for every artifact (answers, "
                    "telemetry, logs, gameday.json)")
    gd.add_argument("--seed", type=int, default=0,
                    help="traffic seed — same seed, same compressed "
                    "day, byte for byte (default 0)")
    gd.add_argument("--duration", type=float, default=75.0,
                    metavar="S",
                    help="traffic window in seconds (default 75)")
    gd.add_argument("--schedule", metavar="PATH",
                    help="chaos schedule JSON (default: the shipped "
                    "compressed-day schedule; day scenario only)")
    gd.add_argument("--replicas", type=int, default=2,
                    help="serving replicas (default 2; >= 2 so the "
                    "day scenario's replica-crash entry has a "
                    "survivor)")
    gd.add_argument("--scenario", choices=("day", "tenant_skew"),
                    default="day",
                    help="'day' = the full compressed-day chaos drill; "
                    "'tenant_skew' = the multi-tenant noisy-neighbor "
                    "drill (docs/SERVING.md §Multi-tenant): one tier, "
                    "three tenant galleries, a hot-tenant burst that "
                    "must quota-shed and page WITHOUT degrading the "
                    "other tenants (default %(default)s)")
    gd.set_defaults(fn=cmd_gameday)

    sc = sub.add_parser(
        "staticcheck",
        help="repo-wide invariant linter (docs/STATICCHECK.md) — "
        "jax-free, enforces the contracts the runtime gates can only "
        "catch after the fact",
    )
    _add_staticcheck_options(sc)
    sc.set_defaults(fn=cmd_staticcheck)

    pp = sub.add_parser("parse", help="parse + dump a prototxt file")
    pp.add_argument("file")
    pp.add_argument("--json", action="store_true")
    pp.set_defaults(fn=cmd_parse)

    args = p.parse_args(argv)
    if args.platform != "default":
        import jax

        jax.config.update("jax_platforms", args.platform)
    if args.cmd in ("train", "serve", "index"):
        # Before the first compile, so the cache covers every program
        # this process builds; the closing line is what lets a caller
        # see a cold recompile (chip_smoke.py reads it).
        from npairloss_tpu.pipeline import CacheCounter, enable_compile_cache

        enable_compile_cache()
        with CacheCounter() as cache:
            rc = args.fn(args)
        print("compile_cache " + json.dumps(cache.stats()),
              file=sys.stderr, flush=True)
        return rc
    return args.fn(args)


def _refuse_cpu(args, what: str) -> bool:
    """True (after logging why) when a measuring command found no
    accelerator and the CPU was not asked for by name: a CPU timing
    must never appear where a device number is expected."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "cpu" or args.platform == "cpu":
        return False
    log.error("%s: no accelerator found (platform %s); pass --platform "
              "cpu for an explicit CPU run", what, dev.platform)
    return True


if __name__ == "__main__":
    raise SystemExit(main())
