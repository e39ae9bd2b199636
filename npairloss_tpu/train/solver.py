"""The solver loop — the TPU-native counterpart of the Caffe Solver contract.

Reproduces the behavior implied by usage/solver.prototxt (SURVEY.md C21):
step-decayed momentum SGD, ``display``/``average_loss`` sliding-window
monitoring, a TEST phase every ``test_interval`` iterations over
``test_iter`` batches (the reference has no separate eval path — the same
loss+metrics forward runs on eval batches, SURVEY.md §3.4), and
``snapshot``/``snapshot_prefix`` checkpoints (Orbax, async-capable, instead
of Caffe's .caffemodel writes).

The whole training step — model forward, loss with all_gather negative
pooling, backward, optimizer update, in-graph metrics — is ONE jitted
function; multi-chip runs shard the batch over a 1-D ``dp`` mesh with
parameters replicated, collectives compiled into the step by XLA.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import logging
import threading
import time
import warnings
from functools import partial
from typing import Any, Callable, Dict, Iterator, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from npairloss_tpu.obs.health import (
    HealthConfig,
    embedding_health,
    pair_hardness_health,
    update_health,
)
from npairloss_tpu.obs import tracing
from npairloss_tpu.obs.run import RunTelemetry
from npairloss_tpu.ops.metrics import retrieval_metrics
from npairloss_tpu.resilience import failpoints
from npairloss_tpu.resilience.guard import (
    DivergenceConfig,
    DivergenceError,
    DivergenceGuard,
    RollbackRequest,
)
from npairloss_tpu.resilience.preempt import PreemptionSignal, TrainingPreempted
from npairloss_tpu.resilience.retrying import RetryPolicy, call_with_retry
from npairloss_tpu.resilience.snapshot import (
    SnapshotValidationError,
    commit_snapshot,
    gc_snapshots,
    list_snapshots,
    quarantine_snapshots,
    read_manifest,
    state_checksums,
    validate_snapshot,
    validate_snapshot_wait,
    verify_restored,
    write_manifest,
)
from npairloss_tpu.utils.debug import assert_all_finite, debug_checks_enabled
from npairloss_tpu.ops.npair_loss import NPairLossConfig, npair_loss_with_aux
from npairloss_tpu.train.optim import CaffeSGDState, caffe_sgd, lr_schedule

log = logging.getLogger("npairloss_tpu.solver")


@dataclasses.dataclass
class SolverConfig:
    """Mirror of the SolverParameter subset the reference uses
    (usage/solver.prototxt:1-17); defaults are the shipped values."""

    base_lr: float = 0.001
    lr_policy: str = "step"
    gamma: float = 0.5
    stepsize: int = 10000
    power: float = 1.0
    stepvalues: Sequence[int] = ()
    momentum: float = 0.9
    weight_decay: float = 0.00002
    max_iter: int = 2000000
    display: int = 100
    average_loss: int = 100
    test_iter: int = 2000
    test_interval: int = 2000
    test_initialization: bool = True
    snapshot: int = 5000
    snapshot_prefix: str = "./snap/model_"
    random_seed: int = 0
    # Retention GC (docs/RESILIENCE.md): committed snapshots beyond the
    # newest N are deleted after each successful commit; 0 keeps all
    # (Caffe's behavior — snapshot_max_keep is this framework's own
    # extension, not a SolverParameter field).
    snapshot_max_keep: int = 0
    # Sync-free stepping (docs/PIPELINE.md) — framework extensions, not
    # SolverParameter fields.  ``pipeline`` routes ``train`` through the
    # async loop: device-resident prefetch, per-step scalars accumulated
    # in a device-side ring read back only at display/test/snapshot
    # window boundaries, dispatch depth bounded by ``pipeline_depth``.
    # Default OFF; the pipelined loop is parity-pinned bit-identical to
    # the synchronous one (tests/test_pipeline.py).  ``pipeline_window``
    # caps the steps between host syncs (0 = auto: the smallest active
    # cadence, else 64) — it bounds the divergence guard's staleness.
    pipeline: bool = False
    pipeline_depth: int = 2
    pipeline_window: int = 0


class Solver:
    """Train an embedding model with the N-pair loss.

    Args:
      model: a Flax module mapping (images, train=...) -> [N, D] embeddings.
      loss_cfg: mining/margin configuration.
      cfg: solver hyperparameters.
      train_iter/test_iter_fn: iterators yielding (inputs, labels) numpy
        batches (identity-balanced per the MultibatchData contract).
      mesh: optional 1-D device mesh; when given, batches are sharded over
        its axis and the loss pools negatives across all shards.
      top_ks: Recall@k list emitted every step (def.prototxt tops).
    """

    def __init__(
        self,
        model,
        loss_cfg: NPairLossConfig = NPairLossConfig(),
        cfg: Optional[SolverConfig] = None,
        mesh: Optional[Mesh] = None,
        axis: str = "dp",
        top_ks: Sequence[int] = (1, 5, 10),
        input_shape: Sequence[int] = (224, 224, 3),
        use_ring: bool = False,
        engine: Optional[str] = None,
        sim_cache: Optional[bool] = None,
        pos_topk: Optional[int] = None,
        matmul_precision: Optional[str] = None,
        precision: Optional[Any] = None,
        partition_rules: Optional[Sequence] = None,
        param_mults: Optional[tuple] = None,
        loss_weight: float = 1.0,
        health: Optional[HealthConfig] = None,
        telemetry: Optional[RunTelemetry] = None,
        divergence: Optional[DivergenceConfig] = None,
        preempt: Optional[PreemptionSignal] = None,
        snapshot_retry: Optional[RetryPolicy] = None,
        perf_metrics: bool = False,
    ):
        self.model = model
        self.loss_cfg = loss_cfg
        # Run-telemetry subsystem (docs/OBSERVABILITY.md): ``health``
        # folds in-graph training-health signals into the step's metric
        # dict (None = no extra ops, HLO identical to a health-free
        # build); ``telemetry`` routes per-step records + host spans
        # through obs.run.RunTelemetry.  Both are plain attributes —
        # assignable after construction; health changes take effect at
        # the next (re)compile.
        self.health = health
        self.telemetry = telemetry
        # Fault-tolerance subsystem (docs/RESILIENCE.md), all plain
        # attributes like health/telemetry: ``divergence`` arms the
        # non-finite-loss guard (costs one host sync per step when set),
        # ``preempt`` is the SIGTERM/SIGINT stop flag ``train`` polls
        # once per step, ``snapshot_retry`` bounds the backoff around
        # snapshot I/O (None = the default 3-attempt policy).
        self.divergence = divergence
        self.preempt = preempt
        self.snapshot_retry = (
            snapshot_retry if snapshot_retry is not None else RetryPolicy()
        )
        # Batch signatures already dispatched through the jitted step/
        # eval fns: a NEW signature means jit will trace+compile before
        # dispatching, so the telemetry span is named */compile and the
        # stall is a visible event, not a mystery (the dynamic-batch
        # path recompiles per shape).
        self._seen_step_shapes: set = set()
        self._seen_eval_shapes: set = set()
        # Latched on the first sink-write failure (disk full): telemetry
        # must never abort training, so further metric emission stops
        # (spans, which are in-memory, keep recording).
        self._telemetry_failed = False
        # Perf observatory hook (docs/OBSERVABILITY.md §Perf): when ON
        # and telemetry is attached, one ``phase="perf"`` row per
        # display window carries ms_per_step / emb_per_sec / MFU (from
        # XLA's analytic step FLOPs, obs.perf.costs).  OFF by default —
        # the rows carry wall-clock values, so the sync-vs-pipelined
        # byte-parity contract only covers them when both runs opt in.
        self.perf_metrics = bool(perf_metrics)
        self._step_flops: Optional[float] = None
        self._perf_last: Optional[Tuple[float, int]] = None
        self._last_batch_size: Optional[int] = None
        self._dev_kind: Optional[str] = None
        # Fleet observatory state (docs/OBSERVABILITY.md §Fleet): under
        # fleet-stamped telemetry on a mesh, the first dispatch prices
        # the step's collectives from its HLO (written to
        # fleet_comms.json for `prof --fleet`) and per-step comm marks
        # carry the per-kind payload bytes; ``_step_seq`` numbers the
        # dispatch spans so the offline aggregator can join the i-th
        # span across ranks without trusting ordinal position.
        self._comm_kinds: Optional[list] = None
        self._step_seq: int = 0
        # The loss top's `loss_weight` (reference: cu:435 scales the
        # whole backward by top[0]'s weight; Caffe's objective is the
        # weighted loss).  The shipped template uses 1.
        self.loss_weight = float(loss_weight)
        # Per-parameter lr/decay multipliers ((w_lr, w_decay), (b_lr,
        # b_decay)) — Caffe `param { lr_mult decay_mult }` semantics;
        # the reference template trains biases at 2x lr with no decay
        # (usage/def.prototxt:90-97).  Set BEFORE the cfg property
        # below builds the optimizer.
        self.param_mults = param_mults
        self.mesh = mesh
        self.axis = axis
        # Declarative state sharding (parallel.partition,
        # docs/DISTRIBUTED.md): ordered (regex, PartitionSpec) rules
        # over the flattened state-tree path, first match wins,
        # unmatched leaves LOUD.  None = the shipped replicated table —
        # byte-identical placement to the hand-written
        # NamedSharding(mesh, P()) calls this replaced (parity pinned
        # by tests/test_partition.py).  A 2-D mesh (build_mesh mp>1)
        # plus a table sharding kernels over "mp" is how params scale
        # past replicated.
        self.partition_rules = (tuple(partition_rules)
                                if partition_rules is not None else None)
        # The DCN-aware engine decision (parallel.plan.EnginePlan) the
        # CLI resolved for this run, if any — stamped into the run
        # manifest so "which engine and why" is provenance.
        self.engine_plan = None
        # Loss engine (see docs/DESIGN.md §2): "dense" materializes the
        # pair matrix, "ring" streams it over ppermute hops on a mesh,
        # "blockwise" streams Pallas tiles on a single device (the
        # engine for self-pools too large for the dense matrix).  All
        # three support every mining method (RELATIVE_* via exact
        # streamed radix selection).  ``use_ring`` is the historical
        # spelling of engine="ring".
        if engine is None:
            engine = "ring" if use_ring else "dense"
        elif use_ring and engine != "ring":
            raise ValueError(
                f'use_ring=True contradicts engine={engine!r}'
            )
        if engine not in ("dense", "ring", "blockwise"):
            raise ValueError(f"unknown engine {engine!r}")
        self.engine = engine
        # Streaming engines' fp32 similarity cache (None = auto by size;
        # False forces strict streaming memory) — see ops.pallas_npair /
        # parallel.ring ``sim_cache``.
        self.sim_cache = sim_cache
        # Streaming engines' sparse-positive buffer size (None = auto 8;
        # 0 forces radix selection) — see ``pos_topk`` there.
        self.pos_topk = pos_topk
        # Declarative mixed-precision policy (models.precision): a name
        # ("mxu"/"bf16"/"fp32_parity") or PrecisionPolicy.  The MODEL's
        # dtypes are the model's own business (get_model(policy=...));
        # here the policy supplies the loss engines' gemm precision when
        # ``matmul_precision`` isn't set explicitly, and is recorded so
        # telemetry stamps which recipe a run trained under.
        if precision is not None:
            from npairloss_tpu.models.precision import get_policy

            self.precision_policy = get_policy(precision)
            if matmul_precision is None:
                matmul_precision = \
                    self.precision_policy.loss_matmul_precision
        else:
            self.precision_policy = None
        # Sim/backward gemm MXU precision: None/"highest" = oracle
        # bit-parity; "default" = the ~6x single-pass bf16 throughput
        # mode (ops.npair_loss.resolve_matmul_precision).
        self.matmul_precision = matmul_precision
        self.use_ring = engine == "ring"
        if engine == "ring" and mesh is None:
            raise ValueError('engine="ring" requires a mesh')
        if engine == "blockwise" and mesh is not None:
            raise ValueError(
                'engine="blockwise" is the single-device streaming path; '
                'use engine="ring" to stream across a mesh'
            )
        self.top_ks = tuple(top_ks)
        self.input_shape = tuple(input_shape)
        self.state: Optional[Dict[str, Any]] = None
        self._step_fn = None
        self._eval_fn = None
        # Pipelined-loop state (docs/PIPELINE.md): the ring-carrying
        # jitted step, its device-side reset, and the window's key/
        # capacity bookkeeping — rebuilt whenever cfg changes, like
        # _step_fn.  ``sync_monitor`` is a test/CI hook: an attached
        # pipeline.HostSyncMonitor counts (or, strict, forbids) host
        # transfers outside window boundaries.
        self._pipe_step_fn = None
        self._ring_reset_fn = None
        self._metric_window = None
        self.sync_monitor = None
        self._checkpointer = None
        # Externally requested rollback (the alert→actuation control
        # plane, docs/RESILIENCE.md §Remediation): any thread may set a
        # RollbackRequest via ``request_rollback``; the train loop
        # takes it at its next safe point (sync: per step; pipelined:
        # the window boundary) and restores a pre-incident snapshot.
        self._rollback_request: Optional[RollbackRequest] = None
        self._rollback_lock = threading.Lock()
        # A fresh config per solver: SolverConfig is mutable, so a shared
        # default instance would leak cfg edits across solvers.
        self.cfg = cfg if cfg is not None else SolverConfig()

    # -- config (schedule/optimizer/window are derived; keep them in sync) --

    @property
    def cfg(self) -> SolverConfig:
        return self._cfg

    @cfg.setter
    def cfg(self, cfg: SolverConfig):
        self._cfg = cfg
        self.rate_fn = lr_schedule(
            cfg.lr_policy, cfg.base_lr, cfg.gamma, cfg.stepsize, cfg.power,
            cfg.max_iter, cfg.stepvalues,
        )
        # Direct read: __init__ assigns param_mults before this setter
        # runs (constructor-only — assigning solver.param_mults later
        # does NOT rebuild the optimizer).
        self.tx = caffe_sgd(
            self.rate_fn, cfg.momentum, cfg.weight_decay,
            param_mults=self.param_mults,
        )
        self._loss_window: collections.deque = collections.deque(
            maxlen=max(cfg.average_loss, 1)
        )
        self._step_fn = None  # recompile with the new schedule
        self._eval_fn = None
        self._pipe_step_fn = None
        self._ring_reset_fn = None
        self._metric_window = None

    # -- state ------------------------------------------------------------

    def init(self, example_input: Optional[np.ndarray] = None):
        if example_input is None:
            example_input = np.zeros((2, *self.input_shape), np.float32)
        # One jitted program builds the WHOLE training state — flax init
        # plus the optimizer's zeros-like momentum tree: one compiled
        # program (cached like any other) instead of hundreds of small
        # eager dispatches, each with its own trace + compile.
        def build_state(key, x):
            variables = self.model.init(key, x, train=False)
            return variables, self.tx.init(variables["params"])

        variables, opt = jax.jit(build_state)(
            jax.random.PRNGKey(self.cfg.random_seed),
            jnp.asarray(example_input),
        )
        self.state = self._place_state({
            "params": variables["params"],
            "batch_stats": variables.get("batch_stats", {}),
            "opt": opt,
        })
        return self.state

    # -- declarative state sharding (parallel.partition) -------------------

    def _rules(self):
        """The effective partition ruleset: the caller's table, or the
        shipped all-replicated one (the pre-partition behavior, by
        construction)."""
        if self.partition_rules is not None:
            return self.partition_rules
        from npairloss_tpu.parallel.partition import replicated_rules

        return replicated_rules()

    def _state_shardings(self, state=None):
        """The state tree's NamedShardings, resolved through the rule
        table — THE one source of placement truth: ``_place_state``
        puts with it, the jitted step/eval fns take it as their state
        ``in_shardings``, and ``--dump-partitions`` renders it.  Loud
        (PartitionRuleError) on an unmatched leaf or an axis the mesh
        lacks — at build time, not hours into a run."""
        from npairloss_tpu.parallel.partition import match_partition_shardings

        state = state if state is not None else self.state
        return match_partition_shardings(self._rules(), state, self.mesh)

    def _place_state(self, state):
        """Rule-resolved device placement of a (host or device) state
        tree.  Multi-controller processes each hold the full value
        (identical seeds / identical restores) and contribute their
        addressable shards; single-process is a plain sharded
        device_put.  No mesh: leave placement to jit."""
        if self.mesh is None:
            return state
        from npairloss_tpu.parallel.partition import place_tree

        return place_tree(state, self._state_shardings(state))

    def _abstract_state(self):
        """The state tree as ShapeDtypeStructs, no arrays materialized
        — lets ``partition_table``/``partition_summary`` run before
        ``init()`` (manifest stamping, ``--dump-partitions`` preflight)
        without paying device work."""
        if self.state is not None:
            return self.state

        def build(key, x):
            variables = self.model.init(key, x, train=False)
            return {
                "params": variables["params"],
                "batch_stats": variables.get("batch_stats", {}),
                "opt": self.tx.init(variables["params"]),
            }

        return jax.eval_shape(
            build, jax.random.PRNGKey(self.cfg.random_seed),
            jnp.zeros((2, *self.input_shape), jnp.float32),
        )

    def partition_table(self) -> Dict[str, Any]:
        """The resolved rule -> PartitionSpec table per state leaf,
        with per-rule match counts — zero-match (silent no-op) rules
        flagged.  ``train --dump-partitions`` prints this."""
        from npairloss_tpu.parallel.partition import partition_table

        return partition_table(self._rules(), self._abstract_state(),
                               mesh=self.mesh)

    def partition_summary(self) -> Dict[str, Any]:
        """Manifest-sized digest of :meth:`partition_table`."""
        from npairloss_tpu.parallel.partition import partition_summary

        return partition_summary(self._rules(), self._abstract_state(),
                                 mesh=self.mesh)

    # -- compiled step ----------------------------------------------------

    def apply_model(self, params, batch_stats, inputs, train: bool):
        """Trunk forward in the given mode; returns
        ``(embeddings, new_batch_stats)``.  The single home for the
        variables/mutable-collections plumbing — the jitted train/eval
        steps AND external timers (``cli.py cmd_time``) build on this, so
        a benchmarked graph is the trained graph."""
        variables = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats
            if train:
                emb, updates = self.model.apply(
                    variables, inputs, train=True, mutable=["batch_stats"]
                )
                return emb, updates["batch_stats"]
            return self.model.apply(variables, inputs, train=False), \
                batch_stats
        return self.model.apply(variables, inputs, train=train), batch_stats

    def compute_loss(self, emb, labels):
        """(loss, metrics) through the configured engine — sharded over
        the mesh when one is attached, single-device otherwise.  The
        loss is the OBJECTIVE: scaled by the loss top's ``loss_weight``
        (reference cu:435 semantics), so gradients and the displayed
        loss both carry it."""
        if self.mesh is not None:
            loss, metrics = self._sharded_loss(emb, labels)
        else:
            loss, metrics = self._loss_and_metrics(emb, labels)
        if self.loss_weight != 1.0:
            loss = loss * jnp.float32(self.loss_weight)
        return loss, metrics

    def _loss_and_metrics(self, emb, labels):
        if self.engine == "blockwise":
            from npairloss_tpu.ops.pallas_npair import (
                blockwise_npair_loss_with_aux,
                blockwise_retrieval_metrics,
            )

            loss, _ = blockwise_npair_loss_with_aux(
                emb, labels, self.loss_cfg, sim_cache=self.sim_cache,
                pos_topk=self.pos_topk,
                matmul_precision=self.matmul_precision,
            )
            metrics = blockwise_retrieval_metrics(
                jax.lax.stop_gradient(emb), labels, self.top_ks
            )
            return loss, metrics
        axis = self.axis if self.mesh is not None else None
        loss, aux = npair_loss_with_aux(
            emb, labels, self.loss_cfg, axis_name=axis,
            matmul_precision=self.matmul_precision)
        metrics = retrieval_metrics(
            jax.lax.stop_gradient(aux), labels, jax.lax.stop_gradient(emb),
            self.top_ks,
        )
        if self.health is not None and self.health.pair_hardness:
            # Mined-pair hardness summaries ride the dense engine's loss
            # aux (the streaming engines never materialize it — their
            # health coverage is the norm/magnitude signals).
            metrics.update(pair_hardness_health(
                aux, mining=self.health.mining_health))
        return loss, metrics

    def _sharded_loss(self, emb, labels):
        """Per-shard loss under shard_map; scalars come back stacked (G,)."""

        def per_shard(e, l):
            if self.use_ring:
                from npairloss_tpu.parallel.ring import (
                    ring_npair_loss_and_metrics,
                )

                loss, metrics = ring_npair_loss_and_metrics(
                    e, l, self.loss_cfg, self.axis, self.top_ks,
                    sim_cache=self.sim_cache, pos_topk=self.pos_topk,
                    matmul_precision=self.matmul_precision,
                )
                metrics = {
                    k: v for k, v in metrics.items()
                    if k not in ("ident_num", "diff_num")
                }
            else:
                loss, metrics = self._loss_and_metrics(e, l)
            out = {"loss": loss, **metrics}
            return jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], out)

        stacked = jax.shard_map(
            per_shard,
            mesh=self.mesh,
            in_specs=(P(self.axis), P(self.axis)),
            out_specs=P(self.axis),
        )(emb, labels)
        loss = stacked["loss"].mean()
        metrics = {k: v.mean() for k, v in stacked.items() if k != "loss"}
        return loss, metrics

    def _train_step_body(self):
        def train_step(state, inputs, labels):
            def loss_fn(params):
                emb, new_bs = self.apply_model(
                    params, state["batch_stats"], inputs, train=True
                )
                loss, metrics = self.compute_loss(emb, labels)
                if self.health is not None and \
                        self.health.embedding_magnitude:
                    metrics = {
                        **metrics,
                        **embedding_health(jax.lax.stop_gradient(emb)),
                    }
                return loss, (metrics, new_bs)

            (loss, (metrics, new_bs)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state["params"])
            # The lr reported and the lr applied both read the optimizer's
            # own step counter — a single source of truth.
            metrics["lr"] = self.rate_fn(state["opt"].step)
            # named_scope: the optimizer shows up as its own region in
            # the prof report (obs.perf) instead of bloating (unscoped);
            # metadata-only, the compiled program is unchanged.
            with jax.named_scope("optim/update"):
                upd, opt = self.tx.update(
                    grads, state["opt"], state["params"])
            if self.health is not None:
                # Optimizer-side health signals (obs.health): whole-tree
                # fp32 reductions folded into the same jitted graph.
                with jax.named_scope("health"):
                    metrics.update(
                        update_health(grads, state["params"], upd,
                                      self.health)
                    )
            with jax.named_scope("optim/apply"):
                params = jax.tree_util.tree_map(
                    lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype),
                    state["params"],
                    upd,
                )
            new_state = {
                "params": params,
                "batch_stats": new_bs,
                "opt": opt,
            }
            metrics["loss"] = loss
            return new_state, metrics

        return train_step

    def _eval_step_body(self):
        def eval_step(state, inputs, labels):
            emb, _ = self.apply_model(
                state["params"], state["batch_stats"], inputs, train=False
            )
            loss, metrics = self.compute_loss(emb, labels)
            metrics["loss"] = loss
            return metrics

        return eval_step

    def _make_step(self):
        train_step = self._train_step_body()
        eval_step = self._eval_step_body()
        donate = (0,)
        if self.mesh is not None:
            data_sharding = NamedSharding(self.mesh, P(self.axis))
            # State placement comes from the partition-rule table (one
            # source of truth with _place_state), not hand-placed specs;
            # None (state not built yet) defers to the arguments' own
            # shardings, which _place_state already resolved.
            state_sh = (self._state_shardings()
                        if self.state is not None else None)
            # out_shardings pins the NEW state to the same rule table:
            # without it XLA may propagate a sharded kernel's layout
            # onto e.g. its bias in the OUTPUT, and the next step's
            # input contract breaks (the rules are the invariant, for
            # inputs and outputs alike).
            self._step_fn = jax.jit(
                train_step,
                donate_argnums=donate,
                in_shardings=(state_sh, data_sharding, data_sharding),
                out_shardings=(state_sh, None),
            )
            self._eval_fn = jax.jit(
                eval_step,
                in_shardings=(state_sh, data_sharding, data_sharding),
            )
        else:
            self._step_fn = jax.jit(train_step, donate_argnums=donate)
            self._eval_fn = jax.jit(eval_step)
        # Fresh jitted fns compile every signature anew — reset the
        # compile-capture bookkeeping so telemetry reports them as such.
        self._seen_step_shapes = set()
        self._seen_eval_shapes = set()

    # -- pipelined step (docs/PIPELINE.md) ---------------------------------

    def _pipeline_window_capacity(self, test_active: bool) -> int:
        """Steps between host syncs: the smallest active cadence (a
        window read happens AT every display/test/snapshot step, so the
        ring never needs to span more than the smallest gap), capped by
        ``cfg.pipeline_window``; 64 when no cadence is active."""
        cfg = self.cfg
        cads = [c for c in (
            cfg.display,
            cfg.test_interval if test_active else 0,
            cfg.snapshot,
        ) if c]
        cap = min(cads) if cads else 0
        user = int(cfg.pipeline_window or 0)
        if user:
            cap = min(cap, user) if cap else user
        return max(int(cap) if cap else 64, 1)

    def _make_pipelined_step(self, x, lab, capacity: int):
        """Build the ring-carrying jitted step: the SAME train_step body
        as the synchronous path (parity by construction) plus the
        MetricWindow scatter and the in-graph non-finite streak counter.
        Donation covers state AND the ring AND the batch args — the
        prefetcher guarantees batch buffers are fresh per step, so the
        jitted step can reuse them in place (the sync path cannot make
        that promise: a caller may redispatch one buffer)."""
        from npairloss_tpu.pipeline import MetricWindow

        train_step = self._train_step_body()
        _, metrics_shape = jax.eval_shape(train_step, self.state, x, lab)
        # Pytree dicts flatten key-sorted, so sorted() IS the jitted
        # output dict's iteration order — the key-stream parity anchor.
        window = MetricWindow(sorted(metrics_shape), capacity)

        def pipelined_step(state, ring, inputs, labels):
            new_state, metrics = train_step(state, inputs, labels)
            new_ring = window.update(ring, metrics)
            # ``tick`` is the dispatch controller's completion token:
            # the host holds it across dispatches, so it needs its OWN
            # buffer.  An identity (pos + 0) is folded by XLA and would
            # alias pos — the next step's ring donation then conflicts
            # with the held token on backends that honor donation
            # (TPU).  pos + 1 is a distinct value, hence a distinct
            # buffer, on every backend.
            tick = new_ring["pos"] + jnp.int32(1)
            return new_state, new_ring, tick

        donate = (0, 1, 2, 3)
        if self.mesh is not None:
            data_sharding = NamedSharding(self.mesh, P(self.axis))
            replicated = NamedSharding(self.mesh, P())
            state_sh = (self._state_shardings()
                        if self.state is not None else None)
            # Same out-pinning as _make_step: state stays on the rule
            # table, the ring stays replicated, across every step.
            self._pipe_step_fn = jax.jit(
                pipelined_step,
                donate_argnums=donate,
                in_shardings=(state_sh, replicated,
                              data_sharding, data_sharding),
                out_shardings=(state_sh, replicated, None),
            )
        else:
            self._pipe_step_fn = jax.jit(pipelined_step,
                                         donate_argnums=donate)
        self._ring_reset_fn = jax.jit(window.reset, donate_argnums=(0,))
        self._metric_window = window
        # A rebuilt pipelined step is a NEW program (same policy as
        # _make_step): without this reset, the real compile after a
        # rollback's set_config would be mislabeled step/dispatch and
        # skip the expected-donation-warning filter.
        self._seen_step_shapes = set()

    def _init_ring(self):
        ring = self._metric_window.init_ring()
        if self.mesh is not None:
            ring = jax.device_put(ring, NamedSharding(self.mesh, P()))
        return ring

    def _stage_batch(self, inputs, labels):
        """Device placement for the prefetcher's STAGING THREAD: an
        explicit ``jax.device_put`` with the step's input sharding (so
        the batch arrives resident and the put is visible to the
        syncguard counting shim).  Dtypes are canonicalized to match
        ``_put_batch``'s jnp.asarray semantics — the pipelined and
        synchronous paths must compile identical signatures."""
        if self.mesh is not None and jax.process_count() > 1:
            from npairloss_tpu.parallel.distributed import process_local_batch

            with tracing.span("comm/assemble", staged=True):
                return process_local_batch(
                    self.mesh, (np.asarray(inputs), np.asarray(labels)),
                    self.axis,
                )
        inputs = np.asarray(inputs)
        labels = np.asarray(labels)
        if inputs.dtype == np.float64:
            inputs = inputs.astype(np.float32)
        if labels.dtype == np.int64:
            labels = labels.astype(np.int32)
        if self.mesh is not None:
            sharding = NamedSharding(self.mesh, P(self.axis))
            return jax.device_put((inputs, labels), sharding)
        return jax.device_put((inputs, labels))

    def warmup(self, batch_size: int) -> float:
        """AOT-compile the train step for ``batch_size`` without
        dispatching it (``.lower().compile()`` on shape structs — no
        data, no state mutation); returns the compile seconds.

        With the persistent compilation cache on
        (``pipeline.enable_compile_cache``) this populates it, so the
        first REAL dispatch — and every other process compiling the
        same program — pays deserialization instead of the XLA
        compile."""
        import time as _time

        if self.state is None:
            self.init()
        if self._step_fn is None:
            self._make_step()
        x_sds = jax.ShapeDtypeStruct(
            (int(batch_size), *self.input_shape), jnp.float32
        )
        lab_sds = jax.ShapeDtypeStruct((int(batch_size),), jnp.int32)
        t0 = _time.perf_counter()
        with tracing.span("step/compile", batch=int(batch_size), aot=True):
            self._step_fn.lower(self.state, x_sds, lab_sds).compile()
        return _time.perf_counter() - t0

    def _tel_log(self, phase: str, step: int, metrics, **extra) -> None:
        """Metric emission that can never abort training: a sink-write
        failure (disk full/quota) is reported once, then per-step
        emission latches off for the rest of the run."""
        tel = self.telemetry
        if tel is None or not tel.metrics_enabled or self._telemetry_failed:
            return
        try:
            tel.log(phase, step, metrics, **extra)
        except Exception as e:  # noqa: BLE001 — telemetry is not the run
            self._telemetry_failed = True
            log.error(
                "telemetry metric emission failed (disabling for the "
                "rest of the run): %s", e,
            )

    def _want_perf(self) -> bool:
        tel = self.telemetry
        return (self.perf_metrics and tel is not None
                and tel.metrics_enabled and not self._telemetry_failed)

    def _capture_step_flops(self, fn, args):
        """XLA's analytic per-step FLOPs of the program about to
        dispatch (client-side lowering, no extra compile) — feeds the
        continuous ``perf`` rows' MFU.  Best-effort: a backend without
        cost analysis just means MFU-less rows.  Returns the Lowered
        (or None) so a same-signature fleet-comms capture can reuse it
        instead of paying a second re-trace."""
        from npairloss_tpu.obs.perf.costs import cost_flops

        try:
            # Spanned: the client-side lowering costs a full re-trace
            # (once per signature) and must show in the host timeline
            # as obs overhead, not as unattributed wall time.
            with tracing.span("step/cost_analysis"):
                lowered = fn.lower(*args)
                self._step_flops = cost_flops(lowered)
            return lowered
        except Exception as e:  # noqa: BLE001 — perf rows are optional
            log.debug("step flops estimate unavailable: %s", e)
            return None

    def _device_kind(self) -> str:
        if self._dev_kind is None:
            self._dev_kind = jax.devices()[0].device_kind
        return self._dev_kind

    # -- fleet observatory hooks (docs/OBSERVABILITY.md §Fleet) -----------

    def _fleet_stamp(self):
        """The attached telemetry's FleetStamp, or None — every fleet
        hook below gates on this, so non-fleet runs keep byte-identical
        telemetry streams and span timelines."""
        tel = self.telemetry
        return getattr(tel, "fleet", None) if tel is not None else None

    def _step_span_args(self, batch: int) -> Dict[str, Any]:
        """step/dispatch|compile span args: fleet runs additionally
        stamp the step number so the cross-rank aggregator can join the
        same step's spans across ranks."""
        args: Dict[str, Any] = {"batch": batch}
        if self._fleet_stamp() is not None:
            args["step"] = self._step_seq + 1
        return args

    def _capture_fleet_comms(self, fn, args, lowered=None) -> None:
        """Collective pricing at FIRST DISPATCH under fleet telemetry
        on a mesh (not first compile: telemetry attached after the
        step already compiled — a warmed solver, the mp harness — must
        still capture): extract the compiled step's HLO, price every
        collective per opcode
        (``obs.perf.hlo.collective_bytes_by_opcode``), add the
        analytic grad-sync claim for the SPMD-inserted parameter
        all-reduce, and leave ``fleet_comms.json`` in the run dir for
        ``prof --fleet`` (rank 0 writes; the pricing is identical on
        every rank of an SPMD program).  Costs one extra AOT compile
        of the step — spanned, fleet-opt-in only; ``lowered`` reuses a
        just-captured perf lowering instead of re-tracing.
        Best-effort: a backend that cannot re-lower just means a
        comms-less fleet report."""
        stamp = self._fleet_stamp()
        if stamp is None or self.mesh is None \
                or self._comm_kinds is not None:
            return
        try:
            from npairloss_tpu.obs.fleet import comms as comms_mod
            from npairloss_tpu.obs.fleet.aggregate import COMMS_FILENAME
            from npairloss_tpu.obs.perf.hlo import (
                collective_bytes_by_opcode,
                stage_hlo_text,
            )

            with tracing.span("comm/price", aot=True):
                per_opcode = collective_bytes_by_opcode(
                    stage_hlo_text(
                        lowered if lowered is not None
                        else fn.lower(*args)))
            param_bytes = float(sum(
                leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree_util.tree_leaves(self.state["params"])
            ))
            extra = (comms_mod.grad_sync_claim_bytes(
                param_bytes, stamp.process_count)
                if self.mesh.size > 1 else {})
            payload = {
                "per_opcode": per_opcode,
                "extra_claims": extra,
                "device_kind": self._device_kind(),
                # Collectives crossing host processes ride DCN; a
                # single-process mesh keeps them on-chip/ICI.
                "link": "dcn" if stamp.process_count > 1 else "ici",
                "batch": self._last_batch_size,
                "engine": self.engine,
                "mesh_devices": int(self.mesh.size),
            }
            rows = comms_mod.comm_rows_from_hlo(per_opcode, extra)
            self._comm_kinds = [
                (k["kind"], k["bytes_per_step"], k["claimed"])
                for k in rows["kinds"]
            ]
            if stamp.process_index == 0 and self.telemetry is not None:
                import json as _json
                import os as _os

                path = _os.path.join(self.telemetry.run_dir,
                                     COMMS_FILENAME)
                tmp = path + f".tmp-{_os.getpid()}"
                with open(tmp, "w") as f:
                    _json.dump(payload, f)
                _os.replace(tmp, path)
        except Exception as e:  # noqa: BLE001 — comms rows are optional
            self._comm_kinds = []
            log.debug("fleet comm pricing unavailable: %s", e)

    def _emit_comm_marks(self, step_num: int) -> None:
        """Per-step ``comm/<kind>`` marks carrying the HLO-priced
        payload bytes — the host cannot time an in-graph collective, so
        these are zero-duration accounting marks on the timeline, not
        fabricated durations (the bandwidth math lives offline in
        ``obs.fleet.comms``)."""
        tel = self.telemetry
        if tel is None or not self._comm_kinds:
            return
        for kind, nbytes, claimed in self._comm_kinds:
            tel.instant(f"comm/{kind}", bytes=nbytes, claimed=claimed,
                        step=step_num)

    def _emit_perf_row(self, step_num: int) -> None:
        """One ``phase="perf"`` row per display window: wall clock
        between boundary emissions over the steps they cover (honest in
        BOTH loops — the pipelined window's deferred emission still
        spans the window's dispatched steps)."""
        now = time.perf_counter()
        prev = self._perf_last
        self._perf_last = (now, step_num)
        if prev is None:
            return
        t0, s0 = prev
        steps_n = step_num - s0
        if steps_n <= 0 or now <= t0:
            return
        sec = (now - t0) / steps_n
        row: Dict[str, Any] = {"ms_per_step": round(sec * 1e3, 3)}
        if self._last_batch_size:
            row["emb_per_sec"] = round(self._last_batch_size / sec, 1)
        from npairloss_tpu.obs.perf.costs import mfu_from_timing

        est = mfu_from_timing(flops=self._step_flops, seconds=sec,
                              steps=1, device_kind=self._device_kind())
        if est["mfu"] is not None:
            row["mfu"] = round(est["mfu"], 4)
        if self._step_flops is not None:
            row["step_flops"] = self._step_flops
        self._tel_log("perf", step_num, row)

    def _tel_event(self, kind: str, step: int, **extra) -> None:
        """Resilience events (``retry``/``rollback``/``preempt``/
        ``resume_skip``) through the telemetry pipeline: one metrics row
        with ``phase="event"`` plus an instant marker on the span
        timeline — both no-ops without telemetry attached."""
        tel = self.telemetry
        if tel is None:
            return
        args = {k: v for k, v in extra.items() if v is not None}
        tel.instant(f"resilience/{kind}", **args)
        self._tel_log("event", step, {"event": kind, **args})

    # -- public API -------------------------------------------------------

    def _put_batch(self, inputs, labels):
        """Device placement for one batch.  Multi-process meshes follow
        the reference's per-rank data model (each MPI rank loads its own
        N rows, cu:17-43): the local batch becomes this process's shard
        of the global batch, concatenated in process order."""
        if self.mesh is not None and jax.process_count() > 1:
            from npairloss_tpu.parallel.distributed import process_local_batch

            # The one HOST-side exchange path: assembling this
            # process's rows into the global batch.  Unlike the
            # in-graph collectives (accounting marks only), this has a
            # real host duration — spanned as comm/ so the fleet
            # decomposition sees it.
            with tracing.span("comm/assemble"):
                return process_local_batch(
                    self.mesh, (np.asarray(inputs), np.asarray(labels)),
                    self.axis,
                )
        return jnp.asarray(inputs), jnp.asarray(labels)

    def step(self, inputs: np.ndarray, labels: np.ndarray) -> Dict[str, float]:
        """One training iteration; returns the step's metric dict."""
        if self.state is None:
            # Shape-only init: two examples suffice (and avoid an eager,
            # unsharded full-batch forward on one device).
            self.init(np.asarray(inputs)[:2])
        if self._step_fn is None:
            self._make_step()
        x, lab = self._put_batch(inputs, labels)
        # First-dispatch compile capture: jit compiles synchronously on a
        # new argument signature before the async dispatch, so a span
        # around the call IS the compile time.  A signature seen after
        # the first one is a RECOMPILE (the dynamic-batch path) — marked
        # with an instant event so Perfetto shows it at a glance.
        sig = (tuple(np.shape(x)), tuple(np.shape(lab)))
        compiling = sig not in self._seen_step_shapes
        self._seen_step_shapes.add(sig)
        if self.telemetry is not None and compiling \
                and len(self._seen_step_shapes) > 1:
            self.telemetry.instant("step/recompile", batch=int(np.shape(x)[0]))
        self._last_batch_size = int(np.shape(x)[0])
        lowered = None
        if compiling and self._want_perf():
            lowered = self._capture_step_flops(
                self._step_fn, (self.state, x, lab))
        if compiling:
            # A new signature is a NEW program with new collective
            # payloads (the dynamic-batch tail step is smaller):
            # invalidate so the pricing below re-captures; marks then
            # always carry the CURRENT program's bytes.
            self._comm_kinds = None
        # Self-gated: fleet comms must also capture at the first
        # dispatch AFTER telemetry attaches, which need not be a
        # compile (a warmed solver re-dispatches the same signature).
        self._capture_fleet_comms(self._step_fn, (self.state, x, lab),
                                  lowered=lowered)
        with tracing.span(
            "step/compile" if compiling else "step/dispatch",
            **self._step_span_args(int(np.shape(x)[0])),
        ):
            self.state, metrics = self._step_fn(self.state, x, lab)
        self._step_seq += 1
        self._emit_comm_marks(self._step_seq)
        if debug_checks_enabled():
            # utils.debug switch: validate every step's scalars on host
            # (SURVEY.md §5.2 — the reference had no numeric checks).
            assert_all_finite(metrics, "step metrics")
        return metrics

    def evaluate(
        self, batches: Iterator[Tuple[np.ndarray, np.ndarray]], num_iters: int
    ) -> Dict[str, float]:
        """TEST phase: average loss+metrics over ``num_iters`` batches."""
        acc: Dict[str, float] = collections.defaultdict(float)
        n = 0
        with tracing.span("eval", num_iters=num_iters):
            for _ in range(num_iters):
                inputs, labels = next(batches)
                if self.state is None:
                    self.init(np.asarray(inputs)[:2])
                if self._eval_fn is None:
                    self._make_step()
                x, lab = self._put_batch(inputs, labels)
                sig = (tuple(np.shape(x)), tuple(np.shape(lab)))
                compiling = sig not in self._seen_eval_shapes
                self._seen_eval_shapes.add(sig)
                if compiling:
                    with tracing.span("eval/compile",
                                    batch=int(np.shape(x)[0])):
                        m = self._eval_fn(self.state, x, lab)
                else:
                    m = self._eval_fn(self.state, x, lab)
                for k, v in m.items():
                    acc[k] += float(v)
                n += 1
        out = {k: v / max(n, 1) for k, v in acc.items()}
        if n:
            self._tel_log("eval", self.iteration, out, eval_batches=n)
        return out

    @property
    def iteration(self) -> int:
        """Current solver iteration — the Caffe solverstate ``iter``.

        Reads the optimizer's step counter, which every snapshot persists
        and ``restore_snapshot`` brings back, so display/test/snapshot
        cadence AND the lr schedule resume from the same single source of
        truth (the reference resumes from ``.solverstate`` files the same
        way, solver.prototxt:15-16 semantics).
        """
        if self.state is None:
            return 0
        return int(jax.device_get(self.state["opt"].step))

    def train(
        self,
        train_batches: Iterator[Tuple[np.ndarray, np.ndarray]],
        num_iters: Optional[int] = None,
        test_batches: Optional[Iterator[Tuple[np.ndarray, np.ndarray]]] = None,
        log_fn: Callable[[str], None] = log.info,
        record_fn: Optional[Callable[[Dict[str, Any]], None]] = None,
    ) -> Dict[str, float]:
        """The Caffe Solver::Solve loop: train/display/test/snapshot cadence.

        ``num_iters`` is the TOTAL iteration target (Caffe ``max_iter``):
        a solver restored from the iteration-k snapshot continues at k+1
        and runs ``num_iters - k`` more steps, keeping every cadence
        aligned (next snapshot lands at k + ``snapshot``).

        ``record_fn`` receives one structured dict per display/test/
        snapshot event (``{"event": ..., "iteration": ..., metrics...}``)
        — the machine-readable counterpart of ``log_fn``'s Caffe-style
        text lines (CLI ``--log-json`` writes them as JSONL).
        """
        cfg = self.cfg
        num_iters = num_iters if num_iters is not None else cfg.max_iter
        if cfg.pipeline:
            return self._train_pipelined(
                train_batches, num_iters, test_batches, log_fn, record_fn
            )
        start = self._train_prologue(num_iters, test_batches, log_fn,
                                     record_fn)
        tel = self.telemetry
        last = {}
        guard = (DivergenceGuard(self.divergence)
                 if self.divergence is not None else None)
        try:
            it = start
            while it < num_iters:
                with tracing.span("data/next_batch"):
                    inputs, labels = next(train_batches)
                # Keep metrics as device scalars so the loop never blocks
                # on a host sync; floats are materialized only at display/
                # test/return boundaries (JAX async dispatch keeps the TPU
                # pipeline full) — UNLESS per-step telemetry or the
                # divergence guard is attached; both require materializing
                # here (the recorded cost; see docs/OBSERVABILITY.md).
                metrics = self.step(inputs, labels)
                step_num = int(it) + 1
                if failpoints.should_fire("step.nan_loss"):
                    metrics = dict(metrics)
                    metrics["loss"] = jnp.float32(float("nan"))
                self._loss_window.append(metrics["loss"])
                last = metrics
                if guard is not None and \
                        guard.observe(float(metrics["loss"])):
                    it = self._handle_divergence(
                        guard, step_num, log_fn, record_fn
                    )
                    continue
                req = self._take_rollback_request()
                if req is not None:
                    rolled = self._handle_requested_rollback(
                        req, step_num, log_fn, record_fn)
                    if rolled is not None:
                        it = rolled
                        continue
                self._emit_step_row(step_num, metrics, log_fn, record_fn)
                self._boundary_actions(step_num, test_batches, log_fn,
                                       record_fn)
                it = step_num
        finally:
            self._train_epilogue()
        return {k: float(v) for k, v in last.items()}

    def _train_prologue(self, num_iters, test_batches, log_fn,
                        record_fn) -> int:
        """Shared entry of both train loops: resume logging + the
        iteration-0 TEST pass.  Returns the start iteration."""
        cfg = self.cfg
        start = self.iteration
        # Fleet dispatch spans number steps from the resume point so
        # span step args and row step numbers agree across a restart.
        self._step_seq = start
        if start:
            log_fn(f"resuming from iteration {start}")
            if start >= num_iters:
                log_fn(
                    f"nothing to do: restored iteration {start} >= "
                    f"target {num_iters} (num_iters is the TOTAL "
                    "max_iter target, not an increment)"
                )
        if (
            start == 0
            and cfg.test_initialization
            and test_batches is not None
            and cfg.test_iter > 0
        ):
            m = self.evaluate(test_batches, cfg.test_iter)
            log_fn(f"iter 0 TEST {_fmt(m)}")
            if record_fn is not None:
                record_fn({"event": "test", "iteration": 0,
                           **{k: float(v) for k, v in m.items()}})
        return start

    def _emit_step_row(self, step_num: int, row, log_fn=None,
                       record_fn=None) -> None:
        """Post-guard per-step emission — telemetry row + display line —
        shared by the sync loop, the pipelined window replay, and the
        pending-window flush, so the byte-identical-stream parity
        contract (docs/PIPELINE.md) holds by construction instead of by
        keeping three copies in lockstep.  ``log_fn=None`` (flush path)
        skips display; a pending tail can never contain a display step
        anyway (boundary steps always flush in-loop)."""
        if failpoints.should_fire("train.collapse"):
            # Deterministic embedding-collapse signal
            # (docs/RESILIENCE.md): the health key the collapse
            # watchdog reads goes degenerate in THIS row only —
            # telemetry/display see a collapsing space, the actual
            # training state is untouched.
            row = {**row, "an_threshold_mean": 1.0}
        cfg = self.cfg
        tel = self.telemetry
        if tel is not None and tel.metrics_enabled \
                and not self._telemetry_failed:
            extra: Dict[str, Any] = {}
            if cfg.display and step_num % cfg.display == 0 \
                    and tel.tracer is not None and tel.tracer.dropped:
                # The tracer cap is eating spans: surface the drop
                # count in the display-window row (the serve window
                # rows' spans_dropped contract, uniform for training)
                # instead of letting the host timeline silently go
                # partial.  Absent unless drops happened, so ordinary
                # runs keep byte-identical streams.
                extra["spans_dropped"] = tel.tracer.dropped
            self._tel_log("train", step_num,
                          {k: float(v) for k, v in row.items()}, **extra)
        if self._want_perf() and cfg.display \
                and step_num % cfg.display == 0:
            # Continuous perf/mfu rows at display cadence (a pending-
            # window flush can never contain a display step, so the
            # log_fn=None path never reaches here).
            self._emit_perf_row(step_num)
        if log_fn is not None and cfg.display \
                and step_num % cfg.display == 0:
            host = {k: float(v) for k, v in row.items()}
            avg = float(jnp.stack(list(self._loss_window)).mean())
            log_fn(
                f"iter {step_num} lr={host.get('lr', 0):.6g} "
                f"loss={avg:.6g} (avg over {len(self._loss_window)}) "
                + _fmt({k: v for k, v in host.items()
                        if k not in ('loss', 'lr')})
            )
            if record_fn is not None:
                record_fn({"event": "display", "iteration": step_num,
                           "loss_avg": avg, **host})

    def _boundary_actions(self, step_num: int, test_batches, log_fn,
                          record_fn) -> None:
        """The test/snapshot/preempt cadence block shared by both train
        loops (the pipelined loop runs it only at window boundaries —
        which is no restriction, since those cadences force a boundary).
        Raises :class:`TrainingPreempted` on a requested preemption:
        the in-flight step finished above; commit an emergency snapshot
        (unless the cadence just did) and surface a typed stop the CLI
        maps to EXIT_PREEMPTED for the supervisor."""
        cfg = self.cfg
        if (
            test_batches is not None
            and cfg.test_interval
            and step_num % cfg.test_interval == 0
        ):
            m = self.evaluate(test_batches, cfg.test_iter)
            log_fn(f"iter {step_num} TEST {_fmt(m)}")
            if record_fn is not None:
                record_fn({"event": "test", "iteration": step_num,
                           **{k: float(v) for k, v in m.items()}})
        snapped = None
        if cfg.snapshot and step_num % cfg.snapshot == 0:
            snapped = self.save_snapshot(step_num)
            if record_fn is not None:
                record_fn({"event": "snapshot",
                           "iteration": step_num})
        if self.preempt is not None and self.preempt.requested:
            path = snapped or self.save_snapshot(step_num)
            log_fn(
                f"preempted at iter {step_num}: emergency "
                f"snapshot {path}; relaunch with --resume auto"
            )
            self._tel_event("preempt", step_num,
                            snapshot=path,
                            signum=self.preempt.signum)
            if record_fn is not None:
                record_fn({"event": "preempt",
                           "iteration": step_num,
                           "snapshot": path})
            raise TrainingPreempted(
                step_num, snapshot_path=path,
                signum=self.preempt.signum,
            )

    def _train_epilogue(self) -> None:
        """Shared exit of both train loops — EVERY exit path (normal
        completion, preemption, a raised step error) must land in-flight
        Orbax work before the process can exit, or the last snapshot is
        left as an .orbax-checkpoint-tmp dir.  Guarded: cleanup must not
        mask the in-flight exception."""
        if self._checkpointer is not None:
            try:
                self._checkpointer.wait_until_finished()
            except Exception as e:  # noqa: BLE001
                log.error("checkpointer drain failed: %s", e)
        if self.telemetry is not None:
            # Land metrics.jsonl/trace.json even when the owner forgets
            # close() — flush is idempotent and the owner may keep
            # logging.  Guarded like _tel_log: a full disk must not
            # swallow a completed run's final metrics.
            try:
                self.telemetry.flush()
            except Exception as e:  # noqa: BLE001
                log.error("telemetry flush failed: %s", e)

    def _train_pipelined(self, train_batches, num_iters, test_batches,
                         log_fn, record_fn) -> Dict[str, float]:
        """The sync-free counterpart of the loop above (docs/PIPELINE.md).

        Steady state does NO host transfers: batches arrive device-
        resident from the prefetcher's staging thread, the jitted step
        scatters its scalars into a device-side ring, and the host reads
        the whole window back in one ``device_get`` only at display/
        test/snapshot boundaries (``step/window_sync`` span).  Per-step
        records (telemetry rows, the loss window, display lines, the
        divergence guard's observations) are reconstructed from the ring
        at the boundary with IDENTICAL keys/values to the synchronous
        loop — only their wall-clock emission time is deferred (bounded
        staleness: at most ``_pipeline_window_capacity()`` steps).
        Dispatch depth is bounded by ``cfg.pipeline_depth`` so async
        dispatch cannot queue unboundedly against a wedging backend.
        """
        from npairloss_tpu.pipeline import (
            DevicePrefetcher,
            DispatchController,
            monitor_from_env,
        )

        cfg = self.cfg
        if self.state is None:
            self.init()
        if self._eval_fn is None:
            # Build the sync/eval fns up front: a lazy _make_step inside
            # a mid-run evaluate() would reset the compile-capture
            # bookkeeping and mislabel the next dispatch as a compile.
            self._make_step()
        start = self._train_prologue(num_iters, test_batches, log_fn,
                                     record_fn)
        tel = self.telemetry
        guard = (DivergenceGuard(self.divergence)
                 if self.divergence is not None else None)
        mon = (self.sync_monitor if self.sync_monitor is not None
               else monitor_from_env())

        def allowed():
            return (mon.allowed() if mon is not None
                    else contextlib.nullcontext())

        depth = max(int(cfg.pipeline_depth), 1)
        window_cap = self._pipeline_window_capacity(test_batches is not None)
        controller = DispatchController(depth)
        prefetcher = DevicePrefetcher(
            train_batches, self._stage_batch, depth=depth
        )
        last: Dict[str, Any] = {}
        ring = None
        it = start
        window_start = it + 1
        poisoned: list = []  # step.nan_loss fires, host-side
        try:
            with warnings.catch_warnings(), \
                    (mon if mon is not None else contextlib.nullcontext()):
                # Batch-arg donation is best-effort: backends that
                # cannot alias the batch buffers (CPU) fall back to
                # copies, and XLA's per-compile warning about it is
                # expected, not a bug.  ONE filter for the whole loop
                # (a per-step catch_warnings would copy global filter
                # state on the hot path), covering sharding-keyed
                # recompiles the shape heuristic cannot predict.
                warnings.filterwarnings(
                    "ignore",
                    message="Some donated buffers were not usable",
                )
                while it < num_iters:
                    with tracing.span("data/next_batch", staged=True):
                        x, lab = prefetcher.get()
                    if self._pipe_step_fn is None:
                        with allowed():
                            self._make_pipelined_step(x, lab, window_cap)
                    if ring is None:
                        with allowed():
                            ring = self._init_ring()
                    # The loop's one blocking point in steady state:
                    # the oldest in-flight step's completion token.
                    with tracing.span("step/device_wait"):
                        controller.reserve()
                    sig = (tuple(np.shape(x)), tuple(np.shape(lab)))
                    compiling = sig not in self._seen_step_shapes
                    self._seen_step_shapes.add(sig)
                    if tel is not None and compiling \
                            and len(self._seen_step_shapes) > 1:
                        tel.instant("step/recompile",
                                    batch=int(np.shape(x)[0]))
                    self._last_batch_size = int(np.shape(x)[0])
                    lowered = None
                    if compiling and self._want_perf():
                        lowered = self._capture_step_flops(
                            self._pipe_step_fn, (self.state, ring, x, lab))
                    if compiling:
                        # New signature = new collective payloads;
                        # re-price (see the sync loop).
                        self._comm_kinds = None
                    self._capture_fleet_comms(
                        self._pipe_step_fn, (self.state, ring, x, lab),
                        lowered=lowered)
                    cache_size = getattr(self._pipe_step_fn,
                                         "_cache_size", lambda: None)
                    n_before = cache_size()
                    with tracing.span(
                        "step/compile" if compiling else "step/dispatch",
                        pipeline=True,
                        **self._step_span_args(int(np.shape(x)[0])),
                    ):
                        self.state, ring, tick = self._pipe_step_fn(
                            self.state, ring, x, lab
                        )
                    if (tel is not None and not compiling
                            and n_before is not None
                            and cache_size() != n_before):
                        # The executable cache grew under an already-
                        # seen shape: a sharding/aval-keyed recompile
                        # the heuristic mislabeled step/dispatch —
                        # surface the stall in the trace anyway.
                        tel.instant("step/recompile",
                                    batch=int(np.shape(x)[0]),
                                    keyed="sharding")
                    controller.admit(tick)
                    step_num = int(it) + 1
                    self._step_seq = step_num
                    self._emit_comm_marks(step_num)
                    if failpoints.should_fire("step.nan_loss"):
                        # The sync loop poisons the OBSERVED loss on
                        # host (state untouched); here the observation
                        # lives in the ring, so remember the step and
                        # poison the row at window-read time.
                        poisoned.append(step_num)
                    it = step_num
                    preempt_now = (self.preempt is not None
                                   and self.preempt.requested)
                    boundary = (
                        (cfg.display and step_num % cfg.display == 0)
                        or (test_batches is not None and cfg.test_interval
                            and step_num % cfg.test_interval == 0)
                        or (cfg.snapshot and step_num % cfg.snapshot == 0)
                        or (step_num - window_start + 1 >= window_cap)
                        or step_num >= num_iters
                        or preempt_now
                    )
                    if not boundary:
                        continue
                    # ---- window boundary: the ONE host sync ----------
                    with allowed():
                        with tracing.span(
                            "step/window_sync",
                            steps=step_num - window_start + 1,
                        ):
                            host_ring = jax.device_get(ring)
                            ring = self._ring_reset_fn(ring)
                        with tracing.span(
                            "step/window_rows",
                            steps=step_num - window_start + 1,
                        ):
                            rows = self._metric_window.read(host_ring)
                            for s in poisoned:
                                rows[s - window_start]["loss"] = \
                                    np.float32("nan")
                            # The in-graph counter IS the window-edge trip
                            # check: max_streak == 0 proves every loss in
                            # (or carried into) this window was finite, so
                            # the guard's per-row replay below can be
                            # skipped wholesale.  Host-side poison
                            # (step.nan_loss) is invisible to the device
                            # counter, hence the OR on ``poisoned`` — and
                            # on guard.streak, so an all-finite window
                            # still replays to RESET a streak a previous
                            # window's poison left in flight.
                            nonfinite_seen = bool(poisoned) or \
                                int(host_ring["max_streak"]) > 0 or \
                                (guard is not None and guard.streak > 0)
                            tripped = None
                            for off, row in enumerate(rows):
                                s = window_start + off
                                self._loss_window.append(row["loss"])
                                last = row
                                if guard is not None and nonfinite_seen and \
                                        guard.observe(float(row["loss"])):
                                    tripped = s
                                    break
                                self._emit_step_row(s, row, log_fn, record_fn)
                        if tripped is not None:
                            # In-graph counter + window replay agreed the
                            # streak crossed patience; the steps already
                            # dispatched past the trip are discarded (the
                            # documented bounded-staleness cost) and the
                            # rollback machinery runs unchanged.
                            controller.drain()
                            it = self._handle_divergence(
                                guard, tripped, log_fn, record_fn
                            )
                            ring = None  # cfg may have been replaced
                            window_start = it + 1
                            poisoned = []
                            continue
                        req = self._take_rollback_request()
                        if req is not None:
                            # Same safe point as the divergence trip:
                            # drain in-flight dispatches, then restore.
                            controller.drain()
                            rolled = self._handle_requested_rollback(
                                req, step_num, log_fn, record_fn)
                            if rolled is not None:
                                it = rolled
                                ring = None  # cfg may have been replaced
                                window_start = it + 1
                                poisoned = []
                                continue
                        self._boundary_actions(step_num, test_batches,
                                               log_fn, record_fn)
                    window_start = step_num + 1
                    poisoned = []
        finally:
            prefetcher.close()
            last = self._flush_pending_window(ring, window_start,
                                              poisoned, last)
            self._train_epilogue()
        return {k: float(v) for k, v in last.items()}

    def _flush_pending_window(self, ring, window_start: int, poisoned,
                              last):
        """Salvage the un-flushed tail of a window on an abnormal exit
        (data exhaustion, a staging-thread error, a raised step error)
        — the synchronous loop would already have emitted these rows,
        and the deferred-emission contract (docs/PIPELINE.md) promises
        only their TIMING differs.  Boundary steps always flush
        in-loop, so a pending tail can never contain a display/test/
        snapshot step: telemetry rows + the loss window are the whole
        debt.  Best-effort — teardown must not mask the in-flight
        exception."""
        if ring is None or self._metric_window is None:
            return last
        try:
            # The same two spans as the in-loop boundary: the read-back
            # waits for every step still in flight.
            with tracing.span("step/window_sync", tail=True):
                host_ring = jax.device_get(ring)
            with tracing.span("step/window_rows", tail=True):
                rows = self._metric_window.read(host_ring)
                for s in poisoned:
                    if 0 <= s - window_start < len(rows):
                        rows[s - window_start]["loss"] = np.float32("nan")
                for off, row in enumerate(rows):
                    s = window_start + off
                    self._loss_window.append(row["loss"])
                    last = row
                    self._emit_step_row(s, row)
        except Exception as e:  # noqa: BLE001
            log.error("pending-window flush failed: %s", e)
        return last

    def _handle_divergence(self, guard, step_num: int, log_fn,
                           record_fn) -> int:
        """Guard tripped at ``step_num``: roll back to the newest valid
        snapshot (optionally lr-scaled) or halt.  Returns the iteration
        to continue from."""
        dcfg = self.divergence
        reason = (f"{guard.streak} consecutive non-finite losses "
                  f"at iteration {step_num}")
        if dcfg.action != "rollback" or guard.rollbacks >= dcfg.max_rollbacks:
            why = (reason if dcfg.action != "rollback"
                   else f"{reason} (rollback budget "
                        f"{dcfg.max_rollbacks} exhausted)")
            self._tel_event("divergence_halt", step_num, reason=why)
            raise DivergenceError(f"training diverged: {why}")
        guard.rollbacks += 1
        # A snapshot taken during the non-finite streak captured poisoned
        # params — and so may the one right before it: the first NaN loss
        # at step f implicates the update of step f-1 (finite loss does
        # not guarantee finite grads).  Only snapshots strictly older
        # than f-1 are trustworthy rollback targets.
        max_step = step_num - guard.streak - 1
        guard.streak = 0
        restored = self.restore_auto(max_step=max_step)
        if restored is None:
            raise DivergenceError(
                f"training diverged ({reason}) and no valid snapshot "
                f"at iteration <= {max_step} under "
                f"{self.cfg.snapshot_prefix!r} to roll back to"
            )
        # The excluded snapshots are checksum-valid but NaN-poisoned:
        # left in place, a later crash + --resume auto would restore
        # them newest-first and dive straight back into divergence.
        quarantine_snapshots(self.cfg.snapshot_prefix, max_step)
        resumed = self._post_restore(dcfg.lr_scale)
        msg = (f"divergence: {reason}; rolled back to iteration {resumed} "
               f"({restored}), lr={self.cfg.base_lr:.6g} "
               f"[rollback {guard.rollbacks}/{dcfg.max_rollbacks}]")
        log.warning(msg)
        log_fn(msg)
        self._tel_event("rollback", step_num, to_iteration=resumed,
                        snapshot=restored, base_lr=float(self.cfg.base_lr),
                        rollback=guard.rollbacks)
        if record_fn is not None:
            record_fn({"event": "rollback", "iteration": step_num,
                       "to_iteration": resumed, "snapshot": restored})
        return resumed

    def _post_restore(self, lr_scale: float) -> int:
        """Shared tail of BOTH rollback paths (divergence + requested):
        apply the lr damp — the cfg setter rebuilds schedule + optimizer
        and drops the jitted step, so the scaled lr takes effect at
        recompile — or, cfg unchanged, clear the poisoned loss window by
        hand; then re-anchor fleet span numbering at the restored
        iteration (the next dispatch is resumed+1 again).  One copy, so
        a future field that must reset after a restore cannot miss a
        path."""
        if lr_scale != 1.0:
            self.cfg = dataclasses.replace(
                self.cfg, base_lr=self.cfg.base_lr * lr_scale
            )
        else:
            self._loss_window.clear()
        resumed = self.iteration
        self._step_seq = resumed
        return resumed

    # -- requested rollback (alert→actuation, docs/RESILIENCE.md) ----------

    def request_rollback(self, request: RollbackRequest) -> None:
        """Ask the train loop to roll back at its next safe point — the
        remediation action for health-signal alerts (embedding
        collapse).  Thread-safe: the live-obs tick thread sets it, the
        loop takes it.  A second request before the first is taken
        replaces it (the newer alert context wins)."""
        with self._rollback_lock:
            self._rollback_request = request

    def _take_rollback_request(self) -> Optional[RollbackRequest]:
        if self._rollback_request is None:  # cheap pre-check, hot path
            return None
        with self._rollback_lock:
            req, self._rollback_request = self._rollback_request, None
            return req

    def _handle_requested_rollback(self, req: RollbackRequest,
                                   step_num: int, log_fn,
                                   record_fn) -> Optional[int]:
        """Execute a requested rollback: restore the newest valid
        snapshot COMMITTED before ``req.before_wall_time`` (a snapshot
        captured mid-incident is not a recovery target).  Unlike the
        divergence path this never quarantines (a health-signal
        collapse leaves finite, checksum-honest params — post-mortem
        wants them restorable) and SKIPS gracefully when no qualifying
        snapshot exists: the remediation engine's budget owns retries,
        so a skip is a telemetry event and training continues, never a
        halt.  Returns the resumed iteration, or None on a skip."""
        max_step = step_num - 1
        if req.before_wall_time is not None:
            qualifying = []
            for step, path in list_snapshots(self.cfg.snapshot_prefix):
                if step > max_step:
                    continue
                created = snapshot_info(path)["created"]
                if created is not None and created < req.before_wall_time:
                    qualifying.append(step)
            if not qualifying:
                msg = (f"rollback request ({req.reason}) skipped: no "
                       f"snapshot under {self.cfg.snapshot_prefix!r} "
                       f"predates the incident")
                log.warning(msg)
                log_fn(msg)
                self._tel_event("rollback_skip", step_num,
                                reason=req.reason)
                return None
            max_step = max(qualifying)
        restored = self.restore_auto(max_step=max_step)
        if restored is None:
            msg = (f"rollback request ({req.reason}) skipped: no valid "
                   f"snapshot at iteration <= {max_step}")
            log.warning(msg)
            log_fn(msg)
            self._tel_event("rollback_skip", step_num, reason=req.reason)
            return None
        resumed = self._post_restore(req.lr_scale)
        msg = (f"remediation rollback ({req.reason}): rolled back to "
               f"iteration {resumed} ({restored}), "
               f"lr={self.cfg.base_lr:.6g}")
        log.warning(msg)
        log_fn(msg)
        self._tel_event("rollback", step_num, to_iteration=resumed,
                        snapshot=restored,
                        base_lr=float(self.cfg.base_lr),
                        requested=True, reason=req.reason)
        if record_fn is not None:
            record_fn({"event": "rollback", "iteration": step_num,
                       "to_iteration": resumed, "snapshot": restored,
                       "requested": True})
        return resumed

    # -- checkpointing (Orbax; Caffe snapshot contract) --------------------

    def _ckpt(self):
        if self._checkpointer is None:
            import orbax.checkpoint as ocp

            self._checkpointer = ocp.StandardCheckpointer()
        return self._checkpointer

    def snapshot_path(self, step: int) -> str:
        import os

        prefix = self.cfg.snapshot_prefix
        parent = os.path.dirname(os.path.abspath(prefix))
        os.makedirs(parent, exist_ok=True)
        return os.path.abspath(f"{prefix}iter_{step}.ckpt")

    def save_snapshot(self, step: int) -> str:
        """Commit the snapshot for ``step`` atomically (tmp dir +
        checksum manifest + rename — resilience.snapshot), retrying
        transient I/O under ``snapshot_retry``, then apply retention GC
        (``cfg.snapshot_max_keep``).

        Multi-controller runs cannot use the tmp-dir commit (Orbax's
        ``save`` is a collective every rank must enter with the SAME
        path, and per-rank tmp dirs would race the rename): they rely
        on Orbax's own multihost tmp/rename atomicity on the final
        path, with rank 0 adding the manifest after the save lands — a
        crash in that window leaves a committed-but-manifest-less dir,
        which auto-resume conservatively skips.
        """
        path = self.snapshot_path(step)
        if jax.process_count() > 1:
            with tracing.span("snapshot", step=step):
                self._ckpt().save(path, self.state, force=True)
                self._ckpt().wait_until_finished()
                if jax.process_index() == 0:
                    write_manifest(path, step, state_checksums(self.state))
                    gc_snapshots(self.cfg.snapshot_prefix,
                                 self.cfg.snapshot_max_keep)
            log.info("snapshot -> %s", path)
            return path

        def on_retry(attempt, delay, exc):
            self._tel_event("retry", step, op="snapshot.save",
                            attempt=attempt, delay_s=round(delay, 3),
                            error=str(exc))

        with tracing.span("snapshot", step=step):
            commit_snapshot(
                self._ckpt(), path, self.state, step,
                policy=self.snapshot_retry, on_retry=on_retry,
            )
        log.info("snapshot -> %s", path)
        gc_snapshots(self.cfg.snapshot_prefix, self.cfg.snapshot_max_keep)
        return path

    def load_params(self, params, batch_stats=None):
        """Start from externally-loaded parameters (the pretrained-weights
        finetune workflow — e.g. a migrated .caffemodel trunk).

        Structure/shape must match the model's own init tree (enforced by
        the tree_map below — a silent partial load corrupts finetunes);
        values are cast to the model's dtypes.  The optimizer state
        re-initializes (fresh momentum); ``batch_stats`` (BN trunks:
        migrated running mean/var) replace the init stats when given.
        """
        if self.state is None:
            self.init()
        cur = self.state["params"]
        new = jax.tree_util.tree_map(
            lambda c, n: jnp.asarray(np.asarray(n), dtype=c.dtype),
            cur,
            params,
        )
        state = dict(self.state)
        state["params"] = new
        state["opt"] = self.tx.init(new)
        if batch_stats is not None:
            state["batch_stats"] = jax.tree_util.tree_map(
                lambda c, n: jnp.asarray(np.asarray(n), dtype=c.dtype),
                self.state["batch_stats"],
                batch_stats,
            )
        self.state = self._place_state(state)
        return self.state

    def load_caffe_solverstate(self, path: str, model_name: str = "googlenet"):
        """Resume the OPTIMIZER from a Caffe ``.solverstate`` — momentum
        history + iteration, the ``caffe train --snapshot`` semantics
        (solver.prototxt:15-16).  Weights come separately (the paired
        .caffemodel via ``load_params``/--weights); call this after
        them, since ``load_params`` re-initializes the optimizer.

        GoogLeNet trunks only (the reference's flagship,
        def.prototxt:1): history blobs are unnamed and ordered by net
        parameter order, which the GoogLeNet layer map pins down.
        """
        if model_name.lower() != "googlenet":
            # Exactly the plain trunk: the MXU variants (s2d/fused/mxu)
            # and the BN trunk have different param trees the unnamed
            # positional history cannot map onto — and a genuine Caffe
            # solverstate only ever comes from the reference's plain
            # def.prototxt net anyway.  Resume on plain `googlenet`,
            # then switch variants via the weight converters.
            raise NotImplementedError(
                "solverstate migration is defined for the plain "
                f"GoogLeNet trunk only (got model {model_name!r}): "
                "Caffe history blobs are unnamed and positional; resume "
                "with --model googlenet"
            )
        from npairloss_tpu.config.caffemodel import parse_solverstate
        from npairloss_tpu.models.caffe_import import (
            googlenet_momentum_from_history,
        )

        if self.state is None:
            self.init()
        with open(path, "rb") as f:
            st = parse_solverstate(f.read())
        mom, skipped = googlenet_momentum_from_history(
            st["history"], self.state["opt"].momentum_buf
        )
        if skipped:
            log.info(
                "solverstate: skipped %d non-trunk history blobs "
                "(aux-classifier params of the full training net)",
                skipped,
            )
        mom = jax.tree_util.tree_map(
            lambda c, n: jnp.asarray(np.asarray(n), dtype=c.dtype),
            self.state["opt"].momentum_buf,
            mom,
        )
        state = dict(self.state)
        state["opt"] = CaffeSGDState(
            momentum_buf=mom, step=jnp.asarray(int(st["iter"]), jnp.int32)
        )
        self.state = self._place_state(state)
        return int(st["iter"])

    def _resume_rank(self) -> int:
        """This process's rank for multi-writer snapshot coordination:
        jax's own when a multi-controller runtime is up, else the
        declared harness rank (``NPAIRLOSS_FLEET_PROCESS``), else 0.
        Non-zero ranks WAIT on rank 0's manifest instead of reading a
        just-committed multihost save as torn (docs/DISTRIBUTED.md)."""
        from npairloss_tpu.obs.fleet.stamp import resolved_process

        return resolved_process()[0]

    def restore_snapshot(self, path: str):
        """Restore an explicit snapshot path (retrying transient I/O).

        When the snapshot carries a commit manifest, the restored tree
        is checksum-verified against it — a corrupt snapshot raises
        ``SnapshotValidationError`` instead of silently resuming from
        garbage.  Manifest-less dirs (pre-resilience snapshots, raw
        Orbax trees) restore unverified, preserving the old contract —
        but a NON-ZERO rank first waits out the multihost commit race
        (rank 0 writes the manifest after the collective save lands)
        before concluding the dir is legacy.
        """
        if self.state is None:
            self.init()
        self._ckpt().wait_until_finished()
        if self._resume_rank() != 0:
            try:
                validate_snapshot_wait(path, self.snapshot_retry)
            except Exception:  # noqa: BLE001 — verdict below, per contract
                pass

        def do_restore():
            failpoints.fire("snapshot.restore.io")
            return self._ckpt().restore(path, self.state)

        def on_retry(attempt, delay, exc):
            self._tel_event("retry", 0, op="snapshot.restore",
                            attempt=attempt, delay_s=round(delay, 3),
                            error=str(exc))

        state = call_with_retry(
            do_restore, self.snapshot_retry,
            describe=f"snapshot restore ({path})", on_retry=on_retry,
        )
        try:
            manifest = read_manifest(path)
        except FileNotFoundError:
            # Legacy contract: manifest-less dirs (pre-resilience
            # snapshots, raw Orbax trees) restore unverified.
            log.info("restored %s without checksum verification "
                     "(no commit manifest)", path)
        except (OSError, ValueError) as e:
            # A manifest that EXISTS but cannot be read/parsed is
            # corruption — exactly what verification exists to catch.
            raise SnapshotValidationError(
                f"unreadable manifest in {path}: {e}"
            ) from e
        else:
            verify_restored(state, manifest)
        self.state = state
        return self.state

    def restore_auto(self, max_step: Optional[int] = None) -> Optional[str]:
        """Scan ``cfg.snapshot_prefix`` and restore the newest *valid*
        snapshot: manifests are validated newest-first, the restored
        tree checksum-verified, and torn/corrupt candidates skipped with
        a logged reason.  ``max_step`` bounds the candidates (divergence
        rollback must not restore a snapshot captured during the
        non-finite streak).  Returns the restored path, or None (fresh
        start) when no valid snapshot exists."""
        if self.state is None:
            self.init()
        self._ckpt().wait_until_finished()
        prefix = self.cfg.snapshot_prefix
        rank = self._resume_rank()
        for step, path in reversed(list_snapshots(prefix)):
            if max_step is not None and step > max_step:
                continue
            try:
                # A non-zero rank can scan this dir BETWEEN the
                # collective Orbax save landing and rank 0 writing
                # manifest.json; waiting (the shared retry/backoff)
                # turns that race into a pause instead of skipping a
                # perfectly valid snapshot as torn.  Rank 0 never
                # waits: for it a missing manifest IS a torn commit.
                manifest = (validate_snapshot_wait(path,
                                                   self.snapshot_retry)
                            if rank != 0 else validate_snapshot(path))

                def do_restore(path=path):
                    failpoints.fire("snapshot.restore.io")
                    return self._ckpt().restore(path, self.state)

                state = call_with_retry(
                    do_restore, self.snapshot_retry,
                    describe=f"snapshot restore ({path})",
                )
                verify_restored(state, manifest)
            except Exception as e:  # noqa: BLE001 — skip, try the next
                log.warning("resume: skipping snapshot %s: %s", path, e)
                self._tel_event("resume_skip", step, snapshot=path,
                                reason=str(e))
                continue
            self.state = state
            log.info("resume: restored %s (iteration %d)", path, step)
            return path
        log.info("resume: no valid snapshot under prefix %r — starting "
                 "fresh", prefix)
        return None


def restore_for_inference(
    path: str,
    retry: Optional[RetryPolicy] = None,
) -> Dict[str, Any]:
    """Snapshot -> ``{"params", "batch_stats"}`` for the serving path.

    The snapshot->inference direction, split out of the Solver: serving
    (``serve.QueryEngine``) needs the model variables from a committed
    training snapshot but must not drag in the optimizer rebuild, the
    schedule, or a Solver instance.  Raw Orbax restore (no target tree),
    retried like ``Solver.restore_snapshot``, with the params/batch_stats
    SUBSET checksum-verified against the commit manifest — the optimizer
    leaves are skipped both because inference never touches them and
    because the raw restore rehydrates the opt NamedTuple as a plain
    dict, which would shift every keystr.  Manifest-less dirs restore
    unverified (the legacy contract).
    """
    import os

    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    ckpt = ocp.StandardCheckpointer()

    def do_restore():
        failpoints.fire("snapshot.restore.io")
        return ckpt.restore(path)

    state = call_with_retry(
        do_restore, retry if retry is not None else RetryPolicy(),
        describe=f"inference restore ({path})",
    )
    if not isinstance(state, dict) or "params" not in state:
        raise SnapshotValidationError(
            f"{path} does not look like a training snapshot "
            "(no 'params' subtree)"
        )
    infer = {
        "params": state["params"],
        "batch_stats": state.get("batch_stats") or {},
    }
    try:
        manifest = read_manifest(path)
    except FileNotFoundError:
        log.info("restored %s for inference without checksum "
                 "verification (no commit manifest)", path)
    except (OSError, ValueError) as e:
        raise SnapshotValidationError(
            f"unreadable manifest in {path}: {e}"
        ) from e
    else:
        prefixes = ("['params']", "['batch_stats']")
        subset = {
            k: v for k, v in manifest.get("arrays", {}).items()
            if k.startswith(prefixes)
        }
        verify_restored(infer, {"arrays": subset})
    return infer


def snapshot_info(path: str) -> Dict[str, Any]:
    """Freshness identity of a committed snapshot (docs/OBSERVABILITY.md
    §Live observatory): ``{"path", "step", "created"}`` from the commit
    manifest — no array loads, no Solver.  ``step``/``created`` are
    None for manifest-less dirs (pre-resilience snapshots), so the
    serving path can still report WHICH snapshot it restored even when
    it cannot date it."""
    import os

    out: Dict[str, Any] = {
        "path": os.path.abspath(path), "step": None, "created": None,
    }
    try:
        manifest = read_manifest(path)
    except (OSError, ValueError):
        return out
    step = manifest.get("step")
    created = manifest.get("created")
    if isinstance(step, int):
        out["step"] = step
    if isinstance(created, (int, float)):
        out["created"] = float(created)
    return out


def _fmt(metrics: Dict[str, float]) -> str:
    return " ".join(f"{k}={float(v):.4g}" for k, v in sorted(metrics.items()))
