"""Benchmark rows: the flagship training step, the loss engines at a
4096 pool, the retrieval server at 4096 and 1M rows, and the
batch-scaling sweep.

Everything runs in ONE process on the device JAX finds (a chip belongs
to one process at a time).  The record is stamped with ``platform`` /
``device_kind`` / ``device_count``.  With no accelerator the script
prints no record and exits 2 — unless ``--platform cpu`` asks for the
CPU by name, in which case the record says ``"platform": "cpu"`` and
is not a device measurement.  A row that raises is recorded as
``{"error": ...}`` and the exit code is 1.  Nothing here reports a
number from another run or another platform.

Timing is the host clock around dispatches that end in
``jax.block_until_ready``; every window's time is published beside the
min.  The loss-engine rows time ``steps`` loss+grad evaluations inside
one jitted ``lax.scan`` (inputs perturbed per iteration so none can be
CSE'd or elided).  MFU comes from XLA's per-step FLOPs estimate
(``obs.perf.costs.mfu_from_timing``) against the chip's table peak.

These rows are raw material.  Which cells, metrics and regression
bounds the repo is judged by is for the benchmark PR (ROADMAP S0) to
define; the reference publishes no numbers (BASELINE.md), so
``vs_baseline`` divides by a documented ESTIMATE of the Caffe+MPI
original (~400 embeddings/sec/GPU).

    python bench.py                      # all rows, on the chip
    python bench.py --rows headline,ring_flagship
    python bench.py --platform cpu --smoke

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

import argparse
import json
import os
import sys
import time

BASELINE_EMBEDDINGS_PER_SEC = 400.0
REPO = os.path.dirname(os.path.abspath(__file__))
METRIC = "googlenet_npair_train_embeddings_per_sec_per_chip"
UNIT = "embeddings/sec/chip"

_T0 = time.time()


def _log(msg: str) -> None:
    print(f"[bench t={time.time() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def _measure(jax, step, args_list, warmup: int, steps: int, repeats=2):
    """Per-window seconds for ``steps`` sequential ``step`` calls, each
    window ending in ``block_until_ready``.  ``step`` calls chain
    through the solver state, so every dispatch is real work."""
    for i in range(warmup):
        _log(f"warmup {i + 1}/{warmup}")
        jax.block_until_ready(step(*args_list))
    dts = []
    for r in range(repeats):
        _log(f"timing {steps} steps (window {r + 1}/{repeats})...")
        t0 = time.perf_counter()
        out = None
        for _ in range(steps):
            out = step(*args_list)
        jax.block_until_ready(out)
        dts.append(time.perf_counter() - t0)
    return dts


def _mfu(solver, x, lab, dt: float, steps: int, device_kind: str):
    """``{"step_flops", "mfu"}`` (values possibly None) via THE shared
    helper (obs.perf.costs.mfu_from_timing)."""
    from npairloss_tpu.utils.profiling import mfu_from_timing

    compiled = solver._step_fn.lower(solver.state, x, lab).compile()
    return mfu_from_timing(compiled, seconds=dt, steps=steps,
                           device_kind=device_kind)


def _train_inputs(jax, jnp, np, batch: int, image: int):
    rng = np.random.default_rng(0)
    x = jax.device_put(jnp.asarray(
        rng.standard_normal((batch, image, image, 3)).astype(np.float32)))
    lab = jax.device_put(jnp.asarray(
        np.repeat(np.arange(batch // 2), 2).astype(np.int32)))
    return x, lab


def headline_row(jax, jnp, np, dev, args):
    """The precision-policy flagship: the parity-preserving MXU trunk
    (s2d stem + fused 1x1s) under the "mxu" policy, batch 120 @ 224,
    def.prototxt mining, analytic backward, Caffe-SGD, in-graph
    Recall@{1,5,10} — one jitted step."""
    from npairloss_tpu.models import FLAGSHIP_POLICY, FLAGSHIP_TRUNK

    _log(f"building flagship solver ({FLAGSHIP_TRUNK} under the "
         f"{FLAGSHIP_POLICY!r} precision policy, batch {args.batch})")
    solver = _solver_for_spec(
        jnp, FLAGSHIP_TRUNK, {"policy": FLAGSHIP_POLICY}, {}, args.image)
    x, lab = _train_inputs(jax, jnp, np, args.batch, args.image)
    dts = _measure(jax, lambda a, b: solver.step(a, b)["loss"], [x, lab],
                   args.warmup, args.steps)
    dt = min(dts)
    emb_per_sec = args.batch * args.steps / dt
    est = _mfu(solver, x, lab, dt, args.steps, dev.device_kind)
    row = {
        "trunk": FLAGSHIP_TRUNK,
        "policy": FLAGSHIP_POLICY,
        "value": round(emb_per_sec, 2),
        "vs_baseline": round(emb_per_sec / BASELINE_EMBEDDINGS_PER_SEC, 3),
        "ms_per_step": round(dt / args.steps * 1e3, 2),
        "ms_per_step_windows": [round(d / args.steps * 1e3, 2)
                                for d in dts],
    }
    if est["mfu"] is not None:
        row["mfu"] = round(est["mfu"], 4)
    if est["step_flops"] is not None:
        row["step_flops"] = est["step_flops"]
    # The recorded price of the policy: same trunk, same trained
    # params, one forward+loss under each recipe.
    row.update(_policy_loss_delta(jax, jnp, np, solver, x, lab))
    return row


def _policy_loss_delta(jax, jnp, np, solver, x, lab):
    """``|loss(mxu policy) - loss(fp32_parity)|`` on the SAME flagship
    trunk, SAME (post-measurement) params, SAME batch — the honest
    apples-to-apples price of the single-pass-bf16 recipe, reported in
    the headline record (and bounded by tests/test_precision_policy.py
    at test scale)."""
    from npairloss_tpu.models import FLAGSHIP_TRUNK, get_model
    from npairloss_tpu.train import Solver, SolverConfig

    s32 = Solver(
        get_model(FLAGSHIP_TRUNK, policy="fp32_parity"),
        solver.loss_cfg,
        SolverConfig(display=0, snapshot=0),
        input_shape=solver.input_shape,
        precision="fp32_parity",
    )
    s32.state = solver.state  # fp32 master params: shared verbatim

    def one_loss(s):
        def f(state, xx, ll):
            emb, _ = s.apply_model(
                state["params"], state["batch_stats"], xx, train=True)
            loss, _ = s.compute_loss(emb, ll)
            return loss

        return float(np.asarray(jax.jit(f)(s.state, x, lab)))

    l_pol = one_loss(solver)
    l_32 = one_loss(s32)
    return {
        "policy_loss": round(l_pol, 6),
        "fp32_parity_loss": round(l_32, 6),
        "policy_fp32_loss_delta": round(abs(l_pol - l_32), 6),
    }


# Engine/serving row names — the vocabulary --rows selects from (plus
# "headline" and the batch_scaling keys below).
ENGINE_ROWS = (
    "dense_abs", "blockwise_abs", "dense_flagship", "blockwise_flagship",
    "blockwise_flagship_nocache", "blockwise_flagship_radix",
    "blockwise_flagship_bf16matmul", "dense_flagship_bf16matmul",
    "ring_abs", "ring_flagship", "ring_flagship_nocache",
    "ring_flagship_bf16matmul", "serve_qps",
    "flat_qps_1m", "ivf_qps_1m", "ivf_fused_qps_1m",
    "ivf_probe_kernel_micro",
)


def engine_rows(jax, jnp, np, selected, extras, run_row):
    """Loss-engine comparison at a large self-pool: dense XLA graph vs
    the Pallas blockwise kernels (Mosaic-compiled on a TPU) vs the ring
    engine on a 1-device mesh, fwd+bwd each — then the serving rows.

    Each engine is timed as ``steps`` loss+grad evaluations inside ONE
    jitted ``lax.scan`` (inputs perturbed per step so no two steps are
    identical), each window ending in ``block_until_ready``.
    """
    n, d = 4096, 512
    steps = 10
    extras.update({"pool": n, "steps": steps})
    # Plan provenance (parallel.plan): what the engine selector would
    # choose for THIS pool on THIS box's topology.
    from npairloss_tpu.parallel.plan import host_counts, plan_engine

    devs = jax.devices()
    extras["engine_plan"] = plan_engine(
        n_devices=len(devs), n_hosts=len(host_counts(devs)),
        shard_rows=max(n // len(devs), 1), emb_dim=d,
        device_kind=getattr(devs[0], "device_kind", ""),
    ).to_dict()
    if selected is not None and not (set(ENGINE_ROWS) & selected):
        return

    from jax.sharding import PartitionSpec as P

    from npairloss_tpu import NPairLossConfig, REFERENCE_CONFIG
    from npairloss_tpu.ops.npair_loss import (
        MiningMethod,
        MiningRegion,
        npair_loss,
    )
    from npairloss_tpu.ops.pallas_npair import blockwise_npair_loss
    from npairloss_tpu.parallel.mesh import data_parallel_mesh
    from npairloss_tpu.parallel.ring import ring_npair_loss_and_metrics

    rng = np.random.default_rng(1)
    f = rng.standard_normal((n, d)).astype(np.float32)
    f /= np.linalg.norm(f, axis=1, keepdims=True)
    feats = jax.device_put(jnp.asarray(f))
    labels = jax.device_put(
        jnp.asarray(np.repeat(np.arange(n // 2), 2).astype(np.int32))
    )
    # Absolute-mining config (single-pass thresholds) plus the flagship
    # RELATIVE config (streamed radix selection) on every engine.
    abs_cfg = NPairLossConfig(
        margin_diff=-0.05,
        ap_mining_method=MiningMethod.RAND,
        an_mining_method=MiningMethod.HARD,
        an_mining_region=MiningRegion.LOCAL,
    )
    losses = {}

    def bench_one(name, loss_fn):
        """loss_fn(features, labels) -> scalar loss; timed fwd+bwd."""
        assert name in ENGINE_ROWS, f"{name} missing from ENGINE_ROWS"
        vg = jax.value_and_grad(loss_fn)

        @jax.jit
        def many(f_, l_):
            def body(acc, s):
                # Perturb the input per step: every scan iteration is a
                # distinct computation, and the gradient feeds the carry
                # so no step can be elided.  losses[0] (s == 0) is the
                # unperturbed input — the cross-engine parity value.
                loss, grad = vg(f_ * (1.0 + s * 1e-6), l_)
                return acc + loss + grad[0, 0], loss

            acc, ls = jax.lax.scan(
                body, jnp.float32(0.0), jnp.arange(steps, dtype=jnp.float32)
            )
            return acc, ls[0]

        def row():
            _log(f"extras: compiling {name}...")
            for _ in range(2):  # compile + one-time backend setup
                _, l0 = jax.block_until_ready(many(feats, labels))
            dts = []
            for _ in range(2):
                t0 = time.perf_counter()
                jax.block_until_ready(many(feats, labels))
                dts.append(time.perf_counter() - t0)
            dt = min(dts)
            losses[name] = float(l0)
            return {
                "emb_per_sec": round(n * steps / dt, 1),
                "ms_per_step": round(dt / steps * 1e3, 2),
                "ms_per_step_windows": [round(t / steps * 1e3, 2)
                                        for t in dts],
                "loss": round(losses[name], 6),
            }

        run_row(name, row, extras)

    mesh = data_parallel_mesh(jax.devices()[:1])

    def ring_loss(cfg, sim_cache=None, matmul_precision=None):
        # top_ks=() keeps the comparison fair: dense/blockwise are timed
        # as loss+grad only, so the ring must not pay for streamed
        # retrieval-metric top-k maintenance the others skip.
        fn = jax.shard_map(
            lambda f_, l_: ring_npair_loss_and_metrics(
                f_, l_, cfg, "dp", top_ks=(), sim_cache=sim_cache,
                matmul_precision=matmul_precision,
            )[0][None],
            mesh=mesh,
            in_specs=(P("dp"), P("dp")),
            out_specs=P("dp"),
        )
        return lambda f_, l_: fn(f_, l_).sum()

    def delta(key, a, b):
        if a in losses and b in losses:
            extras[key] = abs(losses[a] - losses[b])

    bench_one("dense_abs", lambda f_, l_: npair_loss(f_, l_, abs_cfg))
    bench_one("blockwise_abs",
              lambda f_, l_: blockwise_npair_loss(f_, l_, abs_cfg))
    delta("dense_blockwise_abs_delta", "dense_abs", "blockwise_abs")
    bench_one("dense_flagship",
              lambda f_, l_: npair_loss(f_, l_, REFERENCE_CONFIG))
    bench_one("blockwise_flagship",
              lambda f_, l_: blockwise_npair_loss(f_, l_, REFERENCE_CONFIG))
    delta("dense_blockwise_flagship_delta", "dense_flagship",
          "blockwise_flagship")
    # The rows above run with sim_cache auto (ON at this pool: 67 MB);
    # the _nocache rows force the O(N x block) recompute path so the
    # cache's effect is a recorded delta.
    bench_one("blockwise_flagship_nocache",
              lambda f_, l_: blockwise_npair_loss(
                  f_, l_, REFERENCE_CONFIG, sim_cache=False))
    delta("blockwise_cache_nocache_delta", "blockwise_flagship",
          "blockwise_flagship_nocache")
    # pos_topk=0 forces the streamed radix path for the AP threshold.
    bench_one("blockwise_flagship_radix",
              lambda f_, l_: blockwise_npair_loss(
                  f_, l_, REFERENCE_CONFIG, pos_topk=0))
    delta("blockwise_postopk_radix_delta", "blockwise_flagship",
          "blockwise_flagship_radix")
    # matmul_precision="default": the opt-in single-pass bf16 MXU mode;
    # the loss delta vs the HIGHEST rows is the recorded price.
    bench_one("blockwise_flagship_bf16matmul",
              lambda f_, l_: blockwise_npair_loss(
                  f_, l_, REFERENCE_CONFIG, matmul_precision="default"))
    delta("blockwise_bf16matmul_loss_delta", "blockwise_flagship",
          "blockwise_flagship_bf16matmul")
    bench_one("dense_flagship_bf16matmul",
              lambda f_, l_: npair_loss(
                  f_, l_, REFERENCE_CONFIG, matmul_precision="default"))
    delta("dense_bf16matmul_loss_delta", "dense_flagship",
          "dense_flagship_bf16matmul")
    # Ring engine on a 1-device mesh: same pool, same math — isolates
    # the ring machinery's overhead against dense at an identical size.
    bench_one("ring_abs", ring_loss(abs_cfg))
    delta("dense_ring_abs_delta", "dense_abs", "ring_abs")
    bench_one("ring_flagship", ring_loss(REFERENCE_CONFIG))
    delta("dense_ring_flagship_delta", "dense_flagship", "ring_flagship")
    bench_one("ring_flagship_nocache",
              ring_loss(REFERENCE_CONFIG, sim_cache=False))
    delta("ring_cache_nocache_delta", "ring_flagship",
          "ring_flagship_nocache")
    bench_one("ring_flagship_bf16matmul",
              ring_loss(REFERENCE_CONFIG, matmul_precision="default"))
    delta("ring_bf16matmul_loss_delta", "ring_flagship",
          "ring_flagship_bf16matmul")

    # serve_qps: the online path (serve.QueryEngine) against the same
    # 4096 x 512 pool as a gallery — warmed-bucket query latency p50/p99
    # + QPS at each fixed padding bucket, plus the counted proof that
    # steady-state serving performed zero post-warmup compiles.
    def _serve_qps():
        from npairloss_tpu.serve import (
            EngineConfig,
            GalleryIndex,
            QueryEngine,
        )

        buckets = (8, 32)
        trials = 20
        idx = GalleryIndex.build(f, np.asarray(labels), normalize=False)
        engine = QueryEngine(
            idx, EngineConfig(top_k=10, buckets=buckets)
        )
        warm_s = engine.warmup()
        qpool = np.random.default_rng(7).standard_normal(
            (max(buckets) * trials, d)
        ).astype(np.float32)
        row = {"gallery": n, "top_k": 10, "warmup_s": round(warm_s, 2)}
        for bucket in buckets:
            lats = []
            for t in range(trials):
                q = qpool[t * bucket:(t + 1) * bucket]
                t0 = time.perf_counter()
                engine.query(q, normalize=True)
                # query() materializes the answer on the host
                lats.append((time.perf_counter() - t0) * 1e3)
            lats.sort()
            row[f"bucket_{bucket}"] = {
                "p50_ms": round(lats[len(lats) // 2], 2),
                "p99_ms": round(lats[min(int(len(lats) * 0.99),
                                         len(lats) - 1)], 2),
                "qps": round(bucket * trials / (sum(lats) / 1e3), 1),
            }
        row["compiles_after_warmup"] = \
            engine.compile_stats()["compiles_after_warmup"]
        return row

    run_row("serve_qps", _serve_qps, extras)

    # flat_qps_1m / ivf_qps_1m: production-gallery-scale serving.  A
    # 1M x 128 synthetic gallery served through the flat exact scan
    # (the recall oracle) and through the IVF probe path (serve/ivf.py:
    # k-means clusters, probe-top-C, bf16 cluster-scan scoring).  The
    # IVF rows carry build time and recall@1/@10 against the flat
    # ground truth computed on IDENTICAL queries.
    def _serve_scale_rows(want_flat, want_ivf, want_fused, want_micro):
        import gc

        from npairloss_tpu.ops.pallas_ivf import PROBE_IMPLS
        from npairloss_tpu.serve import (
            EngineConfig,
            GalleryIndex,
            QueryEngine,
        )
        from npairloss_tpu.serve.ivf import IVFIndex, topk_recall

        n1, d1, kc, probes = 1_000_000, 128, 1024, 32
        bucket, trials, top_k = 8, 12, 10
        platform = jax.devices()[0].platform
        # The cluster-scan matmul dtype: bf16 on an accelerator; XLA
        # *CPU* scalarizes bf16, so an explicit --platform cpu run
        # scores in fp32.  The recall-parity gates for bf16/int8 live
        # in tests/test_ivf.py either way.
        scoring = "fp32" if platform == "cpu" else "bf16"
        # Off TPU the fused Pallas probe runs in interpret mode — a
        # parity harness, not a serving path — so the row is stamped
        # skipped there rather than timed.
        measure_fused = want_fused and platform == "tpu"
        if want_fused and not measure_fused:
            extras["ivf_fused_qps_1m"] = {
                "skipped": "fused probe kernel measures on TPU only "
                           "(interpret mode is a parity harness, not "
                           "a serving path)"}
            _log("extras: skipping ivf_fused_qps_1m (platform "
                 f"{platform}: interpret emulation is not a "
                 "measurement)")
        # Clustered synthetic gallery — the geometry a trained
        # metric-learning gallery actually has (4096 classes, tight
        # class clusters), and the structure IVF's probe-recall story
        # is ABOUT.  An isotropic-gaussian pool is the adversarial
        # no-structure case: true neighbors scatter uniformly over
        # clusters and no sublinear index can hold recall there.
        classes = 4096
        rng1 = np.random.default_rng(11)
        centers = rng1.standard_normal(
            (classes, d1), dtype=np.float32)
        centers /= np.linalg.norm(centers, axis=1, keepdims=True)
        plab = (np.arange(n1) % classes).astype(np.int32)
        pool = centers[plab] + 0.045 * rng1.standard_normal(
            (n1, d1), dtype=np.float32)
        pool /= np.linalg.norm(pool, axis=1, keepdims=True)
        sel_rows = rng1.choice(n1, bucket * trials, replace=False)
        qs = pool[sel_rows] + 0.045 * rng1.standard_normal(
            (bucket * trials, d1), dtype=np.float32)
        qs /= np.linalg.norm(qs, axis=1, keepdims=True)

        def timed(engine):
            lats, rows_out = [], []
            for t in range(trials):
                q = qs[t * bucket:(t + 1) * bucket]
                t0 = time.perf_counter()
                out = engine.query(q, normalize=False)
                lats.append((time.perf_counter() - t0) * 1e3)
                rows_out.append(out["rows"][:, :top_k])
            lats.sort()
            return lats, np.concatenate(rows_out)

        def base_row(lats, warm_s, engine):
            return {
                "gallery": n1, "dim": d1, "top_k": top_k,
                "bucket": bucket, "platform": platform,
                "warmup_s": round(warm_s, 2),
                "p50_ms": round(lats[len(lats) // 2], 2),
                "p99_ms": round(lats[min(int(len(lats) * 0.99),
                                         len(lats) - 1)], 2),
                "qps": round(bucket * trials / (sum(lats) / 1e3), 1),
                "compiles_after_warmup":
                    engine.compile_stats()["compiles_after_warmup"],
            }

        # Which passes this selection actually needs: the flat oracle
        # feeds every recall number; the scan engine feeds its own row
        # AND the micro row's baseline clock; the dispatch-count-only
        # micro row never forces the oracle pass.
        need_oracle = want_flat or want_ivf or measure_fused
        need_index = want_ivf or measure_fused or want_micro
        flat_lats = flat_rows = None
        if need_oracle:
            _log(f"extras: building 1M x {d1} gallery "
                 "(flat oracle pass)...")
            idx_f = GalleryIndex.build(pool, plab, normalize=False)
            eng_f = QueryEngine(idx_f, EngineConfig(
                top_k=top_k, buckets=(bucket,), gallery_block=131072))
            warm_f = eng_f.warmup()
            flat_lats, flat_rows = timed(eng_f)
            if want_flat:
                extras["flat_qps_1m"] = base_row(flat_lats, warm_f,
                                                 eng_f)
                _log(f"extras: flat_qps_1m: {extras['flat_qps_1m']}")
            # Free the flat device residency before the IVF build
            # doubles it (the flat answers — the recall ground truth —
            # are host-side).
            del eng_f
            idx_f.emb = idx_f.labels = idx_f.valid = None
            gc.collect()
        if not need_index:
            return
        t0 = time.perf_counter()
        idx_i = IVFIndex.build_ivf(
            pool, plab, normalize=False, clusters=kc, iters=8,
            train_size=65536)
        build_s = time.perf_counter() - t0

        def ivf_row_extras(row, eng_rows):
            return {
                "clusters": kc, "probes": probes, "scoring": scoring,
                "cap": idx_i.layout.cap,
                "build_s": round(build_s, 1),
                "recall_at_1": round(
                    topk_recall(eng_rows, flat_rows, k=1), 4),
                "recall_at_10": round(
                    topk_recall(eng_rows, flat_rows, k=10), 4),
                "speedup_vs_flat_p50": round(
                    flat_lats[len(flat_lats) // 2]
                    / max(row["p50_ms"], 1e-9), 1),
            }

        eng_i = None
        if want_ivf or want_micro:
            eng_i = QueryEngine(idx_i, EngineConfig(
                top_k=top_k, buckets=(bucket,), probes=probes,
                scoring=scoring))
            warm_i = eng_i.warmup()
        if want_ivf:
            ivf_lats, ivf_rows = timed(eng_i)
            row = base_row(ivf_lats, warm_i, eng_i)
            row.update(ivf_row_extras(row, ivf_rows))
            extras["ivf_qps_1m"] = row
            _log(f"extras: ivf_qps_1m: {row}")
        eng_fu = None
        if measure_fused or (want_micro and platform == "tpu"):
            # SAME index object, probe_impl the only delta — the row
            # isolates the kernel, not a rebuild.
            eng_fu = QueryEngine(idx_i, EngineConfig(
                top_k=top_k, buckets=(bucket,), probes=probes,
                scoring=scoring, probe_impl="fused"))
            warm_fu = eng_fu.warmup()
        if measure_fused:
            fu_lats, fu_rows = timed(eng_fu)
            rowf = base_row(fu_lats, warm_fu, eng_fu)
            rowf.update(ivf_row_extras(rowf, fu_rows))
            rowf.update({
                "probe_impl": eng_fu.probe_impl,
                "dispatch_count":
                    PROBE_IMPLS["fused"]["dispatch_count"],
            })
            extras["ivf_fused_qps_1m"] = rowf
            _log(f"extras: ivf_fused_qps_1m: {rowf}")
        if want_micro:
            # Kernel-level micro: ONE steady-state probe dispatch per
            # impl (no host gather, no batcher), plus the registry's
            # declared pipeline dispatch counts — the 4 -> 2 claim,
            # stamped where bench_check can gate it jax-free.
            qm = jnp.asarray(qs[:bucket])

            def one_dispatch_ms(eng):
                args, _ = eng._topk_call(bucket)
                reps = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    jax.block_until_ready(eng._topk_fn(qm, *args))
                    reps.append(time.perf_counter() - t0)
                reps.sort()
                return round(reps[len(reps) // 2] * 1e3, 2)

            mrow = {
                "gallery": n1, "dim": d1, "clusters": kc,
                "probes": probes, "bucket": bucket,
                "scoring": scoring, "platform": platform,
                "cap": idx_i.layout.cap,
                "scan_dispatches":
                    PROBE_IMPLS["scan"]["dispatch_count"],
                "fused_dispatches":
                    PROBE_IMPLS["fused"]["dispatch_count"],
                "scan_ms": one_dispatch_ms(eng_i),
            }
            if eng_fu is not None:
                mrow["fused_ms"] = one_dispatch_ms(eng_fu)
            else:
                mrow["fused_ms_note"] = (
                    "not measured: needs a TPU (interpret emulation "
                    "excluded)")
            extras["ivf_probe_kernel_micro"] = mrow
            _log(f"extras: ivf_probe_kernel_micro: {mrow}")


    scale_names = ("flat_qps_1m", "ivf_qps_1m", "ivf_fused_qps_1m",
                   "ivf_probe_kernel_micro")
    wants = {name: selected is None or name in selected
             for name in scale_names}
    if any(wants.values()):
        try:
            _serve_scale_rows(*(wants[name] for name in scale_names))
        except Exception as e:  # noqa: BLE001 — recorded, fails the run
            _log(f"extras: serve scale rows FAILED: {e}")
            for name in scale_names:
                # Never clobber a half-pass's MEASURED row.
                if wants[name] and name not in extras:
                    extras[name] = {"error": str(e)[:300]}


# Batch-scaling sweep: (batch, model_name, row_key, model_kw, solver_kw).
# The parity-preserving MXU rewrites (s2d stem, fused inception 1x1s,
# both = "mxu") and the remat row are attribution rows.  A ``"policy"``
# key in model_kw routes
# the row through the named precision policy (models.precision) — the
# *_policy rows are the flagship recipe's 240/480/960 scaling curve
# (the 120 point is the headline itself), googlenet_fp32_parity keeps
# the prototxt-parity fp32 delta measured, and 120_pallas_stem times
# the Pallas conv epilogues (Mosaic-compiled on TPU; LRN is a kernel in
# every row there).  The vit_b16
# rows time BASELINE.json config 5's trunk (real ViT-B/16) through the
# blockwise (stretch-path) engine; the 256 row probes the largest batch
# and runs LAST.  The row_key column is the other half of the --rows
# vocabulary (with "headline" and ENGINE_ROWS).
BATCH_SCALING_SPECS = (
    (120, "googlenet", "120", {}, {}),
    (120, "googlenet_mxu", "120_mxu", {}, {}),
    (120, "googlenet", "googlenet_fp32_parity",
     {"policy": "fp32_parity"}, {}),
    (240, "googlenet", "240", {}, {}),
    (240, "flagship", "240_policy", {"policy": "mxu"}, {}),
    (480, "googlenet", "480", {}, {}),
    (480, "flagship", "480_policy", {"policy": "mxu"}, {}),
    (128, "vit_b16", "vit_b16_128", {}, {"engine": "blockwise"}),
    (120, "googlenet_s2d", "120_s2d", {}, {}),
    (120, "googlenet_fused", "120_fused", {}, {}),
    (120, "googlenet_pallas", "120_pallas_stem", {"policy": "mxu"}, {}),
    # Remat row: does relieving activation HBM pressure recover the
    # batch-480 MFU decay?  (~25% extra trunk FLOPs for O(block)
    # activation memory; numerically identical.)
    (480, "googlenet", "480_remat", {"remat": True}, {}),
    (960, "flagship", "960_policy", {"policy": "mxu"}, {}),
    (256, "vit_b16", "vit_b16_256", {}, {"engine": "blockwise"}),
)




def known_row_names():
    """The full --rows vocabulary; a name outside it is a typo."""
    return {"headline"} | set(ENGINE_ROWS) | {
        spec[2] for spec in BATCH_SCALING_SPECS
    }


def _solver_for_spec(jnp, model_name, model_kw, solver_kw, image):
    """The ONE solver constructor for the headline and every
    BATCH_SCALING_SPECS row.  A ``"policy"`` key in model_kw selects a named precision policy
    (threaded through trunk AND solver); the legacy rows stay the
    bf16-dtype construction byte-for-byte."""
    from npairloss_tpu import REFERENCE_CONFIG
    from npairloss_tpu.models import get_model
    from npairloss_tpu.train import Solver, SolverConfig

    model_kw = dict(model_kw)
    policy = model_kw.pop("policy", None)
    if policy is not None:
        model = get_model(model_name, policy=policy, **model_kw)
    else:
        model = get_model(model_name, dtype=jnp.bfloat16, **model_kw)
    return Solver(
        model,
        REFERENCE_CONFIG,
        SolverConfig(
            base_lr=0.001, lr_policy="step", stepsize=10000, gamma=0.5,
            momentum=0.9, weight_decay=2e-5, display=0, snapshot=0,
        ),
        input_shape=(image, image, 3),
        precision=policy,
        **solver_kw,
    )


def _batch_scaling_row(jax, jnp, np, dev, batch, model_name, model_kw,
                       solver_kw, image):
    solver = _solver_for_spec(jnp, model_name, model_kw, solver_kw, image)
    x, lab = _train_inputs(jax, jnp, np, batch, image)
    steps = 10
    dts = _measure(jax, lambda a, b: solver.step(a, b), [x, lab], 1, steps)
    dt = min(dts)
    est = _mfu(solver, x, lab, dt, steps, dev.device_kind)
    return {
        "emb_per_sec": round(batch * steps / dt, 1),
        "ms_per_step": round(dt / steps * 1e3, 2),
        "ms_per_step_windows": [round(d / steps * 1e3, 2) for d in dts],
        **({"mfu": round(est["mfu"], 4)}
           if est["mfu"] is not None else {}),
    }


def smoke_row(jax, jnp, np):
    """Tiny MLP + loss, 5 steps — checks the script, measures nothing
    anyone deploys."""
    from npairloss_tpu import REFERENCE_CONFIG
    from npairloss_tpu.models import get_model
    from npairloss_tpu.train import Solver, SolverConfig

    batch = 64
    solver = Solver(
        get_model("mlp", hidden=(256,), embedding_dim=64),
        REFERENCE_CONFIG,
        SolverConfig(base_lr=0.01, lr_policy="fixed", display=0, snapshot=0),
        input_shape=(32, 32, 3),
    )
    x, lab = _train_inputs(jax, jnp, np, batch, 32)
    dt = min(_measure(jax, lambda a, b: solver.step(a, b), [x, lab], 1, 5))
    return {
        "metric": "smoke_mlp_npair_train_embeddings_per_sec",
        "value": round(batch * 5 / dt, 2),
        "unit": UNIT,
        "vs_baseline": 0.0,
        "mode": "smoke",
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--platform", choices=["default", "cpu"],
                    default="default",
                    help="'cpu' runs on the CPU by explicit choice (the "
                    "record says so); default: the accelerator JAX "
                    "finds, and no accelerator is an error")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny MLP row only")
    ap.add_argument("--rows", metavar="A,B,...",
                    help="measure only these rows (see known_row_names)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--batch", type=int, default=120,
                    help="headline batch (reference geometry: 120)")
    ap.add_argument("--image", type=int, default=224,
                    help="input side (reference geometry: 224)")
    args = ap.parse_args(argv)
    selected = None
    if args.rows:
        selected = {r.strip() for r in args.rows.split(",") if r.strip()}
        unknown = selected - known_row_names()
        if unknown:
            print(f"bench: unknown --rows {sorted(unknown)}; known: "
                  f"{sorted(known_row_names())}", file=sys.stderr)
            return 2

    sys.path.insert(0, REPO)
    import jax

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from npairloss_tpu.pipeline import enable_compile_cache

    enable_compile_cache()
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    _log(f"backend up: platform={dev.platform} kind={dev.device_kind} "
         f"count={len(jax.devices())}")
    if dev.platform == "cpu" and args.platform != "cpu":
        print("bench: no accelerator found; pass --platform cpu for an "
              "explicit CPU run", file=sys.stderr)
        return 2

    record = {
        "metric": METRIC,
        "unit": UNIT,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "batch": args.batch,
        "image": args.image,
    }

    def run_row(name, fn, into):
        """One row: measured, or recorded as the error that stopped it
        (an error row makes the run exit non-zero)."""
        if selected is not None and name not in selected:
            return
        try:
            into[name] = fn()
            _log(f"{name}: {into[name]}")
        except Exception as e:  # noqa: BLE001 — recorded, fails the run
            _log(f"{name} FAILED: {e}")
            into[name] = {"error": f"{type(e).__name__}: {str(e)[:300]}"}

    def failed_in(rows):
        return [k for k, v in rows.items()
                if isinstance(v, dict) and "error" in v]

    failed = []
    if args.smoke:
        record.update(smoke_row(jax, jnp, np))
    else:
        record["mode"] = "full"
        if selected is not None:
            record["rows_filter"] = sorted(selected)
        head = {}
        run_row("headline", lambda: headline_row(jax, jnp, np, dev, args),
                head)
        record.update(head.get("headline", {}))
        extras = record["extras"] = {}
        try:
            engine_rows(jax, jnp, np, selected, extras, run_row)
        except Exception as e:  # noqa: BLE001 — recorded, fails the run
            _log(f"engine rows FAILED: {e}")
            extras["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            failed.append("engine_rows")
        failed += failed_in(head) + failed_in(extras)
        scaling = extras["batch_scaling"] = {}
        for batch, model_name, key, model_kw, solver_kw in \
                BATCH_SCALING_SPECS:
            run_row(key, lambda: _batch_scaling_row(
                jax, jnp, np, dev, batch, model_name, model_kw,
                solver_kw, args.image), scaling)
        failed += failed_in(scaling)
    if failed:
        record["failed_rows"] = failed
    print(json.dumps(record))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
