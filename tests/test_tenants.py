"""Multi-tenant serving (docs/SERVING.md §Multi-tenant).

Load-bearing pins:
  * the ``npairloss-tenants-v1`` manifest validates TOTALLY and
    loudly: unknown keys, duplicate ids, malformed ids and
    out-of-range quotas are refused with every problem listed (the
    same validator bench_check's ``--tenants`` gate file-path-loads);
  * one front end, one replica tier, MANY galleries: a query routes on
    its ``tenant`` key to that tenant's engine set, answers come back
    tenant-stamped, and an unknown tenant is a malformed request
    (error), never an admitted query;
  * hot-swapping ONE tenant republished exactly that tenant — every
    other tenant's engines are untouched by identity and its answers
    stay bit-identical;
  * same-geometry tenants share compiled programs through the
    :class:`ProgramCache` — tenant count must not multiply compiles;
  * a noisy tenant's quota sheds land on THAT tenant's counters only,
    the per-tenant counters cross-sum EXACTLY into the aggregates,
    and the quota gauge stream is tenant-labeled (the samples its
    tenant-scoped SLO burns on);
  * the tenant_skew gameday verdict refuses a run whose hot tenant
    was never shed or paged, and any neighbor that saw errors, leaked
    sheds, a p99 breach, or a recall dip.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

from npairloss_tpu.gameday import schedule as chaos
from npairloss_tpu.gameday import traffic as tg
from npairloss_tpu.gameday.verdict import (
    build_gameday_report,
    validate_gameday_report,
)
from npairloss_tpu.obs.live.export import prometheus_text
from npairloss_tpu.obs.live.registry import MetricRegistry
from npairloss_tpu.serve import (
    BatcherConfig,
    EngineConfig,
    GalleryIndex,
    QueryEngine,
    RetrievalServer,
    ServerConfig,
)
from npairloss_tpu.serve.tenants import (
    TENANTS_SCHEMA,
    ProgramCache,
    QuotaGate,
    TenantEntry,
    TenantRegistry,
    TenantSpec,
    tenant_of_slo,
    tenant_slo_specs,
    validate_tenants_manifest,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def bench_check():
    spec = importlib.util.spec_from_file_location(
        "bench_check_mod", os.path.join(REPO, "scripts",
                                        "bench_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _entry(tid="acme", **kw):
    d = {"tenant_id": tid, "index_prefix": f"/tmp/idx/{tid}-"}
    d.update(kw)
    return d


def _manifest(*entries):
    return {"schema": TENANTS_SCHEMA,
            "tenants": list(entries) or [_entry()]}


# -- manifest validation ------------------------------------------------------


def test_manifest_valid_and_registry_roundtrip():
    man = _manifest(_entry("acme", index_kind="ivf", probe_impl="fused",
                           quota_qps=5.0, recall_floor=0.9,
                           p99_ms=150.0),
                    _entry("b-corp_2"))
    assert validate_tenants_manifest(man) == []
    reg = TenantRegistry.from_manifest(man)
    assert reg.ids() == ["acme", "b-corp_2"]
    assert "acme" in reg and len(reg) == 2
    assert reg.get("acme").index_kind == "ivf"
    with pytest.raises(KeyError, match="unknown tenant"):
        reg.get("nope")


def test_manifest_refusals_are_total_and_loud():
    # Every problem listed in ONE pass, not first-error-wins.
    man = {"schema": "wrong-schema",
           "tenants": [_entry("acme", quota_qps=-1),
                       _entry("acme"),
                       _entry("bad id!"),
                       dict(_entry("c"), mystery_key=1)]}
    problems = validate_tenants_manifest(man)
    text = "\n".join(problems)
    assert "schema" in text
    assert "quota_qps" in text
    assert "duplicate" in text
    assert "bad id!" in text
    assert "mystery_key" in text
    with pytest.raises(ValueError, match="invalid tenants manifest"):
        TenantRegistry.from_manifest(man)


def test_manifest_shape_refusals():
    assert validate_tenants_manifest(None)
    assert validate_tenants_manifest({"schema": TENANTS_SCHEMA})
    assert validate_tenants_manifest(
        {"schema": TENANTS_SCHEMA, "tenants": []})
    assert validate_tenants_manifest(
        {"schema": TENANTS_SCHEMA, "tenants": [17]})
    assert validate_tenants_manifest(_manifest(
        _entry("a", index_kind="hnsw")))      # unknown kind
    assert validate_tenants_manifest(_manifest(
        _entry("a", probe_impl="magic")))     # unknown probe impl
    assert validate_tenants_manifest(_manifest(
        {"tenant_id": "a"}))                  # index_prefix missing


def test_tenant_spec_validates_through_the_one_contract():
    with pytest.raises(ValueError, match="quota_qps"):
        TenantSpec(tenant_id="a", index_prefix="/p/a-", quota_qps=-2)
    spec = TenantSpec.from_dict(
        dict(_entry("a"), tenant="ignored-unknown-key"))
    assert spec.tenant_id == "a"


def test_registry_load_refuses_bad_json(tmp_path):
    path = tmp_path / "tenants.json"
    path.write_text("{not json")
    with pytest.raises(ValueError, match="bad JSON"):
        TenantRegistry.load(str(path))
    path.write_text(json.dumps(_manifest()))
    assert TenantRegistry.load(str(path)).ids() == ["acme"]


# -- tenant-scoped SLO naming -------------------------------------------------


def test_tenant_slo_specs_and_name_roundtrip():
    spec = TenantSpec(tenant_id="acme", index_prefix="/p/a-",
                      quota_qps=5.0, p99_ms=150.0, recall_floor=0.9,
                      recall_k=10)
    specs = tenant_slo_specs(spec)
    names = {s.name for s in specs}
    assert names == {"tenant_p99@acme", "tenant_quota@acme",
                     "tenant_recall@acme"}
    for s in specs:
        assert tenant_of_slo(s.name) == "acme"
        # Each spec burns on a tenant-labeled sample stream.
        assert 'tenant="acme"' in s.metric
    assert tenant_of_slo("serve_p99") is None
    # A tenant with no declared contracts arms no SLOs.
    bare = TenantSpec(tenant_id="b", index_prefix="/p/b-")
    assert tenant_slo_specs(bare) == []


# -- quota gate ---------------------------------------------------------------


def test_quota_gate_token_bucket_deterministic():
    now = [0.0]
    gate = QuotaGate(qps=2.0, burst_s=1.0, clock=lambda: now[0])
    assert gate.admit() and gate.admit()   # capacity 2*1
    assert not gate.admit()                # bucket dry
    now[0] = 1.0                           # refill 2 tokens
    assert gate.admit() and gate.admit()
    assert not gate.admit()
    s = gate.stats()
    assert s["sheds"] == 2 and s["qps"] == 2.0 and s["burst_s"] == 1.0


def test_quota_gate_zero_qps_disarms():
    gate = QuotaGate(qps=0.0)
    assert all(gate.admit() for _ in range(50))
    assert gate.stats()["sheds"] == 0
    with pytest.raises(ValueError, match="qps"):
        QuotaGate(qps=-1)
    with pytest.raises(ValueError, match="burst_s"):
        QuotaGate(qps=1, burst_s=0)


def test_quota_gauge_stream_is_tenant_labeled():
    reg = MetricRegistry()
    now = [0.0]
    gate = QuotaGate(qps=1.0, burst_s=1.0, clock=lambda: now[0],
                     registry=reg.view(tenant="acme"))
    assert gate.admit()
    assert not gate.admit()
    snap = reg.snapshot()
    assert snap['serve_quota_exhausted{tenant="acme"}']["value"] == 1.0
    assert snap['serve_quota_shed{tenant="acme"}']["value"] == 1.0
    # The exporter renders the label as a REAL Prometheus label.
    assert 'serve_quota_exhausted{tenant="acme"} 1' in \
        prometheus_text(reg)


# -- one tier, many galleries -------------------------------------------------


def _tenant_gallery(seed, n=24, dim=16, id_base=0):
    r = np.random.default_rng(seed)
    emb = r.standard_normal((n, dim)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    labels = (np.arange(n) % 6).astype(np.int32)
    ids = (np.arange(n) + id_base).astype(np.int64)
    return emb, GalleryIndex.build(emb, labels, ids=ids,
                                   normalize=False)


def _tenant_server(tenant_ids, *, quotas=None, replicas=1,
                   max_queue=64, programs=None):
    """One replica tier serving one distinct gallery per tenant, all
    engines sharing programs through one cache (the cli wiring in
    miniature).  Returns (server, {tid: query embeddings})."""
    programs = programs if programs is not None else ProgramCache()
    cfg = EngineConfig(top_k=3, buckets=(1, 4))
    entries, embs = {}, {}
    anchor = None
    for t_i, tid in enumerate(tenant_ids):
        emb, index = _tenant_gallery(7 + t_i, id_base=1000 * t_i)
        embs[tid] = emb
        primary = programs.engine_for(index, cfg)
        if anchor is None:
            primary.warmup()
        else:
            primary.warmed = True  # shares the anchor's programs
        engines = [primary] + [
            QueryEngine(index, cfg, share_compiled_with=primary)
            for _ in range(replicas - 1)]
        for e in engines[1:]:
            e.warmed = True
        if anchor is None:
            anchor = engines
        spec = TenantSpec(
            tenant_id=tid, index_prefix=f"/tmp/idx/{tid}-",
            quota_qps=(quotas or {}).get(tid, 0.0), quota_burst_s=1.0)
        quota = None
        if spec.quota_qps:
            quota = QuotaGate(spec.quota_qps, spec.quota_burst_s,
                              clock=lambda: 0.0)  # frozen: no refill
        entries[tid] = TenantEntry(spec, engines, quota=quota)
    server = RetrievalServer(
        anchor,
        BatcherConfig(max_batch=4, max_delay_ms=1.0,
                      max_queue=max_queue),
        ServerConfig(metrics_window=0, explicit_drops=True),
    )
    server.enable_tenants(entries)
    return server, embs


def _q(tid, emb, i, qid=None):
    return {"id": qid if qid is not None else i, "tenant": tid,
            "embedding": emb[i].tolist()}


def test_tenant_routing_answers_from_own_gallery(rng):
    server, embs = _tenant_server(["acme", "bcorp"])
    server.replicaset.start()
    try:
        for tid in ("acme", "bcorp"):
            a = server.handle(_q(tid, embs[tid], 3))
            assert a["tenant"] == tid
            # The query IS gallery row 3 of its own tenant: top-1
            # must be the exact match — proof it scored against the
            # right gallery, not a neighbor's.
            assert a["neighbors"][0]["row"] == 3
            assert a["neighbors"][0]["score"] == pytest.approx(
                1.0, abs=1e-5)
    finally:
        server.replicaset.close(drain=True)


def test_tenant_groups_of_one_batch_launch_together_then_finish(
        rng, tracer):
    """A batch that holds two tenants' records launches both groups
    (one ``serve/launch`` each, inside the batch's dispatch) before its
    finish collects either (one ``serve/topk`` each, inside the
    finish); under a backlog that finish runs on the completion thread.
    Every answer comes from its own tenant's gallery."""
    server, embs = _tenant_server(["acme", "bcorp"])
    records = [_q(tid, embs[tid], i, qid=f"{tid}{i}")
               for i in range(4) for tid in ("acme", "bcorp")]
    start = tracer.num_events  # past the warm-up's spans
    # queued before the dispatcher starts: two batches of four
    futs = [server.submit(rec)[0] for rec in records]
    server.replicaset.start()
    server.replicaset.close(drain=True)
    for rec, fut in zip(records, futs):
        ans = fut.result(timeout=0)
        assert ans["id"] == rec["id"] and ans["tenant"] == rec["tenant"]
        # the query IS its tenant's gallery row: the exact match
        assert ans["neighbors"][0]["row"] == int(rec["id"][-1])
        assert ans["neighbors"][0]["score"] == pytest.approx(1.0, abs=1e-5)
    events = tracer.events_since(start)[0]
    spans = lambda name: [e for e in events if e["name"] == name
                          and e.get("args", {}).get("batch") == 1]
    (dispatch,), (finish,) = spans("serve/dispatch"), spans("serve/finish")
    end = lambda e: e["ts"] + e["dur"]
    inside = lambda child, parent: (
        parent["ts"] <= child["ts"] and end(child) <= end(parent))
    launches, topks = spans("serve/launch"), spans("serve/topk")
    assert len(launches) == 2 and all(inside(e, dispatch) for e in launches)
    assert len(topks) == 2 and all(inside(e, finish) for e in topks)
    assert end(dispatch) <= finish["ts"]
    assert dispatch["tid"] != finish["tid"]


def test_unknown_tenant_is_an_error_not_a_query(rng):
    server, embs = _tenant_server(["acme"])
    server.replicaset.start()
    try:
        a = server.handle(_q("ghost", embs["acme"], 0, qid="x"))
        assert "unknown tenant" in a["error"]
        b = server.handle({"id": "y", "embedding": embs["acme"][0].tolist()})
        assert "unknown tenant" in b["error"]  # missing key too
        assert server.errors == 2
        # Never admitted: a malformed request must not dilute the
        # drain invariant's admitted-query population.
        assert server.queries == 0
        # No tenant row owns a refusal — the drain names the remainder
        # so the error audit stays exact (Σ per-tenant + unattributed
        # == aggregate, the bench_check --tenants identity).
        summ = server.summary()
        assert summ["errors_unattributed"] == 2
        per = summ["tenants"]
        assert sum(row["errors"] for row in per.values()) == 0
        assert (sum(row["errors"] for row in per.values())
                + summ["errors_unattributed"] == summ["errors"])
        # A refusal never entered ``queries``, so it must not read
        # back as a negative drop count.
        assert summ["queries_dropped"] == 0
    finally:
        server.replicaset.close(drain=True)


def test_swap_one_tenant_leaves_neighbors_bit_identical(rng):
    server, embs = _tenant_server(["acme", "bcorp"])
    server.replicaset.start()
    try:
        before = server.handle(_q("bcorp", embs["bcorp"], 5))
        b_engines = server.tenants["bcorp"].engines
        # Republish acme on a brand-new gallery (new ids namespace).
        emb2, index2 = _tenant_gallery(99, id_base=5000)
        old = server.tenants["acme"].engines[0]
        fresh = QueryEngine(index2, old.cfg, share_programs_with=old)
        fresh.warmed = True
        server.swap_tenant_engines("acme", [fresh])
        assert server.tenants["acme"].swaps == 1
        assert server.tenants["bcorp"].swaps == 0
        # bcorp's engine OBJECTS are untouched...
        assert server.tenants["bcorp"].engines is b_engines
        # ...and its answers bit-identical across the neighbor swap.
        after = server.handle(_q("bcorp", embs["bcorp"], 5))
        assert after["neighbors"] == before["neighbors"]
        # acme now answers from the new gallery's id namespace.
        a = server.handle(_q("acme", emb2, 2))
        assert a["neighbors"][0]["row"] == 2
        with pytest.raises(Exception, match="unknown tenant"):
            server.swap_tenant_engines("ghost", [fresh])
        with pytest.raises(ValueError, match="replica count"):
            server.swap_tenant_engines("acme", [fresh, fresh])
    finally:
        server.replicaset.close(drain=True)


def test_same_geometry_tenants_share_compiles(rng):
    programs = ProgramCache()
    server, embs = _tenant_server(["acme", "bcorp", "ccorp"],
                                  programs=programs)
    server.replicaset.start()
    try:
        for tid in ("acme", "bcorp", "ccorp"):
            server.handle(_q(tid, embs[tid], 0))
        # One program family serves every tenant: ONLY the anchor's
        # warmup compiled; the other tenants' first dispatches found
        # every program hot (tenant count must not multiply compiles).
        assert programs.stats() == {"families": 1}
        assert server._compiles_after_warmup() == 0
    finally:
        server.replicaset.close(drain=True)


def test_quota_shed_isolation_and_cross_sums(rng):
    # acme's frozen-clock bucket admits exactly 2 (capacity 2*1);
    # everything beyond sheds on acme alone.
    server, embs = _tenant_server(["acme", "bcorp"],
                                  quotas={"acme": 2.0})
    server.replicaset.start()
    try:
        records = [_q("acme", embs["acme"], i, qid=f"a{i}")
                   for i in range(6)]
        records += [_q("bcorp", embs["bcorp"], i, qid=f"b{i}")
                    for i in range(3)]
        answers = server.handle_many(records)
        shed = [a for a in answers if "error" in a
                and "quota exceeded" in a["error"]]
        assert len(shed) == 4
        summ = server.summary()
        per = summ["tenants"]
        assert per["acme"]["answered"] == 2
        assert per["acme"]["rejected"] == 4
        assert per["acme"]["quota"]["sheds"] == 4
        # The noisy neighbor's sheds never leak onto bcorp.
        assert per["bcorp"]["answered"] == 3
        assert per["bcorp"]["rejected"] == 0
        assert per["bcorp"]["errors"] == 0
        # Per-tenant counters cross-sum EXACTLY into the aggregates
        # (the bench_check --tenants gate's accounting invariant).
        for key in ("queries", "answered", "errors", "rejected"):
            assert sum(row[key] for row in per.values()) == summ[key], key
        assert summ["queries_dropped"] == 0
    finally:
        server.replicaset.close(drain=True)


def test_enable_tenants_is_loud(rng):
    server, _ = _tenant_server(["acme"])
    with pytest.raises(ValueError, match="already installed"):
        server.enable_tenants(dict(server.tenants))
    emb, index = _tenant_gallery(1)
    cfg = EngineConfig(top_k=3, buckets=(1,))
    eng = QueryEngine(index, cfg)
    bad = TenantEntry(
        TenantSpec(tenant_id="x", index_prefix="/p/x-"), [eng, eng])
    fresh = RetrievalServer(
        [eng], BatcherConfig(max_batch=1, max_delay_ms=1.0,
                             max_queue=4),
        ServerConfig(metrics_window=0))
    with pytest.raises(ValueError, match="replica tier"):
        fresh.enable_tenants({"x": bad})  # 2 engines vs 1 replica
    with pytest.raises(ValueError, match=">= 1 tenant"):
        fresh.enable_tenants({})


# -- tenant-aware traffic plans ----------------------------------------------


def _skew_cfg(**over):
    kw = dict(seed=0, duration_s=30.0, base_qps=4.0, peak_qps=8.0,
              burst_qps=30.0, bursts=1, burst_s=6.0, catalog=64,
              zipf_s=1.1,
              tenants=(("acme", 1.0), ("bcorp", 1.0), ("ccorp", 1.0)),
              hot_tenant="acme", hot_burst_factor=8.0)
    kw.update(over)
    return tg.TrafficConfig(**kw)


def test_traffic_tenant_draws_and_burst_skew():
    plan = tg.generate(_skew_cfg())
    tids = {q.tenant for q in plan.queries}
    assert tids == {"acme", "bcorp", "ccorp"}
    # Inside the burst window ([12, 18] — one burst centered at 15)
    # the hot tenant's weight is multiplied 8x, so its arrival share
    # must dominate there and stay ~fair outside.
    assert plan.burst_windows == ((12.0, 18.0),)
    burst = [q for q in plan.queries if plan.in_burst(q.t)]
    steady = [q for q in plan.queries if not plan.in_burst(q.t)]
    hot_burst = sum(q.tenant == "acme" for q in burst) / len(burst)
    hot_steady = sum(q.tenant == "acme" for q in steady) / len(steady)
    assert hot_burst > 0.6 > hot_steady
    assert hot_steady == pytest.approx(1 / 3, abs=0.12)
    assert plan_stats_hot_share(plan) == pytest.approx(hot_burst)


def plan_stats_hot_share(plan):
    stats = tg.plan_stats(plan)
    row = stats["tenants"]["acme"]
    return row["burst"] / stats["burst_queries"]


def test_traffic_tenant_plans_are_deterministic_and_serializable():
    a, b = tg.generate(_skew_cfg()), tg.generate(_skew_cfg())
    assert tg.plan_lines(a) == tg.plan_lines(b)
    assert tg.plan_digest(a) == tg.plan_digest(b)
    assert tg.plan_digest(tg.generate(_skew_cfg(seed=1))) != \
        tg.plan_digest(a)
    rec = json.loads(tg.plan_lines(a)[1])  # line 0 is the cfg header
    assert rec["tenant"] in ("acme", "bcorp", "ccorp")
    # Tenant-free configs keep the old single-tenant line shape (and
    # so the recorded single-tenant days' digests).
    plain = tg.generate(tg.TrafficConfig(seed=0, duration_s=10.0))
    assert "tenant" not in json.loads(tg.plan_lines(plain)[1])
    assert "tenants" not in json.loads(tg.plan_lines(plain)[0])["cfg"]


def test_traffic_tenant_config_is_loud():
    with pytest.raises(ValueError, match="hot_tenant"):
        tg.TrafficConfig(seed=0, duration_s=10.0,
                         tenants=(("a", 1.0),), hot_tenant="ghost")
    with pytest.raises(ValueError, match="weight"):
        tg.TrafficConfig(seed=0, duration_s=10.0,
                         tenants=(("a", -1.0),))
    with pytest.raises(ValueError, match="hot_burst_factor"):
        tg.TrafficConfig(seed=0, duration_s=10.0,
                         tenants=(("a", 1.0),), hot_tenant="a",
                         hot_burst_factor=0.0)


def test_tenant_skew_schedule_declares_the_alert_pair():
    entries = chaos.tenant_skew_schedule("acme", 75.0)
    [e] = entries
    assert e.kind == "traffic" and e.target == "serve"
    assert e.alert == "tenant_quota@acme"
    assert tenant_of_slo(e.alert) == "acme"
    with pytest.raises(ValueError, match="hot tenant"):
        chaos.tenant_skew_schedule("", 75.0)
    with pytest.raises(ValueError, match="alert pair"):
        chaos.ChaosEntry(name="x", target="serve", kind="traffic")


# -- tenant_skew verdict ------------------------------------------------------


def _alert_pair(aid, slo, t0, t1):
    base = {"schema": "alerts-v1", "alert_id": aid, "slo": slo,
            "metric": "m", "severity": "warning", "ts": t0,
            "fired_at": t0, "bad_fraction": 1.0, "samples": 4,
            "target": 1.0, "op": "<=", "message": "x"}
    return [dict(base, state="firing"),
            dict(base, state="resolved", ts=t1, bad_fraction=0.0)]


def _tenant_row(queries=100, answered=100, errors=0, rejected=0,
                sheds=0, p99=30.0):
    return {"queries": queries, "answered": answered, "errors": errors,
            "rejected": rejected, "p99_ms": p99, "index_kind": "flat",
            "quota": {"qps": 6.0, "burst_s": 1.0, "sheds": sheds,
                      "tokens": 0.0}}


def _skew_report(**over):
    entries = chaos.entry_dicts(chaos.tenant_skew_schedule("acme", 75.0))
    tenants = {
        "acme": _tenant_row(queries=300, answered=100, rejected=200,
                            sheds=200),
        "bcorp": _tenant_row(),
        "ccorp": _tenant_row(),
    }
    kw = dict(
        traffic={"planned": 500, "fed": 500, "answered": 300,
                 "errors": 0, "rejected": 200, "sha256": "d" * 64},
        serve_alerts=_alert_pair("a1", "tenant_quota@acme", 36.0, 66.0),
        train_alerts=[], serve_remediation=[], train_remediation=[],
        serve_rows=[{"p99_ms": 35.0, "wall_time": float(t)}
                    for t in range(0, 76, 5)],
        quality_windows=[],
        drain={"queries": 500, "answered": 300, "errors": 0,
               "rejected": 200, "queries_dropped": 0, "hot_swaps": 0,
               "tenants": tenants},
        comms={"available": False, "reason": "no trainer"},
        trainer={"segments": 0, "exit_codes": [], "resumed": False},
        observed_fires={}, client_errors=0, window_s=75.0, seed=0,
        p99_target_ms=150.0, recall_floor=0.9, min_hot_swaps=0,
        tenant_hot="acme",
        tenant_quality={tid: [{"recall_at_10": 0.97,
                               "wall_time": float(t)}
                              for t in range(0, 76, 10)]
                        for tid in tenants})
    kw.update(over)
    return build_gameday_report(entries, **kw)


def test_tenant_skew_report_passes_and_validates():
    rep = _skew_report()
    assert rep["verdict"] == "pass", rep["failures"]
    assert validate_gameday_report(rep) is None
    tb = rep["tenants"]
    assert tb["available"] and tb["hot"] == "acme"
    assert tb["tenants"]["acme"]["shed"] == 200  # the quota sheds
    assert tb["tenants"]["acme"]["alerted"] is True
    assert tb["tenants"]["bcorp"]["alerted"] is False
    assert tb["tenants"]["bcorp"]["recall_worst"] == pytest.approx(0.97)


def test_tenant_skew_verdict_demands_shed_and_page():
    # Hot tenant never shed -> isolation unproven.
    quiet = {"acme": _tenant_row(), "bcorp": _tenant_row(),
             "ccorp": _tenant_row()}
    rep = _skew_report(drain={"queries": 500, "answered": 500,
                              "errors": 0, "rejected": 0,
                              "queries_dropped": 0, "hot_swaps": 0,
                              "tenants": quiet})
    assert rep["verdict"] == "fail"
    assert any("never shed" in f for f in rep["failures"])
    # Shed but never paged: the alert pair is the declared evidence.
    rep = _skew_report(serve_alerts=[])
    assert rep["verdict"] == "fail"
    assert any("tenant-scoped alert" in f for f in rep["failures"])
    assert any("unremediated injected fault" in f or
               "fired=False" in f for f in rep["failures"])


def test_tenant_skew_verdict_protects_the_neighbors():
    base = {
        "acme": _tenant_row(queries=300, answered=100, rejected=200,
                            sheds=200),
        "bcorp": _tenant_row(errors=2),
        "ccorp": _tenant_row(),
    }
    rep = _skew_report(drain={"queries": 500, "answered": 298,
                              "errors": 2, "rejected": 200,
                              "queries_dropped": 0, "hot_swaps": 0,
                              "tenants": base})
    assert rep["verdict"] == "fail"
    assert any("'bcorp' saw 2 error(s)" in f for f in rep["failures"])
    # A neighbor p99 breach fails even with the hot tenant shed.
    slow = dict(base, bcorp=_tenant_row(p99=400.0))
    rep = _skew_report(drain={"queries": 500, "answered": 300,
                              "errors": 0, "rejected": 200,
                              "queries_dropped": 0, "hot_swaps": 0,
                              "tenants": slow})
    assert any("p99" in f and "bcorp" in f for f in rep["failures"])
    # A neighbor recall dip outside incident windows fails.
    rep = _skew_report(tenant_quality={
        "acme": [], "ccorp": [],
        "bcorp": [{"recall_at_10": 0.5, "wall_time": 5.0}]})
    assert any("recall" in f and "bcorp" in f for f in rep["failures"])


def test_tenant_block_shape_is_validated():
    rep = _skew_report()
    broken = json.loads(json.dumps(rep))
    del broken["tenants"]["tenants"]["acme"]["shed"]
    assert "shed" in validate_gameday_report(broken)
    broken = json.loads(json.dumps(rep))
    broken["tenants"] = "yes"
    assert validate_gameday_report(broken)
    # Pre-multi-tenant reports (no "tenants" key) must keep validating.
    legacy = json.loads(json.dumps(rep))
    del legacy["tenants"]
    assert validate_gameday_report(legacy) is None


# -- bench_check --tenants gate ----------------------------------------------


def _run_dir(tmp_path, manifest=None, drain=None, answers=None):
    man = manifest if manifest is not None else _manifest(
        _entry("acme", quota_qps=6.0), _entry("bcorp"))
    (tmp_path / "tenants.json").write_text(json.dumps(man))
    if answers is None:
        answers = [{"id": 1, "tenant": "acme", "neighbors": []},
                   {"id": 2, "tenant": "bcorp", "neighbors": []}]
        if drain is None:
            drain = {"event": "serve_drain", "queries": 2,
                     "answered": 2, "errors": 0, "rejected": 0,
                     "tenants": {
                         "acme": _tenant_row(queries=1, answered=1),
                         "bcorp": _tenant_row(queries=1, answered=1)}}
        answers = answers + [drain]
    (tmp_path / "answers.jsonl").write_text(
        "\n".join(json.dumps(a) for a in answers) + "\n")
    return str(tmp_path / "tenants.json")


def test_check_tenants_accepts_consistent_run(bench_check, tmp_path):
    assert bench_check.check_tenants(_run_dir(tmp_path)) == []


def test_check_tenants_refuses_tampered_manifest(bench_check, tmp_path):
    man = _manifest(_entry("acme", quota_qps=-5))
    path = _run_dir(tmp_path, manifest=man)
    out = bench_check.check_tenants(path)
    assert out and all("manifest refused" in v for v in out)


def test_check_tenants_refuses_broken_cross_sums(bench_check, tmp_path):
    drain = {"event": "serve_drain", "queries": 2, "answered": 7,
             "errors": 0, "rejected": 0,
             "tenants": {"acme": _tenant_row(queries=1, answered=1),
                         "bcorp": _tenant_row(queries=1, answered=1)}}
    path = _run_dir(tmp_path, drain=drain)
    out = bench_check.check_tenants(path)
    assert any("cross-sum" in v for v in out)


def test_check_tenants_accounts_unattributed_errors(bench_check,
                                                    tmp_path):
    # An unknown-tenant refusal belongs to NO tenant row; the drain's
    # errors_unattributed remainder keeps the error identity exact —
    # omit it (or fake a negative one) and the gate refuses.
    drain = {"event": "serve_drain", "queries": 2, "answered": 2,
             "errors": 2, "rejected": 0, "errors_unattributed": 2,
             "tenants": {"acme": _tenant_row(queries=1, answered=1),
                         "bcorp": _tenant_row(queries=1, answered=1)}}
    path = _run_dir(tmp_path, drain=drain)
    assert bench_check.check_tenants(path) == []
    no_rem = dict(drain)
    del no_rem["errors_unattributed"]
    path = _run_dir(tmp_path, drain=no_rem)
    assert any("cross-sum" in v
               for v in bench_check.check_tenants(path))
    bad_rem = dict(drain, errors_unattributed=-2)
    path = _run_dir(tmp_path, drain=bad_rem)
    assert any("non-negative" in v
               for v in bench_check.check_tenants(path))


def test_check_tenants_refuses_unregistered_and_aggregate_quality(
        bench_check, tmp_path):
    answers = [
        {"id": 1, "tenant": "ghost", "neighbors": []},
        {"event": "serve_drain", "queries": 1, "answered": 1,
         "errors": 0, "rejected": 0, "quality": {"recall_at_10": 1.0},
         "tenants": {"acme": _tenant_row(queries=1, answered=1),
                     "bcorp": _tenant_row(queries=0, answered=0)}},
    ]
    path = _run_dir(tmp_path, answers=answers)
    out = bench_check.check_tenants(path)
    assert any("unknown tenant" in v for v in out)
    assert any("aggregate quality" in v for v in out)


def test_check_tenants_manifest_only_when_no_answers(bench_check,
                                                     tmp_path):
    man = _manifest(_entry("acme"))
    (tmp_path / "tenants.json").write_text(json.dumps(man))
    assert bench_check.check_tenants(
        str(tmp_path / "tenants.json")) == []
