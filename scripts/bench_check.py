#!/usr/bin/env python
"""Artifact gates, jax-free (docs/OBSERVABILITY.md §Perf observatory).

One mode per ``npairloss-*-v1`` artifact the program writes (fleet
report, alert log, remediation audit, quality log, gameday verdict,
qtrace, WAL, tenants manifest) plus ``--static`` (the invariant
linter): each validates outside input and FAILS (exit != 0) on the
first contract it finds broken.  Speed is not gated here: the
benchmark (``BENCHMARK.json``, ``benchmarks/run.py``) and the driver's
per-cell comparison of every PR do that.

Stdlib-only and jax-free by design: a gate must run on any box that
can read the artifacts.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _log(msg: str) -> None:
    print(f"[bench_check] {msg}", file=sys.stderr, flush=True)


# -- fleet-report gate --------------------------------------------------------

def _load_fleet_aggregate():
    """File-path-load ``obs.fleet.aggregate`` (and its ``stamp``
    dependency) WITHOUT importing the package — the jax-free contract.
    Pre-seeding the dotted names in sys.modules makes aggregate's own
    ``from npairloss_tpu.obs.fleet.stamp import ...`` resolve against
    the seeded module instead of triggering the jax-importing package
    ``__init__``."""
    import importlib.util

    base = os.path.join(REPO, "npairloss_tpu", "obs", "fleet")
    for name, fname in (
        ("npairloss_tpu.obs.fleet.stamp", "stamp.py"),
        ("npairloss_tpu.obs.fleet.aggregate", "aggregate.py"),
    ):
        if name in sys.modules:
            continue
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(base, fname))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules["npairloss_tpu.obs.fleet.aggregate"]


def check_fleet_report(path: str,
                       expect_link: Optional[str] = None) -> List[str]:
    """Gate one fleet report artifact: schema-valid per the one
    contract (validate_fleet_report), per-rank step counts in
    agreement (ranks not training in lockstep is a broken fleet, not a
    measurement), and zero unattributed collective bytes when the
    comms join ran (an unclaimed collective kind means an exchange
    path went uninstrumented).  ``expect_link`` additionally pins the
    comms link kind — the multi-controller ci smoke demands "dcn"
    (collectives priced as crossing host processes), so a run that
    silently fell back to single-process pricing fails the gate."""
    try:
        with open(path) as f:
            report = json.load(f)
    except (OSError, ValueError) as e:
        return [f"fleet report {path} unreadable: {e}"]
    agg = _load_fleet_aggregate()
    err = agg.validate_fleet_report(report)
    if err is not None:
        return [f"fleet report schema-invalid: {err}"]
    violations: List[str] = []
    counts = {r["rank"]: r["steps"] for r in report["ranks"]}
    if len(set(counts.values())) > 1:
        violations.append(
            f"per-rank step counts disagree: {counts} — refusing the "
            "fleet report (ranks did not train in lockstep, or a "
            "stream was truncated)")
    elif not any(counts.values()):
        # All-zero counts AGREE, but a fleet that measured nothing is
        # a dead run (streams lost before the first flush), not a
        # passing one.
        violations.append(
            f"every rank reports 0 steps: {counts} — the fleet "
            "measured nothing (streams lost or training never ran)")
    comms = report.get("comms", {})
    if comms.get("available") and comms.get("unattributed_bytes", 0) > 0:
        violations.append(
            f"{comms['unattributed_bytes']:.0f} collective bytes "
            "unattributed — an exchange path is missing its comm/ "
            "instrumentation")
    if expect_link is not None:
        if not comms.get("available"):
            violations.append(
                f"comms join unavailable but --expect-link {expect_link} "
                "was demanded (no fleet_comms.json priced)")
        elif comms.get("link") != expect_link:
            violations.append(
                f"comms link is {comms.get('link')!r}, expected "
                f"{expect_link!r} — the run did not price its "
                "collectives as crossing host processes")
    if not violations:
        _log(f"fleet report OK ({len(counts)} rank(s), "
             f"{next(iter(counts.values()))} steps each)")
    return violations


# -- alert-log gate -----------------------------------------------------------

def _load_live_alerts():
    """File-path-load ``obs.live.alerts`` WITHOUT importing the package
    (the jax-free contract; same pattern as the fleet loader above —
    alerts.py is deliberately self-contained, so no pre-seeding chain
    is needed beyond its own name)."""
    import importlib.util

    name = "npairloss_tpu.obs.live.alerts"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(REPO, "npairloss_tpu", "obs", "live",
                               "alerts.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def check_alert_log(path: str) -> List[str]:
    """Gate one ``npairloss-alerts-v1`` JSONL artifact: schema-valid
    per the one contract (validate_alert_log), and no CRITICAL alert
    left unresolved — a run that drained while a critical SLO was
    still burning is a failed run, not a noisy one.  Resolved alerts
    of any severity and unresolved warnings are evidence, not
    failures."""
    alerts = _load_live_alerts()
    try:
        records = alerts.load_alert_log(path)
    except OSError as e:
        return [f"alert log {path} unreadable: {e}"]
    err = alerts.validate_alert_log(records)
    if err is not None:
        return [f"alert log schema-invalid: {err}"]
    violations = []
    for alert_id, slo, severity in alerts.unresolved_alerts(records):
        if severity == "critical":
            violations.append(
                f"critical alert {alert_id!r} (SLO {slo!r}) still "
                "firing at end of log — the run drained while burning")
        else:
            _log(f"unresolved {severity} alert {alert_id!r} "
                 f"(SLO {slo!r}) — noted, not gated")
    if not violations:
        fired = sum(1 for r in records if r["state"] == "firing")
        _log(f"alert log OK ({len(records)} event(s), {fired} "
             "alert(s) fired)")
    return violations


# -- remediation-log gate -----------------------------------------------------

def _load_remediate():
    """File-path-load ``resilience.remediate`` (self-contained, stdlib
    only — the same contract as the alerts module) WITHOUT importing
    the package."""
    import importlib.util

    name = "npairloss_tpu.resilience.remediate"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(REPO, "npairloss_tpu", "resilience",
                               "remediate.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def check_remediation_log(path: str,
                          alerts_path: Optional[str] = None) -> List[str]:
    """Gate one ``npairloss-remediation-v1`` audit artifact: schema +
    lifecycle valid per the one contract (validate_remediation_log),
    every action justified by an alert that actually FIRED (cross-
    checked against the paired alerts.jsonl — default: the one next to
    the audit log; an audit with actions but NO alert log is refused,
    because an unjustifiable action cannot be distinguished from a
    justified one), and no CRITICAL incident abandoned mid-budget (a
    failed attempt with attempts remaining and no retry is an actuator
    walking away from a live incident).  Outcome-less attempts (killed
    mid-action) are noted, not gated — the alert gate owns the
    unresolved-incident verdict."""
    rem = _load_remediate()
    try:
        records = rem.load_remediation_log(path)
    except OSError as e:
        return [f"remediation log {path} unreadable: {e}"]
    if alerts_path is None:
        alerts_path = os.path.join(os.path.dirname(os.path.abspath(path)),
                                   "alerts.jsonl")
    alert_records = None
    if os.path.exists(alerts_path):
        alerts = _load_live_alerts()
        try:
            alert_records = alerts.load_alert_log(alerts_path)
        except OSError as e:
            return [f"alert log {alerts_path} unreadable: {e}"]
    elif records:
        return [f"remediation log holds {len(records)} record(s) but no "
                f"alert log exists at {alerts_path} — actions cannot be "
                "justified (action-without-alert refused)"]
    err = rem.validate_remediation_log(records,
                                       alert_records=alert_records)
    if err is not None:
        return [f"remediation log invalid: {err}"]
    violations = []
    # Incidents the alert log shows RESOLVED are never abandonment —
    # an alert that healed after a failed attempt needed no retry.
    resolved = {str(r.get("alert_id")) for r in (alert_records or ())
                if isinstance(r, dict) and r.get("state") == "resolved"}
    for rec_id, policy, aid in rem.abandoned_remediations(
            records, resolved_alert_ids=resolved):
        violations.append(
            f"critical remediation {rec_id!r} (policy {policy!r}, alert "
            f"{aid!r}) failed with attempts remaining and was never "
            "retried — the actuator gave up on a live incident")
    for rec_id, policy, aid in rem.unresolved_remediations(records):
        _log(f"attempt {rec_id!r} (policy {policy!r}, alert {aid!r}) "
             "has no outcome — noted, not gated")
    if not violations:
        attempted = sum(1 for r in records if r["state"] == "attempted")
        ok = sum(1 for r in records if r["state"] == "succeeded")
        _log(f"remediation log OK ({len(records)} event(s), {attempted} "
             f"attempt(s), {ok} succeeded)")
    return violations


# -- quality-log gate ---------------------------------------------------------

def _load_quality():
    """File-path-load ``obs.quality.report`` (self-contained, stdlib
    only — the same contract as the alerts/remediate modules) WITHOUT
    importing the package."""
    import importlib.util

    name = "npairloss_tpu.obs.quality.report"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(REPO, "npairloss_tpu", "obs", "quality",
                               "report.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def _load_gameday():
    """File-path-load ``gameday.verdict`` (self-contained, stdlib only
    — the same contract as the alerts/remediate/quality modules)
    WITHOUT importing the package."""
    import importlib.util

    name = "npairloss_tpu.gameday.verdict"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(REPO, "npairloss_tpu", "gameday",
                               "verdict.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def _load_tenants():
    """File-path-load ``serve.tenants`` (module level stdlib-only —
    the same contract as the alerts/remediate/quality/gameday modules)
    WITHOUT importing the package.  The manifest schema id lives in
    that module alone; this gate never restates the literal."""
    import importlib.util

    name = "npairloss_tpu.serve.tenants"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(REPO, "npairloss_tpu", "serve",
                               "tenants.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def check_tenants(manifest_path: str,
                  answers_path: Optional[str] = None) -> List[str]:
    """Gate one multi-tenant serving run: the tenants manifest must be
    schema-valid per the one contract (validate_tenants_manifest — a
    tampered manifest with unknown keys, a duplicate tenant id, or an
    out-of-range quota is refused with every problem listed), and —
    when an answers log sits next to it (or is named via
    ``--answers-log``) — the run's evidence must be tenant-consistent:
    no answer claiming an unregistered tenant id, per-tenant drain
    counters that cross-sum EXACTLY into the aggregates (quota
    accounting that leaks across tenants shows up as a sum mismatch),
    and recall evidence per tenant (an aggregate quality block with no
    per-tenant breakdown hides exactly the noisy-neighbor regression
    this tier exists to catch)."""
    tmod = _load_tenants()
    try:
        with open(manifest_path, "r", encoding="utf-8") as f:
            manifest = json.load(f)
    except OSError as e:
        return [f"tenants manifest {manifest_path} unreadable: {e}"]
    except ValueError as e:
        return [f"tenants manifest {manifest_path} not JSON: {e}"]
    problems = tmod.validate_tenants_manifest(manifest)
    if problems:
        return [f"tenants manifest refused: {p}" for p in problems]
    specs = {t["tenant_id"]: t for t in manifest["tenants"]}

    if answers_path is None:
        cand = os.path.join(
            os.path.dirname(os.path.abspath(manifest_path)),
            "answers.jsonl")
        answers_path = cand if os.path.exists(cand) else None
    if answers_path is None:
        _log(f"tenants manifest OK ({len(specs)} tenant(s); no "
             "answers log to cross-check)")
        return []
    answers: List[Dict[str, Any]] = []
    try:
        with open(answers_path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    answers.append(json.loads(line))
                except ValueError:
                    continue  # torn tail
    except OSError as e:
        return [f"answers log {answers_path} unreadable: {e}"]

    violations: List[str] = []
    unknown = sorted({a["tenant"] for a in answers
                      if isinstance(a, dict)
                      and isinstance(a.get("tenant"), str)
                      and a["tenant"] not in specs})
    if unknown:
        violations.append(
            f"answers claim unregistered tenant id(s) {unknown} — an "
            "unknown tenant must be refused as an error, never served")
    drain = None
    for a in answers:
        if isinstance(a, dict) and a.get("event") == "serve_drain":
            drain = a
    if drain is None:
        violations.append(
            f"{answers_path}: no serve_drain summary — the per-tenant "
            "accounting cannot be audited")
        return violations
    per = drain.get("tenants")
    if not isinstance(per, dict) or not per:
        violations.append(
            "drain summary has no per-tenant block — a multi-tenant "
            "run must leave per-tenant evidence")
        return violations
    extra = sorted(set(per) - set(specs))
    if extra:
        violations.append(
            f"drain reports unregistered tenant(s) {extra}")
    # The aggregates must be EXACTLY the per-tenant sums: a quota or
    # shed accounted against the wrong tenant cancels nowhere and
    # shows up as a sum mismatch.
    # "errors" alone admits an explicit remainder: unknown-tenant
    # refusals and bad JSON are never admitted, so no tenant row can
    # own them — the drain's errors_unattributed names that count and
    # the identity stays EXACT (a negative or unexplained remainder is
    # still refused).
    unattributed = drain.get("errors_unattributed", 0)
    if not isinstance(unattributed, int) or unattributed < 0:
        violations.append(
            f"errors_unattributed {unattributed!r} is not a "
            "non-negative count")
        unattributed = 0
    for key in ("queries", "answered", "errors", "rejected"):
        agg = drain.get(key)
        total = sum(int(row.get(key, 0)) for row in per.values()
                    if isinstance(row, dict))
        if key == "errors":
            total += unattributed
        if isinstance(agg, int) and total != agg:
            violations.append(
                f"per-tenant {key} sum {total} != aggregate {agg} — "
                "the tenant accounting does not cross-sum"
                + (" (errors_unattributed included)"
                   if key == "errors" else ""))
    if "quality" in drain:
        violations.append(
            "aggregate quality block in a multi-tenant drain — recall "
            "evidence must live inside each tenant's block (one "
            "cross-tenant average hides a single tenant's collapse)")
    for tid, spec in specs.items():
        row = per.get(tid)
        if not isinstance(row, dict):
            violations.append(
                f"tenant {tid!r} missing from the drain's per-tenant "
                "block")
            continue
        if spec.get("recall_floor") is not None \
                and "quality" not in row:
            violations.append(
                f"tenant {tid!r} declares recall_floor "
                f"{spec['recall_floor']} but its drain block carries "
                "no quality evidence (shadow scorer never armed?)")
    if not violations:
        served = sum(int(row.get("answered", 0))
                     for row in per.values() if isinstance(row, dict))
        _log(f"tenants evidence OK ({len(specs)} tenant(s), "
             f"{served} answered, per-tenant sums match the "
             "aggregates)")
    return violations


def _load_qtrace():
    """File-path-load ``obs.qtrace.report`` (self-contained, stdlib
    only — the same contract as the alerts/remediate/quality/gameday
    modules) WITHOUT importing the package."""
    import importlib.util

    name = "npairloss_tpu.obs.qtrace.report"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(REPO, "npairloss_tpu", "obs", "qtrace",
                               "report.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def check_qtrace_log(path: str) -> List[str]:
    """Gate one ``npairloss-qtrace-v1`` exemplar artifact: schema-valid
    per the one contract (validate_qtrace_report — stage vocabulary,
    span nesting/ordering, trace-id uniqueness) AND internally
    consistent (qtrace_p99_consistency — an exemplar set whose worst
    span tree disagrees with the logged p99 budget by more than the
    artifact's declared ring tolerance is doctored evidence: the
    retention rule guarantees the worst query is always retained)."""
    qmod = _load_qtrace()
    try:
        report = qmod.load_qtrace_report(path)
    except OSError as e:
        return [f"qtrace artifact {path} unreadable: {e}"]
    except ValueError as e:
        return [f"qtrace artifact {path} not JSON: {e}"]
    err = qmod.validate_qtrace_report(report)
    if err is not None:
        return [f"qtrace artifact refused: {err}"]
    err = qmod.qtrace_p99_consistency(report)
    if err is not None:
        return [f"qtrace artifact inconsistent: {err}"]
    totals = report["totals"]
    budget = report["budget"]
    _log(f"qtrace artifact OK ({totals['queries']} query(ies), "
         f"{totals['exemplars']} exemplar(s), p99 "
         f"{budget['p99_ms']:.1f}ms dominated by "
         f"{budget['dominant'] or 'n/a'})")
    return []


def _load_wal():
    """File-path-load ``resilience.wal`` + its jax-free seams
    (failpoints, retrying) WITHOUT importing the package — the
    multi-module pre-seed idiom of ``_load_staticcheck``: parent
    package names are stubbed and each loaded leaf is set as an
    attribute so wal.py's guarded ``from npairloss_tpu.resilience
    import failpoints`` resolves."""
    import importlib.util
    import types

    name = "npairloss_tpu.resilience.wal"
    if name in sys.modules:
        return sys.modules[name]
    pkg = "npairloss_tpu.resilience"
    for stub in ("npairloss_tpu", pkg):
        if stub not in sys.modules:
            sys.modules[stub] = types.ModuleType(stub)
    base = os.path.join(REPO, "npairloss_tpu", "resilience")
    for leaf in ("failpoints", "retrying", "wal"):
        mod_name = f"{pkg}.{leaf}"
        if mod_name in sys.modules:
            setattr(sys.modules[pkg], leaf, sys.modules[mod_name])
            continue
        spec = importlib.util.spec_from_file_location(
            mod_name, os.path.join(base, leaf + ".py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
        setattr(sys.modules[pkg], leaf, mod)
    return sys.modules[name]


def check_wal_dir(path: str,
                  min_last_seq: Optional[int] = None) -> List[str]:
    """Gate one ``npairloss-wal-v1`` directory: manifest schema-valid
    per the one contract (validate_wal_dir — record CRCs, sealed-
    segment seals, contiguous sequence numbers; a torn tail on the
    FINAL segment is a crash artifact and passes), and — with
    ``--wal-watermark`` — refusing a log whose last replayable record
    falls short of the externally acknowledged watermark (the
    truncated-then-patched copy the ci.sh cold-restart smoke feeds
    it)."""
    wal_mod = _load_wal()
    err = wal_mod.validate_wal_dir(path, min_last_seq=min_last_seq)
    if err is not None:
        return [f"wal artifact refused: {err}"]
    info = wal_mod.wal_info(path)
    torn = (f", torn tail: {info['torn_bytes']} byte(s) in "
            f"{info['torn_segment']}" if info.get("torn_tail") else "")
    _log(f"wal artifact OK ({info['segments']} segment(s), "
         f"{info['records']} record(s), last_seq {info['last_seq']}"
         f"{torn})")
    return []


def check_gameday_report(path: str) -> List[str]:
    """Gate one ``npairloss-gameday-v1`` verdict: schema-valid and
    PASSING per the one contract (validate_gameday_report recomputes
    every gate from the report's own evidence — schema violations,
    an unremediated injected fault, an SLO breach outside the declared
    incident windows, a dropped query, or a tampered ``verdict:
    "pass"`` are all refused).  When the run directory's serve alert
    log sits next to the report, the fault blocks are additionally
    cross-checked against it: a fault claiming its alert fired while
    the on-disk log shows no firing for that SLO is a fabricated
    report, refused."""
    gmod = _load_gameday()
    try:
        report = gmod.load_gameday_report(path)
    except OSError as e:
        return [f"gameday report {path} unreadable: {e}"]
    except ValueError as e:
        return [f"gameday report {path} not JSON: {e}"]
    err = gmod.validate_gameday_report(report)
    if err is not None:
        return [f"gameday verdict refused: {err}"]
    violations: List[str] = []
    alerts_path = os.path.join(
        os.path.dirname(os.path.abspath(path)), "serve_tel",
        "alerts.jsonl")
    if os.path.exists(alerts_path):
        alerts = _load_live_alerts()
        try:
            records = alerts.load_alert_log(alerts_path)
        except OSError as e:
            return [f"alert log {alerts_path} unreadable: {e}"]
        fired_slos = {r.get("slo") for r in records
                      if isinstance(r, dict)
                      and r.get("state") == "firing"}
        for fault in report.get("faults", []):
            if (fault.get("target") == "serve" and fault.get("alert")
                    and fault.get("alert_fired")
                    and fault["alert"] not in fired_slos):
                violations.append(
                    f"fault {fault.get('name')}: report claims alert "
                    f"{fault['alert']!r} fired but {alerts_path} shows "
                    "no firing for it — fabricated evidence")
    if not violations:
        zero = report["zero_drop"]
        _log(f"gameday verdict OK ({len(report['faults'])} fault(s) "
             f"remediated, {zero['hot_swaps']} hot-swap(s), "
             f"{zero['queries_dropped']} dropped)")
    return violations


def check_quality_log(path: str,
                      alerts_path: Optional[str] = None) -> List[str]:
    """Gate one ``npairloss-quality-v1`` shadow-recall artifact:
    schema-valid per the one contract (validate_quality_report); every
    window that breached the DECLARED recall floor must be matched by a
    recall alert that actually FIRED (cross-checked against the paired
    alerts.jsonl — a breach with no alert log at all is refused, since
    an unobserved quality regression cannot be distinguished from an
    observed one); and the shadow scorer must not have silently stopped
    sampling mid-run (the summary's stale last-sample wall time).
    Breaches WITH a fired alert are evidence the loop worked, not
    failures — the alert gate owns the unresolved-incident verdict."""
    qmod = _load_quality()
    try:
        records = qmod.load_quality_report(path)
    except OSError as e:
        return [f"quality log {path} unreadable: {e}"]
    err = qmod.validate_quality_report(records)
    if err is not None:
        return [f"quality log schema-invalid: {err}"]
    violations: List[str] = []
    breaches = qmod.quality_breaches(records)
    if breaches:
        if alerts_path is None:
            alerts_path = os.path.join(
                os.path.dirname(os.path.abspath(path)), "alerts.jsonl")
        fired_metrics = set()
        if os.path.exists(alerts_path):
            alerts = _load_live_alerts()
            try:
                alert_records = alerts.load_alert_log(alerts_path)
            except OSError as e:
                return [f"alert log {alerts_path} unreadable: {e}"]
            fired_metrics = {r.get("metric") for r in alert_records
                             if isinstance(r, dict)
                             and r.get("state") == "firing"}
        for i, metric, recall, floor in breaches:
            if metric not in fired_metrics:
                violations.append(
                    f"window record {i}: recall {recall:.4f} below the "
                    f"declared floor {floor:g} with NO fired alert on "
                    f"{metric!r} ({alerts_path}) — the quality SLO "
                    "slept through a real regression")
        matched = sum(1 for _, m, _, _ in breaches if m in fired_metrics)
        if matched:
            _log(f"{matched} floor breach(es) matched by a fired recall "
                 "alert — the loop observed them; noted, not gated")
    stale = qmod.stale_shadow(records)
    if stale is not None:
        violations.append(f"quality log: {stale}")
    if not violations:
        summary = qmod.quality_summary(records)
        _log(f"quality log OK ({summary['windows']} window(s), "
             f"{summary['sampled_total']} sample(s), "
             f"{summary['breaches']} breach(es))")
    return violations


# -- staticcheck gate ---------------------------------------------------------

def _load_staticcheck():
    """File-path-load the ``npairloss_tpu.analysis`` chain WITHOUT
    importing the package (the jax-free contract).  Unlike the
    single-file loaders above, the suite is a multi-module package
    whose driver does ``from npairloss_tpu.analysis import contracts``
    — so the parent package names are seeded as stub modules and each
    loaded submodule is set as an attribute on its parent."""
    import importlib.util
    import types

    pkg = "npairloss_tpu.analysis"
    if pkg in sys.modules:
        return sys.modules[pkg + ".runner"]
    for stub in ("npairloss_tpu", pkg):
        if stub not in sys.modules:
            sys.modules[stub] = types.ModuleType(stub)
    base = os.path.join(REPO, "npairloss_tpu", "analysis")
    # Dependency order: leaves first, the driver last.
    for leaf in ("findings", "tree", "report", "purity", "scopes",
                 "locks", "contracts", "vocab", "markers", "runner"):
        name = f"{pkg}.{leaf}"
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(base, leaf + ".py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        setattr(sys.modules[pkg], leaf, mod)
    return sys.modules[pkg + ".runner"]


def check_static(root: str, diff_base: Optional[str] = None) -> List[str]:
    """Run the invariant linter over ``root`` (docs/STATICCHECK.md):
    every finding not in the tree's committed allowlist is a
    violation.  The ci.sh staticcheck-stage wiring — and the teeth the
    seeded fixture trees under tests/fixtures/staticcheck are held
    to."""
    runner = _load_staticcheck()
    try:
        report = runner.run_suite(root, diff_base=diff_base)
    except ValueError as e:
        return [f"staticcheck could not run: {e}"]
    violations = [
        f"staticcheck [{rec['pass']}] {rec['path']}:{rec['line']}: "
        f"{rec['message']}"
        for rec in report["findings"]
    ]
    if not violations:
        ran = [p["name"] for p in report["passes"] if not p["skipped"]]
        skipped = [p["name"] for p in report["passes"] if p["skipped"]]
        _log(f"staticcheck OK ({', '.join(ran)}"
             + (f"; skipped: {', '.join(skipped)}" if skipped else "")
             + f"; {report['summary']['allowlisted']} allowlisted)")
    return violations


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="artifact gates: validate one npairloss-*-v1 "
        "artifact, or run the invariant linter (stdlib-only, jax-free)")
    ap.add_argument(
        "--fleet-report", dest="fleet_report", metavar="PATH",
        help="gate a fleet report artifact: schema-valid "
        "(npairloss-fleet-report-v1), per-rank step counts agree, "
        "zero unattributed collective bytes — the ci.sh fleet-smoke "
        "wiring",
    )
    ap.add_argument(
        "--expect-link", dest="expect_link", choices=["ici", "dcn"],
        help="with --fleet-report: additionally require the comms "
        "block's link kind (the multi-controller smoke pins 'dcn')",
    )
    ap.add_argument(
        "--alerts", metavar="PATH",
        help="gate a live-observatory alert log: schema-valid "
        "(npairloss-alerts-v1) and no unresolved critical alert — "
        "the ci.sh live-obs-smoke wiring",
    )
    ap.add_argument(
        "--remediation", metavar="PATH",
        help="gate a remediation audit log: schema-valid "
        "(npairloss-remediation-v1), every action justified by a "
        "fired alert, no abandoned critical remediation — the ci.sh "
        "chaos-suite wiring",
    )
    ap.add_argument(
        "--alerts-log", dest="alerts_log", metavar="PATH",
        help="with --remediation/--quality: the paired alerts.jsonl "
        "for the cross-checks (default: alerts.jsonl next to the "
        "gated log)",
    )
    ap.add_argument(
        "--quality", metavar="PATH",
        help="gate a shadow-recall quality log: schema-valid "
        "(npairloss-quality-v1), every recall-floor breach matched by "
        "a fired alert, no silently-stalled shadow scorer — the ci.sh "
        "quality-smoke wiring",
    )
    ap.add_argument(
        "--gameday", metavar="PATH",
        help="gate a gameday verdict: schema-valid "
        "(npairloss-gameday-v1) and PASSING — every "
        "injected fault remediated, SLOs held outside incident "
        "windows, zero dropped queries across the hot-swaps — with "
        "the fault blocks cross-checked against the run's serve "
        "alert log when present — the ci.sh gameday-stage wiring",
    )
    ap.add_argument(
        "--qtrace", metavar="PATH",
        help="gate a query-trace exemplar artifact: schema-valid "
        "(npairloss-qtrace-v1), stage vocabulary and span nesting "
        "intact, trace ids unique, and "
        "the exemplar worst case consistent with the logged p99 "
        "budget within the ring tolerance — the ci.sh qtrace-smoke "
        "wiring",
    )
    ap.add_argument(
        "--wal", metavar="PATH",
        help="gate a durable-ingest WAL directory: schema-valid "
        "(npairloss-wal-v1), record CRCs and sealed-segment seals "
        "intact, sequence numbers contiguous — the ci.sh "
        "cold-restart-smoke wiring",
    )
    ap.add_argument(
        "--wal-watermark", dest="wal_watermark", type=int,
        metavar="SEQ",
        help="with --wal: additionally refuse a log whose last "
        "replayable record falls short of this acknowledged sequence "
        "number (a truncated-then-patched copy)",
    )
    ap.add_argument(
        "--static", nargs="?", const=REPO, default=None, metavar="ROOT",
        help="run the invariant linter (docs/STATICCHECK.md) over ROOT "
        "(default: this repo) and fail on any finding outside the "
        "committed allowlist — the ci.sh staticcheck-stage wiring",
    )
    ap.add_argument(
        "--static-diff", dest="static_diff", metavar="BASE",
        help="with --static: restrict findings to files changed since "
        "the git ref (the fast incremental hook)",
    )
    ap.add_argument(
        "--tenants", metavar="MANIFEST",
        help="gate a multi-tenant serving run: refuse a tampered "
        "tenants manifest (schema, duplicate ids, out-of-range "
        "quotas) and, against the answers log next to it (or "
        "--answers-log), refuse answers claiming unregistered "
        "tenants, drain counters that do not cross-sum, and recall "
        "evidence hidden in an aggregate block",
    )
    ap.add_argument(
        "--answers-log", dest="answers_log", metavar="PATH",
        help="with --tenants: the serve answers JSONL to cross-check "
        "(default: answers.jsonl beside the manifest, when present)",
    )
    args = ap.parse_args(argv)

    if args.static:
        violations = check_static(args.static, diff_base=args.static_diff)
        if violations:
            for v in violations:
                print(f"REGRESSION: {v}")
            return 1
        print(f"bench_check OK (staticcheck over {args.static})")
        return 0

    if args.tenants:
        violations = check_tenants(args.tenants,
                                   answers_path=args.answers_log)
        if violations:
            for v in violations:
                print(f"REGRESSION: {v}")
            return 1
        print(f"bench_check OK (tenants manifest {args.tenants})")
        return 0

    if args.wal:
        violations = check_wal_dir(args.wal,
                                   min_last_seq=args.wal_watermark)
        if violations:
            for v in violations:
                print(f"REGRESSION: {v}")
            return 1
        print(f"bench_check OK (wal artifact {args.wal})")
        return 0

    if args.gameday:
        violations = check_gameday_report(args.gameday)
        if violations:
            for v in violations:
                print(f"REGRESSION: {v}")
            return 1
        print(f"bench_check OK (gameday verdict {args.gameday})")
        return 0

    if args.qtrace:
        violations = check_qtrace_log(args.qtrace)
        if violations:
            for v in violations:
                print(f"REGRESSION: {v}")
            return 1
        print(f"bench_check OK (qtrace artifact {args.qtrace})")
        return 0

    if args.quality:
        violations = check_quality_log(args.quality,
                                       alerts_path=args.alerts_log)
        if violations:
            for v in violations:
                print(f"REGRESSION: {v}")
            return 1
        print(f"bench_check OK (quality log {args.quality})")
        return 0

    if args.remediation:
        violations = check_remediation_log(args.remediation,
                                           alerts_path=args.alerts_log)
        if violations:
            for v in violations:
                print(f"REGRESSION: {v}")
            return 1
        print(f"bench_check OK (remediation log {args.remediation})")
        return 0

    if args.alerts:
        violations = check_alert_log(args.alerts)
        if violations:
            for v in violations:
                print(f"REGRESSION: {v}")
            return 1
        print(f"bench_check OK (alert log {args.alerts})")
        return 0

    if args.fleet_report:
        violations = check_fleet_report(args.fleet_report,
                                        expect_link=args.expect_link)
        if violations:
            for v in violations:
                print(f"REGRESSION: {v}")
            return 1
        print(f"bench_check OK (fleet report {args.fleet_report})")
        return 0

    ap.error("pick a gate: --fleet-report, --alerts, --remediation, "
             "--quality, --gameday, --qtrace, --wal, --tenants or "
             "--static (see --help)")


if __name__ == "__main__":
    sys.exit(main())
