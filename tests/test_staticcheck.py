"""The invariant linter (npairloss_tpu/analysis, docs/STATICCHECK.md).

Accept/refuse fixtures per pass (tests/fixtures/staticcheck), the
npairloss-staticcheck-v1 report contract, allowlist + --diff modes,
the jax-free CLI entry, and the ``bench_check --static`` gate driven
via subprocess like the existing --alerts/--fleet-report modes.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from npairloss_tpu.analysis import (
    PASS_NAMES,
    run_suite,
    validate_staticcheck_report,
)
from npairloss_tpu.analysis.markers import parse_durations_log
from npairloss_tpu.analysis.runner import changed_files, update_timings

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "staticcheck")
BENCH_CHECK = os.path.join(REPO, "scripts", "bench_check.py")


def _keys(report, pass_name=None):
    return [rec["key"] for rec in report["findings"]
            if pass_name is None or rec["pass"] == pass_name]


def _write_tree(root, files):
    for rel, content in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(textwrap.dedent(content))
    return str(root)


# -- vocabulary pins ----------------------------------------------------------


def test_cli_pass_choices_pinned():
    """cli.py hardcodes the pass vocabulary (jax-free parser contract);
    pinned against the runner's registry so drift is a test failure —
    the same contract as _PRECISION_CHOICES."""
    from npairloss_tpu.cli import _STATICCHECK_PASSES

    assert tuple(_STATICCHECK_PASSES) == tuple(PASS_NAMES)


# -- fixtures: accept / refuse per pass ---------------------------------------


def test_clean_fixture_accepted():
    report = run_suite(os.path.join(FIXTURES, "clean"))
    assert report["findings"] == []
    assert report["allowlisted"] == []
    # Every pass actually RAN on the clean tree (markers included —
    # it ships a timing history), so acceptance is evidence, not a
    # skipped suite.
    assert all(not p["skipped"] for p in report["passes"])
    assert validate_staticcheck_report(report) is None


@pytest.mark.parametrize("tree,pass_name,detail_fragment", [
    ("jax_leak", "purity", "reaches-jax"),
    ("unscoped_collective", "scopes", "psum"),
    ("unguarded_mutation", "locks", "Registry.reset._items"),
    ("orphan_validator", "contracts", "npairloss-orphan-v1"),
    ("undocumented_flag", "vocab", "failpoint-serve.bogus"),
    ("unmarked_slow", "markers", "test_giant_compile"),
])
def test_seeded_fixture_refused(tree, pass_name, detail_fragment):
    report = run_suite(os.path.join(FIXTURES, tree))
    keys = _keys(report, pass_name)
    assert any(detail_fragment in k for k in keys), \
        f"{tree}: expected a {pass_name} finding matching " \
        f"{detail_fragment!r}, got {_keys(report)}"


def test_undocumented_flag_fixture_also_flags_doc_drift():
    report = run_suite(os.path.join(FIXTURES, "undocumented_flag"))
    assert any("flag---no-such-flag" in k for k in _keys(report, "vocab"))


def test_repo_is_clean():
    """The repo's own gate: zero non-allowlisted findings.  This IS
    the acceptance criterion — a violation introduced anywhere fails
    here first."""
    report = run_suite(REPO)
    assert report["findings"] == [], [
        r["message"] for r in report["findings"]]


# -- per-pass teeth on synthesized trees --------------------------------------


def test_purity_undeclared_file_path_load(tmp_path):
    root = _write_tree(tmp_path, {
        "scripts/gate.py": """\
            import importlib.util
            import os

            spec = importlib.util.spec_from_file_location(
                "npairloss_tpu.obs.sneaky",
                os.path.join("npairloss_tpu", "obs", "sneaky.py"))
        """,
        "npairloss_tpu/obs/sneaky.py": "VALUE = 1\n",
    })
    report = run_suite(root)
    assert any("undeclared-npairloss_tpu.obs.sneaky" in k
               for k in _keys(report, "purity"))


def test_purity_lazy_import_tolerated(tmp_path):
    root = _write_tree(tmp_path, {
        "npairloss_tpu/obs/live/alerts.py": """\
            import json


            def percentile(xs, q):
                from npairloss_tpu.heavy import jax_percentile
                return jax_percentile(xs, q)
        """,
        "npairloss_tpu/heavy.py": "import jax\n",
    })
    assert _keys(run_suite(root), "purity") == []


def test_scopes_annotation_honored(tmp_path):
    root = _write_tree(tmp_path, {
        "npairloss_tpu/ops/x.py": """\
            import jax


            def peek(x, axis_name):
                return jax.lax.pmax(x, axis_name)  # comm-scope-ok: scalar probe priced by the harness
        """,
    })
    assert _keys(run_suite(root), "scopes") == []


def test_locks_mutating_call_in_expression_context(tmp_path):
    """``x = self._d.pop(k)`` mutates exactly like the bare-statement
    form — the review-round blind spot, pinned."""
    root = _write_tree(tmp_path, {
        "npairloss_tpu/z.py": """\
            import threading


            class Engine:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._pending = {}  # guarded-by: _lock
                    self._last = {}  # guarded-by: _lock

                def take(self, k):
                    stale = self._pending.pop(k, None)
                    return stale

                def chain_store(self, p, k, v):
                    self._last[p][k] = v

                def fine(self, k):
                    with self._lock:
                        return self._pending.pop(k, None)
        """,
    })
    keys = _keys(run_suite(root), "locks")
    assert any("Engine.take._pending" in k for k in keys)
    assert any("Engine.chain_store._last" in k for k in keys)
    assert not any("Engine.fine" in k for k in keys)


def test_locks_annotation_on_continuation_line(tmp_path):
    """A '# guarded-by:' trailing the SECOND line of a backslash-
    continued assignment must still register (the SLOEvaluator._burning
    shape) — a dead annotation is worse than none."""
    root = _write_tree(tmp_path, {
        "npairloss_tpu/w.py": """\
            import threading


            class Ev:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._burning = \\
                        {}  # guarded-by: _lock

                def poke(self, k):
                    self._burning[k] = True
        """,
    })
    keys = _keys(run_suite(root), "locks")
    assert any("Ev.poke._burning" in k for k in keys)


def test_locks_real_annotations_register():
    """Every class this PR annotated actually ARMS the checker — a
    dead annotation (e.g. on a continuation line the comment scan
    misses) would claim enforcement that does not exist."""
    import ast as ast_mod

    from npairloss_tpu.analysis.locks import guarded_attrs
    from npairloss_tpu.analysis.tree import SourceTree

    tree = SourceTree(REPO)
    expected = {
        ("npairloss_tpu/obs/live/slo.py", "SLOEvaluator"):
            ({"_burning"}, {"_lock"}),
        ("npairloss_tpu/obs/live/registry.py", "MetricRegistry"):
            ({"_metrics"}, {"_lock"}),
        ("npairloss_tpu/resilience/remediate.py", "RemediationEngine"):
            ({"_seq", "_pending", "_undos", "_last", "history"},
             {"_lock"}),
        ("npairloss_tpu/serve/server.py", "RetrievalServer"):
            ({"engines", "engine", "freshness", "swaps", "queries",
              "answered", "errors", "_ingest_watermark",
              "_ckpt_watermark"},
             {"_lock", "_ingest_lock"}),
    }
    for (rel, cls_name), (attrs, locks) in expected.items():
        mod = tree.parse(rel)
        cls = next(n for n in ast_mod.walk(mod)
                   if isinstance(n, ast_mod.ClassDef)
                   and n.name == cls_name)
        guarded = guarded_attrs(cls, tree.comments(rel))
        missing = attrs - set(guarded)
        assert not missing, f"{cls_name}: {missing} never registered"
        assert set(guarded.values()) == locks, cls_name


def test_locks_missing_lock_attr_flagged(tmp_path):
    root = _write_tree(tmp_path, {
        "npairloss_tpu/y.py": """\
            class Thing:
                def __init__(self):
                    self.items = []  # guarded-by: _lock
        """,
    })
    keys = _keys(run_suite(root), "locks")
    assert any("Thing.items" in k for k in keys)


def test_contracts_key_twin_drift(tmp_path):
    root = _write_tree(tmp_path, {
        "npairloss_tpu/obs/sinks.py":
            'FLEET_KEYS = ("process_index", "process_count")\n',
        "npairloss_tpu/obs/fleet/stamp.py":
            'STAMP_KEYS = ("process_index", "process_count", '
            '"local_device_ids")\n',
    })
    assert any("twin-FLEET_KEYS" in k
               for k in _keys(run_suite(root), "contracts"))


def test_contracts_restated_literal(tmp_path):
    root = _write_tree(tmp_path, {
        "npairloss_tpu/a.py": """\
            A_SCHEMA = "npairloss-aaa-v1"


            def validate_a(rec):
                return None if rec.get("schema") == A_SCHEMA else "bad"
        """,
        "npairloss_tpu/b.py": """\
            def build():
                return {"schema": "npairloss-aaa-v1"}
        """,
    })
    assert any("restated-npairloss-aaa-v1" in k
               for k in _keys(run_suite(root), "contracts"))


def test_vocab_choice_pin_drift(tmp_path):
    root = _write_tree(tmp_path, {
        "npairloss_tpu/cli.py":
            '_PRECISION_CHOICES = ("bf16", "mxu")\n',
        "npairloss_tpu/models/precision.py": """\
            _POLICIES = {"bf16": 1, "mxu": 2, "fp32_parity": 3}
        """,
    })
    assert any("pin-_PRECISION_CHOICES" in k
               for k in _keys(run_suite(root), "vocab"))


@pytest.mark.parametrize("flag,finding", [
    ("--no-such-flag", True), ("--trace 1", False)])
def test_vocab_lints_the_documented_benchmark_command(
        tmp_path, flag, finding):
    """A fenced `python3 benchmarks/run.py ...` is held to THAT file's
    argparse: the benchmark that exists is the one the docs may cite."""
    root = _write_tree(tmp_path, {
        "benchmarks/run.py": """            import argparse
            ap = argparse.ArgumentParser()
            ap.add_argument("--workload", required=True)
            ap.add_argument("--trace", type=int, default=0)
        """,
        "README.md": f"""            ```
            python3 benchmarks/run.py --workload x {flag}
            ```
        """,
    })
    keys = _keys(run_suite(root), "vocab")
    assert any("flag---no-such-flag" in k for k in keys) if finding \
        else not keys


def test_vocab_undocumented_watchdog(tmp_path):
    root = _write_tree(tmp_path, {
        "npairloss_tpu/obs/live/watchdogs.py": """\
            def ghost():
                return Spec(name="ghost_dog", metric="x")
        """,
        "docs/OBSERVABILITY.md": "# Obs\n\nNothing here.\n",
    })
    assert any("watchdog-ghost_dog" in k
               for k in _keys(run_suite(root), "vocab"))


def test_vocab_stale_failpoint_row(tmp_path):
    root = _write_tree(tmp_path, {
        "npairloss_tpu/x.py": """\
            from npairloss_tpu.resilience import failpoints


            def go():
                failpoints.fire("real.fault")
        """,
        "docs/RESILIENCE.md": """\
            | failpoint | injects |
            |---|---|
            | `real.fault` | a real one |
            | `ghost.fault` | documented but never fired |
        """,
    })
    assert any("failpoint-ghost.fault" in k
               for k in _keys(run_suite(root), "vocab"))


def test_syntax_error_is_a_finding(tmp_path):
    root = _write_tree(tmp_path, {
        "npairloss_tpu/broken.py": "def broken(:\n",
    })
    report = run_suite(root)
    assert any("parse-error" in k for k in _keys(report))


# -- allowlist + diff ---------------------------------------------------------


def test_allowlist_tolerates_named_finding(tmp_path):
    fixture = os.path.join(FIXTURES, "unguarded_mutation")
    base = run_suite(fixture)
    (key,) = _keys(base, "locks")
    allow = tmp_path / "allow.json"
    allow.write_text(json.dumps(
        {"allow": [{"key": key, "why": "fixture test"}]}))
    report = run_suite(fixture, allowlist_path=str(allow))
    assert report["findings"] == []
    assert [r["key"] for r in report["allowlisted"]] == [key]
    # The allowlisted finding still counts in its pass row (visible,
    # not vanished) and the report stays validator-clean.
    assert validate_staticcheck_report(report) is None


def test_bad_allowlist_is_loud(tmp_path):
    allow = tmp_path / "allow.json"
    allow.write_text('{"allow": [42]}')
    with pytest.raises(ValueError):
        run_suite(os.path.join(FIXTURES, "clean"),
                  allowlist_path=str(allow))


def _git(cwd, *args):
    subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
        cwd=cwd, check=True, capture_output=True)


def test_diff_mode_restricts_to_changed_files(tmp_path):
    root = _write_tree(tmp_path, {
        "npairloss_tpu/old.py": """\
            import jax


            def old(x, a):
                return jax.lax.psum(x, a)
        """,
    })
    _git(root, "init", "-q")
    _git(root, "add", "-A")
    _git(root, "commit", "-qm", "base")
    _write_tree(tmp_path, {
        "npairloss_tpu/new.py": """\
            import jax


            def new(x, a):
                return jax.lax.pmean(x, a)
        """,
    })
    full = run_suite(root)
    assert len(_keys(full, "scopes")) == 2
    diffed = run_suite(root, diff_base="HEAD")
    keys = _keys(diffed, "scopes")
    assert keys and all("new.py" in k for k in keys)
    # And the plumbing: changed_files sees exactly the untracked file.
    assert changed_files(root, "HEAD") == ["npairloss_tpu/new.py"]


def test_diff_mode_bad_ref_is_loud(tmp_path):
    with pytest.raises(ValueError):
        run_suite(str(tmp_path), diff_base="no-such-ref")


def test_diff_mode_on_subtree_root(tmp_path):
    """--diff scanning a SUBTREE of the git repo: diff paths must be
    rebased to the tree root (git emits repo-root-relative without
    --relative), or tracked-file findings silently vanish."""
    repo = tmp_path / "repo"
    sub = repo / "sub"
    _write_tree(sub, {
        "npairloss_tpu/x.py": """\
            import jax


            def f(x, a):
                return jax.lax.psum(x, a)
        """,
    })
    _git(str(repo), "init", "-q")
    _git(str(repo), "add", "-A")
    _git(str(repo), "commit", "-qm", "base")
    # Modify the tracked file (stays a violation).
    path = sub / "npairloss_tpu" / "x.py"
    path.write_text(path.read_text() + "\n# touched\n")
    report = run_suite(str(sub), diff_base="HEAD")
    assert any("psum" in k for k in _keys(report, "scopes")), \
        "tracked-modified finding dropped on a subtree root"


def test_diff_mode_excludes_unrelated_parse_error(tmp_path):
    """A pre-existing broken file must not fail an incremental run of
    an unrelated change (the --diff contract)."""
    root = _write_tree(tmp_path, {
        "npairloss_tpu/broken.py": "def broken(:\n",
    })
    _git(root, "init", "-q")
    _git(root, "add", "-A")
    _git(root, "commit", "-qm", "base")
    _write_tree(tmp_path, {"npairloss_tpu/fine.py": "VALUE = 1\n"})
    assert _keys(run_suite(root))  # full run still reports it
    assert _keys(run_suite(root, diff_base="HEAD")) == []


def test_files_scanned_counts_every_pass(tmp_path):
    """Per-pass files_scanned reports what the pass actually looked at
    (cache hits included) — not a parse-cache delta that credits
    everything to whichever pass ran first."""
    report = run_suite(os.path.join(FIXTURES, "clean"))
    by_name = {p["name"]: p["files_scanned"] for p in report["passes"]}
    # purity and scopes both read the package sources; with the old
    # delta accounting every pass after the first reported 0.
    assert by_name["purity"] > 0
    assert by_name["scopes"] > 0
    assert by_name["locks"] > 0


def test_both_drivers_share_one_vocabulary():
    """The cli subcommand and the runner's own parser are two front
    doors to one run_from_args — their option sets and defaults are
    pinned equal so a new flag cannot land in only one."""
    import argparse

    from npairloss_tpu import cli
    from npairloss_tpu.analysis import runner

    def options(parser):
        out = {}
        for a in parser._actions:
            if isinstance(a, argparse._HelpAction):
                continue
            out[a.dest] = (tuple(a.option_strings),
                           tuple(a.choices) if a.choices else None,
                           a.default)
        return out

    runner_opts = options(runner._build_parser())
    sc = argparse.ArgumentParser()
    cli._add_staticcheck_options(sc)
    assert options(sc) == runner_opts


# -- report contract ----------------------------------------------------------


def test_report_validator_teeth():
    good = run_suite(os.path.join(FIXTURES, "unscoped_collective"))
    assert validate_staticcheck_report(good) is None

    def broken(mutate):
        rep = json.loads(json.dumps(good))
        mutate(rep)
        return validate_staticcheck_report(rep)

    assert "schema" in broken(
        lambda r: r.update(schema="npairloss-staticcheck-v2"))
    assert broken(lambda r: r.pop("summary")) is not None
    assert broken(
        lambda r: r["findings"][0].pop("message")) is not None
    assert "pass" in broken(
        lambda r: r["findings"][0].update({"pass": "ghost"}))
    assert "key" in broken(
        lambda r: r["findings"][0].update(key="wrong:format"))
    assert "claims" in broken(
        lambda r: r["passes"][1].update(findings=99))
    assert "summary.findings" in broken(
        lambda r: r["summary"].update(findings=0))
    assert "skipped" in broken(
        lambda r: r["passes"][1].update(skipped=True))
    assert "duplicate" in broken(
        lambda r: r["passes"].append(dict(r["passes"][0])))
    assert broken(lambda r: r.update(passes=[])) is not None


# -- timing history plumbing --------------------------------------------------


def test_parse_durations_log():
    text = textwrap.dedent("""\
        ============== slowest durations ===============
        12.34s call     tests/test_a.py::test_one
        0.50s setup    tests/test_a.py::test_one
        3.21s call     tests/test_b.py::TestC::test_two[case0]
        (durations < 0.005s hidden)
    """)
    d = parse_durations_log(text)
    assert d["tests/test_a.py::test_one"] == pytest.approx(12.84)
    assert d["tests/test_b.py::TestC::test_two[case0]"] == \
        pytest.approx(3.21)


def test_update_timings_roundtrip(tmp_path):
    log = tmp_path / "t1.log"
    log.write_text("55.00s call tests/test_x.py::test_slow\n")
    root = _write_tree(tmp_path, {
        "tests/test_x.py": """\
            def test_slow():
                assert True
        """,
    })
    out = update_timings(root, str(log), 10.0)
    payload = json.load(open(out))
    assert payload["threshold_s"] == 10.0
    report = run_suite(root)
    assert any("test_slow" in k for k in _keys(report, "markers"))


# -- subprocess drives: the gate + the jax-free CLI ---------------------------


def _poison_env(tmp_path):
    """An env whose ``import jax`` raises: proves the jax-free
    contract by execution, not by inspection."""
    poison = tmp_path / "poison"
    poison.mkdir()
    (poison / "jax.py").write_text(
        'raise ImportError("jax imported inside a jax-free tool")\n')
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{poison}{os.pathsep}{REPO}"
    env.pop("JAX_PLATFORMS", None)
    return env


def test_bench_check_static_gate_subprocess(tmp_path):
    env = _poison_env(tmp_path)
    ok = subprocess.run(
        [sys.executable, BENCH_CHECK, "--static",
         os.path.join(FIXTURES, "clean")],
        capture_output=True, text=True, env=env)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    for tree in ("jax_leak", "unscoped_collective",
                 "unguarded_mutation", "orphan_validator",
                 "undocumented_flag", "unmarked_slow"):
        bad = subprocess.run(
            [sys.executable, BENCH_CHECK, "--static",
             os.path.join(FIXTURES, tree)],
            capture_output=True, text=True, env=env)
        assert bad.returncode == 1, f"{tree}: {bad.stdout}{bad.stderr}"
        assert "REGRESSION: staticcheck" in bad.stdout, bad.stdout


def test_cli_staticcheck_jax_free_end_to_end(tmp_path):
    """``python -m npairloss_tpu staticcheck`` in a venv whose jax
    import RAISES: the whole entry path (package __init__, cli parser,
    analysis) must never touch it, and the emitted report must be
    validator-accepted."""
    env = _poison_env(tmp_path)
    out = tmp_path / "report.json"
    proc = subprocess.run(
        [sys.executable, "-m", "npairloss_tpu", "staticcheck",
         os.path.join(FIXTURES, "clean"), "--out", str(out)],
        capture_output=True, text=True, env=env, cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.load(open(out))
    assert validate_staticcheck_report(report) is None
    assert report["summary"]["findings"] == 0
