#!/usr/bin/env bash
# The one CI entry point: lint + fault-injection smoke + the ROADMAP.md
# tier-1 test command.
#
#   scripts/ci.sh            # lint, smoke, then full tier-1 pytest
#   scripts/ci.sh --lint-only
#
# Keep the pytest invocation in sync with ROADMAP.md "Tier-1 verify" —
# the driver enforces that exact command; this script exists so humans
# and hooks run the same thing.

set -euo pipefail
cd "$(dirname "$0")/.."

echo "== lint: no bare print() in library code =="
python scripts/check_no_print.py

echo "== invariant staticcheck (docs/STATICCHECK.md) =="
# Jax-free by contract (the tool never imports jax; JAX_PLATFORMS may
# be anything): the full suite must run clean — every finding outside
# scripts/staticcheck_allow.json fails here, in milliseconds, instead
# of minutes into a chip run.
python scripts/bench_check.py --static
# The report artifact is itself a versioned contract: emit + revalidate.
SC_TMP=$(mktemp -d)
python -m npairloss_tpu staticcheck --out "$SC_TMP/staticcheck_report.json" >/dev/null
python - "$SC_TMP/staticcheck_report.json" <<'EOF'
import json, sys
sys.path.insert(0, ".")
from npairloss_tpu.analysis.report import validate_staticcheck_report
err = validate_staticcheck_report(json.load(open(sys.argv[1])))
assert err is None, f"staticcheck report invalid: {err}"
EOF
# Teeth probe: a seeded-violation fixture tree must be REFUSED — a
# gate that accepts everything is worse than no gate.
if python scripts/bench_check.py --static \
        tests/fixtures/staticcheck/unscoped_collective >/dev/null 2>&1; then
    echo "FAIL: staticcheck accepted a seeded violation (gate has no teeth)"
    exit 1
fi
rm -rf "$SC_TMP"
echo "staticcheck OK (suite clean, report valid, gate has teeth)"

if [[ "${1:-}" == "--lint-only" ]]; then
    exit 0
fi

echo "== fault-injection smoke (docs/RESILIENCE.md) =="
# Train with an injected transient snapshot fault (must be retried, not
# fatal), then SIGTERM a long run mid-train (must exit 75 with a
# committed emergency snapshot) and relaunch with --resume auto (must
# restore and finish).  Exercises the whole preemption-safety loop in
# two real processes, exactly as a supervisor would drive it.
smoke_dir=$(mktemp -d)
trap 'rm -rf "$smoke_dir"' EXIT
cat > "$smoke_dir/solver.prototxt" <<EOF
net: "examples/tiny_net.prototxt"
base_lr: 0.05
lr_policy: "fixed"
momentum: 0.9
max_iter: 1000000
display: 0
test_interval: 0
test_iter: 0
snapshot: 2
snapshot_prefix: "$smoke_dir/m_"
EOF

NPAIRLOSS_FAILPOINTS="snapshot.save.io:1" JAX_PLATFORMS=cpu \
    python -m npairloss_tpu train --solver "$smoke_dir/solver.prototxt" \
    --model mlp --synthetic --resume auto --max_iter 4 \
    > "$smoke_dir/run1.log" 2>&1 \
    || { echo "smoke: injected-fault run failed"; cat "$smoke_dir/run1.log"; exit 1; }
[[ -f "$smoke_dir/m_iter_4.ckpt/manifest.json" ]] \
    || { echo "smoke: snapshot 4 missing after injected fault"; exit 1; }

JAX_PLATFORMS=cpu python -m npairloss_tpu train \
    --solver "$smoke_dir/solver.prototxt" --model mlp --synthetic \
    --resume auto > "$smoke_dir/run2.log" 2>&1 &
pid=$!
for _ in $(seq 1 120); do  # wait for a post-resume snapshot, then preempt
    [[ -f "$smoke_dir/m_iter_6.ckpt/manifest.json" ]] && break
    # The run dying before its first snapshot is exactly the regression
    # this smoke exists to catch — surface its log instead of burning
    # the full wait and failing on the kill below.
    kill -0 "$pid" 2>/dev/null \
        || { echo "smoke: resumed run died early"; cat "$smoke_dir/run2.log"; exit 1; }
    sleep 1
done
kill -TERM "$pid" 2>/dev/null || true
rc=0; wait "$pid" || rc=$?
[[ "$rc" -eq 75 ]] \
    || { echo "smoke: expected exit 75 after SIGTERM, got $rc"; cat "$smoke_dir/run2.log"; exit 1; }
k=$(ls "$smoke_dir" | grep -oE 'm_iter_[0-9]+' | grep -oE '[0-9]+' | sort -n | tail -1)
JAX_PLATFORMS=cpu python -m npairloss_tpu train \
    --solver "$smoke_dir/solver.prototxt" --model mlp --synthetic \
    --resume auto --max_iter "$((k + 2))" > "$smoke_dir/run3.log" 2>&1 \
    || { echo "smoke: auto-resume relaunch failed"; cat "$smoke_dir/run3.log"; exit 1; }
grep -q "resuming from iteration" "$smoke_dir/run3.log" \
    || { echo "smoke: relaunch did not resume"; cat "$smoke_dir/run3.log"; exit 1; }
echo "fault-injection smoke OK (preempted at iter $k, resumed, finished)"

echo "== pipelined-solver smoke (docs/PIPELINE.md) =="
# Sync-free loop, 20 steps, with the strict sync guard armed: ANY host
# transfer on the step-loop thread between window boundaries raises
# SyncGuardViolation and fails the run — the counting-device_put-shim
# assertion of the no-mid-window-host-syncs contract.
cat > "$smoke_dir/p_solver.prototxt" <<EOF
net: "examples/tiny_net.prototxt"
base_lr: 0.05
lr_policy: "fixed"
momentum: 0.9
max_iter: 20
display: 5
test_interval: 0
test_iter: 0
snapshot: 0
snapshot_prefix: "$smoke_dir/p_"
EOF
NPAIRLOSS_PIPELINE_SYNC_GUARD=strict JAX_PLATFORMS=cpu \
    python -m npairloss_tpu train --solver "$smoke_dir/p_solver.prototxt" \
    --model mlp --synthetic --pipeline > "$smoke_dir/pipe.log" 2>&1 \
    || { echo "smoke: pipelined run failed (mid-window host sync?)"; cat "$smoke_dir/pipe.log"; exit 1; }
grep -q "iter 20 " "$smoke_dir/pipe.log" \
    || { echo "smoke: pipelined run missing display output"; cat "$smoke_dir/pipe.log"; exit 1; }
echo "pipelined smoke OK (20 steps, zero mid-window host syncs)"

echo "== compile-cache round-trip (persistent XLA cache) =="
# Two fresh processes compile the same step; the second must hit the
# cache: the cache dir gains no new entries and its step/compile span
# is the deserialization cost, not an XLA compile.  The cache is on by
# default; JAX_COMPILATION_CACHE_DIR places it (here: outside the
# checkout, so .jax_cache/ must not appear because of this run).
cache_dir="$smoke_dir/xla_cache"
for i in 1 2; do
    JAX_PLATFORMS=cpu JAX_COMPILATION_CACHE_DIR="$cache_dir" \
        python -m npairloss_tpu train \
        --solver "$smoke_dir/p_solver.prototxt" --model mlp --synthetic \
        --max_iter 2 \
        --trace-dir "$smoke_dir/trace$i" > "$smoke_dir/cc$i.log" 2>&1 \
        || { echo "smoke: compile-cache run $i failed"; cat "$smoke_dir/cc$i.log"; exit 1; }
    n=$(ls "$cache_dir" | grep -c -- '-cache$' || true)
    eval "entries$i=$n"
done
[[ "${entries1:-0}" -gt 0 ]] \
    || { echo "smoke: compile cache not populated"; exit 1; }
[[ "${entries2}" -eq "${entries1}" ]] \
    || { echo "smoke: second process MISSED the compile cache (${entries1} -> ${entries2} entries)"; exit 1; }
python - "$smoke_dir/trace1/trace.json" "$smoke_dir/trace2/trace.json" <<'EOF'
import json, sys
durs = []
for path in sys.argv[1:]:
    evs = json.load(open(path))["traceEvents"]
    compiles = [e for e in evs if e["name"] == "step/compile"]
    assert compiles, f"{path}: no step/compile span"
    durs.append(max(e["dur"] for e in compiles) / 1e3)
print(f"step/compile: cold {durs[0]:.0f} ms -> cached {durs[1]:.0f} ms")
EOF
echo "compile-cache round-trip OK (no new entries on the second process)"

echo "== serving smoke (docs/SERVING.md) =="
# Build a synthetic gallery index, serve it over stdin/JSONL with the
# strict compile guard armed, issue 100 queries, assert every answer
# (incl. exact self-match top-1), a p99 bound, and ZERO post-warmup
# compiles from the counted drain summary — then kill -TERM and assert
# the graceful-drain contract: exit 75, all admitted queries answered,
# telemetry flushed to disk.
serve_dir="$smoke_dir/serve"
mkdir -p "$serve_dir"
python - "$serve_dir" <<'EOF'
import json, sys
import numpy as np
d = sys.argv[1]
rng = np.random.default_rng(0)
emb = rng.standard_normal((512, 64)).astype(np.float32)
emb /= np.linalg.norm(emb, axis=1, keepdims=True)
np.save(d + "/g.emb.npy", emb)
np.save(d + "/g.labels.npy", np.repeat(np.arange(64), 8).astype(np.int32))
with open(d + "/queries.jsonl", "w") as f:
    for i in range(100):  # queries ARE gallery rows: top-1 must self-match
        f.write(json.dumps({"id": i, "embedding": emb[i].tolist()}) + "\n")
EOF
JAX_PLATFORMS=cpu python -m npairloss_tpu index \
    --emb "$serve_dir/g.emb.npy" --labels "$serve_dir/g.labels.npy" \
    --no-normalize --out "$serve_dir/g.gidx" > "$serve_dir/index.log" 2>&1 \
    || { echo "smoke: index build failed"; cat "$serve_dir/index.log"; exit 1; }
mkfifo "$serve_dir/in"
JAX_PLATFORMS=cpu NPAIRLOSS_SERVE_COMPILE_GUARD=strict \
    python -m npairloss_tpu serve --index "$serve_dir/g.gidx" \
    --top-k 5 --buckets 1,8,32 --telemetry-dir "$serve_dir/tel" \
    < "$serve_dir/in" > "$serve_dir/answers.jsonl" \
    2> "$serve_dir/serve.log" &
spid=$!
exec 3> "$serve_dir/in"  # hold the writer open: EOF must not end the run
cat "$serve_dir/queries.jsonl" >&3
for _ in $(seq 1 240); do  # wait for all 100 answers (warmup included)
    [[ "$(wc -l < "$serve_dir/answers.jsonl")" -ge 100 ]] && break
    kill -0 "$spid" 2>/dev/null \
        || { echo "smoke: server died mid-serve"; cat "$serve_dir/serve.log"; exit 1; }
    sleep 0.5
done
kill -TERM "$spid" 2>/dev/null || true
exec 3>&-
rc=0; wait "$spid" || rc=$?
[[ "$rc" -eq 75 ]] \
    || { echo "smoke: expected exit 75 after SIGTERM, got $rc"; cat "$serve_dir/serve.log"; exit 1; }
python - "$serve_dir" <<'EOF'
import json, sys
d = sys.argv[1]
lines = [json.loads(ln) for ln in open(d + "/answers.jsonl") if ln.strip()]
drain = lines[-1]
assert drain.get("event") == "serve_drain", f"last line is not the drain summary: {drain}"
answers = {a["id"]: a for a in lines[:-1]}
assert len(answers) == 100, f"expected 100 answers, got {len(answers)}"
for i in range(100):
    a = answers[i]
    assert "neighbors" in a, f"query {i} answered with an error: {a}"
    top1 = a["neighbors"][0]
    assert top1["row"] == i, f"query {i}: top-1 row {top1['row']} != self"
assert drain["answered"] == 100 and drain["errors"] == 0, drain
assert drain["compiles_after_warmup"] == 0, drain  # counted, not eyeballed
assert drain["p99_ms"] < 500.0, f"p99 {drain['p99_ms']} ms over bound"
tel = [json.loads(ln) for ln in open(d + "/tel/metrics.jsonl") if ln.strip()]
assert any(r.get("event") == "serve_drain" for r in tel), "drain summary not flushed to telemetry"
assert json.load(open(d + "/tel/manifest.json"))["config"]["serve"], "manifest missing"
print(f"serving smoke OK (100 answers, p99 {drain['p99_ms']:.1f} ms, "
      f"0 post-warmup compiles, clean drain)")
EOF

echo "== durable-ingest cold-restart smoke (docs/RESILIENCE.md §Durability) =="
# SIGKILL the serving tier mid-ingest (no handler, no drain), then
# cold-restart from the published artifacts + WAL alone: every ACKED
# ingest batch must survive, the jax-free gate must accept the real
# WAL at the acked watermark, and refuse a truncated-then-patched copy
# (clean record-boundary truncation — structurally valid, but the
# acked records are gone).
wd="$smoke_dir/waldrill"
mkdir -p "$wd/idx"
cp -r "$serve_dir/g.gidx" "$wd/idx/g_0000.gidx"
mkfifo "$wd/in"
JAX_PLATFORMS=cpu python -m npairloss_tpu serve \
    --index-prefix "$wd/idx/g_" --wal-dir "$wd/wal" \
    --wal-checkpoint-every 2 --top-k 5 --buckets 1,8 \
    < "$wd/in" > "$wd/answers.jsonl" 2> "$wd/serve1.log" &
wpid=$!
exec 4> "$wd/in"
python - <<'EOF' >&4  # three ingest batches (ids 1000+, seeded vectors)
import json
import numpy as np
rng = np.random.default_rng(7)
for b in range(3):
    v = rng.standard_normal((2, 64)).astype(np.float32)
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    print(json.dumps({"id": f"ing-{b}", "ingest": {
        "ids": [1000 + 10 * b, 1001 + 10 * b],
        "labels": [7, 7], "embeddings": v.tolist()}}), flush=True)
EOF
for _ in $(seq 1 240); do  # wait for the three acks (warmup included)
    [[ "$(grep -c '"ingested"' "$wd/answers.jsonl" 2>/dev/null)" -ge 3 ]] && break
    kill -0 "$wpid" 2>/dev/null \
        || { echo "smoke: server died before acking ingest"; cat "$wd/serve1.log"; exit 1; }
    sleep 0.5
done
[[ "$(grep -c '"ingested"' "$wd/answers.jsonl")" -ge 3 ]] \
    || { echo "smoke: ingest never acked"; cat "$wd/serve1.log"; exit 1; }
# a fourth batch races the kill: it may or may not be acked — the
# durability claim is about ACKED batches only
python - <<'EOF' >&4
import json
import numpy as np
rng = np.random.default_rng(8)
v = rng.standard_normal((2, 64)).astype(np.float32)
v /= np.linalg.norm(v, axis=1, keepdims=True)
print(json.dumps({"id": "ing-race", "ingest": {
    "ids": [2000, 2001], "labels": [7, 7],
    "embeddings": v.tolist()}}), flush=True)
EOF
kill -KILL "$wpid" 2>/dev/null || true
rc=0; wait "$wpid" || rc=$?
exec 4>&-
[[ "$rc" -ne 75 ]] \
    || { echo "smoke: SIGKILL ran the drain handler (exit 75)?"; exit 1; }
wm=$(python - "$wd/answers.jsonl" <<'EOF'
import json, sys
seqs = []
for line in open(sys.argv[1]):
    try:
        r = json.loads(line)
    except ValueError:
        continue  # torn tail — the writer was SIGKILLed
    if isinstance(r, dict) and r.get("ingested"):
        seqs.append(int(r["seq"]))
print(max(seqs) if seqs else 0)
EOF
)
[[ "$wm" -ge 3 ]] || { echo "smoke: acked watermark $wm < 3"; exit 1; }
python scripts/bench_check.py --wal "$wd/wal" --wal-watermark "$wm" \
    || { echo "smoke: gate refused the REAL crashed WAL at watermark $wm"; exit 1; }
python - "$wd/wal" "$wd/walcopy" "$wm" <<'EOF'
import os, shutil, struct, sys
src, dst, wm = sys.argv[1], sys.argv[2], int(sys.argv[3])
shutil.copytree(src, dst)
segs = sorted(n for n in os.listdir(dst) if n.endswith(".seg"))
last = os.path.join(dst, segs[-1])
with open(last, "rb") as f:
    data = f.read()
H = struct.Struct("<II")
ends, off = [0], 0
while off + H.size <= len(data):
    ln, _ = H.unpack_from(data, off)
    if off + H.size + ln > len(data):
        break  # torn tail from the kill — drop it too
    off += H.size + ln
    ends.append(off)
keep = wm - 1  # one ACKED record short of the watermark
assert len(ends) > keep, f"segment holds {len(ends) - 1} record(s)"
with open(last, "r+b") as f:
    f.truncate(ends[keep])
EOF
if python scripts/bench_check.py --wal "$wd/walcopy" --wal-watermark "$wm" \
    > "$wd/tamper.log" 2>&1; then
    echo "smoke: gate ACCEPTED a truncated-then-patched WAL copy"
    cat "$wd/tamper.log"; exit 1
fi
grep -q "acknowledged watermark" "$wd/tamper.log" \
    || { echo "smoke: tampered WAL refused for the wrong reason"; cat "$wd/tamper.log"; exit 1; }
# cold restart: recovery replays the WAL tail above the newest
# checkpoint; the first acked batch's vector must retrieve ITSELF.
mkfifo "$wd/in2"
JAX_PLATFORMS=cpu python -m npairloss_tpu serve \
    --index-prefix "$wd/idx/g_" --wal-dir "$wd/wal" \
    --wal-checkpoint-every 2 --top-k 5 --buckets 1,8 \
    < "$wd/in2" > "$wd/answers2.jsonl" 2> "$wd/serve2.log" &
wpid=$!
exec 4> "$wd/in2"
python - <<'EOF' >&4
import json
import numpy as np
rng = np.random.default_rng(7)  # batch 0's vectors, regenerated
v = rng.standard_normal((2, 64)).astype(np.float32)
v /= np.linalg.norm(v, axis=1, keepdims=True)
print(json.dumps({"id": "q-replay", "embedding": v[0].tolist()}),
      flush=True)
EOF
for _ in $(seq 1 240); do
    [[ -s "$wd/answers2.jsonl" ]] && break
    kill -0 "$wpid" 2>/dev/null \
        || { echo "smoke: restarted server died"; cat "$wd/serve2.log"; exit 1; }
    sleep 0.5
done
kill -TERM "$wpid" 2>/dev/null || true
exec 4>&-
rc=0; wait "$wpid" || rc=$?
[[ "$rc" -eq 75 ]] \
    || { echo "smoke: restart drain expected exit 75, got $rc"; cat "$wd/serve2.log"; exit 1; }
grep -q "wal: recovered" "$wd/serve2.log" \
    || { echo "smoke: restart did not run WAL recovery"; cat "$wd/serve2.log"; exit 1; }
ls "$wd"/idx/g_w*.gidx > /dev/null 2>&1 \
    || { echo "smoke: no ingest checkpoint published under the prefix"; ls "$wd/idx"; exit 1; }
python - "$wd/answers2.jsonl" <<'EOF'
import json, sys
lines = [json.loads(ln) for ln in open(sys.argv[1]) if ln.strip()]
drain = lines[-1]
assert drain.get("event") == "serve_drain", f"no drain summary: {drain}"
ans = next(a for a in lines if a.get("id") == "q-replay")
assert "neighbors" in ans, f"replay query errored: {ans}"
top1 = ans["neighbors"][0]
assert top1.get("gallery_id") == 1000, \
    f"acked ingest vector did not survive the crash: top-1 {top1}"
ing = drain.get("ingest") or {}
wal = ing.get("wal") or {}
print(f"cold-restart smoke OK (watermark {ing.get('watermark')}, "
      f"checkpoint {ing.get('checkpoint_watermark')}, "
      f"torn_records {wal.get('torn_records')})")
EOF

echo "== perf observatory smoke (docs/OBSERVABILITY.md §Perf) =="
# A 10-step prof run on the tiny trunk must produce a schema-valid
# report whose step-time decomposition reconciles to wall time.  prof
# measures, so the CPU is named explicitly (--platform cpu).
prof_dir="$smoke_dir/prof"
JAX_PLATFORMS=cpu python -m npairloss_tpu --platform cpu prof --step train \
    --model mlp --image 32 --batch 16 --steps 10 --out "$prof_dir" \
    > "$prof_dir.log" 2>&1 \
    || { echo "smoke: prof run failed"; cat "$prof_dir.log"; exit 1; }
python - "$prof_dir/perf_report.json" <<'EOF'
import json, sys
from npairloss_tpu.obs.perf import validate_report
report = json.load(open(sys.argv[1]))
# validate_report IS the contract (bound enum, region keys, the
# reconciliation invariant) — the smoke only adds what it can't know:
# that THIS run produced a non-degenerate report.
err = validate_report(report)
assert err is None, f"schema-invalid prof report: {err}"
assert report["regions"], "prof report has no regions"
assert "decomposition" in report, "prof report has no decomposition"
dec = report["decomposition"]
print(f"prof smoke OK ({len(report['regions'])} regions, wall "
      f"{dec['wall_ms']:.0f} ms, unattributed {dec['unattributed_ms']:.0f} ms)")
EOF

echo "== pallas stem interpret smoke (ops/pallas_stem.py) =="
# The fused stem kernels must hold interpret-mode parity against the
# XLA references — forward and backward — on every box that runs CI
# (the full ragged-tile matrix lives in tests/test_pallas_stem.py).
JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np, jax, jax.numpy as jnp
from npairloss_tpu.models.layers import local_response_norm
from npairloss_tpu.ops import pallas_stem as ps
x = jnp.asarray(np.random.default_rng(0).standard_normal(
    (2, 6, 6, 24)).astype(np.float32))
b = jnp.asarray(np.random.default_rng(1).standard_normal(
    (24,)).astype(np.float32))
np.testing.assert_allclose(np.asarray(ps.fused_lrn(x)),
                           np.asarray(local_response_norm(x)), atol=2e-6)
g1 = jax.grad(lambda v: ps.fused_lrn(v).sum())(x)
g2 = jax.grad(lambda v: local_response_norm(v).sum())(x)
np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)
np.testing.assert_allclose(np.asarray(ps.fused_bias_relu(x, b)),
                           np.asarray(jnp.maximum(x + b, 0)), atol=1e-6)
np.testing.assert_allclose(
    np.asarray(ps.fused_bias_relu_pool(x, b)),
    np.asarray(ps._reference_bias_relu_pool(x, b, 3, 2)), atol=1e-6)
print("pallas stem interpret smoke OK (lrn fwd+bwd, bias_relu, pool)")
EOF

echo "== pallas probe kernel interpret smoke (ops/pallas_ivf.py) =="
# The fused IVF probe kernel (gather + score + running top-k in one
# VMEM pass) must hold interpret-mode parity against the lax.scan
# baseline AND the brute-force recall gate on every box that runs CI
# (the full scoring x geometry matrix lives in tests/test_pallas_ivf.py).
JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
from npairloss_tpu.serve import EngineConfig, GalleryIndex, QueryEngine
from npairloss_tpu.serve.ivf import IVFIndex, topk_recall
rng = np.random.default_rng(0)
cents = rng.standard_normal((8, 24)).astype(np.float32)
cents /= np.linalg.norm(cents, axis=1, keepdims=True)
emb = np.repeat(cents, 25, axis=0) + 0.1 * rng.standard_normal(
    (200, 24)).astype(np.float32)
emb /= np.linalg.norm(emb, axis=1, keepdims=True)
lab = np.repeat(np.arange(8), 25).astype(np.int32)
q = emb[rng.choice(200, 8, replace=False)]
ivf = IVFIndex.build_ivf(emb, lab, normalize=False, clusters=6,
                         train_size=None)
out = {}
for impl in ("scan", "fused"):
    eng = QueryEngine(ivf, EngineConfig(top_k=10, buckets=(8,), probes=3,
                                        probe_impl=impl))
    out[impl] = eng.query(q, normalize=False)
np.testing.assert_allclose(out["fused"]["scores"], out["scan"]["scores"],
                           rtol=1e-6, atol=1e-6)
oracle = QueryEngine(GalleryIndex.build(emb, lab, normalize=False),
                     EngineConfig(top_k=10, buckets=(8,)))
exact = oracle.query(q, normalize=False)["rows"]
for k in (1, 10):
    rf = topk_recall(out["fused"]["rows"], exact, k=k)
    rs = topk_recall(out["scan"]["rows"], exact, k=k)
    assert rf == rs, (k, rf, rs)
assert topk_recall(out["fused"]["rows"], exact, k=1) >= 0.95
print("pallas probe kernel interpret smoke OK (fused==scan to 1e-6, "
      "recall@{1,10} identical, recall@1 >= 0.95)")
EOF

echo "== precision-policy prof guard (models/precision.py) =="
# The default (mxu) flagship's compute must live in the conv/inception
# gemms, not the LRN tail: prof the default-policy flagship and assert
# the top trunk region by flops share is a conv/inception region, the
# lrn region exists (the named_scope attribution is wired), and lrn
# stays under 1% of step flops.  Catches a policy regression that
# silently reverts the trunk to an elementwise-dominated step.
pol_dir="$smoke_dir/prof_policy"
JAX_PLATFORMS=cpu python -m npairloss_tpu --platform cpu prof --step train \
    --model flagship --precision mxu --batch 4 --image 32 --steps 2 \
    --region-depth 2 --out "$pol_dir" > "$pol_dir.log" 2>&1 \
    || { echo "smoke: policy prof run failed"; cat "$pol_dir.log"; exit 1; }
python - "$pol_dir/perf_report.json" <<'EOF'
import json, sys
report = json.load(open(sys.argv[1]))
assert report.get("policy") == "mxu", report.get("policy")
trunk = [r for r in report["regions"]
         if r["region"].startswith("GoogLeNetEmbedding/")]
assert trunk, "no trunk regions attributed"
lrn = [r for r in trunk if r["region"].endswith("/lrn")]
assert lrn, "lrn region missing — named_scope attribution broken"
top = max(trunk, key=lambda r: r["pct_flops"])
assert not top["region"].endswith("/lrn"), \
    f"trunk's top region is the LRN tail: {top}"
assert lrn[0]["pct_flops"] < 1.0, f"lrn flops share grew: {lrn[0]}"
print(f"policy prof guard OK (top trunk region {top['region']} "
      f"{top['pct_flops']:.1f}% flops; lrn {lrn[0]['pct_flops']:.2f}%, "
      f"bound {lrn[0]['bound']})")
EOF

echo "== fleet observatory smoke (docs/OBSERVABILITY.md §Fleet) =="
# Two cooperating CPU processes train a short run under the strict sync
# guard, each writing its own rank-stamped telemetry stream into ONE
# shared run dir; then `prof --fleet` must aggregate them into a
# schema-valid npairloss-fleet-report-v1 with both ranks present, skew
# computed, and ZERO unattributed collective bytes, and bench_check
# must accept the report (it refuses per-rank step-count disagreement).
#
# Real multi-controller (jax.distributed) CPU collectives are an env
# capability — some jaxlib CPU backends form the cluster and then
# refuse to EXECUTE a cross-process computation.  Probe first
# (tests/mp_probe.py); fall back to the declared-rank harness mode
# (NPAIRLOSS_FLEET_PROCESS=<rank>/<count>) where the env can't, so the
# whole fleet observability path is smoked on every box either way.
fleet_dir="$smoke_dir/fleet"
mkdir -p "$fleet_dir"
cat > "$fleet_dir/solver.prototxt" <<EOF
net: "examples/tiny_net.prototxt"
base_lr: 0.05
lr_policy: "fixed"
momentum: 0.9
max_iter: 8
display: 4
test_interval: 0
test_iter: 0
snapshot: 0
snapshot_prefix: "$fleet_dir/f_"
EOF
probe_port=$(python -c 'import socket; s=socket.socket(); s.bind(("localhost",0)); print(s.getsockname()[1])')
probe_ok=1
for i in 0 1; do
    JAX_PLATFORMS=cpu XLA_FLAGS= PYTHONPATH=. \
        python tests/mp_probe.py "$i" 2 "$probe_port" \
        > "$fleet_dir/probe$i.log" 2>&1 &
    eval "ppid$i=\$!"
done
wait "$ppid0" || probe_ok=0
wait "$ppid1" || probe_ok=0
grep -q PROBE_OK "$fleet_dir/probe0.log" || probe_ok=0

if [[ "$probe_ok" -eq 1 ]]; then
    echo "fleet smoke: real jax.distributed 2-process mode"
    mp_port=$(python -c 'import socket; s=socket.socket(); s.bind(("localhost",0)); print(s.getsockname()[1])')
    for i in 0 1; do
        JAX_PLATFORMS=cpu XLA_FLAGS= NPAIRLOSS_PIPELINE_SYNC_GUARD=strict \
            python -m npairloss_tpu train --solver "$fleet_dir/solver.prototxt" \
            --model mlp --synthetic --engine ring --pipeline \
            --coordinator "localhost:$mp_port" --num-processes 2 --process-id "$i" \
            --telemetry-dir "$fleet_dir/run" > "$fleet_dir/train$i.log" 2>&1 &
        eval "tpid$i=\$!"
    done
else
    echo "fleet smoke: declared-rank harness mode (env cannot execute" \
         "multi-process CPU collectives: $(tail -1 "$fleet_dir/probe0.log" | cut -c1-120))"
    for i in 0 1; do
        JAX_PLATFORMS=cpu NPAIRLOSS_FLEET_PROCESS="$i/2" \
            NPAIRLOSS_PIPELINE_SYNC_GUARD=strict \
            python -m npairloss_tpu train --solver "$fleet_dir/solver.prototxt" \
            --model mlp --synthetic --engine ring --mesh 1 --pipeline \
            --telemetry-dir "$fleet_dir/run" > "$fleet_dir/train$i.log" 2>&1 &
        eval "tpid$i=\$!"
    done
fi
for i in 0 1; do
    eval "pid=\$tpid$i"
    wait "$pid" \
        || { echo "fleet smoke: rank $i training failed"; cat "$fleet_dir/train$i.log"; exit 1; }
done
for i in 0 1; do
    [[ -f "$fleet_dir/run/telemetry.r$i.jsonl" ]] \
        || { echo "fleet smoke: rank $i left no stream"; ls "$fleet_dir/run"; exit 1; }
done
JAX_PLATFORMS=cpu python -m npairloss_tpu prof --fleet "$fleet_dir/run" \
    > "$fleet_dir/prof.log" 2>&1 \
    || { echo "fleet smoke: prof --fleet failed"; cat "$fleet_dir/prof.log"; exit 1; }
python - "$fleet_dir/run/fleet_report.json" <<'EOF'
import json, sys
rep = json.load(open(sys.argv[1]))
assert rep["ranks_present"] == [0, 1], rep["ranks_present"]
assert rep["skew"]["steps_analyzed"] > 0, rep["skew"]
assert rep["skew"]["slowest"]["rank"] in (0, 1), rep["skew"]
comms = rep["comms"]
assert comms["available"], comms
assert comms["unattributed_bytes"] == 0, comms
assert all(k["claimed"] for k in comms["kinds"]), comms
counts = {r["rank"]: r["steps"] for r in rep["ranks"]}
print(f"fleet smoke OK (ranks {sorted(counts)}, {counts[0]} steps each, "
      f"dispatch skew p50 {rep['skew']['dispatch_spread_ms_p50']} ms, "
      f"slowest rank {rep['skew']['slowest']['rank']}, "
      f"0 unattributed collective bytes)")
EOF
python scripts/bench_check.py --fleet-report "$fleet_dir/run/fleet_report.json" \
    || { echo "fleet smoke: bench_check refused the fleet report"; exit 1; }

echo "== pod-scale multi-controller smoke (docs/DISTRIBUTED.md) =="
# One global batch, three ways: a single-process virtual 2-device mesh
# BASELINE, then TWO controller processes covering the same global
# mesh — real jax.distributed where the capability probe passed (each
# process owning 1 device, per-process disjoint data shards), else the
# declared-rank harness (NPAIRLOSS_FLEET_PROCESS, each process running
# the full virtual mesh on the same global batch).  The contract: the
# 2-process run produces byte-identical metric-key streams and
# bit-identical final params vs the baseline, for BOTH the dense and
# ring engines, under the strict sync guard; then `prof --fleet` over
# the shared run dir must reconcile with ZERO unattributed collective
# bytes and the DCN link, gated by bench_check --expect-link dcn.
# (Reuses $probe_ok from the fleet smoke's capability probe.)
pod_dir="$smoke_dir/pod"
mkdir -p "$pod_dir"
cat > "$pod_dir/solver.prototxt" <<EOF
net: "examples/tiny_net.prototxt"
base_lr: 0.05
lr_policy: "fixed"
momentum: 0.9
max_iter: 6
display: 3
test_interval: 0
test_iter: 0
snapshot: 6
snapshot_prefix: "$pod_dir/unused_"
EOF
for eng in dense ring; do
    # Baseline: one process, the whole 2-device virtual mesh.
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 \
        NPAIRLOSS_PIPELINE_SYNC_GUARD=strict \
        python -m npairloss_tpu train --solver "$pod_dir/solver.prototxt" \
        --model mlp --synthetic --engine "$eng" --mesh 2 --pipeline \
        --snapshot_prefix "$pod_dir/base_${eng}_s_" \
        --telemetry-dir "$pod_dir/base_$eng" \
        > "$pod_dir/base_$eng.log" 2>&1 \
        || { echo "pod smoke: baseline $eng failed"; cat "$pod_dir/base_$eng.log"; exit 1; }
    if [[ "$probe_ok" -eq 1 ]]; then
        pod_mode=real
        pod_port=$(python -c 'import socket; s=socket.socket(); s.bind(("localhost",0)); print(s.getsockname()[1])')
        for i in 0 1; do
            JAX_PLATFORMS=cpu XLA_FLAGS= NPAIRLOSS_PIPELINE_SYNC_GUARD=strict \
                python -m npairloss_tpu train --solver "$pod_dir/solver.prototxt" \
                --model mlp --synthetic --engine "$eng" --pipeline \
                --coordinator "localhost:$pod_port" --num-processes 2 --process-id "$i" \
                --snapshot_prefix "$pod_dir/pod_${eng}_s_" \
                --telemetry-dir "$pod_dir/pod_$eng" \
                > "$pod_dir/pod_${eng}_$i.log" 2>&1 &
            eval "podpid$i=\$!"
        done
    else
        pod_mode=harness
        for i in 0 1; do
            JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 \
                NPAIRLOSS_FLEET_PROCESS="$i/2" NPAIRLOSS_PIPELINE_SYNC_GUARD=strict \
                python -m npairloss_tpu train --solver "$pod_dir/solver.prototxt" \
                --model mlp --synthetic --engine "$eng" --mesh 2 --pipeline \
                --snapshot_prefix "$pod_dir/pod_${eng}_r${i}_s_" \
                --telemetry-dir "$pod_dir/pod_$eng" \
                > "$pod_dir/pod_${eng}_$i.log" 2>&1 &
            eval "podpid$i=\$!"
        done
    fi
    for i in 0 1; do
        eval "pid=\$podpid$i"
        wait "$pid" \
            || { echo "pod smoke: $eng rank $i failed"; cat "$pod_dir/pod_${eng}_$i.log"; exit 1; }
    done
    python - "$pod_dir" "$eng" "$pod_mode" <<'EOF'
import json, sys

d, eng, mode = sys.argv[1], sys.argv[2], sys.argv[3]

# -- params: bit-identical final snapshots, proven from the commit
# manifests' per-leaf CRC-32s (identical bits <=> identical checksums)
# — no backend, no device-mesh coupling to how the snapshot was saved.
def arrays(path):
    m = json.load(open(path + "/manifest.json"))
    assert m["step"] == 6, m["step"]
    return m["arrays"]

base = arrays(f"{d}/base_{eng}_s_iter_6.ckpt")
assert base, "baseline snapshot manifest empty"
pods = ([f"{d}/pod_{eng}_s_iter_6.ckpt"] if mode == "real" else
        [f"{d}/pod_{eng}_r{i}_s_iter_6.ckpt" for i in (0, 1)])
for p in pods:
    got = arrays(p)
    assert got == base, (
        f"{eng}: params differ vs {p}: "
        + str([k for k in base if got.get(k) != base[k]][:4]))

# -- streams: byte-identical metric-key streams -------------------------
DROP = {"run_id", "wall_time", "process_index", "process_count",
        "local_device_ids"}

def rows(path):
    out = []
    for ln in open(path):
        if not ln.strip():
            continue
        r = json.loads(ln)
        out.append((r.get("phase"), r.get("step"),
                    tuple(sorted((k, v) for k, v in r.items()
                                 if k not in DROP and k not in
                                 ("phase", "step")))))
    return out

want = rows(f"{d}/base_{eng}/metrics.jsonl")
assert want, "baseline stream empty"
for i in (0, 1):
    got = rows(f"{d}/pod_{eng}/telemetry.r{i}.jsonl")
    assert got == want, (
        f"{eng}: rank {i} stream diverges from the single-process "
        f"baseline ({len(got)} vs {len(want)} rows)")
print(f"pod smoke [{mode}] {eng}: params bit-identical, "
      f"{len(want)}-row metric streams byte-identical across "
      "baseline + both ranks")
EOF
done
# The shared run dir of the LAST engine (ring) feeds the fleet gate:
# both ranks present, zero unattributed bytes, DCN link selected.
JAX_PLATFORMS=cpu python -m npairloss_tpu prof --fleet "$pod_dir/pod_ring" \
    > "$pod_dir/prof.log" 2>&1 \
    || { echo "pod smoke: prof --fleet failed"; cat "$pod_dir/prof.log"; exit 1; }
python scripts/bench_check.py --fleet-report "$pod_dir/pod_ring/fleet_report.json" \
    --expect-link dcn \
    || { echo "pod smoke: fleet report not valid/DCN"; exit 1; }
python - "$pod_dir/pod_ring" <<'EOF'
import glob, json, sys
d = sys.argv[1]
man = json.load(open(sorted(glob.glob(d + "/manifest.r0.json"))[0]))
plan = man["config"]["engine_plan"]
assert plan and plan["link"] == "dcn" and plan["hosts"] == 2, plan
part = man["config"]["partition"]
assert part["unmatched"] == 0 and part["noop_rules"] == [], part
print(f"pod smoke manifest OK (engine_plan link={plan['link']}, "
      f"hosts={plan['hosts']}, partition {part['leaves']} leaves / "
      f"{part['sharded_leaves']} sharded)")
EOF
# --engine auto + --dump-partitions preflight: the resolved table must
# print (no zero-match rules on the default table) and the manifest
# must stamp the auto plan.
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 \
    python -m npairloss_tpu train --solver "$pod_dir/solver.prototxt" \
    --model mlp --synthetic --engine auto --mesh 2 --max_iter 0 \
    --dump-partitions --telemetry-dir "$pod_dir/auto" \
    > "$pod_dir/auto.log" 2>&1 \
    || { echo "pod smoke: --engine auto preflight failed"; cat "$pod_dir/auto.log"; exit 1; }
grep -q "partition rules (first match wins):" "$pod_dir/auto.log" \
    || { echo "pod smoke: --dump-partitions printed no table"; cat "$pod_dir/auto.log"; exit 1; }
python - "$pod_dir/auto/manifest.json" <<'EOF'
import json, sys
cfg = json.load(open(sys.argv[1]))["config"]
plan = cfg["engine_plan"]
assert plan["requested"] == "auto" and plan["engine"] in ("dense", "ring")
assert cfg["engine"] == plan["engine"], (cfg["engine"], plan["engine"])
print(f"pod smoke auto OK (auto -> {plan['engine']}: "
      + plan["reason"][:70] + "...)")
EOF

echo "== live observatory smoke (docs/OBSERVABILITY.md §Live) =="
# The alert lifecycle end-to-end: a CLEAN serve run under an SLO config
# fires ZERO alerts; a run with the serve.latency failpoint armed fires
# the p99 alert and RESOLVES it once the injected fault clears; the
# jax-free bench_check --alerts gate accepts that log and refuses one
# holding an unresolved critical alert (and a schema violation).
live_dir="$smoke_dir/live"
mkdir -p "$live_dir"
python - "$live_dir" <<'EOF'
import json, sys
import numpy as np
d = sys.argv[1]
rng = np.random.default_rng(0)
emb = rng.standard_normal((256, 32)).astype(np.float32)
emb /= np.linalg.norm(emb, axis=1, keepdims=True)
np.save(d + "/g.emb.npy", emb)
np.save(d + "/g.labels.npy", (np.arange(256) % 16).astype(np.int32))
with open(d + "/queries.jsonl", "w") as f:
    for i in range(40):
        f.write(json.dumps({"id": i, "embedding": emb[i].tolist()}) + "\n")
json.dump({"slos": [{
    "name": "p99", "metric": "serve_p99_ms", "op": "<=", "target": 150.0,
    "window_s": 2.0, "burn_threshold": 0.5, "min_samples": 1,
    "severity": "critical"}]}, open(d + "/slo.json", "w"))
EOF
JAX_PLATFORMS=cpu python -m npairloss_tpu index \
    --emb "$live_dir/g.emb.npy" --labels "$live_dir/g.labels.npy" \
    --no-normalize --out "$live_dir/g.gidx" > "$live_dir/index.log" 2>&1 \
    || { echo "live smoke: index build failed"; cat "$live_dir/index.log"; exit 1; }

run_live_serve() {  # $1 = telemetry dir, $2 = extra env (failpoints or "")
    local tel="$1" fp="$2"
    mkfifo "$live_dir/in.$$"
    env JAX_PLATFORMS=cpu NPAIRLOSS_FAILPOINTS="$fp" \
        python -m npairloss_tpu serve --index "$live_dir/g.gidx" \
        --top-k 3 --buckets 1 --deadline-ms 1 --metrics-window 4 \
        --telemetry-dir "$tel" --live-obs --slo-config "$live_dir/slo.json" \
        --slo-tick 0.2 < "$live_dir/in.$$" > "$tel.answers.jsonl" \
        2> "$tel.log" &
    lpid=$!
    exec 4> "$live_dir/in.$$"
    # Throttled feed: a 40-query burst through single-query buckets
    # would queue real ~100ms tails on a loaded CPU box — the CLEAN
    # run must owe its p99 to dispatch alone, so the injected 250ms
    # fault is the ONLY thing that can cross the 150ms bar.
    head -20 "$live_dir/queries.jsonl" | while IFS= read -r ln; do
        printf '%s\n' "$ln" >&4; sleep 0.05
    done
    sleep 3   # failpoint burst (if armed) fires + the alert with it
    tail -20 "$live_dir/queries.jsonl" | while IFS= read -r ln; do
        printf '%s\n' "$ln" >&4; sleep 0.05
    done
    sleep 3   # fault cleared: fast windows age the burn out -> resolve
    kill -TERM "$lpid" 2>/dev/null || true
    exec 4>&-
    rc=0; wait "$lpid" || rc=$?
    rm -f "$live_dir/in.$$"
    [[ "$rc" -eq 75 ]] \
        || { echo "live smoke: expected exit 75, got $rc"; cat "$tel.log"; exit 1; }
}

run_live_serve "$live_dir/clean" ""
[[ ! -s "$live_dir/clean/alerts.jsonl" ]] \
    || { echo "live smoke: CLEAN run fired alerts (false positives)"; cat "$live_dir/clean/alerts.jsonl"; exit 1; }
python scripts/bench_check.py --alerts "$live_dir/clean/alerts.jsonl" \
    || { echo "live smoke: gate refused the empty clean log"; exit 1; }

run_live_serve "$live_dir/fault" "serve.latency:6"
python - "$live_dir/fault/alerts.jsonl" <<'EOF'
import json, sys
records = [json.loads(ln) for ln in open(sys.argv[1]) if ln.strip()]
states = [r["state"] for r in records]
assert "firing" in states, f"latency failpoint never fired the p99 alert: {records}"
assert states[-1] == "resolved", f"alert did not resolve after the fault cleared: {states}"
assert all(r["slo"] == "p99" and r["severity"] == "critical" for r in records)
fired = [r for r in records if r["state"] == "firing"]
print(f"live smoke: p99 alert fired {len(fired)}x and resolved "
      f"(worst window in message: {fired[0]['message'].split('worst ')[-1]}")
EOF
python scripts/bench_check.py --alerts "$live_dir/fault/alerts.jsonl" \
    || { echo "live smoke: gate refused the resolved fire->resolve log"; exit 1; }
# gate teeth: an unresolved critical (truncate the resolve off) and a
# schema violation must both be refused
head -1 "$live_dir/fault/alerts.jsonl" > "$live_dir/unresolved.jsonl"
python scripts/bench_check.py --alerts "$live_dir/unresolved.jsonl" > /dev/null \
    && { echo "live smoke: gate ACCEPTED an unresolved critical alert"; exit 1; }
sed 's/npairloss-alerts-v1/npairloss-alerts-v0/' \
    "$live_dir/fault/alerts.jsonl" > "$live_dir/badschema.jsonl"
python scripts/bench_check.py --alerts "$live_dir/badschema.jsonl" > /dev/null \
    && { echo "live smoke: gate ACCEPTED a schema violation"; exit 1; }
# the offline feed agrees: watch over the fault run's telemetry must
# reproduce a fire->resolve sequence through the SAME engine
JAX_PLATFORMS=cpu python -m npairloss_tpu watch "$live_dir/fault" \
    --slo-config "$live_dir/slo.json" > "$live_dir/watch.log" 2>&1 \
    || { echo "live smoke: watch refused the run dir"; cat "$live_dir/watch.log"; exit 1; }
python - "$live_dir/fault/alerts.watch.jsonl" <<'EOF'
import json, sys
states = [json.loads(ln)["state"] for ln in open(sys.argv[1]) if ln.strip()]
assert "firing" in states and states[-1] == "resolved", states
print(f"watch feed agrees: {states}")
EOF
echo "live observatory smoke OK (0 false positives, fire->resolve, gate teeth, watch agreement)"

echo "== query tracing smoke (docs/OBSERVABILITY.md §Query tracing) =="
# Per-query stage attribution end-to-end: a throttled serve run with
# the serve.latency failpoint armed must retain SLO-violating
# exemplars whose dominant stage is DISPATCH (the feed is slower than
# the 0.25s stall, so each faulted query pays the stall as dispatch
# self-time and no queue builds behind it — the gameday covers the
# saturated case where the same fault shows up as queue_wait); the
# jax-free bench_check --qtrace gate accepts the real artifact and
# refuses doctored copies; the merged timeline carries the exemplar
# span trees next to the alert instants.
qt_dir="$smoke_dir/qtrace"
mkdir -p "$qt_dir"
mkfifo "$qt_dir/in.$$"
env JAX_PLATFORMS=cpu NPAIRLOSS_FAILPOINTS="serve.latency:6@4" \
    python -m npairloss_tpu serve --index "$live_dir/g.gidx" \
    --top-k 3 --buckets 1 --deadline-ms 1 --metrics-window 4 \
    --telemetry-dir "$qt_dir/tel" --live-obs \
    --slo-config "$live_dir/slo.json" --slo-tick 0.2 \
    --qtrace --qtrace-slo-ms 150 \
    < "$qt_dir/in.$$" > "$qt_dir/answers.jsonl" 2> "$qt_dir/serve.log" &
qtpid=$!
exec 8> "$qt_dir/in.$$"
# Readiness probe: the FIFO buffers lines while the server is still
# importing/warming, and a buffered backlog arrives as a BURST whose
# tail pays queue_wait, not dispatch — the very confound this smoke
# must exclude.  One query, wait for its answer, then throttle the
# rest; the @4 delay keeps the stalls clear of the probe boundary.
head -1 "$live_dir/queries.jsonl" >&8
for _ in $(seq 1 120); do
    [[ -s "$qt_dir/answers.jsonl" ]] && break
    sleep 0.5
done
[[ -s "$qt_dir/answers.jsonl" ]] \
    || { echo "qtrace smoke: server never answered the probe"; cat "$qt_dir/serve.log"; exit 1; }
sed -n '2,24p' "$live_dir/queries.jsonl" | while IFS= read -r ln; do
    printf '%s\n' "$ln" >&8; sleep 0.3
done
sleep 3   # fault long gone: fast windows age the p99 burn out -> resolve
kill -TERM "$qtpid" 2>/dev/null || true
exec 8>&-
rc=0; wait "$qtpid" || rc=$?
rm -f "$qt_dir/in.$$"
[[ "$rc" -eq 75 ]] \
    || { echo "qtrace smoke: expected exit 75, got $rc"; cat "$qt_dir/serve.log"; exit 1; }
python - "$qt_dir" <<'EOF'
import json, sys
d = sys.argv[1]
rep = json.load(open(d + "/tel/qtrace.json"))
t, b = rep["totals"], rep["budget"]
assert t["queries"] == 24 and t["errors"] == 0, t
assert t["violations"] >= 1, f"no SLO violation retained: {t}"
slo_ex = [ex for ex in rep["exemplars"] if ex["reason"] == "slo"]
assert slo_ex, "fault run retained no SLO exemplars"
assert b["dominant"] == "dispatch", \
    f"injected dispatch stall attributed to {b['dominant']!r}: {b}"
for ex in slo_ex:
    stages = {e["name"]: e["dur"] for e in ex["events"]
              if e["name"].startswith("qtrace/") and e["name"] != "qtrace/query"}
    worst = max(stages, key=stages.get)
    assert worst == "qtrace/dispatch", (ex["trace_id"], worst, stages)
drain = [json.loads(ln) for ln in open(d + "/answers.jsonl") if ln.strip()][-1]
assert drain.get("event") == "serve_drain", drain
assert drain["qtrace"]["budget"]["dominant"] == "dispatch", drain["qtrace"]
rows = [json.loads(ln) for ln in open(d + "/tel/metrics.jsonl") if ln.strip()]
doms = [r["qtrace_dominant"] for r in rows
        if r.get("phase") == "serve" and "qtrace_dominant" in r]
assert "dispatch" in doms, f"no window row pinned the stall on dispatch: {doms}"
states = [json.loads(ln)["state"] for ln in open(d + "/tel/alerts.jsonl") if ln.strip()]
assert "firing" in states and states[-1] == "resolved", states
print(f"qtrace smoke: {len(slo_ex)} SLO exemplar(s), dominant dispatch "
      f"(p99 {b['p99_ms']:.0f}ms), alert fired+resolved")
EOF
python scripts/bench_check.py --qtrace "$qt_dir/tel/qtrace.json" \
    || { echo "qtrace smoke: gate refused the real artifact"; exit 1; }
# gate teeth: a schema rename and a duplicated trace id must be refused
sed 's/npairloss-qtrace-v1/npairloss-qtrace-v0/' \
    "$qt_dir/tel/qtrace.json" > "$qt_dir/badschema.json"
python scripts/bench_check.py --qtrace "$qt_dir/badschema.json" > /dev/null \
    && { echo "qtrace smoke: gate ACCEPTED a schema violation"; exit 1; }
python - "$qt_dir" <<'EOF'
import json, sys
d = sys.argv[1]
rep = json.load(open(d + "/tel/qtrace.json"))
assert len(rep["exemplars"]) >= 2, "need two exemplars to forge a duplicate"
tid = rep["exemplars"][0]["trace_id"]
rep["exemplars"][1]["trace_id"] = tid
for ev in rep["exemplars"][1]["events"]:
    ev["args"]["trace_id"] = tid
json.dump(rep, open(d + "/dup.json", "w"))
EOF
python scripts/bench_check.py --qtrace "$qt_dir/dup.json" > /dev/null \
    && { echo "qtrace smoke: gate ACCEPTED a duplicate trace id"; exit 1; }
# the composed-system timeline: serve query spans + alert instants in
# one Perfetto file (gameday layout: the telemetry dir as serve_tel)
mkdir -p "$qt_dir/run"
cp -r "$qt_dir/tel" "$qt_dir/run/serve_tel"
JAX_PLATFORMS=cpu python -m npairloss_tpu timeline "$qt_dir/run" \
    > "$qt_dir/timeline.log" 2>&1 \
    || { echo "qtrace smoke: timeline merge failed"; cat "$qt_dir/timeline.log"; exit 1; }
python - "$qt_dir" <<'EOF'
import json, sys
d = sys.argv[1]
out = json.loads(open(d + "/timeline.log").read().strip().splitlines()[-1])
assert out["sources"]["qtrace"] is True and out["sources"]["serve_host"] is True, out
merged = json.load(open(out["timeline"]))
events = merged["traceEvents"]
spans = {e["name"] for e in events if e.get("ph") == "X" and e.get("pid", 0) >= 1000}
assert "qtrace/query" in spans and "qtrace/dispatch" in spans, spans
instants = {e["name"] for e in events if e.get("ph") == "i"}
assert any(n.startswith("alert:") and n.endswith("firing") for n in instants), instants
print(f"timeline OK ({out['events']} events; serve query spans + alert instants merged)")
EOF
echo "qtrace smoke OK (dispatch attribution, artifact gate + teeth, merged timeline)"

echo "== overload / admission-control smoke (docs/SERVING.md §Approximate index) =="
# The graceful-degradation scenario (ISSUE 11): a 2-replica IVF tier
# under a p99 SLO is rammed past capacity (deterministically — the
# serve.latency failpoint stalls every dispatch 0.25s during the ramp).
# Required behavior: the p99 alert FIRES, SLO-driven admission control
# SHEDS load (fast-rejects counted in the rejected invariant) while a
# probe trickle keeps recovery observable, answered queries keep
# flowing end to end (no stall), and once the ramp ends the alert
# RESOLVES and full admission returns — then the jax-free
# bench_check --alerts gate must accept the fire->resolve log.
ov_dir="$smoke_dir/overload"
mkdir -p "$ov_dir"
python - "$ov_dir" <<'EOF'
import json, sys
import numpy as np
d = sys.argv[1]
rng = np.random.default_rng(0)
emb = rng.standard_normal((512, 32)).astype(np.float32)
emb /= np.linalg.norm(emb, axis=1, keepdims=True)
np.save(d + "/g.emb.npy", emb)
np.save(d + "/g.labels.npy", (np.arange(512) % 32).astype(np.int32))
with open(d + "/flood.jsonl", "w") as f:
    for i in range(300):
        f.write(json.dumps({"id": i, "embedding": emb[i % 512].tolist()}) + "\n")
with open(d + "/recover.jsonl", "w") as f:
    for i in range(100):
        f.write(json.dumps({"id": 1000 + i, "embedding": emb[i].tolist()}) + "\n")
with open(d + "/tail.jsonl", "w") as f:
    for i in range(20):
        f.write(json.dumps({"id": 4000 + i, "embedding": emb[i].tolist()}) + "\n")
json.dump({"slos": [{
    "name": "serve_p99", "metric": "serve_p99_ms", "op": "<=",
    "target": 150.0, "window_s": 2.0, "burn_threshold": 0.5,
    "min_samples": 1, "severity": "critical"}]},
    open(d + "/slo.json", "w"))
EOF
JAX_PLATFORMS=cpu python -m npairloss_tpu index \
    --emb "$ov_dir/g.emb.npy" --labels "$ov_dir/g.labels.npy" \
    --no-normalize --kind ivf --clusters 16 --out "$ov_dir/g.gidx" \
    > "$ov_dir/index.log" 2>&1 \
    || { echo "overload smoke: ivf index build failed"; cat "$ov_dir/index.log"; exit 1; }
mkfifo "$ov_dir/in"
JAX_PLATFORMS=cpu NPAIRLOSS_FAILPOINTS="serve.latency:60" \
    python -m npairloss_tpu serve --index "$ov_dir/g.gidx" \
    --index-kind ivf --probes 4 --scoring bf16 --replicas 2 \
    --admission slo --admission-slos serve_p99 \
    --top-k 3 --buckets 1 --deadline-ms 1 --max-queue 64 \
    --metrics-window 4 --telemetry-dir "$ov_dir/tel" --live-obs \
    --slo-config "$ov_dir/slo.json" --slo-tick 0.2 \
    < "$ov_dir/in" > "$ov_dir/answers.jsonl" 2> "$ov_dir/serve.log" &
ovpid=$!
exec 5> "$ov_dir/in"
# Phase A — the ramp: 300 queries at ~33 qps against ~8 qps of faulted
# capacity.  The queues saturate, the p99 alert fires, shedding engages.
while IFS= read -r ln; do printf '%s\n' "$ln" >&5; sleep 0.03; done \
    < "$ov_dir/flood.jsonl"
sleep 3  # ramp over; fault budget exhausts, queues drain
# Phase B — recovery: throttled traffic; the probe trickle's fast
# answers age the burn out, the alert resolves, admission returns.
while IFS= read -r ln; do printf '%s\n' "$ln" >&5; sleep 0.04; done \
    < "$ov_dir/recover.jsonl"
sleep 2.5
# Phase C — steady state again: the tail queries must nearly all land.
while IFS= read -r ln; do printf '%s\n' "$ln" >&5; sleep 0.05; done \
    < "$ov_dir/tail.jsonl"
sleep 1.5
kill -TERM "$ovpid" 2>/dev/null || true
exec 5>&-
rc=0; wait "$ovpid" || rc=$?
[[ "$rc" -eq 75 ]] \
    || { echo "overload smoke: expected exit 75, got $rc"; cat "$ov_dir/serve.log"; exit 1; }
python - "$ov_dir" <<'EOF'
import json, sys
d = sys.argv[1]
lines = [json.loads(ln) for ln in open(d + "/answers.jsonl") if ln.strip()]
drain = lines[-1]
assert drain.get("event") == "serve_drain", drain
answers = lines[:-1]
served = [a for a in answers if "neighbors" in a]
tail_served = [a for a in served if isinstance(a.get("id"), int) and a["id"] >= 4000]
# shedding engaged: admission sheds happened and are counted in rejected
assert drain["shed"] > 0, f"admission control never shed: {drain}"
assert drain["rejected"] >= drain["shed"] > 0, drain
# no stall: answers kept flowing through and after the incident
assert drain["answered"] >= 60, drain
assert len(tail_served) >= 15, \
    f"only {len(tail_served)}/20 tail queries served — tier never readmitted"
assert drain["shedding"] is False, "still shedding at drain"
assert drain["replicas"] == 2 and drain["replicas_alive"] == 2, drain
# the invariant holds through overload: nothing dropped, nothing counted twice
assert drain["queries"] == drain["answered"] + drain["errors"] + drain["rejected"], drain
states = [json.loads(ln)["state"] for ln in open(d + "/tel/alerts.jsonl") if ln.strip()]
assert "firing" in states, "p99 alert never fired under the ramp"
assert states[-1] == "resolved", f"alert did not resolve after the ramp: {states}"
print(f"overload smoke OK (shed {drain['shed']}, rejected {drain['rejected']}, "
      f"answered {drain['answered']}, tail {len(tail_served)}/20, "
      f"alert fired+resolved)")
EOF
python scripts/bench_check.py --alerts "$ov_dir/tel/alerts.jsonl" \
    || { echo "overload smoke: gate refused the fire->resolve log"; exit 1; }

echo "== alert->actuation chaos suite (docs/RESILIENCE.md §Remediation) =="
# Four fault->alert->remedy->resolve loops, each driven by a failpoint,
# proven end to end, and gated by BOTH jax-free validators:
# `bench_check --alerts` on the alert log and `bench_check
# --remediation` on the npairloss-remediation-v1 audit log.
chaos_dir="$smoke_dir/chaos"
mkdir -p "$chaos_dir"
python - "$chaos_dir" <<'EOF'
import json, sys
import numpy as np
d = sys.argv[1]
rng = np.random.default_rng(0)
emb = rng.standard_normal((256, 64)).astype(np.float32)
emb /= np.linalg.norm(emb, axis=1, keepdims=True)
np.save(d + "/g.emb.npy", emb)
np.save(d + "/g.labels.npy", (np.arange(256) % 16).astype(np.int32))
with open(d + "/queries.jsonl", "w") as f:
    for i in range(600):
        f.write(json.dumps({"id": i, "embedding": emb[i % 256].tolist()}) + "\n")
EOF
JAX_PLATFORMS=cpu python -m npairloss_tpu index \
    --emb "$chaos_dir/g.emb.npy" --labels "$chaos_dir/g.labels.npy" \
    --no-normalize --out "$chaos_dir/g.gidx" > "$chaos_dir/index.log" 2>&1 \
    || { echo "chaos: index build failed"; cat "$chaos_dir/index.log"; exit 1; }

chaos_gates() {  # $1 = telemetry dir, $2 = scenario label
    python scripts/bench_check.py --alerts "$1/alerts.jsonl" \
        || { echo "chaos $2: alert gate refused"; exit 1; }
    python scripts/bench_check.py --remediation "$1/remediation.jsonl" \
        || { echo "chaos $2: remediation gate refused"; exit 1; }
}

echo "-- chaos A: compile storm -> re-warm --"
# serve.compile_storm counts phantom post-warmup compiles; the
# post-warmup-compile alert fires, the rewarm policy re-primes the
# buckets and resets the counters, and the now-EXPLICIT zero rows
# resolve the alert.
python - "$chaos_dir" <<'EOF'
import json, sys
d = sys.argv[1]
json.dump({"slos": [{
    "name": "serve_post_warmup_compile", "metric": "serve_compiles_after_warmup",
    "op": "<=", "target": 0.0, "window_s": 3.0, "burn_threshold": 0.01,
    "min_samples": 1, "severity": "warning"}]}, open(d + "/a_slo.json", "w"))
json.dump({"policies": [{
    "name": "rewarm", "slo": "serve_post_warmup_compile", "action": "rewarm",
    "cooldown_s": 4.0, "max_attempts": 3}]}, open(d + "/a_rem.json", "w"))
EOF
mkfifo "$chaos_dir/a_in"
JAX_PLATFORMS=cpu NPAIRLOSS_FAILPOINTS="serve.compile_storm:2" \
    python -m npairloss_tpu serve --index "$chaos_dir/g.gidx" \
    --top-k 3 --buckets 1 --deadline-ms 1 --metrics-window 4 \
    --telemetry-dir "$chaos_dir/a_tel" --live-obs \
    --slo-config "$chaos_dir/a_slo.json" --slo-tick 0.2 \
    --remediate --remediation-config "$chaos_dir/a_rem.json" \
    < "$chaos_dir/a_in" > "$chaos_dir/a_answers.jsonl" \
    2> "$chaos_dir/a.log" &
apid=$!
exec 6> "$chaos_dir/a_in"
head -30 "$chaos_dir/queries.jsonl" | while IFS= read -r ln; do
    printf '%s\n' "$ln" >&6; sleep 0.05
done
sleep 2    # storm rows land, alert fires, rewarm runs
sed -n '31,90p' "$chaos_dir/queries.jsonl" | while IFS= read -r ln; do
    printf '%s\n' "$ln" >&6; sleep 0.05
done
sleep 2.5  # explicit-0 rows age the burn out -> resolve
kill -TERM "$apid" 2>/dev/null || true
exec 6>&-
rc=0; wait "$apid" || rc=$?
[[ "$rc" -eq 75 ]] \
    || { echo "chaos A: expected exit 75, got $rc"; cat "$chaos_dir/a.log"; exit 1; }
python - "$chaos_dir" <<'EOF'
import json, sys
d = sys.argv[1]
lines = [json.loads(ln) for ln in open(d + "/a_answers.jsonl") if ln.strip()]
drain = lines[-1]
assert drain.get("event") == "serve_drain", drain
assert drain["errors"] == 0 and drain["answered"] == 90, drain
assert drain["compiles_after_warmup"] == 0, drain  # re-warm reset them
states = [json.loads(ln)["state"] for ln in open(d + "/a_tel/alerts.jsonl") if ln.strip()]
assert "firing" in states and states[-1] == "resolved", states
rem = [json.loads(ln) for ln in open(d + "/a_tel/remediation.jsonl") if ln.strip()]
assert any(r["policy"] == "rewarm" and r["state"] == "succeeded" for r in rem), rem
assert drain["remediation"]["rewarm"]["outcome"] == "succeeded", drain
print(f"chaos A OK (storm counted, rewarm succeeded, alert resolved; "
      f"{len(rem)} audit event(s))")
EOF
chaos_gates "$chaos_dir/a_tel" A

echo "-- chaos B: queue saturation -> audited load-shed --"
# serve.latency wedges the dispatcher; the queue-saturation alert fires
# and the load_shed policy ENGAGES the admission throttle (an audited
# action, not an implicit behavior); the probe trickle keeps recovery
# observable, the alert resolves once the queue drains, and the
# engine's undo releases admission.
python - "$chaos_dir" <<'EOF'
import json, sys
d = sys.argv[1]
json.dump({"slos": [{
    "name": "serve_queue_saturation", "metric": "serve_queue_depth",
    "op": "<=", "target": 6.0, "window_s": 2.0, "burn_threshold": 0.5,
    "min_samples": 1, "severity": "warning"}]}, open(d + "/b_slo.json", "w"))
json.dump({"policies": [{
    "name": "load_shed", "slo": "serve_queue_saturation", "action": "load_shed",
    "cooldown_s": 8.0, "max_attempts": 4}]}, open(d + "/b_rem.json", "w"))
EOF
mkfifo "$chaos_dir/b_in"
JAX_PLATFORMS=cpu NPAIRLOSS_FAILPOINTS="serve.latency:30" \
    python -m npairloss_tpu serve --index "$chaos_dir/g.gidx" \
    --top-k 3 --buckets 1 --deadline-ms 1 --max-queue 24 \
    --metrics-window 4 --telemetry-dir "$chaos_dir/b_tel" --live-obs \
    --slo-config "$chaos_dir/b_slo.json" --slo-tick 0.2 \
    --remediate --remediation-config "$chaos_dir/b_rem.json" \
    < "$chaos_dir/b_in" > "$chaos_dir/b_answers.jsonl" \
    2> "$chaos_dir/b.log" &
bpid=$!
exec 7> "$chaos_dir/b_in"
# flood: ~100 qps against ~4 qps of faulted capacity -> queue saturates
head -150 "$chaos_dir/queries.jsonl" | while IFS= read -r ln; do
    printf '%s\n' "$ln" >&7; sleep 0.01
done
sleep 6    # fault budget exhausts, queue drains under shed
# recovery traffic: the probe trickle's answers emit the good
# queue-depth rows resolution requires
sed -n '151,250p' "$chaos_dir/queries.jsonl" | while IFS= read -r ln; do
    printf '%s\n' "$ln" >&7; sleep 0.04
done
sleep 2
kill -TERM "$bpid" 2>/dev/null || true
exec 7>&-
rc=0; wait "$bpid" || rc=$?
[[ "$rc" -eq 75 ]] \
    || { echo "chaos B: expected exit 75, got $rc"; cat "$chaos_dir/b.log"; exit 1; }
python - "$chaos_dir" <<'EOF'
import json, sys
d = sys.argv[1]
lines = [json.loads(ln) for ln in open(d + "/b_answers.jsonl") if ln.strip()]
drain = lines[-1]
assert drain.get("event") == "serve_drain", drain
assert drain["shed"] > 0, f"load_shed never engaged: {drain}"
assert drain["rejected"] >= drain["shed"], drain
assert drain["queries"] == drain["answered"] + drain["errors"] + drain["rejected"], drain
assert drain["shedding"] is False, "forced shed never released"
states = [json.loads(ln)["state"] for ln in open(d + "/b_tel/alerts.jsonl") if ln.strip()]
assert "firing" in states and states[-1] == "resolved", states
rem = [json.loads(ln) for ln in open(d + "/b_tel/remediation.jsonl") if ln.strip()]
assert any(r["policy"] == "load_shed" and r["state"] == "succeeded" for r in rem), rem
print(f"chaos B OK (shed {drain['shed']}, answered {drain['answered']}, "
      f"alert resolved, shed released)")
EOF
chaos_gates "$chaos_dir/b_tel" B

echo "-- chaos C: embedding collapse -> trainer rollback --"
# train.collapse (delay-armed: 60 healthy steps first, so pre-incident
# snapshots exist) forces the health signal degenerate; the
# embedding-collapse alert fires, the trainer_rollback policy requests
# a rollback the loop executes at its next safe point (restoring a
# snapshot COMMITTED BEFORE the alert fired), and once the injected
# collapse exhausts, the real health rows resolve the alert.
cat > "$chaos_dir/c_solver.prototxt" <<EOF
net: "examples/tiny_net.prototxt"
base_lr: 0.05
lr_policy: "fixed"
momentum: 0.9
max_iter: 800
display: 0
test_interval: 0
test_iter: 0
snapshot: 5
snapshot_prefix: "$chaos_dir/c_snap/m_"
EOF
python - "$chaos_dir" <<'EOF'
import json, sys
d = sys.argv[1]
json.dump({"slos": [{
    "name": "embedding_collapse", "metric": "train_an_threshold_mean",
    "op": "<=", "target": 0.98, "window_s": 2.0, "burn_threshold": 0.5,
    "min_samples": 3, "severity": "warning"}]}, open(d + "/c_slo.json", "w"))
json.dump({"policies": [{
    "name": "trainer_rollback", "slo": "embedding_collapse",
    "action": "trainer_rollback", "cooldown_s": 6.0, "max_attempts": 5}]},
    open(d + "/c_rem.json", "w"))
EOF
JAX_PLATFORMS=cpu NPAIRLOSS_FAILPOINTS="train.collapse:160@60" \
    python -m npairloss_tpu train --solver "$chaos_dir/c_solver.prototxt" \
    --model mlp --synthetic --health-metrics \
    --telemetry-dir "$chaos_dir/c_tel" --live-obs \
    --slo-config "$chaos_dir/c_slo.json" --slo-tick 0.2 \
    --remediate --remediation-config "$chaos_dir/c_rem.json" \
    > "$chaos_dir/c.log" 2>&1 \
    || { echo "chaos C: train run failed"; cat "$chaos_dir/c.log"; exit 1; }
python - "$chaos_dir" <<'EOF'
import json, sys
d = sys.argv[1]
rows = [json.loads(ln) for ln in open(d + "/c_tel/metrics.jsonl") if ln.strip()]
rollbacks = [r for r in rows if r.get("event") == "rollback" and r.get("requested")]
assert rollbacks, "no requested rollback executed"
assert all(r["to_iteration"] < r["step"] for r in rollbacks), rollbacks
states = [json.loads(ln)["state"] for ln in open(d + "/c_tel/alerts.jsonl") if ln.strip()]
assert "firing" in states, "collapse alert never fired"
assert states[-1] == "resolved", f"collapse alert never resolved: {states}"
rem = [json.loads(ln) for ln in open(d + "/c_tel/remediation.jsonl") if ln.strip()]
assert any(r["policy"] == "trainer_rollback" and r["state"] == "succeeded"
           for r in rem), rem
print(f"chaos C OK ({len(rollbacks)} rollback(s) to iteration "
      f"{rollbacks[0]['to_iteration']}, alert resolved, "
      f"{len(rem)} audit event(s))")
EOF
chaos_gates "$chaos_dir/c_tel" C

echo "-- chaos D (headline): model staleness -> zero-downtime hot-swap --"
# The train->serve freshness loop's actuation half, end to end: a
# trainer snapshots continuously (and is killed + resumed MID-STREAM);
# the server watches its snapshot_prefix, the model-staleness alert
# fires as the served snapshot ages past target, the hot-swap
# remediation republishes a freshly-warmed engine tier WITHOUT dropping
# a single in-flight query, and the per-answer model_age_s visibly
# drops at each swap — the staleness watchdog proving the swap.
hs="$chaos_dir/hs"
mkdir -p "$hs"
cat > "$hs/solver.prototxt" <<EOF
net: "examples/tiny_net.prototxt"
base_lr: 0.05
lr_policy: "fixed"
momentum: 0.9
max_iter: 100000
display: 0
test_interval: 0
test_iter: 0
snapshot: 40
snapshot_prefix: "$hs/snap/m_"
snapshot_max_keep: 10
EOF
python - "$hs" <<'EOF'
import json, sys
d = sys.argv[1]
json.dump({"slos": [{
    "name": "model_staleness", "metric": "serve_model_age_s", "op": "<=",
    "target": 5.0, "window_s": 2.0, "burn_threshold": 0.5,
    "min_samples": 1, "severity": "warning"}]}, open(d + "/slo.json", "w"))
json.dump({"policies": [{
    "name": "hotswap_model", "slo": "model_staleness",
    "action": "snapshot_hotswap", "cooldown_s": 4.0, "max_attempts": 4}]},
    open(d + "/rem.json", "w"))
EOF
# Phase 0: one short run commits the INITIAL snapshot the server restores.
JAX_PLATFORMS=cpu python -m npairloss_tpu train --solver "$hs/solver.prototxt" \
    --model mlp --synthetic --max_iter 40 > "$hs/seed.log" 2>&1 \
    || { echo "chaos D: seed training failed"; cat "$hs/seed.log"; exit 1; }
[[ -f "$hs/snap/m_iter_40.ckpt/manifest.json" ]] \
    || { echo "chaos D: seed snapshot missing"; exit 1; }
# The trainer, snapshotting continuously (the supervisor loop: kill ->
# relaunch same command, the docs/RESILIENCE.md recipe).
JAX_PLATFORMS=cpu python -m npairloss_tpu train --solver "$hs/solver.prototxt" \
    --model mlp --synthetic --resume auto > "$hs/train1.log" 2>&1 &
tr_pid=$!
mkfifo "$hs/in"
JAX_PLATFORMS=cpu python -m npairloss_tpu serve --index "$chaos_dir/g.gidx" \
    --snapshot "$hs/snap/m_iter_40.ckpt" --model mlp --input-size 8 \
    --watch-snapshots "$hs/snap/m_" \
    --top-k 3 --buckets 1 --deadline-ms 1 --metrics-window 4 \
    --telemetry-dir "$hs/tel" --live-obs --slo-config "$hs/slo.json" \
    --slo-tick 0.2 --remediate --remediation-config "$hs/rem.json" \
    < "$hs/in" > "$hs/answers.jsonl" 2> "$hs/serve.log" &
sv_pid=$!
exec 8> "$hs/in"
( head -500 "$chaos_dir/queries.jsonl" | while IFS= read -r ln; do
    printf '%s\n' "$ln" >&8; sleep 0.05; done ) &
feeder=$!
sleep 10
# Kill the trainer MID-STREAM; the server must keep answering.
kill -TERM "$tr_pid" 2>/dev/null || true
rc=0; wait "$tr_pid" || rc=$?
[[ "$rc" -eq 75 ]] \
    || { echo "chaos D: trainer kill expected 75, got $rc"; cat "$hs/train1.log"; exit 1; }
# ...and resume it (same command line — the auto-resume contract).
JAX_PLATFORMS=cpu python -m npairloss_tpu train --solver "$hs/solver.prototxt" \
    --model mlp --synthetic --resume auto > "$hs/train2.log" 2>&1 &
tr_pid=$!
wait "$feeder" || true
for _ in $(seq 1 240); do  # every fed query must be answered
    n=$(grep -c '"neighbors"' "$hs/answers.jsonl" 2>/dev/null || true)
    [[ "${n:-0}" -ge 500 ]] && break
    kill -0 "$sv_pid" 2>/dev/null \
        || { echo "chaos D: server died mid-serve"; tail -30 "$hs/serve.log"; exit 1; }
    sleep 0.5
done
sleep 2  # let the last swap's resolution land before the drain
kill -TERM "$sv_pid" 2>/dev/null || true
exec 8>&-
rc=0; wait "$sv_pid" || rc=$?
[[ "$rc" -eq 75 ]] \
    || { echo "chaos D: serve expected exit 75, got $rc"; tail -30 "$hs/serve.log"; exit 1; }
kill -TERM "$tr_pid" 2>/dev/null || true
wait "$tr_pid" || true
grep -q "resuming from iteration" "$hs/train2.log" \
    || { echo "chaos D: relaunched trainer did not resume"; cat "$hs/train2.log"; exit 1; }
python - "$hs" <<'EOF'
import json, sys
d = sys.argv[1]
lines = [json.loads(ln) for ln in open(d + "/answers.jsonl") if ln.strip()]
drain = lines[-1]
assert drain.get("event") == "serve_drain", drain
served = [a for a in lines[:-1] if "neighbors" in a]
# zero downtime: EVERY fed query answered, none dropped or errored,
# through two trainer generations and every swap
assert len(served) == 500 and drain["errors"] == 0, (len(served), drain)
assert drain["queries"] == drain["answered"] + drain["errors"] + drain["rejected"], drain
assert drain["hot_swaps"] >= 2, f"expected >=2 hot swaps, got {drain.get('hot_swaps')}"
# the served model ADVANCED: the drain's snapshot_step is a later
# training iteration than the seed snapshot the server started from
assert drain["snapshot_step"] > 40, drain["snapshot_step"]
# per-answer model_age_s drops at each swap (the staleness watchdog's
# proof): count strict drops of > 2s between consecutive answers
ages = [a["model_age_s"] for a in served if "model_age_s" in a]
assert len(ages) == 500, len(ages)
drops = sum(1 for i in range(1, len(ages)) if ages[i] < ages[i - 1] - 2.0)
assert drops >= 2, f"model age dropped {drops}x, expected >= 2 swaps visible"
states = [json.loads(ln)["state"] for ln in open(d + "/tel/alerts.jsonl") if ln.strip()]
assert states.count("firing") >= 2, states
assert "resolved" in states, states
rem = [json.loads(ln) for ln in open(d + "/tel/remediation.jsonl") if ln.strip()]
swaps_ok = [r for r in rem if r["policy"] == "hotswap_model"
            and r["state"] == "succeeded"]
assert len(swaps_ok) >= 1, rem
print(f"chaos D OK ({drain['hot_swaps']} hot swap(s), {drops} visible "
      f"age drops, served snapshot_step {drain['snapshot_step']}, "
      f"500/500 answered, {states.count('firing')} staleness incident(s))")
EOF
chaos_gates "$hs/tel" D

echo "== quality observatory smoke (docs/OBSERVABILITY.md §Quality) =="
# The recall loop end to end: a clean IVF serve run under a recall@10
# SLO (shadow-scoring EVERY query against the flat oracle) fires ZERO
# alerts and the jax-free --quality gate accepts its log; a run with
# serve.recall_drop armed fires the recall alert, the probe-escalation
# remediation runs, the alert resolves, --quality and --remediation
# both accept; the watch replay reproduces firing->resolved through
# the same engine; and the gate's teeth refuse a schema violation and
# a floor breach with no fired alert.
q_dir="$smoke_dir/quality"
mkdir -p "$q_dir"
python - "$q_dir" <<'EOF'
import json, sys
import numpy as np
d = sys.argv[1]
rng = np.random.default_rng(0)
# Well-separated blobs: IVF geometry where partial probes still find
# the true neighbors, so only the INJECTED mis-probe can drop recall.
centers = rng.standard_normal((8, 32)).astype(np.float32)
centers /= np.linalg.norm(centers, axis=1, keepdims=True)
emb = np.repeat(centers, 32, axis=0) + 0.1 * rng.standard_normal(
    (256, 32)).astype(np.float32)
emb /= np.linalg.norm(emb, axis=1, keepdims=True)
np.save(d + "/g.emb.npy", emb)
np.save(d + "/g.labels.npy", np.repeat(np.arange(8), 32).astype(np.int32))
with open(d + "/queries.jsonl", "w") as f:
    for i in range(200):
        f.write(json.dumps({"id": i, "embedding": emb[i % 256].tolist()}) + "\n")
json.dump({"slos": [{
    "name": "serve_recall_floor", "metric": "serve_recall_at_10",
    "op": ">=", "target": 0.9, "window_s": 2.0, "burn_threshold": 0.5,
    "min_samples": 1, "severity": "critical"}]},
    open(d + "/slo.json", "w"))
json.dump({"policies": [{
    "name": "probe_escalation", "slo": "serve_recall_floor",
    "action": "escalate_probes", "cooldown_s": 4.0, "max_attempts": 4}]},
    open(d + "/rem.json", "w"))
EOF
JAX_PLATFORMS=cpu python -m npairloss_tpu index \
    --emb "$q_dir/g.emb.npy" --labels "$q_dir/g.labels.npy" \
    --no-normalize --kind ivf --clusters 8 --parity-sample 64 \
    --out "$q_dir/g.gidx" > "$q_dir/index.log" 2>&1 \
    || { echo "quality smoke: ivf index build failed"; cat "$q_dir/index.log"; exit 1; }
python - "$q_dir/g.gidx/manifest.json" <<'EOF'
import json, sys
par = json.load(open(sys.argv[1])).get("parity")
assert par and par["recall"]["fp32"]["at_10"] >= 0.95, par
print(f"parity birth certificate committed (fp32 recall@10 "
      f"{par['recall']['fp32']['at_10']}, probes {par['probes']})")
EOF

run_quality_serve() {  # $1 = tel dir, $2 = probes, $3 = failpoints, $4 = extra args
    local tel="$1" probes="$2" fp="$3"; shift 3
    mkfifo "$q_dir/in.$$"
    env JAX_PLATFORMS=cpu NPAIRLOSS_FAILPOINTS="$fp" \
        python -m npairloss_tpu serve --index "$q_dir/g.gidx" \
        --index-kind ivf --probes "$probes" --top-k 10 --buckets 1 \
        --deadline-ms 1 --metrics-window 4 --shadow-rate 1 \
        --shadow-window 4 --telemetry-dir "$tel" --live-obs \
        --slo-config "$q_dir/slo.json" --slo-tick 0.2 "$@" \
        < "$q_dir/in.$$" > "$tel.answers.jsonl" 2> "$tel.log" &
    qpid=$!
    exec 9> "$q_dir/in.$$"
    # phase 1: (possibly fault-poisoned) traffic
    head -40 "$q_dir/queries.jsonl" | while IFS= read -r ln; do
        printf '%s\n' "$ln" >&9; sleep 0.08
    done
    sleep 2.5  # fault (if armed) exhausts; alert fires; remediation runs
    # phase 2: clean traffic — good recall windows age the burn out
    sed -n '41,100p' "$q_dir/queries.jsonl" | while IFS= read -r ln; do
        printf '%s\n' "$ln" >&9; sleep 0.05
    done
    sleep 3    # resolution lands before the drain
    kill -TERM "$qpid" 2>/dev/null || true
    exec 9>&-
    rc=0; wait "$qpid" || rc=$?
    rm -f "$q_dir/in.$$"
    [[ "$rc" -eq 75 ]] \
        || { echo "quality smoke: expected exit 75, got $rc"; cat "$tel.log"; exit 1; }
}

echo "-- quality clean run: zero alerts, gate accepts --"
run_quality_serve "$q_dir/clean" 8 ""
[[ ! -s "$q_dir/clean/alerts.jsonl" ]] \
    || { echo "quality smoke: CLEAN run fired alerts"; cat "$q_dir/clean/alerts.jsonl"; exit 1; }
python - "$q_dir" <<'EOF'
import json, sys
d = sys.argv[1]
lines = [json.loads(ln) for ln in open(d + "/clean.answers.jsonl") if ln.strip()]
drain = lines[-1]
assert drain.get("event") == "serve_drain", drain
assert drain["errors"] == 0 and drain["answered"] == 100, drain
q = drain["quality"]
assert q["sampled"] == 100 and q["windows"] >= 20, q
assert q["last"]["recall_at_10"] == 1.0, q
assert q["baseline"]["recall"]["fp32"]["at_10"] >= 0.95, q
recs = [json.loads(ln) for ln in open(d + "/clean/quality.jsonl") if ln.strip()]
assert recs[0]["kind"] == "config" and recs[0]["recall_floor"] == 0.9, recs[0]
assert recs[-1]["kind"] == "summary", recs[-1]
print(f"quality clean OK ({q['windows']} windows, recall@10 "
      f"{q['last']['recall_at_10']}, baseline committed)")
EOF
python scripts/bench_check.py --quality "$q_dir/clean/quality.jsonl" \
    || { echo "quality smoke: gate refused the clean log"; exit 1; }
JAX_PLATFORMS=cpu python -m npairloss_tpu prof --quality "$q_dir/clean" \
    > "$q_dir/prof.log" 2>&1 \
    || { echo "quality smoke: prof --quality refused"; cat "$q_dir/prof.log"; exit 1; }

echo "-- quality fault run: recall_drop -> alert -> probe escalation -> resolve --"
run_quality_serve "$q_dir/fault" 2 "serve.recall_drop:12" \
    --remediate --remediation-config "$q_dir/rem.json"
python - "$q_dir" <<'EOF'
import json, sys
d = sys.argv[1]
lines = [json.loads(ln) for ln in open(d + "/fault.answers.jsonl") if ln.strip()]
drain = lines[-1]
assert drain.get("event") == "serve_drain", drain
assert drain["errors"] == 0 and drain["answered"] == 100, drain
states = [json.loads(ln)["state"] for ln in open(d + "/fault/alerts.jsonl") if ln.strip()]
assert "firing" in states, "recall_drop never fired the recall alert"
assert states[-1] == "resolved", f"recall alert never resolved: {states}"
rem = [json.loads(ln) for ln in open(d + "/fault/remediation.jsonl") if ln.strip()]
esc = [r for r in rem if r["policy"] == "probe_escalation"]
assert esc, "probe escalation never attempted"
ok = [r for r in esc if r["state"] == "succeeded"]
assert ok, f"probe escalation never succeeded: {esc}"
assert drain["hot_swaps"] >= 1, drain  # the escalation republished the tier
assert drain["remediation"]["probe_escalation"]["outcome"] == "succeeded", drain
qrecs = [json.loads(ln) for ln in open(d + "/fault/quality.jsonl") if ln.strip()]
bad = [r for r in qrecs if r.get("kind") == "window" and r["recall_at_10"] < 0.9]
assert bad, "no breaching window recorded — the fault never reached the shadow"
print(f"quality fault OK ({len(bad)} breaching window(s), "
      f"{len(ok)} escalation(s) succeeded, alert resolved, "
      f"{drain['hot_swaps']} hot swap(s))")
EOF
python scripts/bench_check.py --quality "$q_dir/fault/quality.jsonl" \
    || { echo "quality smoke: gate refused the remediated fault log"; exit 1; }
python scripts/bench_check.py --remediation "$q_dir/fault/remediation.jsonl" \
    || { echo "quality smoke: remediation gate refused"; exit 1; }
python scripts/bench_check.py --alerts "$q_dir/fault/alerts.jsonl" \
    || { echo "quality smoke: alert gate refused the fire->resolve log"; exit 1; }
# the offline feed agrees: watch must reproduce firing->resolved from
# the recall rows on disk, and surface a valid quality block
JAX_PLATFORMS=cpu python -m npairloss_tpu watch "$q_dir/fault" \
    --slo-config "$q_dir/slo.json" > "$q_dir/watch.log" 2>&1 \
    || { echo "quality smoke: watch refused the run dir"; cat "$q_dir/watch.log"; exit 1; }
python - "$q_dir" <<'EOF'
import json, sys
d = sys.argv[1]
states = [json.loads(ln)["state"]
          for ln in open(d + "/fault/alerts.watch.jsonl") if ln.strip()]
assert "firing" in states and states[-1] == "resolved", states
summary = json.loads(open(d + "/watch.log").read().strip().splitlines()[-1])
assert summary["quality"]["valid"] is True, summary.get("quality")
assert summary["quality"]["breaches"] >= 1, summary["quality"]
print(f"watch feed agrees: {states}; quality block valid "
      f"({summary['quality']['breaches']} breach(es) surfaced)")
EOF
# gate teeth: a schema violation and a breach with NO fired alert must
# both be refused
sed 's/npairloss-quality-v1/npairloss-quality-v0/' \
    "$q_dir/fault/quality.jsonl" > "$q_dir/badschema.jsonl"
python scripts/bench_check.py --quality "$q_dir/badschema.jsonl" > /dev/null \
    && { echo "quality smoke: gate ACCEPTED a schema violation"; exit 1; }
mkdir -p "$q_dir/ghost"
cp "$q_dir/fault/quality.jsonl" "$q_dir/ghost/quality.jsonl"
python scripts/bench_check.py --quality "$q_dir/ghost/quality.jsonl" > /dev/null \
    && { echo "quality smoke: gate ACCEPTED a breach with no alert log"; exit 1; }
echo "quality observatory smoke OK (clean zero-alert + gate, fault->alert->escalation->resolve, watch agreement, gate teeth)"

echo "== multi-tenant serving smoke (docs/SERVING.md §Multi-tenant) =="
# Three tenants (mixed flat/IVF, distinct galleries) behind ONE front
# end / ONE replica tier / ONE compile cache: routed self-match answers
# per tenant, an unknown tenant refused as an error, a MID-TRAFFIC
# hot-swap of one tenant with zero drops and bit-level proof the
# others kept serving, a noisy tenant quota-shed in isolation (its
# tenant-scoped alert fires; neighbors keep zero errors/rejects), zero
# post-warmup compiles across the shared geometry, and the jax-free
# bench_check --tenants gate accepting the evidence and refusing
# tampered copies of it.
mt_dir="$smoke_dir/mt"
mkdir -p "$mt_dir/idx" "$mt_dir/tel"
python - "$mt_dir" <<'EOF'
import json, sys
import numpy as np
from npairloss_tpu.serve import GalleryIndex
d = sys.argv[1]
for t_i, tid in enumerate(("acme", "bcorp", "ccorp")):
    rng = np.random.default_rng(11 + t_i)
    emb = rng.standard_normal((192, 32)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    labels = (np.arange(192) % 16).astype(np.int32)
    GalleryIndex.build(emb, labels, normalize=False).save(
        f"{d}/idx/{tid}-0000.gidx")
    np.save(f"{d}/{tid}.emb.npy", emb)
tenants = [
    # capacity = qps*burst_s = 10 tokens: phase A's 10 paced probes
    # fit the bucket, the 30-query flood cannot.
    {"tenant_id": "acme", "index_prefix": d + "/idx/acme-",
     "index_kind": "ivf", "quota_qps": 2.0, "quota_burst_s": 5.0},
    {"tenant_id": "bcorp", "index_prefix": d + "/idx/bcorp-"},
    {"tenant_id": "ccorp", "index_prefix": d + "/idx/ccorp-"},
]
json.dump({"schema": "npairloss-tenants-v1", "tenants": tenants},
          open(d + "/tenants.json", "w"))
with open(d + "/phase_a.jsonl", "w") as f:
    for tid in ("acme", "bcorp", "ccorp"):
        emb = np.load(f"{d}/{tid}.emb.npy")
        for i in range(10):
            f.write(json.dumps({"id": f"{tid[0]}-{i}", "tenant": tid,
                                "embedding": emb[i].tolist()}) + "\n")
    f.write(json.dumps({"id": "x-1", "tenant": "ghost",
                        "embedding": emb[0].tolist()}) + "\n")
    f.write(json.dumps({"id": "x-2",
                        "embedding": emb[0].tolist()}) + "\n")
EOF
mkfifo "$mt_dir/in"
# Strict guard: ANY post-warmup compile aborts the server — the
# cross-tenant program-sharing claim fails loudly, not just by counter.
JAX_PLATFORMS=cpu NPAIRLOSS_SERVE_COMPILE_GUARD=strict \
    python -m npairloss_tpu serve \
    --tenant-config "$mt_dir/tenants.json" \
    --top-k 5 --buckets 1,8 --deadline-ms 2 --poll-s 0.02 \
    --max-queue 64 --metrics-window 4 \
    --explicit-drops --live-obs --slo-tick 0.2 \
    --telemetry-dir "$mt_dir/tel" \
    < "$mt_dir/in" > "$mt_dir/answers.jsonl" \
    2> "$mt_dir/serve.log" &
mt_pid=$!
exec 4> "$mt_dir/in"
cat "$mt_dir/phase_a.jsonl" >&4
for _ in $(seq 1 240); do  # 32 answers: 30 routed + 2 refused
    [[ "$(wc -l < "$mt_dir/answers.jsonl")" -ge 32 ]] && break
    kill -0 "$mt_pid" 2>/dev/null \
        || { echo "mt smoke: server died in phase A"; cat "$mt_dir/serve.log"; exit 1; }
    sleep 0.5
done
# Mid-traffic hot-swap: commit a STRICTLY newer bcorp gallery; the
# per-tenant watch must republish bcorp alone within a sweep or two.
python - "$mt_dir" <<'EOF'
import sys
import numpy as np
from npairloss_tpu.serve import GalleryIndex
d = sys.argv[1]
rng = np.random.default_rng(99)
emb = rng.standard_normal((192, 32)).astype(np.float32)
emb /= np.linalg.norm(emb, axis=1, keepdims=True)
labels = (np.arange(192) % 16).astype(np.int32)
GalleryIndex.build(emb, labels, normalize=False).save(
    d + "/idx/bcorp-0001.gidx")
np.save(d + "/bcorp2.emb.npy", emb)
EOF
for _ in $(seq 1 60); do
    grep -q "tenant 'bcorp' republished" "$mt_dir/serve.log" && break
    kill -0 "$mt_pid" 2>/dev/null \
        || { echo "mt smoke: server died awaiting hot-swap"; cat "$mt_dir/serve.log"; exit 1; }
    sleep 0.5
done
grep -q "tenant 'bcorp' republished" "$mt_dir/serve.log" \
    || { echo "mt smoke: bcorp hot-swap never landed"; cat "$mt_dir/serve.log"; exit 1; }
# Phase B: bcorp answers from the NEW gallery; then the noisy-neighbor
# flood — acme's 1-token bucket sheds the burst while bcorp/ccorp ride
# along untouched.
python - "$mt_dir" <<'EOF'
import json, sys
import numpy as np
d = sys.argv[1]
with open(d + "/phase_b.jsonl", "w") as f:
    emb2 = np.load(d + "/bcorp2.emb.npy")
    for i in range(10):
        f.write(json.dumps({"id": f"b2-{i}", "tenant": "bcorp",
                            "embedding": emb2[i].tolist()}) + "\n")
    embs = {t: np.load(f"{d}/{t}.emb.npy")
            for t in ("acme", "bcorp", "ccorp")}
    for i in range(30):
        f.write(json.dumps({"id": f"hot-{i}", "tenant": "acme",
                            "embedding": embs["acme"][i % 192].tolist()})
                + "\n")
        if i % 3 == 0:
            for t in ("bcorp", "ccorp"):
                emb = embs[t] if t != "bcorp" else emb2
                f.write(json.dumps({"id": f"q-{t}-{i}", "tenant": t,
                                    "embedding": emb[i].tolist()}) + "\n")
EOF
cat "$mt_dir/phase_b.jsonl" >&4
for _ in $(seq 1 120); do  # 32 + 10 + 30 + 20 = 92 answers
    [[ "$(wc -l < "$mt_dir/answers.jsonl")" -ge 92 ]] && break
    kill -0 "$mt_pid" 2>/dev/null \
        || { echo "mt smoke: server died in phase B"; cat "$mt_dir/serve.log"; exit 1; }
    sleep 0.5
done
for _ in $(seq 1 60); do  # the tenant-scoped quota alert must page
    grep -q '"slo": "tenant_quota@acme"' "$mt_dir/tel/alerts.jsonl" 2>/dev/null && break
    sleep 0.5
done
kill -TERM "$mt_pid" 2>/dev/null || true
exec 4>&-
rc=0; wait "$mt_pid" || rc=$?
[[ "$rc" -eq 75 ]] \
    || { echo "mt smoke: expected exit 75 after SIGTERM, got $rc"; cat "$mt_dir/serve.log"; exit 1; }
python - "$mt_dir" <<'EOF'
import json, sys
d = sys.argv[1]
lines = [json.loads(ln) for ln in open(d + "/answers.jsonl") if ln.strip()]
drain = lines[-1]
assert drain.get("event") == "serve_drain", drain
answers = {a["id"]: a for a in lines[:-1]}
for tid in ("acme", "bcorp", "ccorp"):
    for i in range(10):  # phase A: routed self-match per tenant
        a = answers[f"{tid[0]}-{i}"]
        assert a.get("tenant") == tid and a["neighbors"][0]["row"] == i, a
for i in range(10):  # post-swap bcorp: NEW gallery's rows self-match
    a = answers[f"b2-{i}"]
    top1 = a["neighbors"][0]
    assert top1["row"] == i and top1["score"] > 0.99, a
for rid in ("x-1", "x-2"):  # unknown tenant: refused, never admitted
    assert "unknown tenant" in answers[rid]["error"], answers[rid]
shed = [a for a in answers.values()
        if "quota exceeded" in a.get("error", "")]
assert shed and all("'acme'" in a["error"] for a in shed), len(shed)
per = drain["tenants"]
assert per["acme"]["quota"]["sheds"] >= 15, per["acme"]
assert per["bcorp"]["errors"] == 0 and per["bcorp"]["rejected"] == 0, per["bcorp"]
assert per["ccorp"]["errors"] == 0 and per["ccorp"]["rejected"] == 0, per["ccorp"]
assert per["bcorp"]["hot_swaps"] == 1 and "hot_swaps" not in per["ccorp"], per
assert per["acme"]["index_kind"] == "ivf" and per["bcorp"]["index_kind"] == "flat"
assert drain["errors_unattributed"] == 2, drain  # the 2 unknown-tenant refusals
for key in ("queries", "answered", "errors", "rejected"):
    total = sum(row[key] for row in per.values())
    if key == "errors":
        total += drain["errors_unattributed"]
    assert total == drain[key], (key, total, drain[key])
assert drain["queries_dropped"] == 0, drain
assert drain["compiles_after_warmup"] == 0, drain
alerts = [json.loads(ln) for ln in open(d + "/tel/alerts.jsonl")]
fired = [a for a in alerts if a.get("state") == "firing"]
assert any(a["slo"] == "tenant_quota@acme" for a in fired), fired
# Noisy-neighbor isolation at the paging layer: acme's incident never
# becomes a bcorp/ccorp-scoped page.
assert not [a for a in fired
            if a["slo"].endswith(("@bcorp", "@ccorp"))], fired
print(f"mt smoke: {drain['answered']} answered across 3 tenants, "
      f"{per['acme']['quota']['sheds']} acme sheds contained, "
      f"1 bcorp hot-swap, 0 dropped, 0 post-warmup compiles")
EOF
python scripts/bench_check.py --tenants "$mt_dir/tenants.json" > /dev/null \
    || { echo "mt smoke: gate REFUSED honest tenant evidence"; exit 1; }
python - "$mt_dir" <<'EOF'
import json, sys
d = sys.argv[1]
man = json.load(open(d + "/tenants.json"))
man["tenants"][0]["quota_qps"] = -1
json.dump(man, open(d + "/tampered_manifest.json", "w"))
out = []
for ln in open(d + "/answers.jsonl"):
    rec = json.loads(ln)
    if rec.get("event") == "serve_drain":
        rec["tenants"]["acme"]["rejected"] = 0  # hide the sheds
    out.append(json.dumps(rec))
open(d + "/tampered_answers.jsonl", "w").write("\n".join(out) + "\n")
EOF
python scripts/bench_check.py --tenants "$mt_dir/tampered_manifest.json" > /dev/null \
    && { echo "mt smoke: gate ACCEPTED a tampered manifest"; exit 1; }
python scripts/bench_check.py --tenants "$mt_dir/tenants.json" \
    --answers-log "$mt_dir/tampered_answers.jsonl" > /dev/null \
    && { echo "mt smoke: gate ACCEPTED broken tenant cross-sums"; exit 1; }
echo "multi-tenant smoke OK (3 tenants one tier, routed answers, mid-traffic hot-swap, quota isolation + tenant-scoped alert, gate + teeth)"

echo "== gameday: composed-system soak (docs/RESILIENCE.md §8) =="
# The whole stack as one production-shaped group — snapshotting trainer
# (preempted mid-stream, relaunched, resumed), replicated serving tier
# (SLO admission, shadow scoring, snapshot/index hot-swap), watch
# evaluator — driven by the seeded compressed day while the chaos
# schedule arms every fault family.  The npairloss-gameday-v1 verdict
# IS the pass/fail contract: every injected fault alerted AND
# remediated, SLOs held outside declared incident windows, zero
# dropped queries across >= 3 live hot-swaps, comms fully attributed.
g_dir="$smoke_dir/gameday"
JAX_PLATFORMS=cpu python -m npairloss_tpu gameday \
    --out "$g_dir" --seed 0 --duration 75 > "$g_dir.cli.log" 2>&1 \
    || { echo "gameday: run failed"; tail -30 "$g_dir.cli.log"; \
         tail -30 "$g_dir/serve.log" 2>/dev/null; exit 1; }
python scripts/bench_check.py --gameday "$g_dir/gameday.json" \
    || { echo "gameday: gate refused a passing run"; exit 1; }
python - "$g_dir" <<'EOF'
import json, sys
r = json.load(open(sys.argv[1] + "/gameday.json"))
assert r["verdict"] == "pass", r["failures"]
assert r["zero_drop"]["hot_swaps"] >= 3, r["zero_drop"]
assert r["zero_drop"]["queries_dropped"] == 0, r["zero_drop"]
bad = [f["name"] for f in r["faults"] if not f["ok"]]
assert not bad, bad
print(f"gameday: {len(r['faults'])} fault(s) injected+remediated, "
      f"{r['zero_drop']['hot_swaps']} hot-swap(s), 0 dropped, "
      f"{r['drain']['answered']} answered "
      f"(traffic sha {r['traffic']['sha256'][:12]})")
EOF
# gate teeth: a schema tamper and doctored evidence under a forged
# "pass" verdict must BOTH be refused (the validator recomputes every
# gate from the report's own evidence)
sed 's/npairloss-gameday-v1/npairloss-gameday-v0/' \
    "$g_dir/gameday.json" > "$g_dir/badschema.json"
python scripts/bench_check.py --gameday "$g_dir/badschema.json" > /dev/null \
    && { echo "gameday: gate ACCEPTED a schema violation"; exit 1; }
python - "$g_dir" <<'EOF'
import json, sys
d = sys.argv[1]
r = json.load(open(d + "/gameday.json"))
r["zero_drop"]["queries_dropped"] = 7  # doctored; verdict left "pass"
json.dump(r, open(d + "/tampered.json", "w"))
EOF
python scripts/bench_check.py --gameday "$g_dir/tampered.json" > /dev/null \
    && { echo "gameday: gate ACCEPTED doctored evidence under a pass verdict"; exit 1; }
echo "gameday smoke OK (compressed day, scripted chaos, verdict gate + teeth)"

echo "== tier-1 tests (ROADMAP.md) =="
rm -f /tmp/_t1.log
# `|| rc=$?` keeps set -e from aborting on test failures so the
# DOTS_PASSED diagnostic still prints; the script's exit code is the
# pytest pipeline's.
rc=0
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
    -p no:randomly 2>&1 | tee /tmp/_t1.log || rc=$?
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
exit "$rc"
