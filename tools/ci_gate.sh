#!/usr/bin/env bash
# CI gate — graftlint (23 rules, baseline-gated) + the tier-1 pytest line,
# as ONE exit-coded command. Either failing fails the gate; both always
# run so a single CI pass reports lint findings AND test failures.
#
# Usage:
#   tools/ci_gate.sh                 # text findings
#   tools/ci_gate.sh --bench-smoke   # + the 50k-row pipelined GBM bench leg
#   tools/ci_gate.sh --bench-gate    # + smoke bench at baseline config,
#                                    #   gated vs BENCH_r06_baseline.jsonl
#   tools/ci_gate.sh --sanitize-stress  # + serving+train+sweep stress with
#                                    #   ALL FOUR sanitizer arms armed
#   tools/ci_gate.sh --health-gate   # + boot a server, assert /3/Health
#                                    #   ready -> wedged (typed reason) ->
#                                    #   recovered across a failpoint drill
#   tools/ci_gate.sh --workload-gate # + boot a server with 2 managed
#                                    #   slots, 3-tenant mixed stress with
#                                    #   boundary kills auto-resumed, SLO
#                                    #   held, zero sanitizer violations
#   GRAFTLINT_FORMAT=github tools/ci_gate.sh   # ::error annotations
#   GRAFTLINT_JOBS=4 tools/ci_gate.sh          # parallel lint scan
#
# --bench-smoke runs the airlines bench leg (the pipelined-training
# scoreboard) at 50k rows with H2O_TPU_PIPELINE on and asserts rc=0,
# forest_parity=true (pipelined forest + predictions bit-equal to the
# synchronous oracle) and 0 steady-state uncached compiles on the warm
# train. The >=1.25x speedup stays a recorded number, not a gate — CI
# machines' walls are noisy; parity and compile hygiene are not.
#
# --bench-gate runs the gbm+glm legs at the BENCH_r06 baseline's exact
# config (60k rows / 100 trees, so walls are comparable) and pipes the
# sidecar through tools/bench_gate.py: per-leg tolerance bands on wall,
# peak HBM bytes, AUC, parity flags — nonzero exit names the regressed
# (leg, metric). Band overrides: H2O_TPU_BENCH_GATE_BANDS.
#
# --health-gate boots a REAL server (watchdog armed at a 100ms sweep),
# asserts GET /3/Health reports ready over the wire, arms the registered
# watchdog.trip failpoint to force-wedge every detector, asserts the
# endpoint degrades with the TYPED watchdog-trip reason, disarms, and
# asserts recovery once the trips age out — the full signal path the
# autoscaling loop will poll, exit-coded.
#
# --workload-gate boots a REAL server with H2O_TPU_WORKLOAD_SLOTS=2 and
# the recompile sanitizer armed, then (1) kills a REST-submitted GBM at
# EVERY chunk boundary via the workload.preempt failpoint and asserts the
# scheduler entry auto-resumes to DONE each time, (2) runs a 3-tenant
# mixed-priority stress (three concurrent REST builds + a serving score
# loop) and asserts every tenant's job completes (no starvation), GET
# /3/Health stays ready (per-tenant serving SLO held) and the sanitizer
# + steady-state recompile counters read ZERO.
#
# --sanitize-stress re-runs the PR 11 serving+train+sweep stress pass
# with H2O_TPU_SANITIZE=locks,guards,transfers,recompiles all armed
# (instrumented locks + guard assertions + transfer guards over every
# hot section + steady-state compile scopes) and asserts SILENCE —
# zero typed violations across concurrent scoring, a real GBM train,
# and forced Cleaner sweeps. The drill twins (failpoint + live
# host->device trip + serving bucket-miss) ride along so the typed
# violation -> flight-bundle seams stay exercised. These tests also run
# inside the tier-1 line above; the flag is the DELIBERATE duplicate — a
# named, exit-coded leg a nightly/hardware pipeline can point at without
# parsing the 1100-test tier-1 summary, re-run in a fresh interpreter so
# sanitizer arming never inherits tier-1 process state.
set -u -o pipefail
cd "$(dirname "$0")/.."

fmt="${GRAFTLINT_FORMAT:-text}"
jobs="${GRAFTLINT_JOBS:-2}"
bench_smoke=0
bench_gate=0
sanitize_stress=0
health_gate=0
workload_gate=0
for arg in "$@"; do
    case "$arg" in
        --bench-smoke) bench_smoke=1 ;;
        --bench-gate) bench_gate=1 ;;
        --sanitize-stress) sanitize_stress=1 ;;
        --health-gate) health_gate=1 ;;
        --workload-gate) workload_gate=1 ;;
        *) echo "ci_gate.sh: unknown argument '$arg'" >&2; exit 2 ;;
    esac
done

echo "== graftlint =="
python -m tools.graftlint --format "$fmt" --jobs "$jobs"
lint_rc=$?

echo "== tier-1 pytest =="
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly
test_rc=$?

bench_rc=0
if [ "$bench_smoke" -eq 1 ]; then
    echo "== bench smoke (pipelined 50k-row GBM) =="
    sidecar="$(mktemp /tmp/h2o_tpu_bench_smoke.XXXXXX.jsonl)"
    timeout -k 10 900 env JAX_PLATFORMS=cpu \
        H2O_TPU_BENCH_WORKLOADS=airlines \
        H2O_TPU_BENCH_AIRLINES_ROWS=50000 \
        H2O_TPU_PIPELINE=1 \
        H2O_TPU_BENCH_SIDECAR="$sidecar" \
        python bench.py > /dev/null
    bench_rc=$?
    if [ "$bench_rc" -eq 0 ]; then
        python - "$sidecar" <<'EOF'
import json, sys

rec = None
for line in open(sys.argv[1]):
    d = json.loads(line)
    if d.get("workload") == "airlines116m":
        rec = d["record"]
assert rec is not None, "airlines leg record missing from sidecar"
assert rec["forest_parity"] is True, \
    f"pipelined forest NOT bit-equal to the synchronous oracle: {rec}"
assert rec["uncached_compiles_warm"] == 0, \
    f"steady-state uncached compiles: {rec['uncached_compiles_warm']}"
print(json.dumps({"bench_smoke": "ok",
                  "wall_s": rec["wall_s"],
                  "wall_sync_s": rec["wall_sync_s"],
                  "pipeline_speedup_x": rec["pipeline_speedup_x"]}))
EOF
        bench_rc=$?
    fi
    rm -f "$sidecar"
fi

gate_rc=0
if [ "$bench_gate" -eq 1 ]; then
    echo "== bench gate (gbm+glm @ BENCH_r06 config vs baseline bands) =="
    sidecar="$(mktemp /tmp/h2o_tpu_bench_gate.XXXXXX.jsonl)"
    timeout -k 10 1500 env JAX_PLATFORMS=cpu \
        H2O_TPU_BENCH_WORKLOADS=gbm,glm \
        H2O_TPU_BENCH_ROWS=60000 \
        H2O_TPU_BENCH_TREES=100 \
        H2O_TPU_BENCH_SIDECAR="$sidecar" \
        python bench.py > /dev/null
    gate_rc=$?
    if [ "$gate_rc" -eq 0 ]; then
        python tools/bench_gate.py --run "$sidecar"
        gate_rc=$?
    fi
    rm -f "$sidecar"
fi

stress_rc=0
if [ "$sanitize_stress" -eq 1 ]; then
    echo "== sanitize stress (serving+train+sweep, all four arms armed) =="
    timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest -q \
        -p no:cacheprovider -p no:xdist -p no:randomly \
        "tests/test_sanitizer.py::TestStressSilence::test_serving_train_sweep_stress_stays_silent[locks,guards,transfers,recompiles]" \
        "tests/test_sanitizer.py::TestTransferSanitizer::test_live_h2d_guard_trips_typed_on_cpu_mesh" \
        "tests/test_sanitizer.py::TestTransferSanitizer::test_failpoint_drill_types_and_bundles" \
        "tests/test_sanitizer.py::TestRecompileSanitizer::test_serving_bucket_miss_raises_typed_and_bundles"
    stress_rc=$?
fi

health_rc=0
if [ "$health_gate" -eq 1 ]; then
    echo "== health gate (/3/Health ready -> wedged -> recovered) =="
    timeout -k 10 300 env JAX_PLATFORMS=cpu \
        H2O_TPU_WATCHDOG_MS=100 \
        python - <<'EOF'
import json
import time
import urllib.request

from h2o_tpu.api.server import H2OServer
from h2o_tpu.utils import failpoints

srv = H2OServer(port=54941).start()


def health():
    with urllib.request.urlopen(f"{srv.url}/3/Health", timeout=10) as r:
        return json.loads(r.read().decode())


h = health()
assert h["live"] and h["ready"], \
    f"expected ready on boot, degraded: {h['degraded']}"

# wedge: the registered watchdog.trip failpoint force-trips all four
# detectors on the next sweep — nothing is actually wrong, which is the
# point: the gate drills the SIGNAL path, not a real outage
failpoints.arm("watchdog.trip", "raise*4")
deadline = time.time() + 20
while time.time() < deadline:
    h = health()
    if not h["ready"]:
        break
    time.sleep(0.1)
assert not h["ready"], "health never degraded under the armed drill"
reasons = {d["reason"] for d in h["degraded"]}
assert "watchdog-trip" in reasons, f"wrong typed reasons: {reasons}"

# recover: disarm, trips age out after 10 sweep intervals (~1s here)
failpoints.disarm("watchdog.trip")
deadline = time.time() + 30
while time.time() < deadline:
    h = health()
    if h["ready"]:
        break
    time.sleep(0.2)
assert h["ready"], f"health never recovered after disarm: {h['degraded']}"
srv.stop()
print(json.dumps({"health_gate": "ok"}))
EOF
    health_rc=$?
fi

workload_rc=0
if [ "$workload_gate" -eq 1 ]; then
    echo "== workload gate (3-tenant stress, boundary kills, SLO held) =="
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
        H2O_TPU_WORKLOAD_SLOTS=2 \
        H2O_TPU_WORKLOAD_TICK_MS=100 \
        H2O_TPU_CHECKPOINT_SECS=0 \
        H2O_TPU_SANITIZE=recompiles \
        python - <<'EOF'
import json
import threading
import time
import urllib.request

import numpy as np

from h2o_tpu.api.server import H2OServer
from h2o_tpu.frame.frame import Frame
from h2o_tpu.frame.vec import T_CAT, Vec
from h2o_tpu.utils import failpoints

srv = H2OServer(port=54946).start()


def req(method, path, body=None, hdrs=None):
    r = urllib.request.Request(
        srv.url + path, method=method,
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json", **(hdrs or {})})
    with urllib.request.urlopen(r, timeout=60) as resp:
        return json.loads(resp.read().decode())


rng = np.random.default_rng(5)
n = 2000
x1 = rng.normal(size=n).astype(np.float32)
x2 = rng.normal(size=n).astype(np.float32)
y = ((x1 - 0.4 * x2 + rng.normal(scale=0.4, size=n)) > 0.1) \
    .astype(np.float32)
fr = Frame.from_dict({"x1": x1, "x2": x2})
fr.add("y", Vec.from_numpy(y, type=T_CAT, domain=["0", "1"]))
fid = str(fr.key)


def build(tenant, prio, rdir=None):
    body = {"training_frame": fid, "response_column": "y", "ntrees": 6,
            "max_depth": 3, "seed": 42, "score_tree_interval": 2}
    if rdir:
        body["auto_recovery_dir"] = rdir
    out = req("POST", "/3/ModelBuilders/gbm", body,
              {"X-H2O-TPU-Tenant": tenant, "X-H2O-TPU-Priority": prio})
    return out["job"]["key"]["name"]


def entry_of(job_key, timeout=30):
    deadline = time.time() + timeout
    while time.time() < deadline:
        for e in req("GET", "/3/Workload")["entries"]:
            if e["job"] == job_key:
                return e["id"]
        time.sleep(0.1)
    raise AssertionError(f"no scheduler entry for {job_key}")


def wait_entry_done(eid, timeout=240):
    deadline = time.time() + timeout
    while time.time() < deadline:
        ent = next(e for e in req("GET", "/3/Workload")["entries"]
                   if e["id"] == eid)
        if ent["state"] in ("DONE", "FAILED", "CANCELLED"):
            return ent
        time.sleep(0.2)
    raise AssertionError(f"entry {eid} never finished")


def wait_job(key, timeout=240):
    deadline = time.time() + timeout
    while time.time() < deadline:
        j = req("GET", f"/3/Jobs/{key}")["jobs"][0]
        if j["status"] in ("DONE", "FAILED", "CANCELLED"):
            return j
        time.sleep(0.2)
    raise AssertionError(f"job {key} never finished")


# -- phase 1: kill a managed build at EVERY chunk boundary over the wire.
# The REST job lands PREEMPTED; the scheduler entry must auto-resume and
# finish DONE with >= 1 preemption recorded — no operator action.
for k in (1, 2, 3):
    failpoints.reset()
    failpoints.arm("workload.preempt", f"raise(preempt)@{k}")
    key = build("drill", "batch", rdir=f"/tmp/h2o_tpu_wl_gate_k{k}")
    eid = entry_of(key)
    ent = wait_entry_done(eid)
    failpoints.reset()
    assert ent["state"] == "DONE", f"boundary-{k} kill not healed: {ent}"
    assert ent["preemptions"] >= 1, f"boundary-{k} never preempted: {ent}"
print(json.dumps({"boundary_kills": "ok", "boundaries": 3}))

# -- phase 2: 3-tenant mixed-priority stress with serving scores between
scorer_model = wait_job(build("serving", "interactive"))["dest"]["name"]
stop_scores = threading.Event()
score_errors = []


def score_loop():
    while not stop_scores.is_set():
        try:
            req("POST",
                f"/3/Predictions/models/{scorer_model}/frames/{fid}",
                body={})
        except Exception as e:  # noqa: BLE001
            score_errors.append(repr(e))
            return
        time.sleep(0.05)


scorer = threading.Thread(target=score_loop, daemon=True)
scorer.start()
keys = {t: build(t, p) for t, p in
        (("acme", "interactive"), ("beta", "batch"),
         ("gamma", "background"))}
jobs = {t: wait_job(k) for t, k in keys.items()}
stop_scores.set()
scorer.join(timeout=10)
assert not score_errors, f"serving failed mid-stress: {score_errors[0]}"
for t, j in jobs.items():
    assert j["status"] == "DONE", f"tenant {t} starved/failed: {j}"
    assert j["tenant"] == t, f"tenant stamp lost: {j}"

# the SLO/health plane held through the stress, and the sanitizer arms
# stayed silent: zero violations, zero steady-state recompiles
h = req("GET", "/3/Health")
assert h["live"] and h["ready"], f"health degraded: {h['degraded']}"
metrics = req("GET", "/3/Metrics")["metrics"]
for name in ("sanitizer.violation.count", "serving.recompile.count"):
    v = (metrics.get(name) or {}).get("value")
    assert not v, f"{name} = {v}"
snap = req("GET", "/3/Workload")
assert {"acme", "beta", "gamma"} <= set(snap["tenants"]), snap["tenants"]
prom = urllib.request.urlopen(
    srv.url + "/3/Metrics?format=prometheus", timeout=30).read().decode()
assert 'h2o_tpu_tenant_running_jobs{tenant="acme"}' in prom
srv.stop()
print(json.dumps({"workload_gate": "ok",
                  "tenants": sorted(keys),
                  "preempt_count": metrics["workload.preempt.count"]
                  ["value"]}))
EOF
    workload_rc=$?
fi

echo "== gate: lint rc=${lint_rc}, tests rc=${test_rc}, bench rc=${bench_rc}, bench-gate rc=${gate_rc}, sanitize-stress rc=${stress_rc}, health rc=${health_rc}, workload rc=${workload_rc} =="
if [ "$lint_rc" -ne 0 ] || [ "$test_rc" -ne 0 ] || [ "$bench_rc" -ne 0 ] || [ "$gate_rc" -ne 0 ] || [ "$stress_rc" -ne 0 ] || [ "$health_rc" -ne 0 ] || [ "$workload_rc" -ne 0 ]; then
    exit 1
fi
exit 0
