"""Multi-tenant workload manager drills (the ISSUE 19 acceptance pins).

- the `workload.preempt` failpoint kills a GBM build at EVERY chunk
  boundary; `resume_training` replays to a forest and predictions
  BIT-equal to the uninterrupted run;
- managed mode (slots > 0) parks a preempted job and auto-resumes it to
  the same bit-equal model without operator action;
- tenant quotas debit the ONE reservation ledger: an over-quota tenant
  gets the typed WorkloadAdmissionError (REST: 429 + Retry-After) while
  another tenant's submissions are untouched;
- the fair-share lottery replays the SAME dispatch order under the same
  seed, and aging bounds starvation: a background job behind a stream of
  interactive arrivals still dispatches within the aging bound;
- the shed policy picks the highest-pressure-per-weight tenant's weakest
  job on memory/serving pressure, and REQUEUES (not pages) jobs the
  watchdog flags;
- the MRTask FairGate wakes the lowest-virtual-time tenant first;
- `/3/Workload` + per-tenant Prometheus series round-trip over a live
  server.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import h2o_tpu
from h2o_tpu import workload
from h2o_tpu.backend import memory
from h2o_tpu.backend.jobs import Job
from h2o_tpu.frame.frame import Frame
from h2o_tpu.frame.vec import T_CAT, Vec
from h2o_tpu.models.gbm import GBM, GBMParameters
from h2o_tpu.utils import failpoints as fp
from h2o_tpu.workload import fairshare, tenants
from h2o_tpu.workload.manager import _reset_for_tests as _reset_workload

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _workload_hygiene(monkeypatch):
    monkeypatch.delenv("H2O_TPU_FAILPOINTS", raising=False)
    monkeypatch.setenv("H2O_TPU_CHECKPOINT_SECS", "0")  # every boundary
    for k in ("H2O_TPU_WORKLOAD_SLOTS", "H2O_TPU_WORKLOAD_QUOTA",
              "H2O_TPU_HBM_LIMIT_BYTES", "H2O_TPU_TENANT",
              "H2O_TPU_WORKLOAD_DISPATCH_SLOTS"):
        monkeypatch.delenv(k, raising=False)
    fp.reset()
    _reset_workload()
    yield
    fp.reset()
    _reset_workload()


_RNG = np.random.default_rng(11)
_N = 300
_COLS = {
    "x1": _RNG.normal(size=_N).astype(np.float32),
    "x2": _RNG.normal(size=_N).astype(np.float32),
}
_Y = ((_COLS["x1"] - 0.5 * _COLS["x2"]
       + _RNG.normal(scale=0.3, size=_N)) > 0.1).astype(np.float32)


def _frame():
    fr = Frame.from_dict({"x1": _COLS["x1"], "x2": _COLS["x2"]})
    fr.add("y", Vec.from_numpy(_Y, type=T_CAT, domain=["0", "1"]))
    return fr


def _params(**kw):
    base = dict(training_frame=_frame(), response_column="y", ntrees=6,
                max_depth=3, score_tree_interval=2, seed=42)
    base.update(kw)
    return GBMParameters(**base)


def _forest_equal(a, b) -> bool:
    if set(a.forest) != set(b.forest):
        return False
    return all(np.array_equal(np.asarray(a.forest[k]), np.asarray(b.forest[k]))
               for k in a.forest)


def _wait(pred, timeout=90.0, every=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(every)
    return False


# ---------------------------------------------------------------------------
# chunk-boundary preemption: kill at EVERY boundary, resume bit-equal
# ---------------------------------------------------------------------------
def test_preempt_failpoint_every_boundary_resume_bit_parity(tmp_path):
    base = GBM(_params()).train_model()
    base_pred = np.asarray(base.predict(_frame()).vec(2).data)
    n_chunks = 3  # ntrees=6 / interval=2
    for k in range(1, n_chunks + 1):
        rdir = str(tmp_path / f"wl_k{k}")
        fp.reset()
        fp.arm("workload.preempt", f"raise(preempt)@{k}")
        gbm = GBM(_params(auto_recovery_dir=rdir))
        # unmanaged preemption is NOT an error: join() returns None and
        # the job lands PREEMPTED with the checkpoint dir on it
        assert gbm.train_model() is None
        assert gbm.job.status == Job.PREEMPTED
        assert gbm.job.preempt_dir
        fp.reset()
        # the scheduler parked the entry with the same recovery dir
        parked = [e for e in workload.snapshot()["entries"]
                  if e["state"] == "PARKED"]
        assert parked and parked[-1]["recovery_dir"] == gbm.job.preempt_dir
        m = h2o_tpu.resume_training(gbm.job.preempt_dir)
        assert m.ntrees == 6
        assert _forest_equal(m, base), f"forest diverged at boundary {k}"
        assert np.array_equal(
            np.asarray(m.predict(_frame()).vec(2).data), base_pred), \
            f"predictions diverged at boundary {k}"


def test_preempt_without_recovery_armed_never_fires(tmp_path):
    """A job that never armed recovery is not preemptible — the boundary
    hook must ignore both the flag and the failpoint (work is never
    discarded without a checkpoint to resume from)."""
    fp.arm("workload.preempt", "raise(preempt)@1")
    m = GBM(_params()).train_model()  # no auto_recovery_dir
    assert m is not None and m.ntrees == 6


def test_managed_preempt_auto_resume_bit_parity(tmp_path, monkeypatch):
    base = GBM(_params()).train_model()
    base_pred = np.asarray(base.predict(_frame()).vec(2).data)
    _reset_workload()

    monkeypatch.setenv("H2O_TPU_WORKLOAD_SLOTS", "1")
    monkeypatch.setenv("H2O_TPU_WORKLOAD_TICK_MS", "100")
    rdir = str(tmp_path / "managed")
    fp.arm("workload.preempt", "raise(preempt)@1")
    gbm = GBM(_params(auto_recovery_dir=rdir))
    gbm.train(background=True)

    # parked at the first boundary, then auto-resumed by the maintenance
    # thread — no operator resume_training call
    m = workload.manager()
    assert _wait(lambda: any(e.id == 1 and e.job is not None
                             and e.job.status == Job.DONE
                             for e in list(m._done)))
    entry = next(e for e in list(m._done) if e.id == 1)
    assert entry.preempt_count >= 1
    snap = workload.snapshot()
    assert snap["counters"]["preempt"] >= 1
    assert snap["counters"]["resume"] >= 1
    assert tenants.get("default").preemptions >= 1

    from h2o_tpu.backend.kvstore import STORE
    resumed = STORE.get(str(entry.job.dest_key))
    assert resumed is not None and resumed.ntrees == 6
    assert _forest_equal(resumed, base)
    assert np.array_equal(
        np.asarray(resumed.predict(_frame()).vec(2).data), base_pred)


# ---------------------------------------------------------------------------
# quota admission through the one reservation ledger
# ---------------------------------------------------------------------------
def test_quota_isolation_between_tenants(monkeypatch):
    monkeypatch.setenv("H2O_TPU_HBM_LIMIT_BYTES", str(1 << 30))
    # alice: ~1 KB quota (under any real frame); bob: half the budget
    monkeypatch.setenv("H2O_TPU_WORKLOAD_QUOTA",
                       "alice=0.000001,bob=0.5")
    with pytest.raises(workload.WorkloadAdmissionError) as ei:
        workload.submit(Job("alice build"), lambda: None,
                        tenant="alice", cost_bytes=4800)
    e = ei.value
    assert e.tenant == "alice"
    assert e.cost_bytes == 4800
    assert e.quota_bytes < 4800
    assert e.retry_after_s > 0
    snap = workload.snapshot()
    assert snap["tenants"]["alice"]["rejected"] == 1
    assert snap["counters"]["rejected"] == 1

    # bob is untouched by alice's rejection: trains through the manager,
    # holds a ledger reservation while running, releases it after
    with tenants.request_scope("bob"):
        m = GBM(_params()).train_model()
    assert m is not None and m.ntrees == 6
    assert memory.reserved_bytes() == 0  # released on finish
    snap = workload.snapshot()
    assert snap["tenants"]["bob"]["rejected"] == 0
    done = [e for e in snap["entries"] if e["tenant"] == "bob"]
    assert done and done[0]["state"] == Job.DONE


def test_unlimited_tenant_never_reserves(monkeypatch):
    monkeypatch.setenv("H2O_TPU_HBM_LIMIT_BYTES", str(1 << 30))
    workload.submit(Job("free"), lambda: None, cost_bytes=10 ** 9)
    assert memory.reserved_bytes() == 0  # no quota -> admission open


# ---------------------------------------------------------------------------
# fair-share dispatch: determinism under a seed, starvation bound
# ---------------------------------------------------------------------------
def _drain_order(monkeypatch, seed):
    """Hold the single slot, queue 8 entries across two weighted tenants,
    release, and return the tenant dispatch order."""
    _reset_workload()
    monkeypatch.setenv("H2O_TPU_WORKLOAD_SEED", str(seed))
    monkeypatch.setenv("H2O_TPU_WORKLOAD_SLOTS", "1")
    monkeypatch.setenv("H2O_TPU_WORKLOAD_TICK_MS", "100")
    tenants.configure("a", weight=3.0)
    tenants.configure("b", weight=1.0)
    hold = threading.Event()
    holder = Job("hold")
    workload.submit(holder, lambda: hold.wait(30), tenant="a")
    order: list[str] = []
    jobs = []
    for i in range(8):
        name = "a" if i % 2 == 0 else "b"
        j = Job(f"{name}{i}")

        def mk(n):
            return lambda: order.append(n)

        workload.submit(j, mk(name), tenant=name)
        jobs.append(j)
    # one scheduler entry per submission on the fresh manager (telemetry
    # counters are process-global — entries are the per-run accounting)
    assert len(workload.snapshot()["entries"]) == 9
    hold.set()
    assert _wait(lambda: all(j.status == Job.DONE for j in jobs),
                 timeout=30)
    return order


def test_fair_share_dispatch_deterministic_under_seed(monkeypatch):
    first = _drain_order(monkeypatch, seed=1234)
    second = _drain_order(monkeypatch, seed=1234)
    assert len(first) == 8
    assert first == second  # same seed + same submissions -> same order


def test_background_job_dispatches_within_aging_bound(monkeypatch):
    """Interactive lane always beats background in the lottery — only
    aging dispatches the background entry. With aging=2 it must win the
    third drawing, ahead of the remaining interactive stream."""
    monkeypatch.setenv("H2O_TPU_WORKLOAD_SLOTS", "1")
    monkeypatch.setenv("H2O_TPU_WORKLOAD_TICK_MS", "100")
    monkeypatch.setenv("H2O_TPU_WORKLOAD_AGING", "2")
    hold = threading.Event()
    workload.submit(Job("hold"), lambda: hold.wait(30))
    order: list[str] = []
    jobs = []

    def mk(n):
        return lambda: order.append(n)

    bg = Job("bg")
    workload.submit(bg, mk("bg"), priority="background")
    jobs.append(bg)
    for i in range(4):
        j = Job(f"i{i}")
        workload.submit(j, mk(f"i{i}"), priority="interactive")
        jobs.append(j)
    hold.set()
    assert _wait(lambda: all(j.status == Job.DONE for j in jobs),
                 timeout=30)
    assert len(order) == 5
    assert order.index("bg") == 2  # 2 lottery losses, then force-dispatch


def test_stronger_arrival_requests_preemption_of_weaker_running(monkeypatch):
    monkeypatch.setenv("H2O_TPU_WORKLOAD_SLOTS", "1")
    monkeypatch.setenv("H2O_TPU_WORKLOAD_TICK_MS", "100")
    release = threading.Event()
    weak = Job("weak batch")
    workload.submit(weak, lambda: release.wait(30), priority="batch")
    weak.preemptible = True  # stands in for an armed recovery
    strong = Job("interactive arrival")
    workload.submit(strong, lambda: None, priority="interactive")
    assert weak.preempt_requested  # asked to yield at its next boundary
    release.set()
    assert _wait(lambda: strong.status == Job.DONE, timeout=30)


# ---------------------------------------------------------------------------
# shed policy: health-driven victim selection, watchdog requeue
# ---------------------------------------------------------------------------
def _running_job(tenant, priority, release, cost=0):
    j = Job(f"{tenant} {priority}")
    workload.submit(j, lambda: release.wait(30), tenant=tenant,
                    priority=priority, cost_bytes=cost)
    j.preemptible = True
    return j


def test_shed_check_picks_highest_pressure_tenant(monkeypatch):
    release = threading.Event()
    tenants.configure("hog", weight=1.0)
    tenants.configure("vip", weight=4.0)
    j1 = _running_job("hog", "batch", release)
    j2 = _running_job("hog", "background", release)
    j3 = _running_job("vip", "batch", release)
    snap = {"degraded": [{"check": "serving",
                          "reason": "serving-queue-saturation"}],
            "slo": {}}
    decisions = workload.manager().shed_check(snap)
    # hog holds 2 slots per unit weight vs vip's 0.25 — hog sheds, and
    # its WEAKEST lane (background) is the victim
    assert decisions == ["shed:hog:wl-2"]
    assert j2.preempt_requested
    assert not j1.preempt_requested and not j3.preempt_requested
    release.set()


def test_shed_check_burn_threshold_triggers(monkeypatch):
    monkeypatch.setenv("H2O_TPU_WORKLOAD_SHED_BURN", "10")
    release = threading.Event()
    j = _running_job("solo", "batch", release)
    decisions = workload.manager().shed_check(
        {"degraded": [], "slo": {"serving.score": {"burn": 99.0}}})
    assert decisions == ["shed:solo:wl-1"]
    assert j.preempt_requested
    release.set()


def test_shed_check_requeues_watchdog_flagged_job(monkeypatch):
    release = threading.Event()
    j = _running_job("acme", "batch", release)
    snap = {"degraded": [{"check": "jobs", "reason": "job-heartbeat",
                          "jobs": [{"subject": str(j.key)}]}],
            "slo": {}}
    decisions = workload.manager().shed_check(snap)
    assert decisions == ["requeue:acme:wl-1"]
    assert j.preempt_requested  # requeued at its next boundary, not paged
    release.set()


def test_serving_pressure_preempts_weakest(monkeypatch):
    release = threading.Event()
    j1 = _running_job("a", "interactive", release)
    j2 = _running_job("b", "background", release)
    assert workload.note_serving_pressure()
    assert j2.preempt_requested and not j1.preempt_requested
    release.set()


def test_healthy_snapshot_sheds_nothing():
    release = threading.Event()
    _running_job("a", "batch", release)
    assert workload.manager().shed_check({"degraded": [], "slo": {}}) == []
    release.set()


# ---------------------------------------------------------------------------
# the MRTask FairGate: lowest virtual time wakes first
# ---------------------------------------------------------------------------
def test_fairgate_weighted_wakeup_order():
    gate = fairshare.FairGate()
    # pre-load one grant each: heavy's vtime 1/10, light's 1/1
    gate.acquire("heavy", 1, 10.0)
    gate.release()
    gate.acquire("light", 1, 1.0)
    gate.release()
    gate.acquire("holder", 1, 1.0)
    order: list[str] = []

    def contend(name, weight):
        gate.acquire(name, 1, weight)
        order.append(name)
        gate.release()

    # light enqueues FIRST — FIFO alone would wake it first; the lower
    # virtual time must win instead
    tl = threading.Thread(target=contend, args=("light", 1.0))
    tl.start()
    assert _wait(lambda: len(gate._waiters) == 1, timeout=5)
    th = threading.Thread(target=contend, args=("heavy", 10.0))
    th.start()
    assert _wait(lambda: len(gate._waiters) == 2, timeout=5)
    gate.release()
    tl.join(timeout=5)
    th.join(timeout=5)
    assert order == ["heavy", "light"]
    assert gate.grants() == {"heavy": 2, "light": 2, "holder": 1}


def test_draw_is_deterministic_and_uniform_ish():
    seq = [fairshare.draw(42, i) for i in range(1000)]
    assert seq == [fairshare.draw(42, i) for i in range(1000)]
    assert all(0.0 <= x < 1.0 for x in seq)
    assert abs(sum(seq) / len(seq) - 0.5) < 0.05
    assert seq[:10] != [fairshare.draw(43, i) for i in range(10)]


# ---------------------------------------------------------------------------
# priority-laned grid dispatch (satellite a)
# ---------------------------------------------------------------------------
def test_grid_runs_under_its_priority_lane():
    from h2o_tpu.models.grid import GridSearch

    gs = GridSearch(GBM, _params(ntrees=2), {"max_depth": [2, 3]},
                    priority="interactive")
    grid = gs.train()
    assert len(grid.models) == 2
    ents = workload.snapshot()["entries"]
    mine = [e for e in ents if e["priority"] == "interactive"]
    # ONE scheduler entry for the whole search — candidates ran nested
    # inside its slot, not as anonymous top-level submissions
    assert len(mine) == 1 and mine[0]["state"] == Job.DONE
    assert len(ents) == 1


# ---------------------------------------------------------------------------
# REST surface: /3/Workload, 429 + Retry-After, per-tenant Prometheus
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def srv(worker_port):
    from h2o_tpu.api.server import H2OServer

    s = H2OServer(port=worker_port(54944), name="workload-rest").start()
    yield s
    s.stop()


def _req(srv, method, path, body=None, hdrs=None):
    r = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}{path}", method=method,
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json", **(hdrs or {})})
    try:
        with urllib.request.urlopen(r) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def test_rest_workload_snapshot_and_configure(srv):
    status, snap, _ = _req(srv, "GET", "/3/Workload")
    assert status == 200
    assert snap["priorities"] == list(Job.PRIORITIES)
    status, snap, _ = _req(srv, "POST", "/3/Workload",
                           {"tenant": "acme", "weight": 2.5,
                            "quota_fraction": 0.25})
    assert status == 200
    assert snap["tenants"]["acme"]["weight"] == 2.5
    assert snap["tenants"]["acme"]["quota_fraction"] == 0.25
    status, err, _ = _req(srv, "POST", "/3/Workload", {})
    assert status == 400
    status, err, _ = _req(srv, "POST", "/3/Workload",
                          {"tenant": "acme", "weight": -1})
    assert status == 400


def test_rest_over_quota_build_is_429_with_retry_after(srv, monkeypatch):
    monkeypatch.setenv("H2O_TPU_HBM_LIMIT_BYTES", str(1 << 30))
    monkeypatch.setenv("H2O_TPU_WORKLOAD_QUOTA", "starved=0.000001")
    fr = _frame()
    status, payload, hdrs = _req(
        srv, "POST", "/3/ModelBuilders/gbm",
        {"training_frame": str(fr.key), "response_column": "y",
         "ntrees": 2, "seed": 1},
        hdrs={"X-H2O-TPU-Tenant": "starved"})
    assert status == 429
    assert payload["error_type"] == "quota_rejected"
    assert payload["tenant"] == "starved"
    assert int(hdrs["Retry-After"]) >= 1
    # the same build WITHOUT the starved tenant header sails through
    status, job, _ = _req(
        srv, "POST", "/3/ModelBuilders/gbm",
        {"training_frame": str(fr.key), "response_column": "y",
         "ntrees": 2, "seed": 1})
    assert status == 200
    key = job["job"]["key"]["name"] if "job" in job else None
    assert _wait(lambda: _req(srv, "GET", f"/3/Jobs/{key}")[1]
                 ["jobs"][0]["status"] == Job.DONE, timeout=60)


def test_rest_job_schema_carries_tenant_and_priority(srv):
    with tenants.request_scope("acme", "interactive"):
        m = GBM(_params(ntrees=2)).train_model()
    assert m is not None
    status, payload, _ = _req(srv, "GET", "/3/Jobs")
    assert status == 200
    mine = [j for j in payload["jobs"] if j.get("tenant") == "acme"]
    assert mine and mine[-1]["priority"] == "interactive"


def test_per_tenant_prometheus_series(srv):
    with tenants.request_scope("prom-t"):
        workload.submit(Job("noop"), lambda: None)
    status, _, _ = _req(srv, "GET", "/3/Workload")
    assert status == 200
    r = urllib.request.urlopen(
        f"http://127.0.0.1:{srv.port}/3/Metrics?format=prometheus")
    text = r.read().decode()
    assert 'h2o_tpu_tenant_running_jobs{tenant="prom-t"}' in text
    assert 'h2o_tpu_tenant_preemptions_total{tenant="prom-t"} 0' in text
    assert "h2o_tpu_workload_dispatch_count" in text
