"""Causal observability (PR 15): cross-process/thread trace propagation
(traceparent on the wire, carry_context at thread boundaries), the SLO
registry + burn windows, GET /3/Health typed degradation, the watchdog
supervisor's four detectors + drill failpoint, tail-based slow-request
capture behind GET /3/SlowTraces, the /3/Timeline incremental cursor,
and the <2% overhead bound re-asserted with everything armed."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import h2o_tpu.utils.failpoints as fp
from h2o_tpu.frame.frame import Frame
from h2o_tpu.frame.vec import T_CAT, Vec
from h2o_tpu.utils import (fleetobs, health, slo, slowtrace, telemetry,
                           timeline, watchdog)

pytestmark = pytest.mark.causal

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolate():
    yield
    fp.reset()
    slo.reset()
    slowtrace.clear()
    watchdog.stop()


def _small_frame(n=400, seed=0):
    rng = np.random.default_rng(seed)
    fr = Frame.from_dict({"a": rng.normal(size=n).astype(np.float32),
                          "b": rng.normal(size=n).astype(np.float32),
                          "c": rng.normal(size=n).astype(np.float32)})
    y = (fr.vec("a").to_numpy() > 0).astype(np.float32)
    fr.add("y", Vec.from_numpy(y, type=T_CAT, domain=["n", "p"]))
    return fr


def _train_gbm(fr, ntrees=3, interval=2):
    from h2o_tpu.models.gbm import GBM, GBMParameters

    return GBM(GBMParameters(training_frame=fr, response_column="y",
                             ntrees=ntrees, max_depth=3, seed=1,
                             score_tree_interval=interval)).train_model()


# ---------------------------------------------------------------------------
# traceparent mint / parse / adopt
# ---------------------------------------------------------------------------
class TestTraceparent:
    def test_mint_parse_roundtrip(self):
        assert telemetry.current_traceparent() is None
        with telemetry.span("tp.root") as sp:
            tp = telemetry.current_traceparent()
            trace, parent = telemetry._traceparent_parse(tp)
            assert trace == sp.trace_id
            assert int(parent, 16) == sp.span_id
        assert telemetry.current_traceparent() is None

    @pytest.mark.parametrize("bad", [
        None, "", "garbage", "00-short-span-01",
        "00-" + "0" * 32 + "-" + "1" * 16 + "-01",   # all-zero trace
        "ff-" + "a" * 32 + "-" + "1" * 16 + "-01",   # forbidden version
    ])
    def test_malformed_header_degrades_to_fresh_trace(self, bad):
        assert telemetry._traceparent_parse(bad) is None
        with telemetry.remote_context(bad):
            with telemetry.span("fresh.root") as sp:
                # a fresh 32-hex trace id, not an adoption
                assert len(sp.trace_id) == 32
                assert sp.parent_id is None

    def test_remote_context_adopts_trace_and_parent(self):
        with telemetry.span("client.op") as client_sp:
            tp = telemetry.current_traceparent()
        with telemetry.remote_context(tp):
            with telemetry.span("server.op") as srv_sp:
                assert srv_sp.trace_id == client_sp.trace_id
                assert srv_sp.parent_id == f"{client_sp.span_id:016x}"

    def test_trace_ids_are_w3c_shaped(self):
        with telemetry.span("shape.check") as sp:
            assert len(sp.trace_id) == 32
            assert set(sp.trace_id) <= set("0123456789abcdef")


# ---------------------------------------------------------------------------
# carry_context — the thread-boundary satellite, each adoption site pinned
# ---------------------------------------------------------------------------
class TestCarryContext:
    def test_plain_thread_orphans_without_carry(self):
        """The hole the helper closes: an unwrapped thread target mints a
        fresh trace id."""
        seen = []

        def work():
            with telemetry.span("orphan.op") as sp:
                seen.append(sp.trace_id)

        with telemetry.span("parent.op") as sp:
            t = threading.Thread(target=work)
            t.start()
            t.join()
            assert seen[0] != sp.trace_id

    def test_carry_context_propagates_trace(self):
        seen = []

        def work():
            with telemetry.span("carried.op") as sp:
                seen.append((sp.trace_id, sp.parent_id))

        with telemetry.span("parent.op") as sp:
            t = threading.Thread(target=telemetry.carry_context(work))
            t.start()
            t.join()
            assert seen[0] == (sp.trace_id, sp.span_id)

    def test_job_start_carries_request_context(self):
        """Job.start (backend/jobs.py): the background worker's spans
        share the submitting (REST handler) thread's trace id."""
        from h2o_tpu.backend.jobs import Job

        seen = []

        def build():
            with telemetry.span("job.work") as sp:
                seen.append((sp.trace_id, sp.parent_id))
            return 42

        with telemetry.span("rest.fake") as sp:
            job = Job(description="carry test").start(build)
            assert job.join(timeout=10) == 42
            assert seen[0][0] == sp.trace_id
            assert seen[0][1] == sp.span_id

    def test_microbatcher_worker_carries_creation_context(self):
        """MicroBatcher (serving/batcher.py): the batch worker adopts the
        registering thread's context — device-call-side spans carry the
        registration trace id instead of orphaning."""
        from h2o_tpu.serving.batcher import MicroBatcher
        from h2o_tpu.serving.stats import ServingStats

        seen = []

        def score(X):
            seen.append(telemetry.trace_id())
            return X * 2.0

        with telemetry.span("registration.op") as sp:
            b = MicroBatcher("carry_m", score, ServingStats(),
                             max_batch=8, max_wait_us=0, queue_depth=8)
        try:
            out = b.submit(np.ones((2, 3), np.float32), deadline_s=5.0)
            assert out.shape == (2, 3)
            assert seen[0] == sp.trace_id
        finally:
            b.stop()

    def test_shadow_worker_carries_each_requests_context(self):
        """Router shadow scorer (serving/router.py): the context is
        carried PER JOB — the long-lived worker must attribute every
        shadow score to ITS enqueuing request's trace, not pin the first
        request's context forever. Shadow scoring also bypasses the SLO
        boundary (slo=False) — droppable work must not burn the budget."""
        from h2o_tpu.serving.router import Router

        shadow_calls = []

        class _Stub:
            def model(self, mid):
                return object()

            def score(self, mid, rows, deadline_ms=None, slo=True):
                if mid == "shadow_m":
                    shadow_calls.append((telemetry.trace_id(), slo))
                return [{"value": 1.0} for _ in rows]

        router = Router(_Stub())
        try:
            router.create_route("ep", [
                {"model_id": "prim_m", "weight": 1.0},
                {"model_id": "shadow_m", "shadow": True}])
            with telemetry.span("request.one") as sp1:
                router.score("ep", [{"a": 1.0}])
            assert router.drain_shadow(timeout_s=10.0)
            with telemetry.span("request.two") as sp2:
                router.score("ep", [{"a": 2.0}])
            assert router.drain_shadow(timeout_s=10.0)
            assert [t for t, _ in shadow_calls] == \
                [sp1.trace_id, sp2.trace_id]
            assert all(s is False for _, s in shadow_calls)
        finally:
            router.shutdown()

    def test_fleet_scrape_pool_carries_context(self, monkeypatch):
        """fleetobs collector pool: executor-submitted scrapes run under
        the collecting caller's trace."""
        seen = []
        real = fleetobs._scrape_one

        def probe(url, timeout_s):
            seen.append(telemetry.trace_id())
            return real(url, 0.05)

        monkeypatch.setattr(fleetobs, "_scrape_one", probe)
        monkeypatch.setenv("H2O_TPU_FLEET_PEERS", "127.0.0.1:9")
        fleetobs.invalidate_cache()
        with telemetry.span("collect.op") as sp:
            view = fleetobs.collect(force=True)
        assert seen and seen[0] == sp.trace_id
        assert view["live"] >= 1
        fleetobs.invalidate_cache()

    def test_nested_capture_root_folds_into_outer_sink(self):
        """A nested capture root (serving.score inside a rest.request
        capture) must not sever the enclosing tree: the inner subtree
        folds back into the outer sink at inner-root exit."""
        outer = telemetry.SpanSink()
        inner = telemetry.SpanSink()
        with telemetry.span("outer.req", sink=outer):
            with telemetry.span("inner.req", sink=inner):
                with telemetry.span("inner.child"):
                    pass
        assert [r["name"] for r in inner.items] == \
            ["inner.child", "inner.req"]
        assert [r["name"] for r in outer.items] == \
            ["inner.child", "inner.req", "outer.req"]

    def test_sink_collects_across_carried_thread(self):
        """Span sinks survive the thread hop: a carried worker's spans
        land in the request's tree."""
        sink = telemetry.SpanSink()
        with telemetry.span("tree.root", sink=sink):
            def work():
                with telemetry.span("tree.worker"):
                    pass
            t = threading.Thread(target=telemetry.carry_context(work))
            t.start()
            t.join()
        names = [r["name"] for r in sink.items]
        assert names == ["tree.worker", "tree.root"]
        assert sink.closed


# ---------------------------------------------------------------------------
# SLO registry + burn
# ---------------------------------------------------------------------------
class TestSLO:
    def test_undeclared_slo_raises_typed(self):
        with pytest.raises(KeyError, match="undeclared SLO"):
            slo.objective("no.such.slo")
        with pytest.raises(KeyError, match="undeclared SLO"):
            slo.note("no.such.slo", 0.1)

    def test_declared_defaults_present(self):
        assert slo.objective("rest.request").p99_ms > 0
        assert slo.objective("serving.score").error_budget > 0

    def test_env_override_retunes_objective(self, monkeypatch):
        monkeypatch.setenv(
            "H2O_TPU_SLO",
            "serving.score.p99_ms=42,serving.score.error_budget=0.5")
        s = slo.objective("serving.score")
        assert s.p99_ms == 42.0 and s.error_budget == 0.5
        # other SLOs untouched
        assert slo.objective("rest.request").p99_ms == 2500.0

    def test_bad_override_raises_loudly(self, monkeypatch):
        monkeypatch.setenv("H2O_TPU_SLO", "rest.request.nonsense=1")
        with pytest.raises(ValueError, match="bad H2O_TPU_SLO entry"):
            slo.objective("rest.request")
        monkeypatch.setenv("H2O_TPU_SLO", "no.such.slo.p99_ms=1")
        with pytest.raises(KeyError, match="undeclared SLO"):
            slo.objective("rest.request")

    def test_error_burn_from_window(self):
        slo.declare("test.errors", "test objective", p99_ms=1000,
                    error_budget=0.1)
        for i in range(20):
            slo.note("test.errors", 0.001, error=(i % 2 == 0))
        snap = slo.burn_snapshot()
        rec = snap["test.errors"]
        assert rec["errors"]["window"] == 20
        assert rec["errors"]["error_fraction"] == 0.5
        assert rec["errors"]["burn"] == pytest.approx(5.0)
        assert rec["burn"] >= 5.0
        assert telemetry.value("slo.worst_burn") >= 5.0
        del slo.SLOS["test.errors"]

    def test_latency_burn_prefers_note_window_over_hist(self):
        """The note window holds exactly the SLO-relevant requests — it
        wins over the raw telemetry ring, so monitor-poll samples in the
        shared hist cannot dilute a real breach."""
        slo.declare("test.latency", "test objective", p99_ms=100,
                    error_budget=0.1, hist="serving.request.seconds")
        try:
            # the shared ring full of fast "poll" samples...
            for _ in range(50):
                telemetry.observe("serving.request.seconds", 0.001)
            # ...while every SLO-relevant request breaches
            for _ in range(10):
                slo.note("test.latency", 0.5)
            rec = slo.burn_snapshot()["test.latency"]
            assert rec["latency"]["source"] == "window"
            assert rec["latency"]["breach_fraction"] == 1.0
            assert rec["latency"]["burn"] >= 100.0
        finally:
            del slo.SLOS["test.latency"]
            telemetry._HISTS["serving.request.seconds"].ring.clear()

    def test_latency_burn_falls_back_to_hist_ring(self):
        """With an empty note window, an SLO that declares a backing
        histogram reads the EXISTING telemetry ring."""
        slo.declare("test.latfall", "test objective", p99_ms=100,
                    error_budget=0.1, hist="serving.request.seconds")
        try:
            for _ in range(10):
                telemetry.observe("serving.request.seconds", 0.5)  # 500ms
            rec = slo.burn_snapshot()["test.latfall"]
            assert rec["latency"]["source"] == "serving.request.seconds"
            assert rec["latency"]["breach_fraction"] > 0
            assert rec["latency"]["burn"] >= 1.0
        finally:
            del slo.SLOS["test.latfall"]
            # drop the seeded observations — the shared serving ring also
            # backs the REAL serving.score SLO, and 500ms fakes would
            # read as a latency burn to every later health check
            telemetry._HISTS["serving.request.seconds"].ring.clear()

    def test_declare_rejects_undeclared_hist(self):
        with pytest.raises(KeyError):
            slo.declare("test.bad", "x", p99_ms=1, error_budget=0.1,
                        hist="no.such.metric")


# ---------------------------------------------------------------------------
# tail-based slow-request capture
# ---------------------------------------------------------------------------
class TestSlowTrace:
    def test_breaching_request_persists_full_tree(self):
        slo.declare("test.slow", "test objective", p99_ms=5,
                    error_budget=0.1)
        with slowtrace.request("test.slow", "GET /test", endpoint="test"):
            with telemetry.span("test.slow.child"):
                time.sleep(0.03)
        traces = slowtrace.snapshot()
        assert len(traces) == 1
        rec = traces[0]
        assert rec["slo"] == "test.slow" and rec["what"] == "GET /test"
        assert rec["dur_ms"] > 5 and rec["p99_target_ms"] == 5
        assert rec["error"] is False
        names = [s["name"] for s in rec["spans"]]
        assert names == ["test.slow.child", "test.slow"]
        # the whole tree shares one trace id
        assert {s["trace"] for s in rec["spans"]} == {rec["trace"]}
        assert telemetry.value("slowtrace.captured.count") >= 1
        del slo.SLOS["test.slow"]

    def test_fast_request_not_captured(self):
        slo.declare("test.fast", "test objective", p99_ms=10_000,
                    error_budget=0.1)
        with slowtrace.request("test.fast", "GET /fast"):
            pass
        assert slowtrace.snapshot() == []
        del slo.SLOS["test.fast"]

    def test_exception_counts_as_error_and_propagates(self):
        slo.declare("test.err", "test objective", p99_ms=0.0001,
                    error_budget=0.5)
        with pytest.raises(RuntimeError, match="boom"):
            with slowtrace.request("test.err", "GET /err"):
                raise RuntimeError("boom")
        (rec,) = slowtrace.snapshot()
        assert rec["error"] is True
        snap = slo.burn_snapshot()
        assert snap["test.err"]["errors"]["error_fraction"] == 1.0
        del slo.SLOS["test.err"]

    def test_ring_bounded_by_keep_knob(self, monkeypatch):
        monkeypatch.setenv("H2O_TPU_SLOWTRACE_KEEP", "2")
        slo.declare("test.ring", "test objective", p99_ms=0.0001,
                    error_budget=0.1)
        total0 = slowtrace.total_captured()     # monotone across clears
        for i in range(3):
            with slowtrace.request("test.ring", f"GET /r{i}"):
                pass
        traces = slowtrace.snapshot()
        assert len(traces) == 2
        assert [t["what"] for t in traces] == ["GET /r1", "GET /r2"]
        assert slowtrace.total_captured() - total0 == 3
        del slo.SLOS["test.ring"]

    def test_min_ms_floor_suppresses_tight_slo(self, monkeypatch):
        monkeypatch.setenv("H2O_TPU_SLOWTRACE_MIN_MS", "60000")
        slo.declare("test.floor", "test objective", p99_ms=0.0001,
                    error_budget=0.1)
        with slowtrace.request("test.floor", "GET /floor"):
            pass
        assert slowtrace.snapshot() == []
        del slo.SLOS["test.floor"]

    def test_serving_score_path_feeds_slo_and_capture(self, monkeypatch):
        """The serving.score SLO boundary (runtime.score_rows): a scored
        request lands in the SLO window, and under a tight override its
        span tree persists with the model id as the subject."""
        from h2o_tpu.models.glm import GLM, GLMParameters
        from h2o_tpu.serving.runtime import ServingRuntime

        rng = np.random.default_rng(5)
        fr = Frame.from_dict(
            {"a": rng.normal(size=200).astype(np.float32),
             "z": rng.normal(size=200).astype(np.float32)})
        m = GLM(GLMParameters(training_frame=fr, response_column="z",
                              family="gaussian")).train_model()
        rt = ServingRuntime()
        try:
            rt.register_model(m, "slo_m", overrides={"buckets": (4,),
                                                     "max_wait_us": 0})
            monkeypatch.setenv("H2O_TPU_SLO",
                               "serving.score.p99_ms=0.0001")
            preds = rt.score("slo_m", [{"a": 0.5}])
            assert len(preds) == 1
            recs = [r for r in slowtrace.snapshot()
                    if r["slo"] == "serving.score"]
            assert recs and recs[-1]["what"] == "slo_m"
            assert any(s["name"] == "serving.score"
                       for s in recs[-1]["spans"])
            snap = slo.burn_snapshot()
            assert snap["serving.score"]["errors"]["window"] >= 1
        finally:
            monkeypatch.delenv("H2O_TPU_SLO", raising=False)
            rt.shutdown()

    def test_program_walls_ride_along(self):
        """The bundle answers 'what was dispatching' — program walls from
        utils/programs.py are embedded when any program has run."""
        import jax
        import jax.numpy as jnp

        from h2o_tpu.utils import programs

        t = programs.tracked("test.slowtrace.prog", jax.jit(lambda x: x + 1),
                             "dispatch")
        t(jnp.ones((4,)))
        slo.declare("test.walls", "test objective", p99_ms=0.0001,
                    error_budget=0.1)
        with slowtrace.request("test.walls", "GET /walls"):
            pass
        (rec,) = slowtrace.snapshot()
        assert any(w["program"].startswith("test.slowtrace.prog")
                   or "test.slowtrace.prog" in w["program"]
                   for w in rec["program_walls"])
        del slo.SLOS["test.walls"]


# ---------------------------------------------------------------------------
# timeline incremental cursor
# ---------------------------------------------------------------------------
class TestTimelineSince:
    def test_since_filters_by_seq(self):
        timeline.record("test", "cursor-a")
        evs = timeline.snapshot(kind="test")
        cursor = evs[-1]["seq"]
        timeline.record("test", "cursor-b")
        timeline.record("test", "cursor-c")
        fresh = timeline.snapshot(since=cursor)
        assert [e["what"] for e in fresh if e["kind"] == "test"] \
            == ["cursor-b", "cursor-c"]
        assert all(e["seq"] > cursor for e in fresh)
        # cursor at the newest seq returns nothing — the poller's steady
        # state costs ~no serialization
        assert timeline.snapshot(since=timeline.total_recorded()) == []

    def test_since_composes_with_kind_and_limit_oldest_first(self):
        """Under a cursor the limit keeps the OLDEST events — a catch-up
        poller drains a >limit gap losslessly by advancing its cursor,
        instead of silently losing the gap's middle to a newest-biased
        cap."""
        t0 = timeline.total_recorded()
        for i in range(5):
            timeline.record("test", f"ck-{i}")
        got = timeline.snapshot(kind="test", since=t0, limit=2)
        assert [e["what"] for e in got] == ["ck-0", "ck-1"]
        # advancing the cursor to the last returned seq drains the rest
        got2 = timeline.snapshot(kind="test", since=got[-1]["seq"], limit=2)
        assert [e["what"] for e in got2] == ["ck-2", "ck-3"]


# ---------------------------------------------------------------------------
# watchdog supervisor
# ---------------------------------------------------------------------------
class TestWatchdog:
    def test_drill_trips_all_four_detectors_while_job_completes(
            self, monkeypatch, tmp_path):
        """The acceptance drill: armed watchdog.trip forces every
        detector in one sweep — each lands a typed timeline event + a
        flight bundle — while a real guarded training job runs to
        completion untouched."""
        monkeypatch.setenv("H2O_TPU_FLIGHT_DIR", str(tmp_path))
        fr = _small_frame(n=500, seed=2)
        from h2o_tpu.models.gbm import GBM, GBMParameters

        builder = GBM(GBMParameters(training_frame=fr, response_column="y",
                                    ntrees=4, max_depth=3, seed=1))
        job = builder.train(background=True)

        trips_before = telemetry.value("watchdog.trip.count")
        fp.arm("watchdog.trip", "raise*4")
        dog = watchdog.Watchdog(interval_s=3600)
        findings = dog.sweep()
        fp.disarm("watchdog.trip")

        # every detector force-tripped once
        assert all(len(findings[d]) == 1 for d, _ in watchdog.DETECTORS)
        assert telemetry.value("watchdog.trip.count") - trips_before == 4
        for _, gauge in watchdog.DETECTORS:
            assert telemetry.value(gauge) == 1.0
        # typed timeline events, one per detector
        evs = timeline.snapshot(kind="watchdog")
        whats = [e["what"] for e in evs[-4:]]
        assert whats == [d for d, _ in watchdog.DETECTORS]
        # one flight bundle per detector, reason-named
        bundles = sorted(os.listdir(tmp_path))
        assert len(bundles) == 4, bundles
        for d, _ in watchdog.DETECTORS:
            assert any(f"watchdog-{d}" in b for b in bundles), (d, bundles)
        # the guarded job ran to completion — observation, not killing
        model = job.join(timeout=120)
        assert model is not None
        assert job.status == "DONE"

    def test_hung_job_detector_real_condition(self, monkeypatch):
        from h2o_tpu.backend.jobs import Job

        monkeypatch.setenv("H2O_TPU_WATCHDOG_JOB_BUDGET_MS", "50")
        release = threading.Event()
        job = Job(description="wedged").start(lambda: release.wait(30))
        try:
            deadline = time.time() + 10
            dog = watchdog.Watchdog(interval_s=3600)
            findings = []
            while time.time() < deadline:
                findings = dog.sweep()["hung-job"]
                if findings:
                    break
                time.sleep(0.05)
            assert findings, "hung job never detected"
            mine = [f for f in findings if f["subject"] == str(job.key)]
            assert mine, findings
            # stale_s is rounded to 3 decimals — a sweep catching the job
            # at ~50.1ms legitimately reports exactly the 0.05 budget
            assert mine[0]["stale_s"] >= 0.05
            # health reports the same typed reason with the watchdog off
            snap = health.snapshot()
            assert not snap["ready"]
            assert "job-heartbeat" in {d["reason"] for d in snap["degraded"]}
        finally:
            release.set()
            job.join(timeout=10)

    def test_mrtask_stall_detector(self, monkeypatch):
        from h2o_tpu.parallel import mrtask

        monkeypatch.setenv("H2O_TPU_WATCHDOG_DISPATCH_BUDGET_MS", "100")
        mrtask._INFLIGHT[999999] = (time.monotonic() - 10.0, "fake_map")
        try:
            dog = watchdog.Watchdog(interval_s=3600)
            findings = dog.sweep()["mrtask-stall"]
            assert findings and findings[0]["fn"] == "fake_map"
            assert findings[0]["in_flight_s"] > 1.0
        finally:
            mrtask._INFLIGHT.pop(999999, None)
        # cleared table: next sweep is quiet
        assert dog.sweep()["mrtask-stall"] == []

    def test_cleaner_thrash_detector(self, monkeypatch):
        monkeypatch.setenv("H2O_TPU_WATCHDOG_THRASH_OPS", "4")
        dog = watchdog.Watchdog(interval_s=3600)
        dog.sweep()                      # baseline sample
        telemetry.inc("cleaner.spill.count", 10)
        telemetry.inc("cleaner.rehydrate.count", 10)
        findings = dog.sweep()["cleaner-thrash"]
        assert findings
        assert findings[0]["spills"] == 10
        assert findings[0]["rehydrates"] == 10
        # spill WITHOUT rehydrate is pressure, not thrash
        telemetry.inc("cleaner.spill.count", 10)
        assert dog.sweep()["cleaner-thrash"] == []

    def test_queue_stall_probe_on_real_batcher(self):
        from h2o_tpu.serving.batcher import MicroBatcher
        from h2o_tpu.serving.stats import ServingStats

        b = MicroBatcher("stall_m", lambda X: X, ServingStats(),
                         max_batch=8, max_wait_us=0, queue_depth=8)
        try:
            assert b.oldest_wait_s() is None
            b.pause()
            waiter = threading.Thread(
                target=lambda: b.submit(np.ones((1, 2), np.float32), 5.0))
            waiter.start()
            deadline = time.time() + 5
            while b.depth == 0 and time.time() < deadline:
                time.sleep(0.01)
            time.sleep(0.05)
            wait = b.oldest_wait_s()
            assert wait is not None and wait >= 0.05
            b.resume()
            waiter.join(timeout=10)
            assert b.oldest_wait_s() is None
        finally:
            b.stop()

    def test_cooldown_suppresses_repeat_bundles(self, monkeypatch,
                                                tmp_path):
        monkeypatch.setenv("H2O_TPU_FLIGHT_DIR", str(tmp_path))
        from h2o_tpu.parallel import mrtask

        monkeypatch.setenv("H2O_TPU_WATCHDOG_DISPATCH_BUDGET_MS", "100")
        mrtask._INFLIGHT[999998] = (time.monotonic() - 10.0, "fake_map")
        try:
            dog = watchdog.Watchdog(interval_s=3600)
            dog.sweep()
            dog.sweep()                  # same subject, inside cooldown
            bundles = [b for b in os.listdir(tmp_path) if "mrtask" in b]
            assert len(bundles) == 1
        finally:
            mrtask._INFLIGHT.pop(999998, None)

    def test_ensure_started_gated_by_knob(self, monkeypatch):
        monkeypatch.delenv("H2O_TPU_WATCHDOG_MS", raising=False)
        assert watchdog.ensure_started() is None
        monkeypatch.setenv("H2O_TPU_WATCHDOG_MS", "50")
        dog = watchdog.ensure_started()
        assert dog is not None
        assert watchdog.ensure_started() is dog   # idempotent
        deadline = time.time() + 5
        while dog._sweeps == 0 and time.time() < deadline:
            time.sleep(0.02)
        assert dog._sweeps > 0                    # the thread sweeps
        watchdog.stop()


# ---------------------------------------------------------------------------
# health checks (direct; the HTTP surface is below)
# ---------------------------------------------------------------------------
class TestHealth:
    def test_ready_on_quiet_process(self):
        snap = health.snapshot()
        assert snap["live"] is True
        assert snap["ready"] is True, snap["degraded"]
        assert snap["degraded"] == []
        assert set(snap["checks"]) == {"devices", "cleaner", "serving",
                                       "jobs", "watchdog", "slo"}
        assert "rest.request" in snap["slo"]
        assert telemetry.value("health.poll.count") >= 1

    def test_slo_burn_degrades_with_typed_reason(self, monkeypatch):
        slo.declare("test.burning", "test objective", p99_ms=1000,
                    error_budget=0.01)
        for _ in range(30):
            slo.note("test.burning", 0.001, error=True)
        snap = health.snapshot()
        assert not snap["ready"]
        reasons = {d["reason"] for d in snap["degraded"]}
        assert "slo-burn" in reasons
        (deg,) = [d for d in snap["degraded"] if d["reason"] == "slo-burn"]
        assert "test.burning" in deg["burning"]
        del slo.SLOS["test.burning"]

    def test_watchdog_trip_degrades_then_ages_out(self):
        dog = watchdog.Watchdog(interval_s=0.05)
        watchdog._DOG = dog              # install as the singleton
        try:
            fp.arm("watchdog.trip", "raise@1")
            dog.sweep()
            fp.disarm("watchdog.trip")
            snap = health.snapshot()
            assert not snap["ready"]
            assert "watchdog-trip" in {d["reason"] for d in snap["degraded"]}
            # trips age out after 10 intervals (0.5s here)
            deadline = time.time() + 10
            while time.time() < deadline:
                if health.snapshot()["ready"]:
                    break
                time.sleep(0.05)
            assert health.snapshot()["ready"]
        finally:
            watchdog._DOG = None

    def test_cleaner_headroom_math(self, monkeypatch):
        """The degradation condition reads the ONE Cleaner/reservation
        accounting: pin the budget under a HELD frame's residency and the
        reason is cleaner-headroom. (The held reference matters: pinning
        against whatever happens to be tracked flakes when gc reaps other
        modules' dead frames between the read and the health poll.)"""
        import h2o_tpu.backend.memory as mem

        before = mem.CLEANER.tracked_bytes()
        fr = _small_frame(n=20_000, seed=7)          # held until the end
        mine = mem.CLEANER.tracked_bytes() - before
        assert mine > 0
        # limit = half OUR residency: live stays >= mine while fr is
        # held, so headroom is 0 no matter what else gc collects
        monkeypatch.setenv("H2O_TPU_HBM_LIMIT_BYTES",
                           str(max(int(mine) // 2, 1024)))
        try:
            snap = health.snapshot()
            reasons = {d["reason"] for d in snap["degraded"]}
            assert "cleaner-headroom" in reasons, snap["checks"]["cleaner"]
        finally:
            monkeypatch.delenv("H2O_TPU_HBM_LIMIT_BYTES")
            del fr


# ---------------------------------------------------------------------------
# HTTP surface over an in-process cloud: /3/Health, /3/SlowTraces,
# /3/Timeline?since, wire propagation through a real socket
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cloud(worker_port):
    import h2o_tpu.api as h2o

    conn = h2o.init(port=worker_port(54791))
    yield conn
    try:
        h2o.shutdown()
    except Exception:
        pass


class TestHTTPSurface:
    def test_health_endpoint_and_helper(self, cloud):
        import h2o_tpu.api as h2o

        snap = h2o.health()
        assert snap["live"] is True and isinstance(snap["ready"], bool)
        assert "checks" in snap and "slo" in snap
        # health polls stay OUT of the timeline ring (monitor-poll rule)
        before = timeline.total_recorded()
        h2o.health()
        evs = timeline.snapshot(since=before)
        assert not any(e["kind"] == "rest" for e in evs)

    def test_timeline_since_over_http(self, cloud):
        import h2o_tpu.api as h2o

        timeline.record("test", "http-cursor")
        full = h2o.connection().request("GET", "/3/Timeline?limit=0")
        cursor = full["total_recorded"]
        timeline.record("test", "http-cursor-2")
        inc = h2o.connection().request("GET",
                                       f"/3/Timeline?since={cursor}")
        assert inc["since"] == cursor
        whats = [e["what"] for e in inc["events"] if e["kind"] == "test"]
        assert whats == ["http-cursor-2"]

    def test_wire_propagation_and_slowtrace_over_http(self, cloud,
                                                     monkeypatch):
        """One real socket round trip: the client span's traceparent is
        adopted server-side (same process, different threads here — the
        subprocess variant is TestCrossProcess), pinned through the
        slow-trace capture whose bundle records the request span's
        trace id."""
        import h2o_tpu.api as h2o

        slowtrace.clear()
        monkeypatch.setenv("H2O_TPU_SLO", "rest.request.p99_ms=0.0001")
        with telemetry.span("client.wire") as sp:
            h2o.connection().request("GET", "/3/About")
        monkeypatch.delenv("H2O_TPU_SLO")
        traces = h2o.slow_traces()
        assert traces, "tight SLO should have captured the request"
        rec = traces[-1]
        assert rec["slo"] == "rest.request"
        assert rec["trace"] == sp.trace_id      # adopted, not re-minted
        root = [s for s in rec["spans"] if s["name"] == "rest.request"]
        assert root and root[0]["remote"] == 1
        # DELETE clears the ring
        h2o.connection().request("DELETE", "/3/SlowTraces")
        assert h2o.slow_traces() == []

    def test_slow_traces_limit_param(self, cloud, monkeypatch):
        import h2o_tpu.api as h2o

        slowtrace.clear()
        monkeypatch.setenv("H2O_TPU_SLO", "rest.request.p99_ms=0.0001")
        for _ in range(3):
            h2o.connection().request("GET", "/3/About")
        monkeypatch.delenv("H2O_TPU_SLO")
        assert len(h2o.slow_traces(limit=2)) == 2
        assert len(h2o.slow_traces()) >= 3


# ---------------------------------------------------------------------------
# the acceptance pin: ONE merged Perfetto session, ONE trace id, >=2 pids
# ---------------------------------------------------------------------------
class TestCrossProcess:
    def test_client_rest_job_chunk_one_trace_across_two_processes(
            self, tmp_path, monkeypatch):
        """Boot the full REST stack in a SUBPROCESS (its own trace dir),
        drive a real train over the wire from this process (its own
        trace dir) inside a client span, then merge_traces over both
        dirs and assert client->REST->job->train-chunk spans share ONE
        trace id across two distinct pids."""
        import pandas as pd

        import h2o_tpu.api as h2o
        from h2o_tpu.api import client as client_mod

        client_dir = tmp_path / "client_traces"
        server_dir = tmp_path / "server_traces"
        client_dir.mkdir()
        server_dir.mkdir()

        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   H2O_TPU_TRACE_DIR=str(server_dir))
        proc = subprocess.Popen(
            [sys.executable, os.path.join(REPO_ROOT, "tests",
                                          "rest_server_worker.py"), "54931"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env, cwd=REPO_ROOT)
        prev_conn = client_mod._conn
        try:
            line = ""
            deadline = time.time() + 180
            while time.time() < deadline:
                line = proc.stdout.readline()
                if line.startswith("READY"):
                    break
                assert proc.poll() is None, f"worker died: {line}"
            assert line.startswith("READY"), "worker never came up"
            port = int(line.split()[1])

            monkeypatch.setenv("H2O_TPU_TRACE_DIR", str(client_dir))
            h2o.connect(f"http://127.0.0.1:{port}")
            rng = np.random.default_rng(0)
            n = 300
            df = pd.DataFrame({
                "x1": rng.normal(size=n).astype(np.float64),
                "x2": rng.normal(size=n).astype(np.float64),
                "y": np.where(rng.random(n) < 0.5, "a", "b")})
            with telemetry.span("client.train") as client_sp:
                fr = h2o.upload_frame(df, "wiretrace_frame")
                est = h2o.H2OGradientBoostingEstimator(
                    ntrees=2, max_depth=2, seed=1)
                est.train(y="y", training_frame=fr)
            trace_id = client_sp.trace_id

            # the health + slow-trace helpers work against the remote too
            assert h2o.health()["live"] is True
            assert isinstance(h2o.slow_traces(), list)

            merged = fleetobs.merge_traces(
                str(client_dir), extra_dirs=[str(server_dir)],
                out_path=str(tmp_path / "merged.json"))
            events = json.loads(open(merged).read())
            assert events, "merged session is empty"
            in_trace = [e for e in events
                        if e.get("args", {}).get("trace") == trace_id]
            pids = {e["pid"] for e in in_trace}
            assert len(pids) >= 2, (
                f"one trace id must span >=2 processes, got pids {pids}")
            names = {e["name"] for e in in_trace}
            assert "client.train" in names          # client process
            assert "rest.request" in names          # server request span
            assert "train.gbm" in names             # background job root
            assert "train.gbm.chunk" in names       # chunk spans
            # client span and server spans live in DIFFERENT pids
            client_pid = {e["pid"] for e in in_trace
                          if e["name"] == "client.train"}
            server_pid = {e["pid"] for e in in_trace
                          if e["name"] == "train.gbm.chunk"}
            assert client_pid and server_pid and client_pid != server_pid
        finally:
            client_mod._conn = prev_conn
            proc.kill()
            proc.wait(timeout=30)


# ---------------------------------------------------------------------------
# overhead bound — propagation + SLO + watchdog armed (PR 6 methodology)
# ---------------------------------------------------------------------------
class TestOverheadArmed:
    def test_overhead_under_2pct_with_causal_plane_armed(
            self, monkeypatch, tmp_path):
        """PR 6's <2% contract, re-measured with EVERYTHING this PR adds
        hot: trace export on, traceparent reads on the wire path, SLO
        windows fed, the watchdog sweeping at 100ms on its own thread —
        every emit point (old and new) wrapped into the accumulating
        timer against a real train wall."""
        monkeypatch.setenv("H2O_TPU_TRACE_DIR", str(tmp_path))
        monkeypatch.setenv("H2O_TPU_WATCHDOG_MS", "100")
        spent = [0.0]

        def timed(fn):
            def w(*a, **k):
                t0 = time.perf_counter()
                try:
                    return fn(*a, **k)
                finally:
                    spent[0] += time.perf_counter() - t0
            return w

        monkeypatch.setattr(telemetry, "inc", timed(telemetry.inc))
        monkeypatch.setattr(telemetry, "observe", timed(telemetry.observe))
        monkeypatch.setattr(telemetry, "set_gauge",
                            timed(telemetry.set_gauge))
        monkeypatch.setattr(telemetry, "_trace_emit",
                            timed(telemetry._trace_emit))
        monkeypatch.setattr(telemetry, "current_traceparent",
                            timed(telemetry.current_traceparent))
        monkeypatch.setattr(timeline, "record", timed(timeline.record))
        monkeypatch.setattr(slo, "note", timed(slo.note))
        dog = watchdog.ensure_started()
        assert dog is not None
        fr = _small_frame(n=2000, seed=3)
        m = _train_gbm(fr, ntrees=10, interval=1)
        wall = m.output.run_time_ms / 1000.0
        assert wall > 0
        assert spent[0] < 0.02 * wall, (
            f"causal observability spent {spent[0]:.4f}s of a "
            f"{wall:.3f}s train ({100 * spent[0] / wall:.2f}% >= 2%)")
