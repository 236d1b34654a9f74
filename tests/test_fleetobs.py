"""Fleet observability plane (PR 13): program cost registry populated by
real GBM/GLM/serving programs, cross-process metric merge over live peer
processes, span-scoped device profiler capture, the crash flight
recorder, the bench perf-regression gate, concurrent trace-writer
integrity, and the always-on overhead bound re-asserted with program +
trace accounting enabled."""

from __future__ import annotations

import glob
import gzip
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
from h2o_tpu.utils import (fleetobs, flightrec, programs, telemetry,
                           timeline)

pytestmark = pytest.mark.fleetobs

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_failpoints():
    yield
    fp.reset()


def _small_frame(n=400, seed=0):
    rng = np.random.default_rng(seed)
    fr = Frame.from_dict({"a": rng.normal(size=n).astype(np.float32),
                          "b": rng.normal(size=n).astype(np.float32),
                          "c": rng.normal(size=n).astype(np.float32)})
    y = (fr.vec("a").to_numpy() > 0).astype(np.float32)
    fr.add("y", Vec.from_numpy(y, type=T_CAT, domain=["n", "p"]))
    return fr


def _train_gbm(fr, ntrees=4, interval=2):
    from h2o_tpu.models.gbm import GBM, GBMParameters

    return GBM(GBMParameters(training_frame=fr, response_column="y",
                             ntrees=ntrees, max_depth=3, seed=1,
                             score_tree_interval=interval)).train_model()


def _train_glm(n=300, seed=3):
    from h2o_tpu.models.glm import GLM, GLMParameters

    rng = np.random.default_rng(seed)
    fr = Frame.from_dict({"a": rng.normal(size=n).astype(np.float32),
                          "b": rng.normal(size=n).astype(np.float32),
                          "z": rng.normal(size=n).astype(np.float32)})
    return GLM(GLMParameters(training_frame=fr, response_column="z",
                             family="gaussian")).train_model()


# ---------------------------------------------------------------------------
# program cost registry
# ---------------------------------------------------------------------------
class TestProgramRegistry:
    def test_gbm_glm_serving_programs_have_cost_entries(self):
        """The acceptance shape: a small GBM + GLM + serving score leaves
        every exercised train/dispatch/serving program in the registry
        with NONZERO flops and memory figures."""
        programs.reset()
        fr = _small_frame(n=600, seed=1)
        m = _train_gbm(fr, ntrees=3)
        _train_glm()
        from h2o_tpu.serving.scorer import CompiledScorer

        sc = CompiledScorer(m, buckets=(4, 8))
        sc.warmup()
        out = sc.score(np.zeros((3, len(m.output.names)), np.float32))
        assert out.shape[0] == 3
        snap = programs.snapshot()
        kinds = {rec["kind"] for rec in snap.values()}
        assert {"train", "dispatch", "serving"} <= kinds
        names = {rec["name"] for rec in snap.values()}
        assert "train.tree.step" in names
        assert any(n.startswith("train.glm.irls") for n in names)
        assert any(n.startswith("mrtask.") for n in names)
        assert any(n.startswith("serving.score") for n in names)
        for pid, rec in snap.items():
            assert rec["flops"] > 0, pid
            assert rec["bytes_accessed"] > 0, pid
            assert rec["memory"].get("argument_bytes", 0) > 0, pid
        assert telemetry.value("programs.registered.count") >= len(snap)

    def test_tracked_dispatch_counts_and_walls(self):
        import jax
        import jax.numpy as jnp

        programs.reset()
        t = programs.tracked("test.tracked", jax.jit(lambda x: x * 2),
                            "dispatch")
        x = jnp.ones((16,))
        for _ in range(3):
            t(x)
        (rec,) = programs.snapshot().values()
        assert rec["dispatch_count"] == 3
        assert rec["wall"]["count"] == 3
        assert rec["wall"]["p50_s"] >= 0
        # host enqueue walls divide nothing (ISSUE 36): the device's side
        # is the module's seconds from a capture, null before any
        assert rec["module"] == "jit_<lambda>" and rec["device"] is None

    def test_tracked_steps_aside_under_enclosing_trace(self):
        import jax
        import jax.numpy as jnp

        programs.reset()
        t = programs.tracked("test.nested", jax.jit(lambda x: x + 1),
                            "dispatch")
        outer = jax.jit(lambda x: t(x) * 3)
        assert float(outer(jnp.float32(1.0))) == 6.0
        # tracer-called: no AOT registration happened for the inner
        assert all(r["name"] != "test.nested"
                   for r in programs.snapshot().values())

    def test_clear_compiled_recompiles_on_next_dispatch(self):
        import jax
        import jax.numpy as jnp

        t = programs.tracked("test.clear", jax.jit(lambda x: x - 1),
                            "dispatch")
        x = jnp.ones((4,))
        t(x)
        assert any(v is not False for v in t._compiled.values())
        programs.clear_compiled()  # the jobs.py sweep's call
        assert not t._compiled
        assert float(t(x)[0]) == 0.0  # recompiles transparently

    def test_stable_pid_has_no_process_identity(self):
        """Same (kind, name, sig, labels) -> same id across calls (and
        by construction across processes: the hash sees no id()/pid)."""
        pid1 = programs._stable_pid("train", "x.y", (((4,), "f32"),),
                                    {"k": 1})
        pid2 = programs._stable_pid("train", "x.y", (((4,), "f32"),),
                                    {"k": 1})
        pid3 = programs._stable_pid("train", "x.y", (((8,), "f32"),),
                                    {"k": 1})
        assert pid1 == pid2 != pid3

    def test_prometheus_provider_emits_program_families(self):
        programs.reset()
        import jax
        import jax.numpy as jnp

        t = programs.tracked("test.prom", jax.jit(lambda x: x * x),
                            "kernel")
        t(jnp.ones((8,)))
        text = telemetry.prometheus()
        assert "h2o_tpu_program_flops" in text
        assert 'kind="kernel"' in text


# ---------------------------------------------------------------------------
# cross-process fleet merge (live subprocess peers)
# ---------------------------------------------------------------------------
def _spawn_worker(n_incs: int, latency_s: float) -> tuple:
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO_ROOT, "tests",
                                      "fleet_worker.py"),
         str(n_incs), str(latency_s)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, text=True,
        cwd=REPO_ROOT)
    line = proc.stdout.readline().strip()
    assert line.startswith("READY "), f"worker failed to boot: {line!r}"
    return proc, int(line.split()[1])


class TestFleetMerge:
    def test_merge_over_three_live_processes(self, monkeypatch):
        """Collector merges >= 3 live processes (self + 2 subprocess
        peers) with per-process labels: counters SUM, gauges max, and
        histogram quantiles merge count-weighted."""
        w1, p1 = _spawn_worker(3, 0.01)
        w2, p2 = _spawn_worker(7, 0.03)
        try:
            monkeypatch.setenv("H2O_TPU_FLEET_PEERS",
                               f"127.0.0.1:{p1},127.0.0.1:{p2}")
            monkeypatch.setenv("H2O_TPU_FLEET_SPOOL", "")
            self_snap = telemetry.snapshot()
            fleetobs.invalidate_cache()
            view = fleetobs.collect(force=True)
            assert view["live"] >= 3
            ok_pids = {p.get("pid") for p in view["processes"]
                       if p.get("ok")}
            assert len(ok_pids) >= 3  # three DISTINCT processes
            assert os.getpid() in ok_pids
            cnt = view["metrics"]["rest.request.count"]
            assert cnt["kind"] == "counter"
            assert len(cnt["per_process"]) >= 3
            self_v = self_snap["rest.request.count"]["value"]
            assert cnt["value"] == pytest.approx(self_v + 3 + 7)
            # per-process label -> that process's own value
            by_label = {lbl.split("@")[0]: v
                        for lbl, v in cnt["per_process"].items()}
            assert str(w1.pid) in by_label and by_label[str(w1.pid)] == 3
            assert by_label[str(w2.pid)] == 7
            hist = view["metrics"]["rest.request.seconds"]
            self_h = self_snap["rest.request.seconds"]
            assert hist["count"] == self_h["count"] + 10
            assert hist["p99_max"] >= 0.03  # worker 2's latency, exact max
            assert "approximate" in hist["quantile_merge"]
            gauge = view["metrics"]["cleaner.hbm.live.bytes"]
            assert gauge["max"] >= 7000.0  # worker 2 set 7 * 1000
        finally:
            w1.kill()
            w2.kill()

    def test_dead_peer_bounds_not_blocks(self, monkeypatch):
        monkeypatch.setenv("H2O_TPU_FLEET_PEERS", "127.0.0.1:9")  # dead
        monkeypatch.setenv("H2O_TPU_FLEET_TIMEOUT_MS", "200")
        fleetobs.invalidate_cache()
        t0 = time.monotonic()
        view = fleetobs.collect(force=True)
        assert time.monotonic() - t0 < 5.0
        dead = [p for p in view["processes"] if not p.get("ok")]
        assert dead and "error" in dead[0]
        assert view["live"] >= 1  # self still merged

    def test_spool_snapshot_joins_the_merge(self, monkeypatch, tmp_path):
        monkeypatch.setenv("H2O_TPU_FLEET_PEERS", "")
        monkeypatch.setenv("H2O_TPU_FLEET_SPOOL", str(tmp_path))
        path = fleetobs.write_spool(label="bench_sub")
        assert path and os.path.exists(path)
        fleetobs.invalidate_cache()
        view = fleetobs.collect(force=True)
        sources = {p["source"] for p in view["processes"]}
        assert any(s.startswith("spool:") for s in sources)

    def test_same_pid_merged_once(self, monkeypatch, tmp_path):
        """A process visible through two sources (its port in the peer
        list AND a spool snapshot — here: self + own spool) must not have
        its counters SUMmed twice."""
        monkeypatch.setenv("H2O_TPU_FLEET_PEERS", "")
        monkeypatch.setenv("H2O_TPU_FLEET_SPOOL", str(tmp_path))
        fleetobs.write_spool(label="me_again")
        self_v = telemetry.snapshot()["rest.request.count"]["value"]
        fleetobs.invalidate_cache()
        view = fleetobs.collect(force=True)
        assert view["live"] == 1  # one process, however many sources
        dup = [p for p in view["processes"] if not p.get("ok")]
        assert dup and "duplicate pid" in dup[0]["error"]
        assert view["metrics"]["rest.request.count"]["value"] == \
            pytest.approx(self_v)

    def test_non_dict_spool_file_degrades_typed(self, monkeypatch,
                                                tmp_path):
        """A stray JSON array in the spool dir (e.g. a merged trace file
        sharing the directory) must not 500 the fleet endpoint."""
        monkeypatch.setenv("H2O_TPU_FLEET_PEERS", "")
        monkeypatch.setenv("H2O_TPU_FLEET_SPOOL", str(tmp_path))
        (tmp_path / "trace_merged.json").write_text('[{"ts": 1}]')
        fleetobs.invalidate_cache()
        view = fleetobs.collect(force=True)  # must not raise
        bad = [p for p in view["processes"] if not p.get("ok")]
        assert bad and "expected object" in bad[0]["error"]

    def test_stale_spool_reported_not_merged(self, monkeypatch, tmp_path):
        monkeypatch.setenv("H2O_TPU_FLEET_PEERS", "")
        monkeypatch.setenv("H2O_TPU_FLEET_SPOOL", str(tmp_path))
        path = tmp_path / "dead_worker.json"
        path.write_text(json.dumps({
            "pid": 999_999_999, "ok": True,
            "metrics": {"rest.request.count":
                        {"kind": "counter", "value": 1e9}}}))
        old = time.time() - 3600
        os.utime(path, (old, old))  # an hour-dead process's snapshot
        self_v = telemetry.snapshot()["rest.request.count"]["value"]
        fleetobs.invalidate_cache()
        view = fleetobs.collect(force=True)
        stale = [p for p in view["processes"] if not p.get("ok")]
        assert stale and "stale" in stale[0]["error"]
        assert view["metrics"]["rest.request.count"]["value"] == \
            pytest.approx(self_v)  # the 1e9 did NOT merge

    def test_scrape_cache_honors_interval(self, monkeypatch):
        monkeypatch.setenv("H2O_TPU_FLEET_PEERS", "")
        monkeypatch.setenv("H2O_TPU_FLEET_INTERVAL_MS", "60000")
        fleetobs.invalidate_cache()
        v1 = fleetobs.collect()
        v2 = fleetobs.collect()  # within the window: the SAME object
        assert v2 is v1
        v3 = fleetobs.collect(force=True)
        assert v3 is not v1
        fleetobs.invalidate_cache()


# ---------------------------------------------------------------------------
# concurrent trace writing + tolerant reads + fleet merge of traces
# ---------------------------------------------------------------------------
class TestTraceConcurrency:
    def test_eight_threads_two_k_spans_parse_whole(self, monkeypatch,
                                                   tmp_path):
        """The regression the satellite names: 8 threads x 2k spans
        hammering the per-process chrome-trace file must yield a trace
        that parses, with every span present exactly once."""
        monkeypatch.setenv("H2O_TPU_TRACE_DIR", str(tmp_path))
        n_threads, n_spans = 8, 2000

        def worker(k):
            for j in range(n_spans):
                with telemetry.span(f"hammer.t{k}", j=j):
                    pass

        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        evs = telemetry.read_trace(telemetry.trace_path())
        ours = [e for e in evs if e["name"].startswith("hammer.t")]
        assert len(ours) == n_threads * n_spans
        # no interleaved/torn records: every event round-trips as a dict
        # with the writer's full field set
        assert all({"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
                   for e in ours)

    def test_read_trace_drops_torn_tail(self, tmp_path):
        path = str(tmp_path / "trace_1.trace.json")
        with open(path, "w") as f:
            f.write('[\n{"name": "a", "ph": "X", "ts": 1, "pid": 1}')
            f.write(',\n{"name": "b", "ph": "X", "ts": 2, "pi')  # torn
        evs = telemetry.read_trace(path)
        assert [e["name"] for e in evs] == ["a"]

    def test_merge_traces_one_perfetto_session(self, tmp_path):
        for pid, names in ((111, ["x", "y"]), (222, ["z"])):
            with open(tmp_path / f"trace_{pid}.trace.json", "w") as f:
                parts = [json.dumps({"name": n, "ph": "X",
                                     "ts": 10 * pid + i, "dur": 1,
                                     "pid": pid, "tid": 1})
                         for i, n in enumerate(names)]
                f.write("[\n" + ",\n".join(parts))
        merged = fleetobs.merge_traces(str(tmp_path))
        with open(merged) as f:
            evs = json.load(f)  # strictly well-formed now
        assert [e["name"] for e in evs] == ["x", "y", "z"]
        assert {e["pid"] for e in evs} == {111, 222}


# ---------------------------------------------------------------------------
# on-demand device profiling
# ---------------------------------------------------------------------------
class TestProfilerCapture:
    def test_span_scoped_capture_loadable_with_annotations(
            self, monkeypatch, tmp_path):
        import jax
        import jax.numpy as jnp

        monkeypatch.setenv("H2O_TPU_PROFILE_DIR", str(tmp_path))
        with telemetry.device_profile("test.capture") as path:
            assert path is not None and path.startswith(str(tmp_path))
            with telemetry.span("fleetobs.annotated.span"):
                jax.block_until_ready(
                    jax.jit(lambda x: x @ x.T)(jnp.ones((128, 128))))
        gz = glob.glob(os.path.join(path, "**", "*.trace.json.gz"),
                       recursive=True)
        if not gz:  # pragma: no cover — backend without profiler output
            pytest.skip("jax.profiler produced no trace on this backend")
        data = json.loads(gzip.open(gz[0]).read())
        names = {str(e.get("name")) for e in data.get("traceEvents", [])
                 if isinstance(e, dict)}
        # the telemetry span rode into the device trace as an annotation,
        # so XLA ops nest under the span names in Perfetto
        assert any("fleetobs.annotated.span" in n for n in names)
        assert telemetry.value("profiler.capture.count") >= 1

    def test_no_session_when_unarmed(self, monkeypatch):
        monkeypatch.delenv("H2O_TPU_PROFILE_DIR", raising=False)
        with telemetry.device_profile("off") as path:
            assert path is None

    def test_capture_bounds_and_busy_rejection(self, monkeypatch,
                                               tmp_path):
        monkeypatch.setenv("H2O_TPU_PROFILE_DIR", str(tmp_path))
        with pytest.raises(ValueError):
            telemetry.capture(0)
        with pytest.raises(ValueError):
            telemetry.capture(61_000)
        with telemetry.device_profile("busy") as path:
            if path is None:  # pragma: no cover
                pytest.skip("profiler unsupported on this backend")
            with pytest.raises(ValueError, match="already live"):
                telemetry.capture(10)
        out = telemetry.capture(30)
        assert os.path.isdir(out)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
def _bundle_reasons(d):
    return [b["reason"] for b in flightrec.list_bundles(str(d))]


class TestFlightRecorder:
    def test_drill_failpoint_writes_bundle_and_train_continues(
            self, monkeypatch, tmp_path):
        monkeypatch.setenv("H2O_TPU_FLIGHT_DIR", str(tmp_path))
        fp.arm("flightrec.dump", "raise@1")
        m = _train_gbm(_small_frame(n=300, seed=5), ntrees=2)
        assert m.output.run_time_ms >= 0  # the drill did NOT kill the job
        assert _bundle_reasons(tmp_path) == ["drill"]
        (b,) = flightrec.list_bundles(str(tmp_path))
        bundle = flightrec.read_bundle(b["name"], str(tmp_path))
        for key in ("metrics", "timeline", "logs", "threads", "cleaner",
                    "programs", "knobs", "failpoints"):
            assert key in bundle, key
        assert bundle["reason"] == "drill"
        assert bundle["error"]["type"] == "InjectedFault"
        assert any(t["stack"] for t in bundle["threads"])
        assert "H2O_TPU_FLIGHT_DIR" in bundle["knobs"]["set_in_env"]
        assert bundle["metrics"]["train.chunk.count"]["value"] >= 1
        assert bundle["failpoints"] == {"flightrec.dump": "raise@1"}

    def test_bundle_on_injected_device_oom(self, monkeypatch, tmp_path):
        from h2o_tpu.backend.memory import CLEANER

        monkeypatch.setenv("H2O_TPU_FLIGHT_DIR", str(tmp_path))
        v = Vec.from_numpy(np.arange(32, dtype=np.float32))
        assert CLEANER._spill(v) > 0
        fp.arm("cleaner.rehydrate", "raise(oom)")  # sweep + retry fail too
        with pytest.raises(fp.InjectedOOM):
            _ = v.data
        fp.reset()
        assert "device-oom" in _bundle_reasons(tmp_path)
        name = next(b["name"] for b in flightrec.list_bundles(str(tmp_path))
                    if b["reason"] == "device-oom")
        bundle = flightrec.read_bundle(name, str(tmp_path))
        assert "RESOURCE_EXHAUSTED" in bundle["error"]["message"]
        assert "device_bytes" in bundle["cleaner"]
        # the vec still rehydrates fine once the injection is gone
        assert np.array_equal(np.asarray(v.data)[:32],
                              np.arange(32, dtype=np.float32))

    def test_bundle_on_lock_order_violation(self, monkeypatch, tmp_path):
        from h2o_tpu.utils import sanitizer

        monkeypatch.setenv("H2O_TPU_FLIGHT_DIR", str(tmp_path))
        sanitizer.reset_order_graph()
        a = sanitizer.SanitizedLock("fleetobs.A")
        b = sanitizer.SanitizedLock("fleetobs.B")
        with a:
            with b:
                pass  # establish A -> B
        b.acquire()
        try:
            with pytest.raises(sanitizer.LockOrderViolation):
                a.acquire()  # inversion: A while holding B
        finally:
            b.release()
            sanitizer.reset_order_graph()
        # the bundle is written from a DETACHED thread (the violating
        # thread still holds application locks) — poll briefly
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if "lock-order-violation" in _bundle_reasons(tmp_path):
                break
            time.sleep(0.02)
        assert "lock-order-violation" in _bundle_reasons(tmp_path)

    def test_bundle_on_unhandled_train_crash(self, monkeypatch, tmp_path):
        monkeypatch.setenv("H2O_TPU_FLIGHT_DIR", str(tmp_path))
        fp.arm("train.gbm.chunk", "raise@1")
        with pytest.raises(fp.InjectedFault):
            _train_gbm(_small_frame(n=200, seed=7), ntrees=2)
        fp.reset()
        assert "train-crash" in _bundle_reasons(tmp_path)

    def test_bundle_on_serving_batch_crash(self, monkeypatch, tmp_path):
        monkeypatch.setenv("H2O_TPU_FLIGHT_DIR", str(tmp_path))
        from h2o_tpu.serving.runtime import ServingRuntime

        m = _train_gbm(_small_frame(n=200, seed=9), ntrees=2)
        rt = ServingRuntime()
        rt.register_model(m, model_id="flight_crash_m",
                          overrides={"buckets": (4,)})
        try:
            fp.arm("serving.batch", "raise@1")
            rows = [{n: 0.0 for n in m.output.names}]
            with pytest.raises(Exception):
                rt.score("flight_crash_m", rows)
        finally:
            fp.reset()
            rt.shutdown()
        assert "serving-crash" in _bundle_reasons(tmp_path)

    def test_atomic_write_and_rotation(self, monkeypatch, tmp_path):
        monkeypatch.setenv("H2O_TPU_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("H2O_TPU_FLIGHT_MAX_BUNDLES", "2")
        for i in range(3):
            assert flightrec.dump(f"rotate-{i}") is not None
        bundles = flightrec.list_bundles(str(tmp_path))
        assert len(bundles) == 2
        assert [b["reason"] for b in bundles] == ["rotate-1", "rotate-2"]
        # no torn temp files behind the atomic writes
        assert not [f for f in os.listdir(tmp_path) if ".tmp" in f]

    def test_disarmed_is_a_noop(self, monkeypatch, tmp_path):
        monkeypatch.delenv("H2O_TPU_FLIGHT_DIR", raising=False)
        assert flightrec.dump("nope") is None
        assert flightrec.list_bundles(str(tmp_path)) == []

    def test_recorder_failure_never_masks_the_real_error(
            self, monkeypatch, tmp_path):
        monkeypatch.setenv("H2O_TPU_FLIGHT_DIR",
                           str(tmp_path / "sub" / "x"))
        # break the bundle collection — dump must swallow and return None
        monkeypatch.setattr(flightrec, "_bundle",
                            lambda *a: (_ for _ in ()).throw(
                                RuntimeError("sick recorder")))
        assert flightrec.dump("whatever") is None


# ---------------------------------------------------------------------------
# bench sidecar schema + perf-regression gate
# ---------------------------------------------------------------------------
def _load_bench():
    import importlib.util

    path = os.path.join(REPO_ROOT, "bench.py")
    spec = importlib.util.spec_from_file_location("h2o_tpu_bench", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestBenchGate:
    BASELINE = os.path.join(REPO_ROOT, "BENCH_r06_baseline.jsonl")

    def _gate(self, run_path, env=None):
        return subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tools",
                                          "bench_gate.py"),
             "--run", str(run_path), "--baseline", self.BASELINE],
            capture_output=True, text=True, cwd=REPO_ROOT,
            env={**os.environ, **(env or {})})

    def test_unmodified_run_passes(self):
        r = self._gate(self.BASELINE)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "all compared legs within bands" in r.stdout

    def test_seeded_wall_regression_fails_named(self, tmp_path):
        lines = [json.loads(ln) for ln in open(self.BASELINE)]
        for d in lines:
            if d.get("workload") == "gbm":
                d["record"]["score_once_s"] = round(
                    d["record"]["score_once_s"] * 1.3, 3)  # 30% slower
        run = tmp_path / "regressed.jsonl"
        run.write_text("".join(json.dumps(d) + "\n" for d in lines))
        r = self._gate(run)
        assert r.returncode == 1
        assert "gbm.score_once_s" in r.stdout  # leg + metric, named

    def test_seeded_parity_flip_fails(self, tmp_path):
        lines = [json.loads(ln) for ln in open(self.BASELINE)]
        for d in lines:
            if d.get("workload") == "sharded":
                d["record"]["forest_struct_equal"] = False
        run = tmp_path / "parity.jsonl"
        run.write_text("".join(json.dumps(d) + "\n" for d in lines))
        r = self._gate(run)
        assert r.returncode == 1
        assert "sharded.forest_struct_equal" in r.stdout

    def test_band_override_widens_the_gate(self, tmp_path):
        lines = [json.loads(ln) for ln in open(self.BASELINE)]
        for d in lines:
            if d.get("workload") == "gbm":
                d["record"]["score_once_s"] = round(
                    d["record"]["score_once_s"] * 1.3, 3)
        run = tmp_path / "regressed.jsonl"
        run.write_text("".join(json.dumps(d) + "\n" for d in lines))
        r = self._gate(run, env={"H2O_TPU_BENCH_GATE_BANDS": "wall=0.5"})
        assert r.returncode == 0, r.stdout

    def test_zero_overlap_is_not_a_green_gate(self, tmp_path):
        """A run sharing no leg with the baseline (typo'd workload list,
        renamed legs) must fail loudly, not pass by vacuity."""
        run = tmp_path / "disjoint.jsonl"
        run.write_text(
            json.dumps({"bench_run": {"rows": 1}}) + "\n"
            + json.dumps({"workload": "nosuchleg",
                          "record": {"wall_s": 1.0}}) + "\n")
        r = self._gate(run)
        assert r.returncode == 1
        assert "no metric was actually compared" in r.stdout

    def test_scale_mismatch_skips_walls_keeps_flags(self, tmp_path):
        lines = [json.loads(ln) for ln in open(self.BASELINE)]
        for d in lines:
            if "bench_run" in d:
                d["bench_run"]["rows"] = 999  # different config
            if d.get("workload") == "gbm":
                d["record"]["score_once_s"] = 9999.0  # huge "regression"
        run = tmp_path / "rescaled.jsonl"
        run.write_text("".join(json.dumps(d) + "\n" for d in lines))
        r = self._gate(run)
        assert r.returncode == 0  # cross-scale walls are noise, not gated
        assert "skip (scale)" in r.stdout

    def test_sidecar_lines_carry_schema_version_and_programs(
            self, tmp_path, monkeypatch):
        bench = _load_bench()
        sidecar = tmp_path / "side.jsonl"
        monkeypatch.setenv("H2O_TPU_BENCH_SIDECAR", str(sidecar))
        bench._sidecar_start({"rows": 1})
        bench._leg({}, "noop", lambda: {"wall_s": 0.0})
        lines = [json.loads(ln) for ln in open(sidecar)]
        assert lines[0]["bench_run"]["schema_version"] == \
            bench.SIDECAR_SCHEMA_VERSION
        assert lines[1]["schema_version"] == bench.SIDECAR_SCHEMA_VERSION
        assert "programs" in lines[1]["record"]
        assert "telemetry" in lines[1]["record"]


# ---------------------------------------------------------------------------
# overhead bound re-asserted with programs + trace accounting enabled
# ---------------------------------------------------------------------------
class TestOverheadWithPlane:
    def test_overhead_under_2pct_with_programs_and_trace(
            self, monkeypatch, tmp_path):
        """PR 6's <2% contract, re-measured with the NEW accounting hot:
        chrome-trace export writing every span and the program registry's
        tracked dispatch path both wrapped into the accumulating timer."""
        monkeypatch.setenv("H2O_TPU_TRACE_DIR", str(tmp_path))
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
        monkeypatch.setattr(timeline, "record", timed(timeline.record))
        monkeypatch.setattr(programs, "note_wall",
                            timed(programs.note_wall))
        monkeypatch.setattr(programs, "register_compiled",
                            timed(programs.register_compiled))
        fr = _small_frame(n=2000, seed=3)
        m = _train_gbm(fr, ntrees=10, interval=1)
        wall = m.output.run_time_ms / 1000.0
        assert wall > 0
        assert spent[0] < 0.02 * wall, (
            f"observability spent {spent[0]:.4f}s of a {wall:.3f}s train "
            f"({100 * spent[0] / wall:.2f}% >= 2%)")


# ---------------------------------------------------------------------------
# HTTP surface — /3/Programs, /3/Metrics?fleet=1, /3/Flight, capture
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cloud(worker_port):
    import h2o_tpu.api as h2o

    conn = h2o.init(port=worker_port(54787))
    yield conn
    try:
        h2o.shutdown()
    except Exception:
        pass


class TestHTTPSurface:
    def test_programs_endpoint_over_http(self, cloud):
        import h2o_tpu.api as h2o

        _train_gbm(_small_frame(n=300, seed=11), ntrees=2)
        payload = h2o.connection().request("GET", "/3/Programs")
        assert payload["count"] >= 1
        progs = payload["programs"]
        assert any(rec["kind"] == "train" and rec["flops"] > 0
                   and rec["memory"].get("argument_bytes", 0) > 0
                   for rec in progs.values())
        # the client helper unwraps the same payload
        assert set(h2o.programs()) == set(progs)

    def test_fleet_metrics_over_http(self, cloud, monkeypatch):
        import h2o_tpu.api as h2o

        w1, p1 = _spawn_worker(2, 0.01)
        w2, p2 = _spawn_worker(4, 0.01)
        try:
            monkeypatch.setenv("H2O_TPU_FLEET_PEERS",
                               f"127.0.0.1:{p1},127.0.0.1:{p2}")
            fleetobs.invalidate_cache()
            fleet = h2o.fleet_metrics(force=True)
            assert fleet["live"] >= 3
            cnt = fleet["metrics"]["rest.request.count"]
            assert len(cnt["per_process"]) >= 3
        finally:
            w1.kill()
            w2.kill()

    def test_flight_listing_over_http(self, cloud, monkeypatch, tmp_path):
        import h2o_tpu.api as h2o

        monkeypatch.setenv("H2O_TPU_FLIGHT_DIR", str(tmp_path))
        flightrec.dump("http-drill")
        listing = h2o.flight_bundles()
        assert listing["armed"] is True
        assert any(b["reason"] == "http-drill" for b in listing["bundles"])
        name = listing["bundles"][-1]["name"]
        bundle = h2o.flight_bundle(name)
        assert bundle["reason"] == "http-drill"
        assert "threads" in bundle

    def test_flight_name_traversal_rejected(self, cloud, monkeypatch,
                                            tmp_path):
        import h2o_tpu.api as h2o
        from h2o_tpu.api.client import H2OConnectionError

        monkeypatch.setenv("H2O_TPU_FLIGHT_DIR", str(tmp_path))
        with pytest.raises(H2OConnectionError):
            h2o.connection().request(
                "GET", "/3/Flight/..%2F..%2Fetc%2Fpasswd")

    def test_profiler_capture_over_http(self, cloud, monkeypatch,
                                        tmp_path):
        import h2o_tpu.api as h2o

        monkeypatch.setenv("H2O_TPU_PROFILE_DIR", str(tmp_path))
        out = h2o.profiler_capture(ms=30)
        assert out.startswith(str(tmp_path))
        files = glob.glob(os.path.join(out, "**", "*"), recursive=True)
        if not any(os.path.isfile(f) for f in files):  # pragma: no cover
            pytest.skip("jax.profiler produced no trace on this backend")
