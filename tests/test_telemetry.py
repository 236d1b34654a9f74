"""Unified telemetry (utils/telemetry.py): registry contracts, span
tracing, timeline population from real training, the /3/Metrics +
/3/Timeline + Prometheus HTTP surface, the Perfetto export, and the
always-on overhead bound.
"""

import json
import time

import numpy as np
import pytest

from h2o_tpu.frame.frame import Frame
from h2o_tpu.frame.vec import T_CAT, Vec
from h2o_tpu.utils import telemetry, timeline

pytestmark = pytest.mark.telemetry


def _small_frame(n=400, seed=0):
    rng = np.random.default_rng(seed)
    fr = Frame.from_dict({"a": rng.normal(size=n).astype(np.float32),
                          "b": rng.normal(size=n).astype(np.float32),
                          "c": rng.normal(size=n).astype(np.float32)})
    y = (fr.vec("a").to_numpy() > 0).astype(np.float32)
    fr.add("y", Vec.from_numpy(y, type=T_CAT, domain=["n", "p"]))
    return fr


def _train_gbm(fr, ntrees=6, interval=2):
    from h2o_tpu.models.gbm import GBM, GBMParameters

    return GBM(GBMParameters(training_frame=fr, response_column="y",
                             ntrees=ntrees, max_depth=3, seed=1,
                             score_tree_interval=interval)).train_model()


# ---------------------------------------------------------------------------
# the program registry on the device's clock (ISSUE 36)
# ---------------------------------------------------------------------------
def _capture(tmp_path, planes: dict) -> str:
    """A hand-built capture: {plane name: [(module event name, seconds)]} on
    each plane's ``XLA Modules`` line, beside an ``XLA Ops`` line that no
    module reading may count, and one more ``/device:`` plane with neither
    (a one-chip TPU trace holds such a plane: it is no chip to average
    over)."""
    from jax.profiler import ProfileData

    text = ""
    for i, (plane, events) in enumerate(planes.items()):
        names = sorted({n for n, _ in events})
        text += f'planes {{ id: {i} name: "{plane}" lines {{ id: 1 name: ' \
                f'"XLA Modules" '
        at = 0
        for n, secs in events:
            ps = int(secs * 1e12)
            text += (f"events {{ metadata_id: {names.index(n) + 1} "
                     f"offset_ps: {at} duration_ps: {ps} }} ")
            at += ps
        text += ('} lines { id: 2 name: "XLA Ops" events { metadata_id: 1 '
                 'offset_ps: 0 duration_ps: 5000000000000 } } ')
        for j, n in enumerate(names):
            text += (f'event_metadata {{ key: {j + 1} value {{ id: {j + 1} '
                     f'name: "{n}" }} }} ')
        text += "} "
    text += ('planes { id: 99 name: "/device:TPU:0 other" lines { id: 1 '
             'name: "Steps" events { metadata_id: 1 offset_ps: 0 '
             'duration_ps: 1000 } } event_metadata { key: 1 value { id: 1 '
             'name: "step" } } } ')
    d = tmp_path / "capture" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path / "capture")


class TestFoldCapture:
    def test_fold_gives_mean_seconds_executions_and_undeclared(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from h2o_tpu.utils import programs

        programs.reset()

        @telemetry.program("gbm_level")
        def step(x):
            return x * 2

        programs.tracked("test.fold", jax.jit(step), "train")(jnp.ones(8))
        ((pid, rec),) = programs.snapshot().items()
        assert rec["module"] == "jit_gbm_level" and rec["device"] is None
        path = _capture(tmp_path, {
            "/device:TPU:0": [("jit_gbm_level(11)", 0.5),
                              ("jit_gbm_level(12)", 0.7),
                              ("jit_add(3)", 0.002),
                              ("jit_gbm_setup_sketch(4)", 0.04)],
            "/device:TPU:1": [("jit_gbm_level(11)", 0.6),
                              ("jit_gbm_level(12)", 0.8),
                              ("jit_gbm_setup_sketch(4)", 0.04)],
            "/host:CPU": [("jit_gbm_level(11)", 9.0)]})
        out = programs.fold_capture(path)
        assert out["planes"] == 2 and out["capture"] == path
        level = out["programs"]["jit_gbm_level"]
        assert level["seconds"] == pytest.approx(1.3)       # (1.2 + 1.4) / 2
        assert level["executions"] == 2 and level["records"] == [pid]
        # declared in PROGRAMS, no registry record: listed, not undeclared
        sketch = out["programs"]["jit_gbm_setup_sketch"]
        assert sketch["seconds"] == pytest.approx(0.04)
        assert sketch["records"] == []
        # an eager primitive, on one plane of two
        assert out["undeclared"] == {"jit_add": {
            "seconds": pytest.approx(0.001), "executions": 0.5}}
        dev = programs.snapshot()[pid]["device"]
        assert dev == {"seconds": pytest.approx(1.3), "executions": 2,
                       "capture": path}
        assert programs.last_fold() is out
        programs.reset()
        assert programs.last_fold() is None

    def test_capture_folds_its_own_and_device_profile_does_not(
            self, tmp_path, monkeypatch):
        """`telemetry.capture` (POST /3/Profiler/capture) folds the capture
        it made before it returns; `device_profile`, which the benchmark's
        slice goes through, reads nothing back."""
        from h2o_tpu.utils import programs

        folded = []
        monkeypatch.setattr(programs, "fold_capture",
                            lambda p: folded.append(p) or {})
        with telemetry.device_profile("t36", out_dir=str(tmp_path)) as path:
            pass
        assert path and folded == []
        got = telemetry.capture(20, out_dir=str(tmp_path))
        assert folded == [got]


@pytest.mark.parametrize("name", [
    "level_program_device_s", "gbm_setup_device_s", "sketch_device_s",
    "bin_device_s", "metrics_device_s.gbm", "metrics_device_s.glm",
    "glm_probe_device_s", "gbm_prep_s", "glm_start_s"])
def test_program_metrics_of_the_benchmark_name_a_reader(name):
    """The benchmark's entries for ISSUE 36 are data: the manifest holds its
    rules and each new file names a reader `benchmark/readers.py` has; a
    module reader's ``contains`` is ``jit_`` and a declared prefix."""
    from benchmark import manifest, readers

    assert manifest.check() == []
    man = manifest.load()
    (m,) = [m for m in man["per_layer"] if m["name"] == name]
    with open(manifest.layer_metric_file(man, name)) as f:
        spec = json.load(f)
    assert spec["reader"] in readers.READERS
    if spec["reader"] == "trace_module_s":
        assert m["source"] == "device_trace"
        prefix = spec["args"]["contains"]
        assert prefix.startswith("jit_") and any(
            p.startswith(prefix[len("jit_"):]) for p in telemetry.PROGRAMS)
    else:
        assert (spec["reader"], m["source"]) == ("span_sum_per",
                                                 "program_span")


# ---------------------------------------------------------------------------
# registry contracts
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_undeclared_name_raises(self):
        with pytest.raises(KeyError, match="unregistered metric"):
            telemetry.inc("never.declared.metric")  # graftlint: disable=unregistered-metric
        with pytest.raises(KeyError, match="unregistered metric"):
            telemetry.observe("never.declared.metric", 1.0)  # graftlint: disable=unregistered-metric
        with pytest.raises(KeyError, match="unregistered metric"):
            telemetry.set_gauge("never.declared.metric", 1.0)  # graftlint: disable=unregistered-metric
        with pytest.raises(KeyError, match="unregistered metric"):
            telemetry.value("never.declared.metric")  # graftlint: disable=unregistered-metric

    def test_kind_mismatch_raises(self):
        with pytest.raises(KeyError, match="gauge"):
            telemetry.inc("cleaner.hbm.live.bytes")
        with pytest.raises(KeyError, match="counter"):
            telemetry.observe("rest.request.count", 1.0)
        with pytest.raises(KeyError, match="histogram"):
            telemetry.set_gauge("train.seconds", 1.0)

    def test_counter_gauge_histogram_roundtrip(self):
        v0 = telemetry.value("retry.attempt.count")
        telemetry.inc("retry.attempt.count")
        telemetry.inc("retry.attempt.count", 3)
        assert telemetry.value("retry.attempt.count") == v0 + 4
        telemetry.set_gauge("cleaner.hbm.limit.bytes", 123.0)
        assert telemetry.value("cleaner.hbm.limit.bytes") == 123.0
        before = telemetry.snapshot()
        telemetry.observe("parser.parse.seconds", 0.25)
        snap = telemetry.snapshot()["parser.parse.seconds"]
        assert snap["kind"] == "histogram"
        assert snap["count"] == before["parser.parse.seconds"]["count"] + 1
        assert snap["p99"] is not None and snap["max"] >= 0.25

    def test_counters_are_thread_safe(self):
        import threading

        v0 = telemetry.value("retry.attempt.count")
        n_threads, per = 8, 2000

        def worker():
            for _ in range(per):
                telemetry.inc("retry.attempt.count")

        ts = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        # the lock-free shard design loses NO updates across threads
        assert telemetry.value("retry.attempt.count") == v0 + n_threads * per

    def test_snapshot_delta_is_compact(self):
        before = telemetry.snapshot()
        telemetry.inc("failpoint.fired.count")
        d = telemetry.snapshot_delta(before)
        assert d["failpoint.fired.count"]["delta"] == 1
        # untouched counters are dropped from the delta
        assert "serving.rejected.count" not in d

    def test_disabled_registry_validates_but_skips(self, monkeypatch):
        monkeypatch.setenv("H2O_TPU_METRICS_ENABLED", "0")
        v0 = telemetry.value("retry.attempt.count")
        telemetry.inc("retry.attempt.count")
        assert telemetry.value("retry.attempt.count") == v0
        with pytest.raises(KeyError):
            telemetry.inc("still.validated")  # graftlint: disable=unregistered-metric
        # the master switch gates DIRECT timeline.record sites too (jobs,
        # REST, Cleaner, compiles), not just spans/counters
        total0 = timeline.total_recorded()
        timeline.record("unit", "must.not.land")
        assert timeline.total_recorded() == total0

    def test_prometheus_exposition(self):
        telemetry.inc("rest.request.count")
        telemetry.observe("rest.request.seconds", 0.01)
        txt = telemetry.prometheus()
        assert "# TYPE h2o_tpu_rest_request_count counter" in txt
        assert "# HELP h2o_tpu_rest_request_count" in txt
        assert "# TYPE h2o_tpu_rest_request_seconds summary" in txt
        assert 'h2o_tpu_rest_request_seconds{quantile="0.5"}' in txt
        assert "h2o_tpu_cleaner_hbm_live_bytes_peak" in txt
        # every line is HELP/TYPE/sample — no stray JSON
        for line in txt.strip().splitlines():
            assert line.startswith("#") or line.split()[0].startswith(
                "h2o_tpu_")

    def test_describe_lists_every_metric(self):
        d = telemetry.describe()
        for name in ("mrtask.dispatch.count", "cleaner.spill.bytes",
                     "serving.request.seconds"):
            assert name in d


# ---------------------------------------------------------------------------
# spans + laps
# ---------------------------------------------------------------------------
class TestSpans:
    def test_nesting_and_trace_id_propagation(self):
        timeline.clear()
        assert telemetry.trace_id() is None
        with telemetry.span("outer.op", tag="x") as outer:
            assert telemetry.trace_id() == outer.trace_id
            with telemetry.span("inner.op") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
        assert telemetry.trace_id() is None
        evs = timeline.snapshot(kind="span")
        by_what = {e["what"]: e for e in evs}
        assert by_what["inner.op"]["trace"] == by_what["outer.op"]["trace"]
        assert by_what["inner.op"]["parent"] == by_what["outer.op"]["span"]
        assert by_what["outer.op"]["tag"] == "x"
        assert by_what["outer.op"]["dur_us"] >= 0

    def test_sibling_spans_get_fresh_traces(self):
        with telemetry.span("op.a") as a:
            pass
        with telemetry.span("op.b") as b:
            pass
        assert a.trace_id != b.trace_id

    def test_span_metric_and_phases(self):
        before = telemetry.snapshot()["parser.parse.seconds"]["count"]
        timeline.clear()
        with telemetry.span("phased.op",
                            metric="parser.parse.seconds") as sp:
            with sp.phase("build"):
                pass
            with sp.phase("dispatch"):
                pass
        after = telemetry.snapshot()["parser.parse.seconds"]["count"]
        assert after == before + 1
        ev = timeline.snapshot(kind="span")[-1]
        assert "build_s" in ev and "dispatch_s" in ev

    def test_span_undeclared_metric_raises(self):
        with pytest.raises(KeyError):
            with telemetry.span("x", metric="no.such.histogram"):  # graftlint: disable=unregistered-metric
                pass

    def test_lap_first_tick_starts_only(self):
        lap = telemetry.lap(metric="train.epoch.seconds", what="t.lap")
        assert lap.tick() is None
        time.sleep(0.01)
        dt = lap.tick(epoch=1)
        assert dt is not None and dt >= 0.005


# ---------------------------------------------------------------------------
# timeline ring
# ---------------------------------------------------------------------------
class TestTimeline:
    def test_typed_events_seq_ordered_and_capped(self):
        timeline.clear()
        for i in range(10):
            timeline.record("unit", f"ev{i}", idx=i)
        evs = timeline.snapshot()
        assert [e["what"] for e in evs] == [f"ev{i}" for i in range(10)]
        seqs = [e["seq"] for e in evs]
        assert seqs == sorted(seqs)
        for e in evs:
            assert {"seq", "ns", "ms", "kind", "what", "idx"} <= set(e)
        # limit keeps the MOST RECENT events
        tail = timeline.snapshot(limit=3)
        assert [e["what"] for e in tail] == ["ev7", "ev8", "ev9"]
        assert timeline.snapshot(kind="nope") == []
        assert timeline.total_recorded() >= 10
        assert timeline.capacity() >= 64


# ---------------------------------------------------------------------------
# real training + MRTask dispatch population
# ---------------------------------------------------------------------------
class TestRealRuns:
    def test_mrtask_dispatch_records_phases_and_payload(self):
        import jax.numpy as jnp

        from h2o_tpu import mr_reduce

        timeline.clear()
        before = telemetry.snapshot()
        x = jnp.arange(4096, dtype=jnp.float32)

        def total(cols, rows):
            return {"s": jnp.sum(jnp.where(rows.mask, cols[0], 0.0))}

        out = mr_reduce(total, [x], nrow=4096, reduce="sum")
        assert float(out["s"]) == float(np.arange(4096).sum())
        d = telemetry.snapshot_delta(before)
        assert d["mrtask.dispatch.count"]["delta"] == 1
        assert d["mrtask.payload.in.bytes"]["delta"] == 4096 * 4
        assert d["mrtask.payload.out.bytes"]["delta"] >= 4
        ev = [e for e in timeline.snapshot(kind="span")
              if e["what"] == "mrtask.dispatch"][-1]
        assert ev["fn"] == "total" and ev["rows"] == 4096
        assert "build_s" in ev and "dispatch_s" in ev

    def test_rollups_via_mrtask_match_fused_kernel_oracle(self):
        """The ensure_rollups mr_reduce path against the fused-kernel
        oracle `_rollup_kernel_cols` — the two implementations of the
        rollup math must agree to float tolerance (exact for counts,
        min/max, is_int)."""
        import jax
        import jax.numpy as jnp

        from h2o_tpu.frame.vec import (_rollup_kernel_cols,
                                       _rollups_from_scalars)

        rng = np.random.default_rng(11)
        n = 1500
        cols = {"a": rng.normal(7, 3, n).astype(np.float32),
                "b": rng.integers(-5, 5, n).astype(np.float32),
                "c": np.where(rng.random(n) < 0.2, np.nan,
                              rng.normal(size=n)).astype(np.float32)}
        fr = Frame.from_dict(cols)
        fr.ensure_rollups()  # the mr_reduce path
        stack = jnp.stack([fr.vec(k).data for k in cols], axis=1)
        oracle = jax.device_get(_rollup_kernel_cols(stack))
        for i, name in enumerate(cols):
            got = fr.vec(name).rollups()
            want = _rollups_from_scalars(fr.vec(name).nrow,
                                         {k: oracle[k][i] for k in oracle})
            assert (got.nacnt, got.zerocnt, got.nrow, got.is_int) == \
                (want.nacnt, want.zerocnt, want.nrow, want.is_int)
            assert got.mins == want.mins and got.maxs == want.maxs
            np.testing.assert_allclose(got.mean, want.mean, rtol=1e-5)
            np.testing.assert_allclose(got.sigma, want.sigma, rtol=1e-4)

    def test_gbm_train_populates_registry_and_timeline(self):
        timeline.clear()
        before = telemetry.snapshot()
        fr = _small_frame()
        m = _train_gbm(fr, ntrees=6, interval=2)
        assert m.auc() is not None
        d = telemetry.snapshot_delta(before)
        assert d["train.count"]["delta"] == 1
        assert d["train.chunk.count"]["delta"] == 3
        assert d["train.seconds"]["count"] == 1
        # the rollup pre-pass rides the MRTask driver
        assert d["mrtask.dispatch.count"]["delta"] >= 1
        # the HBM ledger gauge is live
        assert telemetry.snapshot()["cleaner.hbm.live.bytes"]["peak"] > 0
        evs = timeline.snapshot()
        assert len(evs) >= 5
        spans = [e for e in evs if e["kind"] == "span"]
        root = [e for e in spans if e["what"] == "train.gbm"]
        chunks = [e for e in spans if e["what"] == "train.gbm.chunk"]
        assert len(root) == 1 and len(chunks) == 3
        # every chunk span shares the training job's trace id
        assert {e["trace"] for e in chunks} == {root[0]["trace"]}

    def test_profile_aggregation(self):
        from h2o_tpu.utils.profile import aggregate_snapshot, task_profile

        with task_profile("unit.agg") as prof:
            with prof.phase("map"):
                pass
        agg = {r["task"]: r for r in aggregate_snapshot()}
        assert agg["unit.agg"]["count"] >= 1
        assert "map" in agg["unit.agg"]["phases"]

    def test_serving_stats_feed_registry(self):
        from h2o_tpu.serving.stats import ServingStats

        before = telemetry.snapshot()
        st = ServingStats(window=64)
        st.observe_request(0.004, 8)
        st.observe_batch(2, 16)
        st.observe_rejected()
        st.observe_timeout()
        d = telemetry.snapshot_delta(before)
        assert d["serving.request.count"]["delta"] == 1
        assert d["serving.request.rows"]["delta"] == 8
        assert d["serving.batch.rows"]["delta"] == 16
        assert d["serving.rejected.count"]["delta"] == 1
        assert d["serving.timeout.count"]["delta"] == 1
        assert d["serving.request.seconds"]["count"] == 1

    def test_log_ring_typed_records(self):
        import logging

        from h2o_tpu.utils.log import get_buffer, get_records, warn

        warn("ring-warn-probe")
        # bare stdlib logging under the h2o_tpu namespace lands in the ring
        logging.getLogger("h2o_tpu.unit").error("bare-logging-probe")
        recs = get_records(limit=50)
        msgs = [r["msg"] for r in recs]
        assert "ring-warn-probe" in msgs
        assert "bare-logging-probe" in msgs
        errs = get_records(level="errr")
        assert any(r["msg"] == "bare-logging-probe" for r in errs)
        assert all(r["level"] == "ERRR" for r in errs)
        # friendly spellings resolve to the internal 5-char codes
        assert get_records(level="error") == errs
        assert any(r["msg"] == "ring-warn-probe"
                   for r in get_records(level="warning"))
        lines = get_buffer(limit=5)
        assert len(lines) <= 5


# ---------------------------------------------------------------------------
# Perfetto / chrome-tracing export
# ---------------------------------------------------------------------------
class TestTraceExport:
    def test_export_is_valid_json_and_nested(self, tmp_path, monkeypatch):
        monkeypatch.setenv("H2O_TPU_TRACE_DIR", str(tmp_path))
        # fresh file per test: the writer re-opens when the dir changes
        with telemetry.span("export.outer", leg="t") as outer:
            with outer.phase("build"):
                pass
            with telemetry.span("export.inner"):
                pass
        path = telemetry.trace_path()
        assert path and str(tmp_path) in path
        evs = telemetry.read_trace(path)
        assert isinstance(evs, list)
        names = [e["name"] for e in evs]
        assert "export.outer" in names and "export.inner" in names
        for e in evs:
            assert e["ph"] == "X" and e["dur"] >= 1 and "ts" in e
            assert "trace" in e["args"]
        inner = next(e for e in evs if e["name"] == "export.inner")
        out = next(e for e in evs if e["name"] == "export.outer")
        assert inner["args"]["trace"] == out["args"]["trace"]
        assert out["args"]["leg"] == "t" and "build_s" in out["args"]
        # the raw normalized text is plain valid JSON
        text = open(path).read().rstrip().rstrip(",")
        json.loads(text if text.endswith("]") else text + "]")

    def test_no_export_without_knob(self, monkeypatch):
        monkeypatch.delenv("H2O_TPU_TRACE_DIR", raising=False)
        assert telemetry.trace_path() is None
        with telemetry.span("no.export"):
            pass  # must not raise / write anywhere


# ---------------------------------------------------------------------------
# overhead bound — the always-on contract
# ---------------------------------------------------------------------------
class TestOverhead:
    def test_telemetry_overhead_under_2pct_of_train(self, monkeypatch):
        """Directly measure the wall spent INSIDE telemetry during a real
        timed train by wrapping every emit point with an accumulating
        timer (the wrapper itself inflates the measurement, so the bound
        is conservative), then assert < 2% of the drained train wall."""
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
        monkeypatch.setattr(timeline, "record", timed(timeline.record))
        fr = _small_frame(n=2000, seed=3)
        m = _train_gbm(fr, ntrees=10, interval=1)
        wall = m.output.run_time_ms / 1000.0  # drained-compute contract
        assert wall > 0
        assert spent[0] < 0.02 * wall, (
            f"telemetry spent {spent[0]:.4f}s of a {wall:.3f}s train "
            f"({100 * spent[0] / wall:.2f}% >= 2%)")


# ---------------------------------------------------------------------------
# HTTP surface — /3/Metrics, /3/Timeline, /3/Logs, /3/Profiler
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cloud(worker_port):
    """This worker's own server, with one REST-driven train behind it: the
    tests below read that train's metrics, spans and log lines, whichever
    of them a worker is dealt first."""
    import h2o_tpu.api as h2o
    import pandas as pd

    conn = h2o.init(port=worker_port(54772))
    rng = np.random.default_rng(7)
    df = pd.DataFrame({"x1": rng.normal(size=300),
                       "x2": rng.normal(size=300)})
    df["y"] = np.where(df.x1 > 0, "yes", "no")
    m = h2o.H2OGradientBoostingEstimator(ntrees=4, max_depth=3, seed=1,
                                         score_tree_interval=2)
    m.train(y="y", training_frame=h2o.H2OFrame(df))
    yield conn
    try:
        h2o.shutdown()
    except Exception:
        pass


class TestHTTPSurface:
    def test_programs_carry_module_and_device_not_rooflines(self, cloud):
        """`GET /3/Programs` (ISSUE 36): each record names its XLA module,
        the device block is null before any capture, and nothing divides by
        a host enqueue wall any more."""
        import h2o_tpu.api as h2o

        payload = h2o.connection().request("GET", "/3/Programs")
        steps = [r for r in payload["programs"].values()
                 if r["name"] == "train.tree.step"]
        assert steps and {r["module"] for r in steps} == {"jit_gbm_level"}
        for rec in payload["programs"].values():
            assert rec["module"].startswith("jit_")
            assert "device" in rec and "wall" in rec
            assert not {"roofline_fraction", "achieved_flops_per_s"} & set(rec)
        assert "peak_flops_per_s" not in payload and "capture" in payload

    def test_metrics_json_over_http(self, cloud):
        import h2o_tpu.api as h2o

        # the fixture's REST-driven train makes the registry non-trivial
        payload = h2o.connection().request("GET", "/3/Metrics")
        mx = payload["metrics"]
        assert mx["train.count"]["value"] >= 1
        assert mx["train.chunk.count"]["value"] >= 2
        assert mx["mrtask.dispatch.count"]["value"] >= 1
        assert mx["rest.request.count"]["value"] >= 1
        assert mx["cleaner.hbm.live.bytes"]["peak"] > 0
        assert mx["xla.compile.count"]["value"] >= 1
        assert mx["train.seconds"]["kind"] == "histogram"
        assert payload["ts_ms"] > 0

    def test_metrics_prometheus_over_http(self, cloud):
        import urllib.request

        url = cloud.url if hasattr(cloud, "url") else None
        import h2o_tpu.api as h2o

        base = h2o.connection().url
        with urllib.request.urlopen(
                base + "/3/Metrics?format=prometheus") as r:
            body = r.read().decode()
            assert "text/plain" in r.headers["Content-Type"]
        assert "# TYPE h2o_tpu_rest_request_count counter" in body
        assert "h2o_tpu_train_count" in body

    def test_timeline_over_http(self, cloud):
        import h2o_tpu.api as h2o

        tl = h2o.connection().request("GET", "/3/Timeline")
        evs = tl["events"]
        assert len(evs) >= 3
        for e in evs:
            assert {"seq", "ns", "ms", "kind", "what"} <= set(e)
        assert tl["total_recorded"] >= len(evs)
        assert tl["capacity"] >= 64
        kinds = {e["kind"] for e in evs}
        assert "rest" in kinds  # every routed request is an event
        assert "span" in kinds  # the REST-driven train's spans
        capped = h2o.connection().request("GET", "/3/Timeline",
                                          params={"limit": 2})
        assert len(capped["events"]) == 2
        spans_only = h2o.connection().request(
            "GET", "/3/Timeline", params={"kind": "span"})["events"]
        assert spans_only and all(e["kind"] == "span" for e in spans_only)

    def test_logs_over_http(self, cloud):
        import h2o_tpu.api as h2o

        from h2o_tpu.utils.log import info

        info("http-logs-probe")
        got = h2o.connection().request("GET", "/3/Logs")
        assert "http-logs-probe" in got["log"]
        assert any(r["msg"] == "http-logs-probe" for r in got["records"])
        one = h2o.connection().request("GET", "/3/Logs",
                                       params={"limit": 1})
        assert len(one["log"].splitlines()) == 1

    def test_profiler_serves_task_aggregation(self, cloud):
        import h2o_tpu.api as h2o

        from h2o_tpu.utils.profile import task_profile

        with task_profile("http.profiler.probe") as prof:
            with prof.phase("reduce"):
                pass
        prof_payload = h2o.connection().request("GET", "/3/Profiler",
                                                params={"depth": 1})
        assert prof_payload["nodes"]
        tasks = {t["task"]: t for t in prof_payload["task_profiles"]}
        assert "http.profiler.probe" in tasks
        assert "reduce" in tasks["http.profiler.probe"]["phases"]
