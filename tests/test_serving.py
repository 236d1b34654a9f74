"""Online scoring runtime (`h2o_tpu/serving/`): shape-bucketed compiled
scorers, micro-batching scheduler, REST + client surface.

The load-bearing pins:

- **bit-parity**: batched scoring through padded buckets is BIT-identical
  to single-row scoring, across every bucket size and model category
  (GBM binomial, GLM regression, KMeans) — padding-mask correctness at
  non-bucket batch sizes included.
- **zero steady-state compiles**: after registration (which AOT-compiles
  every bucket), serving traffic performs no XLA compiles — asserted via
  the process compile counter (`utils/compilemeter.py`).
- **typed failure modes**: queue-full → `QueueFullError` → HTTP 429 with
  Retry-After; deadline expiry → `DeadlineExceededError` → HTTP 408.
  Nothing hangs.
- **shared row encoder**: `mojo/easy.py`'s vectorized `_encode_rows`
  batch path is value- and accounting-identical to the historical
  per-row loop.
"""

import threading
import time

import numpy as np
import pytest

import h2o_tpu.api as h2o
from h2o_tpu.frame.frame import Frame
from h2o_tpu.frame.vec import T_CAT, Vec
from h2o_tpu.models.gbm import GBM, GBMParameters
from h2o_tpu.models.glm import GLM, GLMParameters
from h2o_tpu.models.kmeans import KMeans, KMeansParameters
from h2o_tpu.mojo.easy import (EasyPredictModelWrapper,
                               PredictUnknownCategoricalLevelException)
from h2o_tpu.serving import (DeadlineExceededError, ModelNotRegisteredError,
                             QueueFullError, ServingRuntime,
                             UnsupportedModelError)
from h2o_tpu.utils import compilemeter

pytestmark = pytest.mark.serving

BUCKETS = [1, 8, 64]


def _training_frames():
    rng = np.random.default_rng(7)
    n = 300
    x1 = rng.normal(size=n).astype(np.float32)
    cat = rng.integers(0, 3, size=n).astype(np.float32)
    logits = x1 + 0.8 * (cat - 1)
    lab = (rng.random(n) < 1 / (1 + np.exp(-logits))).astype(np.float32)

    def catv(codes):
        return Vec.from_numpy(codes, type=T_CAT, domain=["a", "b", "c"])

    binom = Frame(["x1", "cat", "y"],
                  [Vec.from_numpy(x1), catv(cat),
                   Vec.from_numpy(lab, type=T_CAT, domain=["no", "yes"])])
    yreg = (logits + rng.normal(scale=0.1, size=n)).astype(np.float32)
    reg = Frame(["x1", "cat", "y"],
                [Vec.from_numpy(x1), catv(cat), Vec.from_numpy(yreg)])
    km = Frame.from_dict({
        "x": np.concatenate([np.zeros(50), np.ones(50) * 10]).astype(
            np.float32),
        "z": np.concatenate([np.zeros(50), np.ones(50) * 10]).astype(
            np.float32)})
    return binom, reg, km


@pytest.fixture(scope="module")
def models():
    binom, reg, kmfr = _training_frames()
    gbm = GBM(GBMParameters(training_frame=binom, response_column="y",
                            ntrees=8, max_depth=3, seed=1)).train_model()
    glm = GLM(GLMParameters(training_frame=reg, response_column="y",
                            family="gaussian", seed=1)).train_model()
    km = KMeans(KMeansParameters(training_frame=kmfr, k=2,
                                 seed=1)).train_model()
    return {"gbm": gbm, "glm": glm, "km": km}


@pytest.fixture(scope="module")
def runtime(models):
    rt = ServingRuntime()
    ov = {"buckets": BUCKETS}
    for mid, m in models.items():
        rt.register_model(m, mid, overrides=ov)
    yield rt
    rt.shutdown()


def _rows(n, seed=0, missing_every=0):
    """Row dicts over the (x1, cat) feature space; every k-th row drops a
    cell (absent → NaN) so padding/NaN handling is in the parity set."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        row = {"x1": float(rng.normal()),
               "cat": ["a", "b", "c"][int(rng.integers(0, 3))]}
        if missing_every and i % missing_every == 0:
            row.pop("cat")
        out.append(row)
    return out


def _km_rows(n, seed=0):
    rng = np.random.default_rng(seed)
    return [{"x": float(v), "z": float(w)}
            for v, w in zip(rng.uniform(0, 10, n), rng.uniform(0, 10, n))]


# ---------------------------------------------------------------------------
# bit-parity + padding mask
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("nrows", [1, 2, 3, 5, 8, 13, 64, 100])
@pytest.mark.parametrize("mid", ["gbm", "glm", "km"])
def test_batched_vs_single_row_bit_parity(runtime, mid, nrows):
    """Every batch size — exact bucket fits (1, 8, 64), padded remainders
    (2, 3, 5, 13) and beyond-the-largest-bucket chunking (100) — must score
    bit-identically to the single-row loop, for every model category."""
    rows = (_km_rows(nrows, seed=nrows) if mid == "km"
            else _rows(nrows, seed=nrows, missing_every=4))
    batched = runtime.score(mid, rows)
    singles = [runtime.score(mid, [r])[0] for r in rows]
    assert batched == singles  # dict equality == float bit equality


def test_padded_rows_masked_out(runtime):
    """A 5-row request pads to the 8-bucket: exactly 5 predictions come
    back, equal to the same rows scored in other paddings."""
    rows = _rows(5, seed=42)
    out5 = runtime.score("gbm", rows)
    assert len(out5) == 5
    out_in_13 = runtime.score("gbm", rows + _rows(8, seed=43))[:5]
    assert out5 == out_in_13


def test_prediction_shapes(runtime):
    b = runtime.score("gbm", _rows(2, seed=1))
    assert {"label", "labelIndex", "classProbabilities"} <= set(b[0])
    assert b[0]["label"] in ("no", "yes")
    assert len(b[0]["classProbabilities"]) == 2
    r = runtime.score("glm", _rows(2, seed=2))
    assert set(r[0]) == {"value"}
    c = runtime.score("km", _km_rows(2, seed=3))
    assert c[0]["cluster"] in (0, 1)


def test_parity_with_engine_predict(runtime, models):
    """Serving output matches the engine's frame-scoring path for the same
    row (the EasyPredict cross-check of test_easy_predict, serving-side)."""
    one = Frame(["x1", "cat"],
                [Vec.from_numpy(np.array([1.5], np.float32)),
                 Vec.from_numpy(np.array([1.0], np.float32), type=T_CAT,
                                domain=["a", "b", "c"])])
    p1 = float(models["gbm"].predict(one).vec(2).to_numpy()[0])
    served = runtime.score("gbm", [{"x1": 1.5, "cat": "b"}])[0]
    assert abs(served["classProbabilities"][1] - p1) < 1e-6
    kone = Frame(["x", "z"],
                 [Vec.from_numpy(np.array([9.5], np.float32)),
                  Vec.from_numpy(np.array([10.0], np.float32))])
    want = int(models["km"].predict(kone).vec(0).to_numpy()[0])
    assert runtime.score("km", [{"x": 9.5, "z": 10.0}])[0]["cluster"] == want


# ---------------------------------------------------------------------------
# warmup / compile counter
# ---------------------------------------------------------------------------
def test_zero_recompiles_after_registration(runtime):
    """The tentpole invariant: steady-state serving never compiles. Every
    bucket was AOT-compiled at registration; traffic across assorted batch
    sizes (bucket hits, padded remainders, chunked oversize) must leave
    the process compile counter untouched."""
    for mid in ("gbm", "glm", "km"):  # prime every formatting path once
        runtime.score(mid, _rows(1) if mid != "km" else _km_rows(1))
    before = compilemeter.count()
    for nrows in (1, 3, 8, 21, 64, 90):
        runtime.score("gbm", _rows(nrows, seed=nrows))
        runtime.score("glm", _rows(nrows, seed=nrows))
        runtime.score("km", _km_rows(nrows, seed=nrows))
    assert compilemeter.count() - before == 0
    for mid in ("gbm", "glm", "km"):
        assert runtime.stats(mid)["recompiles"] == 0


def test_registration_reports_warmup():
    """A freshly trained model (weights are trace-time constants, so its
    HLO is new to the process) pays one compile per bucket AT registration
    — warmup_compiles reports them. Re-registering the same model reports
    0/low: jax's in-process executable cache already holds the programs,
    which is exactly the no-new-compiles invariant."""
    binom, _, _ = _training_frames()
    fresh = GBM(GBMParameters(training_frame=binom, response_column="y",
                              ntrees=3, max_depth=2, seed=99)).train_model()
    rt = ServingRuntime()
    try:
        info = rt.register_model(fresh, "w", overrides={"buckets": [1, 4]})
        assert info["buckets"] == [1, 4]
        assert info["warmup_compiles"] >= 2   # one per bucket, paid up front
        assert info["n_features"] == 2 and info["category"] == "Binomial"
        again = rt.register_model(fresh, "w2",
                                  overrides={"buckets": [1, 4]})
        assert again["warmup_compiles"] <= info["warmup_compiles"]
    finally:
        rt.shutdown()


def test_unsupported_model_refused(models):
    """A model that reshapes frames in adapt_frame without a score_raw
    matrix twin must be refused loudly, not silently mis-scored."""
    from h2o_tpu.models.model_base import Model, ModelOutput, Parameters

    class _FrameOnlyModel(Model):
        algo_name = "frameonly"

        def adapt_frame(self, fr):  # pragma: no cover - never called
            return fr

    out = ModelOutput()
    out.names = ["x1"]
    weird = _FrameOnlyModel(Parameters(), out)
    rt = ServingRuntime()
    try:
        with pytest.raises(UnsupportedModelError):
            rt.register_model(weird, "weird")
    finally:
        rt.shutdown()


def test_frozen_categorical_encoding_refused():
    """A model trained with categorical_encoding publishes ENCODED column
    names; the serving row encoder would NaN every client cell and serve
    imputed garbage with a 200 — registration must refuse instead."""
    binom, _, _ = _training_frames()
    enc = GBM(GBMParameters(training_frame=binom, response_column="y",
                            ntrees=3, max_depth=2, seed=5,
                            categorical_encoding="one_hot_explicit")
              ).train_model()
    assert getattr(enc.output, "encoding_state", None) is not None
    rt = ServingRuntime()
    try:
        with pytest.raises(UnsupportedModelError):
            rt.register_model(enc, "enc")
    finally:
        rt.shutdown()


# ---------------------------------------------------------------------------
# micro-batcher: coalescing, backpressure, deadlines
# ---------------------------------------------------------------------------
def test_concurrent_requests_coalesce(models):
    rt = ServingRuntime()
    try:
        rt.register_model(models["gbm"], "co",
                          overrides={"buckets": BUCKETS})
        served = rt.model("co")
        served.batcher.pause()
        results = {}

        def one(i):
            results[i] = rt.score("co", [_rows(1, seed=i)[0]])[0]

        threads = [threading.Thread(target=one, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        deadline = time.time() + 5
        while served.batcher.depth < 6 and time.time() < deadline:
            time.sleep(0.005)
        assert served.batcher.depth == 6
        served.batcher.resume()
        for t in threads:
            t.join(timeout=5)
        assert len(results) == 6
        snap = rt.stats("co")
        # six requests released together must have scored in one batch
        assert snap["batches"] >= 1
        assert snap["mean_batch_occupancy"] > 1
        for i in range(6):
            assert results[i] == rt.score("co", [_rows(1, seed=i)[0]])[0]
    finally:
        rt.shutdown()


def test_queue_full_raises_typed_error(models):
    rt = ServingRuntime()
    try:
        rt.register_model(models["gbm"], "qf",
                          overrides={"buckets": [1, 8], "queue_depth": 1,
                                     "deadline_ms": 0})
        served = rt.model("qf")
        served.batcher.pause()
        t = threading.Thread(
            target=lambda: rt.score("qf", [{"x1": 0.1, "cat": "a"}]),
            daemon=True)
        t.start()
        deadline = time.time() + 5
        while served.batcher.depth < 1 and time.time() < deadline:
            time.sleep(0.005)
        with pytest.raises(QueueFullError) as ei:
            rt.score("qf", [{"x1": 0.2, "cat": "b"}])
        assert ei.value.retry_after_s > 0
        served.batcher.resume()
        t.join(timeout=5)
        assert rt.stats("qf")["rejected"] == 1
    finally:
        rt.shutdown()


def test_deadline_expiry_raises_timeout(models):
    rt = ServingRuntime()
    try:
        rt.register_model(models["gbm"], "dl",
                          overrides={"buckets": [1, 8]})
        served = rt.model("dl")
        served.batcher.pause()
        t0 = time.time()
        with pytest.raises(DeadlineExceededError):
            rt.score("dl", [{"x1": 0.1, "cat": "a"}], deadline_ms=50)
        assert time.time() - t0 < 5          # timed out, did not hang
        assert rt.stats("dl")["timeouts"] == 1
        served.batcher.resume()
        # the lane is healthy again after the timeout
        assert rt.score("dl", [{"x1": 0.1, "cat": "a"}])
    finally:
        rt.shutdown()


def test_unknown_model_raises(runtime):
    with pytest.raises(ModelNotRegisteredError):
        runtime.score("nope", [{"x1": 0.0}])


def test_stats_snapshot_shape(runtime):
    runtime.score("gbm", _rows(3, seed=9))
    snap = runtime.stats("gbm")
    assert snap["requests"] > 0 and snap["rows"] >= snap["requests"]
    lat = snap["latency_ms"]
    assert lat["p50"] is not None and lat["p50"] <= lat["p99"]
    assert snap["queue_depth"] == 0
    assert snap["mean_batch_occupancy"] >= 1


# ---------------------------------------------------------------------------
# MOJO registration path
# ---------------------------------------------------------------------------
def test_mojo_registration_bit_parity(models, tmp_path):
    path = str(tmp_path / "gbm.zip")
    models["gbm"].save_mojo(path)
    rt = ServingRuntime()
    try:
        info = rt.register_mojo(path, "mj", overrides={"buckets": [1, 8]})
        assert info["warmup_compiles"] == 0   # numpy scorer: nothing to jit
        wrapper = EasyPredictModelWrapper(path)
        rows = _rows(13, seed=5)
        served = rt.score("mj", rows)
        for row, got in zip(rows, served):
            want = wrapper.predict_binomial(
                {k: v for k, v in row.items()})
            assert got["classProbabilities"] == want.classProbabilities
            assert got["label"] == want.label
    finally:
        rt.shutdown()


# ---------------------------------------------------------------------------
# mojo/easy.py vectorized batch encoding (satellite regression)
# ---------------------------------------------------------------------------
def test_encode_rows_matches_per_row_loop(models, tmp_path):
    path = str(tmp_path / "enc.zip")
    models["gbm"].save_mojo(path)
    wrapper = EasyPredictModelWrapper(
        path, convert_unknown_categorical_levels_to_na=True)
    rows = _rows(17, seed=11, missing_every=3)
    rows[2]["cat"] = "zebra"                 # unknown level
    rows[9]["cat"] = "zebra"
    rows[12]["x1"] = None                    # explicit null
    rows[14]["cat"] = 1                      # pre-encoded level index
    batch = wrapper._encode_rows(rows)
    wrapper2 = EasyPredictModelWrapper(
        path, convert_unknown_categorical_levels_to_na=True)
    singles = np.stack([wrapper2._encode_row(r) for r in rows])
    np.testing.assert_array_equal(batch, singles)
    # unknown-level accounting identical between the two paths
    assert wrapper.unknown_categorical_levels_seen == \
        wrapper2.unknown_categorical_levels_seen == {"cat": 2}
    # and batch scoring equals the row loop bit-exactly
    out_batch = wrapper._score_rows(rows)
    out_rows = np.stack([wrapper2._score_row(r) for r in rows])
    np.testing.assert_array_equal(out_batch, out_rows)


def test_encode_rows_strict_raises(models, tmp_path):
    path = str(tmp_path / "strict.zip")
    models["gbm"].save_mojo(path)
    wrapper = EasyPredictModelWrapper(path)
    with pytest.raises(PredictUnknownCategoricalLevelException) as ei:
        wrapper._encode_rows([{"x1": 0.0, "cat": "a"},
                              {"x1": 0.0, "cat": "zebra"}])
    assert ei.value.column == "cat" and ei.value.level == "zebra"


# ---------------------------------------------------------------------------
# REST + client surface
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cloud(worker_port):
    conn = h2o.init(port=worker_port(54641))
    yield conn
    try:
        h2o.shutdown()
    except Exception:
        pass


def test_rest_register_score_stats_unregister(cloud, models):
    reg = h2o.register_serving(models["gbm"].key, serving_id="rest_gbm",
                               buckets="1,8")
    try:
        assert reg["buckets"] == [1, 8]
        assert "warmup_compiles" in reg  # count depends on the process's
        one = h2o.score_rows("rest_gbm", {"x1": 1.5, "cat": "b"})  # jit cache
        many = h2o.score_rows("rest_gbm", _rows(5, seed=3))
        assert len(one) == 1 and len(many) == 5
        assert one[0]["label"] in ("no", "yes")
        stats = h2o.serving_stats("rest_gbm")["rest_gbm"]
        assert stats["requests"] >= 2
        listed = cloud.request("GET", "/3/Serving/models")["models"]
        assert any(m["model_id"] == "rest_gbm" for m in listed)
        one_info = cloud.request("GET", "/3/Serving/models/rest_gbm")
        assert one_info["model_id"] == "rest_gbm"
        with pytest.raises(h2o.H2OConnectionError) as missing:
            cloud.request("GET", "/3/Serving/models/ghost")
        assert missing.value.status == 404
    finally:
        assert h2o.unregister_serving("rest_gbm")["unregistered"]
    with pytest.raises(h2o.H2OConnectionError) as ei:
        h2o.score_rows("rest_gbm", {"x1": 0.0, "cat": "a"})
    assert ei.value.status == 404


def test_rest_mojo_register(cloud, models, tmp_path):
    path = str(tmp_path / "rest_mojo.zip")
    models["gbm"].save_mojo(path)
    reg = h2o.register_serving(mojo_file=path, serving_id="rest_mojo",
                               buckets="1,8")
    try:
        assert reg["warmup_compiles"] == 0
        out = h2o.score_rows("rest_mojo", {"x1": 1.5, "cat": "b"})
        assert len(out[0]["classProbabilities"]) == 2
    finally:
        h2o.unregister_serving("rest_mojo")


def test_rest_queue_full_is_429_with_retry_after(cloud, models):
    from h2o_tpu.serving import get_runtime

    h2o.register_serving(models["gbm"].key, serving_id="rest_qf",
                         buckets="1,8", queue_depth=1, deadline_ms=0)
    rt = get_runtime()
    served = rt.model("rest_qf")
    try:
        served.batcher.pause()
        t = threading.Thread(
            target=lambda: rt.score("rest_qf", [{"x1": 0.1, "cat": "a"}]),
            daemon=True)
        t.start()
        deadline = time.time() + 5
        while served.batcher.depth < 1 and time.time() < deadline:
            time.sleep(0.005)
        with pytest.raises(h2o.H2OServingOverloadError) as ei:
            h2o.score_rows("rest_qf", {"x1": 0.2, "cat": "b"})
        assert ei.value.status == 429
        assert ei.value.retry_after_s > 0
        assert int(ei.value.headers["Retry-After"]) >= 1
        served.batcher.resume()
        t.join(timeout=5)
    finally:
        h2o.unregister_serving("rest_qf")


def test_rest_deadline_is_408(cloud, models):
    from h2o_tpu.serving import get_runtime

    h2o.register_serving(models["gbm"].key, serving_id="rest_dl",
                         buckets="1,8")
    served = get_runtime().model("rest_dl")
    try:
        served.batcher.pause()
        with pytest.raises(h2o.H2OServingTimeoutError) as ei:
            h2o.score_rows("rest_dl", {"x1": 0.1, "cat": "a"},
                           deadline_ms=50)
        assert ei.value.status == 408
        served.batcher.resume()
    finally:
        h2o.unregister_serving("rest_dl")
