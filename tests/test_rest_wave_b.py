"""REST route tail wave B: POJO download, server-side MOJO/JSON export,
calc model_id, the full ModelMetrics GET/POST/DELETE family, metrics made
from a predictions frame (`h2o.make_metrics`), async /4 predictions, stored
partial-dependence results, and Recovery/resume."""

import os
import time

import numpy as np
import pandas as pd
import pytest

import h2o_tpu.api as h2o

PORT = 54793


def _req(method, path, body=None, params=None, **kw):
    return h2o.connection().request(method, path, data=body, params=params,
                                    **kw)


@pytest.fixture(scope="module")
def setup(worker_port):
    h2o.init(port=worker_port(PORT))
    rng = np.random.default_rng(11)
    df = pd.DataFrame({
        "x1": rng.normal(size=400),
        "x2": rng.normal(size=400)})
    df["y"] = 2 * df.x1 - df.x2 + rng.normal(scale=0.1, size=400)
    fr = h2o.H2OFrame(df, destination_frame="wave_b.hex")
    from h2o_tpu.api.client import H2OGradientBoostingEstimator

    est = H2OGradientBoostingEstimator(ntrees=3, max_depth=3, seed=1)
    est.train(x=["x1", "x2"], y="y", training_frame=fr)
    return fr, est.model_id


# -- POJO / MOJO / JSON export ----------------------------------------------

def test_models_java_pojo(setup):
    _, mid = setup
    src = _req("GET", f"/3/Models.java/{mid}", raw=True)
    assert "double[] score0" in src
    assert "class" in src
    prev = _req("GET", f"/3/Models.java/{mid}/preview", raw=True)
    assert prev.splitlines()[0] == src.splitlines()[0]


def test_models_mojo_server_side(setup, tmp_path):
    _, mid = setup
    out = _req("GET", f"/99/Models.mojo/{mid}",
               params={"dir": str(tmp_path) + os.sep})
    assert os.path.exists(out["dir"])
    import zipfile

    assert zipfile.is_zipfile(out["dir"])
    # force-overwrite contract
    with pytest.raises(Exception, match="force"):
        _req("GET", f"/99/Models.mojo/{mid}", params={"dir": out["dir"]})


def test_models_json_export(setup, tmp_path):
    _, mid = setup
    out = _req("GET", f"/99/Models/{mid}/json")
    assert out["models"][0]["model_id"]["name"] == mid
    out2 = _req("GET", f"/99/Models/{mid}/json",
                params={"dir": str(tmp_path) + os.sep})
    import json

    with open(out2["dir"]) as fh:
        assert json.load(fh)["model_id"]["name"] == mid


def test_calc_model_id(setup):
    a = _req("POST", "/3/ModelBuilders/gbm/model_id")["model_id"]["name"]
    b = _req("POST", "/3/ModelBuilders/gbm/model_id")["model_id"]["name"]
    assert a != b and a.startswith("GBM_model")


# -- ModelMetrics family -----------------------------------------------------

def test_metrics_family(setup):
    fr, mid = setup
    # compute-on-frame caches the result
    got = _req("GET", f"/3/ModelMetrics/models/{mid}/frames/wave_b.hex")
    assert got["model_metrics"][0]["frame"]["name"] == "wave_b.hex"
    mse = got["model_metrics"][0]["MSE"]
    assert mse >= 0
    # frame-first form answers the same
    got2 = _req("GET", f"/3/ModelMetrics/frames/wave_b.hex/models/{mid}")
    assert got2["model_metrics"][0]["MSE"] == mse
    # per-model listing includes training AND the cached recompute
    per_model = _req("GET", f"/3/ModelMetrics/models/{mid}")["model_metrics"]
    assert len(per_model) >= 2
    # per-frame listing sees the cache
    per_frame = _req("GET",
                     "/3/ModelMetrics/frames/wave_b.hex")["model_metrics"]
    assert any(e["model"]["name"] == mid for e in per_frame)
    # scoped delete removes just that entry
    _req("DELETE", f"/3/ModelMetrics/models/{mid}/frames/wave_b.hex")
    assert _req("GET",
                "/3/ModelMetrics/frames/wave_b.hex")["model_metrics"] == []
    # POST recomputes and can store predictions
    out = _req("POST", f"/3/ModelMetrics/models/{mid}/frames/wave_b.hex",
               body={"predictions_frame": "wave_b_preds"})
    assert out["model_metrics"][0]["MSE"] == pytest.approx(mse)
    pf = _req("GET", "/3/Frames/wave_b_preds/summary")["frames"][0]
    assert pf["rows"] == 400
    _req("DELETE", "/3/ModelMetrics")  # cache cleared, training-only now
    assert _req("GET",
                "/3/ModelMetrics/frames/wave_b.hex")["model_metrics"] == []


def test_make_metrics_regression(setup):
    rng = np.random.default_rng(3)
    act = rng.normal(size=100)
    pred = act + rng.normal(scale=0.5, size=100)
    h2o.H2OFrame(pd.DataFrame({"p": pred}), destination_frame="mk_pred.hex")
    h2o.H2OFrame(pd.DataFrame({"a": act}), destination_frame="mk_act.hex")
    out = _req("POST",
               "/3/ModelMetrics/predictions_frame/mk_pred.hex"
               "/actuals_frame/mk_act.hex")
    mm = out["model_metrics"][0]
    ref = float(np.mean((act - pred) ** 2))
    assert mm["MSE"] == pytest.approx(ref, rel=1e-4)


def test_make_metrics_binomial(setup):
    rng = np.random.default_rng(4)
    y = (rng.random(size=300) < 0.4).astype(float)
    p1 = np.clip(0.7 * y + 0.15 + rng.normal(scale=0.1, size=300), 0.01, 0.99)
    h2o.H2OFrame(pd.DataFrame({"p1": p1}), destination_frame="mkb_pred.hex")
    h2o.H2OFrame(pd.DataFrame(
        {"a": np.where(y > 0, "yes", "no")}),
        destination_frame="mkb_act.hex")
    out = _req("POST",
               "/3/ModelMetrics/predictions_frame/mkb_pred.hex"
               "/actuals_frame/mkb_act.hex",
               body={"domain": ["no", "yes"]})
    mm = out["model_metrics"][0]
    assert 0.8 < mm["AUC"] <= 1.0
    from sklearn.metrics import roc_auc_score

    assert mm["AUC"] == pytest.approx(roc_auc_score(y, p1), abs=1e-3)


def test_make_metrics_shape_errors(setup):
    h2o.H2OFrame(pd.DataFrame({"a": [1.0, 2.0], "b": [3.0, 4.0]}),
                 destination_frame="mk2.hex")
    h2o.H2OFrame(pd.DataFrame({"y": [1.0, 2.0]}),
                 destination_frame="mk1.hex")
    with pytest.raises(Exception, match="exactly 1 column"):
        _req("POST", "/3/ModelMetrics/predictions_frame/mk2.hex"
                     "/actuals_frame/mk1.hex")


# -- async /4 predictions ----------------------------------------------------

def test_async_predictions(setup):
    fr, mid = setup
    out = _req("POST", f"/4/Predictions/models/{mid}/frames/wave_b.hex",
               body={"predictions_frame": "async_preds"})
    key = out["job"]["key"]["name"]
    for _ in range(200):
        j = _req("GET", f"/3/Jobs/{key}")["jobs"][0]
        if j["status"] in ("DONE", "FAILED", "CANCELLED"):
            break
        time.sleep(0.05)
    assert j["status"] == "DONE"
    pf = _req("GET", "/3/Frames/async_preds/summary")["frames"][0]
    assert pf["rows"] == 400


# -- stored partial dependence ----------------------------------------------

def test_pdp_store_and_fetch(setup):
    fr, mid = setup
    out = _req("POST", "/3/PartialDependence",
               body={"model_id": mid, "frame_id": "wave_b.hex",
                     "cols": "x1", "nbins": 5,
                     "destination_key": "pdp_wave_b"})
    assert out["destination_key"]["name"] == "pdp_wave_b"
    got = _req("GET", "/3/PartialDependence/pdp_wave_b")
    assert got["partial_dependence_data"] == \
        out["partial_dependence_data"]
    with pytest.raises(Exception, match="no partial dependence"):
        _req("GET", "/3/PartialDependence/nope")


# -- recovery resume ---------------------------------------------------------

def test_recovery_resume_route(setup, tmp_path):
    rec = str(tmp_path / "rec")
    out = _req("POST", "/99/Grid/gbm",
               body={"training_frame": "wave_b.hex", "response_column": "y",
                     "ntrees": 2, "max_depth": 2, "seed": 1,
                     "grid_id": "rec_grid", "recovery_dir": rec,
                     "hyper_parameters": {"learn_rate": [0.1, 0.3]}})
    key = out["job"]["key"]["name"]
    for _ in range(400):
        j = _req("GET", f"/3/Jobs/{key}")["jobs"][0]
        if j["status"] in ("DONE", "FAILED", "CANCELLED"):
            break
        time.sleep(0.05)
    assert j["status"] == "DONE"
    # wipe the grid, then resume from the recovery dir over REST
    _req("DELETE", "/99/Grids/rec_grid")
    out2 = _req("POST", "/3/Recovery/resume", body={"recovery_dir": rec})
    key2 = out2["job"]["key"]["name"]
    for _ in range(400):
        j2 = _req("GET", f"/3/Jobs/{key2}")["jobs"][0]
        if j2["status"] in ("DONE", "FAILED", "CANCELLED"):
            break
        time.sleep(0.05)
    assert j2["status"] == "DONE"
    gid = out2["grid_id"]["name"]
    g = _req("GET", f"/99/Grids/{gid}")
    assert len(g["model_ids"]) == 2
    with pytest.raises(Exception, match="no recovery dir"):
        _req("POST", "/3/Recovery/resume",
             body={"recovery_dir": str(tmp_path / "nothing")})
