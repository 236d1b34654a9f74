"""Tests for UpliftDRF, DT, and the XGBoost-surface builder.

Modeled on the reference pyunits (`h2o-py/tests/testdir_algos/uplift/`,
`.../dt/`, `.../xgboost/`): synthetic data with a known effect, assert the
model recovers it and the parameter surface behaves.
"""

import numpy as np
import pytest

from h2o_tpu import Frame


def _uplift_data(n=4000, seed=3):
    rng = np.random.default_rng(seed)
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)
    treat = rng.integers(0, 2, size=n).astype(np.float32)
    # uplift only where x1 > 0: treated positives much likelier
    base = 0.2 + 0.1 * (x2 > 0)
    lift = np.where(x1 > 0, 0.4, 0.0) * treat
    y = (rng.random(n) < base + lift).astype(np.float32)
    return Frame.from_dict({
        "x1": x1.astype(np.float32), "x2": x2.astype(np.float32),
        "treatment": treat, "y": y,
    })


def test_uplift_drf_recovers_effect():
    from h2o_tpu.models.uplift import UpliftDRF, UpliftDRFParameters

    fr = _uplift_data()
    fr.replace("y", fr.vec("y").astype_cat(["0", "1"]))
    p = UpliftDRFParameters(training_frame=fr, response_column="y",
                            treatment_column="treatment", ntrees=20,
                            max_depth=4, seed=42, uplift_metric="KL")
    m = UpliftDRF(p).train_model()
    pred = m.predict(fr)
    assert pred.names == ["uplift_predict", "p_y1_ct1", "p_y1_ct0"]
    up = pred.vec("uplift_predict").to_numpy()
    x1 = fr.vec("x1").to_numpy()
    # mean predicted uplift where x1>0 should exceed where x1<=0 by a margin
    diff = up[x1 > 0].mean() - up[x1 <= 0].mean()
    assert diff > 0.15, f"uplift separation too weak: {diff}"
    mm = m.output.training_metrics
    assert np.isfinite(mm.auuc)
    assert 0.2 < mm.ate < 0.3  # true ATE ~ 0.2 (half the rows have 0.4 lift)


@pytest.mark.parametrize("metric", ["Euclidean", "ChiSquared"])
def test_uplift_divergences_run(metric):
    from h2o_tpu.models.uplift import UpliftDRF, UpliftDRFParameters

    fr = _uplift_data(n=1000)
    fr.replace("y", fr.vec("y").astype_cat(["0", "1"]))
    p = UpliftDRFParameters(training_frame=fr, response_column="y",
                            treatment_column="treatment", ntrees=5,
                            max_depth=3, seed=1, uplift_metric=metric)
    m = UpliftDRF(p).train_model()
    assert np.isfinite(m.output.training_metrics.auuc)


def test_dt_single_tree():
    from h2o_tpu.models.dt import DT, DTParameters

    rng = np.random.default_rng(0)
    n = 2000
    x = rng.normal(size=(n, 3)).astype(np.float32)
    y = (x[:, 0] > 0.3).astype(np.float32)
    fr = Frame.from_dict({"a": x[:, 0], "b": x[:, 1], "c": x[:, 2], "y": y})
    fr.replace("y", fr.vec("y").astype_cat(["0", "1"]))
    m = DT(DTParameters(training_frame=fr, response_column="y",
                        max_depth=4, min_rows=5, seed=7)).train_model()
    assert m.ntrees == 1
    acc = (m.predict(fr).vec("predict").to_numpy() == y).mean()
    assert acc > 0.95, f"single tree should nail an axis split, acc={acc}"


def test_xgboost_surface_aliases_and_fit():
    from h2o_tpu.models.xgboost import XGBoost, XGBoostParameters

    rng = np.random.default_rng(5)
    n = 2000
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] ** 2 + 0.1 * rng.normal(size=n)).astype(np.float32)
    fr = Frame.from_dict({f"x{i}": x[:, i] for i in range(4)} | {"y": y})
    p = XGBoostParameters(training_frame=fr, response_column="y",
                          n_estimators=30, eta=0.3, max_depth=4,
                          subsample=0.9, colsample_bytree=0.9,
                          reg_lambda=1.0, reg_alpha=0.1, seed=11)
    assert p.ntrees == 30 and p.learn_rate == 0.3 and p.sample_rate == 0.9
    m = XGBoost(p).train_model()
    r2 = m.output.training_metrics.r2
    assert r2 > 0.8, f"xgboost-surface underfit: r2={r2}"


def test_xgboost_dart_booster():
    """`booster='dart'` runs the real DART driver: dropout rounds change
    the forest (vs gbtree with the same seed), leaf weights are baked in
    (predictions = margin path), and the fit still learns the signal."""
    from h2o_tpu.models.xgboost import XGBoost, XGBoostParameters

    rng = np.random.default_rng(9)
    n = 1500
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = (x[:, 0] - 0.7 * x[:, 1] + 0.1 * rng.normal(size=n)).astype(np.float32)
    fr = Frame.from_dict({f"x{i}": x[:, i] for i in range(4)} | {"y": y})

    kw = dict(training_frame=fr, response_column="y", ntrees=25,
              max_depth=3, eta=0.3, seed=7)
    dart = XGBoost(XGBoostParameters(booster="dart", rate_drop=0.3,
                                     **kw)).train_model()
    plain = XGBoost(XGBoostParameters(booster="gbtree", **kw)).train_model()

    r2 = dart.output.training_metrics.r2
    assert r2 > 0.9, f"dart underfit: r2={r2}"
    # dropout must actually alter the ensemble relative to plain boosting
    dv = np.asarray(dart.forest["val"])
    pv = np.asarray(plain.forest["val"])
    assert dv.shape == pv.shape
    assert not np.allclose(dv, pv)
    # normalization: with drops, no tree keeps the full learn_rate-scaled
    # leaf magnitude pattern of plain boosting beyond the first tree
    pred = dart.predict(fr).vec(0).to_numpy()
    ss_res = float(np.sum((y - pred) ** 2))
    ss_tot = float(np.sum((y - y.mean()) ** 2))
    # scoring path agrees with the training-metrics margin (weights baked)
    assert abs((1 - ss_res / ss_tot) - r2) < 0.02

    # one_drop guarantees dropout every round even at rate_drop=0
    od = XGBoost(XGBoostParameters(booster="dart", rate_drop=0.0,
                                   one_drop=True, **kw)).train_model()
    assert not np.allclose(np.asarray(od.forest["val"]), pv)
    # skip_drop=1.0 disables dropout entirely: identical to gbtree
    sk = XGBoost(XGBoostParameters(booster="dart", rate_drop=0.5,
                                   skip_drop=1.0, **kw)).train_model()
    np.testing.assert_allclose(np.asarray(sk.forest["val"]),
                               pv, rtol=1e-5, atol=1e-6)


def test_xgboost_dart_multinomial():
    """Round-4: the multinomial gate is gone — DART drops whole boosting
    rounds (all K class-trees share one weight) and still learns."""
    from h2o_tpu.models.xgboost import XGBoost, XGBoostParameters
    from h2o_tpu.frame.vec import T_CAT, Vec

    rng = np.random.default_rng(3)
    n = 1200
    x = rng.normal(size=(n, 3)).astype(np.float32)
    yc = (np.argmax(x, axis=1)).astype(np.float32)
    noisy = rng.random(n) < 0.1
    yc[noisy] = rng.integers(0, 3, noisy.sum())
    fr = Frame.from_dict({f"x{i}": x[:, i] for i in range(3)})
    fr.add("y", Vec.from_numpy(yc, type=T_CAT, domain=["a", "b", "c"]))
    m = XGBoost(XGBoostParameters(training_frame=fr, response_column="y",
                                  booster="dart", rate_drop=0.3, ntrees=15,
                                  max_depth=3, seed=5)).train_model()
    tm = m.output.training_metrics
    assert tm.logloss < 0.6, tm.logloss
    # scoring path (baked leaves) agrees with the carried-margin metrics
    perf = m.model_performance(fr)
    np.testing.assert_allclose(perf.logloss, tm.logloss, rtol=1e-4)
    # per-class trees: forest arrays carry the K axis
    assert np.asarray(m.forest["feat"]).ndim == 3


def test_xgboost_dart_checkpoint_continuation():
    """Round-4: DART continues from a prior model's baked forest (prior
    trees enter at weight 1.0 and stay droppable/rescalable)."""
    from h2o_tpu.models.xgboost import XGBoost, XGBoostParameters

    rng = np.random.default_rng(6)
    n = 1500
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = (x[:, 0] - 0.7 * x[:, 1] + 0.1 * rng.normal(size=n)).astype(np.float32)
    fr = Frame.from_dict({f"x{i}": x[:, i] for i in range(4)} | {"y": y})
    # 20 bins, named: what these forests were built at before the builder's
    # default became XGBoost's 256 (continuation is not about the bins)
    kw = dict(training_frame=fr, response_column="y", max_depth=3, eta=0.3,
              seed=7, booster="dart", rate_drop=0.3, nbins=20)
    m1 = XGBoost(XGBoostParameters(ntrees=8, **kw)).train_model()
    m2 = XGBoost(XGBoostParameters(ntrees=16, checkpoint=m1,
                                   **kw)).train_model()
    assert m2.ntrees == 16
    # the prior's trees ride along (first 8 feat arrays identical)
    np.testing.assert_array_equal(np.asarray(m2.forest["feat"])[:8],
                                  np.asarray(m1.forest["feat"]))
    r1 = m1.model_performance(fr).mse
    r2 = m2.model_performance(fr).mse
    assert r2 <= r1 + 1e-9, (r1, r2)
    # checkpoint from a plain gbtree forest also continues
    g1 = XGBoost(XGBoostParameters(ntrees=6, training_frame=fr,
                                   response_column="y", max_depth=3,
                                   eta=0.3, seed=7, nbins=20)).train_model()
    g2 = XGBoost(XGBoostParameters(ntrees=12, checkpoint=g1,
                                   **kw)).train_model()
    assert g2.ntrees == 12


def test_xgboost_dart_export_checkpoints(tmp_path):
    from h2o_tpu.models.xgboost import XGBoost, XGBoostParameters
    from h2o_tpu.backend.persist import load_model

    rng = np.random.default_rng(8)
    n = 600
    x = rng.normal(size=(n, 3)).astype(np.float32)
    y = (x[:, 0] + 0.1 * rng.normal(size=n)).astype(np.float32)
    fr = Frame.from_dict({f"x{i}": x[:, i] for i in range(3)} | {"y": y})
    d = str(tmp_path / "snaps")
    m = XGBoost(XGBoostParameters(training_frame=fr, response_column="y",
                                  booster="dart", rate_drop=0.3, ntrees=6,
                                  score_tree_interval=2, max_depth=3,
                                  nbins=20, seed=3, export_checkpoints_dir=d)
                ).train_model()
    import os

    snaps = sorted(os.listdir(d))
    assert len(snaps) >= 2, snaps
    snap = load_model(os.path.join(d, snaps[0]))
    assert snap.ntrees == 2
    out = snap.predict(fr).vec(0).to_numpy()
    assert np.isfinite(out).all()


def test_xgboost_gblinear():
    """booster='gblinear' fits the penalized LINEAR model on the GLM
    elastic-net path: near-exact recovery of linear signal, and the l1
    penalty actually sparsifies."""
    from h2o_tpu.models.xgboost import XGBoost, XGBoostParameters

    rng = np.random.default_rng(4)
    n = 2000
    x = rng.normal(size=(n, 5)).astype(np.float32)
    y = (2.0 * x[:, 0] - 1.0 * x[:, 1] + 0.05 * rng.normal(size=n)
         ).astype(np.float32)
    fr = Frame.from_dict({f"x{i}": x[:, i] for i in range(5)} | {"y": y})
    m = XGBoost(XGBoostParameters(training_frame=fr, response_column="y",
                                  booster="gblinear", reg_lambda=0.0,
                                  reg_alpha=0.0, seed=1)).train_model()
    assert m.booster == "gblinear"
    c = m.coef()
    assert abs(c["x0"] - 2.0) < 0.05 and abs(c["x1"] + 1.0) < 0.05
    assert m.output.training_metrics.r2 > 0.99
    # heavy l1 zeroes the noise coefficients
    ml1 = XGBoost(XGBoostParameters(training_frame=fr, response_column="y",
                                    booster="gblinear", reg_alpha=200.0,
                                    reg_lambda=0.0, seed=1)).train_model()
    cl1 = ml1.coef()
    assert abs(cl1["x3"]) < 1e-3 and abs(cl1["x4"]) < 1e-3

    # binomial response routes through the logistic elastic net
    from h2o_tpu.frame.vec import T_CAT, Vec

    lab = (y > 0).astype(np.float32)
    frb = Frame.from_dict({f"x{i}": x[:, i] for i in range(5)})
    frb.add("y", Vec.from_numpy(lab, type=T_CAT, domain=["n", "p"]))
    mb = XGBoost(XGBoostParameters(training_frame=frb, response_column="y",
                                   booster="gblinear", reg_lambda=1.0,
                                   seed=1)).train_model()
    assert mb.output.training_metrics.auc > 0.95


def test_dt_exact_splits_match_sklearn():
    """Exact-mode DT reproduces sklearn's exact-threshold tree on data whose
    values quantile binning would merge (`hex/tree/dt/DT.java` per-value
    search; VERDICT r4 missing #8)."""
    from sklearn.tree import DecisionTreeClassifier

    from h2o_tpu.frame.frame import Frame
    from h2o_tpu.frame.vec import T_CAT, Vec
    from h2o_tpu.models.dt import DT, DTParameters

    rng = np.random.default_rng(31)
    n = 800
    # 60 distinct values >> nbins default 20: binned splits would round the
    # thresholds; exact mode must find the true cut between 2.0 and 2.1
    x1 = rng.integers(0, 60, n).astype(np.float32) / 10.0
    x2 = rng.normal(size=n).astype(np.float32)     # uninformative
    y = (x1 > 2.05).astype(np.float32)
    fr = Frame.from_dict({"x1": x1, "x2": x2})
    fr.add("y", Vec.from_numpy(y, type=T_CAT, domain=["0", "1"]))
    m = DT(DTParameters(training_frame=fr, response_column="y",
                        max_depth=1, min_rows=1, seed=1)).train_model()
    pred = m.predict(fr).vec(0).to_numpy()
    sk = DecisionTreeClassifier(max_depth=1, random_state=0).fit(
        np.stack([x1, x2], 1), y)
    assert np.mean(pred == y) == 1.0          # exact cut → perfect stump
    # the root split is the same exact threshold sklearn finds: the midpoint
    # between the adjacent distinct values 2.0 and 2.1
    thr = float(np.asarray(m.forest["thr"])[0, 0])
    assert 2.0 < thr < 2.1, thr
    sk_thr = float(sk.tree_.threshold[0])
    assert abs(thr - sk_thr) < 1e-6, (thr, sk_thr)
