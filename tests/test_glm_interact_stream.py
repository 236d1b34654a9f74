"""Numeric x numeric GLM interactions formed inside the IRLS step (ISSUE 40).

The streamed fit (`glm._make_interact_step`: each row block's products made
from the main design's columns in the loop body, never stored) is held to a
plain expanded-design fit in numpy float64 by the equations of
``benchmark/reference/glm_interact.py``: the product of the RAW columns,
standardised by its own mean and sample deviation, IRLS to convergence.
CPU mesh, a few thousand rows, all pairs of six columns and an explicit
``interaction_pairs`` list. Tolerances are float32's: the program sums in
float32 over a few thousand rows (relative error about 1e-6 a sum), the
reference in float64.
"""

import numpy as np
import pytest

from h2o_tpu.frame.frame import Frame
from h2o_tpu.frame.vec import T_CAT, Vec
from h2o_tpu.models.datainfo import DataInfo
from h2o_tpu.models.glm import GLM, GLMParameters, _apply_interactions
from h2o_tpu.utils import telemetry

N, F = 3000, 6
NAMES = [f"x{i}" for i in range(F)]
ALL_PAIRS = [(a, b) for i, a in enumerate(NAMES) for b in NAMES[i + 1:]]
LISTED = [("x0", "x2"), ("x3", "x1"), ("x4", "x5"), ("x0", "x5")]


def _data(seed=40):
    rng = np.random.default_rng(seed)
    # shifted and scaled columns: the products' means are not nought
    X = (rng.normal(size=(N, F)) * (1 + 0.4 * np.arange(F))
         + 0.5 * np.arange(F)).astype(np.float32)
    logit = (0.6 * X[:, 0] - 0.4 * X[:, 1] + 0.3 * X[:, 0] * X[:, 2]
             - 0.25 * X[:, 3] * X[:, 4] - 1.0)
    y = (rng.random(N) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return X, y


def _frame(X, y):
    fr = Frame(list(NAMES), [Vec.from_numpy(X[:, i]) for i in range(F)])
    fr.add("y", Vec.from_numpy(y, type=T_CAT, domain=["n", "p"]))
    return fr


def _design(X, pairs):
    idx = {n: i for i, n in enumerate(NAMES)}
    x = X.astype(np.float64)
    prods = np.stack([x[:, idx[a]] * x[:, idx[b]] for a, b in pairs], axis=1)
    return np.concatenate([x, prods], axis=1)


def _plain_fit(X, y, pairs, standardize=True):
    """The reference's equations in numpy float64: (names, raw coefficients,
    standardised coefficients, product means, product deviations,
    deviance, probabilities). An NA (NaN) in ``X`` makes its products NA;
    a column's statistics are over its values, an NA imputes to the
    column's mean (0 once standardised), as an appended column's does."""
    cols = _design(X, pairs)
    m, s = np.nanmean(cols, 0), np.nanstd(cols, 0, ddof=1)
    if not standardize:
        m, s = np.zeros_like(m), np.ones_like(s)
    Z = np.concatenate([np.nan_to_num((cols - m) / s), np.ones((N, 1))],
                       axis=1)
    beta = np.zeros(Z.shape[1])
    beta[-1] = np.log(y.mean() / (1 - y.mean()))
    for _ in range(25):
        mu = 1 / (1 + np.exp(-(Z @ beta)))
        w = mu * (1 - mu)
        beta = np.linalg.solve(Z.T @ (Z * w[:, None]),
                               Z.T @ (w * (Z @ beta) + (y - mu)))
    mu = 1 / (1 + np.exp(-(Z @ beta)))
    dev = -2 * np.sum(y * np.log(mu) + (1 - y) * np.log(1 - mu))
    raw = beta.copy()
    raw[:-1] = beta[:-1] / s
    raw[-1] = beta[-1] - np.sum(raw[:-1] * m)
    names = NAMES + [f"{a}_{b}" for a, b in pairs] + ["Intercept"]
    return names, raw, beta, m[F:], s[F:], dev, mu, (m, s)


def _auc(p, y):
    order = np.argsort(p)
    ranks = np.empty(len(p))
    ranks[order] = np.arange(1, len(p) + 1)
    pos = y == 1
    return (ranks[pos].sum() - pos.sum() * (pos.sum() + 1) / 2) / (
        pos.sum() * (~pos).sum())


def _train(fr, **kw):
    kw.setdefault("lambda_", 0.0)
    return GLM(GLMParameters(training_frame=fr, response_column="y",
                             family="binomial", **kw)).train_model()


@pytest.fixture(scope="module")
def data():
    return _data()


@pytest.mark.parametrize("how", ["interactions", "interaction_pairs"])
def test_the_streamed_fit_is_the_expanded_design_s_fit(data, how):
    X, y = data
    fr = _frame(X, y)
    pairs = ALL_PAIRS if how == "interactions" else LISTED
    kw = ({"interactions": NAMES} if how == "interactions"
          else {"interaction_pairs": LISTED})
    m = _train(fr, **kw)
    names, raw, std, pm, ps, dev, mu, _ = _plain_fit(X, y, pairs)
    assert list(m.coef()) == names
    # the products' statistics: float32 moments against float64 numpy
    got_m = np.array([m.dinfo.num_means[f"{a}_{b}"] for a, b in pairs])
    got_s = np.array([m.dinfo.num_sigmas[f"{a}_{b}"] for a, b in pairs])
    np.testing.assert_allclose(got_m, pm, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_s, ps, rtol=1e-5)
    # coefficients: the standardised scale is the solver's (float32 Gram,
    # 1e-4 of the largest); the raw scale divides by deviations of up to 12
    big = np.max(np.abs(std))
    np.testing.assert_allclose(np.array(list(m.coef_norm().values())), std,
                               atol=2e-4 * big)
    np.testing.assert_allclose(np.array(list(m.coef().values())), raw,
                               atol=2e-4 * np.max(np.abs(raw)))
    tm = m.output.training_metrics
    assert tm.residual_deviance == pytest.approx(dev, rel=1e-5)
    ll = dev / (2 * N)
    assert tm.logloss == pytest.approx(ll, rel=1e-5)
    assert tm.auc == pytest.approx(_auc(mu, y), abs=2e-4)   # binned AUC
    pred = m.predict(fr).vec(2).to_numpy()[:N]
    np.testing.assert_allclose(pred, mu, atol=2e-5)


def test_unstandardised_products_are_the_raw_products(data):
    X, y = data
    m = _train(_frame(X, y), interactions=NAMES[:3], standardize=False)
    pairs = [p for p in ALL_PAIRS if p[0] in NAMES[:3] and p[1] in NAMES[:3]]
    names, raw, *_ = _plain_fit(X, y, pairs, standardize=False)
    got = m.coef()
    np.testing.assert_allclose([got[n] for n in names], raw,
                               atol=2e-4 * np.max(np.abs(raw)))


def _with_missing(X):
    Xn = X.copy()
    Xn[::17, 1] = np.nan
    Xn[5::23, 3] = np.nan
    return Xn


@pytest.mark.parametrize("missing", [False, True])
def test_the_moments_match_the_appended_columns_rollups(data, missing):
    """The statistics `DataInfo.add_products` gives each product are what
    `DataInfo.make` gives the same product appended as a column (its
    rollup: mean, deviation with divisor n - 1), NAs in two columns or
    none: an appended product of an NA is NA and left out of its rollup."""
    X, y = data
    fr = _frame(_with_missing(X) if missing else X, y)
    specs = [{"kind": "numnum", "a": a, "b": b} for a, b in ALL_PAIRS]
    appended, extra = _apply_interactions(fr, specs)
    today = DataInfo.make(appended, NAMES + extra)
    di = DataInfo.make(fr, NAMES)
    Xd, _ = di.expand(fr)
    di.add_products(di.mark_missing(Xd, fr, ALL_PAIRS), ALL_PAIRS, fr.nrow)
    for n in extra:
        assert di.num_means[n] == pytest.approx(today.num_means[n],
                                                rel=1e-5, abs=1e-6)
        assert di.num_sigmas[n] == pytest.approx(today.num_sigmas[n],
                                                 rel=1e-5)
    assert di.expanded_names == today.expanded_names
    assert di.products == tuple(ALL_PAIRS)


def test_a_job_streams_its_pairs_and_appends_no_column(data):
    from h2o_tpu.backend.kvstore import STORE
    from h2o_tpu.utils import programs

    X, y = data
    fr = _frame(X, y)
    vecs = set(STORE.keys(of_type=Vec))
    s0 = telemetry.value("train.glm.interact.pairs_streamed")
    c0 = telemetry.value("train.glm.interact.columns_appended")
    m = _train(fr, interactions=NAMES)
    assert telemetry.value("train.glm.interact.pairs_streamed") - s0 == 15
    assert telemetry.value("train.glm.interact.columns_appended") == c0
    m.predict(fr)
    assert telemetry.value("train.glm.interact.columns_appended") == c0
    assert set(STORE.keys(of_type=Vec)) == vecs
    assert fr.names == NAMES + ["y"]
    assert any(r["name"].startswith("train.glm.irls.binomial.interact")
               for r in programs.snapshot().values())


def test_categorical_pairs_and_lbfgs_still_append(data):
    """`catnum` / `catcat` pairs and the L-BFGS solver (it takes the design
    whole) keep `_apply_interactions`; a numeric pair beside a categorical
    one is streamed."""
    X, y = data
    fr = _frame(X, y)
    fr.add("g", Vec.from_numpy((X[:, 5] > 2).astype(np.float32), type=T_CAT,
                               domain=["lo", "hi"]))
    s0 = telemetry.value("train.glm.interact.pairs_streamed")
    c0 = telemetry.value("train.glm.interact.columns_appended")
    m = _train(fr, interaction_pairs=[("g", "x0"), ("x1", "x2")])
    assert {"g_x0.hi", "x1_x2"} <= set(m.coef())
    assert telemetry.value("train.glm.interact.pairs_streamed") - s0 == 1
    assert telemetry.value("train.glm.interact.columns_appended") - c0 == 1
    assert m.dinfo.products == (("x1", "x2"),)
    assert m.predict(fr).nrow == N
    lb = _train(_frame(X, y), interactions=NAMES[:3], solver="L_BFGS")
    assert lb.dinfo.products == () and "x0_x1" in lb.coef()


def test_missing_values_stream_as_appended_columns_impute(data):
    """A pair with an NA in either column is streamed too: its main imputes
    to the column's mean and its product to the PRODUCT's, as appended
    columns impute (the plain fit's NaN columns). A model trained without
    NAs scores a frame with them the same way."""
    X, y = data
    Xn = _with_missing(X)
    c0 = telemetry.value("train.glm.interact.columns_appended")
    m = _train(_frame(Xn, y), interactions=NAMES[:4])
    assert telemetry.value("train.glm.interact.columns_appended") == c0
    pairs = [p for p in ALL_PAIRS if p[1] in NAMES[:4]]
    assert m.dinfo.products == tuple(pairs)
    names, raw, std, pm, ps, dev, mu, _ = _plain_fit(Xn, y, pairs)
    got_m = np.array([m.dinfo.num_means[f"{a}_{b}"] for a, b in pairs])
    np.testing.assert_allclose(got_m, pm, rtol=1e-5, atol=1e-5)
    big = np.max(np.abs(std))
    np.testing.assert_allclose(np.array(list(m.coef_norm().values())), std,
                               atol=2e-4 * big)
    assert m.output.training_metrics.residual_deviance == pytest.approx(
        dev, rel=1e-5)
    pred = m.predict(_frame(Xn, y)).vec(2).to_numpy()[:N]
    np.testing.assert_allclose(pred, mu, atol=2e-5)
    # trained on complete columns, scoring NAs
    clean = _train(_frame(X, y), interactions=NAMES[:4])
    _, _, std, _, _, _, _, (cm, cs) = _plain_fit(X, y, pairs)
    Z = np.nan_to_num((_design(Xn, pairs) - cm) / cs)
    want = 1 / (1 + np.exp(-(Z @ std[:-1] + std[-1])))
    got = clean.predict(_frame(Xn, y)).vec(2).to_numpy()[:N]
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_product_name_that_is_a_column_s_still_collides(data):
    X, y = data
    fr = _frame(X, y)
    fr.add("x0_x1", Vec.from_numpy(X[:, 0] * 0))
    with pytest.raises(ValueError, match="collides"):
        _train(fr, interactions=["x0", "x1"])


def test_lambda_search_and_p_values_run_on_the_streamed_step(data):
    X, y = data
    fr = _frame(X, y)
    path = _train(fr, interactions=NAMES[:4], lambda_=None,
                  lambda_search=True, nlambdas=5)
    assert path.dinfo.products and len(path.output.lambdas) >= 2
    pv = _train(fr, interactions=NAMES[:4], compute_p_values=True)
    names, raw, *_ = _plain_fit(X, y, [p for p in ALL_PAIRS
                                       if p[1] in NAMES[:4]])
    assert set(pv.p_values) == set(names)
    assert all(np.isfinite(pv.std_errs[n]) and pv.std_errs[n] > 0
               for n in names)
