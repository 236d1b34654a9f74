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


# ---------------------------------------------------------------------------
# the step's Pallas kernel (`interact.interaction_gram`), interpreted on the CPU:
# (G, b) against numpy's float64 Gram of the explicitly built design, and
# the step's (G, b, deviance, neff) around it
# ---------------------------------------------------------------------------
def _kernel_case(case):
    """(layout, X, y, w, beta, xshift, xscale, pshift, pscale, budget)."""
    rng = np.random.default_rng(41)
    R, Pm, k = 2048, 5, 5
    cols, budget = tuple(range(5)), None
    pairs = tuple((i, j) for i in range(k) for j in range(i + 1, k))
    if case == "partial_tile":
        R = 3001
    if case == "cols_subset":        # paired columns 0, 2, 3, 5, 6 of 7
        Pm, cols = 7, (0, 2, 3, 5, 6)
        pairs = ((0, 1), (0, 3), (1, 2), (1, 4), (2, 3), (3, 4))
    if case == "gram_blocks":        # 16 columns: P1 + 1 = 138 -> 2 x 128
        Pm = k = 16
        cols = tuple(range(k))
        pairs = tuple((i, j) for i in range(k) for j in range(i + 1, k))
        budget = 1 << 20
    X = (rng.normal(size=(R, Pm + 1)) * 1.3 + 0.2).astype(np.float32)
    X[:, -1] = 1.0
    if case == "nan":
        X[::13, cols[1]] = np.nan
        X[5::29, cols[3]] = np.nan
    y = (rng.random(R) < 0.4).astype(np.float32)
    w = np.ones(R, np.float32)
    if case == "zero_weights":
        w[R // 3:R // 2] = 0.0
        w[::7] = 0.0
    xshift = rng.normal(size=len(cols)).astype(np.float32)
    xscale = (0.5 + rng.random(len(cols))).astype(np.float32)
    pshift = rng.normal(size=len(pairs)).astype(np.float32)
    pscale = (0.5 + 2 * rng.random(len(pairs))).astype(np.float32)
    if case == "unstandardised":
        xshift, xscale = np.zeros_like(xshift), np.ones_like(xscale)
        pshift, pscale = np.zeros_like(pshift), np.ones_like(pscale)
    beta = (0.05 * rng.normal(size=Pm + len(pairs) + 1)).astype(np.float32)
    return ((Pm, cols, pairs), X, y, w, beta, xshift, xscale, pshift,
            pscale, budget)


def _built_design(layout, X, xshift, xscale, pshift, pscale):
    """The float32 ``[mains, products, 1]`` design the kernel forms, built
    here in numpy by the kernel's own float32 steps, NaN set to 0."""
    Pm, cols, pairs = layout
    x = X[:, list(cols)] * xscale + xshift
    ia, ib = (np.array(a) for a in zip(*pairs))
    prods = (x[:, ia] * x[:, ib] - pshift) * (np.float32(1) / pscale)
    Z = np.concatenate([X[:, :Pm], prods, X[:, Pm:]], axis=1)
    return np.where(np.isnan(Z), np.float32(0), Z).astype(np.float32)


def _bf16(a):
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(a, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32), np.float64)


@pytest.mark.parametrize("case", ["partial_tile", "nan", "zero_weights",
                                  "cols_subset", "unstandardised",
                                  "gram_blocks"])
def test_the_kernel_is_the_built_design_s_gram(case):
    """The kernel with its Gram's operands rounded to bfloat16 (the chip's
    precision) against the float64 Gram of the same bfloat16 operands and
    the float64 sum of its float32 right-hand side; the step (float32
    operands where the kernel is interpreted, as the step's matmul had
    there) against the float64 Gram, right-hand side and deviance of the
    unrounded design. Tolerances, of the largest entry: the kernel's Gram
    3e-5 (float32 sums, about 1e-7, and the few operands whose float32
    value it forms one ulp apart from numpy, a fused multiply-add, and so
    rounds to the other bfloat16 neighbour: up to 1.5e-6 on these cases;
    the unrounded Gram lies 1e-4 to 3e-4 away, so a kernel that skipped
    the rounding fails); every float32 sum 3e-6 (read: up to 3.4e-7; a
    right-hand side from bfloat16 operands lies 1e-4 and more away)."""
    import jax.numpy as jnp

    from h2o_tpu.backend.kernels import interact
    from h2o_tpu.models import glm

    (layout, X, y, w, beta, xshift, xscale, pshift, pscale,
     budget) = _kernel_case(case)
    plan = interact.plan(X.shape[0], layout, budget)
    if case == "gram_blocks":
        assert plan.width // plan.block == 2
    if case == "partial_tile":
        assert X.shape[0] % plan.tile
    Z = _built_design(layout, X, xshift, xscale, pshift, pscale)
    Z64 = Z.astype(np.float64)
    eta = Z64 @ beta.astype(np.float64)
    mu = 1 / (1 + np.exp(-eta))
    W = w * mu * (1 - mu)
    z = eta + (y - mu) / (mu * (1 - mu))
    W32, z32 = W.astype(np.float32), z.astype(np.float32)
    small = tuple(map(jnp.asarray, (xshift, xscale, pshift, pscale)))

    G, b = interact.interaction_gram(
        jnp.asarray(X), jnp.asarray(W32), jnp.asarray(z32), *small, layout,
        budget=budget, operands=jnp.bfloat16)
    ZW32 = Z * W32[:, None]
    want_G = _bf16(ZW32).T @ _bf16(Z)
    want_b = ZW32.astype(np.float64).T @ z32
    np.testing.assert_allclose(np.asarray(G), want_G,
                               atol=3e-5 * np.abs(want_G).max())
    np.testing.assert_allclose(np.asarray(b), want_b,
                               atol=3e-6 * np.abs(want_b).max())

    step = glm._make_interact_step(glm.BinomialF(), layout).one_shard
    G, b, dev, neff = step(jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
                           jnp.asarray(beta), jnp.zeros(X.shape[0]), *small)
    ZW = Z64 * W[:, None]
    want_G, want_b = ZW.T @ Z64, ZW.T @ z
    np.testing.assert_allclose(np.asarray(G), want_G,
                               atol=3e-6 * np.abs(want_G).max())
    np.testing.assert_allclose(np.asarray(b), want_b,
                               atol=3e-6 * np.abs(want_b).max())
    want_dev = -2 * np.sum(w * (y * np.log(mu) + (1 - y) * np.log(1 - mu)))
    assert float(dev) == pytest.approx(want_dev, rel=1e-5)
    assert float(neff) == pytest.approx(w.sum(), rel=1e-6)


def test_only_a_streamed_step_imports_pallas():
    """`jax.experimental.pallas` costs about a second to import; a plain
    GLM and a GBM never pay it (one fresh process trains both, then a GLM
    with streamed products, which must)."""
    import os
    import subprocess
    import sys

    code = """
import sys
import numpy as np
from h2o_tpu.frame.frame import Frame
from h2o_tpu.frame.vec import T_CAT, Vec
from h2o_tpu.models.gbm import GBM, GBMParameters
from h2o_tpu.models.glm import GLM, GLMParameters

rng = np.random.default_rng(0)
X = rng.normal(size=(2000, 3)).astype(np.float32)
fr = Frame(["a", "b", "c"], [Vec.from_numpy(X[:, i]) for i in range(3)])
fr.add("y", Vec.from_numpy((X[:, 0] > 0).astype(np.float32), type=T_CAT,
                           domain=["n", "p"]))
pallas = lambda: sorted(m for m in sys.modules if m.startswith("jax._src.pallas"))
GLM(GLMParameters(training_frame=fr, response_column="y",
                  family="binomial", lambda_=0.0)).train_model()
GBM(GBMParameters(training_frame=fr, response_column="y", ntrees=2,
                  max_depth=2)).train_model()
print("plain", len(pallas()))
GLM(GLMParameters(training_frame=fr, response_column="y", family="binomial",
                  lambda_=0.0, interactions=["a", "b"])).train_model()
print("streamed", len(pallas()) > 0)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.split("\n")
    assert "plain 0" in lines and "streamed True" in lines, out.stdout
