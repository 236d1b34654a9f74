"""Tests for GAM, RuleFit, PSVM, ANOVA GLM, ModelSelection.

Modeled on the reference pyunits (`h2o-py/tests/testdir_algos/{gam,rulefit,
psvm,anovaglm,modelselection}`)."""

import numpy as np
import pytest

from h2o_tpu import Frame


def test_gam_fits_nonlinearity():
    from h2o_tpu.models.gam import GAM, GAMParameters

    rng = np.random.default_rng(0)
    n = 3000
    x = rng.uniform(-3, 3, n).astype(np.float32)
    z = rng.normal(size=n).astype(np.float32)
    y = (np.sin(x) * 2 + 0.5 * z + 0.1 * rng.normal(size=n)).astype(np.float32)
    fr = Frame.from_dict({"x": x, "z": z, "y": y})
    # `scale` weighs the penalty against the MEAN objective (PR 38; it was
    # added to the raw Gram, so 0.1 at 3000 rows smoothed 6000 times less)
    p = GAMParameters(training_frame=fr, response_column="y",
                      gam_columns=["x"], num_knots=10, scale=0.001,
                      family="gaussian", lambda_=0.0, alpha=0.0)
    m = GAM(p).train_model()
    r2 = m.output.training_metrics.r2
    assert r2 > 0.9, f"GAM should capture sin(x): r2={r2}"
    # a plain linear GLM can't get close on sin(x)
    from h2o_tpu.models.glm import GLM, GLMParameters
    lm = GLM(GLMParameters(training_frame=fr, response_column="y",
                           family="gaussian", lambda_=0.0)).train_model()
    assert r2 > lm.output.training_metrics.r2 + 0.2
    # predict on fresh data follows the curve
    x2 = np.linspace(-2, 2, 50).astype(np.float32)
    fr2 = Frame.from_dict({"x": x2, "z": np.zeros(50, np.float32)})
    pred = m.predict(fr2).vec("predict").to_numpy()
    assert np.corrcoef(pred, np.sin(x2) * 2)[0, 1] > 0.95


def test_gam_binomial():
    from h2o_tpu.models.gam import GAM, GAMParameters

    rng = np.random.default_rng(1)
    n = 2000
    x = rng.uniform(-3, 3, n).astype(np.float32)
    pr = 1 / (1 + np.exp(-3 * np.sin(x)))
    y = (rng.random(n) < pr).astype(np.float32)
    fr = Frame.from_dict({"x": x, "y": y})
    fr.replace("y", fr.vec("y").astype_cat(["0", "1"]))
    m = GAM(GAMParameters(training_frame=fr, response_column="y",
                          gam_columns=["x"], family="binomial",
                          num_knots=8, scale=0.5)).train_model()
    assert m.output.training_metrics.auc > 0.7


def test_rulefit_rules_and_importance():
    from h2o_tpu.models.rulefit import RuleFit, RuleFitParameters

    rng = np.random.default_rng(2)
    n = 3000
    a = rng.normal(size=n).astype(np.float32)
    b = rng.normal(size=n).astype(np.float32)
    y = ((a > 0.5) & (b < 0.0)).astype(np.float32)  # a sharp rule
    fr = Frame.from_dict({"a": a, "b": b, "y": y})
    fr.replace("y", fr.vec("y").astype_cat(["0", "1"]))
    p = RuleFitParameters(training_frame=fr, response_column="y",
                          min_rule_length=2, max_rule_length=3,
                          rule_generation_ntrees=20, seed=5,
                          family="binomial", model_type="rules_and_linear")
    m = RuleFit(p).train_model()
    assert m.output.training_metrics.auc > 0.95
    imp = m.rule_importance()
    assert len(imp) > 0
    assert "a" in imp[0]["rule"] or "b" in imp[0]["rule"]
    # prediction on a fresh frame
    pred = m.predict(fr)
    assert pred.nrow == n


@pytest.mark.parametrize("kernel", ["linear", "gaussian"])
def test_psvm(kernel):
    from h2o_tpu.models.psvm import PSVM, SVMParameters

    rng = np.random.default_rng(3)
    n = 1500
    x = rng.normal(size=(n, 2)).astype(np.float32)
    if kernel == "linear":
        y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    else:
        y = (np.sqrt((x ** 2).sum(1)) < 1.1).astype(np.float32)  # circle
    fr = Frame.from_dict({"x1": x[:, 0], "x2": x[:, 1], "y": y})
    fr.replace("y", fr.vec("y").astype_cat(["0", "1"]))
    m = PSVM(SVMParameters(training_frame=fr, response_column="y",
                           kernel_type=kernel, hyper_param=1.0,
                           seed=4)).train_model()
    acc = (m.predict(fr).vec("predict").to_numpy() == y).mean()
    assert acc > 0.9, f"{kernel} svm acc={acc}"
    assert m.sv_count > 0


def test_anovaglm_table():
    from h2o_tpu.models.anovaglm import ANOVAGLM, ANOVAGLMParameters

    rng = np.random.default_rng(4)
    n = 2000
    a = rng.normal(size=n).astype(np.float32)
    b = rng.normal(size=n).astype(np.float32)
    noise = rng.normal(size=n).astype(np.float32)
    y = (2 * a + 0.0 * b + 0.3 * noise).astype(np.float32)
    fr = Frame.from_dict({"a": a, "b": b, "y": y})
    m = ANOVAGLM(ANOVAGLMParameters(
        training_frame=fr, response_column="y", family="gaussian",
        lambda_=0.0, alpha=0.0, highest_interaction_term=1)).train_model()
    tbl = {r["term"]: r for r in m.result()}
    assert tbl["a"]["p_value"] < 0.01        # a matters
    assert tbl["b"]["p_value"] > 0.05        # b doesn't
    assert tbl["a"]["deviance"] > tbl["b"]["deviance"]


@pytest.mark.parametrize("mode", ["forward", "backward", "maxr", "allsubsets"])
def test_modelselection_finds_true_predictors(mode):
    from h2o_tpu.models.modelselection import (ModelSelection,
                                               ModelSelectionParameters)

    rng = np.random.default_rng(5)
    n = 1500
    X = rng.normal(size=(n, 5)).astype(np.float32)
    y = (3 * X[:, 0] - 2 * X[:, 3] + 0.2 * rng.normal(size=n)).astype(np.float32)
    cols = {f"x{i}": X[:, i] for i in range(5)}
    fr = Frame.from_dict(cols | {"y": y})
    m = ModelSelection(ModelSelectionParameters(
        training_frame=fr, response_column="y", mode=mode,
        max_predictor_number=3, family="gaussian")).train_model()
    res = m.result()
    two = next(r for r in res if len(r["predictors"]) == 2)
    assert set(two["predictors"]) == {"x0", "x3"}, \
        f"{mode} picked {two['predictors']}"
    assert two["r2"] > 0.95


class TestGamSplineFamilies:
    """All four reference `bs` families (GAMV3.java:263: 0=cr, 1=thin plate,
    2=monotone I-splines, 3=M/P-splines) — VERDICT r1 #10."""

    def _frame(self, n=3000, seed=4):
        rng = np.random.default_rng(seed)
        x = rng.uniform(-3, 3, n).astype(np.float32)
        z = rng.normal(size=n).astype(np.float32)
        y = (np.sin(x) + 0.5 * x + 0.5 * z
             + 0.2 * rng.normal(size=n)).astype(np.float32)
        return Frame.from_dict({"x": x, "z": z, "y": y}), y

    @pytest.mark.parametrize("bs", [0, 1, 2, 3])
    def test_family_fits_and_agrees_with_pspline(self, bs):
        from h2o_tpu.models.gam import GAM, GAMParameters

        fr, y = self._frame()
        def fit(b):
            return GAM(GAMParameters(
                training_frame=fr, response_column="y", gam_columns=["x"],
                bs=b, num_knots=8, scale=0.1,
                family="gaussian")).train_model()

        m = fit(bs)
        p = m.predict(fr).vec("predict").to_numpy()
        assert 1 - np.var(y - p) / np.var(y) > 0.6
        if bs != 3:  # families agree with the P-spline path on smooth data
            p3 = fit(3).predict(fr).vec("predict").to_numpy()
            nrmse = np.sqrt(np.mean((p - p3) ** 2)) / np.std(y)
            assert nrmse < 0.2, f"bs={bs} diverges from P-splines: {nrmse}"

    def test_monotone_isplines_nondecreasing(self):
        from h2o_tpu.models.gam import GAM, GAMParameters

        rng = np.random.default_rng(7)
        n = 3000
        x = rng.uniform(-3, 3, n).astype(np.float32)
        # noisy monotone signal: an unconstrained smoother wiggles, the
        # I-spline fit must not
        y = (2 * np.tanh(x) + 0.3 * rng.normal(size=n)).astype(np.float32)
        fr = Frame.from_dict({"x": x, "y": y})
        m = GAM(GAMParameters(training_frame=fr, response_column="y",
                              gam_columns=["x"], bs=2, num_knots=8,
                              scale=0.01, family="gaussian")).train_model()
        grid = Frame.from_dict(
            {"x": np.linspace(-3, 3, 300).astype(np.float32),
             "y": np.zeros(300, np.float32)})
        g = m.predict(grid).vec("predict").to_numpy()
        assert np.min(np.diff(g)) >= -1e-5, "monotone fit decreased"

    @pytest.mark.parametrize("bs", [0, 1, 2, 3])
    def test_mojo_roundtrip_new_families(self, bs, tmp_path):
        from h2o_tpu.models.gam import GAM, GAMParameters
        from h2o_tpu.mojo.reader import MojoModel

        fr, y = self._frame(n=1200, seed=9)
        m = GAM(GAMParameters(training_frame=fr, response_column="y",
                              gam_columns=["x"], bs=bs, num_knots=6,
                              scale=0.1, family="gaussian")).train_model()
        path = str(tmp_path / f"gam_bs{bs}.zip")
        m.save_mojo(path)
        mojo = MojoModel.load(path)
        ours = m.predict(fr).vec("predict").to_numpy()
        theirs = mojo.predict(fr)
        np.testing.assert_allclose(theirs, ours, rtol=1e-4, atol=1e-4)


class TestRuleFitStreaming:
    def test_streaming_matches_materialized(self, monkeypatch):
        """Benchmark-scale mode: the streamed (design-never-materializes)
        fit must agree with the small-data materialized path."""
        import h2o_tpu.models.rulefit as rf
        from h2o_tpu.models.rulefit import RuleFit, RuleFitParameters

        rng = np.random.default_rng(12)
        n = 4000
        x = rng.normal(size=(n, 5)).astype(np.float32)
        y = ((x[:, 0] > 0.3) & (x[:, 1] < 0.5)).astype(np.float32) \
            + 0.2 * x[:, 2] + 0.05 * rng.normal(size=n).astype(np.float32)
        fr = Frame.from_dict({f"x{i}": x[:, i] for i in range(5)} | {"y": y})
        kw = dict(training_frame=fr, response_column="y", seed=3,
                  min_rule_length=2, max_rule_length=2,
                  rule_generation_ntrees=10)
        m_small = RuleFit(RuleFitParameters(**kw)).train_model()
        assert not m_small.stream

        # force the streaming branch by shrinking the cell budget
        monkeypatch.setattr(rf, "_STREAM_CELL_BUDGET", 1)
        m_stream = RuleFit(RuleFitParameters(**kw)).train_model()
        assert m_stream.stream, "streaming mode did not engage"

        p1 = m_small.predict(fr).vec(0).to_numpy()
        p2 = m_stream.predict(fr).vec(0).to_numpy()
        # same rules, same lambda path, same solver family: predictions agree
        # to optimizer tolerance
        assert np.corrcoef(p1, p2)[0, 1] > 0.999
        assert abs(p1.mean() - p2.mean()) < 0.02
        tm1 = m_small.output.training_metrics.mse
        tm2 = m_stream.output.training_metrics.mse
        assert abs(tm1 - tm2) / max(tm1, 1e-9) < 0.1
        # rule importances populated in both modes
        ri = m_stream.rule_importance()
        assert len(ri) > 0 and all("rule" in r for r in ri)


class TestPsvmNystromAccuracyBridge:
    def test_matches_exact_kernel_svm(self):
        """The accuracy bridge for the Nystrom divergence (the reference
        solves the EXACT primal-dual ICF SVM): on data small enough to
        solve the exact RBF dual QP directly (via the constrained-GLM
        active-set solver), the Nystrom PSVM's decision function must agree
        in sign almost everywhere and correlate strongly — pinning how far
        the approximation sits from the exact machine."""
        from h2o_tpu.models.glm import _constrained_qp
        from h2o_tpu.models.psvm import PSVM, SVMParameters
        from h2o_tpu.frame.vec import T_CAT, Vec

        rng = np.random.default_rng(7)
        n = 400
        X = rng.normal(size=(n, 2)).astype(np.float64)
        yy = np.where(np.hypot(X[:, 0], X[:, 1]) < 1.1, 1.0, -1.0)  # ring
        flip = rng.random(n) < 0.03
        yy[flip] *= -1

        fr = Frame.from_dict({"x0": X[:, 0].astype(np.float32),
                              "x1": X[:, 1].astype(np.float32)})
        fr.add("y", Vec.from_numpy(((yy + 1) / 2).astype(np.float32),
                                   type=T_CAT, domain=["neg", "pos"]))
        C, gamma = 1.0, 0.5
        m = PSVM(SVMParameters(training_frame=fr, response_column="y",
                                hyper_param=C, gamma=gamma,
                                seed=1)).train_model()
        dec_nystrom = np.asarray(
            m.decision_function(m.adapt_frame(fr)))[:n]

        # exact dual: min ½αᵀQα − 1ᵀα, 0 ≤ α ≤ C, yᵀα = 0, Q = yyᵀ∘K
        d2 = ((X[:, None, :] - X[None, :, :]) ** 2).sum(-1)
        K = np.exp(-gamma * d2)
        Q = (yy[:, None] * yy[None, :]) * K
        Aeq = yy[None, :]
        ceq = np.zeros(1)
        Ain = np.vstack([np.eye(n) * -1.0, np.eye(n)])
        cin = np.concatenate([np.zeros(n), -np.full(n, C)])
        alpha = _constrained_qp(Q + 1e-8 * np.eye(n), np.ones(n),
                                Aeq, ceq, Ain, cin, max_iter=2000)
        sv = alpha > 1e-6
        on_margin = sv & (alpha < C - 1e-6)
        dec_exact_nob = (alpha * yy) @ K
        b = float(np.mean(yy[on_margin] - dec_exact_nob[on_margin])) \
            if on_margin.any() else 0.0
        dec_exact = dec_exact_nob + b

        # the bridge numbers: sign agreement and correlation
        agree = float(np.mean(np.sign(dec_nystrom) == np.sign(dec_exact)))
        corr = float(np.corrcoef(dec_nystrom, dec_exact)[0, 1])
        assert agree > 0.95, f"sign agreement vs exact SVM: {agree}"
        assert corr > 0.9, f"decision-function correlation: {corr}"
