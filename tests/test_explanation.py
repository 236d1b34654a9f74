"""Client explanation module — mirrors the assertion structure of the
reference's `testdir_misc/explain/pyunit_explain.py` (its wine/titanic
smalldata is not in-image, so the same checks run on synthetic + prostate
data): every plot verb returns a decorated result whose `.figure()` is a
matplotlib Figure, `explain`/`explain_row` return H2OExplanation, and the
varimp/model_correlation data surfaces have the documented shapes."""

import os
import tempfile

import matplotlib

matplotlib.use("Agg")
import matplotlib.pyplot  # noqa: E402
import numpy as np  # noqa: E402
import pandas as pd  # noqa: E402
import pytest  # noqa: E402

import h2o_tpu.api as h2o  # noqa: E402
from h2o_tpu.api.explanation import (H2OExplanation,  # noqa: E402
                                     _get_xy, _shorten_model_ids)

Figure = matplotlib.pyplot.Figure


@pytest.fixture(scope="module")
def cloud(worker_port):
    conn = h2o.init(port=worker_port(54591))
    yield conn
    try:
        h2o.shutdown()
    except Exception:
        pass


def _upload(df):
    fd, tmp = tempfile.mkstemp(suffix=".csv")
    os.close(fd)
    df.to_csv(tmp, index=False)
    try:
        return h2o.import_file(tmp)
    finally:
        os.unlink(tmp)


@pytest.fixture(scope="module")
def reg_frame(cloud):
    rng = np.random.default_rng(4)
    n = 500
    df = pd.DataFrame({
        "x1": rng.normal(size=n),
        "x2": rng.uniform(-2, 2, size=n),
        "c": rng.choice(["a", "b", "cc"], size=n),
    })
    eff = {"a": -1.0, "b": 0.5, "cc": 2.0}
    df["y"] = (3 * df.x1 - df.x2 ** 2
               + df.c.map(eff) + rng.normal(0, 0.3, size=n))
    return _upload(df)


@pytest.fixture(scope="module")
def reg_gbm(reg_frame):
    gbm = h2o.H2OGradientBoostingEstimator(ntrees=10, max_depth=3, seed=1)
    gbm.train(y="y", training_frame=reg_frame)
    return h2o.get_model(gbm.model_id)


@pytest.fixture(scope="module")
def bin_frame(cloud):
    rng = np.random.default_rng(5)
    n = 400
    df = pd.DataFrame({"x1": rng.normal(size=n), "x2": rng.normal(size=n)})
    df["y"] = np.where(
        rng.random(n) < 1 / (1 + np.exp(-(2 * df.x1 - df.x2))), "yes", "no")
    return _upload(df)


class TestSingleModelRegression:
    """pyunit_explain.test_explanation_single_model_regression analog."""

    def test_shap_summary(self, reg_gbm, reg_frame):
        assert isinstance(reg_gbm.shap_summary_plot(reg_frame).figure(),
                          Figure)
        matplotlib.pyplot.close()

    def test_shap_explain_row(self, reg_gbm, reg_frame):
        assert isinstance(
            reg_gbm.shap_explain_row_plot(reg_frame, 1).figure(), Figure)
        matplotlib.pyplot.close()

    def test_residual_analysis(self, reg_gbm, reg_frame):
        assert isinstance(reg_gbm.residual_analysis_plot(reg_frame).figure(),
                          Figure)
        matplotlib.pyplot.close()

    def test_pd_and_ice_plots(self, reg_gbm, reg_frame):
        for col in ["x1", "c"]:
            assert isinstance(reg_gbm.pd_plot(reg_frame, col).figure(),
                              Figure)
            assert isinstance(reg_gbm.ice_plot(reg_frame, col).figure(),
                              Figure)
        matplotlib.pyplot.close("all")

    def test_pd_plot_with_row(self, reg_gbm, reg_frame):
        assert isinstance(
            reg_gbm.pd_plot(reg_frame, "x1", row_index=3).figure(), Figure)
        matplotlib.pyplot.close()

    def test_learning_curve(self, reg_gbm):
        assert isinstance(reg_gbm.learning_curve_plot().figure(), Figure)
        for metric in ["auto", "deviance", "rmse"]:
            assert isinstance(
                reg_gbm.learning_curve_plot(metric=metric.upper()).figure(),
                Figure)
            assert isinstance(reg_gbm.learning_curve_plot(metric).figure(),
                              Figure)
        matplotlib.pyplot.close("all")

    def test_explain(self, reg_gbm, reg_frame):
        exp = reg_gbm.explain(reg_frame, render=False)
        assert isinstance(exp, H2OExplanation)
        assert "residual_analysis" in exp
        assert "varimp" in exp
        assert "pdp" in exp and len(exp["pdp"]["plots"]) > 0
        assert "ice" in exp

    def test_explain_row(self, reg_gbm, reg_frame):
        exp = reg_gbm.explain_row(reg_frame, 1, render=False)
        assert isinstance(exp, H2OExplanation)
        assert "ice" in exp and len(exp["ice"]["plots"]) > 0

    def test_get_xy(self, reg_gbm):
        x, y = _get_xy(reg_gbm)
        assert y == "y"
        assert set(x) == {"x1", "x2", "c"}


class TestMultiModel:
    """pyunit_explain.test_explanation_automl_regression analog, on an
    explicit model list + an AutoML run."""

    @pytest.fixture(scope="class")
    def models(self, reg_frame):
        out = []
        for cls, kw in [
                (h2o.H2OGradientBoostingEstimator,
                 dict(ntrees=8, max_depth=3, seed=1)),
                (h2o.H2ORandomForestEstimator,
                 dict(ntrees=8, max_depth=4, seed=2)),
                (h2o.H2OGradientBoostingEstimator,
                 dict(ntrees=4, max_depth=2, seed=3))]:
            est = cls(**kw)
            est.train(y="y", training_frame=reg_frame)
            out.append(h2o.get_model(est.model_id))
        return out

    def test_varimp_matrix(self, models):
        df = h2o.varimp(models, use_pandas=True)
        assert df.shape == (3, 3)  # 3 features x 3 models
        M, model_ids, varnames = h2o.varimp(models, num_of_features=2,
                                            use_pandas=False)
        assert M.shape == (2, 3)
        assert len(model_ids) == 3 and len(varnames) == 2

    def test_varimp_heatmap(self, models):
        assert isinstance(h2o.varimp_heatmap(models).figure(), Figure)
        matplotlib.pyplot.close()

    def test_model_correlation(self, models, reg_frame):
        df = h2o.model_correlation(models, reg_frame, use_pandas=True)
        assert df.shape == (3, 3)
        C, ids = h2o.model_correlation(models, reg_frame, use_pandas=False)
        assert C.shape == (3, 3) and len(ids) == 3
        assert np.allclose(np.diag(C), 1.0)
        assert isinstance(
            h2o.model_correlation_heatmap(models, reg_frame).figure(),
            Figure)
        matplotlib.pyplot.close()

    def test_pd_multi_plot(self, models, reg_frame):
        for col in ["x1", "c"]:
            assert isinstance(
                h2o.pd_multi_plot(models, reg_frame, col).figure(), Figure)
        matplotlib.pyplot.close("all")

    def test_explain_multi(self, models, reg_frame):
        exp = h2o.explain(models, reg_frame, render=False)
        assert isinstance(exp, H2OExplanation)
        assert "varimp_heatmap" in exp
        assert "model_correlation_heatmap" in exp
        assert "pdp" in exp

    def test_explain_row_multi(self, models, reg_frame):
        exp = h2o.explain_row(models, reg_frame, 2, render=False)
        assert isinstance(exp, H2OExplanation)
        assert "ice" in exp and len(exp["ice"]["plots"]) > 0


class TestAutoMLExplain:
    def test_automl_explain(self, bin_frame):
        # GBM+GLM keep the AutoML run CPU-mesh-fast (DRF's depth-12 trees
        # over small-data exact bins and DeepLearning grind on the virtual
        # mesh; the algos' own coverage lives in test_automl.py)
        aml = h2o.H2OAutoML(max_models=3, seed=1, nfolds=0,
                            include_algos=["GBM", "GLM"])
        aml.train(y="y", training_frame=bin_frame)
        assert isinstance(aml.varimp_heatmap().figure(), Figure)
        matplotlib.pyplot.close()
        assert isinstance(aml.varimp(use_pandas=True), pd.DataFrame)
        assert isinstance(
            aml.model_correlation_heatmap(bin_frame).figure(), Figure)
        matplotlib.pyplot.close()
        exp = aml.explain(bin_frame, render=False)
        assert isinstance(exp, H2OExplanation)
        assert "leaderboard" in exp
        assert "confusion_matrix" in exp
        exp_row = aml.explain_row(bin_frame, 0, render=False)
        assert isinstance(exp_row, H2OExplanation)

    def test_shorten_model_ids(self):
        ids = ["GBM_1_AutoML_20200316_123456", "DRF_1_AutoML_20200316_123456"]
        short = _shorten_model_ids(ids)
        assert short == ["GBM_1", "DRF_1"]
        assert len(set(short)) == len(set(ids))


class TestBinomialExplain:
    def test_binomial_model(self, bin_frame):
        gbm = h2o.H2OGradientBoostingEstimator(ntrees=6, max_depth=3, seed=1)
        gbm.train(y="y", training_frame=bin_frame)
        m = h2o.get_model(gbm.model_id)
        assert isinstance(m.shap_summary_plot(bin_frame).figure(), Figure)
        assert isinstance(m.shap_explain_row_plot(bin_frame, 0).figure(),
                          Figure)
        matplotlib.pyplot.close("all")
        exp = m.explain(bin_frame, render=False)
        assert isinstance(exp, H2OExplanation)
        assert "confusion_matrix" in exp
        assert "residual_analysis" not in exp
