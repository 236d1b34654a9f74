"""Shared fixtures for the accuracy regression suite (`h2o-test-accuracy`
analog): deterministic synthetic datasets + one metric per (algo, dataset)."""

import numpy as np

from h2o_tpu.frame.frame import Frame
from h2o_tpu.frame.vec import T_CAT, Vec


def binomial_dataset(n=4000, seed=11):
    rng = np.random.default_rng(seed)
    x1 = rng.normal(size=n).astype(np.float32)
    x2 = rng.normal(size=n).astype(np.float32)
    g = rng.integers(0, 4, n)
    logits = 1.2 * x1 - 0.7 * x2 + np.array([0.5, -0.5, 1.0, -1.0])[g]
    y = (rng.random(n) < 1 / (1 + np.exp(-logits))).astype(np.float32)
    fr = Frame(["x1", "x2", "g", "y"],
               [Vec.from_numpy(x1), Vec.from_numpy(x2),
                Vec.from_numpy(g.astype(np.float32), type=T_CAT,
                               domain=["a", "b", "c", "d"]),
                Vec.from_numpy(y, type=T_CAT, domain=["no", "yes"])])
    return fr


def regression_dataset(n=4000, seed=12):
    rng = np.random.default_rng(seed)
    x1 = rng.normal(size=n).astype(np.float32)
    x2 = rng.normal(size=n).astype(np.float32)
    y = (2 * x1 + np.sin(3 * x2) + 0.2 * rng.normal(size=n)).astype(
        np.float32)
    return Frame.from_dict({"x1": x1, "x2": x2, "y": y})


def linear_fit_rmse(fr) -> float:
    """RMSE of the plain numpy least-squares fit of ``y`` on the other
    columns + intercept — the float64 reference a learned model is
    sanity-checked against."""
    y = fr.vec("y").to_numpy().astype(np.float64)
    A = np.stack([fr.vec(n).to_numpy() for n in fr.names if n != "y"]
                 + [np.ones_like(y)], axis=1).astype(np.float64)
    beta, *_ = np.linalg.lstsq(A, y, rcond=None)
    return float(np.sqrt(np.mean((A @ beta - y) ** 2)))


def multinomial_dataset(n=3000, seed=13):
    rng = np.random.default_rng(seed)
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)
    scores = np.stack([x1, x2 - 0.5 * x1, -x2 + 0.3 * x1], axis=1)
    cls = np.argmax(scores + 0.5 * rng.gumbel(size=(n, 3)), axis=1)
    fr = Frame.from_dict({"x1": x1.astype(np.float32),
                          "x2": x2.astype(np.float32)})
    fr.add("y", Vec.from_numpy(cls.astype(np.float32), type=T_CAT,
                               domain=["k0", "k1", "k2"]))
    return fr


def run_case(name):
    """→ (metric_name, value) for one named (algo, dataset) case."""
    if name == "gbm_binomial_auc":
        from h2o_tpu.models.gbm import GBM, GBMParameters

        m = GBM(GBMParameters(training_frame=binomial_dataset(),
                              response_column="y", ntrees=30, max_depth=4,
                              seed=7)).train_model()
        return "auc", float(m.output.training_metrics.auc)
    if name == "drf_binomial_auc":
        from h2o_tpu.models.drf import DRF, DRFParameters

        m = DRF(DRFParameters(training_frame=binomial_dataset(),
                              response_column="y", ntrees=30, max_depth=8,
                              seed=7)).train_model()
        return "auc", float(m.output.training_metrics.auc)
    if name == "glm_binomial_auc":
        from h2o_tpu.models.glm import GLM, GLMParameters

        m = GLM(GLMParameters(training_frame=binomial_dataset(),
                              response_column="y", family="binomial",
                              lambda_=0.0)).train_model()
        return "auc", float(m.output.training_metrics.auc)
    if name == "gbm_regression_rmse":
        from h2o_tpu.models.gbm import GBM, GBMParameters

        m = GBM(GBMParameters(training_frame=regression_dataset(),
                              response_column="y", ntrees=40, max_depth=4,
                              seed=7)).train_model()
        return "rmse", float(m.output.training_metrics.rmse)
    if name == "glm_regression_r2":
        from h2o_tpu.models.glm import GLM, GLMParameters

        m = GLM(GLMParameters(training_frame=regression_dataset(),
                              response_column="y", family="gaussian",
                              lambda_=0.0)).train_model()
        return "r2", float(m.output.training_metrics.r2)
    if name == "dl_regression_rmse":
        from h2o_tpu.models.deeplearning import (DeepLearning,
                                                 DeepLearningParameters)

        m = DeepLearning(DeepLearningParameters(
            training_frame=regression_dataset(), response_column="y",
            hidden=[32, 32], epochs=30, seed=7)).train_model()
        return "rmse", float(m.output.training_metrics.rmse)
    if name == "glm_multinomial_logloss":
        from h2o_tpu.models.glm import GLM, GLMParameters

        m = GLM(GLMParameters(training_frame=multinomial_dataset(),
                              response_column="y", family="multinomial",
                              lambda_=0.0)).train_model()
        return "logloss", float(m.output.training_metrics.logloss)
    if name == "gbm_multinomial_logloss":
        from h2o_tpu.models.gbm import GBM, GBMParameters

        m = GBM(GBMParameters(training_frame=multinomial_dataset(),
                              response_column="y", ntrees=20, max_depth=4,
                              seed=7)).train_model()
        return "logloss", float(m.output.training_metrics.logloss)
    if name == "naivebayes_binomial_accuracy":
        from h2o_tpu.models.naivebayes import (NaiveBayes,
                                               NaiveBayesParameters)

        fr = binomial_dataset()
        m = NaiveBayes(NaiveBayesParameters(
            training_frame=fr, response_column="y")).train_model()
        pred = m.predict(fr).vec(0).to_numpy()
        actual = fr.vec("y").to_numpy()
        return "accuracy", float(np.mean(pred == actual))
    if name == "kmeans_two_blob_withinss":
        from h2o_tpu.models.kmeans import KMeans, KMeansParameters

        rng = np.random.default_rng(5)
        X = np.concatenate([rng.normal(0, 0.5, (500, 3)),
                            rng.normal(4, 0.5, (500, 3))]).astype(np.float32)
        fr = Frame.from_dict({f"x{j}": X[:, j] for j in range(3)})
        m = KMeans(KMeansParameters(training_frame=fr, k=2,
                                    seed=7)).train_model()
        return "tot_withinss", float(m.output.training_metrics.tot_withinss)
    raise KeyError(name)


CASES = ["gbm_binomial_auc", "drf_binomial_auc", "glm_binomial_auc",
         "gbm_regression_rmse", "glm_regression_r2", "dl_regression_rmse",
         "glm_multinomial_logloss", "gbm_multinomial_logloss",
         "naivebayes_binomial_accuracy", "kmeans_two_blob_withinss"]
