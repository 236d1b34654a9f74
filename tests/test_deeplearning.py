"""DeepLearning tests — analog of `hex/deeplearning/DeepLearningTest.java`."""

import numpy as np
import pytest

from h2o_tpu.frame.frame import Frame
from h2o_tpu.frame.vec import T_CAT, Vec
from h2o_tpu.models.deeplearning import DeepLearning, DeepLearningParameters


@pytest.fixture(scope="module")
def xor_frame():
    rng = np.random.default_rng(0)
    n = 800
    a = rng.random(n) > 0.5
    b = rng.random(n) > 0.5
    y = (a ^ b).astype(np.float32)
    fr = Frame.from_dict({
        "a": a.astype(np.float32) + 0.05 * rng.normal(size=n).astype(np.float32),
        "b": b.astype(np.float32) + 0.05 * rng.normal(size=n).astype(np.float32),
    })
    fr.add("y", Vec.from_numpy(y, type=T_CAT, domain=["no", "yes"]))
    return fr


def test_dl_binomial_xor(xor_frame):
    m = DeepLearning(DeepLearningParameters(
        training_frame=xor_frame, response_column="y",
        hidden=[16, 16], epochs=60, seed=42, mini_batch_size=64,
    )).train_model()
    assert m.output.training_metrics.auc > 0.95  # XOR is not linearly separable


def test_dl_regression():
    rng = np.random.default_rng(1)
    n = 600
    x = rng.normal(size=n).astype(np.float32)
    y = (np.sin(2 * x) + 0.05 * rng.normal(size=n)).astype(np.float32)
    fr = Frame.from_dict({"x": x, "y": y})
    m = DeepLearning(DeepLearningParameters(
        training_frame=fr, response_column="y", hidden=[32, 32],
        epochs=80, seed=3, mini_batch_size=64, activation="Tanh",
    )).train_model()
    assert m.output.training_metrics.rmse < 0.25
    pred = m.predict(fr)
    assert pred.nrow == n


def test_dl_multinomial():
    rng = np.random.default_rng(2)
    n = 600
    x1 = rng.normal(size=n).astype(np.float32)
    x2 = rng.normal(size=n).astype(np.float32)
    cls = (x1 > 0).astype(int) + (x2 > 0).astype(int)  # 3 classes
    fr = Frame.from_dict({"x1": x1, "x2": x2})
    fr.add("y", Vec.from_numpy(cls.astype(np.float32), type=T_CAT,
                               domain=["lo", "mid", "hi"]))
    m = DeepLearning(DeepLearningParameters(
        training_frame=fr, response_column="y", hidden=[16],
        epochs=40, seed=4, mini_batch_size=64,
    )).train_model()
    # 0.4193 on the installed jax 0.9.0. The bound is what a plain numpy
    # softmax REGRESSION reaches on the same data (0.487; the class prior
    # alone gives 1.018): the hidden layer has to earn its keep
    assert m.output.training_metrics.logloss < 0.48
    pred = m.predict(fr)
    assert pred.names[0] == "predict" and pred.ncol == 4


def test_dl_autoencoder():
    rng = np.random.default_rng(5)
    n = 400
    z = rng.normal(size=(n, 2))
    X = (z @ rng.normal(size=(2, 6))).astype(np.float32)
    fr = Frame.from_dict({f"c{i}": X[:, i] for i in range(6)})
    m = DeepLearning(DeepLearningParameters(
        training_frame=fr, autoencoder=True, hidden=[4], epochs=60,
        seed=6, mini_batch_size=64, activation="Tanh",
    )).train_model()
    anom = m.anomaly(fr)
    assert anom.names == ["Reconstruction.MSE"]
    # bottleneck of 4 >= true rank 2: reconstruction should be decent
    assert m.output.training_metrics.mse < 0.5


def test_dl_sgd_and_dropout(xor_frame):
    m = DeepLearning(DeepLearningParameters(
        training_frame=xor_frame, response_column="y",
        hidden=[16], epochs=30, seed=7, adaptive_rate=False, rate=0.05,
        activation="RectifierWithDropout", hidden_dropout_ratios=[0.2],
        input_dropout_ratio=0.05, mini_batch_size=64,
    )).train_model()
    assert m.output.training_metrics.auc > 0.8


def test_deepfeatures_layer_extraction():
    rng = np.random.default_rng(0)
    n = 300
    X = rng.normal(size=(n, 4)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] ** 2).astype(np.float32)
    cols = {f"x{j}": X[:, j] for j in range(4)}
    cols["y"] = y
    fr = Frame.from_dict(cols)
    m = DeepLearning(DeepLearningParameters(
        training_frame=fr, response_column="y", hidden=[16, 8],
        epochs=3, seed=1)).train_model()
    df0 = m.deepfeatures(fr, 0)
    df1 = m.deepfeatures(fr, 1)
    assert df0.ncol == 16 and df1.ncol == 8 and df0.nrow == n
    assert df0.names[0] == "DF.L1.C1"
    import pytest
    with pytest.raises(ValueError):
        m.deepfeatures(fr, 2)
