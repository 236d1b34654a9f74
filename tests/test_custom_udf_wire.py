"""Wire-uploaded custom metric UDFs (`water/udf/CFuncRef`/`CMetricFunc`
role): a REST-only client pushes metric SOURCE to the server and any model
can reference it — closing the VERDICT r2 #6 gap (previously custom metrics
had to be in-process callables)."""

import numpy as np
import pandas as pd
import pytest

import h2o_tpu.api as h2o

PORT = 54761


@pytest.fixture(scope="module")
def fr(worker_port):
    h2o.init(port=worker_port(PORT))
    rng = np.random.default_rng(5)
    df = pd.DataFrame({"x1": rng.normal(size=300),
                       "x2": rng.normal(size=300)})
    df["y"] = 2 * df.x1 - df.x2 + 0.1 * rng.normal(size=300)
    return h2o.H2OFrame(df)


class CustomMaeFunc:
    def map(self, pred, act, w, o, model):
        return [abs(act[0] - pred[0]), 1]

    def reduce(self, l, r):  # noqa: E741
        return [l[0] + r[0], l[1] + r[1]]

    def metric(self, l):  # noqa: E741
        return l[0] / l[1]


def test_upload_class_and_train(fr):
    ref = h2o.upload_custom_metric(CustomMaeFunc, func_name="mae")
    assert ref == "python:mae=metrics.CustomMaeFunc"
    m = h2o.H2OGradientBoostingEstimator(ntrees=5, max_depth=3, seed=5,
                                         custom_metric_func=ref)
    m.train(x=["x1", "x2"], y="y", training_frame=fr)
    tm = m._model_json["output"]["training_metrics"]
    assert tm["custom_metric_name"] == "mae"
    # the custom MAE must equal the actual MAE of the model's predictions
    preds = m.predict(fr).as_data_frame()["predict"].to_numpy()
    y = fr.as_data_frame()["y"].to_numpy()
    np.testing.assert_allclose(tm["custom_metric_value"],
                               np.abs(y - preds).mean(), rtol=1e-5)


def test_upload_source_string_with_reference_template(fr):
    # the REAL h2o-py wraps the user class with a template that imports
    # water.udf and derives a Wrapper class — that exact shape must exec
    src = '''# Generated code
import water.udf.CMetricFunc as MetricFunc

class CustomRmse:
    def map(self, pred, act, w, o, model):
        d = act[0] - pred[0]
        return [d * d, 1]
    def reduce(self, l, r):
        return [l[0] + r[0], l[1] + r[1]]
    def metric(self, l):
        import math
        return math.sqrt(l[0] / l[1])

class CustomRmseWrapper(CustomRmse, MetricFunc, object):
    pass
'''
    ref = h2o.upload_custom_metric(src, class_name="CustomRmseWrapper",
                                   func_name="rmse_udf")
    assert ref == "python:rmse_udf=metrics.CustomRmseWrapper"
    m = h2o.H2OGradientBoostingEstimator(ntrees=5, max_depth=3, seed=5,
                                         custom_metric_func=ref)
    m.train(x=["x1", "x2"], y="y", training_frame=fr)
    tm = m._model_json["output"]["training_metrics"]
    preds = m.predict(fr).as_data_frame()["predict"].to_numpy()
    y = fr.as_data_frame()["y"].to_numpy()
    np.testing.assert_allclose(tm["custom_metric_value"],
                               np.sqrt(((y - preds) ** 2).mean()), rtol=1e-5)


def test_udf_sandbox_rejects_escapes(fr, tmp_path):
    marker = tmp_path / "pwned"
    evil = f'''import os
class Evil:
    def map(self, pred, act, w, o, model):
        return [0]
    def reduce(self, l, r):
        return l
    def metric(self, l):
        os.system("touch {marker}")
        return 0.0
'''
    ref = h2o.upload_custom_metric(evil, class_name="Evil",
                                   func_name="evil_udf")
    m = h2o.H2OGradientBoostingEstimator(ntrees=2, max_depth=2, seed=5,
                                         custom_metric_func=ref)
    # the import is refused at exec time, so training surfaces the error
    # (or, at minimum, the escape never runs)
    try:
        m.train(x=["x1", "x2"], y="y", training_frame=fr)
    except Exception:
        pass
    assert not marker.exists()

    # builtins like open are absent too
    evil2 = '''class Evil2:
    def map(self, pred, act, w, o, model):
        open("/tmp/should_not_exist_udf", "w").write("x")
        return [0]
    def reduce(self, l, r):
        return l
    def metric(self, l):
        return 0.0
'''
    ref2 = h2o.upload_custom_metric(evil2, class_name="Evil2",
                                    func_name="evil_udf2")
    m2 = h2o.H2OGradientBoostingEstimator(ntrees=2, max_depth=2, seed=5,
                                          custom_metric_func=ref2)
    import os

    try:
        m2.train(x=["x1", "x2"], y="y", training_frame=fr)
    except Exception:
        pass
    assert not os.path.exists("/tmp/should_not_exist_udf")

    # the AST guard refuses dunder-attribute gadget chains up front
    from h2o_tpu.models.custom_udf import exec_udf_source

    gadget = '''class G:
    def map(self, pred, act, w, o, model):
        return [0]
    def reduce(self, l, r):
        return l
    def metric(self, l):
        for c in ().__class__.__bases__[0].__subclasses__():
            pass
        return 0.0
'''
    with pytest.raises(ValueError, match="dunder"):
        exec_udf_source(gadget, "metrics.G")

    # and the kill switch disables wire UDFs entirely
    import os as _os

    _os.environ["H2O_TPU_ALLOW_WIRE_UDF"] = "0"
    try:
        with pytest.raises(PermissionError):
            exec_udf_source("class X:\n    pass\n", "metrics.X")
    finally:
        del _os.environ["H2O_TPU_ALLOW_WIRE_UDF"]
