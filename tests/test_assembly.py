"""H2OAssembly munging pipelines (`h2o-py/h2o/assembly.py` +
`h2o/transforms/preprocessing.py`)."""

import numpy as np
import pytest

import h2o_tpu.api as h2o
from h2o_tpu.api.assembly import (H2OAssembly, H2OBinaryOp, H2OColOp,
                                  H2OColSelect)


@pytest.fixture(scope="module")
def cloud(worker_port):
    conn = h2o.init(port=worker_port(54700))
    yield conn
    try:
        h2o.shutdown()
    except Exception:
        pass


def _frame():
    return h2o.H2OFrame({"a": [0.0, 1.0, 2.0], "b": [1.0, 2.0, 3.0],
                         "c": [10.0, 20.0, 30.0]})


def test_assembly_steps(cloud):
    fr = _frame()
    asm = H2OAssembly(steps=[
        ("select", H2OColSelect(["a", "b"])),
        ("cos_a", H2OColOp(op=h2o.H2OFrame.cos, col="a", inplace=True)),
        ("b_plus", H2OBinaryOp(op="+", col="b", right=10.0, inplace=False,
                               new_col_name="b10")),
    ])
    out = asm.fit(fr)
    df = out.as_data_frame()
    assert list(df.columns) == ["a", "b", "b10"]
    np.testing.assert_allclose(df["a"], np.cos([0, 1, 2]), atol=1e-6)
    np.testing.assert_allclose(df["b10"], [11, 12, 13])


def test_assembly_save_load_roundtrip(cloud, tmp_path):
    asm = H2OAssembly(steps=[
        ("select", H2OColSelect(["a", "c"])),
        ("log_c", H2OColOp(op="log", col="c", inplace=False)),
        ("a_x2", H2OBinaryOp(op="*", col="a", right=2.0, inplace=True)),
    ])
    p = str(tmp_path / "asm.json")
    asm.save(p)
    again = H2OAssembly.load(p)
    df = again.fit(_frame()).as_data_frame()
    assert list(df.columns) == ["a", "c", "c0"]
    np.testing.assert_allclose(df["a"], [0, 2, 4])
    np.testing.assert_allclose(df["c0"], np.log([10, 20, 30]), atol=1e-6)


def test_unary_math_surface(cloud):
    fr = _frame()
    df = fr["b"].sqrt().as_data_frame()
    np.testing.assert_allclose(df.iloc[:, 0], np.sqrt([1, 2, 3]), atol=1e-6)
    assert abs(fr["b"].log().sum() - np.log([1, 2, 3]).sum()) < 1e-5
