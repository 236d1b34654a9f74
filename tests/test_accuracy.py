"""Accuracy regression suite — `h2o-test-accuracy/` analog: metrics on
deterministic datasets must stay inside the stored expectation bands
(regenerate tests/accuracy_expectations.json deliberately when an algorithm
change moves a metric)."""

import json
import os

import pytest

from accuracy_util import (CASES, linear_fit_rmse, regression_dataset,
                           run_case)

_EXPECT = json.load(open(os.path.join(os.path.dirname(__file__),
                                      "accuracy_expectations.json")))

# relative tolerance per metric kind: AUC/accuracy/R2 are bounded [0,1] and
# stable; loss metrics wiggle a bit more across backend/threading changes
_RTOL = {"auc": 0.02, "accuracy": 0.02, "r2": 0.02,
         "rmse": 0.08, "logloss": 0.08, "tot_withinss": 0.05}


@pytest.mark.parametrize("case", CASES)
def test_accuracy_band(case):
    metric, value = run_case(case)
    exp = _EXPECT[case]
    assert metric == exp["metric"]
    expected = exp["value"]
    tol = _RTOL[metric] * max(abs(expected), 1e-6)
    assert abs(value - expected) <= tol, (
        f"{case}: {metric}={value:.6f} drifted from expected "
        f"{expected:.6f} (±{tol:.6f})")
    if case == "dl_regression_rmse":
        # a pin can be re-pinned wrong; a plain least-squares fit on the
        # same data cannot — the MLP must beat the best LINEAR model
        assert value < linear_fit_rmse(regression_dataset())
