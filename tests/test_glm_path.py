"""The regularisation path of a ``lambda_search`` GLM is a result (ISSUE 32).

On the benchmark generator's arithmetic at 20,000 x 28, seeded, through the
estimator over REST: the program's WHOLE path against the plain reference's
own walk of the documented path (`benchmark/reference/glm_path.py`, which
imports nothing of the program): ``lambda_max`` and the grid, the lambda the
early stop ends on, and every lambda's coefficients and explained deviance.
`GET /3/GetGLMRegPath` and `getGLMRegularizationPath` return those arrays
under h2o-py's names; ``nlambdas`` -1 resolves as documented.

CPU mesh: values and counts, never a time.
"""

import numpy as np
import pytest

import h2o_tpu.api as h2o
from benchmark import datagen, manifest
from benchmark.reference import glm_path as ref
from h2o_tpu.backend.kvstore import STORE
from h2o_tpu.utils import timeline

ROWS, SEED = 20_000, 32
NAMES = list(datagen.FEATURES) + ["Intercept"]
#: largest absolute difference of a coefficient, any lambda of the path:
#: the program stops a lambda at beta_epsilon 1e-5 / objective_epsilon 1e-6
#: where the reference goes on to 1e-7 (read: 2.1e-5 at most)
COEF_TOL = 1e-4


@pytest.fixture(scope="module")
def fitted(worker_port):
    """(the estimator after its train, the server's model, the reference's
    walk, the client's path) for the new cell's configuration."""
    cfg = manifest.config_of(manifest.load(), "higgs_glm_path")
    h2o.init(port=worker_port(54611))
    try:
        fr, cols = datagen.higgs_frame(SEED, ROWS)
        params = dict(cfg["params"], nlambdas=-1)   # the documented default
        est = h2o.H2OGeneralizedLinearEstimator(**params)
        est.train(x=list(datagen.FEATURES), y=datagen.RESPONSE,
                  training_frame=h2o.get_frame(fr.key))
        walk = ref.walk(ref.Data(cols, ROWS), cfg)
        path = h2o.H2OGeneralizedLinearEstimator.getGLMRegularizationPath(est)
        one = h2o.H2OGeneralizedLinearEstimator(family="binomial", lambda_=0.0)
        one.train(x=list(datagen.FEATURES), y=datagen.RESPONSE,
                  training_frame=h2o.get_frame(fr.key))
        raw = h2o.connection().request(
            "GET", "/3/GetGLMRegPath", params={"model": one.model_id})
        yield est, STORE.get(est.model_id), walk, path, raw
    finally:
        h2o.shutdown()


def _rel(a, b):
    return float(np.max(np.abs(np.asarray(a) / np.asarray(b) - 1.0)))


def _grid(est, model, walk, path):
    """``lambda_max`` and every lambda fitted lie on the reference's grid."""
    lam = model.output.lambdas
    assert len(walk["lambdas"]) == 100
    assert _rel(lam, walk["lambdas"][: len(lam)]) < 1e-6


def _stop(est, model, walk, path):
    """The early stop ends both walks on the same lambda, short of 100."""
    assert len(model.output.lambdas) == len(walk["betas"]) < 100
    assert model.output.lambda_best_index == len(walk["betas"]) - 1
    assert model.output.lambda_best == model.output.lambdas[-1]


def _coefficients(est, model, walk, path):
    got = np.asarray(model.output.coefficients)
    want = np.stack(walk["betas"])
    assert got.shape == want.shape == (len(walk["betas"]), len(NAMES))
    assert np.max(np.abs(got - want)) < COEF_TOL
    assert not got[0, :-1].any()            # lambda_max: nothing is on
    # the model returned is the path's last lambda
    coef = model.coef()
    assert [coef[n] for n in NAMES] == list(got[-1])


def _deviance(est, model, walk, path):
    want = 1.0 - np.asarray(walk["deviances"]) / walk["null_deviance"]
    got = np.asarray(model.output.explained_deviance_train)
    assert np.max(np.abs(got - want)) < 1e-6
    assert np.all(np.diff(got) > 0)


def _client(est, model, walk, path):
    """The client's dict is h2o-py's: lists a lambda, coefficients by name
    on both scales, the arrays the server's model holds."""
    o = model.output
    assert sorted(path) == ["alphas", "coefficients", "coefficients_std",
                            "explained_deviance_train",
                            "explained_deviance_valid", "lambdas"]
    assert path["lambdas"] == list(o.lambdas)
    assert path["alphas"] == [0.5] * len(o.lambdas)
    assert path["explained_deviance_train"] == list(o.explained_deviance_train)
    assert path["explained_deviance_valid"] is None
    for key, arr in (("coefficients", o.coefficients),
                     ("coefficients_std", o.coefficients_std)):
        assert [list(c) for c in path[key]] == [NAMES] * len(o.lambdas)
        assert np.array_equal(
            np.array([[c[n] for n in NAMES] for c in path[key]]),
            np.asarray(arr))
    # standardised and natural coefficients differ by the columns' scale
    assert path["coefficients_std"][-1]["f0"] != path["coefficients"][-1]["f0"]


@pytest.mark.parametrize("check", [_grid, _stop, _coefficients, _deviance,
                                   _client])
def test_the_whole_path_against_the_plain_reference(fitted, check):
    check(*fitted[:4])


def test_a_model_fitted_without_a_search_answers_with_its_one_lambda(fitted):
    raw = fitted[4]
    assert raw["lambdas"] == [0.0] and raw["alphas"] == [0.5]
    assert raw["coefficient_names"] == NAMES
    assert len(raw["coefficients"]) == len(raw["coefficients_std"]) == 1
    assert len(raw["explained_deviance_train"]) == 1


def test_the_route_refuses_what_keeps_no_path(fitted):
    with pytest.raises(Exception, match="not found"):
        h2o.connection().request("GET", "/3/GetGLMRegPath",
                                 params={"model": "no_such_model"})


@pytest.mark.parametrize("kw,planned", [
    ({}, 100),                      # alpha's default is 0.5
    ({"alpha": 0.5}, 100),
    ({"alpha": 0.0}, 30),
    ({"alpha": 0.5, "nlambdas": 30}, 30),
])
def test_nlambdas_resolves_as_documented(kw, planned):
    """-1, the default: 100 lambdas when alpha > 0, else 30; read off the
    path span of a short job (the path is cut at its second lambda)."""
    from h2o_tpu.models.glm import GLM, GLMParameters

    assert GLMParameters().nlambdas == -1
    fr, _ = datagen.higgs_frame(SEED, 2_000)
    seq0 = timeline.total_recorded()
    m = GLM(GLMParameters(training_frame=fr, response_column=datagen.RESPONSE,
                          family="binomial", lambda_search=True,
                          max_runtime_secs=1e-3, **kw)).train_model()
    (span,) = [e for e in timeline.snapshot(since=seq0)
               if e["what"] == "train.glm.path"]
    assert span["lambdas_planned"] == planned
    assert 1 <= span["lambdas_fit"] == len(m.output.lambdas) < planned
