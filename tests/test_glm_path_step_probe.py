"""A lambda search's plateau test answered by the next step.

Inside a ``lambda_search`` path, while another lambda follows, `GLM._fit`
dispatches the IRLS step at each new beta at once and reads its deviance
where it used to dispatch the deviance probe: the next iteration, or the
next lambda's first, takes that step, since G and b depend on beta alone.
The path it walks is held to a copy of the probe-based loop, built here
from the same step, solve and probe programs; the dispatches and the two
path counters are counted. A fit at one lambda, whose lambda is the last
planned one, keeps the probe. CPU mesh, a few thousand rows, a weak signal
so that the path stops early, as the benchmark's does.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from h2o_tpu.frame.frame import Frame
from h2o_tpu.frame.vec import T_CAT, Vec
from h2o_tpu.models import glm
from h2o_tpu.models.glm import GLM, GLMParameters
from h2o_tpu.utils import programs, telemetry

N, F = 3000, 4
NAMES = [f"x{i}" for i in range(F)]
NLAMBDAS = 8
SOLVERS = ["IRLSM", "COORDINATE_DESCENT"]
DESIGNS = ["stored", "streamed"]


def _frame():
    rng = np.random.default_rng(42)
    X = rng.normal(size=(N, F)).astype(np.float32)
    logit = 0.5 * X[:, 0] - 0.3 * X[:, 1] + 0.2 * X[:, 0] * X[:, 2] - 0.2
    y = (rng.random(N) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    fr = Frame(list(NAMES), [Vec.from_numpy(X[:, i]) for i in range(F)])
    fr.add("y", Vec.from_numpy(y, type=T_CAT, domain=["n", "p"]))
    return fr


def _params(solver, design, **kw):
    if design == "streamed":     # every numeric pair formed in the step
        kw["interactions"] = list(NAMES)
    return GLMParameters(training_frame=_frame(), response_column="y",
                         family="binomial", solver=solver, alpha=0.5, **kw)


def _train_counted(monkeypatch, params):
    """Train, counting the step's and the probe's dispatches (through
    their factories, either design) and the IRLS dispatches the program
    registry records (what the benchmark counts); returns the counts, the
    fit's arguments and what it returned."""
    counts = {"step": 0, "probe": 0}
    seen = {}

    def counted(fn, what):
        def call(*a):
            counts[what] += 1
            return fn(*a)
        return call

    real_fit = GLM._fit
    real = {"irls": glm._make_irls_kernel, "dev": glm._make_dev_kernel,
            "step": glm._Products.step, "probe": glm._Products.probe}

    def fit(self, X, y, w, offset, family, job):
        seen["args"] = (self, X, y, w, offset, family)
        seen["out"] = real_fit(self, X, y, w, offset, family, job)
        return seen["out"]

    def irls_dispatches():
        return sum(r["dispatch_count"] for r in programs.snapshot().values()
                   if r["name"].startswith("train.glm.irls."))

    with monkeypatch.context() as m:
        m.setattr(GLM, "_fit", fit)
        m.setattr(glm, "_make_irls_kernel",
                  lambda fam: counted(real["irls"](fam), "step"))
        m.setattr(glm, "_make_dev_kernel",
                  lambda fam: counted(real["dev"](fam), "probe"))
        m.setattr(glm._Products, "step",
                  lambda s, fam: counted(real["step"](s, fam), "step"))
        m.setattr(glm._Products, "probe",
                  lambda s, fam: counted(real["probe"](s, fam), "probe"))
        before = {k: telemetry.value(f"train.glm.path.{k}")
                  for k in ("steps_as_probe", "probes")}
        registered = irls_dispatches()
        GLM(params).train_model()
        counts["registered"] = irls_dispatches() - registered
        counts.update({k: telemetry.value(f"train.glm.path.{k}") - v
                       for k, v in before.items()})
    return counts, seen["args"], seen["out"]


def _probe_loop(fit, X, y, w, offset, family):
    """The lambda loop as it was: after each solve the deviance probe at the
    new beta (after the loop where beta_epsilon ended it), every step
    dispatched by the iteration that uses it, the lambda_max pass's too.
    No bounds, no constraints; ADMM or coordinate descent."""
    p = fit.params
    stream = fit._stream
    if stream is None:
        P, step = X.shape[1], glm._make_irls_kernel(family)
        probe = glm._make_dev_kernel(family)
    else:
        P, step, probe = (stream.width - 1, stream.step(family),
                          stream.probe(family))
    Xi = jnp.concatenate([X, jnp.ones((X.shape[0], 1), jnp.float32)], axis=1)
    at = lambda b: jnp.asarray(b, jnp.float32)  # noqa: E731
    free = np.zeros(P + 1, dtype=bool)
    free[-1] = True
    beta = np.zeros(P + 1)
    b0 = float(family.init_intercept(y, w))
    beta[-1] = b0
    mu0 = family.linkinv(jnp.full_like(y, b0) + offset)
    nulldev = float(jnp.sum(family.deviance(y, mu0, w)))
    neff = float(jnp.sum(w))
    G0, b_, _, _ = step(Xi, y, w, at(beta), offset)
    grad0 = np.abs(np.asarray(b_) - np.asarray(G0) @ beta)[:-1]
    lmax = float(grad0.max()) / p.alpha / neff
    lambdas = np.geomspace(lmax, lmax * p.lambda_min_ratio, p.nlambdas)
    lo, hi = np.full(P + 1, -np.inf), np.full(P + 1, np.inf)
    admm: dict = {}
    path = []
    for lam in lambdas:
        l1, l2 = p.alpha * lam * neff, (1 - p.alpha) * lam * neff
        its, dev_final = 0, None
        for _ in range(p.max_iterations):
            G, b, dev, _ = step(Xi, y, w, at(beta), offset)
            Gn, bn = np.asarray(G, np.float64), np.asarray(b, np.float64)
            its += 1
            if p.solver == "COORDINATE_DESCENT":
                new = glm._cod_solve(Gn, bn, l1, l2, free, beta,
                                     p.beta_epsilon, lo, hi)
            else:
                new = glm._admm_solve(Gn, bn, l1, l2, free, state=admm)
            diff, beta = np.max(np.abs(new - beta)), new
            if diff < p.beta_epsilon:
                dev_final = None
                break
            dev_final = float(probe(Xi, y, w, at(beta), offset))
            if abs(float(dev) - dev_final) < p.objective_epsilon * abs(nulldev):
                break
        if dev_final is None:
            dev_final = float(probe(Xi, y, w, at(beta), offset))
        path.append((float(lam), dev_final, its, beta.copy()))
        if len(path) > 1 and path[-2][1] - dev_final < 1e-4 * abs(nulldev):
            break
    return path


@pytest.mark.parametrize("design", DESIGNS)
@pytest.mark.parametrize("solver", SOLVERS)
def test_a_path_takes_the_next_step_as_its_probe(monkeypatch, solver,
                                                 design):
    counts, args, out = _train_counted(monkeypatch, _params(
        solver, design, lambda_=None, lambda_search=True,
        nlambdas=NLAMBDAS))
    path, iterations = out[6], out[5]
    # the path stops before its last planned lambda: no probe anywhere
    assert 1 < len(path) < NLAMBDAS
    assert (args[0]._stream is None) == (design == "stored")
    assert counts["probe"] == 0
    assert counts["step"] == counts["registered"] == 1 + iterations
    assert (counts["steps_as_probe"], counts["probes"]) == (iterations, 0)
    # the same walk as the probe-based loop: lambdas, iterations a lambda,
    # stop, betas; deviances from the step's reduction, not the probe's
    want = _probe_loop(*args)
    assert [e[0] for e in path] == [e[0] for e in want]
    assert [e[2] for e in path] == [e[2] for e in want]
    np.testing.assert_allclose(np.stack([e[3] for e in path]),
                               np.stack([e[3] for e in want]),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose([e[1] for e in path], [e[1] for e in want],
                               rtol=1e-5)


@pytest.mark.parametrize("design", DESIGNS)
@pytest.mark.parametrize("solver", SOLVERS)
def test_a_one_lambda_fit_keeps_its_probe(monkeypatch, solver, design):
    """The bypass: the one lambda is the last planned one, so each
    iteration dispatches its own step and one probe, and nothing ahead."""
    counts, _, out = _train_counted(monkeypatch, _params(
        solver, design, lambda_=1e-3))
    iterations = out[5]
    assert iterations >= 2
    assert counts["step"] == counts["registered"] == iterations
    assert counts["probe"] == iterations
    assert (counts["steps_as_probe"], counts["probes"]) == (0, 0)
