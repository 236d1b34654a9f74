"""A GLM's two programs (IRLS step, deviance probe) are built once a family
and kept for the process (`glm._kept`), and the cache sweep of
`backend/jobs.py` counts only jobs that built a program.

What a second job of the same family and shapes must NOT do is the point:
enter the compile path, open a ``train.program.load`` span, build a program;
what it must do is return the first job's coefficients to the bit. Each
test starts with no kept program (`conftest._no_kept_glm_programs`)."""

import contextlib
import sys
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from h2o_tpu.backend import jobs
from h2o_tpu.backend.jobs import Job
from h2o_tpu.frame.frame import Frame
from h2o_tpu.models import glm as glm_mod
from h2o_tpu.models.glm import GLM, GLMParameters
from h2o_tpu.parallel import mesh as meshmod
from h2o_tpu.utils import compilemeter, programs, telemetry, timeline

_N = 2048


@pytest.fixture(autouse=True)
def _no_sweep_between_two_trains(monkeypatch):
    """This worker's count of jobs that built a program stands wherever
    earlier tests left it: a sweep falling due between a test's first train
    and its second would drop what the test is about. The sweep's own tests
    arm it (`sweep_every`)."""
    monkeypatch.setenv("H2O_TPU_CLEAR_CACHES_EVERY", "0")


def _xy(seed, n=_N):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 3)).astype(np.float32)
    eta = X @ np.array([0.8, -0.5, 0.3], np.float32) + 0.2
    return rng, X, eta


def _cols(X):
    return {f"x{i}": X[:, i] for i in range(X.shape[1])}


def _binomial_frame(n=_N):
    rng, X, eta = _xy(1, n)
    y = rng.uniform(size=n) < 1 / (1 + np.exp(-eta))
    return Frame.from_pandas(pd.DataFrame(
        dict(_cols(X), y=pd.Categorical(np.where(y, "1", "0")))))


def _gaussian_frame():
    rng, X, eta = _xy(2)
    return Frame.from_dict(dict(_cols(X), y=eta + rng.normal(0, 0.1, _N)))


def _count_frame():
    rng, X, eta = _xy(3)
    return Frame.from_dict(dict(
        _cols(X), y=rng.poisson(np.exp(0.5 * eta)).astype(np.float64)))


def _multinomial_frame():
    rng, X, eta = _xy(4)
    k = np.digitize(eta + rng.normal(0, 0.5, _N), [-0.3, 0.6])
    return Frame.from_pandas(pd.DataFrame(
        dict(_cols(X), y=pd.Categorical(np.array(["a", "b", "c"])[k]))))


def _glm(family, **kw):
    def build(fr):
        return GLM(GLMParameters(training_frame=fr, response_column="y",
                                 family=family, lambda_=0.0, **kw))
    return build


def _gam(fr):
    from h2o_tpu.models.gam import GAM, GAMParameters

    return GAM(GAMParameters(training_frame=fr, response_column="y",
                             gam_columns=["x0"], num_knots=6, scale=0.1,
                             family="gaussian", lambda_=0.0, alpha=0.0))


#: case -> (frame, builder)
_CASES = {
    "binomial": (_binomial_frame, _glm("binomial")),
    "gaussian": (_gaussian_frame, _glm("gaussian")),
    "poisson": (_count_frame, _glm("poisson")),
    "tweedie_1.2": (_count_frame,
                    _glm("tweedie", tweedie_variance_power=1.2)),
    "tweedie_1.5": (_count_frame,
                    _glm("tweedie", tweedie_variance_power=1.5)),
    "multinomial": (_multinomial_frame, _glm("multinomial")),
    "gam": (_gaussian_frame, _gam),
}


@contextlib.contextmanager
def _Watch():
    """What one stretch of the process did: programs through the compile
    path, ``*.program.load`` spans, and the factories' two counters."""
    w = types.SimpleNamespace()
    seq = timeline.total_recorded()
    kept = telemetry.value("train.glm.program.kept")
    built = telemetry.value("train.glm.program.built")
    with compilemeter.scoped() as sc:
        yield w
    w.compiles = sc.compiles
    w.loads = [e for e in timeline.snapshot(kind="span", since=seq)
               if e["what"].endswith("program.load")]
    w.kept = telemetry.value("train.glm.program.kept") - kept
    w.built = telemetry.value("train.glm.program.built") - built


def _beta(model) -> bytes:
    return np.asarray(model.beta).tobytes()


@pytest.mark.parametrize("case", list(_CASES))
def test_a_second_train_builds_and_loads_nothing(case):
    make_frame, build = _CASES[case]
    fr = make_frame()
    with _Watch() as first:
        m1 = build(fr).train_model()
    assert first.built >= 1 and first.loads
    with _Watch() as second:
        m2 = build(fr).train_model()
    assert second.compiles == 0
    assert second.loads == []
    assert second.built == 0 and second.kept >= 1
    assert _beta(m2) == _beta(m1)


def test_two_tweedie_powers_are_two_programs_and_two_fits():
    fr = _count_frame()
    build12, build15 = _CASES["tweedie_1.2"][1], _CASES["tweedie_1.5"][1]
    alone15 = _beta(build15(fr).train_model())
    glm_mod.drop_kept_programs()
    b12 = _beta(build12(fr).train_model())
    with _Watch() as w:
        b15 = _beta(build15(fr).train_model())
    assert w.built >= 1            # 1.5 is not handed 1.2's program
    assert b12 != b15
    assert b15 == alone15          # and fits as it does with no 1.2 before it
    with _Watch() as again:
        assert _beta(build12(fr).train_model()) == b12
        assert _beta(build15(fr).train_model()) == b15
    assert again.built == 0 and again.compiles == 0


def test_the_key_is_what_the_traced_body_reads():
    F = glm_mod
    step = F._make_irls_kernel(F.BinomialF())
    assert F._make_irls_kernel(F.BinomialF()) is step
    assert F._make_dev_kernel(F.BinomialF()) is F._make_dev_kernel(
        F.BinomialF(theta=3.0))      # a binomial reads no theta
    assert F._make_irls_kernel(F.QuasibinomialF()) is not step   # its name
    assert F._make_irls_kernel(F.BinomialF("log")) is not step   # its link
    t12 = F._make_irls_kernel(F.TweedieF(tweedie_variance_power=1.2))
    assert F._make_irls_kernel(
        F.TweedieF(tweedie_variance_power=1.2, theta=9.0)) is t12
    assert F._make_irls_kernel(
        F.TweedieF(tweedie_variance_power=1.5)) is not t12
    assert F._make_irls_kernel(F.NegBinomialF(theta=2.0)) is not \
        F._make_irls_kernel(F.NegBinomialF(theta=0.5))


def test_the_kept_program_reads_a_copy_of_the_family():
    """The caller's instance is its model's: changed later, it must not
    reach a signature the kept program traces afterwards."""
    fam = glm_mod.TweedieF(tweedie_variance_power=1.2)
    X, y, w, beta, off = _step_args(256)
    y = y + 1.0
    dev = glm_mod._make_dev_kernel(fam)
    dev(X, y, w, beta, off)
    fam.p = 1.9
    half = (X[:128], y[:128], w[:128], beta, off[:128])   # traced now
    fresh = glm_mod._make_dev_kernel.__wrapped__(
        glm_mod.TweedieF(tweedie_variance_power=1.2))
    assert float(dev(*half)) == float(fresh(*half))


def test_the_store_is_bounded():
    powers = np.linspace(1.05, 1.95, glm_mod._KEPT_PROGRAMS + 8)
    first = glm_mod._make_irls_kernel(
        glm_mod.TweedieF(tweedie_variance_power=float(powers[0])))
    for p in powers[1:]:
        glm_mod._make_irls_kernel(
            glm_mod.TweedieF(tweedie_variance_power=float(p)))
    assert len(glm_mod._KEPT) == glm_mod._KEPT_PROGRAMS
    # the least recently used went: the first power builds anew
    assert glm_mod._make_irls_kernel(glm_mod.TweedieF(
        tweedie_variance_power=float(powers[0]))) is not first


def test_concurrent_jobs_are_handed_one_program_a_key():
    """More threads than cores at a short switch interval: a lost update
    would hand two jobs two steps, or build a key twice."""
    fams = [glm_mod.BinomialF, glm_mod.PoissonF,
            lambda: glm_mod.TweedieF(tweedie_variance_power=1.3)]
    got = [[] for _ in range(32)]

    def worker(mine):
        for i in range(60):
            fam = fams[i % len(fams)]()
            mine.append((i % len(fams), id(glm_mod._make_irls_kernel(fam)),
                         id(glm_mod._make_dev_kernel(fam))))

    built = telemetry.value("train.glm.program.built")
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(g,)) for g in got]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert len({row for g in got for row in g}) == len(fams)
    assert telemetry.value("train.glm.program.built") - built == 2 * len(fams)


def _step_args(n, p=4, seed=5):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(n, p)).astype(np.float32))
    y = jnp.asarray((rng.random(n) > 0.5).astype(np.float32))
    return (X, y, jnp.ones(n, jnp.float32), jnp.zeros(p, jnp.float32),
            jnp.zeros(n, jnp.float32))


def _gram(step, args) -> bytes:
    return np.asarray(step(*args)[0]).tobytes()


def test_a_changed_row_count_builds_anew_and_the_old_one_still_dispatches():
    step = glm_mod._make_irls_kernel(glm_mod.BinomialF())
    small, large = _step_args(1024), _step_args(2048)
    with _Watch() as a:
        g_small = _gram(step, small)
    with _Watch() as b:
        _gram(step, large)
    assert len(a.loads) == 1 and len(b.loads) == 1
    with _Watch() as c:
        assert _gram(step, small) == g_small
        _gram(step, large)
    assert c.loads == [] and c.compiles == 0


def test_a_changed_mesh_builds_anew_and_the_old_one_still_dispatches():
    step = glm_mod._make_irls_kernel(glm_mod.BinomialF())
    args = _step_args(1024)
    with _Watch() as eight:
        g8 = _gram(step, args)
    one = meshmod.make_mesh(devices=jax.devices()[:1])
    with meshmod.use_mesh(one), _Watch() as single:
        one_args = tuple(jax.device_put(np.asarray(a), jax.devices()[0])
                         for a in args)
        _gram(step, one_args)
    assert len(eight.loads) == 1 and len(single.loads) == 1
    recs = {r["name"] for r in programs.snapshot().values()}
    assert {"train.glm.irls.binomial.sharded",
            "train.glm.irls.binomial"} <= recs
    with _Watch() as back:
        assert _gram(step, args) == g8
    assert back.loads == [] and back.compiles == 0


# ---------------------------------------------------------------------------
# the sweep: H2O_TPU_CLEAR_CACHES_EVERY counts jobs that built a program
# ---------------------------------------------------------------------------
@pytest.fixture
def sweep_every(monkeypatch):
    """``sweep_every(n)``: the knob set to ``n`` and the sweep's count at
    zero, as in a process whose last job has just finished."""
    def arm(n):
        monkeypatch.setenv("H2O_TPU_CLEAR_CACHES_EVERY", str(n))
        monkeypatch.setattr(jobs, "_jobs_built", 0)
        monkeypatch.setattr(jobs, "_compiles_seen", compilemeter.count())
    return arm


def _sweeps() -> float:
    return telemetry.value("jobs.cache_sweeps")


def _run_job(fn):
    job = Job("test job")
    job.start(fn, background=False)
    assert job.status == Job.DONE
    return job


_fresh = iter(range(10_000))


def _compiles_something():
    """A program no earlier call of the process has built."""
    k = float(next(_fresh))
    return float(jax.jit(lambda x: x * 3.0 + k)(jnp.ones((3,)))[0])


def test_steady_jobs_that_build_nothing_never_sweep(sweep_every):
    add = jax.jit(lambda x: x + 1.0)
    x = jnp.ones((4,))

    def work():
        return float(add(x)[0])

    work()        # the process has built what these jobs dispatch
    sweep_every(3)
    before = _sweeps()
    for _ in range(6):
        _run_job(work)
    assert _sweeps() == before
    assert jobs._jobs_built == 0


def test_three_jobs_that_each_compile_sweep_once(sweep_every):
    sweep_every(3)
    before = _sweeps()
    for _ in range(2):
        _run_job(_compiles_something)
    assert _sweeps() == before
    _run_job(_compiles_something)
    assert _sweeps() == before + 1
    for _ in range(2):
        _run_job(_compiles_something)
    assert _sweeps() == before + 1


def test_the_default_still_sweeps_at_the_64th_job_that_built(sweep_every,
                                                             monkeypatch):
    sweep_every(64)
    monkeypatch.delenv("H2O_TPU_CLEAR_CACHES_EVERY")     # the default
    before = _sweeps()
    for i in range(63):
        _run_job(_compiles_something)
        if i % 8 == 0:
            _run_job(lambda: None)         # builds nothing: not counted
    assert _sweeps() == before and jobs._jobs_built == 63
    _run_job(_compiles_something)
    assert _sweeps() == before + 1


def test_zero_never_sweeps(sweep_every):
    sweep_every(0)
    before = _sweeps()
    for _ in range(5):
        _run_job(_compiles_something)
    assert _sweeps() == before
    assert jobs._jobs_built == 0


def test_after_a_sweep_the_kept_step_rebuilds_under_the_same_id(sweep_every):
    fr = _binomial_frame()
    build = _CASES["binomial"][1]
    sweep_every(3)
    before = _sweeps()
    m1 = build(fr).train_model()                 # a job that built: 1 of 3
    irls = lambda: {pid for pid, r in programs.snapshot().items()  # noqa: E731
                    if r["name"].startswith("train.glm.irls.")}
    ids = irls()
    assert ids
    kept_step = glm_mod._make_irls_kernel(glm_mod.BinomialF())
    for _ in range(2):
        _run_job(_compiles_something)
    assert _sweeps() == before + 1
    assert len(glm_mod._KEPT) == 0
    registered = telemetry.value("programs.registered.count")
    with _Watch() as w:
        m2 = build(fr).train_model()
    assert w.built >= 2 and w.loads
    assert glm_mod._make_irls_kernel(glm_mod.BinomialF()) is not kept_step
    assert telemetry.value("programs.registered.count") > registered
    assert irls() == ids
    assert _beta(m2) == _beta(m1)
