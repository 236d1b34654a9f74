"""GLM — generalized linear models via distributed Gram + IRLS.

Analog of `hex/glm/GLM.java` (5,331 LoC), `hex/glm/GLMTask.java` (the
`GLMIterationTask` computing XᵀWX and XᵀWz in one distributed pass,
`GLMTask.java:35-37,1398`), `hex/gram/Gram.java` (distributed Gram + Cholesky)
and `hex/optimization/ADMM.java` (elastic-net solve).

TPU-native structure (SURVEY.md §7.6b): the expensive part — the Gram matrix
XᵀWX and vector XᵀWz — is ONE jitted einsum over the row-sharded design matrix;
XLA inserts the psum over ICI (this replaces the whole GLMIterationTask
map/reduce). The small P×P solve runs on host per iteration, exactly like the
reference's home-node Cholesky (`hex/glm/GLM.java:1743`). Elastic net uses ADMM
with soft-thresholding over the factorized Gram (the `L1Solver` design);
`lambda_search` walks a geometric λ path warm-starting each solution.

Families: gaussian, binomial, quasibinomial, poisson, gamma, tweedie,
negativebinomial, multinomial (per-class block IRLS, the reference's multiclass
coordinate approach), ordinal (proportional odds, device gradient descent —
the reference's GRADIENT_DESCENT_LH role), HGLM (random-intercept mixed model
via device one-hot cross-products + host Henderson/EM solve).
"""

from __future__ import annotations

import collections
import contextlib
import copy
import functools
import threading
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as _P

from ..backend.jobs import Job
from ..backend.memory import hbm_span_attrs
from ..frame.frame import Frame
from ..frame.vec import Vec
from ..utils import telemetry
from .datainfo import DataInfo
from .model_base import Model, ModelBuilder, ModelOutput, Parameters, make_metrics


# ---------------------------------------------------------------------------
# family/link definitions (hex/glm/GLMModel.GLMParameters.Family + Link)
# ---------------------------------------------------------------------------
class Family:
    name = "gaussian"
    default_link = "identity"
    #: the numeric attributes `variance` / `deviance` read: with the class
    #: and `link_name`, everything a traced program sees of a family, and so
    #: what a kept program is keyed by (`_program_key`)
    program_params: tuple = ()

    def __init__(self, link=None, **kw):
        self.link_name = link or self.default_link
        self.params = kw

    # link-scale helpers (vectorized, jittable)
    def linkinv(self, eta):
        return _LINKINV[self.link_name](eta)

    def dmu_deta(self, eta):
        return _DMUDETA[self.link_name](eta)

    def variance(self, mu):
        return jnp.ones_like(mu)

    def deviance(self, y, mu, w):
        return w * (y - mu) ** 2

    def init_intercept(self, y, w):
        ybar = jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-10)
        return _LINK[self.link_name](jnp.clip(ybar, 1e-6, None)
                                     if self.link_name == "log" else ybar)


class GaussianF(Family):
    name = "gaussian"

    def init_intercept(self, y, w):
        return jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-10)


class BinomialF(Family):
    name = "binomial"
    default_link = "logit"

    def variance(self, mu):
        return mu * (1 - mu)

    def deviance(self, y, mu, w):
        mu = jnp.clip(mu, 1e-10, 1 - 1e-10)
        return -2 * w * (y * jnp.log(mu) + (1 - y) * jnp.log(1 - mu))

    def init_intercept(self, y, w):
        p = jnp.clip(jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-10), 1e-6, 1 - 1e-6)
        return jnp.log(p / (1 - p))


class QuasibinomialF(BinomialF):
    name = "quasibinomial"


class PoissonF(Family):
    name = "poisson"
    default_link = "log"

    def variance(self, mu):
        return jnp.maximum(mu, 1e-10)

    def deviance(self, y, mu, w):
        mu = jnp.maximum(mu, 1e-10)
        return 2 * w * (jnp.where(y > 0, y * jnp.log(y / mu), 0.0) - (y - mu))


class GammaF(Family):
    name = "gamma"
    default_link = "log"

    def variance(self, mu):
        return jnp.maximum(mu * mu, 1e-10)

    def deviance(self, y, mu, w):
        mu = jnp.maximum(mu, 1e-10)
        ys = jnp.maximum(y, 1e-10)
        return 2 * w * (-jnp.log(ys / mu) + (y - mu) / mu)


class TweedieF(Family):
    name = "tweedie"
    default_link = "log"
    program_params = ("p",)

    def __init__(self, link=None, tweedie_variance_power=1.5, **kw):
        super().__init__(link, **kw)
        self.p = tweedie_variance_power

    def variance(self, mu):
        return jnp.power(jnp.maximum(mu, 1e-10), self.p)

    def deviance(self, y, mu, w):
        p = self.p
        mu = jnp.maximum(mu, 1e-10)
        yp = jnp.maximum(y, 0.0)
        return 2 * w * (jnp.power(yp, 2 - p) / ((1 - p) * (2 - p))
                        - y * jnp.power(mu, 1 - p) / (1 - p)
                        + jnp.power(mu, 2 - p) / (2 - p))


class NegBinomialF(Family):
    name = "negativebinomial"
    default_link = "log"
    program_params = ("theta",)

    def __init__(self, link=None, theta=1.0, **kw):
        super().__init__(link, **kw)
        self.theta = theta

    def variance(self, mu):
        return jnp.maximum(mu + self.theta * mu * mu, 1e-10)

    def deviance(self, y, mu, w):
        t = 1.0 / self.theta
        mu = jnp.maximum(mu, 1e-10)
        return 2 * w * (jnp.where(y > 0, y * jnp.log(y / mu), 0.0)
                        - (y + t) * jnp.log((y + t) / (mu + t)))


_LINK = {
    "identity": lambda mu: mu,
    "log": lambda mu: jnp.log(jnp.maximum(mu, 1e-10)),
    "logit": lambda mu: jnp.log(jnp.clip(mu, 1e-10, 1 - 1e-10)
                                / (1 - jnp.clip(mu, 1e-10, 1 - 1e-10))),
    "inverse": lambda mu: 1.0 / jnp.where(jnp.abs(mu) < 1e-10, 1e-10, mu),
}
_LINKINV = {
    "identity": lambda eta: eta,
    "log": lambda eta: jnp.exp(jnp.clip(eta, -30, 30)),
    "logit": lambda eta: 1 / (1 + jnp.exp(-eta)),
    "inverse": lambda eta: 1.0 / jnp.where(jnp.abs(eta) < 1e-10, 1e-10, eta),
}
_DMUDETA = {
    "identity": lambda eta: jnp.ones_like(eta),
    "log": lambda eta: jnp.exp(jnp.clip(eta, -30, 30)),
    "logit": lambda eta: (lambda p: p * (1 - p))(1 / (1 + jnp.exp(-eta))),
    "inverse": lambda eta: -1.0 / jnp.maximum(eta * eta, 1e-10),
}

_FAMILIES = {c.name: c for c in
             [GaussianF, BinomialF, QuasibinomialF, PoissonF, GammaF, TweedieF,
              NegBinomialF]}


# ---------------------------------------------------------------------------
# device kernels
# ---------------------------------------------------------------------------
@jax.jit
def _iteration_kernel_args(X, y, w, beta, linkname_id):  # pragma: no cover
    raise RuntimeError("placeholder")


def _row_shardable(X, mesh) -> bool:
    """True when a design matrix can dispatch through the MRTask-shaped
    shard_map Gram on ``mesh``'s rows axis: committed to that mesh (or
    uncommitted) and NOT feature-parallel (a cols-partitioned design —
    `_shard_cols` — keeps the GSPMD einsum path that shards the Gram over
    the feature axis too)."""
    sh = getattr(X, "sharding", None)
    m = getattr(sh, "mesh", None)
    if m is not None and m != mesh:
        return False
    spec = getattr(sh, "spec", None)
    if spec is not None and len(spec) > 1 and spec[1] is not None:
        return False
    return True


def _step_row_shards(X, w, offset, mesh) -> int:
    """Row shards the IRLS step runs over: the mesh's when the design goes
    through ``shard_map`` (rows divide, plain row vectors, `_row_shardable`),
    else 1 — the jit/GSPMD program sees the whole design."""
    from ..parallel.mesh import n_row_shards

    ns = n_row_shards(mesh)
    if (ns > 1 and X.shape[0] % ns == 0 and jnp.ndim(w) == 1
            and jnp.ndim(offset) == 1 and _row_shardable(X, mesh)):
        return ns
    return 1


def _gram_plan_attrs(X, w, offset) -> dict:
    """``train.glm.gram``'s attributes: the row blocks `gram_accumulate`
    cuts the design into inside the step (a shard's rows under
    ``shard_map``), from `gram.block_plan` on the shapes alone."""
    from ..backend.kernels import gram as gram_kernels
    from ..parallel.mesh import default_mesh

    ns = _step_row_shards(X, w, offset, default_mesh())
    nblk, rb, tail = gram_kernels.block_plan(X.shape[0] // ns, X.shape[1])
    return {"gram_blocks": nblk, "gram_block_rows": rb,
            "gram_tail_rows": tail}


#: programs the process keeps (an IRLS step and a deviance probe a family).
#: A search over `tweedie_variance_power` or `theta` builds a pair a value:
#: beyond this many the least recently used goes, with its executables
_KEPT_PROGRAMS = 32
_KEPT: collections.OrderedDict = collections.OrderedDict()
_KEPT_LOCK = threading.Lock()


def _program_key(family: Family) -> tuple:
    """What a traced IRLS step or probe reads of ``family``, and nothing
    else: two instances with equal keys share a program, a tweedie power of
    1.2 and one of 1.5 do not."""
    return (type(family), family.link_name,
            tuple(getattr(family, a) for a in family.program_params))


def _kept(build):
    """A program factory whose product lives as long as the process needs
    it: ``build(family)`` runs once a `_program_key`, and every later call,
    from any job, is handed the object it returned — jitted functions,
    their `programs.Tracked` wrappers and, through them, the executables of
    every signature dispatched so far. A job of a family and shapes the
    process has trained before therefore traces, lowers and loads nothing
    (`train.glm.program.kept` counts such a call, `.built` the other kind;
    rebuilt per train, the two programs were 0.17-0.20 s of a 0.43 s job).
    The program closes over a COPY of the family: the caller's instance
    belongs to its model. `drop_kept_programs` empties the store."""

    @functools.wraps(build)
    def factory(family: Family):
        key = (build.__name__,) + _program_key(family)
        with _KEPT_LOCK:
            prog = _KEPT.get(key)
            if prog is None:
                prog = _KEPT[key] = build(copy.copy(family))
                if len(_KEPT) > _KEPT_PROGRAMS:
                    _KEPT.popitem(last=False)
                telemetry.inc("train.glm.program.built")
            else:
                _KEPT.move_to_end(key)
                telemetry.inc("train.glm.program.kept")
        return prog

    return factory


def drop_kept_programs() -> None:
    """Forget every kept program (`backend/jobs.py`'s sweep, with the other
    stores of compiled programs): the next train of a family builds anew.
    A job in flight keeps the objects it was handed."""
    with _KEPT_LOCK:
        _KEPT.clear()


@_kept
def _make_irls_kernel(family: Family):
    """One GLMIterationTask: (X, y, w, beta, offset) -> (Gram, XWz, dev, neff).
    Built once a family and kept (`_kept`): callers share the step, its
    per-mesh ``sharded`` table and its executables.

    X is row-sharded; the Gram/XWz accumulation routes through the
    kernels layer (`backend/kernels/gram.py`): XᵀWX and XᵀWz accumulate in
    ONE pass over row blocks sliced out of the design in place — the (R, P)
    weighted design never materializes.

    Dispatch is the DrJAX MapReduce shape on a multi-shard mesh: the whole
    step runs inside ``mesh.shard_map`` over the ``rows`` axis — each
    device feeds ONLY its local row shard through the kernels layer (the
    per-block math is shard-size-agnostic, so it slots in unchanged) and
    the (P,P)/(P,) partials ride ONE ``psum`` over ICI, exactly
    `GLMTask.java:35-37`'s map + cluster reduce. Feature-parallel designs
    (`_shard_cols`) and row counts that don't divide the shard count keep
    the jit/GSPMD fallback. Sharded-vs-single coefficients agree to
    reduction-order ulps (the psum combines per-shard partial Grams in a
    different order than one device's sequential block pass) — pinned at
    tolerance in tests/test_sharded_frames.py."""
    from ..backend.kernels import gram as gram_kernels
    from ..parallel.mesh import ROWS, default_mesh, shard_map

    # device scopes (telemetry.SCOPES) split the step in a capture; the
    # function keeps its name: the XLA module `jit__core` is read by name
    @telemetry.program("_core")
    def _core(X, y, w, beta, offset):
        with telemetry.scope("glm.eta"):
            eta = X @ beta + offset
            mu = family.linkinv(eta)
            d = family.dmu_deta(eta)
            V = family.variance(mu)
            W = w * d * d / jnp.maximum(V, 1e-10)
            z = eta - offset + (y - mu) / jnp.where(jnp.abs(d) < 1e-10,
                                                    1e-10, d)
        G, b = gram_kernels.gram_accumulate(X, W, z)   # scope glm.gram
        with telemetry.scope("glm.deviance"):
            dev = jnp.sum(family.deviance(y, mu, w))
        return G, b, dev, jnp.sum(w)

    from ..utils import programs

    fam = getattr(family, "name", "family")
    # cost-registry instrumentation at the IRLS choke point: the tracked
    # wrapper registers each compiled step's flops/bytes/memory under a
    # stable id and degrades to the plain jit dispatch on any signature
    # the AOT executable rejects (utils/programs.py)
    jit_step = programs.tracked(f"train.glm.irls.{fam}", jax.jit(_core),
                                "train")
    sharded: dict = {}

    def step(X, y, w, beta, offset):
        mesh = default_mesh()
        ns = _step_row_shards(X, w, offset, mesh)
        if ns > 1:
            prog = sharded.get(mesh)
            if prog is None:
                @telemetry.program("glm_irls_sharded")
                def spmd(X, y, w, beta, offset):
                    out = _core(X, y, w, beta, offset)
                    return tuple(jax.lax.psum(o, ROWS) for o in out)

                prog = programs.tracked(
                    f"train.glm.irls.{fam}.sharded",
                    jax.jit(shard_map(
                        spmd, mesh=mesh,
                        in_specs=(_P(ROWS, None), _P(ROWS), _P(ROWS), _P(),
                                  _P(ROWS)),
                        out_specs=(_P(), _P(), _P(), _P()),
                        check_vma=False)),
                    "train", shards=ns)
                # the step is shared: of two jobs that built at once, one wins
                prog = sharded.setdefault(mesh, prog)
            return prog(X, y, w, beta, offset)
        return jit_step(X, y, w, beta, offset)

    return step


@_kept
def _make_dev_kernel(family: Family):
    """Deviance-only probe: one matvec + the family deviance — ~P× cheaper
    than a full GLMIterationTask. The IRLS loop uses it to detect the
    deviance plateau WITHOUT paying the Gram a converged solution no
    longer needs (the historic loop burned one full Gram pass per lambda
    purely to confirm convergence — a third of RuleFit's lasso-path
    wall). Built once a family and kept (`_kept`): the jitted function's
    own cache holds a signature's executable from job to job."""

    @jax.jit
    @telemetry.program("glm_probe")
    def dev_eval(X, y, w, beta, offset):
        with telemetry.scope("glm.eta"):
            mu = family.linkinv(X @ beta + offset)
        with telemetry.scope("glm.deviance"):
            return jnp.sum(family.deviance(y, mu, w))

    return dev_eval


def _admm_solve(G, b, l1, l2, free: np.ndarray, rho=None, iters=500, tol=1e-6,
                state: dict | None = None):
    """Elastic-net solve of ½βᵀGβ − bᵀβ + l1·|β|₁ + ½l2·‖β‖² on host.

    `free` marks unpenalized coefficients (intercept). Mirrors
    `hex/optimization/ADMM.java` L1Solver over the Cholesky of (G + (l2+ρ)I).

    ``state`` (a mutable dict the caller keeps across calls) warm-starts
    the (z, u) ADMM iterates from the previous solve — an IRLS/lambda-path
    caller re-solves an almost-unchanged problem every call, and a cold
    (0, 0) start re-pays the iterations the previous solve already did.
    Convergence criterion and tolerance are unchanged; the problem is
    convex, so the warm start changes only the iteration count, not the
    tolerance the returned solution satisfies."""
    P = G.shape[0]
    ridge = np.diag(np.where(free, 0.0, l2))  # the intercept is not shrunk
    if l1 <= 0:
        A = G + ridge
        A[np.diag_indices(P)] += 1e-8
        return np.linalg.solve(A, b)
    # rho on the Gram's own scale keeps the x-update well conditioned and the
    # soft threshold l1/rho small relative to coefficient magnitudes.
    rho = rho or max(float(np.mean(np.diag(G))), l1, 1e-3)
    A = G + ridge + rho * np.eye(P)
    # one inversion, then the x-update is a matvec: numpy's generic solve
    # re-factorizes every call (it cannot exploit triangularity), which made
    # the ADMM loop O(iters·P³) — RuleFit's ~600-rule Gram measured 170 s in
    # exactly this loop before the hoist
    Ainv = np.linalg.inv(A + 1e-8 * np.eye(P))
    z = np.zeros(P)
    u = np.zeros(P)
    if state and "z" in state and state["z"].shape == (P,):
        z = state["z"].copy()
        u = state["u"].copy()
    thr = np.where(free, 0.0, l1 / rho)
    for _ in range(iters):
        beta = Ainv @ (b + rho * (z - u))
        z_new = np.clip(np.abs(beta + u) - thr, 0, None) * np.sign(beta + u)
        u = u + beta - z_new
        # converged when both primal (beta≈z) and dual (z stable) residuals die
        if (np.max(np.abs(z_new - z)) < tol
                and np.max(np.abs(beta - z_new)) < tol * max(1.0, np.abs(z_new).max())):
            z = z_new
            break
        z = z_new
    if state is not None:
        state["z"], state["u"] = z.copy(), u.copy()
    return z


@functools.partial(jax.jit, static_argnames=("max_iter",))
def _cod_kernel(G, xy, beta0, diag_inv, thr, lo, hi, eps2, max_iter: int):
    """One compiled COD program: Gauss-Seidel sweeps (lax.scan over
    coordinates) inside a convergence while_loop. Carries (beta, grads) with
    grads[j] = xy[j] − Σ_k G[j,k]β_k + G[j,j]β_j — exactly the reference's
    CODGradients invariant (`hex/glm/ComputationState.java:1356`), updated
    per accepted coordinate like `GLM.doUpdateCD` (grads[j] itself stays
    put: its own-diagonal term is excluded by construction)."""
    P = G.shape[0]
    eye = jnp.eye(P, dtype=G.dtype)
    grads0 = xy - G @ beta0 + jnp.diag(G) * beta0

    def coord(carry, xs):
        beta, grads = carry
        grow, e, dinv, t, l, h = xs
        gj = jnp.sum(grads * e)
        bnew = jnp.clip(jnp.sign(gj) * jnp.maximum(jnp.abs(gj) - t, 0.0)
                        * dinv, l, h)
        bd = jnp.sum(beta * e) - bnew
        grads = grads + bd * grow * (1.0 - e)
        beta = beta - bd * e
        return (beta, grads), bd * bd * jnp.sum(grow * e)

    def sweep(state):
        beta, grads, it, _ = state
        (beta, grads), diffs = jax.lax.scan(
            coord, (beta, grads), (G, eye, diag_inv, thr, lo, hi))
        return beta, grads, it + 1, jnp.max(diffs)

    def keep_going(state):
        _, _, it, maxdiff = state
        return (it < max_iter) & (maxdiff >= eps2)

    state = (beta0, grads0, jnp.array(0, jnp.int32),
             jnp.array(jnp.inf, G.dtype))
    beta, _, it, _ = jax.lax.while_loop(keep_going, sweep, state)
    return beta, it


def _cod_solve(G, b, l1, l2, free: np.ndarray, beta0, beta_epsilon=1e-5,
               lo=None, hi=None):
    """Cyclic coordinate descent on the Gram — the reference's distinct
    COORDINATE_DESCENT solver (`hex/glm/GLM.java:4373` COD_solve), not an
    IRLSM alias: per coordinate, a soft-threshold step on the residual
    gradient b = S(grads_j, λα)/(G_jj + λ(1−α)), unpenalized coordinates
    (the intercept) step by grads_j/G_jj, convergence when
    max_j Δβ_j²·G_jj < beta_epsilon², max(P, 500) sweeps. The whole solve
    is ONE jitted XLA loop over the tiny Gram (no P host round trips)."""
    P = G.shape[0]
    diag = np.diag(G).copy()
    diag_inv = 1.0 / np.where(free, np.maximum(diag, 1e-12),
                              np.maximum(diag + l2, 1e-12))
    thr = np.where(free, 0.0, l1)
    lo = np.full(P, -np.inf) if lo is None else np.asarray(lo, np.float64)
    hi = np.full(P, np.inf) if hi is None else np.asarray(hi, np.float64)
    # device f32 (x64 is off in this runtime): the Gauss-Seidel sweeps are
    # self-correcting — each step re-reads the residual gradient — so f32
    # carries converge to the same coefficients as the f64 ADMM path (match
    # verified at 1e-4 on elastic-net problems)
    f32 = jnp.float32
    beta, _ = _cod_kernel(
        jnp.asarray(G, f32), jnp.asarray(b, f32),
        jnp.asarray(beta0, f32), jnp.asarray(diag_inv, f32),
        jnp.asarray(thr, f32), jnp.asarray(lo, f32), jnp.asarray(hi, f32),
        jnp.asarray(max(beta_epsilon ** 2, 1e-10), f32), max(P, 500))
    return np.asarray(beta, np.float64)


# ---------------------------------------------------------------------------
# parameters / model / builder
# ---------------------------------------------------------------------------
@dataclass
class GLMParameters(Parameters):
    """Mirrors `hex/glm/GLMModel.GLMParameters` / `hex/schemas/GLMV3`."""

    family: str = "AUTO"
    link: str | None = None
    solver: str = "IRLSM"          # IRLSM | COORDINATE_DESCENT | L_BFGS —
                                   # COD is a distinct inner solver (cyclic
                                   # soft-threshold sweeps on the Gram,
                                   # GLM.java:4373), not an IRLSM alias
    alpha: float | None = None     # elastic-net mix; default .5 like reference
    lambda_: float | None = None   # penalty strength; None -> 0 or search
    lambda_search: bool = False
    early_stopping: bool = True    # lambda_search walks the path only while
                                   # deviance still improves materially
                                   # (reference default; `hex/glm/GLM.java`
                                   # _early_stop_search) — False forces the
                                   # full nlambdas path
    nlambdas: int = -1             # -1 (the reference's documented default):
                                   # 100 when alpha > 0, else 30
    lambda_min_ratio: float = 1e-4
    standardize: bool = True
    intercept: bool = True
    non_negative: bool = False
    dispersion_parameter_method: str = "pearson"  # pearson | deviance | ml
                                     # (`hex/glm/GLMModel.DispersionMethod`);
                                     # ml: exact for gamma (digamma Newton),
                                     # Dunn-Smyth series likelihood for tweedie
    fix_dispersion_parameter: bool = False
    init_dispersion_parameter: float = 1.0
    fix_tweedie_variance_power: bool = True  # False: joint (p, φ) ML over the
                                     # fitted means via the series likelihood
                                     # (`hex/glm/TweedieEstimator` analog)
    HGLM: bool = False               # hierarchical GLM: y = Xβ + Zu + e with
                                     # one categorical random-intercept column
                                     # (`hex/glm/GLMModel.java:499,638-641` —
                                     # the reference also requires exactly one
                                     # random column, gaussian rand_family)
    random_columns: list = None      # [column name or index]
    rand_family: list = None         # ["gaussian"] (only member supported)
    interactions: list = None        # columns whose pairwise interactions
                                     # enter the design (`GLMModel.java:515`):
                                     # num×num products, cat×num gated
                                     # columns, cat×cat product-domain
                                     # categoricals (`hex/DataInfo.java:133`)
    interaction_pairs: list = None   # explicit (a, b) tuples instead of the
                                     # all-pairs expansion of `interactions`
                                     # (`Model.InteractionPair` / h2o-py
                                     # interaction_pairs)
    beta_constraints: object = None  # Frame or {names, lower_bounds,
                                     # upper_bounds} — box constraints per
                                     # coefficient on the natural scale
                                     # (`hex/glm/GLM.BetaConstraint`); applied
                                     # by projection in IRLSM/COD; rejected
                                     # with L_BFGS like the reference
    linear_constraints: object = None  # Frame or {names, values, types,
                                     # constraint_numbers} — Equal /
                                     # LessThanEqual constraints over
                                     # coefficient linear combinations +
                                     # 'constant' rows
                                     # (`hex/glm/GLMModel.java:519`,
                                     # `ConstrainedGLMUtils.java:214`);
                                     # solved here by an exact active-set QP
                                     # on the IRLS normal equations instead
                                     # of the reference's exact-penalty
                                     # augmented-Lagrangian loop (deliberate
                                     # divergence: exact at GLM scale)
    constraint_eta0: float = 0.1258925  # AL-loop tuning knobs, accepted for
    constraint_tau: float = 10.0        # API parity; the QP solve has no
    constraint_c0: float = 10.0         # use for them (see
    constraint_alpha: float = 0.1       # linear_constraints note above)
    constraint_beta: float = 0.9
    max_iterations: int = 50
    beta_epsilon: float = 1e-5
    objective_epsilon: float = 1e-6
    tweedie_variance_power: float = 1.5
    theta: float = 1.0
    missing_values_handling: str = "MeanImputation"
    compute_p_values: bool = False
    feature_parallelism: int = 1   # >1: shard the expanded design over a 2-D
                                   # rows×cols mesh — the wide/one-hot Gram
                                   # sharding axis (SURVEY.md §5.7); GSPMD
                                   # inserts the cross-axis collectives


def _shard_cols(X, y_dev, fp: int):
    """Re-lay the design over a rows×cols mesh (feature_parallelism > 1):
    wide one-hot designs shard the Gram accumulation over the feature axis
    too (SURVEY §5.7). Zero-pads the feature axis to the shard count (the
    cols-axis ESPC analog); padded columns solve to beta=0 and callers strip
    them."""
    if fp <= 1:
        return X, y_dev, 0
    from jax.sharding import PartitionSpec as _P

    from ..parallel.mesh import COLS, ROWS as _R, make_mesh, put_sharded

    ndev = len(jax.devices())
    if ndev % fp:
        raise ValueError(f"feature_parallelism={fp} must divide the "
                         f"device count {ndev}")
    pad_cols = (-X.shape[1]) % fp
    if pad_cols:
        X = jnp.concatenate(
            [X, jnp.zeros((X.shape[0], pad_cols), X.dtype)], axis=1)
    mesh2 = make_mesh(row_parallel=ndev // fp)
    X = put_sharded(X, _P(_R, COLS), mesh2)
    y_dev = put_sharded(y_dev, _P(_R), mesh2)
    return X, y_dev, pad_cols


def _beta_bounds(spec, di, pad_cols: int = 0):
    """(lo, hi) arrays over [expanded coefs..., intercept] on the TRAINING
    (standardized) scale, from a natural-scale constraint spec — a Frame or
    dict with names/lower_bounds/upper_bounds (`hex/glm/GLM.BetaConstraint`).
    Natural bound b on a standardized numeric coef becomes b·σ (β_std = β·σ);
    one-hot and unstandardized coefs carry bounds unchanged."""
    if spec is None:
        return None
    if hasattr(spec, "vec"):  # Frame
        names = [str(x) for x in
                 (spec.vec("names").host_data
                  if spec.vec("names").host_data is not None else
                  [spec.vec("names").domain[int(c)]
                   for c in spec.vec("names").to_numpy()])]
        lob = spec.vec("lower_bounds").to_numpy()
        upb = spec.vec("upper_bounds").to_numpy()
    else:
        names = list(spec["names"])
        lob = np.asarray(spec.get("lower_bounds",
                                  [-np.inf] * len(names)), dtype=np.float64)
        upb = np.asarray(spec.get("upper_bounds",
                                  [np.inf] * len(names)), dtype=np.float64)
    P = di.ncols_expanded
    lo = np.full(P + 1 + pad_cols, -np.inf)
    hi = np.full(P + 1 + pad_cols, np.inf)
    idx = {n: j for j, n in enumerate(di.expanded_names)}
    for n, l, u in zip(names, lob, upb):
        if n not in idx:
            raise ValueError(f"beta_constraints: unknown coefficient '{n}' "
                             f"(expanded names: numeric column or "
                             f"'col.level')")
        j = idx[n]
        s = di.num_sigmas.get(n, 1.0) if di.standardize else 1.0
        if not np.isnan(l):
            lo[j] = l * s
        if not np.isnan(u):
            hi[j] = u * s
    if pad_cols:
        # padded design columns sit between the real coefs and the intercept
        lo[P:P + pad_cols], hi[P:P + pad_cols] = -np.inf, np.inf
        lo[-1], hi[-1] = -np.inf, np.inf
    return lo, hi


def _linear_constraint_system(spec, di, pad_cols: int = 0):
    """Parse linear_constraints into (Aeq, ceq, Ain, cin) over the TRAINING
    coefficient layout [expanded coefs..., pad..., intercept].

    Wire format (`ConstrainedGLMUtils.extractLinearConstraints`): rows of
    {names, values, types, constraint_numbers}; rows sharing a
    constraint_number form one constraint Σ value·coef + constant (op) 0,
    with the name 'constant' carrying the constant and types Equal /
    LessThanEqual. Natural→standardized transform: β_nat_j = β_std_j/σ_j
    for standardized numerics (the reference multiplies by _normMul), and a
    constraint naming the intercept picks up the centering cross-terms
    −a_int·m_j/σ_j (int_nat = int_std − Σ β_std_j·m_j/σ_j)."""
    if spec is None:
        return None
    if hasattr(spec, "vec"):  # Frame
        def _strings(col):
            v = spec.vec(col)
            if v.is_categorical():
                return [v.domain[int(c)] for c in v.to_numpy()]
            return [str(x) for x in (v.host_data if v.host_data is not None
                                     else v.to_numpy())]

        names = _strings("names")
        values = np.asarray(spec.vec("values").to_numpy(), np.float64)
        types = [t.lower() for t in _strings("types")]
        numbers = np.asarray(spec.vec("constraint_numbers").to_numpy(),
                             np.int64)
    else:
        names = list(spec["names"])
        values = np.asarray(spec["values"], np.float64)
        types = [str(t).lower() for t in spec["types"]]
        numbers = np.asarray(spec["constraint_numbers"], np.int64)
    P = di.ncols_expanded
    P1 = P + pad_cols + 1
    idx = {n: j for j, n in enumerate(di.expanded_names)}
    rows_eq, rows_in = [], []
    for cn in sorted(set(int(n) for n in numbers)):
        sel = [i for i in range(len(names)) if int(numbers[i]) == cn]
        ctypes = {types[i] for i in sel}
        if len(ctypes) != 1 or not ctypes <= {"equal", "lessthanequal"}:
            raise ValueError(
                f"linear_constraints: constraint {cn} must have one type, "
                f"Equal or LessThanEqual (got {sorted(ctypes)})")
        a = np.zeros(P1)
        c = 0.0
        ncoef = 0
        for i in sel:
            n = names[i]
            v = float(values[i])
            if n == "constant":
                c += v
                continue
            ncoef += 1
            if n == "Intercept" or n == "intercept":
                a[-1] += v
                # centering cross-terms from int_nat = int_std − Σ β·m/σ
                for j, en in enumerate(di.expanded_names):
                    if en in di.num_means and di.effective_center:
                        s = di.num_sigmas[en] if di.standardize else 1.0
                        a[j] -= v * di.num_means[en] / s
                continue
            if n not in idx:
                raise ValueError(
                    f"linear_constraints: coefficient name '{n}' is not a "
                    f"valid coefficient name (numeric column or "
                    f"'col.level') or 'constant'")
            s = (di.num_sigmas.get(n, 1.0)
                 if di.standardize and n in di.num_means else 1.0)
            a[idx[n]] += v / s
        if ncoef < 2:
            raise ValueError(
                "Linear constraint must have at least two coefficients. For "
                "constraints on just one coefficient use beta_constraints "
                "instead.")
        (rows_eq if "equal" in ctypes else rows_in).append((a, c))
    Aeq = np.array([r[0] for r in rows_eq]).reshape(-1, P1)
    ceq = np.array([r[1] for r in rows_eq], np.float64)
    Ain = np.array([r[0] for r in rows_in]).reshape(-1, P1)
    cin = np.array([r[1] for r in rows_in], np.float64)
    # redundancy check (`checkAssignLinearConstraints` full-rank guard)
    M = np.vstack([Aeq, Ain]) if len(Aeq) + len(Ain) else np.zeros((0, P1))
    if len(M) and np.linalg.matrix_rank(M) < len(M):
        raise ValueError("redundant and possibly conflicting linear "
                         "constraints: the constraint matrix is not full "
                         "rank — remove redundant constraints")
    return Aeq, ceq, Ain, cin


def _constrained_qp(G, b, Aeq, ceq, Ain, cin, tol=1e-8, max_iter=200):
    """min ½βᵀGβ − bᵀβ  s.t.  Aeq·β + ceq = 0, Ain·β + cin ≤ 0.

    Dense primal active-set over KKT solves — each iteration solves
    [[G, Aᵀ], [A, 0]] [β; λ] = [b; −c] for the working set, adds the most
    violated inactive inequality, drops the most negative multiplier.
    Exact at GLM coefficient counts (the matrix is (P+m)²)."""
    P = G.shape[0]
    Greg = G + 1e-10 * np.eye(P)
    active: list[int] = []

    def solve(act):
        rows = [Aeq] + [Ain[i:i + 1] for i in act]
        A = np.vstack([r for r in rows if len(r)]) if (len(Aeq) or act) \
            else np.zeros((0, P))
        c = np.concatenate([ceq] + [cin[i:i + 1] for i in act]) \
            if (len(ceq) or act) else np.zeros(0)
        m = A.shape[0]
        K = np.zeros((P + m, P + m))
        K[:P, :P] = Greg
        K[:P, P:] = A.T
        K[P:, :P] = A
        rhs = np.concatenate([b, -c])
        try:
            sol = np.linalg.solve(K, rhs)
        except np.linalg.LinAlgError:
            sol = np.linalg.lstsq(K, rhs, rcond=None)[0]
        return sol[:P], sol[P + len(ceq):]  # β, inequality multipliers

    beta, lam = solve(active)
    for _ in range(max_iter):
        # drop the most negative multiplier (constraint no longer binding)
        if len(active) and len(lam) and lam.min() < -tol:
            del active[int(np.argmin(lam))]
            beta, lam = solve(active)
            continue
        # add the most violated inactive inequality
        if len(Ain):
            viol = Ain @ beta + cin
            viol[active] = -np.inf
            worst = int(np.argmax(viol))
            if viol[worst] > tol:
                active.append(worst)
                beta, lam = solve(active)
                continue
        break
    return beta


def _tweedie_loglik(y, mu, phi, p):
    """Σ log f(y; μ, φ) for Tweedie 1<p<2, by the Dunn & Smyth (2005) series
    (`hex/glm/TweedieMLDispersionOnly` analog). Host-side f64; the series
    index window is centered on j_max = y^{2−p}/(φ(2−p))."""
    from scipy.special import gammaln

    y = np.asarray(y, np.float64)
    mu = np.maximum(np.asarray(mu, np.float64), 1e-10)
    alpha = (2.0 - p) / (p - 1.0)
    ll = (y * mu ** (1 - p) / (1 - p) - mu ** (2 - p) / (2 - p)) / phi
    pos = y > 0
    yp = y[pos]
    if yp.size:
        jmax = np.max(np.maximum(yp ** (2 - p) / (phi * (2 - p)), 1.0))
        J = int(min(max(3 * jmax + 20, 40), 4000))
        j = np.arange(1, J + 1, dtype=np.float64)[None, :]
        logz = (alpha * np.log(yp) - alpha * np.log(p - 1)
                - (1 + alpha) * np.log(phi) - np.log(2 - p))[:, None]
        logWj = j * logz - gammaln(j + 1) - gammaln(alpha * j)
        m = logWj.max(axis=1, keepdims=True)
        logW = m[:, 0] + np.log(np.exp(logWj - m).sum(axis=1))
        ll[pos] += logW - np.log(yp)
    return float(ll.sum())


def _tweedie_phi_ml(yh, muh, p_var: float, df: float) -> float:
    """Golden-section ML over log φ at fixed variance power, seeded from the
    Pearson estimate."""
    pearson = _estimate_dispersion_pearson(
        TweedieF(tweedie_variance_power=p_var), yh, muh,
        np.ones_like(yh), df)
    a, b = np.log(max(pearson, 1e-8)) - 4.0, np.log(max(pearson, 1e-8)) + 4.0
    gr = (np.sqrt(5.0) - 1) / 2
    f = lambda lp: _tweedie_loglik(yh, muh, np.exp(lp), p_var)
    c1, c2 = b - gr * (b - a), a + gr * (b - a)
    f1, f2 = f(c1), f(c2)
    for _ in range(40):
        if f1 < f2:
            a, c1, f1 = c1, c2, f2
            c2 = a + gr * (b - a)
            f2 = f(c2)
        else:
            b, c2, f2 = c2, c1, f1
            c1 = b - gr * (b - a)
            f1 = f(c1)
        if b - a < 1e-8:
            break
    return float(np.exp(0.5 * (a + b)))


def _gamma_ml_dispersion(dev: float, neff: float) -> float:
    """Exact gamma ML: solve log α − ψ(α) = D/(2n) for the shape α = 1/φ
    by Newton with digamma/trigamma (`hex/glm/DispersionTask` ml branch)."""
    from scipy.special import digamma, polygamma

    c = max(dev / (2.0 * max(neff, 1.0)), 1e-12)
    # Minka's initializer, then Newton on f(α) = log α − ψ(α) − c
    a = (3.0 - c + np.sqrt((c - 3.0) ** 2 + 24.0 * c)) / (12.0 * c)
    for _ in range(30):
        f = np.log(a) - float(digamma(a)) - c
        fp = 1.0 / a - float(polygamma(1, a))
        step = f / fp
        a_new = a - step
        if a_new <= 0:
            a_new = a / 2.0
        if abs(a_new - a) < 1e-12 * max(a, 1.0):
            a = a_new
            break
        a = a_new
    return 1.0 / max(a, 1e-12)


def _estimate_dispersion(p, family, y, mu, w, dev, neff, rank) -> float:
    """Dispersion φ per `dispersion_parameter_method`
    (`hex/glm/GLMModel.java:528`, `hex/glm/DispersionTask.java`)."""
    if p.fix_dispersion_parameter:
        return float(p.init_dispersion_parameter)
    method = (p.dispersion_parameter_method or "pearson").lower()
    df = max(neff - rank, 1.0)
    if method == "deviance":
        return float(dev) / df
    if method == "ml":
        if family.name == "gamma":
            return _gamma_ml_dispersion(float(dev), float(neff))
        if family.name == "tweedie":
            if not (1.0 < family.p < 2.0):
                raise ValueError("ml dispersion for tweedie requires "
                                 "1 < tweedie_variance_power < 2")
            yh = np.asarray(y)
            muh = np.asarray(mu)
            wh = np.asarray(w)
            keep = wh > 0
            yh, muh = yh[keep], muh[keep]
            # subsample bound: the series likelihood is O(rows × series len);
            # 50k rows pins the estimate to ±1e-2 at a fraction of the cost
            if yh.size > 50_000:
                sel = np.random.default_rng(42).choice(yh.size, 50_000,
                                                       replace=False)
                yh, muh = yh[sel], muh[sel]
            if getattr(p, "fix_tweedie_variance_power", True):
                return _tweedie_phi_ml(yh, muh, family.p, df)
            best = (-np.inf, family.p, 1.0)
            for vp in np.arange(1.1, 1.91, 0.05):  # joint (p, φ) profile ML
                phi = _tweedie_phi_ml(yh, muh, float(vp), df)
                ll = _tweedie_loglik(yh, muh, phi, float(vp))
                if ll > best[0]:
                    best = (ll, float(vp), phi)
            family.estimated_p = best[1]  # per-model family instance
            return best[2]
        raise ValueError(f"ml dispersion is supported for gamma and tweedie "
                         f"(got family={family.name}) — use pearson/deviance")
    # pearson (default)
    return _estimate_dispersion_pearson(family, np.asarray(y),
                                        np.asarray(mu), np.asarray(w), df)


def _estimate_dispersion_pearson(family, y, mu, w, df) -> float:
    V = np.asarray(family.variance(jnp.asarray(mu)))
    resid2 = w * (y - mu) ** 2 / np.maximum(V, 1e-12)
    return float(np.nansum(resid2) / df)


#: cap on a cat×cat product domain — the EnumLimited analog for interaction
#: columns (`hex/DataInfo.java:133` InteractionPair domains; the reference's
#: `Interaction.java` max_factors defaults to 100)
_INTERACTION_MAX_LEVELS = 100


def _freeze_interaction_pairs(fr: Frame, interactions, interaction_pairs,
                              reserved: set,
                              max_levels: int = _INTERACTION_MAX_LEVELS):
    """Resolve `interactions` (all pairwise combos among the columns) and/or
    `interaction_pairs` (explicit (a, b) tuples) into frozen per-pair specs
    (`hex/DataInfo.java:133,223` Model.InteractionPair):

    - num×num → one product column "a_b"
    - cat×num → one gated numeric column "a_b.lvl" per non-reference level
      (first level dropped: the full gated set sums to the numeric column)
    - cat×cat → one categorical column "a_b" whose domain is the OBSERVED
      level combos "la_lb", most-frequent first, capped at ``max_levels``
      (EnumLimited semantics); rarer combos score as NA → mode

    Everything needed to replay at score time (levels, combo labels) is
    frozen here from the TRAINING frame.
    """
    def resolve(c):
        return fr.names[int(c)] if not isinstance(c, str) else c

    pairs = []
    listed = []
    if interactions:
        cols = [resolve(c) for c in interactions]
        listed += cols
        if len(cols) < 2:
            raise ValueError(
                "interactions needs at least two columns to form pairs "
                f"(got {cols}) — use interaction_pairs for explicit tuples")
        pairs += [(a, b) for i, a in enumerate(cols) for b in cols[i + 1:]]
    for a, b in (interaction_pairs or []):
        pairs.append((resolve(a), resolve(b)))
        listed += [resolve(a), resolve(b)]
    for c in listed:
        if c in reserved:
            raise ValueError(f"interactions may not include the special "
                             f"column '{c}' (response/weights/offset)")
        if fr.vec(c).is_string():
            raise ValueError(f"interactions: column '{c}' is a string "
                             "column")
    specs = []
    for a, b in pairs:
        # canonical order: categorical first (stable generated names)
        if fr.vec(b).is_categorical() and not fr.vec(a).is_categorical():
            a, b = b, a
        acat, bcat = fr.vec(a).is_categorical(), fr.vec(b).is_categorical()
        if not acat:
            specs.append({"kind": "numnum", "a": a, "b": b})
        elif not bcat:
            specs.append({"kind": "catnum", "a": a, "b": b,
                          "levels": list(fr.vec(a).domain)})
        else:
            ca = fr.vec(a).to_numpy()
            cb = fr.vec(b).to_numpy()
            ok = ~(np.isnan(ca) | np.isnan(cb))
            da, db = fr.vec(a).domain, fr.vec(b).domain
            combo = ca[ok].astype(np.int64) * len(db) + cb[ok].astype(np.int64)
            codes, counts = np.unique(combo, return_counts=True)
            order = np.argsort(-counts, kind="stable")[:max_levels]
            # combos are keyed by the LEVEL-NAME PAIR (labels are display
            # only: "New_York"-style underscores must not merge combos)
            combos = [(da[c // len(db)], db[c % len(db)])
                      for c in codes[order]]
            labels, seen = [], set()
            for la, lb in combos:
                lab = f"{la}_{lb}"
                while lab in seen:
                    lab += "."
                seen.add(lab)
                labels.append(lab)
            specs.append({"kind": "catcat", "a": a, "b": b,
                          "combos": combos, "labels": labels})
    return specs


def _primary_interaction_name(s: dict) -> str:
    if s["kind"] == "catnum":
        return f"{s['a']}_{s['b']}.{s['levels'][1]}" if len(s["levels"]) > 1 \
            else f"{s['a']}_{s['b']}"
    return f"{s['a']}_{s['b']}"


def _apply_interactions(fr: Frame, specs: list, skip_existing: bool = False):
    """Append the frozen interaction columns to (a shallow copy of) ``fr`` —
    runs identically at train and score time; score-frame domains are matched
    BY LABEL so unseen levels/combos become NA (→ DataInfo imputation).
    ``skip_existing`` makes replay idempotent (model-side scoring on a frame
    that already carries the expansion, e.g. the training frame itself)."""
    from ..frame.vec import T_CAT

    out = Frame(list(fr.names), list(fr.vecs))
    new_names = []

    def add(nm, vec):
        if nm in out.names:
            raise ValueError(
                f"interactions: generated column name '{nm}' collides "
                f"with an existing column — rename it")
        out.add(nm, vec)
        new_names.append(nm)

    if skip_existing:
        specs = [s for s in specs
                 if _primary_interaction_name(s) not in fr.names]
    for s in specs:
        va, vb = fr.vec(s["a"]), fr.vec(s["b"])
        if s["kind"] == "numnum":
            add(f"{s['a']}_{s['b']}",
                Vec.from_device(va.data * vb.data, fr.nrow))
        elif s["kind"] == "catnum":
            dom = va.domain or []
            for lvl in s["levels"][1:]:   # reference level dropped
                code = dom.index(lvl) if lvl in dom else -1
                gate = (va.data == code).astype(jnp.float32)
                col = jnp.where(jnp.isnan(va.data), jnp.nan, gate) * vb.data
                add(f"{s['a']}_{s['b']}.{lvl}",
                    Vec.from_device(col, fr.nrow))
        else:  # catcat
            da, db = va.domain or [], vb.domain or []
            combos = s.get("combos")
            if combos is None:
                # legacy specs (pre-fix exports) stored display labels only.
                # Reconstruct each (level_a, level_b) pair by exact match
                # against the domains — a blind rsplit("_", 1) mis-parses
                # levels that themselves contain underscores ("New_York")
                # and would silently score those combos as NA. Any label
                # that does not match exactly one pair fails the load loudly.
                # O(|labels|·|da|) prefix match — never materializes the
                # |da|×|db| cross product (5k×5k domains would be ~25M keys)
                db_set = set(db)
                combos = []
                for lab in s["labels"]:
                    hits = [(la, lab[len(la) + 1:]) for la in da
                            if lab.startswith(la + "_")
                            and lab[len(la) + 1:] in db_set]
                    if len(hits) != 1:
                        raise ValueError(
                            f"interaction '{s['a']}_{s['b']}': legacy level "
                            f"label '{lab}' matches {len(hits)} "
                            f"(level_a, level_b) pairs — cannot recover the "
                            f"combo mapping; re-export the model with "
                            f"'combos' in its interaction spec")
                    combos.append(hits[0])
            combo_idx = {tuple(c): i for i, c in enumerate(combos)}
            table = np.full(max(len(da), 1) * max(len(db), 1), np.nan,
                            np.float32)
            for i, la in enumerate(da):
                for j, lb in enumerate(db):
                    k = combo_idx.get((la, lb))
                    if k is not None:
                        table[i * len(db) + j] = k
            combo = va.data * len(db) + vb.data   # NaN propagates
            codes = jnp.where(jnp.isnan(combo), 0,
                              combo).astype(jnp.int32)
            mapped = jnp.asarray(table)[jnp.clip(codes, 0, len(table) - 1)]
            mapped = jnp.where(jnp.isnan(combo), jnp.nan, mapped)
            add(f"{s['a']}_{s['b']}",
                Vec.from_device(mapped, fr.nrow, type=T_CAT,
                                domain=list(s["labels"])))
    return out, new_names


def _destandardize(beta: np.ndarray, di) -> np.ndarray:
    """Map coefficients from the standardized training scale back to the
    original feature scale: b → b/s, intercept → intercept − Σ b·m/s.
    Accepts (P+1,) or multinomial (K, P+1) [classes × coefs, intercept last]."""
    beta = beta.copy()
    if not (di.standardize or di.effective_center):
        return beta
    B = beta[None, :] if beta.ndim == 1 else beta
    shift = np.zeros(B.shape[0])
    for j, n in enumerate(di.expanded_names):
        if n in di.num_means:  # numeric (one-hot names never collide)
            s = di.num_sigmas[n] if di.standardize else 1.0
            m = di.num_means[n] if di.effective_center else 0.0
            B[:, j] = B[:, j] / s
            shift += B[:, j] * m
    B[:, -1] -= shift
    return B[0] if beta.ndim == 1 else B


class GLMModel(Model):
    algo_name = "glm"
    dispersion_estimated = None  # φ per dispersion_parameter_method

    def __init__(self, params, output, dinfo: DataInfo, beta, family, key=None):
        self.dinfo = dinfo
        self.beta = beta        # (P+1,) host array, intercept LAST (H2O layout)
        self.family = family
        super().__init__(params, output, key=key)

    def coef(self) -> dict:
        """Coefficients on the ORIGINAL feature scale (`GLMModel.coefficients()`).

        beta is stored on the (possibly standardized) training scale used by
        score0; numeric columns were transformed x → (x−m)/s, so the original
        scale is b/s with the intercept absorbing Σ b·m/s.
        """
        names = self.dinfo.expanded_names + ["Intercept"]
        beta = _destandardize(np.asarray(self.beta, dtype=np.float64), self.dinfo)
        return dict(zip(names, beta))

    def coef_norm(self) -> dict:
        """Coefficients on the standardized scale (`coefficients(standardize=True)`)."""
        names = self.dinfo.expanded_names + ["Intercept"]
        return dict(zip(names, np.asarray(self.beta)))

    interaction_spec = None   # frozen pair specs (levels/labels by name)
    interaction_cols = None   # legacy (pre-round-5 binary exports): numeric
                              # pairwise column names

    def adapt_frame(self, fr: Frame):
        fr = self.pre_adapt(fr)  # categorical-encoding replay FIRST, so the
        spec = self.interaction_spec  # products see the training-time values
        if spec is None and self.interaction_cols:
            cols = self.interaction_cols
            spec = [{"kind": "numnum", "a": a, "b": b}
                    for i, a in enumerate(cols) for b in cols[i + 1:]]
        if spec:
            fr, _ = _apply_interactions(fr, spec, skip_existing=True)
        X, ok = self.dinfo.expand(fr)
        return X

    def score_raw(self, X):
        """Serving-path scoring straight from the raw (B, F) feature matrix
        (columns in output.names order): reorder into the DataInfo's
        cats-first layout, expand to the design matrix, then score — the
        traceable twin of ``adapt_frame``+``score0``.

        The linear predictor is an elementwise-mul + row-sum rather than
        score0's ``X @ beta``: XLA CPU's dot picks shape-dependent
        accumulation strategies, so the SAME row matmul'd in a (1, P) and
        an (8, P) batch can differ in the last ulp — which breaks the
        serving contract that padded-batch outputs are BIT-identical to
        single-row outputs across bucket sizes. A per-row reduction is
        batch-size-invariant (measured: matmul maxdiff 1 ulp, mul+sum 0).
        """
        if self.interaction_spec or self.interaction_cols or \
                getattr(self.output, "encoding_state", None) is not None:
            raise NotImplementedError(
                "raw-matrix serving of GLMs with interactions or a frozen "
                "categorical encoding: their adapt path needs a Frame")
        idx = [self.output.names.index(n) for n in self.dinfo.names]
        Xe = self.dinfo.expand_matrix(X[:, jnp.asarray(idx)])
        beta = jnp.asarray(self.beta)
        if beta.ndim != 1 or type(self).score0 is not GLMModel.score0:
            # multinomial/ordinal subclasses own their score0 — delegate
            return self.score0(Xe)
        eta = jnp.sum(Xe * beta[:-1], axis=1) + beta[-1]
        mu = self.family.linkinv(eta)
        if self.output.model_category == "Binomial":
            thr = float(getattr(self, "default_threshold", 0.5))
            label = (mu >= thr).astype(jnp.float32)
            return jnp.stack([label, 1 - mu, mu], axis=1)
        return mu

    def score0(self, X: jax.Array) -> jax.Array:
        beta = jnp.asarray(self.beta)
        eta = X @ beta[:-1] + beta[-1]
        mu = self.family.linkinv(eta)
        if self.output.model_category == "Binomial":
            thr = float(getattr(self, "default_threshold", 0.5))
            label = (mu >= thr).astype(jnp.float32)
            return jnp.stack([label, 1 - mu, mu], axis=1)
        if self.output.model_category == "Multinomial":
            pass  # handled by GLMMultinomialModel
        return mu


class GLM(ModelBuilder):
    algo_name = "glm"

    def _validate(self):
        super()._validate()
        p = self.params
        if p.compute_p_values:  # reference: reject up front, before training
            if p.lambda_search or (p.lambda_ is not None and p.lambda_ > 0):
                raise ValueError("compute_p_values requires lambda = 0 / no "
                                 "lambda_search (no regularization)")
            if (p.family or "").lower() == "multinomial":
                raise ValueError("compute_p_values is not supported for "
                                 "multinomial family")
            if p.feature_parallelism > 1:
                raise NotImplementedError(
                    "compute_p_values with feature_parallelism: follow-up "
                    "(the Fisher information needs the unpadded design)")
        if p.linear_constraints is not None:
            # `GLM.checkInitLinearConstraints` mirror
            if (p.solver or "IRLSM").upper() not in ("IRLSM", "AUTO"):
                raise ValueError(
                    "constrained GLM is only available for IRLSM. Please "
                    "set solver to IRLSM/irlsm explicitly.")
            if not p.intercept:
                raise ValueError("constrained GLM is only supported with "
                                 "intercept=true.")
            if p.lambda_search or (p.lambda_ is not None and p.lambda_ > 0):
                raise ValueError("Regularization is not allowed for "
                                 "constrained GLM.")
            if (p.family or "").lower() in ("multinomial", "ordinal"):
                raise ValueError("Constrained GLM is not supported for "
                                 "multinomial and ordinal families")

    def _family(self, category) -> Family:
        p = self.params
        name = (p.family or "AUTO").lower()
        if name == "auto":
            name = {"Binomial": "binomial", "Multinomial": "multinomial",
                    "Regression": "gaussian"}[category]
        if name == "multinomial":
            return BinomialF(p.link if p.link not in (None, "family_default") else None)
        cls = _FAMILIES.get(name)
        if cls is None:
            raise ValueError(f"unsupported GLM family '{name}'")
        link = p.link if p.link not in (None, "family_default") else None
        return cls(link, tweedie_variance_power=p.tweedie_variance_power,
                   theta=p.theta)

    def build_impl(self, job: Job) -> Model:
        p = self.params
        if isinstance(p.alpha, (list, tuple)):
            return self._build_alpha_search(job)
        fr = p.training_frame
        names = self.feature_names()
        y_dev, category, resp_domain = self.response_info()
        self._interaction_spec = None
        if getattr(p, "interactions", None) \
                or getattr(p, "interaction_pairs", None):
            if category == "Multinomial" or getattr(p, "HGLM", False):
                raise NotImplementedError(
                    "interactions are supported for single-block GLM "
                    "families (not multinomial/ordinal/HGLM)")
            reserved = {p.response_column, p.weights_column, p.offset_column}
            self._interaction_spec = _freeze_interaction_pairs(
                fr, p.interactions, getattr(p, "interaction_pairs", None),
                reserved)
            fr, extra = _apply_interactions(fr, self._interaction_spec)
            names = names + extra
        if getattr(p, "HGLM", False):
            return self._build_hglm(job, names, y_dev, category)
        return self._build_single(job, p, fr, names, y_dev, category,
                                  resp_domain)

    def _build_alpha_search(self, job: Job) -> Model:
        """`alpha` given as an ARRAY (`hex/glm/GLM.java` submodel scan over
        alphas × lambdas): fit one model per alpha and keep the best by
        deviance — validation when present, else training."""
        import dataclasses

        p = self.params
        alphas = [float(a) for a in p.alpha]
        if not alphas:
            raise ValueError("alpha: empty array")
        best, best_dev, best_alpha = None, float("inf"), None
        for a in alphas:
            sub = type(self)(dataclasses.replace(p, alpha=a, nfolds=0))
            m = sub.build_impl(job)
            mm = (m.output.validation_metrics
                  if p.validation_frame is not None
                  else m.output.training_metrics)
            dev = None
            for attr in ("residual_deviance", "mean_residual_deviance",
                         "logloss", "mse"):
                dev = getattr(mm, attr, None)
                if dev is not None and dev == dev:
                    break
            if best is None or (dev is not None and dev < best_dev):
                best, best_alpha = m, a
                best_dev = dev if dev is not None else best_dev
        best.best_alpha = best_alpha
        return best

    def _build_single(self, job, p, fr, names, y_dev, category, resp_domain):
        if category == "Multinomial":
            if p.compute_p_values:  # AUTO family resolving to multinomial
                raise ValueError("compute_p_values is not supported for "
                                 "multinomial family")
            if p.linear_constraints is not None:
                raise ValueError("Constrained GLM is not supported for "
                                 "multinomial and ordinal families")

            if (p.family or "").lower() == "ordinal":
                if p.feature_parallelism > 1:
                    raise NotImplementedError(
                        "feature_parallelism is not supported for ordinal "
                        "GLM (the gradient path has no column-sharded Gram)")
                return self._build_ordinal(job, names, y_dev, resp_domain)
            return self._build_multinomial(job, names, y_dev, resp_domain)
        family = self._family(category)

        with telemetry.span("train.glm.design") as design_span:
            dinfo = DataInfo.make(
                fr, names, standardize=p.standardize,
                missing_values_handling=p.missing_values_handling)
            X, okrow = dinfo.expand(fr)
            X, y_dev, pad_cols = _shard_cols(X, y_dev, p.feature_parallelism)
            y = jnp.nan_to_num(y_dev)
            w = ((~jnp.isnan(y_dev)).astype(jnp.float32)
                 * okrow.astype(jnp.float32))
            if p.weights_column:
                w = w * jnp.nan_to_num(fr.vec(p.weights_column).data)
            offset = (jnp.nan_to_num(fr.vec(p.offset_column).data)
                      if p.offset_column else jnp.zeros_like(y))
            design_span.attrs.update(hbm_span_attrs())

        self._bounds = _beta_bounds(p.beta_constraints, dinfo,
                                    pad_cols=pad_cols)
        self._lincon = _linear_constraint_system(p.linear_constraints, dinfo,
                                                 pad_cols=pad_cols)
        beta, lambda_used, dev, nulldev, neff, iters, path = self._fit(
            X, y, w, offset, family, job)
        # from the fit's end to the model in the store
        with telemetry.span("train.glm.finish"):
            betas = np.stack([e[3] for e in path])
            if pad_cols:  # strip padding: coefficients (all ~0), design cols
                keep = np.r_[:dinfo.ncols_expanded, -1]
                beta, betas = beta[keep], betas[:, keep]
                X = X[:, :dinfo.ncols_expanded]

            output = ModelOutput()
            output.names = names
            output.domains = {n: fr.vec(n).domain for n in names}
            output.response_domain = (list(resp_domain) if resp_domain
                                      else None)
            output.model_category = category
            # the regularisation path as fitted
            # (`GLMModel.RegularizationPath`): one lambda without a search;
            # the model returned is its last
            output.lambdas = [e[0] for e in path]
            output.explained_deviance_train = [
                1.0 - e[1] / nulldev if nulldev else float("nan")
                for e in path]
            output.path_iterations = [e[2] for e in path]
            output.coefficients_std = betas
            output.coefficients = np.stack(
                [_destandardize(b, dinfo) for b in betas])
            output.lambda_best = lambda_used
            output.lambda_best_index = len(path) - 1
            model = GLMModel(p, output, dinfo, beta, family)
            model.interaction_spec = self._interaction_spec
        # final scoring and metrics, through dispersion and p-values
        with telemetry.span("train.glm.metrics"):
            raw = model.score0(X)
            ym = jnp.where(w > 0, y, jnp.nan)
            m = make_metrics(category, ym, raw, w if p.weights_column else None,
                             auc_type=p.auc_type, domain=output.response_domain)
            m.residual_deviance = float(dev)
            m.null_deviance = float(nulldev)
            rank = int(np.sum(np.abs(np.asarray(beta)) > 1e-12))
            m.aic = float(dev + 2 * rank)
            m.residual_degrees_of_freedom = int(neff) - rank
            m.null_degrees_of_freedom = int(neff) - 1
            output.training_metrics = m
            output.scoring_history = [{"iterations": iters, "lambda": lambda_used,
                                       "deviance": float(dev)}]
            output.variable_importances = self._varimp_from_beta(dinfo, beta)
            if getattr(self, "_lincon", None) is not None:
                # `GLMModel.output._linear_constraint_states` analog: per
                # constraint, its value at the solution and whether it holds
                from ..utils.twodimtable import TwoDimTable

                Aeq, ceq, Ain, cin = self._lincon
                if Aeq.shape[1] != len(beta):
                    # feature_parallelism stripped the pad columns from beta;
                    # drop the matching (all-zero) constraint columns
                    keep = list(range(dinfo.ncols_expanded)) + [Aeq.shape[1] - 1]
                    Aeq, Ain = Aeq[:, keep], Ain[:, keep]
                rows_t = []
                for i in range(len(ceq)):
                    val = float(Aeq[i] @ beta + ceq[i])
                    rows_t.append([f"equality_{i}", "Equal", val,
                                   bool(abs(val) < 1e-5)])
                for i in range(len(cin)):
                    val = float(Ain[i] @ beta + cin[i])
                    rows_t.append([f"lessthanequal_{i}", "LessThanEqual", val,
                                   bool(val < 1e-5)])
                output.linear_constraints_table = TwoDimTable(
                    table_header="Linear Constraints", description="",
                    col_header=["constraint", "type", "value",
                                "condition_satisfied"],
                    col_types=["string", "string", "double", "string"],
                    cell_values=rows_t)
            if family.name in ("gaussian", "gamma", "tweedie", "negativebinomial",
                               "quasibinomial"):
                mu = raw if raw.ndim == 1 else raw[:, -1]
                model.dispersion_estimated = _estimate_dispersion(
                    p, family, ym, mu, np.asarray(w), float(dev), float(neff),
                    len(beta))
                if getattr(family, "estimated_p", None) is not None:
                    model.tweedie_variance_power_estimated = family.estimated_p
            if p.compute_p_values:
                self._compute_p_values(model, X, y, w, offset, family, beta,
                                       float(dev), float(neff))
        if p.validation_frame is not None:
            output.validation_metrics = model.model_performance(p.validation_frame)
        return model

    def _compute_p_values(self, model, X, y, w, offset, family, beta,
                          dev, neff):
        """Std errors / z-values / p-values from the inverse Fisher
        information at the solution (`hex/glm/GLM.java` computeSubmodel
        p-values path). Unpenalized-fit requirement enforced in _validate."""
        step = _make_irls_kernel(family)
        ones = jnp.ones((X.shape[0], 1), jnp.float32)
        Xi = jnp.concatenate([X, ones], axis=1)
        G, _, _, _ = step(Xi, y, w, jnp.asarray(beta, jnp.float32), offset)
        Gn = np.asarray(G, np.float64)
        rank = len(beta)
        gaussian = family.name == "gaussian"
        # families with a free dispersion parameter scale the covariance by
        # the estimate (`hex/glm/GLM.java` computeSubmodel p-values path)
        est = getattr(model, "dispersion_estimated", None)
        dispersion = (est if est is not None
                      else dev / max(neff - rank, 1.0) if gaussian else 1.0)
        try:
            cov = np.linalg.inv(Gn + 1e-10 * np.eye(Gn.shape[0])) * dispersion
        except np.linalg.LinAlgError:
            return
        # beta/cov live on the (possibly standardized) training scale, but
        # coef() reports the ORIGINAL scale — transform the covariance with
        # the same linear map beta_orig = A·beta_std so the reported
        # (se, z, p) test the reported coefficients
        di = model.dinfo
        P1 = len(beta)
        A = np.eye(P1)
        if di.standardize or di.effective_center:
            for j, n in enumerate(di.expanded_names):
                if n in di.num_means:
                    s = di.num_sigmas[n] if di.standardize else 1.0
                    m = di.num_means[n] if di.effective_center else 0.0
                    A[j, j] = 1.0 / s
                    A[-1, j] = -m / s
        cov = A @ cov @ A.T
        beta_orig = A @ np.asarray(beta, np.float64)
        se = np.sqrt(np.clip(np.diag(cov), 0, None))
        z = np.where(se > 0, beta_orig / se, np.nan)
        df = max(neff - rank, 1.0)
        az = np.abs(np.nan_to_num(z))
        if gaussian:  # t-tail via the regularized incomplete beta (no scipy)
            import jax.scipy.special as jss

            pvals = np.asarray(jss.betainc(df / 2.0, 0.5,
                                           df / (df + az ** 2)))
        else:  # two-sided z-test
            import math

            pvals = np.array([math.erfc(v / math.sqrt(2.0)) for v in az])
        names = di.expanded_names + ["Intercept"]
        model.std_errs = dict(zip(names, se))
        model.z_values = dict(zip(names, z))
        model.p_values = dict(zip(names, pvals))
        model.dispersion = dispersion

    # -- the IRLS driver (`hex/glm/GLM.java:1682` GLMDriver.computeImpl) ------
    def _fit(self, X, y, w, offset, family, job):
        p = self.params
        P = X.shape[1]
        step = _make_irls_kernel(family)
        alpha = p.alpha if p.alpha is not None else 0.5
        with telemetry.span("train.glm.design") as design_span:
            ones = jnp.ones((X.shape[0], 1), jnp.float32)
            Xi = jnp.concatenate([X, ones], axis=1)  # intercept column last
            design_span.attrs.update(hbm_span_attrs())
        # start intercept, null deviance and neff: eager reductions, each
        # read back with a sync, between the design and the first step
        with telemetry.span("train.glm.start"):
            free = np.zeros(P + 1, dtype=bool)
            free[-1] = True

            beta = np.zeros(P + 1, dtype=np.float64)
            b0 = float(family.init_intercept(y, w))
            beta[-1] = b0 if p.intercept else 0.0

            # null deviance
            mu0 = family.linkinv(jnp.full_like(y, b0) + offset)
            nulldev = float(jnp.sum(family.deviance(y, mu0, w)))
            neff = float(jnp.sum(w))

            gram_plan = _gram_plan_attrs(Xi, w, offset)
        if p.lambda_search:
            with telemetry.span("train.glm.gram", **gram_plan):
                G0, b_, _, _ = step(Xi, y, w, jnp.asarray(beta, jnp.float32),
                                    offset)
                grad0 = np.abs(np.asarray(b_) - np.asarray(G0) @ beta)[:-1]
            lmax = float(grad0.max()) / max(alpha, 1e-3) / max(neff, 1.0)
            nlambdas = p.nlambdas if p.nlambdas > 0 else (
                100 if alpha > 0 else 30)
            lambdas = np.geomspace(lmax, lmax * p.lambda_min_ratio, nlambdas)
        else:
            lambdas = [p.lambda_ if p.lambda_ is not None else 0.0]

        lbfgs = bool(p.solver) and p.solver.upper() in ("L_BFGS", "LBFGS")
        if lbfgs and getattr(self, "_bounds", None) is not None:
            # reference restriction: L-BFGS has no projection step
            # (`hex/glm/GLM.java` beta constraints require IRLSM/COD)
            raise ValueError("beta_constraints are not supported with "
                             "solver=L_BFGS — use IRLSM or "
                             "COORDINATE_DESCENT")

        use_cod = bool(p.solver) and p.solver.upper() in (
            "COORDINATE_DESCENT", "COORDINATE_DESCENT_NAIVE")
        cod_lo = cod_hi = None
        if use_cod:
            # COD applies bounds per coordinate like the reference's
            # bc.applyBounds inside the sweep
            P1 = len(beta)
            cod_lo, cod_hi = np.full(P1, -np.inf), np.full(P1, np.inf)
            if p.non_negative:
                cod_lo[:-1] = 0.0
            if getattr(self, "_bounds", None) is not None:
                lo_b, hi_b = self._bounds
                cod_lo, cod_hi = np.maximum(cod_lo, lo_b), np.minimum(cod_hi, hi_b)

        dev_probe = _make_dev_kernel(family)
        admm_state: dict = {}  # (z, u) warm start across IRLS/path solves

        # the path: one entry a lambda fitted, each warm-started at the last
        # (lambda, deviance, iterations, beta on the training scale); without
        # a search it has the one lambda. A search stops early
        # (`GLM.java` _early_stop_search, default-on like the reference)
        # once an extra lambda stops buying deviance: the remaining path
        # only densifies coefficients, and each skipped lambda costs 1+
        # full Gram passes
        path: list = []
        stopped_early = False
        with (telemetry.span("train.glm.path", lambdas_planned=len(lambdas))
              if p.lambda_search else contextlib.nullcontext()) as path_span:
            for lam in lambdas:
                job.check_cancelled()
                if path and job.time_exceeded():
                    break  # keep the last lambda fitted (partial path)
                lam = float(lam)
                its, dev_final = 0, None
                if lbfgs:  # its own solver at this lambda: no IRLS below
                    beta, dev_final, its = self._fit_lbfgs(
                        Xi, y, w, offset, family, beta, lam, alpha, neff,
                        nulldev, job)
                l1 = alpha * lam * neff
                l2 = (1 - alpha) * lam * neff
                for it in range(0 if lbfgs else max(p.max_iterations, 1)):
                    if it and job.time_exceeded():
                        break
                    # dispatch, the wait for the Gram and its copy out
                    # (the copy drains the step)
                    with telemetry.span("train.glm.gram", **gram_plan):
                        G, b, dev, _ = step(
                            Xi, y, w, jnp.asarray(beta, jnp.float32), offset)
                        Gn = np.asarray(G, np.float64)
                        bn = np.asarray(b, np.float64)
                    its += 1
                    with telemetry.span("train.glm.solve"):
                        lincon = getattr(self, "_lincon", None)
                        if lincon is not None:
                            # exact active-set QP on the normal equations; box
                            # bounds / non_negative fold into the inequality rows
                            # (a post-hoc clip would break the linear constraints)
                            Aeq, ceq, Ain, cin = lincon
                            rows_in = [(Ain, cin)]
                            P1 = len(beta)
                            if p.non_negative:
                                E = -np.eye(P1)[: P1 - 1]
                                rows_in.append((E, np.zeros(P1 - 1)))
                            if getattr(self, "_bounds", None) is not None:
                                lo, hi = self._bounds
                                for j in range(P1):
                                    if np.isfinite(hi[j]):
                                        e = np.zeros(P1)
                                        e[j] = 1.0
                                        rows_in.append((e[None, :],
                                                        np.array([-hi[j]])))
                                    if np.isfinite(lo[j]):
                                        e = np.zeros(P1)
                                        e[j] = -1.0
                                        rows_in.append((e[None, :],
                                                        np.array([lo[j]])))
                            Ain_all = np.vstack([r[0] for r in rows_in])
                            cin_all = np.concatenate([r[1] for r in rows_in])
                            beta_new = _constrained_qp(Gn + l2 * np.eye(len(beta)),
                                                       bn, Aeq, ceq, Ain_all,
                                                       cin_all)
                        elif use_cod:
                            beta_new = _cod_solve(Gn, bn, l1, l2, free, beta,
                                                  p.beta_epsilon, cod_lo, cod_hi)
                        else:
                            beta_new = _admm_solve(Gn, bn, l1, l2, free,
                                                   state=admm_state)
                        if lincon is None and p.non_negative:
                            nb = beta_new[:-1]
                            beta_new[:-1] = np.clip(nb, 0, None)
                        if lincon is None \
                                and getattr(self, "_bounds", None) is not None:
                            lo, hi = self._bounds
                            beta_new = np.clip(beta_new, lo, hi)
                    # convergence vs the INCOMING beta, first iteration
                    # included: a warm-started lambda whose solution has not
                    # moved converges in ONE step — the glmnet warm-path
                    # economics RuleFit's streaming IRLS already rides (the
                    # historic `if it else np.inf` guard forced every lambda
                    # to pay at least two Gram passes)
                    diff = np.max(np.abs(beta_new - beta))
                    beta = beta_new
                    if diff < p.beta_epsilon:
                        dev_final = None  # beta moved since `dev` — probe below
                        break
                    # deviance-plateau check via the CHEAP probe (one matvec)
                    # at the post-solve beta, instead of discovering the
                    # plateau one full Gram pass later: same epsilon, same
                    # criterion, measured one iteration earlier and ~P× cheaper
                    with telemetry.span("train.glm.probe"):
                        dev_new = float(dev_probe(
                            Xi, y, w, jnp.asarray(beta, jnp.float32), offset))
                    dev_final = dev_new
                    if (abs(float(dev) - dev_new)
                            < p.objective_epsilon * abs(nulldev)):
                        break
                if dev_final is None:
                    with telemetry.span("train.glm.probe"):
                        dev_final = float(dev_probe(
                            Xi, y, w, jnp.asarray(beta, jnp.float32), offset))
                path.append((lam, dev_final, its, beta.copy()))
                if (p.lambda_search and p.early_stopping and len(path) > 1
                        and path[-2][1] - dev_final < 1e-4 * abs(nulldev)):
                    stopped_early = True
                    break
            iters_total = sum(e[2] for e in path)
            if path_span is not None:
                path_span.attrs.update(
                    lambdas_fit=len(path), iterations=iters_total,
                    active=int(np.count_nonzero(beta[:-1])),
                    lambda_final=path[-1][0], stopped_early=stopped_early)
                telemetry.inc("train.glm.path.lambdas", len(path))
                telemetry.inc("train.glm.path.iterations", iters_total)
        lam, dev = path[-1][:2]
        return beta, lam, dev, nulldev, neff, iters_total, path

    def _fit_lbfgs(self, Xi, y, w, offset, family, beta0, lam, alpha, neff,
                   nulldev, job):
        """L-BFGS solver — `hex/optimization/L_BFGS.java` + the GLM L_BFGS
        path (`hex/glm/GLM.java:2130`). Minimizes ½·deviance + ½·λℓ₂‖β‖² on
        device via optax.lbfgs (autodiff supplies the gradient the reference
        derives per family by hand). Like the reference, only the ridge part
        of the penalty applies (ℓ₁ needs IRLSM/COORDINATE_DESCENT)."""
        import optax

        p = self.params
        l2 = (1.0 - alpha) * lam * neff if alpha < 1.0 else 0.0
        if alpha > 0 and lam > 0:
            from ..utils.log import warn

            warn("L_BFGS ignores the l1 share of the penalty "
                 "(reference behavior); use IRLSM for lasso paths")

        def obj(b):
            eta = Xi @ b + offset
            mu = family.linkinv(eta)
            dev = jnp.sum(family.deviance(y, mu, w))
            return 0.5 * dev + 0.5 * l2 * jnp.sum(b[:-1] ** 2)

        opt = optax.lbfgs()
        beta = jnp.asarray(beta0, jnp.float32)
        state = opt.init(beta)
        vg = optax.value_and_grad_from_state(obj)

        @jax.jit
        def step(beta, state):
            value, grad = vg(beta, state=state)
            updates, state = opt.update(grad, state, beta, value=value,
                                        grad=grad, value_fn=obj)
            return optax.apply_updates(beta, updates), state, value, grad

        prev = np.inf
        iters = 0
        for i in range(max(p.max_iterations, 1) * 4):  # cheap iterations
            job.check_cancelled()
            if i and job.time_exceeded():
                break
            beta, state, value, grad = step(beta, state)
            if p.non_negative:  # projected L-BFGS (IRLSM clips likewise)
                beta = beta.at[:-1].set(jnp.clip(beta[:-1], 0, None))
            iters += 1
            v = float(value)
            if abs(prev - v) < p.objective_epsilon * max(abs(nulldev), 1.0):
                break
            if float(jnp.max(jnp.abs(grad))) < p.beta_epsilon:
                break
            prev = v
        mu = family.linkinv(Xi @ beta + offset)
        dev = float(jnp.sum(family.deviance(y, mu, w)))
        return np.asarray(beta, np.float64), dev, iters

    def _build_ordinal(self, job, names, y_dev, resp_domain):
        """Ordinal (proportional-odds) regression — `hex/glm/GLM.java`'s
        ordinal family (solved there by GRADIENT_DESCENT_LH/SQERR). Cumulative
        logits P(y≤k) = σ(θ_k − xβ) with monotone thresholds enforced by a
        softplus reparameterization; fitted by full-batch Adam on device
        (autodiff supplies the reference's hand-derived likelihood gradients)."""
        import optax

        p = self.params
        fr = p.training_frame
        K = len(resp_domain)
        dinfo = DataInfo.make(fr, names, standardize=p.standardize,
                              missing_values_handling=p.missing_values_handling)
        X, okrow = dinfo.expand(fr)
        y = jnp.nan_to_num(y_dev)
        w = (~jnp.isnan(y_dev)).astype(jnp.float32) * okrow.astype(jnp.float32)
        if p.weights_column:
            w = w * jnp.nan_to_num(fr.vec(p.weights_column).data)
        P = X.shape[1]
        lam = p.lambda_ or 0.0
        alpha = p.alpha if p.alpha is not None else 0.5
        if alpha > 0 and lam > 0:
            from ..utils.log import warn

            warn("ordinal family ignores the l1 share of the penalty "
                 "(gradient solver; same restriction as L_BFGS)")
        l2 = (1 - alpha) * lam * float(jnp.sum(w))

        def thresholds(params):
            # θ_1 free; θ_k = θ_{k-1} + softplus(d_k) keeps them ordered
            return params["t0"] + jnp.concatenate(
                [jnp.zeros(1), jnp.cumsum(jax.nn.softplus(params["d"]))])

        def nll(params):
            eta = X @ params["beta"]
            th = thresholds(params)                       # (K-1,)
            cum = jax.nn.sigmoid(th[None, :] - eta[:, None])  # (R, K-1)
            cdf = jnp.concatenate([jnp.zeros((X.shape[0], 1)), cum,
                                   jnp.ones((X.shape[0], 1))], axis=1)
            yk = y.astype(jnp.int32)
            pk = (jnp.take_along_axis(cdf, yk[:, None] + 1, axis=1)
                  - jnp.take_along_axis(cdf, yk[:, None], axis=1))[:, 0]
            ll = jnp.sum(w * jnp.log(jnp.clip(pk, 1e-12, None)))
            return -ll + 0.5 * l2 * jnp.sum(params["beta"] ** 2)

        params = {"beta": jnp.zeros(P, jnp.float32),
                  "t0": jnp.zeros(1, jnp.float32),
                  "d": jnp.zeros(max(K - 2, 0), jnp.float32)}
        opt = optax.adam(1e-1)
        state = opt.init(params)
        # box beta_constraints apply by projection after each step (the
        # IRLSM/COD clip, here on the gradient path; closed the round-3
        # 'ordinal beta_constraints' gate)
        bounds = _beta_bounds(p.beta_constraints, dinfo)
        blo = bhi = None
        if bounds is not None:
            blo = jnp.asarray(bounds[0][:P], jnp.float32)
            bhi = jnp.asarray(bounds[1][:P], jnp.float32)

        @jax.jit
        def step(params, state):
            v, g = jax.value_and_grad(nll)(params)
            updates, state = opt.update(g, state, params)
            params = optax.apply_updates(params, updates)
            if blo is not None:
                params["beta"] = jnp.clip(params["beta"], blo, bhi)
            return params, state, v

        prev = np.inf
        for i in range(max(p.max_iterations, 1) * 10):
            job.check_cancelled()
            if i and job.time_exceeded():
                break
            params, state, v = step(params, state)
            v = float(v)
            if i % 20 == 19:
                if abs(prev - v) < p.objective_epsilon * max(abs(prev), 1.0):
                    break
                prev = v

        output = ModelOutput()
        output.names = names
        output.domains = {n: fr.vec(n).domain for n in names}
        output.response_domain = list(resp_domain)
        output.model_category = "Multinomial"  # ordinal scores like multiclass
        beta = np.asarray(params["beta"], np.float64)
        th = np.asarray(thresholds(params), np.float64)
        model = GLMOrdinalModel(p, output, dinfo, beta, th)
        raw = model.score0(X)
        ym = jnp.where(w > 0, y, jnp.nan)
        m = make_metrics("Multinomial", ym, raw,
                         w if p.weights_column else None,
                         auc_type=p.auc_type, domain=output.response_domain)
        output.training_metrics = m
        output.scoring_history = [{"iterations": i + 1,
                                   "negloglik": float(v)}]
        if p.validation_frame is not None:
            output.validation_metrics = model.model_performance(
                p.validation_frame)
        return model

    def _build_multinomial(self, job, names, y_dev, resp_domain):
        """Per-class block IRLS — `hex/glm/GLM.java` multinomial loop analog."""
        p = self.params
        fr = p.training_frame
        K = len(resp_domain)
        dinfo = DataInfo.make(fr, names, standardize=p.standardize,
                              missing_values_handling=p.missing_values_handling)
        X, okrow = dinfo.expand(fr)
        X, y_dev, pad_cols = _shard_cols(X, y_dev, p.feature_parallelism)
        ones = jnp.ones((X.shape[0], 1), jnp.float32)
        Xi = jnp.concatenate([X, ones], axis=1)
        y = jnp.nan_to_num(y_dev)
        w = (~jnp.isnan(y_dev)).astype(jnp.float32) * okrow.astype(jnp.float32)
        if p.weights_column:
            w = w * jnp.nan_to_num(fr.vec(p.weights_column).data)
        P = X.shape[1]
        betas = np.zeros((K, P + 1), dtype=np.float64)
        family = BinomialF()
        step = _make_irls_kernel(family)
        free = np.zeros(P + 1, dtype=bool)
        free[-1] = True
        alpha = p.alpha if p.alpha is not None else 0.5
        lam = p.lambda_ or 0.0
        neff = float(jnp.sum(w))
        # box constraints apply identically to every class block (the
        # reference projects each class against the shared BetaConstraint)
        bounds = _beta_bounds(p.beta_constraints, dinfo, pad_cols=pad_cols)
        sweeps = max(2, min(6, p.max_iterations // 5))
        for _ in range(sweeps):
            job.check_cancelled()
            for k in range(K):
                # offset = log-sum of other classes (softmax block coordinate)
                eta_all = Xi @ jnp.asarray(betas.T, jnp.float32)  # (R, K)
                other = (jax.nn.logsumexp(
                    jnp.where(jnp.arange(K)[None, :] == k, -jnp.inf, eta_all),
                    axis=1))
                off = other
                yk = (y == k).astype(jnp.float32)
                bk = betas[k].copy()
                for _ in range(3):
                    G, b, dev, _ = step(Xi, yk, w, jnp.asarray(bk, jnp.float32),
                                        -off)
                    bk = _admm_solve(np.asarray(G, np.float64),
                                     np.asarray(b, np.float64),
                                     alpha * lam * neff, (1 - alpha) * lam * neff,
                                     free)
                    if bounds is not None:
                        bk = np.clip(bk, bounds[0], bounds[1])
                betas[k] = bk
        if pad_cols:  # strip padding: per-class coefs (~0) and design cols
            betas = np.concatenate(
                [betas[:, :dinfo.ncols_expanded], betas[:, -1:]], axis=1)
            X = X[:, :dinfo.ncols_expanded]
        output = ModelOutput()
        output.names = names
        output.domains = {n: fr.vec(n).domain for n in names}
        output.response_domain = list(resp_domain)
        output.model_category = "Multinomial"
        model = GLMMultinomialModel(p, output, dinfo, betas, family)
        raw = model.score0(X)
        ym = jnp.where(w > 0, y, jnp.nan)
        output.training_metrics = make_metrics(
            "Multinomial", ym, raw, w if p.weights_column else None,
            auc_type=p.auc_type, domain=output.response_domain)
        return model

    def _build_hglm(self, job, names, y_dev, category):
        """Hierarchical GLM — linear mixed model with one categorical random
        intercept (`hex/glm/GLM.java` HGLM path, Lee & Nelder fitting;
        `GLMModel.java:638-641` restricts to exactly one random column).

        TPU-native structure: all data-sized cross products (XᵀX, XᵀZ, ZᵀZ,
        Xᵀy, Zᵀy) are one-hot einsums over the row-sharded design — Z never
        materializes beyond a one-hot matmul; the (P+q) Henderson solve and
        EM variance-component updates run on host per iteration, like the
        reference's home-node solve.
        """
        p = self.params
        fr = p.training_frame
        fam = (p.family or "AUTO").lower()
        if category != "Regression" or fam not in ("gaussian", "auto"):
            raise NotImplementedError("HGLM supports family=gaussian with a "
                                      "numeric response (the reference's "
                                      "tested path)")
        if not p.random_columns or len(p.random_columns) != 1:
            raise ValueError("HGLM requires exactly one random column "
                             "(`GLMModel.java:641`)")
        if p.rand_family and [str(f).lower() for f in p.rand_family] != [
                "gaussian"]:
            raise NotImplementedError("rand_family supports [gaussian]")
        rc = p.random_columns[0]
        rname = fr.names[int(rc)] if not isinstance(rc, str) else rc
        rvec = fr.vec(rname)
        if not rvec.is_categorical():
            raise ValueError(f"HGLM random column '{rname}' must be "
                             f"categorical")
        names = [n for n in names if n != rname]
        dinfo = DataInfo.make(fr, names, standardize=p.standardize,
                              missing_values_handling=p.missing_values_handling)
        X, okrow = dinfo.expand(fr)
        ones = jnp.ones((X.shape[0], 1), jnp.float32)
        Xi = jnp.concatenate([X, ones], axis=1)  # intercept last
        y = jnp.nan_to_num(y_dev)
        w = (~jnp.isnan(y_dev)).astype(jnp.float32) * okrow.astype(jnp.float32)
        if p.weights_column:
            w = w * jnp.nan_to_num(fr.vec(p.weights_column).data)
        q = len(rvec.domain)
        zi = jnp.nan_to_num(rvec.data, nan=-1.0).astype(jnp.int32)
        Zoh = jax.nn.one_hot(zi, q, dtype=jnp.float32)  # (R, q)
        Zoh = jnp.where((zi >= 0)[:, None], Zoh, 0.0)  # NA level → zero row

        @jax.jit
        def crossprods(Xi, Zoh, y, w):
            Xw = Xi * w[:, None]
            return (jnp.einsum("rp,rq->pq", Xw, Xi),      # XᵀWX
                    jnp.einsum("rp,rq->pq", Xw, Zoh),     # XᵀWZ
                    jnp.einsum("rp,rq->pq", Zoh * w[:, None], Zoh),  # ZᵀWZ
                    Xw.T @ y, (Zoh * w[:, None]).T @ y,
                    jnp.sum(w * y * y), jnp.sum(w))

        XtX, XtZ, ZtZ, Xty, Zty, yty, neff = (
            np.asarray(a, np.float64) for a in crossprods(Xi, Zoh, y, w))
        neff = float(neff)
        P1 = XtX.shape[0]

        # EM on variance components over Henderson's mixed-model equations
        sig_e, sig_u = 1.0, 1.0
        beta = np.zeros(P1)
        u = np.zeros(q)
        M = np.block([[XtX, XtZ], [XtZ.T, ZtZ]])  # iteration-invariant block
        rhs = np.concatenate([Xty, Zty])
        for it in range(max(p.max_iterations, 10)):
            job.check_cancelled()
            lam = sig_e / max(sig_u, 1e-12)
            A = M.copy()
            A[P1:, P1:] += lam * np.eye(q)
            A[np.diag_indices_from(A)] += 1e-8
            Ainv = np.linalg.inv(A)  # one factorization serves solve + traces
            sol = Ainv @ rhs
            beta_new, u_new = sol[:P1], sol[P1:]
            # E-step traces from the random-effect block of A⁻¹·σe²
            Tuu = Ainv[P1:, P1:] * sig_e
            sse = yty - 2 * rhs @ sol + sol @ (M @ sol)
            # standard LMM EM updates (Laird-Ware / Searle):
            #   σe² ← (êᵀê + σe²[(p+q) − λ·tr(A⁻¹_uu)])/n
            #   σu² ← (ûᵀû + tr(Tuu))/q,  Tuu = σe²·A⁻¹_uu
            sig_e_new = float((sse + sig_e * (P1 + q)
                               - lam * np.trace(Tuu)) / max(neff, 1.0))
            sig_u_new = float((u_new @ u_new + np.trace(Tuu)) / q)
            done = (abs(sig_e_new - sig_e) < 1e-8 * max(sig_e, 1.0)
                    and abs(sig_u_new - sig_u) < 1e-8 * max(sig_u, 1.0))
            beta, u = beta_new, u_new
            sig_e = max(sig_e_new, 1e-10)
            sig_u = max(sig_u_new, 1e-10)
            if done:
                break

        output = ModelOutput()
        output.names = names + [rname]
        output.domains = {n: fr.vec(n).domain for n in output.names}
        output.response_domain = None
        output.model_category = "Regression"
        model = HGLMModel(p, output, dinfo, beta, GaussianF(), u,
                          rname, list(rvec.domain))
        model.varfix = sig_e       # residual variance (`to2dTableHGLM`)
        model.varranef = sig_u     # random-effect variance
        raw = model.score0_with_ranef(X, zi)
        ym = jnp.where(w > 0, y, jnp.nan)
        m = make_metrics("Regression", ym, raw,
                         w if p.weights_column else None)
        output.training_metrics = m
        output.scoring_history = [{"iterations": it + 1,
                                   "varfix": sig_e, "varranef": sig_u}]
        return model

    def _varimp_from_beta(self, dinfo, beta):
        mag = np.abs(np.asarray(beta)[:-1])
        if mag.sum() <= 0:
            return None
        order = np.argsort(-mag)
        return {"variable": [dinfo.expanded_names[i] for i in order],
                "relative_importance": mag[order],
                "scaled_importance": mag[order] / mag.max(),
                "percentage": mag[order] / mag.sum()}


class HGLMModel(GLMModel):
    """Mixed model y = Xβ + Zu + e. Predictions add the level's BLUP random
    intercept when the level is known; unseen/NA levels fall back to the
    fixed-effects mean (the reference scores HGLM the same way)."""

    def __init__(self, params, output, dinfo, beta, family, ubeta,
                 random_column, random_domain, key=None):
        super().__init__(params, output, dinfo, beta, family, key=key)
        self.ubeta = np.asarray(ubeta, np.float64)
        self.random_column = random_column
        self.random_domain = list(random_domain)

    def coef_random(self) -> dict:
        """Per-level random intercepts (the reference's ubeta table)."""
        return {lvl: float(v) for lvl, v in zip(self.random_domain,
                                                self.ubeta)}

    def score0_with_ranef(self, X, zi) -> jax.Array:
        beta = jnp.asarray(self.beta, jnp.float32)
        eta = X @ beta[:-1] + beta[-1]
        ub = jnp.asarray(self.ubeta, jnp.float32)
        ranef = jnp.where((zi >= 0) & (zi < len(self.random_domain)),
                          ub[jnp.clip(zi, 0, len(self.random_domain) - 1)],
                          0.0)
        return eta + ranef

    def predict(self, fr: Frame) -> Frame:
        from ..frame.vec import Vec

        X = self.adapt_frame(fr)
        rv = (fr.vec(self.random_column)
              if self.random_column in fr.names else None)
        if rv is not None and rv.domain is not None:
            # remap the scoring frame's levels into the training domain
            lut = np.full(len(rv.domain), -1, np.int32)
            for i, lvl in enumerate(rv.domain):
                if lvl in self.random_domain:
                    lut[i] = self.random_domain.index(lvl)
            codes = np.nan_to_num(rv.to_numpy(), nan=-1.0).astype(np.int32)
            zi_np = np.full(X.shape[0], -1, np.int32)  # X rows are padded
            zi_np[:len(codes)] = np.where(codes >= 0,
                                          lut[np.clip(codes, 0, None)], -1)
            zi = jnp.asarray(zi_np)
        else:
            zi = jnp.full((X.shape[0],), -1, jnp.int32)
        mu = self.score0_with_ranef(X, zi)
        return Frame(["predict"], [Vec.from_device(mu, fr.nrow)])


class GLMOrdinalModel(GLMModel):
    """Proportional-odds model: β shared across classes + ordered thresholds."""

    def __init__(self, params, output, dinfo, beta, thresholds, key=None):
        super().__init__(params, output, dinfo, beta, BinomialF(), key=key)
        self.thresholds = thresholds  # (K-1,) ordered cutpoints

    def coef_norm(self) -> dict:
        out = dict(zip(self.dinfo.expanded_names,
                       np.asarray(self.beta, np.float64)))
        for k, t in enumerate(self.thresholds):
            out[f"threshold_{k + 1}"] = float(t)
        return out

    def coef(self) -> dict:
        base = _destandardize(
            np.concatenate([np.asarray(self.beta, np.float64), [0.0]]),
            self.dinfo)
        out = dict(zip(self.dinfo.expanded_names, base[:-1]))
        # σ(θ − x_std·β_std) = σ((θ − c) − x_orig·β_orig) with
        # c = −Σ β_j·m_j/s_j (= base[-1]); original-scale cutpoint is θ − c
        for k, t in enumerate(self.thresholds):
            out[f"threshold_{k + 1}"] = float(t) - float(base[-1])
        return out

    def score0(self, X):
        eta = X @ jnp.asarray(self.beta, jnp.float32)
        th = jnp.asarray(self.thresholds, jnp.float32)
        cum = jax.nn.sigmoid(th[None, :] - eta[:, None])
        cdf = jnp.concatenate([jnp.zeros((X.shape[0], 1)), cum,
                               jnp.ones((X.shape[0], 1))], axis=1)
        probs = jnp.diff(cdf, axis=1)
        label = jnp.argmax(probs, axis=1).astype(jnp.float32)
        return jnp.concatenate([label[:, None], probs], axis=1)


class GLMMultinomialModel(GLMModel):
    def coef(self) -> dict:
        """Per-class coefficient maps — h2o-py's coef() multinomial shape:
        {class_name: {coef_name: value}} on the original feature scale."""
        names = self.dinfo.expanded_names + ["Intercept"]
        B = _destandardize(np.asarray(self.beta, dtype=np.float64), self.dinfo)
        classes = self.output.response_domain or [str(k) for k in range(B.shape[0])]
        return {str(c): dict(zip(names, B[k])) for k, c in enumerate(classes)}

    def coef_norm(self) -> dict:
        names = self.dinfo.expanded_names + ["Intercept"]
        B = np.asarray(self.beta)
        classes = self.output.response_domain or [str(k) for k in range(B.shape[0])]
        return {str(c): dict(zip(names, B[k])) for k, c in enumerate(classes)}

    def score0(self, X):
        B = jnp.asarray(self.beta, jnp.float32)  # (K, P+1)
        eta = X @ B[:, :-1].T + B[:, -1][None, :]
        probs = jax.nn.softmax(eta, axis=1)
        label = jnp.argmax(probs, axis=1).astype(jnp.float32)
        return jnp.concatenate([label[:, None], probs], axis=1)
