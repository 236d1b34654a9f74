"""Plain elastic-net binomial GLM on the documented ``lambda_search`` path
(Friedman, Hastie, Tibshirani 2010, as H2O's GLM booklet states it),
independent of the program: the reference for ``correct``, and, walked in
a lower precision or with a planted fault, the control put in the
program's place.

The problem, over N rows, on columns standardised by their own mean and
sample standard deviation, the intercept unpenalised:

    minimise  -loglik(b) / N + lambda (alpha |b|_1 + (1 - alpha)/2 |b|_2^2)

with lambda on the geometric grid of ``nlambdas`` points from
``lambda_max`` (the smallest lambda whose solution is all zero) down to
``lambda_min_ratio`` times it. The Gram passes are ``reference/glm.py``'s:
float32 at ``highest`` matmul precision over blocks of rows on the device,
the blocks' sums in float64 on the host. Everything else is float64 on the
host: the standardisation is a linear map of the 29 coordinates, applied to
the sums, so the design is read as it lies. Coefficients go in and out on
the columns' natural scale, the intercept last.
"""

from __future__ import annotations

import sys

import numpy as np

from .gbm import logloss_auc  # the same float64 metrics, by the same definitions
from .glm import Data  # noqa: F401 - the harness builds ``ref.Data(cols, nrow)``

FAULTS = ("state_unchanged", "half_batch", "altered", "unpenalised",
          "unstandardised")


def settings(config: dict) -> dict:
    """What the deployment states of the path, from the configuration."""
    p, c = config["params"], config["correct"]
    return {"alpha": float(p["alpha"]), "nlambdas": int(p["nlambdas"]),
            "ratio": float(p["lambda_min_ratio"]),
            "stop": float(c["stop_tolerance"])}


class Scale:
    """The reference's own standardisation, as a linear map of the
    coordinates: ``std = T @ [x, 1]``. One pass at beta = 0, where every
    weight is 1/4, gives the columns' sums and sums of squares."""

    def __init__(self, data, standardise: bool = True, rows=None):
        P = int(data.X.shape[0])
        G = 4.0 * data.step(np.zeros(P + 1), None, rows)[0]
        self.n = float(G[-1, -1])
        self.mean = G[:-1, -1] / self.n
        var = (np.diag(G)[:-1] - self.n * self.mean ** 2) / (self.n - 1.0)
        self.sd = np.sqrt(var)
        self.T = np.eye(P + 1)
        if standardise:
            self.T[:-1, :-1] = np.diag(1.0 / self.sd)
            self.T[:-1, -1] = -self.mean / self.sd

    def natural(self, beta_std):
        return self.T.T @ np.asarray(beta_std, np.float64)

    def standard(self, beta_nat):
        return np.linalg.solve(self.T.T, np.asarray(beta_nat, np.float64))

    def quadratic(self, data, beta_std, dtype=None, rows=None):
        """The IRLS quadratic at beta, a row: (X'WX / N, X'Wz / N) and the
        score X'(y - mu) / N, all on the standardised coordinates."""
        G, b, score = data.step(self.natural(beta_std), dtype, rows)
        T = self.T
        return T @ G @ T.T / self.n, T @ b / self.n, T @ score / self.n


def null_beta(data, y=None) -> np.ndarray:
    """Every coefficient zero, the intercept the logit of the mean of
    ``y`` (the data's own unless a fault leaves rows out)."""
    ybar = float(np.mean(data.y if y is None else y))
    beta = np.zeros(int(data.X.shape[0]) + 1)
    beta[-1] = np.log(ybar / (1.0 - ybar))
    return beta


def lambda_grid(data, scale, s: dict, rows=None, y=None) -> np.ndarray:
    """``lambda_max`` = the largest |x_j'(y - ybar)| / (N alpha) over the
    standardised columns, and the geometric grid below it."""
    score = scale.quadratic(data, null_beta(data, y), None, rows)[2]
    lmax = float(np.max(np.abs(score[:-1]))) / s["alpha"]
    return np.geomspace(lmax, lmax * s["ratio"], s["nlambdas"])


def descend(A, c, l1, l2, beta, tol=1e-12, sweeps=100_000) -> np.ndarray:
    """Cyclic coordinate descent on
    ``b'Ab/2 - c'b + l1 |b[:-1]|_1 + l2/2 |b[:-1]|_2^2`` from ``beta``."""
    beta = np.array(beta, np.float64)
    last = len(beta) - 1
    for _ in range(sweeps):
        moved = 0.0
        for j in range(last + 1):
            r = c[j] - A[j] @ beta + A[j, j] * beta[j]
            new = (r / A[j, j] if j == last else
                   np.sign(r) * max(abs(r) - l1, 0.0) / (A[j, j] + l2))
            moved = max(moved, abs(new - beta[j]))
            beta[j] = new
        if moved < tol:
            break
    return beta


def solve_at(data, scale, lam, alpha, beta, dtype=None, rows=None,
             tol=1e-7, cap=30, keep=True):
    """The penalised optimum at one lambda from ``beta`` (standardised):
    IRLS outside, coordinate descent on its quadratic inside. Returns the
    solution and the passes it took. ``keep`` False is the fault of a
    solve whose result is never kept."""
    l1, l2 = lam * alpha, lam * (1.0 - alpha)
    for it in range(cap):
        A, c, _ = scale.quadratic(data, beta, dtype, rows)
        new = descend(A, c, l1, l2, beta)
        moved = float(np.max(np.abs(new - beta)))
        if keep:
            beta = new
        if moved < tol or not keep:
            break
    return beta, it + 1


def deviance(p: np.ndarray, y: np.ndarray) -> float:
    p = np.clip(p, 1e-15, 1.0 - 1e-15)
    return float(-2.0 * np.sum(np.where(y > 0, np.log(p), np.log1p(-p))))


def null_deviance(y: np.ndarray) -> float:
    return deviance(np.full(len(y), np.mean(y)), y)


def walk(data, config: dict, dtype_name: str | None = None,
         metrics_dtype: str | None = None, fault: str | None = None) -> dict:
    """The whole path as the deployment states it: every lambda of the grid
    warm-started at the last, until one more lambda buys less than
    ``stop_tolerance`` of the null deviance. Returns the last lambda's
    model as a job returns it (``coef`` natural, ``logloss``, ``auc``) and
    the path (``lambdas`` the grid, then a list a lambda fitted).

    ``dtype_name`` rounds the Gram's operands once to that type and
    ``metrics_dtype`` every probability the reported metrics are made
    from: the control. ``fault``: ``state_unchanged`` (no solve's result
    is kept), ``half_batch`` (every second row left out), ``altered`` (the
    largest coefficient returned 1% larger), ``unpenalised`` (lambda 0
    fitted and returned), ``unstandardised`` (the penalty applied to the
    columns as they lie)."""
    import jax.numpy as jnp

    s = settings(config)
    dtype = None if dtype_name is None else getattr(jnp, dtype_name)
    rows = (jnp.arange(data.plen) % 2) == 0 if fault == "half_batch" else None
    kept = slice(None) if rows is None else np.asarray(rows)[: data.nrow]
    y = data.y[kept]

    def dev_of(beta_std):
        return deviance(data.prob(scale.natural(beta_std))[kept], y)

    scale = Scale(data, fault != "unstandardised", rows)
    grid = lambda_grid(data, scale, s, rows, y)
    beta = null_beta(data, y)
    null = null_deviance(y)
    out = {"lambdas": grid, "null_deviance": null, "betas": [],
           "deviances": [], "passes": []}
    for lam in ([0.0] if fault == "unpenalised" else grid):
        # a lambda from a warm start takes three passes; in the control's
        # precision the iterates stop short of ``tol`` and the cap ends them
        beta, n = solve_at(data, scale, lam, s["alpha"], beta, dtype, rows,
                           cap=10, keep=fault != "state_unchanged")
        out["betas"].append(scale.natural(beta))
        out["deviances"].append(dev_of(beta))
        out["passes"].append(n)
        d = out["deviances"]
        if len(d) > 1 and d[-2] - d[-1] < s["stop"] * abs(null):
            break
    coef = out["betas"][-1].copy()
    if fault == "altered":
        coef[np.argmax(np.abs(coef))] *= 1.01
    ll, auc = logloss_auc(data.prob(coef), data.y, metrics_dtype)
    return dict(out, coef=coef, logloss=ll, auc=auc)


def check(cand: dict, data, config: dict) -> dict:
    """The numbers compared, for a candidate (the program's, the control's
    or a faulty one): is it a stationary point of the penalised problem at
    some lambda, is that lambda a point of the documented grid, is it the
    optimum there, does the path end there, and are the reported metrics
    those of its coefficients."""
    s = settings(config)
    alpha, tol = s["alpha"], s["stop"]
    scale = Scale(data)
    grid = lambda_grid(data, scale, s)
    got = scale.standard(cand["coef"])
    b = got[:-1]
    g = scale.quadratic(data, got)[2]
    # stationarity reads the lambda off the largest coordinate:
    # g_j = lambda (alpha sign(b_j) + (1 - alpha) b_j) where b_j != 0.
    # With no coordinate on, zero solves every lambda from the largest
    # |g_j| / alpha up: the smallest of them is read
    j = int(np.argmax(np.abs(b)))
    lam = (g[j] / (alpha * np.sign(b[j]) + (1.0 - alpha) * b[j]) if b[j]
           else float(np.max(np.abs(g[:-1]))) / alpha)
    on = b != 0
    viol = np.where(on, np.abs(g[:-1] - lam * (alpha * np.sign(b)
                                               + (1.0 - alpha) * b)),
                    np.maximum(np.abs(g[:-1]) - lam * alpha, 0.0))
    out = {"kkt_gap": float(max(viol.max(), abs(g[-1])) / abs(lam * alpha))}
    step = np.log(grid[0] / grid[1])
    k = int(np.argmin(np.abs(np.log(grid) - np.log(abs(lam)))))
    out["lambda_grid_gap"] = float(
        abs(np.log(abs(lam)) - np.log(grid[k])) / step)
    # the reference's own optimum at that grid point and at the two before
    # it: the early stop fires at the first lambda that buys less than
    # ``stop_tolerance`` of the null deviance over the one before, so at k
    # it must have fired and at k - 1 it must not. ``stop_gap`` is the
    # larger of the two ratios, at most 1 on a path that ends where the
    # rule puts it. The first lambda has no lambda before it, so no path
    # ends there: it reads as buying the whole null deviance
    null = null_deviance(data.y)
    beta, devs = null_beta(data), {}
    for i in range(max(k - 2, 0), k + 1):
        beta, _ = solve_at(data, scale, grid[i], alpha, beta)
        devs[i] = deviance(data.prob(scale.natural(beta)), data.y)
    bought = {i: (devs[i - 1] - devs[i]) / (tol * abs(null))
              for i in devs if i - 1 in devs}
    out["stop_gap"] = float(max(
        bought.get(k, 1.0 / tol),
        1.0 / max(bought[k - 1], tol) if k - 1 in bought else 0.0))
    ref = scale.natural(beta)
    coef = np.asarray(cand["coef"], np.float64)
    out["coef_gap"] = float(np.max(np.abs(coef - ref)) / np.max(np.abs(ref)))
    out["support_gap"] = float(np.sum((beta[:-1] != 0) != on))
    ll, auc = logloss_auc(data.prob(coef), data.y)
    out["logloss_gap"] = abs(float(cand["logloss"]) - ll) / ll
    out["auc_gap"] = abs(float(cand["auc"]) - auc)
    print(f"reference glm_path: path_index={k} lambda_hat={float(lam)!r} "
          f"active={int(on.sum())} lambda_max={float(grid[0])!r} "
          f"explained_deviance={1.0 - devs[k] / null!r}", file=sys.stderr,
          flush=True)
    return out


def compare(result: dict, data, config: dict) -> dict:
    """The numbers compared for what one timed job returned."""
    names = [f"f{j}" for j in range(int(data.X.shape[0]))] + ["Intercept"]
    coef = result["coef"]
    cand = dict(result, coef=np.array([coef[n] for n in names], np.float64))
    return check(cand, data, config)
