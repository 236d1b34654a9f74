"""Plain binomial GLM (logit link, no penalty, intercept) by iteratively
reweighted least squares, independent of the program: the reference for
``correct``, and, with lower-precision Gram operands or a planted fault,
the control put in the program's place.

The weighted Gram and its right-hand side are float32 at ``highest``
matmul precision over blocks of rows on the device, and the blocks' sums
and the solve are float64 on the host. Coefficients are on the columns'
natural scale, the intercept last: without a penalty the maximum
likelihood does not depend on whether a solver standardises.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from .gbm import logloss_auc  # the same float64 metrics, by the same definitions

BLOCK = 8192


@functools.lru_cache(maxsize=None)
def _jits():
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def _round(a, dtype):
        return a if dtype is None else a.astype(dtype).astype(jnp.float32)

    def gram(X, y, live, beta, dtype, BLOCK):
        """Per block of rows: X'WX (P+1, P+1), X'Wz (P+1,) and the score
        X'(y - mu), with the intercept's column of ones appended; rows
        that are not live carry weight 0. ``dtype`` rounds the product's operands (XW, X and
        z) once to a lower type: the lower-precision control."""
        F, R = X.shape
        nblk = R // BLOCK

        def body(_, blk):
            xb, yb, lb = blk                               # (F, rb) (rb,)
            xb = jnp.where(lb[None, :], xb, 0.0)
            xi = jnp.concatenate([xb, jnp.ones((1, BLOCK), jnp.float32)], 0)
            eta = jnp.einsum("pr,p->r", xi, beta, precision=hi)
            mu = jax.nn.sigmoid(eta)
            w = jnp.where(lb, mu * (1.0 - mu), 0.0)
            yb = jnp.where(lb, yb, 0.0)
            z = eta + (yb - mu) / jnp.maximum(mu * (1.0 - mu), 1e-10)
            xw = _round(xi * w[None, :], dtype)
            G = jnp.einsum("pr,qr->pq", xw, _round(xi, dtype), precision=hi)
            b = jnp.einsum("pr,r->p", xw, _round(z, dtype), precision=hi)
            score = jnp.einsum("pr,r->p", xi, jnp.where(lb, yb - mu, 0.0),
                               precision=hi)
            return None, (G, b, score)

        _, out = jax.lax.scan(
            body, None,
            (X.reshape(F, nblk, BLOCK).transpose(1, 0, 2),
             y.reshape(nblk, BLOCK), live.reshape(nblk, BLOCK)))
        return out

    def prob(X, beta):
        xi_b = jnp.einsum("pr,p->r", X, beta[:-1], precision=hi) + beta[-1]
        return jax.nn.sigmoid(xi_b)

    return {"gram": jax.jit(gram, static_argnums=(4, 5)),
            "prob": jax.jit(prob)}


class Data:
    """The cell's data as the reference holds it: X (F, plen) and y on the
    device, NaN padding replaced by weight 0."""

    def __init__(self, cols, nrow: int):
        import jax.numpy as jnp

        self.nrow = int(nrow)
        self.X = jnp.stack([c for c in cols[:-1]], axis=0)
        self.plen = int(self.X.shape[1])
        self.block = math.gcd(self.plen, BLOCK)
        self.live = jnp.arange(self.plen) < self.nrow
        self.y_dev = jnp.where(self.live, cols[-1], 0.0)
        self.y = np.asarray(cols[-1], np.float64)[: self.nrow]

    def step(self, beta: np.ndarray, dtype=None, rows=None):
        """One IRLS pass at beta: (G, b, score) in float64. ``rows``
        restricts the live rows (the half-batch fault)."""
        import jax.numpy as jnp

        live = self.live if rows is None else (self.live & rows)
        out = _jits()["gram"](self.X, self.y_dev, live,
                              jnp.asarray(beta, jnp.float32), dtype,
                              self.block)
        return tuple(np.asarray(a, np.float64).sum(0) for a in out)

    def prob(self, beta: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        p = _jits()["prob"](self.X, jnp.asarray(beta, jnp.float32))
        return np.asarray(p, np.float64)[: self.nrow]


def fit(data: Data, iterations: int, dtype_name: str | None = None,
        metrics_dtype: str | None = None, fault: str | None = None) -> dict:
    """``iterations`` IRLS steps from (0, ..., 0, logit of the mean).
    ``dtype_name`` rounds the Gram's operands once to that type, and
    ``metrics_dtype`` every probability the reported metrics are made from.
    ``fault``: ``state_unchanged`` (the solve's result is never kept),
    ``half_batch`` (every second row left out), ``altered`` (the largest
    coefficient returned 1% larger)."""
    import jax.numpy as jnp

    dtype = None if dtype_name is None else getattr(jnp, dtype_name)
    P1 = int(data.X.shape[0]) + 1
    ybar = float(np.mean(data.y))
    beta = np.zeros(P1)
    beta[-1] = np.log(ybar / (1 - ybar))
    rows = None
    if fault == "half_batch":
        rows = (jnp.arange(data.plen) % 2) == 0
    for _ in range(iterations):
        G, b, _ = data.step(beta, dtype, rows)
        new = np.linalg.solve(G, b)
        if fault != "state_unchanged":
            beta = new
    if fault == "altered":
        beta = beta.copy()
        beta[np.argmax(np.abs(beta))] *= 1.01
    ll, auc = logloss_auc(data.prob(beta), data.y, metrics_dtype)
    return {"coef": beta, "logloss": ll, "auc": auc}


def check(cand: dict, data: Data, converge: int) -> dict:
    """The numbers compared, for a candidate fit (the program's, the
    control's or a faulty one) against the reference's own maximum
    likelihood, ``converge`` float64 IRLS steps from its own start, and
    against its own scoring of the candidate's coefficients."""
    ref = fit(data, converge)["coef"]
    got = np.asarray(cand["coef"], np.float64)
    out = {"coef_gap": float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))}
    # the score equation at the candidate: X'(y - mu) over the rows, which
    # the maximum likelihood makes nought
    out["score_gap"] = float(np.max(np.abs(data.step(got)[2])) / data.nrow)
    ll, auc = logloss_auc(data.prob(got), data.y)
    out["logloss_gap"] = abs(float(cand["logloss"]) - ll) / ll
    out["auc_gap"] = abs(float(cand["auc"]) - auc)
    return out


def compare(result: dict, data: Data, config: dict) -> dict:
    """The numbers compared for what one timed job returned."""
    names = [f"f{j}" for j in range(int(data.X.shape[0]))] + ["Intercept"]
    coef = result["coef"]
    cand = dict(result, coef=np.array([coef[n] for n in names], np.float64))
    return check(cand, data, int(config["correct"]["converge_iterations"]))
