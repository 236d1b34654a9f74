"""Plain binomial additive model with cubic regression splines (Wood,
*Generalized Additive Models*, 2nd ed. 2017, sections 5.3.1, 5.4.1, 6.1, as
H2O-3's GAM page states it), independent of the program: the reference for
``correct``, and, fitted in a lower precision or with a planted fault, the
control put in the program's place.

The model, over N rows of weight 1: columns named in ``gam_columns`` are
smooth, the others linear, ``eta = b0 + x_lin'b + sum_s (X_s Z_s) g_s``, and

    minimise  -loglik(eta) / N + sum_s scale_s g_s' Z_s' S_s Z_s g_s

(the penalty with factor 1, not 1/2; intercept and linear block unpenalised).

- knots of a smooth: ``K`` of them, the ``(j - 1) / (K - 1)`` quantiles of
  the column over the real rows, EXACT (float64 on the host, linear
  interpolation between order statistics), so the first is the minimum and
  the last the maximum;
- basis ``X_s`` (N x K), values at the knots: for ``x_j <= x <= x_j+1`` the
  row is ``a- e_j + a+ e_j+1 + (c- e_j + c+ e_j+1) F`` with
  ``a- = (x_j+1 - x) / h_j``, ``a+ = (x - x_j) / h_j``,
  ``c- = ((x_j+1 - x)^3 / h_j - h_j (x_j+1 - x)) / 6``,
  ``c+ = ((x - x_j)^3 / h_j - h_j (x - x_j)) / 6``, ``F = [0; B^-1 D; 0]``,
  ``S = D' B^-1 D`` (``D`` and ``B`` the banded matrices of section 5.3.1);
- identifiability: ``c = X_s' 1``; ``Z_s`` (K x K-1) is the last K-1 columns
  of the Householder reflection ``I - 2 v v' / v'v``, ``v = c + sign(c_1)
  |c| e_1``, so ``1' X_s Z_s = 0``;
- fitted by Newton's method on the penalised objective, float64 on the
  host, to a gradient under ``gradient_tolerance`` (its own stop).

The passes over the rows (basis, design, Gram, score) are plain
``jax.numpy`` in float32 at ``highest`` matmul precision over blocks of
rows on the device, the blocks' sums in float64 on the host, as
``reference/glm.py``'s are. Coefficients go in and out under the program's
names: the linear columns, ``<col>_gam.0..K-2`` a smooth, ``Intercept``.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from .gbm import logloss_auc  # the same float64 metrics, by the same definitions
from .glm import BLOCK, Data as _Frame

FAULTS = ("unpenalised", "raw_scale_penalty", "unconstrained",
          "uniform_knots", "half_batch", "state_unchanged")


def cr_matrices(knots: np.ndarray):
    """(F, S) of section 5.3.1 for one knot vector, float64."""
    K = len(knots)
    h = np.diff(knots)
    D = np.zeros((K - 2, K))
    B = np.zeros((K - 2, K - 2))
    for i in range(K - 2):
        D[i, i:i + 3] = (1 / h[i], -1 / h[i] - 1 / h[i + 1], 1 / h[i + 1])
        B[i, i] = (h[i] + h[i + 1]) / 3
        if i < K - 3:
            B[i, i + 1] = B[i + 1, i] = h[i + 1] / 6
    BinvD = np.linalg.solve(B, D)
    return np.vstack([np.zeros(K), BinvD, np.zeros(K)]), D.T @ BinvD


def householder_z(c: np.ndarray) -> np.ndarray:
    v = np.array(c, np.float64)
    v[0] += math.copysign(np.linalg.norm(c), c[0])
    return (np.eye(len(c)) - 2.0 * np.outer(v, v) / (v @ v))[:, 1:]


@functools.lru_cache(maxsize=None)
def _jits():
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def _round(a, dtype):
        return a if dtype is None else a.astype(dtype).astype(jnp.float32)

    def basis(x, knots, F):
        """(rb,) values -> (rb, K) cubic regression spline basis."""
        K = knots.shape[0]
        x = jnp.clip(x, knots[0], knots[-1])
        j = jnp.clip(jnp.searchsorted(knots, x, side="right",
                                      method="compare_all") - 1, 0, K - 2)
        lo = jax.nn.one_hot(j, K, dtype=jnp.float32)
        up = jax.nn.one_hot(j + 1, K, dtype=jnp.float32)
        xj = jnp.einsum("rk,k->r", lo, knots, precision=hi)
        xj1 = jnp.einsum("rk,k->r", up, knots, precision=hi)
        h = xj1 - xj
        am, ap = (xj1 - x) / h, (x - xj) / h
        cm = ((xj1 - x) ** 3 / h - h * (xj1 - x)) / 6.0
        cp = ((x - xj) ** 3 / h - h * (x - xj)) / 6.0
        return (lo * am[:, None] + up * ap[:, None]
                + jnp.einsum("rk,kl->rl", lo * cm[:, None] + up * cp[:, None],
                             F, precision=hi))

    def blocks(X, y, live, BLOCK):
        F, R = X.shape
        n = R // BLOCK
        return (X.reshape(F, n, BLOCK).transpose(1, 0, 2),
                y.reshape(n, BLOCK), live.reshape(n, BLOCK))

    def sums(X, y, live, knots, Fm, smooth, BLOCK):
        """Per block, each smooth's basis summed over the live rows."""
        def body(_, blk):
            xb, _, lb = blk
            return None, tuple(
                jnp.sum(jnp.where(lb[:, None], basis(
                    jnp.where(lb, xb[c], knots[s][0]), knots[s], Fm[s]), 0.0),
                    axis=0) for s, c in enumerate(smooth))
        return jax.lax.scan(body, None, blocks(X, y, live, BLOCK))[1]

    def design(xb, lb, knots, Fm, Z, smooth, linear, bdtype):
        """One block's (rb, P+1) design: linear columns, each smooth's
        basis through its Z, ones; rows that are not live are zero.
        ``bdtype`` rounds each basis value once to a lower type."""
        parts = [jnp.stack([xb[c] for c in linear], axis=1)]
        for s, c in enumerate(smooth):
            B = _round(basis(jnp.where(lb, xb[c], knots[s][0]), knots[s],
                             Fm[s]), bdtype)
            parts.append(jnp.einsum("rk,kl->rl", B, Z[s], precision=hi))
        parts.append(jnp.ones((xb.shape[1], 1), jnp.float32))
        return jnp.where(lb[:, None], jnp.concatenate(parts, axis=1), 0.0)

    def gram(X, y, live, knots, Fm, Z, beta, smooth, linear, dtype, bdtype,
             BLOCK):
        """Per block: X'WX and X'Wz (the IRLS step's two sums), the score
        X'(y - mu) and the design's column sums; ``dtype`` rounds the two
        products' operands (XW, X and z) once to a lower type: the
        lower-precision control. The score is never rounded: it judges."""
        def body(_, blk):
            xb, yb, lb = blk
            xi = design(jnp.where(lb[None, :], xb, 0.0), lb, knots, Fm, Z,
                        smooth, linear, bdtype)
            eta = jnp.einsum("rp,p->r", xi, beta, precision=hi)
            mu = jax.nn.sigmoid(eta)
            w = jnp.where(lb, mu * (1.0 - mu), 0.0)
            yb = jnp.where(lb, yb, 0.0)
            z = eta + (yb - mu) / jnp.maximum(mu * (1.0 - mu), 1e-10)
            xw = _round(xi * w[:, None], dtype)
            G = jnp.einsum("rp,rq->pq", xw, _round(xi, dtype), precision=hi)
            b = jnp.einsum("rp,r->p", xw, _round(z, dtype), precision=hi)
            score = jnp.einsum("rp,r->p", xi, jnp.where(lb, yb - mu, 0.0),
                               precision=hi)
            return None, (G, b, score, jnp.sum(xi, axis=0))
        return jax.lax.scan(body, None, blocks(X, y, live, BLOCK))[1]

    def prob(X, y, live, knots, Fm, Z, beta, smooth, linear, BLOCK):
        def body(_, blk):
            xb, _, lb = blk
            xi = design(jnp.where(lb[None, :], xb, 0.0), lb, knots, Fm, Z,
                        smooth, linear, None)
            return None, jax.nn.sigmoid(
                jnp.einsum("rp,p->r", xi, beta, precision=hi))
        return jax.lax.scan(body, None, blocks(X, y, live, BLOCK))[1]

    return {"sums": jax.jit(sums, static_argnums=(5, 6)),
            "gram": jax.jit(gram, static_argnums=(7, 8, 9, 10, 11)),
            "prob": jax.jit(prob, static_argnums=(7, 8, 9))}


class Data(_Frame):
    """The cell's data as ``reference/glm.py`` holds it (X (F, plen) and y on
    the device, NaN padding replaced by weight 0), and a place for the
    reference's own optimum, fitted once a run."""

    def __init__(self, cols, nrow: int):
        super().__init__(cols, nrow)
        self._optimum: dict = {}


class Smooths:
    """What the rows decide of the model before any fit: which columns are
    smooth, their knots, F, S, the column sums c and Z. ``rows`` restricts
    the live rows (the half-batch fault); ``uniform`` places the knots
    evenly between the extremes instead of at the quantiles."""

    def __init__(self, data: Data, config: dict, rows=None,
                 uniform: bool = False):
        import jax.numpy as jnp

        p = config["params"]
        F = int(data.X.shape[0])
        names = [f"f{j}" for j in range(F)]
        self.smooth = tuple(names.index(c) for c in p["gam_columns"])
        self.linear = tuple(j for j in range(F) if j not in self.smooth)
        self.live = data.live if rows is None else (data.live & rows)
        keep = np.asarray(self.live)
        self.n = int(keep.sum())
        knots = []
        for s, c in enumerate(self.smooth):
            x = np.asarray(data.X[c], np.float64)[keep]
            K = int(p["num_knots"][s])
            knots.append(np.linspace(x.min(), x.max(), K) if uniform
                         else np.quantile(x, np.linspace(0.0, 1.0, K)))
        self.knots = knots
        FS = [cr_matrices(k) for k in knots]
        f32 = lambda arrays: tuple(  # noqa: E731
            jnp.asarray(a, jnp.float32) for a in arrays)
        self.k32, self.F32 = f32(knots), f32(f for f, _ in FS)
        c = _jits()["sums"](data.X, data.y_dev, self.live, self.k32,
                            self.F32, self.smooth, data.block)
        self.Z = [householder_z(np.asarray(ci, np.float64).sum(0)) for ci in c]
        self.Z32 = f32(self.Z)
        # the penalty of the model's columns, scale and factor 1 included
        P1 = len(self.linear) + sum(z.shape[1] for z in self.Z) + 1
        self.S = np.zeros((P1, P1))
        off = len(self.linear)
        self.blocks = []
        for s, ((_, S), Z) in enumerate(zip(FS, self.Z)):
            k = Z.shape[1]
            self.S[off:off + k, off:off + k] = (
                float(p["scale"][s]) * Z.T @ S @ Z)
            self.blocks.append(slice(off, off + k))
            off += k
        self.names = ([names[j] for j in self.linear]
                      + [f"{c}_gam.{i}" for c, Z in zip(p["gam_columns"], self.Z)
                         for i in range(Z.shape[1])] + ["Intercept"])

    def step(self, data: Data, beta, dtype=None, bdtype=None):
        """One pass at beta: (X'WX, X'Wz, score, column sums) in float64."""
        import jax.numpy as jnp

        out = _jits()["gram"](
            data.X, data.y_dev, self.live, self.k32, self.F32, self.Z32,
            jnp.asarray(beta, jnp.float32), self.smooth, self.linear, dtype,
            bdtype, data.block)
        return tuple(np.asarray(a, np.float64).sum(0) for a in out)

    def prob(self, data: Data, beta) -> np.ndarray:
        import jax.numpy as jnp

        p = _jits()["prob"](
            data.X, data.y_dev, self.live, self.k32, self.F32, self.Z32,
            jnp.asarray(beta, jnp.float32), self.smooth, self.linear,
            data.block)
        return np.asarray(p, np.float64).reshape(-1)[: data.nrow]

    def gradient(self, G, score, beta, penalty: float) -> float:
        """The penalised objective's gradient (times N) over its scale: per
        coordinate over sqrt(N H_jj), the largest."""
        H = G + penalty * 2.0 * self.S
        g = score - penalty * 2.0 * self.S @ beta
        return float(np.max(np.abs(g) / np.sqrt(self.n * np.diag(H))))


def fit(data: Data, config: dict, dtype_name: str | None = None,
        basis_dtype: str | None = None, metrics_dtype: str | None = None,
        fault: str | None = None) -> dict:
    """Newton's method on the penalised objective, in its IRLS form
    (``beta <- (X'WX + 2 N S)^-1 X'Wz``), from (0, ..., 0, logit of the mean)
    until the gradient over its scale is under the configuration's
    ``gradient_tolerance`` or no longer halves (a lower precision has a
    floor), ``newton_cap`` steps at the most. ``dtype_name`` rounds the
    Gram products' operands once to that type, ``basis_dtype`` every basis
    value, ``metrics_dtype`` every probability the reported metrics are
    made from. ``fault``: ``unpenalised`` (no penalty), ``raw_scale_penalty``
    (the penalty not multiplied by N: the parent's), ``unconstrained`` (K
    columns a smooth, each less its mean: the parent's), ``uniform_knots``,
    ``half_batch`` (every second row left out), ``state_unchanged`` (the
    solve's result is never kept). Returns what the program returns:
    coefficients by name, logloss, AUC."""
    import jax.numpy as jnp

    c = config["correct"]
    rows = (jnp.arange(data.plen) % 2) == 0 if fault == "half_batch" else None
    sm = Smooths(data, config, rows, uniform=fault == "uniform_knots")
    dtype = None if dtype_name is None else getattr(jnp, dtype_name)
    bdtype = None if basis_dtype is None else getattr(jnp, basis_dtype)
    penalty = {"unpenalised": 0.0, "raw_scale_penalty": 1.0}.get(
        fault, float(sm.n))
    ybar = float(np.mean(data.y))
    beta = np.zeros(len(sm.names))
    beta[-1] = np.log(ybar / (1 - ybar))
    last = np.inf
    for _ in range(int(c["newton_cap"])):
        G, b, score, _ = sm.step(data, beta, dtype, bdtype)
        size = sm.gradient(G, score, beta, penalty)
        if size < float(c["gradient_tolerance"]) or size > 0.5 * last:
            break
        last = size
        if fault != "state_unchanged":
            beta = np.linalg.solve(G + penalty * 2.0 * sm.S, b)
    ll, auc = logloss_auc(sm.prob(data, beta), data.y, metrics_dtype)
    coef = dict(zip(sm.names, beta))
    if fault == "unconstrained":
        # a curve's K values at the knots, less their mean: ten names a smooth
        coef = {k: v for k, v in coef.items() if "_gam." not in k}
        for col, Z, blk in zip(config["params"]["gam_columns"], sm.Z,
                               sm.blocks):
            vals = Z @ beta[blk]
            coef.update({f"{col}_gam.{i}": v - vals.mean()
                         for i, v in enumerate(vals)})
    return {"coef": coef, "logloss": ll, "auc": auc}


def check(cand: dict, data: Data, config: dict) -> dict:
    """The numbers compared, for a candidate fit (the program's, the
    control's or a faulty one) against the reference's own optimum and its
    own scoring of the candidate's coefficients. Each with its reason:

    - ``names_gap``: coefficient names the model should have and lacks, or
      has and should not: a smooth of K columns is another model;
    - ``smooth_gap``: the largest ``|Z_s g_s(candidate) - Z_s g_s(reference)|``
      over smooths and knots: the centred curve's values at the knots, in
      the linear predictor's units, which no choice of basis moves;
    - ``coef_gap``: the linear block and the intercept, largest difference
      over the largest coefficient of the reference;
    - ``kkt_gap``: the penalised gradient at the CANDIDATE's coefficients on
      the reference's design, per coordinate over sqrt(N H_jj): nought at
      the optimum of the stated objective, whatever path led there;
    - ``zero_sum_gap``: ``|1' X_s Z_s g_s| / N`` a smooth at the candidate's
      coefficients, on the reference's float32 columns;
    - ``logloss_gap``, ``auc_gap``: the client's numbers against float64
      metrics of the reference's scoring of the same coefficients."""
    if "sm" not in data._optimum:
        sm = data._optimum["sm"] = Smooths(data, config)
        own = fit(data, config)["coef"]
        data._optimum["beta"] = np.array([own[n] for n in sm.names])
    sm, ref = data._optimum["sm"], data._optimum["beta"]
    coef = cand["coef"]
    out = {"names_gap": float(len(set(coef) ^ set(sm.names)))}
    if out["names_gap"]:
        return out
    got = np.array([coef[n] for n in sm.names], np.float64)
    out["smooth_gap"] = float(max(
        np.max(np.abs(Z @ (got[b] - ref[b]))) for Z, b in zip(sm.Z, sm.blocks)))
    lin = np.r_[0:len(sm.linear), -1]
    out["coef_gap"] = float(np.max(np.abs(got[lin] - ref[lin]))
                            / np.max(np.abs(ref[lin])))
    G, _, score, colsum = sm.step(data, got)
    out["kkt_gap"] = sm.gradient(G, score, got, float(sm.n))
    out["zero_sum_gap"] = float(max(
        abs(colsum[b] @ got[b]) for b in sm.blocks) / sm.n)
    ll, auc = logloss_auc(sm.prob(data, got), data.y)
    out["logloss_gap"] = abs(float(cand["logloss"]) - ll) / ll
    out["auc_gap"] = abs(float(cand["auc"]) - auc)
    return out


def compare(result: dict, data: Data, config: dict) -> dict:
    """The numbers compared for what one timed job returned."""
    return check(result, data, config)
