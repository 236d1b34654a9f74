"""Plain binomial GLM (logit link, no penalty, intercept) with all pairwise
products of its columns, as H2O-3's GLM ``interactions`` states them,
independent of the program: the reference for ``correct``, and, with
lower-precision Gram operands or a planted fault, the control put in the
program's place.

The model, over N rows of weight 1, for the columns ``x_1..x_F`` and each
pair ``a < b``: the product ``p_ab = x_a x_b`` of the RAW columns; every
column, product or not, standardised by its own mean and sample standard
deviation (divisor N - 1), ``z = (x - m) / s``; ``eta = b_0 + sum_a b_a z_a +
sum_{a<b} b_ab z_ab``, ``p = sigmoid(eta)``; the maximum likelihood.

- statistics: a pass of per-block sums (float32 on the device, a block of
  rows each) for the means, then a pass of centred squares for the
  deviations, the blocks summed in float64 on the host;
- the design ``[z_mains, z_products, 1]`` (N x (F + F(F-1)/2 + 1)) is never
  held whole: each block of rows is built in plain ``jax.numpy`` float32
  (the products by a gather of the pairs' rows) and its Gram, right-hand
  side and score taken at ``highest`` matmul precision; the blocks' sums
  in float64 on the host, as ``reference/glm.py``'s passes are;
- IRLS in float64 on the host, ``converge_iterations`` steps from
  ``(0, ..., 0, logit of the mean)``.

Coefficients go in and out on the columns' RAW scale under the program's
names (``f0..f27``, ``f0_f1 .. f26_f27``, ``Intercept``), as ``coef()``
gives them.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from .gbm import logloss_auc  # the same float64 metrics, by the same definitions
from .glm import Data as _Frame

#: rows a block of the passes: the (BLOCK, 407) design block is 107 MB
BLOCK = 65536
FAULTS = ("dropped_pair", "raw_products", "half_batch")


@functools.lru_cache(maxsize=None)
def _jits():
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def _round(a, dtype):
        return a if dtype is None else a.astype(dtype).astype(jnp.float32)

    def blocks(X, y, live, B):
        F, R = X.shape
        n = R // B
        return (X.reshape(F, n, B).transpose(1, 0, 2), y.reshape(n, B),
                live.reshape(n, B))

    def columns(xb, ia, ib):
        """(F + npairs, rb): the block's columns and the pairs' products."""
        return jnp.concatenate([xb, xb[ia] * xb[ib]], axis=0)

    def sums(X, y, live, ia, ib, centre, B):
        """Per block over the live rows: sum of each column (``centre``
        None), or of its square less ``centre`` (the second pass)."""
        def body(_, blk):
            xb, _, lb = blk
            c = columns(xb, ia, ib)
            if centre is not None:
                c = (c - centre[:, None]) ** 2
            return None, jnp.sum(jnp.where(lb[None, :], c, 0.0), axis=1)
        return jax.lax.scan(body, None, blocks(X, y, live, B))[1]

    def design(xb, lb, ia, ib, m, s, keep):
        """One block's (P + 1, rb) design: standardised columns, ones;
        rows that are not live are zero; ``keep`` zeroes a column (the
        dropped-pair fault)."""
        z = (columns(xb, ia, ib) - m[:, None]) / s[:, None] * keep[:, None]
        z = jnp.concatenate([z, jnp.ones((1, xb.shape[1]), jnp.float32)], 0)
        return jnp.where(lb[None, :], z, 0.0)

    def gram(X, y, live, ia, ib, m, s, keep, beta, dtype, B):
        """Per block: Z'WZ, Z'Wz and the score Z'(y - mu); ``dtype`` rounds
        the two products' operands once to a lower type (the control). The
        score is never rounded: it judges."""
        def body(_, blk):
            xb, yb, lb = blk
            zi = design(xb, lb, ia, ib, m, s, keep)
            eta = jnp.einsum("pr,p->r", zi, beta, precision=hi)
            mu = jax.nn.sigmoid(eta)
            w = jnp.where(lb, mu * (1.0 - mu), 0.0)
            yb = jnp.where(lb, yb, 0.0)
            z = eta + (yb - mu) / jnp.maximum(mu * (1.0 - mu), 1e-10)
            zw = _round(zi * w[None, :], dtype)
            G = jnp.einsum("pr,qr->pq", zw, _round(zi, dtype), precision=hi)
            b = jnp.einsum("pr,r->p", zw, _round(z, dtype), precision=hi)
            score = jnp.einsum("pr,r->p", zi, jnp.where(lb, yb - mu, 0.0),
                               precision=hi)
            return None, (G, b, score)
        return jax.lax.scan(body, None, blocks(X, y, live, B))[1]

    def prob(X, y, live, ia, ib, m, s, keep, beta, B):
        def body(_, blk):
            xb, _, lb = blk
            zi = design(xb, lb, ia, ib, m, s, keep)
            return None, jax.nn.sigmoid(
                jnp.einsum("pr,p->r", zi, beta, precision=hi))
        return jax.lax.scan(body, None, blocks(X, y, live, B))[1]

    return {"sums": jax.jit(sums, static_argnums=(6,)),
            "gram": jax.jit(gram, static_argnums=(9, 10)),
            "prob": jax.jit(prob, static_argnums=(9,))}


class Data(_Frame):
    """The cell's data as ``reference/glm.py`` holds it (X (F, plen) and y on
    the device, NaN padding replaced by weight 0), its pairs, and a place
    for the reference's own optimum, fitted once a run."""

    def __init__(self, cols, nrow: int):
        super().__init__(cols, nrow)
        F = int(self.X.shape[0])
        self.block = math.gcd(self.plen, BLOCK)
        self.pairs = [(a, b) for a in range(F) for b in range(a + 1, F)]
        self.names = ([f"f{j}" for j in range(F)]
                      + [f"f{a}_f{b}" for a, b in self.pairs] + ["Intercept"])
        self._optimum: dict = {}


class Design:
    """What the rows decide of the design before any fit: each column's
    mean and deviation, mains then products. ``rows`` restricts the live
    rows (the half-batch fault); ``raw`` leaves the products unstandardised
    (the raw-products fault)."""

    def __init__(self, data: Data, rows=None, raw: bool = False,
                 drop: int | None = None):
        import jax.numpy as jnp

        self.live = data.live if rows is None else (data.live & rows)
        self.n = int(np.asarray(self.live).sum())
        ia, ib = (jnp.asarray(np.array(a, np.int32)) for a in zip(*data.pairs))
        self.ia, self.ib = ia, ib
        run = _jits()["sums"]
        tot = np.asarray(run(data.X, data.y_dev, self.live, ia, ib, None,
                             data.block), np.float64).sum(0)
        self.m = tot / self.n
        sq = np.asarray(run(data.X, data.y_dev, self.live, ia, ib,
                            jnp.asarray(self.m, jnp.float32), data.block),
                        np.float64).sum(0)
        self.s = np.sqrt(sq / max(self.n - 1, 1))
        self.s = np.where(np.isfinite(self.s) & (self.s > 0), self.s, 1.0)
        F = int(data.X.shape[0])
        if raw:
            self.m[F:], self.s[F:] = 0.0, 1.0
        self.keep = np.ones(len(self.m))
        if drop is not None:
            self.keep[drop] = 0.0
        self.m32, self.s32, self.keep32 = (jnp.asarray(a, jnp.float32) for a in
                                           (self.m, self.s, self.keep))

    def _args(self, data):
        return (data.X, data.y_dev, self.live, self.ia, self.ib, self.m32,
                self.s32, self.keep32)

    def step(self, data: Data, beta, dtype=None):
        """One pass at standardised beta: (G, b, score) in float64."""
        import jax.numpy as jnp

        out = _jits()["gram"](*self._args(data), jnp.asarray(beta, jnp.float32),
                              dtype, data.block)
        return tuple(np.asarray(a, np.float64).sum(0) for a in out)

    def prob(self, data: Data, beta) -> np.ndarray:
        import jax.numpy as jnp

        p = _jits()["prob"](*self._args(data), jnp.asarray(beta, jnp.float32),
                            data.block)
        return np.asarray(p, np.float64).reshape(-1)[: data.nrow]

    def to_raw(self, beta) -> np.ndarray:
        """Standardised coefficients (intercept last) on the raw scale."""
        raw = np.array(beta, np.float64)
        raw[:-1] = beta[:-1] * self.keep / self.s
        raw[-1] = beta[-1] - float(np.sum(raw[:-1] * self.m))
        return raw

    def to_std(self, raw) -> np.ndarray:
        """Raw coefficients on this design's standardised scale."""
        beta = np.array(raw, np.float64)
        beta[:-1] = raw[:-1] * self.s
        beta[-1] = raw[-1] + float(np.sum(raw[:-1] * self.m))
        return beta


def irls(data: Data, des: Design, iterations: int, dtype=None) -> np.ndarray:
    """``iterations`` IRLS steps on the standardised design, float64 solve
    (least squares: a dropped column leaves the Gram singular)."""
    ybar = float(np.mean(data.y))
    beta = np.zeros(len(des.m) + 1)
    beta[-1] = np.log(ybar / (1 - ybar))
    for _ in range(iterations):
        G, b, _ = des.step(data, beta, dtype)
        beta = np.linalg.lstsq(G, b, rcond=None)[0]
    return beta


def fit(data: Data, config: dict, dtype_name: str | None = None,
        metrics_dtype: str | None = None, fault: str | None = None) -> dict:
    """The reference's own fit, returned as the program returns one:
    coefficients by name on the raw scale, logloss, AUC. ``dtype_name``
    rounds the Gram's operands once to that type, ``metrics_dtype`` every
    probability the metrics are made from. ``fault``: ``dropped_pair`` (the
    product of the reference optimum's largest product coefficient held at
    zero), ``raw_products`` (the products fitted unstandardised, and their
    coefficients then reported as if they had been: divided by the
    deviation), ``half_batch`` (every second row left out)."""
    import jax.numpy as jnp

    its = int(config["correct"]["converge_iterations"])
    dtype = None if dtype_name is None else getattr(jnp, dtype_name)
    rows = (jnp.arange(data.plen) % 2) == 0 if fault == "half_batch" else None
    drop = None
    if fault == "dropped_pair":
        F = int(data.X.shape[0])
        ref = optimum(data, config)[1]
        drop = F + int(np.argmax(np.abs(ref[F:-1])))
    des = Design(data, rows, raw=fault == "raw_products", drop=drop)
    beta = irls(data, des, its, dtype)
    raw = des.to_raw(beta)
    if fault == "raw_products":
        F = int(data.X.shape[0])
        s_true = Design(data).s
        raw[F:-1] = beta[F:-1] / s_true[F:]
    ll, auc = logloss_auc(des.prob(data, beta), data.y, metrics_dtype)
    return {"coef": dict(zip(data.names, raw)), "logloss": ll, "auc": auc}


def optimum(data: Data, config: dict):
    """(design, standardised coefficients, logloss, AUC) of the reference's
    own maximum likelihood, fitted once a run."""
    if "beta" not in data._optimum:
        des = Design(data)
        beta = irls(data, des, int(config["correct"]["converge_iterations"]))
        ll, auc = logloss_auc(des.prob(data, beta), data.y)
        data._optimum.update(des=des, beta=beta, ll=ll, auc=auc)
    o = data._optimum
    return o["des"], o["beta"], o["ll"], o["auc"]


def check(cand: dict, data: Data, config: dict) -> dict:
    """The numbers compared, for a candidate fit (the program's, the
    control's or a faulty one), each with its reason:

    - ``names_gap``: coefficient names the model should have and lacks, or
      has and should not (407: 28 mains, 378 products, the intercept);
    - ``coef_gap``: the largest difference from the reference's optimum,
      over its largest coefficient, on the STANDARDISED scale of the
      reference's own statistics (the candidate's raw coefficients mapped
      there): the raw scale divides a product's coefficient by its
      deviation, which ranges over a factor of several between pairs, so a
      raw gap weighs the columns unevenly; standardised, every column is in
      the units of the linear predictor per deviation;
    - ``score_gap``: the gradient of the log-likelihood at the CANDIDATE's
      coefficients on the reference's design, ``max |Z'(y - mu)| / N``:
      nought at the maximum, whatever path led there;
    - ``logloss_gap``, ``auc_gap``: the client's numbers against float64
      metrics of the reference's optimum."""
    des, ref, ref_ll, ref_auc = optimum(data, config)
    coef = cand["coef"]
    out = {"names_gap": float(len(set(coef) ^ set(data.names)))}
    if out["names_gap"]:
        return out
    got = des.to_std(np.array([coef[n] for n in data.names], np.float64))
    out["coef_gap"] = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    out["score_gap"] = float(np.max(np.abs(des.step(data, got)[2])) / des.n)
    out["logloss_gap"] = abs(float(cand["logloss"]) - ref_ll) / ref_ll
    out["auc_gap"] = abs(float(cand["auc"]) - ref_auc)
    return out


def compare(result: dict, data: Data, config: dict) -> dict:
    """The numbers compared for what one timed job returned."""
    return check(result, data, config)
