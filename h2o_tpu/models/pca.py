"""PCA / SVD — dimensionality reduction via the distributed Gram path.

Analog of `hex/pca/PCA.java` (987 LoC) and `hex/svd/SVD.java` (1,244 LoC).
Reference methods: GramSVD (default: distributed XᵀX then local SVD), Power
iteration, Randomized subspace iteration, GLRM. Here:

- **GramSVD**: the Gram matrix is ONE jitted einsum over the row-sharded design
  matrix (XLA all-reduces over ICI — replaces `hex/gram/Gram.java` GramTask),
  then `eigh` of the small P×P matrix on device.
- **Power / Randomized**: matrix-free iterations where each matvec/matmat is a
  sharded `X.T @ (X @ v)` pair — never materializes XᵀX; right for very wide
  expanded designs.

SVD exposes U/D/V like the reference (u_key frame optional); PCA reports
std-deviation/proportion/cumulative tables and projects via `predict`.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..backend.jobs import Job
from ..frame.frame import Frame
from ..frame.vec import Vec
from .datainfo import DataInfo
from .model_base import Model, ModelBuilder, ModelOutput, Parameters


@dataclass
class PCAParameters(Parameters):
    """Mirrors `hex/schemas/PCAV3`."""

    k: int = 1
    transform: str = "NONE"   # NONE | STANDARDIZE | NORMALIZE | DEMEAN | DESCALE
    pca_method: str = "GramSVD"  # GramSVD | Power | Randomized | GLRM
    max_iterations: int = 1000
    use_all_factor_levels: bool = False
    compute_metrics: bool = True


@dataclass
class SVDParameters(Parameters):
    nv: int = 1
    transform: str = "NONE"
    svd_method: str = "GramSVD"
    max_iterations: int = 1000
    use_all_factor_levels: bool = True


def _transform_info(transform: str):
    t = (transform or "NONE").upper()
    demean = t in ("STANDARDIZE", "DEMEAN")
    descale = t in ("STANDARDIZE", "NORMALIZE", "DESCALE")
    return demean, descale


def kernel(X, wmask):
    """Masked Gram through the kernels layer: ``wmask`` is a 0/1 row mask
    (w² == w), so the single-application weighted Gram Xᵀdiag(w)X equals
    the historic (X·w)ᵀ(X·w) — accumulated in one blocked pass
    (backend/kernels/gram.py) with the (R, P) masked copy never
    materialized."""
    from ..backend.kernels import gram as gram_kernels

    G, _ = gram_kernels.gram_accumulate(X, wmask)
    return G, jnp.sum(wmask)


#: jitted under the function name the compile cache knows the program by
#: (XLA module ``jit_kernel``)
_gram_kernel = jax.jit(kernel)


def _gram_svd(X, wmask, k):
    """XᵀX (one sharded matmul) → eigh → top-k singular pairs."""
    G, n = _gram_kernel(X, wmask)
    evals, evecs = jnp.linalg.eigh(G)        # ascending
    evals = evals[::-1][:k]
    V = evecs[:, ::-1][:, :k]
    d = jnp.sqrt(jnp.maximum(evals, 0.0))
    return d, V, n


def _randomized_svd(X, wmask, k, iters, key):
    """Halko randomized subspace iteration — X touched only via sharded matmuls."""
    P = X.shape[1]
    Xm = X * wmask[:, None]
    Q = jax.random.normal(key, (P, min(k + 8, P)), dtype=jnp.float32)
    for _ in range(max(2, min(iters, 8))):
        Z = Xm @ Q                      # (R, k+p) row-sharded
        Q2 = Xm.T @ Z                   # (P, k+p) all-reduced by XLA
        Q, _ = jnp.linalg.qr(Q2)
    B = Xm @ Q
    G = B.T @ B
    evals, evecs = jnp.linalg.eigh(G)
    evals = evals[::-1][:k]
    W = evecs[:, ::-1][:, :k]
    d = jnp.sqrt(jnp.maximum(evals, 0.0))
    V = Q @ W
    return d, V, jnp.sum(wmask)


def _power_svd(X, wmask, k, iters):
    """Sequential power iteration with deflation (`hex/svd` Power method)."""
    Xm = X * wmask[:, None]
    P = X.shape[1]
    V = []
    d = []
    G = Xm.T @ Xm
    for j in range(k):
        v = jnp.ones((P,)) / np.sqrt(P)
        for _ in range(min(iters, 100)):
            v2 = G @ v
            nrm = jnp.linalg.norm(v2)
            v = v2 / jnp.maximum(nrm, 1e-12)
        lam = v @ (G @ v)
        V.append(v)
        d.append(jnp.sqrt(jnp.maximum(lam, 0.0)))
        G = G - lam * jnp.outer(v, v)
    return jnp.stack(d), jnp.stack(V, axis=1), jnp.sum(wmask)


class PCAModel(Model):
    algo_name = "pca"

    def __init__(self, params, output, V, d, dinfo, mu, key=None):
        self.V = V          # (P, k) eigenvectors in expanded space
        self.d = d          # (k,) singular values
        self.dinfo = dinfo
        self.mu = mu        # (P,) training-time expanded-space mean (0 if no demean)
        super().__init__(params, output, key=key)

    def predict(self, fr: Frame) -> Frame:
        X, _ = self.dinfo.expand(fr)
        proj = (X - self.mu) @ self.V
        names = [f"PC{i+1}" for i in range(self.V.shape[1])]
        return Frame(names, [Vec.from_device(proj[:, i], fr.nrow)
                             for i in range(len(names))])


class PCA(ModelBuilder):
    algo_name = "pca"
    supervised = False

    def build_impl(self, job: Job) -> PCAModel:
        p: PCAParameters = self.params
        fr = p.training_frame
        names = self.feature_names()
        demean, descale = _transform_info(p.transform)
        dinfo = DataInfo.make(fr, names, standardize=descale,
                              use_all_factor_levels=p.use_all_factor_levels)
        if not demean:
            # NONE / DESCALE-only: kill centering by zeroing stored means
            dinfo = _no_center(dinfo, descale)
        X, ok = dinfo.expand(fr)
        wmask = ((jnp.arange(X.shape[0]) < fr.nrow) & ok).astype(jnp.float32)
        if demean:
            mu = jnp.sum(X * wmask[:, None], axis=0) / jnp.maximum(jnp.sum(wmask), 1.0)
            X = X - mu  # categorical block means too (reference demeans expanded)
        else:
            mu = jnp.zeros((X.shape[1],), jnp.float32)

        k = min(p.k, X.shape[1])
        seed = p.seed if p.seed not in (-1, None) else 1234
        method = (p.pca_method or "GramSVD").lower()
        if method == "randomized":
            d, V, n = _randomized_svd(X, wmask, k, p.max_iterations,
                                      jax.random.PRNGKey(seed))
        elif method == "power":
            d, V, n = _power_svd(X, wmask, k, p.max_iterations)
        else:
            d, V, n = _gram_svd(X, wmask, k)

        n = float(n)
        sdev = np.asarray(d) / np.sqrt(max(n - 1, 1.0))
        var = sdev ** 2
        # total variance = tr(XᵀX)/(n-1), one O(N·P) pass (no second Gram)
        totvar = float(jnp.sum(wmask * jnp.sum(X * X, axis=1))) / max(n - 1, 1.0)
        prop = var / totvar if totvar > 0 else var * 0

        output = ModelOutput()
        output.names = names
        output.domains = {nn: fr.vec(nn).domain for nn in names}
        output.model_category = "DimReduction"
        output.variable_importances = {
            "pc": [f"PC{i+1}" for i in range(k)],
            "std_deviation": sdev,
            "proportion_of_variance": prop,
            "cumulative_proportion": np.cumsum(prop),
        }
        output.training_metrics = None
        model = PCAModel(p, output, V, d, dinfo, mu)
        model.eigenvectors = np.asarray(V)
        model.eigenvector_names = dinfo.expanded_names
        return model


class SVDModel(Model):
    algo_name = "svd"

    def __init__(self, params, output, V, d, dinfo, mu, key=None):
        self.V = V
        self.d = d
        self.dinfo = dinfo
        self.mu = mu
        super().__init__(params, output, key=key)

    def predict(self, fr: Frame) -> Frame:
        """Returns U·D (the projection) like scoring a PCA."""
        X, _ = self.dinfo.expand(fr)
        proj = (X - self.mu) @ self.V
        names = [f"svd{i+1}" for i in range(self.V.shape[1])]
        return Frame(names, [Vec.from_device(proj[:, i], fr.nrow)
                             for i in range(len(names))])


class SVD(ModelBuilder):
    algo_name = "svd"
    supervised = False

    def build_impl(self, job: Job) -> SVDModel:
        p: SVDParameters = self.params
        fr = p.training_frame
        names = self.feature_names()
        demean, descale = _transform_info(p.transform)
        dinfo = DataInfo.make(fr, names, standardize=descale,
                              use_all_factor_levels=p.use_all_factor_levels)
        if not demean:
            dinfo = _no_center(dinfo, descale)
        X, ok = dinfo.expand(fr)
        wmask = ((jnp.arange(X.shape[0]) < fr.nrow) & ok).astype(jnp.float32)
        if demean:
            mu = jnp.sum(X * wmask[:, None], axis=0) / jnp.maximum(jnp.sum(wmask), 1.0)
            X = X - mu
        else:
            mu = jnp.zeros((X.shape[1],), jnp.float32)

        k = min(p.nv, X.shape[1])
        method = (p.svd_method or "GramSVD").lower()
        seed = p.seed if p.seed not in (-1, None) else 1234
        if method == "randomized":
            d, V, _ = _randomized_svd(X, wmask, k, p.max_iterations,
                                      jax.random.PRNGKey(seed))
        elif method == "power":
            d, V, _ = _power_svd(X, wmask, k, p.max_iterations)
        else:
            d, V, _ = _gram_svd(X, wmask, k)

        output = ModelOutput()
        output.names = names
        output.domains = {nn: fr.vec(nn).domain for nn in names}
        output.model_category = "DimReduction"
        model = SVDModel(p, output, V, d, dinfo, mu)
        model.singular_values = np.asarray(d)
        model.v = np.asarray(V)
        return model


def _no_center(dinfo: DataInfo, descale: bool) -> DataInfo:
    """Strip mean-centering from a DataInfo (transform=NONE/DESCALE modes).

    NA imputation keeps using the column means either way — DataInfo.center
    only controls the (x - mean) subtraction.
    """
    if descale:
        dinfo.center = False  # x/sigma, mean-imputed NAs
    else:
        dinfo.standardize = False
    return dinfo
