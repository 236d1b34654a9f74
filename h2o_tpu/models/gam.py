"""GAM — generalized additive models.

Analog of `hex/gam/` (4,743 LoC): the reference expands each `gam_column` into
a spline basis added as frame columns, then fits a penalized GLM
(`hex/gam/GAMModel.java`, basis builders under `hex/gam/MatrixFrameUtils/`).
All four of the reference's `bs` families are implemented, matching its codes:

- ``bs=0`` **cubic regression splines** (mgcv 'cr', the reference default) —
  values-at-knots natural-cubic basis with the EXACT integrated-squared-
  second-derivative penalty S = DᵀB⁻¹D (`CubicRegressionSplines.java`);
- ``bs=1`` **thin-plate** (1-D): |x−k|³ radial bumps + linear null space,
  radial-energy penalty (`ThinPlateRegressionUtils.java` role);
- ``bs=2`` **monotone I-splines**: I_i = Σ_{j≥i} B_j with non-negative
  coefficients enforced per-coordinate inside the COD solver, giving a
  non-decreasing smooth (`ISplines.java` + splines_non_negative);
- ``bs=3`` **M/P-splines**: B-spline basis with the 2nd-order difference
  penalty (Eilers & Marx; `NBSplinesTypeI.java` role).

The fit is one penalized IRLS: the Gram/XᵀWz come from the same sharded einsum
kernel GLM uses (`glm._make_irls_kernel`); the block-diagonal penalty is added
to the Gram before the host-side solve (`hex/gam/GAMModel` _penaltyMatrix),
which is ADMM normally and cyclic COD when monotone bounds are present.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..backend.jobs import Job
from ..frame.frame import Frame
from ..frame.vec import Vec
# the basis evaluators are pure numpy and live with the standalone scorer so
# GAM MOJOs score without the engine/JAX (gam_basis dispatches on spec["bs"])
from ..mojo.format import cr_matrices, gam_basis
from .datainfo import DataInfo
from .glm import GLMParameters, _admm_solve, _cod_solve, _make_irls_kernel
from .model_base import Model, ModelBuilder, ModelOutput, make_metrics


# ---------------------------------------------------------------------------
# B-spline basis (pure numpy Cox–de Boor, vectorized over rows)
# ---------------------------------------------------------------------------
def diff_penalty(n_basis: int, order: int = 2) -> np.ndarray:
    """P-spline penalty DᵀD (2nd-order differences of adjacent coefficients)."""
    D = np.diff(np.eye(n_basis), n=order, axis=0)
    return D.T @ D


# ---------------------------------------------------------------------------
# device-side basis evaluation — mirrors `mojo/format.py`'s numpy versions
# (which stay as the zero-JAX standalone MOJO scorer). The numpy path pulled
# every gam column AND the full linear design to the host and pushed the
# concatenated design back — multiple GB per _design call at benchmark
# scale, for basis math that is itself trivial.
# ---------------------------------------------------------------------------
def _cr_basis_dev(x, knots, F):
    """Natural cubic regression spline, values-at-knots parameterization."""
    knots = jnp.asarray(knots, jnp.float32)
    K = knots.shape[0]
    x = jnp.clip(jnp.nan_to_num(x, nan=knots[K // 2]), knots[0], knots[-1])
    j = jnp.clip(jnp.searchsorted(knots, x, side="right") - 1, 0, K - 2)
    kj = jnp.take(knots, j)
    kj1 = jnp.take(knots, j + 1)
    h = kj1 - kj
    am = (kj1 - x) / h
    ap = (x - kj) / h
    cm = ((kj1 - x) ** 3 / h - h * (kj1 - x)) / 6.0
    cp = ((x - kj) ** 3 / h - h * (x - kj)) / 6.0
    oh_j = jax.nn.one_hot(j, K, dtype=jnp.float32)
    oh_j1 = jax.nn.one_hot(j + 1, K, dtype=jnp.float32)
    Fj = jnp.asarray(F, jnp.float32)
    # row j of F per x via one-hot matmul (no per-row gathers)
    F_j = oh_j @ Fj
    F_j1 = oh_j1 @ Fj
    return (oh_j * am[:, None] + oh_j1 * ap[:, None]
            + cm[:, None] * F_j + cp[:, None] * F_j1)


def _bspline_basis_dev(x, lo, hi, interior, degree: int = 3):
    """Cox-de-Boor B-splines; NA/out-of-range clamp to the boundary."""
    lo, hi = float(lo), float(hi)
    interior = np.asarray(interior, np.float64)
    x = jnp.clip(jnp.nan_to_num(x, nan=(lo + hi) / 2), lo, hi)
    t = np.concatenate([[lo] * (degree + 1), interior, [hi] * (degree + 1)])
    n_basis = len(interior) + degree + 1
    cols = []
    for i in range(len(t) - 1):
        if t[i + 1] > t[i]:
            right_closed = t[i + 1] == hi
            c = (x >= t[i]) & ((x < t[i + 1]) | right_closed)
            cols.append(c.astype(jnp.float32))
        else:
            cols.append(jnp.zeros_like(x))
    B = jnp.stack(cols, axis=1)
    for d in range(1, degree + 1):
        nxt = []
        for i in range(len(t) - 1 - d):
            left = 0.0
            if t[i + d] > t[i]:
                left = (x - t[i]) / (t[i + d] - t[i]) * B[:, i]
            right = 0.0
            if t[i + d + 1] > t[i + 1]:
                right = (t[i + d + 1] - x) / (t[i + d + 1] - t[i + 1]) \
                    * B[:, i + 1]
            # left/right may both be the scalar 0.0 (repeated knots)
            nxt.append(jnp.zeros_like(x) + left + right)
        B = jnp.stack(nxt, axis=1)
    return B[:, :n_basis]


def _gam_basis_dev(x, spec):
    """Device twin of `mojo.format.gam_basis` (same spec dict)."""
    bs = int(spec.get("bs", 3))
    if bs == 0:
        return _cr_basis_dev(x, spec["knots"], spec["F"])
    if bs == 1:
        knots = jnp.asarray(spec["knots"], jnp.float32)
        scale = float(spec["tp_scale"])
        xm = jnp.nan_to_num(x, nan=float(np.median(np.asarray(spec["knots"]))))
        r = jnp.abs(xm[:, None] - knots[None, :]) / scale
        Z = jnp.asarray(np.asarray(spec["Z"]), jnp.float32)
        return jnp.concatenate([(r ** 3) @ Z, (xm / scale)[:, None]], axis=1)
    if bs == 2:
        B = _bspline_basis_dev(x, spec["lo"], spec["hi"], spec["interior"],
                               spec["degree"])
        I = jnp.cumsum(B[:, ::-1], axis=1)[:, ::-1]
        return I[:, 1:]
    return _bspline_basis_dev(x, spec["lo"], spec["hi"], spec["interior"],
                              spec["degree"])


def _device_quantiles(col_data, qs) -> np.ndarray:
    """Per-column quantiles via the binning sketch — only (nq,) floats cross
    to the host (np.quantile pulled the whole column)."""
    from .tree.binning import hist_quantile_sketch

    return hist_quantile_sketch(col_data[:, None],
                                tuple(float(q) for q in qs))[:, 0]


# ---------------------------------------------------------------------------
@dataclass
class GAMParameters(GLMParameters):
    """Mirrors `hex/schemas/GAMV3` (gam_columns, num_knots, scale, bs)."""

    gam_columns: list = field(default_factory=list)
    num_knots: list | int = 8        # knot count per gam column
    scale: list | float = 1.0        # smoothing penalty weight per gam column
    bs: list | int = 0               # 0=cr | 1=thin plate | 2=monotone
                                     # I-splines | 3=M/P-splines — the
                                     # reference's `bs` codes (GAMV3.java:263)
    spline_degree: int = 3
    splines_non_negative: list | bool = True  # bs=2: True → non-decreasing
    keep_gam_cols: bool = False

    def knots_for(self, j: int) -> int:
        return (self.num_knots[j] if isinstance(self.num_knots, (list, tuple))
                else int(self.num_knots))

    def scale_for(self, j: int) -> float:
        return (self.scale[j] if isinstance(self.scale, (list, tuple))
                else float(self.scale))

    def bs_for(self, j: int) -> int:
        return (int(self.bs[j]) if isinstance(self.bs, (list, tuple))
                else int(self.bs))

    def nonneg_for(self, j: int) -> bool:
        v = self.splines_non_negative
        return bool(v[j]) if isinstance(v, (list, tuple)) else bool(v)


class GAMModel(Model):
    algo_name = "gam"

    def __init__(self, params, output, dinfo, gam_specs, beta, family,
                 key=None):
        self.dinfo = dinfo          # DataInfo over non-gam features (or None)
        self.gam_specs = gam_specs  # list of dicts per gam column
        self.interaction_spec = None  # frozen cat/num interaction pairs
        self.beta = beta            # (P_total+1,), intercept last
        self.family = family
        super().__init__(params, output, key=key)

    def _design(self, fr: Frame):
        """Design matrix fully ON DEVICE: linear block from DataInfo.expand
        plus the spline bases via `_gam_basis_dev`. (The earlier numpy path
        shipped the whole design to the host and back on every call.)"""
        blocks = []
        if self.interaction_spec:
            from .glm import _apply_interactions

            fr, _ = _apply_interactions(fr, self.interaction_spec,
                                           skip_existing=True)
        if self.dinfo is not None and self.dinfo.names:
            Xlin, _ = self.dinfo.expand(fr)
            blocks.append(Xlin)
        nref = int(blocks[0].shape[0]) if blocks else fr.vec(0).plen
        for spec in self.gam_specs:
            B = _gam_basis_dev(fr.vec(spec["column"]).data, spec)
            B = B - jnp.asarray(np.asarray(spec["col_means"]),
                                jnp.float32)[None, :]  # centering
            if B.shape[0] != nref:
                B = jnp.pad(B, ((0, nref - B.shape[0]), (0, 0)))
            blocks.append(B.astype(jnp.float32))
        return jnp.concatenate(blocks, axis=1)

    def adapt_frame(self, fr: Frame):
        return self._design(self.pre_adapt(fr))

    def score0(self, X):
        beta = jnp.asarray(self.beta, jnp.float32)
        eta = X @ beta[:-1] + beta[-1]
        mu = self.family.linkinv(eta)
        if self.output.model_category == "Binomial":
            label = (mu > 0.5).astype(jnp.float32)
            return jnp.stack([label, 1 - mu, mu], axis=1)
        return mu

    def coef(self) -> dict:
        names = []
        if self.dinfo is not None:
            names += self.dinfo.expanded_names
        for spec in self.gam_specs:
            names += [f"{spec['column']}_gam.{i}"
                      for i in range(len(spec["col_means"]))]
        names.append("Intercept")
        return dict(zip(names, np.asarray(self.beta)))


class GAM(ModelBuilder):
    algo_name = "gam"

    def _validate(self):
        super()._validate()
        p = self.params
        if not p.gam_columns:
            raise ValueError("gam: gam_columns is required")
        for c in p.gam_columns:
            if p.training_frame.find(c) < 0:
                raise ValueError(f"gam: gam column '{c}' not in frame")
            if p.training_frame.vec(c).is_categorical():
                raise ValueError(f"gam: gam column '{c}' must be numeric")

    def feature_names(self):
        names = super().feature_names()
        return [n for n in names if n not in self.params.gam_columns]

    def build_impl(self, job: Job) -> GAMModel:
        from .glm import GLM  # family resolution

        p = self.params
        fr = p.training_frame
        y_dev, category, resp_domain = self.response_info()
        if category == "Multinomial":
            raise ValueError("gam: multinomial family not yet supported")
        family = GLM._family(self, category)

        lin_names = self.feature_names()
        inter_spec = None
        if p.interactions or p.interaction_pairs:
            from .glm import _apply_interactions, _freeze_interaction_pairs

            reserved = {p.response_column, p.weights_column, p.offset_column}
            inter_spec = _freeze_interaction_pairs(
                fr, p.interactions, p.interaction_pairs, reserved)
            fr, extra = _apply_interactions(fr, inter_spec)
            lin_names = lin_names + extra
        dinfo = (DataInfo.make(fr, lin_names, standardize=p.standardize,
                               missing_values_handling=p.missing_values_handling)
                 if lin_names else None)

        # build spline specs (basis family per column) + per-block penalties
        # — knot quantiles come off the device sketch (only K floats cross),
        # basis evaluation and column means stay on device
        gam_specs, pen_sizes, pen_blocks, mono_blocks = [], [], [], []
        for j, c in enumerate(p.gam_columns):
            v = fr.vec(c)
            r = v.rollups()
            xmin, xmax = float(r.mins), float(r.maxs)
            bs = p.bs_for(j)
            if bs not in (0, 1, 2, 3):
                raise ValueError(f"gam: bs={bs} unknown (0=cr, 1=thin plate, "
                                 f"2=monotone I-splines, 3=M/P-splines)")
            scale = p.scale_for(j)
            if bs in (0, 1):
                K = max(p.knots_for(j), 3)
                knots = np.unique(_device_quantiles(
                    v.data, np.linspace(0, 1, K)).astype(np.float64))
                if len(knots) < 3:  # degenerate quantiles: span the DATA
                    knots = np.linspace(xmin, max(xmax, xmin + 1.0), 3)
            if bs == 0:
                # cr: knots at quantiles spanning the data; penalty DᵀB⁻¹D
                F, S_blk = cr_matrices(knots)
                spec = dict(column=c, bs=0, knots=knots, F=F, scale=scale)
            elif bs == 1:
                # thin plate: null-space-projected radial block (PSD energy
                # penalty) + unpenalized linear null space
                from ..mojo.format import tp_constraint

                tp_scale = max(float(knots[-1] - knots[0]), 1e-12)
                Z, S_rad = tp_constraint(knots, tp_scale)
                nb = S_rad.shape[0] + 1  # projected radial + linear
                S_blk = np.zeros((nb, nb))
                S_blk[:-1, :-1] = S_rad
                spec = dict(column=c, bs=1, knots=knots, tp_scale=tp_scale,
                            Z=Z, scale=scale)
            else:
                lo = xmin
                hi = xmax if xmax > xmin else xmin + 1.0
                qs = np.linspace(0, 1, max(p.knots_for(j), 1) + 2)[1:-1]
                interior = np.unique(_device_quantiles(v.data, qs)
                                     .astype(np.float64))
                spec = dict(column=c, bs=bs, lo=lo, hi=hi, interior=interior,
                            degree=p.spline_degree, scale=scale)
                nb = len(interior) + p.spline_degree + 1 - (1 if bs == 2
                                                            else 0)
                S_blk = diff_penalty(nb)
            B = _gam_basis_dev(v.data, spec)
            # means over REAL rows only (padding rows clamp to mid-knot)
            spec["col_means"] = np.asarray(
                jnp.mean(B[: fr.nrow], axis=0), np.float64)
            gam_specs.append(spec)
            pen_sizes.append(int(B.shape[1]))
            pen_blocks.append(scale * S_blk)
            mono_blocks.append(bs == 2 and p.nonneg_for(j))

        output = ModelOutput()
        output.names = lin_names + list(p.gam_columns)
        output.domains = {n: fr.vec(n).domain for n in output.names}
        output.response_domain = list(resp_domain) if resp_domain else None
        output.model_category = category
        model = GAMModel(p, output, dinfo, gam_specs, None, family)
        model.interaction_spec = inter_spec

        X = model._design(fr)
        P_lin = X.shape[1] - sum(pen_sizes)
        Ptot = X.shape[1]

        # block-diagonal smoothing penalty (zeros over linear block +
        # intercept); per-coordinate lower bounds realize the monotone blocks
        S = np.zeros((Ptot + 1, Ptot + 1))
        lo_bounds = np.full(Ptot + 1, -np.inf)
        off = P_lin
        for blk, sz, mono in zip(pen_blocks, pen_sizes, mono_blocks):
            S[off:off + sz, off:off + sz] = blk
            if mono:
                lo_bounds[off:off + sz] = 0.0
            off += sz
        any_mono = any(mono_blocks)

        y = jnp.nan_to_num(y_dev)
        w = (~jnp.isnan(y_dev)).astype(jnp.float32)
        w = w * (jnp.arange(X.shape[0]) < fr.nrow)  # mask padding rows
        if p.weights_column:
            w = w * jnp.nan_to_num(fr.vec(p.weights_column).data)
        offset = (jnp.nan_to_num(fr.vec(p.offset_column).data)
                  if p.offset_column else jnp.zeros_like(y))

        # penalized IRLS (GLMDriver loop + S added to the Gram)
        step = _make_irls_kernel(family)
        ones = jnp.ones((X.shape[0], 1), jnp.float32)
        Xi = jnp.concatenate([X, ones], axis=1)
        free = np.zeros(Ptot + 1, dtype=bool)
        free[-1] = True
        alpha = p.alpha if p.alpha is not None else 0.0
        lam = p.lambda_ if p.lambda_ is not None else 0.0
        neff = float(jnp.sum(w))
        beta = np.zeros(Ptot + 1, dtype=np.float64)
        beta[-1] = float(family.init_intercept(y, w)) if p.intercept else 0.0

        mu0 = family.linkinv(jnp.full_like(y, beta[-1]) + offset)
        nulldev = float(jnp.sum(family.deviance(y, mu0, w)))
        dev_prev = np.inf
        iters = 0
        for it in range(max(p.max_iterations, 1)):
            job.check_cancelled()
            G, b, dev, _ = step(Xi, y, w, jnp.asarray(beta, jnp.float32), offset)
            iters += 1
            Gn = np.asarray(G, np.float64) + S
            bn = np.asarray(b, np.float64)
            if any_mono:
                # COD applies the I-spline non-negativity per coordinate
                # inside the sweep (ADMM has no bound projection)
                beta_new = _cod_solve(Gn, bn, alpha * lam * neff,
                                      (1 - alpha) * lam * neff, free, beta,
                                      p.beta_epsilon, lo=lo_bounds)
            else:
                beta_new = _admm_solve(Gn, bn, alpha * lam * neff,
                                       (1 - alpha) * lam * neff, free)
            diff = np.max(np.abs(beta_new - beta)) if it else np.inf
            beta = beta_new
            if diff < p.beta_epsilon:
                break
            if abs(dev_prev - float(dev)) < p.objective_epsilon * abs(nulldev):
                break
            dev_prev = float(dev)

        model.beta = beta
        raw = model.score0(Xi[:, :-1])
        ym = jnp.where(w > 0, y, jnp.nan)
        m = make_metrics(category, ym, raw, w if p.weights_column else None,
                         auc_type=p.auc_type, domain=output.response_domain)
        mu = family.linkinv(Xi @ jnp.asarray(beta, jnp.float32) + offset)
        m.residual_deviance = float(jnp.sum(family.deviance(y, mu, w)))
        m.null_deviance = nulldev
        output.training_metrics = m
        output.scoring_history = [{"iterations": iters,
                                   "deviance": m.residual_deviance}]
        if p.validation_frame is not None:
            output.validation_metrics = model.model_performance(p.validation_frame)
        job.update(1.0)
        return model
