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

**Identifiability** (Wood 2017, section 5.4.1). A basis that holds the
constants (bs 0 and 3: their columns sum to 1 in every row) is confounded
with the intercept, so each such smooth is fitted through the sum-to-zero
constraint `mojo.format.sum_to_zero`: with ``c = Xᵀ1`` over the training
rows, the model's columns are ``X Z`` (one fewer than the basis has), its
penalty ``Zᵀ S Z``, and every column sums to zero over those rows. The
bases of bs 1 (radial bumps orthogonal to {1, x}, plus x) and bs 2 (the
all-ones I-spline dropped; coefficients bounded one by one, which a
rotation would break) do not hold the constants and are centred by their
column means instead.

**The objective** is H2O-3's mean over rows plus the smoothing penalty,
``-loglik(eta) / N + Σ_s scale_s g_sᵀ S_s g_s`` (factor 1, not 1/2), so the
working Gram of an IRLS step is the raw XᵀWX plus ``N`` times the
penalty's Hessian ``2 scale_s S_s``, as every other penalty of the loop
(``alpha * lambda * N``) is on the raw Gram's scale.

**The design** is built once a job, by ONE jitted program (`gam_design`,
scope ``gam.basis``): linear block (`datainfo.expand_columns`), every
smooth's constrained columns and the intercept's ones written as the
columns of the one (R, P+1) buffer the IRLS step reads. A first program of
the family (`gam_design_sums`) gives the column sums ``c``. Every smooth is
``W(x) · M``: a few row vectors ``W`` of the column's values (for bs 0 the
2K interpolation weights a-, a+, c-, c+ scattered to their knots by
compares against the interior knots: no search, no gather) times a small
host matrix ``M`` (for bs 0 ``[I; F]``), so a program contracts a block of
``W`` with ``M Z`` on the MXU at ``highest`` precision (the default would
round the weights to bfloat16), a block of rows at a time, written into
the output in place: nothing row-sized exists beside it. Knots, ``M Z`` and
the linear block's means are ARGUMENTS: a second job traces and compiles
nothing.

The fit is one penalized IRLS on the step GLM uses
(`glm._make_irls_kernel`), the host-side solve ADMM normally and cyclic COD
when monotone bounds are present.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..backend.jobs import Job
from ..backend.kernels import gram as gram_kernels
from ..backend.memory import hbm_span_attrs
from ..frame.frame import Frame
# the numpy evaluators live with the standalone scorer so GAM MOJOs score
# without the engine/JAX (`mojo.format.gam_columns`)
from ..mojo.format import cr_matrices, sum_to_zero, tp_constraint
from ..parallel.mesh import ROWS, default_mesh, n_row_shards, shard_map
from ..utils import telemetry
from .datainfo import DataInfo, expand_columns
from .glm import (GLMParameters, _admm_solve, _cod_solve, _gram_plan_attrs,
                  _make_dev_kernel, _make_irls_kernel)
from .model_base import Model, ModelBuilder, ModelOutput, make_metrics


def diff_penalty(n_basis: int, order: int = 2) -> np.ndarray:
    """P-spline penalty DᵀD (2nd-order differences of adjacent coefficients)."""
    D = np.diff(np.eye(n_basis), n=order, axis=0)
    return D.T @ D


# ---------------------------------------------------------------------------
# device-side evaluation: each returns the (R, n_w) array W whose product
# with the spec's host matrix M (`_weights_matrix`) is the basis
# `mojo.format.gam_basis` evaluates in numpy. Knots are traced arrays read
# at static positions (slices, no gather). W is built as a few 2-D
# element-wise operations: one row vector an operation costs a loop step of
# the design program a kernel launch a column (0.195 s of launches a job at
# 85 columns and 336 steps; PERF.md, PR 38).
# ---------------------------------------------------------------------------
def _cr_weights(x, knots):
    """Cubic regression spline (Wood 2017, section 5.3.1): for x in
    [k_j, k_j+1] the basis row is a- e_j + a+ e_j+1 + (c- e_j + c+ e_j+1) F.
    Returns (R, 2K): [A | C], A = a- at column j and a+ at j+1, C likewise
    of c-, c+; the interval j comes from K-2 compares against the interior
    knots (no search, no gather)."""
    K = knots.shape[0]
    x = jnp.clip(jnp.where(jnp.isnan(x), knots[K // 2], x),
                 knots[0], knots[K - 1])
    kj, kj1 = knots[0], knots[1]
    for i in range(1, K - 1):
        g = x >= knots[i]
        kj, kj1 = jnp.where(g, knots[i], kj), jnp.where(g, knots[i + 1], kj1)
    h, u, t = kj1 - kj, kj1 - x, x - kj
    am, ap = u / h, t / h
    cm = (u ** 3 / h - h * u) / 6.0
    cp = (t ** 3 / h - h * t) / 6.0
    # column m holds the lower weight where k_m is the interval's lower knot
    # and the upper weight where it is its upper knot
    lower = kj[:, None] == knots[None, :]
    upper = kj1[:, None] == knots[None, :]

    def scatter(lo, hi):
        return (jnp.where(lower, lo[:, None], 0.0)
                + jnp.where(upper, hi[:, None], 0.0))

    return jnp.concatenate([scatter(am, ap), scatter(cm, cp)], axis=1)


def _tp_weights(x, knots, tp_scale, nanfill):
    """1-D thin plate: the K radial bumps (|x - k| / scale)^3 and x / scale."""
    xm = jnp.where(jnp.isnan(x), nanfill, x)
    return jnp.concatenate(
        [(jnp.abs(xm[:, None] - knots[None, :]) / tp_scale) ** 3,
         (xm / tp_scale)[:, None]], axis=1)


def _bspline_weights(x, t, degree: int):
    """Cox-de-Boor B-splines over the clamped knot vector ``t`` (lo and hi
    repeated degree+1 times around the interior knots); NA/out-of-range
    clamp to the boundary. A repeated knot's empty span contributes 0."""
    nt = t.shape[0]
    lo, hi = t[0], t[nt - 1]
    x = jnp.clip(jnp.where(jnp.isnan(x), (lo + hi) / 2, x), lo, hi)
    B = [((x >= t[i]) & ((x < t[i + 1]) | (t[i + 1] == hi))
          & (t[i + 1] > t[i])).astype(jnp.float32) for i in range(nt - 1)]

    def ratio(num, den):
        return jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)

    for d in range(1, degree + 1):
        B = [ratio(x - t[i], t[i + d] - t[i]) * B[i]
             + ratio(t[i + d + 1] - x, t[i + d + 1] - t[i + 1]) * B[i + 1]
             for i in range(nt - 1 - d)]
    return jnp.stack(B[: nt - degree - 1], axis=1)


def _spec_weights(x, kind, args):
    bs, degree = kind
    if bs == 0:
        return _cr_weights(x, *args)
    if bs == 1:
        return _tp_weights(x, *args)
    return _bspline_weights(x, *args, degree)


def _weights_matrix(spec) -> np.ndarray:
    """The host matrix M with basis = W @ M for a spec's device weights."""
    bs = int(spec["bs"])
    if bs == 0:
        return np.vstack([np.eye(len(spec["knots"])), spec["F"]])
    if bs == 1:
        Z = np.asarray(spec["Z"], np.float64)
        M = np.zeros((Z.shape[0] + 1, Z.shape[1] + 1))
        M[:-1, :-1], M[-1, -1] = Z, 1.0
        return M
    nb = len(spec["interior"]) + spec["degree"] + 1
    if bs == 2:     # I_i = sum_{j >= i} B_j, the all-ones I_0 dropped
        return np.tril(np.ones((nb, nb)))[:, 1:]
    return np.eye(nb)


def _device_args(spec):
    """A spec's static kind and the small arrays its evaluator reads."""
    bs = int(spec["bs"])
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    if bs == 0:
        return (0, 0), (f32(spec["knots"]),)
    if bs == 1:
        return (1, 0), (f32(spec["knots"]), f32(spec["tp_scale"]),
                        f32(np.median(np.asarray(spec["knots"]))))
    d = int(spec["degree"])
    t = np.concatenate([[spec["lo"]] * (d + 1), spec["interior"],
                        [spec["hi"]] * (d + 1)])
    return (bs, d), (f32(t),)


#: rows of one step of the design programs' loops: a block's columns and
#: their intermediates stay tens of MB whatever the frame's rows (unblocked,
#: the compiler kept every column of an 11M-row design as a temporary of its
#: own beside the output: 3.75 GB at 85 columns), and the steps few enough
#: that their kernels' launches are not the program
_BLOCK_ROWS = 65536


def _rows(a, start, rows: int):
    return jax.lax.dynamic_slice_in_dim(a, start, rows, 0)


def _sums_body(gam_cols, args, nrow, *, kinds, axis=None):
    """Per smooth, the (n_w,) sums of its weight vectors over the frame's
    real rows: ``sums @ M`` is ``c = Xᵀ1`` (`sum_to_zero`'s argument, or the
    column means times the rows). ``axis`` names the mesh axis the rows are
    split over when this is the body of a ``shard_map``."""
    R = gam_cols[0].shape[0]
    first = 0 if axis is None else jax.lax.axis_index(axis) * R

    def add(acc, start, rows):
        live = first + start + jnp.arange(rows) < nrow
        with telemetry.scope("gam.basis"):
            return tuple(
                a + jnp.sum(jnp.where(
                    live[:, None],
                    _spec_weights(_rows(x, start, rows), kind, arg), 0.0),
                    axis=0)
                for a, x, kind, arg in zip(acc, gam_cols, kinds, args))

    init = tuple(jnp.zeros((_n_weights(kind, arg),), jnp.float32)
                 for kind, arg in zip(kinds, args))
    sums = gram_kernels.over_row_blocks(R, 2 * len(gam_cols), init, add,
                                       block=_BLOCK_ROWS)
    return sums if axis is None else jax.lax.psum(sums, axis)


def _n_weights(kind, arg) -> int:
    """How many weight vectors `_spec_weights` returns, from shapes."""
    bs, degree = kind
    n = arg[0].shape[0]
    return 2 * n if bs == 0 else n + 1 if bs == 1 else n - degree - 1


def _design_body(lin, gam_cols, args, maps, shifts, *, layout, kinds):
    """The whole design of a shard's rows, (R, P+1): the linear block, each
    smooth's columns ``W (M T) - shift`` (``maps`` holds ``M T``: T the
    sum-to-zero Z or the identity) and the intercept's ones, in that order;
    and the rows no NA under Skip flagged. Built a block of rows at a time
    and written into the one buffer in place. The contraction with the
    small matrix runs at ``highest`` precision: at the default the MXU
    rounds the weights to bfloat16, three digits of a basis value (as
    unrolled multiply-adds on the VPU the compiler's estimate was five
    times the cycles)."""
    R = gam_cols[0].shape[0]
    cards, lo, _ = layout if lin is not None else ((), 0, False)
    P1 = (sum(c - lo if c else 1 for c in cards)
          + sum(m.shape[1] for m in maps) + 1)

    def block(start, rows):
        parts, valid = [], None
        if lin is not None:
            parts, valid = expand_columns(
                tuple(_rows(c, start, rows) for c in lin[0]), *lin[1:],
                layout)
        with telemetry.scope("gam.basis"):
            parts += [
                jnp.dot(_spec_weights(_rows(x, start, rows), kind, a), MT,
                        precision=jax.lax.Precision.HIGHEST) - shift
                for x, kind, a, MT, shift in zip(gam_cols, kinds, args, maps,
                                                 shifts)]
        ones = jnp.ones((rows, 1), jnp.float32)
        return (jnp.concatenate(parts + [ones], axis=1),
                ones[:, 0] > 0 if valid is None else valid)

    def put(out, start, rows):
        return tuple(jax.lax.dynamic_update_slice_in_dim(o, b, start, 0)
                     for o, b in zip(out, block(start, rows)))

    return gram_kernels.over_row_blocks(
        R, P1, (jnp.zeros((R, P1), jnp.float32), jnp.zeros((R,), jnp.bool_)),
        put, block=_BLOCK_ROWS)


# the two design programs (`telemetry.PROGRAMS`), built once a mesh and
# static layout and kept: a second job of the same shapes traces nothing.
# Plain jit on one row shard, the body of a ``shard_map`` over the rows axis
# on several (a blocked loop over a row-sharded array left to GSPMD gathers
# the whole array every step)
@functools.lru_cache(maxsize=64)
def _sums_program(mesh, sharded: bool, kinds):
    @telemetry.program("gam_design_sums")
    def gam_design_sums(gam_cols, args, nrow):
        return _sums_body(gam_cols, args, nrow, kinds=kinds,
                          axis=ROWS if sharded else None)

    if not sharded:
        return jax.jit(gam_design_sums)
    return jax.jit(shard_map(gam_design_sums, mesh=mesh,
                             in_specs=(P(ROWS), P(), P()), out_specs=P(),
                             check_vma=False))


@functools.lru_cache(maxsize=64)
def _design_program(mesh, sharded: bool, layout, kinds):
    @telemetry.program("gam_design")
    def gam_design(lin, gam_cols, args, maps, shifts):
        return _design_body(lin, gam_cols, args, maps, shifts, layout=layout,
                            kinds=kinds)

    if not sharded:
        return jax.jit(gam_design)
    lin_spec = None if layout is None else (P(ROWS), P(), P(), P())
    return jax.jit(shard_map(gam_design, mesh=mesh,
                             in_specs=(lin_spec, P(ROWS), P(), P(), P()),
                             out_specs=(P(ROWS, None), P(ROWS)),
                             check_vma=False))


def _sharded(R: int):
    """``(mesh, whether an R-row frame's rows split evenly over its shards)``."""
    mesh = default_mesh()
    ns = n_row_shards(mesh)
    return mesh, ns > 1 and R % ns == 0


def _ncols(spec) -> int:
    """Columns a smooth has in the design (and coefficients in the model)."""
    return int(np.shape(spec["Zc"])[1] if "Zc" in spec
               else len(spec["col_means"]))


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
        """``(X, valid)``: the (R, P+1) design of ``fr`` with the intercept's
        ones as its last column, from the one device program training and
        scoring share (`_design_body`), and the rows it did not flag."""
        if self.interaction_spec:
            from .glm import _apply_interactions

            fr, _ = _apply_interactions(fr, self.interaction_spec,
                                           skip_existing=True)
        lin, layout = None, None
        if self.dinfo is not None and self.dinfo.names:
            lin, layout = self.dinfo.column_plan(fr)
        kinds, args = zip(*(_device_args(s) for s in self.gam_specs))
        maps, shifts = [], []
        for s in self.gam_specs:
            M = _weights_matrix(s)
            maps.append(jnp.asarray(M @ s["Zc"] if "Zc" in s else M,
                                    jnp.float32))
            shifts.append(jnp.asarray(
                s.get("col_means", np.zeros(maps[-1].shape[1])), jnp.float32))
        gam_cols = tuple(fr.vec(s["column"]).data for s in self.gam_specs)
        program = _design_program(*_sharded(gam_cols[0].shape[0]), layout,
                                  kinds)
        return program(lin, gam_cols, args, tuple(maps), tuple(shifts))

    def adapt_frame(self, fr: Frame):
        return self._design(self.pre_adapt(fr))[0]

    def score0(self, X):
        """``X`` is `_design`'s: the ones column is its last."""
        mu = self.family.linkinv(X @ jnp.asarray(self.beta, jnp.float32))
        if self.output.model_category == "Binomial":
            label = (mu > 0.5).astype(jnp.float32)
            return jnp.stack([label, 1 - mu, mu], axis=1)
        return mu

    def coef(self) -> dict:
        names = []
        if self.dinfo is not None:
            names += self.dinfo.expanded_names
        for spec in self.gam_specs:
            names += [f"{spec['column']}_gam.{i}" for i in range(_ncols(spec))]
        names.append("Intercept")
        return dict(zip(names, np.asarray(self.beta)))


class GAM(ModelBuilder):
    algo_name = "gam"

    def _validate(self):
        super()._validate()
        p = self.params
        if not p.gam_columns:
            raise ValueError("gam: gam_columns is required")
        for j, c in enumerate(p.gam_columns):
            if p.training_frame.find(c) < 0:
                raise ValueError(f"gam: gam column '{c}' not in frame")
            if p.training_frame.vec(c).is_categorical():
                raise ValueError(f"gam: gam column '{c}' must be numeric")
            if p.bs_for(j) not in (0, 1, 2, 3):
                raise ValueError(
                    f"gam: bs={p.bs_for(j)} unknown (0=cr, 1=thin plate, "
                    f"2=monotone I-splines, 3=M/P-splines)")

    def feature_names(self):
        names = super().feature_names()
        return [n for n in names if n not in self.params.gam_columns]

    def _knot_specs(self, fr: Frame):
        """A spec and an (unscaled) penalty a gam column: all that the knots
        alone decide. The interior knots lie at the column's quantiles off
        the tree engine's sketch, ONE call for all the columns that ask for
        the same quantiles (only those floats cross to the host); the end
        knots are the column's true minimum and maximum (the sketch brackets
        [0.1%, 99.9%])."""
        from .tree.binning import hist_quantile_sketch_cols

        p = self.params
        fr.ensure_rollups(p.gam_columns)
        inner = [max(p.knots_for(j), 3) - 2 if p.bs_for(j) in (0, 1)
                 else max(p.knots_for(j), 1) for j in range(len(p.gam_columns))]
        cuts: dict = {}
        for n in sorted(set(inner)):
            cols = [c for c, m in zip(p.gam_columns, inner) if m == n]
            q = hist_quantile_sketch_cols(
                [fr.vec(c) for c in cols],
                tuple(float(v) for v in np.linspace(0, 1, n + 2)[1:-1]))
            cuts.update(zip(cols, q.T.astype(np.float64)))
        specs, penalties = [], []
        for j, c in enumerate(p.gam_columns):
            r = fr.vec(c).rollups()
            lo, hi = float(r.mins), float(r.maxs)
            hi = hi if hi > lo else lo + 1.0
            bs = p.bs_for(j)
            interior = np.unique(np.clip(cuts[c], lo, hi))
            interior = interior[(interior > lo) & (interior < hi)]
            spec = dict(column=c, bs=bs, scale=p.scale_for(j))
            if bs in (0, 1):
                knots = np.concatenate([[lo], interior, [hi]])
                if len(knots) < 3:  # degenerate quantiles: span the DATA
                    knots = np.linspace(lo, hi, 3)
                spec["knots"] = knots
            if bs == 0:
                # penalty DᵀB⁻¹D, exact for the natural cubic interpolant
                spec["F"], S = cr_matrices(knots)
            elif bs == 1:
                # null-space-projected radial block (PSD energy penalty) +
                # the unpenalized linear term
                spec["tp_scale"] = max(float(knots[-1] - knots[0]), 1e-12)
                spec["Z"], S_rad = tp_constraint(knots, spec["tp_scale"])
                S = np.zeros((S_rad.shape[0] + 1,) * 2)
                S[:-1, :-1] = S_rad
            else:
                spec.update(lo=lo, hi=hi, interior=interior,
                            degree=p.spline_degree)
                S = diff_penalty(len(interior) + p.spline_degree + 1
                                 - (1 if bs == 2 else 0))
            specs.append(spec)
            penalties.append(S)
        return specs, penalties

    @staticmethod
    def _identify(spec, S, sums, nrow: int) -> np.ndarray:
        """Makes the smooth identifiable beside the intercept, from its
        weight sums over the training rows: the sum-to-zero constraint
        ``Zc`` where the basis holds the constants (bs 0, 3), else the
        column means. Returns the penalty of the columns the model has."""
        c = np.asarray(sums, np.float64) @ _weights_matrix(spec)
        if spec["bs"] in (0, 3):
            Z = spec["Zc"] = sum_to_zero(c)
            return Z.T @ S @ Z
        spec["col_means"] = c / max(nrow, 1)
        return S

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

        with telemetry.span("train.gam.knots", smooths=len(p.gam_columns),
                            knots=sum(p.knots_for(j)
                                      for j in range(len(p.gam_columns)))):
            gam_specs, penalties = self._knot_specs(fr)

        with telemetry.span("train.gam.design") as design_span:
            dinfo = (DataInfo.make(
                fr, lin_names, standardize=p.standardize,
                missing_values_handling=p.missing_values_handling)
                if lin_names else None)
            gam_cols = tuple(fr.vec(c).data for c in p.gam_columns)
            # the constraint needs the column sums: the first program's
            kinds, args = zip(*(_device_args(s) for s in gam_specs))
            sums = _sums_program(*_sharded(gam_cols[0].shape[0]), kinds)(
                gam_cols, args, jnp.int32(fr.nrow))
            pen_blocks = [s["scale"] * self._identify(s, S, w, fr.nrow)
                          for s, S, w in zip(gam_specs, penalties, sums)]
            mono_blocks = [s["bs"] == 2 and p.nonneg_for(j)
                           for j, s in enumerate(gam_specs)]

            output = ModelOutput()
            output.names = lin_names + list(p.gam_columns)
            output.domains = {n: fr.vec(n).domain for n in output.names}
            output.response_domain = list(resp_domain) if resp_domain else None
            output.model_category = category
            model = GAMModel(p, output, dinfo, gam_specs, None, family)
            model.interaction_spec = inter_spec

            X, valid = model._design(fr)   # ones column last
            P1 = X.shape[1]
            y = jnp.nan_to_num(y_dev)
            w = ((~jnp.isnan(y_dev)) & valid
                 & (jnp.arange(X.shape[0]) < fr.nrow)).astype(jnp.float32)
            if p.weights_column:
                w = w * jnp.nan_to_num(fr.vec(p.weights_column).data)
            offset = (jnp.nan_to_num(fr.vec(p.offset_column).data)
                      if p.offset_column else jnp.zeros_like(y))
            # from shapes alone: what the two programs read and write
            telemetry.inc("train.gam.design_bytes", 4 * X.shape[0] * (
                2 * len(gam_cols) + (len(dinfo.names) if dinfo else 0) + P1))
            design_span.attrs.update(design_cols=P1,
                                     design_gb=4 * X.shape[0] * P1 / 1e9,
                                     **hbm_span_attrs())

        # block-diagonal smoothing penalty (zeros over linear block +
        # intercept) as the Hessian of `scale * g'Sg`; per-coordinate lower
        # bounds realize the monotone blocks
        S = np.zeros((P1, P1))
        lo_bounds = np.full(P1, -np.inf)
        off = P1 - 1 - sum(_ncols(s) for s in gam_specs)
        for blk, spec, mono in zip(pen_blocks, gam_specs, mono_blocks):
            sz = _ncols(spec)
            S[off:off + sz, off:off + sz] = 2.0 * blk
            if mono:
                lo_bounds[off:off + sz] = 0.0
            off += sz
        any_mono = any(mono_blocks)

        # penalized IRLS (GLMDriver loop; the penalty, like alpha and
        # lambda's, on the raw Gram's scale: times neff)
        step = _make_irls_kernel(family)
        with telemetry.span("train.gam.start"):
            free = np.zeros(P1, dtype=bool)
            free[-1] = True
            alpha = p.alpha if p.alpha is not None else 0.0
            lam = p.lambda_ if p.lambda_ is not None else 0.0
            neff = float(jnp.sum(w))
            beta = np.zeros(P1, dtype=np.float64)
            b0 = float(family.init_intercept(y, w))
            beta[-1] = b0 if p.intercept else 0.0
            mu0 = family.linkinv(jnp.full_like(y, beta[-1]) + offset)
            nulldev = float(jnp.sum(family.deviance(y, mu0, w)))
            gram_plan = _gram_plan_attrs(X, w, offset)
        dev_prev = np.inf
        iters = 0
        for it in range(max(p.max_iterations, 1)):
            job.check_cancelled()
            with telemetry.span("train.gam.gram", **gram_plan):
                G, b, dev, _ = step(X, y, w, jnp.asarray(beta, jnp.float32),
                                    offset)
                Gn = np.asarray(G, np.float64) + neff * S
                bn = np.asarray(b, np.float64)
            iters += 1
            with telemetry.span("train.gam.solve"):
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
        telemetry.inc("train.gam.iterations", iters)

        with telemetry.span("train.gam.finish"):
            model.beta = beta
        # final scoring and metrics, by the GLM's path: score0 of the design
        # in hand, the fused metric kernel, the deviance off the probe
        with telemetry.span("train.gam.metrics"):
            raw = model.score0(X)
            ym = jnp.where(w > 0, y, jnp.nan)
            m = make_metrics(category, ym, raw,
                             w if p.weights_column else None,
                             auc_type=p.auc_type,
                             domain=output.response_domain)
            m.residual_deviance = float(_make_dev_kernel(family)(
                X, y, w, jnp.asarray(beta, jnp.float32), offset))
            m.null_deviance = nulldev
            output.training_metrics = m
            output.scoring_history = [{"iterations": iters,
                                       "deviance": m.residual_deviance}]
        if p.validation_frame is not None:
            output.validation_metrics = model.model_performance(p.validation_frame)
        job.update(1.0)
        return model
