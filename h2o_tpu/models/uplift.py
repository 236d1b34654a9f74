"""Uplift DRF — treatment-effect random forest.

Analog of `hex/tree/uplift/UpliftDRF.java` (771 LoC) with the divergence split
criteria baked into the histogram accumulator (`hex/tree/DHistogram.java:79-87`
keeps {numerator, denominator} per treatment group; the KL / EuclideanDistance /
ChiSquared divergences live in `hex/tree/uplift/Divergence.java`).

TPU-native structure mirrors the shared tree engine (engine.py): per level ONE
histogram build — here a 4-channel one-hot matmul accumulating
{w_treat, w_treat·y, w_ctrl, w_ctrl·y} per (feature, node, bin) — followed by
vectorized divergence-gain split finding on device and a psum over the rows
mesh axis. Trees are independent subsample fits (DRF semantics); leaves store
both treatment and control positive rates so prediction emits
(uplift, p_y1_ct1, p_y1_ct0) like the reference's UpliftDRFModel.

Divergences (p = P(y=1|treat), q = P(y=1|ctrl)):
  KL        : p·log(p/q) + (1−p)·log((1−p)/(1−q))
  Euclidean : (p−q)² + ((1−p)−(1−q))²
  ChiSquared: (p−q)²/q + ((1−p)−(1−q))²/(1−q)
Gain = Σ_child (n_child/n)·D(child) − D(parent). NA rows route right
(the reference picks the NA direction by gain; fixed-right is a documented
simplification).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..backend.jobs import Job
from ..frame.frame import Frame
from ..frame.vec import Vec
from ..parallel.mesh import ROWS, default_mesh, put_replicated, shard_map
from ..utils import telemetry
from .drf import DRFParameters
from .metrics import ModelMetrics
from .model_base import Model, ModelBuilder, ModelOutput
from .tree.binning import bin_matrix, compute_bin_edges
from .tree.engine import (TreeConfig, _build_level_hist, _level_col_mask,
                          _node_totals, plan_hist_groups, predict_forest)


@dataclass
class UpliftDRFParameters(DRFParameters):
    """Mirrors `hex/schemas/UpliftDRFV3`."""

    treatment_column: str = "treatment"
    uplift_metric: str = "AUTO"   # AUTO(=KL) | KL | Euclidean | ChiSquared
    auuc_type: str = "AUTO"       # AUTO(=qini) | qini | lift | gain
    auuc_nbins: int = -1          # -1 -> min(1000, 10% rows)


def _divergence(metric: str):
    eps = 1e-6

    def kl(p, q):
        p = jnp.clip(p, eps, 1 - eps)
        q = jnp.clip(q, eps, 1 - eps)
        return p * jnp.log(p / q) + (1 - p) * jnp.log((1 - p) / (1 - q))

    def euclid(p, q):
        return 2.0 * (p - q) ** 2

    def chisq(p, q):
        q = jnp.clip(q, eps, 1 - eps)
        return (p - q) ** 2 / q + (p - q) ** 2 / (1 - q)

    return {"KL": kl, "AUTO": kl, "EUCLIDEAN": euclid,
            "CHISQUARED": chisq}[metric.upper()]


def _find_uplift_splits(hist, colmask, edge_ok, div, cfg: TreeConfig):
    """hist: (F, n_lv, B, 4) = {wt, wty, wc, wcy}. Returns best splits/node."""
    nb = cfg.nbins
    eps = 1e-10
    WT, WTY = hist[..., 0], hist[..., 1]
    WC, WCY = hist[..., 2], hist[..., 3]
    # totals per node (identical across features; feature 0 slice)
    WTt = jnp.sum(WT, axis=2)[0]
    WTYt = jnp.sum(WTY, axis=2)[0]
    WCt = jnp.sum(WC, axis=2)[0]
    WCYt = jnp.sum(WCY, axis=2)[0]

    # cumulative left stats over real bins + NA bucket forced right
    cwt = jnp.cumsum(WT[:, :, :nb], axis=2)[:, :, :-1]
    cwty = jnp.cumsum(WTY[:, :, :nb], axis=2)[:, :, :-1]
    cwc = jnp.cumsum(WC[:, :, :nb], axis=2)[:, :, :-1]
    cwcy = jnp.cumsum(WCY[:, :, :nb], axis=2)[:, :, :-1]

    def rate(num, den):
        return num / jnp.maximum(den, eps)

    pL = rate(cwty, cwt)
    qL = rate(cwcy, cwc)
    wtR = WTt[None, :, None] - cwt
    wcR = WCt[None, :, None] - cwc
    pR = rate(WTYt[None, :, None] - cwty, wtR)
    qR = rate(WCYt[None, :, None] - cwcy, wcR)
    pP = rate(WTYt, WTt)
    qP = rate(WCYt, WCt)

    nL = cwt + cwc
    nR = wtR + wcR
    n = jnp.maximum(nL + nR, eps)
    gain = (nL / n) * div(pL, qL) + (nR / n) * div(pR, qR) - div(pP, qP)[None, :, None]

    ok = ((nL >= cfg.min_rows) & (nR >= cfg.min_rows)
          & (cwt > 0) & (cwc > 0) & (wtR > 0) & (wcR > 0))
    gain = jnp.where(ok, gain, -jnp.inf)
    gain = jnp.where(colmask[:, :, None], gain, -jnp.inf)
    gain = jnp.where(edge_ok[:, None, :], gain, -jnp.inf)

    F, n_lv = gain.shape[0], gain.shape[1]
    flat = jnp.transpose(gain, (1, 0, 2)).reshape(n_lv, -1)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    bf = (best // (nb - 1)).astype(jnp.int32)
    bb = (best % (nb - 1)).astype(jnp.int32)
    return best_gain, bf, bb, WTt + WCt


def _grow_uplift_tree(Xb, y, treat, w, edges, edge_ok, colkey, div,
                      cfg: TreeConfig):
    Rl, F = Xb.shape
    N = cfg.n_nodes
    B = cfg.nbins + 1

    feat = jnp.full((N,), -1, dtype=jnp.int32)
    thr = jnp.zeros((N,), dtype=jnp.float32)
    garr = jnp.zeros((N,), dtype=jnp.float32)
    node = jnp.zeros((Rl,), dtype=jnp.int32)
    wt = w * treat
    wc = w * (1.0 - treat)
    vals4 = jnp.stack([wt, wt * y, wc, wc * y], axis=1)

    tree_cols = (jax.random.uniform(jax.random.fold_in(colkey, 997), (F,))
                 < cfg.col_sample_rate_per_tree)
    tree_cols = jnp.where(jnp.any(tree_cols), tree_cols, True)

    for level in range(cfg.max_depth):
        n_lv = 2 ** level
        offset = n_lv - 1
        hist = _build_level_hist(Xb, node, vals4, offset, n_lv, B,
                                 cfg.block_rows, groups=cfg.hist_groups)
        cmask = _level_col_mask(jax.random.fold_in(colkey, level), F, n_lv,
                                cfg, tree_cols)

        gain, bf, bb, Wt = _find_uplift_splits(hist, cmask, edge_ok, div, cfg)
        do_split = (gain > cfg.min_split_improvement) & (Wt >= 2 * cfg.min_rows)

        feat = jax.lax.dynamic_update_slice(
            feat, jnp.where(do_split, bf, -1), (offset,))
        thr = jax.lax.dynamic_update_slice(thr, edges[bf, bb], (offset,))
        garr = jax.lax.dynamic_update_slice(
            garr, jnp.where(do_split, gain, 0.0).astype(jnp.float32), (offset,))

        local = node - offset
        active = (local >= 0) & (local < n_lv)
        lc = jnp.clip(local, 0, n_lv - 1)
        row_split = do_split[lc] & active
        rb_val = jnp.take_along_axis(Xb, bf[lc][:, None], axis=1)[:, 0]
        go_right = rb_val > bb[lc]  # NA bucket (bin==nbins) also routes right
        node = jnp.where(row_split, 2 * node + 1 + go_right.astype(jnp.int32),
                         node)

    # leaf stats: per-node {wt, wty, wc, wcy} -> p_t, p_c
    tot = _node_totals(node, vals4, N, cfg.block_rows)
    val_t = tot[:, 1] / jnp.maximum(tot[:, 0], 1e-10)
    val_c = tot[:, 3] / jnp.maximum(tot[:, 2], 1e-10)
    return feat, thr, garr, val_t, val_c


def make_uplift_train_fn(cfg: TreeConfig, metric: str, mesh=None):
    mesh = mesh or default_mesh()
    div = _divergence(metric)

    @telemetry.program("uplift_level")
    def spmd(Xb, y, treat, w, edges, edge_ok, keys):
        def tree_step(_, key):
            rowkey = jax.random.fold_in(key, jax.lax.axis_index(ROWS))
            if cfg.sample_rate < 1.0:
                s = (jax.random.uniform(rowkey, w.shape) < cfg.sample_rate
                     ).astype(jnp.float32)
            else:
                s = jnp.ones_like(w)
            out = _grow_uplift_tree(Xb, y, treat, w * s, edges, edge_ok, key,
                                    div, cfg)
            return 0.0, out

        _, trees = jax.lax.scan(tree_step, 0.0, keys)
        return trees

    fn = shard_map(
        spmd, mesh=mesh,
        in_specs=(P(ROWS, None), P(ROWS), P(ROWS), P(ROWS), P(), P(), P()),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


class ModelMetricsBinomialUplift(ModelMetrics):
    """AUUC-based metrics — `hex/ModelMetricsBinomialUplift` analog."""

    def __init__(self, auuc, auuc_normalized, qini, ate, att, atc, nbins):
        self.auuc = auuc
        self.auuc_normalized = auuc_normalized
        self.qini = qini
        self.ate = ate   # average treatment effect
        self.att = att   # ... on the treated
        self.atc = atc   # ... on control
        self.nbins = nbins
        self.mse = np.nan
        self.rmse = np.nan

    def __repr__(self):
        return (f"ModelMetricsBinomialUplift(AUUC={self.auuc:.4f}, "
                f"qini={self.qini:.4f}, ATE={self.ate:.4f})")


def make_uplift_metrics(y, treat, uplift, nbins=-1, auuc_type="AUTO"):
    """AUUC from sorted uplift predictions (`hex/AUUC.java` analog).

    auuc_type picks the curve whose area is reported as `auuc`
    (`hex/AUUC.AUUCType`): qini (AUTO) = cum. treated positives − scaled
    control positives; lift = p̂_t − p̂_c among targeted rows; gain = lift ×
    fraction targeted. ATE/ATT/ATC are means of the predicted uplift over
    all / treated / control rows (`hex/ModelMetricsBinomialUplift`).
    """
    y = np.asarray(y)
    treat = np.asarray(treat)
    uplift = np.asarray(uplift)
    ok = ~np.isnan(y)
    y, treat, uplift = y[ok], treat[ok], uplift[ok]
    n = len(y)
    nbins = int(min(nbins if nbins > 0 else 1000, max(n // 10, 1)))
    order = np.argsort(-uplift)
    ys, ts = y[order], treat[order]
    ct = np.cumsum(ts)
    cc = np.cumsum(1 - ts)
    cyt = np.cumsum(ys * ts)
    cyc = np.cumsum(ys * (1 - ts))
    idx = np.linspace(0, n - 1, nbins, dtype=np.int64)
    with np.errstate(divide="ignore", invalid="ignore"):
        qini_curve = cyt[idx] - np.where(
            cc[idx] > 0, cyc[idx] * ct[idx] / np.maximum(cc[idx], 1), 0)
        lift_curve = (np.where(ct[idx] > 0, cyt[idx] / np.maximum(ct[idx], 1), 0)
                      - np.where(cc[idx] > 0, cyc[idx] / np.maximum(cc[idx], 1), 0))
        gain_curve = lift_curve * (idx + 1)
    curves = {"QINI": qini_curve, "LIFT": lift_curve, "GAIN": gain_curve,
              "AUTO": qini_curve}
    auuc = float(np.mean(curves[(auuc_type or "AUTO").upper()]))
    qini = float(np.mean(qini_curve))
    ate = float(np.mean(uplift)) if n else np.nan
    att = float(np.mean(uplift[treat == 1])) if (treat == 1).any() else np.nan
    atc = float(np.mean(uplift[treat == 0])) if (treat == 0).any() else np.nan
    rand_auuc = ate * (n + 1) / 2
    norm = float(auuc / rand_auuc) if abs(rand_auuc) > 1e-12 else np.nan
    return ModelMetricsBinomialUplift(auuc, norm, qini, ate, att, atc, nbins)


class UpliftDRFModel(Model):
    algo_name = "upliftdrf"

    def __init__(self, params, output, forest, cfg, key=None):
        self.forest = forest  # feat/thr/val_t/val_c: (T, N)
        self.cfg = cfg
        super().__init__(params, output, key=key)

    def score0(self, X):
        T = self.forest["feat"].shape[0]
        nanL = jnp.zeros_like(self.forest["feat"], dtype=jnp.bool_)  # NA right
        pt = predict_forest(X, self.forest["feat"], self.forest["thr"], nanL,
                            self.forest["val_t"], self.cfg.max_depth) / T
        pc = predict_forest(X, self.forest["feat"], self.forest["thr"], nanL,
                            self.forest["val_c"], self.cfg.max_depth) / T
        return jnp.stack([pt - pc, pt, pc], axis=1)

    def _predictions_frame(self, raw, nrow):
        names = ["uplift_predict", "p_y1_ct1", "p_y1_ct0"]
        return Frame(names, [Vec.from_device(raw[:, j], nrow)
                             for j in range(3)])


class UpliftDRF(ModelBuilder):
    algo_name = "upliftdrf"

    def _validate(self):
        super()._validate()
        p = self.params
        if not p.treatment_column or p.training_frame.find(p.treatment_column) < 0:
            raise ValueError("upliftdrf: treatment_column must name a column")

    def feature_names(self):
        names = super().feature_names()
        return [n for n in names if n != self.params.treatment_column]

    def build_impl(self, job: Job) -> UpliftDRFModel:
        p = self.params
        fr = p.training_frame
        names = self.feature_names()
        y_dev, category, resp_domain = self.response_info()
        if category != "Binomial":
            raise ValueError("upliftdrf requires a binary (2-level) response "
                             "(`hex/tree/uplift/UpliftDRF.java` binomial-only)")

        X = fr.as_matrix(names)
        is_cat = np.array([fr.vec(n).is_categorical() for n in names])
        tvec = fr.vec(p.treatment_column)
        tvals = tvec.to_numpy()
        if np.isnan(tvals).any():
            raise ValueError(
                f"upliftdrf: treatment_column '{p.treatment_column}' has "
                f"{int(np.isnan(tvals).sum())} missing values; treatment "
                "assignment must be known for every row")
        uniq = np.unique(tvals)
        if not np.isin(uniq, (0.0, 1.0)).all():
            # the reference requires a 2-level categorical treatment
            # (`hex/tree/uplift/UpliftDRF.java` init checks)
            raise ValueError(
                f"upliftdrf: treatment_column '{p.treatment_column}' must be "
                f"binary 0/1 (2-level categorical); found values {uniq[:5]}")
        treat = jnp.nan_to_num(tvec.data)
        y = jnp.nan_to_num(y_dev)
        w = (~jnp.isnan(y_dev)).astype(jnp.float32)
        if p.weights_column:
            w = w * jnp.nan_to_num(fr.vec(p.weights_column).data)

        import math
        F = len(names)
        mtries = p.mtries if p.mtries and p.mtries > 0 else max(
            1, int(math.sqrt(F)))
        mesh = default_mesh()
        # nbins_cats pinned to nbins: the uplift engine splits categoricals
        # ordinally (no set splits), where a wider-than-nbins bin space only
        # inflates the (F, n_lv, B, 4) histograms without adding split power
        edges_np = compute_bin_edges(X, is_cat, p.nbins,
                                     seed=p.seed if p.seed not in (-1, None) else 1234,
                                     nbins_cats=p.nbins)
        cfg = TreeConfig(
            ntrees=p.ntrees, max_depth=min(p.max_depth, 12),
            # effective bin count follows the edge matrix (small-data exact
            # binning may widen it past p.nbins)
            nbins=edges_np.shape[1] + 1,
            min_rows=p.min_rows, sample_rate=p.sample_rate, mtries=mtries,
            min_split_improvement=max(p.min_split_improvement, 1e-9),
            col_sample_rate_per_tree=p.col_sample_rate_per_tree,
            drf_mode=True)
        # width-bucketed histogram accumulation (ROADMAP open item: the
        # uplift trees ran the flat path) — same auto-tuned plan as GBM but
        # over the 4-channel {wt, wty, wc, wcy} accumulator, with the row
        # block fitted to the live HBM budget
        from ..backend.memory import hbm_budget_bytes

        nedges_np = (~np.isnan(edges_np)).sum(axis=1).astype(np.int32)
        hist_groups, blk = plan_hist_groups(
            nedges_np, cfg.nbins + 1, cfg.block_rows,
            budget_bytes=hbm_budget_bytes(),
            n_lv_max=2 ** max(cfg.max_depth - 1, 0), nvals=4)
        cfg = dataclasses.replace(cfg, hist_groups=hist_groups,
                                  block_rows=blk)

        edges = put_replicated(np.nan_to_num(edges_np, nan=np.inf), mesh)
        edge_ok = put_replicated(~np.isnan(edges_np), mesh)
        Xb = bin_matrix(X, put_replicated(edges_np, mesh))

        train_fn = make_uplift_train_fn(cfg, p.uplift_metric, mesh)
        seed = p.seed if p.seed not in (-1, None) else 1234
        keys = jax.random.split(jax.random.PRNGKey(seed), p.ntrees)
        job.check_cancelled()
        feat, thr, gain, val_t, val_c = train_fn(Xb, y, treat, w, edges,
                                                 edge_ok, keys)
        forest = {"feat": feat, "thr": thr, "gain": gain,
                  "val_t": val_t, "val_c": val_c}

        output = ModelOutput()
        output.names = names
        output.domains = {n: fr.vec(n).domain for n in names}
        output.response_domain = list(resp_domain)
        output.model_category = "BinomialUplift"
        model = UpliftDRFModel(p, output, forest, cfg)
        raw = model.score0(X)
        uplift = np.asarray(raw[:, 0])[: fr.nrow]
        output.training_metrics = make_uplift_metrics(
            np.asarray(y_dev)[: fr.nrow], np.asarray(treat)[: fr.nrow],
            uplift, p.auuc_nbins, p.auuc_type)
        job.update(1.0)
        return model
