"""GBM — gradient boosting on the shared tree engine.

Analog of `hex/tree/gbm/GBM.java` (2,031 LoC) + the `hex/tree/SharedTree.java`
driver loop (`SharedTree.java:231,483-540` scoreAndBuildTrees). Supported
distributions mirror the reference (`GBM.java:464,510`): gaussian, bernoulli,
quasibinomial, multinomial, poisson, gamma, tweedie, laplace, quantile, huber.
Per-class trees for multinomial are one fused vmapped pass
(`SharedTree.java:361-363`).

Leaf values: Newton steps -G/(H+λ) for most families; laplace/quantile fit
QUANTILE gamma leaves and huber fits its hybrid gamma (median + clipped
mean, per-tree δ) like the reference (`GBM.java:685,730,814`), all via
distributed residual histograms with iterative range refinement. The one
huber residue: split-search gradients clip at unit delta rather than the
per-iteration δ. Binning is global-quantile by default with
UniformAdaptive/Random selectable (see tree/binning.py).
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..backend.jobs import Job
from ..backend.memory import hbm_budget_bytes, hbm_span_attrs
from ..frame.frame import Frame
from ..frame.vec import T_CAT, Vec
from ..parallel.mesh import (ROWS, default_mesh, n_row_shards,
                             per_shard_nbytes, put_replicated, put_sharded)
from ..utils import telemetry
from .distributions import Bernoulli, Gaussian, get_distribution
from .model_base import Model, ModelBuilder, ModelOutput, Parameters, make_metrics
from .tree.binning import (bin_matrix, compute_bin_edges,
                           compute_bin_edges_cols, sketch_span_attrs)
from .tree.engine import (TreeConfig, hist_onehot_cells, hist_plan_attrs,
                          hist_psum_bytes, make_train_fn, plan_hist_groups,
                          predict_forest, psum_payload_bytes)

#: last build's training-matrix accounting (mode, per-matrix bytes) — the
#: bench binned-storage leg and the chunk-store tests read this to put the
#: measured peak-bytes reduction on the record
LAST_TRAIN_MATRIX_BYTES: dict = {}

#: AOT-compiled chunked train steps, keyed by (program identity, arg
#: signature) — reused across builder instances like engine's
#: _TRAIN_FN_CACHE, so only the FIRST build of a shape family pays the
#: lower+compile (and with a warmed persistent compile cache that cost is
#: a disk replay)
_AOT_STEP_CACHE: dict = {}


def _aot_train_step(train_fn, args, key_base):
    """AOT lower+compile of the chunked train step at build setup — the
    serving-scorer discipline (`serving/scorer.py` compiles every bucket at
    registration) applied to training: the chunk loop dispatches a
    prebuilt executable, the compile wall is measured where it happens
    (``train.gbm.compile`` span + ``train.compile.seconds`` histogram,
    compile count on the span detail), and a process with a warmed
    persistent compile cache replays it from disk instead of compiling.
    Returns None when the builder has no stable program identity (custom
    distribution UDFs bypass every cache)."""
    if key_base is None:
        return None
    sig = tuple((tuple(a.shape), str(a.dtype)) for a in args)
    key = (key_base, sig)
    hit = _AOT_STEP_CACHE.get(key)
    if hit is not None:
        return hit
    from ..utils import compilemeter

    with telemetry.span("train.gbm.compile",
                        metric="train.compile.seconds") as sp:
        with compilemeter.scoped() as sc:
            compiled = train_fn.lower(*args).compile()
        sp.attrs["compiles"] = sc.compiles
        sp.attrs["uncached"] = sc.uncached
    # the tree train program's XLA cost/memory analyses land in the
    # program registry here — the one site every cached train fn's
    # executable passes through (engine._TRAIN_FN_CACHE programs reach
    # XLA via this AOT step; the jitted twin fallback re-runs the SAME
    # program, so one registration covers both dispatch paths)
    from ..utils import programs

    programs.register_compiled("train.tree.step", compiled, "train",
                               sig=sig, wall_metric="train.chunk.seconds",
                               module=programs.module_of(train_fn))
    _AOT_STEP_CACHE[key] = compiled
    return compiled


@dataclass
class GBMParameters(Parameters):
    """Mirrors `hex/schemas/GBMV3` / `hex/tree/gbm/GBMModel.GBMParameters`."""

    ntrees: int = 50
    max_depth: int = 5
    min_rows: float = 10.0
    learn_rate: float = 0.1
    learn_rate_annealing: float = 1.0
    sample_rate: float = 1.0
    histogram_type: str = "AUTO"  # AUTO/QuantilesGlobal (global sampled
                                  # quantiles — this engine's default) |
                                  # UniformAdaptive | Random
                                  # (`hex/tree/SharedTreeModel.HistogramType`)
    col_sample_rate: float = 1.0
    col_sample_rate_per_tree: float = 1.0
    col_sample_rate_change_per_level: float = 1.0
    max_abs_leafnode_pred: float = float("inf")
    nbins: int = 20
    nbins_cats: int = 1024
    min_split_improvement: float = 1e-5
    score_tree_interval: int = 0
    tweedie_power: float = 1.5
    quantile_alpha: float = 0.5
    huber_alpha: float = 0.9
    reg_lambda: float = 0.0
    custom_distribution_func: object = None  # Distribution-like object for
                                             # distribution="custom" — the
                                             # `water/udf` custom-distribution
                                             # UDF analog (in-process Python)
    monotone_constraints: dict = None        # {col: +1|-1} — `hex/tree/
                                             # Constraints.java` (h2o-py dict
                                             # format); regression/binomial only
    interaction_constraints: list = None     # [[cols...], ...] allowed
                                             # interaction groups (`hex/tree/
                                             # GlobalInteractionConstraints`)
    calibrate_model: bool = False            # Platt-scale p1 on a holdout
    calibration_frame: object = None         # (`hex/tree/CalibrationHelper`)


class GBMModel(Model):
    algo_name = "gbm"

    def __init__(self, params, output, forest, f0, dist, cfg, is_cat, key=None,
                 cat_nedges=None):
        self.forest = forest    # dict feat/thr/nanL/val[/catd]: (T,[K,]N[,B])
        self.f0 = f0            # scalar or (K,) initial link prediction
        self.dist = dist
        self.cfg = cfg
        self.is_cat = is_cat
        # per-feature cut counts (categorical level->bin map: bin =
        # min(level, n_edges)); only read when cfg.use_sets
        self.cat_nedges = cat_nedges
        super().__init__(params, output, key=key)

    def _set_args(self):
        """(catd, iscat, nedges) for the routing helpers — Nones when this
        model has no categorical set splits."""
        if not getattr(self.cfg, "use_sets", False) \
                or "catd" not in self.forest:
            return None, None, None
        return (self.forest["catd"], jnp.asarray(np.asarray(self.is_cat)),
                jnp.asarray(np.asarray(self.cat_nedges, dtype=np.int32)))

    def set_split_arrays_np(self):
        """Host-side (catd, iscat, nedges, cards) for codegen/export paths
        (MOJO writer, POJO) — all None when the model has no set splits.
        ``cards`` is the per-feature domain cardinality (0 for numeric):
        level -> bin is always ``min(level, nedges[f])``."""
        if not getattr(self.cfg, "use_sets", False) \
                or "catd" not in self.forest:
            return None, None, None, None
        cards = np.array([len(self.output.domains.get(n) or [])
                          for n in self.output.names], dtype=np.int64)
        return (np.asarray(self.forest["catd"]), np.asarray(self.is_cat),
                np.asarray(self.cat_nedges, dtype=np.int64), cards)

    @property
    def ntrees(self) -> int:
        return int(self.forest["feat"].shape[0])

    calib = None   # (a, b) Platt coefficients when calibrate_model was set
    cat_nedges = None  # class fallback for models persisted before round 4

    def score0(self, X: jax.Array) -> jax.Array:
        return _score_fn(self, X)

    def predict(self, fr: Frame) -> Frame:
        out = super().predict(fr)
        if self.calib is not None:
            # `CalibrationHelper.postProcessPredictions`: cal_p columns appended
            a, b = self.calib
            p1 = out.vec(2).data
            pc = jnp.clip(p1, 1e-6, 1 - 1e-6)
            margin = jnp.log(pc / (1 - pc))
            cal = jax.nn.sigmoid(a * margin + b)
            out.add("cal_p0", Vec.from_device(1.0 - cal, fr.nrow))
            out.add("cal_p1", Vec.from_device(cal, fr.nrow))
        return out

    #: row budget for one scoring pass when set-split tables are wide —
    #: caps the (rows, nbins) bin one-hot the routing builds per depth step
    _SET_SCORE_CELLS = 1 << 26

    def _score_chunk_rows(self, X, catd):
        """Rows per predict_forest call: unbounded without set splits;
        bounded so rows x catd-width stays under the cell budget with them
        (the training-side router blocks the same intermediate)."""
        if catd is None:
            return X.shape[0]
        return max(8192, self._SET_SCORE_CELLS // max(catd.shape[-1], 1))

    def _raw_f(self, X):
        catd, iscat, nedges = self._set_args()
        fo = self.forest
        step = self._score_chunk_rows(X, catd)
        parts = []
        for s0 in range(0, X.shape[0], step):
            parts.append(predict_forest(
                X[s0:s0 + step], fo["feat"], fo["thr"], fo["nanL"],
                fo["val"], self.cfg.max_depth, catd=catd, iscat=iscat,
                nedges=nedges))
        s = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
        if self.cfg.drf_mode:
            n = self.ntrees
            return self.f0 + s / jnp.maximum(n, 1)
        return self.f0 + s

    #: row budget for one code-space replay block — bounds the transient
    #: f32 upcast of the binned codes, NOT a whole (R, F) matrix
    _CODE_SCORE_CELLS = 1 << 26

    def _raw_f_codes(self, Xb, thr_codes, na_code: int):
        """Prior-forest replay over the chunk store's BINNED view, in
        bin-code space — the checkpoint-restart path that never stacks the
        raw f32 matrix (the PR 2 residual).

        Exactness: codes are ``#edges < x`` (`tree/binning.bin_column`), so
        for any threshold that IS an edge value — and GBM splits only at
        edges — ``x > thr  <=>  code(x) > #edges < thr``, duplicates and
        all. Per row-block the codes upcast to f32 with the NA bucket
        restored to NaN, and `predict_forest` runs with the code-space
        thresholds: every routing decision matches the raw-value traversal,
        the same leaf values accumulate in the same scan order, and the
        result is bit-equal to ``_raw_f`` on the stacked matrix (rows are
        independent in the traversal, so blocking is exact)."""
        catd, iscat, nedges = self._set_args()
        fo = self.forest
        thr = jnp.asarray(thr_codes)
        step = min(self._score_chunk_rows(Xb, catd),
                   max(8192, self._CODE_SCORE_CELLS // max(Xb.shape[1], 1)))
        parts = []
        for s0 in range(0, Xb.shape[0], step):
            xf = _codes_to_f32(Xb[s0:s0 + step], na_code)
            parts.append(predict_forest(
                xf, fo["feat"], thr, fo["nanL"], fo["val"],
                self.cfg.max_depth, catd=catd, iscat=iscat, nedges=nedges))
        s = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
        if self.cfg.drf_mode:
            n = self.ntrees
            return self.f0 + s / jnp.maximum(n, 1)
        return self.f0 + s

    # -- TreeSHAP contributions (`Model.scoreContributions`,
    #    `hex/genmodel/algos/tree/TreeSHAP.java`) ---------------------------
    def predict_contributions(self, fr: Frame) -> Frame:
        """Per-feature SHAP contributions + BiasTerm, in margin space.
        Rows sum to the raw (link-scale) prediction — same contract as the
        reference (binomial/regression tree models only)."""
        if self.output.model_category not in ("Regression", "Binomial"):
            raise ValueError("predict_contributions supports regression and "
                             "binomial tree models only (as in the reference)")
        self._ensure_covers()
        from .tree.shap import tree_shap

        X = np.asarray(self.adapt_frame(fr))[:fr.nrow]
        scale = 1.0 / max(self.ntrees, 1) if self.cfg.drf_mode else 1.0
        catd, iscat, nedges, _ = self.set_split_arrays_np()
        phi = tree_shap(
            X, np.asarray(self.forest["feat"]), np.asarray(self.forest["thr"]),
            np.asarray(self.forest["nanL"]), np.asarray(self.forest["val"]),
            np.asarray(self.forest["cover"]), bias0=float(self.f0),
            scale=scale, catd=catd, iscat=iscat, nedges=nedges)
        names = list(self.output.names) + ["BiasTerm"]
        return Frame.from_dict(
            {n: phi[:, i].astype(np.float32) for i, n in enumerate(names)})

    def _ensure_covers(self) -> None:
        """Compute node covers lazily, on first SHAP use.

        `forest_covers` is a full routing pass over the training rows — real
        wall-clock (≈8 s at HIGGS scale) that the common train→predict path
        never needs, so it runs here instead of inside training, from the
        still-attached training frame (the reference pays this cost at
        training time by writing node weights into the tree format;
        `hex/genmodel/algos/tree/TreeSHAP.java` only reads them at SHAP
        time)."""
        if "cover" in self.forest:
            return
        p = self.params
        fr = p.training_frame
        if fr is None:
            raise ValueError(
                "model has no stored node covers and no attached training "
                "frame to compute them from (model was imported without node "
                "weights)")
        from .tree.engine import forest_covers

        X = self.adapt_frame(fr)  # padded device matrix, training column order
        if p.weights_column:
            w = jnp.nan_to_num(fr.vec(p.weights_column).data)  # padding -> 0
        else:
            w = jnp.ones(X.shape[0], jnp.float32)
        # rows with NA response carried zero weight during training (and
        # padding rows have NaN response), so covers must exclude them too
        w = w * (~jnp.isnan(fr.vec(p.response_column).data)).astype(jnp.float32)
        catd, iscat, nedges = self._set_args()
        step = self._score_chunk_rows(X, catd)
        cover = None
        for s0 in range(0, X.shape[0], step):  # counts sum across chunks
            c = forest_covers(
                X[s0:s0 + step], w[s0:s0 + step], self.forest["feat"],
                self.forest["thr"], self.forest["nanL"], self.cfg.max_depth,
                catd=catd, iscat=iscat, nedges=nedges)
            cover = c if cover is None else cover + c
        self.forest["cover"] = cover

    def _leaf_nodes(self, X: np.ndarray) -> np.ndarray:
        """(R, T*[K]) final heap node index per row per tree via host routing."""
        feat = np.asarray(self.forest["feat"])
        thr = np.asarray(self.forest["thr"])
        nanL = np.asarray(self.forest["nanL"]).astype(bool)
        catd_a, _, _ = self._set_args()
        catd = None if catd_a is None else np.asarray(catd_a)
        iscat = np.asarray(self.is_cat) if catd is not None else None
        ne = (np.asarray(self.cat_nedges, dtype=np.int64)
              if catd is not None else None)
        multi = feat.ndim == 3
        idxs = ([(t, None) for t in range(feat.shape[0])] if not multi else
                [(t, k) for t in range(feat.shape[0])
                 for k in range(feat.shape[1])])
        trees = [(feat[t] if k is None else feat[t, k],
                  thr[t] if k is None else thr[t, k],
                  nanL[t] if k is None else nanL[t, k],
                  None if catd is None else
                  (catd[t] if k is None else catd[t, k]))
                 for t, k in idxs]
        R = X.shape[0]
        out = np.zeros((R, len(trees)), dtype=np.int64)
        rows = np.arange(R)
        for ti, (f, th, nl, cd) in enumerate(trees):
            node = np.zeros(R, dtype=np.int64)
            for _ in range(self.cfg.max_depth):
                fs = f[node]
                leaf = fs < 0
                fc = np.clip(fs, 0, None)
                x = X[rows, fc]
                right = np.where(np.isnan(x), ~nl[node], x > th[node])
                if cd is not None:
                    isset = iscat[fc] & (fs >= 0)
                    xb = np.clip(np.nan_to_num(x), 0,
                                 ne[fc]).astype(np.int64)
                    set_right = cd[node, xb] > 0.5
                    right = np.where(np.isnan(x), right,
                                     np.where(isset, set_right, right))
                node = np.where(leaf, node, 2 * node + 1 + right)
            out[:, ti] = node
        return out

    def predict_leaf_node_assignment(self, fr: Frame,
                                     type: str = "Path") -> Frame:
        """`Model.scoreLeafNodeAssignment` analog: per-tree terminal leaf as a
        root-to-leaf L/R path string (default) or the heap node id."""
        X = np.asarray(self.adapt_frame(fr))[:fr.nrow]
        nodes = self._leaf_nodes(X)
        feat = np.asarray(self.forest["feat"])
        multi = feat.ndim == 3
        K = feat.shape[1] if multi else 1
        dom = self.output.response_domain or [str(i) for i in range(K)]
        names = [f"T{t + 1}" if not multi else f"T{t + 1}.C{dom[k]}"
                 for t in range(feat.shape[0]) for k in range(K)][:nodes.shape[1]]
        if type == "Node_ID":
            return Frame.from_dict({nm: nodes[:, i].astype(np.float32)
                                    for i, nm in enumerate(names)})
        out = Frame([], [])
        for i, nm in enumerate(names):
            uniq = np.unique(nodes[:, i])
            lut = {int(n): _heap_path(int(n)) for n in uniq}
            domain = sorted(set(lut.values()))
            code = {s: j for j, s in enumerate(domain)}
            codes = np.array([code[lut[int(n)]] for n in nodes[:, i]],
                             dtype=np.float32)
            out.add(nm, Vec.from_numpy(codes, type=T_CAT, domain=domain))
        return out

    def staged_predict_proba(self, fr: Frame) -> Frame:
        """Cumulative class-1 probability (binomial) or prediction
        (regression) after each successive tree (`Model.scoreStagedPredictions`)."""
        if self.output.model_category not in ("Regression", "Binomial"):
            raise ValueError("staged predictions support regression and "
                             "binomial models only")
        X = np.asarray(self.adapt_frame(fr))[:fr.nrow]
        nodes = self._leaf_nodes(X)
        val = np.asarray(self.forest["val"])
        per_tree = np.stack([val[t][nodes[:, t]]
                             for t in range(val.shape[0])], axis=1)
        cum = np.cumsum(per_tree, axis=1)
        if self.cfg.drf_mode:
            cum = cum / np.arange(1, val.shape[0] + 1)[None, :]
        f = float(self.f0) + cum
        if self.cfg.drf_mode and self.output.model_category == "Binomial":
            out = np.clip(f, 0.0, 1.0)
        else:
            out = np.asarray(self.dist.linkinv(jnp.asarray(f)))
        return Frame.from_dict({f"T{t + 1}": out[:, t].astype(np.float32)
                                for t in range(out.shape[1])})


def _score_fn(model: GBMModel, X):
    cat = model.output.model_category
    f = model._raw_f(X)
    if cat == "Regression":
        return model.dist.linkinv(f)
    if cat == "Binomial":
        p1 = model.dist.linkinv(f) if not model.cfg.drf_mode else jnp.clip(f, 0.0, 1.0)
        # default_threshold is settable via rapids model.reset.threshold;
        # >= matches the MOJO reader and the reference's getPrediction
        thr = float(getattr(model, "default_threshold", 0.5))
        label = (p1 >= thr).astype(jnp.float32)
        return jnp.stack([label, 1 - p1, p1], axis=1)
    # Multinomial: f (R, K)
    if model.cfg.drf_mode:
        p = jnp.clip(f, 1e-9, 1.0)
        p = p / jnp.sum(p, axis=1, keepdims=True)
    else:
        p = jax.nn.softmax(f, axis=1)
    label = jnp.argmax(p, axis=1).astype(jnp.float32)
    return jnp.concatenate([label[:, None], p], axis=1)


class GBM(ModelBuilder):
    algo_name = "gbm"
    drf_mode = False
    _constant_response_check = True  # `hex/tree/SharedTree.init` check

    def _tree_config(self, K, nbins: int | None = None) -> TreeConfig:
        p = self.params
        if getattr(p, "monotone_constraints", None) and K > 1:
            raise ValueError("monotone_constraints are not supported for "
                             "multinomial models (reference restriction)")
        return TreeConfig(
            use_monotone=bool(getattr(p, "monotone_constraints", None)),
            use_interaction=bool(getattr(p, "interaction_constraints", None)),
            ntrees=p.ntrees, max_depth=p.max_depth,
            nbins=p.nbins if nbins is None else nbins,
            min_rows=p.min_rows, learn_rate=p.learn_rate,
            reg_lambda=getattr(p, "reg_lambda", 0.0),
            min_split_improvement=p.min_split_improvement,
            sample_rate=p.sample_rate, col_sample_rate=p.col_sample_rate,
            col_sample_rate_per_tree=p.col_sample_rate_per_tree,
            col_sample_rate_change_per_level=p.col_sample_rate_change_per_level,
            max_abs_leafnode_pred=p.max_abs_leafnode_pred,
            drf_mode=self.drf_mode, nclass=K,
        )

    def _distribution(self, category):
        p = self.params
        if self.drf_mode:
            return Gaussian()  # DRF leaves = per-leaf response means
        name = (p.distribution or "AUTO").upper()
        if name == "CUSTOM":
            if p.custom_distribution_func is None:
                raise ValueError("distribution='custom' requires "
                                 "custom_distribution_func")
            return p.custom_distribution_func
        if name == "AUTO":
            name = {"Binomial": "bernoulli", "Multinomial": "multinomial",
                    "Regression": "gaussian"}[category]
        return get_distribution(name, tweedie_power=p.tweedie_power,
                                quantile_alpha=p.quantile_alpha,
                                huber_alpha=p.huber_alpha)

    def _setup_build(self, need_raw: bool = False):
        """Shared pre-training setup: design matrix, weights/mask, bin
        edges, constraints, init prediction, grad fn, tree config, initial
        margin — used by the standard boosting loop and the DART driver.

        By default the training matrix is the chunk store's int8/int16
        BINNED VIEW, built column-by-column from the frame's Vecs — the raw
        f32 matrix is never stacked (`frame/chunks.py`; disable with
        ``H2O_TPU_BINNED_STORE=0``). ``need_raw`` forces the legacy stacked
        path for drivers that replay prior forests over raw thresholds
        (checkpoint restarts, DART's dropped-tree evaluation)."""
        import types as _types

        # train.gbm.prep names every stretch of the set-up that is neither
        # the sketch nor the coded view (response and weights here, edges
        # and constraints to the mesh, start margin and plan below): it
        # opens more than once a job, a child of the job's root each time
        with telemetry.span("train.gbm.prep"):
            p = self.params
            fr = p.training_frame
            names = self.feature_names()
            y_dev, category, resp_domain = self.response_info()
            dist = self._distribution(category)
            K = len(resp_domain) if category == "Multinomial" else 1

            from ..utils.knobs import get_bool

            use_binned = not need_raw and get_bool("H2O_TPU_BINNED_STORE")
            is_cat = np.array([fr.vec(n).is_categorical() for n in names])
            w_in = (jnp.nan_to_num(
                Vec.from_numpy(np.nan_to_num(
                    fr.vec(p.weights_column).to_numpy())).data)
                if p.weights_column else None)
            # ONE compiled program for the y/w/mask prep — the per-op eager
            # version paid a fixed compile+load per tiny program on a cold
            # process
            y, ymask, w, ym = _jit_prep(y_dev, w_in)

            bin_kw = dict(
                seed=p.seed if p.seed not in (-1, None) else 1234,
                histogram_type=p.histogram_type,
                nbins_top_level=int(getattr(p, "nbins_top_level", 1024) or 1024),
                nbins_cats=int(getattr(p, "nbins_cats", 1024) or 1024))
            if use_binned:
                X = None
                feat_vecs = [fr.vec(n) for n in names]
            else:
                X = fr.as_matrix(names)
        # the quantile sketch, until the edges are on the host (the copy out
        # drains it); its attributes say which count contraction ran
        with telemetry.span("train.gbm.sketch", **sketch_span_attrs(
                y_dev.shape[0], len(names), p.histogram_type)) as sk_span:
            edges_np = (
                compute_bin_edges_cols(feat_vecs, is_cat, p.nbins, **bin_kw)
                if use_binned
                else compute_bin_edges(X, is_cat, p.nbins, **bin_kw))
            sk_span.attrs.update(hbm_span_attrs())
        with telemetry.span("train.gbm.prep"):
            mesh = default_mesh()
            edges = put_replicated(np.nan_to_num(edges_np, nan=np.inf), mesh)
            mono_np = np.zeros(len(names), dtype=np.float32)
            for col, d in (getattr(p, "monotone_constraints", None) or {}).items():
                if col not in names:
                    raise ValueError(f"monotone_constraints column '{col}' is not "
                                     f"a feature")
                if fr.vec(col).is_categorical():
                    raise ValueError(f"monotone_constraints on categorical column "
                                     f"'{col}' (numeric only, as in the reference)")
                mono_np[names.index(col)] = float(np.sign(d))
            mono = put_replicated(mono_np, mesh)
            imat_np = _interaction_matrix(names,
                                          getattr(p, "interaction_constraints",
                                                  None))
            imat = put_replicated(imat_np, mesh)
            edge_ok = put_replicated(~np.isnan(edges_np), mesh)
            binned_view = None
        # HOST wall of the coded-matrix build: no sync is added here, so
        # where the build does not drain by itself the device's side is the
        # scope gbm.bin in a capture
        with telemetry.span("train.gbm.binned_view") as bv_span:
            if use_binned:
                # device-resident coded training matrix, packed column-by-
                # column (Cleaner-tracked; the engine upcasts blocks in-scan)
                from ..frame.chunks import BinnedView

                binned_view = BinnedView.build(feat_vecs, edges_np,
                                               names=names)
                Xb = binned_view.matrix
            else:
                Xb = bin_matrix(X, put_replicated(edges_np, mesh))
            # what a stored code costs: 1 or 2 bytes in the binned view
            # (`BinnedView.code_dtype`), 4 on the stacked path
            bv_span.attrs["code_bytes"] = int(Xb.dtype.itemsize)
            bv_span.attrs["coded_gb"] = Xb.size * Xb.dtype.itemsize / 1e9
            bv_span.attrs.update(hbm_span_attrs())
        with telemetry.span("train.gbm.prep"):
            plen = Xb.shape[0]
            # how the job's rows lie on the mesh, on the job's root span
            # (train.<algo>; a CV fold's builder has none)
            root = getattr(self, "_train_span", None)
            if root is not None:
                root.attrs["row_shards"] = n_row_shards(mesh)
                root.attrs["rows_per_shard"] = plen // n_row_shards(mesh)
            global LAST_TRAIN_MATRIX_BYTES
            LAST_TRAIN_MATRIX_BYTES = {
                "mode": "binned" if use_binned else "stacked_f32",
                "raw_bytes": 0 if X is None else int(X.size * X.dtype.itemsize),
                "binned_bytes": int(Xb.size * Xb.dtype.itemsize),
                "binned_dtype": str(Xb.dtype),
                "cells": int(plen * len(names)),
                # multi-chip accounting: the LARGEST single-device slice of the
                # training matrix (row-sharded ⇒ ~binned_bytes/n_shards; the
                # per-chip HBM number the sharded bench leg steers by)
                "per_shard_bytes": per_shard_nbytes(Xb),
                "n_row_shards": n_row_shards(mesh),
            }

            # initial prediction (`hex/tree/gbm/GBM.java:265` init) — one
            # compiled program per (drf, K, distribution) family
            f0 = _jit_init_f(self.drf_mode, K, dist, y, w)

            grad_fn = self._make_grad_fn(dist, K)
            # effective bin count follows the edge matrix: small-data exact
            # binning and nbins_cats may widen it past p.nbins
            cfg = self._tree_config(K, nbins=edges_np.shape[1] + 1)
            # categorical SET splits (IcedBitSet analog) whenever categorical
            # features exist; RuleFit's internal forests opt out (threshold-only
            # rule language)
            use_sets = bool(is_cat.any()) and getattr(self, "_use_set_splits",
                                                      True)
            nedges_np = (~np.isnan(edges_np)).sum(axis=1).astype(np.int32)
            iscat_dev = put_replicated(is_cat, mesh)
            nedges_dev = put_replicated(nedges_np, mesh)
            # histogram accumulation plan: width-bucketed hist_groups (auto-tuned
            # from the per-column bin counts) plus a row block fitted to the live
            # HBM budget, so wide bin spaces (high-cardinality categoricals /
            # exact binning) bound the per-block one-hot footprint by
            # construction — see engine.plan_hist_groups
            B_hist = cfg.nbins + 1
            hist_groups, blk = plan_hist_groups(
                nedges_np, B_hist, cfg.block_rows,
                budget_bytes=hbm_budget_bytes(),
                n_lv_max=2 ** max(cfg.max_depth - 1, 0), nvals=3)
            cfg = dataclasses.replace(cfg, use_sets=use_sets, block_rows=blk,
                                      hist_groups=hist_groups)
            # per-tree ICI reduction payload (per-level hist psums + the node-
            # totals psum) — static accounting, beside the matrix bytes
            LAST_TRAIN_MATRIX_BYTES["psum_bytes_per_tree"] = \
                psum_payload_bytes(cfg, len(names))
            if not self.drf_mode and K == 1 and dist.name in ("laplace",
                                                              "quantile"):
                # exact gamma leaves: median (laplace) / alpha-quantile of the
                # in-leaf residuals replaces the Newton step (`GBM.java:730,814`)
                cfg = dataclasses.replace(
                    cfg, leaf_quantile=(0.5 if dist.name == "laplace"
                                        else p.quantile_alpha))
            elif not self.drf_mode and K == 1 and dist.name == "huber":
                # hybrid gamma leaves (`GBM.java:685`); the split-search
                # gradients still clip at unit delta (documented residue)
                cfg = dataclasses.replace(cfg, huber_leaf_alpha=p.huber_alpha)
            # the overlapped reduction is BIT-equal to the joint scan, so
            # it defaults on
            cfg = dataclasses.replace(
                cfg, async_psum=get_bool("H2O_TPU_ASYNC_PSUM"))
            # the cache key must pin everything grad_fn's behavior depends on;
            # custom distribution UDFs bypass the cache entirely (an id()-based
            # key could alias a new UDF at a recycled address after GC)
            if p.custom_distribution_func is dist:
                grad_key = None
            else:
                grad_key = (type(self).__name__, self.drf_mode, K, dist.name,
                            p.tweedie_power, p.quantile_alpha, p.huber_alpha)

            if K > 1:
                y_k = jnp.broadcast_to(y, (K, y.shape[0]))
                f = jnp.broadcast_to(f0[:, None], (K, y.shape[0])).astype(jnp.float32)
            else:
                y_k = y
                f = _jit_full_like(y, f0)
        return _types.SimpleNamespace(
            p=p, fr=fr, names=names, category=category,
            resp_domain=resp_domain, dist=dist, K=K, X=X, is_cat=is_cat,
            w=w, y=y, ymask=ymask, ym=ym, edges_np=edges_np, mesh=mesh,
            edges=edges, mono=mono, imat=imat, edge_ok=edge_ok, Xb=Xb,
            f0=f0, grad_fn=grad_fn, cfg=cfg, grad_key=grad_key, y_k=y_k,
            f=f, iscat_dev=iscat_dev, nedges_dev=nedges_dev,
            nedges_np=nedges_np, binned_view=binned_view)

    def build_impl(self, job: Job) -> GBMModel:
        rs = self._take_resume_state()
        # checkpoint restarts replay the prior forest in BIN-CODE space over
        # the chunk store's binned view (_raw_f_codes — exact, because GBM
        # splits sit on bin edges), so even they no longer stack the raw f32
        # matrix; only a prior whose thresholds are off the current grid
        # (continuation on different data/binning) forces the stacked path.
        # An auto-recovery resume carries f in its state — no replay at all.
        prior = None
        if self.params.checkpoint is not None and rs is None:
            prior = self._resolve_checkpoint(self.params.checkpoint)
        s = self._setup_build(need_raw=False)
        prior_thr_codes = None
        if prior is not None and s.X is None:
            prior_thr_codes = _prior_thr_codes(prior, s.edges_np)
            if prior_thr_codes is None:
                from ..utils.log import info

                info("checkpoint restart: prior split thresholds are not on "
                     "the current bin grid — replaying over the stacked raw "
                     "matrix instead")
                s = self._setup_build(need_raw=True)
        # the set-up's last stretch, train.gbm.prep as in _setup_build: keys,
        # rates, the train function and its arguments, up to the step's
        # load (train.gbm.compile where the executable is not in hand)
        with telemetry.span("train.gbm.prep"):
            p, fr, names = s.p, s.fr, s.names
            category, resp_domain, dist, K = (s.category, s.resp_domain,
                                              s.dist, s.K)
            is_cat, w, y, ymask = s.is_cat, s.w, s.y, s.ymask
            # the RAW stacked matrix (present only with BINNED_STORE=0 or the
            # off-grid fallback above) is binning input / replay input only —
            # drop it the moment nothing needs it: at airlines-116M scale it is
            # ~4 GB of HBM the whole train would otherwise hold. (XGBoost's
            # DART driver keeps its own s.X.)
            X = s.X
            if prior is None:
                X = s.X = None
            edges, mono, imat, edge_ok, Xb = (s.edges, s.mono, s.imat,
                                              s.edge_ok, s.Xb)
            mesh, f0, grad_fn, cfg, grad_key = (s.mesh, s.f0, s.grad_fn,
                                                s.cfg, s.grad_key)
            y_k, f = s.y_k, s.f

            # checkpoint restart (`hex/tree/SharedTree.java:146,243,470`): resume
            # the boosting sequence from a prior model's carried link predictions.
            prior_parts = []
            if prior is not None:
                if p.ntrees <= prior.ntrees:
                    raise ValueError(
                        f"checkpoint model already has {prior.ntrees} trees; "
                        f"ntrees must exceed that (got {p.ntrees})")
                # parameter-compatibility validation, up front (the reference
                # validates before training, `SharedTree` checkpoint checks)
                prior_mono = getattr(prior.params, "monotone_constraints", None) or {}
                for fld, ours, theirs in (
                        ("max_depth", p.max_depth, prior.cfg.max_depth),
                        # cfg.nbins is the EFFECTIVE bin count (small-data exact
                        # binning may widen it); the user contract is the param
                        ("nbins", p.nbins,
                         getattr(prior.params, "nbins", prior.cfg.nbins)),
                        ("nbins_cats", getattr(p, "nbins_cats", 1024),
                         getattr(prior.params, "nbins_cats", 1024)),
                        ("nclasses", K, prior.cfg.nclass),
                        ("drf_mode", self.drf_mode, prior.cfg.drf_mode),
                        ("monotone_constraints",
                         dict(getattr(p, "monotone_constraints", None) or {}),
                         dict(prior_mono))):
                    if ours != theirs:
                        raise ValueError(
                            f"checkpoint incompatible: {fld} differs "
                            f"(checkpoint={theirs}, request={ours})")
                # the stored params reference the prior by key, not by object —
                # keeps binary export/import free of nested models/frames
                p = self.params = dataclasses.replace(p, checkpoint=prior.key)
                # continuation trees must speak the prior forest's split
                # language: inherit its use_sets so pre-round-4 models (ordinal
                # categorical splits) stay continuable, and a set-split prior
                # keeps its routing tables live
                prior_sets = bool(getattr(prior.cfg, "use_sets", False))
                if cfg.use_sets != prior_sets:
                    cfg = dataclasses.replace(cfg, use_sets=prior_sets)
                f0 = prior.f0
                if prior_thr_codes is not None:  # binned replay — X never stacked
                    fprev = prior._raw_f_codes(Xb, prior_thr_codes,
                                               s.edges_np.shape[1] + 1)
                else:
                    fprev = prior._raw_f(X)  # includes f0, link scale
                X = s.X = None  # replay done — release the raw matrix (if any)
                f = fprev.T.astype(jnp.float32) if K > 1 else fprev.astype(jnp.float32)
                if self.drf_mode:
                    # _raw_f averages DRF trees; the carried f is the raw sum
                    f = f * prior.ntrees
                pf = prior.forest
                prior_parts = [tuple(
                    pf[k] if k in pf else
                    jnp.zeros(pf["feat"].shape + (1,), jnp.float32)
                    for k in ("feat", "thr", "nanL", "val", "gain", "catd"))]

            n_prior = prior.ntrees if prior else 0
            if rs is not None:
                # auto-recovery resume: the state carries everything the prior
                # block would have derived (n_prior/f0/use_sets), so a resumed
                # continuation never needs the prior model object back
                n_prior = int(rs["n_prior"])
                f0 = jnp.asarray(np.asarray(rs["f0"]))
                if bool(rs["use_sets"]) != cfg.use_sets:
                    cfg = dataclasses.replace(cfg, use_sets=bool(rs["use_sets"]))
            n_new = p.ntrees - n_prior
            base_seed = p.seed if p.seed not in (-1, None) else 1234
            all_keys = _jit_keys(base_seed, p.ntrees)[n_prior:]
            # learn_rate_annealing: rate_i = annealing^i (GBM.java lr schedule);
            # indices continue across chunks and checkpoint restarts. DRF has no
            # learning rate at all — leaves are response means — so annealing is
            # forced off there like learn_rate itself.
            anneal = (1.0 if self.drf_mode
                      else float(getattr(p, "learn_rate_annealing", 1.0) or 1.0))
            all_rates = (anneal ** np.arange(n_prior, p.ntrees)
                         ).astype(np.float32)

            interval = p.score_tree_interval or n_new
            interval = min(interval, n_new)
            chunks = [(all_keys[i:i + interval],
                       jnp.asarray(all_rates[i:i + interval]))
                      for i in range(0, n_new, interval)]
            from jax.sharding import PartitionSpec as _Pspec

            # pipelined chunk dispatch: fold cadence scoring into the train
            # step (the score0-layout raw predictions come out of the
            # program that already holds the final margin), and donate the
            # carried margin's buffer across chunk dispatches. DRF keeps
            # standalone scoring (its cadence metrics are the OOB path's,
            # computed from the OOB accumulators).
            fused_score = not self.drf_mode
            score_fn = score_spec = None
            if fused_score:
                cfg = dataclasses.replace(cfg, fused_score=True)
                score_fn = _metrics_raw_fn(category, dist, self.drf_mode)
                score_spec = (_Pspec(ROWS) if category == "Regression"
                              else _Pspec(ROWS, None))
            # trees done after each chunk (the fused score's traced nt scalar)
            nd_after = []
            run = n_prior
            for keys_c, _rates_c in chunks:
                run += int(keys_c.shape[0])
                nd_after.append(run)
            # The compiled program depends on the CHUNK length (the scan is over
            # the per-chunk keys), never on the total tree count — keying the
            # train-fn cache on the interval makes a 10-tree warm-up compile serve
            # a 1000-tree run at the same scoring cadence.
            train_fn = make_train_fn(dataclasses.replace(cfg, ntrees=interval),
                                     grad_fn, mesh, cache_key=grad_key,
                                     score_fn=score_fn, score_spec=score_spec,
                                     donate=True)
            # pin the carried f to the trainer's OUTPUT sharding before the AOT
            # lower: chunk 0's freshly-broadcast f can come back replicated
            # (GSPMD's choice for a data-independent broadcast) while every
            # later chunk carries the P(ROWS)-sharded train output — an AOT
            # executable compiled for the former rejects the latter, and the
            # whole job silently pays the jitted fallback on a multi-shard mesh
            fspec = _Pspec(ROWS) if K == 1 else _Pspec(None, ROWS)
            f = put_sharded(f, fspec, mesh)

            def _step_args(ci, f_in):
                keys_c, rates_c = chunks[ci]
                args = (Xb, y_k, w, f_in, edges, edge_ok, keys_c, rates_c,
                        mono, imat, s.iscat_dev, s.nedges_dev)
                if fused_score:
                    args += (jnp.asarray(nd_after[ci], jnp.float32),)
                return args

        # AOT lower+compile the uniform-chunk step NOW (build setup), so the
        # chunk loop dispatches a prebuilt executable and the compile wall /
        # persistent-cache replay is measured at one attributable site
        train_step = None
        if chunks and grad_key is not None:
            aot_key = (dataclasses.replace(cfg, ntrees=interval), grad_key,
                       id(mesh))
            # a compile error surfaces HERE, once: the jitted twin would
            # hand the same program to the same compiler and fail again
            train_step = _aot_train_step(
                train_fn, _step_args(0, f), aot_key)

        output = ModelOutput()
        output.names = names
        output.domains = {n: fr.vec(n).domain for n in names}
        output.response_domain = list(resp_domain) if resp_domain else None
        output.model_category = category

        parts = list(prior_parts)
        history = []
        import time as _t

        from ..utils import failpoints

        stop_metric_series = []
        oob_sum = oob_cnt = None
        start_ci = 0
        if rs is not None and rs.get("chunks_done"):
            # restore the EXACT carried state: the remaining chunks then see
            # bit-identical inputs (keys/rates are indexed by global tree
            # number; Xb/edges rebuild deterministically from the frame), so
            # the resumed forest is bit-equal to the uninterrupted one
            start_ci = int(rs["chunks_done"])
            parts = [tuple(jnp.asarray(np.asarray(a)) for a in t)
                     for t in rs["parts"]]
            # restore to the trainer's output sharding (values, not
            # placement, carry parity — and matching the AOT executable's
            # compiled sharding keeps the prebuilt step usable on resume)
            f = put_sharded(np.asarray(rs["f"]), fspec, mesh)
            oob_sum = (None if rs.get("oob_sum") is None
                       else jnp.asarray(np.asarray(rs["oob_sum"])))
            oob_cnt = (None if rs.get("oob_cnt") is None
                       else jnp.asarray(np.asarray(rs["oob_cnt"])))
            history = list(rs["history"])
            stop_metric_series = list(rs["stop_series"])
        # dispatch-ahead engages when nothing at a boundary needs the
        # carried margin back on host: fused scoring supplies the metric
        # input, no early stopping / time budget / auto-recovery reads
        # in-flight state mid-sequence
        dispatch_ahead = (fused_score and len(chunks) > 1
                          and p.stopping_rounds <= 0
                          and not getattr(p, "max_runtime_secs", 0)
                          and not p.export_checkpoints_dir
                          and self._recovery is None)
        ahead = None
        # H2O_TPU_SANITIZE=recompiles: after the first boundary completes
        # (the model_base post-setup warmup: train step + boundary metric
        # programs all compiled) every later chunk dispatch is declared
        # steady — an uncached compile there raises typed. Only uniform
        # chunk plans declare it: a ragged tail chunk legitimately
        # compiles its own shape on first dispatch.
        uniform_chunks = len({len(k) for k, _ in chunks}) <= 1
        # bytes of level histogram one tree iteration hands to the psum,
        # from shapes (one shard reduces nothing): the counter
        # train.gbm.psum_bytes adds them at each chunk dispatch
        psum_tree = (K * hist_psum_bytes(cfg, len(names))
                     if n_row_shards(mesh) > 1 else 0)
        # the level histogram's plan, on every chunk span, and the one-hot
        # cells a tree iteration's level passes generate over all rows
        # (the counter train.gbm.hist_onehot_cells), both from shapes
        hist_plan = hist_plan_attrs(cfg, Xb.shape[0] // n_row_shards(mesh))
        onehot_tree = K * hist_onehot_cells(cfg, Xb.shape[0], len(names))
        steady = [False]
        for ci in range(start_ci, len(chunks)):
            keys, rates = chunks[ci]
            failpoints.hit("train.gbm.chunk")
            job.check_cancelled()
            if history and job.time_exceeded():  # keep the partial forest —
                break   # the first chunk ALWAYS trains (a budget that
                        # expires instantly still yields a usable 1-chunk
                        # model, the reference's max_runtime contract);
                        # callers with nothing partial to keep get the typed
                        # path via Job.check_max_runtime/join(timeout)
            # one span per score_tree_interval boundary: the chunk wall
            # (train_fn dispatch + metrics + checkpoint) is the number the
            # kernel-tuning ROADMAP items steer by; scoring below reads
            # metric values to host, so the wall is near-drained
            with telemetry.span("train.gbm.chunk",
                                metric="train.chunk.seconds",
                                chunk=ci, trees=int(len(keys)),
                                **hist_plan):
                def _dispatch(cj, f_in):
                    nonlocal train_step
                    import contextlib as _ctx

                    from ..utils import compilemeter, sanitizer
                    args = _step_args(cj, f_in)
                    if psum_tree:
                        telemetry.inc("train.gbm.psum_bytes",
                                      int(chunks[cj][0].shape[0]) * psum_tree)
                    telemetry.inc("train.gbm.hist_onehot_cells",
                                  int(chunks[cj][0].shape[0]) * onehot_tree)
                    use_aot = (train_step is not None
                               and chunks[cj][0].shape[0]
                               == len(chunks[0][0]))
                    # transfers: an implicit device->host sync inside the
                    # chunk dispatch raises typed; recompiles: once steady
                    # (post-first-boundary), an uncached compile raises
                    # typed — incl. the AOT-rejection jitted retrace below,
                    # which is exactly the mid-job resharding hazard the
                    # sanitizer exists to surface. Both no-ops when off.
                    # Fresh scope objects per entry: a @contextmanager
                    # cannot be re-entered on the fallback path.
                    def _scopes():
                        return (sanitizer.transfer_scope("train.gbm.chunk"),
                                compilemeter.no_compile_scope(
                                    "train.gbm.chunk") if steady[0]
                                else _ctx.nullcontext())

                    try:
                        t_sc, c_sc = _scopes()
                        with t_sc, c_sc:
                            return (train_step if use_aot
                                    else train_fn)(*args)
                    except (TypeError, ValueError):
                        if not use_aot:
                            raise
                        # the AOT executable is stricter than jit (it
                        # refuses argument shardings/layouts it was not
                        # lowered for — e.g. a resume-restored f placed
                        # differently); the jitted twin re-places and
                        # proceeds
                        from ..utils.log import warn

                        warn("AOT train step rejected its arguments "
                             "— jitted fallback for this job")
                        train_step = None
                        t_sc, c_sc = _scopes()
                        with t_sc, c_sc:
                            return train_fn(*args)

                outs = ahead if ahead is not None else _dispatch(ci, f)
                ahead = None
                if fused_score:
                    f, osum, ocnt, trees, mraw = outs
                else:
                    f, osum, ocnt, trees = outs
                    mraw = None
                if dispatch_ahead and ci + 1 < len(chunks):
                    # dispatch-ahead: enqueue the NEXT chunk's step before
                    # this boundary's metrics/history host work drains —
                    # the device trains chunk ci+1 while the host scores
                    # chunk ci. The margin passed on is DONATED — the
                    # rebind to None makes that explicit: any accidental
                    # read below this boundary fails loudly on None
                    # instead of "array has been deleted" at dispatch,
                    # graftlint rule donate-across-calls sees the
                    # *step_args donation through the call graph, and
                    # tests/test_pipeline.py pins the runtime behavior.
                    # (Fused scoring consumes mraw; the dispatch_ahead
                    # gate keeps every f-reading boundary consumer —
                    # recovery, export, stopping — out of this mode.)
                    ahead = _dispatch(ci + 1, f)
                    f = None
                # boundary scoring, from the step's outputs in hand to the
                # history row: reading the metrics back is the host's wait
                # for the chunk itself (and, dispatched ahead, for nothing
                # else), so this span names gaps and backs no metric
                with telemetry.span("train.gbm.score"):
                    oob_sum = osum if oob_sum is None else oob_sum + osum
                    oob_cnt = ocnt if oob_cnt is None else oob_cnt + ocnt
                    parts.append(trees)
                    ntrees_done = sum(t[0].shape[0] for t in parts)
                    # DRF scores OOB throughout (history + early stopping), so
                    # the stopping signal is honest, not in-bag memorization;
                    # OOB spans only this build's trees, hence the checkpoint
                    # gate below
                    m = None
                    if self.drf_mode and p.sample_rate < 1.0 and n_prior == 0:
                        m = self._oob_metrics(category, oob_sum, oob_cnt, y,
                                              ymask,
                                              w if p.weights_column else None,
                                              output.response_domain)
                        if m is not None:
                            m.description = "Reported on OOB data"
                    if m is None:
                        m = make_metrics(category, s.ym,
                                         mraw if mraw is not None else
                                         _metrics_raw(category, dist, f,
                                                      self.drf_mode,
                                                      ntrees_done),
                                         None if p.weights_column is None else w,
                                         auc_type=p.auc_type,
                                         domain=output.response_domain)
                    history.append({"timestamp": _t.time(),
                                    "number_of_trees": ntrees_done,
                                    "training_metrics": m})
                job.update(len(keys) / max(n_new, 1))
                if p.export_checkpoints_dir:
                    self._export_snapshot(p, output, parts, f0, dist, cfg,
                                          is_cat, ntrees_done, m,
                                          cat_nedges=s.nedges_np)
                # preemption-proof auto-checkpoint: capture the exact
                # carried state at this resumable boundary (written only
                # when the wall-clock interval knob says it's due)
                self._recovery_tick(
                    lambda ci=ci: {
                        "algo": self.algo_name, "chunks_done": ci + 1,
                        "n_prior": n_prior, "f0": f0,
                        "use_sets": bool(cfg.use_sets),
                        "parts": [tuple(t) for t in parts], "f": f,
                        "oob_sum": oob_sum, "oob_cnt": oob_cnt,
                        "history": list(history),
                        "stop_series": list(stop_metric_series)},
                    progress={"ntrees_done": int(ntrees_done),
                              "ntrees_total": int(p.ntrees)})
            telemetry.inc("train.chunk.count")
            # the first boundary IS the warmup boundary: the train step,
            # boundary metric programs, and (when fused) the score layout
            # all compiled above — from here every chunk dispatch is
            # declared steady for H2O_TPU_SANITIZE=recompiles
            steady[0] = uniform_chunks
            # flight-recorder drill window — AFTER the chunk completes, so
            # a raise@K drill bundles the drilled train's OWN progress
            # (chunk counters, history, margins), not pre-train state; the
            # injected fault is consumed, the loop continues
            from ..utils import flightrec

            flightrec.maybe_drill()
            if self._should_stop(m, stop_metric_series):
                break
        # from the last chunk's end to the model in the store
        with telemetry.span("train.gbm.finish"):
            output.scoring_history = history
            # DRF training metrics are the OOB metrics from the chunk loop above;
            # checkpoint continuations fall back to in-bag (prior trees' bags are
            # not recoverable, and one new tree's OOB would misrepresent the
            # whole forest)
            output.training_metrics = history[-1]["training_metrics"]

            forest = _assemble_forest(parts)
            # node covers for TreeSHAP are computed lazily on first
            # predict_contributions call (GBMModel._ensure_covers) — the routing
            # pass over all training rows is pure overhead for the common
            # train→predict path
            output.variable_importances = self._varimp(forest, names)
            model = GBMModel(p, output, forest, f0, dist, cfg, is_cat,
                             cat_nedges=s.nedges_np)
            if getattr(p, "calibrate_model", False):
                model.calib = self._fit_calibration(model, category)
            if p.validation_frame is not None:
                output.validation_metrics = model.model_performance(p.validation_frame)
        return model

    def _oob_metrics(self, category, osum, ocnt, y, ymask, w, domain=None):
        """Metrics over out-of-bag predictions: rows never out of bag (tiny
        forests) are excluded like the reference's OOB scorer."""
        seen = ocnt > 0
        if not bool(jnp.any(seen & ymask)):
            return None
        cnt = jnp.maximum(ocnt, 1.0)
        ym = jnp.where(ymask & seen, y, jnp.nan)
        if category == "Regression":
            raw = osum / cnt
        elif category == "Binomial":
            p1 = jnp.clip(osum / cnt, 0.0, 1.0)
            raw = jnp.stack([(p1 > 0.5).astype(jnp.float32), 1 - p1, p1],
                            axis=1)
        else:  # Multinomial: per-class sums (K, R)
            p = jnp.clip(osum / cnt[None, :], 1e-9, 1.0).T
            p = p / jnp.sum(p, axis=1, keepdims=True)
            label = jnp.argmax(p, axis=1).astype(jnp.float32)
            raw = jnp.concatenate([label[:, None], p], axis=1)
        return make_metrics(category, ym, raw, w,
                            auc_type=self.params.auc_type, domain=domain)

    def _fit_calibration(self, model, category):
        """Platt scaling on a holdout (`hex/tree/CalibrationHelper`): a 1-D
        logistic fit of the actuals against the model's margin."""
        p = self.params
        if category != "Binomial":
            raise ValueError("calibrate_model requires a binomial model")
        if p.calibration_frame is None:
            raise ValueError("calibrate_model requires calibration_frame")
        cf = p.calibration_frame
        X = model.adapt_frame(cf)
        f = model._raw_f(X)  # margin (or probability for DRF)
        if model.cfg.drf_mode:
            pc = jnp.clip(f, 1e-6, 1 - 1e-6)
            f = jnp.log(pc / (1 - pc))
        y = jnp.nan_to_num(cf.vec(p.response_column).data)
        wm = (~jnp.isnan(cf.vec(p.response_column).data)).astype(jnp.float32)

        # 2-parameter Newton iterations for sigmoid(a*f + b), on device
        ab = jnp.array([1.0, 0.0])
        for _ in range(25):
            eta = ab[0] * f + ab[1]
            mu = jax.nn.sigmoid(eta)
            g_eta = wm * (mu - y)
            h_eta = jnp.maximum(wm * mu * (1 - mu), 1e-10)
            g = jnp.array([jnp.sum(g_eta * f), jnp.sum(g_eta)])
            H = jnp.array([[jnp.sum(h_eta * f * f), jnp.sum(h_eta * f)],
                           [jnp.sum(h_eta * f), jnp.sum(h_eta)]])
            ab = ab - jnp.linalg.solve(H + 1e-8 * jnp.eye(2), g)
        return (float(ab[0]), float(ab[1]))

    @staticmethod
    def _resolve_checkpoint(cp) -> "GBMModel":
        from ..backend.kvstore import STORE

        prior = STORE.get(cp) if isinstance(cp, str) else cp
        if prior is None:
            raise ValueError(f"checkpoint model '{cp}' not found")
        return prior

    def _export_snapshot(self, p, output, parts, f0, dist, cfg, is_cat,
                         ntrees_done, metrics, cat_nedges=None):
        """In-training checkpoint to disk every scoring interval
        (`hex/tree/SharedTree.java:164,202-204,515` _in_training_checkpoints)."""
        import os

        from ..backend.kvstore import STORE
        from ..backend.persist import save_model

        forest = _assemble_forest(parts)
        snap_out = ModelOutput()
        snap_out.__dict__.update(output.__dict__)
        snap_out.training_metrics = metrics
        snap = GBMModel(p, snap_out, forest, f0, dist, cfg, is_cat,
                        key=f"{self.algo_name}_checkpoint_snapshot",
                        cat_nedges=cat_nedges)
        try:
            os.makedirs(p.export_checkpoints_dir, exist_ok=True)
            save_model(snap, os.path.join(
                p.export_checkpoints_dir,
                f"{self.algo_name}_{ntrees_done:05d}.bin"))
        finally:
            STORE.remove(snap.key, cascade=False)

    def _make_grad_fn(self, dist, K):
        if K == 1:
            if self.drf_mode:
                # DRF trees are independent fits at f=0: leaf = weighted mean(y)
                return lambda y, f, w: (-w * y, w)
            return lambda y, f, w: (dist.gradient(y, f, w), dist.hessian(y, f, w))

        def grad(y_k, f_k, w):
            # y_k (K, Rl) same codes broadcast; f_k (K, Rl)
            p = jax.nn.softmax(f_k, axis=0)
            y1h = (y_k == jnp.arange(K)[:, None]).astype(jnp.float32)
            if self.drf_mode:
                return -w * y1h, jnp.broadcast_to(w, y1h.shape)
            g = w * (p - y1h)
            h = jnp.maximum(w * p * (1 - p), 1e-10)
            return g, h

        return grad

    def _should_stop(self, m, series) -> bool:
        p = self.params
        if p.stopping_rounds <= 0:
            return False
        name = p.stopping_metric.upper()
        if name == "AUTO":
            name = {"Binomial": "LOGLOSS", "Multinomial": "LOGLOSS",
                    "Regression": "DEVIANCE"}.get(
                        getattr(m, "__class__", type(m)).__name__
                        .replace("ModelMetrics", ""), "DEVIANCE")
        val = {
            "LOGLOSS": getattr(m, "logloss", np.nan),
            "AUC": -getattr(m, "auc", np.nan),
            "MSE": m.mse, "RMSE": m.rmse, "DEVIANCE": m.mse,
            "MAE": getattr(m, "mae", np.nan),
        }.get(name, m.mse)
        series.append(val)
        k = p.stopping_rounds
        if len(series) <= k:
            return False
        best_recent = min(series[-k:])
        best_before = min(series[:-k])
        return best_recent > best_before * (1 - p.stopping_tolerance)

    def _varimp(self, forest, names):
        gains = np.asarray(forest["gain"])
        feats = np.asarray(forest["feat"])
        imp = np.zeros(len(names))
        np.add.at(imp, feats[feats >= 0].ravel(),
                  gains[feats >= 0].ravel())
        if imp.sum() <= 0:
            return None
        rel = imp / imp.max() if imp.max() > 0 else imp
        order = np.argsort(-imp)
        return {
            "variable": [names[i] for i in order],
            "relative_importance": imp[order],
            "scaled_importance": rel[order],
            "percentage": (imp / imp.sum())[order],
        }


@jax.jit
@telemetry.program("gbm_setup_init")
def _jit_full_like(y, f0):
    return jnp.full_like(y, f0, dtype=jnp.float32)


@functools.partial(jax.jit, static_argnames=("n",))
@telemetry.program("gbm_setup_keys")
def _jit_keys(seed, n: int):
    """PRNGKey + split in one program (eagerly: 2 programs + a slice)."""
    return jax.random.split(jax.random.PRNGKey(seed), n)


_PREP_CACHE: dict = {}


def _jit_prep(y_dev, w_in):
    """(y, ymask, w, ym) in ONE compiled program (eagerly these were ~6
    tiny programs, each paying the per-program cold cost)."""
    has_w = w_in is not None
    fn = _PREP_CACHE.get(has_w)
    if fn is None:
        @telemetry.program("gbm_setup_prep")
        def prep(y_dev, w_in):
            y = jnp.nan_to_num(y_dev)
            ymask = ~jnp.isnan(y_dev)
            base = w_in if has_w else jnp.ones_like(y, dtype=jnp.float32)
            w = base * ymask.astype(jnp.float32)
            ym = jnp.where(ymask, y, jnp.nan)  # metrics actuals, hoisted
            return y, ymask, w, ym
        fn = _PREP_CACHE.setdefault(has_w, jax.jit(prep))
    return fn(y_dev, w_in)


_INIT_F_CACHE: dict = {}


def _jit_init_f(drf_mode, K, dist, y, w):
    builtin = type(dist).__module__.endswith("models.distributions")
    # the closure captures the dist OBJECT, so every parameter its init_f
    # reads must pin the cache key (quantile's alpha; tweedie's power);
    # custom distribution objects bypass the cache entirely
    key = (drf_mode, K, getattr(dist, "name", None),
           getattr(dist, "alpha", None), getattr(dist, "p", None),
           getattr(dist, "power", None))
    fn = _INIT_F_CACHE.get(key) if builtin else None
    if fn is None:
        @telemetry.program("gbm_setup_init")
        def init(y, w):
            if drf_mode:
                return jnp.zeros((K,)) if K > 1 else jnp.array(0.0)
            if K > 1:
                counts = jnp.stack([jnp.sum(w * (y == k))
                                    for k in range(K)])
                pri = counts / jnp.maximum(jnp.sum(counts), 1e-10)
                return jnp.log(jnp.maximum(pri, 1e-10))
            return jnp.nan_to_num(dist.init_f(y, w))
        fn = jax.jit(init)
        if builtin:
            fn = _INIT_F_CACHE.setdefault(key, fn)
    return fn(y, w)


@jax.jit
@telemetry.program("gbm_setup_stack")
def _codes_to_f32(blk, na_code):
    """One replay block: int8/int16 bin codes -> f32 with the NA bucket
    restored to NaN (codes upcast to int32 first — the NA code can exceed
    the narrow dtype's range check otherwise)."""
    bi = blk.astype(jnp.int32)
    return jnp.where(bi == na_code, jnp.nan, bi.astype(jnp.float32))


def _prior_thr_codes(prior: "GBMModel", edges_np: np.ndarray):
    """Map a prior forest's split thresholds onto the CURRENT bin grid for
    code-space replay (`GBMModel._raw_f_codes`). Returns the code-space
    threshold array (forest thr shape, f32), or None when some numeric
    split threshold is not an edge value of the new grid — a continuation
    on different data or binning, where code-space routing would diverge;
    the caller then falls back to the stacked raw replay."""
    feat = np.asarray(prior.forest["feat"])
    thr = np.asarray(prior.forest["thr"], dtype=np.float32)
    internal = feat >= 0
    f_idx = np.clip(feat, 0, None)
    e = edges_np[f_idx]  # (..., E) per-node edge rows (NaN-padded)
    with np.errstate(invalid="ignore"):
        codes = np.sum(e < thr[..., None], axis=-1).astype(np.float32)
        on_grid = np.any(e == thr[..., None], axis=-1)
    needs_grid = internal
    if getattr(prior.cfg, "use_sets", False) and "catd" in prior.forest \
            and prior.is_cat is not None:
        # set-split nodes route through catd bitsets; their thr is never
        # compared, so an off-grid value there is irrelevant
        needs_grid = internal & ~np.asarray(prior.is_cat)[f_idx]
    if not bool(np.all(on_grid[needs_grid])):
        return None
    return codes


def _heap_path(node: int) -> str:
    """Heap index → root-to-leaf L/R path string ('' for the root)."""
    return "".join("R" if b == "1" else "L" for b in bin(node + 1)[3:])


def _assemble_forest(parts) -> dict:
    """Stack per-chunk tree arrays into the model's forest dict.

    catd widths may differ across chunks (a checkpoint prior built on data
    whose exact binning chose a different edge width, or whose categorical
    domains have since grown). Pad narrower tables on the right with each
    node's NA direction — a level landing in a bin the prior build never had
    is routed like missing, the engine's empty-bin/out-of-bitset rule."""
    out = {}
    for i, k in enumerate(("feat", "thr", "nanL", "val", "gain", "catd")):
        arrs = [t[i] for t in parts]
        if k == "catd":
            w = max(a.shape[-1] for a in arrs)
            padded = []
            for a, part in zip(arrs, parts):
                if a.shape[-1] < w:
                    na_right = 1.0 - jnp.asarray(part[2], jnp.float32)
                    ext = jnp.broadcast_to(na_right[..., None],
                                           a.shape[:-1]
                                           + (w - a.shape[-1],))
                    a = jnp.concatenate([a, ext], axis=-1)
                padded.append(a)
            arrs = padded
        out[k] = jnp.concatenate(arrs, axis=0)
    return out


def _interaction_matrix(names, groups) -> np.ndarray:
    """(F, F) may-interact matrix from interaction_constraints groups.
    Features in no group form implicit singletons (may only split alone) —
    `hex/tree/GlobalInteractionConstraints.java` semantics."""
    F = len(names)
    M = np.eye(F, dtype=bool)
    if not groups:
        return np.ones((F, F), dtype=bool)
    idx = {n: i for i, n in enumerate(names)}
    for grp in groups:
        if isinstance(grp, str) or not isinstance(grp, (list, tuple)):
            raise ValueError(
                "interaction_constraints must be a list of column-name "
                f"LISTS (e.g. [['a','b'],['c']]), got group {grp!r}")
        ids = []
        for col in grp:
            if col not in idx:
                raise ValueError(f"interaction_constraints column '{col}' is "
                                 f"not a feature")
            ids.append(idx[col])
        for a in ids:
            for b in ids:
                M[a, b] = True
    return M


#: cached jitted link->score0 conversions — the eager version cost one tiny
#: XLA program per op (exp/where/stack/...), each paying its own fixed
#: compile+load latency on a cold process
_METRICS_RAW_CACHE: dict = {}


def _metrics_raw_fn(category, dist, drf_mode):
    """The carried-link → score0-layout conversion as a pure function of
    (f, ntrees) — consumed by `_metrics_raw`'s standalone jitted program
    AND, under fused cadence scoring (cfg.fused_score), traced straight
    into the chunk train step so the margin never rematerializes."""
    @telemetry.program("gbm_score_raw")
    def raw(f, nt):
        if category == "Regression":
            # DRF carries the SUM of per-tree leaf means; the
            # prediction is the average (prediction path divides in
            # _raw_f — metrics must too)
            return f / nt if drf_mode else dist.linkinv(f)
        if category == "Binomial":
            p1 = (dist.linkinv(f) if not drf_mode
                  else jnp.clip(f / nt, 0, 1))
            return jnp.stack([(p1 > 0.5).astype(jnp.float32),
                              1 - p1, p1], axis=1)
        if drf_mode:
            p = jnp.clip(f.T / nt, 1e-9, 1.0)
            p = p / jnp.sum(p, axis=1, keepdims=True)
        else:
            p = jax.nn.softmax(f, axis=0).T
        label = jnp.argmax(p, axis=1).astype(jnp.float32)
        return jnp.concatenate([label[:, None], p], axis=1)

    return raw


def _metrics_raw(category, dist, f, drf_mode, ntrees):
    """Convert carried link predictions to the score0 output layout —
    ONE compiled program per (category, dist, drf) shape family; the tree
    count rides as a traced scalar so DRF chunks never recompile."""
    builtin = type(dist).__module__.endswith("models.distributions")
    key = (category, getattr(dist, "name", None), drf_mode)
    # only BUILTIN distributions cache (their behavior is pinned by name —
    # a user's custom object has no stable identity a value-key could
    # capture, and an id() key could alias a recycled address)
    fn = _METRICS_RAW_CACHE.get(key) if builtin else None
    if fn is None:
        fn = jax.jit(_metrics_raw_fn(category, dist, drf_mode))
        if builtin:
            fn = _METRICS_RAW_CACHE.setdefault(key, fn)
    return fn(f, jnp.float32(max(ntrees, 1)))
