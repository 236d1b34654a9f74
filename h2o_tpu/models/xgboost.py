"""XGBoost-equivalent — the `hex/tree/xgboost` parameter surface retargeted
onto our own histogram tree engine.

The reference wraps the native xgboost4j JNI build behind a full ModelBuilder
(`h2o-extensions/xgboost/src/main/java/hex/tree/xgboost/XGBoostModel.java:67,148`
— params eta/subsample/colsample_bytree/... aliased to the H2O names, backends
auto/CPU/GPU with `tree_method=gpu_hist`). Per SURVEY.md §7.6e the TPU rebuild
does NOT wrap native XGBoost; `tree_method=hist` semantics (global quantile
binning, Newton leaf values -G/(H+λ), L1 soft-thresholding via α) are exactly
what our shared tree engine implements, so this builder is a parameter-mapping
layer over it — the same relationship the reference has between its H2O-facing
params and the rabit workers, minus the JNI.

Supported param aliases (mirroring `XGBoostV3.XGBoostParametersV3`):
  ntrees/n_estimators, eta/learn_rate, max_depth, min_child_weight/min_rows,
  subsample/sample_rate, colsample_bytree/col_sample_rate_per_tree,
  colsample_bylevel/col_sample_rate, reg_lambda, reg_alpha, max_bins,
  booster (gbtree | dart — a real DART driver with rate_drop/skip_drop/
  one_drop/normalize_type incl. multinomial + checkpoint continuation, see
  `XGBoost._build_dart` | gblinear — the penalized linear model retargeted
  onto the GLM elastic-net path, see `XGBoost._build_gblinear`), tree_method
  (auto | hist: the engine IS hist), grow_policy (depthwise), max_leaves (0:
  no leaf cap) — any other value of the three raises at validation, it is
  not ignored — and backend (ignored: always TPU).

`min_child_weight` bounds a child's HESSIAN sum, as in XGBoost (the GBM's
`min_rows` bounds its row weight): `XGBoost._tree_config` sets
`TreeConfig.child_weight_hessian`.
"""

from __future__ import annotations

from dataclasses import dataclass

from .gbm import GBM, GBMParameters


@dataclass
class XGBoostParameters(GBMParameters):
    """Mirrors `hex/tree/xgboost/XGBoostModel.XGBoostParameters` field names.

    xgboost-default field overrides (the H2O-3 XGBoost documentation's:
    eta 0.3, max_depth 6, min_child_weight 1, max_bins 256, lambda 1,
    gamma 0) are declared as dataclass defaults, so that a builder with
    nothing set IS the documented model; the xgboost-native spellings below are
    sentinel-valued aliases that, when set, overwrite their H2O-named twin and
    then reset to the sentinel — so ``dataclasses.replace`` (clone / grid
    search) re-running ``__post_init__`` is a no-op and an explicitly-passed
    H2O-named value is never clobbered.
    """

    learn_rate: float = 0.3   # xgboost default eta
    max_depth: int = 6        # xgboost default max_depth
    nbins: int = 256          # xgboost default max_bins
    min_split_improvement: float = 0.0  # xgboost default gamma
    min_rows: float = 1.0     # xgboost default min_child_weight
    reg_lambda: float = 1.0   # xgboost default lambda
    reg_alpha: float = 0.0

    # DART booster knobs (`XGBoostParameters._rate_drop` et al.)
    rate_drop: float = 0.0
    skip_drop: float = 0.0
    one_drop: bool = False
    normalize_type: str = "tree"   # tree|forest

    # GBLinear booster knobs (`XGBoostParameters` gblinear section) —
    # feature_selector/top_k/updater tune xgboost's shotgun coordinate
    # descent; the GLM elastic-net retarget subsumes them (accepted, unused)
    feature_selector: str = "cyclic"
    top_k: int = 0
    updater: str | None = None

    # xgboost-native spellings; sentinel = "not set"
    n_estimators: int = 0          # alias of ntrees
    eta: float = -1.0              # alias of learn_rate
    min_child_weight: float = -1.0  # alias of min_rows
    subsample: float = -1.0        # alias of sample_rate
    colsample_bytree: float = -1.0  # alias of col_sample_rate_per_tree
    colsample_bylevel: float = -1.0  # alias of col_sample_rate
    max_bins: int = 0              # alias of nbins
    gamma: float = -1.0            # min split loss == min_split_improvement
    booster: str = "gbtree"
    tree_method: str = "hist"      # auto | hist
    grow_policy: str = "depthwise"  # the engine grows level by level
    max_leaves: int = 0            # 0 = no cap (lossguide's knob)
    backend: str = "auto"

    def __post_init__(self):
        # resolve aliases the way the reference resolves dual-named params,
        # then park the alias back at its sentinel (idempotent re-init)
        if self.n_estimators > 0:
            self.ntrees, self.n_estimators = self.n_estimators, 0
        if self.eta >= 0:
            self.learn_rate, self.eta = self.eta, -1.0
        if self.min_child_weight >= 0:
            self.min_rows, self.min_child_weight = self.min_child_weight, -1.0
        if self.subsample > 0:
            self.sample_rate, self.subsample = self.subsample, -1.0
        if self.colsample_bytree > 0:
            self.col_sample_rate_per_tree, self.colsample_bytree = \
                self.colsample_bytree, -1.0
        if self.colsample_bylevel > 0:
            self.col_sample_rate, self.colsample_bylevel = \
                self.colsample_bylevel, -1.0
        if self.max_bins > 0:
            self.nbins, self.max_bins = self.max_bins, 0
        if self.gamma >= 0:
            self.min_split_improvement, self.gamma = self.gamma, -1.0


class XGBoost(GBM):
    algo_name = "xgboost"

    def _validate(self):
        super()._validate()
        p = self.params
        # what the level-wise histogram engine does not implement raises
        # here; accepted and ignored, the model would not be the one asked
        for name, ok in (("tree_method", ("auto", "hist")),
                         ("grow_policy", ("depthwise",))):
            if (getattr(p, name) or ok[0]).lower() not in ok:
                raise ValueError(
                    f"xgboost: {name}='{getattr(p, name)}' is not "
                    f"implemented (supported: {', '.join(ok)})")
        if p.max_leaves:
            raise ValueError(
                f"xgboost: max_leaves={p.max_leaves} is not implemented "
                "(trees grow depthwise to max_depth; 0 = no leaf cap)")

    def _tree_config(self, K, nbins=None):
        import dataclasses
        cfg = super()._tree_config(K, nbins=nbins)
        return dataclasses.replace(cfg, reg_alpha=self.params.reg_alpha,
                                   child_weight_hessian=True)

    def build_impl(self, job):
        booster = (self.params.booster or "gbtree").lower()
        if booster == "dart":
            model = self._build_dart(job)
        elif booster == "gblinear":
            model = self._build_gblinear(job)
        else:
            model = super().build_impl(job)
        # the model object is engine-native (GBM/GLM class), but the wire
        # reports the builder's algo like the reference (`XGBoostV3` schema)
        model.algo_override = "xgboost"
        return model

    def _build_gblinear(self, job):
        """booster='gblinear' (`XGBoostModel.java:56,150`): xgboost's
        L1/L2-penalized LINEAR model (shotgun coordinate descent over the
        same reg_alpha/reg_lambda penalty). TPU-native retarget: the GLM
        elastic-net path — identical objective, solved by the engine's
        sharded-Gram IRLSM/COD instead of per-feature shotgun updates.
        xgboost's penalties are ABSOLUTE (not per-row-normalized):
        alpha·λ·N = reg_alpha and (1−alpha)·λ·N = reg_lambda fixes the
        (lambda, alpha) mapping."""
        from .glm import GLM, GLMParameters

        p = self.params
        fr = p.training_frame
        nrow = max(int(fr.nrow), 1)
        l1, l2 = max(p.reg_alpha, 0.0), max(p.reg_lambda, 0.0)
        tot = l1 + l2
        lam = tot / nrow
        alpha = (l1 / tot) if tot > 0 else 0.0
        _, category, _ = self.response_info()
        family = {"Binomial": "binomial", "Multinomial": "multinomial",
                  "Regression": "gaussian"}[category]
        gp = GLMParameters(
            training_frame=fr, response_column=p.response_column,
            validation_frame=p.validation_frame,
            weights_column=p.weights_column, offset_column=p.offset_column,
            ignored_columns=list(p.ignored_columns or []),
            family=family, alpha=alpha, lambda_=lam,
            solver="COORDINATE_DESCENT" if category != "Multinomial"
            else "IRLSM",
            standardize=False,  # xgboost's linear booster fits raw features
            max_iterations=max(p.ntrees, 50),  # boosting rounds ≙ sweeps
            seed=p.seed)
        sub = GLM(gp)
        model = sub.build_impl(job)
        model.booster = "gblinear"
        return model

    def _build_dart(self, job):
        """DART booster (Rashmi & Gilad-Bachrach 2015; xgboost `booster=
        dart`): each round drops a random subset of the existing trees,
        fits the new tree against the DROPPED ensemble's residuals, then
        renormalizes so the expected prediction is unchanged —
        normalize_type="tree": new-tree weight lr/(k+lr), dropped trees
        scaled k/(k+lr); "forest": lr/(1+lr) and 1/(1+lr). skip_drop
        short-circuits a round to plain boosting; one_drop forces at least
        one dropped tree. Predictions are linear in leaf values, so the
        final forest stores each tree's leaves pre-scaled by its weight —
        scoring, MOJO export and SHAP all work unchanged.

        The engine builds each round's tree at rate 1.0 with the carried
        margin = f0 + Σ_{i∉D} w_i·tree_i; the new tree's raw contribution
        falls out of the train step (f_out − f_in), so each round costs
        |D| single-tree evaluations plus one tree build.

        Multinomial drops whole ROUNDS (all K class-trees of a round share
        one weight — xgboost's dart drops by boosting round); checkpoint
        continuation restarts the dropout trajectory over the prior's BAKED
        trees (leaves already carry their weights, so prior trees enter with
        weight 1.0 and future drop-rescales just multiply their leaves)."""
        import dataclasses
        import time as _t

        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..backend.jobs import Job  # noqa: F401  (signature parity)
        from .gbm import GBMModel, _assemble_forest, _metrics_raw
        from .model_base import ModelOutput, make_metrics
        from .tree.engine import make_train_fn, predict_forest

        # DART re-evaluates dropped trees over raw thresholds every
        # iteration (dropped_sum below) — it keeps the stacked f32 matrix
        s = self._setup_build(need_raw=True)
        p = s.p
        K = s.K
        rng = np.random.default_rng(
            p.seed if p.seed not in (-1, None) else 1234)
        cfg = s.cfg
        f0 = s.f0

        parts, weights = [], []
        prior = None
        if p.checkpoint is not None:
            prior = self._resolve_checkpoint(p.checkpoint)
            if p.ntrees <= prior.ntrees:
                raise ValueError(
                    f"checkpoint model already has {prior.ntrees} trees; "
                    f"ntrees must exceed that (got {p.ntrees})")
            for fld, ours, theirs in (
                    ("max_depth", p.max_depth, prior.cfg.max_depth),
                    ("nbins", p.nbins,
                     getattr(prior.params, "nbins", prior.cfg.nbins)),
                    ("nclasses", K, prior.cfg.nclass),
                    ("drf_mode", False, prior.cfg.drf_mode)):
                if ours != theirs:
                    raise ValueError(
                        f"checkpoint incompatible: {fld} differs "
                        f"(checkpoint={theirs}, request={ours})")
            p = self.params = dataclasses.replace(p, checkpoint=prior.key)
            # continuation trees speak the prior forest's split language
            prior_sets = bool(getattr(prior.cfg, "use_sets", False))
            if cfg.use_sets != prior_sets:
                cfg = dataclasses.replace(cfg, use_sets=prior_sets)
            pf = prior.forest
            keysx = ("feat", "thr", "nanL", "val", "gain", "catd")
            for t in range(prior.ntrees):
                parts.append(tuple(
                    (pf[k][t:t + 1] if k in pf else
                     jnp.zeros(pf["feat"][t:t + 1].shape + (1,),
                               jnp.float32)) for k in keysx))
                weights.append(1.0)
            f0 = prior.f0

        # trees build UNSCALED (engine's effective rate = cfg.learn_rate x
        # per-tree rate; DART owns the scaling via the weight vector), and
        # unclipped: max_abs_leafnode_pred caps the FINAL stored leaf, so
        # the clip applies at weight-bake time below (GBM.java:716 parity)
        cfg1 = dataclasses.replace(cfg, ntrees=1, learn_rate=1.0,
                                   max_abs_leafnode_pred=float("inf"))
        train_fn = make_train_fn(cfg1, s.grad_fn, s.mesh,
                                 cache_key=s.grad_key)
        keys = jax.random.split(
            jax.random.PRNGKey(p.seed if p.seed not in (-1, None)
                               else 1234), p.ntrees)
        one_rate = jnp.ones((1,), dtype=jnp.float32)

        lr = float(p.learn_rate)
        # f0 broadcast to the carried-margin shape ((K, R) for multinomial)
        f0b = (jnp.asarray(f0)[:, None] if K > 1
               else jnp.asarray(f0, jnp.float32))
        S = jnp.zeros_like(s.f)        # sum of w_i * raw_i over built trees
        if prior is not None:
            fprev = prior._raw_f(s.X)
            S = (fprev.T if K > 1 else fprev).astype(jnp.float32) - f0b
        history = []
        stop_series: list = []
        interval = min(p.score_tree_interval or p.ntrees, p.ntrees)
        last_scored = n_prior = len(parts)

        use_sets = cfg.use_sets

        def dropped_sum(idxs):
            """sum_{i in D} w_i * raw_i in ONE forest evaluation: stack the
            dropped trees with their weights pre-multiplied into the leaves
            — O(1) extra memory, no per-tree prediction cache. Multinomial
            trees carry a (D, K, N) class axis; the (R, K) output transposes
            onto the carried-margin layout."""
            feat = jnp.concatenate([parts[i][0] for i in idxs], axis=0)
            thr = jnp.concatenate([parts[i][1] for i in idxs], axis=0)
            nanL = jnp.concatenate([parts[i][2] for i in idxs], axis=0)
            val = jnp.concatenate(
                [jnp.asarray(parts[i][3]) * jnp.float32(weights[i])
                 for i in idxs], axis=0)
            catd = (jnp.concatenate([parts[i][5] for i in idxs], axis=0)
                    if use_sets else None)
            out = predict_forest(s.X, feat, thr, nanL, val,
                                 cfg.max_depth, catd=catd,
                                 iscat=s.iscat_dev if use_sets else None,
                                 nedges=s.nedges_dev if use_sets else None)
            return out.T if K > 1 else out

        output = ModelOutput()
        output.names = list(s.names)
        output.domains = {n: s.fr.vec(n).domain for n in s.names}
        output.response_domain = (list(s.resp_domain) if s.resp_domain
                                  else None)
        output.model_category = s.category

        def bake(parts_w):
            # bake each tree's DART weight into its stored leaf values; the
            # max_abs_leafnode_pred cap applies on the FINAL stored leaf
            # (the reference clips after the effective rate,
            # GBM.java:716-719)
            cap = float(getattr(p, "max_abs_leafnode_pred", float("inf"))
                        or float("inf"))
            out = []
            for (feat, thr, nanL, val, gain, catd), wgt in parts_w:
                v = jnp.asarray(val) * jnp.float32(wgt)
                if np.isfinite(cap):
                    v = jnp.clip(v, -cap, cap)
                out.append((feat, thr, nanL, v, gain, catd))
            return out

        for t in range(n_prior, p.ntrees):
            job.check_cancelled()
            if history and job.time_exceeded():  # keep the partial forest
                break
            dropped: list[int] = []
            if t > 0 and rng.random() >= p.skip_drop:
                dropped = [i for i in range(t)
                           if rng.random() < p.rate_drop]
                if not dropped and p.one_drop:
                    dropped = [int(rng.integers(t))]
            if dropped:
                drop_raw = dropped_sum(dropped)
                margin = f0b + S - drop_raw
            else:
                drop_raw = None
                margin = f0b + S
            f_out, _os, _oc, trees = train_fn(
                s.Xb, s.y_k, s.w, margin.astype(jnp.float32), s.edges,
                s.edge_ok, keys[t:t + 1], one_rate, s.mono, s.imat,
                s.iscat_dev, s.nedges_dev)
            raw_new = f_out - margin
            k = len(dropped)
            if k == 0:
                w_new, scale_dropped = lr, 1.0
            elif (p.normalize_type or "tree").lower() == "forest":
                w_new, scale_dropped = lr / (1.0 + lr), 1.0 / (1.0 + lr)
            else:
                w_new = lr / (k + lr)
                scale_dropped = k / (k + lr)
            if dropped:
                # S' = S + (scale-1) * sum w_i raw_i — the dropped sum is
                # already in hand, no re-evaluation
                S = S + (scale_dropped - 1.0) * drop_raw
                for i in dropped:
                    weights[i] *= scale_dropped
            S = S + w_new * raw_new
            parts.append(trees)
            weights.append(w_new)
            if (t + 1) % interval == 0 or t + 1 == p.ntrees:
                m = make_metrics(
                    s.category, s.ym,
                    _metrics_raw(s.category, s.dist, f0b + S,
                                 False, t + 1),
                    None if p.weights_column is None else s.w,
                    auc_type=p.auc_type, domain=output.response_domain)
                history.append({"timestamp": _t.time(),
                                "number_of_trees": t + 1,
                                "training_metrics": m})
                # incremental progress by trees actually elapsed since the
                # last update (the final round may be shorter than interval)
                job.update((t + 1 - last_scored) / p.ntrees)
                last_scored = t + 1
                if p.export_checkpoints_dir:
                    # in-training snapshots carry the CURRENT weights baked
                    self._export_snapshot(
                        p, output, bake(zip(parts, weights)), f0, s.dist,
                        cfg, s.is_cat, t + 1, m, cat_nedges=s.nedges_np)
                if self._should_stop(m, stop_series):
                    break

        scaled = bake(zip(parts, weights))
        output.scoring_history = history
        output.training_metrics = history[-1]["training_metrics"]
        forest = _assemble_forest(scaled)
        output.variable_importances = self._varimp(forest, s.names)
        model = GBMModel(p, output, forest, f0, s.dist, cfg, s.is_cat,
                         cat_nedges=s.nedges_np)
        if getattr(p, "calibrate_model", False):
            # same Platt step as the gbtree path — leaves are already baked,
            # so the margin the calibrator sees is the final DART margin
            model.calib = self._fit_calibration(model, s.category)
        if p.validation_frame is not None:
            output.validation_metrics = model.model_performance(
                p.validation_frame)
        return model

