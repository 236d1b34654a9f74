"""Model metrics — analog of `hex/ModelMetrics*.java` + `hex/AUC2.java` (684 LoC)
+ `hex/ConfusionMatrix.java` / `hex/GainsLift.java`.

The reference builds metrics incrementally inside scoring MRTasks
(`MetricBuilder.perRow/reduce`, `hex/Model.java:2232` BigScore). Here each
metric family is ONE fused jitted reduction over the sharded prediction /
response arrays — XLA's all-reduce replaces the builder merge tree.

AUC follows the `hex/AUC2.java` design: a fixed-size threshold histogram
(reference: 400 bins of candidate thresholds; here 1024 uniform probability
bins, device-friendly) accumulating TP/FP counts, then trapezoidal integration
and threshold-criterion maximization (F1, accuracy, MCC...) over the bins.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import telemetry

NBINS = 1024


# ---------------------------------------------------------------------------
# device kernels
# ---------------------------------------------------------------------------
@jax.jit
@telemetry.program("metrics_regression")
def _regression_kernel(y, pred, w):
    n = jnp.sum(w)
    err = pred - y
    mse = jnp.sum(w * err * err) / n
    mae = jnp.sum(w * jnp.abs(err)) / n
    ybar = jnp.sum(w * y) / n
    ss_tot = jnp.sum(w * (y - ybar) ** 2) / n
    ok_log = (y > -1) & (pred > -1)
    rmsle2 = jnp.sum(jnp.where(ok_log, w * (jnp.log1p(pred) - jnp.log1p(y)) ** 2, 0.0)) \
        / jnp.maximum(jnp.sum(jnp.where(ok_log, w, 0.0)), 1e-10)
    return dict(n=n, mse=mse, mae=mae, ss_tot=ss_tot, rmsle2=rmsle2,
                mean_residual=jnp.sum(w * err) / n)


@jax.jit
@telemetry.program("metrics_binomial")
def _binomial_hist_kernel(y, p, w):
    """Per-bin {TP,FP} histogram over NBINS probability thresholds + logloss."""
    pc = jnp.clip(p, 1e-15, 1 - 1e-15)
    logloss = jnp.sum(-w * (y * jnp.log(pc) + (1 - y) * jnp.log(1 - pc)))
    n = jnp.sum(w)
    bins = jnp.clip((p * NBINS).astype(jnp.int32), 0, NBINS - 1)
    onehot = jax.nn.one_hot(bins, NBINS, dtype=jnp.float32)
    pos_hist = onehot.T @ (w * y)
    neg_hist = onehot.T @ (w * (1 - y))
    err = p - y
    mse = jnp.sum(w * err * err)
    return dict(pos=pos_hist, neg=neg_hist, logloss=logloss, n=n, mse=mse,
                npos=jnp.sum(w * y), nneg=jnp.sum(w * (1 - y)))


@jax.jit
@telemetry.program("metrics_multinomial")
def _multinomial_kernel(y, probs, w):
    """logloss + confusion matrix + hit-ratio table for K classes."""
    k = probs.shape[1]
    yi = y.astype(jnp.int32)
    py = jnp.clip(jnp.take_along_axis(probs, yi[:, None], axis=1)[:, 0], 1e-15, 1.0)
    logloss = jnp.sum(-w * jnp.log(py))
    pred = jnp.argmax(probs, axis=1)
    cm = (jax.nn.one_hot(yi, k, dtype=jnp.float32) * w[:, None]).T @ \
        jax.nn.one_hot(pred, k, dtype=jnp.float32)
    # hit ratios: is the true class within the top-j predictions?
    order = jnp.argsort(-probs, axis=1)
    hit_at = jnp.cumsum(order == yi[:, None], axis=1)
    hits = jnp.sum(w[:, None] * hit_at, axis=0)
    err1h = jax.nn.one_hot(yi, k, dtype=jnp.float32)
    mse = jnp.sum(w * jnp.sum((probs - err1h) ** 2, axis=1))
    return dict(logloss=logloss, cm=cm, hits=hits, n=jnp.sum(w), mse=mse)


# ---------------------------------------------------------------------------
# host-side metric objects
# ---------------------------------------------------------------------------
@dataclass
class ModelMetrics:
    """Base — mirrors `hex/ModelMetrics.java` fields."""

    mse: float = np.nan
    rmse: float = np.nan
    nobs: int = 0
    description: str = ""

    def _fmt(self, pairs):
        return "\n".join(f"{k}: {v}" for k, v in pairs)


@dataclass
class ModelMetricsRegression(ModelMetrics):
    mae: float = np.nan
    rmsle: float = np.nan
    r2: float = np.nan
    mean_residual_deviance: float = np.nan

    def __repr__(self):
        return self._fmt([("MSE", self.mse), ("RMSE", self.rmse), ("MAE", self.mae),
                          ("RMSLE", self.rmsle), ("R^2", self.r2),
                          ("Mean Residual Deviance", self.mean_residual_deviance)])


@dataclass
class ModelMetricsBinomial(ModelMetrics):
    auc: float = np.nan
    pr_auc: float = np.nan
    gini: float = np.nan
    logloss: float = np.nan
    mean_per_class_error: float = np.nan
    ks: float = np.nan
    max_f1: float = np.nan
    max_f1_threshold: float = np.nan
    confusion_matrix: Any = None  # 2x2 [[tn, fp], [fn, tp]] at max-F1 threshold
    thresholds_and_metric_scores: Any = None
    max_criteria_and_metric_scores: Any = None   # TwoDimTable
    gains_lift_table: Any = None                 # TwoDimTable

    # `hex/AUC2.java` ThresholdCriterion surface
    def find_threshold_by_max_metric(self, metric: str) -> float:
        t = self.thresholds_and_metric_scores
        i = int(np.nanargmax(t[metric]))
        return float(t["thresholds"][i])

    def metric_at_threshold(self, metric: str, threshold: float) -> float:
        t = self.thresholds_and_metric_scores
        i = int(np.argmin(np.abs(t["thresholds"] - threshold)))
        return float(t[metric][i])

    def confusion_matrix_at(self, threshold: float):
        t = self.thresholds_and_metric_scores
        i = int(np.argmin(np.abs(t["thresholds"] - threshold)))
        return np.array([[t["tns"][i], t["fps"][i]], [t["fns"][i], t["tps"][i]]])

    def __repr__(self):
        return self._fmt([("AUC", self.auc), ("pr_auc", self.pr_auc),
                          ("LogLoss", self.logloss), ("Gini", self.gini),
                          ("KS", self.ks),
                          ("MSE", self.mse), ("RMSE", self.rmse),
                          ("mean_per_class_error", self.mean_per_class_error),
                          ("max F1", f"{self.max_f1} @ {self.max_f1_threshold}")])


@dataclass
class ModelMetricsMultinomial(ModelMetrics):
    logloss: float = np.nan
    mean_per_class_error: float = np.nan
    confusion_matrix: Any = None
    hit_ratio_table: Any = None
    # `hex/MultinomialAUC.java` surface: populated when auc_type != AUTO/NONE
    auc: float = np.nan
    pr_auc: float = np.nan
    auc_type: str = "none"
    _mauc: Any = None                      # MultinomialAUC (all aggregates)

    @property
    def multinomial_auc_table(self):       # lazy: scoring-history snapshots
        return self._mauc.table(pr=False) if self._mauc else None

    @property
    def multinomial_aucpr_table(self):     # only ever read the scalar
        return self._mauc.table(pr=True) if self._mauc else None

    def auc_by_type(self, auc_type: str) -> float:
        """Any aggregate on demand (`MultinomialAUC.getAucTable` accessors)."""
        return self._mauc.get(auc_type, pr=False) if self._mauc else np.nan

    def pr_auc_by_type(self, auc_type: str) -> float:
        return self._mauc.get(auc_type, pr=True) if self._mauc else np.nan

    def __repr__(self):
        pairs = [("LogLoss", self.logloss), ("MSE", self.mse),
                 ("mean_per_class_error", self.mean_per_class_error)]
        if not np.isnan(self.auc):
            pairs += [("AUC", f"{self.auc} ({self.auc_type})"),
                      ("pr_auc", self.pr_auc)]
        return self._fmt(pairs)


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------
def make_regression_metrics(y, pred, weights=None) -> ModelMetricsRegression:
    """y/pred: padded sharded arrays (NaN padding); weights optional."""
    r = jax.device_get(_fused_metric_kernel(
        y, pred, weights if weights is not None else y,
        _regression_kernel, weights is not None))
    mse = float(r["mse"])
    ss_tot = float(r["ss_tot"])
    return ModelMetricsRegression(
        mse=mse, rmse=float(np.sqrt(mse)), nobs=int(r["n"]), mae=float(r["mae"]),
        rmsle=float(np.sqrt(max(r["rmsle2"], 0))),
        r2=1.0 - mse / ss_tot if ss_tot > 0 else np.nan,
        mean_residual_deviance=mse,
    )


def make_binomial_metrics(y, p, weights=None) -> ModelMetricsBinomial:
    """y in {0,1} (padded NaN), p = P(class 1)."""
    r = jax.device_get(_fused_metric_kernel(
        y, p, weights if weights is not None else y,
        _binomial_hist_kernel, weights is not None))
    pos, neg = r["pos"], r["neg"]
    npos, nneg = float(r["npos"]), float(r["nneg"])
    n = float(r["n"])
    # Cumulative from the top bin down: predictions >= threshold are "positive".
    tp = np.cumsum(pos[::-1])[::-1]
    fp = np.cumsum(neg[::-1])[::-1]
    tn = nneg - fp
    fn = npos - tp
    tpr = tp / max(npos, 1e-10)
    fpr = fp / max(nneg, 1e-10)
    # append the (0,0) endpoint; prepend (1,1) is bin 0 cumulative
    tpr_full = np.concatenate([tpr, [0.0]])
    fpr_full = np.concatenate([fpr, [0.0]])
    auc = float(-np.trapezoid(tpr_full, fpr_full))
    precision = tp / np.maximum(tp + fp, 1e-10)
    recall = tpr
    specificity = tn / max(nneg, 1e-10)
    order = np.argsort(recall)
    pr_auc = float(np.trapezoid(precision[order], recall[order]))
    # `hex/AUC2.java` ThresholdCriterion family over every threshold bin.
    f1 = 2 * precision * recall / np.maximum(precision + recall, 1e-10)
    f2 = 5 * precision * recall / np.maximum(4 * precision + recall, 1e-10)
    f0point5 = 1.25 * precision * recall / np.maximum(0.25 * precision + recall, 1e-10)
    accuracy = (tp + tn) / max(n, 1e-10)
    mcc_den = np.sqrt(np.maximum((tp + fp) * (tp + fn) * (tn + fp) * (tn + fn), 1e-10))
    absolute_mcc = np.abs((tp * tn - fp * fn) / mcc_den)
    min_per_class_accuracy = np.minimum(tpr, specificity)
    mean_per_class_accuracy = 0.5 * (tpr + specificity)
    best = int(np.argmax(f1))
    thr = best / NBINS
    cm = np.array([[tn[best], fp[best]], [fn[best], tp[best]]])
    mpce = 0.5 * (fp[best] / max(nneg, 1e-10) + fn[best] / max(npos, 1e-10))
    mse = float(r["mse"]) / max(n, 1e-10)
    thresholds = np.arange(NBINS) / NBINS
    scores = dict(
        thresholds=thresholds, f1=f1, f2=f2, f0point5=f0point5,
        accuracy=accuracy, precision=precision, recall=recall, tpr=tpr,
        fpr=fpr, specificity=specificity, absolute_mcc=absolute_mcc,
        min_per_class_accuracy=min_per_class_accuracy,
        mean_per_class_accuracy=mean_per_class_accuracy,
        tps=tp, fps=fp, tns=tn, fns=fn)
    return ModelMetricsBinomial(
        mse=mse, rmse=float(np.sqrt(mse)), nobs=int(n),
        auc=auc, pr_auc=pr_auc, gini=2 * auc - 1,
        logloss=float(r["logloss"]) / max(n, 1e-10),
        mean_per_class_error=float(mpce),
        ks=float(np.max(tpr - fpr)),
        max_f1=float(f1[best]), max_f1_threshold=thr,
        confusion_matrix=cm,
        thresholds_and_metric_scores=scores,
        max_criteria_and_metric_scores=_max_criteria_table(scores),
        gains_lift_table=_gains_lift(pos, neg, npos, n),
    )


_MAX_CRITERIA = ("f1", "f2", "f0point5", "accuracy", "precision", "recall",
                 "specificity", "absolute_mcc", "min_per_class_accuracy",
                 "mean_per_class_accuracy")


def _max_criteria_table(scores):
    """`hex/AUC2.java` maxCriteria table: best value + threshold per criterion."""
    from ..utils.twodimtable import TwoDimTable
    rows = []
    for crit in _MAX_CRITERIA:
        v = scores[crit]
        i = int(np.nanargmax(v))
        rows.append([f"max {crit}", float(scores["thresholds"][i]),
                     float(v[i]), i])
    return TwoDimTable(
        table_header="Maximum Metrics", description="Maximum metrics at their respective thresholds",
        col_header=["metric", "threshold", "value", "idx"],
        col_types=["string", "double", "double", "long"], cell_values=rows)


def _gains_lift(pos, neg, npos, n, groups: int = 16):
    """`hex/GainsLift.java`: quantile groups of predicted probability (top
    first), capture/response rates and lift, from the same threshold histogram
    the AUC uses (reference uses exact quantiles of the prediction column)."""
    from ..utils.twodimtable import TwoDimTable
    if npos <= 0 or n <= 0:
        return None
    tot = pos + neg                      # per-bin weighted counts
    # walk bins from the top prob down, cutting a group at each n/groups
    cum = np.cumsum(tot[::-1])           # cumulative rows from top
    cum_pos = np.cumsum(pos[::-1])
    targets = n * (np.arange(1, groups + 1) / groups)
    idx = np.searchsorted(cum, targets - 1e-9)
    idx = np.minimum(idx, len(cum) - 1)
    rows, prev_rows, prev_pos = [], 0.0, 0.0
    overall_rate = npos / n
    for g in range(groups):
        c_rows, c_pos = float(cum[idx[g]]), float(cum_pos[idx[g]])
        g_rows, g_pos = c_rows - prev_rows, c_pos - prev_pos
        if g_rows <= 0:
            prev_rows, prev_pos = c_rows, c_pos
            continue
        lower_thr = 1.0 - (idx[g] + 1) / NBINS
        resp_rate = g_pos / g_rows
        cum_resp_rate = c_pos / c_rows
        lift = resp_rate / overall_rate
        cum_lift = cum_resp_rate / overall_rate
        rows.append([g + 1, c_rows / n, lower_thr, resp_rate, cum_resp_rate,
                     g_pos / npos, c_pos / npos, lift, cum_lift,
                     100.0 * (lift - 1), 100.0 * (cum_lift - 1)])
        prev_rows, prev_pos = c_rows, c_pos
    return TwoDimTable(
        table_header="Gains/Lift Table", description="Avg response rate: %5.2f %%" % (100 * overall_rate),
        col_header=["group", "cumulative_data_fraction", "lower_threshold",
                    "response_rate", "cumulative_response_rate",
                    "capture_rate", "cumulative_capture_rate", "lift",
                    "cumulative_lift", "gain", "cumulative_gain"],
        col_types=["long"] + ["double"] * 10, cell_values=rows)


# ---------------------------------------------------------------------------
# Multinomial AUC (`hex/MultinomialAUC.java:1-319` + `hex/PairwiseAUC.java`)
#
# The reference builds per-class / per-pair AUC2 threshold histograms. Here
# the whole family — every directed ROC-AUC numerator and every average-
# precision value — comes from ONE jitted pass: per class k, sort prob_k once
# and carry the (rows, K) per-true-class weight matrix through cumulative
# sums; tie groups are resolved exactly via searchsorted edges, so the
# result is the exact rank-statistic AUC (matches sklearn), not a binned
# approximation.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("K",))
@telemetry.program("metrics_mauc")
def _mauc_kernel(y, probs, w, K):
    yi = y.astype(jnp.int32)
    W = jax.nn.one_hot(yi, K, dtype=jnp.float32) * w[:, None]    # (n, K)
    N = jnp.sum(W, axis=0)                                        # (K,)

    def per_class(k):
        pk = jax.lax.dynamic_index_in_dim(probs, k, axis=1, keepdims=False)
        order = jnp.argsort(pk)
        ps = pk[order]
        Ws = W[order]                                             # (n, K)
        cum = jnp.cumsum(Ws, axis=0)                              # inclusive
        left = jnp.searchsorted(ps, ps, side="left")
        right = jnp.searchsorted(ps, ps, side="right")
        # per-class weight strictly below / tied-with each row's value
        before = jnp.where((left > 0)[:, None],
                           cum[jnp.maximum(left - 1, 0)], 0.0)
        tied = cum[right - 1] - before
        wpos = jax.lax.dynamic_index_in_dim(Ws, k, axis=1, keepdims=False)
        # directed ROC numerator vs every negative class (ties count 1/2)
        s_roc = jnp.sum(wpos[:, None] * (before + 0.5 * tied), axis=0)
        # average precision: descending tie-group-END cumulatives are
        # N_c - (strictly below) — one row term per distinct threshold group
        nk = jax.lax.dynamic_index_in_dim(N, k, keepdims=False)
        tp_end = nk - jax.lax.dynamic_index_in_dim(before, k, axis=1,
                                                   keepdims=False)
        fp_end = N[None, :] - before                              # (n, K)
        contrib = wpos / jnp.maximum(nk, 1e-10)
        ap_pair = jnp.sum(contrib[:, None] * tp_end[:, None]
                          / jnp.maximum(tp_end[:, None] + fp_end, 1e-10),
                          axis=0)
        fp_ovr = jnp.sum(fp_end, axis=1) - tp_end
        ap_ovr = jnp.sum(contrib * tp_end
                         / jnp.maximum(tp_end + fp_ovr, 1e-10))
        return s_roc, ap_pair, ap_ovr

    s_roc, ap_pair, ap_ovr = jax.lax.map(per_class, jnp.arange(K))
    return dict(s_roc=s_roc, ap_pair=ap_pair, ap_ovr=ap_ovr, N=N)


_AUC_TYPES = ("macro_ovr", "weighted_ovr", "macro_ovo", "weighted_ovo")


class MultinomialAUC:
    """Host aggregation of the kernel stats — all `auc_type` aggregates.

    OVO pairwise AUC is the average of the two directed AUCs
    (`hex/PairwiseAUC.java` getAuc); WEIGHTED_OVO pair weights are
    (N_i + N_j) / ((K-1)·N) (`MultinomialAUC.java` computeWeightedOVO).
    """

    def __init__(self, s_roc, ap_pair, ap_ovr, N, domain=None):
        K = len(N)
        self.K = K
        self.N = N
        self.domain = (list(domain) if domain is not None
                       else [str(i) for i in range(K)])
        ntot = N.sum()
        nneg = ntot - N
        with np.errstate(divide="ignore", invalid="ignore"):
            self.auc_ovr = s_roc.sum(axis=1) - np.diag(s_roc)
            self.auc_ovr = np.where(N * nneg > 0,
                                    self.auc_ovr / np.maximum(N * nneg, 1e-30),
                                    np.nan)
            denom = N[:, None] * N[None, :]
            auc_dir = np.where(denom > 0, s_roc / np.maximum(denom, 1e-30),
                               np.nan)
        self.auc_pair = 0.5 * (auc_dir + auc_dir.T)       # symmetric OVO
        self.ap_ovr = ap_ovr
        self.ap_pair_sym = 0.5 * (ap_pair + ap_pair.T)
        prev = N / max(ntot, 1e-30)
        iu = np.triu_indices(K, 1)
        pair_w = (N[iu[0]] + N[iu[1]]) / max((K - 1) * ntot, 1e-30)
        self._agg = {}
        for pr, ovr, pair in ((False, self.auc_ovr, self.auc_pair),
                              (True, self.ap_ovr, self.ap_pair_sym)):
            vals = pair[iu]
            self._agg[("macro_ovr", pr)] = float(np.nanmean(ovr))
            self._agg[("weighted_ovr", pr)] = float(np.nansum(prev * ovr))
            self._agg[("macro_ovo", pr)] = float(np.nanmean(vals))
            self._agg[("weighted_ovo", pr)] = float(np.nansum(pair_w * vals))
        self._iu = iu

    def get(self, auc_type: str, pr: bool = False) -> float:
        t = auc_type.lower()
        if t in ("auto", "none"):
            return np.nan
        if t not in _AUC_TYPES:
            raise ValueError(f"unknown auc_type '{auc_type}' "
                             f"(one of {_AUC_TYPES})")
        return self._agg[(t, pr)]

    def table(self, pr: bool = False):
        """One TwoDimTable with OVR rows, OVO rows and the four aggregates —
        the `MultinomialAUC.getTable` publication."""
        from ..utils.twodimtable import TwoDimTable

        ovr = self.ap_ovr if pr else self.auc_ovr
        pair = self.ap_pair_sym if pr else self.auc_pair
        rows = []
        for k in range(self.K):
            rows.append([f"{self.domain[k]} vs Rest", float(ovr[k])])
        for i, j in zip(*self._iu):
            rows.append([f"{self.domain[i]} vs {self.domain[j]}",
                         float(pair[i, j])])
        for t in _AUC_TYPES:
            rows.append([t, self._agg[(t, pr)]])
        name = "PR AUC" if pr else "AUC"
        return TwoDimTable(
            table_header=f"Multinomial {name} values",
            description="One-vs-Rest, One-vs-One and aggregated "
                        f"{name} (`hex/MultinomialAUC.java`)",
            col_header=["auc_kind", name.lower().replace(" ", "_")],
            col_types=["string", "double"], cell_values=rows)


def make_multinomial_auc(y, probs, weights=None, domain=None) -> MultinomialAUC:
    K = int(probs.shape[1])
    w = _weights(y, weights)
    r = jax.device_get(_mauc_kernel(jnp.nan_to_num(y), jnp.nan_to_num(probs),
                                    w, K))
    return MultinomialAUC(np.asarray(r["s_roc"], np.float64),
                          np.asarray(r["ap_pair"], np.float64),
                          np.asarray(r["ap_ovr"], np.float64),
                          np.asarray(r["N"], np.float64), domain)


def make_multinomial_metrics(y, probs, weights=None, auc_type: str = "AUTO",
                             domain=None) -> ModelMetricsMultinomial:
    r = jax.device_get(_fused_metric_kernel(
        y, probs, weights if weights is not None else y,
        _multinomial_kernel, weights is not None))
    n = float(r["n"])
    cm = r["cm"]
    per_class_err = 1.0 - np.diag(cm) / np.maximum(cm.sum(axis=1), 1e-10)
    k = cm.shape[0]
    mm = ModelMetricsMultinomial(
        mse=float(r["mse"]) / max(n, 1e-10),
        rmse=float(np.sqrt(r["mse"] / max(n, 1e-10))),
        nobs=int(n),
        logloss=float(r["logloss"]) / max(n, 1e-10),
        mean_per_class_error=float(per_class_err.mean()),
        confusion_matrix=cm,
        hit_ratio_table=np.asarray(r["hits"]) / max(n, 1e-10),
    )
    # default AUTO == NONE: multinomial AUC is opt-in, like the reference
    # (`ModelMetricsMultinomial` only fills it when _auc_type != AUTO/NONE)
    at = (auc_type or "AUTO").lower()
    if at not in ("auto", "none"):
        mauc = make_multinomial_auc(y, probs, weights, domain)
        mm._mauc = mauc
        mm.auc_type = at
        mm.auc = mauc.get(at, pr=False)
        mm.pr_auc = mauc.get(at, pr=True)
    return mm


def _weights(y, weights):
    base = (~jnp.isnan(y)).astype(jnp.float32)
    if weights is not None:
        base = base * jnp.nan_to_num(weights)
    return base


@functools.partial(jax.jit, static_argnames=("kernel", "has_w"))
@telemetry.program("metrics_fused")
def _fused_metric_kernel(y, pred, weights, kernel, has_w):
    """NaN masking + weight prep + the metric kernel in ONE program —
    eagerly the prelude cost 4-5 tiny XLA programs per metrics family,
    each paying its own cold compile+load."""
    base = (~jnp.isnan(y)).astype(jnp.float32)
    w = base * jnp.nan_to_num(weights) if has_w else base
    return kernel(jnp.nan_to_num(y),
                  pred if kernel is _multinomial_kernel
                  else jnp.nan_to_num(pred), w)
