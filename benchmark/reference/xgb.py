"""Plain XGBoost ``hist`` booster for a binary label (Chen and Guestrin,
KDD 2016, eq. 6-7 and section 3.3; XGBoost's parameter documentation),
independent of the program: the reference for ``correct`` of the
``higgs_xgb`` configuration and, with a lower addend precision or a planted
fault, the control put in the program's place.

What it follows, and where it departs:

- per row, logistic loss on the margin ``f``: ``p = sigmoid(f)``,
  ``g = p - y``, ``h = p (1 - p)``;
- cuts: per feature the ``max_bins - 1`` values at the ``k / max_bins``
  quantiles of the column, here the EXACT order statistics of the sorted
  column (XGBoost's weighted quantile sketch promises them to a rank error
  of ``1 / (kFactor x max_bins)``, kFactor 8: `sketch_eps`); a row's bin is
  the count of cuts below its value. The data have no missing value, so the
  missing-value bin and its learnt direction are not built;
- per node, feature and cut ``loss_chg = G_L^2 / (H_L + lambda) +
  G_R^2 / (H_R + lambda) - G^2 / (H + lambda)``, compared WITHOUT the factor
  1/2 of the paper's eq. 7 (XGBoost's code compares ``loss_chg`` against
  ``gamma`` without it, and so does the program); a cut is allowed where
  both children's HESSIAN sums reach ``min_child_weight``; the node splits
  on the largest ``loss_chg`` if it exceeds ``gamma``;
- leaf value ``w = -eta G / (H + lambda)`` (``reg_alpha`` is 0: no soft
  threshold);
- start margin: whatever the candidate carries as ``f0``; `check` reports
  its distance from the prior log-odds ``log(mean(y) / (1 - mean(y)))``
  (``f0_gap``), which is what the shared engine starts from (XGBoost's
  ``base_score`` 0.5 would be a margin of 0).

Integer work (routing rows down a tree, bin codes) runs on the device in
plain ``jax.numpy`` and is exact; every sum that decides a value is float64
on the host: per-leaf and per-node ``G``, ``H`` by ``numpy.bincount``, and
the (feature, node, bin) sums of the split search likewise, a feature at a
time (a 256-bin one-hot contraction at ``highest`` precision over 32 nodes
is 66 GFLOP a block of 8192 rows: minutes a level on the chip). The cuts
are read off columns sorted on the host. From ``reference/gbm.py`` it takes
what bakes in neither 20 bins nor int8 codes: the forest walk
(`gbm.Data.leaves`; its int8 node ids hold a depth-6 tree's 127 nodes),
the metrics, the rounding helper.

A forest is ``gbm.py``'s: heap order, ``feat`` -1 at a leaf, a row goes
left when ``x <= thr``, ``val`` on the link scale with the learn rate in.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import gbm

#: XGBoost's ``WQSketch::kFactor``: the hist sketch is built with
#: ``eps = 1 / (kFactor x max_bins)``, the rank error it promises a cut
KFACTOR = 8

#: nodes of the deepest tree `gbm.Data.leaves`' int8 node ids can name
_MAX_NODES = 127

#: host threads of the column sort and of the (feature, node, bin) sums
THREADS = 8


def params_of(config: dict) -> dict:
    """The booster's parameters as the configuration's ``params`` spell
    them (H2O-3's names)."""
    p = config["params"]
    return {"max_bins": int(p["max_bins"]), "max_depth": int(p["max_depth"]),
            "eta": float(p["learn_rate"]), "lam": float(p["reg_lambda"]),
            "min_child_weight": float(p["min_rows"]),
            "gamma": float(p["min_split_improvement"])}


def sketch_eps(max_bins: int) -> float:
    return 1.0 / (KFACTOR * max_bins)


@functools.lru_cache(maxsize=None)
def _jit_codes():
    import jax
    import jax.numpy as jnp

    def codes(X, edges):
        """(F, R) int16 bin of each value: the count of cuts below it."""
        def one(args):
            x, e = args
            return jnp.sum(x[None, :] > e[:, None], axis=0).astype(jnp.int16)
        return jax.lax.map(one, (X, edges))

    return jax.jit(codes)


def _loss_chg(gl, hl, gr, hr, lam):
    gt, ht = gl + gr, hl + hr
    return gl * gl / (hl + lam) + gr * gr / (hr + lam) - gt * gt / (ht + lam)


class Data(gbm.Data):
    """The cell's data as this reference holds it: `gbm.Data`'s X on the
    device and y on the host, plus the columns sorted on the host (made on
    first use), from which the cuts and every rank are read."""

    def __init__(self, cols, nrow: int, max_bins: int = 256):
        super().__init__(cols, nrow)
        self.max_bins = int(max_bins)
        self._sorted = self._hcodes = None

    def with_bins(self, max_bins: int) -> "Data":
        """The same data cut into another number of bins (the sorted
        columns are shared, the cuts and codes made anew)."""
        import copy

        if int(max_bins) == self.max_bins:
            return self
        other = copy.copy(self)
        other.max_bins = int(max_bins)
        other._edges = other._hcodes = None
        return other

    @property
    def sorted_cols(self) -> np.ndarray:
        """(F, n) float32, each live column ascending (the generator makes
        no NaN; one would sort last)."""
        if self._sorted is None:
            X = np.asarray(self.X)[:, : self.nrow]
            with ThreadPoolExecutor(THREADS) as ex:
                self._sorted = np.stack(list(ex.map(np.sort, X)))
        return self._sorted

    @property
    def edges(self) -> np.ndarray:
        """(F, max_bins - 1) f32: for k = 1 .. max_bins - 1 the smallest
        value v with ``count(x <= v) >= k / max_bins`` of the rows."""
        if self._edges is None:
            k = np.ceil(np.arange(1, self.max_bins) / self.max_bins * self.nrow)
            self._edges = self.sorted_cols[:, k.astype(np.int64) - 1]
        return self._edges

    def rank_share(self, f: int, v: float) -> float:
        """The share of the rows with ``x[f] <= v``."""
        return float(np.searchsorted(self.sorted_cols[f], np.float32(v),
                                     side="right")) / self.nrow

    @property
    def codes(self) -> np.ndarray:
        """(F, n) int16 on the HOST: the live rows' bins by `edges`."""
        if self._hcodes is None:
            import jax.numpy as jnp

            dev = _jit_codes()(self.X, jnp.asarray(self.edges))
            self._hcodes = np.asarray(dev)[:, : self.nrow]
        return self._hcodes

    def level_hists(self, leaf: np.ndarray, g: np.ndarray, h: np.ndarray,
                    level: int) -> np.ndarray:
        """(F, 2^level, max_bins, 3) float64 sums of (1, g, h) over the rows
        whose path passes a node of ``level``, by feature and bin."""
        lv = gbm._level_of(leaf)
        anc = ((leaf.astype(np.int64) + 1) >> np.maximum(lv - level, 0)) - 1
        B, n_lv = self.max_bins, 2 ** level
        base = (anc - (n_lv - 1)) * B
        on = lv >= level
        everyone = bool(on.all())
        if not everyone:
            rows = np.flatnonzero(on)
            base, g, h = base[rows], g[rows], h[rows]
        C = self.codes

        def one(f):
            key = base + (C[f] if everyone else C[f, rows])
            return np.stack([np.bincount(key, weights=wt, minlength=n_lv * B)
                             for wt in (None, g, h)], axis=1)

        with ThreadPoolExecutor(THREADS) as ex:
            out = np.stack(list(ex.map(one, range(C.shape[0]))))
        return out.reshape(C.shape[0], n_lv, B, 3)       # float64 by the stack


def best_splits(hist: np.ndarray, lam: float, min_child_weight: float,
                child_weight_on: str = "hessian") -> tuple:
    """Per node of a level: (largest loss_chg, feature, bin) over every cut
    of every feature that leaves ``min_child_weight`` of hessian on both
    sides (``child_weight_on="rows"``: of rows, the planted fault)."""
    cum = np.cumsum(hist, axis=2)[:, :, :-1, :]           # (F, n, B-1, 3)
    tot = hist.sum(axis=2)[:, :, None, :]
    wl, gl, hl = cum[..., 0], cum[..., 1], cum[..., 2]
    wr, gr, hr = (tot[..., 0] - wl, tot[..., 1] - gl, tot[..., 2] - hl)
    cl, cr = (wl, wr) if child_weight_on == "rows" else (hl, hr)
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = np.where((cl >= min_child_weight) & (cr >= min_child_weight),
                        _loss_chg(gl, hl, gr, hr, lam), -np.inf)
    _, n, B1 = gain.shape
    flat = gain.transpose(1, 0, 2).reshape(n, -1)
    best = np.argmax(flat, axis=1)
    return flat[np.arange(n), best], best // B1, best % B1


# --------------------------------------------------------------- builder ---
#: the planted faults: lambda left out of the leaf values; the child-weight
#: test on row counts (both at `binding_weight`); the root cut of every tree
#: moved half a bin off the grid; a forest of 20-bin cuts under the
#: configuration's name
FAULTS = ("no_lambda", "child_weight_rows", "cut_moved", "bins20")


def binding_weight(nrow: int) -> float:
    """A ``min_child_weight``, and a ``lambda``, at which a fault in how
    they are applied shows on ``nrow`` rows. At the documented 1 and 1 it
    does not, in the first trees: a level-5 node of 11M rows holds about
    nrow / 32 rows and a fifth of that in hessian, so no cut comes near a
    child of 1 (the row test and the hessian test allow the same cuts),
    and ``lambda`` 1 moves a leaf of 4,000 in hessian by 2e-4, under the
    stated precision's own noise. At nrow / 256 such a node cannot be cut
    into two children of that much hessian and can into two of that many
    rows, and ``lambda`` is of a leaf's own hessian. The faults
    ``child_weight_rows`` and ``no_lambda`` are planted, and checked, with
    the one parameter at this weight."""
    return nrow / 256.0


def build(data: Data, ntrees: int, prm: dict,
          addend_dtype: str | None = None, metrics_dtype: str | None = None,
          fault: str | None = None) -> dict:
    """Train ``ntrees`` trees with the parameters of `params_of`.
    ``addend_dtype`` rounds every addend (g, h) once to that type and
    ``metrics_dtype`` every probability the reported metrics are made from:
    the lower-precision control. ``fault`` plants one of `FAULTS`."""
    y, n = data.y, data.nrow
    depth, lam, eta = prm["max_depth"], prm["lam"], prm["eta"]
    N = 2 ** (depth + 1) - 1
    if N > _MAX_NODES:
        raise ValueError(f"max_depth {depth}: the walk's int8 node ids hold "
                         f"{_MAX_NODES} nodes")
    data = data.with_bins(20 if fault == "bins20" else prm["max_bins"])
    on = "rows" if fault == "child_weight_rows" else "hessian"
    f0 = float(np.log(np.mean(y) / (1 - np.mean(y))))
    margin = np.full(n, f0)
    forest = {k: np.zeros((ntrees, N), np.float32) for k in ("thr", "val", "gain")}
    forest["feat"] = np.full((ntrees, N), -1, np.int32)
    for t in range(ntrees):
        p = gbm._sigmoid(margin)
        g = gbm._round_to(p - y, addend_dtype)
        h = gbm._round_to(p * (1 - p), addend_dtype)
        leaf = np.zeros(n, np.int8)
        feat, thr = forest["feat"][t], forest["thr"][t]
        for level in range(depth):
            hist = data.level_hists(leaf, g, h, level)
            gains, bf, bb = best_splits(hist, lam, prm["min_child_weight"], on)
            off = 2 ** level - 1
            for i in range(2 ** level):
                if gains[i] > prm["gamma"]:
                    feat[off + i] = bf[i]
                    thr[off + i] = data.edges[bf[i], bb[i]]
                    forest["gain"][t, off + i] = gains[i]
                    if fault == "cut_moved" and level == 0:
                        up = data.edges[bf[i], min(bb[i] + 1,
                                                   data.max_bins - 2)]
                        thr[0] = (thr[0] + up) / 2
            leaf = data.leaves(feat[None], thr[None])[0]
        G = np.bincount(leaf, weights=g, minlength=N)
        H = np.bincount(leaf, weights=h, minlength=N)
        W = np.bincount(leaf, minlength=N)
        with np.errstate(divide="ignore", invalid="ignore"):
            val = np.where(W > 0, -G / (H + (0.0 if fault == "no_lambda"
                                             else lam)), 0.0) * eta
        forest["val"][t] = val
        margin = margin + forest["val"][t].astype(np.float64)[leaf]
    ll, auc = gbm.logloss_auc(gbm._sigmoid(margin), y, metrics_dtype)
    return {**forest, "f0": f0, "logloss": ll, "auc": auc}


#: the parameter a fault is planted at `binding_weight` in
_BINDING = {"no_lambda": "lam", "child_weight_rows": "min_child_weight"}


def candidates(config: dict, data: Data):
    """(name, candidate, the parameters its check runs under) for the
    lower-precision control and each planted fault, ``control_trees`` trees
    each, built as their turn comes."""
    c, prm = config["correct"], params_of(config)
    n = int(c["control_trees"])
    yield "control", build(data, n, prm, addend_dtype=c["control_dtype"],
                           metrics_dtype=c["control_metrics_dtype"]), prm
    for f in FAULTS:
        p = (dict(prm, **{_BINDING[f]: binding_weight(data.nrow)})
             if f in _BINDING else prm)
        yield f, build(data, n, p, fault=f), p


# --------------------------------------------------------------- checker ---
def check(cand: dict, data: Data, prm: dict, verify_trees,
          regret_trees) -> dict:
    """The numbers compared, for a candidate forest (the program's, the
    control's, or a faulty one) against the reference's own arithmetic
    along the candidate's structure."""
    import time

    tm, last = data.times, [time.perf_counter()]

    def lap(name):
        now = time.perf_counter()
        tm[name] = tm.get(name, 0.0) + now - last[0]
        last[0] = now

    y, n = data.y, data.nrow
    lam, eta, mcw = prm["lam"], prm["eta"], prm["min_child_weight"]
    feat = np.asarray(cand["feat"], np.int64)
    thr = np.asarray(cand["thr"], np.float32)
    val = np.asarray(cand["val"], np.float64)
    gain = np.asarray(cand["gain"], np.float64)
    T, N = feat.shape
    if N != 2 ** (prm["max_depth"] + 1) - 1 or N > _MAX_NODES:
        raise ValueError(f"a forest of {N} nodes a tree is not one of "
                         f"max_depth {prm['max_depth']}")
    out = {}
    verify = [t for t in verify_trees if t < T]
    regret = [t for t in regret_trees if t < T]
    leaf = data.leaves(feat, thr)                                 # (T, n)
    lap("leaves")

    # every threshold used sits on a k / max_bins quantile of its feature,
    # to the rank error XGBoost's sketch promises (`sketch_eps`)
    used = sorted({(int(f), float(t)) for f, t in
                   zip(feat[feat >= 0], thr[feat >= 0])})
    B = data.max_bins
    pos = np.array([data.rank_share(f, t) for f, t in used])
    out["edge_rank_gap"] = float(np.max(
        np.abs(pos - np.round(pos * B) / B), initial=0.0))
    lap("edge_rank_gap")

    y_mean = float(np.mean(y))
    out["f0_gap"] = abs(float(cand["f0"]) - np.log(y_mean / (1 - y_mean)))
    margin = np.full(n, float(cand["f0"]))
    leaf_gap = gain_gap = regret_gap = child_weight_gap = 0.0
    for t in range(T):
        if t in verify:
            p = gbm._sigmoid(margin)
            g, h = p - y, p * (1 - p)
            G = np.bincount(leaf[t], weights=g, minlength=N)
            H = np.bincount(leaf[t], weights=h, minlength=N)
            W = np.bincount(leaf[t], minlength=N).astype(np.float64)
            is_leaf = W > 0
            with np.errstate(divide="ignore", invalid="ignore"):
                ref = np.where(is_leaf, -G / (H + lam), 0.0) * eta
            scale = np.maximum(np.abs(ref), np.median(np.abs(ref[is_leaf])))
            leaf_gap = max(leaf_gap, float(np.max(
                np.abs(val[t] - ref)[is_leaf] / scale[is_leaf])))
            for k in range((N - 1) // 2 - 1, -1, -1):       # node totals
                if feat[t, k] >= 0:
                    G[k] = G[2 * k + 1] + G[2 * k + 2]
                    H[k] = H[2 * k + 1] + H[2 * k + 2]
            split = np.flatnonzero(feat[t] >= 0)
            gref = np.array([_loss_chg(G[2 * k + 1], H[2 * k + 1],
                                       G[2 * k + 2], H[2 * k + 2], lam)
                             for k in split])
            if len(split):
                gscale = np.maximum(gref, np.median(gref))
                gain_gap = max(gain_gap, float(np.max(
                    np.abs(gain[t, split] - gref) / gscale)))
                # no child of a split holds less hessian than allowed
                kids = np.concatenate([2 * split + 1, 2 * split + 2])
                child_weight_gap = max(child_weight_gap, float(np.max(
                    mcw - H[kids])) / max(mcw, 1e-300))
            lap("verify")
            if t in regret:
                pos_of = {int(k): i for i, k in enumerate(split)}
                for level in range(int(np.log2(N + 1)) - 1):
                    off, n_lv = 2 ** level - 1, 2 ** level
                    nodes = [k for k in range(off, off + n_lv) if k in pos_of]
                    if not nodes:
                        continue
                    best, _, _ = best_splits(
                        data.level_hists(leaf[t], g, h, level), lam, mcw)
                    bmed = np.median([best[k - off] for k in nodes])
                    for k in nodes:
                        b = best[k - off]
                        regret_gap = max(regret_gap, float(
                            (b - gref[pos_of[k]]) / max(b, bmed)))
                lap("regret")
        margin = margin + val[t][leaf[t]]
        lap("margin")
    out["leaf_gap"], out["gain_gap"] = leaf_gap, gain_gap
    out["regret_gap"] = regret_gap
    out["child_weight_gap"] = max(child_weight_gap, 0.0)
    ll, auc = gbm.logloss_auc(gbm._sigmoid(margin), y)
    out["logloss_gap"] = abs(float(cand["logloss"]) - ll) / ll
    out["auc_gap"] = abs(float(cand["auc"]) - auc)
    lap("metrics")
    return out


def compare(result: dict, data: Data, config: dict) -> dict:
    """The numbers compared for what one timed job returned."""
    c = config["correct"]
    prm = params_of(config)
    return check(result, data.with_bins(prm["max_bins"]), prm,
                 c["verify_trees"], c["regret_trees"])
