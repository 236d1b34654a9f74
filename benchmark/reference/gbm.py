"""Plain histogram GBM for a binary label (bernoulli deviance, Newton
leaves), independent of the program: the reference for ``correct``, and,
with a lower addend precision or a planted fault, the control put in the
program's place.

Integer work (routing rows down a tree, bin codes, counts) runs on the
device in plain ``jax.numpy`` and is exact; sums that decide a value are
float64 on the host, except the (feature, bin) histograms of the split
search, which are float32 at ``highest`` matmul precision in row blocks.

A forest is a dict of arrays in heap order (node k has children 2k+1 and
2k+2): ``feat`` (T, N) int, -1 at a leaf; ``thr`` (T, N), a row goes left
when ``x <= thr``; ``val`` (T, N) leaf values on the link scale, learn rate
included; ``gain`` (T, N) split gains; ``f0`` the initial link value.
"""

from __future__ import annotations

import functools
import math

import numpy as np

NBINS = 20
MIN_ROWS = 10.0
MIN_SPLIT_IMPROVEMENT = 1e-5
BLOCK = 8192


# ---------------------------------------------------------------- device ---
@functools.lru_cache(maxsize=None)
def _jits():
    import jax
    import jax.numpy as jnp

    def leaves(X, feat, thr):
        """(T, R) int8: the node each row of X (F, R) ends in, per tree."""
        n_int = (feat.shape[1] - 1) // 2

        def one(tree):
            f_t, t_t = tree

            def body(k, node):
                fk = f_t[k]
                x = jax.lax.dynamic_index_in_dim(X, jnp.maximum(fk, 0), 0,
                                                 keepdims=False)
                child = 2 * k + 1 + (x > t_t[k]).astype(jnp.int32)
                return jnp.where((node == k) & (fk >= 0), child, node)

            node = jax.lax.fori_loop(0, n_int, body,
                                     jnp.zeros(X.shape[1], jnp.int32))
            return node.astype(jnp.int8)

        return jax.lax.map(one, (feat, thr))

    def count_le(X, v):
        """(F, Q) rows with X[f] <= v[f, q] (NaN rows never count)."""
        return jax.lax.map(lambda q: jnp.sum(X <= q[:, None], axis=1,
                                             dtype=jnp.int32), v.T).T

    def minmax(X):
        return jnp.nanmin(X, axis=1), jnp.nanmax(X, axis=1)

    def codes(X, edges):
        """(F, R) int8 bin of each value: the count of edges below it."""
        def one(args):
            x, e = args
            return jnp.sum(x[None, :] > e[:, None], axis=0).astype(jnp.int8)
        return jax.lax.map(one, (X, edges))

    def level_hist(C, slot, vals, nslot):
        """(F, nslot * NBINS, V) sums of vals (R, V) by (feature, slot of the
        row's node at this level, bin); rows with slot < 0 drop out."""
        F, R = C.shape
        K = nslot * NBINS
        rb = math.gcd(R, BLOCK)
        nblk = R // rb
        idx = jnp.where(slot[None, :] >= 0,
                        slot[None, :] * NBINS + C.astype(jnp.int32), -1)

        def body(acc, blk):
            ib, vb = blk
            oh = jax.nn.one_hot(ib, K, dtype=jnp.float32)      # (F, rb, K)
            return acc + jnp.einsum("frk,rv->fkv", oh, vb,
                                    precision=jax.lax.Precision.HIGHEST), None

        acc, _ = jax.lax.scan(
            body, jnp.zeros((F, K, vals.shape[1]), jnp.float32),
            (idx.reshape(F, nblk, rb).transpose(1, 0, 2),
             vals.reshape(nblk, rb, vals.shape[1])))
        return acc

    return {"leaves": jax.jit(leaves), "count_le": jax.jit(count_le),
            "minmax": jax.jit(minmax), "codes": jax.jit(codes),
            "level_hist": jax.jit(level_hist, static_argnums=3)}


def quantile_edges(X, nrow: int, nbins: int = NBINS) -> np.ndarray:
    """(F, nbins-1) f32: for q = 1/nbins .. (nbins-1)/nbins the smallest
    value v with count(x <= v) >= q * nrow, by bisection on the value."""
    import jax.numpy as jnp

    j = _jits()
    lo, hi = (np.asarray(a, np.float64) for a in j["minmax"](X))
    F = lo.shape[0]
    target = np.ceil(np.arange(1, nbins) / nbins * nrow)[None, :]
    lo = np.repeat(lo[:, None], nbins - 1, 1) - 1e-3
    hi = np.repeat(hi[:, None], nbins - 1, 1)
    for _ in range(40):
        mid = ((lo + hi) / 2).astype(np.float32).astype(np.float64)
        cnt = np.asarray(j["count_le"](X, jnp.asarray(mid, jnp.float32)))
        ok = cnt >= target
        hi = np.where(ok, mid, hi)
        lo = np.where(ok, lo, mid)
    assert F == X.shape[0]
    return hi.astype(np.float32)


# ------------------------------------------------------------------ host ---
def _level_of(node: np.ndarray) -> np.ndarray:
    return np.floor(np.log2(node.astype(np.int64) + 1)).astype(np.int64)


def _sigmoid(f):
    return 1.0 / (1.0 + np.exp(-f))


def _gain(gl, hl, gr, hr):
    gt, ht = gl + gr, hl + hr
    return gl * gl / (hl + 1e-10) + gr * gr / (hr + 1e-10) - gt * gt / (ht + 1e-10)


#: the reported AUC is a trapezoid over this many probability thresholds
#: (H2O's AUC2 design), not the rank statistic: the reference follows the
#: definition
AUC_BINS = 1024


def logloss(p: np.ndarray, y: np.ndarray) -> float:
    pc = np.clip(p, 1e-15, 1 - 1e-15)
    return float(-np.mean(y * np.log(pc) + (1 - y) * np.log(1 - pc)))


def binned_auc(p: np.ndarray, y: np.ndarray, nbins: int = AUC_BINS) -> float:
    """AUC as a trapezoid over ``nbins`` probability thresholds: rows at or
    above a threshold count as positive."""
    bins = np.clip((p * nbins).astype(np.int64), 0, nbins - 1)
    pos = np.bincount(bins, weights=y, minlength=nbins)
    neg = np.bincount(bins, weights=1.0 - y, minlength=nbins)
    tpr = np.concatenate([np.cumsum(pos[::-1])[::-1] / pos.sum(), [0.0]])
    fpr = np.concatenate([np.cumsum(neg[::-1])[::-1] / neg.sum(), [0.0]])
    return float(-np.trapezoid(tpr, fpr))


def logloss_auc(p: np.ndarray, y: np.ndarray,
                dtype_name: str | None = None) -> tuple[float, float]:
    """The reported metrics; ``dtype_name`` rounds each probability once to
    a lower type first (the control's metrics, a step below float32)."""
    p = _round_to(p, dtype_name)
    return logloss(p, y), binned_auc(p, y)


def _round_to(a: np.ndarray, dtype_name: str | None) -> np.ndarray:
    """a rounded once to a lower float type (None: untouched)."""
    if dtype_name is None:
        return a
    import ml_dtypes

    return a.astype(np.float32).astype(getattr(ml_dtypes, dtype_name)) \
            .astype(np.float64)


class Data:
    """The cell's data as the reference holds it: X (F, plen) on the device,
    y on the host, the live-row mask, and its own bin edges and codes
    (made on first use)."""

    def __init__(self, cols, nrow: int):
        import jax.numpy as jnp

        self.nrow = int(nrow)
        self.X = jnp.stack([c for c in cols[:-1]], axis=0)
        self.plen = int(self.X.shape[1])
        self.y = np.asarray(cols[-1], np.float64)[: self.nrow]
        self._edges = self._codes = None
        self.edge_rows = None       # None: the edges are of all live rows
        self.times: dict = {}       # seconds each part of the checks took

    def binned_on(self, rows: np.ndarray) -> "Data":
        """The same data with its bin edges (and codes) made from the live
        rows where ``rows`` is true only: what a job sees that leaves the
        others out."""
        import copy

        other = copy.copy(self)
        other._edges = other._codes = None
        other.edge_rows = np.asarray(rows, bool)
        return other

    @property
    def edges(self) -> np.ndarray:
        if self._edges is None:
            if self.edge_rows is None:
                self._edges = quantile_edges(self.X, self.nrow)
            else:
                import jax.numpy as jnp

                keep = np.zeros(self.plen, bool)
                keep[: self.nrow] = self.edge_rows
                self._edges = quantile_edges(
                    jnp.where(jnp.asarray(keep)[None, :], self.X, jnp.nan),
                    int(keep.sum()))
        return self._edges

    @property
    def codes(self):
        if self._codes is None:
            import jax.numpy as jnp

            self._codes = _jits()["codes"](self.X, jnp.asarray(self.edges))
        return self._codes

    def leaves(self, feat, thr) -> np.ndarray:
        import jax.numpy as jnp

        out = _jits()["leaves"](self.X, jnp.asarray(feat, jnp.int32),
                                jnp.asarray(thr, jnp.float32))
        return np.asarray(out)[:, : self.nrow]

    def level_hists(self, leaf: np.ndarray, g: np.ndarray, h: np.ndarray,
                    level: int) -> np.ndarray:
        """(F, 2^level, NBINS, 3) float64 sums of (1, g, h) over the rows
        whose path passes a node of ``level``, by feature and bin."""
        import jax.numpy as jnp

        lv = _level_of(leaf)
        anc = ((leaf.astype(np.int64) + 1) >> np.maximum(lv - level, 0)) - 1
        slot = np.where(lv >= level, anc - (2 ** level - 1), -1)
        pad = self.plen - self.nrow
        slot = np.concatenate([slot, np.full(pad, -1)]).astype(np.int32)
        vals = np.zeros((self.plen, 3), np.float32)
        vals[: self.nrow, 0] = 1.0
        vals[: self.nrow, 1] = g
        vals[: self.nrow, 2] = h
        hist = _jits()["level_hist"](self.codes, jnp.asarray(slot),
                                     jnp.asarray(vals), 2 ** level)
        F = hist.shape[0]
        return np.asarray(hist, np.float64).reshape(F, 2 ** level, NBINS, 3)


def best_splits(hist: np.ndarray) -> tuple:
    """Per node of a level: (best gain, feature, bin) over every cut of
    every feature that leaves MIN_ROWS on both sides."""
    cum = np.cumsum(hist, axis=2)[:, :, :-1, :]           # (F, n, B-1, 3)
    tot = hist.sum(axis=2)[:, :, None, :]
    wl, gl, hl = cum[..., 0], cum[..., 1], cum[..., 2]
    wr, gr, hr = (tot[..., 0] - wl, tot[..., 1] - gl, tot[..., 2] - hl)
    gain = np.where((wl >= MIN_ROWS) & (wr >= MIN_ROWS),
                    _gain(gl, hl, gr, hr), -np.inf)
    F, n, B1 = gain.shape
    flat = gain.transpose(1, 0, 2).reshape(n, -1)
    best = np.argmax(flat, axis=1)
    return flat[np.arange(n), best], best // B1, best % B1


# --------------------------------------------------------------- builder ---
def build(data: Data, ntrees: int, depth: int, learn_rate: float,
          addend_dtype: str | None = None, metrics_dtype: str | None = None,
          fault: str | None = None) -> dict:
    """Train ``ntrees`` trees. ``addend_dtype`` rounds every histogram and
    leaf addend (g, h) once to that type, and ``metrics_dtype`` every
    probability that the reported metrics are made from: the
    lower-precision control. ``fault`` plants one: ``state_unchanged``
    (the margin is never updated), ``half_batch`` (every second row left
    out of the sketch and of every sum), ``altered`` (one leaf value of
    each tree doubled)."""
    y, n = data.y, data.nrow
    N = 2 ** (depth + 1) - 1
    f0 = float(np.log(np.mean(y) / (1 - np.mean(y))))
    margin = np.full(n, f0)
    keep = np.ones(n) if fault != "half_batch" else (np.arange(n) % 2 == 0) * 1.0
    if fault == "half_batch":
        data = data.binned_on(keep > 0)
    forest = {k: np.zeros((ntrees, N), np.float32) for k in ("thr", "val", "gain")}
    forest["feat"] = np.full((ntrees, N), -1, np.int32)
    for t in range(ntrees):
        p = _sigmoid(margin)
        g = _round_to((p - y) * keep, addend_dtype)
        h = _round_to(p * (1 - p) * keep, addend_dtype)
        leaf = np.zeros(n, np.int8)
        feat, thr = forest["feat"][t], forest["thr"][t]
        for level in range(depth):
            hist = data.level_hists(leaf, g, h, level)
            if fault == "half_batch":
                # the weights follow the rows kept: recount them
                hist[..., 0] = data.level_hists(leaf, keep, keep, level)[..., 1]
            gains, bf, bb = best_splits(hist)
            off = 2 ** level - 1
            for i in range(2 ** level):
                wt = hist[0, i, :, 0].sum()
                if gains[i] > MIN_SPLIT_IMPROVEMENT and wt >= 2 * MIN_ROWS:
                    feat[off + i] = bf[i]
                    thr[off + i] = data.edges[bf[i], bb[i]]
                    forest["gain"][t, off + i] = gains[i]
            leaf = data.leaves(feat[None], thr[None])[0]
        G = np.bincount(leaf, weights=g, minlength=N)
        H = np.bincount(leaf, weights=h, minlength=N)
        W = np.bincount(leaf, weights=keep, minlength=N)
        val = np.where(W > 0, -G / (H + 1e-10), 0.0) * learn_rate
        if fault == "altered":
            val[np.argmax(W)] *= 2.0
        forest["val"][t] = val
        if fault != "state_unchanged":
            margin = margin + forest["val"][t].astype(np.float64)[leaf]
    ll, auc = logloss_auc(_sigmoid(margin), y, metrics_dtype)
    return {**forest, "f0": f0, "logloss": ll, "auc": auc}


# --------------------------------------------------------------- checker ---
def check(cand: dict, data: Data, learn_rate: float, verify_trees,
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
    feat = np.asarray(cand["feat"], np.int64)
    thr = np.asarray(cand["thr"], np.float32)
    val = np.asarray(cand["val"], np.float64)
    gain = np.asarray(cand["gain"], np.float64)
    T, N = feat.shape
    verify = [t for t in verify_trees if t < T]
    regret = [t for t in regret_trees if t < T]
    leaf = data.leaves(feat, thr)                                 # (T, n)
    lap("leaves")
    out = {}

    # thresholds sit on the 1/NBINS quantiles of their feature
    used = sorted({(int(f), float(t)) for f, t in
                   zip(feat[feat >= 0], thr[feat >= 0])})
    import jax.numpy as jnp

    Q = max((sum(1 for u in used if u[0] == f) for f in range(data.X.shape[0])),
            default=1)
    v = np.full((data.X.shape[0], max(Q, 1)), -np.inf, np.float32)
    where = {}
    for f in range(data.X.shape[0]):
        for q, u in enumerate([u for u in used if u[0] == f]):
            v[f, q] = u[1]
            where[u] = (f, q)
    cnt = np.asarray(_jits()["count_le"](data.X, jnp.asarray(v)), np.float64)
    pos = cnt / n * NBINS
    out["edge_gap"] = float(max((abs(pos[where[u]] - round(pos[where[u]]))
                                 for u in used), default=0.0))

    lap("edge_gap")
    margin = np.full(n, float(cand["f0"]))
    leaf_gap = gain_gap = regret_gap = 0.0
    for t in range(T):
        if t in verify:
            p = _sigmoid(margin)
            g, h = p - y, p * (1 - p)
            G = np.bincount(leaf[t], weights=g, minlength=N)
            H = np.bincount(leaf[t], weights=h, minlength=N)
            W = np.bincount(leaf[t], minlength=N).astype(np.float64)
            is_leaf = W > 0
            ref = np.where(is_leaf, -G / (H + 1e-10), 0.0) * learn_rate
            scale = np.maximum(np.abs(ref), np.median(np.abs(ref[is_leaf])))
            leaf_gap = max(leaf_gap, float(np.max(
                np.abs(val[t] - ref)[is_leaf] / scale[is_leaf])))
            for k in range((N - 1) // 2 - 1, -1, -1):       # node totals
                if feat[t, k] >= 0:
                    G[k] = G[2 * k + 1] + G[2 * k + 2]
                    H[k] = H[2 * k + 1] + H[2 * k + 2]
                    W[k] = W[2 * k + 1] + W[2 * k + 2]
            split = np.flatnonzero(feat[t] >= 0)
            gref = np.array([_gain(G[2 * k + 1], H[2 * k + 1],
                                   G[2 * k + 2], H[2 * k + 2]) for k in split])
            if len(split):
                gscale = np.maximum(gref, np.median(gref))
                gain_gap = max(gain_gap, float(np.max(
                    np.abs(gain[t, split] - gref) / gscale)))
            lap("verify")
            if t in regret:
                pos_of = {int(k): i for i, k in enumerate(split)}
                for level in range(int(np.log2(N + 1)) - 1):
                    off, n_lv = 2 ** level - 1, 2 ** level
                    nodes = [k for k in range(off, off + n_lv) if k in pos_of]
                    if not nodes:
                        continue
                    best, _, _ = best_splits(
                        data.level_hists(leaf[t], g, h, level))
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
    ll, auc = logloss_auc(_sigmoid(margin), y)
    out["logloss_gap"] = abs(float(cand["logloss"]) - ll) / ll
    out["auc_gap"] = abs(float(cand["auc"]) - auc)
    lap("metrics")
    return out


def compare(result: dict, data: Data, config: dict) -> dict:
    """The numbers compared for what one timed job returned."""
    c = config["correct"]
    return check(result, data, float(config["params"]["learn_rate"]),
                 c["verify_trees"], c["regret_trees"])
