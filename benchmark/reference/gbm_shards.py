"""The plain GBM reference (``reference/gbm.py``) for a frame whose rows live
on several chips. It adds no arithmetic of its own: the functions are
``gbm.py``'s, run on each chip's own rows, and what they count and sum per
chip is added on the host in float64.

Two things differ from ``gbm.py``, and only in where the work runs:

- ``level_hists``: ``gbm.py``'s ``level_hist`` is a plain-``jit`` scan over
  row blocks; on a row-sharded array the TPU compiler all-gathers the whole
  coded matrix for it (4.9 GB at 44M rows, compile-only v5e 2x2). Here the
  same function runs under ``jax.shard_map`` on each chip's rows and returns
  one partial histogram a chip. Everything else of ``gbm.Data`` (row
  routing, bin codes, the counts under the quantile bisection) is
  elementwise or a sum over the row axis and is left as it is: the counts
  are int32 and add exactly across chips.
- ``check``: the float64 walk of the forest over all rows (gradients, leaf
  sums, the margin, the reported metrics) runs one thread a chip's rows and
  the per-chip sums are added, so 44M rows x 50 trees take the time of 11M.

It imports nothing of ``h2o_tpu``. On one device it is ``gbm.py`` again.
"""

from __future__ import annotations

import functools
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import gbm as plain
from .gbm import AUC_BINS, NBINS, best_splits, build  # noqa: F401 (build: re-exported)


@functools.lru_cache(maxsize=None)
def _sharded_level_hist(mesh, axis: str, nslot: int):
    """``gbm.py``'s ``level_hist`` on each chip's own rows: (chips, F,
    nslot * NBINS, V) partial sums, one a chip."""
    import jax
    from jax.sharding import PartitionSpec as P

    level_hist = plain._jits()["level_hist"]
    return jax.jit(jax.shard_map(
        lambda C, slot, vals: level_hist(C, slot, vals, nslot)[None],
        mesh=mesh, in_specs=(P(None, axis), P(axis), P(axis, None)),
        out_specs=P(axis), check_vma=False))


def _row_axis(arr):
    """(mesh, axis name) when ``arr`` (F, R) has its rows split over the
    devices of a mesh axis, else None."""
    from jax.sharding import NamedSharding

    sh = arr.sharding
    if not isinstance(sh, NamedSharding) or len(sh.device_set) < 2:
        return None
    axis = sh.spec[1] if len(sh.spec) > 1 else None
    if isinstance(axis, tuple):
        axis = axis[0] if len(axis) == 1 else None
    return (sh.mesh, axis) if axis is not None else None


class Data(plain.Data):
    """``gbm.Data`` with the rows' split over the chips known: ``ranges``
    are the live rows each chip holds, in row order."""

    def __init__(self, cols, nrow: int):
        super().__init__(cols, nrow)
        starts = sorted({(s.index[1].start or 0) for s in
                         self.X.addressable_shards})
        ends = starts[1:] + [self.plen]
        self.ranges = [(a, min(b, self.nrow)) for a, b in zip(starts, ends)
                       if a < self.nrow]

    def by_chip(self, fn) -> list:
        """``fn(a, b)`` for each chip's live rows [a, b), a thread a chip."""
        if len(self.ranges) == 1:
            return [fn(*self.ranges[0])]
        with ThreadPoolExecutor(len(self.ranges)) as pool:
            return list(pool.map(lambda r: fn(*r), self.ranges))

    def level_hists(self, leaf, g, h, level: int) -> np.ndarray:
        where = _row_axis(self.codes)
        if where is None:
            return super().level_hists(leaf, g, h, level)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh, axis = where
        slot = np.full(self.plen, -1, np.int32)
        vals = np.zeros((self.plen, 3), np.float32)

        def fill(a, b):
            lf = leaf[a:b]
            lv = plain._level_of(lf)
            anc = ((lf.astype(np.int64) + 1) >> np.maximum(lv - level, 0)) - 1
            slot[a:b] = np.where(lv >= level, anc - (2 ** level - 1), -1)
            vals[a:b, 0] = 1.0
            vals[a:b, 1] = g[a:b]
            vals[a:b, 2] = h[a:b]

        self.by_chip(fill)
        part = _sharded_level_hist(mesh, axis, 2 ** level)(
            self.codes,
            jax.device_put(slot, NamedSharding(mesh, P(axis))),
            jax.device_put(vals, NamedSharding(mesh, P(axis, None))))
        hist = np.asarray(part, np.float64).sum(axis=0)
        return hist.reshape(hist.shape[0], 2 ** level, NBINS, 3)


def check(cand: dict, data: Data, learn_rate: float, verify_trees,
          regret_trees) -> dict:
    """``gbm.check``'s numbers for a candidate forest, each chip's rows
    walked in a thread of their own and the per-chip sums added."""
    tm, last = data.times, [time.perf_counter()]

    def lap(name):
        now = time.perf_counter()
        tm[name] = tm.get(name, 0.0) + now - last[0]
        last[0] = now

    import jax.numpy as jnp

    n = data.nrow
    feat = np.asarray(cand["feat"], np.int64)
    thr = np.asarray(cand["thr"], np.float32)
    val = np.asarray(cand["val"], np.float64)
    gain = np.asarray(cand["gain"], np.float64)
    T, N = feat.shape
    verify = [t for t in verify_trees if t < T]
    regret = [t for t in regret_trees if t in verify]
    leaf = data.leaves(feat, thr)                                 # (T, n)
    lap("leaves")
    out = {}

    # thresholds sit on the 1/NBINS quantiles of their feature
    F = data.X.shape[0]
    used = sorted({(int(f), float(t)) for f, t in
                   zip(feat[feat >= 0], thr[feat >= 0])})
    per_f = [[u for u in used if u[0] == f] for f in range(F)]
    v = np.full((F, max(max(map(len, per_f)), 1)), -np.inf, np.float32)
    for f, us in enumerate(per_f):
        v[f, : len(us)] = [u[1] for u in us]
    cnt = np.asarray(plain._jits()["count_le"](data.X, jnp.asarray(v)),
                     np.float64)
    pos = cnt / n * NBINS
    out["edge_gap"] = float(max(
        (abs(pos[f, q] - round(pos[f, q]))
         for f, us in enumerate(per_f) for q in range(len(us))), default=0.0))
    lap("edge_gap")

    f0 = float(cand["f0"])

    def walk(a, b):
        """One chip's rows through the forest: the (G, H, W) leaf sums of
        each verified tree, the gradients of each regret tree, and the sums
        the reported metrics are made of."""
        y, lf = data.y[a:b], leaf[:, a:b]
        margin = np.full(b - a, f0)
        sums, grads = {}, {}
        for t in range(T):
            if t in verify:
                p = plain._sigmoid(margin)
                g, h = p - y, p * (1 - p)
                sums[t] = np.stack([
                    np.bincount(lf[t], weights=g, minlength=N),
                    np.bincount(lf[t], weights=h, minlength=N),
                    np.bincount(lf[t], minlength=N).astype(np.float64)])
                if t in regret:
                    grads[t] = (g, h)
            margin += val[t][lf[t]]
        p = plain._sigmoid(margin)
        pc = np.clip(p, 1e-15, 1 - 1e-15)
        ll = -np.sum(y * np.log(pc) + (1 - y) * np.log(1 - pc))
        bins = np.clip((p * AUC_BINS).astype(np.int64), 0, AUC_BINS - 1)
        return (sums, grads, ll,
                np.bincount(bins, weights=y, minlength=AUC_BINS),
                np.bincount(bins, weights=1.0 - y, minlength=AUC_BINS))

    parts = data.by_chip(walk)
    lap("walk")
    leaf_gap = gain_gap = regret_gap = 0.0
    for t in verify:
        G, H, W = sum(p[0][t] for p in parts)
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
        gref = np.array([plain._gain(G[2 * k + 1], H[2 * k + 1],
                                     G[2 * k + 2], H[2 * k + 2])
                         for k in split])
        if len(split):
            gscale = np.maximum(gref, np.median(gref))
            gain_gap = max(gain_gap, float(np.max(
                np.abs(gain[t, split] - gref) / gscale)))
        lap("verify")
        if t in regret:
            g = np.concatenate([p[1][t][0] for p in parts])
            h = np.concatenate([p[1][t][1] for p in parts])
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
    out["leaf_gap"], out["gain_gap"] = leaf_gap, gain_gap
    out["regret_gap"] = regret_gap
    ll = sum(p[2] for p in parts) / n
    pos_b, neg_b = sum(p[3] for p in parts), sum(p[4] for p in parts)
    tpr = np.concatenate([np.cumsum(pos_b[::-1])[::-1] / pos_b.sum(), [0.0]])
    fpr = np.concatenate([np.cumsum(neg_b[::-1])[::-1] / neg_b.sum(), [0.0]])
    auc = float(-np.trapezoid(tpr, fpr))
    out["logloss_gap"] = abs(float(cand["logloss"]) - ll) / ll
    out["auc_gap"] = abs(float(cand["auc"]) - auc)
    lap("metrics")
    return out


def compare(result: dict, data: Data, config: dict) -> dict:
    """The numbers compared for what one timed job returned."""
    c = config["correct"]
    return check(result, data, float(config["params"]["learn_rate"]),
                 c["verify_trees"], c["regret_trees"])
