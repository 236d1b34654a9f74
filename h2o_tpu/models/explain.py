"""Model explanation tools — partial dependence + permutation importance.

Analog of `h2o-core/src/main/java/hex/PartialDependence.java` (the
`/3/PartialDependence` handler's worker) and `hex/PermutationVarImp.java`.
The reference runs one scoring MRTask per grid point / per shuffled column;
here each grid point is one batched `model.predict` over the sharded frame —
the mutate-column-and-rescore loop stays on host, the scoring stays on
device."""

from __future__ import annotations

import numpy as np

from ..frame.frame import Frame
from ..frame.vec import T_CAT, Vec
from ..utils.twodimtable import TwoDimTable


def _response_col(model, pred: Frame, target: str | None = None) -> np.ndarray:
    """The PDP target: p1 for binomial, p(target) for multinomial,
    prediction for regression."""
    cat = model.output.model_category
    if cat == "Binomial":
        return pred.vec(2).to_numpy()
    if cat == "Multinomial":
        return pred.vec(f"p{target}").to_numpy()
    return pred.vec(0).to_numpy()


def partial_dependence(model, fr: Frame, cols=None, nbins: int = 20,
                       weight_column: str | None = None,
                       targets=None, row_index: int = -1) -> list[TwoDimTable]:
    """One table per column (per target class for multinomial): grid value,
    weighted mean response, stddev, stderr of the per-row responses with the
    column pinned to the value.

    ``row_index >= 0`` computes the ICE curve of that single row instead of
    the all-rows average (`hex/PartialDependence.java:21` _row_index) — the
    grid still comes from the FULL frame's column range, and the stddev /
    stderr columns are 0 (one row)."""
    cat = model.output.model_category
    if cat == "Multinomial" and not targets:
        raise ValueError("multinomial PDP requires `targets` (class labels), "
                         "as in the reference's PartialDependence.targets")
    targets = [None] if cat != "Multinomial" else (
        [targets] if isinstance(targets, str) else list(targets))
    cols = cols or [n for n in model.output.names][:2]
    cols = [cols] if isinstance(cols, str) else list(cols)
    w = None
    if weight_column is not None:
        w = np.nan_to_num(fr.vec(weight_column).to_numpy())
    ice = row_index is not None and row_index >= 0
    out = []
    for col, target in [(c, t) for c in cols for t in targets]:
        v = fr.vec(col)
        if v.is_categorical():
            grid = np.arange(len(v.domain), dtype=np.float64)
            labels = list(v.domain)
        else:
            x = v.to_numpy()
            ok = ~np.isnan(x)
            lo, hi = float(np.min(x[ok])), float(np.max(x[ok]))
            grid = np.linspace(lo, hi, nbins)
            labels = None
        rows = []
        # only the model's features (plus the swept/weight columns) enter the
        # rebuilt frames: string/id columns pass through predict unused in
        # the original frame, but a float rebuild of them would throw
        used = set(model.output.names) | {col}
        if weight_column:
            used.add(weight_column)
        pd_names = [n for n in fr.names if n in used]
        if ice:
            # one predict over a G-row frame: the chosen row replicated with
            # the column swept over the grid; the base row reads ONE element
            # per column (a full to_numpy here would ship whole columns
            # to the host for a single-row curve)
            base = {n: float(np.asarray(fr.vec(n).data[row_index]))
                    for n in pd_names if n != col}
            reps = Frame(pd_names, [
                Vec.from_numpy(
                    grid.astype(np.float32) if n == col else
                    np.full(len(grid), base[n], dtype=np.float32),
                    type=fr.vec(n).type, domain=fr.vec(n).domain)
                for n in pd_names])
            resp = _response_col(model, model.predict(reps), target)
            for gi, val in enumerate(grid):
                rows.append([labels[gi] if labels else float(val),
                             float(resp[gi]), 0.0, 0.0])
        else:
            # batched sweep: many grid points per predict as one tall frame
            # (grid-block-major) — the per-point rescore loop paid one full
            # REST+device round trip per bin; batching turns a 20-bin PDP
            # into 1-2 predicts
            from ..utils.knobs import get_int

            budget = get_int("H2O_TPU_PDP_BATCH_ROWS")
            per_batch = max(1, budget // max(fr.nrow, 1))
            host_cols = {n: fr.vec(n).to_numpy() for n in pd_names
                         if n != col}
            R = fr.nrow
            for b0 in range(0, len(grid), per_batch):
                gb = grid[b0:b0 + per_batch]
                k = len(gb)
                vecs = []
                for n2 in pd_names:
                    if n2 == col:
                        arr = np.repeat(np.asarray(gb, np.float32), R)
                    else:
                        arr = np.tile(host_cols[n2], k)
                    vv = fr.vec(n2)
                    vecs.append(Vec.from_numpy(arr.astype(np.float32),
                                               type=vv.type,
                                               domain=vv.domain))
                tall = Frame(pd_names, vecs)
                resp = _response_col(model, model.predict(tall), target)
                resp = resp[:k * R].reshape(k, R)
                for ki in range(k):
                    gi = b0 + ki
                    r = resp[ki]
                    ok = ~np.isnan(r)
                    ww = (w[ok] if w is not None else np.ones(ok.sum()))
                    tot = max(ww.sum(), 1e-12)
                    mean = float(np.sum(ww * r[ok]) / tot)
                    var = float(np.sum(ww * (r[ok] - mean) ** 2) / tot)
                    std = np.sqrt(var)
                    rows.append([labels[gi] if labels else float(grid[gi]),
                                 mean, std,
                                 std / np.sqrt(max(ok.sum(), 1))])
        hdr = f"PartialDependence: {col}" + \
            (f" (target {target})" if target is not None else "") + \
            (f" for row {row_index}" if ice else "")
        out.append(TwoDimTable(
            table_header=hdr,
            col_header=[col, "mean_response", "stddev_response",
                        "std_error_mean_response"],
            col_types=["string" if labels else "double"] + ["double"] * 3,
            cell_values=rows))
    return out


def permutation_varimp(model, fr: Frame, metric: str = "AUTO",
                       n_repeats: int = 1, seed: int = -1) -> TwoDimTable:
    """Permutation feature importance (`hex/PermutationVarImp.java`): metric
    degradation when one feature column is shuffled, per feature."""
    from .metrics import (make_binomial_metrics, make_multinomial_metrics,
                          make_regression_metrics)
    import jax.numpy as jnp

    cat = model.output.model_category
    mname = metric.upper()
    allowed = {"Binomial": ("AUTO", "AUC", "LOGLOSS"),
               "Multinomial": ("AUTO", "LOGLOSS"),
               "Regression": ("AUTO", "RMSE", "MSE")}.get(cat)
    if allowed is None:
        raise ValueError(f"permutation importance is not supported for "
                         f"{cat} models")
    if mname not in allowed:
        raise ValueError(f"metric '{metric}' is not supported for {cat} "
                         f"models (one of {allowed})")
    y_name = model.params.response_column
    y = fr.vec(y_name).to_numpy()

    def score_metric(frame) -> float:
        pred = model.predict(frame)
        if cat == "Binomial":
            p1 = pred.vec(2).to_numpy()
            m = make_binomial_metrics(jnp.asarray(y), jnp.asarray(p1))
            return m.auc if mname in ("AUTO", "AUC") else -m.logloss
        if cat == "Multinomial":
            P = np.stack([pred.vec(i).to_numpy()
                          for i in range(1, pred.ncol)], axis=1)
            m = make_multinomial_metrics(jnp.asarray(y), jnp.asarray(P))
            return -m.logloss
        p = pred.vec(0).to_numpy()
        m = make_regression_metrics(jnp.asarray(y), jnp.asarray(p))
        return -m.rmse if mname in ("AUTO", "RMSE") else -m.mse

    base = score_metric(fr)
    rng = np.random.default_rng(None if seed in (-1, None) else int(seed))
    names = list(model.output.names)
    rows = []
    for col in names:
        v = fr.vec(col)
        x = v.to_numpy().copy()
        deltas = []
        for _ in range(max(1, n_repeats)):
            perm = rng.permutation(fr.nrow)
            shuffled = Frame(list(fr.names),
                             [Vec.from_numpy(x[perm], type=v.type,
                                             domain=v.domain)
                              if n == col else fr.vec(n) for n in fr.names])
            deltas.append(base - score_metric(shuffled))
        rows.append([col, float(np.mean(deltas))])
    imp = np.array([r[1] for r in rows])
    mx = imp.max() if imp.max() > 0 else 1.0
    tot = imp.sum() if imp.sum() > 0 else 1.0
    table_rows = [[r[0], r[1], r[1] / mx, r[1] / tot]
                  for r in sorted(rows, key=lambda r: -r[1])]
    return TwoDimTable(
        table_header="Permutation Variable Importance",
        col_header=["Variable", "Relative Importance", "Scaled Importance",
                    "Percentage"],
        col_types=["string", "double", "double", "double"],
        cell_values=table_rows)
