"""Feature binning for the histogram tree engine.

The reference re-bins every (leaf, column) pair adaptively per tree level
(`hex/tree/DHistogram.java:19-99` UniformAdaptive). That design needs per-level
host decisions and dynamic bin ranges — poison for XLA (recompilation storms,
SURVEY.md §7 "hard parts"). We instead bin once per training run on global
quantiles (the LightGBM/XGBoost-hist design, and what H2O itself does in
`histogram_type="QuantilesGlobal"` — `hex/tree/DHistogram.java` quantiles mode),
which keeps every downstream shape static. Deliberate divergence, documented.

Layout:
- ``edges``  (F, nbins-1) float32 — right-inclusive cut points per feature.
  For categorical columns the "edges" are the category codes 0..card-2, so a
  bin IS a category and split thresholds stay meaningful on raw codes.
- binned matrix (R, F) int8/int32 — bin index in [0, nbins-1]; missing values
  get the dedicated NA bin ``nbins`` (the DHistogram NA bucket analog).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...parallel.mesh import ROWS, default_mesh, n_row_shards, shard_map
from ...utils import telemetry

_UNSET = object()  # "resolve the budget live" sentinel; an explicit None
                   # means "no accelerator budget" and plans at the
                   # conservative _DEFAULT_SKETCH_BUDGET, not unbounded


def _pow2_block(R: int, want: int) -> int:
    """Largest power-of-two divisor of R up to `want` (>= 1 always)."""
    b = 1
    while b * 2 <= want and R % (b * 2) == 0:
        b *= 2
    return b


#: planning fallback when no accelerator budget is resolvable (CPU dev
#: boxes): size the sketch as if on a small chip so the code path that ships
#: is the code path that is tested
_DEFAULT_SKETCH_BUDGET = 4 << 30


def _rows_per_device(R: int) -> int:
    """Rows of an R-row frame that ONE device holds when its rows split
    evenly over several shards (fewer than R: `_sketch_block` then goes
    through `shard_map`), else R. The budget is one device's, so the
    sketch is planned against one device's rows: 44M rows over four chips
    plan like 11M rows on one."""
    ns = n_row_shards()
    return R // ns if ns > 1 and R % ns == 0 else R


#: rows of one step of the sketch's loop at the most: on a v5e a pass over
#: 11M x 28 reads 20.0 ms at 8192, 18.4 at 32768, 18.1 at 65536 (PERF.md,
#: PR 33); frames of a million rows and up are padded to a multiple of 65536
#: a shard (`mesh.padded_len`), so this divides them
_SKETCH_ROW_BLOCK = 32768


def _sketch_digits(nb: int) -> tuple[int, int]:
    """(d_hi, d_lo): the widths of the two digits a bin index splits into,
    ``b = d_lo * hi + lo``. ``d_lo`` is the smallest power of two whose
    square reaches nb and ``d_hi`` covers the rest: 32 x 32 at 1024 (and at
    1000, 24 surplus cells that no index reaches), 16 x 16 at 256, 8 x 8 at
    64."""
    d_lo = 1 << (((nb - 1).bit_length() + 1) // 2)
    return -(-nb // d_lo), d_lo


def _sketch_tile_bytes(rb: int, Fb: int, nb: int) -> int:
    """Bytes one loop step of `_sketch_hist` holds: two bf16 digit one-hots
    and their f32 counts."""
    d_hi, d_lo = _sketch_digits(nb)
    return rb * Fb * (d_hi + d_lo) * 2 + Fb * d_hi * d_lo * 4


def _sketch_plan(R: int, F: int, nb: int,
                 budget_bytes: int | None) -> tuple[int, int]:
    """Pick (rb, Fb) — row-block and feature-block sizes — for the quantile
    sketch from a live HBM budget, so the sketch scales to any (R, F) by
    construction. ``R`` is the rows ONE device holds (`_rows_per_device`).

    Peak footprint the sketch ADDS on top of the caller's (R, F) matrix:
    the f32 (R, Fb) column block it slices out (≤ budget/4), and a loop
    step's tile (≤ budget/8): the two bf16 digit one-hots, (rb, Fb, d_hi)
    and (rb, Fb, d_lo), and the f32 (Fb, d_hi, d_lo) counts they contract
    to. The quantile read-out is noise. At the airlines-116M×31 shape under
    a v5e budget this yields Fb≈7, rb=32768 — 3.3 GB of column block and a
    29 MB tile (the (rb, Fb, nb) f32 one-hot this replaced would be 940 MB
    at that rb). ``rb`` starts at `_SKETCH_ROW_BLOCK` and halves only under
    a budget that small, or down to the first power of two that holds all
    R rows."""
    budget = budget_bytes or _DEFAULT_SKETCH_BUDGET
    col_cap = max(budget // 4, 1 << 20)
    tile_cap = max(budget // 8, 1 << 20)
    Fb = int(min(F, max(1, col_cap // (4 * max(R, 1)))))
    rb = _SKETCH_ROW_BLOCK
    while rb > 64 and (rb // 2 >= R
                       or _sketch_tile_bytes(rb, Fb, nb) > tile_cap):
        rb //= 2
    while Fb > 1 and _sketch_tile_bytes(rb, Fb, nb) > tile_cap:
        Fb = max(1, Fb // 2)
    return rb, Fb


def _sketch_bins(x, lo, span, nb: int):
    """Index of each value's bin among ``nb`` equal bins from ``lo`` (F,)
    over ``span`` (F,): int32 in [0, nb - 1], values outside clipped into
    the edge bins, NaN -> -1 (counted nowhere)."""
    b = jnp.clip(((x - lo[None, :]) / span[None, :] * nb).astype(jnp.int32),
                 0, nb - 1)
    return jnp.where(jnp.isnan(x), -1, b)


def _sketch_hist(X, lo, hi, nb: int, rb: int):
    """(F, nb) f32 counts of `_sketch_bins` over all rows of X (a multiple of
    ``rb`` rows), one loop step a row block, sliced out of X where it lies
    (handed to ``lax.scan`` as ``xs`` the matrix was copied whole first: as
    long again as the counting, and 2.6 GB of temporaries at 11M x 28). A
    block's indices split into two digits (`_sketch_digits`), each digit
    becomes a narrow bf16 one-hot, and ``rfa,rfb->fab`` contracts the two
    over the block's rows on the MXU into f32: per feature a (d_hi, d_lo)
    table whose cell (hi, lo) is bin ``d_lo * hi + lo``'s count. 0/1 is
    exact in bf16 and the sums are f32, so every cell is the exact integer
    an nb-wide one-hot summed over rows gives, for d_hi + d_lo compares a
    value and not nb."""
    R, F = X.shape
    d_hi, d_lo = _sketch_digits(nb)
    shift = d_lo.bit_length() - 1
    span = jnp.maximum(hi - lo, 1e-30)

    def body(i, acc):
        xb = jax.lax.dynamic_slice_in_dim(X, i * rb, rb, axis=0)
        b = _sketch_bins(xb, lo, span, nb)
        # b = -1 has high digit -1: a zero one-hot row, so NaN adds nothing
        oh_hi = jax.nn.one_hot(b >> shift, d_hi, dtype=jnp.bfloat16)
        oh_lo = jax.nn.one_hot(b & (d_lo - 1), d_lo, dtype=jnp.bfloat16)
        return acc + jnp.einsum("rfa,rfb->fab", oh_hi, oh_lo,
                                preferred_element_type=jnp.float32)

    h = jax.lax.fori_loop(0, R // rb, body,
                          jnp.zeros((F, d_hi, d_lo), jnp.float32))
    return h.reshape(F, d_hi * d_lo)[:, :nb]


@telemetry.program("gbm_setup_sketch")
@telemetry.scope("gbm.sketch")
def _sketch_core(X, qs, nb: int = 1024, rb: int = _SKETCH_ROW_BLOCK,
                 axis=None):
    """(nq, F) per-column quantiles via a TWO-PASS histogram sketch, all on
    device over ALL rows.

    ``axis`` names the mesh axis the rows are split over when this runs as
    the body of a ``shard_map`` (`_sharded_sketch`): counts, extrema and
    both histograms then reduce across it, and every shard reads the same
    quantiles. Histogram cells are exact integer counts in f32, so the
    sharded result is bit-identical to the single-device one.

    Replaces the sampled-sort design: a TPU sort program costs ~14 s of XLA
    COMPILE time alone (measured; structural, independent of size), which
    was the single largest item in the GBM cold-start wall. Histograms are
    one-hot einsums — the engine's bread-and-butter shape — and compile in
    ~1 s: `_sketch_hist` contracts two narrow digit one-hots of the bin
    index over a row block's rows on the MXU. Pass 1 spans [min, max];
    pass 2 re-bins inside the [0.1%, 99.9%] bracket (outliers clip into
    edge bins but keep their cumulative mass, the `_leaf_quantile_vals`
    trick), so each quantile is read at (robust span)/nb resolution — far
    finer than the 20-bin edges it feeds.

    Row counts that don't divide ``rb`` are NaN-padded up to the next block
    boundary (NaN rows drop out of every count), so ``rb`` is a free memory
    knob, not a divisibility constraint. Callers stream column blocks
    through this via `hist_quantile_sketch`.
    """
    R, F = X.shape
    pad = (-R) % rb
    if pad:
        X = jnp.concatenate(
            [X, jnp.full((pad, F), jnp.nan, X.dtype)], axis=0)
    ok = ~jnp.isnan(X)
    nval = jnp.sum(ok, axis=0).astype(jnp.float32)
    cmin = jnp.nanmin(X, axis=0)
    cmax = jnp.nanmax(X, axis=0)
    if axis is not None:
        nval = jax.lax.psum(nval, axis)
        # a shard whose slice of a column is all NaN (padding rows) must
        # not poison the global extremum
        cmin = jax.lax.pmin(jnp.where(jnp.isnan(cmin), jnp.inf, cmin), axis)
        cmax = jax.lax.pmax(jnp.where(jnp.isnan(cmax), -jnp.inf, cmax), axis)
        cmin = jnp.where(nval > 0, cmin, jnp.nan)
        cmax = jnp.where(nval > 0, cmax, jnp.nan)

    def hist(lo, hi):
        h = _sketch_hist(X, lo, hi, nb, rb)
        return h if axis is None else jax.lax.psum(h, axis)

    cum1 = jnp.cumsum(hist(cmin, cmax), axis=1)
    span1 = jnp.maximum(cmax - cmin, 1e-30)
    edges1 = (cmin[:, None] + span1[:, None]
              * jnp.arange(1, nb + 1, dtype=jnp.float32)[None, :] / nb)

    def bracket(frac):
        target = frac * nval
        idx = jnp.argmax(cum1 >= target[:, None], axis=1)
        return jnp.take_along_axis(edges1, idx[:, None], axis=1)[:, 0]

    lo2 = jnp.minimum(bracket(0.001) - span1 / nb, cmax)
    hi2 = jnp.maximum(bracket(0.999) + span1 / nb, lo2 + 1e-30)
    h2 = hist(lo2, hi2)
    cum2 = jnp.cumsum(h2, axis=1)
    span2 = jnp.maximum(hi2 - lo2, 1e-30)
    q = jnp.asarray(qs, jnp.float32)[:, None]                 # (nq, 1)
    target = q * jnp.maximum(nval[None, :] - 1.0, 0.0)        # (nq, F)
    # first bin whose cumulative reaches the target, then linear within it
    ge = cum2[None, :, :] >= target[:, :, None]               # (nq, F, nb)
    bidx = jnp.argmax(ge, axis=2)                             # (nq, F)
    cum_before = jnp.where(bidx > 0, jnp.take_along_axis(
        jnp.broadcast_to(cum2[None], ge.shape[:2] + (nb,)),
        jnp.maximum(bidx - 1, 0)[:, :, None], axis=2)[:, :, 0], 0.0)
    cnt = jnp.take_along_axis(
        jnp.broadcast_to(h2[None], ge.shape[:2] + (nb,)),
        bidx[:, :, None], axis=2)[:, :, 0]
    frac = jnp.clip((target - cum_before) / jnp.maximum(cnt, 1e-30), 0, 1)
    out = (lo2[None, :] + (bidx.astype(jnp.float32) + frac)
           * span2[None, :] / nb)
    return jnp.where(nval[None, :] > 0, out, jnp.nan)


_hist_quantile_rows = jax.jit(_sketch_core,
                              static_argnames=("qs", "nb", "rb"))


@functools.lru_cache(maxsize=32)
def _sharded_sketch(mesh, qs, nb: int, rb: int):
    return jax.jit(shard_map(
        functools.partial(_sketch_core, qs=qs, nb=nb, rb=rb, axis=ROWS),
        mesh=mesh, in_specs=P(ROWS, None), out_specs=P(), check_vma=False))


def _sketch_block(X, qs, nb: int, rb: int):
    """One (R, Fb) column block through the sketch. Rows split over several
    shards go through `shard_map` — each device scans ITS rows and the
    (Fb, nb) histograms psum. Left to GSPMD, the scan over row blocks of a
    row-sharded matrix compiled on a four-chip v5e to an all-gather of the
    WHOLE matrix inside every one of its 2 x R/rb iterations (PERF.md)."""
    if _rows_per_device(X.shape[0]) < X.shape[0]:
        return _sharded_sketch(default_mesh(), tuple(qs), nb, rb)(X)
    return _hist_quantile_rows(X, tuple(qs), nb=nb, rb=rb)


def hist_quantile_sketch(X, qs, nb: int = 1024,
                         budget_bytes=_UNSET) -> np.ndarray:
    """Memory-bounded streaming driver for `_hist_quantile_rows`: columns go
    through the two-pass sketch in blocks of Fb, with (rb, Fb) planned from
    the live HBM budget (`_sketch_plan`), so the (R, Fb) column block and
    the per-step digit one-hots ((rb, Fb, d_hi) and (rb, Fb, d_lo)) never
    exceed memory at any (R, F) — 116M×31 included. Each column's quantiles
    depend only on that column, so blocking is exact, not an approximation.
    Returns the host (nq, F) array (the only thing that crosses back)."""
    if budget_bytes is _UNSET:
        from ...backend.memory import hbm_budget_bytes

        budget_bytes = hbm_budget_bytes()
    R, F = X.shape
    rb, Fb = _sketch_plan(_rows_per_device(R), F, nb, budget_bytes)
    if Fb >= F:
        return np.asarray(_sketch_block(X, qs, nb, rb))
    out = np.empty((len(qs), F), np.float32)
    for f0 in range(0, F, Fb):
        # no donation: the only outputs are (nq, Fb) quantiles, so XLA has
        # nothing to alias an (R, Fb) input to — on the v5e a donated block
        # bought one "donated buffers were not usable" warning per call;
        # the block is freed when this reference drops
        blk = jnp.asarray(X[:, f0:f0 + Fb])
        out[:, f0:f0 + Fb] = np.asarray(_sketch_block(blk, qs, nb, rb))
    return out


def _coldata(c):
    """Column handle -> device array: Vecs (coded ones decode on access)
    or plain arrays both work, so callers can stream straight off a Frame."""
    return c.data if hasattr(c, "data") else jnp.asarray(c)


def _col_plen(c) -> int:
    return int(c.plen) if hasattr(c, "plen") else int(jnp.asarray(c).shape[0])


def hist_quantile_sketch_cols(cols, qs, nb: int = 1024,
                              budget_bytes=_UNSET) -> np.ndarray:
    """`hist_quantile_sketch` fed from PER-COLUMN Vecs/arrays — the raw
    (R, F) matrix is never stacked. The (rb, Fb) plan is the one the stacked
    driver would pick for the same (R, F, budget) and columns stream through
    the two-pass sketch in the same Fb-sized blocks, so the output is
    bit-identical to the stacked path (histogram cells are exact integer
    counts in f32 — accumulation order can't perturb them)."""
    if budget_bytes is _UNSET:
        from ...backend.memory import hbm_budget_bytes

        budget_bytes = hbm_budget_bytes()
    cols = list(cols)
    F = len(cols)
    R = _col_plen(cols[0])
    rb, Fb = _sketch_plan(_rows_per_device(R), F, nb, budget_bytes)
    out = np.empty((len(qs), F), np.float32)
    for f0 in range(0, F, Fb):
        blk = jnp.stack([_coldata(c) for c in cols[f0:f0 + Fb]], axis=1)
        out[:, f0:f0 + Fb] = np.asarray(_sketch_block(blk, qs, nb, rb))
    return out


@jax.jit
@telemetry.program("gbm_setup_minmax")
def _col_minmax(X):
    return jnp.nanmin(X, axis=0), jnp.nanmax(X, axis=0)


@functools.partial(jax.jit, static_argnames=("cap",))
@telemetry.program("gbm_setup_distinct")
def _distinct_values(X, cap: int):
    """Per-column distinct values, on device: (cap, F) ascending and
    NaN-padded, plus the true (F,) distinct counts (which may exceed cap —
    callers treat such columns as continuous). One sort + scatter."""
    R, F = X.shape
    S = jnp.sort(X, axis=0)  # NaN to the end
    new = jnp.concatenate(
        [jnp.ones((1, F), bool), S[1:] != S[:-1]], axis=0) & ~jnp.isnan(S)
    counts = new.sum(axis=0)
    pos = jnp.cumsum(new, axis=0) - 1
    rows = jnp.where(new, jnp.minimum(pos, cap - 1), cap)  # cap = dump slot
    out = jnp.full((cap + 1, F), jnp.nan, jnp.float32)
    cols = jnp.broadcast_to(jnp.arange(F), (R, F))
    out = out.at[rows, cols].set(S.astype(jnp.float32), mode="drop")
    return out[:cap], counts


#: rows at or below which small-data exact binning may engage (env override)
def _exact_bin_row_limit() -> int:
    from ...utils.knobs import get_int

    return get_int("H2O_TPU_EXACT_BIN_ROWS")


#: the histogram types whose cuts are read off the quantile sketch
_QUANTILE_HT = ("auto", "quantilesglobal", "exact")


def _validate_ht(histogram_type: str) -> str:
    ht = (histogram_type or "AUTO").lower()
    if ht not in ("auto", "quantilesglobal", "uniformadaptive", "random",
                  "exact"):
        raise ValueError(
            f"unsupported histogram_type '{histogram_type}' — supported: "
            f"AUTO, QuantilesGlobal, UniformAdaptive, Random, Exact")
    return ht


def _wants_exact(ht: str, R: int, nbins: int, nbins_top_level: int) -> bool:
    """Small-data exact binning engagement rule (see compute_bin_edges)."""
    return (ht == "exact"
            or (R <= _exact_bin_row_limit() and nbins_top_level > nbins
                and ht in ("auto", "quantilesglobal", "uniformadaptive")))


def sketch_span_attrs(R: int, F: int, histogram_type: str = "AUTO") -> dict:
    """``train.gbm.sketch``'s attributes: the plan the quantile sketch of an
    (R, F) frame's edges runs under, from shapes and the live budget as
    `hist_quantile_sketch` resolves it — the digit widths of the count
    contraction, the rows of a loop step, the column blocks streamed and a
    device's loop steps in one pass over one of them. Empty where the
    histogram type reads no quantiles."""
    if F == 0 or _validate_ht(histogram_type) not in _QUANTILE_HT:
        return {}
    from ...backend.memory import hbm_budget_bytes

    nb = 1024                   # `hist_quantile_sketch`'s, which no caller sets
    rows = _rows_per_device(R)
    rb, Fb = _sketch_plan(rows, F, nb, hbm_budget_bytes())
    return {"sketch_digits": "%dx%d" % _sketch_digits(nb),
            "sketch_row_block": rb, "sketch_col_blocks": -(-F // Fb),
            "sketch_scan_steps": -(-rows // rb)}


def _edges_from_stats(F, is_cat, col_min, col_max, qrows, exact, ht,
                      nbins, nbins_top_level, nbins_cats,
                      seed) -> np.ndarray:
    """Per-feature cut assembly from host-side column stats — the shared
    tail of `compute_bin_edges` (stacked matrix) and
    `compute_bin_edges_cols` (per-column streaming)."""
    all_cuts: list = []
    for f in range(F):
        if not np.isfinite(col_max[f]):  # all-NaN column
            all_cuts.append(np.zeros(0, np.float32))
            continue
        if exact is not None and not is_cat[f] and \
                0 < int(exact[1][f]) <= nbins_top_level:
            u = exact[0][:int(exact[1][f]), f].astype(np.float64)
            cuts = ((u[:-1] + u[1:]) / 2).astype(np.float32)
            all_cuts.append(cuts)
            continue
        if is_cat[f]:
            # one bin per level, capped by nbins_cats: cuts at codes
            # 0..min(card, nbins_cats)-2 so bin = min(level, n_cuts)
            card = int(col_max[f]) + 1
            cuts = np.arange(min(card - 1, nbins_cats - 1), dtype=np.float32)
        elif ht == "uniformadaptive":
            lo, hi = float(col_min[f]), float(col_max[f])
            cuts = (np.unique(np.linspace(lo, hi, nbins + 1)[1:-1]
                              .astype(np.float32)) if hi > lo
                    else np.zeros(0, np.float32))
        elif ht == "random":
            lo, hi = float(col_min[f]), float(col_max[f])
            rrng = np.random.default_rng(seed + 7919 * f)
            cuts = (np.unique(rrng.uniform(lo, hi, nbins - 1)
                              .astype(np.float32)) if hi > lo
                    else np.zeros(0, np.float32))
        else:  # AUTO / QuantilesGlobal
            col = qrows[:, f]
            cuts = np.unique(col[~np.isnan(col)].astype(np.float32))
        all_cuts.append(cuts)
    width = max(nbins - 1, max((len(c) for c in all_cuts), default=0))
    edges = np.full((F, width), np.nan, dtype=np.float32)
    for f, cuts in enumerate(all_cuts):
        edges[f, : len(cuts)] = cuts
    return edges


def compute_bin_edges(X: jax.Array, is_cat: np.ndarray, nbins: int,
                      sample: int = 200_000, seed: int = 1234,
                      histogram_type: str = "QuantilesGlobal",
                      nbins_top_level: int = 1024,
                      nbins_cats: int = 1024) -> np.ndarray:
    """Global bin edges per feature.

    ``histogram_type`` mirrors `hex/tree/SharedTreeModel.HistogramType`:
    AUTO/QuantilesGlobal → sampled global quantiles (this engine's default —
    bins adapt to the data distribution); UniformAdaptive → equal-width
    between per-feature min/max; Random → uniform random cut points (the
    extremely-randomized-trees flavor). Categorical features always bin on
    their category codes, one bin per level up to ``nbins_cats`` bins
    (`hex/tree/SharedTreeModel.java:57` nbins_cats — the categorical
    histogram width; levels at/above the cap share the top bin).

    X: (R, F) padded feature matrix (NaN = NA/padding). Quantiles come from
    the two-pass device histogram sketch over ALL rows (see
    `_hist_quantile_rows` — the reference's QuantilesGlobal samples; we can
    afford exhaustive because the sketch is one-hot matmuls) — only the
    (F, nbins-1) result crosses to the host. ``sample``/``seed`` are kept
    for API compatibility (the sketch is deterministic and sample-free).
    Returns (F, nbins-1) float32 edges, NaN-padded where a feature has fewer
    distinct cut points.
    """
    ht = _validate_ht(histogram_type)
    Xj = jnp.asarray(X)
    R, F = Xj.shape
    # Small-data exact binning — the `nbins_top_level` role: the reference's
    # DHistogram re-bins each node at up to 1024 cuts, so on small data its
    # splits are effectively exact. Matching that with static shapes: when
    # the dataset is small and a column's distinct count fits under
    # nbins_top_level, its cuts are the exact midpoints BETWEEN distinct
    # values; high-cardinality columns keep the sampled-quantile cuts. Big
    # data (above H2O_TPU_EXACT_BIN_ROWS) is untouched — histogram cost
    # scales with the bin-axis length, and 20 global quantile bins is the
    # measured-fast design there.
    exact = None
    if _wants_exact(ht, R, nbins, nbins_top_level):
        # "Exact" (the single-DT mode, `hex/tree/dt/DT.java`'s per-value
        # search): exact midpoints at ANY row count; columns above the
        # nbins_top_level distinct-value cap fall back to global quantiles
        vals, counts = _distinct_values(Xj, int(nbins_top_level))
        exact = (np.asarray(vals), np.asarray(counts))
    qs = np.linspace(0, 1, nbins + 1)[1:-1]
    col_min, col_max = (np.asarray(v) for v in _col_minmax(Xj))
    qrows = None
    if ht in _QUANTILE_HT:
        qrows = hist_quantile_sketch(Xj, tuple(qs))
    return _edges_from_stats(F, is_cat, col_min, col_max, qrows, exact, ht,
                             nbins, nbins_top_level, nbins_cats, seed)


def compute_bin_edges_cols(cols, is_cat: np.ndarray, nbins: int,
                           sample: int = 200_000, seed: int = 1234,
                           histogram_type: str = "QuantilesGlobal",
                           nbins_top_level: int = 1024,
                           nbins_cats: int = 1024,
                           budget_bytes=_UNSET) -> np.ndarray:
    """`compute_bin_edges` fed from per-column Vecs/arrays — the chunk-store
    ingest path: the raw (R, F) f32 matrix is NEVER stacked. Column stats
    (min/max, small-data distinct values, quantile sketch) stream through
    device programs in Fb-sized column blocks planned from the live HBM
    budget; each column's cuts depend only on that column and on exact
    integer histogram counts, so the result is bit-identical to the stacked
    path on the same data."""
    ht = _validate_ht(histogram_type)
    if budget_bytes is _UNSET:
        from ...backend.memory import hbm_budget_bytes

        budget_bytes = hbm_budget_bytes()
    cols = list(cols)
    F = len(cols)
    if F == 0:
        return np.zeros((0, max(nbins - 1, 0)), np.float32)
    R = _col_plen(cols[0])
    _, Fb = _sketch_plan(_rows_per_device(R), F, 1024, budget_bytes)
    col_min = np.empty(F, np.float32)
    col_max = np.empty(F, np.float32)
    exact = None
    if _wants_exact(ht, R, nbins, nbins_top_level):
        exact = (np.empty((int(nbins_top_level), F), np.float32),
                 np.empty(F, np.int64))
    for f0 in range(0, F, Fb):
        blk = jnp.stack([_coldata(c) for c in cols[f0:f0 + Fb]], axis=1)
        mn, mx = _col_minmax(blk)
        col_min[f0:f0 + Fb] = np.asarray(mn)
        col_max[f0:f0 + Fb] = np.asarray(mx)
        if exact is not None:
            vals, counts = _distinct_values(blk, int(nbins_top_level))
            exact[0][:, f0:f0 + Fb] = np.asarray(vals)
            exact[1][f0:f0 + Fb] = np.asarray(counts)
    qs = np.linspace(0, 1, nbins + 1)[1:-1]
    qrows = None
    if ht in _QUANTILE_HT:
        qrows = hist_quantile_sketch_cols(cols, tuple(qs),
                                          budget_bytes=budget_bytes)
    return _edges_from_stats(F, is_cat, col_min, col_max, qrows, exact, ht,
                             nbins, nbins_top_level, nbins_cats, seed)


@jax.jit
@telemetry.program("gbm_setup_bin")
@telemetry.scope("gbm.bin")
def bin_matrix(X: jax.Array, edges: jax.Array) -> jax.Array:
    """Map raw values to bin indices: bin = #edges < x; NA -> nbins (NA bucket).

    One vectorized compare-and-sum — (R, F, nbins-1) broadcast, XLA fuses it.
    """
    nbins = edges.shape[1] + 1
    cmp = X[:, :, None] > edges[None, :, :]  # NaN compares false
    b = jnp.sum(cmp, axis=2, dtype=jnp.int32)
    # int32 deliberately: an int8 variant (C1Chunk-style packing) measured 5x
    # SLOWER end-to-end on v5e when the one-hots consumed int8 DIRECTLY —
    # sub-word (32,128) tiling forces relayouts in every one-hot. The
    # chunk-store binned view (frame/chunks.py BinnedView) gets the HBM
    # savings anyway by storing int8 and upcasting per row-block inside the
    # engine's histogram scan (engine._build_level_hist), where the convert
    # is VMEM-granular and fuses.
    return jnp.where(jnp.isnan(X), nbins, b).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("dtype",))
@telemetry.program("gbm_setup_bin")
@telemetry.scope("gbm.bin")
def bin_column(x: jax.Array, erow: jax.Array, dtype=jnp.int32) -> jax.Array:
    """One column of `bin_matrix`: (plen,) raw values + that feature's
    NaN-padded edge row -> bin codes in ``dtype`` (the BinnedView packer).
    Identical values to the stacked kernel — same compare-and-sum, NA (and
    padding) to the ``nbins`` bucket — just never materializing (R, F)."""
    nbins = erow.shape[0] + 1
    b = jnp.sum(x[:, None] > erow[None, :], axis=1, dtype=jnp.int32)
    return jnp.where(jnp.isnan(x), nbins, b).astype(dtype)
