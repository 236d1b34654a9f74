"""Column-blocked streaming quantile sketch (`binning.hist_quantile_sketch`)
— the memory-bounded replacement for the unblocked `_hist_quantile_rows`
that OOM'd the Airlines-116M leg in round 5. Covers the budget-driven
(rb, Fb) plan against a mocked v5e HBM budget, exactness of blocking, odd
row counts, NA/constant columns, and the compute_bin_edges integration."""

import warnings

import numpy as np
import pytest

from h2o_tpu.models.tree import binning

V5E_BUDGET = int(16 * (1 << 30) * 0.85)  # v5e HBM × the Cleaner headroom


def test_sketch_plan_airlines_shape_fits_v5e_budget():
    """116M×31 (the north-star airlines leg): the planned intermediates —
    the streamed (R, Fb) column block and a loop step's tile (two bf16
    digit one-hots and their f32 counts) — stay inside their budget
    fractions by construction."""
    R, F, nb = 116_000_000, 31, 1024
    rb, Fb = binning._sketch_plan(R, F, nb, V5E_BUDGET)
    assert 1 <= Fb < F          # must block: the full matrix can't re-slice
    assert rb == binning._SKETCH_ROW_BLOCK     # the tile is no constraint
    assert R * Fb * 4 <= V5E_BUDGET // 4          # column block
    tile = rb * Fb * (32 + 32) * 2 + Fb * 32 * 32 * 4
    assert binning._sketch_tile_bytes(rb, Fb, nb) == tile
    assert tile <= V5E_BUDGET // 8                # per-step tile
    assert tile < 32 << 20      # 29 MB where an nb-wide f32 one-hot is 940


def test_sketch_plan_scales_to_any_shape():
    for R, F in [(100, 3), (10**9, 1000), (7, 1), (50_000_000, 64)]:
        rb, Fb = binning._sketch_plan(R, F, 1024, V5E_BUDGET)
        assert 1 <= Fb <= F and 64 <= rb <= binning._SKETCH_ROW_BLOCK
        assert R * Fb * 4 <= V5E_BUDGET // 4 or Fb == 1
        # few rows: one block that holds them, not 32768 rows of padding
        assert rb == binning._SKETCH_ROW_BLOCK or rb // 2 < max(R, 64)


def test_sketch_plan_tiny_budget_degrades_to_single_columns():
    rb, Fb = binning._sketch_plan(1_000_000, 64, 1024, 1 << 20)
    assert Fb == 1
    assert rb == 4096           # shrunk to the tile cap, floored at 64
    assert binning._sketch_tile_bytes(rb, Fb, 1024) <= 1 << 20
    assert binning._sketch_tile_bytes(2 * rb, Fb, 1024) > 1 << 20


@pytest.mark.parametrize("nb,digits", [
    (1024, (32, 32)), (1000, (32, 32)), (256, (16, 16)), (64, (8, 8)),
    (512, (16, 32)), (2, (1, 2)), (1, (1, 1))])
def test_sketch_digits_cover_nb_with_a_power_of_two_low_digit(nb, digits):
    d_hi, d_lo = binning._sketch_digits(nb)
    assert (d_hi, d_lo) == digits
    assert d_lo & (d_lo - 1) == 0 and d_hi * d_lo >= nb > (d_hi - 1) * d_lo


@pytest.mark.parametrize("F", [1, 3, 28, 31])
@pytest.mark.parametrize("nb", [64, 256, 1000, 1024])
def test_sketch_counts_equal_bincount_exactly(nb, F):
    """A pass's (F, nb) counts — two digit one-hots contracted over each
    row block — are `np.bincount` of the same bin indices, cell for cell:
    NaNs (and the padding rows of the last block) count nowhere, an all-NaN
    column is all zeros, a constant column is one cell."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(1000 * nb + F)
    R, rb = 1531, 256                    # six blocks, the last one padded
    X = rng.normal(size=(R, F)).astype(np.float32)
    X[rng.random((R, F)) < 0.05] = np.nan
    if F >= 3:
        X[:, 1] = np.nan
        X[:, 2] = -4.25
    Xp = np.concatenate(
        [X, np.full(((-R) % rb, F), np.nan, np.float32)], axis=0)
    with warnings.catch_warnings():      # "All-NaN slice": column 1
        warnings.simplefilter("ignore", RuntimeWarning)
        lo, hi = np.nanmin(X, axis=0), np.nanmax(X, axis=0)
    h = np.asarray(jax.jit(binning._sketch_hist, static_argnames=("nb", "rb"))(
        Xp, lo, hi, nb=nb, rb=rb))
    b = np.asarray(binning._sketch_bins(
        jnp.asarray(X), jnp.asarray(lo), jnp.maximum(hi - lo, 1e-30), nb))
    assert h.shape == (F, nb) and h.dtype == np.float32
    assert b.min() == -1 and b.max() == nb - 1
    want = np.stack([np.bincount(b[:, f][b[:, f] >= 0], minlength=nb)
                     for f in range(F)])
    np.testing.assert_array_equal(h, want.astype(np.float32))
    assert h.sum() == np.count_nonzero(~np.isnan(X))
    if F >= 3:
        assert not h[1].any()
        assert h[2, 0] == R and h[2].sum() == R


#: `hist_quantile_sketch(X, QS_FROZEN, budget_bytes=None)` of the frame
#: below at commit ddd22d2 (PR 32), where a pass's counts were
#: `sum(one_hot(b, 1024), axis=0)` in 1024-row blocks
QS_FROZEN = (0.05, 0.25, 0.5, 0.75, 0.95)
FROZEN = np.array(
    [[-1.6303430e+00, 1.9175678e-01, -1.6494994e+00, 7.5, np.nan],
     [-6.7520618e-01, 5.1503599e-01, -6.5637970e-01, 7.5, np.nan],
     [-6.9518089e-03, 9.9423748e-01, 1.6892195e-02, 7.5, np.nan],
     [6.6196489e-01, 1.9625468e+00, 6.7429924e-01, 7.5, np.nan],
     [1.6260235e+00, 5.2258010e+00, 1.6411405e+00, 7.5, np.nan]],
    dtype=np.float32)


def test_sketch_quantiles_are_the_parents_bit_for_bit():
    """The counts are the same integers however they are summed, so the
    quantiles read off them are the ones the VPU form gave, to the bit."""
    rng = np.random.default_rng(33)
    X = rng.normal(size=(20011, 5)).astype(np.float32)
    X[:, 1] = np.exp(X[:, 1])          # skewed: pass 2's bracket matters
    X[::9, 2] = np.nan
    X[:, 3] = 7.5
    X[:, 4] = np.nan
    out = binning.hist_quantile_sketch(X, QS_FROZEN, budget_bytes=None)
    assert out.dtype == np.float32
    np.testing.assert_array_equal(out, FROZEN)


def test_sketch_matches_numpy_quantiles_odd_rows_nans_consts():
    rng = np.random.default_rng(0)
    R = 9973  # prime: no power-of-two block divides it
    X = rng.normal(size=(R, 5)).astype(np.float32)
    X[::7, 2] = np.nan
    X[:, 4] = 3.0
    qs = tuple(np.linspace(0, 1, 21)[1:-1])
    out = binning.hist_quantile_sketch(X, qs, budget_bytes=None)
    assert out.shape == (len(qs), 5)
    ref = np.nanquantile(X, qs, axis=0)
    # sketch resolution is (robust span)/nb per pass-2 bin
    assert np.nanmax(np.abs(out - ref)) < 0.02
    assert np.all(out[:, 4] == 3.0)


def test_blocked_sketch_is_exact_not_approximate():
    """Column blocking must be a pure memory transform: each column's
    quantiles depend only on that column, so a blocked run at the same rb
    matches the unblocked one to float associativity (XLA fuses the
    reductions differently per shape — ≤1 ulp), orders of magnitude below
    the sketch's own (span/nb) resolution."""
    rng = np.random.default_rng(1)
    R = 131072
    X = np.abs(rng.normal(size=(R, 6))).astype(np.float32)
    qs = tuple(np.linspace(0, 1, 11)[1:-1])
    # budget sized so col_cap = budget/4 allows exactly 2 columns per block
    budget = 2 * 4 * R * 4
    rb, Fb = binning._sketch_plan(R, 6, 256, budget)
    assert Fb == 2
    blocked = binning.hist_quantile_sketch(X, qs, nb=256,
                                           budget_bytes=budget)
    full = np.asarray(binning._hist_quantile_rows(X, qs, nb=256, rb=rb))
    assert np.max(np.abs(blocked - full)) < 1e-6


def test_hist_quantile_rows_pads_odd_row_counts():
    rng = np.random.default_rng(2)
    X = rng.normal(size=(1000, 3)).astype(np.float32)  # 1000 % 512 != 0
    qs = (0.25, 0.5, 0.75)
    out = np.asarray(binning._hist_quantile_rows(X, qs, nb=256, rb=512))
    ref = np.quantile(X, qs, axis=0)
    assert np.max(np.abs(out - ref)) < 0.05


def test_compute_bin_edges_streams_above_exact_limit(monkeypatch):
    """Force the big-data path (sketch, not exact midpoints) at small R and
    with a tight mocked budget, so the streamed loop is what is tested."""
    monkeypatch.setenv("H2O_TPU_EXACT_BIN_ROWS", "100")
    monkeypatch.setenv("H2O_TPU_HBM_LIMIT_BYTES", str(4000 * 2 * 4 * 4))
    rng = np.random.default_rng(3)
    X = rng.normal(size=(4000, 6)).astype(np.float32)
    edges = binning.compute_bin_edges(X, np.zeros(6, bool), 20)
    assert edges.shape[0] == 6
    for f in range(6):
        cuts = edges[f][~np.isnan(edges[f])]
        assert len(cuts) >= 15
        assert np.all(np.diff(cuts) >= 0)
        assert abs(cuts[len(cuts) // 2]) < 0.1  # median cut near 0


def test_sharded_sketch_is_bit_equal_to_single_device():
    """Rows split over the mesh go through shard_map (per-shard scan +
    psum); histogram cells are exact counts, so the quantiles must match
    the single-device program bit for bit — including a column whose rows
    on one shard are all NaN (padding) and an all-NaN column."""
    from h2o_tpu.parallel.mesh import default_mesh, n_row_shards

    rng = np.random.default_rng(4)
    ns = n_row_shards(default_mesh())
    R = ns * 1536                       # 1.5 blocks per shard: pads inside
    X = rng.normal(size=(R, 4)).astype(np.float32)
    X[::5, 1] = np.nan
    X[-R // ns:, 2] = np.nan            # last shard: nothing but NaN
    X[:, 3] = np.nan
    qs = tuple(np.linspace(0, 1, 21)[1:-1])
    single = np.asarray(binning._hist_quantile_rows(X, qs, nb=256, rb=1024))
    sharded = np.asarray(binning._sketch_block(X, qs, 256, 1024))
    assert ns > 1
    np.testing.assert_array_equal(sharded, single)
    assert np.isnan(single[:, 3]).all() and np.isfinite(single[:, :3]).all()
