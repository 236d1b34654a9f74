"""Compressed columnar chunk store (`frame/chunks.py`).

Pins the subsystem's three contracts:
- codec ROUND-TRIP BIT-EQUALITY per chunk type (const / int8 / int16 /
  cat / sparse-zero / raw fallback), NaN- and -0.0-aware;
- the int8 binned view: per-column edges and codes bit-identical to the
  stacked `compute_bin_edges` + `bin_matrix` path, and a GBM trained from
  the binned view producing a bit-equal forest (hence bit-equal
  predictions) to the raw-matrix path on the CPU mesh;
- Cleaner residency: coded bytes tracked, budget-driven eviction of coded
  columns with transparent rehydrate+decode.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from h2o_tpu.frame.chunks import (BinnedView, CodedVec, compress_frame,
                                  decode_chunk, encode_column)
from h2o_tpu.frame.frame import Frame
from h2o_tpu.frame.vec import T_CAT, Vec
from h2o_tpu.models.tree.binning import (bin_column, bin_matrix,
                                         compute_bin_edges,
                                         compute_bin_edges_cols)

pytestmark = pytest.mark.chunks


def _bits_eq(a, b):
    a = np.ascontiguousarray(a, np.float32)
    b = np.ascontiguousarray(b, np.float32)
    same = a.view(np.int32) == b.view(np.int32)
    return bool(np.all(same | (np.isnan(a) & np.isnan(b))))


# ---------------------------------------------------------------------------
# codecs
# ---------------------------------------------------------------------------
def _col_cases():
    rng = np.random.default_rng(3)
    n = 1000
    cases = {
        "const": np.full(n, 2.5, np.float32),
        "const_nan": np.full(n, np.nan, np.float32),
        "int8": rng.integers(-3, 250, n).astype(np.float32),
        "int8_na": np.where(rng.random(n) < 0.1, np.nan,
                            rng.integers(0, 100, n)).astype(np.float32),
        "int8_scaled": (rng.integers(0, 200, n) * 0.25 + 7.0
                        ).astype(np.float32),
        "int16": rng.integers(0, 40_000, n).astype(np.float32),
        "sparse0": np.where(rng.random(n) < 0.03,
                            rng.normal(size=n), 0.0).astype(np.float32),
        "raw": rng.normal(size=n).astype(np.float32),
    }
    cases["sparse0"][::97] = np.nan      # sparse with NA entries
    cases["sparse0"][5] = -0.0           # sign bit must survive
    cases["raw"][::31] = np.nan
    return cases


@pytest.mark.parametrize("name", list(_col_cases()))
def test_codec_roundtrip_bit_equality(name):
    col = _col_cases()[name]
    v = Vec.from_numpy(col)
    cv = CodedVec.from_vec(v)
    expect_kind = {"const": "const", "const_nan": "const", "int8": "int8",
                   "int8_na": "int8", "int8_scaled": "int8",
                   "int16": "int16", "sparse0": "sparse0", "raw": "raw"}
    if expect_kind[name] == "raw":
        assert cv is v, "no codec wins -> the original Vec passes through"
        return
    assert isinstance(cv, CodedVec)
    assert cv.meta.kind == expect_kind[name]
    # full padded round trip (padding rows are NaN, like the source Vec)
    assert _bits_eq(np.asarray(cv.data), np.asarray(v.data))
    # logical view too
    assert _bits_eq(cv.to_numpy(), col)
    # the coded payload is strictly smaller than 4 B/row f32
    assert cv.coded_nbytes() < v.data.size * 4


def test_categorical_codec_labelled_and_domain_kept():
    codes = np.array([0, 1, 2, 1, 0, np.nan, 2], np.float32)
    v = Vec.from_numpy(codes, type=T_CAT, domain=["a", "b", "c"])
    cv = CodedVec.from_vec(v)
    assert cv.meta.kind == "cat8"
    assert cv.domain == ["a", "b", "c"] and cv.is_categorical()
    assert _bits_eq(np.asarray(cv.data), np.asarray(v.data))


def test_coded_vec_setter_degrade_updates_plen():
    """Overwriting a CodedVec's data degrades the codec to raw — and plen
    must track the NEW buffer, or ensure_rollups' same-plen stacking groups
    the vec with columns of the stale length."""
    v = Vec.from_numpy(np.arange(64, dtype=np.float32))
    cv = CodedVec.from_vec(v)
    old_plen = cv.plen
    new = jnp.zeros(old_plen * 2, jnp.float32)
    cv.data = new
    assert cv.meta.kind == "raw"
    assert cv.plen == old_plen * 2 == cv.data.shape[0]


def test_encode_column_padding_rows_stay_nan():
    col = np.arange(64, dtype=np.float32)
    buf = np.full(96, np.nan, np.float32)  # 32 padding rows
    buf[:64] = col
    coded, meta = encode_column(buf, nrow=64)
    assert meta.kind == "int8"
    dec = np.asarray(decode_chunk(jnp.asarray(coded), meta))
    assert _bits_eq(dec, buf)
    assert np.isnan(dec[64:]).all()


def test_compressed_rollups_from_codes():
    rng = np.random.default_rng(11)
    col = np.where(rng.random(2000) < 0.05, np.nan,
                   rng.integers(0, 200, 2000) * 0.5 - 10).astype(np.float32)
    v = Vec.from_numpy(col)
    cv = CodedVec.from_vec(v)
    assert cv.meta.kind == "int8"
    r, rc = v.rollups(), cv.rollups()
    assert rc.nacnt == r.nacnt and rc.nrow == r.nrow
    assert rc.zerocnt == r.zerocnt
    np.testing.assert_allclose([rc.mins, rc.maxs], [r.mins, r.maxs],
                               rtol=1e-6)
    np.testing.assert_allclose([rc.mean, rc.sigma], [r.mean, r.sigma],
                               rtol=1e-4)


def test_compress_frame_and_batched_rollups():
    rng = np.random.default_rng(7)
    fr = Frame.from_dict({
        "ints": rng.integers(0, 50, 3000).astype(np.float32),
        "const": np.full(3000, 1.5, np.float32),
        "real": rng.normal(size=3000).astype(np.float32),
    })
    cfr = fr.compress()
    kinds = {n: getattr(cfr.vec(n), "meta", None) and cfr.vec(n).meta.kind
             for n in cfr.names}
    assert kinds["ints"] == "int8" and kinds["const"] == "const"
    assert kinds["real"] is None  # raw passthrough keeps the plain Vec
    cfr.ensure_rollups()          # code-space stats + decode-path batch
    for n in fr.names:
        np.testing.assert_allclose(cfr.vec(n).rollups().mean,
                                   fr.vec(n).rollups().mean, rtol=1e-4)
        assert _bits_eq(np.asarray(cfr.vec(n).data), np.asarray(fr.vec(n).data))


# ---------------------------------------------------------------------------
# Cleaner residency: tracked bytes + budget-driven eviction
# ---------------------------------------------------------------------------
@pytest.fixture()
def fresh_cleaner(monkeypatch):
    """Hermetic Cleaner: Vec construction imports memory.CLEANER at call
    time, so swapping the module attribute isolates the ledger from every
    other test's still-live vecs."""
    from h2o_tpu.backend import memory

    c = memory.Cleaner()
    monkeypatch.setattr(memory, "CLEANER", c)
    yield c, monkeypatch


def test_coded_bytes_tracked_and_evicted_under_budget(fresh_cleaner):
    cleaner, monkeypatch = fresh_cleaner
    rng = np.random.default_rng(0)
    cols = [rng.integers(0, 200, 1000).astype(np.float32) for _ in range(5)]
    coded = [CodedVec.from_vec(Vec.from_numpy(c)) for c in cols]
    assert all(cv.meta.kind == "int8" for cv in coded)
    # the Cleaner ledger carries the CODED bytes (hbm_budget_bytes honesty)
    assert cleaner.tracked_bytes() >= sum(cv.coded_nbytes() for cv in coded)

    # pin a budget two coded columns short -> the coldest coded columns spill
    monkeypatch.setenv("H2O_TPU_HBM_LIMIT_BYTES",
                       str(cleaner.tracked_bytes()
                           - 2 * coded[0].coded_nbytes() + 1))
    cleaner.maybe_sweep()
    spilled = [cv for cv in coded if cv._data is None and cv._spill_path]
    assert spilled, "over-budget coded columns must spill"
    assert coded[0] in spilled, "LRU: the coldest coded column goes first"
    # transparent rehydrate + decode: values bit-identical after the cycle
    for cv, src in zip(coded, cols):
        assert _bits_eq(cv.to_numpy(), src)
        assert cv._data is not None and cv._spill_path is None
    monkeypatch.delenv("H2O_TPU_HBM_LIMIT_BYTES")


def test_binned_view_pinned_never_spills(fresh_cleaner):
    """A live BinnedView's buffer is held by the trainer — spilling it
    would debit the ledger and pay an ice write while freeing no HBM, so
    the sweep must skip pinned views and take unpinned columns instead."""
    cleaner, monkeypatch = fresh_cleaner
    rng = np.random.default_rng(1)
    col = rng.normal(size=2048).astype(np.float32)
    vec = Vec.from_numpy(col)
    edges = compute_bin_edges(vec.data[:, None], np.array([False]), 8,
                              seed=1)
    view = BinnedView.build([vec], edges)
    victim = Vec.from_numpy(rng.normal(size=2048).astype(np.float32))
    monkeypatch.setenv("H2O_TPU_HBM_LIMIT_BYTES", "1")
    cleaner.maybe_sweep()
    assert view._data is not None, "pinned binned view must stay resident"
    assert victim._data is None, "unpinned columns still spill"


def test_sparse_coded_vec_rehydrates_replicated(fresh_cleaner):
    cleaner, monkeypatch = fresh_cleaner
    col = np.zeros(4000, np.float32)
    # random reals at the sparse positions: no affine int code covers them,
    # so the sparse-zero codec is the winner
    col[::203] = np.random.default_rng(2).normal(size=col[::203].shape)
    cv = CodedVec.from_vec(Vec.from_numpy(col))
    assert cv.meta.kind == "sparse0"
    monkeypatch.setenv("H2O_TPU_HBM_LIMIT_BYTES", "1")
    cleaner.maybe_sweep()
    assert cv._data is None and cv._spill_path
    monkeypatch.delenv("H2O_TPU_HBM_LIMIT_BYTES")
    assert _bits_eq(cv.to_numpy(), col)  # (2, nnz) payload reloads fine


# ---------------------------------------------------------------------------
# binned view: edges + codes + GBM parity
# ---------------------------------------------------------------------------
def _mixed_frame(n=1500, seed=5, wide_cat=False):
    rng = np.random.default_rng(seed)
    card = 200 if wide_cat else 12
    cols = {
        "num1": rng.normal(size=n).astype(np.float32),
        "num2": np.where(rng.random(n) < 0.1, np.nan,
                         rng.gamma(2.0, 2.0, n)).astype(np.float32),
        "cat": Vec.from_numpy(rng.integers(0, card, n).astype(np.float32),
                              type=T_CAT,
                              domain=[f"L{i}" for i in range(card)]),
    }
    fr = Frame.from_dict(cols)
    logit = (cols["num1"] + 0.1 * fr.vec("cat").to_numpy()
             - np.nan_to_num(cols["num2"]) * 0.2)
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    fr.add("y", Vec.from_numpy(y, type=T_CAT, domain=["n", "p"]))
    return fr


def test_edges_cols_bitmatch_stacked():
    fr = _mixed_frame()
    names = ["num1", "num2", "cat"]
    is_cat = np.array([fr.vec(n).is_categorical() for n in names])
    X = fr.as_matrix(names)
    vecs = [fr.vec(n) for n in names]
    for ht in ("QuantilesGlobal", "UniformAdaptive", "Random"):
        stacked = compute_bin_edges(X, is_cat, 20, seed=42,
                                    histogram_type=ht)
        cols = compute_bin_edges_cols(vecs, is_cat, 20, seed=42,
                                      histogram_type=ht)
        assert np.array_equal(stacked, cols, equal_nan=True), ht


def test_binned_view_codes_match_bin_matrix():
    fr = _mixed_frame()
    names = ["num1", "num2", "cat"]
    is_cat = np.array([fr.vec(n).is_categorical() for n in names])
    X = fr.as_matrix(names)
    edges = compute_bin_edges(X, is_cat, 20, seed=42)
    view = BinnedView.build([fr.vec(n) for n in names], edges, names=names)
    assert view.matrix.dtype == jnp.int8
    ref = np.asarray(bin_matrix(X, jnp.asarray(edges)))
    assert np.array_equal(np.asarray(view.matrix, dtype=np.int32), ref)


def test_binned_view_widens_to_int16_for_wide_cats():
    fr = _mixed_frame(wide_cat=True)
    names = ["num1", "num2", "cat"]
    is_cat = np.array([fr.vec(n).is_categorical() for n in names])
    X = fr.as_matrix(names)
    edges = compute_bin_edges(X, is_cat, 20, seed=42)
    assert edges.shape[1] + 1 > 127  # 200-level cat needs > int8 codes
    view = BinnedView.build([fr.vec(n) for n in names], edges, names=names)
    assert view.matrix.dtype == jnp.int16
    ref = np.asarray(bin_matrix(X, jnp.asarray(edges)))
    assert np.array_equal(np.asarray(view.matrix, dtype=np.int32), ref)


def _train_gbm(fr, store_on: bool, **kw):
    from h2o_tpu.models import gbm as gbm_mod
    from h2o_tpu.models.gbm import GBM, GBMParameters

    os.environ["H2O_TPU_BINNED_STORE"] = "1" if store_on else "0"
    try:
        p = GBMParameters(training_frame=fr, response_column="y", ntrees=5,
                          max_depth=3, nbins=12, seed=7,
                          score_tree_interval=5, **kw)
        model = GBM(p).train_model()
        return model, dict(gbm_mod.LAST_TRAIN_MATRIX_BYTES)
    finally:
        os.environ.pop("H2O_TPU_BINNED_STORE", None)


def test_gbm_binned_vs_raw_prediction_parity():
    """The acceptance pin: forests (and therefore predictions) bit-equal
    between the int8 binned view and the raw stacked-matrix path."""
    fr = _mixed_frame(n=1200)
    m_raw, b_raw = _train_gbm(fr, store_on=False)
    m_bin, b_bin = _train_gbm(fr, store_on=True)
    assert b_raw["mode"] == "stacked_f32" and b_bin["mode"] == "binned"
    assert b_raw["raw_bytes"] > 0 and b_bin["raw_bytes"] == 0
    # >= 3x peak training-matrix reduction (f32 + int32 vs int8)
    peak_raw = b_raw["raw_bytes"] + b_raw["binned_bytes"]
    assert peak_raw >= 3 * b_bin["binned_bytes"]
    for k in ("feat", "thr", "nanL", "val", "gain"):
        assert np.array_equal(np.asarray(m_raw.forest[k]),
                              np.asarray(m_bin.forest[k])), k
    pr, pb = m_raw.predict(fr), m_bin.predict(fr)
    for i in range(pr.ncol):
        assert _bits_eq(np.asarray(pr.vec(i).data), np.asarray(pb.vec(i).data))


def test_drf_binned_vs_raw_prediction_parity():
    from h2o_tpu.models.drf import DRF, DRFParameters

    fr = _mixed_frame(n=1000, seed=9)

    def train(on):
        os.environ["H2O_TPU_BINNED_STORE"] = "1" if on else "0"
        try:
            p = DRFParameters(training_frame=fr, response_column="y",
                              ntrees=3, max_depth=3, nbins=10, seed=3,
                              score_tree_interval=3)
            return DRF(p).train_model()
        finally:
            os.environ.pop("H2O_TPU_BINNED_STORE", None)

    m0, m1 = train(False), train(True)
    assert np.array_equal(np.asarray(m0.forest["feat"]),
                          np.asarray(m1.forest["feat"]))
    assert np.array_equal(np.asarray(m0.forest["val"]),
                          np.asarray(m1.forest["val"]))


# ---------------------------------------------------------------------------
# uplift hist groups (ROADMAP satellite: uplift off the flat path)
# ---------------------------------------------------------------------------
def test_uplift_grouped_hist_matches_flat_4channel():
    """_build_level_hist with the 4-channel uplift accumulator: grouped ==
    flat bitwise (integer-valued channels make every sum exact in f32)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from h2o_tpu.models.tree import engine
    from h2o_tpu.parallel.mesh import ROWS, default_mesh, shard_map

    widths = [3, 8, 16, 33]
    B = 33
    rng = np.random.default_rng(4)
    R = 2048
    Xb = np.stack([rng.integers(0, w - 1, R) for w in widths],
                  axis=1).astype(np.int32)
    Xb[rng.random(Xb.shape) < 0.1] = B - 1
    vals = rng.integers(0, 4, (R, 4)).astype(np.float32)
    node = rng.integers(0, 7, R).astype(np.int32)
    groups, _ = engine.plan_hist_groups(np.asarray(widths) - 2, B, 512,
                                        nvals=4)
    assert groups is not None

    def run(g):
        fn = shard_map(
            lambda xb, nd, vv: engine._build_level_hist(
                xb, nd, vv, 3, 4, B, 512, g),
            mesh=default_mesh(),
            in_specs=(P(ROWS, None), P(ROWS), P(ROWS, None)),
            out_specs=P(), check_vma=False)
        return np.asarray(jax.jit(fn)(Xb, node, vals))

    assert np.array_equal(run(None), run(groups))


def test_uplift_train_engages_hist_groups():
    """End-to-end: an uplift build over mixed-width features plans groups
    and still trains (the per-build cfg carries the partition)."""
    from h2o_tpu.models.uplift import UpliftDRF, UpliftDRFParameters

    rng = np.random.default_rng(21)
    n = 800
    fr = Frame.from_dict({
        "num": rng.normal(size=n).astype(np.float32),
        "cat": Vec.from_numpy(rng.integers(0, 60, n).astype(np.float32),
                              type=T_CAT,
                              domain=[f"c{i}" for i in range(60)]),
        "treatment": rng.integers(0, 2, n).astype(np.float32),
    })
    y = (rng.random(n) < 0.4).astype(np.float32)
    fr.add("y", Vec.from_numpy(y, type=T_CAT, domain=["0", "1"]))
    p = UpliftDRFParameters(training_frame=fr, response_column="y",
                            treatment_column="treatment", ntrees=3,
                            max_depth=3, nbins=16, seed=1)
    model = UpliftDRF(p).train_model()
    assert model.forest["feat"].shape[0] == 3
    out = model.predict(fr)
    assert out.names[0] == "uplift_predict"


