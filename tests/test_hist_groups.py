"""Width-bucketed histogram accumulation (hist_groups) vs the flat one-hot
path — grouped/segment-sum bit-equality over mixed widths on the virtual CPU
mesh, the auto-tuner's engagement rules, and a full GBM train with the
grouped path forced on/off (the ADVICE r5 medium finding)."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from h2o_tpu.frame.frame import Frame
from h2o_tpu.frame.vec import T_CAT, Vec
from h2o_tpu.models.tree import engine
from h2o_tpu.parallel.mesh import ROWS, default_mesh, shard_map

#: mixed per-feature bin-space widths (real bins + NA slot), deliberately
#: straddling power-of-two boundaries: 8 exactly, 9 just over, 16 exactly,
#: 32 exactly, and 33 = the full flat width
_WIDTHS = [3, 8, 9, 16, 32, 33]
_B = 33  # flat nbins_tot (32 real bins + the NA bucket at 32)


def _mixed_case(seed=0, R=4096):
    rng = np.random.default_rng(seed)
    Xb = np.stack([rng.integers(0, w - 1, R) for w in _WIDTHS],
                  axis=1).astype(np.int32)
    na = rng.random(Xb.shape) < 0.1
    Xb[na] = _B - 1  # NA rows land in the global NA bucket
    # integer-valued channels: every partial sum is exact in f32, so any
    # accumulation order (matmul, segment-sum) must agree BITWISE
    vals = rng.integers(-8, 8, (R, 3)).astype(np.float32)
    nedges = np.asarray(_WIDTHS) - 2
    return Xb, vals, nedges


def _run_hist(Xb, node, vals, offset, n_lv, groups):
    mesh = default_mesh()

    def spmd(xb, nd, vv):
        return engine._build_level_hist(xb, nd, vv, offset, n_lv, _B, 512,
                                        groups)

    fn = shard_map(spmd, mesh=mesh,
                   in_specs=(P(ROWS, None), P(ROWS), P(ROWS, None)),
                   out_specs=P(), check_vma=False)
    return np.asarray(jax.jit(fn)(Xb, node, vals))


@pytest.mark.parametrize("n_lv,offset", [(1, 0), (4, 3), (16, 15)])
def test_grouped_matches_flat_bit_exact(n_lv, offset):
    Xb, vals, nedges = _mixed_case()
    rng = np.random.default_rng(5)
    # node ids straddle the level window so inactive rows are exercised
    node = rng.integers(0, offset + 2 * n_lv, Xb.shape[0]).astype(np.int32)
    groups, _blk = engine.plan_hist_groups(nedges, _B, 512)
    assert groups is not None
    flat = _run_hist(Xb, node, vals, offset, n_lv, None)
    grouped = _run_hist(Xb, node, vals, offset, n_lv, groups)
    assert flat.shape == (len(_WIDTHS), n_lv, _B, 3)
    assert np.array_equal(flat, grouped)


def test_legacy_two_tuple_groups_still_accumulate():
    """Persisted pre-mode models carry (idxs, width) 2-tuples."""
    Xb, vals, nedges = _mixed_case(seed=2)
    node = np.zeros(Xb.shape[0], np.int32)
    groups, _ = engine.plan_hist_groups(nedges, _B, 512)
    legacy = tuple((g[0], g[1]) for g in groups)
    assert np.array_equal(_run_hist(Xb, node, vals, 0, 1, None),
                          _run_hist(Xb, node, vals, 0, 1, legacy))


def test_segment_sum_path_matches_flat_exactly():
    """Force EVERY group through the narrow-bin scatter-add path."""
    Xb, vals, nedges = _mixed_case(seed=3)
    rng = np.random.default_rng(7)
    node = rng.integers(0, 11, Xb.shape[0]).astype(np.int32)
    groups, _ = engine.plan_hist_groups(nedges, _B, 512)
    seg = tuple((g[0], g[1], "segsum") for g in groups)
    assert np.array_equal(_run_hist(Xb, node, vals, 3, 4, None),
                          _run_hist(Xb, node, vals, 3, 4, seg))


def test_plan_engages_only_when_padding_dominates():
    # uniform widths: nothing to bucket
    groups, blk = engine.plan_hist_groups(np.full(6, 20), 22, 8192)
    assert groups is None and blk == 8192
    # one 300-level categorical next to narrow numerics: engages, with the
    # narrow buckets on the segment-sum path
    nedges = np.array([300, 18, 18, 18, 2])
    groups, _ = engine.plan_hist_groups(nedges, 302, 8192)
    assert groups is not None
    widths = {g[1] for g in groups}
    assert 302 in widths  # wide bucket capped at the flat width
    assert any(g[2] == "segsum" for g in groups)  # width-4 bucket
    assert all(g[2] == "onehot" for g in groups if g[1] > 8)
    covered = sorted(i for g in groups for i in g[0])
    assert covered == list(range(5))  # a partition, not a subset


def test_plan_block_rows_follow_hbm_budget():
    nedges = np.full(32, 300)  # wide flat space, no grouping win
    _, blk_big = engine.plan_hist_groups(nedges, 302, 8192,
                                         budget_bytes=64 << 30)
    _, blk_small = engine.plan_hist_groups(nedges, 302, 8192,
                                           budget_bytes=1 << 28)
    assert blk_big == 8192
    assert 512 <= blk_small < blk_big


def _mixed_frame(n=2500, seed=11):
    rng = np.random.default_rng(seed)
    hi = rng.integers(0, 60, n)
    lo = rng.integers(0, 2, n)
    x1 = rng.integers(0, 16, n).astype(np.float32)
    x2 = rng.integers(0, 16, n).astype(np.float32)
    eff = rng.normal(0, 1.0, 60)
    y = (eff[hi] + 0.8 * (lo == 1) + 0.1 * x1
         + 0.2 * rng.normal(size=n) > 0.4).astype(np.float32)
    fr = Frame.from_dict({"x1": x1, "x2": x2})
    fr.add("hi", Vec.from_numpy(hi.astype(np.float32), type=T_CAT,
                                domain=[f"L{i}" for i in range(60)]))
    fr.add("lo", Vec.from_numpy(lo.astype(np.float32), type=T_CAT,
                                domain=["off", "on"]))
    fr.add("y", Vec.from_numpy(y, type=T_CAT, domain=["n", "p"]))
    return fr


def test_gbm_hist_groups_forced_on_off_same_model(monkeypatch):
    """End-to-end GBM with the grouped path auto-engaged vs forced flat:
    identical forests, identical predictions. Also pins the auto-tune
    default ENGAGING on a mixed high-cardinality-categorical + numeric
    frame, with the binary categorical on the segment-sum path."""
    from h2o_tpu.models import gbm as gbm_mod
    from h2o_tpu.models.gbm import GBM, GBMParameters

    fr = _mixed_frame()
    params = GBMParameters(training_frame=fr, response_column="y", ntrees=4,
                           max_depth=3, seed=3)
    orig = gbm_mod.plan_hist_groups
    preds = {}
    for forced in ("auto", "off"):
        if forced == "off":
            monkeypatch.setattr(
                gbm_mod, "plan_hist_groups",
                lambda *a, **k: (None, orig(*a, **k)[1]))
        else:
            monkeypatch.setattr(gbm_mod, "plan_hist_groups", orig)
        m = GBM(params).train_model()
        if forced == "auto":
            assert m.cfg.hist_groups is not None
            assert any(g[2] == "segsum" for g in m.cfg.hist_groups)
        else:
            assert m.cfg.hist_groups is None
        preds[forced] = m.predict(fr).vec(2).to_numpy()
    np.testing.assert_allclose(preds["auto"], preds["off"], atol=1e-6)
