"""Async pipelined GBM training (ISSUE 12) — the acceptance pins.

Everything here runs on the suite's 8-device virtual CPU mesh
(tests/conftest.py), so the pipelined-vs-synchronous parity pins exercise
REAL psums on the 8-shard mesh; the single-shard pin re-runs the same
comparison on a one-device mesh.

- Pipelined forests AND predictions are BIT-equal to the synchronous
  oracle across the knob matrix (pipeline × async-psum), on the
  8-shard mesh and single-shard, at the one-chunk and multi-chunk
  (fused cadence scoring + dispatch-ahead + donated margin) cadences;
- the fused-scoring metric series is identical to the oracle's;
- an in-flight pipelined dispatch killed by the `mrtask.dispatch`
  failpoint fails TYPED (no hang) and re-runs clean to the oracle forest.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o_tpu.frame.frame import Frame
from h2o_tpu.frame.vec import T_CAT, Vec
from h2o_tpu.models.gbm import GBM, GBMParameters
from h2o_tpu.parallel import mesh as meshmod
from h2o_tpu.utils import failpoints as fp

pytestmark = pytest.mark.pipeline

_RNG = np.random.default_rng(12)
_N = 4096
#: mixed widths on purpose: a 40-level categorical (wide one-hot bucket +
#: SET splits), a 5-level categorical (segsum-width bucket), two numerics
_C1 = _RNG.integers(0, 40, size=_N).astype(np.float32)
_C2 = _RNG.integers(0, 5, size=_N).astype(np.float32)
_X1 = _RNG.normal(size=_N).astype(np.float32)
_X2 = _RNG.normal(size=_N).astype(np.float32)
_EFF = _RNG.normal(0, 0.8, 40)
_Y = ((_EFF[_C1.astype(int)] + 0.6 * _X1 - 0.4 * _X2
       + 0.3 * (_C2 == 2) + _RNG.normal(scale=0.5, size=_N)) > 0.2
      ).astype(np.float32)

_FOREST_KEYS = ("feat", "thr", "nanL", "val", "gain", "catd")


def _frame(rows=slice(None), mesh=None):
    fr = Frame(["x1", "x2"], [Vec.from_numpy(_X1[rows], mesh=mesh),
                              Vec.from_numpy(_X2[rows], mesh=mesh)])
    fr.add("c1", Vec.from_numpy(_C1[rows], type=T_CAT,
                                domain=[f"L{i}" for i in range(40)],
                                mesh=mesh))
    fr.add("c2", Vec.from_numpy(_C2[rows], type=T_CAT,
                                domain=list("abcde"), mesh=mesh))
    fr.add("y", Vec.from_numpy(_Y[rows], type=T_CAT, domain=["n", "p"],
                               mesh=mesh))
    return fr


def _train(fr, monkeypatch, pipeline, async_psum="1", interval=None,
           ntrees=8, seed=7, **kw):
    monkeypatch.setenv("H2O_TPU_PIPELINE", pipeline)
    monkeypatch.setenv("H2O_TPU_ASYNC_PSUM", async_psum)
    p = GBMParameters(training_frame=fr, response_column="y",
                      ntrees=ntrees, max_depth=4, nbins=16, seed=seed,
                      learn_rate=0.2,
                      score_tree_interval=interval or ntrees, **kw)
    return GBM(p).train_model()


def _forest_equal(a, b):
    return all(bool(np.array_equal(np.asarray(a.forest[k]),
                                   np.asarray(b.forest[k])))
               for k in _FOREST_KEYS)


def _preds_equal(a, b, fr):
    X = a.adapt_frame(fr)
    return bool(np.array_equal(np.asarray(a.score0(X)),
                               np.asarray(b.score0(X))))


# ---------------------------------------------------------------------------
# Bit parity: pipelined vs the synchronous oracle, knob matrix
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("async_psum", ["0", "1"])
def test_pipelined_bit_parity_8shard(monkeypatch, async_psum):
    fr = _frame()
    oracle = _train(fr, monkeypatch, pipeline="0", async_psum="0")
    m = _train(fr, monkeypatch, pipeline="1", async_psum=async_psum)
    assert _forest_equal(oracle, m)
    assert _preds_equal(oracle, m, fr)


def test_async_psum_alone_bit_parity(monkeypatch):
    fr = _frame()
    oracle = _train(fr, monkeypatch, pipeline="0", async_psum="0")
    m = _train(fr, monkeypatch, pipeline="0", async_psum="1")
    assert _forest_equal(oracle, m)
    assert _preds_equal(oracle, m, fr)


def test_pipelined_bit_parity_single_shard(monkeypatch):
    one = meshmod.make_mesh(devices=jax.devices()[:1])
    with meshmod.use_mesh(one):
        fr = _frame(mesh=one)
        oracle = _train(fr, monkeypatch, pipeline="0", async_psum="0")
        m = _train(fr, monkeypatch, pipeline="1")
        assert _forest_equal(oracle, m)
        assert _preds_equal(oracle, m, fr)


def test_cadence_parity_and_fused_metric_series(monkeypatch):
    """Multi-chunk cadence engages fused scoring + dispatch-ahead + the
    donated margin carry; forests, predictions AND the per-boundary
    metric series must match the oracle's exactly."""
    fr = _frame()
    oracle = _train(fr, monkeypatch, pipeline="0", async_psum="0",
                    interval=2)
    m = _train(fr, monkeypatch, pipeline="1", interval=2)
    assert _forest_equal(oracle, m)
    assert _preds_equal(oracle, m, fr)
    h0 = [h["training_metrics"].auc for h in oracle.output.scoring_history]
    h1 = [h["training_metrics"].auc for h in m.output.scoring_history]
    assert len(h0) == len(h1) == 4
    assert h0 == h1
    ll0 = [h["training_metrics"].logloss
           for h in oracle.output.scoring_history]
    ll1 = [h["training_metrics"].logloss for h in m.output.scoring_history]
    assert ll0 == ll1


def test_drf_pipelined_parity(monkeypatch):
    from h2o_tpu.models.drf import DRF, DRFParameters

    fr = _frame()

    def drf(pipeline):
        monkeypatch.setenv("H2O_TPU_PIPELINE", pipeline)
        p = DRFParameters(training_frame=fr, response_column="y",
                          ntrees=6, max_depth=4, nbins=16, seed=7,
                          sample_rate=0.8)
        return DRF(p).train_model()

    oracle, m = drf("0"), drf("1")
    assert _forest_equal(oracle, m)
    assert _preds_equal(oracle, m, fr)


def test_multinomial_pipelined_parity(monkeypatch):
    y3 = (_C1 % 3).astype(np.float32)
    fr = _frame()
    fr.add("y3", Vec.from_numpy(y3, type=T_CAT, domain=["a", "b", "c"]))

    def tri(pipeline):
        monkeypatch.setenv("H2O_TPU_PIPELINE", pipeline)
        p = GBMParameters(training_frame=fr, response_column="y3",
                          ntrees=4, max_depth=3, nbins=16, seed=7)
        return GBM(p).train_model()

    oracle, m = tri("0"), tri("1")
    assert _forest_equal(oracle, m)
    assert _preds_equal(oracle, m, fr)


# ---------------------------------------------------------------------------
# Failpoint drill: in-flight pipelined dispatch fails typed, re-runs clean
# ---------------------------------------------------------------------------
def test_pipelined_dispatch_failpoint_typed_and_rerun_clean(monkeypatch):
    # a FRESH frame: its rollups ride an mr_reduce dispatch during build
    # setup, so the armed failpoint hits an in-flight pipelined build
    # (an already-rolled-up frame would dodge the site)
    fr = _frame()
    fp.reset()
    try:
        fp.arm("mrtask.dispatch", "raise(fault)@1")
        with pytest.raises(fp.InjectedFault):
            _train(fr, monkeypatch, pipeline="1")
    finally:
        fp.reset()
    # the fault unwound typed (no hang, no corrupted caches): the re-run
    # lands the oracle forest bit-equal
    oracle = _train(fr, monkeypatch, pipeline="0", async_psum="0")
    m = _train(fr, monkeypatch, pipeline="1")
    assert _forest_equal(oracle, m)


def test_chunk_failpoint_mid_cadence_typed(monkeypatch):
    """Kill the pipelined chunk loop at the second boundary — with
    dispatch-ahead in flight — and verify the typed unwind + clean
    re-run."""
    fr = _frame()
    fp.reset()
    try:
        fp.arm("train.gbm.chunk", "raise(fault)@2")
        with pytest.raises(fp.InjectedFault):
            _train(fr, monkeypatch, pipeline="1", interval=2)
    finally:
        fp.reset()
    oracle = _train(fr, monkeypatch, pipeline="0", async_psum="0",
                    interval=2)
    m = _train(fr, monkeypatch, pipeline="1", interval=2)
    assert _forest_equal(oracle, m)


def test_knob_armed_recovery_disables_dispatch_ahead(monkeypatch, tmp_path):
    """H2O_TPU_AUTO_RECOVERY_DIR arms checkpointing fleet-wide with the
    PARAM unset — the dispatch-ahead gate must see the armed state (the
    checkpoint reads the carried margin, which dispatch-ahead would have
    already donated to the next chunk; review catch, reproduced as
    'Array has been deleted' before the fix)."""
    monkeypatch.setenv("H2O_TPU_AUTO_RECOVERY_DIR", str(tmp_path))
    monkeypatch.setenv("H2O_TPU_CHECKPOINT_SECS", "0")
    fr = _frame(rows=slice(0, 1024))
    m = _train(fr, monkeypatch, pipeline="1", interval=2, ntrees=6)
    assert m.output.scoring_history  # trained through every boundary
    monkeypatch.delenv("H2O_TPU_AUTO_RECOVERY_DIR")
    monkeypatch.delenv("H2O_TPU_CHECKPOINT_SECS")
    oracle = _train(fr, monkeypatch, pipeline="0", async_psum="0",
                    interval=2, ntrees=6)
    assert _forest_equal(oracle, m)


# ---------------------------------------------------------------------------
# Engine-level: streamed route+hist pass vs the two-pass shape
# ---------------------------------------------------------------------------
def test_streamed_route_hist_matches_two_pass():
    from h2o_tpu.backend.kernels import hist as hist_kernels

    rng = np.random.default_rng(3)
    R, F, n_lv, B = 1024, 4, 2, 9
    Xb = jnp.asarray(rng.integers(0, B, (R, F)).astype(np.int16))
    node = jnp.asarray(rng.integers(1, 3, R).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(R, 3)).astype(np.float32))

    def fake_route(xb, nd):
        return nd + (xb[:, 0].astype(jnp.int32) % 2)

    # two-pass: route whole array, then the oracle accumulation
    routed = fake_route(Xb, node)
    offset, width = 1, 4
    local = routed - offset
    active = (local >= 0) & (local < width)
    lc = jnp.clip(local, 0, width - 1)
    v = jnp.where(active[:, None], vals, 0.0)
    want = hist_kernels.level_hist_blocks(Xb, lc, v, n_lv=width,
                                          nbins_tot=B, block=256)
    (got,), node_out = hist_kernels.streamed_route_hist(
        Xb, node, vals, fake_route, offset=offset, n_lv=width,
        nbins_tot=B, block=256)
    assert np.array_equal(np.asarray(want), np.asarray(got))
    assert np.array_equal(np.asarray(routed), np.asarray(node_out))
