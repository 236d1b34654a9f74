"""Kernels-layer reference suite — the blocked-scan histogram and Gram
(`h2o_tpu/backend/kernels/`) against independent float64 references, plus
the cold-start compile-cache wiring.

The references use different arithmetic by design (a float64 ``np.add.at``
per cell, a per-row mul+sum with no matmul), so the comparisons carry a
tolerance; tests/test_hist_contrib.py holds the cell-for-cell EQUALITY
cases on exactly representable statistics.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o_tpu.backend.kernels import gram, hist, pow2_block_rows
from test_hist_contrib import _ref, _ref_groups

pytestmark = pytest.mark.kernels


def _hist_inputs(R, F, B, n_lv, V, dtype, seed=0, na_frac=0.0,
                 weighted=False):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, B - 1, (R, F))
    if na_frac > 0:
        mask = rng.random((R, F)) < na_frac
        codes = np.where(mask, B - 1, codes)   # NA bucket = last slot
    Xb = jnp.asarray(codes, dtype)
    lc = jnp.asarray(rng.integers(0, n_lv, (R,)), jnp.int32)
    vv = rng.normal(size=(R, V)).astype(np.float32)
    if weighted:
        vv[:, 0] = rng.random(R).astype(np.float32) * 3.0
    return Xb, lc, jnp.asarray(vv)


def _hist_ref(Xb, lc, vv, n_lv, B):
    """tests/test_hist_contrib.py's float64 ``np.add.at`` per cell."""
    return _ref(np.asarray(Xb), np.asarray(lc), np.asarray(vv), n_lv, B)


def _close(got, want):
    got = np.asarray(got, np.float64)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# histogram scan against the per-cell reference
# ---------------------------------------------------------------------------
class TestHistParity:
    @pytest.mark.parametrize("R,F,B,n_lv,dtype,block,kw", [
        (1024, 3, 9, 2, jnp.int8, 256, {}),
        *[(4096, 7, 21, n_lv, dtype, 1024, {})
          for dtype in (jnp.int8, jnp.int16, jnp.int32)
          for n_lv in (1, 4, 16)],
        # NA bucket populated, a non-negative weight channel
        (8192, 5, 33, 8, jnp.int8, 2048,
         dict(na_frac=0.15, weighted=True))],
        ids=["small", *[f"{d}-nodes{n}" for d in ("int8", "int16", "int32")
                        for n in (1, 4, 16)], "na-bucket-weights"])
    def test_flat_matches_per_cell_reference(self, R, F, B, n_lv, dtype,
                                             block, kw):
        Xb, lc, vv = _hist_inputs(R, F, B, n_lv, 3, dtype, **kw)
        h = hist.level_hist_blocks(Xb, lc, vv, n_lv=n_lv, nbins_tot=B,
                                   block=block)
        assert h.shape == (F, n_lv, B, 3)
        _close(h, _hist_ref(Xb, lc, vv, n_lv, B))
        if kw:   # NA-bucket mass really landed in the last slot
            assert float(jnp.sum(h[:, :, -1, 0])) > 0

    @pytest.mark.parametrize("n_lv", [1, 4])
    def test_grouped_matches_per_cell_reference(self, n_lv):
        # mixed widths: one narrow segsum bucket, one wide onehot bucket
        B = 33
        groups = (((0, 2, 4), 8, "segsum"), ((1, 3, 5, 6), 32, "onehot"))
        Xb, lc, vv = _hist_inputs(4096, 7, B, n_lv, 3, jnp.int16,
                                  na_frac=0.1, weighted=True)
        # a group's codes fit its width; NA stays the global last slot
        codes = np.array(Xb)
        for idxs, Bg, _mode in groups:
            sub = codes[:, list(idxs)]
            codes[:, list(idxs)] = np.where(sub == B - 1, B - 1,
                                            sub % (Bg - 1))
        hs = hist.level_hist_blocks(jnp.asarray(codes), lc, vv, n_lv=n_lv,
                                    nbins_tot=B, block=1024, groups=groups)
        # each group's NA bucket is ITS last slot
        want = _ref_groups(codes, np.asarray(lc), np.asarray(vv), n_lv, B,
                           groups)
        assert len(hs) == len(want) == 2
        for h, w in zip(hs, want):
            _close(h, w)

    def test_inside_jit_and_scan(self):
        """The scan composes under jit + an outer lax.scan (the engine
        wraps it in jit(shard_map(scan)) for real training)."""
        Xb, lc, vv = _hist_inputs(2048, 4, 11, 2, 3, jnp.int8)

        @jax.jit
        def run(Xb, lc, vv):
            def body(acc, _):
                h = hist.level_hist_blocks(Xb, lc, vv, n_lv=2,
                                           nbins_tot=11, block=512)
                return acc + h, None
            out, _ = jax.lax.scan(
                body, jnp.zeros((4, 2, 11, 3), jnp.float32), None,
                length=3)
            return out

        _close(run(Xb, lc, vv), 3 * _hist_ref(Xb, lc, vv, 2, 11))


# ---------------------------------------------------------------------------
# Gram scan against the per-row reference
# ---------------------------------------------------------------------------
class TestGramParity:
    @pytest.mark.parametrize("R,P,seed,block,has_z", [
        (2048, 6, 3, None, True),
        (4096, 8, 1, None, True), (5000, 33, 1, None, True),
        (16384, 65, 1, None, True),
        # a block under one lane tile is one tile: four 1024-row blocks
        # sliced in place, then the 904 rows left as the tail slice
        (5000, 17, 2, 999, True),
        # a 0/1 mask as the weight and no response (PCA's Gram)
        (1024, 9, 4, None, False),
        # a lane-aligned block with a ragged tail: 2 x 2048 + 904
        (5000, 7, 5, 2048, True),
        # R under one lane tile: one block whatever the budget
        (700, 5, 6, 100, True),
        # no response, blocks and a tail: 3 x 1024 + 928
        (4000, 9, 7, 1024, False),
        # an exact multiple with 8 blocks, no tail
        (8192, 6, 8, 1024, True)])
    def test_gram_matches_per_row_mul_sum_reference(self, R, P, seed, block,
                                                    has_z):
        """The PR 4 last-ulp policy reference: G[p,q] accumulated by
        per-row mul+sum in f64 (not a matmul)."""
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(R, P)).astype(np.float32)
        W = (rng.random(R) if has_z
             else rng.random(R) < 0.8).astype(np.float32)
        z = rng.normal(size=R).astype(np.float32) if has_z else None
        G, b = gram.gram_accumulate(
            jnp.asarray(X), jnp.asarray(W),
            None if z is None else jnp.asarray(z), block=block)
        X64, W64 = X.astype(np.float64), W.astype(np.float64)
        ref_G = np.zeros((P, P))
        for r in range(R):          # per-row mul+sum, no matmul
            ref_G += np.outer(X64[r] * W64[r], X64[r])
        np.testing.assert_allclose(np.asarray(G), ref_G, rtol=1e-5,
                                   atol=1e-3)
        if not has_z:
            assert b is None
            return
        ref_b = (X64 * (W64 * z.astype(np.float64))[:, None]).sum(axis=0)
        np.testing.assert_allclose(np.asarray(b), ref_b, rtol=1e-5,
                                   atol=1e-3)


@pytest.mark.parametrize("R,P,block,want", [
    # the HIGGS design on one chip: 16 blocks, no tail
    (11_010_048, 29, None, (16, 688_128, 0)),
    # a four-chip shard of it
    (2_752_512, 29, None, (3, 917_504, 0)),
    # the shape that once made 16-row slivers: under the budget, one block
    (50_000, 33, None, (1, 50_000, 0)),
    (5_000, 17, 999, (4, 1024, 904)),
    (7, 3, None, (1, 7, 0)),
    # 11M unpadded rows: the lane floor leaves a tail under one block
    (11_000_000, 29, None, (16, 687_104, 6_336)),
    # 168 blocks would leave more than a block over: the count moves on
    # to the next multiple of 8 whose lane-floored block leaves less
    (11_000_000, 29, 65_536, (176, 62_464, 6_336)),
    (11_010_048, 29, 65_536, (168, 65_536, 0)),
])
def test_gram_block_plan(R, P, block, want):
    from h2o_tpu.backend.kernels.gram import _BLOCK_CELLS, _LANE, block_plan

    nblk, rb, tail = got = block_plan(R, P, block)
    assert got == want
    assert nblk * rb + tail == R and 0 <= tail < rb
    if nblk > 1 or tail:
        assert rb % _LANE == 0
        assert rb <= max(block or _BLOCK_CELLS // P, _LANE)
    assert nblk < 8 or nblk % 8 == 0


def test_gram_under_the_budget_is_one_contraction():
    """A design under the block budget lowers to the plain fused einsum:
    no loop, no slice, one contraction for G and one for b."""
    X = jnp.zeros((50_000, 33), jnp.float32)
    v = jnp.zeros((50_000,), jnp.float32)
    text = jax.jit(gram.gram_accumulate).lower(X, v, v).as_text()
    assert text.count("dot_general") == 2
    assert "while" not in text and "dynamic_slice" not in text
    blocked = jax.jit(lambda X, W, z: gram.gram_accumulate(
        X, W, z, block=8192)).lower(X, v, v).as_text()
    assert "while" in blocked and "dynamic_slice" in blocked
    assert not re.search(r"\b(pad|concatenate|reshape)\b.*50000", blocked)


def test_pow2_block_rows():
    assert pow2_block_rows(8192, 2048) == 2048
    assert pow2_block_rows(50000, 16384) == 16  # why gram takes no divisor
    assert pow2_block_rows(7, 4) == 1  # degenerate: only 1 divides


def _higgs_like(n, seed=7, response_cat=True):
    from h2o_tpu.frame.frame import Frame
    from h2o_tpu.frame.vec import T_CAT, Vec

    rng = np.random.default_rng(seed)
    cols = {f"f{j}": rng.normal(size=n).astype(np.float32)
            for j in range(6)}
    logits = cols["f0"] - 0.5 * cols["f1"] + 0.25 * cols["f2"]
    y = (rng.random(n) < 1 / (1 + np.exp(-logits))).astype(np.float32)
    fr = Frame.from_dict(cols)
    if response_cat:
        fr.add("y", Vec.from_numpy(y, type=T_CAT, domain=["n", "p"]))
    else:
        fr.add("y", Vec.from_numpy((logits + 0.1 * rng.normal(size=n))
                                   .astype(np.float32)))
    return fr


def test_glm_coefficients_pinned():
    """End-to-end IRLS pin: the gaussian fit recovers the generating
    coefficients (f0=1, f1=-0.5, f2=0.25) through the blocked Gram."""
    from h2o_tpu.models.glm import GLM, GLMParameters

    fr = _higgs_like(8000, response_cat=False)
    c = GLM(GLMParameters(training_frame=fr, response_column="y",
                          family="gaussian", lambda_=0.0,
                          seed=3)).train_model().coef()
    assert abs(c["f0"] - 1.0) < 0.05
    assert abs(c["f1"] + 0.5) < 0.05
    assert abs(c["f2"] - 0.25) < 0.05


# ---------------------------------------------------------------------------
# rulefit: covers-based support == membership-eval support
# ---------------------------------------------------------------------------
def test_rulefit_covers_support_matches_membership():
    from h2o_tpu.models.rulefit import (RuleFit, RuleFitParameters,
                                        _stream_rule_support, eval_rules)

    fr = _higgs_like(4000, seed=9)
    p = RuleFitParameters(training_frame=fr, response_column="y",
                          min_rule_length=2, max_rule_length=2,
                          rule_generation_ntrees=10, seed=4,
                          model_type="rules")
    m = RuleFit(p).train_model()
    assert m.rules and all(r.origin is not None for r in m.rules)
    X = fr.as_matrix(m.output.names)
    memb = np.asarray(eval_rules(X, *m.rule_arrays))
    sup_eval = memb[: fr.nrow].mean(axis=0)
    sup_cov = np.array([r.support for r in m.rules], np.float32)
    # covers count the same rows the membership eval counts — exact
    # integers below 2^24, so the two paths agree to f32 exactness
    assert np.allclose(sup_cov, sup_eval, atol=1e-6)
    # and the streaming membership oracle agrees too
    sup_stream = np.asarray(_stream_rule_support(X, m.rule_arrays, fr.nrow))
    assert np.allclose(sup_cov, sup_stream, atol=1e-6)


# ---------------------------------------------------------------------------
# cold start: compile-cache wiring + AOT train step + compilemeter hits
# ---------------------------------------------------------------------------
class TestColdStart:
    @pytest.fixture()
    def cache_rule(self, monkeypatch):
        """compile_cache with ``jax.config.update`` replaced by a recorder
        (no global jax state leaks out of the test) and the once-per-process
        latch cleared."""
        import jax

        from h2o_tpu.utils import compile_cache

        updates: dict = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.__setitem__(k, v))
        monkeypatch.setattr(compile_cache, "_ENSURED", False)
        monkeypatch.setattr(compile_cache, "_LOC", None)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        return compile_cache, updates, jax

    def test_env_dir_wins_and_no_dir_is_set_in_code(self, cache_rule,
                                                    tmp_path, monkeypatch):
        compile_cache, updates, _jax = cache_rule
        loc = str(tmp_path / "placed")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", loc)
        assert compile_cache.ensure() == loc   # honoured on CPU too
        assert os.path.isdir(loc)
        assert "jax_compilation_cache_dir" not in updates
        # idempotent: later calls return the frozen first answer
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "y"))
        assert compile_cache.ensure() == loc

    def test_unset_on_cpu_is_off(self, cache_rule):
        compile_cache, updates, _jax = cache_rule
        assert compile_cache.ensure() is None
        assert updates == {}

    def test_unset_on_accelerator_is_the_checkout_dir(self, cache_rule,
                                                      tmp_path, monkeypatch):
        compile_cache, updates, jax = cache_rule
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        # ONE fixed default: beside the package, no home, temp, pid or time
        assert compile_cache.DEFAULT_DIR == os.path.join(repo, ".xla_cache")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(compile_cache, "DEFAULT_DIR",
                            str(tmp_path / ".xla_cache"))
        assert compile_cache.ensure() == str(tmp_path / ".xla_cache")
        assert updates["jax_compilation_cache_dir"] == \
            str(tmp_path / ".xla_cache")

    def test_unusable_dir_is_an_error(self, cache_rule, tmp_path,
                                      monkeypatch):
        compile_cache, _updates, _jax = cache_rule
        (tmp_path / "file").write_text("not a directory")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "file" / "cache"))
        with pytest.raises(OSError):
            compile_cache.ensure()

    def test_train_arms_the_cache(self, monkeypatch):
        """model_base.train calls compile_cache.ensure() before the first
        dispatch — any process that trains gets the placed cache."""
        from h2o_tpu.models.gbm import GBM, GBMParameters
        from h2o_tpu.utils import compile_cache

        called = []
        monkeypatch.setattr(compile_cache, "ensure",
                            lambda *a, **k: called.append(1))
        fr = _higgs_like(2000, seed=17)
        GBM(GBMParameters(training_frame=fr, response_column="y",
                          ntrees=2, max_depth=2, seed=1)).train_model()
        assert called

    def test_aot_train_step_compiles_once_and_is_reused(self):
        """The AOT-compiled chunk step is cached by program identity + arg
        signature: a second identical build performs ZERO lower+compiles
        (the serving-scorer discipline applied to training)."""
        from h2o_tpu.models import gbm as gbm_mod
        from h2o_tpu.utils import telemetry

        fr = _higgs_like(4000, seed=19)

        def train():
            return gbm_mod.GBM(gbm_mod.GBMParameters(
                training_frame=fr, response_column="y", ntrees=4,
                max_depth=3, seed=2)).train_model()

        m1 = train()
        compiles_after_first = telemetry.snapshot()[
            "train.compile.seconds"]["count"]
        m2 = train()
        assert telemetry.snapshot()["train.compile.seconds"]["count"] \
            == compiles_after_first
        # and the AOT path trains the same forest as the first build
        for k in ("feat", "thr", "val"):
            assert np.array_equal(np.asarray(m1.forest[k]),
                                  np.asarray(m2.forest[k]))

    def test_compilemeter_separates_cache_hits(self):
        from h2o_tpu.utils import compilemeter

        with compilemeter.scoped() as sc:
            pass
        assert sc.compiles == 0 and sc.hits == 0 and sc.uncached == 0
        assert compilemeter.uncached_count() \
            == max(compilemeter.count() - compilemeter.cache_hits(), 0)
