"""Kernels-layer bit-parity suite — Pallas (interpret on CPU) vs the XLA
oracle (`h2o_tpu/backend/kernels/`), plus the cold-start compile-cache
wiring.

The contract under test is exact, not approximate: both backends execute
the SAME per-block math in the SAME ascending block order, so every
histogram cell, Gram entry and downstream forest/coefficient must be
bit-equal across ``H2O_TPU_HIST_KERNEL=pallas|xla``. Tolerance-based
checks appear only against independent references (f64 numpy, per-row
mul+sum) that use different arithmetic by design.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o_tpu.backend.kernels import (gram, hist, hist_backend,
                                     pow2_block_rows)

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


# ---------------------------------------------------------------------------
# histogram kernel parity
# ---------------------------------------------------------------------------
class TestHistParity:
    @pytest.mark.parametrize("dtype", [jnp.int8, jnp.int16, jnp.int32])
    @pytest.mark.parametrize("n_lv", [1, 4, 16])
    def test_flat_bit_parity_across_dtypes_and_node_counts(self, dtype,
                                                           n_lv):
        Xb, lc, vv = _hist_inputs(4096, 7, 21, n_lv, 3, dtype)
        kw = dict(n_lv=n_lv, nbins_tot=21, block=1024)
        h_x = hist.level_hist_blocks(Xb, lc, vv, backend="xla", **kw)
        h_p = hist.level_hist_blocks(Xb, lc, vv, backend="pallas", **kw)
        assert h_x.shape == (7, n_lv, 21, 3)
        assert bool(jnp.all(h_x == h_p))

    def test_flat_parity_with_na_bucket_and_weights(self):
        Xb, lc, vv = _hist_inputs(8192, 5, 33, 8, 3, jnp.int8,
                                  na_frac=0.15, weighted=True)
        kw = dict(n_lv=8, nbins_tot=33, block=2048)
        h_x = hist.level_hist_blocks(Xb, lc, vv, backend="xla", **kw)
        h_p = hist.level_hist_blocks(Xb, lc, vv, backend="pallas", **kw)
        assert bool(jnp.all(h_x == h_p))
        # NA-bucket mass really landed in the last slot on both
        assert float(jnp.sum(h_x[:, :, -1, 0])) > 0

    @pytest.mark.parametrize("n_lv", [1, 4])
    def test_grouped_bit_parity_onehot_and_segsum(self, n_lv):
        # mixed widths: one narrow segsum bucket, one wide onehot bucket
        B = 33
        groups = (((0, 2, 4), 8, "segsum"), ((1, 3, 5, 6), 32, "onehot"))
        Xb, lc, vv = _hist_inputs(4096, 7, B, n_lv, 3, jnp.int16,
                                  na_frac=0.1, weighted=True)
        kw = dict(n_lv=n_lv, nbins_tot=B, block=1024, groups=groups)
        hx = hist.level_hist_blocks(Xb, lc, vv, backend="xla", **kw)
        hp = hist.level_hist_blocks(Xb, lc, vv, backend="pallas", **kw)
        assert len(hx) == len(hp) == 2
        for a, b in zip(hx, hp):
            assert a.shape == b.shape
            assert bool(jnp.all(a == b))

    def test_flat_matches_per_cell_reference(self):
        """Both backends agree with a direct per-cell f64 reference (not
        just with each other)."""
        Xb, lc, vv = _hist_inputs(1024, 3, 9, 2, 3, jnp.int8)
        h = hist.level_hist_blocks(Xb, lc, vv, n_lv=2, nbins_tot=9,
                                   block=256, backend="pallas")
        codes = np.asarray(Xb, np.int64)
        l = np.asarray(lc)
        v = np.asarray(vv, np.float64)
        for f in range(3):
            for n in range(2):
                for b in (0, 4, 8):
                    sel = (codes[:, f] == b) & (l == n)
                    ref = v[sel].sum(axis=0)
                    got = np.asarray(h[f, n, b], np.float64)
                    assert np.allclose(got, ref, rtol=1e-5, atol=1e-4)

    def test_inside_jit_and_scan(self):
        """The pallas path composes under jit + lax.scan (the engine wraps
        it in jit(shard_map(scan)) for real training)."""
        Xb, lc, vv = _hist_inputs(2048, 4, 11, 2, 3, jnp.int8)

        def once(backend):
            @jax.jit
            def run(Xb, lc, vv):
                def body(acc, _):
                    h = hist.level_hist_blocks(Xb, lc, vv, n_lv=2,
                                               nbins_tot=11, block=512,
                                               backend=backend)
                    return acc + h, None
                out, _ = jax.lax.scan(
                    body, jnp.zeros((4, 2, 11, 3), jnp.float32), None,
                    length=3)
                return out
            return run(Xb, lc, vv)

        assert bool(jnp.all(once("xla") == once("pallas")))


# ---------------------------------------------------------------------------
# Gram kernel parity
# ---------------------------------------------------------------------------
class TestGramParity:
    @pytest.mark.parametrize("R,P", [(4096, 8), (5000, 33), (16384, 65)])
    def test_weighted_gram_bit_parity(self, R, P):
        rng = np.random.default_rng(1)
        X = jnp.asarray(rng.normal(size=(R, P)), jnp.float32)
        W = jnp.asarray(rng.random(R), jnp.float32)
        z = jnp.asarray(rng.normal(size=R), jnp.float32)
        G1, b1 = gram.gram_accumulate(X, W, z, backend="xla")
        G2, b2 = gram.gram_accumulate(X, W, z, backend="pallas")
        assert bool(jnp.all(G1 == G2)) and bool(jnp.all(b1 == b2))

    def test_blocked_path_parity(self):
        """Force multi-block accumulation with an awkward block (pad rows
        engage). The bit-parity contract is pinned at PRODUCTION block
        shapes (the default budget: single or gemm-sized blocks — the
        end-to-end GLM tests below are bit-equal); at deliberately tiny
        forced blocks XLA may pick a different reduction strategy for the
        fused scan than the interpreted kernel, so this boundary case
        pins tight closeness plus exactness of the padding itself."""
        rng = np.random.default_rng(2)
        R, P = 5000, 17
        X = jnp.asarray(rng.normal(size=(R, P)), jnp.float32)
        W = jnp.asarray(rng.random(R), jnp.float32)
        z = jnp.asarray(rng.normal(size=R), jnp.float32)
        G1, b1 = gram.gram_accumulate(X, W, z, block=999, backend="xla")
        G2, b2 = gram.gram_accumulate(X, W, z, block=999, backend="pallas")
        assert np.allclose(np.asarray(G1), np.asarray(G2), rtol=1e-6,
                           atol=1e-4)
        assert np.allclose(np.asarray(b1), np.asarray(b2), rtol=1e-6,
                           atol=1e-4)
        # blocking + padding vs the unblocked single pass: same sums
        G3, _b3 = gram.gram_accumulate(X, W, z, backend="xla")
        assert np.allclose(np.asarray(G1), np.asarray(G3), rtol=1e-6,
                           atol=1e-4)

    def test_gram_matches_per_row_mul_sum_reference(self):
        """The PR 4 last-ulp policy reference: G[p,q] accumulated by
        per-row mul+sum in f64 (not a matmul) bounds both backends."""
        rng = np.random.default_rng(3)
        R, P = 2048, 6
        X = rng.normal(size=(R, P)).astype(np.float32)
        W = rng.random(R).astype(np.float32)
        z = rng.normal(size=R).astype(np.float32)
        G, b = gram.gram_accumulate(jnp.asarray(X), jnp.asarray(W),
                                    jnp.asarray(z), backend="pallas")
        X64, W64, z64 = (a.astype(np.float64) for a in (X, W, z))
        ref_G = np.zeros((P, P))
        ref_b = np.zeros(P)
        for r in range(R):          # per-row mul+sum, no matmul
            ref_G += np.outer(X64[r] * W64[r], X64[r])
            ref_b += X64[r] * W64[r] * z64[r]
        assert np.allclose(np.asarray(G), ref_G, rtol=1e-5, atol=1e-3)
        assert np.allclose(np.asarray(b), ref_b, rtol=1e-5, atol=1e-3)

    def test_mask_gram_no_z(self):
        rng = np.random.default_rng(4)
        X = jnp.asarray(rng.normal(size=(1024, 9)), jnp.float32)
        m = jnp.asarray((rng.random(1024) < 0.8), jnp.float32)
        G1, b1 = gram.gram_accumulate(X, m, backend="xla")
        G2, b2 = gram.gram_accumulate(X, m, backend="pallas")
        assert b1 is None and b2 is None
        assert bool(jnp.all(G1 == G2))


# ---------------------------------------------------------------------------
# backend selection
# ---------------------------------------------------------------------------
class TestBackendKnob:
    def test_auto_resolves_xla_off_tpu(self, monkeypatch):
        monkeypatch.delenv("H2O_TPU_HIST_KERNEL", raising=False)
        assert hist_backend() == ("pallas" if jax.default_backend() == "tpu"
                                  else "xla")

    def test_explicit_values(self, monkeypatch):
        monkeypatch.setenv("H2O_TPU_HIST_KERNEL", "pallas")
        assert hist_backend() == "pallas"
        monkeypatch.setenv("H2O_TPU_HIST_KERNEL", "xla")
        assert hist_backend() == "xla"
        monkeypatch.setenv("H2O_TPU_HIST_KERNEL", "cuda")
        with pytest.raises(ValueError, match="H2O_TPU_HIST_KERNEL"):
            hist_backend()

    def test_pow2_block_rows(self):
        assert pow2_block_rows(8192, 2048) == 2048
        assert pow2_block_rows(50000, 16384) == 16  # why gram pads instead
        assert pow2_block_rows(7, 4) == 1  # degenerate: only 1 divides


# ---------------------------------------------------------------------------
# end-to-end: forests and GLM coefficients bit-equal across backends
# ---------------------------------------------------------------------------
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


class TestEndToEndParity:
    def _train_gbm(self, fr, backend, drf=False, **kw):
        from h2o_tpu.models.drf import DRF, DRFParameters
        from h2o_tpu.models.gbm import GBM, GBMParameters

        os.environ["H2O_TPU_HIST_KERNEL"] = backend
        try:
            cls, pcls = (DRF, DRFParameters) if drf else (GBM, GBMParameters)
            p = pcls(training_frame=fr, response_column="y", ntrees=6,
                     max_depth=4, nbins=20, seed=11, **kw)
            return cls(p).train_model()
        finally:
            os.environ.pop("H2O_TPU_HIST_KERNEL", None)

    @pytest.mark.parametrize("drf", [False, True])
    def test_small_forest_bit_equal(self, drf):
        fr = _higgs_like(8000)
        m_x = self._train_gbm(fr, "xla", drf=drf)
        m_p = self._train_gbm(fr, "pallas", drf=drf)
        for k in ("feat", "thr", "nanL", "val", "gain"):
            assert np.array_equal(np.asarray(m_x.forest[k]),
                                  np.asarray(m_p.forest[k])), k
        X = m_x.adapt_frame(fr)
        assert np.array_equal(np.asarray(m_x.score0(X)),
                              np.asarray(m_p.score0(X)))

    def test_grouped_hist_forest_bit_equal(self):
        """Width-bucketed hist_groups engage (mixed categorical widths) —
        the grouped pallas path must match the grouped xla path through a
        whole forest."""
        from h2o_tpu.frame.frame import Frame
        from h2o_tpu.frame.vec import T_CAT, Vec

        rng = np.random.default_rng(5)
        n = 6000
        wide = rng.integers(0, 120, n).astype(np.float32)
        narrow = rng.integers(0, 3, n).astype(np.float32)
        num = rng.normal(size=n).astype(np.float32)
        y = ((wide % 7 < 3) & (num > 0)).astype(np.float32)
        fr = Frame.from_dict({"num": num})
        fr.add("wide", Vec.from_numpy(wide, type=T_CAT,
                                      domain=[f"L{i}" for i in range(120)]))
        fr.add("narrow", Vec.from_numpy(narrow, type=T_CAT,
                                        domain=["a", "b", "c"]))
        fr.add("y", Vec.from_numpy(y, type=T_CAT, domain=["0", "1"]))
        m_x = self._train_gbm(fr, "xla")
        m_p = self._train_gbm(fr, "pallas")
        assert m_x.cfg.hist_groups is not None, \
            "fixture no longer engages hist groups"
        for k in ("feat", "thr", "nanL", "val", "gain", "catd"):
            assert np.array_equal(np.asarray(m_x.forest[k]),
                                  np.asarray(m_p.forest[k])), k
        X = m_x.adapt_frame(fr)
        assert np.array_equal(np.asarray(m_x.score0(X)),
                              np.asarray(m_p.score0(X)))

    def test_glm_coefficients_bit_equal_and_pinned(self):
        from h2o_tpu.models.glm import GLM, GLMParameters

        fr = _higgs_like(8000, response_cat=False)

        def fit(backend):
            os.environ["H2O_TPU_HIST_KERNEL"] = backend
            try:
                p = GLMParameters(training_frame=fr, response_column="y",
                                  family="gaussian", lambda_=0.0, seed=3)
                return GLM(p).train_model()
            finally:
                os.environ.pop("H2O_TPU_HIST_KERNEL", None)

        m_x, m_p = fit("xla"), fit("pallas")
        assert np.array_equal(np.asarray(m_x.beta), np.asarray(m_p.beta))
        # end-to-end IRLS pin: the gaussian fit recovers the generating
        # coefficients (f0=1, f1=-0.5, f2=0.25) through the fused Gram
        c = m_x.coef()
        assert abs(c["f0"] - 1.0) < 0.05
        assert abs(c["f1"] + 0.5) < 0.05
        assert abs(c["f2"] - 0.25) < 0.05

    def test_glm_binomial_bit_equal(self):
        from h2o_tpu.models.glm import GLM, GLMParameters

        fr = _higgs_like(6000)

        def fit(backend):
            os.environ["H2O_TPU_HIST_KERNEL"] = backend
            try:
                p = GLMParameters(training_frame=fr, response_column="y",
                                  family="binomial", seed=3)
                return GLM(p).train_model()
            finally:
                os.environ.pop("H2O_TPU_HIST_KERNEL", None)

        m_x, m_p = fit("xla"), fit("pallas")
        assert np.array_equal(np.asarray(m_x.beta), np.asarray(m_p.beta))


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
