"""The XGBoost builder as the documented ``hist`` booster (ISSUE 34):
its parameter defaults and aliases, what it refuses, the hessian form of
``min_child_weight``, and the program against the plain reference
``benchmark/reference/xgb.py`` at 256 bins and depth 6.

CPU mesh, thousands of rows, two or three trees: values, never a time.
"""

import dataclasses
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark.reference import xgb as ref  # noqa: E402
from h2o_tpu.frame.frame import Frame  # noqa: E402
from h2o_tpu.frame.vec import T_CAT, Vec  # noqa: E402
from h2o_tpu.models.gbm import GBM, GBMParameters  # noqa: E402
from h2o_tpu.models.xgboost import XGBoost, XGBoostParameters  # noqa: E402

_N, _F = 4096, 6


def _frame(n=_N, F=_F, seed=34, sharp=1.0):
    """(frame, reference data): F continuous columns (more distinct values
    than small-data exact binning takes, so the cuts are the sketch's 255)
    and a binary label from a logistic in two of them."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    logit = sharp * (X[:, 0] - 0.5 * X[:, 1])
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    vecs = [Vec.from_numpy(X[:, j]) for j in range(F)]
    fr = Frame([f"x{j}" for j in range(F)], list(vecs))
    yv = Vec.from_numpy(y, type=T_CAT, domain=["b", "s"])
    fr.add("y", yv)
    return fr, ref.Data([v.data for v in vecs] + [yv.data], n)


def _params(fr, **kw):
    kw = {"ntrees": 3, "score_tree_interval": 3, "seed": 1, **kw}
    return XGBoostParameters(training_frame=fr, response_column="y", **kw)


def _harvest(model) -> dict:
    """What `benchmark/drive_train._harvest` takes of a served model."""
    m = model.output.training_metrics
    got = {k: np.asarray(model.forest[k])
           for k in ("feat", "thr", "val", "gain", "nanL")}
    return {**got, "f0": float(np.asarray(model.f0)),
            "logloss": m.logloss, "auc": m.auc}


def _prm(p) -> dict:
    return {"max_bins": p.nbins, "max_depth": p.max_depth,
            "eta": p.learn_rate, "lam": p.reg_lambda,
            "min_child_weight": p.min_rows, "gamma": p.min_split_improvement}


# --------------------------------------------------------- the parameters ---
def test_parameters_read_the_documented_defaults():
    """H2O-3's XGBoost documentation: ntrees 50, max_depth 6, eta 0.3,
    min_child_weight 1, max_bins 256, lambda 1, alpha 0, gamma 0, no
    sampling, gbtree, hist."""
    p = XGBoostParameters()
    assert (p.ntrees, p.max_depth, p.learn_rate, p.min_rows, p.nbins) == (
        50, 6, 0.3, 1.0, 256)
    assert (p.reg_lambda, p.reg_alpha, p.min_split_improvement) == (1.0, 0, 0)
    assert (p.sample_rate, p.col_sample_rate, p.col_sample_rate_per_tree) == (
        1.0, 1.0, 1.0)
    assert (p.booster, p.tree_method, p.grow_policy, p.max_leaves) == (
        "gbtree", "hist", "depthwise", 0)
    # the GBM's own defaults are where they were
    g = GBMParameters()
    assert (g.max_depth, g.nbins, g.min_split_improvement, g.min_rows) == (
        5, 20, 1e-5, 10.0)


def test_native_aliases_resolve_onto_the_h2o_names():
    p = XGBoostParameters(n_estimators=7, eta=0.2, min_child_weight=3,
                          subsample=0.5, colsample_bytree=0.6,
                          colsample_bylevel=0.7, max_bins=64, gamma=0.25)
    assert (p.ntrees, p.learn_rate, p.min_rows, p.sample_rate) == (
        7, 0.2, 3, 0.5)
    assert (p.col_sample_rate_per_tree, p.col_sample_rate, p.nbins,
            p.min_split_improvement) == (0.6, 0.7, 64, 0.25)
    # parked back at their sentinels: a clone re-runs __post_init__
    assert dataclasses.replace(p, ntrees=9).nbins == 64


@pytest.mark.parametrize("kw", [
    {"tree_method": "approx"}, {"tree_method": "exact"},
    {"grow_policy": "lossguide"}, {"max_leaves": 8}])
def test_what_the_engine_does_not_implement_raises(kw):
    fr, _ = _frame(64, 2)
    with pytest.raises(ValueError, match="not implemented"):
        XGBoost(_params(fr, **kw))


def test_the_accepted_spellings_build_a_builder():
    fr, _ = _frame(64, 2)
    for kw in ({"tree_method": "auto"}, {"tree_method": "hist"},
               {"grow_policy": "depthwise"}, {"max_leaves": 0}):
        XGBoost(_params(fr, **kw))


def test_only_the_xgboost_builder_tests_the_hessian():
    fr, _ = _frame(64, 2)
    assert XGBoost(_params(fr))._tree_config(1).child_weight_hessian is True
    gbm = GBM(GBMParameters(training_frame=fr, response_column="y"))
    assert gbm._tree_config(1).child_weight_hessian is False


def test_a_gbm_step_lowers_to_the_same_text_with_the_field_at_its_default():
    """The child-weight branch is a static Python one: at its default the
    field leaves the level program's lowered text as it was (the GBM cells'
    compiled programs and cache keys stay), and set it changes it."""
    import jax

    from h2o_tpu.models.tree.engine import TreeConfig, make_train_fn
    from h2o_tpu.parallel.mesh import default_mesh

    R, F, nb = 1024, 3, 8
    cfg = TreeConfig(ntrees=1, max_depth=2, nbins=nb, min_rows=1.0)
    assert cfg == dataclasses.replace(cfg, child_weight_hessian=False)

    def text(c):
        def grad(y, f, w):
            return (f - y) * w, w
        fn = make_train_fn(c, grad, default_mesh())
        return fn.lower(
            jnp.zeros((R, F), jnp.int8), jnp.zeros(R), jnp.ones(R),
            jnp.zeros(R), jnp.zeros((F, nb - 1)),
            jnp.ones((F, nb - 1), bool), jax.random.split(
                jax.random.PRNGKey(0), 1), jnp.ones(1), jnp.zeros(F),
            jnp.ones((F, F), bool), jnp.zeros(F, bool),
            jnp.zeros(F, jnp.int32)).as_text()

    plain = text(cfg)
    assert plain == text(TreeConfig(ntrees=1, max_depth=2, nbins=nb,
                                    min_rows=1.0, child_weight_hessian=False))
    assert plain != text(dataclasses.replace(cfg, child_weight_hessian=True))


# ------------------------------------------- the program and the reference ---
#: every tolerance with its reason. The CPU multiplies in float32, so the
#: program's sums differ from the reference's float64 ones by float32
#: rounding over 4096 addends; on the chip (bfloat16 addends) the cell's
#: own limits apply, not these.
TOL = {
    "leaf_gap": 1e-4,        # -eta G / (H + lambda): float32 sums
    "gain_gap": 1e-3,        # a difference of three quotients of such sums
    "logloss_gap": 1e-5,     # float32 metrics against a float64 walk
    "auc_gap": 1e-5,
    "f0_gap": 1e-5,          # the prior log-odds, in float32
    "child_weight_gap": 1e-4,  # no child under min_child_weight 1 of
                               # hessian, to float32 sums
    # the sketch reads a cut between two of 4096 values by interpolating in
    # a fine bin of about four rows: a rank off by some rows (five here; on
    # the chip's 11M rows it read 2.1e-5 against XGBoost's 4.9e-4)
    "edge_rank_gap": 8.0 / _N,
    # the program searches the sketch's cuts and the reference its own
    # exact ones, some rows apart: at a level-5 node of 128 rows the best
    # gain moves by that much
    "regret_gap": 0.2,
}


@pytest.fixture(scope="module")
def trained():
    fr, data = _frame()
    p = _params(fr)
    return p, data, _harvest(XGBoost(p).train_model())


def test_the_documented_model_is_256_bins_deep_6_on_int16_codes(trained):
    from h2o_tpu.models import gbm as gbm_mod

    p, _data, got = trained
    assert got["feat"].shape == (3, 127) and got["thr"].shape == (3, 127)
    assert gbm_mod.LAST_TRAIN_MATRIX_BYTES["binned_dtype"] == "int16"
    assert (got["feat"][0] >= 0).sum() > 31      # tree 0 splits below level 4


def test_the_program_agrees_with_the_plain_reference(trained):
    p, data, got = trained
    assert p.nbins == 256 and p.max_depth == 6
    numbers = ref.check(got, data, _prm(p), range(3), [0])
    assert set(numbers) == set(TOL)
    over = {k: (v, TOL[k]) for k, v in numbers.items() if not v <= TOL[k]}
    assert not over, over


def test_the_reference_passes_its_own_check_and_its_faults_do_not(trained):
    p, data, _got = trained
    prm = _prm(p)
    own = ref.check(ref.build(data, 2, prm), data, prm, range(2), [0])
    # its cuts are whole rows (a rank within one row of k / 256) and its
    # forest is stored in float32
    assert own.pop("edge_rank_gap") <= 1.0 / _N + 1e-12
    assert all(v <= 1e-6 for v in own.values()), own
    moved = ref.check(ref.build(data, 2, prm, fault="cut_moved"), data, prm,
                      range(2), [])
    assert moved["edge_rank_gap"] > ref.sketch_eps(256)
    coarse = ref.check(ref.build(data, 2, prm, fault="bins20"), data, prm,
                       range(2), [])
    assert coarse["edge_rank_gap"] > ref.sketch_eps(256)
    bare = ref.check(ref.build(data, 2, prm, fault="no_lambda"), data, prm,
                     range(2), [])
    assert bare["leaf_gap"] > 1e-3


@pytest.mark.parametrize("lam", [0.0, 1.0, 10.0])
def test_lambda_is_in_the_gain_and_in_the_leaf(lam):
    fr, data = _frame(2048, 3, seed=int(lam) + 5)
    p = _params(fr, reg_lambda=lam, ntrees=2, score_tree_interval=2,
                max_depth=3, max_bins=64)
    got = _harvest(XGBoost(p).train_model())
    numbers = ref.check(got, data.with_bins(64), _prm(p), range(2), [])
    assert numbers["leaf_gap"] <= TOL["leaf_gap"], numbers
    assert numbers["gain_gap"] <= TOL["gain_gap"], numbers
    # and the reference at another lambda does NOT read these leaves
    other = ref.check(got, data.with_bins(64),
                      dict(_prm(p), lam=lam + 5.0), range(2), [])
    assert other["leaf_gap"] > 100 * TOL["leaf_gap"], other


def test_min_child_weight_binds_on_the_hessian_not_on_the_rows(monkeypatch):
    """Few rows, confident margins: after two sharp trees at eta 1 a row's
    hessian p (1 - p) is far under 1, so a child of 8 rows holds 8 in row
    weight and not 8 in hessian. The builder leaves no child under
    ``min_child_weight`` of hessian; the parent's row test (the GBM's
    ``_tree_config`` with ``reg_alpha``, which is what the builder had)
    does."""
    fr, data = _frame(1500, 3, seed=9, sharp=6.0)
    p = _params(fr, ntrees=3, score_tree_interval=3, max_depth=4,
                learn_rate=1.0, min_rows=8.0, reg_lambda=0.0, max_bins=32)
    prm = _prm(p)

    def gap():
        got = _harvest(XGBoost(p).train_model())
        return ref.check(got, data.with_bins(32), prm, range(3),
                         [])["child_weight_gap"]

    assert gap() <= 1e-3         # float32 sums against float64 ones
    monkeypatch.setattr(
        XGBoost, "_tree_config",
        lambda self, K, nbins=None: dataclasses.replace(
            GBM._tree_config(self, K, nbins=nbins),
            reg_alpha=self.params.reg_alpha))
    assert gap() > 0.25          # a child with under three quarters of it
