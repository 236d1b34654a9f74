"""What a training job names about itself (ISSUE 26).

- Device scopes: every name declared in ``telemetry.SCOPES`` reaches the
  compiled HLO of the program it belongs to (the GBM train step with the
  pipelined level program on and off, the sketch, the coding, the IRLS
  step, the deviance probe) as a path element of an ``op_name``; an
  undeclared name raises.
- Host spans: a GBM and a GLM train record the spans inside ``train.gbm``
  / ``train.glm`` with the right parent, one trace id, and children that
  fit inside their parent; a lambda path records the same parts; the
  IRLS step's load is a ``train.program.load`` span.
- ``compile`` timeline events carry the compiled function's name and
  whether the program came from the persistent cache.
- Device programs (ISSUE 36): every name declared in ``telemetry.PROGRAMS``
  is on a function the package jits, an undeclared one raises, a job
  compiles under declared names or bare primitives only, and no jitted
  function of the package is still called ``spmd``.
- A train through the client is one trace from ``client.train`` down.

CPU mesh, small frames: names, parents and counts, never a time.
"""

import ast
import functools
import pathlib
import re
import socket

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o_tpu.frame.frame import Frame
from h2o_tpu.frame.vec import T_CAT, Vec
from h2o_tpu.utils import compilemeter, telemetry, timeline

_N = 4096
_F = 4


def _frame(n=_N):
    rng = np.random.default_rng(26)
    X = rng.normal(size=(n, _F)).astype(np.float32)
    y = ((X[:, 0] - X[:, 1] + rng.normal(scale=0.5, size=n)) > 0
         ).astype(np.float32)
    fr = Frame([f"x{i}" for i in range(_F)],
               [Vec.from_numpy(X[:, i]) for i in range(_F)])
    fr.add("y", Vec.from_numpy(y, type=T_CAT, domain=["n", "p"]))
    return fr


def _train_gbm(fr, **kw):
    from h2o_tpu.models.gbm import GBM, GBMParameters

    return GBM(GBMParameters(
        training_frame=fr, response_column="y", ntrees=4, max_depth=3,
        seed=1, score_tree_interval=2, **kw)).train_model()


def _train_xgboost(fr, **kw):
    from h2o_tpu.models.xgboost import XGBoost, XGBoostParameters

    return XGBoost(XGBoostParameters(
        training_frame=fr, response_column="y", ntrees=4, max_depth=3,
        seed=1, score_tree_interval=2, **kw)).train_model()


def _train_glm(fr, **kw):
    from h2o_tpu.models.glm import GLM, GLMParameters

    kw.setdefault("lambda_", 0.0)
    return GLM(GLMParameters(training_frame=fr, response_column="y",
                             family="binomial", **kw)).train_model()


def _events_of(run):
    seq0 = timeline.total_recorded()
    run()
    return timeline.snapshot(since=seq0)


def _spans(events, name):
    return [e for e in events if e["kind"] == "span" and e["what"] == name]


# ---------------------------------------------------------------------------
# (a) device scopes in the compiled programs
# ---------------------------------------------------------------------------
def _gbm_step_text(pipeline: str) -> str:
    """HLO text of the train step a small GBM job compiled: THIS job's
    entry of the process-wide step cache, by its own key (the level program
    asked for, this job's depth and chunk length, this frame's coded
    matrix: tests/test_pipeline.py trains on the same shape in the same
    process at depth 4). Under ``-n 6 --dist load`` the
    process may also be serving another worker's REST jobs (``h2o.init``
    at a module's fixed port connects to whichever worker bound it first),
    and their steps land in the same cache while this one trains."""
    from h2o_tpu.models import gbm as gbm_mod

    def mine(key):
        (cfg, *_), sig = key
        return (cfg.pipeline == (pipeline == "1")
                and (cfg.max_depth, cfg.ntrees) == (3, 2)
                and sig[0] == ((_N, _F), "int8"))

    mp = pytest.MonkeyPatch()
    try:
        mp.setenv("H2O_TPU_PIPELINE", pipeline)
        # an earlier test of this worker may have built the same key with a
        # persistent compile cache on: that entry is a replay and carries
        # no scope names, so this job compiles its step afresh
        for key in [k for k in gbm_mod._AOT_STEP_CACHE if mine(k)]:
            del gbm_mod._AOT_STEP_CACHE[key]
        _train_gbm(_frame())
        (compiled,) = [c for k, c in list(gbm_mod._AOT_STEP_CACHE.items())
                       if mine(k)]
        return compiled.as_text()
    finally:
        mp.undo()


def _glm_texts():
    from h2o_tpu.models import glm as glm_mod

    fam = glm_mod.BinomialF()
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.normal(size=(1024, 5)).astype(np.float32))
    y = jnp.asarray((rng.random(1024) > 0.5).astype(np.float32))
    w, off = jnp.ones(1024, jnp.float32), jnp.zeros(1024, jnp.float32)
    beta = jnp.zeros(5, jnp.float32)
    step = glm_mod._make_irls_kernel(fam)
    # under an enclosing trace the tracked wrapper steps aside: the step
    # lowers as the job dispatches it (shard_map on this 8-device mesh)
    irls = jax.jit(lambda *a: step(*a)).lower(X, y, w, beta, off)
    probe = glm_mod._make_dev_kernel(fam).lower(X, y, w, beta, off)
    return irls.compile().as_text(), probe.compile().as_text()


def _binning_texts():
    from h2o_tpu.models.tree import binning

    X = jnp.zeros((2048, 3), jnp.float32)
    sketch = binning._hist_quantile_rows.lower(
        X, (0.25, 0.5, 0.75), nb=64, rb=256)
    col = binning.bin_column.lower(X[:, 0], jnp.zeros(7, jnp.float32),
                                   dtype=jnp.int8)
    mat = binning.bin_matrix.lower(X, jnp.zeros((3, 7), jnp.float32))
    return (sketch.compile().as_text(), col.compile().as_text(),
            mat.compile().as_text())


def _gam_texts():
    """The two design programs (plain jit, one shard) at two cubic
    regression splines and one linear column."""
    from h2o_tpu.models import gam
    from h2o_tpu.parallel import mesh as meshmod

    col = jnp.zeros((2048,), jnp.float32)
    vec = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    knots = jnp.linspace(-1.0, 1.0, 6)
    kinds, args = ((0, 0),) * 2, ((knots,),) * 2
    mesh = meshmod.default_mesh()
    sums = gam._sums_program(mesh, False, kinds)
    design = gam._design_program(mesh, False, ((0,), 1, False), kinds)
    return (sums.lower((col, col), args, jnp.int32(2000)).compile().as_text(),
            design.lower(((col,), vec(1), vec(1), vec(1)), (col, col), args,
                         (jnp.ones((12, 5)),) * 2,
                         (vec(5),) * 2).compile().as_text())


@pytest.fixture(scope="module")
def hlo():
    """The programs' compiled texts, every one compiled HERE: an executable
    replayed from a persistent compile cache carries no scope names (its
    key leaves op metadata out; PERF.md section 7 no. 7(b)), so whatever
    cache the environment or an earlier test of this process placed is off
    while these compile."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        irls, probe = _glm_texts()
        sketch, bin_col, bin_mat = _binning_texts()
        gam_sums, gam_design = _gam_texts()
        return {"gam_sums": gam_sums, "gam_design": gam_design,
                "gbm_step_pipelined": _gbm_step_text("1"),
                "gbm_step_synchronous": _gbm_step_text("0"),
                "sketch": sketch, "bin_column": bin_col,
                "bin_matrix": bin_mat,
                "irls_step": irls, "deviance_probe": probe}
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()


_LEVEL = ("gbm.grad", "gbm.route", "gbm.hist", "gbm.psum", "gbm.split",
          "gbm.leaf")
#: (program, scope): where each declared scope has to arrive
_SCOPE_CASES = (
    [("gbm_step_pipelined", s) for s in _LEVEL + ("gbm.score",)]
    + [("gbm_step_synchronous", s) for s in _LEVEL]
    + [("sketch", "gbm.sketch"), ("bin_column", "gbm.bin"),
       ("bin_matrix", "gbm.bin")]
    + [("irls_step", s) for s in ("glm.eta", "glm.gram", "glm.deviance")]
    + [("deviance_probe", s) for s in ("glm.eta", "glm.deviance")]
    + [("gam_sums", "gam.basis"), ("gam_design", "gam.basis")])


def test_every_declared_scope_has_a_case():
    assert {s for _, s in _SCOPE_CASES} == set(telemetry.SCOPES)


@pytest.mark.parametrize("program,scope", _SCOPE_CASES)
def test_scope_reaches_the_compiled_program(hlo, program, scope):
    paths = set(re.findall(r'op_name="([^"]*)"', hlo[program]))
    assert any(scope in p.split("/") for p in paths), (
        f"{scope} is in no op_name of {program}")


def test_scopes_rename_no_program():
    """``irls_program_s`` reads the XLA module ``jit__core`` by name: on one
    device the IRLS step still compiles as ``jit(_core)``."""
    from h2o_tpu.parallel import mesh as meshmod

    with meshmod.use_mesh(meshmod.make_mesh(devices=jax.devices()[:1])):
        fr = _frame()
        events = _events_of(lambda: _train_glm(fr))
    assert "jit(_core)" in {e["what"] for e in events
                            if e["kind"] == "compile"}


def test_undeclared_scope_raises():
    with pytest.raises(KeyError, match="nope"):
        telemetry.scope("nope")


# ---------------------------------------------------------------------------
# (b) host spans: presence, parents, one trace, children inside the parent
# ---------------------------------------------------------------------------
#: algo -> {span: the span its parent has to be}
_TREE = {
    "gbm": {"train.gbm.prep": "train.gbm", "train.gbm.finish": "train.gbm",
            "train.gbm.sketch": "train.gbm",
            "train.gbm.binned_view": "train.gbm",
            "train.gbm.chunk": "train.gbm",
            "train.gbm.score": "train.gbm.chunk"},
    "glm": {"train.glm.design": "train.glm", "train.glm.start": "train.glm",
            "train.glm.finish": "train.glm", "train.glm.gram": "train.glm",
            "train.glm.solve": "train.glm", "train.glm.probe": "train.glm",
            "train.glm.metrics": "train.glm",
            "train.program.load": "train.glm.gram"},
}
# a lambda search has the one recording path: the same parts on the ring,
# the lambda loop's inside its one train.glm.path (the lambda_max pass
# keeps its train.glm.gram under the job)
_TREE["glm_search"] = {
    "train.glm.design": "train.glm", "train.glm.metrics": "train.glm",
    "train.glm.start": "train.glm", "train.glm.finish": "train.glm",
    "train.glm.path": "train.glm",
    "train.glm.gram": ("train.glm", "train.glm.path"),
    "train.glm.solve": "train.glm.path", "train.glm.probe": "train.glm.path",
    "train.program.load": "train.glm.gram"}
# the XGBoost builder's job: the GBM's parts under ITS root, and no
# train.gbm span (why the benchmark reads xgb_setup_s, not gbm_setup_s)
_TREE["xgboost"] = {k: "train.xgboost" if v == "train.gbm" else v
                    for k, v in _TREE["gbm"].items()}
# the GAM's job (ISSUE 38): its own parts under train.gam, the GLM's step
# (and its load) under ITS gram span, and no train.glm span
_TREE["gam"] = {
    "train.gam.knots": "train.gam", "train.gam.design": "train.gam",
    "train.gam.start": "train.gam", "train.gam.gram": "train.gam",
    "train.gam.solve": "train.gam", "train.gam.finish": "train.gam",
    "train.gam.metrics": "train.gam", "train.program.load": "train.gam.gram"}


def _train_gam(fr, **kw):
    from h2o_tpu.models.gam import GAM, GAMParameters

    return GAM(GAMParameters(
        training_frame=fr, response_column="y", family="binomial",
        gam_columns=["x2", "x3"], num_knots=6, scale=0.001, **kw)).train_model()


_TRAIN = {"gbm": _train_gbm, "glm": _train_glm, "xgboost": _train_xgboost,
          "gam": _train_gam,
          "glm_search": lambda fr: _train_glm(
              fr, lambda_=None, lambda_search=True, nlambdas=6)}
_ROOT = {"xgboost": "train.xgboost"}


@pytest.mark.parametrize("algo", sorted(_TREE))
def test_train_records_the_span_tree(algo):
    fr = _frame()
    events = _events_of(lambda: _TRAIN[algo](fr))
    (root,) = _spans(events, _ROOT.get(algo, f"train.{algo[:3]}"))
    if algo == "xgboost":
        assert _spans(events, "train.gbm") == []
    if algo == "gam":
        assert _spans(events, "train.glm") == []
        (design,) = _spans(events, "train.gam.design")
        assert design["design_cols"] == (_F - 2) + 2 * 5 + 1
        assert len(_spans(events, "train.gam.gram")) == len(
            _spans(events, "train.gam.solve")) >= 2
    by_id = {e["span"]: e for e in events if e["kind"] == "span"}
    for name, parent in _TREE[algo].items():
        got = _spans(events, name)
        assert got, f"no {name} span"
        for e in got:
            assert e["trace"] == root["trace"], name
            assert by_id[e["parent"]]["what"] in (
                parent if isinstance(parent, tuple) else (parent,)), (name, e)
    kids: dict = {}
    for e in by_id.values():
        if e.get("parent") in by_id:
            kids[e["parent"]] = kids.get(e["parent"], 0) + e["dur_us"]
    for sid, total in kids.items():
        assert total <= by_id[sid]["dur_us"], by_id[sid]["what"]
    # the bare stretches of the root have names (ISSUE 36): the set-up's
    # opens four times a tree job (three in `_setup_build`, one before the
    # step's load), the others once
    counts = {n: len(_spans(events, n)) for n in (
        "train.gbm.prep", "train.gbm.finish", "train.glm.start",
        "train.glm.finish")}
    assert counts == ({"train.gbm.prep": 4, "train.gbm.finish": 1,
                       "train.glm.start": 0, "train.glm.finish": 0}
                      if algo in ("gbm", "xgboost") else
                      dict.fromkeys(counts, 0) if algo == "gam" else
                      {"train.gbm.prep": 0, "train.gbm.finish": 0,
                       "train.glm.start": 1, "train.glm.finish": 1})


@pytest.mark.parametrize("algo", ["gbm", "glm"])
def test_peak_setting_spans_carry_the_hbm_reading(monkeypatch, algo):
    """The spans at whose close the HBM peak may stand (sketch, coded view,
    the GLM's two designs, the job's root) carry the fullest device's
    ``memory_stats()`` at their close; the CPU mesh reports none, so the
    reading is planted."""
    from h2o_tpu.backend import memory

    real = memory.hbm_stats
    monkeypatch.setattr(memory, "hbm_stats", lambda: dict(
        real() or {}, bytes_in_use=3_000_000_000,
        peak_bytes_in_use=5_000_000_000, bytes_limit=16_000_000_000))
    fr = _frame()
    events = _events_of(lambda: _TRAIN[algo](fr))
    names = {"gbm": ("train.gbm", "train.gbm.sketch", "train.gbm.binned_view"),
             "glm": ("train.glm", "train.glm.design")}[algo]
    for name in names:
        got = _spans(events, name)
        assert len(got) == (2 if name == "train.glm.design" else 1)
        for e in got:
            assert (e["hbm_in_use_gb"], e["hbm_peak_gb"]) == (3.0, 5.0), name
    assert all("hbm_peak_gb" not in e for e in events
               if e["kind"] == "span" and e["what"] not in names)


@pytest.mark.parametrize("search", [True, False])
def test_a_lambda_search_records_one_path_span_and_counts_it(search):
    """ONE ``train.glm.path`` a ``lambda_search`` job, around the lambda
    loop, with what the walk did; the two declared counters grow by it
    once a job; the ring holds the parts' events plus that one. A job
    without a search opens none and counts nothing."""
    fr = _frame()
    before = {k: telemetry.value(f"train.glm.path.{k}")
              for k in ("lambdas", "iterations")}
    events = _events_of(lambda: _TRAIN["glm_search" if search else "glm"](fr))
    grew = {k: telemetry.value(f"train.glm.path.{k}") - v
            for k, v in before.items()}
    spans = [e for e in events
             if e["kind"] == "span" and e["what"].startswith("train.")]
    paths = _spans(events, "train.glm.path")
    parts = [e for e in spans if e["what"] in (
        "train.glm", "train.glm.design", "train.glm.start", "train.glm.gram",
        "train.glm.solve", "train.glm.probe", "train.glm.finish",
        "train.glm.metrics", "train.program.load")]
    assert len(spans) == len(parts) + len(paths)
    if not search:
        assert paths == [] and grew == {"lambdas": 0, "iterations": 0}
        return
    (path,) = paths
    assert path["lambdas_planned"] == 6
    assert 1 <= path["lambdas_fit"] <= 6
    assert path["stopped_early"] == (path["lambdas_fit"] < 6)
    # every iteration is a .gram and a .solve inside the path; the
    # lambda_max pass is the one .gram outside it
    inside = [e for e in spans if e.get("parent") == path["span"]]
    grams = [e for e in inside if e["what"] == "train.glm.gram"]
    assert path["iterations"] == len(grams) == len(
        [e for e in inside if e["what"] == "train.glm.solve"])
    assert len(_spans(events, "train.glm.gram")) == len(grams) + 1
    assert 0 <= path["active"] <= _F and path["lambda_final"] > 0
    assert grew == {"lambdas": path["lambdas_fit"],
                    "iterations": path["iterations"]}


@pytest.mark.parametrize("algo", ["glm", "glm_search"])
def test_glm_gram_spans_carry_the_block_plan(monkeypatch, algo):
    """Every ``train.glm.gram`` span says how `gram_accumulate` cut the
    design the step was handed (a shard's rows on this 8-device mesh):
    `gram.block_plan` of that shape. The budget is lowered so that the
    frame splits: three 1024-row blocks and a 768-row tail a shard."""
    from h2o_tpu.backend.kernels import gram
    from h2o_tpu.parallel import mesh as meshmod

    P = _F + 1                                    # the intercept column
    monkeypatch.setattr(gram, "_BLOCK_CELLS", 1024 * P)
    n = 30_000
    rows = meshmod.padded_len(n) // meshmod.n_row_shards()
    want = gram.block_plan(rows, P)
    assert want == (3, 1024, 768)
    fr = _frame(n)
    got = _spans(_events_of(lambda: _TRAIN[algo](fr)), "train.glm.gram")
    assert got
    for e in got:
        assert (e["gram_blocks"], e["gram_block_rows"],
                e["gram_tail_rows"]) == want, e


@pytest.mark.parametrize("histogram_type,n", [
    ("AUTO", 30_000), ("QuantilesGlobal", 300_000), ("UniformAdaptive", _N)])
def test_gbm_sketch_span_carries_the_sketch_plan(histogram_type, n):
    """``train.gbm.sketch`` says which count contraction ran and in what
    blocks: `binning._sketch_plan` of a shard's rows (8-device mesh) and
    `_sketch_digits` of the sketch's 1024 bins. A histogram type that reads
    no quantiles runs no sketch and says nothing."""
    from h2o_tpu.backend.memory import hbm_budget_bytes
    from h2o_tpu.models.tree import binning
    from h2o_tpu.parallel import mesh as meshmod

    fr = _frame(n)
    (span,) = _spans(_events_of(
        lambda: _train_gbm(fr, histogram_type=histogram_type)),
        "train.gbm.sketch")
    got = {k: v for k, v in span.items() if k.startswith("sketch_")}
    if histogram_type == "UniformAdaptive":
        assert got == {}
        return
    rows = meshmod.padded_len(n) // meshmod.n_row_shards()
    rb, Fb = binning._sketch_plan(rows, _F, 1024, hbm_budget_bytes())
    assert Fb == _F and rb == min(binning._SKETCH_ROW_BLOCK,
                                  1 << (rows - 1).bit_length())
    assert got == {"sketch_digits": "32x32", "sketch_row_block": rb,
                   "sketch_col_blocks": 1, "sketch_scan_steps": -(-rows // rb)}
    assert got["sketch_scan_steps"] == (1 if n == 30_000 else 2)


@pytest.mark.parametrize("algo,bins,code_bytes", [
    ("gbm", 21, 1), ("xgboost", 257, 2)])
def test_gbm_chunk_spans_carry_the_histogram_plan(algo, bins, code_bytes):
    """``train.gbm.binned_view`` says what a stored code costs, every
    ``train.gbm.chunk`` the plan of the level histogram (`engine.
    hist_plan_attrs` of a shard's rows on this 8-device mesh) with the leaf
    table's length and the form it is read in, and the
    counter ``train.gbm.hist_onehot_cells`` grows at each dispatch by rows
    x features x bins (NA slot in) x levels x the chunk's trees: a GBM at
    its 20 bins on int8 codes, the XGBoost builder at its 256 on int16."""
    from h2o_tpu.parallel import mesh as meshmod

    fr = _frame()
    before = telemetry.value("train.gbm.hist_onehot_cells")
    events = _events_of(lambda: _TRAIN[algo](fr))
    (view,) = _spans(events, "train.gbm.binned_view")
    assert view["code_bytes"] == code_bytes
    assert view["coded_gb"] == pytest.approx(_N * _F * code_bytes / 1e9)
    chunks = _spans(events, "train.gbm.chunk")
    assert len(chunks) == 2
    rows = _N // meshmod.n_row_shards()
    for e in chunks:
        assert {k: e[k] for k in ("hist_bins", "hist_row_block", "hist_blocks",
                                  "hist_groups", "n_lv_max", "leaf_nodes",
                                  "leaf_read")} == {
            "hist_bins": bins, "hist_row_block": rows, "hist_blocks": 1,
            "hist_groups": 0, "n_lv_max": 4, "leaf_nodes": 15,
            "leaf_read": "select_tree"}, e
    grew = telemetry.value("train.gbm.hist_onehot_cells") - before
    assert grew == _N * _F * bins * 3 * 4      # depth 3, 4 trees


# ---------------------------------------------------------------------------
# (c) the program load and the compile events
# ---------------------------------------------------------------------------
def test_glm_job_records_its_program_load_and_named_compiles():
    fr = _frame()
    events = _events_of(lambda: _train_glm(fr))
    (load,) = _spans(events, "train.program.load")
    assert load["program"].startswith("train.glm.irls.binomial")
    assert load["compiles"] >= 1 and load["uncached"] == load["compiles"]
    compiles = [e for e in events if e["kind"] == "compile"]
    assert compiles
    for e in compiles:
        assert e["what"] != "backend_compile" and e["cached"] is False
    assert any(e["what"] == "jit(glm_probe)" for e in compiles)


def test_compile_event_says_when_it_was_a_cache_replay():
    """jax fires the cache-hit event before the duration event of the same
    program on the same thread: the pair is one ``cached`` compile event."""
    compilemeter.install()
    seq0 = timeline.total_recorded()
    compilemeter._event_listener(compilemeter._CACHE_HIT_EVENT)
    compilemeter._listener(compilemeter._COMPILE_EVENT, 0.25,
                           fun_name="jit(replayed)")
    compilemeter._listener(compilemeter._COMPILE_EVENT, 0.5,
                           fun_name="jit(built)")
    compilemeter._listener(compilemeter._COMPILE_EVENT, 0.5)
    # hits whose duration events never came are spent on ONE event
    compilemeter._event_listener(compilemeter._CACHE_HIT_EVENT)
    compilemeter._event_listener(compilemeter._CACHE_HIT_EVENT)
    compilemeter._listener(compilemeter._COMPILE_EVENT, 0.25,
                           fun_name="jit(stale)")
    compilemeter._listener(compilemeter._COMPILE_EVENT, 0.5,
                           fun_name="jit(next)")
    got = [(e["what"], e["cached"])
           for e in timeline.snapshot(kind="compile", since=seq0)]
    assert got == [("jit(replayed)", True), ("jit(built)", False),
                   ("backend_compile", False), ("jit(stale)", True),
                   ("jit(next)", False)]


# ---------------------------------------------------------------------------
# (c2) device programs: one declared name from the jit site to the trace
# ---------------------------------------------------------------------------
_PKG = pathlib.Path(__file__).resolve().parent.parent / "h2o_tpu"


def _dotted(node) -> str:
    return ast.unparse(node) if isinstance(node, (ast.Name, ast.Attribute)) \
        else ""


def _is_jit(node) -> bool:
    """``jax.jit`` itself, a call of it, or ``functools.partial(jax.jit, ..)``."""
    if isinstance(node, ast.Call):
        return _is_jit(node.func) or (
            _dotted(node.func).endswith("partial") and bool(node.args)
            and _is_jit(node.args[0]))
    return _dotted(node) in ("jax.jit", "jit")


@functools.lru_cache(maxsize=1)
def _jit_sites():
    """By an AST walk of the package (no import of jax): ``declared``, every
    literal handed to ``telemetry.program`` with the function it decorates;
    ``jitted``, the names of the functions the package jits: decorated with
    jit, named inside a ``jax.jit(...)`` or ``shard_map(...)`` call, or
    returned by a factory whose call is jitted (``jax.jit(factory(..))``)."""
    declared, jitted = [], set()
    for path in sorted(_PKG.rglob("*.py")):
        tree = ast.parse(path.read_text())
        defs = [n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)]
        for fn in defs:
            for dec in fn.decorator_list:
                if _is_jit(dec):
                    jitted.add((path.name, fn.name))
                if (isinstance(dec, ast.Call)
                        and _dotted(dec.func) == "telemetry.program"):
                    (lit,) = dec.args
                    declared.append((path.name, fn.name, lit.value))
        inside = set()
        for call in (n for n in ast.walk(tree) if isinstance(n, ast.Call)):
            if _is_jit(call.func) or _dotted(call.func) == "shard_map":
                inside |= {n.id for a in call.args for n in ast.walk(a)
                           if isinstance(n, ast.Name)}
        for fn in defs:
            if fn.name in inside:
                jitted.add((path.name, fn.name))
                # a jitted factory's product: the function it returns
                jitted |= {(path.name, r.value.id) for r in ast.walk(fn)
                           if isinstance(r, ast.Return)
                           and isinstance(r.value, ast.Name)}
    return declared, jitted


def test_undeclared_program_raises():
    with pytest.raises(KeyError, match="nope"):
        telemetry.program("nope")
    assert all(re.fullmatch(r"[a-z0-9_]+", p) for p in telemetry.PROGRAMS)
    assert len(set(telemetry.PROGRAMS)) == len(telemetry.PROGRAMS)


@pytest.mark.parametrize("name", telemetry.PROGRAMS)
def test_declared_program_is_on_a_jitted_function(name):
    declared, jitted = _jit_sites()
    on = [(mod, fn) for mod, fn, lit in declared if lit == name]
    assert on, f"{name} decorates nothing"
    for site in on:
        assert site in jitted, f"{site} carries {name} and is never jitted"


def test_no_jitted_function_is_called_spmd_and_names_are_declared():
    """Five programs of the package were ``jit(spmd)`` in a device trace;
    each has a declared name of its own now, and ``telemetry.program`` is
    handed declared literals only."""
    declared, jitted = _jit_sites()
    named = {(mod, fn) for mod, fn, _ in declared}
    assert [s for s in jitted if s[1] == "spmd" and s not in named] == []
    assert {lit for *_, lit in declared} == set(telemetry.PROGRAMS)


def test_program_decorator_renames_the_xla_module():
    @telemetry.program("gbm_setup_keys")
    def anything(x):
        return x + 1

    assert anything.__name__ == "gbm_setup_keys"
    text = jax.jit(anything).lower(jnp.ones(3)).as_text()
    assert "module @jit_gbm_setup_keys " in text


def _compiled_names(run):
    return [e["what"] for e in _events_of(run) if e["kind"] == "compile"]


@pytest.mark.parametrize("algo", sorted(_TRAIN))
def test_a_job_compiles_under_declared_names_or_bare_primitives(algo):
    """A compile event of a job names a declared program, or a primitive jax
    dispatched eagerly (``jit(concatenate)``): never a function the package
    jits under a name of its own that ``PROGRAMS`` does not hold. Fresh
    shapes, so that the job compiles its programs in this process."""
    _, jitted = _jit_sites()
    own = {fn for _, fn in jitted}
    fr = _frame(_N + 8 * (1 + sorted(_TRAIN).index(algo)))
    names = _compiled_names(lambda: _TRAIN[algo](fr))
    assert names
    got = {re.fullmatch(r"jit\((.*)\)", n).group(1) for n in names}
    assert got & set(telemetry.PROGRAMS)
    assert [n for n in got - set(telemetry.PROGRAMS) if n in own] == []


@pytest.mark.parametrize("pipeline", ["1", "0"])
def test_the_gbm_step_is_gbm_level(monkeypatch, pipeline):
    monkeypatch.setenv("H2O_TPU_PIPELINE", pipeline)
    fr = _frame(_N + 64 + 8 * int(pipeline))
    names = _compiled_names(lambda: _train_gbm(fr))
    assert "jit(gbm_level)" in names and "jit(spmd)" not in names
    from h2o_tpu.utils import programs

    assert {r["module"] for r in programs.snapshot().values()
            if r["name"] == "train.tree.step"} == {"jit_gbm_level"}


# ---------------------------------------------------------------------------
# (d) one trace from the client down
# ---------------------------------------------------------------------------
def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_client_train_roots_the_jobs_trace(monkeypatch, tmp_path):
    import h2o_tpu.api as h2o
    from h2o_tpu.backend.kvstore import STORE

    # rest.request stays off the ring (a request-rate span): the
    # per-process trace file sees every span
    monkeypatch.setenv("H2O_TPU_TRACE_DIR", str(tmp_path))
    h2o.init(port=_free_port())
    try:
        fr = _frame(2048)
        STORE.put_keyed(fr)
        est = h2o.H2OGeneralizedLinearEstimator(family="binomial", lambda_=0.0)
        events = _events_of(lambda: est.train(
            x=[f"x{i}" for i in range(_F)], y="y",
            training_frame=h2o.get_frame(fr.key)))
    finally:
        h2o.shutdown()
    (client,) = _spans(events, "client.train")
    assert "parent" not in client                      # the root
    for name in ("train.glm", "train.glm.gram", "train.program.load"):
        got = _spans(events, name)
        assert got and all(e["trace"] == client["trace"] for e in got), name
    traced = {}
    for e in telemetry.read_trace(telemetry.trace_path()):
        traced.setdefault(e["name"], set()).add(e["args"]["trace"])
    assert client["trace"] in traced["rest.request"]
