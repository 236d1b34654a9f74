"""Compile-only checks against the chip the test mesh cannot reach.

``jax.experimental.topologies`` gives a compile-only TPU v5e target from the
installed libtpu, without a chip: what default settings select on a TPU must
COMPILE for it. These run (never skip) on the CPU sandbox — they are the tests
that catch a default program the TPU compiler refuses, or compiles into
something no chip should run, before a chip is used.

Shapes are the HIGGS configurations': 11,000,000 rows a chip as `padded_len`
pads them (44,000,000 over the four chips of the train step, the
deployment of the cell ``higgs_gbm_train_4chip``), F=28, nbins=20, depth 5,
int8 codes, P=29 for the GLM design; and the XGBoost cell's step on one
chip: 256 bins, depth 6, int16 codes.
"""

import dataclasses
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import NamedSharding, PartitionSpec as P

from h2o_tpu.backend.kernels import gram
from h2o_tpu.parallel.mesh import ROWS, make_mesh

F, NBINS, INTERVAL = 28, 20, 10
HIGGS_PLEN = 11_010_048          # padded_len(11_000_000) on one device
HIGGS_4CHIP_ROWS = 44_000_000    # four times HIGGS: 11M rows a chip


@pytest.fixture(scope="module")
def v5e():
    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices


def _spec(mesh, shape, dtype, pspec=P()):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, pspec))


def _step_specs(mesh, R, code, nbins):
    """The arguments of a fused-score chunk step (`make_train_fn`) over R
    rows of ``code``-typed bins, as shapes on ``mesh``."""
    row = lambda dt: _spec(mesh, (R,), dt, P(ROWS))  # noqa: E731
    return (_spec(mesh, (R, F), code, P(ROWS, None)),         # binned codes
            row(jnp.float32), row(jnp.float32), row(jnp.float32),  # y, w, f
            _spec(mesh, (F, nbins - 1), jnp.float32),         # edges
            _spec(mesh, (F, nbins - 1), jnp.bool_),           # edge_ok
            _spec(mesh, (INTERVAL, 2), jnp.uint32),           # keys
            _spec(mesh, (INTERVAL,), jnp.float32),            # rates
            _spec(mesh, (F,), jnp.float32),                   # mono
            _spec(mesh, (F, F), jnp.bool_),                   # imat
            _spec(mesh, (F,), jnp.bool_),                     # iscat
            _spec(mesh, (F,), jnp.int32),                     # nedges
            _spec(mesh, (), jnp.float32))                     # trees done


def test_large_frames_pad_to_a_multiple_of_eight_row_blocks(v5e):
    """The TPU compiler's time on the engine's blocked scans is linear in
    the block count unless that count is a multiple of 8 (this target:
    195 s at 1343 blocks, 2.4 s at 1344) — so frames of a million rows and
    up pad each shard to eight 8192-row blocks."""
    from h2o_tpu.parallel.mesh import padded_len

    assert padded_len(11_000_000, make_mesh(v5e[:1])) == HIGGS_PLEN
    for mesh in (make_mesh(v5e[:1]), make_mesh(v5e)):
        per_shard = padded_len(11_000_000, mesh) // mesh.shape[ROWS]
        for block in (8192, 2048, 512):
            assert per_shard % block == 0 and (per_shard // block) % 8 == 0


@pytest.fixture(scope="module")
def default_train_step(v5e):
    """The chunk step `GBM._train` builds under default settings — pipelined
    level program, fused cadence score, donated margin — lowered and compiled
    for four v5e chips at the four-chip deployment's rows (44M: each chip
    scans what the one-chip cell's chip scans), once for every test that
    reads it: ``(lowered, compiled, seconds the compile took)``."""
    from h2o_tpu.frame.frame import Frame
    from h2o_tpu.models import gbm as gbm_mod
    from h2o_tpu.models.distributions import get_distribution
    from h2o_tpu.models.tree.engine import make_train_fn, plan_hist_groups
    from h2o_tpu.parallel.mesh import padded_len
    from h2o_tpu.utils.knobs import get_bool

    mesh = make_mesh(v5e)
    R = padded_len(HIGGS_4CHIP_ROWS, mesh)
    assert R == 4 * HIGGS_PLEN
    tiny = Frame.from_dict({"a": np.arange(8, dtype=np.float32),
                            "y": np.arange(8, dtype=np.float32) % 2})
    b = gbm_mod.GBM(gbm_mod.GBMParameters(
        training_frame=tiny, response_column="y", ntrees=20, max_depth=5,
        nbins=NBINS, seed=42, score_tree_interval=INTERVAL))
    dist = get_distribution("bernoulli")
    cfg = b._tree_config(1, nbins=NBINS)
    groups, blk = plan_hist_groups(
        np.full(F, NBINS - 1, np.int32), cfg.nbins + 1, cfg.block_rows,
        budget_bytes=12 << 30, n_lv_max=16, nvals=3)
    cfg = dataclasses.replace(
        cfg, ntrees=INTERVAL, block_rows=blk, hist_groups=groups,
        async_psum=get_bool("H2O_TPU_ASYNC_PSUM"), fused_score=True)
    train_fn = make_train_fn(
        cfg, b._make_grad_fn(dist, 1), mesh,
        score_fn=gbm_mod._metrics_raw_fn("Binomial", dist, False),
        score_spec=P(ROWS, None), donate=True)
    lowered = train_fn.lower(*_step_specs(mesh, R, jnp.int8, NBINS))
    t0 = time.time()
    compiled = lowered.compile()
    return lowered, compiled, time.time() - t0


def test_default_train_step_compiles_for_v5e_2x2(default_train_step):
    lowered, compiled, secs = default_train_step
    # the program holds no hand-written kernel (ROADMAP D2): every
    # accumulation is a scan the TPU compiler takes as it is
    assert "tpu_custom_call" not in lowered.as_text()
    assert compiled is not None
    # ~10 s on this sandbox's host; the block-count pathology above is 200+
    assert secs < 90


def test_default_train_step_has_no_gather_on_the_code_block(
        default_train_step):
    """Routing reads a row's code at its node's split feature by a select
    over the block's 28 codes. A per-row gather there is the TPU's serial
    gather path: 10.4 ns a row, 78% of a HIGGS GBM job's device time
    (PERF.md, PR 27). The optimised program must hold no ``gather`` whose
    operand is the ``s8[..., 28]`` code block, inside a fusion or out."""
    hlo = default_train_step[1].as_text()
    # optimised HLO names a gather's operands without their shapes: resolve
    # each through the line that defines it
    shape_of = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+)", hlo, re.M))
    codes = re.compile(r"s8\[[\d,]*\b28\]")
    assert any(codes.match(sh) for sh in shape_of.values())   # the block is there
    gathers = re.findall(r"^.* gather\((%[\w.\-]+),.*$", hlo, re.M)
    assert gathers                      # split search, leaf values: small tables
    bad = [op for op in gathers if codes.match(shape_of[op])]
    assert not bad, [(op, shape_of[op]) for op in bad]


def test_level_histograms_fold_node_and_statistic_into_one_dimension(
        default_train_step):
    """The level histogram contracts the block's one-hot with the node-routed
    statistics folded into ONE free dimension (``rk,rfb->kfb``, k = n_lv * V).
    With two (``rnv,rfb->fnbv``) the TPU compiler made the three statistics a
    convolution window padded by two on each side (``size=1x3 pad=0_0x2_2``)
    and the MXU's output width n_lv alone: 63,904 estimated cycles a block
    over a tree's five levels (7,498 + 9,713 + 13,413 + 11,264 + 22,016)
    against 36,995 folded. ``estimated_cycles`` is the compiler's own figure
    in each fusion's ``backend_config`` (jax 0.9.0 / libtpu 0.0.34); times the
    26,880 blocks of a 20-tree HIGGS job at 1.5 GHz it matched the chip to
    three digits at levels 3 and 4 and within 20% at the others (PERF.md
    section 7 no. 19), so it ranks formulations before a chip is used."""
    hlo = default_train_step[1].as_text()
    cycles = [int(c) for op, c in re.findall(
        r'^.* fusion\(.*op_name="([^"]*)".*"estimated_cycles":"(\d+)"',
        hlo, re.M) if "gbm.hist" in op and "dot_general" in op]
    assert len(cycles) == 5, cycles              # one contraction a level
    assert sum(cycles) < 45_000, cycles
    windows = [w for w, op in re.findall(
        r'^.* convolution\(.*window=\{([^}]*)\}.*op_name="([^"]*)"', hlo, re.M)
        if "gbm.hist" in op]
    assert len(windows) == 5, windows
    assert not [w for w in windows if re.search(r"pad=0_0x\d+_\d+", w)], windows


@pytest.fixture(scope="module")
def xgb_train_step(v5e):
    """The chunk step `XGBoost` builds at its documented settings (the cell
    ``higgs_xgb_train``: 256 bins, depth 6, lambda 1, hessian child weight,
    int16 codes), lowered and compiled for ONE v5e chip at the cell's rows:
    ``(lowered, compiled)``."""
    from h2o_tpu.frame.chunks import BinnedView
    from h2o_tpu.frame.frame import Frame
    from h2o_tpu.models import gbm as gbm_mod
    from h2o_tpu.models import xgboost as xgb_mod
    from h2o_tpu.models.distributions import get_distribution
    from h2o_tpu.models.tree.engine import make_train_fn, plan_hist_groups
    from h2o_tpu.utils.knobs import get_bool

    nb = 256
    mesh = make_mesh(v5e[:1])
    tiny = Frame.from_dict({"a": np.arange(8, dtype=np.float32),
                            "y": np.arange(8, dtype=np.float32) % 2})
    b = xgb_mod.XGBoost(xgb_mod.XGBoostParameters(
        training_frame=tiny, response_column="y", ntrees=20, seed=42,
        score_tree_interval=INTERVAL))
    dist = get_distribution("bernoulli")
    cfg = b._tree_config(1, nbins=nb)
    assert (cfg.max_depth, cfg.nbins, cfg.reg_lambda, cfg.min_rows,
            cfg.child_weight_hessian) == (6, nb, 1.0, 1.0, True)
    groups, blk = plan_hist_groups(
        np.full(F, nb - 1, np.int32), nb + 1, cfg.block_rows,
        budget_bytes=12 << 30, n_lv_max=32, nvals=3)
    assert (groups, blk) == (None, 8192)
    cfg = dataclasses.replace(
        cfg, ntrees=INTERVAL, block_rows=blk, hist_groups=groups,
        async_psum=get_bool("H2O_TPU_ASYNC_PSUM"), fused_score=True)
    train_fn = make_train_fn(
        cfg, b._make_grad_fn(dist, 1), mesh,
        score_fn=gbm_mod._metrics_raw_fn("Binomial", dist, False),
        score_spec=P(ROWS, None), donate=True)
    code = BinnedView.code_dtype(nb + 1)
    assert code == jnp.int16
    lowered = train_fn.lower(*_step_specs(mesh, HIGGS_PLEN, code, nb))
    return lowered, lowered.compile()


def test_xgboost_step_compiles_for_one_v5e_chip_at_256_bins(xgb_train_step):
    """What no GBM cell compiles: ``s16`` codes, a (8192, 28, 257) one-hot
    and six levels. The TPU compiler takes it as it is (no hand-written
    kernel), streams the cell's 1,344 row blocks, holds one ``gbm.hist``
    contraction a level, reads no code by a per-row gather, and needs under
    2 GB of temporaries beside its 0.84 GB of arguments (1.32 GB on jax
    0.9.0 / libtpu 0.0.34; the GBM cells' step 0.66 GB)."""
    lowered, compiled = xgb_train_step
    assert "tpu_custom_call" not in lowered.as_text()
    hlo = compiled.as_text()
    assert re.search(r"s16\[1344,8192,28\]", hlo)        # the scanned codes
    hist = [op for op in re.findall(
        r'^.* fusion\(.*op_name="([^"]*)"', hlo, re.M)
        if "gbm.hist" in op and "dot_general" in op]
    assert len(hist) == 6, hist
    shape_of = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+)", hlo, re.M))
    codes = re.compile(r"s(16|32)\[[\d,]*\b28\]")
    gathers = re.findall(r"^.* gather\((%[\w.\-]+),.*$", hlo, re.M)
    bad = [op for op in gathers if codes.match(shape_of[op])]
    assert not bad, [(op, shape_of[op]) for op in bad]
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


def _computations(hlo: str) -> dict[str, str]:
    """name -> body text of every computation of an optimised program."""
    return dict(re.findall(
        r"^(?:ENTRY )?(%[\w.\-]+) \([^\n]*\) -> [^\n]*\{\n(.*?)^\}", hlo,
        re.M | re.S))


def _depth12_leaf_read(v5e):
    """``f + _leaf_read(v, node)`` alone at DRF's depth cap (12: 8,191
    nodes) over one chip's HIGGS rows: ``(compiled, seconds)``."""
    from h2o_tpu.models.tree.engine import _leaf_read

    mesh = make_mesh(v5e[:1])
    lowered = jax.jit(lambda v, node, f: f + _leaf_read(v, node)).lower(
        _spec(mesh, (8191,), jnp.float32),
        _spec(mesh, (HIGGS_PLEN,), jnp.int32),
        _spec(mesh, (HIGGS_PLEN,), jnp.float32))
    t0 = time.time()
    compiled = lowered.compile()
    return compiled, time.time() - t0


@pytest.mark.parametrize("program,leaf_ops,temp_limit", [
    ("default_train_step", 1, 1.0e9),    # 0.839 GB (0.841 before the read)
    ("xgb_train_step", 1, 2e9),          # 1.3245 GB (1.3241)
    ("depth12_leaf_read", 0, 64 << 20),  # 48.5 MB: seven bit masks of the rows
], ids=["default_train_step", "xgb_train_step", "depth12_leaf_read"])
def test_leaf_values_are_read_in_one_dense_pass(v5e, request, program,
                                                leaf_ops, temp_limit):
    """`leaf_delta` reads ``vl[node]`` as a select tree on the node id's
    bits (`engine._leaf_read`): with the margin's update and the out-of-bag
    sums it is ONE row-sized fusion under ``gbm.leaf`` in each compiled
    step, and no ``gather`` has a row-sized result anywhere. As `jnp.take`
    the 127-entry table of the XGBoost step was a serial gather (``fusion
    f32[11010048] = fusion(f32[127], s32[11010048])``, 9.4 ns a row, 2.06 s
    of a 12.1 s job) and the 63-entry table of the GBM step, inside the
    step's loop body, 56 ``compare_reduce_fusion pred[11010048]``, a
    ``compare_select_fusion`` and an add: 58 row-sized operations where
    this counts 1, 3 and a row-sized gather in the XGBoost step (PERF.md,
    PR 35). The third case is the read alone at depth 12, 8,191 nodes: a
    64-step loop of 128-entry trees that compiles in seconds (1.3 s here)
    and holds no row-by-``n_nodes`` temporary."""
    if program == "depth12_leaf_read":
        compiled, secs = _depth12_leaf_read(v5e)
        assert secs < 30
    else:
        compiled = request.getfixturevalue(program)[1]
    hlo = compiled.as_text()
    fused = set(re.findall(r"calls=(%[\w.\-]+)", hlo))
    gathers, leaf = [], []
    for comp, body in _computations(hlo).items():
        for line in body.splitlines():
            m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
            if not m or str(HIGGS_PLEN) not in m.group(1):
                continue
            result, kind = m.groups()
            if kind == "gather":
                gathers.append(line.strip()[:120])
            op = re.search(r'op_name="([^"]*)"', line)
            if (comp not in fused and op and "gbm.leaf" in op.group(1)
                    and "_node_totals" not in op.group(1)
                    and kind not in ("get-tuple-element", "bitcast", "tuple",
                                     "parameter")):
                leaf.append((kind, result[:60]))
    assert not gathers, gathers
    assert len(leaf) == leaf_ops, leaf
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit
    if program == "depth12_leaf_read":
        # the table is read 128 entries a loop step (64 steps by
        # `_leaf_tree_plan`), never unrolled into 8,190 selects
        assert len(re.findall(r" while\(", hlo)) == 1


def _collectives(hlo: str) -> list[tuple[str, str]]:
    """(result shapes, kind) of every collective in an optimised program."""
    return re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?) (all-gather|all-reduce|all-to-all|"
        r"collective-permute|reduce-scatter)(?:-start)?\(", hlo, re.M)


def test_default_train_step_moves_no_rows_between_chips(default_train_step):
    """Across four chips the train step reduces histograms and node totals,
    never rows. Left to GSPMD, a scan over row blocks of a row-sharded array
    compiled to an all-gather of the whole array in every iteration (PR 21:
    the GBM phase 288 s instead of 53 s), which no one-chip cell shows. The
    optimised four-chip program holds no ``all-gather`` (nor an all-to-all
    or a collective-permute), and no ``all-reduce`` whose result has a
    dimension the size of a shard's rows or of one of its row blocks."""
    hlo = default_train_step[1].as_text()
    colls = _collectives(hlo)
    assert colls, "a four-chip step with no collective reduces nothing"
    assert {k for _, k in colls} == {"all-reduce"}, sorted(
        {(k, sh[:60]) for sh, k in colls if k != "all-reduce"})
    row_sized = {HIGGS_PLEN, HIGGS_PLEN // 8192, 8192, 4096, 2048, 1024, 512}
    for shapes, _ in colls:
        for dims in re.findall(r"\[([\d,]*)\]", shapes):
            assert not row_sized & {int(d) for d in dims.split(",") if d}, shapes
    # the level histograms are among them: f32[28, n_lv, 21, 3]
    assert any(re.search(r"f32\[28,16,21,3\]", sh) for sh, _ in colls)


@pytest.mark.parametrize("rows,has_z", [
    (HIGGS_PLEN, True),            # the one-chip cell's design
    (HIGGS_PLEN // 4, True),       # a shard of it on four chips: 2,752,512
    (HIGGS_PLEN, False),           # PCA's Gram: a mask as weight, no z
], ids=["higgs", "four_chip_shard", "no_response"])
def test_default_gram_compiles_for_v5e_at_higgs_rows(v5e, rows, has_z):
    """`gram_accumulate` at the plan it really picks for 11M x 29 (sixteen
    688,128-row blocks; three of 917,504 for a four-chip shard) reads the
    design where it lies: the optimised program holds no ``copy``, ``pad``,
    ``concatenate`` or ``dynamic-update-slice`` with a row-sized dimension
    and no temporary to speak of. Handed to ``lax.scan`` as ``xs`` in ten
    blocks of 1,101,005 the same design was padded, copied whole and
    re-tiled a block: 3.96 GB of temporaries, 0.37 s of copies around
    0.014 s of arithmetic in every IRLS job of the chip (PERF.md, PR 31)."""
    mesh = make_mesh(v5e[:1])
    vec = _spec(mesh, (rows,), jnp.float32)
    X = _spec(mesh, (rows, F + 1), jnp.float32)
    args = (X, vec, vec) if has_z else (X, vec)
    lowered = jax.jit(lambda X, W, z=None: gram.gram_accumulate(
        X, W, z)).lower(*args)
    t0 = time.time()
    compiled = lowered.compile()
    secs = time.time() - t0
    nblk, rb, tail = gram.block_plan(rows, F + 1)
    assert nblk > 1 and rb % 1024 == 0      # the blocked path is compiled
    moved = re.findall(
        r"^\s*(?:ROOT )?%[\w.\-]+ = (.*?) (copy|copy-start|pad|concatenate|"
        r"dynamic-update-slice)\(", compiled.as_text(), re.M)
    assert moved        # the (29, 29) and (29,) results copied out at the end
    row_sized = [(op, sh) for sh, op in moved
                 for dims in re.findall(r"\[([\d,]+)\]", sh)
                 if max(int(d) for d in dims.split(",")) >= 1024]
    assert not row_sized, row_sized
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    # about a second on this sandbox's host; 51.6 s at 12 blocks of a scan
    assert secs < 30


# ---------------------------------------------------------------------------
# the quantile sketch (binning._sketch_core) at the HIGGS cells' shapes
# ---------------------------------------------------------------------------
SKETCH_NB = 1024
_QS = tuple(np.linspace(0, 1, NBINS + 1)[1:-1])


def _while_bodies(hlo: str) -> list[str]:
    """The text of every computation some ``while`` names as its body."""
    comps = _computations(hlo)
    return [comps[b] for b in sorted(set(re.findall(r"body=(%[\w.\-]+)", hlo)))]


def _sketch_program(v5e, chips: int):
    """(compiled text, rb): the sketch as `_sketch_block` dispatches it at
    11,010,048 rows a chip and the `rb` `_sketch_plan` gives under a v5e
    budget — `_hist_quantile_rows` on one chip, the `shard_map` form over
    the 2x2."""
    from h2o_tpu.models.tree import binning

    rb, Fb = binning._sketch_plan(HIGGS_PLEN, F, SKETCH_NB,
                                  int(16 * (1 << 30) * 0.85))
    assert Fb == F and rb != SKETCH_NB
    assert HIGGS_PLEN % (8 * rb) == 0               # block count: 8 divides
    mesh = make_mesh(v5e[:chips])
    X = _spec(mesh, (chips * HIGGS_PLEN, F), jnp.float32,
              P(ROWS, None) if chips > 1 else P())
    fn = (binning._sharded_sketch(mesh, _QS, SKETCH_NB, rb) if chips > 1
          else jax.jit(lambda X: binning._hist_quantile_rows(
              X, _QS, nb=SKETCH_NB, rb=rb)))
    compiled = fn.lower(X).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    return compiled.as_text(), rb


@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "shard_map_2x2"])
def test_sketch_counts_are_a_digit_contraction_on_the_mxu(v5e, chips):
    """Both passes of the sketch count a row block's 1024 bins as two
    32-wide digit one-hots contracted over the block's rows
    (``rfa,rfb->fab``), not as a ``(rb, 28, 1024)`` one-hot summed on the
    VPU: 292,448 estimated cycles a 1024-row block for that sum alone
    (285.6 a row; on the chip 0.2097 s a pass, the two longest operations of
    every GBM job until PR 33). In each loop body nothing is 1024 wide, the
    contraction is under scope ``gbm.sketch`` with no padded window, and the
    body's estimated cycles a row stay under 1.3 times what the kept form
    reads (75,520 a 32,768-row block, 2.30 a row; the chip 18.4 ms a pass,
    PERF.md section 6). The blocks are sliced out of the matrix where it
    lies: as ``xs`` of a scan it was copied whole first (2.64 GB of
    temporaries, and as long again as the counting)."""
    hlo, rb = _sketch_program(v5e, chips)
    bodies = [b for b in _while_bodies(hlo) if "gbm.sketch" in b]
    assert len(bodies) == 2, len(bodies)               # pass 1 and pass 2
    for body in bodies:
        shapes = re.findall(r"\b(?:f32|bf16|s32|s8|pred|u32)\[([\d,]+)\]", body)
        assert shapes
        # no (rb, 28, 1024) one-hot, nor any other 1024-wide value
        assert not [d for d in shapes if str(SKETCH_NB) in d.split(",")]
        assert re.search(
            r'fusion\(.*op_name="[^"]*gbm\.sketch[^"]*dot_general', body)
        cycles = sum(int(c) for c in re.findall(
            r'"estimated_cycles":"(\d+)"', body))
        assert 0 < cycles / rb < 1.3 * 2.30, cycles / rb
    # the contraction itself (inside the body's fusion): one a pass, and the
    # 28 features a batch, not a window the compiler had to pad (PR 29)
    windows = [w for w, op in re.findall(
        r'^.* convolution\(.*window=\{([^}]*)\}.*op_name="([^"]*)"', hlo, re.M)
        if "gbm.sketch" in op]
    assert len(windows) == 2, windows
    assert not [w for w in windows if "pad=" in w], windows
    colls = _collectives(hlo)
    if chips == 1:
        assert not colls
        return
    # four chips reduce counts, extrema and the two (28, 1024) histograms,
    # never rows
    assert {k for _, k in colls} == {"all-reduce"}, colls
    sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
             for shapes, _ in colls
             for dims in re.findall(r"\[([\d,]*)\]", shapes)]
    assert max(sizes) == F * SKETCH_NB and sizes.count(F * SKETCH_NB) == 2, colls


# ---------------------------------------------------------------------------
# the level histogram's two forms (`hist.digit_split`) at the XGBoost cell's
# shapes: 257 bins with the NA slot, int16 codes, 8192-row blocks
# ---------------------------------------------------------------------------
def _level_pass_body(v5e, monkeypatch, n_lv: int, split):
    """(while body text, estimated cycles of its fusions, the text of the
    computation its ``gbm.hist`` contraction's fusion calls): one streamed
    level pass at ``n_lv`` over the cell's 11,010,048 rows, every
    contribution in ``split`` (None: flat)."""
    from h2o_tpu.backend.kernels import hist

    monkeypatch.setattr(hist, "digit_split", lambda k, B: split)
    mesh = make_mesh(v5e[:1])
    fn = jax.jit(lambda X, nd, v: hist.streamed_route_hist(
        X, nd, v, None, offset=n_lv - 1, n_lv=n_lv, nbins_tot=257,
        block=8192)[0])
    hlo = fn.lower(_spec(mesh, (HIGGS_PLEN, F), jnp.int16),
                   _spec(mesh, (HIGGS_PLEN,), jnp.int32),
                   _spec(mesh, (HIGGS_PLEN, 3), jnp.float32)).compile().as_text()
    (body,) = [b for b in _while_bodies(hlo) if "gbm.hist" in b]
    (called,) = re.findall(r'fusion\(.*calls=(%[\w.\-]+).*op_name="[^"]*'
                           r'gbm\.hist[^"]*dot_general', body)
    return body, sum(int(c) for c in re.findall(
        r'"estimated_cycles":"(\d+)"', body)), _computations(hlo)[called]


@pytest.mark.parametrize("n_lv", [1, 2])
def test_shallow_256_bin_levels_are_a_digit_contraction_on_the_mxu(
        v5e, monkeypatch, n_lv):
    """Where the rule takes the digits (levels 0 and 1 of the XGBoost cell's
    tree), the block's contribution is ONE convolution with the 28 features
    as its batch (the sketch's ``window={size=28 stride=27 lhs_dilate=28}``,
    PR 33), its operands (the high digit's compare selecting the folded
    statistics, the low digit's one-hot) generated inside the fusion that
    holds it: nothing in the loop body is 257 wide, and the body's
    estimated cycles are under the flat form's at the same level (35,937
    against 61,473 at level 0, 51,886 against 65,050 at level 1, jax 0.9.0
    / libtpu 0.0.34; the chip: PERF.md section 7 no. 19)."""
    from h2o_tpu.backend.kernels import hist

    split = hist.digit_split(n_lv * 3, 257)
    assert split is not None
    H, L = split
    body, cycles, fused = _level_pass_body(v5e, monkeypatch, n_lv, split)
    _, flat_cycles, flat_fused = _level_pass_body(v5e, monkeypatch, n_lv,
                                                  None)
    wide = re.compile(r"\b(?:f32|bf16|s32|pred)\[[\d,]*\b257\b")
    assert wide.search(flat_fused)                    # flat: B wide
    assert not wide.search(body) and not wide.search(fused)
    convs = re.findall(r"^.* = (\S+) convolution\(.*window=\{([^}]*)\}.*"
                       r'op_name="([^"]*)"', fused, re.M)
    assert len(convs) == 1, convs
    (shape, window, op), = convs
    assert shape.startswith(f"f32[{F},{n_lv * 3 * H},{L}]")
    assert "gbm.hist" in op and "rfa,rfb->fab" in op
    assert f"size={F}" in window and f"lhs_dilate={F}" in window
    assert "pad=" not in window
    # no operand of the contraction is built apart: the body's only
    # (8192, 28, ...) values are the decoded codes
    wide = re.findall(r"\b(?:f32|bf16|s32|pred)\[8192,28,\d+\]", body)
    assert not wide, wide
    assert cycles < flat_cycles, (cycles, flat_cycles)


# ---------------------------------------------------------------------------
# the GAM's design program (gam._design_body) at the cell higgs_gam_train's
# shapes: 21 linear columns, seven smooths of ten knots, the intercept
# ---------------------------------------------------------------------------
def test_gam_design_is_written_in_place_for_v5e_at_higgs_rows(v5e):
    """`gam_design` at 11,010,048 x 85 builds 32,768-row blocks and writes
    each into the one output where it lies: no ``gather`` (the interval of
    a value comes from compares against the interior knots), no ``copy``,
    ``pad`` or ``concatenate`` with the frame's rows in it and one
    ``dynamic-update-slice`` a result, and temporaries of tens of MB.
    Written as one stack of 85 row vectors the same program kept every
    column as a temporary of its own, 3.75 GB beside the 3.89 GB design
    (PERF.md, PR 38); `searchsorted` and two `jnp.take` a smooth are the
    TPU's serial gather path (PERF.md section 7 nos. 14, 25)."""
    from h2o_tpu.models import gam

    mesh = make_mesh(v5e[:1])
    n_lin, n_smooth, K = 21, 7, 10
    col = _spec(mesh, (HIGGS_PLEN,), jnp.float32)
    small = lambda *shape: _spec(mesh, shape, jnp.float32)  # noqa: E731
    lin = ((col,) * n_lin, small(n_lin), small(n_lin), small(n_lin))
    program = gam._design_program(mesh, False, ((0,) * n_lin, 1, False),
                                  ((0, 0),) * n_smooth)
    compiled = program.lower(
        lin, (col,) * n_smooth, ((small(K),),) * n_smooth,
        (small(2 * K, K - 1),) * n_smooth, (small(K - 1),) * n_smooth).compile()
    hlo = compiled.as_text()
    assert "jit_gam_design" in hlo.split("\n", 1)[0]    # the declared name
    width = n_lin + n_smooth * (K - 1) + 1
    assert f"f32[{HIGGS_PLEN},{width}]" in hlo
    assert not re.findall(r"^.* gather\(", hlo, re.M)
    moved = re.findall(
        r"^\s*(?:ROOT )?%[\w.\-]+ = (\S+) (copy|copy-start|pad|concatenate|"
        r"dynamic-update-slice)\(", hlo, re.M)
    whole = sorted((op, sh.split("{")[0]) for sh, op in moved
                   if str(HIGGS_PLEN) in sh)
    assert whole == [("dynamic-update-slice", f"f32[{HIGGS_PLEN},{width}]"),
                     ("dynamic-update-slice", f"pred[{HIGGS_PLEN}]")], whole
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20
    assert mem.output_size_in_bytes < 4.0e9     # 85 -> 88 sublanes, not 128


# ---------------------------------------------------------------------------
# the GLM with all pairwise interactions of HIGGS's 28 columns (the cell
# higgs_glm_interact_train): the streamed IRLS step at P = 407
# ---------------------------------------------------------------------------
def test_the_streamed_interaction_step_never_holds_the_design(v5e):
    """`glm._make_interact_step` at 11,010,048 rows, 28 mains and their 378
    products: it reads the (R, 29) main design, and its Gram is ONE Mosaic
    kernel (`interact.interaction_gram`, a ``tpu_custom_call``) that forms
    each tile of 2,048 rows of the 407 columns in VMEM (5,376 tiles, the
    design and z padded to 512 rows, one Gram block). The compiled program
    holds no buffer of R x 406 cells or more (a stored design would be
    17.9 GB, over the chip), no buffer of 378 or 407 columns by more than a
    tile's rows (the (80896, 378) product block the loop body once built
    is gone) and under 1 GB of temporaries; ``X.T`` is a bitcast, not a
    copy; its XLA module is the declared ``jit_glm_interact_step``."""
    from h2o_tpu.backend.kernels import interact
    from h2o_tpu.models import glm

    mesh = make_mesh(v5e[:1])
    k = F
    pairs = tuple((i, j) for i in range(k) for j in range(i + 1, k))
    P1 = k + len(pairs) + 1
    layout = (k, tuple(range(k)), pairs)
    plan = interact.plan(HIGGS_PLEN, layout)
    assert (P1, plan) == (407, (2048, 5376, 512, 512))
    step = glm._make_interact_step(glm.BinomialF(), layout)
    vec = lambda n: _spec(mesh, (n,), jnp.float32)  # noqa: E731
    compiled = step.one_shard.lower(
        _spec(mesh, (HIGGS_PLEN, k + 1), jnp.float32), vec(HIGGS_PLEN),
        vec(HIGGS_PLEN), vec(P1), vec(HIGGS_PLEN), vec(k), vec(k),
        vec(len(pairs)), vec(len(pairs))).compile()
    hlo = compiled.as_text()
    assert "jit_glm_interact_step" in hlo.split("\n", 1)[0]
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          hlo)) == 1
    shapes = [[int(d) for d in dims.split(",")]
              for dims in re.findall(r"\[([\d,]+)\]", hlo)]
    cells = [int(np.prod(dims)) for dims in shapes]
    assert max(cells) < HIGGS_PLEN * (P1 - 1), max(cells)
    assert max(cells) == HIGGS_PLEN * (k + 1)      # the main design itself
    wide = [dims for dims in shapes if {len(pairs), P1} & set(dims)]
    by = [int(np.prod(dims)) // max({len(pairs), P1} & set(dims))
          for dims in wide]
    assert wide and max(by) <= plan.tile, wide
    moved = re.findall(r"= (\S+) (?:copy|transpose)\(", hlo)
    assert not [sh for sh in moved if str(HIGGS_PLEN) in sh], moved
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
