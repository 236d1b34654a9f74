"""Shared tree engine — the TPU-native `hex/tree/SharedTree.java` +
`ScoreBuildHistogram2` + `DTree` + `DHistogram`.

Reference hot loop (`hex/tree/ScoreBuildHistogram2.java:16-62`): per tree level,
one cluster-wide MRTask walks every row to its current leaf and accumulates
per-(leaf, column) histograms of {w, wY, wYY}; private per-thread copies avoid
CAS; reductions ship histogram arrays up the RPC tree. Split finding then runs
on the driver (`hex/tree/DTree.java` DecidedNode).

TPU-native redesign (SURVEY.md §7.6a):
- The ENTIRE multi-tree training loop is ONE XLA program: jit(shard_map(scan
  over trees)); there are no per-level host round-trips at all.
- Histogram accumulation is a one-hot matmul on the MXU — rows × small
  (node-count × 3) left operand against rows × (features × bins) one-hot right
  operand, blocked over rows via lax.scan so the one-hots live in VMEM and never
  materialize in HBM. This is the no-scatter, no-CAS design: the matmul IS the
  private-copy merge.
- Cross-device reduction is a single psum over the `rows` mesh axis per level
  (replacing `water/MRTask.java:855-926`'s two-level reduce tree).
- Split finding is vectorized over (feature, node, bin, NA-direction) on
  device, replicated on every shard (cheap; avoids a broadcast).
- Trees use a full-binary-tree layout (node i -> children 2i+1/2i+2) with
  static shapes, so deeper trees are masked work, never a recompile.
- Histograms accumulate {w, g, h} (weight/gradient/hessian) rather than
  {w, wY, wYY}: equivalent for gaussian and generalizes every distribution to
  Newton leaf values, which is how the XGBoost-equivalent backend (`hex/tree/
  xgboost`) also scores splits.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...backend import kernels
from ...backend.kernels import hist as hist_kernels
from ...parallel.mesh import ROWS, default_mesh, shard_map
from ...utils import telemetry


@dataclass(frozen=True)
class TreeConfig:
    ntrees: int = 50
    max_depth: int = 5
    nbins: int = 20              # real-value bins; bin index nbins = NA bucket
    min_rows: float = 10.0
    child_weight_hessian: bool = False  # `min_rows` bounds a child's HESSIAN
                                 # sum (XGBoost's min_child_weight), not its
                                 # row weight; set by `XGBoost._tree_config`
                                 # alone (a static branch, like reg_alpha's)
    learn_rate: float = 0.1
    reg_lambda: float = 0.0      # Newton denominator regularizer (0 = H2O SE gain)
    reg_alpha: float = 0.0       # L1 on leaf values (xgboost-style soft threshold)
    min_split_improvement: float = 1e-5
    sample_rate: float = 1.0     # per-tree row subsample
    col_sample_rate: float = 1.0         # per-split (level) column subsample
    col_sample_rate_per_tree: float = 1.0
    mtries: int = -1             # DRF: cols per split; -1 = auto
    drf_mode: bool = False       # trees fit at f=0, averaged at predict
    nclass: int = 1              # trees per iteration (multinomial K)
    block_rows: int = 8192       # row-block size for the histogram scan
    hist_groups: tuple | None = None  # width-bucketed feature partition
                                 # ((idx_tuple, width, mode), ...) for mixed
                                 # narrow/wide bin spaces (see
                                 # _build_level_hist / plan_hist_groups);
                                 # None = flat
    use_monotone: bool = False   # monotone_constraints active (static flag;
                                 # the per-feature directions ride as an array)
    use_interaction: bool = False  # interaction_constraints active (the
                                   # (F,F) may-interact matrix rides as an
                                   # array)
    leaf_quantile: float | None = None  # laplace/quantile leaf refit: leaf
                                   # value = this quantile of the residuals
                                   # in the leaf (`hex/tree/gbm/GBM.java:
                                   # 730,814` exact gamma leaves), computed
                                   # distributed via a 256-bin residual
                                   # histogram (bin-resolution exactness —
                                   # documented divergence)
    max_abs_leafnode_pred: float = float("inf")  # cap on the STORED leaf
                                   # prediction, i.e. AFTER the learn-rate
                                   # scale (`GBM.java:718` clips
                                   # learn_rate·gamma)
    col_sample_rate_change_per_level: float = 1.0  # multiplies the per-level
                                   # column sample rate each level deeper
                                   # (`SharedTreeModel` parameter)
    huber_leaf_alpha: float | None = None  # huber hybrid gamma leaf
                                   # (`GBM.java:685` fitBestConstantsHuber):
                                   # median(resid) + mean(sign·min(|resid −
                                   # median|, δ)), δ = alpha-quantile of
                                   # |resid| per tree
    use_sets: bool = False         # categorical SET splits: send an arbitrary
                                   # subset of levels left (`hex/tree/
                                   # DTree.java:198` IcedBitSet splits), found
                                   # by the sorted-by-G/H prefix search
                                   # (optimal for binary/regression losses —
                                   # Fisher/Breiman; same search the
                                   # reference's histogram runs after sorting
                                   # bins by response). Off = ordinal
                                   # code<=cut splits (pre-round-4 behavior,
                                   # kept for RuleFit's threshold-language
                                   # rules and models without categoricals).
    pipeline: bool = False         # async pipelined level program
                                   # (H2O_TPU_PIPELINE): route(L-1) fuses
                                   # into level L's histogram pass (one
                                   # streamed decode per block instead of
                                   # two), a row reads its node's split and
                                   # its code at the split feature through
                                   # integer selects over the level's nodes
                                   # and the block's F codes (`_route_rows`)
                                   # instead of one-hot matmuls, and the
                                   # carried margin is donated across chunk
                                   # dispatches. Bit-equal to the
                                   # synchronous oracle (pipeline=False) by
                                   # construction — routing is integer/
                                   # boolean work and every float
                                   # accumulation keeps the oracle's
                                   # per-block math and order.
    async_psum: bool = False       # overlapped per-level reduction
                                   # (H2O_TPU_ASYNC_PSUM): each hist
                                   # group's psum is issued as soon as its
                                   # local accumulation completes, before
                                   # the next group's scan is traced, so
                                   # the ICI collective overlaps the next
                                   # bucket's compute. Off = the PR 10
                                   # shape (one joint scan, psums after).
    fused_score: bool = False      # cadence scoring fused into the train
                                   # program: the chunk step emits the
                                   # score0-layout raw predictions as an
                                   # extra output while the final margin is
                                   # still resident, instead of the chunk
                                   # loop rematerializing them from f in a
                                   # standalone program per scoring
                                   # interval. Changes the train fn's
                                   # signature (extra ntrees-done scalar
                                   # arg + extra output) — see
                                   # make_train_fn.
    @property
    def n_nodes(self) -> int:
        return 2 ** (self.max_depth + 1) - 1


#: the row-block sizer lives with the kernels layer (every blocked
#: accumulation shares it); this alias keeps the engine's call sites
_block_rows = kernels.pow2_block_rows


def _onehot_pick(oh: jax.Array, v: jax.Array) -> jax.Array:
    """dot(one_hot, v) that is (near-)exact for real-valued v on TPU.

    The MXU multiplies in bf16 by default, so a plain dot returns bf16(v[j])
    (2⁻⁹ relative error) even though the one-hot has a single exact 1.
    Precision.HIGHEST fixes that but blocks fusion (measured 2.6x slower
    end-to-end on v5e). Instead split v = hi + lo with hi bf16-representable:
    dot(oh, hi) is exact, dot(oh, lo)'s error is ≤|v|·2⁻¹⁸ — f32-grade at
    DEFAULT precision (two cheap matvecs)."""
    hi = v.astype(jnp.bfloat16).astype(jnp.float32)
    lo = v - hi
    return (jnp.dot(oh, hi, preferred_element_type=jnp.float32)
            + jnp.dot(oh, lo, preferred_element_type=jnp.float32))


#: the form `_leaf_read` takes, as ``train.gbm.chunk`` names it
LEAF_READ = "select_tree"


def _leaf_tree_plan(n_nodes: int) -> tuple[int, int]:
    """``(width, chunks)`` of `_leaf_read`'s select tree over an
    ``n_nodes``-entry table, from the table's length alone: the tree spans
    the next power of two up to 128 entries (one fused pass over the rows
    at depth 5 and 6: 63 and 127 nodes), and deeper tables are read 128
    entries a pass (depth 12, DRF's cap: 64 passes over 8,191 nodes)."""
    width = min(1 << (n_nodes - 1).bit_length(), 128)
    return width, -(-n_nodes // width)


def _leaf_read(v: jax.Array, node: jax.Array) -> jax.Array:
    """``v[node]``: a row's value out of a small f32 table, with no
    data-dependent addressing — the element itself, bit for bit, as a
    gather returns it.

    `jnp.take` here was the TPU's serial gather path above 64 entries
    (9.4 ns a row at 127: 2.06 s of a 12.1 s XGBoost job) and, at 64 and
    under inside the step's loop body, the compiler's own expansion into 57
    row-sized compare / select passes (0.21 s of a 1.9 s GBM job; PERF.md,
    PR 35). This is the form the compiler finds for a bare 63-entry take,
    written out: a tree of selects on the index's low bits, ``width - 1``
    selects and ``log2(width)`` bit tests a row with the table's entries
    as scalar operands, one elementwise fusion over the rows. A table
    longer than the tree (`_leaf_tree_plan`) is read a chunk a loop step,
    the chunk picked by the index's high bits.

    A select moves bits, so every entry comes back as it is: -0.0, and a
    non-finite entry too, which reaches the rows of its own node alone
    (under a one-hot contraction ``0 * inf`` would make every row NaN). An
    index outside ``[0, len(v))`` reads 0.0 where `jnp.take` fills NaN;
    node ids never leave the table."""
    n = v.shape[0]
    width, chunks = _leaf_tree_plan(n)
    k = width.bit_length() - 1
    vp = jnp.pad(v, (0, chunks * width - n))
    bits = [((node >> b) & 1) == 1 for b in range(k)]
    hi = node >> k

    def chunk(c, acc):
        t = jax.lax.dynamic_slice_in_dim(vp, c * width, width)
        vals = [t[j] for j in range(width)]
        for bit in bits:
            vals = [jnp.where(bit, vals[j + 1], vals[j])
                    for j in range(0, len(vals), 2)]
        return jnp.where(hi == c, vals[0], acc)

    return jax.lax.fori_loop(0, chunks, chunk,
                             jnp.zeros(node.shape, v.dtype))


def _norm_groups(groups):
    """Normalize hist_groups entries to (idxs, width, mode): legacy 2-tuples
    (pre-mode persisted models) accumulate via the one-hot matmul."""
    return tuple((g[0], g[1], g[2] if len(g) > 2 else "onehot")
                 for g in groups)


# widths at/below the H2O_TPU_HIST_SEG_WIDTH knob accumulate via segment-sum
# (0 disables the path) — see the narrow-bin branch in _build_level_hist.
# The default (8) lives in the knob registry, h2o_tpu/utils/knobs.py.


def plan_hist_groups(nedges, B_hist: int, block_rows: int,
                     budget_bytes: int | None = None,
                     n_lv_max: int = 32, nvals: int = 3):
    """Auto-tuned histogram accumulation plan: (hist_groups | None, block).

    ``nedges`` (F,) per-column real-cut counts. Group width thresholds come
    from the per-column bin counts themselves: each column buckets at the
    next power of two above its width (data bins + NA slot + 1 for the
    cut<=bin offset), capped at the flat ``B_hist``. With mixed bin spaces
    (airlines-style 300-level categoricals next to 20-bin numerics) the flat
    (rb, F, B) one-hot pads EVERY feature to B_hist cells/row; grouped, each
    bucket pays only its own width. Grouping engages when it saves ≥ 40% of
    the accumulated cells (below that the extra scan bodies and scatter-back
    cost more than the padding — measured crossover). Buckets at/below the
    segment-sum width threshold accumulate via scatter-add instead of a
    degenerate-shape one-hot matmul.

    ``block`` is the histogram row-block size fitted to the HBM budget: the
    per-scan-step one-hot footprint rb·(Σ F_g·B_g)·4 B plus the rb·n_lv·V
    channel outer product stays under budget/12 (defaults to a 4 GiB
    planning budget when no accelerator budget is resolvable)."""
    from ...utils.knobs import get_int

    widths = np.asarray(nedges, np.int64) + 2  # data bins + NA slot
    F = int(widths.shape[0])
    by_w: dict[int, list[int]] = {}
    for f, wd in enumerate(widths):
        p2 = 1 << int(np.ceil(np.log2(max(int(wd), 2))))
        by_w.setdefault(min(p2, B_hist), []).append(f)
    grouped_cells = sum(len(fs) * wd for wd, fs in by_w.items())
    seg_w = get_int("H2O_TPU_HIST_SEG_WIDTH")
    groups = None
    if len(by_w) > 1 and grouped_cells < 0.6 * F * B_hist:
        groups = tuple(sorted(
            (tuple(fs), int(wd), "segsum" if wd <= seg_w else "onehot")
            for wd, fs in by_w.items()))
    cells_per_row = grouped_cells if groups else F * B_hist
    budget = budget_bytes or (4 << 30)
    step_cap = max(budget // 12, 1 << 20)
    blk = block_rows
    while blk > 512 and blk * (cells_per_row + n_lv_max * nvals) * 4 > step_cap:
        blk //= 2
    return groups, blk


# ---------------------------------------------------------------------------
# Histogram build (the ScoreBuildHistogram2 analog) — runs inside shard_map.
# ---------------------------------------------------------------------------
def _psum_hist(hist):
    """The level histogram's cross-shard reduction, under its own device
    scope (a capture on several chips tells the collective from the
    accumulation it follows)."""
    with telemetry.scope("gbm.psum"):
        return jax.lax.psum(hist, ROWS)


@telemetry.scope("gbm.hist")
def _build_level_hist(Xb, node, vals, offset, n_lv, nbins_tot, block,
                      groups=None, async_psum=False):
    """Accumulate hist (F, n_lv, nbins_tot, V) for nodes [offset, offset+n_lv).

    Xb: (Rl, F) int32 bins; node: (Rl,) int32 global node ids; vals: (Rl, V)
    accumulated channels ([w, g, h] for GBM; [wt, wty, wc, wcy] for uplift),
    already zeroed for inactive rows.

    ``groups`` (static): width-bucketed feature partition
    ``((feature_idx_tuple, group_width, mode), ...)`` (legacy 2-tuples mean
    mode="onehot") — with mixed bin widths (airlines-style 300-level
    categoricals next to 20-bin numerics) the flat (rb, F, B) one-hot pads
    EVERY feature to the widest feature's bins, so the accumulate burns
    F·B_max cells/row; grouped, each bucket pays only its own width
    (Σ F_g·B_g), each group's accumulator psums per group, and the
    histograms scatter back into the global (F, n_lv, B, V) layout once per
    level. mode="segsum" groups (narrow widths, degenerate MXU shapes)
    accumulate via a flat segment-sum instead of the one-hot matmul. Split
    finding is untouched. The group NA bucket is its last slot; global NA
    stays at ``nbins_tot - 1``. `plan_hist_groups` builds the partition.

    The blocked accumulation itself lives in `backend/kernels/hist.py`
    (one per-block math under a blocked ``lax.scan``); this function keeps
    the mesh concerns — node localization, the per-group
    psum, and the scatter-back into the global bin layout.
    """
    F = Xb.shape[1]

    local = node - offset
    active = (local >= 0) & (local < n_lv)
    lc = jnp.clip(local, 0, n_lv - 1)
    v = jnp.where(active[:, None], vals, 0.0)

    if groups is None:
        hist = hist_kernels.level_hist_blocks(
            Xb, lc, v, n_lv=n_lv, nbins_tot=nbins_tot, block=block)
        return _psum_hist(hist)

    groups = _norm_groups(groups)
    if async_psum:
        # overlapped reduction (H2O_TPU_ASYNC_PSUM): one scan PER group,
        # each group's psum issued before the next group's scan is traced —
        # on a real ICI the collective for bucket g overlaps bucket g+1's
        # local accumulation. Values are bit-equal to the joint scan (same
        # per-block contributions, same block order, same per-group psum).
        hists = [_psum_hist(hist_kernels.level_hist_one_group(
            Xb[:, list(idxs)], lc, v, Bg=Bg, mode=mode, n_lv=n_lv,
            nbins_tot=nbins_tot, block=block))
            for idxs, Bg, mode in groups]
    else:
        hists = [_psum_hist(hg)
                 for hg in hist_kernels.level_hist_blocks(
                     Xb, lc, v, n_lv=n_lv, nbins_tot=nbins_tot, block=block,
                     groups=groups)]
    # psum per group BEFORE the scatter-back: the wire carries Σ F_g·B_g
    # cells instead of the padded F·B_max the flat path reduces
    return _scatter_group_hists(hists, groups, F, n_lv, nbins_tot,
                                vals.shape[1])


@telemetry.scope("gbm.hist")
def _scatter_group_hists(hists, groups, F, n_lv, nbins_tot, V):
    """Per-group accumulators back into the global (F, n_lv, B, V) layout,
    each group's NA slot (its LAST bin) restored to the global NA bucket.
    The ONE definition both the synchronous and pipelined level programs
    scatter through — bit-parity between them rides on this block staying
    single-sourced."""
    na_global = nbins_tot - 1
    full = jnp.zeros((F, n_lv, nbins_tot, V), jnp.float32)
    for (idxs, Bg, _mode), hg in zip(groups, hists):
        ia = jnp.asarray(idxs)
        full = full.at[ia, :, :Bg - 1, :].set(hg[:, :, :Bg - 1, :])
        full = full.at[ia, :, na_global, :].set(hg[:, :, Bg - 1, :])
    return full


# ---------------------------------------------------------------------------
# Pipelined level program (H2O_TPU_PIPELINE) — fused route→hist streaming.
# ---------------------------------------------------------------------------
def _route_rows(xb_blk, node_blk, route_args, cfg: "TreeConfig"):
    """One block's routing off the previous level's splits, inside the
    streamed level pass, with no data-dependent addressing: a per-row
    gather runs on the TPU's serial gather path (10.4 ns a row on a v5e,
    78% of a HIGGS GBM job's device time; PERF.md, PR 27). Both per-row
    reads are SELECTS instead — a boolean one-hot of the row's index keeps
    the one survivor, a reduce brings it out. The node's split parameters
    come through the (rb, n_lv) node mask, and the row's code at the split
    feature through an (rb, F) feature mask: 1/B of the one-hot the
    histogram half builds from the same decoded block, whatever F, n_lv
    and the code dtype are.
    Routing is integer/boolean work end to end (int32 for int8, int16 and
    int32 codes alike), so the node ids are BIT-identical to the
    one-hot-matmul `_route` in `_grow_tree` (the synchronous oracle)."""
    bf, bb, bnal, do_split, catd_lv, isset, offset, n_lv = route_args
    local = node_blk - offset
    active = (local >= 0) & (local < n_lv)
    lc = jnp.clip(local, 0, n_lv - 1)
    at_node = jax.nn.one_hot(lc, n_lv, dtype=jnp.bool_)           # (rb, n_lv)
    bf_r = jnp.sum(jnp.where(at_node, bf[None, :], 0), axis=1)    # (rb,)
    row_bb = jnp.sum(jnp.where(at_node, bb[None, :], 0), axis=1)
    row_nal = jnp.any(at_node & bnal[None, :], axis=1)
    row_split = jnp.any(at_node & do_split[None, :], axis=1) & active
    at_feat = jax.nn.one_hot(bf_r, xb_blk.shape[1], dtype=jnp.bool_)
    xv = jnp.sum(jnp.where(at_feat, xb_blk.astype(jnp.int32), 0), axis=1)
    num_right = xv > row_bb
    if catd_lv is not None:
        # set-split direction read: the node's direction row at the row's
        # bin — one flat gather instead of the (rb, nbins) bin one-hot
        flatd = catd_lv.reshape(-1)
        idx = lc * cfg.nbins + jnp.clip(xv, 0, cfg.nbins - 1)
        cat_right = jnp.take(flatd, idx) > 0.5
        row_isset = jnp.take(isset, lc)
        num_right = jnp.where(row_isset, cat_right, num_right)
    go_right = jnp.where(xv == cfg.nbins, ~row_nal, num_right)
    return jnp.where(row_split,
                     2 * node_blk + 1 + go_right.astype(jnp.int32),
                     node_blk)


@telemetry.scope("gbm.route")
def _route_all(Xb, node, route_args, cfg: "TreeConfig"):
    """Blocked standalone routing pass (`_route_rows` per block) — the
    pipelined path's final route after the last level's splits."""
    Rl, F = Xb.shape
    rb = _block_rows(Rl, cfg.block_rows)
    _, node_b = jax.lax.scan(
        lambda c, blk: (c, _route_rows(blk[0], blk[1], route_args, cfg)),
        None, (Xb.reshape(Rl // rb, rb, F), node.reshape(Rl // rb, rb)))
    return node_b.reshape(Rl)


def _pipelined_level_hist(Xb, node, vals3, route_args, offset, n_lv,
                          nbins_tot, cfg: "TreeConfig"):
    """One pipelined level: advance ``node`` off the previous level's
    splits and accumulate this level's histogram, returning ``(hist,
    node)`` with ``hist`` already psummed and scattered back into the
    global (F, n_lv, B, V) layout — the drop-in replacement for the
    synchronous route-then-`_build_level_hist` pair.

    ONE streamed pass per level (`kernels.hist.streamed_route_hist`) —
    each row block is decoded once, routed, and accumulated while the next
    block streams in. With ``cfg.async_psum`` and a grouped plan, the
    stream carries the routing plus the FIRST width bucket and issues its
    psum before the remaining buckets' scans are traced (collective
    overlaps local accumulation); with async off, all buckets ride the
    single stream and psum after (the PR 10 shape)."""
    F = Xb.shape[1]
    groups = _norm_groups(cfg.hist_groups) if cfg.hist_groups else None

    route_fn = (None if route_args is None
                else lambda xb, nd: _route_rows(xb, nd, route_args, cfg))
    if groups is None:
        (h,), node = hist_kernels.streamed_route_hist(
            Xb, node, vals3, route_fn, offset=offset, n_lv=n_lv,
            nbins_tot=nbins_tot, block=cfg.block_rows)
        return _psum_hist(h), node

    if cfg.async_psum:
        # stream = route + lead bucket; its psum issues while the later
        # buckets' scans accumulate
        (h0,), node = hist_kernels.streamed_route_hist(
            Xb, node, vals3, route_fn, offset=offset, n_lv=n_lv,
            nbins_tot=nbins_tot, block=cfg.block_rows, groups=groups[:1])
        hists = [_psum_hist(h0)]
        local = node - offset
        active = (local >= 0) & (local < n_lv)
        lc = jnp.clip(local, 0, n_lv - 1)
        v = jnp.where(active[:, None], vals3, 0.0)
        for idxs, Bg, mode in groups[1:]:
            hg = hist_kernels.level_hist_one_group(
                Xb[:, list(idxs)], lc, v, Bg=Bg, mode=mode, n_lv=n_lv,
                nbins_tot=nbins_tot, block=cfg.block_rows)
            hists.append(_psum_hist(hg))
    else:
        hs, node = hist_kernels.streamed_route_hist(
            Xb, node, vals3, route_fn, offset=offset, n_lv=n_lv,
            nbins_tot=nbins_tot, block=cfg.block_rows, groups=groups)
        hists = [_psum_hist(h) for h in hs]
    return _scatter_group_hists(hists, groups, F, n_lv, nbins_tot,
                                vals3.shape[1]), node


@telemetry.scope("gbm.leaf")
def _leaf_quantile_vals(resid, w, node, n_nodes, q, block, qbins=256):
    """Per-node q-quantile of the residuals, distributed: (node, bin) weight
    histograms over a linear residual grid (one-hot einsums riding the MXU
    like every other accumulation here), psum across shards, the quantile read
    off the cumulative histogram, then the PER-NODE bracket refined and the
    histogram rebuilt — three passes contract each node's bracket by qbins³
    from the true global range (`hex/quantile/Quantile.java` iterates the same
    way). Refining per node (not one global robust span) means a leaf whose
    residuals sit entirely in the global tail reads its real quantile instead
    of a clamped edge-bin midpoint; rows outside a node's bracket clip into
    the edge bins but keep their cumulative mass, so the target index stays
    exact as long as the true quantile lies inside the bracket (guaranteed by
    the previous pass)."""
    ok = w > 0
    wz = jnp.where(ok, w, 0.0)
    Rl = resid.shape[0]
    rb = _block_rows(Rl, block)
    nblk = Rl // rb

    def node_hist(nd_r, bins_r, w_r):
        def body(acc, blk):
            nd, bb, ww = blk
            n_oh = (jax.nn.one_hot(nd, acc.shape[0], dtype=jnp.float32)
                    * ww[:, None])
            b_oh = jax.nn.one_hot(bb, qbins, dtype=jnp.float32)
            return acc + jnp.einsum("rn,rb->nb", n_oh, b_oh), None

        init = jnp.zeros((n_nodes, qbins), jnp.float32)
        h, _ = jax.lax.scan(body, init, (nd_r.reshape(nblk, rb),
                                         bins_r.reshape(nblk, rb),
                                         w_r.reshape(nblk, rb)))
        return jax.lax.psum(h, ROWS)

    gmin = jax.lax.pmin(jnp.min(jnp.where(ok, resid, jnp.inf)), ROWS)
    gmax = jax.lax.pmax(jnp.max(jnp.where(ok, resid, -jnp.inf)), ROWS)
    lo_n = jnp.full((n_nodes,), gmin, jnp.float32)
    hi_n = jnp.full((n_nodes,), gmax, jnp.float32)
    n_oh = jax.nn.one_hot(node, n_nodes, dtype=jnp.float32)
    tot = jnp.zeros((n_nodes,), jnp.float32)
    for _ in range(3):
        span_n = jnp.maximum(hi_n - lo_n, 1e-12)
        lo_row = _onehot_pick(n_oh, lo_n)
        span_row = jnp.maximum(_onehot_pick(n_oh, span_n), 1e-12)
        bins = jnp.clip(((resid - lo_row) / span_row * qbins)
                        .astype(jnp.int32), 0, qbins - 1)
        hist = node_hist(node, bins, wz)
        cum = jnp.cumsum(hist, axis=1)
        tot = cum[:, -1]
        target = q * tot
        idx = jnp.argmax(cum >= target[:, None], axis=1).astype(jnp.float32)
        lo_n, hi_n = (lo_n + idx / qbins * span_n,
                      lo_n + (idx + 1.0) / qbins * span_n)
    val = 0.5 * (lo_n + hi_n)
    return jnp.where(tot > 0, val, 0.0)


@telemetry.scope("gbm.leaf")
def _node_totals(node, vals, n_nodes, block):
    """Per-node channel totals (n_nodes, V) via the same blocked one-hot scan."""
    Rl = node.shape[0]
    V = vals.shape[1]
    rb = _block_rows(Rl, block)
    nblk = Rl // rb

    def body(acc, blk):
        nd, vv = blk
        n_oh = jax.nn.one_hot(nd, n_nodes, dtype=jnp.float32)
        return acc + jnp.einsum("rn,rv->nv", n_oh, vv), None

    tot, _ = jax.lax.scan(body, jnp.zeros((n_nodes, V), jnp.float32),
                          (node.reshape(nblk, rb), vals.reshape(nblk, rb, V)))
    return jax.lax.psum(tot, ROWS)


def _level_col_mask(lkey, F, n_lv, cfg: "TreeConfig", tree_cols,
                    level: int = 0):
    """Per-(feature, node) sampling mask for one level: mtries k-of-F draw
    (DRF, `hex/tree/drf/DRF.java` mtry) or Bernoulli col_sample_rate (GBM),
    scaled by col_sample_rate_change_per_level^level. The factor's range is
    (0, 2]: the Bernoulli rate saturates at 1.0, but the mtries k keeps
    growing past its base value up to F (DTree.actual_mtries())."""
    rate = min(max(cfg.col_sample_rate
                   * cfg.col_sample_rate_change_per_level ** level, 1e-6),
               1.0)
    if cfg.mtries > 0:
        # per-level factor scales the k-of-F draw in BOTH directions: the
        # reference's DTree.actual_mtries() grows mtries via pow(factor,
        # depth) up to ncols for factor > 1 (parameter range (0, 2])
        k = min(F, max(1, int(round(
            min(cfg.mtries, F)
            * cfg.col_sample_rate_change_per_level ** level))))
        u = jax.random.uniform(lkey, (F, n_lv))
        kth = jnp.sort(u, axis=0)[k - 1]
        cmask = u <= kth[None, :]
    elif rate < 1.0:
        cmask = jax.random.uniform(lkey, (F, n_lv)) < rate
        cmask = jnp.where(jnp.any(cmask, axis=0, keepdims=True), cmask, True)
    else:
        cmask = jnp.ones((F, n_lv), dtype=jnp.bool_)
    return cmask & tree_cols[:, None]


# ---------------------------------------------------------------------------
# Split finding (DTree.DecidedNode analog), vectorized on device.
# ---------------------------------------------------------------------------
@telemetry.scope("gbm.split")
def _find_splits(hist, colmask, edge_ok, cfg: TreeConfig, mono=None,
                 iscat=None, nedges=None):
    """hist: (F, n_lv, B, 3). Returns per-node best (gain, feat, bin, nan_left,
    node weight (the node's hessian sum under ``cfg.child_weight_hessian``:
    whichever `min_rows` bounds), left/right Newton values of the chosen
    split[, bin-direction rows + set flags when cfg.use_sets]).

    Candidates: split at bin b (left = bins <= b), b in 0..nb-2, NA bucket sent
    left or right (`hex/tree/DHistogram.java` NA bucket; direction chosen by
    gain like the reference's NASplitDir). ``mono`` (F,) in {-1,0,1} kills
    candidates whose child values violate the feature's monotone direction
    (`hex/tree/Constraints.java` role).

    With ``cfg.use_sets`` (and ``iscat``/``nedges`` arrays given), categorical
    features search SET splits instead of ordinal cuts: bins sorted by G/H
    (their Newton-value order), candidate k = best k-bin prefix goes left —
    the exact-optimal subset search for convex losses, equivalent to the
    reference's bitset split enumeration (`hex/tree/DTree.java:198`). The
    candidate axis is shared with the numeric search (prefix size k ≙ cut
    index b = k-1), so one argmax picks across both kinds.
    """
    nb = cfg.nbins
    W, G, H = hist[..., 0], hist[..., 1], hist[..., 2]
    lam = cfg.reg_lambda
    Wt = jnp.sum(W, axis=2)[0]  # (n_lv,) — identical across features
    Gt = jnp.sum(G, axis=2)[0]
    Ht = jnp.sum(H, axis=2)[0]

    cw = jnp.cumsum(W[:, :, :nb], axis=2)[:, :, :-1]  # (F, n_lv, nb-1)
    cg = jnp.cumsum(G[:, :, :nb], axis=2)[:, :, :-1]
    ch = jnp.cumsum(H[:, :, :nb], axis=2)[:, :, :-1]
    rank = None
    if cfg.use_sets and iscat is not None:
        # sorted-order prefix candidates for categorical features: empty bins
        # key to +inf (sorted last, never in a left prefix); stable argsort
        # twice gives each bin's rank, which the chosen node's direction row
        # reads back in _grow_tree
        Wr, Gr, Hr = W[:, :, :nb], G[:, :, :nb], H[:, :, :nb]
        key = jnp.where(Wr > 0, Gr / (Hr + 1e-10), jnp.inf)
        order = jnp.argsort(key, axis=2, stable=True)
        rank = jnp.argsort(order, axis=2, stable=True)
        cw_c = jnp.cumsum(jnp.take_along_axis(Wr, order, 2), 2)[:, :, :-1]
        cg_c = jnp.cumsum(jnp.take_along_axis(Gr, order, 2), 2)[:, :, :-1]
        ch_c = jnp.cumsum(jnp.take_along_axis(Hr, order, 2), 2)[:, :, :-1]
        isc = iscat[:, None, None]
        cw = jnp.where(isc, cw_c, cw)
        cg = jnp.where(isc, cg_c, cg)
        ch = jnp.where(isc, ch_c, ch)
        # a prefix of size k (candidate b = k-1) is meaningful up to ALL real
        # bins left + NA right (k = width_f, the NA-vs-rest split)
        cat_ok = jnp.arange(nb - 1)[None, :] <= nedges[:, None]
        edge_ok = jnp.where(iscat[:, None], cat_ok, edge_ok)
    wna = W[:, :, nb][:, :, None]
    gna = G[:, :, nb][:, :, None]
    hna = H[:, :, nb][:, :, None]

    alpha = cfg.reg_alpha

    def _soft(g):
        # xgboost-style L1 soft threshold on score numerators (no-op at α=0)
        return jnp.sign(g) * jnp.maximum(jnp.abs(g) - alpha, 0.0) if alpha > 0 else g

    def child_vals(gl, hl):
        gr = Gt[None, :, None] - gl
        hr = Ht[None, :, None] - hl
        vL = -_soft(gl) / (hl + lam + 1e-10)
        vR = -_soft(gr) / (hr + lam + 1e-10)
        return vL, vR

    def gain_of(wl, gl, hl):
        wr = Wt[None, :, None] - wl
        gr = Gt[None, :, None] - gl
        hr = Ht[None, :, None] - hl
        gl_, gr_, gt_ = _soft(gl), _soft(gr), _soft(Gt)
        g = (gl_ * gl_ / (hl + lam + 1e-10) + gr_ * gr_ / (hr + lam + 1e-10)
             - (gt_ * gt_ / (Ht + lam + 1e-10))[None, :, None])
        if cfg.child_weight_hessian:
            ok = (hl >= cfg.min_rows) & (hr >= cfg.min_rows)
        else:
            ok = (wl >= cfg.min_rows) & (wr >= cfg.min_rows)
        return jnp.where(ok, g, -jnp.inf)

    gain_nar = gain_of(cw, cg, ch)                      # NA right
    gain_nal = gain_of(cw + wna, cg + gna, ch + hna)    # NA left
    gains = jnp.stack([gain_nar, gain_nal], axis=3)     # (F, n_lv, nb-1, 2)
    vL_nar, vR_nar = child_vals(cg, ch)
    vL_nal, vR_nal = child_vals(cg + gna, ch + hna)
    vL = jnp.stack([vL_nar, vL_nal], axis=3)
    vR = jnp.stack([vR_nar, vR_nal], axis=3)
    if mono is not None:
        m = mono[:, None, None, None]
        viol = ((m > 0) & (vL > vR)) | ((m < 0) & (vL < vR))
        gains = jnp.where(viol, -jnp.inf, gains)
    gains = jnp.where(colmask[:, :, None, None], gains, -jnp.inf)
    gains = jnp.where(edge_ok[:, None, :, None], gains, -jnp.inf)

    F, n_lv = gains.shape[0], gains.shape[1]
    flat = jnp.transpose(gains, (1, 0, 2, 3)).reshape(n_lv, -1)  # (n_lv, F*(nb-1)*2)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]

    def pick(arr):  # chosen candidate's value per node (tiny gathers)
        a = jnp.transpose(arr, (1, 0, 2, 3)).reshape(n_lv, -1)
        return jnp.take_along_axis(a, best[:, None], axis=1)[:, 0]

    best_vL, best_vR = pick(vL), pick(vR)
    per_f = (nb - 1) * 2
    bf = (best // per_f).astype(jnp.int32)
    bb = ((best % per_f) // 2).astype(jnp.int32)
    bnal = (best % 2).astype(jnp.bool_)
    node_w = Ht if cfg.child_weight_hessian else Wt
    if rank is None:
        return best_gain, bf, bb, bnal, node_w, best_vL, best_vR, None, None
    # Direction row per node over REAL bins (0 = left, 1 = right): for a set
    # split, bin b goes left iff its sorted rank is inside the chosen prefix;
    # empty bins follow the NA direction (a level unseen at this node is
    # treated like missing — the genmodel out-of-bitset-range rule). Numeric
    # nodes get the ordinal pattern b > cut (unused by routing, which keeps
    # the exact raw-threshold test for them).
    n_lv = bf.shape[0]
    rank_sel = jnp.take_along_axis(jnp.transpose(rank, (1, 0, 2)),
                                   bf[:, None, None], axis=1)[:, 0, :]
    w_sel = jnp.take_along_axis(jnp.transpose(W[:, :, :nb], (1, 0, 2)),
                                bf[:, None, None], axis=1)[:, 0, :]
    dir_c = rank_sel > bb[:, None]
    dir_c = jnp.where(w_sel > 0, dir_c, ~bnal[:, None])
    isset = jnp.take(iscat, bf)
    catd_lv = jnp.where(isset[:, None], dir_c,
                        jnp.arange(nb)[None, :] > bb[:, None]
                        ).astype(jnp.float32)
    return best_gain, bf, bb, bnal, node_w, best_vL, best_vR, catd_lv, isset


# ---------------------------------------------------------------------------
# Grow one tree fully on device (shard-local function; psums inside).
# ---------------------------------------------------------------------------
def _grow_tree(Xb, g, h, w, edges, edge_ok, colkey, cfg: TreeConfig,
               mono=None, imat=None, resid=None, w_full=None,
               iscat=None, nedges=None):
    """Returns (feat (N,), thr (N,), nanL (N,), val (N,), gain (N,),
    catd (N, nb|1), node (Rl,)).

    ``mono`` (F,) f32 in {-1,0,1}: monotone constraints. Split candidates
    violating a direction are masked in _find_splits; per-node [lo, hi] value
    bounds propagate to children through the split midpoint and clip leaf
    values — together these make every tree (hence the additive model)
    monotone in each constrained feature (`hex/tree/Constraints.java`).

    ``imat`` (F, F) bool: may-interact matrix from interaction_constraints
    (`hex/tree/GlobalInteractionConstraints.java`). Each node carries an
    allowed-feature mask; a child's mask is the parent's intersected with the
    split feature's interaction row, so a branch only ever combines features
    from one constraint group."""
    Rl, F = Xb.shape
    N = cfg.n_nodes
    B = cfg.nbins + 1

    use_sets = cfg.use_sets and iscat is not None
    feat = jnp.full((N,), -1, dtype=jnp.int32)
    thr = jnp.zeros((N,), dtype=jnp.float32)
    nanL = jnp.zeros((N,), dtype=jnp.bool_)
    garr = jnp.zeros((N,), dtype=jnp.float32)  # split gains (variable importance)
    # per-node bin-direction table for categorical set splits (1 dummy column
    # when off so scan/stack shapes stay uniform across configs)
    catd = jnp.zeros((N, cfg.nbins if use_sets else 1), dtype=jnp.float32)
    node = jnp.zeros((Rl,), dtype=jnp.int32)
    vals3 = jnp.stack([w, g, h], axis=1)
    constrained = mono is not None
    interacting = imat is not None
    lo = jnp.full((N,), -jnp.inf, dtype=jnp.float32)
    hi = jnp.full((N,), jnp.inf, dtype=jnp.float32)
    allowed = jnp.ones((N, F), dtype=jnp.bool_)  # per-node usable features

    # per-tree column subsample (same on all shards: colkey is not axis-folded)
    tree_cols = (jax.random.uniform(jax.random.fold_in(colkey, 997), (F,))
                 < cfg.col_sample_rate_per_tree)
    tree_cols = jnp.where(jnp.any(tree_cols), tree_cols, True)

    route_args = None   # pipelined: previous level's splits, routed lazily
    for level in range(cfg.max_depth):
        n_lv = 2 ** level
        offset = n_lv - 1
        if cfg.pipeline:
            hist, node = _pipelined_level_hist(Xb, node, vals3, route_args,
                                               offset, n_lv, B, cfg)
        else:
            hist = _build_level_hist(Xb, node, vals3, offset, n_lv, B,
                                     cfg.block_rows, groups=cfg.hist_groups,
                                     async_psum=cfg.async_psum)

        cmask = _level_col_mask(jax.random.fold_in(colkey, level), F, n_lv,
                                cfg, tree_cols, level)
        if interacting:
            allowed_n = jax.lax.dynamic_slice(allowed, (offset, 0), (n_lv, F))
            cmask = cmask & allowed_n.T  # (F, n_lv)

        gain, bf, bb, bnal, Wt, vLs, vRs, catd_lv, isset = _find_splits(
            hist, cmask, edge_ok, cfg, mono if constrained else None,
            iscat if use_sets else None, nedges if use_sets else None)
        do_split = (gain > cfg.min_split_improvement) & (Wt >= 2 * cfg.min_rows)

        if constrained:
            # bound propagation: children of a constrained split may not cross
            # the split midpoint (clipped into the node's own bounds)
            lo_n = jax.lax.dynamic_slice(lo, (offset,), (n_lv,))
            hi_n = jax.lax.dynamic_slice(hi, (offset,), (n_lv,))
            cbf = mono[bf]  # (n_lv,) tiny gather
            mid = jnp.clip((vLs + vRs) * 0.5, lo_n, hi_n)
            use = do_split & (cbf != 0)
            left_hi = jnp.where(use & (cbf > 0), mid, hi_n)
            left_lo = jnp.where(use & (cbf < 0), mid, lo_n)
            right_lo = jnp.where(use & (cbf > 0), mid, lo_n)
            right_hi = jnp.where(use & (cbf < 0), mid, hi_n)
            child_lo = jnp.stack([left_lo, right_lo], axis=1).reshape(-1)
            child_hi = jnp.stack([left_hi, right_hi], axis=1).reshape(-1)
            lo = jax.lax.dynamic_update_slice(lo, child_lo, (2 * offset + 1,))
            hi = jax.lax.dynamic_update_slice(hi, child_hi, (2 * offset + 1,))

        if interacting:
            # children inherit allowed ∩ interact-row(split feature)
            row = imat[bf]  # (n_lv, F) tiny gather
            child_allowed = jnp.where(do_split[:, None],
                                      allowed_n & row, allowed_n)
            both = jnp.repeat(child_allowed, 2, axis=0)  # (2*n_lv, F)
            allowed = jax.lax.dynamic_update_slice(
                allowed, both, (2 * offset + 1, 0))

        feat = jax.lax.dynamic_update_slice(
            feat, jnp.where(do_split, bf, -1), (offset,))
        thr = jax.lax.dynamic_update_slice(
            thr, edges[bf, bb], (offset,))
        nanL = jax.lax.dynamic_update_slice(nanL, bnal, (offset,))
        garr = jax.lax.dynamic_update_slice(
            garr, jnp.where(do_split, gain, 0.0).astype(jnp.float32), (offset,))
        if use_sets:
            catd = jax.lax.dynamic_update_slice(catd, catd_lv, (offset, 0))

        if cfg.pipeline:
            # defer this level's routing into the NEXT level's streamed
            # pass (or the final route below) — the split params are all
            # the route needs, and carrying them keeps each row block's
            # decode single-pass
            route_args = (bf, bb.astype(jnp.int32), bnal, do_split,
                          catd_lv if use_sets else None, isset, offset,
                          n_lv)
            continue

        # Route rows: only rows at split nodes of this level descend.
        # Per-row dynamic gathers (bf[lc], Xb[r, bf]) are catastrophically
        # slow on TPU (~20-40 ns/row on the VPU's serial gather path); instead
        # every per-node quantity is broadcast to rows through one-hot
        # matmuls, which ride the MXU (SURVEY.md §"hard parts" — TPUs lack
        # fast generic scatter/gather).
        # TPU matmuls default to bf16 multiplies; these dots move small
        # INTEGERS (bin ids < nbins, 0/1 flags) through 0/1 one-hots, which
        # bf16 represents exactly up to 256 — above that, force full f32.
        prec = (jax.lax.Precision.HIGHEST if cfg.nbins >= 255
                else jax.lax.Precision.DEFAULT)
        S = jax.nn.one_hot(bf, F, dtype=jnp.float32)              # (n_lv, F)

        def _route(xb_blk, node_blk):
            local = node_blk - offset
            active = (local >= 0) & (local < n_lv)
            lc = jnp.clip(local, 0, n_lv - 1)
            n_oh = jax.nn.one_hot(lc, n_lv, dtype=jnp.float32)  # (rb, n_lv)
            # bin of each row's split feature: Σ_n n_oh[r,n]·(Xb·Sᵀ)[r,n]
            xbs = jnp.dot(xb_blk.astype(jnp.float32), S.T, precision=prec,
                          preferred_element_type=jnp.float32)   # (rb, n_lv)
            rb_val = jnp.sum(xbs * n_oh, axis=1)
            row_bb = jnp.dot(n_oh, bb.astype(jnp.float32), precision=prec)
            row_nal = jnp.dot(n_oh, bnal.astype(jnp.float32)) > 0.5
            row_split = (jnp.dot(n_oh, do_split.astype(jnp.float32))
                         > 0.5) & active
            num_right = rb_val > row_bb
            if use_sets:
                # table route: the row's direction is its bin's entry in the
                # node's direction row — two more small matmuls, no gathers
                Drow = jnp.dot(n_oh, catd_lv,
                               preferred_element_type=jnp.float32)  # (rb, nb)
                bin_oh = jax.nn.one_hot(rb_val.astype(jnp.int32), cfg.nbins,
                                        dtype=jnp.float32)
                cat_right = jnp.sum(bin_oh * Drow, axis=1) > 0.5
                row_isset = jnp.dot(n_oh, isset.astype(jnp.float32)) > 0.5
                num_right = jnp.where(row_isset, cat_right, num_right)
            go_right = jnp.where(rb_val == cfg.nbins, ~row_nal, num_right)
            return jnp.where(row_split,
                             2 * node_blk + 1 + go_right.astype(jnp.int32),
                             node_blk)

        with telemetry.scope("gbm.route"):
            if use_sets or Xb.dtype.itemsize < 4:
                # blocked: the (rows, nbins) bin one-hot lives per block,
                # never materializing an (Rl, nbins) intermediate at wide
                # nbins_cats — and for int8/int16 binned views the f32 cast
                # feeding the routing matmul stays block-sized instead of
                # re-materializing a raw-matrix-sized (Rl, F) f32
                # intermediate
                rb_ = _block_rows(Rl, cfg.block_rows)
                _, node_b = jax.lax.scan(
                    lambda c, blk: (c, _route(*blk)), None,
                    (Xb.reshape(Rl // rb_, rb_, F),
                     node.reshape(Rl // rb_, rb_)))
                node = node_b.reshape(Rl)
            else:
                node = _route(Xb, node)

    if cfg.pipeline and route_args is not None:
        # the last level's routing was deferred — apply it so leaf/stop
        # totals see the final node assignment
        node = _route_all(Xb, node, route_args, cfg)

    # Leaf/stop-node values from one final per-node accumulation (covers both
    # max-depth leaves and early-stopped internal nodes).
    tot = _node_totals(node, vals3, N, cfg.block_rows)
    scale = 1.0 if cfg.drf_mode else cfg.learn_rate
    if cfg.huber_leaf_alpha is not None and resid is not None:
        # huber hybrid gamma (`GBM.java:685`): per-leaf median, then the
        # leaf mean of sign(r−med)·min(|r−med|, δ) with δ the per-tree
        # alpha-quantile of |residual| (Friedman 1999 eq. 24)
        med = _leaf_quantile_vals(resid, w, node, N, 0.5, cfg.block_rows)
        # δ is computed over ALL training rows with the unsampled weights
        # (GBM.java:485 computeWeightedQuantile(_weights, diff, alpha) runs
        # before tree fitting); the per-leaf median/gamma stay in-bag.
        delta = _leaf_quantile_vals(jnp.abs(resid),
                                    w if w_full is None else w_full,
                                    jnp.zeros_like(node), 1,
                                    cfg.huber_leaf_alpha, cfg.block_rows)[0]
        with telemetry.scope("gbm.leaf"):
            med_row = _onehot_pick(
                jax.nn.one_hot(node, N, dtype=jnp.float32), med)
            d = resid - med_row
            clipped = jnp.sign(d) * jnp.minimum(jnp.abs(d), delta)
        tot2 = _node_totals(node, (w * clipped)[:, None], N, cfg.block_rows)
        # per-node weight sums already live in tot[:, 0]
        gamma = jnp.where(tot[:, 0] > 0,
                          tot2[:, 0] / jnp.maximum(tot[:, 0], 1e-10), 0.0)
        newton = jnp.where(tot[:, 0] > 0, med + gamma, 0.0)
    elif cfg.leaf_quantile is not None and resid is not None:
        # laplace/quantile gamma leaves: the leaf value is a QUANTILE of the
        # in-leaf residuals, not a Newton step (`GBM.java:730,814`)
        newton = _leaf_quantile_vals(resid, w, node, N, cfg.leaf_quantile,
                                     cfg.block_rows)
        newton = jnp.where(tot[:, 0] > 0, newton, 0.0)
    else:
        gleaf = tot[:, 1]
        if cfg.reg_alpha > 0:
            gleaf = jnp.sign(gleaf) * jnp.maximum(
                jnp.abs(gleaf) - cfg.reg_alpha, 0.0)
        newton = jnp.where(tot[:, 0] > 0,
                           -gleaf / (tot[:, 2] + cfg.reg_lambda + 1e-10), 0.0)
    if constrained:
        newton = jnp.clip(newton, lo, hi)
    # max_abs_leafnode_pred caps the FINAL stored pred =
    # effective_learning_rate·gamma (GBM.java:716-719) — annealing included,
    # so the clip happens in tree_step after the per-tree rate is applied.
    val = newton * scale
    return feat, thr, nanL, val, garr, catd, node


def _hist_cells_per_row(cfg: TreeConfig, F: int) -> int:
    """(feature, bin) cells of ONE node's level histogram, which is also
    the one-hot cells a row generates in one level pass: F x B flat, each
    width bucket's own F_g x B_g under ``cfg.hist_groups``."""
    groups = _norm_groups(cfg.hist_groups) if cfg.hist_groups else None
    return (F * (cfg.nbins + 1) if groups is None
            else sum(len(idxs) * Bg for idxs, Bg, _ in groups))


def hist_psum_bytes(cfg: TreeConfig, F: int, nvals: int = 3) -> int:
    """Bytes of level histogram ONE tree hands to `_psum_hist` per shard:
    every level's whole f32 (F, n_lv, B, nvals) accumulator (per group
    when ``cfg.hist_groups`` is set — the wire carries Σ F_g·B_g cells
    instead of the padded F·B_max). From the static config alone: the
    counter ``train.gbm.psum_bytes`` adds it at chunk dispatch."""
    return sum((2 ** level) * _hist_cells_per_row(cfg, F)
               for level in range(cfg.max_depth)) * nvals * 4


def hist_plan_attrs(cfg: TreeConfig, rows: int) -> dict:
    """``train.gbm.chunk``'s attributes: the plan the level histogram of a
    shard's ``rows`` rows runs under, from shapes alone — the bins of the
    one-hot (NA slot included), the rows of a scan step and the steps of
    one level pass, the width buckets (0 where flat) and the widest
    level's nodes — and the leaf table's length with the form
    `_leaf_read` reads it in."""
    rb = _block_rows(rows, cfg.block_rows)
    return {"hist_bins": cfg.nbins + 1, "hist_row_block": rb,
            "hist_blocks": rows // rb,
            "hist_groups": len(cfg.hist_groups or ()),
            "n_lv_max": 2 ** max(cfg.max_depth - 1, 0),
            "leaf_nodes": cfg.n_nodes, "leaf_read": LEAF_READ}


def hist_onehot_cells(cfg: TreeConfig, rows: int, F: int) -> int:
    """One-hot cells ONE tree's level passes generate over ``rows`` rows:
    rows x (feature, bin) cells x levels. From shapes alone: the counter
    ``train.gbm.hist_onehot_cells`` adds it at chunk dispatch."""
    return rows * _hist_cells_per_row(cfg, F) * cfg.max_depth


def psum_payload_bytes(cfg: TreeConfig, F: int, nvals: int = 3) -> int:
    """Bytes ONE tree's ICI reductions move per shard: the per-level
    histogram psums (`hist_psum_bytes`) plus the final per-node totals
    psum. Pure accounting off the static config — the bench ``sharded``
    leg records it next to the per-shard matrix bytes so the
    compute-vs-wire tradeoff of a shard count is on the record."""
    return hist_psum_bytes(cfg, F, nvals) + cfg.n_nodes * nvals * 4


_TRAIN_FN_CACHE: dict = {}


def make_train_fn(cfg: TreeConfig, grad_fn: Callable, mesh=None,
                  cache_key=None, score_fn=None, score_spec=None,
                  donate=False):
    """Build the jitted multi-tree trainer.

    grad_fn(y, f, w) -> (g, h) with f the running link-scale prediction carried
    through the scan; for ``nclass > 1`` shapes grow a leading K axis and the
    per-class trees of one iteration are vmapped — the analog of the fused
    K-trees-per-iteration pass (`hex/tree/SharedTree.java:361-363`).

    ``cache_key`` (hashable summary of what grad_fn computes) enables reuse of
    the jitted program across builder instances — without it every GBM() gets
    a fresh closure and jax's compile cache misses (AdaBoost re-trains a
    learner per round; a per-learner recompile turned 30 stumps into minutes).

    Returns train(Xb, y, w, f0, edges, edge_ok, keys, rates, mono, imat,
    iscat, nedges) -> (f, oob_sum, oob_cnt, (feat, thr, nanL, val, gain,
    catd) stacked over trees); oob_sum/oob_cnt accumulate each row's
    out-of-bag tree outputs for DRF's OOB scoring (zeros when
    sample_rate == 1). ``iscat``/``nedges`` are (F,) bool/int32 arrays (only
    read under cfg.use_sets — pass zeros otherwise).

    With ``cfg.fused_score`` the signature grows a trailing traced scalar
    ``ntd`` (trees done after this chunk) and the outputs a trailing
    ``mraw`` — the score0-layout raw predictions ``score_fn(f, ntd)``
    computed INSIDE the program while the final margin is still resident,
    so the chunk loop's cadence scoring never rematerializes an (R,)
    margin in a standalone program (``score_spec`` is mraw's
    PartitionSpec). ``donate=True`` donates the carried margin argument's
    buffer to the output (double-buffer chunk dispatch; the caller must
    not read the donated input again). graftlint rule `use-after-donate`
    pins that discipline for direct positional dispatches of a trainer
    bound from `make_train_fn(..., donate=True)` or a literal donating
    `jax.jit`; the chunk loop's own ``*step_args`` dispatch is outside
    any positional lint's reach — tests/test_pipeline.py's cadence +
    donation pins cover it at runtime.
    """
    mesh = mesh or default_mesh()
    full_key = None
    if cache_key is not None:
        full_key = (cfg, cache_key, id(mesh), donate)
        hit = _TRAIN_FN_CACHE.get(full_key)
        if hit is not None:
            return hit
    K = cfg.nclass

    fused = cfg.fused_score and score_fn is not None

    @telemetry.program("gbm_level")
    def spmd(Xb, y, w, f, edges, edge_ok, keys, rates, mono, imat, iscat,
             nedges, *ntd):
        mono_arg = mono if cfg.use_monotone else None
        imat_arg = imat if cfg.use_interaction else None
        iscat_arg = iscat if cfg.use_sets else None
        nedges_arg = nedges if cfg.use_sets else None

        def tree_step(carry, key_rate):
            f, osum, ocnt = carry
            key, rate = key_rate  # rate: learn_rate_annealing^tree_index
            rowkey = jax.random.fold_in(key, jax.lax.axis_index(ROWS))
            if cfg.sample_rate < 1.0:
                s = (jax.random.uniform(rowkey, w.shape[-1:]) < cfg.sample_rate
                     ).astype(jnp.float32)
            else:
                s = jnp.ones(w.shape[-1:], jnp.float32)
            with telemetry.scope("gbm.grad"):
                g, h = grad_fn(y, f, w)

            def scale_leaves(vlk):
                # annealed rate first, THEN the cap: the reference clips
                # effective_learning_rate()·gamma (GBM.java:716-719)
                vlk = vlk * rate
                if math.isfinite(cfg.max_abs_leafnode_pred):
                    vlk = jnp.clip(vlk, -cfg.max_abs_leafnode_pred,
                                   cfg.max_abs_leafnode_pred)
                return vlk

            @telemetry.scope("gbm.leaf")
            def leaf_delta(vlk, nodek):
                return _leaf_read(vlk, nodek)

            if K == 1:
                resid = ((y - f) if (cfg.leaf_quantile is not None or
                                     cfg.huber_leaf_alpha is not None)
                         else None)
                ft, th, nl, vl, ga, cd, node = _grow_tree(
                    Xb, g * s, h * s, w * s, edges, edge_ok, key, cfg,
                    mono_arg, imat_arg, resid, w_full=w,
                    iscat=iscat_arg, nedges=nedges_arg)
                vl = scale_leaves(vl)
                delta = leaf_delta(vl, node)
            else:
                grow = jax.vmap(
                    lambda gk, hk, ck: _grow_tree(Xb, gk * s, hk * s, w * s,
                                                  edges, edge_ok, ck, cfg,
                                                  mono_arg, imat_arg,
                                                  iscat=iscat_arg,
                                                  nedges=nedges_arg))
                ckeys = jax.random.split(jax.random.fold_in(key, 31), K)
                ft, th, nl, vl, ga, cd, node = grow(g, h, ckeys)
                vl = scale_leaves(vl)
                delta = jax.vmap(leaf_delta)(vl, node)
            with telemetry.scope("gbm.leaf"):
                f = f + delta
                # OOB accumulation (`DRF.java` OOB scoring): rows outside
                # this tree's bag collect its raw output; two (R,)-adds per
                # tree, in the fusion that reads the leaf values
                oob = 1.0 - s
                osum = osum + delta * (oob if K == 1 else oob[None, :])
                ocnt = ocnt + oob
            return (f, osum, ocnt), (ft, th, nl, vl, ga, cd)

        init = (f, jnp.zeros_like(f), jnp.zeros(w.shape[-1:], jnp.float32))
        (f, osum, ocnt), trees = jax.lax.scan(tree_step, init, (keys, rates))
        if fused:
            # cadence scoring folded into the chunk step: the score0-layout
            # raw predictions come out while the final margin is still
            # resident — the chunk loop never redispatches a standalone
            # margin→score0 program per scoring interval
            with telemetry.scope("gbm.score"):
                mraw = score_fn(f, ntd[0])
            return f, osum, ocnt, trees, mraw
        return f, osum, ocnt, trees

    fspec = P(ROWS) if K == 1 else P(None, ROWS)
    in_specs = (P(ROWS, None), fspec, P(ROWS), fspec, P(), P(), P(), P(),
                P(), P(), P(), P())
    out_specs = (fspec, fspec, P(ROWS), (P(), P(), P(), P(), P(), P()))
    if fused:
        in_specs = in_specs + (P(),)
        out_specs = out_specs + (score_spec if score_spec is not None
                                 else P(ROWS),)
    fn = shard_map(
        spmd, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    # double-buffered chunk dispatch: the carried margin's input buffer is
    # donated to the output, so back-to-back chunk dispatches reuse it
    # instead of allocating a fresh (R,) carry per chunk. The caller's
    # use-after-donate discipline is lint-enforced end to end: graftlint's
    # pass-3 `donate-across-calls` resolves this factory's donating return
    # through the call graph and follows the margin through the chunk
    # loop's `*step_args` star-dispatch (tests/test_pipeline.py pins the
    # runtime behavior on top).
    jitted = jax.jit(fn, donate_argnums=(3,)) if donate else jax.jit(fn)
    if full_key is not None:
        _TRAIN_FN_CACHE[full_key] = jitted
    return jitted


# ---------------------------------------------------------------------------
# Forest prediction (vectorized CompressedTree traversal; `hex/tree/
# CompressedTree.java` score0 analog).
# ---------------------------------------------------------------------------
def _split_right(x, x_nan, n_oh, ftk, thk, nlk, cdk, iscat, nedges):
    """Shared per-level decision: (R,) go-right for rows sitting at each
    node. Numeric nodes test the raw threshold; categorical set-split nodes
    (``cdk`` (N, nb) direction rows present + feature flagged in ``iscat``)
    read their level's bin direction; NA follows the node's NA direction."""
    row_thr = _onehot_pick(n_oh, thk)
    row_nal = jnp.dot(n_oh, nlk.astype(jnp.float32)) > 0.5
    num_right = x > row_thr
    if cdk is not None:
        isset_n = (jnp.take(iscat, jnp.clip(ftk, 0)) & (ftk >= 0))
        nedge_n = jnp.take(nedges, jnp.clip(ftk, 0)).astype(jnp.float32)
        row_isset = jnp.dot(n_oh, isset_n.astype(jnp.float32)) > 0.5
        row_ne = _onehot_pick(n_oh, nedge_n)
        # level -> bin is closed-form for categorical codes binned on
        # 0..n_edges-1 integer cuts: bin = min(level, n_edges)
        xb = jnp.clip(x, 0.0, row_ne)
        Drow = jnp.dot(n_oh, cdk, preferred_element_type=jnp.float32)
        bin_oh = jax.nn.one_hot(xb.astype(jnp.int32), cdk.shape[1],
                                dtype=jnp.float32)
        cat_right = jnp.sum(bin_oh * Drow, axis=1) > 0.5
        num_right = jnp.where(row_isset, cat_right, num_right)
    return jnp.where(x_nan, ~row_nal, num_right)


def forest_covers(X, w, feat, thr, nanL, max_depth: int, catd=None,
                  iscat=None, nedges=None):
    """Per-node weighted training-row counts ("cover"), shape (T, [K,] N).

    The reference stores these node weights in the tree format for TreeSHAP
    (`hex/genmodel/algos/tree/TreeSHAP.java` consumes per-node weights written
    at training time). Here one routing pass over the training rows after the
    forest is built: the same one-hot-matmul traversal as `predict_forest`,
    accumulating the weighted occupancy of every node a row visits."""
    multi = feat.ndim == 3
    N = feat.shape[-1]
    Xz = jnp.nan_to_num(X)
    isnan_f = jnp.isnan(X).astype(jnp.float32)

    def traverse(ftk, thk, nlk, cdk):
        node = jnp.zeros(X.shape[0], dtype=jnp.int32)
        S = jax.nn.one_hot(jnp.clip(ftk, 0), X.shape[1], dtype=jnp.float32)
        counts = jnp.zeros(N, jnp.float32).at[0].set(jnp.sum(w))
        for _ in range(max_depth):
            n_oh = jax.nn.one_hot(node, N, dtype=jnp.float32)
            P_feat = jnp.dot(n_oh, S, preferred_element_type=jnp.float32)
            x = jnp.sum(P_feat * Xz, axis=1)
            x_nan = jnp.sum(P_feat * isnan_f, axis=1) > 0.5
            is_leaf = jnp.dot(n_oh, (ftk < 0).astype(jnp.float32)) > 0.5
            go_right = _split_right(x, x_nan, n_oh, ftk, thk, nlk, cdk,
                                    iscat, nedges)
            node = jnp.where(is_leaf, node,
                             2 * node + 1 + go_right.astype(jnp.int32))
            moved = w * (~is_leaf).astype(jnp.float32)
            counts = counts + jnp.dot(
                jax.nn.one_hot(node, N, dtype=jnp.float32).T, moved,
                preferred_element_type=jnp.float32)
        return counts

    has_cd = catd is not None
    cd = catd if has_cd else jnp.zeros(feat.shape + (1,), jnp.float32)

    def one_tree(carry, tree):
        ft, th, nl, cdt = tree
        fn = lambda a, b, c, d: traverse(a, b, c, d if has_cd else None)
        out = jax.vmap(fn)(ft, th, nl, cdt) if multi else fn(ft, th, nl, cdt)
        return carry, out

    _, covers = jax.lax.scan(one_tree, 0, (feat, thr, nanL, cd))
    return covers


def predict_forest(X, feat, thr, nanL, val, max_depth: int, catd=None,
                   iscat=None, nedges=None):
    """X: (R, F) raw values. feat/thr/nanL/val: (T, [K,] N). Returns summed
    tree outputs (R,) or (R, K).

    Traversal broadcasts per-node split params to rows through one-hot
    matmuls instead of per-row gathers (same MXU-over-gather rationale as the
    training-side routing in _grow_tree). ``catd`` (T, [K,] N, nb) +
    ``iscat``/``nedges`` (F,) activate categorical set-split routing."""
    multi = feat.ndim == 3
    N = feat.shape[-1]
    has_cd = catd is not None

    def one_tree(acc, tree):
        ft, th, nl, vl, cdt = tree

        def traverse(ftk, thk, nlk, vlk, cdk):
            node = jnp.zeros(X.shape[0], dtype=jnp.int32)
            S = jax.nn.one_hot(jnp.clip(ftk, 0), X.shape[1],
                               dtype=jnp.float32)               # (N, F)
            Xz = jnp.nan_to_num(X)
            isnan_f = jnp.isnan(X).astype(jnp.float32)
            for _ in range(max_depth):
                n_oh = jax.nn.one_hot(node, N, dtype=jnp.float32)   # (R, N)
                P_feat = jnp.dot(n_oh, S,
                                 preferred_element_type=jnp.float32)  # (R, F)
                x = jnp.sum(P_feat * Xz, axis=1)
                x_nan = jnp.sum(P_feat * isnan_f, axis=1) > 0.5
                is_leaf = jnp.dot(n_oh, (ftk < 0).astype(jnp.float32)) > 0.5
                # thresholds are real f32 values: a plain bf16 multiply would
                # misroute rows whose value falls inside the rounding gap
                go_right = _split_right(x, x_nan, n_oh, ftk, thk, nlk, cdk,
                                        iscat, nedges)
                nxt = 2 * node + 1 + go_right.astype(jnp.int32)
                node = jnp.where(is_leaf, node, nxt)
            n_oh = jax.nn.one_hot(node, N, dtype=jnp.float32)
            return _onehot_pick(n_oh, vlk)

        fn = lambda a, b, c, d, e: traverse(a, b, c, d,
                                            e if has_cd else None)
        if multi:
            out = jax.vmap(fn)(ft, th, nl, vl, cdt).T  # (R, K)
        else:
            out = fn(ft, th, nl, vl, cdt)
        return acc + out, None

    cd = catd if has_cd else jnp.zeros(feat.shape + (1,), jnp.float32)
    K = feat.shape[1] if multi else None
    init = jnp.zeros((X.shape[0], K) if multi else (X.shape[0],), jnp.float32)
    out, _ = jax.lax.scan(one_tree, init, (feat, thr, nanL, val, cd))
    return out
