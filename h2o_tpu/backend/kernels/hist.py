"""Level-histogram accumulation — the ScoreBuildHistogram2 inner loop.

One call accumulates, per shard, the (F, n_lv, B, V) channel histograms of
one tree level from the chunk store's int8/int16 bin codes: a ``lax.scan``
over row blocks, each step upcasting its (rb, F) code block (the PR 2
discipline — the narrow dtype exists only as an HBM storage format) and
adding the block's contribution into the carried accumulator. The GPU
tree-boosting kernels (Booster / XGBoost gpu_hist) do this with
shared-memory atomics; TPUs have no scatter unit, so the accumulate is
expressed as compare-mask contractions riding the MXU — the engine's
standard no-gather idiom.

The block contraction has ONE free dimension a side: the node one-hot and
the V channel values are folded into a 2-D (rb, n_lv * V) operand
(`_node_fold`), contracted with the (rb, F, B) one-hot of the codes as
``rk,rfb->kfb`` (`_fold_contract`), and every scan carries its accumulator
in that (n_lv * V, F, B) shape; `_unfold` makes the (F, n_lv, B, V) layout
once a level from the small result. With node and channel as two free
dimensions the TPU compiler lowered the channels to a padded 3-tap
convolution window and the level-4 pass took twice the time (PERF.md
section 6, PR 29; tests/test_chip_compile.py pins the lowering).

Width-bucketed ``groups`` (engine.plan_hist_groups) are first-class: the
per-group column gather is hoisted out of the block loop (the narrow coded
gather is cheap), each group accumulates at its own width, and
``mode="segsum"`` groups accumulate by segment-sum instead of the one-hot
contraction. The caller (engine._build_level_hist) owns the psum and the
grouped scatter-back; nothing in here touches a mesh axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...utils import telemetry
from . import pow2_block_rows


# ---------------------------------------------------------------------------
# per-block contributions — the ONE definition every scan below executes
# ---------------------------------------------------------------------------
def _node_fold(l, vv, n_lv: int):
    """(rb, n_lv * V) per-row channel values in the row's node columns,
    zero elsewhere: column ``v * n_lv + n`` holds ``vv[:, v]`` where the row
    sits in node ``n``. One masked copy of the node one-hot per channel,
    concatenated — exact (a value or 0.0), and it fuses into the
    contraction that reads it (PERF.md section 6, PR 29)."""
    at_node = jax.nn.one_hot(l, n_lv, dtype=jnp.bool_)            # (rb, n_lv)
    return jnp.concatenate(
        [jnp.where(at_node, vv[:, v:v + 1], 0.0)
         for v in range(vv.shape[1])], axis=1)


def _fold_contract(a2, b_oh):
    """The block's one-hot contracted over its rows with the folded node
    values: (k, F, B), k = n_lv * V — one free dimension a side (the module
    docstring says what two cost)."""
    return jnp.einsum("rk,rfb->kfb", a2, b_oh)


def _unfold(h, n_lv: int):
    """A (k, F, B) accumulator in the (F, n_lv, B, V) layout the psum, the
    split search and the grouped scatter-back read — once a level, on the
    small result, never per block."""
    k, F, B = h.shape
    return h.reshape(k // n_lv, n_lv, F, B).transpose(2, 1, 3, 0)


def _folded(mode: str) -> bool:
    """Whether a width bucket accumulates in the contraction's own (k, Fg,
    Bg) shape: every bucket but a ``segsum`` one."""
    return mode != "segsum"


def _flat_contrib(xb, l, vv, n_lv: int, nbins_tot: int):
    """One row block's (n_lv * V, F, B) contribution, flat bin space."""
    # int8/int16 binned views upcast HERE, one block at a time in-scan:
    # the accumulate below always sees int32 (graftlint
    # narrow-int-accumulate pins the hazard), while HBM keeps 1-2 B/cell.
    xb = xb.astype(jnp.int32)
    b_oh = jax.nn.one_hot(xb, nbins_tot, dtype=jnp.float32)   # (rb, F, B)
    return _fold_contract(_node_fold(l, vv, n_lv), b_oh)


def _one_group_contrib(xg, a2, l, vv, Bg: int, mode: str, n_lv: int,
                       na_global: int):
    """One width bucket's block contribution: (n_lv * V, Fg, Bg) from the
    one-hot contraction, (Fg, n_lv, Bg, V) from the segment-sum (`_folded`
    says which). ``xg`` is the group's already-gathered code block; the
    group NA bucket is its last slot (global NA remaps here, scatter-back
    restores it)."""
    xg = xg.astype(jnp.int32)
    Fg = xg.shape[1]
    xg = jnp.where(xg == na_global, Bg - 1, xg)
    if not _folded(mode):
        # narrow-bin path: at Bg ≪ the 128-lane MXU tile the one-hot
        # matmul is degenerate (mostly-padding tiles); a flat segment-sum
        # over (feature, node, bin) keys accumulates the same cells with
        # no one-hot at all (and in pure f32 adds — the matmul path rounds
        # each contribution through bf16 on TPU, so this path is the
        # *more* exact of the two).
        fi = jax.lax.broadcasted_iota(jnp.int32, (1, Fg), 1)
        seg = (fi * n_lv + l[:, None]) * Bg + xg              # (rb, Fg)
        data = jnp.broadcast_to(vv[:, None, :],
                                (xg.shape[0], Fg, vv.shape[1]))
        h = jax.ops.segment_sum(
            data.reshape(-1, vv.shape[1]), seg.reshape(-1),
            num_segments=Fg * n_lv * Bg)
        return h.reshape(Fg, n_lv, Bg, vv.shape[1])
    b_oh = jax.nn.one_hot(xg, Bg, dtype=jnp.float32)
    return _fold_contract(a2, b_oh)


def _group_contrib(xgs, l, vv, groups, n_lv: int, na_global: int):
    a2 = _node_fold(l, vv, n_lv)   # shared across onehot groups — exact
    return tuple(
        _one_group_contrib(xg, a2, l, vv, Bg, mode, n_lv, na_global)
        for xg, (_idxs, Bg, mode) in zip(xgs, groups))


def _group_acc_shapes(groups, n_lv: int, V: int):
    """Each width bucket's accumulator shape, as `_one_group_contrib`
    returns it."""
    return tuple((n_lv * V, len(idxs), Bg) if _folded(mode)
                 else (len(idxs), n_lv, Bg, V)
                 for idxs, Bg, mode in groups)


def _unfold_groups(hs, groups, n_lv: int):
    return tuple(_unfold(h, n_lv) if _folded(mode) else h
                 for h, (_idxs, _Bg, mode) in zip(hs, groups))


# ---------------------------------------------------------------------------
# the blocked lax.scan over row blocks
# ---------------------------------------------------------------------------
@telemetry.scope("gbm.hist")
def _scan_flat(Xb, lc, vv, n_lv, nbins_tot, rb):
    Rl, F = Xb.shape
    V = vv.shape[1]
    nblk = Rl // rb

    def body(acc, blk):
        xb, l, v = blk
        return acc + _flat_contrib(xb, l, v, n_lv, nbins_tot), None

    init = jnp.zeros((n_lv * V, F, nbins_tot), dtype=jnp.float32)
    hist, _ = jax.lax.scan(body, init, (Xb.reshape(nblk, rb, F),
                                        lc.reshape(nblk, rb),
                                        vv.reshape(nblk, rb, V)))
    return _unfold(hist, n_lv)


@telemetry.scope("gbm.hist")
def _scan_grouped(xgs, lc, vv, groups, n_lv, na_global, rb):
    Rl = lc.shape[0]
    V = vv.shape[1]
    nblk = Rl // rb
    xgs_r = [xg.reshape(nblk, rb, xg.shape[1]) for xg in xgs]

    def body(accs, blk):
        l, v, *xg = blk
        cs = _group_contrib(xg, l, v, groups, n_lv, na_global)
        return tuple(a + c for a, c in zip(accs, cs)), None

    init = tuple(jnp.zeros(shp, jnp.float32)
                 for shp in _group_acc_shapes(groups, n_lv, V))
    hists, _ = jax.lax.scan(body, init, (lc.reshape(nblk, rb),
                                         vv.reshape(nblk, rb, V), *xgs_r))
    return _unfold_groups(hists, groups, n_lv)


# ---------------------------------------------------------------------------
# public entry — what engine._build_level_hist calls
# ---------------------------------------------------------------------------
def level_hist_one_group(xg, lc, vv, *, Bg: int, mode: str, n_lv: int,
                         nbins_tot: int, block: int):
    """ONE width bucket accumulated in its own scan — the async-psum shape:
    the caller issues this group's psum immediately after, BEFORE tracing
    the next group's scan, so on a real ICI the collective overlaps the
    next bucket's local accumulation. Bit-parity with the joint-scan path
    is by construction: the per-block contribution is the same
    `_one_group_contrib` over the same block contents in the same ascending
    block order (the shared folded node operand is recomputed per scan but
    is exact, a value or 0.0 — identical either way)."""
    rb = pow2_block_rows(lc.shape[0], block)
    groups1 = ((tuple(range(xg.shape[1])), Bg, mode),)
    return _scan_grouped([xg], lc, vv, groups1, n_lv, nbins_tot - 1, rb)[0]


@telemetry.scope("gbm.hist")
def streamed_route_hist(Xb, node, vals, route_fn, *, offset: int, n_lv: int,
                        nbins_tot: int, block: int, groups=None):
    """Fused route→accumulate single pass — the column-block stream of the
    pipelined level program (``H2O_TPU_PIPELINE``).

    The synchronous level program walks the row blocks TWICE per level: once
    to route rows off the previous level's splits, once to accumulate the
    new level's histogram. This pass decodes each (rb, F) block once (one
    upcast of the int8/int16 codes, shared by both halves): ``route_fn``
    (the previous level's routing, `engine._route_rows` closed over its
    splits) advances the block's node ids, the level window localizes
    them, and the block's histogram contribution accumulates immediately.
    Returns ``(hists, node)`` with ``hists`` a tuple of per-group
    accumulators (one flat accumulator when ``groups`` is None) and
    ``node`` the advanced (Rl,) ids. No collectives — the caller psums,
    exactly like `level_hist_blocks`.

    The routing half is selects over the block's F codes and the level's
    nodes, a small fraction of the one-hot and contraction the histogram
    half does on the same block (PERF.md section 6, PR 27, has the chip's
    milliseconds).

    Bit-parity with the two-pass shape is by construction: routing is
    integer/boolean work (any formulation that picks the same children is
    exact), and the histogram contributions are the same `_flat_contrib` /
    `_group_contrib` over the same block contents in the same block order,
    carried in the contraction's own (n_lv * V, F, B) shape and laid out as
    (F, n_lv, B, V) after the scan, as `_scan_flat` / `_scan_grouped` do.
    ``route_fn=None`` (level 0) skips the routing half."""
    Rl = Xb.shape[0]
    V = vals.shape[1]
    rb = pow2_block_rows(Rl, block)
    nblk = Rl // rb

    def body(accs, blk):
        xb, nd, v = blk
        xb = xb.astype(jnp.int32)   # the block's one decode, for both halves
        if route_fn is not None:
            # the innermost scope names an operation: routing inside the
            # fused stream reads gbm.route, the accumulate gbm.hist
            with telemetry.scope("gbm.route"):
                nd = route_fn(xb, nd)
        local = nd - offset
        active = (local >= 0) & (local < n_lv)
        lc = jnp.clip(local, 0, n_lv - 1)
        vz = jnp.where(active[:, None], v, 0.0)
        if groups is None:
            cs = (_flat_contrib(xb, lc, vz, n_lv, nbins_tot),)
        else:
            xgs = [xb[:, list(idxs)] for idxs, _Bg, _mode in groups]
            cs = _group_contrib(xgs, lc, vz, groups, n_lv, nbins_tot - 1)
        return tuple(a + c for a, c in zip(accs, cs)), nd

    # the flat accumulator is one one-hot bucket over all F columns
    buckets = groups or ((range(Xb.shape[1]), nbins_tot, "onehot"),)
    accs, node_b = jax.lax.scan(
        body, tuple(jnp.zeros(shp, jnp.float32)
                    for shp in _group_acc_shapes(buckets, n_lv, V)),
        (Xb.reshape(nblk, rb, Xb.shape[1]),
         node.reshape(nblk, rb),
         vals.reshape(nblk, rb, V)))
    accs = _unfold_groups(accs, buckets, n_lv)
    return accs, node_b.reshape(Rl)


def level_hist_blocks(Xb, lc, vv, *, n_lv: int, nbins_tot: int, block: int,
                      groups=None):
    """Per-shard level-histogram accumulation over row blocks.

    ``Xb`` (Rl, F) int8/int16/int32 bin codes; ``lc`` (Rl,) int32 LOCAL
    node ids already clipped to [0, n_lv); ``vv`` (Rl, V) f32 channel
    values already zeroed for inactive rows. Flat (``groups=None``)
    returns the (F, n_lv, nbins_tot, V) accumulator; grouped returns one
    (Fg, n_lv, Bg, V) accumulator per normalized group, each group's NA
    bucket in its LAST slot. No collectives — the caller psums.
    """
    rb = pow2_block_rows(Xb.shape[0], block)
    if groups is None:
        return _scan_flat(Xb, lc, vv, n_lv, nbins_tot, rb)
    # the per-group column gather hoists out of the block loop: values are
    # identical either way (int codes gather exactly), and the gathered
    # narrow views total exactly Xb's bytes
    xgs = [Xb[:, list(idxs)] for idxs, _Bg, _mode in groups]
    return _scan_grouped(xgs, lc, vv, groups, n_lv, nbins_tot - 1, rb)
