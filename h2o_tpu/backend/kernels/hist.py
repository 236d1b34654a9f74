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

That flat one-hot costs B cells a row and feature whatever n_lv is, so at a
wide B and a shallow level its generation, not the MXU, sets the time. There
`_contrib` reads a code as two digits (`digit_split`, one rule from (n_lv *
V, B) alone): the high digit folded into the operand above, per feature, the
low one a narrow one-hot, contracted per feature (`_digit_contract`, the
sketch's ``rfa,rfb->fab`` of PR 33) into an (F, n_lv * V * H, L)
accumulator — the same sums, for k * H + L cells instead of B.

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
import numpy as np

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


# low digits the rule weighs: powers of two, so a code splits by a shift and
# a mask (128 is left out: 3x128 read 201 ms a pass at B 257, k 48, where
# 5x64 read 88 and flat 86)
_DIGIT_LOWS = (8, 16, 32, 64)
# digits only where they build at most this share of the flat one-hot's cells
_DIGIT_CELL_SHARE = 0.75


def digit_split(k: int, B: int):
    """The one rule that picks a block contribution's form, from shapes alone:
    ``None`` (flat) or ``(H, L)``, a code ``c`` read as the digits
    ``c >> log2(L)`` (high, H of them) and ``c & (L - 1)`` (low).

    Flat builds the ``(rb, F, B)`` one-hot of the codes: B cells a row and
    feature, whatever k = n_lv * V is. Digits build k * H + L: the high
    digit's one-hot folded into the node-and-statistic operand, and the low
    digit's one-hot; the MXU does about k * B multiply-adds a row and
    feature either way. On the chip a pass costs about what its cells do,
    over a floor its other work sets (decode, node localisation, the
    statistics' fold), and each split's time follows its k * H + L. So the
    rule takes the L of `_DIGIT_LOWS` (L < B, H = ceil(B / L)) with the
    fewest cells, and digits where those are at most `_DIGIT_CELL_SHARE`
    of B. One streamed pass at 11,010,048 rows, F 28, V 3, ms on one v5e
    (PERF.md section 7 no. 19 has every split measured):

    ===  =====  ===  ==========  ==========  ========================
    B    level  k    picked      cells       flat ms / digits ms
    ===  =====  ===  ==========  ==========  ========================
    257  0      3    9x32        59          69.8 / 27.1
    257  1      6    9x32        86          68.5 / 28.5
    257  2      12   5x64        124         71.3 / 42.0
    257  3      24   5x64        184         73.8 / 47.8
    257  4      48   flat        (5x64 304)  86.0 / 87.9
    257  5      96   flat        (5x64 544)  120.4 / 167.7
    65   0, 1   3-6  5x16        31, 46      24.4 / 16.5, 23.1 / 16.9
    33   0      3    5x8         23          18.0 / 14.8
    21   0      3    flat        (3x8 17)    14.3 / 15.0
    21   1, 2   6+   flat        (26, 40)    13.0 / 15.2, 13.2 / 17.4
    ===  =====  ===  ==========  ==========  ========================
    """
    cands = [(k * -(-B // L) + L, -(-B // L), L) for L in _DIGIT_LOWS if L < B]
    if not cands:
        return None
    cells, H, L = min(cands)
    return (H, L) if cells <= _DIGIT_CELL_SHARE * B else None


def onehot_cells(k: int, B: int) -> int:
    """One-hot cells a row and feature a block contribution generates at
    (k, B): B flat, k * H + L in digits (`digit_split`)."""
    split = digit_split(k, B)
    return B if split is None else k * split[0] + split[1]


def _digit_contract(x, a2, H: int, L: int):
    """(F, k * H, L): the block's codes ``x`` (rb, F) as two digits, the high
    one folded into ``a2`` (rb, k) per feature — ``A[r, f, k' * H + h] =
    a2[r, k']`` where ``x[r, f] >> log2(L) == h``, else 0.0: exact — and
    contracted per feature over the block's rows with the low digit's 0/1
    one-hot on the MXU (``rfa,rfb->fab``, the features a batch). ``A`` is a
    compare of the high digit against a constant column pattern selecting
    from ``a2`` with each column repeated H times: a one-hot's shape, which
    the TPU compiler fuses into the convolution (a 4-D ``(rb, F, k, H)``
    select, or a bool one-hot broadcast over k, it builds apart: twice the
    flat form's cycles). Cell ``(f, k' * H + h, l)`` sums what the flat
    form's ``(k', f, h * L + l)`` does: the same products (``a2`` enters at
    the same precision, the one-hots are exact), in another order. A code
    past the last digit adds nothing, as flat."""
    k = a2.shape[1]
    h_of = jnp.asarray(np.arange(k * H) % H, jnp.int32)      # column's h
    a = jnp.where((x >> (L.bit_length() - 1))[:, :, None] == h_of,
                  jnp.repeat(a2, H, axis=1)[:, None, :], 0.0)  # (rb, F, k*H)
    lo = jax.nn.one_hot(x & (L - 1), L, dtype=jnp.float32)    # (rb, F, L)
    return jnp.einsum("rfa,rfb->fab", a, lo)


def _contrib(x, B: int, n_lv: int, l, vv, a2=None):
    """One row block's one-hot contribution over its codes ``x`` (rb, F),
    int32 in [0, B), in the form `digit_split` picks from (n_lv * V, B):
    (k, F, B) flat, (F, k * H, L) in digits — the accumulator's own shape,
    `_unfold` lays either out. ``a2`` is `_node_fold`'s operand where the
    caller shares it across width buckets, else it is built here — after
    the flat one-hot, the order the flat form has always traced in, so that
    its lowered program stays byte for byte what it was."""
    split = digit_split(n_lv * vv.shape[1], B)
    if split is None:
        b_oh = jax.nn.one_hot(x, B, dtype=jnp.float32)       # (rb, F, B)
        return _fold_contract(
            _node_fold(l, vv, n_lv) if a2 is None else a2, b_oh)
    return _digit_contract(x, _node_fold(l, vv, n_lv) if a2 is None else a2,
                           *split)


def _acc_shape(n_lv: int, V: int, F: int, B: int):
    """The accumulator `_contrib` adds into at (n_lv, V, F, B)."""
    split = digit_split(n_lv * V, B)
    return ((n_lv * V, F, B) if split is None
            else (F, n_lv * V * split[0], split[1]))


def _unfold(h, n_lv: int, V: int, B: int):
    """A `_contrib` accumulator in the (F, n_lv, B, V) layout the psum, the
    split search and the grouped scatter-back read — once a level, on the
    small result, never per block. In digits the H * L - B cells past the
    last code go."""
    if digit_split(n_lv * V, B) is None:
        return h.reshape(V, n_lv, h.shape[1], B).transpose(2, 1, 3, 0)
    F = h.shape[0]
    h = h.reshape(F, n_lv * V, -1)[:, :, :B]
    return h.reshape(F, V, n_lv, B).transpose(0, 2, 3, 1)


def _folded(mode: str) -> bool:
    """Whether a width bucket accumulates in the contraction's own shape
    (`_acc_shape`): every bucket but a ``segsum`` one."""
    return mode != "segsum"


def _flat_contrib(xb, l, vv, n_lv: int, nbins_tot: int):
    """One row block's contribution over all F columns at width B."""
    # int8/int16 binned views upcast HERE, one block at a time in-scan:
    # the accumulate below always sees int32 (graftlint
    # narrow-int-accumulate pins the hazard), while HBM keeps 1-2 B/cell.
    return _contrib(xb.astype(jnp.int32), nbins_tot, n_lv, l, vv)


def _one_group_contrib(xg, a2, l, vv, Bg: int, mode: str, n_lv: int,
                       na_global: int):
    """One width bucket's block contribution: `_contrib`'s from the one-hot
    contraction, (Fg, n_lv, Bg, V) from the segment-sum (`_folded` says
    which). ``xg`` is the group's already-gathered code block; the group NA
    bucket is its last slot (global NA remaps here, scatter-back restores
    it)."""
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
    return _contrib(xg, Bg, n_lv, l, vv, a2)


def _group_contrib(xgs, l, vv, groups, n_lv: int, na_global: int):
    a2 = _node_fold(l, vv, n_lv)   # shared across onehot groups — exact
    return tuple(
        _one_group_contrib(xg, a2, l, vv, Bg, mode, n_lv, na_global)
        for xg, (_idxs, Bg, mode) in zip(xgs, groups))


def _group_acc_shapes(groups, n_lv: int, V: int):
    """Each width bucket's accumulator shape, as `_one_group_contrib`
    returns it."""
    return tuple(_acc_shape(n_lv, V, len(idxs), Bg) if _folded(mode)
                 else (len(idxs), n_lv, Bg, V)
                 for idxs, Bg, mode in groups)


def _unfold_groups(hs, groups, n_lv: int, V: int):
    return tuple(_unfold(h, n_lv, V, Bg) if _folded(mode) else h
                 for h, (_idxs, Bg, mode) in zip(hs, groups))


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

    init = jnp.zeros(_acc_shape(n_lv, V, F, nbins_tot), dtype=jnp.float32)
    hist, _ = jax.lax.scan(body, init, (Xb.reshape(nblk, rb, F),
                                        lc.reshape(nblk, rb),
                                        vv.reshape(nblk, rb, V)))
    return _unfold(hist, n_lv, V, nbins_tot)


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
    return _unfold_groups(hists, groups, n_lv, V)


# ---------------------------------------------------------------------------
# public entries — what the engine's level passes call
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
    """Fused route→accumulate single pass — the row-block stream of the
    tree engine's level program.

    Each level routes rows off the previous level's splits and accumulates
    the new level's histogram in ONE walk over the row blocks. This pass
    decodes each (rb, F) block once (one upcast of the int8/int16 codes,
    shared by both halves): ``route_fn``
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

    Bit-parity with routing first and then `level_hist_blocks` is by
    construction: routing is integer/boolean work, and the histogram
    contributions are the same `_flat_contrib` / `_group_contrib` over the
    same block contents in the same block order, carried in the
    contraction's own (n_lv * V, F, B) shape and laid out as
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
    accs = _unfold_groups(accs, buckets, n_lv, V)
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
