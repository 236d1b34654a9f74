"""The Gram of a design with streamed products, as ONE Pallas kernel.

The streamed interaction step (`glm._make_interact_step`) contracts the
(R, P + 1) design ``[mains, products, 1]`` that is never stored: each row
tile of it is formed in VMEM from the (R, Pm + 1) main design and
contracted there on the MXU, so HBM sees the main design, the per-row IRLS
weight ``W`` and working response ``z``, and the accumulators alone.

Inside a tile of T rows, all of it laid rows-in-lanes (the design's own
layout: ``X.T`` is a bitcast of it on the TPU):

- the raw paired values ``x = X[cols] * xscale + xshift``, one sublane row
  a paired column;
- each run of pairs with one ``i`` and consecutive ``j`` (all pairs of 28
  columns: 27 runs) as one broadcast product ``x_i * x_{j0..j1}``, written
  beside the mains and the column of ones into a (P1p, T) float32 scratch;
- each product standardised as ``(p - pshift) * (1 / pscale)``, every NaN
  (an NA, `DataInfo.mark_missing`) set to 0 (its mean), rows past R zeroed;
- the Gram: ``Z * W`` and ``Z`` rounded once to bfloat16 and contracted
  over the lanes (rows) on the MXU with float32 accumulation, the TPU's
  default matmul precision, as the step's before this kernel (interpreted
  off the TPU the operands stay float32, as that step's were there);
- the right-hand side: ``(Z * W) * z`` in float32 on the VPU, summed into
  a (P1p, 128) lane-partial accumulator (XLA's matrix-vector product of
  the step before this kernel was float32 too; rounding ``z`` to bfloat16
  moved the fitted coefficients by as much again as the Gram's rounding).

The grid is (Gram row block, Gram column block, row tile), the tile axis
innermost; the accumulators are the output blocks, resident across the
tiles. A width whose whole Gram fits the VMEM budget is one block (28
columns and their 378 pairs: P1p = 512); a wider one is cut into square
blocks, each forming only the rows it needs. The tile rows T and the block
edge are the only things the kernel adapts, from the shapes and the
budget.

Compiled by Mosaic on the TPU; interpreted elsewhere (the choice is the
lowering platform's, `lax.platform_dependent`), so the CPU runs the same
kernel. Pallas is imported when a kernel is first built (`_import_pallas`),
never with this module's importers: a job without streamed products never
pays its import.
"""

from __future__ import annotations

import functools
import importlib
import sys
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp

#: bytes of VMEM the kernel's buffers are planned in (T and the Gram block
#: are the largest that fit); the compiler is given `_VMEM_LIMIT`, the
#: budget with room for its own scratch (the matmul's operands)
_VMEM_BUDGET = 24 << 20
_VMEM_LIMIT = 48 << 20
#: row tiles tried, largest first: multiples of the 128 lanes
_TILES = (2048, 1024, 512, 256, 128)
#: Gram block edges tried when the whole width does not fit
_BLOCKS = (512, 256, 128)
#: formed rows standardised and rounded at a time: one bfloat16 tile
_CHUNK = 16
_LANES = 128
_PALLAS = "jax.experimental.pallas.tpu"
#: the Mosaic GPU interpreter, which ``pallas_call`` imports with itself
#: for GPU backends this system never runs: about two thirds of Pallas's
#: import, whole 1.2 s on a v5e host
_GPU_INTERPRETER = "jax._src.pallas.mosaic_gpu.interpret.interpret_pallas_call"
_IMPORT = threading.Lock()


def _up(n: int, m: int) -> int:
    return -(-n // m) * m


def _import_pallas() -> None:
    """Import Pallas and its TPU face, once a process, without the Mosaic
    GPU interpreter: a None in ``sys.modules`` makes that one import raise
    ImportError, which ``pallas_call`` catches for a jax built without it
    (it then offers no GPU interpret mode). The entry is taken out again,
    so a later import of that module works; a jax whose Pallas does not
    survive the absence is imported whole."""
    with _IMPORT:
        if _PALLAS in sys.modules:
            return
        block = _GPU_INTERPRETER not in sys.modules
        if block:
            sys.modules[_GPU_INTERPRETER] = None
        try:
            importlib.import_module(_PALLAS)
        except ImportError:
            if not block:
                raise
            del sys.modules[_GPU_INTERPRETER]
            block = False
            importlib.import_module(_PALLAS)
        finally:
            if block and sys.modules.get(_GPU_INTERPRETER, 0) is None:
                del sys.modules[_GPU_INTERPRETER]


def _pair_runs(pairs) -> tuple:
    """``(i, j0, n)`` for each maximal run of ``pairs`` with one ``i`` and
    the consecutive ``j0 .. j0 + n - 1``: the products a tile forms as one
    broadcast product each, in the model's column order."""
    runs, n = [], 0
    while n < len(pairs):
        i, j0 = pairs[n]
        m = n + 1
        while (m < len(pairs) and pairs[m][0] == i
               and pairs[m][1] == pairs[m - 1][1] + 1):
            m += 1
        runs.append((i, j0, m - n))
        n = m
    return tuple(runs)


def _col_runs(cols) -> tuple:
    """``(pos, col, n)`` for each run of consecutive design columns in
    ``cols``: the paired values' rows copied out of the tile at once."""
    runs, i = [], 0
    while i < len(cols):
        j = i + 1
        while j < len(cols) and cols[j] == cols[j - 1] + 1:
            j += 1
        runs.append((i, cols[i], j - i))
        i = j
    return tuple(runs)


class Plan(NamedTuple):
    """The kernel's shape rule on (R, layout): ``tile`` rows a grid step,
    ``tiles`` steps over the rows, the formed design's ``width`` (P1p: its
    P1 columns padded to whole Gram blocks) and the Gram ``block`` edge
    (``width // block`` blocks a side)."""
    tile: int
    tiles: int
    width: int
    block: int


def _vmem_bytes(block: int, nb: int, tile: int, xrows: int, k: int) -> int:
    """VMEM the kernel's buffers take: the formed rows (float32), the two
    bfloat16 operands, the accumulator blocks and the tiles of X, W and z
    (double-buffered), the paired values and the standardisation's
    per-row constants (lane-padded)."""
    return (block * tile * 4 + 2 * block * tile * 2
            + 2 * block * (block + _LANES) * 4
            + 2 * (_up(xrows, 8) + 16) * tile * 4 + _up(k, 8) * tile * 4
            + 2 * 2 * block * nb * _LANES * 4)


def plan(R: int, layout, budget: int | None = None) -> Plan:
    """`Plan` for R rows of a design with ``layout = (Pm, cols, pairs)``.
    The whole width is one Gram block if it fits the budget at some tile;
    else the largest block of `_BLOCKS` that fits, the width padded to
    whole blocks. The tile is then the largest of `_TILES` that fits, and
    no wider than the rows rounded up to the lanes. ``budget`` overrides
    `_VMEM_BUDGET` (tests)."""
    Pm, cols, pairs = layout
    budget = budget or _VMEM_BUDGET
    full = _up(Pm + len(pairs) + 1, _LANES)
    cap = _up(max(R, 1), _LANES)
    options = [(full, 1)] + [(b, -(-full // b)) for b in _BLOCKS if b < full]
    for block, nb in options:
        for tile in _TILES:
            tile = min(tile, cap)
            if _vmem_bytes(block, nb, tile, Pm + 1, len(cols)) <= budget:
                return Plan(tile, -(-R // tile), block * nb, block)
    tile, block = min(_TILES[-1], cap), _BLOCKS[-1]
    return Plan(tile, -(-R // tile), _up(full, block), block)


def _segments(layout) -> tuple:
    """Where each formed row comes from, as ``(kind, row0, n, a, j0)`` in
    row order: the mains (``"x"``: X's rows ``a ..``), each run of
    products (``"p"``: ``x_a * x_{j0 ..}``) and the column of ones (X's
    row Pm)."""
    Pm, _, pairs = layout
    segs, row = [("x", 0, Pm, 0, 0)] if Pm else [], Pm
    for i, j0, n in _pair_runs(pairs):
        segs.append(("p", row, n, i, j0))
        row += n
    return tuple(segs) + (("x", row, 1, Pm, 0),)


def _kernel(xt_ref, w_ref, z_ref, xsc_ref, xsh_ref, sh_ref, inv_ref,
            g_ref, b_ref, xs_ref, zf_ref, lhs_ref, rhs_ref, *, R, layout,
            plan_):
    """One grid step (Gram block gi, gj; row tile t): form the tile's rows
    of block gi (and gj), standardise and round them, add their contraction
    into the Gram block and, once a row block (gj = 0), their right-hand
    side into its lane partials."""
    from jax.experimental import pallas as pl

    Pm, cols, pairs = layout
    T, GB, nb = plan_.tile, plan_.block, plan_.width // plan_.block
    P1, k = Pm + len(pairs) + 1, len(cols)
    gi, gj, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(t == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)

    @pl.when((t == 0) & (gj == 0))
    def _():
        b_ref[...] = jnp.zeros_like(b_ref)

    for pos, c, n in _col_runs(cols):
        xs_ref[pos:pos + n, :] = xt_ref[c:c + n, :]
    xs_ref[:k, :] = xs_ref[:k, :] * xsc_ref[...] + xsh_ref[...]

    lanes_ok = None
    if R % T:   # the last tile runs past R: its lanes hold no rows
        lanes_ok = (t * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
                    < R)
    w, z = w_ref[...], z_ref[...]
    if lanes_ok is not None:
        w, z = jnp.where(lanes_ok, w, 0.0), jnp.where(lanes_ok, z, 0.0)

    def form(b):
        lo, hi = b * GB, (b + 1) * GB
        for kind, row0, n, a, j0 in _segments(layout):
            s, e = max(row0, lo), min(row0 + n, hi)
            if s >= e:
                continue
            d, o = s - lo, s - row0
            if kind == "x":
                zf_ref[d:d + e - s, :] = xt_ref[a + o:a + o + e - s, :]
            else:
                zf_ref[d:d + e - s, :] = (xs_ref[a:a + 1, :]
                                          * xs_ref[j0 + o:j0 + o + e - s, :])

    def standardise(b, to_lhs, to_rhs):
        lo, rows = b * GB, min(max(P1 - b * GB, 0), GB)

        def chunk(r0, partial):
            sl = pl.ds(r0, _CHUNK)
            v = ((zf_ref[sl, :] - sh_ref[pl.ds(lo + r0, _CHUNK), :])
                 * inv_ref[pl.ds(lo + r0, _CHUNK), :])
            keep = ~jnp.isnan(v)
            if partial:     # the block's last rows: past P1 they are pad
                keep &= (jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, 1), 0)
                         < rows - r0)
            if lanes_ok is not None:
                keep &= lanes_ok
            v = jnp.where(keep, v, 0.0)
            if to_rhs:
                rhs_ref[sl, :] = v.astype(rhs_ref.dtype)
            if to_lhs:
                vw = v * w
                lhs_ref[sl, :] = vw.astype(lhs_ref.dtype)
                vz = vw * z

                def rhs_side():
                    part = vz[:, :_LANES]
                    for c in range(_LANES, T, _LANES):
                        part = part + vz[:, c:c + _LANES]
                    b_ref[sl, :] += part

                if nb == 1:
                    rhs_side()
                else:
                    pl.when(gj == 0)(rhs_side)

        def body(c, carry):
            chunk(pl.multiple_of(c * _CHUNK, _CHUNK), False)
            return carry

        jax.lax.fori_loop(0, rows // _CHUNK, body, 0, unroll=True)
        if rows % _CHUNK:
            chunk(rows // _CHUNK * _CHUNK, True)
        pad = _up(rows, _CHUNK)
        if pad < GB:
            zero = jnp.zeros((GB - pad, T), rhs_ref.dtype)
            if to_lhs:
                lhs_ref[pad:, :] = zero
            if to_rhs:
                rhs_ref[pad:, :] = zero

    if nb == 1:
        form(0)
        standardise(0, True, True)
    else:
        for b in range(nb):
            @pl.when(gi == b)
            def _():
                form(b)
                standardise(b, True, False)

            @pl.when(gj == b)
            def _():
                form(b)
                standardise(b, False, True)

    g_ref[...] += jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def interaction_gram(X, W, z, xshift, xscale, pshift, pscale, layout, *,
                     budget: int | None = None, operands=None):
    """(G, b) of the (R, P1) design ``Z = [mains, products, 1]`` with row
    weights ``W`` and working response ``z``: ``G[p, q] = sum_r (Z_p W)_r
    Z_q,r`` and ``b[p] = sum_r (Z_p W)_r z_r``, float32 sums. ``X`` is the
    (R, Pm + 1) main design, its column of ones last; ``layout = (Pm,
    cols, pairs)`` (static); the four small vectors undo the paired
    columns' standardisation and apply the products'.

    The Gram's operands are rounded as the platform's default matmul
    precision rounds them, the precision of the step this kernel replaced:
    once to bfloat16 on the TPU, not at all (float32) where the kernel is
    interpreted; ``b`` is float32 throughout. ``operands`` (a dtype) fixes
    the Gram's operands on every platform, and ``budget`` overrides the
    VMEM budget (tests)."""
    _import_pallas()
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Pm, cols, pairs = layout
    R, P1 = X.shape[0], Pm + len(pairs) + 1
    p = plan(R, layout, budget)
    nb, k, f32 = p.width // p.block, len(cols), jnp.float32
    pad = p.width - Pm - len(pairs)
    sh = jnp.concatenate([jnp.zeros((Pm,), f32), pshift.astype(f32),
                          jnp.zeros((pad,), f32)])[:, None]
    inv = jnp.concatenate([jnp.ones((Pm,), f32), 1.0 / pscale.astype(f32),
                           jnp.ones((pad,), f32)])[:, None]
    args = (X.T, W[None, :], z[None, :], xscale.astype(f32)[:, None],
            xshift.astype(f32)[:, None], sh, inv)
    whole = lambda a: pl.BlockSpec(a.shape, lambda i, j, t: (0, 0))  # noqa: E731
    tile = lambda a: pl.BlockSpec((a.shape[0], p.tile),  # noqa: E731
                                  lambda i, j, t: (0, t))

    def call(interpret, dt):
        return pl.pallas_call(
            functools.partial(_kernel, R=R, layout=layout, plan_=p),
            out_shape=(jax.ShapeDtypeStruct((p.width, p.width), f32),
                       jax.ShapeDtypeStruct((p.width, _LANES), f32)),
            grid=(nb, nb, p.tiles),
            in_specs=[tile(a) for a in args[:3]]
            + [whole(a) for a in args[3:]],
            out_specs=(pl.BlockSpec((p.block, p.block),
                                    lambda i, j, t: (i, j)),
                       pl.BlockSpec((p.block, _LANES),
                                    lambda i, j, t: (i, 0))),
            scratch_shapes=[pltpu.VMEM((_up(k, 8), p.tile), f32),
                            pltpu.VMEM((p.block, p.tile), f32),
                            pltpu.VMEM((p.block, p.tile), dt),
                            pltpu.VMEM((p.block, p.tile), dt)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * 3,
                vmem_limit_bytes=_VMEM_LIMIT),
            cost_estimate=pl.CostEstimate(
                flops=2 * R * p.width * (p.width + 1),
                bytes_accessed=4 * (nb * nb * R * (Pm + 3)
                                    + p.width * (p.width + _LANES)),
                transcendentals=0),
            interpret=interpret)(*args)

    G, b = jax.lax.platform_dependent(
        tpu=lambda: call(False, operands or jnp.bfloat16),
        default=lambda: call(True, operands or f32))
    return G[:P1, :P1], jnp.sum(b[:P1], axis=1)
