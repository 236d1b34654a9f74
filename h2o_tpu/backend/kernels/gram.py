"""Fused weighted Gram accumulation — the GLMIterationTask inner loop.

One call computes XᵀWX (and optionally XᵀWz) in ONE pass over row blocks:
the (R, P) weighted design never materializes — each block's X·W product
lives only for its own contraction — and both the matrix and the vector
accumulate in the same pass, which is exactly `hex/glm/GLMTask.java:35-37`'s
one-MRTask contract. Consumers: `glm._make_irls_kernel` (IRLS driver),
`pca._gram_kernel` (GramSVD), and RuleFit's streaming IRLS shares
`block_contrib` inside its design-building scan.

The design is read where it lies: a loop over a block index whose body
slices its rows out of X, W and z in place (`lax.dynamic_slice_in_dim`),
the (P, P) accumulator the carry, blocks added in ascending order and the
rows past the last full block as one static tail slice. No operand is
padded, concatenated or reshaped: handed to ``lax.scan`` as ``xs`` the
design was copied whole and every block re-tiled (0.37 s of copies around
0.014 s of arithmetic at 11M x 29 on a v5e; PERF.md, PR 31). A design
under the block budget runs as ONE block, the plain fused einsum.

The W/z row vectors arrive precomputed (they are O(R) elementwise — the
IRLS step builds them from eta in the same jitted program); the fusion
here covers the O(R·P²) part that dominates.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...utils import telemetry

#: transient-cell budget per block: blocks sized so the (rb, P) weighted
#: product stays ~128 MB of f32 (gemm-sized, never HBM-relevant); designs
#: under the budget run as ONE block, the plain fused einsum, and only
#: frame-scale designs split
_BLOCK_CELLS = 1 << 25

#: rows a block is a multiple of: covers the TPU's T(1024) tiling of the
#: (R,) vectors and the T(8,128) tiling of the design (rows in lanes), so
#: a block's slice starts on a tile boundary and is read where it lies
_LANE = 1024


def block_contrib(xb, wb, zb):
    """One row block's (ΔG, Δb): Xᵀ(W∘X) and Xᵀ(W∘z). ``zb=None`` skips
    the vector (PCA's unweighted-response Gram). Public: RuleFit's
    streaming IRLS calls this inside its own design-building scan — the
    design block exists only in-scan there, so the fusion point is the
    shared math, not a second pass."""
    XW = xb * wb[:, None]
    dG = jnp.einsum("rp,rq->pq", XW, xb)
    if zb is None:
        return dG, None
    return dG, XW.T @ zb


def block_plan(R: int, P: int, block: int | None = None):
    """``(nblk, rb, tail)`` for an (R, P) design: ``nblk`` full blocks of
    ``rb`` rows, then ``tail`` rows, ``nblk * rb + tail == R``. Pure
    arithmetic on the static shape; ``block`` overrides the budget's rows
    a block (tests).

    A design at or under the budget (or under one lane tile) is one block.
    Otherwise ``rb`` is a multiple of ``_LANE`` at or under the budget (one
    tile at least), and the block count is kept a multiple of 8 where there
    are 8 or more: the TPU compiler's time on a blocked loop is linear in
    the count otherwise (12 blocks compiled in 51.6 s against 1.0-1.5 s for
    16; `parallel.mesh.padded_len` documents the same trap). ``rb`` is not
    searched among R's divisors — a pow2-divisor fallback once produced
    16-row blocks at R=50000, turning the gemm into 3125 dispatch-bound
    slivers — so what the lane floor leaves over is the tail, under ``rb``
    rows (under 8 one-tile blocks in the corner where no tile multiple
    gives a count that is a multiple of 8)."""
    cap = max(block or _BLOCK_CELLS // max(P, 1), 1)
    if R <= max(cap, _LANE):
        return 1, R, 0
    n = -(-R // max(cap // _LANE * _LANE, _LANE))
    while True:
        if n >= 8:
            n = -(-n // 8) * 8
        rb = max(R // n // _LANE * _LANE, _LANE)
        if R // rb == n or rb == _LANE:
            break
        n += 1
    nblk = R // rb
    if nblk >= 8:
        nblk -= nblk % 8
    return nblk, rb, R - nblk * rb


@telemetry.scope("glm.gram")
def gram_accumulate(X, W, z=None, *, block: int | None = None):
    """(G, b) = (XᵀWX, XᵀWz) in one blocked pass; ``b`` is None when ``z``
    is. ``W`` is the per-row weight vector (a 0/1 mask for PCA — note the
    contraction applies W once, i.e. Xᵀ·diag(W)·X; mask callers rely on
    0²=0, 1²=1).

    Blocks are `block_plan`'s: each a slice of X, W and z at a lane-tile
    offset, contributed in ascending order into the f32 (G, b) carry, the
    tail rows last. The sums differ from another block size's only by the
    order of addition inside a block."""
    R, P = X.shape
    nblk, _, tail = block_plan(R, P, block)
    if nblk == 1 and not tail:
        return block_contrib(X, W, z)

    def add_block(acc, start, rows):
        xb, wb, zb = (
            None if a is None
            else jax.lax.dynamic_slice_in_dim(a, start, rows, 0)
            for a in (X, W, z))
        return jax.tree.map(jnp.add, acc, block_contrib(xb, wb, zb))

    acc = (jnp.zeros((P, P), jnp.float32),
           None if z is None else jnp.zeros((P,), jnp.float32))
    return over_row_blocks(R, P, acc, add_block, block=block)


def over_row_blocks(R: int, P: int, carry, add, *, block: int | None = None):
    """``add(carry, start, rows)`` over the row blocks of `block_plan(R,
    P, block)`, ascending, the tail last: a `fori_loop` over the block
    index whose body slices its rows in place. The one loop of every
    blocked pass over a frame's rows: `gram_accumulate`, the streamed
    interaction step's eta, weights and working response
    (`glm._make_interact_step`; its Gram is `interact.interaction_gram`'s
    kernel) and GAM's design programs. ``P`` is the width the block budget
    is planned on; ``carry`` is any tree of arrays."""
    nblk, rb, tail = block_plan(R, P, block)
    if nblk == 1 and not tail:
        return add(carry, 0, R)
    carry = jax.lax.fori_loop(
        0, nblk, lambda i, c: add(c, i * rb, rb), carry)
    return add(carry, nblk * rb, tail) if tail else carry

