"""Fused weighted Gram accumulation — the GLMIterationTask inner loop.

One call computes XᵀWX (and optionally XᵀWz) in ONE pass over row blocks:
the (R, P) weighted design never materializes — each block's X·W product
lives only for its own contraction — and both the matrix and the vector
accumulate in the same pass, which is exactly `hex/glm/GLMTask.java:35-37`'s
one-MRTask contract. Consumers: `glm._make_irls_kernel` (IRLS driver),
`pca._gram_kernel` (GramSVD), and RuleFit's streaming IRLS shares
`block_contrib` inside its design-building scan.

Like kernels/hist.py it is a blocked ``lax.scan``: the (P, P) accumulator
is the carry, blocks add in ascending order, and a design under the block
budget runs as ONE block.

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
#: under the budget run as ONE block — i.e. exactly the historic fused
#: einsum, byte-for-byte — and only frame-scale designs split
_BLOCK_CELLS = 1 << 25


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



def _scan_gram(X, W, z, rb):
    R, P = X.shape
    nblk = R // rb
    has_z = z is not None

    def body(carry, blk):
        G, b = carry
        xb, wb, zb = blk if has_z else (*blk, None)
        dG, db = block_contrib(xb, wb, zb)
        return (G + dG, b + db if has_z else b), None

    init = (jnp.zeros((P, P), jnp.float32),
            jnp.zeros((P,), jnp.float32) if has_z else 0.0)
    xs = (X.reshape(nblk, rb, P), W.reshape(nblk, rb))
    if has_z:
        xs = xs + (z.reshape(nblk, rb),)
    (G, b), _ = jax.lax.scan(body, init, xs)
    return G, (b if has_z else None)


@telemetry.scope("glm.gram")
def gram_accumulate(X, W, z=None, *, block: int | None = None):
    """(G, b) = (XᵀWX, XᵀWz) in one blocked pass; ``b`` is None when ``z``
    is. ``W`` is the per-row weight vector (a 0/1 mask for PCA — note the
    contraction applies W once, i.e. Xᵀ·diag(W)·X; mask callers rely on
    0²=0, 1²=1).

    Blocks are balanced — nblk = ceil(R·P / cell budget), rb = ceil(R /
    nblk) — and rows pad with zeros up to nblk·rb when R doesn't divide
    (unlike the engine's power-of-two frame padding, GLM designs arrive at
    arbitrary lengths; a pow2-divisor fallback once produced 16-row blocks
    at R=50000, turning the gemm into 3125 dispatch-bound slivers).
    Zero-weight zero-value rows contribute exact +0.0 products, so the
    padded sum is bit-identical to the unpadded one; designs under the
    budget run as a single block, which IS the historic fused einsum."""
    R, P = X.shape
    cells = block * P if block else _BLOCK_CELLS
    nblk = max(1, -(-R * P // max(cells, 1)))
    rb = -(-R // nblk)
    pad = nblk * rb - R
    if pad:
        X = jnp.concatenate(
            [X, jnp.zeros((pad, X.shape[1]), X.dtype)], axis=0)
        W = jnp.concatenate([W, jnp.zeros((pad,), W.dtype)])
        if z is not None:
            z = jnp.concatenate([z, jnp.zeros((pad,), z.dtype)])
    return _scan_gram(X, W, z, rb)
