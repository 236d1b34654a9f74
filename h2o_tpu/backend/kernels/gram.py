"""Fused weighted Gram accumulation — the GLMIterationTask inner loop.

One call computes XᵀWX (and optionally XᵀWz) in ONE pass over row blocks:
the (R, P) weighted design never materializes — each block's X·W product
lives only for its own contraction — and both the matrix and the vector
accumulate in the same pass, which is exactly `hex/glm/GLMTask.java:35-37`'s
one-MRTask contract. Consumers: `glm._make_irls_kernel` (IRLS driver),
`pca._gram_kernel` (GramSVD), and RuleFit's streaming IRLS shares
`_block_contrib` inside its design-building scan.

Backends mirror kernels/hist.py: ``xla`` is the blocked ``lax.scan``
(default on CPU, the parity oracle), ``pallas`` fuses the same per-block
math into one ``pl.pallas_call`` with the (P, P) accumulator VMEM-resident
across the grid (interpreted off-TPU). Identical block math + identical
ascending block order ⇒ bit-equal outputs at production block shapes
(single or gemm-sized blocks under the default budget) — pinned by
tests/test_kernels.py down to the end-to-end IRLS coefficients. The one
measured caveat: at deliberately tiny forced blocks XLA may pick a
different reduction strategy for the fused scan than the interpreted
kernel, so the forced-multiblock boundary is pinned at tight closeness
rather than bitness (the default budget never produces such blocks).

The W/z row vectors arrive precomputed (they are O(R) elementwise — the
IRLS step builds them from eta in the same jitted program); the fusion
here covers the O(R·P²) part that dominates.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...utils import telemetry
from . import hist_backend, interpret_mode, pow2_block_rows

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


_block_contrib = block_contrib


def _xla_gram(X, W, z, rb):
    R, P = X.shape
    nblk = R // rb
    has_z = z is not None

    def body(carry, blk):
        G, b = carry
        xb, wb, zb = blk if has_z else (*blk, None)
        dG, db = _block_contrib(xb, wb, zb)
        return (G + dG, b + db if has_z else b), None

    init = (jnp.zeros((P, P), jnp.float32),
            jnp.zeros((P,), jnp.float32) if has_z else 0.0)
    xs = (X.reshape(nblk, rb, P), W.reshape(nblk, rb))
    if has_z:
        xs = xs + (z.reshape(nblk, rb),)
    (G, b), _ = jax.lax.scan(body, init, xs)
    return G, (b if has_z else None)


def _pallas_gram(X, W, z, rb):
    R, P = X.shape
    nblk = R // rb
    has_z = z is not None

    def kernel(x_ref, w_ref, *refs):
        i = pl.program_id(0)
        if has_z:
            z_ref, g_ref, b_ref = refs
            zb = z_ref[..., 0]
        else:
            (g_ref,) = refs
            zb = None
        dG, db = _block_contrib(x_ref[...], w_ref[..., 0], zb)

        @pl.when(i == 0)
        def _():
            g_ref[...] = dG
            if has_z:
                b_ref[...] = db[None, :]

        @pl.when(i != 0)
        def _():
            g_ref[...] = g_ref[...] + dG
            if has_z:
                b_ref[...] = b_ref[...] + db[None, :]

    in_specs = [pl.BlockSpec((rb, P), lambda i: (i, 0)),
                pl.BlockSpec((rb, 1), lambda i: (i, 0))]
    args = [X, W[:, None]]
    out_specs = [pl.BlockSpec((P, P), lambda i: (0, 0))]
    out_shape = [jax.ShapeDtypeStruct((P, P), jnp.float32)]
    if has_z:
        in_specs.append(pl.BlockSpec((rb, 1), lambda i: (i, 0)))
        args.append(z[:, None])
        out_specs.append(pl.BlockSpec((1, P), lambda i: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((1, P), jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid=(nblk,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        interpret=interpret_mode(),
    )(*args)
    if has_z:
        return out[0], out[1][0]
    return out[0], None


@telemetry.scope("glm.gram")
def gram_accumulate(X, W, z=None, *, block: int | None = None,
                    backend: str | None = None):
    """(G, b) = (XᵀWX, XᵀWz) in one blocked pass; ``b`` is None when ``z``
    is. ``W`` is the per-row weight vector (a 0/1 mask for PCA — note the
    contraction applies W once, i.e. Xᵀ·diag(W)·X; mask callers rely on
    0²=0, 1²=1). Backend routed per kernels package policy; read at trace
    time, so jit-caching callers must key on `hist_backend()`.

    Blocks are balanced — nblk = ceil(R·P / cell budget), rb = ceil(R /
    nblk) — and rows pad with zeros up to nblk·rb when R doesn't divide
    (unlike the engine's power-of-two frame padding, GLM designs arrive at
    arbitrary lengths; a pow2-divisor fallback once produced 16-row blocks
    at R=50000, turning the gemm into 3125 dispatch-bound slivers).
    Zero-weight zero-value rows contribute exact +0.0 products, so the
    padded sum is bit-identical on both backends; designs under the
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
    bk = backend or hist_backend()
    fn = _pallas_gram if bk == "pallas" else _xla_gram
    return fn(X, W, z, rb)
