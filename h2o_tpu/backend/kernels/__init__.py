"""Kernels layer — the one place that knows the blocked-scan formulation
of the two accumulations every hot loop in this repo bottoms out in.

- `hist`: the GBM/DRF level-histogram scan (bin codes → per-(feature, node,
  bin) channel sums), alone (`level_hist_blocks`, `level_hist_one_group`)
  and fused with the previous level's routing (`streamed_route_hist`).
- `gram`: the GLM/PCA weighted Gram (XᵀWX + XᵀWz), `gram_accumulate`, and
  the row blocks it cuts a design into, `block_plan`.

Each is a loop over row blocks whose body adds one block's contribution
(`hist._flat_contrib` / `hist._one_group_contrib` / `gram.block_contrib`,
the ONE definition of a block's contribution) into a carried accumulator in
ascending block order: a ``lax.scan`` over the coded blocks for the
histograms, a ``lax.fori_loop`` that slices each block out of the design in
place for the Gram (as ``xs`` of a scan the f32 design was copied whole and
re-tiled a block). It is the formulation that compiles for the TPU
(tests/test_chip_compile.py) and the one the chip has run; callers own the
mesh concerns (psum, scatter-back).
"""

from __future__ import annotations


def pow2_block_rows(rl: int, want: int) -> int:
    """Largest power-of-two divisor of ``rl`` up to ``want`` (``rl`` itself
    when none divides) — the row-block sizer every blocked accumulation in
    this package (and the engine's scans) shares."""
    if rl % want == 0:
        return want
    b = 1
    while b * 2 <= want and rl % (b * 2) == 0:
        b *= 2
    return b if rl % b == 0 else rl


from . import gram, hist  # noqa: E402  (cycle-free: leaf modules)

__all__ = ["gram", "hist", "pow2_block_rows"]
