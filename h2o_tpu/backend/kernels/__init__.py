"""Kernels layer — the two inner loops PAPER.md's north star names as
Pallas targets, behind one backend switch.

Every accumulation-heavy hot loop in this repo bottoms out in one of two
shapes: the GBM/DRF level-histogram scan (bin codes → per-(feature, node,
bin) channel sums) and the GLM/PCA weighted Gram (XᵀWX + XᵀWz). This
package owns BOTH implementations of each:

- **xla** — the blocked ``lax.scan`` formulation. The default on EVERY
  backend (it is the formulation that compiles for the TPU — see
  tests/test_chip_compile.py — and the one the chip has run), and the
  bit-parity ORACLE: the Pallas path must reproduce it bit-for-bit.
- **pallas** — the same per-block math as ONE fused ``pl.pallas_call``:
  codes stream HBM→VMEM a row block at a time, the sub-int32 upcast and
  the accumulate happen in VMEM, and no per-block one-hot/segment
  intermediate round-trips through HBM. An explicit request only. Off-TPU
  the kernel runs under ``interpret=True`` (the interpreter executes the
  identical jaxpr, which is what makes bit-parity checkable without a
  chip). On a TPU backend it goes to Mosaic, which today REFUSES both
  kernels at HIGGS width (ROADMAP S2 lists the refusals); the compiler's
  error surfaces as raised — nothing substitutes the scan.

Parity is BY CONSTRUCTION, not by tolerance: both backends call the same
block-contribution functions (`hist._flat_contrib` / `hist._group_contrib`
/ `gram._block_contrib`) and accumulate blocks in the same ascending
order, so the only thing that can diverge is the execution engine — and
the tests/test_kernels.py suite pins that it doesn't (forests, histograms
and Gram matrices bit-equal across ``H2O_TPU_HIST_KERNEL=pallas|xla``).

Backend selection (``H2O_TPU_HIST_KERNEL``):

=========  ================================================================
 value      meaning
=========  ================================================================
 ``auto``   (default) xla on every backend
 ``xla``    the scan formulation (also the oracle in parity tests)
 ``pallas`` the fused kernel; interpreted off-TPU, Mosaic-compiled on TPU
=========  ================================================================

graftlint rule 12 (``direct-pallas-call``) pins this package as the only
sanctioned ``pl.pallas_call`` site — kernels grown elsewhere would dodge
the oracle contract and the interpret routing.
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


def hist_backend() -> str:
    """Resolved kernels backend: ``"pallas"`` or ``"xla"``.

    Read at TRACE time — callers that cache jitted programs must fold this
    into their cache key (``engine.make_train_fn`` does)."""
    from ...utils.knobs import get_str

    v = (get_str("H2O_TPU_HIST_KERNEL") or "auto").strip().lower()
    if v == "auto":
        return "xla"
    if v not in ("pallas", "xla"):
        raise ValueError(
            f"H2O_TPU_HIST_KERNEL={v!r} — expected pallas, xla or auto")
    return v


def interpret_mode() -> bool:
    """True when ``pl.pallas_call`` must run interpreted (no Mosaic
    compiler for this backend) — every non-TPU backend, including the CPU
    mesh the tests run on. Never true on a TPU backend."""
    import jax

    return jax.default_backend() != "tpu"


from . import gram, hist  # noqa: E402  (cycle-free: leaf modules)

__all__ = ["gram", "hist", "hist_backend", "interpret_mode",
           "pow2_block_rows"]
