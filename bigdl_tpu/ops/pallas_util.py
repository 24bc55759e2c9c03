"""Shared plumbing for the pallas TPU kernels (``ops/flash_attention.py``,
``ops/paged_attention.py``, ``ops/sampling.py``).

Every kernel follows the same deployment pattern: compiled Mosaic on TPU,
the pallas interpreter everywhere else — so parity tests on the CPU
backend exercise the identical kernel code the chip runs. The helpers
here are the pattern's common parts: backend detection, the TPU compiler
params, and the block-size fitter that keeps grids aligned to the
128-wide MXU/VPU tiles.
"""

from __future__ import annotations

import jax

# finite stand-in for -inf inside kernels: exp(x - _NEG_INF) arithmetic
# stays NaN-free where a true -inf would poison the online softmax
NEG_INF = -1e30


def use_interpret():
    """True when the pallas interpreter should run the kernel (any
    backend without a Mosaic compiler — CPU tests, GPU hosts)."""
    return jax.default_backend() not in ("tpu",)


def compiler_params(interpret, dimension_semantics):
    """TPU compiler params for ``pl.pallas_call`` (None in interpret
    mode). ``dimension_semantics`` marks each grid dim "parallel" or
    "arbitrary" (sequential — required for dims that carry scratch
    accumulators)."""
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics))


def fit_block(s, want, align=128):
    """Largest block <= ``want`` that divides ``s`` and is a multiple of
    ``align``, else all of ``s`` — the only two block sizes the TPU
    lowering accepts on a tiled dimension (``align`` 128 on the lane axis,
    8 on the sublane axis). Interpret mode checks none of this, so a
    merely-dividing block (6 of 12 heads) passes every CPU test and is
    refused on the chip."""
    if s <= want:
        return s
    for b in range(want - want % align, 0, -align):
        if s % b == 0:
            return b
    return s
