"""The grouped product of a routed-expert layer's prompt pass, as a Pallas
grouped matmul.

``nn.RoutedExperts`` sorts its assignments by expert and multiplies each
expert's rows by that expert's matrix: one grouped product a matrix.
XLA's own (``lax.ragged_dot``, ``ragged-dot`` in a device trace) reads an
LFM2 expert layer's 403 MB of weights at 74 % of a v5e's bandwidth with
the 384 rows of a decode step, and at a third of it from 512 rows on,
whatever the rows: 1.4 ms a call, 31 of a prompt pass's 35 ms (PERF.md
section 5, PR 28).

The kernel here walks the row tiles that each group touches, as the
grouped matmul that ships with JAX does
(``jax.experimental.pallas.ops.tpu.megablox``): a grid step multiplies
one row tile by one block of one expert's matrix and stores the rows of
that tile that belong to the group, so an expert's weights are read once
for each row tile its rows fall in. On the chip it runs as fast as the
shipped kernel at the same tiles (my chip sweep, PERF.md section 6,
PR 38). What differs is the walk: the shipped kernel works it out with
``jnp.repeat`` and ``jnp.histogram``, which made the 8 routed layers of
an LFM2 prompt pass compile into 1.9-2.7 times the executable bytes of
``ragged_dot``'s, where :func:`_walk`, a few vector operations on the
group sizes, makes 0.9-1.3 times (my CPU compiles for a described v5e,
PR 38); with the shipped walk the cell's warm ``setup_s`` rose 24 % (my
chip run, PR 38). The gradient (``jax.grad`` through the layer) is the
shipped kernel's.

**Rows not computed here.** ``sizes`` holds the groups' lengths, as
``lax.ragged_dot`` takes them; the rows after their sum (a layer sorts
there what it does not compute: a prompt's padding, the assignments of
experts held elsewhere) are in no group. No grid step visits a tile of
theirs alone and they come out zero.

On non-TPU backends the kernel runs in Pallas interpret mode
(``ops/pallas_util.py``), as every kernel here does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bigdl_tpu.ops.pallas_util import compiler_params, fit_block, \
    use_interpret

# row, contraction and output tiles, each cut to fit the shape. From two
# chip sweeps at the LFM2 and dots3 cells' shapes (PERF.md section 6,
# PR 38): rows of 128 read an expert's weights the fewest times where a
# prompt pass's groups are a few dozen rows and four fifths of its rows
# are padding (rows of 512 took 1.1-1.8 times as long, of 256 up to
# 1.14), contraction and output tiles as large as fit a kernel's VMEM
# (128 x 128 x 128 took 4-7 times as long; 2048 x 1536 led 768-wide
# output tiles by 0-6 %)
TILING = (128, 2048, 1536)


def tiling(m, k, n):
    """``(tm, tk, tn)`` for an ``(m, k) x (k, n)`` product: a row tile of
    at most ``TILING[0]`` (the caller pads ``m`` to a multiple of it),
    the contraction and output tiles the largest multiples of 128 under
    ``TILING`` that divide ``k`` and ``n`` (or all of a smaller one)."""
    tm, tk, tn = TILING
    return (min(m, tm), fit_block(k, tk), fit_block(n, tn))


def _walk(sizes, m, tm):
    """The grid's walk over the groups' row tiles, in group order: for
    each of the ``m // tm + g - 1`` steps it may take (a group adds at
    most one tile it shares with the group before), the group it
    multiplies and the row tile it reads, and how many steps are taken.
    Empty groups take none. Plain vector arithmetic on ``g`` sizes."""
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(tiles)
    step = jnp.arange(m // tm + sizes.shape[0] - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(upto[None, :] <= step[:, None], axis=1, dtype=jnp.int32),
        sizes.shape[0] - 1)
    tile = jnp.clip(first[group] + step - (upto[group] - tiles[group]),
                    0, m // tm - 1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    # a pass where no row is held here (a block of a prompt's padding)
    # still takes one step, which stores no row: no grid of no steps
    return (offsets, group, tile), jnp.maximum(upto[-1], 1)


def _forward(lhs, rhs, sizes, tiles, interpret):
    """The kernel: the rows after the groups are left zero."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = rhs.shape[2]
    tm, tk, tn = tiles
    held = rhs.shape[0]
    meta, steps = _walk(sizes, m, tm)
    last_k = k // tk - 1

    def kernel(meta, lhs_ref, rhs_ref, out_ref, acc_ref):
        offsets, group_ids, m_tiles = meta
        step, k_i = pl.program_id(1), pl.program_id(2)
        part = jnp.dot(lhs_ref[...], rhs_ref[...],
                       preferred_element_type=jnp.float32)

        @pl.when(k_i == 0)
        def _():
            acc_ref[...] = part

        @pl.when(k_i > 0)
        def _():
            acc_ref[...] += part

        @pl.when(k_i == last_k)
        def _():
            g = group_ids[step]
            row = m_tiles[step] * tm + jax.lax.broadcasted_iota(
                jnp.int32, (tm, tn), 0)
            mine = (row >= offsets[g]) & (row < offsets[g + 1])
            out_ref[...] = jnp.where(mine, acc_ref[...], out_ref[...])

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tn, steps, k // tk),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda j, s, i, meta: (
                    meta[2][s], i)),
                pl.BlockSpec((None, tk, tn), lambda j, s, i, meta: (
                    meta[1][s], i, j))],
            out_specs=pl.BlockSpec((tm, tn), lambda j, s, i, meta: (
                meta[2][s], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=compiler_params(
            interpret, ("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name="grouped_matmul",
    )(meta, lhs, rhs)
    # rows of no visited tile (after the groups) were never written
    end = meta[0][held]
    return jnp.where(jnp.arange(m)[:, None] < end, out, 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(lhs, rhs, sizes, tiles, interpret):
    return _forward(lhs, rhs, sizes, tiles, interpret)


def _gmm_fwd(lhs, rhs, sizes, tiles, interpret):
    return (_forward(lhs, rhs, sizes, tiles, interpret), (lhs, rhs, sizes))


def _gmm_bwd(tiles, interpret, saved, grad):
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    lhs, rhs, sizes = saved
    # the shipped kernel's own gradient rule, at the forward's tiles; it
    # takes the rows after the groups as one more group, with no matrix
    group_sizes = jnp.append(sizes, lhs.shape[0] - jnp.sum(sizes))
    g_lhs, g_rhs, _, _, _ = megablox._gmm_bwd(
        jnp.float32, tiles, False, interpret,
        (lhs, rhs, group_sizes, None, rhs.shape[0]), grad)
    return g_lhs.astype(lhs.dtype), g_rhs.astype(rhs.dtype), None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def grouped_matmul(lhs, rhs, sizes, tiles=None, interpret=None):
    """``lhs`` (m, k), ``rhs`` (g, k, n), ``sizes`` (g,) int32 with a sum
    of at most ``m`` -> (m, n) float32: rows ``[s_i, s_i + sizes[i])`` of
    ``lhs`` times ``rhs[i]``, in the operands' dtype with float32 sums;
    the rows after the last group are not visited and come out zero.
    ``tiles`` overrides :func:`tiling` (the chip sweep).

    Jitted so that a prompt pass's calls of one shape share one trace and
    one lowering, which the host pays for every executable even where the
    compile cache holds it."""
    m, k = lhs.shape
    n = rhs.shape[2]
    tm, tk, tn = tiles or tiling(m, k, n)
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _gmm(lhs, rhs, jnp.asarray(sizes, jnp.int32), (tm, tk, tn),
               use_interpret() if interpret is None else interpret)
    return out[:m] if pad else out
