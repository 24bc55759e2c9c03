"""One query a slot against a latent cache, reading each live slot's rows
up to its position and no others, under a mask that says which of them
count.

A latent-attention layer (``nn/latent.py``) keeps one row a position,
``[c_kv | k_r]``, that every head reads: the keys ARE the rows and the
values their first ``kv_rank`` numbers, so a step's attention is one
``(heads, width) x (width, rows)`` product a slot and one ``(heads, rows)
x (rows, kv_rank)`` back, the shape a matrix unit wants. A layer that
reads by selection (``nn.SelectedLatentAttention``) reads only the
``index_topk`` rows its indexer picks, and the plain spelling of that is a
top-k and a gather of the chosen rows: on a v5e XLA's gather of 36 x 2048
rows of ``bf16[576]`` takes 6.0 ms (81 ns a row), more than reading every
row of every live stream (0.6 GB at the cell's contexts, 0.7 ms of
bandwidth), because one bfloat16 row is half a packed sublane of a tiled
table, and Mosaic refuses a one-row DMA outright (PERF.md section 6, PR
34). So this kernel reads BLOCKS of rows, each live slot's own up to its
position, and takes the selection as a bias a row: 0 where the row
counts, ``-inf`` where it does not (not chosen, or past the position).
The bytes it reads are the context's, not the selection's, and the
arithmetic is the context's too; what the selection saves on this chip is
the softmax's reach, not traffic.

The grid walks ``(slot, block of rows)``; a block past a slot's last, or
any block of a free slot, is neither fetched (its index map names the
block already there) nor computed. One running softmax a head in VMEM,
products from the table's dtype with float32 sums. The model keeps its
rows in whole lanes of 128 (zeros behind the 576 or 1088 numbers: the
device pads a tiled table's rows to that anyway, so it costs no memory,
and left at 576 the device keeps the ROWS minor and XLA turns the whole
table over before every call).

On non-TPU backends the kernel runs in pallas interpret mode
(``ops/pallas_util.py``), which its parity tests use; a decode step takes
it only where :func:`applies` says yes of the table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.pallas_util import compiler_params, use_interpret

LANES = 128
# rows a grid step reads: 2048 rows of bf16[640] are 2.6 MB, twice that in
# flight; a slot reads at most BLOCK - 1 rows past its position
BLOCK = 2048


def applies(table, layout=None):
    """Whether a decode step reads ``table`` (one layer's latent rows as
    allocated, ``(slots, rows, width)``) through the kernel: on a TPU,
    with no mesh layout, float32 or bfloat16, rows in whole blocks and a
    width in whole lanes (row-major on the device). Anything else keeps
    the plain read."""
    if layout is not None or jax.default_backend() != "tpu":
        return False
    return (table.ndim == 3 and table.dtype in (jnp.float32, jnp.bfloat16)
            and table.shape[1] % min(BLOCK, table.shape[1]) == 0
            and table.shape[1] % LANES == 0
            and table.shape[2] % LANES == 0)


def fetched_rows(table, layout=None):
    """None where :func:`applies` says no of ``table``; else
    ``fetched(last)``: the rows the kernel moves of a live slot whose last
    readable row is ``last`` (an int or a numpy array), whole blocks up to
    it (of a free slot it moves none). What a model gives the slot table
    as ``RowTable.own_read``."""
    if not applies(table, layout):
        return None
    block = min(BLOCK, table.shape[1])
    return lambda last: (last // block + 1) * block


def _kernel(last_ref, live_ref, q_ref, bias_ref, rows_ref, o_ref,
            top, total, acc, *, v_width):
    slot, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        top[...] = jnp.full(top.shape, -jnp.inf, jnp.float32)
        total[...] = jnp.zeros(total.shape, jnp.float32)
        acc[...] = jnp.zeros(acc.shape, jnp.float32)

    @pl.when((live_ref[slot] != 0) & (j <= last_ref[slot]))
    def _():
        rows = rows_ref[0]                                 # (block, width)
        s = lax.dot_general(q_ref[0], rows, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = s + bias_ref[0]                                # (heads, block)
        new_top = jnp.maximum(top[...], s.max(axis=-1, keepdims=True))
        # a head that has read no row that counts keeps weight 0
        safe = jnp.where(new_top > -jnp.inf, new_top, 0.0)
        p = jnp.exp(s - safe)
        keep = jnp.exp(jnp.where(top[...] > -jnp.inf, top[...] - safe,
                                 -jnp.inf))
        total[...] = total[...] * keep + p.sum(axis=-1, keepdims=True)
        acc[...] = acc[...] * keep + jnp.dot(
            p.astype(rows.dtype), rows[:, :v_width],
            preferred_element_type=jnp.float32)
        top[...] = new_top

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = acc[...] / jnp.where(total[...] > 0.0, total[...], 1.0)


def latent_attention(q, table, bias, pos, live, v_width, interpret=None):
    """``q`` (B, heads, width) scaled queries in the table's dtype,
    ``table`` (B, rows, width), ``bias`` (B, rows) float32 (0 for a row
    that counts, ``-inf`` for one that does not), ``pos`` (B,) int32 the
    last row a slot may read, ``live`` (B,) bool. Returns the mixed
    values (B, heads, v_width) float32: ``softmax(q . rows + bias) @
    rows[:, :v_width]`` over rows ``0 .. pos``; zeros for a slot that is
    not live or reads no row that counts."""
    interpret = use_interpret() if interpret is None else interpret
    return _latent_attention(q, table, bias, pos, live, v_width, interpret)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _latent_attention(q, table, bias, pos, live, v_width, interpret):
    b, heads, width = q.shape
    rows = table.shape[1]
    block = min(BLOCK, rows)
    last = jnp.clip(pos, 0, rows - 1).astype(jnp.int32) // block
    live = live.astype(jnp.int32)

    def at(bb, j, last, live):
        return jnp.where(live[bb] != 0, jnp.minimum(j, last[bb]), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, rows // block),
        in_specs=[
            pl.BlockSpec((1, heads, width),
                         lambda bb, j, last, live: (bb, 0, 0)),
            pl.BlockSpec((1, 1, block),
                         lambda bb, j, last, live: (bb, 0,
                                                    at(bb, j, last, live))),
            pl.BlockSpec((1, block, width),
                         lambda bb, j, last, live: (bb,
                                                    at(bb, j, last, live),
                                                    0))],
        out_specs=pl.BlockSpec((1, heads, v_width),
                               lambda bb, j, last, live: (bb, 0, 0)),
        scratch_shapes=[pltpu.VMEM((heads, 1), jnp.float32),
                        pltpu.VMEM((heads, 1), jnp.float32),
                        pltpu.VMEM((heads, v_width), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, v_width=v_width),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, heads, v_width), jnp.float32),
        compiler_params=compiler_params(interpret,
                                        ("parallel", "arbitrary")),
        interpret=interpret, name="latent_attention",
    )(last, live, q.astype(table.dtype), bias[:, None, :], table)
