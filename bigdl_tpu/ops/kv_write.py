"""In-place write of one decode step's K/V into the dense slot table.

The serving slot table (``serving/slots.py``) keeps each layer's K and V
as ``(slots, H, max_position, D)`` and every decode step writes ONE new
position a slot, each slot at its own length. The plain spelling
(``jax.vmap`` over ``lax.dynamic_update_slice``, ``parallel/sequence.py``)
is a scatter of ``slots`` indices, and XLA's TPU pipeline expands a
scatter into a ``while`` over its indices: four small operations a trip,
in series, 48 trips for K and 48 for V in each of 24 layers. At the
GPT-2 medium cell that was 17 ms of a 38 ms step spent on launch latency
(PERF.md section 6, PR 27), to move 9 MB.

**Where a position lies on the device.** With a head size under 128 the
TPU does not keep ``(B, H, S, D)`` row-major: it makes the positions the
minor (lane) dimension, ``{2,3,1,0:T(8,128)}``, so that nothing is
padded to 128 lanes. One position of one slot is then one lane column of
``H * D / 8`` tiles of 8 x 128, and a kernel over the logical
``(H, r, D)`` rows forces XLA to transpose the whole table into the
kernel's layout and back (two 200 MB copies a table a call). So the
kernel works on the table as the device holds it: the caller's
``swapaxes(2, 3)`` to ``(B, H, D, S)`` is a bitcast of that layout, the
grid walks the slots, the block of slot ``b`` is the 128 positions
``(H, D, 128)`` around ``pos[b]``, found through the scalar-prefetched
positions by the BlockSpec's index map, and the body replaces lane
``pos[b] % 128`` of every tile and hands the block back. The tables are
passed and returned under ``input_output_aliases``: a donated buffer is
updated where it lies and no byte outside those blocks is touched.

The new values arrive as ``(H, r, D / r)`` a slot (``r`` the sublane
tile: 8 rows of float32, 16 of bfloat16), column ``j`` of head ``h``
holding the ``r`` values that belong to that head's tile ``j``: the body
broadcasts the column along the lanes and selects it into lane
``pos[b] % 128``.

Positions are brought into ``[0, max_position)`` before they reach the
index map, as ``dynamic_update_slice`` treats its start (a negative one
counts from the end, then it is clamped): a position from outside can
never send a block's DMA off the table.

On non-TPU backends the kernel runs in pallas interpret mode
(``ops/pallas_util.py``), which its parity tests use; the decode step
takes it only where :func:`in_place_applies` says so.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bigdl_tpu.ops.pallas_util import compiler_params, use_interpret

LANES = 128
# rows of one sublane tile, by the cache's dtype
_TILE_ROWS = {jnp.dtype(jnp.float32): 8, jnp.dtype(jnp.bfloat16): 16}


def _whole_tiles(shape, dtype):
    """The kernel has a tile for ``dtype`` and ``shape`` (B, H, S, D)
    is whole tiles in both minor dimensions."""
    rows = _TILE_ROWS.get(jnp.dtype(dtype))
    return rows is not None and not (shape[2] % LANES or shape[3] % rows)


def in_place_applies(table, layout=None):
    """Whether the kernel takes the per-row write into ``table`` (one
    layer's K or V as allocated, a concrete array), from what the code
    can see: the table lies on a TPU, is not laid out over a mesh
    (``layout`` is the manager's ``ModelLayout``, else None), is whole
    tiles of a dtype the kernel knows, and the device keeps its
    positions minor, which is the layout the kernel's view is a bitcast
    of. Anything else keeps :func:`plain_write`."""
    return (layout is None
            and next(iter(table.devices())).platform == "tpu"
            and _whole_tiles(table.shape, table.dtype)
            and tuple(table.format.layout.major_to_minor) == (0, 1, 3, 2))


def plain_write(k_table, v_table, k_new, v_new, pos):
    """The write in plain XLA, ``jax.vmap`` over
    ``lax.dynamic_update_slice``: a scatter of B rows. What the decode
    step does wherever the kernel does not apply, and what the kernel is
    held to bit for bit."""
    def put(buf, new, i):   # (H, S, D) <- (H, 1, D) at i
        return jax.lax.dynamic_update_slice(buf, new, (0, i, 0))

    return (jax.vmap(put)(k_table, k_new, pos),
            jax.vmap(put)(v_table, v_new, pos))


def _write_kernel(pos_ref, k_new_ref, v_new_ref, k_ref, v_ref,
                  k_out_ref, v_out_ref, *, rows):
    from jax.experimental import pallas as pl

    lane = pos_ref[pl.program_id(0)] % LANES
    hit = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1) == lane
    heads, d, _ = k_ref.shape

    def one_head(h, carry):
        for new_ref, ref, out_ref in ((k_new_ref, k_ref, k_out_ref),
                                      (v_new_ref, v_ref, v_out_ref)):
            new = new_ref[h]                      # (rows, tiles a head)
            for j in range(d // rows):
                tile = (h, pl.ds(j * rows, rows), slice(None))
                # column j along the lanes, kept at the one lane: the
                # tile's other positions go back as they were read
                out_ref[tile] = jnp.where(hit, new[:, j:j + 1], ref[tile])
        return carry

    # a loop, not 2 x H x D / rows unrolled tiles: the body is traced and
    # lowered once a call, and the step makes one call a layer
    jax.lax.fori_loop(0, heads, one_head, None)


def _by_tile(new, rows):
    """(B, H, 1, D) -> (B, H, rows, D / rows): column ``j`` of head ``h``
    holds ``new[b, h, 0, j * rows:(j + 1) * rows]``, the values of that
    head's tile ``j``."""
    b, h, _, d = new.shape
    return new.reshape(b, h, d // rows, rows).swapaxes(2, 3)


def kv_write(k_table, v_table, k_new, v_new, pos, interpret=None):
    """Write ``k_new``/``v_new`` (B, H, 1, D) into ``k_table``/``v_table``
    (B, H, S, D) at ``[b, :, pos[b], :]`` and return the two tables, bit
    for bit what :func:`plain_write` writes for ``pos`` (B,) int32 (out
    of range it is clamped, as there). float32 or bfloat16; ``S`` must be
    a multiple of 128 and ``D`` of the dtype's sublane tile."""
    if not _whole_tiles(k_table.shape, k_table.dtype):
        raise ValueError(
            f"kv_write needs float32 or bfloat16, max_position a multiple "
            f"of {LANES} and the head size a multiple of 8 (16 for "
            f"bfloat16), got {k_table.dtype}{list(k_table.shape)}")
    if interpret is None:
        interpret = use_interpret()
    return _kv_write(k_table, v_table, k_new, v_new, pos, interpret)


# jitted so that the step's 24 calls of one shape are traced and lowered
# once (each lowering builds the kernel's Mosaic module)
@functools.partial(jax.jit, static_argnames="interpret")
def _kv_write(k_table, v_table, k_new, v_new, pos, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = k_table.shape
    rows = _TILE_ROWS[jnp.dtype(k_table.dtype)]
    # what ``lax.dynamic_update_slice`` does to a start index: a negative
    # one counts from the end, then it is clamped into the table
    pos = jnp.asarray(pos, jnp.int32)
    pos = jnp.clip(jnp.where(pos < 0, pos + s, pos), 0, s - 1)
    new_spec = pl.BlockSpec((None, h, rows, d // rows),
                            lambda bb, pos: (bb, 0, 0, 0))
    block_spec = pl.BlockSpec((None, h, d, LANES),
                              lambda bb, pos: (bb, 0, 0, pos[bb] // LANES))
    view = jax.ShapeDtypeStruct((b, h, d, s), k_table.dtype)
    k_out, v_out = pl.pallas_call(
        functools.partial(_write_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[new_spec, new_spec, block_spec, block_spec],
            out_specs=[block_spec, block_spec]),
        out_shape=[view, view],
        # operand indices count the prefetched positions: 3 and 4 are
        # the two tables
        input_output_aliases={3: 0, 4: 1},
        compiler_params=compiler_params(interpret, ("arbitrary",)),
        interpret=interpret,
        name="kv_write",
    )(pos,
      _by_tile(k_new.astype(k_table.dtype), rows),
      _by_tile(v_new.astype(v_table.dtype), rows),
      k_table.swapaxes(2, 3), v_table.swapaxes(2, 3))
    return k_out.swapaxes(2, 3), v_out.swapaxes(2, 3)
