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
block of slot ``b`` is the 128 positions ``(H, D, 128)`` around
``pos[b]``, and the kernel replaces lane ``pos[b] % 128`` of every tile
of it and hands the block back. The tables are passed and returned
under ``input_output_aliases``: a donated buffer is updated where it
lies and no byte outside those blocks is touched.

**Live slots only.** The serving step holds a request in some of its
slots (5 to 12 of 48 at the GPT-2 medium chat cell), and what a free
slot would be written is junk that nothing reads. So the step's mask
``live`` comes with the positions, both scalar-prefetched, and the
tables stay in HBM: the kernel packs the numbers of the live slots into
SMEM and walks THEM, not a grid over the table. A live slot's block of
K, its block of V and its new values are copied into one of
:data:`DEPTH` VMEM buffers, the lane is replaced there, and the two
blocks are copied back to where they came from; the copies in of the
slot :data:`AHEAD` places on are started before this one's are waited
for, so two slots' reads and two slots' writes are under way at once. A
call moves ``live x 2 tables x H x D x 128 x itemsize x 2`` bytes (a read
and a write: 2 MB a live slot at ``f32[48,16,1024,64]``) and a free slot
costs one scalar compare: 1 us + 3.2 us a live slot on a v5e, 155 us with
all 48 live, which is what the walk over every slot took whatever was
live (PERF.md section 6, PR 33). No two live slots are the same slot, so
no block is in two buffers at once; with no slot live nothing is copied
and the tables come back as they were given.

The new values arrive as ``(H, r, 128)`` a slot (``r`` the sublane tile:
8 rows of float32, 16 of bfloat16), column ``j`` of head ``h`` holding
the ``r`` values that belong to that head's tile ``j`` (the columns past
``D / r`` are padding: a copy out of HBM takes whole tiles): the kernel
broadcasts the column along the lanes and selects it into lane
``pos[b] % 128``.

Positions are brought into ``[0, max_position)`` before they reach a
copy, as ``dynamic_update_slice`` treats its start (a negative one
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
# the ring of VMEM buffers a call's live slots pass through, and how many
# slots ahead of the one being written their blocks are asked for: with
# 4 and 2, two slots' reads and two slots' writes are under way at once
DEPTH, AHEAD = 4, 2
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


def _write_kernel(pos_ref, live_ref, k_new_hbm, v_new_hbm, k_hbm, v_hbm,
                  k_out_hbm, v_out_hbm, order, k_buf, v_buf, k_new, v_new,
                  sem_in, sem_out, *, rows):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots = pos_ref.shape[0]
    _, heads, d, _ = k_buf.shape

    # the live slots' numbers, ascending, at the head of ``order``
    def note(b, n):
        @pl.when(live_ref[b] != 0)
        def _():
            order[n] = b

        return n + live_ref[b]

    n = jax.lax.fori_loop(0, slots, note, 0)

    def copies(i, out):
        """The copies of the ``i``-th live slot: in, its block of K and
        of V and its new values to ring buffer ``i % DEPTH``; out, the
        two blocks back to where they came from."""
        slot, buf = order[i], i % DEPTH
        at = pl.ds(pl.multiple_of(pos_ref[slot] // LANES * LANES, LANES),
                   LANES)
        if out:
            return (pltpu.make_async_copy(k_buf.at[buf],
                                          k_out_hbm.at[slot, :, :, at],
                                          sem_out.at[0, buf]),
                    pltpu.make_async_copy(v_buf.at[buf],
                                          v_out_hbm.at[slot, :, :, at],
                                          sem_out.at[1, buf]))
        return (pltpu.make_async_copy(k_hbm.at[slot, :, :, at],
                                      k_buf.at[buf], sem_in.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[slot, :, :, at],
                                      v_buf.at[buf], sem_in.at[1, buf]),
                pltpu.make_async_copy(k_new_hbm.at[slot], k_new.at[buf],
                                      sem_in.at[2, buf]),
                pltpu.make_async_copy(v_new_hbm.at[slot], v_new.at[buf],
                                      sem_in.at[3, buf]))

    def start(i, out=False):
        for c in copies(i, out):
            c.start()

    def wait(i, out=False):
        for c in copies(i, out):
            c.wait()

    def select(i):
        """Lane ``pos % 128`` of every tile of the ``i``-th live slot's
        two blocks takes the new value; the block's other positions stay
        as they were read."""
        buf = i % DEPTH
        hit = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1) \
            == pos_ref[order[i]] % LANES

        def one_head(h, carry):
            # the buffers are written by the copies in and read once
            # those have arrived
            # jaxlint: disable-next-line=pallas-scratch-uninit
            for new_ref, ref in ((k_new, k_buf), (v_new, v_buf)):
                new = new_ref[buf, h]                 # (rows, tiles a head)
                for j in range(d // rows):
                    tile = (buf, h, pl.ds(j * rows, rows), slice(None))
                    # column j along the lanes, kept at the one lane
                    ref[tile] = jnp.where(hit, new[:, j:j + 1], ref[tile])
            return carry

        # a loop, not 2 x H x D / rows unrolled tiles: the body is traced
        # and lowered once a call, and the step makes one call a layer
        jax.lax.fori_loop(0, heads, one_head, None)

    for a in range(AHEAD):
        pl.when(a < n)(functools.partial(start, a))

    def one(i, carry):
        # the buffer that slot i + AHEAD lands in was slot i + AHEAD -
        # DEPTH's: its blocks must have left
        @pl.when(i >= DEPTH - AHEAD)
        def _():
            wait(i + AHEAD - DEPTH, out=True)

        @pl.when(i + AHEAD < n)
        def _():
            start(i + AHEAD)

        wait(i)
        select(i)
        start(i, out=True)
        return carry

    jax.lax.fori_loop(0, n, one, None)
    # the writes that no later slot has waited for
    for a in range(DEPTH - AHEAD):
        pl.when(a < n)(functools.partial(wait, n - 1 - a, out=True))


def _by_tile(new, rows):
    """(B, H, 1, D) -> (B, H, rows, 128): column ``j`` of head ``h``
    holds ``new[b, h, 0, j * rows:(j + 1) * rows]``, the values of that
    head's tile ``j``; the columns past ``D / rows`` are padding, so that
    a slot's values are whole tiles that a copy can take from HBM (the
    device pads a minor dimension of ``D / rows`` to 128 lanes anyway)."""
    b, h, _, d = new.shape
    cols = new.reshape(b, h, d // rows, rows).swapaxes(2, 3)
    return jnp.pad(cols, ((0, 0),) * 3 + ((0, LANES - d // rows),))


def kv_write(k_table, v_table, k_new, v_new, pos, live=None, interpret=None):
    """Write ``k_new``/``v_new`` (B, H, 1, D) into ``k_table``/``v_table``
    (B, H, S, D) at ``[b, :, pos[b], :]`` for every ``b`` that ``live``
    (B,) bool marks, and return the two tables: a live slot's row bit for
    bit what :func:`plain_write` writes for ``pos`` (B,) int32 (out of
    range it is clamped, as there), every other slot bit for bit as it
    came, none of its blocks read or written. ``live`` None: every slot
    is live. float32 or bfloat16; ``S`` must be a multiple of 128 and
    ``D`` of the dtype's sublane tile."""
    if not _whole_tiles(k_table.shape, k_table.dtype):
        raise ValueError(
            f"kv_write needs float32 or bfloat16, max_position a multiple "
            f"of {LANES} and the head size a multiple of 8 (16 for "
            f"bfloat16), got {k_table.dtype}{list(k_table.shape)}")
    if interpret is None:
        interpret = use_interpret()
    if live is None:
        live = jnp.ones(k_table.shape[:1], bool)
    return _kv_write(k_table, v_table, k_new, v_new, pos, live, interpret)


# jitted so that the step's 24 calls of one shape are traced and lowered
# once (each lowering builds the kernel's Mosaic module)
@functools.partial(jax.jit, static_argnames="interpret")
def _kv_write(k_table, v_table, k_new, v_new, pos, live, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = k_table.shape
    rows = _TILE_ROWS[jnp.dtype(k_table.dtype)]
    # what ``lax.dynamic_update_slice`` does to a start index: a negative
    # one counts from the end, then it is clamped into the table
    pos = jnp.asarray(pos, jnp.int32)
    pos = jnp.clip(jnp.where(pos < 0, pos + s, pos), 0, s - 1)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    view = jax.ShapeDtypeStruct((b, h, d, s), k_table.dtype)
    block = pltpu.VMEM((DEPTH, h, d, LANES), k_table.dtype)
    new = pltpu.VMEM((DEPTH, h, rows, LANES), k_table.dtype)
    k_out, v_out = pl.pallas_call(
        functools.partial(_write_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[in_hbm] * 4,
            out_specs=[in_hbm] * 2,
            scratch_shapes=[pltpu.SMEM((b,), jnp.int32), block, block,
                            new, new,
                            pltpu.SemaphoreType.DMA((4, DEPTH)),
                            pltpu.SemaphoreType.DMA((2, DEPTH))]),
        out_shape=[view, view],
        # operand indices count the prefetched positions and mask: 4 and
        # 5 are the two tables
        input_output_aliases={4: 0, 5: 1},
        compiler_params=compiler_params(interpret, ("arbitrary",)),
        interpret=interpret,
        name="kv_write",
    )(pos, jnp.asarray(live).astype(jnp.int32),
      _by_tile(k_new.astype(k_table.dtype), rows),
      _by_tile(v_new.astype(v_table.dtype), rows),
      k_table.swapaxes(2, 3), v_table.swapaxes(2, 3))
    return k_out.swapaxes(2, 3), v_out.swapaxes(2, 3)
