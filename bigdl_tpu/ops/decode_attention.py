"""The decode step's attention over the dense slot table, reading each
slot's live positions and no others.

The plain spelling (``parallel/sequence.py`` ``cached_attention``) scores
all ``max_position`` positions of all slots and masks: at the GPT-2
medium cell every layer read ``f32[48,16,1024,64]`` twice, 9.7 GB a step,
of which the live tokens held a tenth (PERF.md section 6, PR 29). Here
slot ``b`` reads ``ceil(counts[b] / 128)`` blocks of 128 positions of K
and of V, and a slot whose count is 0 (a free slot) reads nothing and
returns zeros.

**The table as the device keeps it.** As ``ops/kv_write.py``: with a head
size under 128 the positions are the minor dimension, the caller's
``swapaxes(2, 3)`` to ``(B, G, D, S)`` is a bitcast, and a block of 128
positions of one slot is ``(G, D, 128)``, whole tiles. The tables stay in
HBM and the grid walks the slots. A live slot's blocks are copied, K and
V, to the same lanes of one of two slot-sized buffers in VMEM; the copies
of the NEXT live slot (``nxt``, beside the counts) are started before
this one is computed, so the copies of a call form one stream, and a grid
step costs its third of a microsecond a slot, not a block.

**The arithmetic.** Queries are ``(B, G, R, D)``, ``R`` queries sharing
one K/V head (GPT-2: ``R = 1``; grouped-query attention: ``R`` > 1), in
float32, scaled here, and handed over by sublane tile like
``kv_write``'s new values: ``(G, rows, R * D / rows)``, column
``r * D / rows + j`` holding the ``rows`` values of query ``r`` that meet
tile ``j`` of a head's K. Products are taken in float32 on the vector
unit from the table's dtype (float32, or bfloat16 raised): nothing is
rounded below the table. With the whole slot in VMEM the softmax needs no
rescaling, and it is KEPT 128 LANES WIDE. First the scores of every block,
a ``(1, 128)`` row a head and block, and lane ``i``'s maximum over its own
positions ``i, i + 128, ...`` (K is computed while V still arrives). Then,
head by head, lane ``i``'s sum and ``(D,)`` accumulator stay in registers
over the slot's blocks: 16 multiplies and 16 adds of a vector register for
every 16 registers of K and V, no reduction across lanes in the loop, and
the 128 lanes are merged once a head and slot. A few heads are taken
together in each loop so that their chains interleave (PR 29's first form
went block by block through a flash recurrence with its accumulator in
VMEM, one head a loop: 150 cycles a head and block against 45 now).
Positions at or past the count are masked by lane in the slot's last
block, K and V both, so whatever lies there (a prefill's padding, another
request's tokens, NaN) changes nothing.

On non-TPU backends the kernel runs in pallas interpret mode
(``ops/pallas_util.py``), which its parity tests use; the decode step
takes it only where :func:`applies` says yes of the table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bigdl_tpu.ops.kv_write import (LANES, _TILE_ROWS, _whole_tiles,
                                    in_place_applies)
from bigdl_tpu.ops.pallas_util import NEG_INF, use_interpret

# positions a block holds: one lane tile, so a slot reads at most 127
# positions past its count
BLOCK = LANES


# what the kernel may ask of a core's VMEM (128 MiB on a v5e)
_VMEM_CEILING = 96 * 2 ** 20


def applies(table, layout=None):
    """Whether the decode step reads ``table`` (one layer's K or V as
    allocated) through the kernel: where ``kv_write.in_place_applies``
    takes the write (a TPU, no mesh layout, float32 or bfloat16 in whole
    tiles, positions minor on the device), and two slots' K and V fit the
    chip's fast memory. Anything else keeps the masked read."""
    return (in_place_applies(table, layout)
            and _vmem_bytes(table.shape, table.dtype, 1) <= _VMEM_CEILING)


def blocks_read(lengths, active, block=BLOCK):
    """How many blocks one decode step reads for host-side ``lengths``
    and ``active`` (numpy, per slot): every live slot reads the position
    it has just written too, hence ``length + 1``."""
    return int((-(-(lengths[active] + 1) // block)).sum())


def _kernel(n_ref, nxt_ref, q_ref, k_hbm, v_hbm, out_ref,
            k_buf, v_buf, sem, turn, s_ref, m_ref, *, rows, reps, together):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    b = pl.program_id(0)
    n = n_ref[b]
    blocks = pl.cdiv(n, BLOCK)
    heads, d = k_buf.shape[1], k_buf.shape[2]
    tiles = d // rows
    cols = reps * tiles

    def lanes_of(i):
        return pl.ds(pl.multiple_of(i * BLOCK, BLOCK), BLOCK)

    def copy(table, slot, i, buf):
        """Block ``i`` of ``slot``, of K (``table`` 0) or of V (1), to
        the same lanes of buffer ``buf``."""
        at = lanes_of(i)
        # the buffers are written by these copies and read once they have
        # arrived
        # jaxlint: disable-next-line=pallas-scratch-uninit
        hbm, vmem = ((k_hbm, k_buf), (v_hbm, v_buf))[table]
        return pltpu.make_async_copy(hbm.at[slot, :, :, at],
                                     vmem.at[buf, :, :, at],
                                     sem.at[table, buf])

    def fetch(slot, buf):
        """Start the copies of every live block of ``slot``."""
        def one(i, carry):
            copy(0, slot, i, buf).start()
            copy(1, slot, i, buf).start()
            return carry

        jax.lax.fori_loop(0, pl.cdiv(n_ref[slot], BLOCK), one, None)

    def arrived(table, buf):
        def one(i, carry):
            copy(table, b, i, buf).wait()
            return carry

        jax.lax.fori_loop(0, blocks, one, None)

    # turn[0]: the buffer that the next live slot's blocks land in;
    # turn[1]: whether an earlier slot has already started them
    @pl.when(b == 0)
    def _():
        turn[0] = 0
        turn[1] = 0

    @pl.when(n == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when(n > 0)
    def _():
        buf = turn[0]

        @pl.when(turn[1] == 0)
        def _():
            fetch(b, buf)

        # the next live slot's blocks arrive while this one is computed
        @pl.when(nxt_ref[b] >= 0)
        def _():
            fetch(nxt_ref[b], 1 - buf)

        turn[0] = 1 - buf
        turn[1] = 1
        last = blocks - 1
        # the lanes of the last block that hold a live position
        valid = (jax.lax.broadcasted_iota(jnp.int32, (1, 1, BLOCK), 2)
                 < n - last * BLOCK)

        def tree(terms):
            """The terms' sum in pairs: a chain log2 of them deep."""
            for _ in range((len(terms) - 1).bit_length()):
                odd = terms[len(terms) - len(terms) % 2:]
                terms = [a + c for a, c in zip(terms[::2], terms[1::2])] + odd
            return terms[0]

        # Both passes take ``together`` heads at a time as ONE array with
        # the heads leading, (together, rows, 128): the heads' chains
        # interleave in the loop's body, and the body is traced once for
        # them all (the step's first call traces and lowers it).

        # ---- scores: s_ref[g, r, i] (1, 128) for every live block, and
        # each lane's maximum over its own positions
        def score_heads(gg, carry):
            hs = pl.ds(gg * together, together)
            q = q_ref[hs]                   # (together, rows, reps * tiles)
            qcol = [[jnp.broadcast_to(q[:, :, r * tiles + j:r * tiles + j + 1],
                                      (together, rows, BLOCK))
                     for j in range(tiles)] for r in range(reps)]

            def one_block(i, m, masked):
                at = lanes_of(i)
                kt = [k_buf[buf, hs, pl.ds(j * rows, rows), at].astype(f32)
                      for j in range(tiles)]
                out = []
                for r in range(reps):
                    s = jnp.sum(tree([qcol[r][j] * kt[j]
                                      for j in range(tiles)]),
                                axis=1, keepdims=True)   # (together, 1, 128)
                    if masked:
                        s = jnp.where(valid, s, NEG_INF)
                    s_ref[hs, r, i] = s
                    out.append(jnp.maximum(m[r], s))
                return tuple(out)

            m = (jnp.full((together, 1, BLOCK), NEG_INF, f32),) * reps
            m = jax.lax.fori_loop(
                0, last, lambda i, m: one_block(i, m, False), m)
            m = one_block(last, m, True)
            for r in range(reps):
                m_ref[hs, pl.ds(r, 1), :] = m[r]
            return carry

        # ---- values: each lane's sum and (D,) accumulator stay in
        # registers over the slot's blocks; the 128 lanes are merged once
        lane = jax.lax.broadcasted_iota(jnp.int32, (together, rows, cols), 2)

        def value_heads(gg, carry):
            hs = pl.ds(gg * together, together)
            m = [m_ref[hs, pl.ds(r, 1), :] for r in range(reps)]

            def one_block(i, state, masked):
                at = lanes_of(i)
                vt = [v_buf[buf, hs, pl.ds(j * rows, rows), at].astype(f32)
                      for j in range(tiles)]
                if masked:
                    vt = [jnp.where(valid, t, 0.0) for t in vt]  # 0 x NaN
                out = []
                for r in range(reps):
                    total, acc = state[r]
                    p = jnp.exp(s_ref[hs, r, i] - m[r])
                    if masked:
                        # a lane without a live position keeps NEG_INF,
                        # and exp(NEG_INF - NEG_INF) is 1
                        p = jnp.where(valid, p, 0.0)
                    out.append((total + p,
                                tuple(a + t * p for a, t in zip(acc, vt))))
                return tuple(out)

            zero = (jnp.zeros((together, 1, BLOCK), f32),
                    (jnp.zeros((together, rows, BLOCK), f32),) * tiles)
            state = jax.lax.fori_loop(
                0, last, lambda i, st: one_block(i, st, False),
                (zero,) * reps)
            state = one_block(last, state, True)
            out = jnp.zeros((together, rows, cols), f32)
            for r in range(reps):
                total, acc = state[r]
                w = jnp.exp(m[r] - jnp.max(m[r], axis=2, keepdims=True))
                w = w / jnp.sum(total * w, axis=2, keepdims=True)
                for j in range(tiles):
                    col = jnp.sum(acc[j] * w, axis=2, keepdims=True)
                    out = jnp.where(lane == r * tiles + j, col, out)
            out_ref[hs] = out
            return carry

        arrived(0, buf)
        jax.lax.fori_loop(0, heads // together, score_heads, None)
        arrived(1, buf)
        jax.lax.fori_loop(0, heads // together, value_heads, None)


def _by_tile(q, rows):
    """(B, G, R, D) -> (B, G, rows, R * D / rows): column
    ``r * D / rows + j`` holds ``q[b, g, r, j * rows:(j + 1) * rows]``."""
    b, g, r, d = q.shape
    return (q.reshape(b, g, r, d // rows, rows).transpose(0, 1, 4, 2, 3)
            .reshape(b, g, rows, r * (d // rows)))


def _from_tile(out, reps):
    """:func:`_by_tile` undone: (B, G, rows, R * D / rows) -> (B, G, R, D)."""
    b, g, rows, cols = out.shape
    return (out.reshape(b, g, rows, reps, cols // reps)
            .transpose(0, 1, 3, 4, 2).reshape(b, g, reps, -1))


def decode_attention(q, k_table, v_table, counts, interpret=None):
    """Softmax attention of ``q`` (B, G, R, D) over the first
    ``counts[b]`` positions of ``k_table``/``v_table`` (B, G, S, D), by
    ``D ** -0.5`` scaled: (B, G, R, D) float32. A row whose count is 0
    reads nothing and is zeros; a count past ``S`` is ``S``. float32 or
    bfloat16 tables; ``S`` a multiple of 128 and ``D`` of the dtype's
    sublane tile."""
    if not _whole_tiles(k_table.shape, k_table.dtype):
        raise ValueError(
            f"decode_attention needs float32 or bfloat16, max_position a "
            f"multiple of {LANES} and the head size a multiple of 8 (16 "
            f"for bfloat16), got {k_table.dtype}{list(k_table.shape)}")
    if interpret is None:
        interpret = use_interpret()
    return _decode_attention(q, k_table, v_table, counts, interpret)


# jitted so that the step's calls of one shape are traced and lowered
# once (each lowering builds the kernel's Mosaic module)
@functools.partial(jax.jit, static_argnames="interpret")
def _decode_attention(q, k_table, v_table, counts, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, g, s, d = k_table.shape
    reps = q.shape[2]
    rows = _TILE_ROWS[jnp.dtype(k_table.dtype)]
    cols = reps * (d // rows)
    # heads taken together in one loop: up to 8 (head, query) chains, as
    # many as keep their accumulators in the 64 vector registers
    together = next((t for t in (4, 2) if g % t == 0 and t * reps <= 8), 1)
    counts = jnp.clip(jnp.asarray(counts, jnp.int32), 0, s)
    # the next live slot after each, -1 after the last
    slots = jnp.arange(b, dtype=jnp.int32)
    later = jax.lax.cummin(jnp.where(counts > 0, slots, b), reverse=True)
    nxt = jnp.concatenate([later[1:], jnp.full((1,), b, jnp.int32)])
    nxt = jnp.where(nxt < b, nxt, -1)
    by_slot = pl.BlockSpec((None, g, rows, cols),
                           lambda bb, n, nxt: (bb, 0, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    f32 = jnp.float32
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=_vmem_bytes(k_table.shape, k_table.dtype, reps))
    out = pl.pallas_call(
        functools.partial(_kernel, rows=rows, reps=reps, together=together),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[by_slot, in_hbm, in_hbm],
            out_specs=by_slot,
            scratch_shapes=[
                pltpu.VMEM((2, g, d, s), k_table.dtype),
                pltpu.VMEM((2, g, d, s), v_table.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((g, reps, s // BLOCK, 1, BLOCK), f32),
                pltpu.VMEM((g, reps, BLOCK), f32)]),
        out_shape=jax.ShapeDtypeStruct((b, g, rows, cols), f32),
        compiler_params=params,
        interpret=interpret,
        name="decode_attention",
    )(counts, nxt,
      _by_tile(q.astype(f32) * d ** -0.5, rows),
      k_table.swapaxes(2, 3), v_table.swapaxes(2, 3))
    return _from_tile(out, reps)


def _vmem_bytes(shape, dtype, reps):
    """What the kernel asks of the chip's fast memory for a table
    ``shape`` (B, G, S, D): one slot's K and V twice over, its scores a
    sublane tile a block, and room for the queries and the compiler."""
    _, g, s, d = shape
    slot = g * s * d * jnp.dtype(dtype).itemsize
    return 4 * slot + g * reps * (s // BLOCK) * 8 * BLOCK * 4 + 8 * 2 ** 20
