"""EVA attention's decode-step read: one query a slot against the window
rows it has written and the summaries of its closed windows, under ONE
softmax, reading each live slot's own blocks of both tables and no others.

``models/evabyte.py`` keeps, a layer, the window's K and V ``(slots,
window, heads, head_dim)`` and the chunk summaries' ``(slots, max_position
/ chunk, heads, head_dim)``, rows before heads: with a head of 128 a row
is whole tiles of ``heads x head_dim``. The plain read scores every row of
both tables of every slot and masks (``EvaAttention._attend``): at the
EvaByte cell 8.05 GB a step, of which the streams need 43 % (PERF.md
section 5). Here slot ``b`` reads window rows ``0 .. pos mod window`` and
summary rows ``0 .. (pos div window) x (window / chunk) - 1``, in blocks
of 128 rows: a block is ``(128, heads, head_dim)``, 1 MB in bfloat16 at
the cell. A count is rounded up to whole blocks and the tail is masked by
row, so what lies past a slot's count (a former stream's rows, NaN)
changes nothing; a block past a slot's last is never fetched, and a free
slot fetches nothing and returns zeros.

**The stream of copies.** The tables stay in HBM and the grid walks the
slots. A live slot's work is its blocks of K (summaries first, then the
window) and then the same blocks of V; the items of all live slots form
one sequence, copied into a ring of ``NBUF`` block buffers ``NBUF - 1``
items ahead of the one computed, across the end of a slot into the next
live slot's (``order`` says which that is), so the copies of a call form
one stream.

**The arithmetic.** Two passes a slot, as ``_attend`` adds: the scores of
every block, the maximum and the sum over both tables' columns, then
``(exp(s - max) / sum)`` rounded to the table's dtype against V. Products
from the table's dtype with float32 sums (float32 tables at full
precision), scores and softmax float32: nothing is rounded below the
table. Both products run on the matrix unit with the block viewed as
``(128 x heads, head_dim)``, row ``r x heads + h``: the scores are ``q
(heads, head_dim)`` against it, ``(heads, 128 x heads)``, of which head
``h`` keeps its own columns ``r x heads + h`` (a mask and a sum over the
heads' axis, which leaves every score in one row of lanes); the values
are those weights spread back to ``(heads, 128 x heads)``, zero off each
head's own columns, times the block. A head's maximum and sum are taken
over its lanes ``h, h + heads, ...`` of that row by rolls, so no array is
ever turned.

On non-TPU backends the kernel runs in pallas interpret mode
(``ops/pallas_util.py``), which its parity tests use; a decode step takes
it only where :func:`applies` says yes of both tables as allocated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.ops.decode_attention import _VMEM_CEILING
from bigdl_tpu.ops.kv_write import _TILE_ROWS, LANES
from bigdl_tpu.ops.pallas_util import NEG_INF, use_interpret

# rows a block holds: a slot fetches at most BLOCK - 1 rows past a count
BLOCK = 128
# block buffers in the ring: NBUF - 1 copies in flight while one computes
NBUF = 8


def applies(table, layout=None):
    """Whether a decode step reads ``table`` (one layer's window or
    summary table as allocated, ``(slots, rows, heads, head_dim)``)
    through the kernel: on a TPU, with no mesh layout, float32 or
    bfloat16, ``heads x head_dim`` whole tiles with the heads a power of
    two that divides 128, rows whole blocks, and the ring and two such
    tables' scores within the VMEM ceiling. A step takes the kernel only
    where both of its tables say yes; anything else keeps the masked
    read."""
    if layout is not None or jax.default_backend() != "tpu":
        return False
    if not _takes(table.shape, table.dtype):
        return False
    _, rows, heads, head_dim = table.shape
    return (head_dim % LANES == 0
            and heads % _TILE_ROWS[jnp.dtype(table.dtype)] == 0
            and _vmem_bytes((heads, head_dim), table.dtype,
                            2 * rows // BLOCK) <= _VMEM_CEILING)


def _takes(shape, dtype):
    """What the kernel's arithmetic needs of a table, on any backend:
    float32 or bfloat16, rows whole blocks, the heads a power of two that
    divides 128."""
    if len(shape) != 4 or jnp.dtype(dtype) not in _TILE_ROWS:
        return False
    _, rows, heads, _ = shape
    return (rows % BLOCK == 0 and heads & (heads - 1) == 0
            and LANES % heads == 0)


def fetched_rows(rows):
    """The rows the kernel fetches of a table of which a live slot reads
    ``rows`` (an int or a numpy array): whole blocks. What
    ``models/evabyte.py`` gives the slot table as ``RowTable.own_read``."""
    return -(-rows // BLOCK) * BLOCK


def _kernel(far_ref, near_ref, order_ref, q_ref, sk_hbm, sv_hbm, wk_hbm,
            wv_hbm, o_ref, ring, sem, st, s_ref, *, heads, scale,
            precision):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    b = pl.program_id(0)
    width = BLOCK * heads

    def blocks(slot):
        """``(summary blocks, all blocks)`` of ``slot``'s read."""
        far = (far_ref[slot] + BLOCK - 1) // BLOCK
        return far, far + (near_ref[slot] + BLOCK - 1) // BLOCK

    # st[0]: the slot whose item is copied next (-1: none is left);
    # st[1]: that item; st[2]: the items started; st[3]: the items of the
    # live slots before this one. Item j of a slot of n blocks is block j
    # of its K for j < n, block j - n of its V after.
    @pl.when(b == 0)
    def _():
        st[0] = order_ref[0]
        st[1] = 0
        st[2] = 0
        st[3] = 0

    def start(slot, item, buf):
        far, n = blocks(slot)
        value = item >= n
        j = jnp.where(value, item - n, item)
        row = pl.multiple_of(jnp.where(j < far, j, j - far) * BLOCK, BLOCK)
        for is_value, is_far, table in ((False, True, sk_hbm),
                                        (True, True, sv_hbm),
                                        (False, False, wk_hbm),
                                        (True, False, wv_hbm)):
            @pl.when((value == is_value) & ((j < far) == is_far))
            def _(table=table):
                pltpu.make_async_copy(table.at[slot, pl.ds(row, BLOCK)],
                                      ring.at[buf], sem.at[buf]).start()

    def issue(limit):
        """Start the items of the sequence up to ``limit - 1``: the ring
        holds NBUF, so at most that many are started at once."""
        for _ in range(NBUF):
            @pl.when((st[2] < limit) & (st[0] >= 0))
            def _():
                slot, item = st[0], st[1]
                start(slot, item, st[2] % NBUF)
                st[2] = st[2] + 1
                done = item + 1 == 2 * blocks(slot)[1]
                st[0] = jnp.where(done, order_ref[slot + 1], slot)
                st[1] = jnp.where(done, 0, item + 1)

    def arrived(g):
        """The buffer of the sequence's item ``g``, once its copy has
        landed (every item is one block of one size)."""
        buf = g % NBUF
        pltpu.make_async_copy(wk_hbm.at[0, pl.ds(0, BLOCK)], ring.at[buf],
                              sem.at[buf]).wait()
        return buf

    far, n = blocks(b)

    @pl.when(n == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(n > 0)
    def _():
        g0 = st[3]
        lane = lax.broadcasted_iota(jnp.int32, (1, width), 1)

        def own():
            """(heads, width): column ``r x heads + h`` is head ``h``'s."""
            return (lax.broadcasted_iota(jnp.int32, (heads, width), 1)
                    % heads
                    == lax.broadcasted_iota(jnp.int32, (heads, width), 0))

        def counted(j):
            """The rows of the slot's block ``j`` that its read counts,
            times the heads: the columns of the block's scores that do."""
            rows = jnp.where(j < far, far_ref[b] - j * BLOCK,
                             near_ref[b] - (j - far) * BLOCK)
            return jnp.minimum(rows, BLOCK) * heads

        def chunks(x):
            return [x[:, c * LANES:(c + 1) * LANES]
                    for c in range(width // LANES)]

        def per_head(x, op):
            """(1, width) -> (1, width): every lane ``op`` over the lanes
            of its head (lane ``i`` is head ``i mod heads``)."""
            y = functools.reduce(op, chunks(x))
            shift = heads
            while shift < LANES:
                y = op(y, pltpu.roll(y, shift, 1))
                shift *= 2
            return jnp.concatenate([y] * (width // LANES), axis=1)

        # ---- scores of every block, K's copies arriving
        def score(j, top):
            g = g0 + j
            issue(g + NBUF)
            # the ring is written by the copies and read once they land
            # jaxlint: disable-next-line=pallas-scratch-uninit
            k = ring[arrived(g)].reshape(width, -1)
            s = lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())),
                                precision=precision,
                                preferred_element_type=f32)
            s = jnp.sum(jnp.where(own(), s, 0.0), axis=0, keepdims=True)
            s = jnp.where(lane < counted(j), s * scale, NEG_INF)
            s_ref[j] = s
            return jnp.maximum(top, s)

        top = lax.fori_loop(0, n, score, jnp.full((1, width), NEG_INF, f32))
        top = per_head(top, jnp.maximum)
        total = per_head(lax.fori_loop(
            0, n, lambda j, t: t + jnp.exp(s_ref[j] - top),
            jnp.zeros((1, width), f32)), jnp.add)

        # ---- the weights against V's blocks
        def mix(j, acc):
            g = g0 + n + j
            issue(g + NBUF)
            # jaxlint: disable-next-line=pallas-scratch-uninit
            v = ring[arrived(g)].reshape(width, -1)
            line = lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(line < counted(j), v, jnp.zeros((), v.dtype))
            # the weights rounded to the table's dtype, as ``_attend``
            # rounds them, and spread in float32 (which holds them)
            p = (jnp.exp(s_ref[j] - top) / total).astype(v.dtype)
            p = jnp.where(own(), jnp.broadcast_to(p.astype(f32),
                                                  (heads, width)), 0.0)
            return acc + jnp.dot(p.astype(v.dtype), v, precision=precision,
                                 preferred_element_type=f32)

        o_ref[0] = lax.fori_loop(0, n, mix,
                                 jnp.zeros(o_ref.shape[1:], f32))
        st[3] = g0 + 2 * n


def eva_attention(q, win_k, win_v, sum_k, sum_v, pos, live, *, window,
                  chunk, interpret=None):
    """One query a slot, ``q`` (B, heads, head_dim) float32 (turned),
    against ``win_k``/``win_v`` (B, window, heads, head_dim) rows ``0 ..
    pos mod window`` and ``sum_k``/``sum_v`` (B, S, heads, head_dim) rows
    ``0 .. (pos div window) x (window / chunk) - 1``, under one softmax,
    scores by ``head_dim ** -0.5`` scaled: (B, heads, head_dim) float32.
    ``pos`` (B,) int32, ``live`` (B,) bool: a slot that is not live reads
    nothing and is zeros. The four tables share one dtype (float32 or
    bfloat16) and their rows are whole blocks of 128."""
    b, rows, heads, head_dim = win_k.shape
    if not (_takes(win_k.shape, win_k.dtype)
            and _takes(sum_k.shape, sum_k.dtype)
            and sum_k.shape[2:] == win_k.shape[2:]
            and len({t.dtype for t in (win_k, win_v, sum_k, sum_v)}) == 1):
        raise ValueError(
            f"eva_attention needs float32 or bfloat16 tables of one dtype, "
            f"rows in whole blocks of {BLOCK} and heads a power of two "
            f"that divides {LANES}, got "
            f"{win_k.dtype}{list(win_k.shape)} and "
            f"{sum_k.dtype}{list(sum_k.shape)}")
    if interpret is None:
        interpret = use_interpret()
    pos = jnp.asarray(pos, jnp.int32)
    live = jnp.asarray(live, bool)
    near = jnp.where(live, pos % window + 1, 0)
    far = jnp.where(live, (pos // window) * (window // chunk), 0)
    return _eva_attention(q, win_k, win_v, sum_k, sum_v, far, near,
                          interpret)


# jitted so that the step's calls of one shape are traced and lowered
# once (each lowering builds the kernel's Mosaic module)
@functools.partial(jax.jit, static_argnames="interpret")
def _eva_attention(q, win_k, win_v, sum_k, sum_v, far, near, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, _, heads, head_dim = win_k.shape
    dtype = win_k.dtype
    n_blocks = (win_k.shape[1] + sum_k.shape[1]) // BLOCK
    # the slot each live slot's items hand on to: order[0] the first live
    # slot, order[s + 1] the next after s; -1 where none is left
    slots = jnp.arange(b, dtype=jnp.int32)
    later = lax.cummin(jnp.where(near > 0, slots, b), reverse=True)
    order = jnp.concatenate([later, jnp.full((1,), b, jnp.int32)])
    order = jnp.where(order < b, order, -1)
    by_slot = pl.BlockSpec((1, heads, head_dim), lambda s, far, near, order: (s, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=_vmem_bytes((heads, head_dim), dtype, n_blocks))
    precision = (lax.Precision.HIGHEST if dtype == jnp.float32
                 else lax.Precision.DEFAULT)
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, scale=head_dim ** -0.5,
                          precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[by_slot, in_hbm, in_hbm, in_hbm, in_hbm],
            out_specs=by_slot,
            scratch_shapes=[
                pltpu.VMEM((NBUF, BLOCK, heads, head_dim), dtype),
                pltpu.SemaphoreType.DMA((NBUF,)),
                pltpu.SMEM((4,), jnp.int32),
                pltpu.VMEM((n_blocks, 1, BLOCK * heads), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, heads, head_dim), jnp.float32),
        compiler_params=params,
        interpret=interpret,
        name="eva_attention",
    )(far, near, order, q.astype(dtype), sum_k, sum_v, win_k, win_v)


def _vmem_bytes(row_shape, dtype, n_blocks):
    """What the kernel asks of the chip's fast memory for rows of
    ``row_shape`` (heads, head_dim) and ``n_blocks`` blocks of scores a
    slot: the ring, the scores a sublane tile a block, the products'
    ``(heads, 128 x heads)`` operands, and room for the compiler."""
    heads, head_dim = row_shape
    block = BLOCK * heads * head_dim * jnp.dtype(dtype).itemsize
    width = BLOCK * heads
    return (NBUF * block + n_blocks * 8 * width * 4 + 4 * heads * width * 4
            + 8 * 2 ** 20)
