"""In-place update of a decode step's state-space state, live slots only.

A Mamba-2 layer (``nn/ssm.py``) keeps, a slot, one float32 state of
``(heads, head_dim, state_size)`` (128 x 64 x 128 at the published
widths: 4 MB a slot a layer) and rewrites ALL of it at every token:

    S_h <- exp(dt_h A_h) S_h + (dt_h x_h) B_hᵀ        y_h = S_h C_h

The arithmetic is two multiply-adds an entry; what costs is moving S in
and out, 8 MB a live slot a layer. The plain spelling
(:func:`plain_update`, ``jnp`` over the whole table) reads and writes
every slot's state, live or not, and XLA fuses the decay, the rank-1
update and the contraction with ``C`` into passes of its own over the
table; the state of a free slot is junk nobody reads, so its bytes are
wasted.

**The kernel** walks a grid of (live slot, block of heads): the numbers
of the live slots, ascending, and how many there are come in as scalars
(``jnp.nonzero`` of the step's mask), and every block spec maps grid row
``i`` to the ``i``-th live slot. A block is :data:`HEAD_BLOCK` heads of
S (1 MB at the published widths) with their ``decay``, ``dt x``, ``B`` and
``C`` rows: the kernel reads it once, applies the decay and the rank-1
update, writes it once to where it came from (``input_output_aliases``:
the donated table is updated where it lies) and hands back ``y``. The
rows past the live count map to the last live block again, so the
pipeline neither fetches nor writes anything for them and their body is
skipped: a free slot's blocks are never moved. (With no slot live, one
block of slot 0 is read and written back as it was.) ``y`` of a free slot
is not written; the caller masks it.

On non-TPU backends the kernel runs in Pallas interpret mode
(``ops/pallas_util.py``), which its parity tests use; the decode step
takes it only where :func:`applies` says so.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bigdl_tpu.ops.pallas_util import compiler_params, fit_block, \
    use_interpret

# heads of one block: 32 x 64 x 128 float32 is 1 MB, moved in and out in
# one DMA each while the block before it is computed (two buffers a side:
# 4 MB of the kernel's fast memory)
HEAD_BLOCK = 32
LANES = 128


def applies(state, layout=None):
    """Whether the decode step updates ``state`` (one layer's S as
    allocated, ``(slots, heads, head_dim, state_size)``) through the
    kernel: on a TPU, with no mesh layout, float32, the state size in
    whole lanes and the head size in whole sublane tiles. Anything else
    keeps :func:`plain_update`."""
    if layout is not None or jax.default_backend() != "tpu":
        return False
    return (state.ndim == 4 and state.dtype == jnp.float32
            and state.shape[3] % LANES == 0 and state.shape[2] % 8 == 0)


def plain_update(state, decay, dtx, b, c, live):
    """The update in plain ``jnp``: ``state`` (S, H, P, N) float32,
    ``decay`` (S, H) the factor ``exp(dt A)``, ``dtx`` (S, H, P) the input
    ``dt x``, ``b`` and ``c`` (S, H, N) each head's rows, ``live`` (S,)
    bool. Returns ``(state, y (S, H, P))``: a live slot's state decayed and
    updated, every other slot's as it came; ``y`` read off the new state
    (junk for a free slot). What the decode step does wherever the kernel
    does not apply, and what the kernel is held to bit for bit."""
    new = decay[:, :, None, None] * state + dtx[..., None] * b[:, :, None, :]
    new = jnp.where(live[:, None, None, None], new, state)
    return new, jnp.sum(new * c[:, :, None, :], axis=-1)


def _kernel(order_ref, count_ref, s_ref, decay_ref, dtx_ref, b_ref, c_ref,
            s_out, y_out):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) < count_ref[0])
    def _():
        s = decay_ref[...][:, :, None] * s_ref[...] \
            + dtx_ref[...][:, :, None] * b_ref[...][:, None, :]
        s_out[...] = s
        y_out[...] = jnp.sum(s * c_ref[...][:, None, :], axis=-1)


def ssm_update(state, decay, dtx, b, c, live, interpret=None):
    """:func:`plain_update` through the kernel: the same arguments and
    results, a live slot's state and ``y`` bit for bit the same, a free
    slot's state untouched and never moved, its ``y`` not written."""
    if interpret is None:
        interpret = use_interpret()
    return _ssm_update(state, decay, dtx, b, c, jnp.asarray(live, bool),
                       interpret)


# jitted so that the step's calls of one shape (one a Mamba layer) are
# traced and lowered once
@functools.partial(jax.jit, static_argnames="interpret")
def _ssm_update(state, decay, dtx, b, c, live, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, p, n = state.shape
    hb = fit_block(heads, HEAD_BLOCK, 8)
    last = heads // hb - 1
    order = jnp.nonzero(live, size=slots, fill_value=0)[0].astype(jnp.int32)
    count = jnp.sum(live, dtype=jnp.int32)[None]

    def at(i, j, order, count):
        """Grid row ``i``'s live slot and head block; past the live count
        the last live block again, which the pipeline does not move."""
        on = i < count[0]
        slot = order[jnp.where(on, i, jnp.maximum(count[0] - 1, 0))]
        return slot, jnp.where(on, j, last)

    def spec(*block):
        zeros = (0,) * (len(block) - 1)
        return pl.BlockSpec((None, hb) + block[1:],
                            lambda i, j, o, k: at(i, j, o, k) + zeros)

    s_spec, row_p, row_n = spec(hb, p, n), spec(hb, p), spec(hb, n)
    new, y = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots, heads // hb),
            in_specs=[s_spec, spec(hb, 1), row_p, row_n, row_n],
            out_specs=[s_spec, row_p]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((slots, heads, p), jnp.float32)],
        # operand indices count the two prefetched scalars: 2 is the state
        input_output_aliases={2: 0},
        compiler_params=compiler_params(interpret, ("arbitrary",
                                                    "arbitrary")),
        interpret=interpret,
        name="ssm_step",
    )(order, count, state, decay.astype(jnp.float32)[..., None],
      dtx.astype(jnp.float32), b.astype(jnp.float32), c.astype(jnp.float32))
    return new, y
