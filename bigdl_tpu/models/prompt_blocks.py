"""A prompt pass walked a block of positions at a time, for the models
whose every layer carries its state from one block to the next
(``models/dots3.py``, ``models/nemotron_h.py``).

A block goes through every layer before the next one starts; only the
blocks up to the longest prompt are walked, so a prompt costs its own
length in blocks and not its bucket. The tables that hold a row a
position are cut to the bucket's rows for the walk and written back
whole after it.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def block_count(block, length):
    """``(size, n)``: ``length`` positions are walked as ``n`` blocks of
    ``size`` (``block``, or ``length`` itself when it is shorter)."""
    size = min(block, length)
    return size, -(-length // size)


def check_positions(max_position, block):
    """Raise ``ValueError`` unless a table of ``max_position`` rows is one
    block at most or whole blocks: a walk rounds a bucket up to whole
    blocks and writes that many rows."""
    if max_position > block and max_position % block:
        raise ValueError(f"max_position {max_position} is not whole "
                         f"prefill blocks of {block}")


def walk(block, width, cache, ids, prompt_len, pass_block, carries=None,
         position_axes=()):
    """Walk ``ids`` (W, bucket) right-padded prompts of ``prompt_len``
    (W,) through ``pass_block(cache, carries, ids, first, prompt_len) ->
    (h, cache, carries)``, which takes one block of ids at positions
    ``first ..`` through every layer. ``carries()`` makes what the layers
    carry from block to block beside the cache (None: nothing);
    ``position_axes`` has, a layer, None or the axis along which that
    layer's cache leaves hold a row a position. Returns the hidden row
    (W, ``width``) float32 at each prompt's last real position, and the
    cache."""
    b, bucket = ids.shape
    size, n = block_count(block, bucket)
    rows = n * size
    ids = jnp.pad(ids, ((0, 0), (0, rows - bucket)))
    prompt_len = jnp.broadcast_to(jnp.asarray(prompt_len, jnp.int32), (b,))
    last = prompt_len - 1
    axes = list(position_axes) + [None] * (len(cache) - len(position_axes))
    # a prompt of this bucket reads no row past it: the walk works on the
    # leading rows of the position tables
    whole = cache
    cache = [c if axis is None else
             {k: v[(slice(None),) * axis + (slice(None, rows),)]
              for k, v in c.items()}
             for c, axis in zip(whole, axes)]

    def one(j, carry):
        cache, carried, h_last = carry
        first = j * size
        ids_j = lax.dynamic_slice_in_dim(ids, first, size, axis=1)
        h, cache, carried = pass_block(cache, carried, ids_j, first,
                                       prompt_len)
        row = jnp.take_along_axis(h, (last % size)[:, None, None],
                                  axis=1)[:, 0]
        h_last = jnp.where((last // size == j)[:, None], row, h_last)
        return cache, carried, h_last

    walked = (jnp.max(prompt_len) + size - 1) // size
    cache, _, h_last = lax.fori_loop(
        0, walked, one,
        (cache, None if carries is None else carries(),
         jnp.zeros((b, width), jnp.float32)))
    cache = [c if axis is None else
             {k: lax.dynamic_update_slice(w[k], v, (0,) * v.ndim)
              for k, v in c.items()}
             for c, w, axis in zip(cache, whole, axes)]
    return h_last, cache
