"""Top-k and nucleus cutoffs by threshold: sampling without a sort.

The XLA chain (``models/gpt.py sample_logits``) finds two numbers a row,
the ``top_k``-th largest logit and the nucleus' cutoff, by sorting the
whole vocabulary twice: ``lax.top_k`` lowers on the TPU to a sort of
``(slots, vocab)`` with its indices, and the nucleus takes a second full
``jnp.sort``. At the GPT-2 medium chat cell the two sorts of
``f32[48,50257]`` were 5.7 ms of a 12.2 ms decode step (PERF.md section
6, PR 31).

Both cuts are THRESHOLDS: keep token ``i`` iff ``measure(l >= l_i)``
stays under a level, where the measure is a count (top-k) or the softmax
mass of the top-k's survivors (top-p), and both are monotone step
functions of the threshold that change only at a logit's own value. So
the kernel holds a block of 8 rows in VMEM and finds each cut by
bisection, no sort and no gather:

- **on the ordered-integer view of the float32 bits** (``b`` if ``b >=
  0`` else ``b ^ 0x7fffffff`` orders int32 as the floats are ordered):
  the threshold is built bit by bit from the sign down, 32 passes, each
  a compare, a select and an add a vector register, and what comes out
  is EXACTLY a logit of the row: ``lax.top_k``'s ``k``-th value, and the
  smallest logit of the smallest sorted prefix whose mass reaches
  ``top_p``. For rows without ties at a boundary (real logits) the kept
  set is ``sample_logits``' own;
- **a block none of whose rows samples is skipped** from a scalar flag
  (the slot heap packs live slots low, so half of the chat cell's blocks
  hold no stream at all);
- **the passes are loops over chunks of 2048 lanes** that fold into one
  vector register a row block and reduce across lanes once a pass: the
  body is traced once, and no temporary is wider than a chunk, so the
  kernel's VMEM is the row block, its keys and its weights whatever the
  vocabulary.

The kernel returns the cutoff alone, one float a row. Scaling by the
temperature, masking under the cutoff and the categorical draw stay the
XLA operations ``sample_logits`` uses, on the same key: the same gumbel
noise over the same kept set, so the same token.

On non-TPU backends the kernel runs in pallas interpret mode
(``ops/pallas_util.py``), which its parity tests use; the serving
executables take it only where :func:`applies` says so.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bigdl_tpu.ops.pallas_util import use_interpret

LANES = 128
# rows of a block: one sublane tile of float32
ROWS = 8
# lanes a loop trip reads: 16 vector registers of 8 x 128
_CHUNK = 2048
_INT_MIN = -2 ** 31
# what the kernel may ask of a core's VMEM (128 MiB on a v5e)
_VMEM_CEILING = 96 * 2 ** 20


def _chunk(vocab):
    """Lanes a trip: ``_CHUNK``, or the whole of a small vocabulary."""
    return min(_CHUNK, -(-vocab // LANES) * LANES)


def _padded(vocab):
    return -(-vocab // _chunk(vocab)) * _chunk(vocab)


def _vmem_bytes(vocab):
    """The row block in two pipeline buffers, its keys and its weights,
    and room for the loops' registers to spill."""
    return (4 * ROWS * _padded(vocab) * 4) + 4 * 2 ** 20


def applies(logits, layout=None):
    """Whether the serving executables draw through the kernel, from
    what the code can see of ``logits`` (the table of next-token logits
    as allocated, a concrete array): it lies on a TPU, is not laid out
    over a mesh (``layout`` is the manager's ``ModelLayout``, else
    None), is float32 (the ordered-integer view is of float32 bits) and
    a block of its rows fits the chip's fast memory. Anything else keeps
    ``models.gpt.sample_logits``."""
    return (layout is None
            and next(iter(logits.devices())).platform == "tpu"
            and logits.ndim == 2 and logits.dtype == jnp.float32
            and _vmem_bytes(logits.shape[1]) <= _VMEM_CEILING)


def _ordered(bits):
    """float32 bits as int32 <-> int32 ordered as the floats are (its
    own inverse)."""
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _fold(x, op):
    """(rows, n * 128) -> (rows, 128): the lane tiles combined pairwise
    (whole registers; no lane crosses)."""
    parts = [x[:, j:j + LANES] for j in range(0, x.shape[1], LANES)]
    while len(parts) > 1:
        parts = [op(a, b) for a, b in zip(parts[::2], parts[1::2])] \
            + parts[len(parts) // 2 * 2:]
    return parts[0]


def _cutoff_kernel(flag_ref, l_ref, out_ref, key_ref, w_ref, *, top_k,
                   top_p, chunk):
    from jax.experimental import pallas as pl

    rows, width = l_ref.shape
    trips = width // chunk

    def lanes_of(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    def over_chunks(body, init):
        """``body(lanes, acc) -> acc`` over the row block, ``acc`` one
        register of partial results; the caller reduces its lanes."""
        return jax.lax.fori_loop(
            0, trips, lambda c, acc: body(lanes_of(c), acc), init)

    def threshold(measure, level):
        """The largest key ``t`` with ``measure(t) >= level``, a row,
        built from the sign bit down (``_INT_MIN`` where no key reaches
        the level: nothing is cut)."""
        def bit(i, t):
            cand = t + jnp.left_shift(jnp.int32(1), 31 - i)
            return jnp.where(measure(cand) >= level, cand, t)

        return jax.lax.fori_loop(
            0, 32, bit, jnp.full((rows, 1), _INT_MIN, jnp.int32))

    def run():
        def keys(lanes, best):
            l = l_ref[:, lanes]
            key_ref[:, lanes] = _ordered(
                jax.lax.bitcast_convert_type(l, jnp.int32))
            return jnp.maximum(best, _fold(l, jnp.maximum))

        top = jnp.max(over_chunks(
            keys, jnp.full((rows, LANES), -jnp.inf, jnp.float32)),
            axis=-1, keepdims=True)
        cut = jnp.full((rows, 1), _INT_MIN, jnp.int32)
        if top_k is not None:
            def count(t):
                def body(lanes, acc):
                    hit = key_ref[:, lanes] >= t
                    return acc + _fold(hit.astype(jnp.int32), jnp.add)
                return jnp.sum(over_chunks(
                    body, jnp.zeros((rows, LANES), jnp.int32)),
                    axis=-1, keepdims=True)

            cut = threshold(count, top_k)
        if top_p is not None:
            def weights(lanes, acc):
                # softmax's numerator over the top-k's survivors
                w = jnp.where(key_ref[:, lanes] >= cut,
                              jnp.exp(l_ref[:, lanes] - top), 0.0)
                w_ref[:, lanes] = w
                return acc + _fold(w, jnp.add)

            def mass(t):
                def body(lanes, acc):
                    hit = key_ref[:, lanes] >= t
                    return acc + _fold(
                        jnp.where(hit, w_ref[:, lanes], 0.0), jnp.add)
                return jnp.sum(over_chunks(
                    body, jnp.zeros((rows, LANES), jnp.float32)),
                    axis=-1, keepdims=True)

            total = jnp.sum(over_chunks(
                weights, jnp.zeros((rows, LANES), jnp.float32)),
                axis=-1, keepdims=True)
            # a survivor's own mass counts: the smallest prefix that
            # REACHES top_p, so never under the top-k's cut
            cut = jnp.maximum(cut, threshold(mass, top_p * total))
        out_ref[:] = jax.lax.bitcast_convert_type(_ordered(cut),
                                                  jnp.float32)

    sampled = flag_ref[pl.program_id(0)] != 0
    pl.when(sampled)(run)

    @pl.when(jnp.logical_not(sampled))
    def _():
        out_ref[:] = jnp.full(out_ref.shape, -jnp.inf, out_ref.dtype)


def cutoffs(scaled, top_k=None, top_p=None, rows=None, interpret=None):
    """The value under which ``sample_logits`` masks each row of
    ``scaled`` (S, vocab) float32, logits already divided by their
    temperature: the larger of its ``top_k``-th largest value and of the
    last value of the smallest sorted prefix of those whose softmax mass
    reaches ``top_p``; (S, 1) float32, ``-inf`` where nothing is cut.
    ``rows`` (S,) bool says which rows will be drawn from (default all);
    a block of 8 rows with none is skipped and reads ``-inf``."""
    if scaled.dtype != jnp.float32 or scaled.ndim != 2:
        raise ValueError(f"cutoffs needs (rows, vocab) float32 logits, "
                         f"got {scaled.dtype}{list(scaled.shape)}")
    s, v = scaled.shape
    if top_k is not None and not 0 < top_k < v:
        top_k = None
    if top_p is not None and top_p >= 1.0:
        top_p = None
    if top_k is None and top_p is None:
        return jnp.full((s, 1), -jnp.inf, jnp.float32)
    if interpret is None:
        interpret = use_interpret()
    if rows is None:
        rows = jnp.ones((s,), bool)
    return _cutoffs(scaled, jnp.asarray(rows, bool), top_k, top_p,
                    interpret)


# jitted so that an executable's calls of one shape are traced and
# lowered once (each lowering builds the kernel's Mosaic module)
@functools.partial(jax.jit, static_argnames=("top_k", "top_p", "interpret"))
def _cutoffs(scaled, rows, top_k, top_p, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, v = scaled.shape
    blocks = -(-s // ROWS)
    width = _padded(v)
    # whole blocks and whole chunks: a padding lane holds -inf, which no
    # count and no mass reaches; a padding row rides in its block unread
    padded = jnp.pad(scaled, ((0, blocks * ROWS - s), (0, width - v)),
                     constant_values=-jnp.inf)
    flags = jnp.pad(rows, (0, blocks * ROWS - s)).reshape(blocks, ROWS)
    out = pl.pallas_call(
        functools.partial(_cutoff_kernel, top_k=top_k, top_p=top_p,
                          chunk=_chunk(v)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(blocks,),
            in_specs=[pl.BlockSpec((ROWS, width), lambda i, flags: (i, 0))],
            out_specs=pl.BlockSpec((ROWS, 1), lambda i, flags: (i, 0)),
            scratch_shapes=[pltpu.VMEM((ROWS, width), jnp.int32),
                            pltpu.VMEM((ROWS, width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((blocks * ROWS, 1), jnp.float32),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_bytes(v)),
        interpret=interpret,
        name="sample_cutoffs",
    )(flags.any(axis=1).astype(jnp.int32), padded)
    return out[:s]


def threshold_sample_logits(logits, key, temperature=1.0, top_k=None,
                            top_p=None, rows=None, interpret=None):
    """Drop-in for ``models.gpt.sample_logits`` on float32 logits: the
    same scaling, the same kept set (found by :func:`cutoffs`, no sort),
    the same categorical draw on the same key, so for rows without ties
    at a cut the same token. ``temperature`` may be a traced scalar or
    an (S, 1) vector a row; ``top_k``/``top_p`` stay compile-time
    config. ``rows`` (S,) bool names the rows whose draw will be used
    (default all): the others' tokens are drawn from the whole
    vocabulary where their whole block was skipped."""
    scaled = logits / jnp.maximum(temperature, 1e-6)
    cut = cutoffs(scaled, top_k, top_p, rows, interpret)
    return jax.random.categorical(
        key, jnp.where(scaled < cut, -jnp.inf, scaled), axis=-1)
