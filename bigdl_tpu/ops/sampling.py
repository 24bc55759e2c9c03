"""Fused sampling kernel: temperature / top-k / top-p / categorical draw
in ONE pass over the (slots, vocab) logits.

The XLA chain (``models/gpt.py sample_logits``) lowers to a multi-op
pipeline — divide, ``lax.top_k``, a full descending ``jnp.sort``,
softmax, cumsum, two gathers, then the categorical's own gumbel-argmax —
each materializing a (slots, vocab) intermediate in HBM. This kernel
keeps one vocab row resident in VMEM and applies every stage in place.

Two tricks make the fusion exact AND Mosaic-lowerable (no sort/top_k
inside a TPU kernel):

- **gumbel outside, argmax inside**: ``jax.random.categorical(key, l)``
  IS ``argmax(l + gumbel(key, l.shape, l.dtype))``, so the wrapper draws
  the gumbel noise with the caller's key outside the kernel and the
  kernel finishes with a plain argmax — the kept logits and the noise
  match the XLA path bit for bit;
- **threshold bisection instead of sort**: both truncations reduce to a
  per-row cutoff VALUE — keep token i iff ``measure(logits > l_i) <
  level`` where the measure is a count (top-k: level k) or softmax mass
  (top-p: level p), both monotone step functions of the threshold. ~60
  halvings bracket the step boundary below float ulp and the cutoff
  snaps to the smallest surviving logit, reproducing ``lax.top_k``'s
  k-th value and the sorted-cumsum nucleus cutoff exactly for tie-free
  rows (real logits; ties at the boundary are measure-zero).

Interpret mode on CPU (``ops/pallas_util.py``); dispatch is gated by
``BIGDL_TPU_FUSED_SAMPLING`` (default off — the XLA chain, bit-identical
to before).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bigdl_tpu.ops.pallas_util import (NEG_INF, compiler_params, fit_block,
                                       use_interpret)

_BISECT_ITERS = 60


def _cutoff(l, weights, level):
    """Per-row threshold c such that keeping ``l >= c`` keeps exactly
    the tokens with ``sum(weights[l > l_i]) < level``. ``l``: (bs, V)
    f32; ``weights``: (bs, V) (ones for top-k counts, probs for top-p
    mass); ``level``: scalar or (bs, 1). Bisection invariant:
    measure(> lo) >= level, measure(> hi) < level.

    The bracket starts at the UNMASKED extremes — a prior truncation's
    NEG_INF entries carry zero weight, and including them would stretch
    the interval to ~1e30, leaving the 60 halvings far above float
    ulp."""
    real = l > 0.5 * NEG_INF
    lo = jnp.min(jnp.where(real, l, -NEG_INF), axis=-1,
                 keepdims=True) - 1.0
    hi = jnp.max(l, axis=-1, keepdims=True)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        mass = jnp.sum(jnp.where(l > mid, weights, 0.0), axis=-1,
                       keepdims=True)
        pred = mass >= level
        return jnp.where(pred, mid, lo), jnp.where(pred, hi, mid)

    lo, _ = jax.lax.fori_loop(0, _BISECT_ITERS, body, (lo, hi))
    # snap to the smallest logit strictly above lo — the boundary value
    # itself (guaranteed to exist: measure(> lo) >= level > 0)
    return jnp.min(jnp.where(l > lo, l, -NEG_INF), axis=-1, keepdims=True)


def _sample_kernel(l_ref, g_ref, t_ref, o_ref, *, top_k, top_p, vocab):
    l = l_ref[:].astype(jnp.float32)                      # (bs, V)
    l = l / jnp.maximum(t_ref[:].astype(jnp.float32), 1e-6)
    if top_k is not None and 0 < top_k < vocab:
        ones = jnp.ones(l.shape, jnp.float32)
        kth = _cutoff(l, ones, jnp.float32(top_k))
        l = jnp.where(l < kth, NEG_INF, l)
    if top_p is not None and top_p < 1.0:
        m = jnp.max(l, axis=-1, keepdims=True)
        e = jnp.exp(l - m)                       # masked rows: exp->0
        probs = e / jnp.sum(e, axis=-1, keepdims=True)
        cut = _cutoff(l, probs, jnp.float32(top_p))
        l = jnp.where(l < cut, NEG_INF, l)
    vals = l + g_ref[:].astype(jnp.float32)
    m = jnp.max(vals, axis=-1, keepdims=True)
    iota = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    # first index achieving the max == jnp.argmax's tie rule
    idx = jnp.min(jnp.where(vals >= m, iota, vocab), axis=-1)
    o_ref[:] = idx[:, None].astype(jnp.int32)


def fused_sample_logits(logits, key, temperature=1.0, top_k=None,
                        top_p=None, block_s=8, interpret=None):
    """Drop-in for ``models.gpt.sample_logits``: one fused kernel pass
    over (S, vocab) ``logits`` instead of the divide / top_k / sort /
    cumsum / categorical chain. ``temperature`` may be a traced scalar
    or (S, 1) per-row vector; ``top_k``/``top_p`` stay compile-time
    config. Returns (S,) int32 tokens drawn from the identical
    truncated distribution (same key, same gumbel noise, same kept
    set — see module docstring for the exactness argument)."""
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = use_interpret()
    s, v = logits.shape
    gumbel = jax.random.gumbel(key, logits.shape, logits.dtype)
    temps = jnp.broadcast_to(
        jnp.asarray(temperature, logits.dtype).reshape(-1, 1)
        if jnp.ndim(temperature) else
        jnp.full((1, 1), temperature, logits.dtype), (s, 1))
    bs = fit_block(s, block_s, align=8)
    kernel = functools.partial(_sample_kernel, top_k=top_k, top_p=top_p,
                               vocab=v)
    out = pl.pallas_call(
        kernel,
        grid=(s // bs,),
        in_specs=[
            pl.BlockSpec((bs, v), lambda i: (i, 0)),
            pl.BlockSpec((bs, v), lambda i: (i, 0)),
            pl.BlockSpec((bs, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bs, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, 1), jnp.int32),
        compiler_params=compiler_params(interpret, ("arbitrary",)),
        interpret=interpret,
    )(logits, gumbel, temps)
    return out[:, 0]
