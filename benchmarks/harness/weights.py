"""Weights from the seed, made by the benchmark and not by the program.

The program gives only the SHAPES of its parameter tree
(``jax.eval_shape`` of its initializer); every value is drawn here, on
the device, in one jitted call, in the dtype the configuration serves or
trains in. The plain reference is handed the same tree, so nothing that
the program computed reaches the reference.

Rules, by the leaf's last key and rank (``spec`` is the configuration's
``weights`` object):

- rank >= 2: normal with ``std`` (a number, or ``"he"`` for
  ``sqrt(2 / fan_in)`` with ``fan_in`` = product of all but the last axis)
- rank 1 named ``weight`` (a norm's gain): ``gain_mean + gain_std * normal``
  (``gain_mean`` 1 unless given)
- any other rank 1 (a bias, a norm's shift): ``bias_std * normal``
"""

from __future__ import annotations

import math


def seed_key(seed):
    """A PRNG key for any whole-number seed, also past 2**31."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make_params(shape_tree, seed, spec, dtype=None):
    """Fill ``shape_tree`` (a pytree of ``ShapeDtypeStruct``) from the
    seed in one jitted call; leaves keep their dtype unless ``dtype``
    is given."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(shape_tree)
    std = spec.get("std", 0.02)
    gain_std = float(spec.get("gain_std", 0.0))
    gain_mean = float(spec.get("gain_mean", 1.0))
    bias_std = float(spec.get("bias_std", 0.0))

    def build(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            last = path[-1]
            name = str(getattr(last, "key", getattr(last, "name", last)))
            dt = dtype or leaf.dtype
            z = jax.random.normal(k, leaf.shape, jnp.float32)
            if len(leaf.shape) >= 2:
                s = (math.sqrt(2.0 / math.prod(leaf.shape[:-1]))
                     if std == "he" else float(std))
                v = s * z
            elif name == "weight":
                v = gain_mean + gain_std * z
            else:
                v = bias_std * z
            out.append(v.astype(dt))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed))
