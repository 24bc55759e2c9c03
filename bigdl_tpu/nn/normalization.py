"""Normalization layers.

Reference: ``nn/BatchNormalization.scala:51`` (+ ``SpatialBatchNormalization``),
``nn/SpatialCrossMapLRN.scala``, ``nn/Normalize.scala``. BN running stats are
the canonical *state* pytree here (the reference mutates runningMean/
runningVar in place); under jit the updated stats are returned functionally.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn.module import Module


class BatchNormalization(Module):
    """1-D batch norm over (batch, feature) (reference
    ``nn/BatchNormalization.scala:51``)."""

    _feature_axis = -1

    def __init__(self, n_output, eps=1e-5, momentum=0.1, affine=True,
                 init_weight=None, init_bias=None):
        super().__init__()
        self.n_output = n_output
        self.eps = eps
        self.momentum = momentum
        self.affine = affine

    def make_params(self, rng, input_spec):
        if not self.affine:
            return {}
        return {"weight": jnp.ones((self.n_output,)),
                "bias": jnp.zeros((self.n_output,))}

    def make_state(self, input_spec):
        return {"running_mean": jnp.zeros((self.n_output,)),
                "running_var": jnp.ones((self.n_output,))}

    def _reduce_axes(self, x):
        ax = self._feature_axis % x.ndim
        return tuple(i for i in range(x.ndim) if i != ax), ax

    def apply(self, params, state, x, *, training=False, rng=None):
        axes, feat_ax = self._reduce_axes(x)
        bshape = [1] * x.ndim
        bshape[feat_ax] = self.n_output
        if training:
            # one-pass stats: E[x^2]-E[x]^2 lets XLA compute both reductions
            # in a single fused sweep over x (jnp.var would re-read x after
            # the mean), and f32 accumulation keeps bf16 inputs exact enough
            x32 = x.astype(jnp.float32)
            mean = jnp.mean(x32, axis=axes)
            var = jnp.maximum(jnp.mean(jnp.square(x32), axis=axes)
                              - jnp.square(mean), 0.0)
            mean = mean.astype(x.dtype)
            var = var.astype(x.dtype)
            m = self.momentum
            n = x.size // self.n_output
            unbiased = var * n / max(n - 1, 1)
            new_state = {
                "running_mean": (1 - m) * state["running_mean"] + m * mean,
                "running_var": (1 - m) * state["running_var"] + m * unbiased,
            }
        else:
            mean, var = state["running_mean"], state["running_var"]
            new_state = state
        inv = lax.rsqrt(var + self.eps)
        y = (x - mean.reshape(bshape)) * inv.reshape(bshape)
        if self.affine:
            y = y * params["weight"].reshape(bshape) + params["bias"].reshape(bshape)
        return y, new_state


class SpatialBatchNormalization(BatchNormalization):
    """BN over NCHW feature axis 1 (reference
    ``nn/SpatialBatchNormalization.scala``)."""

    def __init__(self, n_output, eps=1e-5, momentum=0.1, affine=True,
                 format="NCHW", **kw):
        super().__init__(n_output, eps, momentum, affine, **kw)
        self._feature_axis = 1 if format == "NCHW" else -1


class VolumetricBatchNormalization(BatchNormalization):
    _feature_axis = 1


class LayerNormalization(Module):
    """Layer norm (transformer-era; present in later reference revs)."""

    def __init__(self, hidden_size, eps=1e-5):
        super().__init__()
        self.hidden_size = hidden_size
        self.eps = eps

    def make_params(self, rng, input_spec):
        return {"weight": jnp.ones((self.hidden_size,)),
                "bias": jnp.zeros((self.hidden_size,))}

    def call(self, params, x):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) * lax.rsqrt(var + self.eps)
        return y * params["weight"] + params["bias"]


class RMSNorm(Module):
    """Root-mean-square norm, no mean and no shift:
    ``x * rsqrt(mean(x^2) + eps) * weight`` over the last axis. The
    statistics are taken in float32 whatever ``x`` is stored in; the
    result comes back in ``x``'s dtype. With ``unit_offset`` the gain is
    ``1 + weight`` and ``weight`` starts at zero (the form some decoder
    families store: EvaByte's ``norm_add_unit_offset``)."""

    def __init__(self, hidden_size, eps=1e-5, unit_offset=False):
        super().__init__()
        self.hidden_size = hidden_size
        self.eps = eps
        self.unit_offset = unit_offset

    def make_params(self, rng, input_spec):
        fill = jnp.zeros if self.unit_offset else jnp.ones
        return {"weight": fill((self.hidden_size,))}

    def call(self, params, x):
        xf = x.astype(jnp.float32)
        y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                           + self.eps)
        gain = params["weight"].astype(jnp.float32)
        if self.unit_offset:
            gain = 1.0 + gain
        return (y * gain).astype(x.dtype)


class SpatialCrossMapLRN(Module):
    """Local response normalization across channels
    (reference ``nn/SpatialCrossMapLRN.scala``)."""

    def __init__(self, size=5, alpha=1.0, beta=0.75, k=1.0, format="NCHW"):
        super().__init__()
        self.size, self.alpha, self.beta, self.k = size, alpha, beta, k
        self.format = format

    def call(self, params, x):
        ch_ax = 1 if self.format == "NCHW" else 3
        sq = jnp.square(x)
        half = (self.size - 1) // 2
        dims, strides = [1] * x.ndim, [1] * x.ndim
        dims[ch_ax] = self.size
        padding = [(0, 0)] * x.ndim
        padding[ch_ax] = (half, self.size - 1 - half)
        window_sum = lax.reduce_window(sq, 0.0, lax.add, tuple(dims),
                                       tuple(strides), tuple(padding))
        return x * jnp.power(self.k + self.alpha / self.size * window_sum,
                             -self.beta)


class SpatialWithinChannelLRN(Module):
    """LRN within channel over a spatial window
    (reference ``nn/SpatialWithinChannelLRN.scala``)."""

    def __init__(self, size=5, alpha=1.0, beta=0.75):
        super().__init__()
        self.size, self.alpha, self.beta = size, alpha, beta

    def call(self, params, x):
        half = (self.size - 1) // 2
        dims = (1, 1, self.size, self.size)
        padding = ((0, 0), (0, 0),
                   (half, self.size - 1 - half), (half, self.size - 1 - half))
        window_sum = lax.reduce_window(jnp.square(x), 0.0, lax.add, dims,
                                       (1, 1, 1, 1), padding)
        mean_sq = window_sum / (self.size * self.size)
        return x * jnp.power(1.0 + self.alpha * mean_sq, -self.beta)


class Normalize(Module):
    """Lp-normalize along the last axis (reference ``nn/Normalize.scala``)."""

    def __init__(self, p=2.0, eps=1e-10):
        super().__init__()
        self.p, self.eps = p, eps

    def call(self, params, x):
        if self.p == float("inf"):
            norm = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        else:
            norm = jnp.power(jnp.sum(jnp.power(jnp.abs(x), self.p), axis=-1,
                                     keepdims=True), 1.0 / self.p)
        return x / (norm + self.eps)


class NormalizeScale(Module):
    """Normalize + learnable per-channel scale, used by SSD
    (reference ``nn/NormalizeScale.scala``)."""

    def __init__(self, p=2.0, eps=1e-10, scale=1.0, size=None):
        super().__init__()
        self.p, self.eps, self.scale_init = p, eps, scale
        self.size = size

    def make_params(self, rng, input_spec):
        size = self.size or (1,)
        return {"scale": jnp.full(size, self.scale_init)}

    def call(self, params, x):
        norm = jnp.power(jnp.sum(jnp.power(jnp.abs(x), self.p), axis=1,
                                 keepdims=True), 1.0 / self.p)
        return x / (norm + self.eps) * params["scale"]
