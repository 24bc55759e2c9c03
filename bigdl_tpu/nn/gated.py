"""Gated layers of the decoder families after GPT-2: the SwiGLU MLP and
the gated short convolution (LFM2's ``conv`` operator).

No reference analog. Matrices are stored ``(in, out)`` and carry no bias.
Every product takes its operands in the WEIGHT's dtype and sums in
float32 (:func:`mm`): served in bfloat16 that is one MXU pass with the
activation rounded once, where it enters the product; with float32
weights (the CPU tests) nothing is rounded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import Module


def mm(x, w):
    """``x @ w`` with both operands in ``w``'s dtype and a float32 sum."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _normal(rng, shape, fan_in):
    return jax.random.normal(rng, shape) * (1.0 / fan_in) ** 0.5


class GatedMLP(Module):
    """``w2(silu(w1 x) * w3 x)``: the SwiGLU feed-forward."""

    def __init__(self, hidden_size, ffn_size):
        super().__init__()
        self.hidden_size = hidden_size
        self.ffn_size = ffn_size

    def make_params(self, rng, input_spec):
        d, f = self.hidden_size, self.ffn_size
        k1, k2, k3 = jax.random.split(rng, 3)
        return {"w1": _normal(k1, (d, f), d), "w3": _normal(k2, (d, f), d),
                "w2": _normal(k3, (f, d), f)}

    def call(self, params, x):
        h = jax.nn.silu(mm(x, params["w1"])) * mm(x, params["w3"])
        return mm(h, params["w2"])


class GatedShortConv(Module):
    """Gated depthwise causal convolution over ``taps`` positions:

        [B, C, X] = x @ w_in            (hidden -> 3 x hidden)
        z = B * X
        c_t = sum_j w[j] * z_{t - taps + 1 + j}     (zeros before t = 0)
        y = (C * c) @ w_out

    ``w`` is ``(taps, hidden)``: tap ``taps - 1`` multiplies the current
    position. Three forms of the one sum: :meth:`call` over a whole
    sequence, :meth:`prefill` which also hands back each row's state at
    its own length, and :meth:`decode_step` for one position a row. The
    state is the last ``taps`` positions of ``z``, ``(batch, taps,
    hidden)``, newest last, in the dtype the caller keeps it in; ``z`` is
    rounded to that dtype before the sum on every path, so a prefill
    followed by steps adds the same numbers as one pass over the whole
    sequence does.
    """

    def __init__(self, hidden_size, taps=3):
        super().__init__()
        self.hidden_size = hidden_size
        self.taps = taps

    def make_params(self, rng, input_spec):
        d = self.hidden_size
        k1, k2, k3 = jax.random.split(rng, 3)
        return {"w_in": _normal(k1, (d, 3 * d), d),
                "w": _normal(k2, (self.taps, d), self.taps),
                "w_out": _normal(k3, (d, d), d)}

    def _gates(self, params, x, state_dtype):
        b, c, xx = jnp.split(mm(x, params["w_in"]), 3, axis=-1)
        z = (b * xx).astype(state_dtype).astype(jnp.float32)
        return c, z

    def _sequence(self, params, x, state_dtype):
        c, z = self._gates(params, x, state_dtype)
        t = z.shape[1]
        w = params["w"].astype(jnp.float32)
        padded = jnp.pad(z, ((0, 0), (self.taps - 1, 0), (0, 0)))
        conv = sum(w[j] * padded[:, j:j + t] for j in range(self.taps))
        return mm(c * conv, params["w_out"]), z

    def call(self, params, x):
        return self._sequence(params, x, x.dtype)[0]

    def init_state(self, batch, dtype=jnp.float32):
        return jnp.zeros((batch, self.taps, self.hidden_size), dtype)

    def prefill(self, params, x, length, state_dtype):
        """``x`` (B, T, hidden), right-padded; ``length`` (B,) or a scalar,
        the real positions of each row. Returns ``(y, state)`` with
        ``state[b]`` the ``z`` of positions ``length[b] - taps ..
        length[b] - 1`` (zeros where the row is shorter than the taps):
        what a step at position ``length[b]`` continues from, whatever
        the padding holds."""
        y, z = self._sequence(params, x, state_dtype)
        length = jnp.broadcast_to(jnp.asarray(length, jnp.int32),
                                  (x.shape[0],))
        idx = length[:, None] - self.taps + jnp.arange(self.taps)[None, :]
        rows = jnp.take_along_axis(z, jnp.maximum(idx, 0)[:, :, None],
                                   axis=1)
        state = jnp.where((idx >= 0)[:, :, None], rows, 0.0)
        return y, state.astype(state_dtype)

    def decode_step(self, params, x, state):
        """One position a row: ``x`` (B, hidden), ``state`` (B, taps,
        hidden). Returns ``(y, state)`` with the new ``z`` rolled in."""
        c, z = self._gates(params, x, state.dtype)
        state = jnp.concatenate([state[:, 1:], z[:, None].astype(state.dtype)],
                                axis=1)
        conv = jnp.sum(params["w"].astype(jnp.float32)[None]
                       * state.astype(jnp.float32), axis=1)
        return mm(c * conv, params["w_out"]), state
