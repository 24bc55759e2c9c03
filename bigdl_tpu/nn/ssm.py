"""The Mamba-2 mixer: a selective state-space layer with a fixed-size
state a stream (Dao and Gu, Transformers are SSMs, arXiv:2405.21060), in
the form the Nemotron-H family publishes (``model_type: nemotron_h``).

No reference analog. With ``H`` heads of ``P`` numbers (``d_inner = H
P``), ``G`` groups of ``B`` and ``C`` rows of ``N`` (head ``h`` reads
group ``h // (H / G)``) and a causal depthwise convolution of ``K`` taps:

    [z | xBC | dt] = u W_in                    widths d_inner | d_inner + 2 G N | H
    xBC = silu(conv_K(xBC) + b_conv)           split into x (H x P), B, C (G x N)
    dt = softplus(dt + dt_bias)                A = -exp(A_log), one of each a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_tᵀ  S (P x N) a head, S_{-1} = 0
    y_t = S_t C_t + D x_t
    out = RMSNorm_G(y * silu(z)) W_out         the norm over groups of d_inner / G

A stream carries S in float32 and the convolution's last ``K - 1``
inputs (in the cache's dtype); the inputs are rounded to that dtype on
every path before they are summed, so a prompt pass followed by steps
adds the same numbers as one pass over the whole sequence does.

**The prompt pass** (:meth:`Mamba2Mixer.block_pass`, a block of a
prompt at a time, the state carried from block to block) is the chunked
form (SSD): positions are taken
``chunk_size`` at a time; inside a chunk the outputs are one masked
quadratic product, ``y_l = sum_{s <= l} exp(a_{s+1} + ... + a_l) dt_s
(C_l . B_s) x_s`` (``a = dt A``), plus what the state carried into the
chunk gives; the state is carried from chunk to chunk by a scan over
chunks, never a position at a time. Under right padding ``dt`` is set to
0 past a row's length: ``exp(0 A) = 1`` and ``0 x B`` leave the state as
it was, so each row's state comes out as of its own length.

**The step** updates S through ``ops/ssm_step.py`` where it applies (the
live slots' states only, in place), else through its plain ``jnp`` twin.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn.gated import mm
from bigdl_tpu.nn.module import Module


class Mamba2Mixer(Module):
    """One Mamba-2 mixer of ``num_heads`` heads of ``head_dim``, ``n_groups``
    groups of ``state_size``-wide B and C rows, a convolution of
    ``conv_kernel`` taps with a bias, and no bias on the projections
    (module docstring). ``chunk_size`` is the prompt pass's unit."""

    def __init__(self, hidden_size, num_heads, head_dim, n_groups,
                 state_size, conv_kernel=4, chunk_size=128, norm_eps=1e-5):
        super().__init__()
        if num_heads % n_groups:
            raise ValueError(f"{num_heads} heads do not divide over "
                             f"{n_groups} groups")
        self.hidden_size = hidden_size
        self.heads = num_heads
        self.head_dim = head_dim
        self.groups = n_groups
        self.state_size = state_size
        self.taps = conv_kernel
        self.chunk = chunk_size
        self.eps = norm_eps
        self.inner = num_heads * head_dim
        self.conv_dim = self.inner + 2 * n_groups * state_size

    def make_params(self, rng, input_spec):
        d, di, h = self.hidden_size, self.inner, self.heads
        ks = jax.random.split(rng, 3)
        return {"w_in": jax.random.normal(ks[0], (d, di + self.conv_dim + h))
                * d ** -0.5,
                "conv_w": jax.random.normal(ks[1], (self.taps, self.conv_dim))
                * self.taps ** -0.5,
                "conv_bias": jnp.zeros((self.conv_dim,)),
                "dt_bias": jnp.zeros((h,)),
                "A_log": jnp.zeros((h,)),
                "D": jnp.ones((h,)),
                "norm": {"weight": jnp.ones((di,))},
                "w_out": jax.random.normal(ks[2], (di, d)) * di ** -0.5}

    def init_cache(self, batch, dtype=jnp.float32):
        """``{"ssm", "conv"}``: S float32 ``(batch, heads, head_dim,
        state_size)`` and the convolution's last inputs ``(batch, taps -
        1, conv_dim)`` in ``dtype``, the slot axis first."""
        return {"ssm": jnp.zeros((batch, self.heads, self.head_dim,
                                  self.state_size), jnp.float32),
                "conv": jnp.zeros((batch, self.taps - 1, self.conv_dim),
                                  dtype)}

    # ------------------------------------------------------------ pieces --
    def _project(self, params, u, conv_dtype):
        """``u`` (..., hidden) -> ``(z, xBC, dt)`` float32, ``xBC`` rounded
        to the convolution's dtype (what its taps keep)."""
        di = self.inner
        zxd = mm(u, params["w_in"])
        z, xbc, dt = (zxd[..., :di], zxd[..., di:di + self.conv_dim],
                      zxd[..., di + self.conv_dim:])
        return z, xbc.astype(conv_dtype).astype(jnp.float32), dt

    def _split(self, params, conv, dt):
        """The convolution's sum (before its bias) and the raw ``dt`` ->
        ``(x (..., H, P), B, C (..., G, N), dt (..., H))``, float32."""
        xbc = jax.nn.silu(conv + params["conv_bias"].astype(jnp.float32))
        di, gn = self.inner, self.groups * self.state_size
        lead = xbc.shape[:-1]
        x = xbc[..., :di].reshape(*lead, self.heads, self.head_dim)
        b = xbc[..., di:di + gn].reshape(*lead, self.groups, self.state_size)
        c = xbc[..., di + gn:].reshape(*lead, self.groups, self.state_size)
        dt = jax.nn.softplus(dt + params["dt_bias"].astype(jnp.float32))
        return x, b, c, dt

    def _a(self, params):
        return -jnp.exp(params["A_log"].astype(jnp.float32))

    def _out(self, params, y, z):
        """``y`` (..., H, P) and the gate ``z`` (..., d_inner) -> the
        gated, group-normed rows through ``W_out``."""
        g = y.reshape(z.shape) * jax.nn.silu(z)
        lead = g.shape[:-1]
        g = g.reshape(*lead, self.groups, -1)
        g = g * lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                          + self.eps)
        g = g.reshape(z.shape) * params["norm"]["weight"].astype(jnp.float32)
        return mm(g, params["w_out"])

    def _heads(self, v):
        """Group rows (..., G, N) -> head rows (..., H, N)."""
        return jnp.repeat(v, self.heads // self.groups, axis=-2)

    # ------------------------------------------------------ a prompt pass --
    def scan_chunks(self, x, dt, a, b, c, state=None):
        """The chunked recurrence over ``x`` (B, T, H, P), ``dt`` (B, T, H)
        (0 where a position must not count), ``a`` (H,) = A, ``b``/``c``
        (B, T, G, N); ``T`` is padded here to whole chunks with ``dt``
        0. Returns ``(y (B, T, H, P) without the D term, S (B, H, P, N)
        after the last position)``, S starting from ``state`` (zeros)."""
        bsz, t, h, p = x.shape
        g, n, r = self.groups, self.state_size, self.heads // self.groups
        ln = min(self.chunk, t)
        nc = -(-t // ln)
        pad = nc * ln - t

        def chunks(v):
            v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            return v.reshape(bsz, nc, ln, *v.shape[2:]).swapaxes(0, 1)

        xs = chunks(x).reshape(nc, bsz, ln, g, r, p)
        dts = chunks(dt).reshape(nc, bsz, ln, g, r)
        bs, cs = chunks(b), chunks(c)
        a = a.reshape(g, r)
        if state is None:
            state = jnp.zeros((bsz, h, p, n), jnp.float32)
        causal = jnp.tril(jnp.ones((ln, ln), bool))

        def one(s, chunk):
            xc, dtc, bc, cc = chunk
            cum = jnp.cumsum(dtc * a, axis=1)                  # (B, L, G, R)
            # exp(a_{s+1} + ... + a_l) for s <= l, 0 above
            seg = cum[:, :, None] - cum[:, None]               # (B, L, S, G, R)
            decay = jnp.exp(jnp.where(causal[None, :, :, None, None], seg,
                                      -jnp.inf))
            cb = jnp.einsum("blgn,bsgn->blsg", cc, bc,
                            preferred_element_type=jnp.float32)
            w = cb[..., None] * decay * dtc[:, None]           # (B, L, S, G, R)
            y = jnp.einsum("blsgr,bsgrp->blgrp", w, xc,
                           preferred_element_type=jnp.float32)
            s4 = s.reshape(bsz, g, r, p, n)
            y = y + jnp.exp(cum)[..., None] * jnp.einsum(
                "blgn,bgrpn->blgrp", cc, s4,
                preferred_element_type=jnp.float32)
            to_end = jnp.exp(cum[:, -1:] - cum) * dtc          # (B, L, G, R)
            s4 = jnp.exp(cum[:, -1])[..., None, None] * s4 + jnp.einsum(
                "bsgn,bsgr,bsgrp->bgrpn", bc, to_end, xc,
                preferred_element_type=jnp.float32)
            return s4.reshape(bsz, h, p, n), y.reshape(bsz, ln, h, p)

        state, ys = lax.scan(one, state, (xs, dts, bs, cs))
        y = ys.swapaxes(0, 1).reshape(bsz, nc * ln, h, p)[:, :t]
        return y, state

    def _conv(self, params, window):
        """The causal convolution over ``window`` (B, taps - 1 + L, C)
        float32, the ``taps - 1`` inputs before the first position first:
        ``(B, L, C)``, the bias not added."""
        t = window.shape[1] - (self.taps - 1)
        w = params["conv_w"].astype(jnp.float32)
        return sum(w[j] * window[:, j:j + t] for j in range(self.taps))

    def block_pass(self, params, u, cache, first, length):
        """One block of a prompt: ``u`` (B, L, hidden) at positions
        ``first ..`` of right-padded rows, ``length`` (B,) their real
        lengths, ``cache`` the state as of the block's first position
        (:meth:`init_cache`'s, zeros before position 0). Returns ``(y (B,
        L, hidden), cache)``, the cache as of ``min(length, first + L)``
        for each row: ``dt`` is 0 past a row's length, and the taps are
        its last ``taps - 1`` real inputs (the carried ones where the row
        ended before the block)."""
        dtype, k = cache["conv"].dtype, self.taps - 1
        z, xbc, dt = self._project(params, u, dtype)
        window = jnp.concatenate([cache["conv"].astype(jnp.float32), xbc],
                                 axis=1)
        x, b, c, dt = self._split(params, self._conv(params, window), dt)
        t = u.shape[1]
        real = first + jnp.arange(t)[None, :] < length[:, None]
        dt = jnp.where(real[..., None], dt, 0.0)
        y, s = self.scan_chunks(x, dt, self._a(params), b, c, cache["ssm"])
        y = y + params["D"].astype(jnp.float32)[:, None] * x
        # position p lies at row p - first + k of the window
        at = jnp.clip(length - first, 0, t)[:, None] + jnp.arange(k)
        taps = jnp.take_along_axis(window, at[:, :, None], axis=1)
        return self._out(params, y, z), {"ssm": s, "conv": taps.astype(dtype)}

    def call(self, params, u):
        bsz, t, _ = u.shape
        return self.block_pass(params, u, self.init_cache(bsz, u.dtype), 0,
                               jnp.full((bsz,), t, jnp.int32))[0]

    # ------------------------------------------------------------- a step --
    def decode_step(self, params, u, cache, live=None):
        """One position a row: ``u`` (B, hidden), ``cache`` as
        :meth:`init_cache` made it. ``live`` (B,) bool marks the slots that
        hold a stream (None: all): ``ops/ssm_step.py`` updates their S in
        place where it applies (the table as allocated says), else the
        plain twin does; a free slot's S is left as it was either way and
        its row of ``y`` is junk nobody reads. Returns ``(y, cache)``."""
        from bigdl_tpu.ops import ssm_step
        state, taps = cache["ssm"], cache["conv"]
        z, xbc, dt = self._project(params, u, taps.dtype)
        window = jnp.concatenate([taps.astype(jnp.float32), xbc[:, None]],
                                 axis=1)                       # (B, K, C)
        conv = jnp.sum(params["conv_w"].astype(jnp.float32)[None] * window,
                       axis=1)
        x, b, c, dt = self._split(params, conv, dt)
        decay = jnp.exp(dt * self._a(params))                  # (B, H)
        if live is None:
            live = jnp.ones(u.shape[:1], bool)
        update = ssm_step.ssm_update if ssm_step.applies(state) \
            else ssm_step.plain_update
        state, y = update(state, decay, dt[..., None] * x, self._heads(b),
                          self._heads(c), live)
        y = jnp.where(live[:, None, None], y, 0.0) \
            + params["D"].astype(jnp.float32)[:, None] * x
        return self._out(params, y, z), {
            "ssm": state, "conv": window[:, 1:].astype(taps.dtype)}
