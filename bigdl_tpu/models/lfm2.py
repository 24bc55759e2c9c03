"""LFM2-MoE style decoder: gated short convolutions beside grouped-query
attention, a dense SwiGLU MLP in the leading layers and routed experts in
every later one.

Built from the published keys of the family's ``config.json``
(``model_type: lfm2_moe``; the layer equations follow ``transformers``'
``modeling_lfm2_moe.py``). ``RMS(x) = x * rsqrt(mean(x^2) + eps) * g``.

- Model: ``h = E[tok]``; the layers; ``logits = RMS_out(h) @ E^T`` (tied).
- Every layer: ``h += Op(RMS_op(h))``; ``h += FFN(RMS_ffn(h))``.
- ``Op`` of a ``conv`` layer: ``nn.GatedShortConv`` (``conv_L_cache``
  taps, no bias). Its decode state is the last taps of ``z`` a slot.
- ``Op`` of a ``full_attention`` layer: :class:`GroupedQueryAttention`,
  RMSNorm over each head of q and of k, then rotary positions over the
  whole head, each K/V head serving ``heads / kv_heads`` query heads,
  causal softmax at ``head_dim ** -0.5``, no bias.
- ``FFN`` of the first ``num_dense_layers`` layers: ``nn.GatedMLP`` at
  ``intermediate_size``; of every later layer: ``nn.RoutedExperts``
  (``num_experts`` of ``moe_intermediate_size``, ``num_experts_per_tok``
  a token, no shared expert, no token dropped).

The residual stream, the norms, the softmax and the routing are float32;
every matrix product takes its operands in the weights' dtype and sums in
float32; K, V and the convolution state are kept in the cache's dtype.

:class:`LFM2ForCausalLM` speaks the serving engine's model protocol
(``docs/serving.md``): its cache is, a layer, ``{"k", "v"}`` of
``(slots, kv_heads, max_position, head_dim)`` or ``{"conv"}`` of
``(slots, taps, hidden)``, every leaf with the slot axis first. It
carries none of the engine's optional features yet.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.gated import mm
from bigdl_tpu.nn.module import Module
from bigdl_tpu.nn.rotary import apply_rotary, rotary_angles

CONV, ATTENTION = "conv", "full_attention"


class GroupedQueryAttention(Module):
    """Causal attention with ``n_kv_heads`` K/V heads under ``n_heads``
    query heads (``n_heads / n_kv_heads`` queries share one), QK-norm and
    rotary positions (``qk_norm=False``: q and k as projected;
    ``rotary=False``: no positional encoding at all, as the Nemotron-H
    family's attention layers). The cache is ``{"k", "v"}`` of ``(B,
    n_kv_heads, max_len, head_dim)``, the dense slot table's own shape, so
    a decode step writes through ``ops/kv_write.py`` where the table's
    owner says it applies."""

    def __init__(self, hidden_size, n_heads, n_kv_heads, head_dim=None,
                 rope_theta=10000.0, norm_eps=1e-5, qk_norm=True,
                 rotary=True):
        super().__init__()
        if n_heads % n_kv_heads:
            raise ValueError(f"{n_heads} query heads do not divide over "
                             f"{n_kv_heads} K/V heads")
        self.hidden_size = hidden_size
        self.n_heads = n_heads
        self.n_kv_heads = n_kv_heads
        self.head_dim = head_dim or hidden_size // n_heads
        self.rope_theta = rope_theta
        self.qk_norm = qk_norm
        self.rotary = rotary
        self.q_norm = nn.RMSNorm(self.head_dim, norm_eps)
        self.k_norm = nn.RMSNorm(self.head_dim, norm_eps)

    def make_params(self, rng, input_spec):
        d, hd = self.hidden_size, self.head_dim
        q, kv = self.n_heads * hd, self.n_kv_heads * hd
        ks = jax.random.split(rng, 4)
        std = d ** -0.5
        params = {"wq": jax.random.normal(ks[0], (d, q)) * std,
                  "wk": jax.random.normal(ks[1], (d, kv)) * std,
                  "wv": jax.random.normal(ks[2], (d, kv)) * std,
                  "wo": jax.random.normal(ks[3], (q, d)) * q ** -0.5}
        if self.qk_norm:
            params.update(q_norm=self.q_norm.make_params(None, None),
                          k_norm=self.k_norm.make_params(None, None))
        return params

    def _qkv(self, params, x, positions):
        """``x`` (B, T, hidden), ``positions`` (T,) or (B, T) -> q
        ``(B, kv, rep, T, hd)``, k and v ``(B, kv, T, hd)``, float32, q
        and k normed and turned (where the layer has either)."""
        b, t, _ = x.shape
        g, hd = self.n_kv_heads, self.head_dim
        q = mm(x, params["wq"]).reshape(b, t, g, self.n_heads // g, hd)
        k = mm(x, params["wk"]).reshape(b, t, g, hd)
        v = mm(x, params["wv"]).reshape(b, t, g, hd)
        if self.rotary:
            cos, sin = rotary_angles(positions, hd, self.rope_theta)
            if cos.ndim == 2:
                cos, sin = cos[None], sin[None]
            cos, sin = cos[:, :, None], sin[:, :, None]       # (B|1,T,1,hd)
        if self.qk_norm:
            q = self.q_norm.call(params["q_norm"], q)
        if self.rotary:
            q = apply_rotary(q, cos[:, :, :, None], sin[:, :, :, None])
        if self.qk_norm:
            k = self.k_norm.call(params["k_norm"], k)
        if self.rotary:
            k = apply_rotary(k, cos, sin)
        return (q.transpose(0, 2, 3, 1, 4), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3))

    def _attend(self, params, q, k, v, mask):
        """``q`` (B, kv, rep, Tq, hd) against ``k``/``v`` (B, kv, Tk, hd)
        under ``mask`` (broadcast to (B, kv, rep, Tq, Tk)); operands in
        ``k``'s dtype, scores and softmax float32."""
        dt = k.dtype
        s = jnp.einsum("bgrqd,bgkd->bgrqk", q.astype(dt), k,
                       preferred_element_type=jnp.float32)
        s = jnp.where(mask, s * self.head_dim ** -0.5, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bgrqk,bgkd->bgrqd", p.astype(dt), v,
                       preferred_element_type=jnp.float32)
        return self._project(params, o)

    def _project(self, params, o):
        """The heads' outputs ``o`` (B, kv, rep, T, hd) through ``wo``."""
        b, _, _, t, _ = o.shape
        return mm(o.transpose(0, 3, 1, 2, 4).reshape(b, t, -1), params["wo"])

    def call(self, params, x):
        t = x.shape[1]
        q, k, v = self._qkv(params, x, jnp.arange(t))
        dt = params["wq"].dtype
        return self._attend(params, q, k.astype(dt), v.astype(dt),
                            jnp.tril(jnp.ones((t, t), bool)))

    def init_cache(self, batch, max_len, dtype=jnp.float32):
        shape = (batch, self.n_kv_heads, max_len, self.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def prefill(self, params, x, cache):
        """The prompt pass: causal attention over the (padded) prompt, its
        K and V written into positions ``[0, T)`` of ``cache``. What the
        padding leaves there is never read: the causal mask here and the
        length mask of :meth:`decode_step` keep it out."""
        t = x.shape[1]
        q, k, v = self._qkv(params, x, jnp.arange(t))
        k, v = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
        cache = {"k": lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0)),
                 "v": lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0))}
        return self._attend(params, q, k, v,
                            jnp.tril(jnp.ones((t, t), bool))), cache

    def block_pass(self, params, x, cache, first):
        """One block of a prompt: ``x`` (B, L, hidden) at positions
        ``first .. first + L - 1``, their K and V written into rows
        ``[first, first + L)`` of ``cache``, each query attending to the
        cache's rows up to its own position (what lies past it, the
        padding's or nothing's, is masked off)."""
        t = x.shape[1]
        positions = first + jnp.arange(t)
        q, k, v = self._qkv(params, x, positions)
        k, v = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
        at = (0, 0, first, 0)
        cache = {"k": lax.dynamic_update_slice(cache["k"], k, at),
                 "v": lax.dynamic_update_slice(cache["v"], v, at)}
        seen = jnp.arange(cache["k"].shape[2])[None, :] <= positions[:, None]
        return self._attend(params, q, cache["k"], cache["v"], seen), cache

    def decode_step(self, params, x, cache, pos, in_place=False, read=None,
                    live=None):
        """One position a row: ``x`` (B, hidden), ``pos`` (B,) the
        position each row writes and attends up to. ``in_place``,
        ``read`` and ``live`` as in ``parallel.sequence``'s attention:
        the table's owner says that ``ops/kv_write.py`` takes the write,
        of the rows that ``live`` marks (None: of every row), and hands
        over the per-row counts that ``ops/decode_attention.py`` reads
        (None: every position of every row, masked)."""
        from bigdl_tpu.ops.kv_write import kv_write, plain_write
        pos = jnp.asarray(pos, jnp.int32)
        q, k, v = self._qkv(params, x[:, None], pos[:, None])
        k, v = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
        if in_place:
            kc, vc = kv_write(cache["k"], cache["v"], k, v, pos, live)
        else:
            kc, vc = plain_write(cache["k"], cache["v"], k, v, pos)
        if read is None:
            seen = jnp.arange(kc.shape[2])[None, :] <= pos[:, None]  # (B, S)
            out = self._attend(params, q, kc, vc,
                               seen[:, None, None, None, :])
        else:
            from bigdl_tpu.ops.decode_attention import decode_attention
            out = self._project(params, decode_attention(
                q[:, :, :, 0], kc, vc, read)[:, :, :, None])
        return out[:, 0], {"k": kc, "v": vc}


class LFM2Block(Module):
    """One layer: an operator (convolution or attention) and a
    feed-forward (dense or routed), each behind its RMSNorm, each added
    to the residual stream."""

    def __init__(self, kind, dense, cfg):
        super().__init__()
        self.kind = kind
        d, eps = cfg["hidden_size"], cfg["norm_eps"]
        self.op_norm = nn.RMSNorm(d, eps)
        self.ffn_norm = nn.RMSNorm(d, eps)
        if kind == CONV:
            self.op = nn.GatedShortConv(d, cfg["conv_L_cache"])
        elif kind == ATTENTION:
            self.op = GroupedQueryAttention(
                d, cfg["num_attention_heads"], cfg["num_key_value_heads"],
                rope_theta=cfg["rope_theta"], norm_eps=eps)
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        self.routed = not dense
        if dense:
            self.ffn = nn.GatedMLP(d, cfg["intermediate_size"])
        else:
            self.ffn = nn.RoutedExperts(
                d, cfg["moe_intermediate_size"], cfg["num_experts"],
                cfg["num_experts_per_tok"], first=cfg["experts_first"],
                count=cfg["experts_count"],
                use_bias=cfg["use_expert_bias"],
                norm_topk_prob=cfg["norm_topk_prob"],
                scaling=cfg["routed_scaling_factor"])

    @property
    def _names(self):
        return ("conv" if self.kind == CONV else "attn",
                "moe" if self.routed else "mlp")

    def setup(self, rng, input_spec):
        op, ffn = self._names
        ks = jax.random.split(rng, 2)
        return {"op_norm": self.op_norm.make_params(None, None),
                "ffn_norm": self.ffn_norm.make_params(None, None),
                op: self.op.make_params(ks[0], None),
                ffn: self.ffn.make_params(ks[1], None)}, ()

    def _ffn(self, params, h, live=None):
        """``h`` (..., hidden) plus its feed-forward; the experts hit
        beside it (None for a dense layer)."""
        u = self.ffn_norm.call(params["ffn_norm"], h)
        name = self._names[1]
        if not self.routed:
            return h + self.ffn.call(params[name], u), None
        y, hit = self.ffn.routed(params[name], u.reshape(-1, u.shape[-1]),
                                 live)
        return h + y.reshape(h.shape), hit

    def apply(self, params, state, x, *, training=False, rng=None):
        u = self.op_norm.call(params["op_norm"], x)
        x = x + self.op.call(params[self._names[0]], u)
        return self._ffn(params, x)[0], state

    def init_cache(self, batch, max_len, dtype):
        if self.kind == CONV:
            return {"conv": self.op.init_state(batch, dtype)}
        return self.op.init_cache(batch, max_len, dtype)

    def prefill(self, params, cache, x, prompt_len):
        u = self.op_norm.call(params["op_norm"], x)
        p = params[self._names[0]]
        if self.kind == CONV:
            y, state = self.op.prefill(p, u, prompt_len, cache["conv"].dtype)
            cache = {"conv": state}
        else:
            y, cache = self.op.prefill(p, u, cache)
        # the padding's rows are read by nothing: the experts skip them
        real = jnp.arange(x.shape[1])[None, :] < prompt_len[:, None]
        return self._ffn(params, x + y, real.reshape(-1))[0], cache

    def decode_step(self, params, cache, x, pos, in_place, live, read):
        u = self.op_norm.call(params["op_norm"], x)
        p = params[self._names[0]]
        if self.kind == CONV:
            y, state = self.op.decode_step(p, u, cache["conv"])
            cache = {"conv": state}
        else:
            y, cache = self.op.decode_step(p, u, cache, pos, in_place, read,
                                           live)
        x, hit = self._ffn(params, x + y, live)
        return x, cache, hit


class LFM2ForCausalLM(Module):
    """The decoder with its tied head. Arguments carry the published
    config's names; ``experts_first``/``experts_count`` say which experts
    of every routed layer this holder keeps (default: all)."""

    # which of ``ServingEngine``'s optional features the model carries
    # (serving/engine.py names them); the engine refuses the rest by name
    serving_features = frozenset()
    logits_dtype = jnp.float32

    def __init__(self, vocab_size=65536, hidden_size=2048,
                 intermediate_size=11776, moe_intermediate_size=1536,
                 layer_types=(CONV, CONV, ATTENTION), num_dense_layers=2,
                 num_experts=64, num_experts_per_tok=4,
                 num_attention_heads=32, num_key_value_heads=8,
                 conv_L_cache=3, norm_eps=1e-5, rope_theta=1000000.0,
                 norm_topk_prob=True, use_expert_bias=True,
                 routed_scaling_factor=1.0, max_position=2048,
                 experts_first=0, experts_count=None):
        super().__init__()
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.max_position = max_position
        cfg = dict(hidden_size=hidden_size,
                   intermediate_size=intermediate_size,
                   moe_intermediate_size=moe_intermediate_size,
                   num_experts=num_experts,
                   num_experts_per_tok=num_experts_per_tok,
                   num_attention_heads=num_attention_heads,
                   num_key_value_heads=num_key_value_heads,
                   conv_L_cache=conv_L_cache, norm_eps=norm_eps,
                   rope_theta=float(rope_theta),
                   norm_topk_prob=norm_topk_prob,
                   use_expert_bias=use_expert_bias,
                   routed_scaling_factor=float(routed_scaling_factor),
                   experts_first=experts_first, experts_count=experts_count)
        self.layers = [LFM2Block(kind, i < num_dense_layers, cfg)
                       for i, kind in enumerate(layer_types)]
        self.out_norm = nn.RMSNorm(hidden_size, norm_eps)
        # what the slot table stamps on its spans: the assignments a
        # token makes in a routed layer
        routed = any(l.routed for l in self.layers)
        self.experts_per_token = num_experts_per_tok if routed else 0

    def expert_rows(self, width, length):
        """The assignments a routed layer's call takes in a pass over
        ``width`` rows of ``length`` positions: a prompt goes through a
        layer whole."""
        return width * length * self.experts_per_token

    def setup(self, rng, input_spec):
        ks = jax.random.split(rng, len(self.layers) + 1)
        return {"tok_emb": 0.02 * jax.random.normal(
                    ks[0], (self.vocab_size, self.hidden_size)),
                "out_norm": self.out_norm.make_params(None, None),
                "layers": [l.setup(k, None)[0]
                           for l, k in zip(self.layers, ks[1:])]}, ()

    def _embed(self, params, ids):
        return jnp.take(params["tok_emb"], ids.astype(jnp.int32),
                        axis=0).astype(jnp.float32)

    def apply(self, params, state, x, *, training=False, rng=None):
        h = self._embed(params, x)
        for layer, p in zip(self.layers, params["layers"]):
            h, _ = layer.apply(p, (), h)
        h = self.out_norm.call(params["out_norm"], h)
        return self.logits(params, h).reshape(-1, self.vocab_size), state

    # --------------------------------------------- the serving protocol --
    def serving_dtype(self, params):
        return params["tok_emb"].dtype

    def logits(self, params, h):
        """(…, hidden) final-norm rows -> (…, vocab) float32 logits
        through the tied embedding."""
        e = params["tok_emb"]
        return jnp.einsum("...d,vd->...v", h.astype(e.dtype), e,
                          preferred_element_type=jnp.float32)

    def init_cache(self, batch, dtype=jnp.float32, sharding=None):
        if sharding is not None:
            raise TypeError("LFM2ForCausalLM's cache is not laid out over "
                            "a mesh")
        return [l.init_cache(batch, self.max_position, dtype)
                for l in self.layers]

    def cache_tables(self):
        """K and V of the attention layers; a convolution's taps are
        fixed-size state."""
        from bigdl_tpu.serving.protocol import positions_table
        if not any(l.kind == ATTENTION for l in self.layers):
            return ()
        return (positions_table(self.max_position),)

    def prefill(self, params, cache, ids, prompt_len):
        """``ids`` (W, bucket) right-padded prompts, ``prompt_len`` (W,):
        returns the final-norm row at each prompt's last real position
        and ``cache`` filled: K and V of positions ``[0, bucket)``, the
        convolution state as of ``prompt_len``."""
        h = self._embed(params, ids)
        prompt_len = jnp.broadcast_to(jnp.asarray(prompt_len, jnp.int32),
                                      (ids.shape[0],))
        new_cache = []
        for layer, p, c in zip(self.layers, params["layers"], cache):
            h, c = layer.prefill(p, c, h, prompt_len)
            new_cache.append(c)
        h = jnp.take_along_axis(h, (prompt_len - 1)[:, None, None],
                                axis=1)[:, 0]
        return self.out_norm.call(params["out_norm"], h), new_cache

    def decode_step(self, params, cache, tok, pos, in_place=False,
                    live=None, read=None):
        """One token a row at position ``pos`` (B,): ``(h, cache)`` with
        ``h`` (B, hidden) the final-norm rows. Given ``live`` (B,) bool
        the routed layers leave the dead rows out (their ``h`` is junk
        that nobody reads), and a model that has routed layers
        (``experts_per_token`` > 0) also returns, third, the mean over
        them of how many experts the live rows chose (float32 scalar).
        ``in_place`` and ``read`` are the slot table's words to the
        attention layers, which also write the live rows' K/V only
        (``GroupedQueryAttention.decode_step``)."""
        h = self._embed(params, tok)
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), tok.shape)
        new_cache, hits = [], []
        for layer, p, c in zip(self.layers, params["layers"], cache):
            h, c, hit = layer.decode_step(p, c, h, pos, in_place, live, read)
            new_cache.append(c)
            if hit is not None:
                hits.append(hit)
        h = self.out_norm.call(params["out_norm"], h)
        if live is None or not hits:
            return h, new_cache
        return h, new_cache, jnp.mean(jnp.stack(hits).astype(jnp.float32))
