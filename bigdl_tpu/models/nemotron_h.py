"""Nemotron-H style hybrid decoder: Mamba-2 mixers, latent routed experts
beside a shared expert, and a few attention layers, one kind a layer as
the published pattern string says.

Built from the published keys of the family's ``config.json``
(``model_type: nemotron_h``); the layer equations are the ones
``benchmarks/reference/nemotron3.py`` writes down, and what is taken on
trust there is taken on trust here. ``RMS(x) = x * rsqrt(mean(x^2) + eps)
* g``.

- Model: ``h = E[tok]``; the layers; ``logits = RMS_out(h) @ W_head``
  (untied).
- Every layer, pre-norm, residual stream float32: ``h += mixer(RMS(h))``,
  the mixer by the layer's character in ``hybrid_override_pattern``:
  ``M`` ``nn.Mamba2Mixer``; ``E`` ``nn.SharedAndRoutedExperts`` of squared
  ReLU experts at ``moe_latent_size`` (the router scores the full-width
  row, ``num_experts_per_tok`` of ``n_routed_experts``, of which this
  holder keeps ``experts_held`` from ``experts_first`` on) beside a shared
  expert of ``moe_shared_expert_intermediate_size``; ``*``
  :class:`~bigdl_tpu.models.lfm2.GroupedQueryAttention` with neither
  QK-norm nor any positional encoding.

The matrix products take their operands in the weights' dtype and sum in
float32; the residual stream, the norms, the router, the recurrence and
S are float32; K, V and the convolutions' taps are kept in the cache's
dtype.

:class:`NemotronHForCausalLM` speaks the serving engine's model protocol
(``docs/serving.md``). Its cache is, a layer, ``{"ssm", "conv"}`` of a
Mamba layer (fixed-size state: S float32 ``(slots, heads, head_dim,
state_size)`` and the convolution's last inputs), ``{"k", "v"}`` of an
attention layer (one row table, ``positions_table``) and nothing of an
expert layer. It carries none of the engine's optional features.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import bigdl_tpu.nn as nn
from bigdl_tpu.models import prompt_blocks
from bigdl_tpu.models.lfm2 import GroupedQueryAttention
from bigdl_tpu.nn.gated import mm
from bigdl_tpu.nn.module import Module
from bigdl_tpu.nn.ssm import Mamba2Mixer

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


class NemotronHBlock(Module):
    """One layer: its mixer behind an RMSNorm, added to the residual
    stream."""

    def __init__(self, kind, cfg):
        super().__init__()
        d, eps = cfg["hidden_size"], cfg["eps"]
        self.kind = kind
        self.norm = nn.RMSNorm(d, eps)
        if kind == MAMBA:
            self.mixer = Mamba2Mixer(
                d, cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"],
                cfg["chunk_size"], eps)
        elif kind == EXPERTS:
            self.mixer = nn.SharedAndRoutedExperts(
                d, cfg["moe_intermediate_size"], cfg["n_routed_experts"],
                cfg["num_experts_per_tok"], cfg["n_shared_experts"],
                shared_size=cfg["moe_shared_expert_intermediate_size"],
                first=cfg["experts_first"], count=cfg["experts_held"],
                norm_topk_prob=cfg["norm_topk_prob"],
                scaling=cfg["routed_scaling_factor"], act="relu2",
                latent_size=cfg["moe_latent_size"])
        elif kind == ATTENTION:
            self.mixer = GroupedQueryAttention(
                d, cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], norm_eps=eps, qk_norm=False, rotary=False)
        else:
            raise ValueError(f"unknown layer character {kind!r}")

    def setup(self, rng, input_spec):
        return {"norm": self.norm.make_params(None, None),
                "mixer": self.mixer.make_params(rng, None)}, ()

    def init_cache(self, batch, max_len, dtype):
        if self.kind == MAMBA:
            return self.mixer.init_cache(batch, dtype)
        if self.kind == ATTENTION:
            return self.mixer.init_cache(batch, max_len, dtype)
        return {}

    def _experts(self, params, u, live):
        """``u`` (..., hidden) through the expert layer, ``live`` (...,)
        the rows that count: ``(y, (experts hit, assignments held))``."""
        y, hit, held = self.mixer.routed(
            params["mixer"], u.reshape(-1, u.shape[-1]),
            None if live is None else live.reshape(-1))
        return y.reshape(u.shape), (hit, held)

    def apply(self, params, state, x, *, training=False, rng=None):
        u = self.norm.call(params["norm"], x)
        if self.kind == EXPERTS:
            return x + self._experts(params, u, None)[0], state
        return x + self.mixer.call(params["mixer"], u), state

    def block_pass(self, params, cache, x, first, prompt_len):
        """One block of a prompt through the layer: the Mamba state and
        the K/V rows carried in ``cache``, the expert layer over the real
        rows only (the padding's are read by nothing)."""
        u = self.norm.call(params["norm"], x)
        p = params["mixer"]
        if self.kind == MAMBA:
            y, cache = self.mixer.block_pass(p, u, cache, first, prompt_len)
        elif self.kind == ATTENTION:
            y, cache = self.mixer.block_pass(p, u, cache, first)
        else:
            real = first + jnp.arange(x.shape[1])[None, :] \
                < prompt_len[:, None]
            y = self._experts(params, u, real)[0]
        return x + y, cache

    def decode_step(self, params, cache, x, pos, in_place, read, live):
        u = self.norm.call(params["norm"], x)
        p = params["mixer"]
        counts = None
        if self.kind == MAMBA:
            y, cache = self.mixer.decode_step(p, u, cache, live)
        elif self.kind == ATTENTION:
            y, cache = self.mixer.decode_step(p, u, cache, pos, in_place,
                                              read, live)
        else:
            y, counts = self._experts(params, u, live)
        return x + y, cache, counts


class NemotronHForCausalLM(Module):
    """The hybrid decoder with its untied head. Arguments carry the
    published config's names (``layer_norm_epsilon`` is every norm's);
    ``experts_first``/``experts_held`` say which experts of every expert
    layer this holder keeps (default: all), ``max_position`` the
    positions a served stream may hold (no positional encoding needs a
    table) and ``prefill_block`` the positions a prompt pass walks at a
    time. Every block reads the layers' weights once; the default 768 is
    the fewest whole chunks of 128 at which one prompt's block, at the
    published widths, does as much matrix work on a v5e as the reading
    of those weights takes (1.9 GFLOP a position against 5.2 GB), and a
    longer block adds padding to a prompt's last block and
    temporaries."""

    # which of ``ServingEngine``'s optional features the model carries
    # (serving/engine.py names them); the engine refuses the rest by name
    serving_features = frozenset()
    logits_dtype = jnp.float32

    def __init__(self, vocab_size=131072, hidden_size=4096,
                 hybrid_override_pattern="MEMEMEM*EME", mamba_num_heads=128,
                 mamba_head_dim=64, n_groups=8, ssm_state_size=128,
                 conv_kernel=4, chunk_size=128, num_attention_heads=32,
                 num_key_value_heads=2, head_dim=128, n_routed_experts=512,
                 num_experts_per_tok=22, n_shared_experts=1,
                 moe_intermediate_size=2688, moe_latent_size=1024,
                 moe_shared_expert_intermediate_size=5376,
                 norm_topk_prob=True, routed_scaling_factor=5.0,
                 layer_norm_epsilon=1e-5, max_position=6144,
                 experts_first=0, experts_held=None, prefill_block=768):
        super().__init__()
        prompt_blocks.check_positions(max_position, prefill_block)
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.max_position = max_position
        self.prefill_block = prefill_block
        self.pattern = hybrid_override_pattern
        cfg = dict(
            hidden_size=hidden_size, eps=layer_norm_epsilon,
            mamba_num_heads=mamba_num_heads, mamba_head_dim=mamba_head_dim,
            n_groups=n_groups, ssm_state_size=ssm_state_size,
            conv_kernel=conv_kernel, chunk_size=chunk_size,
            num_attention_heads=num_attention_heads,
            num_key_value_heads=num_key_value_heads, head_dim=head_dim,
            n_routed_experts=n_routed_experts,
            num_experts_per_tok=num_experts_per_tok,
            n_shared_experts=n_shared_experts,
            moe_intermediate_size=moe_intermediate_size,
            moe_latent_size=moe_latent_size,
            moe_shared_expert_intermediate_size=(
                moe_shared_expert_intermediate_size),
            norm_topk_prob=norm_topk_prob,
            routed_scaling_factor=float(routed_scaling_factor),
            experts_first=experts_first, experts_held=experts_held)
        self.layers = [NemotronHBlock(kind, cfg)
                       for kind in hybrid_override_pattern]
        self.out_norm = nn.RMSNorm(hidden_size, layer_norm_epsilon)
        # what the slot table stamps on its spans: the assignments a
        # token makes in an expert layer
        self.experts_per_token = num_experts_per_tok \
            if EXPERTS in hybrid_override_pattern else 0
        self.chunk = chunk_size
        self.mamba_layers = hybrid_override_pattern.count(MAMBA)
        self.state_bytes = 4 * mamba_num_heads * mamba_head_dim \
            * ssm_state_size

    def expert_rows(self, width, length):
        """The assignments an expert layer's call takes in a pass over
        ``width`` rows of ``length`` positions: a prompt goes through a
        layer a block of ``prefill_block`` positions at a time."""
        return width * min(self.prefill_block, length) \
            * self.experts_per_token

    def setup(self, rng, input_spec):
        ks = jax.random.split(rng, len(self.layers) + 2)
        d = self.hidden_size
        return {"tok_emb": 0.02 * jax.random.normal(
                    ks[0], (self.vocab_size, d)),
                "out_norm": self.out_norm.make_params(None, None),
                "head": jax.random.normal(ks[1], (d, self.vocab_size))
                * d ** -0.5,
                "layers": [l.setup(k, None)[0]
                           for l, k in zip(self.layers, ks[2:])]}, ()

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
        """(…, hidden) final-norm rows -> (…, vocab) float32 logits."""
        return mm(h, params["head"])

    def init_cache(self, batch, dtype=jnp.float32, sharding=None):
        if sharding is not None:
            raise TypeError("NemotronHForCausalLM's cache is not laid out "
                            "over a mesh")
        return [l.init_cache(batch, self.max_position, dtype)
                for l in self.layers]

    def cache_tables(self):
        """K and V of the attention layers; a Mamba layer's S and taps
        are fixed-size state."""
        from bigdl_tpu.serving.protocol import positions_table
        if ATTENTION not in self.pattern:
            return ()
        return (positions_table(self.max_position),)

    def step_counts(self, pos):
        """What the step at the live slots' positions ``pos`` (numpy)
        moves: the slots whose S a Mamba layer updates (one a position),
        the bytes of S they read and write over every Mamba layer, and
        the rows of K and V an attention layer reads (each slot's up to
        the one it writes)."""
        pos = np.asarray(pos, np.int64)
        return {"ssm_slots": int(pos.size),
                "ssm_state_bytes": 2 * int(pos.size) * self.mamba_layers
                * self.state_bytes,
                "attn_rows": int((pos + 1).sum())}

    def prefill_counts(self, prompt_len):
        """What an admission's prompt pass scans a Mamba layer (numpy
        lengths): the chunks that hold its real positions, and those
        positions."""
        n = np.asarray(prompt_len, np.int64)
        return {"ssm_chunks": int((-(-n // self.chunk)).sum()),
                "ssm_positions": int(n.sum())}

    def _block(self, params, cache, carries, ids, first, prompt_len):
        """Every layer over one block of ``ids`` (B, T) at positions
        ``first ..``: the hidden rows and the cache with the block's
        state and rows in (nothing else is carried)."""
        h = self._embed(params, ids)
        new = []
        for layer, p, c in zip(self.layers, params["layers"], cache):
            h, c = layer.block_pass(p, c, h, first, prompt_len)
            new.append(c)
        return h, new, carries

    def prefill(self, params, cache, ids, prompt_len):
        """``ids`` (W, bucket) right-padded prompts, ``prompt_len`` (W,):
        returns the final-norm row at each prompt's last real position
        and ``cache`` filled: K and V of every position walked (what lies
        past a row's length is junk that its steps overwrite before they
        read it), each Mamba layer's S and taps as of ``prompt_len``. A
        block of ``prefill_block`` positions goes through every layer
        before the next, the state and K/V carried; the blocks past the
        longest prompt are not walked (``models/prompt_blocks.py``)."""
        h_last, cache = prompt_blocks.walk(
            self.prefill_block, self.hidden_size, cache, ids, prompt_len,
            functools.partial(self._block, params),
            position_axes=[2 if l.kind == ATTENTION else None
                           for l in self.layers])
        return self.out_norm.call(params["out_norm"], h_last), cache

    def decode_step(self, params, cache, tok, pos, in_place=False,
                    read=None, live=None):
        """One token a row at position ``pos`` (B,): ``(h, cache)`` with
        ``h`` (B, hidden) the final-norm rows. ``in_place`` and ``read``
        are the slot table's words to the attention layers. Given ``live``
        (B,) bool the Mamba layers update the live slots' S only (through
        ``ops/ssm_step.py`` where it applies) and the expert layers leave
        the dead rows out; a model with expert layers then also returns,
        third and fourth, the means over them of how many of the experts
        HELD the live rows hit and of how many of their assignments fell
        on those (float32 scalars)."""
        h = self._embed(params, tok)
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), tok.shape)
        new_cache, counts = [], []
        for layer, p, c in zip(self.layers, params["layers"], cache):
            h, c, n = layer.decode_step(p, c, h, pos, in_place, read, live)
            new_cache.append(c)
            if n is not None:
                counts.append(n)
        h = self.out_norm.call(params["out_norm"], h)
        if live is None or not counts:
            return h, new_cache
        hit, held = (jnp.mean(jnp.stack(c).astype(jnp.float32))
                     for c in zip(*counts))
        return h, new_cache, hit, held
