"""dots3-note style decoder: latent attention whose full layers read only
the positions a learned indexer picks, windowed latent attention between
them, a headwise gate on every attention output, a leading dense layer and
then routed experts beside a shared one.

Built from the published keys of the family's ``config.json``
(``model_type: dots3_note``); the layer equations are the ones
``benchmarks/reference/dots3.py`` writes down (``nn/latent.py`` has the
attention's), and what is taken on trust there is taken on trust here.
``RMS(x) = x * rsqrt(mean(x^2) + eps) * g``.

- Model: ``h = E[tok]``; the layers; ``logits = RMS_out(h) @ W_head``
  (untied).
- Every layer: ``h += Attn(RMS(h))``; ``h += FFN(RMS(h))``.
- ``Attn`` of a ``full_attention`` layer: ``nn.SelectedLatentAttention``
  (``num_attention_heads`` heads, the indexer's ``index_topk`` positions);
  of a ``sliding_attention`` layer: ``nn.WindowLatentAttention`` with the
  ``swa_`` sizes over ``sliding_window_size`` positions.
- ``FFN`` of the first ``first_k_dense_replace`` layers: ``nn.GatedMLP`` at
  ``intermediate_size``; of every later one ``nn.SharedAndRoutedExperts``:
  ``n_routed_experts`` of ``moe_intermediate_size``, ``num_experts_per_tok``
  a token, of which this holder keeps ``experts_held`` from
  ``experts_first`` on, and ``n_shared_experts`` that every token passes.

:class:`Dots3ForCausalLM` speaks the serving engine's model protocol
(``docs/serving.md``). Its cache has three shapes side by side, none with a
head axis: a full layer's ``ckv`` (slots, max_position, 640: the latent of
kv_lora_rank and the rotary key, in whole lanes) and ``kidx`` (slots,
max_position, index_head_dim), written at ``pos``; a window layer's
``win``, a ring written at ``pos mod window``. Of ``kidx`` a step reads
every row up to its position, of ``ckv`` the ``index_topk`` rows it then
chooses count: a table read by selection, which neither of the slot
table's kernels takes (the step's read is ``ops/latent_attention.py``'s,
the model's own). It carries none of the engine's optional features.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import bigdl_tpu.nn as nn
from bigdl_tpu.models import prompt_blocks
from bigdl_tpu.nn.gated import mm
from bigdl_tpu.nn.module import Module

FULL, SLIDING = "full_attention", "sliding_attention"


def _least(a, b):
    """``min(a, b)`` by arithmetic alone: numpy on the host and traced
    arrays inside a step alike."""
    return a - (a - b) * (a > b)


class Dots3Block(Module):
    """One layer: latent attention (selected or windowed) and a
    feed-forward (dense, or shared and routed experts), each behind its
    RMSNorm, each added to the float32 residual stream."""

    def __init__(self, kind, dense, cfg):
        super().__init__()
        d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.kind = kind
        self.attn_norm = nn.RMSNorm(d, eps)
        self.ffn_norm = nn.RMSNorm(d, eps)
        shared = dict(norm_eps=eps, rescale=cfg["rescale"])
        if kind == FULL:
            self.attn = nn.SelectedLatentAttention(
                d, cfg["num_attention_heads"], cfg["q_lora_rank"],
                cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                rope_theta=cfg["rope_theta"], gate=cfg["gate"],
                index_heads=cfg["index_n_heads"],
                index_dim=cfg["index_head_dim"],
                index_topk=cfg["index_topk"], **shared)
        elif kind == SLIDING:
            self.attn = nn.WindowLatentAttention(
                d, cfg["swa_num_attention_heads"], cfg["swa_q_lora_rank"],
                cfg["swa_kv_lora_rank"], cfg["swa_qk_nope_head_dim"],
                cfg["swa_qk_rope_head_dim"], cfg["swa_v_head_dim"],
                rope_theta=cfg["swa_rope_theta"], gate=cfg["swa_gate"],
                window=cfg["sliding_window_size"], **shared)
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        self.routed = not dense
        if dense:
            self.ffn = nn.GatedMLP(d, cfg["intermediate_size"])
        else:
            self.ffn = nn.SharedAndRoutedExperts(
                d, cfg["moe_intermediate_size"], cfg["n_routed_experts"],
                cfg["num_experts_per_tok"], cfg["n_shared_experts"],
                first=cfg["experts_first"], count=cfg["experts_held"],
                norm_topk_prob=cfg["norm_topk_prob"],
                scaling=cfg["routed_scaling_factor"])

    @property
    def _ffn_name(self):
        return "moe" if self.routed else "mlp"

    def setup(self, rng, input_spec):
        ks = jax.random.split(rng, 2)
        return {"attn_norm": self.attn_norm.make_params(None, None),
                "ffn_norm": self.ffn_norm.make_params(None, None),
                "attn": self.attn.make_params(ks[0], None),
                self._ffn_name: self.ffn.make_params(ks[1], None)}, ()

    def _ffn(self, params, h, live=None):
        """``h`` (..., hidden) plus its feed-forward; beside it ``(experts
        hit, assignments held)`` of a routed layer (None for a dense
        one), counted over the rows that ``live`` (...,) marks."""
        u = self.ffn_norm.call(params["ffn_norm"], h)
        p = params[self._ffn_name]
        if not self.routed:
            return h + self.ffn.call(p, u), None
        y, hit, held = self.ffn.routed(
            p, u.reshape(-1, u.shape[-1]),
            None if live is None else live.reshape(-1))
        return h + y.reshape(h.shape), (hit, held)

    def decode_step(self, params, cache, x, pos, live):
        u = self.attn_norm.call(params["attn_norm"], x)
        y, cache = self.attn.decode_step(params["attn"], u, cache, pos,
                                         live)
        x, counts = self._ffn(params, x + y, live)
        return x, cache, counts

    def block_pass(self, params, cache, x, first, prompt_len, carry):
        """One block of a prompt (``nn/latent.py``'s ``block_pass``), the
        feed-forward a block at a time too and over the real rows only."""
        u = self.attn_norm.call(params["attn_norm"], x)
        y, cache, carry = self.attn.block_pass(params["attn"], u, cache,
                                               first, carry, prompt_len)
        real = first + jnp.arange(x.shape[1])[None, :] < prompt_len[:, None]
        return self._ffn(params, x + y, real)[0], cache, carry


class Dots3ForCausalLM(Module):
    """The decoder with its untied head. Arguments carry the published
    config's names; ``experts_first``/``experts_held`` say which experts
    of every routed layer this holder keeps (default: all),
    ``max_position`` the positions a served stream may hold (rotary
    positions need no table) and ``prefill_block`` the queries a prompt
    pass walks at a time."""

    # which of ``ServingEngine``'s optional features the model carries
    # (serving/engine.py names them); the engine refuses the rest by name
    serving_features = frozenset()
    logits_dtype = jnp.float32

    def __init__(self, vocab_size=152064, hidden_size=5120,
                 intermediate_size=13824, moe_intermediate_size=1536,
                 layer_types=(FULL, FULL, SLIDING, SLIDING, SLIDING),
                 first_k_dense_replace=1, n_routed_experts=256,
                 n_shared_experts=1, num_experts_per_tok=8,
                 norm_topk_prob=True, routed_scaling_factor=1.0,
                 num_attention_heads=128, q_lora_rank=1024,
                 kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, rope_theta=8e7,
                 index_n_heads=64, index_head_dim=128, index_topk=2048,
                 swa_num_attention_heads=64, swa_q_lora_rank=1024,
                 swa_kv_lora_rank=1024, swa_qk_nope_head_dim=192,
                 swa_qk_rope_head_dim=64, swa_v_head_dim=128,
                 swa_rope_theta=5e4, sliding_window_size=513,
                 rms_norm_eps=1e-5, apply_mla_qkv_lora_rescale=True,
                 attention_gate_type="headwise",
                 swa_attention_gate_type="headwise", max_position=32768,
                 experts_first=0, experts_held=None, prefill_block=2048):
        super().__init__()
        for gate in (attention_gate_type, swa_attention_gate_type):
            if gate not in ("headwise", None):
                raise ValueError(f"unknown attention gate {gate!r}")
        prompt_blocks.check_positions(max_position, prefill_block)
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.max_position = max_position
        self.prefill_block = prefill_block
        self.index_topk = index_topk
        self.window = sliding_window_size
        cfg = dict(
            hidden_size=hidden_size, intermediate_size=intermediate_size,
            moe_intermediate_size=moe_intermediate_size,
            n_routed_experts=n_routed_experts,
            n_shared_experts=n_shared_experts,
            num_experts_per_tok=num_experts_per_tok,
            norm_topk_prob=norm_topk_prob,
            routed_scaling_factor=float(routed_scaling_factor),
            num_attention_heads=num_attention_heads,
            q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=float(rope_theta), index_n_heads=index_n_heads,
            index_head_dim=index_head_dim, index_topk=index_topk,
            swa_num_attention_heads=swa_num_attention_heads,
            swa_q_lora_rank=swa_q_lora_rank,
            swa_kv_lora_rank=swa_kv_lora_rank,
            swa_qk_nope_head_dim=swa_qk_nope_head_dim,
            swa_qk_rope_head_dim=swa_qk_rope_head_dim,
            swa_v_head_dim=swa_v_head_dim,
            swa_rope_theta=float(swa_rope_theta),
            sliding_window_size=sliding_window_size,
            rms_norm_eps=rms_norm_eps,
            rescale=bool(apply_mla_qkv_lora_rescale),
            gate=attention_gate_type == "headwise",
            swa_gate=swa_attention_gate_type == "headwise",
            experts_first=experts_first, experts_held=experts_held)
        self.layers = [Dots3Block(kind, i < first_k_dense_replace, cfg)
                       for i, kind in enumerate(layer_types)]
        self.out_norm = nn.RMSNorm(hidden_size, rms_norm_eps)
        # what the slot table stamps on its spans: the assignments a
        # token makes in a routed layer
        routed = any(l.routed for l in self.layers)
        self.experts_per_token = num_experts_per_tok if routed else 0

    def expert_rows(self, width, length):
        """The assignments a routed layer's call takes in a pass over
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

    # ------------------------------------------------------ a prompt pass --
    def _carries(self, batch, dtype):
        return [l.attn.init_carry(batch, dtype) for l in self.layers]

    def _block(self, params, cache, carries, ids, first, prompt_len):
        """Every layer over one block of ``ids`` (B, T) at positions
        ``first ..``: the hidden rows, the cache with the block's rows
        in, and what the window layers carry to the next block."""
        h = self._embed(params, ids)
        new_cache, new_carries = [], []
        for layer, p, c, carry in zip(self.layers, params["layers"], cache,
                                      carries):
            h, c, carry = layer.block_pass(p, c, h, first, prompt_len, carry)
            new_cache.append(c)
            new_carries.append(carry)
        return h, new_cache, new_carries

    def apply(self, params, state, x, *, training=False, rng=None):
        """``x`` (B, T) tokens -> logits ``(B x T, vocab)``, walked a block
        of ``prefill_block`` positions at a time."""
        b, t = x.shape
        block, n = prompt_blocks.block_count(self.prefill_block, t)
        ids = jnp.pad(x, ((0, 0), (0, n * block - t)))
        dtype = self.serving_dtype(params)
        cache = [l.attn.init_cache(b, n * block, dtype) for l in self.layers]
        lens = jnp.full((b,), t, jnp.int32)

        def one(carry, j):
            cache, carries = carry
            ids_j = lax.dynamic_slice_in_dim(ids, j * block, block, axis=1)
            h, cache, carries = self._block(params, cache, carries, ids_j,
                                            j * block, lens)
            return (cache, carries), h

        _, hs = lax.scan(one, (cache, self._carries(b, dtype)),
                         jnp.arange(n))
        h = hs.swapaxes(0, 1).reshape(b, n * block, -1)[:, :t]
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
            raise TypeError("Dots3ForCausalLM's cache is not laid out over "
                            "a mesh")
        return [l.attn.init_cache(batch, self.max_position, dtype)
                for l in self.layers]

    def cache_tables(self):
        """The index keys, every row up to ``pos`` read (by the step's
        own product, over the whole table); the latents, every row up to
        ``pos`` scored and ``index_topk`` of them read; the window's
        ring, written at ``pos mod window``. The last two are read
        through ``ops/latent_attention.py`` where it takes them, whole
        blocks of rows up to ``pos`` and the whole ring."""
        from bigdl_tpu.ops.latent_attention import fetched_rows
        from bigdl_tpu.serving.protocol import RowTable
        kinds = {l.kind for l in self.layers}
        k, w = self.index_topk, self.window
        tables = ()
        if FULL in kinds:
            tables += (
                RowTable(("kidx",), self.max_position, lambda pos: pos,
                         lambda pos: pos + 1, row_axis=1),
                RowTable(("ckv",), self.max_position, lambda pos: pos,
                         lambda pos: _least(pos + 1, k), row_axis=1,
                         selected=True, own_read=fetched_rows))
        if SLIDING in kinds:
            ring = next(l.attn.ring_rows for l in self.layers
                        if l.kind == SLIDING)

            def whole_ring(table):
                fetched = fetched_rows(table)
                return fetched and (
                    lambda pos: fetched(np.full_like(pos, ring - 1)))

            tables += (RowTable(("win",), ring, lambda pos: pos % w,
                                lambda pos: _least(pos + 1, w), row_axis=1,
                                own_read=whole_ring),)
        return tables

    def step_counts(self, pos):
        """What the step at the live slots' positions ``pos`` (numpy)
        scores and reads a layer of each kind: the rows a full layer's
        indexer scores, the rows it then reads, the rows a window layer
        reads."""
        n = np.asarray(pos, np.int64) + 1
        return {"dsa_context_rows": int(n.sum()),
                "dsa_selected_rows": int(np.minimum(n, self.index_topk).sum()),
                "swa_rows": int(np.minimum(n, self.window).sum())}

    def prefill_counts(self, prompt_len):
        """The same three, summed over every position of the prompts an
        admission prefills (numpy)."""
        n = np.asarray(prompt_len, np.int64)

        def capped(cap):                  # sum over p < n of min(p + 1, cap)
            m = np.minimum(n, cap)
            return int((m * (m + 1) // 2 + (n - m) * cap).sum())

        return {"dsa_context_rows": int((n * (n + 1) // 2).sum()),
                "dsa_selected_rows": capped(self.index_topk),
                "swa_rows": capped(self.window)}

    def prefill(self, params, cache, ids, prompt_len):
        """``ids`` (W, bucket) right-padded prompts, ``prompt_len`` (W,):
        returns the final-norm row at each prompt's last real position
        and ``cache`` as of each row's own length: the latents and index
        keys of every position walked (what lies past a row's length is
        junk that its steps overwrite before they read it), each ring
        slot holding its residue's newest real position. A block of
        ``prefill_block`` queries goes through every layer before the
        next; the blocks past the longest prompt are not walked
        (``models/prompt_blocks.py``)."""
        dtype = jax.tree_util.tree_leaves(cache)[0].dtype
        h_last, cache = prompt_blocks.walk(
            self.prefill_block, self.hidden_size, cache, ids, prompt_len,
            functools.partial(self._block, params),
            carries=lambda: self._carries(ids.shape[0], dtype),
            position_axes=[1 if l.kind == FULL else None
                           for l in self.layers])
        return self.out_norm.call(params["out_norm"], h_last), cache

    def decode_step(self, params, cache, tok, pos, in_place=False,
                    read=None, live=None):
        """One token a row at position ``pos`` (B,): ``(h, cache)`` with
        ``h`` (B, hidden) the final-norm rows. The slot table's two words
        stay unset: its kernels take tables of ``(slots, heads, rows,
        head_dim)`` read from row 0 on, and these have no head axis and
        one of them is read by selection (``cache_tables``). Given
        ``live`` (B,) bool the routed layers leave the dead rows out, and
        a model that has routed layers also returns, third and fourth,
        the means over them of how many of the experts HELD the live rows
        hit and of how many of their assignments fell on those (float32
        scalars)."""
        assert not in_place and read is None, (in_place, read)
        h = self._embed(params, tok)
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), tok.shape)
        new_cache, counts = [], []
        for layer, p, c in zip(self.layers, params["layers"], cache):
            h, c, n = layer.decode_step(p, c, h, pos, live)
            new_cache.append(c)
            if n is not None:
                counts.append(n)
        h = self.out_norm.call(params["out_norm"], h)
        if live is None or not counts:
            return h, new_cache
        hit, held = (jnp.mean(jnp.stack(c).astype(jnp.float32))
                     for c in zip(*counts))
        return h, new_cache, hit, held
