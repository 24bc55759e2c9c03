"""Decoder-only transformer (GPT-2 style) — pre-LN causal LM.

Beyond-parity model family: the reference's only language models are the
scan-based RNN/LSTM zoo (``models/rnn/SimpleRNN.scala``,
``example/languagemodel/PTBWordLM.scala``); this is the modern causal LM
on the same TPU-first primitives as BERT — causal flash attention
(pallas), ring/Ulysses sequence parallelism for long context, per-block
rematerialisation, tied embeddings.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.module import Module
from bigdl_tpu.ops import sampling
from bigdl_tpu.parallel.sequence import MultiHeadAttention


class TransformerDecoderBlock(Module):
    """Pre-LN causal block: x += attn(ln1(x)); x += mlp(ln2(x))."""

    def __init__(self, hidden_size, n_heads, intermediate_size=None,
                 dropout=0.0, sequence_parallel=None):
        super().__init__()
        self.hidden_size = hidden_size
        inter = intermediate_size or 4 * hidden_size
        self.attn = MultiHeadAttention(hidden_size, n_heads, dropout,
                                       sequence_parallel, causal=True)
        self.ln1 = nn.LayerNormalization(hidden_size)
        self.ln2 = nn.LayerNormalization(hidden_size)
        self.fc1 = nn.Linear(hidden_size, inter)
        self.fc2 = nn.Linear(inter, hidden_size)
        self.dropout = dropout

    def setup(self, rng, input_spec):
        ks = jax.random.split(rng, 5)
        params = {"attn": self.attn.setup(ks[0], input_spec)[0],
                  "ln1": self.ln1.setup(ks[1], None)[0],
                  "ln2": self.ln2.setup(ks[2], None)[0],
                  "fc1": self.fc1.setup(ks[3], None)[0],
                  "fc2": self.fc2.setup(ks[4], None)[0]}
        return params, ()

    def _drop(self, h, rng, i, training):
        if training and self.dropout > 0 and rng is not None:
            keep = jax.random.bernoulli(jax.random.fold_in(rng, i),
                                        1 - self.dropout, h.shape)
            h = jnp.where(keep, h / (1 - self.dropout), 0.0)
        return h

    def apply(self, params, state, x, *, training=False, rng=None):
        h = self.attn.call(params["attn"], self.ln1.call(params["ln1"], x))
        x = x + self._drop(h, rng, 0, training)
        h = self.fc2.call(params["fc2"], jax.nn.gelu(
            self.fc1.call(params["fc1"],
                          self.ln2.call(params["ln2"], x))))
        return x + self._drop(h, rng, 1, training), state

    def _mlp(self, params, x):
        return self.fc2.call(params["fc2"], jax.nn.gelu(
            self.fc1.call(params["fc1"], self.ln2.call(params["ln2"], x))))

    def prefill(self, params, cache, x):
        """Prompt pass with K/V capture (inference only, no dropout)."""
        h, cache = self.attn.prefill(params["attn"],
                                     self.ln1.call(params["ln1"], x), cache)
        x = x + h
        return x + self._mlp(params, x), cache

    def decode_step(self, params, cache, x, index, in_place=False,
                    read=None, live=None):
        """One incremental token (x: (B, 1, H)) through the block; the
        attention K/V for slot ``index`` land in ``cache`` (``in_place``,
        ``read`` and ``live``: see ``_MHA.decode_step``)."""
        h, cache = self.attn.decode_step(
            params["attn"], self.ln1.call(params["ln1"], x), cache, index,
            in_place=in_place, read=read, live=live)
        x = x + h
        return x + self._mlp(params, x), cache

    def decode_chunk(self, params, cache, x, pos):
        """C speculative tokens per row (x: (B, C, H)) through the block;
        K/V land at absolute positions ``pos[b] + j`` of ``cache`` (see
        ``_MHA.decode_chunk``)."""
        h, cache = self.attn.decode_chunk(
            params["attn"], self.ln1.call(params["ln1"], x), cache, pos)
        x = x + h
        return x + self._mlp(params, x), cache

    def paged_prefill_chunk(self, params, pool, x, pages, offsets,
                            page_table, q_pos):
        """Chunked-prefill pass through the block against this layer's
        page pool (see ``_MHA.paged_prefill_chunk``)."""
        h, pool = self.attn.paged_prefill_chunk(
            params["attn"], self.ln1.call(params["ln1"], x), pool,
            pages, offsets, page_table, q_pos)
        x = x + h
        return x + self._mlp(params, x), pool

    def paged_decode_step(self, params, pool, x, pages, offsets,
                          page_table, pos):
        """One incremental token (x: (B, 1, H)) through the block in
        paged mode; K/V land at (``pages``, ``offsets``) of ``pool``."""
        h, pool = self.attn.paged_decode_step(
            params["attn"], self.ln1.call(params["ln1"], x), pool,
            pages, offsets, page_table, pos)
        x = x + h
        return x + self._mlp(params, x), pool


class GPT(Module):
    """GPT-2-style decoder stack returning hidden states (B, T, H).

    ``sequence_parallel``: same option as BERT — ("ring_inner", axis, n)
    inside a dp x sp shard_map (make_sp_train_step works unchanged).
    ``remat``: recompute each block's activations in backward.
    """

    def __init__(self, vocab_size=50257, hidden_size=768, n_layers=12,
                 n_heads=12, max_position=1024, intermediate_size=None,
                 dropout=0.0, sequence_parallel=None, remat=False):
        super().__init__()
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.max_position = max_position
        self.layers = [TransformerDecoderBlock(hidden_size, n_heads,
                                               intermediate_size, dropout,
                                               sequence_parallel)
                       for _ in range(n_layers)]
        self.ln_f = nn.LayerNormalization(hidden_size)
        self.remat = remat

    def setup(self, rng, input_spec):
        ks = jax.random.split(rng, len(self.layers) + 3)
        std = 0.02
        params = {
            "tok_emb": std * jax.random.normal(
                ks[0], (self.vocab_size, self.hidden_size)),
            "pos_emb": std * jax.random.normal(
                ks[1], (self.max_position, self.hidden_size)),
            "ln_f": self.ln_f.setup(ks[2], None)[0],
            "layers": [l.setup(k, None)[0]
                       for l, k in zip(self.layers, ks[3:])],
        }
        return params, ()

    def apply(self, params, state, x, *, training=False, rng=None):
        ids = x.astype(jnp.int32)
        t = ids.shape[1]
        h = jnp.take(params["tok_emb"], ids, axis=0)
        sp = self.layers[0].attn.sequence_parallel if self.layers else None
        if sp is not None and sp[0] == "ring_inner":
            from jax import lax
            start = lax.axis_index(sp[1]) * t
            pos = lax.dynamic_slice_in_dim(params["pos_emb"], start, t)
            h = h + pos[None]
        else:
            h = h + params["pos_emb"][None, :t]
        for i, layer in enumerate(self.layers):
            r = jax.random.fold_in(rng, i) if rng is not None else None
            if self.remat:
                def block(p, hh, _layer=layer, _r=r):
                    return _layer.apply(p, (), hh, training=training,
                                        rng=_r)[0]
                h = jax.checkpoint(block)(params["layers"][i], h)
            else:
                h, _ = layer.apply(params["layers"][i], (), h,
                                   training=training, rng=r)
        return self.ln_f.call(params["ln_f"], h), state

    # ------------------------------------------------ KV-cache decoding --
    def init_cache(self, batch, dtype=jnp.float32, sharding=None):
        """Per-layer K/V buffers sized for the full position table:
        ``n_layers`` dicts of (B, n_heads, max_position, head_dim).
        ``sharding`` (head axis over tp — ``parallel/layout.py``)
        commits every layer's buffers onto the mesh."""
        return [l.attn.init_cache(batch, self.max_position, dtype,
                                  sharding=sharding)
                for l in self.layers]

    def prefill(self, params, cache, ids, prompt_len):
        """Fill the cache from a (bucket-padded) prompt in ONE batched
        causal forward and return (h_last, cache), where ``h_last`` is the
        final-norm hidden state at the last REAL prompt position.
        ``prompt_len`` is traced — a scalar (one shared length) or a (B,)
        vector (per-row lengths, the serving engine's batched admission) —
        so prompts of different lengths inside one bucket share the
        executable."""
        ids = ids.astype(jnp.int32)
        t = ids.shape[1]
        h = jnp.take(params["tok_emb"], ids, axis=0) \
            + params["pos_emb"][None, :t]
        new_cache = []
        for i, layer in enumerate(self.layers):
            h, c = layer.prefill(params["layers"][i], cache[i], h)
            new_cache.append(c)
        h = self.ln_f.call(params["ln_f"], h)
        idx = jnp.asarray(prompt_len, jnp.int32) - 1
        if idx.ndim == 0:
            return jnp.take(h, idx, axis=1), new_cache
        return (jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0],
                new_cache)

    def decode_step(self, params, cache, tok, pos, in_place=False,
                    read=None, live=None):
        """One incremental token: embed ``tok`` (B,) at position ``pos``
        (traced scalar, or a (B,) vector when every row sits at its own
        length — the serving engine's slot batch), run every block in
        cache mode, and return the (B, H) final-norm hidden state plus
        the updated cache. ``in_place`` is the slot table's word that
        its buffers take the write kernel, ``live`` its (B,) mask of the
        rows that kernel writes, ``read`` its per-row counts for the
        length-bounded attention (``_MHA.decode_step``)."""
        h = jnp.take(params["tok_emb"], tok.astype(jnp.int32), axis=0)
        h = h + jnp.take(params["pos_emb"], jnp.asarray(pos, jnp.int32),
                         axis=0)
        h = h[:, None, :]
        new_cache = []
        for i, layer in enumerate(self.layers):
            h, c = layer.decode_step(params["layers"][i], cache[i], h, pos,
                                     in_place=in_place, read=read,
                                     live=live)
            new_cache.append(c)
        h = self.ln_f.call(params["ln_f"], h)
        return h[:, 0], new_cache

    def decode_chunk(self, params, cache, toks, pos):
        """Multi-token verify for speculative decoding: embed ``toks``
        (B, C) at absolute positions ``pos[b] + j`` (``pos`` (B,) or
        scalar — each row's committed length), run every block's
        ``decode_chunk``, and return the (B, C, H) final-norm hidden
        states plus the updated cache. Writes past ``max_position`` are
        dropped and the position embedding is clipped, so overshooting
        rows produce masked junk instead of corruption."""
        pos = jnp.asarray(pos, jnp.int32)
        if pos.ndim == 0:
            pos = jnp.broadcast_to(pos, (toks.shape[0],))
        idx = pos[:, None] + jnp.arange(toks.shape[1],
                                        dtype=jnp.int32)[None, :]
        h = jnp.take(params["tok_emb"], toks.astype(jnp.int32), axis=0)
        h = h + jnp.take(params["pos_emb"],
                         jnp.clip(idx, 0, self.max_position - 1), axis=0)
        new_cache = []
        for i, layer in enumerate(self.layers):
            h, c = layer.decode_chunk(params["layers"][i], cache[i], h,
                                      pos)
            new_cache.append(c)
        return self.ln_f.call(params["ln_f"], h), new_cache

    # --------------------------------------------- paged K/V decoding --
    def init_paged_pool(self, num_pages, page_size, dtype=jnp.float32,
                        sharding=None):
        """Per-layer global K/V page pools: ``n_layers`` dicts of
        (num_pages, n_heads, page_size, head_dim). One page index means
        the same page in every layer's pool, so a single per-slot page
        table (and the host allocator's refcounts) cover the whole
        stack. ``sharding`` is the 4-D plane's ``NamedSharding`` (head
        axis over tp); int8 scale planes derive theirs from it."""
        return [l.attn.init_paged_pool(num_pages, page_size, dtype,
                                       sharding=sharding)
                for l in self.layers]

    def _paged_chunk(self, params, pools, page_table, ids, start,
                     nvalid, write_from, page_size):
        """Shared chunk core for paged prefill AND speculative verify:
        run C tokens per row through every block against the page pools,
        writing positions ``[max(start, write_from), start + nvalid)``
        (and ``< max_position``) through the table and scattering
        everything else to the dropped sentinel page. Returns the FULL
        (W, C, H) final-norm hidden states plus the new pools."""
        ids = ids.astype(jnp.int32)
        w, c = ids.shape
        p = page_table.shape[1]
        start = jnp.asarray(start, jnp.int32)
        nvalid = jnp.asarray(nvalid, jnp.int32)
        write_from = jnp.asarray(write_from, jnp.int32)
        j = jnp.arange(c, dtype=jnp.int32)[None, :]
        pos = start[:, None] + j                                  # (W, C)
        h = jnp.take(params["tok_emb"], ids, axis=0) \
            + jnp.take(params["pos_emb"],
                       jnp.clip(pos, 0, self.max_position - 1), axis=0)
        writable = ((j < nvalid[:, None]) & (pos >= write_from[:, None])
                    & (pos < self.max_position))
        page_idx = jnp.clip(pos // page_size, 0, p - 1)
        pages = jnp.where(writable,
                          jnp.take_along_axis(page_table, page_idx, axis=1),
                          jnp.iinfo(jnp.int32).max)   # OOB -> dropped
        offsets = pos % page_size
        new_pools = []
        for i, layer in enumerate(self.layers):
            h, pl = layer.paged_prefill_chunk(
                params["layers"][i], pools[i], h, pages, offsets,
                page_table, pos)
            new_pools.append(pl)
        return self.ln_f.call(params["ln_f"], h), new_pools

    def paged_prefill_chunk(self, params, pools, page_table, ids, start,
                            nvalid, write_from, page_size):
        """One chunk of chunked prefill over up to W rows: ``ids``
        (W, C) tokens, row ``i`` covering absolute positions
        ``[start[i], start[i] + nvalid[i])`` of its prompt. K/V are
        written through ``page_table`` (W, P) — only positions
        ``>= write_from[i]`` (the prefix-shared boundary; ``write_from
        >= start + nvalid`` suppresses all writes, the logits-only
        replay of a fully shared prompt) and ``< start + nvalid``;
        everything else scatters to the dropped sentinel page. Returns
        (h_last, pools) where ``h_last`` is the final-norm hidden state
        at each row's last valid chunk offset — the next-token logits
        input when the chunk is a prompt's final one."""
        h, new_pools = self._paged_chunk(params, pools, page_table, ids,
                                         start, nvalid, write_from,
                                         page_size)
        c = ids.shape[1]
        idx = jnp.clip(jnp.asarray(nvalid, jnp.int32) - 1, 0, c - 1)
        return (jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0],
                new_pools)

    def paged_verify_chunk(self, params, pools, page_table, toks, pos,
                           page_size):
        """Multi-token speculative verify in paged mode: ``toks`` (B, C)
        proposals per slot starting at each row's committed length
        ``pos`` (B,), written through the page table (sentinel rows of
        pageless/inactive slots drop every write — rejected speculative
        tokens can only ever land in pages the slot owns) and attended
        with per-query causal masking. Returns ALL C hidden states
        (B, C, H) — the acceptance rule needs the target logits at every
        proposal position — plus the new pools. Rollback is the caller
        not advancing its write position: rejected positions sit past
        the committed length, masked off and rewritten by the next
        chunk."""
        pos = jnp.asarray(pos, jnp.int32)
        c = toks.shape[1]
        nvalid = jnp.full(pos.shape, c, jnp.int32)
        return self._paged_chunk(params, pools, page_table, toks, pos,
                                 nvalid, pos, page_size)

    def paged_decode_step(self, params, pools, page_table, tok, pos,
                          page_size):
        """One incremental token per slot in paged mode: like
        ``decode_step`` but K/V are written at page
        ``page_table[s, pos // page_size]`` offset ``pos % page_size``
        (the sentinel rows of pageless slots drop the write) and
        attention reads through the page table."""
        pos = jnp.asarray(pos, jnp.int32)
        h = jnp.take(params["tok_emb"], tok.astype(jnp.int32), axis=0)
        h = h + jnp.take(params["pos_emb"], pos, axis=0)
        h = h[:, None, :]
        pages = jnp.take_along_axis(page_table,
                                    (pos // page_size)[:, None],
                                    axis=1)[:, 0]
        offsets = pos % page_size
        new_pools = []
        for i, layer in enumerate(self.layers):
            h, pl = layer.paged_decode_step(
                params["layers"][i], pools[i], h, pages, offsets,
                page_table, pos)
            new_pools.append(pl)
        h = self.ln_f.call(params["ln_f"], h)
        return h[:, 0], new_pools


def prompt_bucket(t, max_position):
    """Static prefill length for a ``t``-token prompt: the next power of
    two (floor 16), capped at ``max_position``. Prompts are right-padded
    to the bucket so nearby lengths share one prefill executable instead
    of compiling per length; the real length rides along as a traced
    scalar."""
    b = 16
    while b < t:
        b <<= 1
    return min(b, max_position) if max_position >= t else t


def sample_logits(logits, key, temperature=1.0, top_k=None, top_p=None):
    """Batched token sampling over (B, vocab) logits.

    Temperature scaling, then optional top-k truncation, then optional
    nucleus (top-p) truncation, then one categorical draw per row.
    ``top_k``/``top_p`` are compile-time config (``top_k`` fixes the
    lax.top_k output shape); ``temperature`` may be traced. Trace-safe —
    this is the per-step sampler inside the jitted decode scan, but it
    works the same on the host. Greedy decoding (temperature 0) is the
    caller's static branch: ``jnp.argmax(logits, -1)``.
    """
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k is not None and top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix whose mass reaches top_p (always >= 1:
        # the exclusive cumulative mass of the first token is 0 < top_p)
        keep = jnp.sum((cum - probs < top_p).astype(jnp.int32), axis=-1,
                       keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, keep - 1, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


class GPTForCausalLM(Module):
    """GPT + tied-embedding LM head -> (B*T, vocab) logits.

    Pair with ``CrossEntropyCriterion`` on next-token labels
    (``labels = ids shifted left``); flatten labels to (B*T,).
    """

    def __init__(self, vocab_size=50257, hidden_size=768, n_layers=12,
                 n_heads=12, max_position=1024, tie_embeddings=True, **kw):
        super().__init__()
        self.vocab_size = vocab_size
        self.tie_embeddings = tie_embeddings
        self.gpt = GPT(vocab_size=vocab_size, hidden_size=hidden_size,
                       n_layers=n_layers, n_heads=n_heads,
                       max_position=max_position, **kw)
        self.head = None if tie_embeddings \
            else nn.Linear(hidden_size, vocab_size, with_bias=False)

    def setup(self, rng, input_spec):
        k1, k2 = jax.random.split(rng)
        params = {"gpt": self.gpt.setup(k1, input_spec)[0]}
        if self.head is not None:
            params["head"] = self.head.setup(k2, None)[0]
        return params, ()

    def apply(self, params, state, x, *, training=False, rng=None):
        h, _ = self.gpt.apply(params["gpt"], (), x,
                              training=training, rng=rng)
        if self.head is not None:
            logits = self.head.call(params["head"], h)
        else:  # GPT-2 ties the output projection to the token embedding
            logits = h @ params["gpt"]["tok_emb"].T
        return logits.reshape(-1, self.vocab_size), state

    def _lm_logits(self, params, h):
        """(…, H) hidden states -> (…, vocab) logits via the tied (or
        separate) LM head."""
        if self.head is not None:
            return self.head.call(params["head"], h)
        return h @ params["gpt"]["tok_emb"].T

    # ------------------------------------------ the serving protocol --
    # (bigdl_tpu/serving/protocol.py): the engine and the dense slot
    # table reach ``.gpt`` through these and through nothing else; the
    # features below (paging, speculation, LoRA, int8, tp, snapshots)
    # still reach into ``.gpt`` from their own modules
    serving_features = frozenset(("paged", "spec_tokens", "lora",
                                  "int8_weights", "int8_kv", "tp",
                                  "kv_snapshot"))
    logits_dtype = None
    experts_per_token = 0
    logits = _lm_logits

    @property
    def max_position(self):
        return self.gpt.max_position

    def serving_dtype(self, params):
        return params["gpt"]["tok_emb"].dtype

    def check_servable(self):
        if self.gpt.layers and \
                self.gpt.layers[0].attn.sequence_parallel is not None:
            raise ValueError(
                "serving does not compose with sequence_parallel; build "
                "the model without it for generation")

    def init_cache(self, batch, dtype=jnp.float32, sharding=None):
        return self.gpt.init_cache(batch, dtype, sharding=sharding)

    def cache_tables(self):
        from bigdl_tpu.serving.protocol import positions_table
        return (positions_table(self.max_position),)

    def prefill(self, params, cache, ids, prompt_len):
        return self.gpt.prefill(params["gpt"], cache, ids, prompt_len)

    def decode_step(self, params, cache, tok, pos, in_place=False,
                    read=None, live=None):
        return self.gpt.decode_step(params["gpt"], cache, tok, pos,
                                    in_place=in_place, read=read, live=live)

    def partition_specs(self, params, spec=None):
        """Canonical GSPMD PartitionSpec pytree for ``params`` — the
        model owns the parameter-name -> layout-role mapping
        (``parallel/layout.SpecLayout`` owns the role -> axes table):
        vocab-sharded embeddings, Megatron column-parallel QKV / fc1,
        row-parallel wo / fc2, replicated norms and position table.
        Int8 leaves (``nn/quantized``: ``{"q", "scale"}`` under the
        weight's name) inherit the weight's spec; the per-output-channel
        scale vector takes the weight's OUTPUT-dim sharding, so a
        column-parallel weight's scales split with its columns."""
        if spec is None:
            from bigdl_tpu.parallel.layout import SpecLayout
            spec = SpecLayout()
        from jax.sharding import PartitionSpec as PS

        def role(names):
            name = names[-1]
            if name in ("q", "scale") and len(names) > 1:
                base = role(names[:-1])
                if name == "q":
                    return base
                parts = tuple(base)
                return PS(parts[-1]) if parts else PS()
            parent = names[-2] if len(names) > 1 else None
            if name == "tok_emb":
                return spec.embeddings()
            if name == "pos_emb":
                return spec.position_embeddings()
            if name in ("wq", "wk", "wv"):
                return spec.qkv_projection()
            if name == "wo":
                return spec.attention_output()
            if parent == "fc1":
                return spec.ffn_up() if name == "weight" \
                    else spec.ffn_up_bias()
            if parent == "fc2":
                return spec.ffn_down() if name == "weight" else spec.norm()
            if parent == "head":
                return spec.lm_head() if name == "weight" else spec.norm()
            return spec.norm()          # ln1/ln2/ln_f and anything else

        def one(path, leaf):
            names = tuple(p.key for p in path if hasattr(p, "key")
                          and isinstance(p.key, str))
            return role(names) if names else PS()

        return jax.tree_util.tree_map_with_path(one, params)

    @property
    def decode_stats(self):
        """{'prefill_traces', 'decode_traces', 'dispatches'} — compile
        (trace) and dispatch counters for the KV-cache generate path
        (a ``utils.profiling.DecodeCounters``, shared machinery with the
        serving engine's gates), consumed by the recompile-count
        regression test."""
        stats = getattr(self, "_decode_stats", None)
        if stats is None:
            from bigdl_tpu.utils.profiling import DecodeCounters
            stats = self._decode_stats = DecodeCounters(
                "prefill_traces", "decode_traces", obs_name="gpt")
        return stats

    def _generate_fns(self):
        """Build (once per instance) the two jitted halves of KV-cache
        generation; jax's executable cache then keys on shapes/static
        config, so one generate() call costs at most 2 compilations."""
        fns = getattr(self, "_gen_fns", None)
        if fns is not None:
            return fns
        stats = self.decode_stats

        def prefill(params, ids, prompt_len):
            stats.tick("prefill_traces")   # trace-time only: counts compiles
            cache = self.gpt.init_cache(
                ids.shape[0], dtype=params["gpt"]["tok_emb"].dtype)
            h_last, cache = self.gpt.prefill(params["gpt"], cache, ids,
                                             prompt_len)
            return self._lm_logits(params, h_last), cache

        def decode(params, cache, logits, key, prompt_len, temperature,
                   n_new, greedy, top_k, top_p, kernel):
            stats.tick("decode_traces")    # trace-time only: counts compiles
            # ``kernel``: the caller's word that ``ops/sampling.py`` applies
            # to the logits as prefill returned them (the cuts by
            # threshold, no sort; the same key and the same kept set)
            draw = sampling.threshold_sample_logits if kernel \
                else sample_logits

            def step(carry, _):
                cache, logits, key, pos = carry
                if greedy:
                    tok = jnp.argmax(logits, axis=-1)
                else:
                    key, sub = jax.random.split(key)
                    tok = draw(logits, sub, temperature, top_k, top_p)
                tok = tok.astype(jnp.int32)
                h, cache = self.gpt.decode_step(params["gpt"], cache, tok,
                                                pos)
                return (cache, self._lm_logits(params, h), key,
                        pos + 1), tok

            pos0 = jnp.asarray(prompt_len, jnp.int32)
            _, toks = lax.scan(step, (cache, logits, key, pos0), None,
                               length=n_new)
            return toks.T                  # (n_new, B) -> (B, n_new)

        # the padded prompt, the cache, the prefill logits and the key are
        # all single-use buffers — donate them; params are reused across
        # calls and deliberately are not
        fns = (jax.jit(prefill, donate_argnums=(1,)),
               jax.jit(decode, static_argnums=(6, 7, 8, 9, 10),
                       donate_argnums=(1, 2, 3)))
        self._gen_fns = fns
        return fns

    def _spec_fns(self, gamma):
        """Jitted halves of SPECULATIVE greedy generation (one pair per
        draft length ``gamma``) — same 2-compile / 2-dispatch budget as
        the sequential pair, but each loop iteration commits 1..gamma
        tokens from one ``decode_chunk`` verify forward.

        The decode half is a ``lax.while_loop`` over per-row commit
        counts, not a fixed-length scan: rows advance at their own
        accept rate and the loop exits when the SLOWEST row has
        ``n_new`` tokens (worst case n_new iterations — sequential
        speed; best case n_new/gamma). Rows that finish early freeze
        (``adv = 0``) so their positions never overflow; their spill
        past ``n_new`` is dropped by the output scatter's bounds."""
        fns = getattr(self, "_spec_gen_fns", None)
        if fns is None:
            fns = self._spec_gen_fns = {}
        if gamma in fns:
            return fns[gamma]
        from bigdl_tpu.models.spec import NGramDraft, accept_counts
        stats = self.decode_stats
        draft = NGramDraft(self.vocab_size)

        def prefill(params, ids, prompt_len):
            stats.tick("prefill_traces")
            b = ids.shape[0]
            cache = self.gpt.init_cache(
                b, dtype=params["gpt"]["tok_emb"].dtype)
            h_last, cache = self.gpt.prefill(params["gpt"], cache, ids,
                                             prompt_len)
            pl = jnp.asarray(prompt_len, jnp.int32)
            table = draft.prime(draft.init_state(b), ids,
                                jnp.broadcast_to(pl, (b,)))
            last = jnp.take(ids.astype(jnp.int32), pl - 1, axis=1)
            return self._lm_logits(params, h_last), cache, table, last

        def decode(params, cache, logits, prompt_len, n_new, table, last):
            stats.tick("decode_traces")
            b = logits.shape[0]
            width = n_new + gamma
            pos0 = jnp.asarray(prompt_len, jnp.int32)
            g_iota = jnp.arange(gamma, dtype=jnp.int32)[None, :]
            rows = jnp.broadcast_to(
                jnp.arange(b, dtype=jnp.int32)[:, None], (b, gamma))

            def cond(st):
                return jnp.min(st[3]) < n_new

            def body(st):
                cache, logits, out, count, table, last = st
                tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                props = draft.propose(table, tok0, gamma)      # (B, g)
                h, cache = self.gpt.decode_chunk(params["gpt"], cache,
                                                 props, pos0 + count)
                acc, carry = accept_counts(props,
                                           self._lm_logits(params, h))
                adv = jnp.where(count >= n_new, 0, acc)
                mask = g_iota < adv[:, None]
                cols = jnp.where(mask, count[:, None] + g_iota, width)
                out = out.at[rows, cols].set(props, mode="drop")
                prevs = jnp.concatenate([last[:, None], props[:, :-1]],
                                        axis=1)
                # Draft.observe is the n-gram table update (a pure
                # array scatter), not an obs histogram
                # jaxlint: disable-next-line=span-in-jit
                table = draft.observe(table, prevs, props, mask)
                lastc = jnp.take_along_axis(props, (acc - 1)[:, None],
                                            axis=1)[:, 0]
                keep = adv > 0
                last = jnp.where(keep, lastc, last)
                logits = jnp.where(keep[:, None],
                                   carry.astype(logits.dtype), logits)
                return (cache, logits, out, count + adv, table, last)

            st = (cache, logits, jnp.zeros((b, width), jnp.int32),
                  jnp.zeros((b,), jnp.int32), table, last)
            out = lax.while_loop(cond, body, st)[2]
            return out[:, :n_new]

        pair = (jax.jit(prefill, donate_argnums=(1,)),
                jax.jit(decode, static_argnums=(4,),
                        donate_argnums=(1, 2, 5, 6)))
        fns[gamma] = pair
        return pair

    def generate(self, params, ids, n_new, temperature=0.0, rng=None,
                 top_k=None, top_p=None, spec_tokens=None):
        """Sample ``n_new`` continuation tokens (greedy at temperature 0,
        otherwise temperature/top-k/top-p sampling from ``rng``).

        KV-cache decoding: a jitted prefill fills per-layer K/V caches
        from the prompt in one batched causal forward (flash-selected by
        ``flash_profitable``), then ONE jitted ``lax.scan`` emits all
        ``n_new`` tokens incrementally against the cache — O(T) attention
        per token inside 2 compilations and O(1) dispatches, instead of
        the O(T²) full recompute that re-traced on every grown sequence
        length. Prompts are right-padded to a ``prompt_bucket`` so nearby
        lengths share the prefill executable; temperature-0 output is
        token-identical to the full-recompute loop. Generations that
        would overflow ``max_position`` fall back to the sliding-window
        loop (a static cache cannot represent the shifting positions).

        ``spec_tokens`` > 1 (or ``BIGDL_TPU_SPEC_DECODE=1`` with
        ``BIGDL_TPU_SPEC_TOKENS``) enables speculative decoding on the
        greedy path: an on-device n-gram draft proposes that many tokens
        per iteration and one ``decode_chunk`` forward verifies them —
        same 2-compile / 2-dispatch budget, token-identical output, up
        to ``spec_tokens``-fold fewer target-model forwards on
        repetitive text (models/spec.py). Sampled generation ignores it
        (speculation would need a rejection-sampling rule to keep the
        output distribution; greedy needs only argmax equality).
        """
        ids = jnp.asarray(ids, jnp.int32)
        if ids.ndim == 1:
            ids = ids[None]
        if n_new <= 0:
            return ids
        t = ids.shape[1]
        sp = (self.gpt.layers[0].attn.sequence_parallel
              if self.gpt.layers else None)
        if t + n_new > self.gpt.max_position or sp is not None:
            return self._generate_sliding(params, ids, n_new, temperature,
                                          rng, top_k, top_p)
        greedy = temperature is None or float(temperature) <= 0.0
        if rng is None:
            rng = jax.random.key(0)      # unused when greedy
        bucket = prompt_bucket(t, self.gpt.max_position)
        ids_pad = jnp.pad(ids, ((0, 0), (0, bucket - t)))
        from bigdl_tpu.models.spec import spec_config
        gamma = (max(int(spec_tokens), 1) if spec_tokens is not None
                 else spec_config())
        if greedy and gamma > 1:
            prefill_fn, decode_fn = self._spec_fns(gamma)
            logits0, cache, table, last = prefill_fn(params, ids_pad, t)
            toks = decode_fn(params, cache, logits0, t, int(n_new),
                             table, last)
            self.decode_stats.dispatched(2)
            return jnp.concatenate([ids, toks.astype(jnp.int32)], axis=1)
        prefill_fn, decode_fn = self._generate_fns()
        logits0, cache = prefill_fn(params, ids_pad, t)
        toks = decode_fn(params, cache, logits0, rng, t,
                         0.0 if temperature is None else temperature,
                         int(n_new), greedy, top_k, top_p,
                         not greedy and sampling.applies(logits0))
        self.decode_stats.dispatched(2)
        return jnp.concatenate([ids, toks.astype(jnp.int32)], axis=1)

    def _generate_sliding(self, params, ids, n_new, temperature, rng,
                          top_k=None, top_p=None):
        """Full-recompute sliding-window decode for generations that
        overflow ``max_position`` (the window shift re-positions every
        token each step, which a static K/V cache cannot express) or for
        sequence-parallel builds. O(T²) per token and one dispatch per
        token — the pre-KV-cache behavior, kept for exactly these
        cases."""
        window = self.gpt.max_position

        def next_logits(p, cur):
            h, _ = self.gpt.apply(p["gpt"], (), cur, training=False)
            return self._lm_logits(p, h[:, -1])

        # each step's window slice is a fresh buffer — donate it; params
        # are reused every step and stay undonated
        step = jax.jit(next_logits, donate_argnames=("cur",))
        greedy = temperature is None or float(temperature) <= 0.0
        for _ in range(n_new):
            logits = step(params, ids[:, -window:])
            if greedy:
                nxt = jnp.argmax(logits, axis=-1)
            else:
                rng, k = jax.random.split(rng)
                nxt = sample_logits(logits, k, temperature, top_k, top_p)
            ids = jnp.concatenate([ids, nxt[:, None].astype(jnp.int32)], 1)
        return ids


def gpt2_small(**kw):
    """GPT-2 124M config (12L, 768H, 12 heads, 1024 ctx)."""
    return GPTForCausalLM(**kw)
