"""EvaByte style decoder: a byte-level causal LM whose attention (EVA) is
exact softmax inside a window and reads one learned summary a chunk of
everything before it, so a stream's state stops growing with its context.

Built from the published keys of the family's ``config.json``
(``model_type: evabyte``, ``attention_class: eva``); the layer equations
are the ones ``benchmarks/reference/evabyte.py`` writes down, and what is
taken on trust there is taken on trust here.
``N(x) = x * rsqrt(mean(x^2) + eps) * (1 + g)`` (``norm_add_unit_offset``).

- Model: ``h = E[byte]``; the layers; ``logits = N_out(h) @ W_head``,
  ``W_head`` ``hidden x (num_pred_heads x vocab)`` in ONE product, head
  ``r`` (columns ``r * vocab ..``) predicting byte ``i + 1 + r``. Serving
  picks the next byte from head 0 (:meth:`EvaByteForCausalLM.logits`);
  :meth:`EvaByteForCausalLM.apply` returns every head.
- Every layer: ``h += Attn(N(h))``; ``h += MLP(N(h))``, ``nn.GatedMLP``, no
  bias anywhere.
- ``Attn``: :class:`EvaAttention`. Position ``i`` lies in window
  ``i div W`` and chunk ``i div C``. Summary of a whole chunk ``c``, from
  the turned keys: ``a_cj = softmax_j(s phi . k_j)``, ``K_c = sum_j a_cj
  k_j + mu``, ``V_c = sum_j a_cj v_j``. Output at ``i``: ONE softmax over
  the exact keys of its own window up to ``i`` and the summaries of the
  chunks of EARLIER windows.

The residual stream, the norms, the softmax, the chunk weights and the
summaries' sums are float32; every matrix product takes its operands in
the weights' dtype and sums in float32; the window's K and V and the
summaries are kept in the cache's dtype, and every path rounds them to it
before it reads them, so a prefill followed by steps adds the same numbers
as one pass over the whole sequence does.

:class:`EvaByteForCausalLM` speaks the serving engine's model protocol
(``docs/serving.md``). Its cache is, a layer, the window's ``win_k`` and
``win_v`` of ``(slots, window, heads, head_dim)``, written at ``pos mod
window`` and starting over at every window boundary, and the summaries'
``sum_k`` and ``sum_v`` of ``(slots, max_position / chunk, heads,
head_dim)``, which gain a row each time a chunk closes and of which a step
reads the rows of closed windows. The ROWS come before the heads: with a
head of 128 the device keeps a table as it is shaped, so a row is whole
tiles of ``heads x head_dim`` and a step writes it where it lies (kept
``(slots, heads, rows, head_dim)`` the compiler turned every table over
for the one-row write and back, 16 ms of a 43 ms step on the chip). It
carries none of the engine's optional features.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.gated import mm
from bigdl_tpu.nn.module import Module
from bigdl_tpu.nn.rotary import apply_rotary, rotary_angles

# queries scored at a time inside a window: one block's scores against the
# window's keys and every summary are alive, not the window's
QUERY_BLOCK = 512


class EvaAttention(Module):
    """EVA attention over ``n_heads`` heads (as many K/V heads), rotary
    positions, a window of ``window`` positions and chunks of ``chunk``.
    ``phi`` and ``mu`` are one vector a head, kept ``(hidden,)``.

    Two forms of the one sum: :meth:`window_pass` over one window of a
    sequence given the summaries before it (what a prefill and the whole
    pass of ``EvaByteForCausalLM.apply`` walk), and :meth:`decode_step`
    for one position a row against the two tables."""

    def __init__(self, hidden_size, n_heads, window=2048, chunk=16,
                 rope_theta=100000.0):
        super().__init__()
        if window % chunk:
            raise ValueError(f"a window of {window} positions is not whole "
                             f"chunks of {chunk}")
        self.hidden_size = hidden_size
        self.n_heads = n_heads
        self.head_dim = hidden_size // n_heads
        self.window = window
        self.chunk = chunk
        self.rope_theta = rope_theta

    def make_params(self, rng, input_spec):
        d = self.hidden_size
        ks = jax.random.split(rng, 6)
        std = d ** -0.5
        return {"wq": jax.random.normal(ks[0], (d, d)) * std,
                "wk": jax.random.normal(ks[1], (d, d)) * std,
                "wv": jax.random.normal(ks[2], (d, d)) * std,
                "wo": jax.random.normal(ks[3], (d, d)) * std,
                "phi": jax.random.normal(ks[4], (d,)),
                "mu": jax.random.normal(ks[5], (d,)) * std}

    def _qkv(self, params, x, positions):
        """``x`` (B, T, hidden), ``positions`` (T,) or (B, T) -> q, k, v
        ``(B, T, heads, hd)`` float32, q and k turned."""
        b, t, _ = x.shape
        shape = (b, t, self.n_heads, self.head_dim)
        cos, sin = rotary_angles(positions, self.head_dim, self.rope_theta)
        if cos.ndim == 2:
            cos, sin = cos[None], sin[None]
        cos, sin = cos[:, :, None], sin[:, :, None]           # (B|1,T,1,hd)
        def heads(y):
            # the product stays (B, T, hidden) and is cut into heads after
            # it: folded into the product, the cut made the compiler
            # transpose each of the three matrices on every call (1.1 ms
            # of an 18.8 ms step on the chip)
            return lax.optimization_barrier(y).reshape(shape)

        return (apply_rotary(heads(mm(x, params["wq"])), cos, sin),
                apply_rotary(heads(mm(x, params["wk"])), cos, sin),
                heads(mm(x, params["wv"])))

    def _per_head(self, vec):
        return vec.astype(jnp.float32).reshape(self.n_heads, self.head_dim)

    def summarize(self, params, k, v):
        """The summaries of whole chunks: ``k``, ``v`` (B, n x chunk,
        heads, hd) as the tables hold them -> ``K_c``, ``V_c`` (B, n,
        heads, hd) float32."""
        b, t, h, d = k.shape
        kc = k.astype(jnp.float32).reshape(b, t // self.chunk, self.chunk,
                                           h, d)
        vc = v.astype(jnp.float32).reshape(kc.shape)
        a = jax.nn.softmax(
            jnp.sum(kc * self._per_head(params["phi"]), -1) * d ** -0.5,
            axis=2)[..., None]
        return (jnp.sum(a * kc, 2) + self._per_head(params["mu"]),
                jnp.sum(a * vc, 2))

    def _project(self, params, o):
        """The heads' outputs ``o`` (B, T, heads, hd) through ``wo``."""
        return mm(o.reshape(*o.shape[:2], -1), params["wo"])

    def _attend(self, q, k, v, sk, sv, local, seen):
        """ONE softmax over two sets of columns: ``q`` (B, Tq, heads, hd),
        or (B, heads, hd) for one query a row, against the exact
        ``k``/``v`` (B, Tk, heads, hd) under ``local`` and the summaries
        ``sk``/``sv`` (B, S, heads, hd) under ``seen`` (both broadcast to
        scores of (B, heads, [Tq,] columns)); operands in the tables'
        dtype, scores and softmax float32. Returns ``q``'s shape."""
        dt, scale = k.dtype, self.head_dim ** -0.5
        q = q.astype(dt)
        s_near = jnp.einsum("b...hd,bkhd->bh...k", q, k,
                            preferred_element_type=jnp.float32)
        s_far = jnp.einsum("b...hd,bchd->bh...c", q, sk,
                           preferred_element_type=jnp.float32)
        s_near = jnp.where(local, s_near * scale, -jnp.inf)
        s_far = jnp.where(seen, s_far * scale, -jnp.inf)
        # a query always sees its own position, so the maximum is finite
        top = jnp.maximum(s_near.max(-1), s_far.max(-1))[..., None]
        e_near, e_far = jnp.exp(s_near - top), jnp.exp(s_far - top)
        total = e_near.sum(-1, keepdims=True) + e_far.sum(-1, keepdims=True)
        return (jnp.einsum("bh...k,bkhd->b...hd", (e_near / total).astype(dt),
                           v, preferred_element_type=jnp.float32)
                + jnp.einsum("bh...c,bchd->b...hd",
                             (e_far / total).astype(dt), sv,
                             preferred_element_type=jnp.float32))

    def window_pass(self, params, x, first, sums):
        """One window of a sequence: ``x`` (B, T, hidden) the positions
        ``first .. first + T - 1`` (``T`` whole chunks, at most a window,
        ``first`` a multiple of ``T``), ``sums`` the two summary tables
        (B, S, heads, hd) holding the summaries of every chunk before
        ``first``. Returns ``(y, (k, v), sums)``: the attention's output,
        the window's K and V in the tables' dtype, and the tables with
        this window's own ``T / chunk`` summaries written at row ``first
        / chunk`` (its queries do not see them)."""
        sk, sv = sums
        t = x.shape[1]
        q, k, v = self._qkv(params, x, first + jnp.arange(t))
        k, v = k.astype(sk.dtype), v.astype(sv.dtype)
        # only whole windows before this one are seen: none while the
        # sequence is still inside its first
        seen = jnp.arange(sk.shape[1]) < (first // self.window) \
            * (self.window // self.chunk)
        block = min(t, QUERY_BLOCK)
        cols = jnp.arange(t)

        def some_queries(args):
            qb, at = args
            local = cols[None, :] <= (at + jnp.arange(block))[:, None]
            return self._attend(qb, k, v, sk, sv, local, seen)

        b, _, h, d = q.shape
        blocks = q.reshape(b, t // block, block, h, d).swapaxes(0, 1)
        o = lax.map(some_queries, (blocks, jnp.arange(0, t, block)))
        o = o.swapaxes(0, 1).reshape(b, t, h, d)
        new_k, new_v = self.summarize(params, k, v)
        row = first // self.chunk
        sums = (lax.dynamic_update_slice(sk, new_k.astype(sk.dtype),
                                         (0, row, 0, 0)),
                lax.dynamic_update_slice(sv, new_v.astype(sv.dtype),
                                         (0, row, 0, 0)))
        return self._project(params, o), (k, v), sums

    def init_cache(self, batch, max_len, dtype=jnp.float32):
        near = (batch, self.window, self.n_heads, self.head_dim)
        far = (batch, max_len // self.chunk, self.n_heads, self.head_dim)
        return {"win_k": jnp.zeros(near, dtype), "win_v": jnp.zeros(near, dtype),
                "sum_k": jnp.zeros(far, dtype), "sum_v": jnp.zeros(far, dtype)}

    def decode_step(self, params, x, cache, pos):
        """One position a row: ``x`` (B, hidden), ``pos`` (B,). Writes
        ``k``, ``v`` to window row ``pos mod window``, reads window rows
        ``0 .. pos mod window`` and the summaries of closed windows, and,
        where ``pos`` ends a chunk, forms that chunk's summary from the
        ``chunk`` window rows that end there and writes it to summary row
        ``pos div chunk``."""
        w, c = self.window, self.chunk
        pos = jnp.asarray(pos, jnp.int32)
        q, k, v = self._qkv(params, x[:, None], pos[:, None])
        row = pos % w
        wk = write_rows(cache["win_k"], k, row)
        wv = write_rows(cache["win_v"], v, row)
        # the chunk that ends at ``pos`` (where one does): its rows of the
        # window as just written, its summary beside the row's old one.
        # The table is viewed as whole chunks and one is taken a slot (a
        # slice of ``chunk`` rows at a row index makes the compiler turn
        # the whole table over for the gather)
        which = (row // c)[:, None, None, None, None]

        def rows_of(table):                    # (B, chunk, heads, hd)
            b, _, h, d = table.shape
            return jnp.take_along_axis(table.reshape(b, w // c, c, h, d),
                                       which, axis=1)[:, 0]

        new_k, new_v = self.summarize(params, rows_of(wk), rows_of(wv))
        closes = (pos % c == c - 1)[:, None, None, None]
        at_row = (pos // c)[:, None, None, None]
        sk, sv = cache["sum_k"], cache["sum_v"]
        sk = write_rows(sk, jnp.where(
            closes, new_k.astype(sk.dtype),
            jnp.take_along_axis(sk, at_row, axis=1)), pos // c)
        sv = write_rows(sv, jnp.where(
            closes, new_v.astype(sv.dtype),
            jnp.take_along_axis(sv, at_row, axis=1)), pos // c)
        local = jnp.arange(w)[None, :] <= row[:, None]              # (B, W)
        seen = jnp.arange(sk.shape[1])[None, :] \
            < ((pos // w) * (w // c))[:, None]                      # (B, S)
        # one query a row: scores of (B, heads, columns), no query axis
        o = self._attend(q[:, 0], wk, wv, sk, sv, local[:, None, :],
                         seen[:, None, :])
        return self._project(params, o[:, None])[:, 0], {
            "win_k": wk, "win_v": wv, "sum_k": sk, "sum_v": sv}


def write_rows(table, new, row):
    """``table`` (B, rows, heads, hd) with ``new`` (B, 1, heads, hd) put
    at ``[b, row[b]]``: one whole row a slot, each slot at its own."""
    def put(buf, one, i):
        return lax.dynamic_update_slice(buf, one.astype(buf.dtype),
                                        (i, 0, 0))

    return jax.vmap(put)(table, new, row)


def window_span(t, window, chunk):
    """``(span, n)``: a sequence of ``t`` positions is walked as ``n``
    spans of ``span`` positions: whole windows, or, where it fits inside
    one, the one span of its whole chunks."""
    span = min(window, -(-t // chunk) * chunk)
    return span, -(-t // span)


class EvaByteBlock(Module):
    """One layer: EVA attention and a gated MLP, each behind its norm,
    each added to the float32 residual stream."""

    def __init__(self, cfg):
        super().__init__()
        d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.attn_norm = nn.RMSNorm(d, eps, unit_offset=True)
        self.mlp_norm = nn.RMSNorm(d, eps, unit_offset=True)
        self.attn = EvaAttention(d, cfg["num_attention_heads"],
                                 cfg["window_size"], cfg["chunk_size"],
                                 cfg["rope_theta"])
        self.mlp = nn.GatedMLP(d, cfg["intermediate_size"])

    def setup(self, rng, input_spec):
        ks = jax.random.split(rng, 2)
        return {"attn_norm": self.attn_norm.make_params(None, None),
                "mlp_norm": self.mlp_norm.make_params(None, None),
                "attn": self.attn.make_params(ks[0], None),
                "mlp": self.mlp.make_params(ks[1], None)}, ()

    def _mlp(self, params, h):
        return h + self.mlp.call(params["mlp"],
                                 self.mlp_norm.call(params["mlp_norm"], h))

    def window_pass(self, params, x, first, sums):
        u = self.attn_norm.call(params["attn_norm"], x)
        y, kv, sums = self.attn.window_pass(params["attn"], u, first, sums)
        return self._mlp(params, x + y), kv, sums

    def decode_step(self, params, cache, x, pos):
        u = self.attn_norm.call(params["attn_norm"], x)
        y, cache = self.attn.decode_step(params["attn"], u, cache, pos)
        return self._mlp(params, x + y), cache


class EvaByteForCausalLM(Module):
    """The decoder with its untied head of ``num_pred_heads`` heads.
    Arguments carry the published config's names; ``max_position`` is the
    positions a served stream may hold (rotary positions need no table)."""

    # which of ``ServingEngine``'s optional features the model carries
    # (serving/engine.py names them); the engine refuses the rest by name
    serving_features = frozenset()
    logits_dtype = jnp.float32
    experts_per_token = 0

    def __init__(self, vocab_size=320, hidden_size=4096,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=32, window_size=2048, chunk_size=16,
                 num_pred_heads=8, rms_norm_eps=1e-5, rope_theta=100000.0,
                 max_position=32768):
        super().__init__()
        if max_position % window_size:
            raise ValueError(f"max_position {max_position} is not whole "
                             f"windows of {window_size}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.max_position = max_position
        self.window = window_size
        self.chunk = chunk_size
        self.num_pred_heads = num_pred_heads
        cfg = dict(hidden_size=hidden_size,
                   intermediate_size=intermediate_size,
                   num_attention_heads=num_attention_heads,
                   window_size=window_size, chunk_size=chunk_size,
                   rms_norm_eps=rms_norm_eps, rope_theta=float(rope_theta))
        self.layers = [EvaByteBlock(cfg) for _ in range(num_hidden_layers)]
        self.out_norm = nn.RMSNorm(hidden_size, rms_norm_eps,
                                   unit_offset=True)

    def setup(self, rng, input_spec):
        ks = jax.random.split(rng, len(self.layers) + 2)
        d, wide = self.hidden_size, self.num_pred_heads * self.vocab_size
        return {"tok_emb": 0.02 * jax.random.normal(
                    ks[0], (self.vocab_size, d)),
                "out_norm": self.out_norm.make_params(None, None),
                "head": jax.random.normal(ks[1], (d, wide)) * d ** -0.5,
                "layers": [l.setup(k, None)[0]
                           for l, k in zip(self.layers, ks[2:])]}, ()

    def _embed(self, params, ids):
        return jnp.take(params["tok_emb"], ids.astype(jnp.int32),
                        axis=0).astype(jnp.float32)

    def _window(self, params, ids, first, sums):
        """Every layer over one span of ``ids`` (B, T) at positions
        ``first ..``; ``sums`` a pair of summary tables a layer. Returns
        the hidden rows, each layer's K and V of the span, and the
        tables with the span's summaries in."""
        h = self._embed(params, ids)
        kvs, new_sums = [], []
        for layer, p, s in zip(self.layers, params["layers"], sums):
            h, kv, s = layer.window_pass(p, h, first, s)
            kvs.append(kv)
            new_sums.append(s)
        return h, kvs, new_sums

    def all_heads(self, params, h):
        """(…, hidden) final-norm rows -> (…, num_pred_heads x vocab)
        float32 logits, every head in the one product."""
        return mm(h, params["head"])

    def apply(self, params, state, x, *, training=False, rng=None):
        """``x`` (B, T) bytes -> every head's logits at every position,
        ``(B x T, num_pred_heads x vocab)``, walked a window at a time."""
        b, t = x.shape
        span, n = window_span(t, self.window, self.chunk)
        ids = jnp.pad(x, ((0, 0), (0, n * span - t)))
        dtype = self.serving_dtype(params)
        sums = [(c["sum_k"], c["sum_v"]) for c in
                (l.attn.init_cache(b, n * span, dtype) for l in self.layers)]

        def one(sums, j):
            ids_j = lax.dynamic_slice_in_dim(ids, j * span, span, axis=1)
            h, _, sums = self._window(params, ids_j, j * span, sums)
            return sums, h

        _, hs = lax.scan(one, sums, jnp.arange(n))
        h = hs.swapaxes(0, 1).reshape(b, n * span, -1)[:, :t]
        h = self.out_norm.call(params["out_norm"], h)
        return self.all_heads(params, h).reshape(
            -1, self.num_pred_heads * self.vocab_size), state

    # --------------------------------------------- the serving protocol --
    def serving_dtype(self, params):
        return params["tok_emb"].dtype

    def logits(self, params, h):
        """(…, hidden) final-norm rows -> (…, vocab) float32 logits of
        head 0, the head that picks the next byte; the product is the
        whole head matrix's."""
        return self.all_heads(params, h)[..., :self.vocab_size]

    def init_cache(self, batch, dtype=jnp.float32, sharding=None):
        if sharding is not None:
            raise TypeError("EvaByteForCausalLM's cache is not laid out "
                            "over a mesh")
        return [l.attn.init_cache(batch, self.max_position, dtype)
                for l in self.layers]

    def cache_tables(self):
        """The window, written at ``pos mod window`` and read up to it,
        and the summaries: row ``pos div chunk`` written where ``pos``
        ends a chunk, the rows of closed windows read."""
        from bigdl_tpu.serving.protocol import RowTable
        w, c = self.window, self.chunk
        return (RowTable(("win_k", "win_v"), w,
                         lambda pos: pos % w, lambda pos: pos % w + 1,
                         row_axis=1),
                RowTable(("sum_k", "sum_v"), self.max_position // c,
                         lambda pos: (pos % c == c - 1) * (pos // c + 1) - 1,
                         lambda pos: (pos // w) * (w // c), row_axis=1))

    def step_counts(self, pos):
        """What the step at the live slots' positions ``pos`` (numpy)
        needs of the two tables, and how many chunks it closes."""
        near, far = self.cache_tables()
        pos = np.asarray(pos, np.int64)
        return {"eva_window_rows": int(near.read_rows(pos).sum()),
                "eva_summary_rows": int(far.read_rows(pos).sum()),
                "eva_chunks_closed": int((far.write_row(pos) >= 0).sum())}

    def prefill_counts(self, prompt_len):
        """The windows a prefill of these prompts walks and the
        summaries it writes, over its real rows (numpy)."""
        n = np.asarray(prompt_len, np.int64)
        return {"eva_windows": int((-(-n // self.window)).sum()),
                "eva_chunks": int((n // self.chunk).sum())}

    def prefill(self, params, cache, ids, prompt_len):
        """``ids`` (W, bucket) right-padded prompts, ``prompt_len`` (W,):
        returns the final-norm row at each prompt's last real position
        and ``cache`` as of each row's own length: the window table holds
        the rows of the window that position ``prompt_len`` lies in, from
        row 0, and the summary table every whole chunk's summary,
        whatever the padding holds. The spans past the longest prompt
        are not walked."""
        b, bucket = ids.shape
        span, n = window_span(bucket, self.window, self.chunk)
        ids = jnp.pad(ids, ((0, 0), (0, n * span - bucket)))
        prompt_len = jnp.broadcast_to(jnp.asarray(prompt_len, jnp.int32),
                                      (b,))
        last = prompt_len - 1

        def one(j, carry):
            sums, wins, h_last = carry
            first = j * span
            ids_j = lax.dynamic_slice_in_dim(ids, first, span, axis=1)
            h, kvs, sums = self._window(params, ids_j, first, sums)
            # the window that the next position lies in keeps its rows
            keep = (prompt_len // self.window
                    == first // self.window)[:, None, None, None]
            wins = [tuple(jnp.where(keep, lax.dynamic_update_slice(
                              old, new, (0, 0, 0, 0)), old)
                          for old, new in zip(win, kv))
                    for win, kv in zip(wins, kvs)]
            row = jnp.take_along_axis(h, (last % span)[:, None, None],
                                      axis=1)[:, 0]
            h_last = jnp.where((last // span == j)[:, None], row, h_last)
            return sums, wins, h_last

        sums = [(c["sum_k"], c["sum_v"]) for c in cache]
        wins = [(c["win_k"], c["win_v"]) for c in cache]
        walked = (jnp.max(prompt_len) + span - 1) // span
        sums, wins, h_last = lax.fori_loop(
            0, walked, one,
            (sums, wins, jnp.zeros((b, self.hidden_size), jnp.float32)))
        cache = [{"win_k": w[0], "win_v": w[1], "sum_k": s[0], "sum_v": s[1]}
                 for w, s in zip(wins, sums)]
        return self.out_norm.call(params["out_norm"], h_last), cache

    def decode_step(self, params, cache, tok, pos, in_place=False,
                    read=None, live=None):
        """One byte a row at position ``pos`` (B,): ``(h, cache)`` with
        ``h`` (B, hidden) the final-norm rows. The slot table's two words
        stay unset: its kernels know tables of ``(slots, heads, rows,
        head_dim)`` read one a slot, and these keep the rows before the
        heads and are read two under one softmax (``cache_tables``). Its
        mask ``live`` is for those kernels and changes nothing here: the
        plain row writes take every row."""
        assert not in_place and read is None, (in_place, read)
        h = self._embed(params, tok)
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), tok.shape)
        new_cache = []
        for layer, p, c in zip(self.layers, params["layers"], cache):
            h, c = layer.decode_step(p, c, h, pos)
            new_cache.append(c)
        return self.out_norm.call(params["out_norm"], h), new_cache
