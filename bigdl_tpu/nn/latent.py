"""Latent attention (the DeepSeek-V2 line's MLA): a position keeps ONE
vector a layer, its normed K/V latent beside one turned rotary key shared
by every head, in place of per-head K and V; queries come through a
low-rank bottleneck. Two readers of that one cache row:

- :class:`SelectedLatentAttention`: a learned indexer (DeepSeek-V3.2's
  sparse attention) scores every earlier position and the softmax runs over
  the ``index_topk`` best of them only;
- :class:`WindowLatentAttention`: the softmax runs over the last ``window``
  positions, kept in a ring.

No reference analog. With ``u`` the layer's normed input at position ``t``,
``H`` heads, ``[a | b]`` a concatenation:

    c_q  = RMSNorm(u W_qa) * a_q                   a_q  = sqrt(hidden / q_lora_rank)
    [q_n^h | q_r^h] = c_q W_qb                     q_r^h = rope(q_r^h, t)
    [c_raw | k_raw] = u W_kva
    c_kv = RMSNorm(c_raw) * a_kv                   a_kv = sqrt(hidden / kv_lora_rank)
    k_r  = rope(k_raw, t)                          one for all heads
    [k_n^h | v^h](s) = c_kv(s) W_kvb               kept a head: W_kb^h, W_vb^h
    e_h(t, s) = (q_n^h . k_n^h(s) + q_r^h . k_r(s)) / sqrt(nope + rope)
    o_h = sum_s softmax_s(e_h(t, s)) v^h(s)        s over the positions read
    Attn = concat_h(sigmoid(u W_g)_h o_h) W_o      one gate scalar a head

(``rescale=False`` sets ``a_q = a_kv = 1``, ``gate=False`` leaves the gate
out.) The cache row is ``[c_kv | k_r]``, ``kv_lora_rank + rope`` numbers,
kept in whole lanes of 128 with zeros behind them.
Nothing here ever expands it to per-head K and V: ``W_kvb``'s key half is
multiplied into the query (``q_lat^h = q_n^h W_kb^h``, so ``q_n^h . k_n^h(s)
= q_lat^h . c_kv(s)``) and its value half into the output (``o_h = (sum_s p_s
c_kv(s)) W_vb^h``), for one query a slot and for a block of a prompt's
queries alike.

The residual stream, the norms, the softmax, the gate and the index scores'
sum are float32; every matrix product takes its operands in the weights'
dtype (the products against the cache in the cache's) and sums in float32;
cache rows are rounded to the cache's dtype before anything reads them, so
a prompt pass followed by steps adds the same numbers as one pass would.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn.gated import mm
from bigdl_tpu.nn.module import Module
from bigdl_tpu.nn.normalization import LayerNormalization, RMSNorm
from bigdl_tpu.nn.rotary import apply_rotary, rotary_angles
from bigdl_tpu.ops.sampling import _ordered as ordered_bits

# queries whose scores against one block of keys are alive at a time in a
# prompt pass: 128 heads x 512 queries x 2048 keys are 0.5 GB in float32
QUERY_SUB_BLOCK = 512


def write_rows(table, new, row):
    """``table`` (B, rows, width) with ``new`` (B, width) put at
    ``[b, row[b]]``: one row a slot, each slot at its own."""
    def put(buf, one, i):
        return lax.dynamic_update_slice(buf, one[None].astype(buf.dtype),
                                        (i, 0))

    return jax.vmap(put)(table, new, row)


def _kth_largest_key(key, k):
    """The ``k``-th largest of each row of int32 ``key`` (..., S),
    EXACTLY and without a sort: 32 halvings of the integer range, each one
    counting pass over the row, close on it."""
    def halve(_, bounds):
        lo, hi = bounds        # count(key >= lo) >= k > count(key >= hi)
        mid = (lo >> 1) + (hi >> 1) + (lo & hi & 1)
        enough = jnp.sum(key >= mid[..., None], axis=-1) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid)

    info = jnp.iinfo(jnp.int32)
    lo, _ = lax.fori_loop(
        0, 32, halve, (jnp.full(key.shape[:-1], info.min, jnp.int32),
                       jnp.full(key.shape[:-1], info.max, jnp.int32)))
    return lo


def top_k_mask(x, k):
    """Which entries of each row of ``x`` (..., S) are its ``k`` largest,
    as ``lax.top_k`` picks them (of equal entries the first), found by
    threshold: ``-inf`` marks an entry that is not there and is never
    picked, so a row with no more than ``k`` others has them all picked.
    Equal entries at the threshold are counted off only where a row has
    any (scores summed in float32 over 64 heads have none)."""
    # the float32 bits as the int32 that orders as they do, as
    # ``ops/sampling.py`` finds its cutoffs
    key = ordered_bits(lax.bitcast_convert_type(x, jnp.int32))
    edge = _kth_largest_key(key, k)[..., None]
    above = key > edge
    picked = (key >= edge) & (x > -jnp.inf)

    def count_off(picked):
        room = k - jnp.sum(above, axis=-1, keepdims=True)
        tied = picked & ~above
        return above | (tied & (jnp.cumsum(tied, axis=-1) <= room))

    return lax.cond(jnp.any(jnp.sum(picked, axis=-1) > k), count_off,
                    lambda picked: picked, picked)


def _turn(x, positions, theta, heads=False):
    """The rotary turn of ``x`` (..., width), or with ``heads`` (...,
    heads, width), at ``positions``: x's leading axes, or the last of
    them (one row of positions for every batch row)."""
    cos, sin = rotary_angles(positions, x.shape[-1], theta)
    if heads:
        cos, sin = cos[..., None, :], sin[..., None, :]
    return apply_rotary(x, cos, sin)


class LatentAttention(Module):
    """What the two readers share: the projections, the cache row and the
    softmax in the latent space. ``n_heads`` heads of ``nope_dim +
    rope_dim`` (queries and keys) and ``v_dim`` (values)."""

    def __init__(self, hidden_size, n_heads, q_lora_rank, kv_lora_rank,
                 nope_dim, rope_dim, v_dim, rope_theta=10000.0,
                 norm_eps=1e-5, rescale=True, gate=True):
        super().__init__()
        self.hidden_size = hidden_size
        self.n_heads = n_heads
        self.q_rank = q_lora_rank
        self.kv_rank = kv_lora_rank
        self.nope_dim = nope_dim
        self.rope_dim = rope_dim
        self.v_dim = v_dim
        self.rope_theta = float(rope_theta)
        self.gate = gate
        self.a_q = (hidden_size / q_lora_rank) ** 0.5 if rescale else 1.0
        self.a_kv = (hidden_size / kv_lora_rank) ** 0.5 if rescale else 1.0
        self.scale = (nope_dim + rope_dim) ** -0.5
        self.row_width = kv_lora_rank + rope_dim
        # a row is kept in whole lanes of 128, zeros behind it: the device
        # pads a tiled table's rows to that anyway, and left at 576 it
        # would rather keep the ROWS minor (nothing to pad), which turns a
        # one-row write into a write across lanes and the whole table
        # over before every read (two copies of 1.4 GB a step, seen when
        # compiling for a described v5e)
        self.table_width = -(-self.row_width // 128) * 128
        self.q_norm = RMSNorm(q_lora_rank, norm_eps)
        self.kv_norm = RMSNorm(kv_lora_rank, norm_eps)

    def make_params(self, rng, input_spec):
        d, h = self.hidden_size, self.n_heads
        ks = jax.random.split(rng, 6)

        def normal(k, shape):
            return jax.random.normal(k, shape) * shape[0] ** -0.5

        params = {
            "wqa": normal(ks[0], (d, self.q_rank)),
            "q_norm": self.q_norm.make_params(None, None),
            "wqb": normal(ks[1], (self.q_rank,
                                  h * (self.nope_dim + self.rope_dim))),
            "wkva": normal(ks[2], (d, self.row_width)),
            "kv_norm": self.kv_norm.make_params(None, None),
            # W_kvb, a head first and its two halves apart, as the step
            # multiplies them: kept (rank, heads x (nope + v)) every step
            # copied the whole matrix into this order (0.14 ms a window
            # layer on the chip)
            "wkb": jax.random.normal(ks[3], (h, self.nope_dim, self.kv_rank))
            * self.kv_rank ** -0.5,
            "wvb": jax.random.normal(jax.random.fold_in(ks[3], 1),
                                     (h, self.kv_rank, self.v_dim))
            * self.kv_rank ** -0.5,
            "wo": normal(ks[4], (h * self.v_dim, d))}
        if self.gate:
            params["wg"] = normal(ks[5], (d, h))
        return params

    # ------------------------------------------------------- projections --
    def _heads_q(self, params, u, positions):
        """``u`` (..., hidden) at ``positions`` (...,) -> the bottleneck
        ``c_q`` (..., q_rank) and the queries a head ``[q_n | q_r]``
        (..., heads, nope + rope), ``q_r`` turned, float32."""
        cq = self.q_norm.call(params["q_norm"], mm(u, params["wqa"])) \
            * self.a_q
        q = mm(cq, params["wqb"]).reshape(
            *u.shape[:-1], self.n_heads, self.nope_dim + self.rope_dim)
        q_r = _turn(q[..., self.nope_dim:], positions, self.rope_theta,
                    heads=True)
        return cq, jnp.concatenate([q[..., :self.nope_dim], q_r], axis=-1)

    def _queries(self, params, u, positions):
        """As :meth:`_heads_q` with the queries as the cache is read:
        ``[q_lat | q_r | 0]`` (..., heads, table_width), ``W_kvb``'s key
        half multiplied into ``q_n``."""
        cq, q = self._heads_q(params, u, positions)
        w_kb = params["wkb"]                               # (H, nope, rank)
        q_lat = jnp.einsum("...hd,hdc->...hc",
                           q[..., :self.nope_dim].astype(w_kb.dtype), w_kb,
                           preferred_element_type=jnp.float32)
        pad = jnp.zeros((*q.shape[:-1], self.table_width - self.row_width),
                        q.dtype)
        return cq, jnp.concatenate([q_lat, q[..., self.nope_dim:], pad],
                                   axis=-1)

    def _expand(self, params, rows):
        """Cache rows (B, K, kv_rank + rope) -> the keys (B, K, heads,
        nope + rope) and values (B, K, heads, v) they stand for, in the
        rows' dtype: what a block of a prompt's queries is scored
        against (one query a slot never expands a row)."""
        c = rows[..., :self.kv_rank]
        k_n = jnp.einsum("bkc,hdc->bkhd", c, params["wkb"].astype(c.dtype),
                         preferred_element_type=jnp.float32).astype(c.dtype)
        v = jnp.einsum("bkc,hcd->bkhd", c, params["wvb"].astype(c.dtype),
                       preferred_element_type=jnp.float32).astype(c.dtype)
        k_r = jnp.broadcast_to(
            rows[:, :, None, self.kv_rank:self.row_width],
            (*k_n.shape[:3], self.rope_dim))
        return jnp.concatenate([k_n, k_r], axis=-1), v

    def _row(self, params, u, positions):
        """The cache row of ``u`` (..., hidden): ``[c_kv | k_r | 0]``
        (..., table_width), float32."""
        kv = mm(u, params["wkva"])
        c = self.kv_norm.call(params["kv_norm"], kv[..., :self.kv_rank]) \
            * self.a_kv
        pad = jnp.zeros((*c.shape[:-1], self.table_width - self.row_width),
                        c.dtype)
        return jnp.concatenate(
            [c, _turn(kv[..., self.kv_rank:], positions, self.rope_theta),
             pad], axis=-1)

    def _attend(self, q, rows, valid, shared=False):
        """ONE softmax a query and head over the rows it reads: ``q``
        (B, Q, heads, width) against ``rows`` (B, Q, K, width), each
        query's own, or with ``shared`` (B, K, width), one set for all of a
        row's queries, under ``valid`` (B, Q, K). Returns the mixed
        latents (B, Q, heads, kv_rank) float32."""
        dt = rows.dtype
        keys = "bkc" if shared else "bqkc"
        s = jnp.einsum(f"bqhc,{keys}->bqhk", q.astype(dt), rows,
                       preferred_element_type=jnp.float32)
        s = jnp.where(valid[:, :, None, :], s * self.scale, -jnp.inf)
        # a query always reads its own position, so the maximum is finite
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum(f"bqhk,{keys}->bqhc", p.astype(dt),
                          rows[..., :self.kv_rank],
                          preferred_element_type=jnp.float32)

    def _read(self, q, table, valid, pos, live):
        """One query a slot, ``q`` (B, heads, width), against its own
        rows of ``table`` (B, rows, width) under ``valid`` (B, rows):
        the mixed latents (B, heads, kv_rank). Through
        ``ops/latent_attention.py`` where it applies to the table as
        allocated (each live slot's blocks of rows up to ``pos`` and
        nothing of a free slot), else every row of every slot, masked."""
        from bigdl_tpu.ops import latent_attention as kernel
        if not kernel.applies(table):
            return self._attend(q[:, None], table, valid[:, None],
                                shared=True)[:, 0]
        if live is None:
            live = jnp.ones(pos.shape, bool)
        return kernel.latent_attention(
            q * self.scale, table, jnp.where(valid, 0.0, -jnp.inf),
            pos, live, self.kv_rank)

    def _finish(self, params, u, o_lat, expanded=False):
        """Mixed latents ``o_lat`` (..., heads, kv_rank) -> the layer's
        output (..., hidden): ``W_kvb``'s value half, the gate, ``W_o``.
        With ``expanded`` they are mixed VALUES (..., heads, v) already."""
        o = o_lat
        if not expanded:
            w_vb = params["wvb"]                           # (H, rank, v)
            o = jnp.einsum("...hc,hcd->...hd", o_lat.astype(w_vb.dtype),
                           w_vb, preferred_element_type=jnp.float32)
        if self.gate:
            o = o * jax.nn.sigmoid(mm(u, params["wg"]))[..., None]
        return mm(o.reshape(*o.shape[:-2], -1), params["wo"])


class SelectedLatentAttention(LatentAttention):
    """Latent attention over the ``index_topk`` positions a learned
    indexer picks (all of them while there are no more):

        q_I^j = c_q W_Iq            ``index_heads`` x ``index_dim``, rope on the first ``rope_dim``
        k_I(s) = LayerNorm(u_s W_Ik)           ``index_dim``, rope likewise
        w(t) = u W_Iw                          one weight an index head
        I(t, s) = sum_j w_j(t) relu(q_I^j(t) . k_I(s))       summed in float32
        S_t = the ``index_topk`` positions s <= t with the largest I(t, s)

    The cache is ``ckv`` (slots, rows, table_width), the latents, and
    ``kidx`` (slots, rows, index_dim), the index keys. A step scores EVERY
    row of ``kidx`` up to its position and ``index_topk`` rows of ``ckv``
    count in its softmax: a table read by selection
    (``serving/protocol.py``). How the chosen rows are fetched is this
    chip's business: a gather of single rows costs more here than reading
    the context (``ops/latent_attention.py`` says why), so ``S_t`` is a
    mask, found by threshold (:func:`top_k_mask`), over the rows read."""

    def __init__(self, *args, index_heads=64, index_dim=128,
                 index_topk=2048, index_eps=1e-6, **kw):
        super().__init__(*args, **kw)
        self.index_heads = index_heads
        self.index_dim = index_dim
        self.index_topk = index_topk
        self.ik_norm = LayerNormalization(index_dim, index_eps)

    def make_params(self, rng, input_spec):
        params = super().make_params(rng, input_spec)
        d = self.hidden_size
        ks = jax.random.split(jax.random.fold_in(rng, 1), 3)
        params.update(
            wiq=jax.random.normal(ks[0], (self.q_rank, self.index_heads
                                          * self.index_dim))
            * self.q_rank ** -0.5,
            wik=jax.random.normal(ks[1], (d, self.index_dim)) * d ** -0.5,
            ik_norm=self.ik_norm.make_params(None, None),
            wiw=jax.random.normal(ks[2], (d, self.index_heads)) * d ** -0.5)
        return params

    def _turn_index(self, x, positions, heads=False):
        """An index vector with its first ``rope_dim`` numbers turned."""
        r = self.rope_dim
        return jnp.concatenate(
            [_turn(x[..., :r], positions, self.rope_theta, heads),
             x[..., r:]], axis=-1)

    def _index(self, params, u, cq, positions):
        """``(q_I (..., index_heads, index_dim), k_I (..., index_dim),
        w (..., index_heads))``, float32, q and k turned."""
        q = mm(cq, params["wiq"]).reshape(*u.shape[:-1], self.index_heads,
                                          self.index_dim)
        norm = {k: v.astype(jnp.float32)
                for k, v in params["ik_norm"].items()}
        k = self.ik_norm.call(norm, mm(u, params["wik"]))
        return (self._turn_index(q, positions, heads=True),
                self._turn_index(k, positions), mm(u, params["wiw"]))

    @staticmethod
    def _scores(q_i, w, keys):
        """``I`` of queries ``q_i`` (B, Q, J, D) with weights ``w``
        (B, Q, J) against ``keys`` (B, S, D): (B, Q, S) float32."""
        dots = jnp.einsum("bqjd,bsd->bqjs", q_i.astype(keys.dtype), keys,
                          preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(dots) * w[..., None], axis=2)

    def init_cache(self, batch, max_len, dtype=jnp.float32):
        return {"ckv": jnp.zeros((batch, max_len, self.table_width), dtype),
                "kidx": jnp.zeros((batch, max_len, self.index_dim), dtype)}

    def decode_step(self, params, u, cache, pos, live=None):
        """One position a slot: ``u`` (B, hidden), ``pos`` (B,). Writes
        row ``pos`` of both leaves, scores the index keys of rows
        ``0 .. pos``, marks the ``index_topk`` best and attends to them
        (:meth:`LatentAttention._read`). ``live`` (B,) bool marks the
        slots that hold a request; the others' rows come back as junk
        nobody reads."""
        cq, q = self._queries(params, u, pos)
        q_i, k_i, w = self._index(params, u, cq, pos)
        ckv = write_rows(cache["ckv"], self._row(params, u, pos), pos)
        kidx = write_rows(cache["kidx"], k_i, pos)
        scores = self._scores(q_i[:, None], w[:, None], kidx)[:, 0]
        rows_held = kidx.shape[1]
        seen = jnp.arange(rows_held)[None, :] <= pos[:, None]
        chosen = top_k_mask(jnp.where(seen, scores, -jnp.inf),
                            min(self.index_topk, rows_held))
        o_lat = self._read(q, ckv, chosen, pos, live)
        return self._finish(params, u, o_lat), {"ckv": ckv, "kidx": kidx}

    def init_carry(self, batch, dtype):
        """Nothing passes from one block of a prompt to the next but the
        cache itself."""
        return None

    def block_pass(self, params, u, cache, first, carry=None,
                   prompt_len=None):
        """One block of a prompt: ``u`` (B, T, hidden) the positions
        ``first .. first + T - 1`` (``first`` a multiple of ``T``),
        ``cache`` holding the rows of every position before ``first``.
        Writes the block's rows and scores every query against the index
        keys up to its own block (a block of ``T`` keys and
        ``QUERY_SUB_BLOCK`` queries at a time, so that no more than (sub,
        index_heads, T) products exist). A query's ``S_t`` is then the
        positions whose score reaches its ``index_topk``-th largest
        (:func:`top_k_mask`: a threshold, no sort and no gather, which
        the chip pays 18 ms a 512 queries and 19 ns a row for), and the
        block attends, a block of keys at a time with their keys and
        values EXPANDED once a block and one running softmax a query,
        under that mask. Returns ``(y, cache, carry)``; there is nothing
        to carry, and what lies past ``prompt_len`` is junk that a row's
        steps overwrite before they read it."""
        b, t, _ = u.shape
        positions = first + jnp.arange(t)
        cq, q = self._heads_q(params, u, positions)
        q_i, k_i, w = self._index(params, u, cq, positions)
        dt = cache["ckv"].dtype
        ckv = lax.dynamic_update_slice(
            cache["ckv"], self._row(params, u, positions).astype(dt),
            (0, first, 0))
        kidx = lax.dynamic_update_slice(cache["kidx"], k_i.astype(dt),
                                        (0, first, 0))
        rows_held = kidx.shape[1]
        sub = min(t, QUERY_SUB_BLOCK)
        n_keys = first // t + 1                 # key blocks up to this one

        def cut(x):                                        # (n, B, sub, ...)
            return x.reshape(b, t // sub, sub, *x.shape[2:]).swapaxes(0, 1)

        def join(x):                                       # (B, T, ...)
            return x.swapaxes(0, 1).reshape(b, t, *x.shape[3:])

        def score(args):
            qi, ws, at = args                              # (B, sub, ...)

            def some_keys(j, scores):
                keys = lax.dynamic_slice_in_dim(kidx, j * t, t, axis=1)
                return lax.dynamic_update_slice(
                    scores, self._scores(qi, ws, keys), (0, 0, j * t))

            scores = lax.fori_loop(
                0, n_keys, some_keys,
                jnp.full((b, sub, rows_held), -jnp.inf, jnp.float32))
            seen = jnp.arange(rows_held) <= (at + jnp.arange(sub))[:, None]
            return jnp.where(seen, scores, -jnp.inf)

        scores = join(lax.map(score, (cut(q_i), cut(w),
                                      first + jnp.arange(0, t, sub))))
        chosen = top_k_mask(scores, min(self.index_topk, rows_held))

        def some_keys(j, carry):
            k, v = self._expand(
                params, lax.dynamic_slice_in_dim(ckv, j * t, t, axis=1))
            ok = lax.dynamic_slice_in_dim(chosen, j * t, t, axis=2)

            def some_queries(args):
                qs, oks, top, total, acc = args
                e = jnp.einsum("bqhd,bkhd->bhqk", qs.astype(dt), k,
                               preferred_element_type=jnp.float32)
                e = jnp.where(oks[:, None], e * self.scale, -jnp.inf)
                new_top = jnp.maximum(top, e.max(-1))
                # a query that has read nothing yet keeps weight 0
                safe = jnp.where(new_top > -jnp.inf, new_top, 0.0)
                p = jnp.exp(e - safe[..., None])
                keep = jnp.exp(jnp.where(top > -jnp.inf, top - safe, -jnp.inf))
                acc = acc * keep[..., None] + jnp.einsum(
                    "bhqk,bkhd->bhqd", p.astype(dt), v,
                    preferred_element_type=jnp.float32)
                return new_top, total * keep + p.sum(-1), acc

            return lax.map(some_queries, (cut(q), cut(ok), *carry))

        heads, n_sub = self.n_heads, t // sub
        top, total, acc = lax.fori_loop(0, n_keys, some_keys, (
            jnp.full((n_sub, b, heads, sub), -jnp.inf, jnp.float32),
            jnp.zeros((n_sub, b, heads, sub), jnp.float32),
            jnp.zeros((n_sub, b, heads, sub, self.v_dim), jnp.float32)))
        o = join((acc / total[..., None]).transpose(0, 1, 3, 2, 4))
        return (self._finish(params, u, o, expanded=True),
                {"ckv": ckv, "kidx": kidx}, carry)


class WindowLatentAttention(LatentAttention):
    """Latent attention over the last ``window`` positions, the query's
    own counted: position ``t`` reads ``s`` with ``t - window < s <= t``.
    The cache is ``win`` (slots, ring_rows, kv_rank + rope), a ring
    written at ``pos mod window`` (``ring_rows`` is ``window`` rounded up
    to whole tiles of 128 rows; the rows past ``window`` are never
    written or read)."""

    def __init__(self, *args, window=513, **kw):
        super().__init__(*args, **kw)
        self.window = window
        self.ring_rows = -(-window // 128) * 128

    def init_cache(self, batch, max_len, dtype=jnp.float32):
        return {"win": jnp.zeros((batch, self.ring_rows, self.table_width),
                                 dtype)}

    def decode_step(self, params, u, cache, pos, live=None):
        """One position a slot: writes ring row ``pos mod window`` and
        reads the ``min(pos + 1, window)`` rows written so far."""
        _, q = self._queries(params, u, pos)
        win = write_rows(cache["win"], self._row(params, u, pos),
                         pos % self.window)
        valid = jnp.arange(self.ring_rows)[None, :] \
            < jnp.minimum(pos + 1, self.window)[:, None]
        # every ring row may count whatever the position: the kernel's
        # bound is the ring's last row
        o_lat = self._read(q, win, valid,
                           jnp.full_like(pos, self.ring_rows - 1), live)
        return self._finish(params, u, o_lat), {"win": win}

    def init_carry(self, batch, dtype):
        """The ``window - 1`` rows before a prompt's first block: none
        yet (they are masked as positions below 0)."""
        return jnp.zeros((batch, self.window - 1, self.table_width), dtype)

    def block_pass(self, params, u, cache, first, carry, prompt_len):
        """One block of a prompt, banded: ``carry`` (B, window - 1,
        width) the rows of the positions just before ``first``. Queries
        ``QUERY_SUB_BLOCK`` at a time against the slab of rows that their
        windows cover. The ring is left as the step at ``prompt_len``
        (B,) needs it: slot ``r`` holds the last position ``p <
        prompt_len`` with ``p mod window == r`` once that position has
        been walked. Returns ``(y, cache, carry)``."""
        b, t, _ = u.shape
        hist = self.window - 1
        positions = first + jnp.arange(t)
        _, q = self._queries(params, u, positions)
        dt = cache["win"].dtype
        new = self._row(params, u, positions).astype(dt)
        slab = jnp.concatenate([carry, new], axis=1)       # (B, hist + T, w)
        sub = min(t, QUERY_SUB_BLOCK)
        offs = jnp.arange(sub)
        cols = jnp.arange(hist + sub)

        def some_queries(args):
            qs, at = args                                  # at: offset in block
            rows = lax.dynamic_slice_in_dim(slab, at, hist + sub, axis=1)
            # column c of the slab is position first + at - hist + c
            kp = first + at - hist + cols[None, :]
            tq = first + at + offs[:, None]
            valid = (kp >= 0) & (kp <= tq) & (kp > tq - self.window)
            return self._attend(qs, rows,
                                jnp.broadcast_to(valid, (b,) + valid.shape),
                                shared=True)

        qs = q.reshape(b, t // sub, sub, *q.shape[2:]).swapaxes(0, 1)
        o_lat = lax.map(some_queries, (qs, jnp.arange(0, t, sub)))
        o_lat = o_lat.swapaxes(0, 1).reshape(b, t, *o_lat.shape[3:])
        # the ring: the newest real position of each residue, where it
        # lies in this block
        last = prompt_len[:, None] - 1
        r = jnp.arange(self.ring_rows)[None, :]
        holds = last - (last - r) % self.window            # (B, ring_rows)
        here = (r < self.window) & (holds >= first) & (holds < first + t)
        take = jnp.clip(holds - first, 0, t - 1)
        fresh = jnp.take_along_axis(new, take[..., None], axis=1)
        win = jnp.where(here[..., None], fresh, cache["win"])
        return (self._finish(params, u, o_lat), {"win": win},
                slab[:, t:])
