"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has NO long-context story (SURVEY.md section 5: sequence
handling is a single-device time loop, ``nn/Recurrent.scala:47``) — this is
green-field TPU design, required for capability-parity at modern scale:

- **Ring attention**: Q stays put; K/V blocks rotate around the mesh axis via
  ``lax.ppermute`` while a flash-attention-style online softmax (running max
  + normalizer) accumulates the output. Peak memory per chip is
  O(T_local^2) instead of O(T^2), and the ring rides neighbouring ICI links.
- **Ulysses**: ``lax.all_to_all`` reshards (seq-sharded, all heads) ->
  (full seq, head-sharded), runs ordinary attention per head group, then
  reshards back. Cheaper for moderate T, needs heads % ndev == 0.

Both are pure shard_map programs usable inside any jitted train step.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P


def flash_profitable(t, causal=False):
    """Shape heuristic for auto-selecting the pallas flash kernel.

    Measured on v5e (BASELINE.md round-2 kernel table): the pallas kernel
    beats XLA's fused attention from S>=2048 causal and S>=8192
    bidirectional; below those, XLA's small-score-matrix fusion wins. The
    kernel's tiling contract additionally needs S % 128 == 0.
    """
    return t % 128 == 0 and t >= (2048 if causal else 8192)


def _attention_block(q, k, v, scale, mask=None):
    """Plain attention scores for one (q-block, k-block) pair.
    q: (B, H, Tq, D); k/v: (B, H, Tk, D)."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    return scores


def full_attention(q, k, v, causal=False):
    """Single-device reference attention (the oracle for the parallel ones)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    mask = None
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((tq, tk), bool))[None, None]
    scores = _attention_block(q, k, v, scale, mask)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def cached_attention(q, k, v, cur_len):
    """Single-query attention against a preallocated K/V cache — the
    decode-phase inner op of KV-cache generation.

    ``q``: (B, H, 1, D), the current token's query. ``k``/``v``:
    (B, H, S, D) cache buffers of which only the first ``cur_len`` slots
    hold real keys; the preallocated tail is masked out. ``cur_len`` is
    either a traced scalar (every row at the same position — the
    ``generate`` path) or a traced (B,) vector (each row at its own
    length — the serving engine's slot batch); both keep one executable
    across all decode positions. O(S·D) work per token instead of the
    O(T²) full-recompute score matrix, and the buffers never change
    shape, so a whole decode loop runs inside one ``lax.scan``. The
    causal constraint is implied: slot ``cur_len - 1`` is the query's
    own position, everything later is masked.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = k.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    cur = jnp.asarray(cur_len, jnp.int32)
    valid = jnp.arange(s)[None, :] < jnp.reshape(cur, (-1, 1))  # (1|B, S)
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def paged_gather(pool, page_table):
    """Materialize per-row K or V views from a paged pool.

    ``pool``: (num_pages, H, page_size, D) — the global page pool one
    layer owns. ``page_table``: (B, P) int32 page indices per row, in
    position order; rows cover positions [0, P*page_size). Out-of-range
    indices (the allocator's ``num_pages`` sentinel for unallocated
    pages) clip to the last page — junk the caller's length/causal mask
    must exclude. Returns (B, H, P*page_size, D), position-contiguous,
    so the result drops into :func:`cached_attention` unchanged.
    """
    b, p = page_table.shape
    n, h, ps, d = pool.shape
    out = jnp.take(pool, page_table, axis=0, mode="clip")  # (B,P,H,ps,D)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, h, p * ps, d)


def paged_write(pool, new, pages, offsets):
    """Scatter per-token K or V values into a paged pool.

    ``new``: (B, H, C, D) values for C tokens per row; ``pages``/
    ``offsets``: (B, C) int32 — global page index and within-page offset
    of each token. An out-of-bounds page index (the ``num_pages``
    sentinel) DROPS the write, which is how padding rows, masked chunk
    positions and pageless slots are expressed without a branch.
    """
    b, h, c, d = new.shape
    vals = new.transpose(0, 2, 1, 3).reshape(b * c, h, d)
    return pool.at[pages.reshape(-1), :, offsets.reshape(-1), :].set(
        vals.astype(pool.dtype), mode="drop")


def paged_write_quant(pool, scales, new, pages, offsets):
    """Quantize-on-write variant of :func:`paged_write` for int8 pools.

    Each written token vector is quantized symmetrically against its own
    per-(token, head) amax, and the f32 scale lands in ``scales``
    (num_pages, H, page_size) at the same (page, head, offset) as the
    int8 values — so dequantisation never rescales previously written
    tokens, and speculative rewrites of rejected positions stay
    self-consistent (each write carries its own scale). The same
    sentinel-index drop semantics apply to both scatters.
    """
    b, h, c, d = new.shape
    vals = (new.transpose(0, 2, 1, 3).reshape(b * c, h, d)
            .astype(jnp.float32))
    amax = jnp.max(jnp.abs(vals), axis=-1)                    # (B*C, H)
    sc = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(vals / sc[..., None]),
                 -127, 127).astype(jnp.int8)
    pg, off = pages.reshape(-1), offsets.reshape(-1)
    pool = pool.at[pg, :, off, :].set(q, mode="drop")
    scales = scales.at[pg, :, off].set(sc.astype(scales.dtype),
                                       mode="drop")
    return pool, scales


def paged_gather_dequant(pool, scales, page_table, dtype):
    """Gather an int8 page pool into a dense per-row view and dequantise
    with the per-(page, head, offset) scales written by
    :func:`paged_write_quant`. Returns (B, H, P*page_size, D) in
    ``dtype`` — drop-in for :func:`paged_gather`'s output."""
    k = paged_gather(pool, page_table)                # (B, H, S, D) int8
    b, p = page_table.shape
    _, h, ps = scales.shape
    s = jnp.take(scales, page_table, axis=0, mode="clip")  # (B, P, H, ps)
    s = s.transpose(0, 2, 1, 3).reshape(b, h, p * ps)
    return k.astype(dtype) * s[..., None].astype(dtype)


def paged_attention(q, k, v, q_pos):
    """Chunk attention against gathered paged K/V with per-query
    positions: key slot ``j`` is visible to the query at absolute
    position ``p`` iff ``j <= p`` — causality and the written-length
    mask in one predicate (positions past a row's write frontier are
    junk, but they are all ``> p``). ``q``: (B, H, C, D); ``k``/``v``:
    (B, H, S, D) from :func:`paged_gather`; ``q_pos``: (B, C) traced
    absolute positions. The C == 1 case degenerates to
    :func:`cached_attention` with ``cur_len = q_pos + 1``.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = k.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    valid = jnp.arange(s)[None, None, None, :] \
        <= jnp.asarray(q_pos, jnp.int32)[:, None, :, None]
    scores = jnp.where(valid, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def ring_attention(q, k, v, mesh, axis="seq", causal=False,
                   use_flash=False):
    """Attention over sequences sharded along ``axis`` (dim 2 of BHTD).

    Returns output sharded the same way. One jitted program; K/V travel
    the ring once (ndev-1 ppermutes). ``use_flash`` runs each chunk pair
    through the pallas flash kernel (ops/flash_attention.py) and combines
    chunks by logsumexp — O(T_local·D) VMEM per pair instead of the
    (T_local, T_local) score block.
    """
    ndev = mesh.shape[axis]

    def local(q_blk, k_blk, v_blk):
        body = _ring_local_flash if use_flash else _ring_local
        return body(q_blk, k_blk, v_blk, axis, ndev, causal)

    spec = P(None, None, axis, None)
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _ring_local_flash(q, k, v, axis, ndev, causal):
    """Ring body on the pallas flash kernel: chunk i's visibility under the
    causal mask is decided OUTSIDE the kernel — for static ring step i>0 the
    source block sits strictly before us (full attention, included iff
    my >= i) or strictly after (excluded); only i == 0 needs the causal
    diagonal kernel. Per-chunk (o, lse) combine by logsumexp weighting, all
    differentiable (the lse cotangent is handled inside the kernel vjp)."""
    from bigdl_tpu.ops.flash_attention import flash_attention_with_lse

    my = lax.axis_index(axis)
    perm = [(j, (j + 1) % ndev) for j in range(ndev)]
    k_cur, v_cur = k, v
    os_, lses = [], []
    for i in range(ndev):
        o_i, lse_i = flash_attention_with_lse(
            q, k_cur, v_cur, causal=causal and i == 0)
        if causal and i > 0:
            include = my >= i          # source block is earlier than ours
            lse_i = jnp.where(include, lse_i, -jnp.inf)
        os_.append(o_i.astype(jnp.float32))
        lses.append(lse_i)
        if i < ndev - 1:
            k_cur = lax.ppermute(k_cur, axis, perm)
            v_cur = lax.ppermute(v_cur, axis, perm)
    lse_stack = jnp.stack(lses)                      # (ndev, B, H, T)
    lse_max = jnp.max(lse_stack, axis=0)
    w = jnp.exp(lse_stack - lse_max[None])           # masked chunks -> 0
    denom = jnp.maximum(jnp.sum(w, axis=0), 1e-30)
    out = sum(w[i][..., None] * os_[i] for i in range(ndev)) / denom[..., None]
    return out.astype(q.dtype)


def _ring_local(q, k, v, axis, ndev, causal):
    """Per-device ring body. q/k/v: (B, H, T_local, D)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    my = lax.axis_index(axis)
    t_local = q.shape[2]
    b, h, _, d = q.shape
    # online-softmax accumulators (flash-attention style)
    o = jnp.zeros(q.shape, jnp.float32)
    l = jnp.zeros((b, h, t_local), jnp.float32)
    m = jnp.full((b, h, t_local), -jnp.inf, jnp.float32)
    perm = [(j, (j + 1) % ndev) for j in range(ndev)]

    def body(i, carry):
        o, l, m, k_cur, v_cur = carry
        src = (my - i) % ndev  # which global block k_cur/v_cur came from
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k_cur).astype(jnp.float32) \
            * scale
        if causal:
            q_pos = my * t_local + jnp.arange(t_local)
            k_pos = src * t_local + jnp.arange(t_local)
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        blk_max = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m, blk_max)
        # guard fully-masked rows (exp(-inf - -inf))
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(scores - safe_m[..., None])
        p = jnp.where(jnp.isfinite(scores), p, 0.0)
        correction = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l_new = l * correction + jnp.sum(p, axis=-1)
        o_new = (o * correction[..., None]
                 + jnp.einsum("bhqk,bhkd->bhqd", p,
                              v_cur.astype(jnp.float32)))
        k_next = lax.ppermute(k_cur, axis, perm)
        v_next = lax.ppermute(v_cur, axis, perm)
        return o_new, l_new, m_new, k_next, v_next

    o, l, m, _, _ = lax.fori_loop(0, ndev, body, (o, l, m, k, v))
    return (o / jnp.maximum(l[..., None], 1e-20)).astype(q.dtype)


def ulysses_attention(q, k, v, mesh, axis="seq", causal=False,
                      use_flash=None):
    """All-to-all sequence parallelism (Ulysses): seq-sharded -> head-sharded
    full-sequence attention -> seq-sharded. Heads must divide the axis size.
    ``use_flash`` runs the per-device full-sequence attention through the
    pallas flash kernel; ``None`` = auto by ``flash_profitable`` on the
    full (gathered) sequence length."""
    ndev = mesh.shape[axis]
    n_heads = q.shape[1]
    if n_heads % ndev:
        raise ValueError(f"heads {n_heads} not divisible by mesh axis {ndev}")
    if use_flash is None:
        # q is the global (pre-shard_map) array: dim 2 IS the full length
        use_flash = (jax.default_backend() == "tpu"
                     and flash_profitable(q.shape[2], causal))

    def local(q_blk, k_blk, v_blk):
        # (B, H, T_local, D) -> all_to_all -> (B, H_local, T, D)
        def a2a(x):
            return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

        def a2a_back(x):
            return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

        qf, kf, vf = a2a(q_blk), a2a(k_blk), a2a(v_blk)
        if use_flash and qf.shape[2] % 128 == 0:
            from bigdl_tpu.ops.flash_attention import flash_attention
            out = flash_attention(qf, kf, vf, causal=causal)
        else:
            out = full_attention(qf, kf, vf, causal=causal)
        return a2a_back(out)

    spec = P(None, None, axis, None)
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def sequence_attention(q, k, v, mesh, axis="seq", causal=False,
                       use_flash=None):
    """Auto-select the sequence-parallel attention kernel for the shape:

    - Ulysses (all-to-all) when heads divide the mesh axis — one a2a each
      way is cheaper than ``ndev-1`` ppermute rounds for moderate T;
    - ring attention otherwise (fully general, O(T_local^2) peak memory,
      K/V ride neighbouring ICI links).

    The per-device attention inside either path picks pallas flash vs XLA
    by ``flash_profitable`` (use_flash=None). This closes the manual-
    selection gap: callers that don't care pick this; the specific kernels
    stay public for callers that do.
    """
    ndev = mesh.shape[axis]
    if q.shape[1] % ndev == 0:
        return ulysses_attention(q, k, v, mesh, axis, causal=causal,
                                 use_flash=use_flash)
    return ring_attention(q, k, v, mesh, axis, causal=causal,
                          use_flash=bool(use_flash))


# --------------------------------------------------------------- nn module --

class MultiHeadAttention:
    """Multi-head self-attention module (transformer primitive the reference
    lacks; needed for the BERT-config parity, BASELINE.md).

    ``sequence_parallel``: None | ("ring"|"ulysses", mesh, axis) — selects the
    distributed attention kernel inside ``apply``.

    ``use_flash``: run local attention through the pallas flash kernel
    (ops/flash_attention.py) — O(S·D) HBM traffic instead of the O(S²)
    score matrix. ``None`` (default) = auto: on TPU the kernel is selected
    whenever ``flash_profitable`` says it beats XLA for the shape; the
    BIGDL_TPU_FLASH_ATTENTION flag forces it on (1) or off (0) globally.
    Explicit True still falls back to XLA when the sequence doesn't satisfy
    the kernel's 128-multiple tiling contract.
    """

    def __new__(cls, hidden_size, n_heads, dropout=0.0,
                sequence_parallel=None, causal=False, use_flash=None):
        from bigdl_tpu.nn.module import Module
        from bigdl_tpu.nn.quantized import qmatmul
        if hidden_size % n_heads:
            raise ValueError(f"hidden_size {hidden_size} must be divisible "
                             f"by n_heads {n_heads}")

        class _MHA(Module):
            def __init__(self):
                super().__init__()
                self.hidden_size = hidden_size
                self.n_heads = n_heads
                self.head_dim = hidden_size // n_heads
                self.causal = causal
                self.sequence_parallel = sequence_parallel
                from bigdl_tpu.utils.engine import get_flag
                if use_flash is None:
                    # auto: flag forces on/off; unset -> per-shape heuristic
                    self.use_flash = get_flag(
                        "BIGDL_TPU_FLASH_ATTENTION", None, bool)
                else:
                    self.use_flash = use_flash
                # pallas paged-attention decode kernel (ops/
                # paged_attention.py): streams K/V pages through the
                # page table instead of materializing the dense gather.
                # Off by default — the XLA gather path is bit-identical
                # to before. ``paged_kernel_mesh`` is (Mesh, tp_axis)
                # when the pools are head-sharded (PagedSlotManager
                # plumbs it in), None single-device.
                self.use_paged_kernel = bool(get_flag(
                    "BIGDL_TPU_PAGED_KERNEL", False, bool))
                self.paged_kernel_mesh = None

            def make_params(self, rng, input_spec):
                from bigdl_tpu.nn.init_methods import Xavier
                ks = jax.random.split(rng, 4)
                hs = hidden_size
                init = Xavier()
                return {k: init.init(kk, (hs, hs), fan_in=hs, fan_out=hs)
                        for k, kk in zip(("wq", "wk", "wv", "wo"), ks)}

            def _qkv(self, params, x):
                b, t, _ = x.shape
                nh, hd = self.n_heads, self.head_dim

                def split(name):
                    # qmatmul routes int8 quantize_params leaves through
                    # the MXU's s8xs8->s32 path; plain arrays are x @ w
                    y = qmatmul(x, params[name])
                    return y.reshape(b, t, nh, hd).transpose(0, 2, 1, 3)

                return split("wq"), split("wk"), split("wv")

            def call(self, params, x):
                b, t, hs = x.shape
                q, k, v = self._qkv(params, x)
                sp = self.sequence_parallel
                uf = self.use_flash
                if uf is None:
                    uf = (jax.default_backend() == "tpu"
                          and flash_profitable(t, self.causal))
                if sp is None:
                    if uf and t % 128 == 0:
                        from bigdl_tpu.ops.flash_attention import \
                            flash_attention
                        out = flash_attention(q, k, v, causal=self.causal)
                    else:
                        out = full_attention(q, k, v, causal=self.causal)
                elif sp[0] == "ring_inner":
                    # already inside a shard_map that carries the seq axis
                    # (e.g. a dp x sp train step): run the per-device ring
                    # body directly, no nested shard_map
                    _, axis, ndev = sp
                    out = _ring_local(q, k, v, axis, ndev, self.causal)
                else:
                    kind, mesh, axis = sp
                    if kind == "ring":
                        # ring flash works on local chunks whose length is
                        # unknown here; only an explicit True opts in
                        out = ring_attention(q, k, v, mesh, axis,
                                             causal=self.causal,
                                             use_flash=bool(self.use_flash))
                    else:
                        out = ulysses_attention(q, k, v, mesh, axis,
                                                causal=self.causal,
                                                use_flash=self.use_flash)
                out = out.transpose(0, 2, 1, 3).reshape(b, t, hs)
                return qmatmul(out, params["wo"])

            # ---------------------------------------- KV-cache decoding --
            def init_cache(self, batch, max_len, dtype=jnp.float32,
                           sharding=None):
                """Preallocated K/V buffers for incremental decoding:
                (B, n_heads, max_len, head_dim) each, filled by
                ``prefill`` / ``decode_step`` and masked by current
                length, so their shapes never change across the loop.
                ``sharding`` (a ``NamedSharding``, head axis over the
                tp mesh axis — ``parallel/layout.py``) commits the
                buffers onto the mesh; None keeps them single-device."""
                shape = (batch, self.n_heads, max_len, self.head_dim)
                cache = {"k": jnp.zeros(shape, dtype),
                         "v": jnp.zeros(shape, dtype)}
                if sharding is not None:
                    cache = jax.device_put(cache, sharding)
                return cache

            def prefill(self, params, x, cache):
                """Prompt pass of KV-cache decoding: one batched causal
                forward over the (bucket-padded) prompt that also writes
                the prompt's K/V into ``cache`` slots [0, T). Junk at
                padded positions is never read — the causal mask here and
                the length mask in ``decode_step`` both exclude it.
                Returns (output, cache)."""
                if self.sequence_parallel is not None:
                    raise ValueError(
                        "KV-cache decoding does not compose with "
                        "sequence_parallel; build the model without it "
                        "for generation")
                if not self.causal:
                    raise ValueError("KV-cache prefill requires causal "
                                     "attention")
                b, t, hs = x.shape
                q, k, v = self._qkv(params, x)
                cache = {
                    "k": lax.dynamic_update_slice(
                        cache["k"], k.astype(cache["k"].dtype),
                        (0, 0, 0, 0)),
                    "v": lax.dynamic_update_slice(
                        cache["v"], v.astype(cache["v"].dtype),
                        (0, 0, 0, 0))}
                uf = self.use_flash
                if uf is None:
                    uf = (jax.default_backend() == "tpu"
                          and flash_profitable(t, True))
                if uf and t % 128 == 0:
                    from bigdl_tpu.ops.flash_attention import \
                        flash_attention
                    out = flash_attention(q, k, v, causal=True)
                else:
                    out = full_attention(q, k, v, causal=True)
                out = out.transpose(0, 2, 1, 3).reshape(b, t, hs)
                return qmatmul(out, params["wo"]), cache

            def decode_step(self, params, x, cache, index,
                            in_place=False, read=None, live=None):
                """Incremental mode: attend ONE query token (x: (B, 1, H))
                against the cache, after writing its own K/V at slot
                ``index``. The buffers keep their shapes, so the step is
                scannable and the cache donatable; the length mask
                admits exactly slots [0, index] per row. The write takes
                one of three paths, all writing the same bits:

                - ``index`` a traced scalar (one shared position for
                  the whole batch, the ``generate`` path): one
                  ``lax.dynamic_update_slice`` a buffer.
                - ``index`` a traced (B,) vector (each row writes and
                  attends at its own length: the serving engine's slot
                  batch, where dim 0 of the cache is the slot table):
                  ``jax.vmap`` over ``dynamic_update_slice``, a scatter
                  (``kv_write.plain_write``). On a TPU, XLA expands it
                  into a loop over the rows,
                  B trips of four small launches for K and again for V.
                - the same vector with ``in_place=True``: one Pallas
                  call for K and V (``ops/kv_write.py``) that rewrites
                  only the tiles holding the positions of the rows that
                  ``live`` (B,) bool marks (None: every row) and leaves
                  every other row of the table as it lies: a free
                  slot's junk K/V, which nothing reads, is not written
                  at all. The owner of the table says so and hands over
                  its mask: ``SlotManager`` asks
                  ``kv_write.in_place_applies`` of the table it
                  allocated (on a TPU, float32 or bfloat16, not laid out
                  over a mesh, positions minor on the device) when it
                  builds its step. No flag selects it, and off the chip
                  or under a layout the step is the scatter's.

                A row out of bounds is clamped on every path (the
                serving step clamps its own beforehand).

                The read takes one of two. ``read`` None:
                :func:`cached_attention` scores every position of every
                row and masks. ``read`` a (B,) vector of counts, which
                the table's owner hands over where ``in_place`` holds
                (``index + 1`` for a live row, 0 for a free slot): one
                Pallas call (``ops/decode_attention.py``) that reads
                row ``b``'s first ``read[b]`` positions in blocks of 128
                and no others; a row of count 0 comes back as zeros."""
                b, t, hs = x.shape
                q, k, v = self._qkv(params, x)
                idx = jnp.asarray(index, jnp.int32)
                k = k.astype(cache["k"].dtype)
                v = v.astype(cache["v"].dtype)
                if idx.ndim == 0:
                    kc = lax.dynamic_update_slice(cache["k"], k,
                                                  (0, 0, idx, 0))
                    vc = lax.dynamic_update_slice(cache["v"], v,
                                                  (0, 0, idx, 0))
                else:
                    from bigdl_tpu.ops.kv_write import kv_write, plain_write
                    if in_place:
                        kc, vc = kv_write(cache["k"], cache["v"], k, v, idx,
                                          live)
                    else:
                        kc, vc = plain_write(cache["k"], cache["v"], k, v,
                                             idx)
                if read is None:
                    out = cached_attention(q, kc, vc, idx + 1)
                else:
                    from bigdl_tpu.ops.decode_attention import \
                        decode_attention
                    out = decode_attention(q, kc, vc, read).astype(q.dtype)
                out = out.transpose(0, 2, 1, 3).reshape(b, t, hs)
                return qmatmul(out, params["wo"]), {"k": kc, "v": vc}

            def decode_chunk(self, params, x, cache, pos):
                """Multi-token verify step for speculative decoding: C
                tokens per row (x: (B, C, H)) write their K/V at
                absolute positions ``pos[b] + j`` of the dense cache and
                attend causally through :func:`paged_attention`'s
                per-query position mask. Writes at or past the cache
                length scatter to an out-of-bounds index and DROP (the
                :func:`paged_write` sentinel trick), so near-
                ``max_position`` overflow never corrupts committed
                entries. The caller commits a prefix of the C outputs by
                advancing its lengths; rejected tokens need no undo —
                their K/V sit past every row's committed length, masked
                off here and overwritten by the next chunk."""
                b, c, hs = x.shape
                q, k, v = self._qkv(params, x)
                s = cache["k"].shape[2]
                pos = jnp.asarray(pos, jnp.int32).reshape(-1)
                idx = pos[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
                rows = jnp.broadcast_to(
                    jnp.arange(b, dtype=jnp.int32)[:, None], (b, c))
                tgt = jnp.where(idx < s, idx, s)          # OOB -> dropped
                kc = cache["k"].at[rows, :, tgt, :].set(
                    k.transpose(0, 2, 1, 3).astype(cache["k"].dtype),
                    mode="drop")
                vc = cache["v"].at[rows, :, tgt, :].set(
                    v.transpose(0, 2, 1, 3).astype(cache["v"].dtype),
                    mode="drop")
                out = paged_attention(q, kc, vc, idx)
                out = out.transpose(0, 2, 1, 3).reshape(b, c, hs)
                return qmatmul(out, params["wo"]), {"k": kc, "v": vc}

            # ------------------------------------- paged K/V decoding --
            def init_paged_pool(self, num_pages, page_size,
                                dtype=jnp.float32, sharding=None):
                """One layer's global K/V page pool for paged decoding
                (vLLM-style): (num_pages, n_heads, page_size, head_dim)
                each. Rows are position-contiguous fixed-size pages a
                host-side allocator hands out; slots reach their K/V
                through int32 page tables instead of owning a dense
                max_position row. ``dtype=jnp.int8`` adds per-(page,
                head, offset) f32 scale planes and switches the pool to
                quantize-on-write / dequantize-in-gather — halving-plus
                the bytes per cached token (``BIGDL_TPU_INT8_KV``)."""
                shape = (num_pages, self.n_heads, page_size, self.head_dim)
                pool = {"k": jnp.zeros(shape, dtype),
                        "v": jnp.zeros(shape, dtype)}
                if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
                    sshape = (num_pages, self.n_heads, page_size)
                    pool["k_scale"] = jnp.zeros(sshape, jnp.float32)
                    pool["v_scale"] = jnp.zeros(sshape, jnp.float32)
                if sharding is not None:
                    # ``sharding`` is the 4-D K/V plane's NamedSharding
                    # (parallel/layout.py kv_pool); the 3-D scale planes
                    # drop its trailing head_dim entry so every plane
                    # splits on the SAME head axis
                    put = {k: sharding for k in ("k", "v")}
                    if "k_scale" in pool:
                        parts = tuple(sharding.spec)
                        parts += (None,) * (3 - len(parts))
                        ssh = jax.sharding.NamedSharding(
                            sharding.mesh, P(*parts[:3]))
                        put["k_scale"] = put["v_scale"] = ssh
                    pool = jax.device_put(pool, put)
                return pool

            def _paged_write(self, pool, k, v, pages, offsets):
                """Write new K/V through the page table, dispatching on
                the pool's precision: int8 pools (marked by their scale
                planes) quantize on write."""
                if "k_scale" in pool:
                    pk, ks = paged_write_quant(pool["k"], pool["k_scale"],
                                               k, pages, offsets)
                    pv, vs = paged_write_quant(pool["v"], pool["v_scale"],
                                               v, pages, offsets)
                    return {"k": pk, "v": pv, "k_scale": ks,
                            "v_scale": vs}
                return {"k": paged_write(pool["k"], k, pages, offsets),
                        "v": paged_write(pool["v"], v, pages, offsets)}

            def _paged_update(self, pool, k, v, pages, offsets,
                              page_table, dtype):
                """Write new K/V through the page table and gather the
                dense per-row views back (int8 pools dequantise in
                gather) — the XLA reference path."""
                pool = self._paged_write(pool, k, v, pages, offsets)
                if "k_scale" in pool:
                    kf = paged_gather_dequant(pool["k"], pool["k_scale"],
                                              page_table, dtype)
                    vf = paged_gather_dequant(pool["v"], pool["v_scale"],
                                              page_table, dtype)
                else:
                    kf = paged_gather(pool["k"], page_table)
                    vf = paged_gather(pool["v"], page_table)
                return kf, vf, pool

            def _paged_attend(self, q, k, v, pool, pages, offsets,
                              page_table, q_pos, dtype):
                """Write-then-attend core shared by the paged chunk and
                step paths. Flag off: the XLA gather path (dense per-row
                views + masked attention), bit-identical to before. Flag
                on (BIGDL_TPU_PAGED_KERNEL): the pallas kernel streams
                K/V pages through the table with no dense gather
                (ops/paged_attention.py), under ``shard_map`` when the
                pools are head-sharded."""
                if self.use_paged_kernel:
                    from bigdl_tpu.ops.paged_attention import \
                        paged_pool_attention
                    pool = self._paged_write(pool, k, v, pages, offsets)
                    out = paged_pool_attention(
                        q, pool, page_table, q_pos,
                        mesh=self.paged_kernel_mesh)
                    return out, pool
                kf, vf, pool = self._paged_update(pool, k, v, pages,
                                                  offsets, page_table,
                                                  dtype)
                return paged_attention(q, kf, vf, q_pos), pool

            def paged_prefill_chunk(self, params, x, pool, pages, offsets,
                                    page_table, q_pos):
                """Chunked-prefill pass: C prompt tokens per row (x:
                (B, C, H)) write their K/V through the page table
                (``pages``/``offsets``: (B, C), sentinel = dropped) and
                attend to everything at or before their own absolute
                positions ``q_pos`` — earlier chunks, shared prefix
                pages and the chunk itself, via one gather through
                ``page_table`` (B, P). Returns (output, pool)."""
                b, t, hs = x.shape
                q, k, v = self._qkv(params, x)
                out, pool = self._paged_attend(q, k, v, pool, pages,
                                               offsets, page_table,
                                               q_pos, x.dtype)
                out = out.transpose(0, 2, 1, 3).reshape(b, t, hs)
                return qmatmul(out, params["wo"]), pool

            def paged_decode_step(self, params, x, pool, pages, offsets,
                                  page_table, pos):
                """Incremental paged mode: ONE query token per row (x:
                (B, 1, H)) writes its K/V at (``pages``, ``offsets``)
                (both (B,); a sentinel page drops the write — pageless
                slots decode masked junk exactly like the dense table's
                inactive rows) and attends through the page table with
                the same length mask as the dense ``decode_step``."""
                b, t, hs = x.shape
                q, k, v = self._qkv(params, x)
                pages = jnp.asarray(pages, jnp.int32)[:, None]
                offsets = jnp.asarray(offsets, jnp.int32)[:, None]
                pos = jnp.asarray(pos, jnp.int32)
                if self.use_paged_kernel:
                    # C == 1 with q_pos = pos is the same predicate as
                    # cached_attention's cur_len = pos + 1 (valid
                    # j <= pos)
                    out, pool = self._paged_attend(
                        q, k, v, pool, pages, offsets, page_table,
                        pos[:, None], x.dtype)
                else:
                    kf, vf, pool = self._paged_update(pool, k, v, pages,
                                                      offsets, page_table,
                                                      x.dtype)
                    out = cached_attention(q, kf, vf, pos + 1)
                out = out.transpose(0, 2, 1, 3).reshape(b, t, hs)
                return qmatmul(out, params["wo"]), pool

        return _MHA()
