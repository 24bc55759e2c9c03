"""dots3-note (``model_type: dots3_note``) as a plain float32 forward pass:
latent attention whose full layers read only the positions a learned indexer
picks, windowed latent attention between them, a headwise gate, a leading
dense layer and then routed experts beside a shared one.

``RMS(x) = x * rsqrt(mean(x^2) + eps) * g``. One whole sequence at a time,
positions ``t = 0, 1, ...``:

- ``h = E[ids]``; the layers; ``logits = RMS_out(h) @ W_head`` (untied).
- every layer, pre-norm, residual stream float32: ``h += Attn(u)``, ``u =
  RMS(h)``; ``h += FFN(RMS(h))``.
- ``Attn``, with ``H`` heads of ``nope + rope`` (queries, keys) and ``v``
  (values), ranks ``r_q`` and ``r_kv``, ``[a | b]`` a concatenation:

      c_q  = RMS(u W_qa) * sqrt(hidden / r_q)
      [q_n^h | q_r^h] = c_q W_qb                 q_r^h = rope(q_r^h, t)
      [c_raw | k_raw] = u W_kva
      c_kv = RMS(c_raw) * sqrt(hidden / r_kv)    k_r = rope(k_raw, t), one for all heads
      [k_n^h | v^h](s) = c_kv(s) W_kvb           K and V EXPANDED here, a head
      e_h(t, s) = (q_n^h . k_n^h(s) + q_r^h . k_r(s)) / sqrt(nope + rope)
      o_h = sum_{s in S_t} softmax_{s in S_t}(e_h(t, s)) v^h(s)
      Attn = concat_h(sigmoid(u W_g)_h o_h) W_o

  - ``full_attention``: ``num_attention_heads`` heads; ``S_t`` is the
    indexer's choice: ``q_I^j = c_q W_Iq`` (``index_n_heads`` x
    ``index_head_dim``, rope on the first ``rope``), ``k_I(s) =
    LayerNorm(u_s W_Ik)`` (gain and shift, eps 1e-6, rope likewise), ``w =
    u W_Iw``; ``I(t, s) = sum_j w_j(t) relu(q_I^j(t) . k_I(s))``; ``S_t`` =
    the ``index_topk`` positions ``s <= t`` with the largest ``I(t, s)``, by
    a plain ``top_k`` of the whole score row; all of them while ``t <
    index_topk``.
  - ``sliding_attention``: the ``swa_`` sizes; ``S_t = {s : t - window < s
    <= t}``, the query's own position counted among the ``window``.
- ``FFN`` of the first ``first_k_dense_replace`` layers: ``w2(silu(w1 u) *
  w3 u)``; of a later layer: ``s = sigmoid(u W_r)``, the
  ``num_experts_per_tok`` largest of ``s + b``, ``w_e = s_e / (sum of the
  chosen s + 1e-6) * scaling``, ``y = sum_e w_e E_e(u) + E_shared(u)``.
  Computed the plain way: every expert HELD over every position, weighted
  by ``w_e`` or by 0. The holder keeps experts ``[experts_first,
  experts_first + experts_held)``; what the absent ones would add is left
  out, as in the program.

Taken on trust (the configuration's ``assumed`` lists the same points): the
two factors ``sqrt(hidden / rank)`` as the meaning of
``apply_mla_qkv_lora_rescale``; the headwise gate as a sigmoid of a linear
map of the layer's normed input; the indexer as DeepSeek-V3.2-Exp's without
its Hadamard rotation and positive scale factors; the rotate-half pairing;
the window counting the query's own position.

No cache, no batching, no kernel, nothing of the program: where the program
folds ``W_kvb`` into the query and the output and never expands the latents,
this expands them. The sequence is walked a block of queries at a time, a
few heads at a time, so that 32 768 positions at the published widths fit
beside bfloat16 weights, which are raised to float32 a matrix at a time;
only the blocks up to the last row asked for are walked. The parameter tree
is the one ``benchmarks/harness/weights.py`` fills from the program's
shapes: ``{"tok_emb", "out_norm": {weight}, "head", "layers": [{"attn_norm",
"ffn_norm", "attn": {wqa, q_norm, wqb, wkva, kv_norm, wkb, wvb, wo, wg[, wiq,
wik, ik_norm: {weight, bias}, wiw]}, "mlp": {w1, w3, w2} | "moe": {"routed":
{wg, expert_bias, w1, w3, w2}, "shared": {w1, w3, w2}}}]}``, matrices ``(in,
out)``, expert matrices ``(held, in, out)``, ``W_kvb`` a head and in two halves:
``wkb`` ``(heads, nope, r_kv)`` and ``wvb`` ``(heads, r_kv, v)``.

The reference multiplies in true float32
(``jax.default_matmul_precision("highest")``). ``make`` also gives the
controls: the same code with both operands of every matrix product rounded
(``operands:bfloat16`` is the stated precision itself, ``operands:
float8_e4m3fn`` the step below it) and three planted faults:
``fault:select_all`` (no selection: every earlier position),
``fault:select_recent`` (the last ``index_topk``) and
``fault:window_unbounded`` (a window layer attends everything before it).
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp

FAULTS = ("select_all", "select_recent", "window_unbounded")
FULL, SLIDING = "full_attention", "sliding_attention"

# queries scored at a time, and heads whose K and V are expanded at a time:
# 8 heads x 512 queries x 32 768 keys are 0.5 GB of float32 scores
QUERY_BLOCK = 256
HEAD_GROUP = 4


def _rms(g, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g["weight"].astype(jnp.float32)


def _layer_norm(g, x, eps=1e-6):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) \
        * g["weight"].astype(jnp.float32) + g["bias"].astype(jnp.float32)


def _round_to(x, operand_dtype):
    """``x`` rounded to ``operand_dtype`` and back: what a multiplication
    in that type sees. An 8-bit float gets one scale per tensor."""
    if operand_dtype is None:
        return x
    dt = jnp.dtype(operand_dtype)
    if dt.itemsize > 1:
        return x.astype(dt).astype(x.dtype)
    top = float(jnp.finfo(dt).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dt).astype(x.dtype) * scale


def _rotary(x, positions, theta):
    """``x`` (T, [heads,] D) turned by its positions, rotate-half pairing."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = positions.astype(jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)
    if x.ndim == 3:
        ang = ang[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def _blocks(x, i, size):
    return jax.lax.dynamic_slice_in_dim(x, i * size, size, axis=0)


def _sizes(kw, kind):
    """``(heads, nope, rope, v, theta)`` of a layer of ``kind``."""
    p = "swa_" if kind == SLIDING else ""
    heads = kw["swa_num_attention_heads" if p else "num_attention_heads"]
    theta = kw["swa_rope_theta" if p else "rope_theta"]
    return (int(heads), int(kw[p + "qk_nope_head_dim"]),
            int(kw[p + "qk_rope_head_dim"]), int(kw[p + "v_head_dim"]),
            float(theta))


def selection(a, u, cq, positions, n_blocks, kw, mm, fault=None):
    """Row ``t`` marks ``S_t``, for the rows of the first ``n_blocks``
    blocks of queries (the others stay empty): ``(T, T / 8)`` uint8, eight
    positions a byte (``jnp.packbits``; a ``(T, T)`` bool table of 32 768
    positions would be 1 GiB)."""
    t = u.shape[0]
    block = min(QUERY_BLOCK, t)
    top = min(int(kw["index_topk"]), t)
    rope = int(kw["qk_rope_head_dim"])
    theta = float(kw["rope_theta"])
    if fault in ("select_all", "select_recent"):
        return None                    # a rule of positions: see _marks
    j_heads, dim = int(kw["index_n_heads"]), int(kw["index_head_dim"])

    def turn_first(x, at):
        return jnp.concatenate(
            [_rotary(x[..., :rope], at, theta), x[..., rope:]], -1)

    k = turn_first(_layer_norm(a["ik_norm"], mm(u, a["wik"])), positions)
    w = mm(u, a["wiw"])

    def one_block(i, marks):
        pb, wb = _blocks(positions, i, block), _blocks(w, i, block)
        # the index queries a block at a time: 32 768 positions of 64 x 128
        # in float32 would be 1 GiB, and as much again while they are turned
        qb = turn_first(mm(_blocks(cq, i, block), a["wiq"]).reshape(
            block, j_heads, dim), pb)

        def one_head(acc, qw):               # summed in float32, a head at a time
            qj, wj = qw
            return acc + wj[:, None] * jax.nn.relu(mm(qj, k.T)), None

        scores, _ = jax.lax.scan(
            one_head, jnp.zeros((block, t), jnp.float32),
            (qb.swapaxes(0, 1), wb.T))
        scores = jnp.where(positions[None, :] <= pb[:, None], scores,
                           -jnp.inf)
        vals, idx = jax.lax.top_k(scores, top)
        chosen = jnp.zeros((block, t), bool).at[
            jnp.arange(block)[:, None], idx].set(vals > -jnp.inf)
        return jax.lax.dynamic_update_slice(
            marks, jnp.packbits(chosen, axis=-1), (i * block, 0))

    return jax.lax.fori_loop(0, n_blocks, one_block,
                             jnp.zeros((t, -(-t // 8)), jnp.uint8))


def attention(a, u, kind, n_blocks, kw, mm, rnd, fault=None):
    """``Attn(u)`` for ``u`` (T, hidden), rows of the first ``n_blocks``
    blocks of queries (the others come back zero)."""
    f32 = jnp.float32
    t, d = u.shape
    heads, nope, rope, v_dim, theta = _sizes(kw, kind)
    block = min(QUERY_BLOCK, t)
    group = min(HEAD_GROUP, heads)
    positions = jnp.arange(t)
    rescale = bool(kw.get("apply_mla_qkv_lora_rescale", True))
    r_q, r_kv = a["wqa"].shape[1], a["wvb"].shape[1]
    a_q = (d / r_q) ** 0.5 if rescale else 1.0
    a_kv = (d / r_kv) ** 0.5 if rescale else 1.0
    eps = float(kw.get("rms_norm_eps", 1e-5))
    cq = _rms(a["q_norm"], mm(u, a["wqa"]), eps) * a_q
    kv = mm(u, a["wkva"])
    ckv = _rms(a["kv_norm"], kv[:, :r_kv], eps) * a_kv
    k_r = _rotary(kv[:, r_kv:], positions, theta)
    window = int(kw["sliding_window_size"])
    top = min(int(kw["index_topk"]), t)
    packed = selection(a, u, cq, positions, n_blocks, kw, mm, fault) \
        if kind == FULL else None

    def marks_of(i):
        """``(block, T)`` bool: which positions the queries of block ``i``
        read."""
        pb = _blocks(positions, i, block)[:, None]
        causal = positions[None, :] <= pb
        if packed is not None:
            return jnp.unpackbits(_blocks(packed, i, block), axis=-1,
                                  count=t).astype(bool)
        if kind == FULL:
            return causal if fault == "select_all" \
                else causal & (positions[None, :] > pb - top)
        return causal if fault == "window_unbounded" \
            else causal & (positions[None, :] > pb - window)

    gate = jax.nn.sigmoid(mm(u, a["wg"])) if "wg" in a \
        else jnp.ones((t, heads), f32)
    scale = (nope + rope) ** -0.5
    wqb = a["wqb"].reshape(r_q, heads // group, group, nope + rope)
    # [k_n^h | v^h] = c_kv W_kvb^h, a group of heads at a time
    wkvb = jnp.concatenate([a["wkb"].swapaxes(1, 2), a["wvb"]], -1).reshape(
        heads // group, group, r_kv, nope + v_dim)
    wo = a["wo"].reshape(heads // group, group * v_dim, d)
    gates = gate.reshape(t, heads // group, group).swapaxes(0, 1)

    def one_group(out, g):
        wq, wkv, wo_g, gate_g = g
        q = mm(cq, wq.reshape(r_q, -1)).reshape(t, group, nope + rope)
        q = jnp.concatenate(
            [q[..., :nope], _rotary(q[..., nope:], positions, theta)], -1)
        kvx = mm(ckv, wkv.swapaxes(0, 1).reshape(r_kv, -1)).reshape(
            t, group, nope + v_dim)
        k = jnp.concatenate(
            [kvx[..., :nope],
             jnp.broadcast_to(k_r[:, None, :], (t, group, rope))], -1)
        k, v = rnd(k), rnd(kvx[..., nope:])

        def one_block(i, out):
            qb = rnd(_blocks(q, i, block))
            s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
            s = jnp.where(marks_of(i)[None], s, -jnp.inf)
            p = jax.nn.softmax(s, -1)
            o = jnp.einsum("hqk,khd->qhd", rnd(p), v) \
                * _blocks(gate_g, i, block)[..., None]
            y = mm(o.reshape(block, -1), wo_g)
            return jax.lax.dynamic_update_slice(
                out, _blocks(out, i, block) + y, (i * block, 0))

        return jax.lax.fori_loop(0, n_blocks, one_block, out), None

    out, _ = jax.lax.scan(
        one_group, jnp.zeros((t, d), f32),
        (wqb.swapaxes(0, 1), wkvb, wo, gates))
    return out


def routed_experts(m, u, kw, mm, rnd):
    """The routed feed-forward of positions ``u`` (T, hidden) with the
    layer's tree ``m``: the part that the experts HELD give (the tree's
    expert leaves hold ``experts_held`` of them from
    ``kw["experts_first"]`` on). Every held expert over every position,
    weighted by ``w_e`` or by 0; the experts are raised to float32 one at
    a time."""
    f32 = jnp.float32
    k_top = int(kw["num_experts_per_tok"])
    first = int(kw.get("experts_first", 0))
    t = u.shape[0]
    s = jax.nn.sigmoid(mm(u, m["wg"]))
    biased = s + m["expert_bias"].astype(f32) if "expert_bias" in m else s
    _, chosen = jax.lax.top_k(biased, k_top)
    w = jnp.take_along_axis(s, chosen, -1)
    if bool(kw.get("norm_topk_prob", True)):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    w = w * float(kw.get("routed_scaling_factor", 1.0))
    # (T, E): the weight of every expert at every position, 0 unchosen
    weight = jnp.sum(
        (chosen[:, :, None] == jnp.arange(s.shape[1])) * w[:, :, None], 1)
    held = jax.lax.dynamic_slice_in_dim(weight, first, m["w1"].shape[0],
                                        axis=1)

    def one(acc, e):
        w1, w3, w2, we = e                     # one expert, raised here
        y = mm(jax.nn.silu(mm(u, w1)) * mm(u, w3), w2)
        return acc + we[:, None] * y, None

    y, _ = jax.lax.scan(one, jnp.zeros((t, m["w2"].shape[-1]), f32),
                        (m["w1"], m["w3"], m["w2"], held.T))
    return y


def forward_logits(params, ids, rows, kw, operand_dtype=None, fault=None,
                   choices=False):
    """Logits ``(len(rows), vocab)`` at positions ``rows`` of the one
    sequence ``ids`` (T,), or with ``choices`` the token each row puts
    first ``(len(rows),)``; ``kw`` the configuration's ``constructor_kwargs``.
    Positions past the real length may hold any token: every layer is
    causal. ``operand_dtype`` rounds both operands of every matrix product
    to that type; sums stay float32. ``fault`` plants one of
    :data:`FAULTS`."""
    f32 = jnp.float32
    eps = float(kw.get("rms_norm_eps", 1e-5))
    kinds = list(kw["layer_types"])
    dense = int(kw.get("first_k_dense_replace", 1))

    def rnd(x):
        return _round_to(x, operand_dtype)

    def mm(x, w):
        return rnd(x) @ rnd(w.astype(f32))

    t_real = ids.shape[0]
    block = min(QUERY_BLOCK, t_real)
    t = -(-t_real // block) * block
    ids = jnp.pad(ids, (0, t - t_real))
    # only the blocks up to the last row asked for are walked
    n_blocks = jnp.max(rows) // block + 1
    h = params["tok_emb"][ids].astype(f32)

    def blockwise(fn, norm, h):
        """``fn(RMS(h))`` over the first ``n_blocks`` blocks of ``h``'s
        rows (the feed-forward is a position's own), the norm taken a
        block at a time so that the normed stream never exists whole."""
        def one(i, out):
            return jax.lax.dynamic_update_slice(
                out, fn(_rms(norm, _blocks(h, i, block), eps)),
                (i * block, 0))
        return jax.lax.fori_loop(0, n_blocks, one, jnp.zeros_like(h))

    for i, lp in enumerate(params["layers"]):
        u = _rms(lp["attn_norm"], h, eps)
        wrong = fault if (fault == "window_unbounded") == (kinds[i] == SLIDING) \
            else None
        h = h + attention(lp["attn"], u, kinds[i], n_blocks, kw, mm, rnd,
                          wrong)
        if i < dense:
            m = lp["mlp"]

            def ffn(ub, m=m):
                return mm(jax.nn.silu(mm(ub, m["w1"])) * mm(ub, m["w3"]),
                          m["w2"])
        else:
            m = lp["moe"]

            def ffn(ub, m=m):
                s = m["shared"]
                return routed_experts(m["routed"], ub, kw, mm, rnd) \
                    + mm(jax.nn.silu(mm(ub, s["w1"])) * mm(ub, s["w3"]),
                         s["w2"])
        h = h + blockwise(ffn, lp["ffn_norm"], h)
    # the head a block of rows at a time, each block written where it
    # belongs: a --control run asks for every position's logits, 2.3 GiB of
    # float32, and a product in one piece (or a ``lax.map``, whose result
    # the compiler lays out block-minor and then copies) kept as much again
    # beside them. A control gives only its first choice a row, found a
    # block at a time, so its logits never exist whole.
    n_rows = rows.shape[0]
    chunk = min(2048, n_rows)
    padded = -(-n_rows // chunk) * chunk
    rows = jnp.pad(rows, (0, padded - n_rows))
    head = rnd(params["head"].astype(f32))

    def some_rows(i, out):
        hb = _rms(params["out_norm"], h[_blocks(rows, i, chunk)], eps)
        logits = rnd(hb) @ head
        return jax.lax.dynamic_update_slice(
            out, jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            if choices else logits, (i * chunk, 0))

    out = jax.lax.fori_loop(
        0, padded // chunk, some_rows,
        jnp.zeros((padded, 1), jnp.int32) if choices
        else jnp.zeros((padded, head.shape[1]), f32))
    out = out if padded == n_rows else out[:n_rows]
    return out[:, 0] if choices else out


class _Choices:
    """What a control hands back in place of its logits: only the token it
    puts first at each row, which is all the comparison reads of a control
    in a greedy request. A whole table of float32 logits (32 768 rows of
    19 008) beside the reference's own would not fit the chip."""

    def __init__(self, tokens):
        self._tokens = tokens

    def argmax(self, axis=-1):
        assert axis == -1, axis
        return self._tokens


def make(config):
    """``reference(params, ids, rows)`` and ``controls``: a dict of
    ``name -> control(params, ids, rows)``, each jitted once. The reference
    multiplies in true float32. ``config["controls"]`` names the lower
    precisions: ``"operands:<dtype>"`` is the reference with both operands
    of every matrix product (weights, activations, expanded keys and
    values, probabilities) rounded to ``<dtype>``, sums in float32.
    ``config["faults"]`` names the planted faults of :data:`FAULTS`, given
    as ``"fault:<name>"``: the true-float32 reference with that part of
    the mechanism changed. A control returns its first choice a row
    (:class:`_Choices`), not its logits."""
    kw = dict(config["constructor_kwargs"])
    # the harness builds the reference once the program's engine is shut
    # down, but what the engine held stays on the device until its objects
    # are collected, and its executables stay loaded, each with its scratch
    # reserved (2 GiB for the longest prefill bucket), until the caches are
    # cleared. A --control run needs the room: weights 7.6 GiB, the
    # reference's logits of every position 2.3 GiB, a control's pass 2.7
    # GiB of 15.75. After these two the chip holds nothing (my chip runs,
    # PR 34: 27 KB in use, no array alive)
    gc.collect()
    jax.clear_caches()

    def build(operand=None, fault=None, choices=False):
        @jax.jit
        def run(params, ids, rows):
            with jax.default_matmul_precision("highest"):
                return forward_logits(params, ids, rows, kw, operand, fault,
                                      choices)

        if not choices:
            return run
        return lambda params, ids, rows: _Choices(run(params, ids, rows))

    controls = {}
    for name in config.get("controls", ()):
        if not name.startswith("operands:"):
            raise ValueError(f"unknown control {name!r}")
        controls[name] = build(operand=name.split(":", 1)[1], choices=True)
    for name in config.get("faults", ()):
        if name not in FAULTS:
            raise ValueError(f"unknown fault {name!r}")
        controls["fault:" + name] = build(fault=name, choices=True)
    return build(), controls
