"""EvaByte (``model_type: evabyte``, ``attention_class: eva``) as a plain
float32 forward pass: a byte-level decoder whose attention is exact inside a
window and reads one learned summary a chunk of everything before it.

One whole sequence at a time, positions ``i = 0, 1, ...``:

- ``h = E[ids]``; the layers; ``logits = N_out(h) @ W_head`` with ``W_head``
  ``hidden x (num_pred_heads x vocab)``: head ``r`` (columns ``r * vocab ..``)
  predicts byte ``i + 1 + r``. ``forward_logits`` returns head 0 alone, the
  head that picks the next byte; ``heads="all"`` returns every column.
- every layer, pre-norm, residual stream float32: ``h += Attn(N(h))``;
  ``h += W_down(silu(W_gate u) * W_up u)``, ``u = N(h)``; no bias anywhere.
- ``N(x) = x * rsqrt(mean(x^2) + eps) * (1 + g)`` (``norm_add_unit_offset``).
- attention, one head of ``D``, ``s = D ** -0.5``: ``q_i, k_i, v_i`` from
  ``W_q, W_k, W_v``; ``q_i`` and ``k_i`` turned by the rotary angles of ``i``
  (rotate-half pairing, ``rope_theta``). Window of ``i``: ``i div W``. Chunk
  ``c`` holds positions ``C c .. C c + C - 1`` and lies in window
  ``c div (W / C)``. Each head has two learned vectors of ``D``, ``phi`` and
  ``mu``.

  - summary of chunk ``c``, from the TURNED keys: ``a_cj = softmax_j(s * phi
    . k_j)`` over the chunk's ``C`` positions; ``K_c = sum_j a_cj k_j + mu``;
    ``V_c = sum_j a_cj v_j``.
  - output at ``i``: ONE softmax over the exact positions ``{j : j div W = i
    div W, j <= i}`` (scores ``s q_i . k_j``, values ``v_j``) and the
    summaries ``{c : c div (W / C) < i div W}`` (scores ``s q_i . K_c``,
    values ``V_c``). A position of the first window sees no summary; no
    position sees a summary of its own window or an exact key of an earlier
    one. Then ``W_o``.

Taken on trust, since nothing can be fetched here (the release's own
reference form is ``eva_pt_ref.py`` beside its config: ``adaptive_phi``,
``adaptive_mu_k``, one softmax over the window's keys and the earlier
windows' chunk keys; the configuration's ``assumed`` lists the same points):
that the summaries are of the turned keys; that ``mu`` is added after the
weighted sum; the rotate-half pairing of the rotary turn; ``num_pred_heads``
as one wide head matrix; ``phi`` and ``mu`` one vector a head a layer.

No cache, no batching, no kernel, nothing of the program. The sequence is
walked a window at a time (queries of one window against its own keys and
the summaries so far; the MLP a window at a time), so that 16 384 positions
at the published widths fit beside bfloat16 weights, which are raised to
float32 a matrix at a time. The parameter tree is the one
``benchmarks/harness/weights.py`` fills from the program's shapes:
``{"tok_emb", "out_norm": {weight}, "head", "layers": [{"attn_norm",
"mlp_norm", "attn": {wq, wk, wv, wo, phi, mu}, "mlp": {w1, w3, w2}}]}``,
matrices ``(in, out)``, ``phi`` and ``mu`` ``(heads x D,)``, a norm's
``weight`` the ``g`` of ``1 + g``.

The reference multiplies in true float32
(``jax.default_matmul_precision("highest")``). ``make`` also gives the
controls: the same code with both operands of every matrix product rounded
one precision down, and two planted faults, ``fault:no_summaries`` (no
position sees a summary) and ``fault:uniform_chunks`` (``a_cj = 1 / C`` and
``mu`` dropped).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

FAULTS = ("no_summaries", "uniform_chunks")


def _norm(g, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + g["weight"].astype(jnp.float32))


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
    """``x`` (T, heads, D) turned by its positions, rotate-half pairing."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = positions.astype(jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def chunk_summaries(k, v, phi, mu, chunk, fault=None):
    """``k``, ``v`` (T, heads, D) turned keys and values, ``T`` a multiple
    of ``chunk``; ``phi``, ``mu`` (heads, D). Returns ``(K, V, a)``: the
    summaries ``(T / chunk, heads, D)`` and the chunk weights
    ``(T / chunk, chunk, heads)``."""
    t, heads, d = k.shape
    kc = k.reshape(t // chunk, chunk, heads, d)
    vc = v.reshape(t // chunk, chunk, heads, d)
    if fault == "uniform_chunks":
        a = jnp.full(kc.shape[:3], 1.0 / chunk, jnp.float32)
        mu = jnp.zeros_like(mu)
    else:
        a = jax.nn.softmax(jnp.sum(kc * phi, -1) * d ** -0.5, axis=1)
    return (jnp.sum(a[..., None] * kc, 1) + mu,
            jnp.sum(a[..., None] * vc, 1), a)


def forward_logits(params, ids, rows, kw, operand_dtype=None, fault=None,
                   heads="first", stats=None):
    """Logits at positions ``rows`` of the one sequence ``ids`` (T,):
    ``(len(rows), vocab)`` of head 0, or with ``heads="all"``
    ``(len(rows), num_pred_heads * vocab)``. ``kw`` the configuration's
    ``constructor_kwargs``. Positions past the real length may hold any
    token: every layer is causal. ``operand_dtype`` rounds both operands of
    every matrix product to that type; sums stay float32. ``fault`` plants
    one of :data:`FAULTS`. ``stats``, a list, is given one dict a layer:
    the share of softmax mass on summaries (mean over the positions past
    the first window) and the chunk weights' mean entropy."""
    eps = float(kw.get("rms_norm_eps", 1e-5))
    theta = float(kw.get("rope_theta", 1e5))
    n_heads = int(kw["num_attention_heads"])
    window, chunk = int(kw["window_size"]), int(kw["chunk_size"])
    vocab = int(kw["vocab_size"])
    f32 = jnp.float32

    def mm(a, b):
        return _round_to(a, operand_dtype) @ _round_to(b.astype(f32),
                                                       operand_dtype)

    t_real = ids.shape[0]
    # whole windows (one short one if the sequence fits a window): what
    # the padding holds comes after every real position
    span = min(window, -(-t_real // chunk) * chunk)
    n_win = -(-t_real // span)
    t = n_win * span
    ids = jnp.pad(ids, (0, t - t_real))
    per_window = window // chunk            # summaries a whole window gives
    h = params["tok_emb"][ids].astype(f32)
    positions = jnp.arange(t)
    local = jnp.tril(jnp.ones((span, span), bool))
    for lp in params["layers"]:
        a = lp["attn"]
        d = a["wq"].shape[1] // n_heads
        u = _norm(lp["attn_norm"], h, eps)
        q = _rotary(mm(u, a["wq"]).reshape(t, n_heads, d), positions, theta)
        k = _rotary(mm(u, a["wk"]).reshape(t, n_heads, d), positions, theta)
        v = mm(u, a["wv"]).reshape(t, n_heads, d)
        sk, sv, weights = chunk_summaries(
            k, v, a["phi"].astype(f32).reshape(n_heads, d),
            a["mu"].astype(f32).reshape(n_heads, d), chunk, fault)
        chunk_window = jnp.arange(sk.shape[0]) // per_window

        def one_window(j, q=q, k=k, v=v, sk=sk, sv=sv,
                       chunk_window=chunk_window):
            at = j * span
            qj = jax.lax.dynamic_slice_in_dim(q, at, span)
            kj = jax.lax.dynamic_slice_in_dim(k, at, span)
            vj = jax.lax.dynamic_slice_in_dim(v, at, span)
            s_local = jnp.einsum(
                "qhd,khd->hqk", _round_to(qj, operand_dtype),
                _round_to(kj, operand_dtype)) * d ** -0.5
            s_local = jnp.where(local[None], s_local, -jnp.inf)
            s_far = jnp.einsum(
                "qhd,chd->hqc", _round_to(qj, operand_dtype),
                _round_to(sk, operand_dtype)) * d ** -0.5
            seen = chunk_window < j if fault != "no_summaries" \
                else jnp.zeros_like(chunk_window, bool)
            s_far = jnp.where(seen[None, None, :], s_far, -jnp.inf)
            p = jax.nn.softmax(jnp.concatenate([s_local, s_far], -1), -1)
            p_local, p_far = p[..., :span], p[..., span:]
            o = jnp.einsum("hqk,khd->qhd", _round_to(p_local, operand_dtype),
                           _round_to(vj, operand_dtype)) \
                + jnp.einsum("hqc,chd->qhd", _round_to(p_far, operand_dtype),
                             _round_to(sv, operand_dtype))
            return o.reshape(span, -1), jnp.mean(jnp.sum(p_far, -1))

        o, far = jax.lax.map(one_window, jnp.arange(n_win))
        if stats is not None:
            stats.append({
                "summary_mass_share": jnp.sum(far[1:]) / max(n_win - 1, 1),
                "chunk_entropy": jnp.mean(-jnp.sum(
                    weights * jnp.log(jnp.maximum(weights, 1e-30)), 1))})
        h = h + mm(o.reshape(t, -1), a["wo"])
        m = lp["mlp"]

        def mlp(hj, lp=lp, m=m):
            uj = _norm(lp["mlp_norm"], hj, eps)
            return hj + mm(jax.nn.silu(mm(uj, m["w1"])) * mm(uj, m["w3"]),
                           m["w2"])

        h = jax.lax.map(mlp, h.reshape(n_win, span, -1)).reshape(t, -1)
    h = _norm(params["out_norm"], h[rows], eps)
    logits = mm(h, params["head"]).astype(f32)
    return logits if heads == "all" else logits[:, :vocab]


def make(config):
    """``reference(params, ids, rows)`` and ``controls``: a dict of
    ``name -> control(params, ids, rows)``, each jitted once. The reference
    multiplies in true float32. ``config["controls"]`` names the lower
    precisions: ``"operands:<dtype>"`` is the reference with both operands
    of every matrix product (weights, activations, keys, values, summaries,
    probabilities) rounded to ``<dtype>``, sums in float32:
    ``float8_e4m3fn`` is the step below the bfloat16 operands the
    configuration states. ``config["faults"]`` names the planted faults of
    :data:`FAULTS`, given as ``"fault:<name>"``: the true-float32 reference
    with that part of the mechanism left out."""
    kw = dict(config["constructor_kwargs"])

    def build(operand=None, fault=None):
        @jax.jit
        def run(params, ids, rows):
            with jax.default_matmul_precision("highest"):
                return forward_logits(params, ids, rows, kw, operand, fault)
        return run

    controls = {}
    for name in config.get("controls", ()):
        if not name.startswith("operands:"):
            raise ValueError(f"unknown control {name!r}")
        controls[name] = build(operand=name.split(":", 1)[1])
    for name in config.get("faults", ()):
        if name not in FAULTS:
            raise ValueError(f"unknown fault {name!r}")
        controls["fault:" + name] = build(fault=name)
    return build(), controls
