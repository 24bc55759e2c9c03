"""LFM2-MoE (LiquidAI, ``model_type: lfm2_moe``) as a plain float32 forward
pass: gated short convolutions beside grouped-query attention, a dense
SwiGLU MLP in the leading layers, routed experts in every later one.

``RMS(x) = x * rsqrt(mean(x^2) + eps) * g``. One whole sequence at a time:

- ``h = E[ids]``; the layers; ``logits = RMS_out(h) @ E^T`` (tied head).
- every layer: ``h += Op(RMS_op(h))``; ``h += FFN(RMS_ffn(h))``.
- ``Op``, conv layer: ``[B, C, X] = u @ w_in``; ``z = B * X``;
  ``c_t = sum_j w[j] * z_{t - taps + 1 + j}`` (zeros before the sequence);
  ``Op = (C * c) @ w_out``.
- ``Op``, attention layer: q over ``heads``, k and v over ``kv_heads``,
  RMSNorm over each head of q and of k, rotary positions over the whole
  head (rotate-half pairing), each K/V head serving ``heads / kv_heads``
  query heads, causal softmax at ``head_dim ** -0.5``, then ``wo``.
- ``FFN``, dense: ``w2(silu(w1 u) * w3 u)``.
- ``FFN``, routed: ``s = sigmoid(u @ wg)``; the ``k`` experts chosen are
  the ``k`` largest of ``s + expert_bias``; ``w_e = s_e / (sum of the
  chosen s + 1e-6) * scaling``; the sum over the chosen of
  ``w_e * w2[e](silu(w1[e] u) * w3[e] u)``. Computed the plain way: every
  expert HELD over every position, weighted by ``w_e`` or by 0. The
  holder keeps experts ``[first, first + count)`` of ``num_experts``
  (the tree's expert leaves have ``count`` rows); what the absent ones
  would add is left out, as in the program.

No cache, no batching, no kernel, nothing of the program. Departures
from the published model are the configuration's ``assumed``.

The parameter tree is the one ``benchmarks/harness/weights.py`` fills
from the program's shapes: ``{"tok_emb", "out_norm": {weight}, "layers":
[{"op_norm", "ffn_norm", "conv": {w_in, w, w_out} | "attn": {wq, wk, wv,
wo, q_norm, k_norm}, "mlp": {w1, w3, w2} | "moe": {wg, expert_bias, w1,
w3, w2}}]}``, matrices ``(in, out)``, ``w`` ``(taps, hidden)``, expert
matrices ``(count, in, out)``. The served weights are bfloat16 and fill
most of the chip: they are raised to float32 a matrix at a time, and the
experts one expert at a time inside a scan.

The reference multiplies in true float32
(``jax.default_matmul_precision("highest")``); a control is the same code
with both operands of every matrix product rounded one precision down
(``make``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(g, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g["weight"].astype(jnp.float32)


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
    """``x`` (T, heads, hd) turned by its positions, rotate-half pairing."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(hd // 2, dtype=jnp.float32) * 2.0 / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def routed_experts(m, u, kw, operand_dtype=None):
    """The routed feed-forward of positions ``u`` (T, hidden) with the
    layer's tree ``m``: the part that the experts HELD give (the tree's
    expert leaves hold ``count`` of them from ``kw["experts_first"]``
    on). Every held expert over every position, weighted by ``w_e`` or
    by 0; the experts are raised to float32 one at a time."""
    f32 = jnp.float32
    k_top = int(kw["num_experts_per_tok"])
    first = int(kw.get("experts_first", 0))
    t = u.shape[0]
    ur = _round_to(u, operand_dtype)
    s = jax.nn.sigmoid(ur @ _round_to(m["wg"].astype(f32), operand_dtype))
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
        g = jax.nn.silu(ur @ _round_to(w1.astype(f32), operand_dtype)) \
            * (ur @ _round_to(w3.astype(f32), operand_dtype))
        y = _round_to(g, operand_dtype) \
            @ _round_to(w2.astype(f32), operand_dtype)
        return acc + we[:, None] * y, None

    y, _ = jax.lax.scan(one, jnp.zeros((t, m["w2"].shape[-1]), f32),
                        (m["w1"], m["w3"], m["w2"], held.T))
    return y


def forward_logits(params, ids, rows, kw, operand_dtype=None):
    """Logits ``(len(rows), vocab)`` at positions ``rows`` of the one
    sequence ``ids`` (T,); ``kw`` the configuration's ``constructor_kwargs``.
    Positions past the real length may hold any token: every layer is
    causal. ``operand_dtype`` rounds both operands of every matrix product
    to that type; sums stay float32."""
    eps = float(kw.get("norm_eps", 1e-5))
    theta = float(kw.get("rope_theta", 1e6))
    heads, kv = int(kw["num_attention_heads"]), int(kw["num_key_value_heads"])
    f32 = jnp.float32

    def mm(a, b):
        return _round_to(a, operand_dtype) @ _round_to(b.astype(f32),
                                                       operand_dtype)

    t = ids.shape[0]
    emb = params["tok_emb"]
    h = emb[ids].astype(f32)
    causal = jnp.tril(jnp.ones((t, t), bool))
    positions = jnp.arange(t)
    for lp in params["layers"]:
        u = _rms(lp["op_norm"], h, eps)
        if "conv" in lp:
            c = lp["conv"]
            b_, c_, x_ = jnp.split(mm(u, c["w_in"]), 3, axis=-1)
            z = b_ * x_
            w = c["w"].astype(f32)
            taps = w.shape[0]
            zp = jnp.pad(z, ((taps - 1, 0), (0, 0)))
            conv = sum(w[j] * zp[j:j + t] for j in range(taps))
            h = h + mm(c_ * conv, c["w_out"])
        else:
            a = lp["attn"]
            hd = a["wq"].shape[1] // heads
            q = mm(u, a["wq"]).reshape(t, heads, hd)
            k = mm(u, a["wk"]).reshape(t, kv, hd)
            v = mm(u, a["wv"]).reshape(t, kv, hd)
            q = _rotary(_rms(a["q_norm"], q, eps), positions, theta)
            k = _rotary(_rms(a["k_norm"], k, eps), positions, theta)
            k = jnp.repeat(k, heads // kv, axis=1)     # head i reads i // rep
            v = jnp.repeat(v, heads // kv, axis=1)
            s = jnp.einsum("qhd,khd->hqk", _round_to(q, operand_dtype),
                           _round_to(k, operand_dtype)) * hd ** -0.5
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
            o = jnp.einsum("hqk,khd->qhd", _round_to(p, operand_dtype),
                           _round_to(v, operand_dtype))
            h = h + mm(o.reshape(t, -1), a["wo"])
        u = _rms(lp["ffn_norm"], h, eps)
        if "mlp" in lp:
            m = lp["mlp"]
            h = h + mm(jax.nn.silu(mm(u, m["w1"])) * mm(u, m["w3"]), m["w2"])
            continue
        h = h + routed_experts(lp["moe"], u, kw, operand_dtype)
    h = _rms(params["out_norm"], h, eps)
    return mm(h[rows], emb.T).astype(f32)


def make(config):
    """``reference(params, ids, rows)`` and ``controls``: a dict of
    ``name -> control(params, ids, rows)``, each jitted once. The reference
    multiplies in true float32. ``config["controls"]`` names the controls:
    ``"operands:<dtype>"`` is the reference with both operands of every
    matrix product (weights, activations, keys, values, probabilities)
    rounded to ``<dtype>``, sums in float32: ``float8_e4m3fn`` is the step
    below the bfloat16 operands the configuration states."""
    kw = dict(config["constructor_kwargs"])

    def build(operand):
        @jax.jit
        def run(params, ids, rows):
            with jax.default_matmul_precision("highest"):
                return forward_logits(params, ids, rows, kw, operand)
        return run

    controls = {}
    for name in config.get("controls", ()):
        if not name.startswith("operands:"):
            raise ValueError(f"unknown control {name!r}")
        controls[name] = build(name.split(":", 1)[1])
    return build(None), controls
