"""Nemotron-H (``model_type: nemotron_h``) as a plain float32 forward pass:
Mamba-2 mixers, latent routed experts of squared ReLU beside a shared
expert, and attention layers with no positional encoding, one kind a layer
as ``hybrid_override_pattern`` says.

``RMS(x) = x * rsqrt(mean(x^2) + eps) * g``. One whole sequence at a time,
positions ``t = 0, 1, ...``:

- ``h = E[ids]``; the layers; ``logits = RMS_out(h) @ W_head`` (untied).
- every layer, pre-norm, residual stream float32: ``h += mixer(RMS(h))``.
- ``M``, Mamba-2, with ``H`` heads of ``P``, ``G`` groups of ``N``, a
  convolution of ``K`` taps (head ``h`` reads group ``h // (H / G)``):

      [z | xBC | dt] = u W_in
      xBC_t = silu(sum_j w_j xBC_{t-K+1+j} + b_conv)      zeros before t = 0
      [x | B | C] = xBC                                   H x P | G x N | G x N
      dt = softplus(dt + dt_bias),  A = -exp(A_log)
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_tᵀ            S_{-1} = 0
      y_t = S_t C_t + D x_t
      out = RMS_G(y * silu(z)) W_out                      the norm over groups of H P / G

  The recurrence is walked LITERALLY, one position after the other (the
  projections for all positions at once); the program's chunked algebra
  is not used.
- ``E``, LatentMoE: ``s = sigmoid(u W_r)`` over every expert, the ``k``
  largest of ``s + bias`` chosen, ``w_e = s_e / (sum of the chosen s +
  1e-6) * scaling``; ``l = u W_down``; ``out = (sum_e w_e W2_e relu(W1_e
  l)^2) W_up + W_s2 relu(W_s1 u)^2``. Computed the plain way: every expert
  HELD over every position, weighted by ``w_e`` or by 0, raised to float32
  one at a time. The holder keeps experts ``[experts_first, experts_first
  + experts_held)``; what the absent ones would add is left out, as in the
  program.
- ``*``: causal softmax attention, ``num_attention_heads`` query heads
  over ``num_key_value_heads`` K/V heads of ``head_dim``, scale
  ``head_dim ** -0.5``, no bias, no QK-norm, NO positional encoding.

Taken on trust (the configuration's ``assumed`` lists the same points):
the gate before the grouped norm, LatentMoE's router on the full-width
row with one down- and one up-projection shared by the routed experts,
and attention without positions.

No cache, no batching, no kernel, nothing of the program. The parameter
tree is the one ``benchmarks/harness/weights.py`` fills from the
program's shapes: ``{"tok_emb", "out_norm": {weight}, "head", "layers":
[{"norm": {weight}, "mixer": M: {w_in, conv_w, conv_bias, dt_bias, A_log,
D, norm: {weight}, w_out} | E: {"routed": {wg, expert_bias, w_down, w1,
w2, w_up}, "shared": {w1, w2}} | *: {wq, wk, wv, wo}}]}``, matrices ``(in,
out)``, expert matrices ``(held, in, out)``, ``conv_w`` ``(K, channels)``
with tap ``K - 1`` on the current position.

The reference multiplies in true float32
(``jax.default_matmul_precision("highest")``). ``make`` also gives the
controls: the same code with both operands of every product rounded
(``operands:bfloat16`` is the stated precision itself, ``operands:
float8_e4m3fn`` the step below it) and two planted faults:
``fault:ssm_no_state`` (S starts from zero at every position: ``S_t = dt_t
x_t B_tᵀ``) and ``fault:latent_unscaled`` (the routed experts' weights
without ``routed_scaling_factor``).
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp

FAULTS = ("ssm_no_state", "latent_unscaled")
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"

# queries scored at a time: 32 heads x 512 queries x 6144 keys are 0.4 GB
# of float32 scores
QUERY_BLOCK = 512


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


def mamba(m, u, kw, mm, rnd, fault=None):
    """The Mamba-2 mixer over ``u`` (T, hidden): the literal recurrence."""
    f32 = jnp.float32
    t = u.shape[0]
    h, p = int(kw["mamba_num_heads"]), int(kw["mamba_head_dim"])
    g, n = int(kw["n_groups"]), int(kw["ssm_state_size"])
    taps = int(kw["conv_kernel"])
    eps = float(kw["layer_norm_epsilon"])
    di = h * p
    zxd = mm(u, m["w_in"])
    z, xbc, dt = zxd[:, :di], zxd[:, di:2 * di + 2 * g * n], zxd[:, -h:]
    w = rnd(m["conv_w"].astype(f32))
    padded = jnp.pad(rnd(xbc), ((taps - 1, 0), (0, 0)))
    conv = sum(w[j] * padded[j:j + t] for j in range(taps))
    xbc = jax.nn.silu(conv + m["conv_bias"].astype(f32))
    x = xbc[:, :di].reshape(t, h, p)
    b = jnp.repeat(xbc[:, di:di + g * n].reshape(t, g, n), h // g, axis=1)
    c = jnp.repeat(xbc[:, di + g * n:].reshape(t, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt + m["dt_bias"].astype(f32))
    a = -jnp.exp(m["A_log"].astype(f32))
    keep = 0.0 if fault == "ssm_no_state" else 1.0

    def one(s, at):                            # one position: S_t, y_t
        x_t, b_t, c_t, dt_t = at
        s = keep * jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], -1)

    _, y = jax.lax.scan(one, jnp.zeros((h, p, n), f32), (x, b, c, dt))
    y = y + m["D"].astype(f32)[:, None] * x
    gated = (y.reshape(t, di) * jax.nn.silu(z)).reshape(t, g, di // g)
    gated = gated * jax.lax.rsqrt(jnp.mean(jnp.square(gated), -1,
                                           keepdims=True) + eps)
    return mm(gated.reshape(t, di) * m["norm"]["weight"].astype(f32),
              m["w_out"])


def experts(m, u, kw, mm, fault=None):
    """The expert layer over ``u`` (T, hidden): the held routed experts'
    part at the latent width, and the shared expert."""
    f32 = jnp.float32
    r = m["routed"]
    k_top = int(kw["num_experts_per_tok"])
    first = int(kw.get("experts_first", 0))
    s = jax.nn.sigmoid(u @ r["wg"].astype(f32))
    _, chosen = jax.lax.top_k(s + r["expert_bias"].astype(f32), k_top)
    w = jnp.take_along_axis(s, chosen, -1)
    if bool(kw.get("norm_topk_prob", True)):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    if fault != "latent_unscaled":
        w = w * float(kw["routed_scaling_factor"])
    # (T, E): the weight of every expert at every position, 0 unchosen
    weight = jnp.sum(
        (chosen[:, :, None] == jnp.arange(s.shape[1])) * w[:, :, None], 1)
    held = jax.lax.dynamic_slice_in_dim(weight, first, r["w1"].shape[0],
                                        axis=1)
    lat = mm(u, r["w_down"])

    def one(acc, e):
        w1, w2, we = e                         # one expert, raised here
        return acc + we[:, None] * mm(jnp.square(jax.nn.relu(mm(lat, w1))),
                                      w2), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(lat), (r["w1"], r["w2"],
                                                   held.T))
    sh = m["shared"]
    return mm(y, r["w_up"]) \
        + mm(jnp.square(jax.nn.relu(mm(u, sh["w1"]))), sh["w2"])


def attention(a, u, kw, mm, rnd):
    """Causal softmax attention over ``u`` (T, hidden), no positions; the
    queries a block of :data:`QUERY_BLOCK` at a time."""
    t = u.shape[0]
    heads, kv = int(kw["num_attention_heads"]), int(kw["num_key_value_heads"])
    hd = int(kw["head_dim"])
    q = mm(u, a["wq"]).reshape(t, heads, hd)
    k = jnp.repeat(mm(u, a["wk"]).reshape(t, kv, hd), heads // kv, axis=1)
    v = jnp.repeat(mm(u, a["wv"]).reshape(t, kv, hd), heads // kv, axis=1)
    k, v = rnd(k), rnd(v)
    block = min(QUERY_BLOCK, t)
    positions = jnp.arange(t)

    def one(i, out):
        qb = rnd(jax.lax.dynamic_slice_in_dim(q, i * block, block))
        pb = jax.lax.dynamic_slice_in_dim(positions, i * block, block)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * hd ** -0.5
        s = jnp.where(positions[None, None, :] <= pb[None, :, None], s,
                      -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", rnd(jax.nn.softmax(s, -1)), v)
        return jax.lax.dynamic_update_slice(out, o.reshape(block, -1),
                                            (i * block, 0))

    o = jax.lax.fori_loop(0, t // block, one,
                          jnp.zeros((t, heads * hd), jnp.float32))
    return mm(o, a["wo"])


def forward_logits(params, ids, rows, kw, operand_dtype=None, fault=None,
                   choices=False):
    """Logits ``(len(rows), vocab)`` at positions ``rows`` of the one
    sequence ``ids`` (T,), or with ``choices`` the token each row puts
    first ``(len(rows),)``; ``kw`` the configuration's
    ``constructor_kwargs``. Positions past the real length may hold any
    token: every layer is causal. ``operand_dtype`` rounds both operands
    of every product to that type; sums stay float32. ``fault`` plants one
    of :data:`FAULTS`."""
    f32 = jnp.float32
    eps = float(kw["layer_norm_epsilon"])

    def rnd(x):
        return _round_to(x, operand_dtype)

    def mm(x, w):
        return rnd(x) @ rnd(w.astype(f32))

    t_real = ids.shape[0]
    block = min(QUERY_BLOCK, t_real)
    t = -(-t_real // block) * block
    h = params["tok_emb"][jnp.pad(ids, (0, t - t_real))].astype(f32)
    for kind, lp in zip(kw["hybrid_override_pattern"], params["layers"]):
        u = _rms(lp["norm"], h, eps)
        if kind == MAMBA:
            h = h + mamba(lp["mixer"], u, kw, mm, rnd, fault)
        elif kind == EXPERTS:
            h = h + experts(lp["mixer"], u, kw, mm, fault)
        elif kind == ATTENTION:
            h = h + attention(lp["mixer"], u, kw, mm, rnd)
        else:
            raise ValueError(f"unknown layer character {kind!r}")
    # the head a block of rows at a time: a control gives only its first
    # choice a row, so its logits never exist whole
    n_rows = rows.shape[0]
    chunk = min(2048, n_rows)
    padded = -(-n_rows // chunk) * chunk
    rows = jnp.pad(rows, (0, padded - n_rows))
    head = rnd(params["head"].astype(f32))

    def some_rows(i, out):
        at = jax.lax.dynamic_slice_in_dim(rows, i * chunk, chunk)
        logits = rnd(_rms(params["out_norm"], h[at], eps)) @ head
        return jax.lax.dynamic_update_slice(
            out, jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            if choices else logits, (i * chunk, 0))

    out = jax.lax.fori_loop(
        0, padded // chunk, some_rows,
        jnp.zeros((padded, 1), jnp.int32) if choices
        else jnp.zeros((padded, head.shape[1]), f32))
    out = out[:n_rows]
    return out[:, 0] if choices else out


class _Choices:
    """What a control hands back in place of its logits: only the token it
    puts first at each row, which is all the comparison reads of a control
    in a greedy request."""

    def __init__(self, tokens):
        self._tokens = tokens

    def argmax(self, axis=-1):
        assert axis == -1, axis
        return self._tokens


def make(config):
    """``reference(params, ids, rows)`` and ``controls``: a dict of
    ``name -> control(params, ids, rows)``, each jitted once. The reference
    multiplies in true float32. ``config["controls"]`` names the lower
    precisions (``"operands:<dtype>"``: both operands of every product
    rounded to ``<dtype>``, sums in float32); ``config["faults"]`` the
    planted faults of :data:`FAULTS`, given as ``"fault:<name>"``. A
    control returns its first choice a row (:class:`_Choices`)."""
    kw = dict(config["constructor_kwargs"])
    # what the engine held stays on the device until its objects are
    # collected and its executables are dropped
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
