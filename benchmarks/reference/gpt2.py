"""GPT-2 (Radford et al. 2019) as a plain float32 forward pass.

Pre-LN decoder: ``x += attn(ln1(x)); x += mlp(ln2(x))``, learned
positions, LayerNorm (eps 1e-5), GELU (tanh form, as GPT-2's
``gelu_new``), causal softmax attention scaled by ``head_dim ** -0.5``,
final LayerNorm, output head tied to the token embedding. No cache, no
batching, no kernels: one whole sequence at a time.

Departure from the published model, shared with the program under test:
the four attention projections carry no bias (the parameter tree the
benchmark fills has none).

The parameter tree is the one ``benchmarks/harness/weights.py`` fills:
``{"gpt": {"tok_emb", "pos_emb", "ln_f": {weight, bias}, "layers":
[{"attn": {wq, wk, wv, wo}, "ln1", "ln2", "fc1": {weight, bias},
"fc2"}]}}`` with matrices stored ``(in, out)``.

The reference computes in float32 with true-float32 products
(``jax.default_matmul_precision("highest")``); the controls, which the
comparison has to fail, are the same code one precision down (``make``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _layer_norm(p, x, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["weight"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def _round_to(x, operand_dtype):
    """``x`` rounded to ``operand_dtype`` and back: what a multiplication
    in that type sees. An 8-bit float gets one scale per tensor (its
    largest element lands on the type's largest), as an 8-bit path would."""
    if operand_dtype is None:
        return x
    dt = jnp.dtype(operand_dtype)
    if dt.itemsize > 1:
        return x.astype(dt).astype(x.dtype)
    top = float(jnp.finfo(dt).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dt).astype(x.dtype) * scale


def forward_logits(params, ids, rows, n_heads, dtype=jnp.float32,
                   operand_dtype=None):
    """Logits ``(len(rows), vocab)`` at positions ``rows`` of the one
    sequence ``ids`` (T,). Positions past the real length may hold any
    token: causal attention keeps them out of the rows before them.
    ``operand_dtype`` rounds both operands of every matrix product (weights,
    activations, keys, values, probabilities) to that type; sums stay in
    ``dtype``."""
    g = jax.tree_util.tree_map(lambda a: a.astype(dtype), params["gpt"])

    def mm(a, b):
        return _round_to(a, operand_dtype) @ _round_to(b, operand_dtype)

    t = ids.shape[0]
    x = g["tok_emb"][ids] + g["pos_emb"][:t]
    hd = x.shape[-1] // n_heads
    causal = jnp.tril(jnp.ones((t, t), bool))
    for lp in g["layers"]:
        h = _layer_norm(lp["ln1"], x)
        a = lp["attn"]

        def heads(w):
            return mm(h, w).reshape(t, n_heads, hd).transpose(1, 0, 2)

        q, k, v = heads(a["wq"]), heads(a["wk"]), heads(a["wv"])
        s = jnp.einsum("hqd,hkd->hqk", _round_to(q, operand_dtype),
                       _round_to(k, operand_dtype)) * (hd ** -0.5)
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dtype)
        o = jnp.einsum("hqk,hkd->hqd", _round_to(p, operand_dtype),
                       _round_to(v, operand_dtype))
        x = x + mm(o.transpose(1, 0, 2).reshape(t, -1), a["wo"])
        h = _layer_norm(lp["ln2"], x)
        h = _gelu_tanh(mm(h, lp["fc1"]["weight"]) + lp["fc1"]["bias"])
        x = x + mm(h, lp["fc2"]["weight"]) + lp["fc2"]["bias"]
    x = _layer_norm(g["ln_f"], x)
    return mm(x[rows], g["tok_emb"].T).astype(jnp.float32)


def make(config):
    """``reference(params, ids, rows)`` and ``controls``: a dict of
    ``name -> control(params, ids, rows)``, each jitted once. The reference
    multiplies in true float32. A control is the reference one precision
    down, put in the program's place; ``config["controls"]`` names them:

    ``"bfloat16"``: everything computed in bfloat16 (what the contract
    prescribes below float32);
    ``"operands:<dtype>"``: true float32 sums, but both operands of every
    matrix product rounded to ``<dtype>`` (``float8_e4m3fn``: what an
    8-bit weight or cache path would multiply).
    """
    n_heads = int(config["constructor_kwargs"]["n_heads"])

    @jax.jit
    def reference(params, ids, rows):
        with jax.default_matmul_precision("highest"):
            return forward_logits(params, ids, rows, n_heads, jnp.float32)

    def build(name):
        if name.startswith("operands:"):
            operand = name.split(":", 1)[1]

            @jax.jit
            def control(params, ids, rows):
                with jax.default_matmul_precision("highest"):
                    return forward_logits(params, ids, rows, n_heads,
                                          jnp.float32, operand)
            return control
        return jax.jit(lambda params, ids, rows: forward_logits(
            params, ids, rows, n_heads, jnp.dtype(name)))

    return reference, {name: build(name) for name in config["controls"]}
