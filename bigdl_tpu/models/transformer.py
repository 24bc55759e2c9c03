"""Transformer encoder + BERT-base builder.

BASELINE.md lists "BERT-base (imported via TF-graph loader)" as a reference
config; beyond import parity we provide a native TPU-first BERT whose
attention can run ring/Ulysses sequence-parallel (parallel/sequence.py) —
the long-context capability the reference lacks entirely.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.module import Module
from bigdl_tpu.parallel.sequence import MultiHeadAttention


class TransformerEncoderLayer(Module):
    def __init__(self, hidden_size, n_heads, intermediate_size=None,
                 dropout=0.0, sequence_parallel=None, causal=False):
        super().__init__()
        self.hidden_size = hidden_size
        inter = intermediate_size or 4 * hidden_size
        self.attn = MultiHeadAttention(hidden_size, n_heads, dropout,
                                       sequence_parallel, causal)
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

    def apply(self, params, state, x, *, training=False, rng=None):
        # post-LN like original BERT
        h = self.attn.call(params["attn"], x)
        if training and self.dropout > 0 and rng is not None:
            keep = jax.random.bernoulli(jax.random.fold_in(rng, 0),
                                        1 - self.dropout, h.shape)
            h = jnp.where(keep, h / (1 - self.dropout), 0.0)
        x = self.ln1.call(params["ln1"], x + h)
        h = self.fc2.call(params["fc2"],
                          jax.nn.gelu(self.fc1.call(params["fc1"], x)))
        if training and self.dropout > 0 and rng is not None:
            keep = jax.random.bernoulli(jax.random.fold_in(rng, 1),
                                        1 - self.dropout, h.shape)
            h = jnp.where(keep, h / (1 - self.dropout), 0.0)
        return self.ln2.call(params["ln2"], x + h), state


class BERT(Module):
    """BERT encoder (base: 12 layers, 768 hidden, 12 heads)."""

    def __init__(self, vocab_size=30522, hidden_size=768, n_layers=12,
                 n_heads=12, max_position=512, type_vocab_size=2,
                 intermediate_size=None, dropout=0.0,
                 sequence_parallel=None, remat=False):
        super().__init__()
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.max_position = max_position
        self.type_vocab_size = type_vocab_size
        self.layers = [TransformerEncoderLayer(hidden_size, n_heads,
                                               intermediate_size, dropout,
                                               sequence_parallel)
                       for _ in range(n_layers)]
        self.ln = nn.LayerNormalization(hidden_size)
        # per-layer rematerialisation: backward recomputes each block's
        # activations instead of storing them — O(sqrt) activation memory,
        # the standard long-context/large-batch trade
        self.remat = remat

    def setup(self, rng, input_spec):
        ks = jax.random.split(rng, len(self.layers) + 4)
        std = 0.02
        params = {
            "tok_emb": std * jax.random.normal(
                ks[0], (self.vocab_size, self.hidden_size)),
            "pos_emb": std * jax.random.normal(
                ks[1], (self.max_position, self.hidden_size)),
            "type_emb": std * jax.random.normal(
                ks[2], (self.type_vocab_size, self.hidden_size)),
            "ln": self.ln.setup(ks[3], None)[0],
            "layers": [l.setup(k, None)[0]
                       for l, k in zip(self.layers, ks[4:])],
        }
        return params, ()

    def apply(self, params, state, x, *, training=False, rng=None):
        from bigdl_tpu.utils.table import Table
        if isinstance(x, (Table, dict)):
            ids, types = x[1], x[2]
        else:
            ids, types = x, None
        ids = ids.astype(jnp.int32)
        t = ids.shape[1]
        h = jnp.take(params["tok_emb"], ids, axis=0)
        sp = self.layers[0].attn.sequence_parallel if self.layers else None
        if sp is not None and sp[0] == "ring_inner":
            # sequence is sharded: use GLOBAL positions for this shard
            from jax import lax
            start = lax.axis_index(sp[1]) * t
            pos = lax.dynamic_slice_in_dim(params["pos_emb"], start, t)
            h = h + pos[None]
        else:
            h = h + params["pos_emb"][None, :t]
        if types is not None:
            h = h + jnp.take(params["type_emb"], types.astype(jnp.int32),
                             axis=0)
        h = self.ln.call(params["ln"], h)
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
        return h, state


def bert_base(sequence_parallel=None, **kw):
    return BERT(sequence_parallel=sequence_parallel, **kw)


class BertForMLM(Module):
    """BERT encoder + dense MLM head producing (B*T, vocab) logits — the
    pretraining configuration (pair with ``CrossEntropyCriterion`` on
    flattened token labels; use padding_value to mask unpredicted
    positions). ``examples/bert_mlm_pretrain.py`` trains it."""

    def __init__(self, vocab_size=30522, hidden_size=768, n_layers=12,
                 n_heads=12, max_position=512, **kw):
        super().__init__()
        self.vocab_size = vocab_size
        self.bert = BERT(vocab_size=vocab_size, hidden_size=hidden_size,
                         n_layers=n_layers, n_heads=n_heads,
                         max_position=max_position, **kw)
        self.head = nn.Linear(hidden_size, vocab_size)

    def setup(self, rng, input_spec):
        k1, k2 = jax.random.split(rng)
        return {"bert": self.bert.setup(k1, input_spec)[0],
                "head": self.head.setup(k2, None)[0]}, ()

    def apply(self, params, state, x, *, training=False, rng=None):
        h, _ = self.bert.apply(params["bert"], (), x,
                               training=training, rng=rng)
        logits = self.head.call(params["head"], h)
        return logits.reshape(-1, self.vocab_size), state


def make_sp_train_step(model, criterion, optim_method, mesh,
                       data_axis="data", seq_axis="seq"):
    """dp x sp train step: batch sharded over ``data_axis``, sequence over
    ``seq_axis`` (model must use sequence_parallel=("ring_inner", seq_axis,
    mesh.shape[seq_axis])). Gradients are psum'd over BOTH axes; params and
    optimizer state stay replicated (the ZeRO path composes the same way via
    parallel/allreduce.py when wanted)."""
    from jax.sharding import PartitionSpec as P
    from jax import lax

    both = (data_axis, seq_axis)

    def local_step(params, opt_state, x, y):
        def loss_fn(p):
            out, _ = model.apply(p, (), x, training=True)
            return criterion.apply(out, y)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # global loss = mean of equal-size local means -> grads average too
        grads = jax.tree_util.tree_map(
            lambda g: lax.pmean(g, both), grads)
        loss = lax.pmean(loss, both)
        new_params, new_opt = optim_method.update(grads, opt_state, params)
        return new_params, new_opt, loss

    x_spec = P(data_axis, seq_axis)
    step = shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P(), x_spec, x_spec),
        out_specs=(P(), P(), P()), check_vma=False)
    return jax.jit(step, donate_argnums=(0, 1))
