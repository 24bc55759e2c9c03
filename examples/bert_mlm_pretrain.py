#!/usr/bin/env python
"""Single-chip BERT MLM pretraining — the compute-bound flagship config.

BertForMLM (models/transformer.py) + CrossEntropyCriterion + Adam in bf16;
attention kernel auto-selected per shape (parallel/sequence.py
flash_profitable), with real masked-LM data handling: 15% of tokens are
masked, only those positions contribute loss (ClassNLL padding_value).

  python examples/bert_mlm_pretrain.py --steps 20           # synthetic data
  python examples/bert_mlm_pretrain.py --hidden 768 --layers 12   # BERT-Base
"""

import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=1000)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--learning-rate", type=float, default=1e-3)
    ap.add_argument("--mask-prob", type=float, default=0.15)
    args = ap.parse_args()

    from bigdl_tpu.utils.engine import Engine
    Engine.init()

    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu.models.transformer import BertForMLM
    from bigdl_tpu.optim import Adam
    from bigdl_tpu.optim.optimizer import make_train_step

    mask_id = args.vocab - 1  # last vocab entry doubles as [MASK]
    model = BertForMLM(vocab_size=args.vocab, hidden_size=args.hidden,
                       n_layers=args.layers, n_heads=args.heads,
                       max_position=max(512, args.seq_len))
    model.build(0, (args.batch, args.seq_len))
    opt = Adam(learningrate=args.learning_rate)
    # unmasked positions carry label -1 -> masked out of the loss
    crit = nn.CrossEntropyCriterion()
    crit.nll.padding_value = -1
    step = make_train_step(model, crit, opt, compute_dtype=jnp.bfloat16)

    params, state = model.params, model.state
    opt_state = opt.init_state(params)
    rng = np.random.default_rng(0)
    key = jax.random.key(0)

    # synthetic corpus with learnable bigram structure
    base = rng.integers(0, args.vocab - 1, (args.batch, args.seq_len))
    base = np.sort(base, axis=1)

    t0 = time.time()
    for it in range(args.steps):
        tokens = base.copy()
        masked = rng.random(tokens.shape) < args.mask_prob
        labels = np.where(masked, tokens, -1).reshape(-1)
        tokens[masked] = mask_id
        params, state, opt_state, loss = step(
            params, state, opt_state, key,
            jnp.asarray(tokens, jnp.int32), jnp.asarray(labels, jnp.int32))
        if it % 5 == 0 or it == args.steps - 1:
            print(f"step {it}: masked-LM loss {float(loss):.4f}", flush=True)
    dt = time.time() - t0
    toks = args.batch * args.seq_len * args.steps
    print(f"{toks / dt:,.0f} tokens/s over {args.steps} steps")


if __name__ == "__main__":
    main()
