"""Operations and bytes a Nemotron-H style hybrid decoder needs, from the
configuration's shapes alone (the published keys at the top level of its
configuration file, as run; the router's width from ``published``) and from
what a step moves.

As ``counts.py``: two operations per multiply-add, every weight that is
needed read once, state and cache rows that the mathematics reads and no
other; padding, a table's unread rows, a free slot and unrouted experts are
not counted, so a share taken of these cannot pass 100 %. Matrix work and
the recurrence only: norms, gates, the convolution's four taps, the softmax
and the routing itself are not counted.

A layer is one mixer behind a norm, by its character in
``hybrid_override_pattern``: ``M`` a Mamba-2 mixer (its projections, and a
position's recurrence over S of ``heads x head_dim x state_size`` numbers:
a multiply for the decay, a multiply-add for the rank-1 update, a
multiply-add for the contraction with C, five operations an entry), ``E``
an expert layer (router, the latent pair, the shared expert; ``k`` routed
experts a token, of which the share held here), ``*`` attention with no
positional encoding (two products a head a row of context).
"""

from __future__ import annotations

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def shape(config):
    """The numbers the counts need, from a configuration file's keys."""
    pattern = config["hybrid_override_pattern"]
    h, p = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    g, n = int(config["n_groups"]), int(config["ssm_state_size"])
    return {
        "vocab": int(config["vocab_size"]), "d": int(config["hidden_size"]),
        "mamba": pattern.count(MAMBA), "moe": pattern.count(EXPERTS),
        "attn": pattern.count(ATTENTION),
        "heads": h, "head_dim": p, "groups": g, "state": n,
        "inner": h * p, "conv_dim": h * p + 2 * g * n,
        "taps": int(config["conv_kernel"]),
        "q_heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]),
        "experts": int(config["published"]["n_routed_experts"]),
        "held": int(config["n_routed_experts"]),
        "k": int(config["num_experts_per_tok"]),
        "f_expert": int(config["moe_intermediate_size"]),
        "latent": int(config["moe_latent_size"]),
        "f_shared": int(config["moe_shared_expert_intermediate_size"])}


def mamba_params(s):
    """One Mamba layer: ``W_in``, the convolution and its bias, ``dt_bias``,
    ``A_log``, ``D``, the grouped norm's gain and ``W_out``."""
    return (s["d"] * (s["inner"] + s["conv_dim"] + s["heads"])
            + (s["taps"] + 1) * s["conv_dim"] + 3 * s["heads"] + s["inner"]
            + s["inner"] * s["d"])


def mamba_matrix_params(s):
    return s["d"] * (s["inner"] + s["conv_dim"] + s["heads"]) \
        + s["inner"] * s["d"]


def attention_params(s):
    """One attention layer's four matrices."""
    q, kv = s["q_heads"] * s["hd"], s["kv_heads"] * s["hd"]
    return 2 * s["d"] * q + 2 * s["d"] * kv


def moe_outside_params(s):
    """An expert layer outside its routed experts: router, the latent
    pair, the shared expert (the router's bias counted with
    :func:`param_count`)."""
    return (s["d"] * s["experts"] + 2 * s["d"] * s["latent"]
            + 2 * s["d"] * s["f_shared"])


def expert_params(s):
    """One routed expert's two matrices, at the latent width."""
    return 2 * s["latent"] * s["f_expert"]


def non_expert_matrix_params(s):
    """Every matrix outside the routed experts and outside the embedding
    and the head: what a token multiplies through whatever it is routed
    to."""
    return (s["mamba"] * mamba_matrix_params(s)
            + s["attn"] * attention_params(s)
            + s["moe"] * moe_outside_params(s))


def param_count(s, experts_held=None):
    """Every parameter as held here (embedding and head untied; norm
    gains and the router's bias included)."""
    held = s["held"] if experts_held is None else experts_held
    layers = s["mamba"] + s["moe"] + s["attn"]
    return (2 * s["vocab"] * s["d"] + s["d"] * (layers + 1)
            + s["mamba"] * mamba_params(s) + s["attn"] * attention_params(s)
            + s["moe"] * (moe_outside_params(s) + s["experts"]
                          + held * expert_params(s)))


def state_numbers(s):
    """One slot's S of one Mamba layer."""
    return s["heads"] * s["head_dim"] * s["state"]


def recurrence_flops(s):
    """One position through every Mamba layer's recurrence."""
    return s["mamba"] * 5 * state_numbers(s)


def token_matrix_flops(s):
    """One token through every matrix but the head: the non-expert ones
    whole, and of its ``k`` routed experts the share that is held here
    (``k x held / experts`` experts' worth, what the routing gives on
    average)."""
    routed = s["k"] * s["held"] / s["experts"] * expert_params(s)
    return 2 * (non_expert_matrix_params(s) + s["moe"] * routed)


def head_flops(s):
    return 2 * s["d"] * s["vocab"]


def context_flops(s):
    """One query against one row of context, every attention layer."""
    return s["attn"] * 4 * s["q_heads"] * s["hd"]


def prefill_flops(s, prompt_len):
    """A prompt pass at its true length: every position through every
    matrix and every recurrence, attending to its own context; one row of
    logits (the last position's)."""
    n = int(prompt_len)
    return (n * (token_matrix_flops(s) + recurrence_flops(s))
            + context_flops(s) * n * (n + 1) // 2 + head_flops(s))


def decode_flops(s, pos):
    """One token fed back at position ``pos``, with its row of logits."""
    return (token_matrix_flops(s) + recurrence_flops(s) + head_flops(s)
            + context_flops(s) * (int(pos) + 1))


def kv_row_bytes(s, cache_bytes):
    """K and V of one position, one attention layer."""
    return 2 * s["kv_heads"] * s["hd"] * cache_bytes


def ssm_step_need(s, slots):
    """What the state kernel does for ``slots`` live slots over every Mamba
    layer: ``(flops, bytes)``. S read and written (float32), and the rows
    it is updated from and read out by: ``x`` (heads x head_dim), ``B``
    and ``C`` (groups x state_size), ``dt`` (heads), float32, and ``y``
    written."""
    rows = 2 * s["inner"] + 2 * s["groups"] * s["state"] + s["heads"]
    nbytes = s["mamba"] * slots * 4 * (2 * state_numbers(s) + rows)
    return slots * recurrence_flops(s), nbytes


def decode_step_need(s, live_slots, context_rows, experts_hit, weight_bytes,
                     cache_bytes, logit_bytes=4):
    """What one decode step over ``live_slots`` streams needs when their
    attention reads ``context_rows`` positions between them (each its own,
    up to the one it writes) and their choices fall on ``experts_hit`` of
    the experts held an expert layer: ``(flops, bytes)``. Every non-expert
    weight once (the embedding excepted: a row a stream), the weights of
    the experts hit and of no other; S read and written and the
    convolution's taps read and written a live slot a Mamba layer; K and
    V of the rows read and of the row written; one float32 row of logits
    a live stream."""
    weights = (non_expert_matrix_params(s) + s["d"] * s["vocab"]
               + s["moe"] * experts_hit * expert_params(s))
    state = s["mamba"] * live_slots * (
        2 * 4 * state_numbers(s)
        + 2 * (s["taps"] - 1) * s["conv_dim"] * cache_bytes)
    nbytes = (weights * weight_bytes + state
              + s["attn"] * kv_row_bytes(s, cache_bytes)
              * (context_rows + live_slots)
              + live_slots * s["vocab"] * logit_bytes)
    flops = (live_slots * (token_matrix_flops(s) + recurrence_flops(s)
                           + head_flops(s))
             + context_flops(s) * context_rows)
    return flops, nbytes
