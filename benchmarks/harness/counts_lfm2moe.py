"""Operations and bytes an LFM2-MoE style decoder needs, from the
configuration's shapes alone (the published keys at the top level of its
configuration file, as run).

As ``counts.py``: two operations per multiply-add, every weight that is
needed read once, K and V of live tokens only; padding, wasted rows and
unrouted experts are not counted, so a share taken of these cannot pass
100 %. Matrix work only: norms, gates, the rotary turn and the softmax
are not counted.

A layer is an operator (``conv``: in-projection to 3 x hidden, a
depthwise convolution of ``conv_L_cache`` taps, out-projection;
``full_attention``: q over ``num_attention_heads``, k and v over
``num_key_value_heads``, the output projection) and a feed-forward (the
first ``num_dense_layers``: three matrices at ``intermediate_size``;
later: a router over ``num_experts`` and ``num_experts_per_tok`` experts
of three matrices at ``moe_intermediate_size`` a token).
"""

from __future__ import annotations


def shape(config):
    """The numbers the counts need, from a configuration file's keys."""
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    kinds = list(config["layer_types"])
    dense = int(config["num_dense_layers"])
    return {
        "vocab": int(config["vocab_size"]), "d": d,
        "heads": heads, "kv": int(config["num_key_value_heads"]),
        "hd": d // heads, "taps": int(config["conv_L_cache"]),
        "f_dense": int(config["intermediate_size"]),
        "f_expert": int(config["moe_intermediate_size"]),
        "experts": int(config["num_experts"]),
        "k": int(config["num_experts_per_tok"]),
        "conv_layers": kinds.count("conv"),
        "attn_layers": kinds.count("full_attention"),
        "dense_layers": min(dense, len(kinds)),
        "routed_layers": max(0, len(kinds) - dense)}


def conv_params(s):
    return s["d"] * 3 * s["d"] + s["d"] * s["d"] + s["taps"] * s["d"]


def attn_params(s):
    q, kv = s["heads"] * s["hd"], s["kv"] * s["hd"]
    return 2 * s["d"] * q + 2 * s["d"] * kv + 2 * s["hd"]   # + the QK gains


def expert_params(s):
    """One expert's three matrices."""
    return 3 * s["d"] * s["f_expert"]


def router_params(s):
    return s["d"] * s["experts"] + s["experts"]              # + the bias


def param_count(s, experts_held=None):
    """Every parameter (tied head counted once); ``experts_held`` of
    each routed layer's experts (default: all)."""
    held = s["experts"] if experts_held is None else experts_held
    layers = s["conv_layers"] + s["attn_layers"]
    return (s["vocab"] * s["d"] + s["d"] + 2 * s["d"] * layers
            + s["conv_layers"] * conv_params(s)
            + s["attn_layers"] * attn_params(s)
            + s["dense_layers"] * 3 * s["d"] * s["f_dense"]
            + s["routed_layers"] * (router_params(s)
                                    + held * expert_params(s)))


def non_expert_params(s):
    return param_count(s, experts_held=0)


def token_flops(s, context):
    """Forward operations of ONE token whose attention spans ``context``
    positions (itself included), the head excluded."""
    d, q, kv = s["d"], s["heads"] * s["hd"], s["kv"] * s["hd"]
    conv = 2 * (d * 3 * d + d * d) + 2 * s["taps"] * d
    attn = 2 * (2 * d * q + 2 * d * kv) + 4 * context * q
    routed = 2 * d * s["experts"] + s["k"] * 2 * expert_params(s)
    return (s["conv_layers"] * conv + s["attn_layers"] * attn
            + s["dense_layers"] * 2 * 3 * d * s["f_dense"]
            + s["routed_layers"] * routed)


def head_flops(s):
    return 2 * s["d"] * s["vocab"]


def prefill_flops(s, prompt_len):
    """A causal prompt pass at its true length: token i attends to i
    positions; one row of logits (the last position's)."""
    n = prompt_len
    pairs = n * (n + 1) // 2
    return (n * token_flops(s, 0)
            + s["attn_layers"] * 4 * s["heads"] * s["hd"] * pairs
            + head_flops(s))


def decode_flops(s, context):
    """One generated token fed back at ``context`` positions, with its
    row of logits."""
    return token_flops(s, context) + head_flops(s)


def kv_token_bytes(s, cache_bytes):
    """K and V of one position, all attention layers."""
    return 2 * s["attn_layers"] * s["kv"] * s["hd"] * cache_bytes


def decode_step_need(s, live_slots, live_tokens, experts_hit, weight_bytes,
                     cache_bytes, logit_bytes=4):
    """What one decode step over ``live_slots`` streams holding
    ``live_tokens`` cached positions between them has to do, when the
    live streams' choices fall on ``experts_hit`` experts a routed layer:
    ``(flops, bytes)``. Every non-expert weight once; the weights of the
    experts hit and of no other; K and V of the live tokens plus the row
    written; every live slot's convolution states read and written; one
    row of logits a live slot."""
    weights = (non_expert_params(s)
               + s["routed_layers"] * experts_hit * expert_params(s))
    kv = kv_token_bytes(s, cache_bytes)
    state = 2 * s["conv_layers"] * s["taps"] * s["d"] * cache_bytes
    nbytes = (weights * weight_bytes + (live_tokens + live_slots) * kv
              + live_slots * state + live_slots * s["vocab"] * logit_bytes)
    flops = (live_slots * (token_flops(s, 0) + head_flops(s))
             + s["attn_layers"] * 4 * s["heads"] * s["hd"] * live_tokens)
    return flops, nbytes


def expert_step_need(s, live_slots, experts_hit, weight_bytes):
    """The expert products of one decode step alone: ``(flops, bytes)``.
    The three matrices of every expert hit, once, in every routed layer;
    ``num_experts_per_tok`` experts' arithmetic a live slot."""
    nbytes = (s["routed_layers"] * experts_hit * expert_params(s)
              * weight_bytes)
    flops = s["routed_layers"] * live_slots * s["k"] * 2 * expert_params(s)
    return flops, nbytes
