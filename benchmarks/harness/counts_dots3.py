"""Operations and bytes a dots3-note style decoder needs, from the
configuration's shapes alone (the published keys at the top level of its
configuration file, as run; the router's width from ``published``) and from
the rows a step scores and reads.

As ``counts.py``: two operations per multiply-add, every weight that is
needed read once, cache rows that the mathematics reads and no other;
padding, a table's unread rows, a free slot and unrouted experts are not
counted, so a share taken of these cannot pass 100 %. Matrix work only:
norms, gates, the rotary turn, the softmax and the selection itself (a
choice of 2048 among the scores, no multiply-add) are not counted.

A layer is latent attention and a feed-forward, each behind a norm. A
``full_attention`` layer keeps ``kv_lora_rank + rope`` numbers a position
(the latent and one turned key) and ``index_head_dim`` more (the index key);
its step scores every position up to its own with ``index_n_heads`` index
heads and reads the ``index_topk`` best. A ``sliding_attention`` layer keeps
``swa_kv_lora_rank + rope`` numbers for its last ``sliding_window_size``
positions. Attention over a row that is read costs least in the form the
cache is kept in: one query a step multiplies ``W_kvb`` into the query and
the output once (its parameters' worth, counted with the matrices) and pays
``heads x (2 x rank + rope)`` multiply-adds a row; a prompt pass expands
each position's keys and values once (again ``W_kvb``'s worth a position)
and pays ``heads x (nope + rope + v)`` a pair.
"""

from __future__ import annotations

FULL, SLIDING = "full_attention", "sliding_attention"


def shape(config):
    """The numbers the counts need, from a configuration file's keys."""
    kinds = list(config["layer_types"])
    dense = min(int(config["first_k_dense_replace"]), len(kinds))

    def attn(p, heads):
        return {"heads": int(config[heads]),
                "rq": int(config[p + "q_lora_rank"]),
                "rkv": int(config[p + "kv_lora_rank"]),
                "nope": int(config[p + "qk_nope_head_dim"]),
                "rope": int(config[p + "qk_rope_head_dim"]),
                "v": int(config[p + "v_head_dim"])}

    return {
        "vocab": int(config["vocab_size"]), "d": int(config["hidden_size"]),
        "full": attn("", "num_attention_heads"),
        "swa": attn("swa_", "swa_num_attention_heads"),
        "index_heads": int(config["index_n_heads"]),
        "index_dim": int(config["index_head_dim"]),
        "topk": int(config["index_topk"]),
        "window": int(config["sliding_window_size"]),
        "f_dense": int(config["intermediate_size"]),
        "f_expert": int(config["moe_intermediate_size"]),
        "experts": int(config["published"]["n_routed_experts"]),
        "held": int(config["n_routed_experts"]),
        "shared": int(config["n_shared_experts"]),
        "k": int(config["num_experts_per_tok"]),
        "full_layers": kinds.count(FULL),
        "swa_layers": kinds.count(SLIDING),
        "dense_layers": dense, "routed_layers": len(kinds) - dense}


def latent_params(a, d, gate=True):
    """The matrices of one latent attention: both query factors, the
    joint latent projection, ``W_kvb``, ``W_o`` and the gate."""
    return (d * a["rq"] + a["rq"] * a["heads"] * (a["nope"] + a["rope"])
            + d * (a["rkv"] + a["rope"])
            + a["rkv"] * a["heads"] * (a["nope"] + a["v"])
            + a["heads"] * a["v"] * d + (d * a["heads"] if gate else 0))


def indexer_params(s):
    return (s["full"]["rq"] * s["index_heads"] * s["index_dim"]
            + s["d"] * s["index_dim"] + s["d"] * s["index_heads"])


def expert_params(s):
    """One expert's three matrices."""
    return 3 * s["d"] * s["f_expert"]


def non_expert_matrix_params(s):
    """Every matrix outside the routed experts and outside the embedding
    and the head: what a token multiplies through whatever it is routed
    to."""
    return (s["full_layers"] * (latent_params(s["full"], s["d"])
                                + indexer_params(s))
            + s["swa_layers"] * latent_params(s["swa"], s["d"])
            + s["dense_layers"] * 3 * s["d"] * s["f_dense"]
            + s["routed_layers"] * (s["d"] * s["experts"]
                                    + s["shared"] * expert_params(s)))


def param_count(s, experts_held=None):
    """Every parameter as held here (embedding and head untied; norm
    gains, the index key's LayerNorm and the router's bias included)."""
    held = s["held"] if experts_held is None else experts_held
    layers = s["full_layers"] + s["swa_layers"]
    small = (s["d"] + 2 * s["d"] * layers
             + s["full_layers"] * (s["full"]["rq"] + s["full"]["rkv"]
                                   + 2 * s["index_dim"])
             + s["swa_layers"] * (s["swa"]["rq"] + s["swa"]["rkv"])
             + s["routed_layers"] * s["experts"])
    return (2 * s["vocab"] * s["d"] + small + non_expert_matrix_params(s)
            + s["routed_layers"] * held * expert_params(s))


def token_matrix_flops(s):
    """One token through every matrix but the head: the non-expert ones
    whole, and of its ``k`` routed experts the share that is held here
    (``k x held / experts`` experts' worth, what the routing gives on
    average)."""
    routed = s["k"] * s["held"] / s["experts"] * expert_params(s)
    return 2 * (non_expert_matrix_params(s) + s["routed_layers"] * routed)


def head_flops(s):
    return 2 * s["d"] * s["vocab"]


def index_row_flops(s):
    """One query scoring one position, every index head."""
    return 2 * s["index_heads"] * s["index_dim"]


def step_row_flops(a):
    """One step's query against one cache row, in the latent space."""
    return 2 * a["heads"] * (2 * a["rkv"] + a["rope"])


def pair_flops(a):
    """One prompt position against one earlier one, keys and values
    expanded."""
    return 2 * a["heads"] * (a["nope"] + a["rope"] + a["v"])


def capped_sum(n, cap):
    """``sum over p < n of min(p + 1, cap)``."""
    m = min(n, cap)
    return m * (m + 1) // 2 + (n - m) * cap


def rows_read(s, pos):
    """``(scored, selected, window)`` rows of the position ``pos``."""
    return pos + 1, min(pos + 1, s["topk"]), min(pos + 1, s["window"])


def prefill_flops(s, prompt_len):
    """A prompt pass at its true length: every position scores and reads
    its own rows; one row of logits (the last position's)."""
    n = int(prompt_len)
    return (n * token_matrix_flops(s)
            + s["full_layers"] * (index_row_flops(s) * n * (n + 1) // 2
                                  + pair_flops(s["full"])
                                  * capped_sum(n, s["topk"]))
            + s["swa_layers"] * pair_flops(s["swa"])
            * capped_sum(n, s["window"])
            + head_flops(s))


def decode_flops(s, pos):
    """One token fed back at position ``pos``, with its row of logits."""
    scored, chosen, near = rows_read(s, int(pos))
    return (token_matrix_flops(s) + head_flops(s)
            + s["full_layers"] * (index_row_flops(s) * scored
                                  + step_row_flops(s["full"]) * chosen)
            + s["swa_layers"] * step_row_flops(s["swa"]) * near)


def row_bytes(s, cache_bytes):
    """``(index key, full layer's latent row, window layer's latent
    row)`` in bytes."""
    return (s["index_dim"] * cache_bytes,
            (s["full"]["rkv"] + s["full"]["rope"]) * cache_bytes,
            (s["swa"]["rkv"] + s["swa"]["rope"]) * cache_bytes)


def decode_step_need(s, live_slots, context_rows, selected_rows, window_rows,
                     experts_hit, weight_bytes, cache_bytes, logit_bytes=4):
    """What one decode step over ``live_slots`` streams has to do when
    between them they score ``context_rows`` positions and read
    ``selected_rows`` of them a full layer and ``window_rows`` a window
    layer, and their choices fall on ``experts_hit`` of the experts held a
    routed layer: ``(flops, bytes)``. Every non-expert weight once (the
    embedding excepted: a row a stream), the weights of the experts hit
    and of no other; the index keys scored, the latents read, the rows
    written; one float32 row of logits a live stream."""
    key, full, near = row_bytes(s, cache_bytes)
    weights = (non_expert_matrix_params(s) + s["d"] * s["vocab"]
               + s["routed_layers"] * experts_hit * expert_params(s))
    nbytes = (weights * weight_bytes
              + s["full_layers"] * (key * (context_rows + live_slots)
                                    + full * (selected_rows + live_slots))
              + s["swa_layers"] * near * (window_rows + live_slots)
              + live_slots * s["vocab"] * logit_bytes)
    flops = (live_slots * (token_matrix_flops(s) + head_flops(s))
             + s["full_layers"] * (index_row_flops(s) * context_rows
                                   + step_row_flops(s["full"])
                                   * selected_rows)
             + s["swa_layers"] * step_row_flops(s["swa"]) * window_rows)
    return flops, nbytes


def latent_read_need(s, selected_rows, window_rows, cache_bytes):
    """The step's reads of its latent caches alone (what
    ``ops/latent_attention.py`` is called for, once a layer): ``(flops,
    bytes)`` for the rows the MATHEMATICS reads, ``selected_rows`` a full
    layer and ``window_rows`` a window layer, each row once and its query's
    products against it."""
    _, full, near = row_bytes(s, cache_bytes)
    nbytes = (s["full_layers"] * full * selected_rows
              + s["swa_layers"] * near * window_rows)
    flops = (s["full_layers"] * step_row_flops(s["full"]) * selected_rows
             + s["swa_layers"] * step_row_flops(s["swa"]) * window_rows)
    return flops, nbytes


def expert_step_need(s, assignments_held, experts_hit, weight_bytes):
    """The ROUTED experts' products of one decode step alone (what the
    grouped product is called for, three times a routed layer; the shared
    expert is a plain product beside it and not counted): ``(flops,
    bytes)``. The three matrices of every expert hit of those held, once,
    in every routed layer, against the arithmetic of the assignments that
    fell on them (``assignments_held``, a layer)."""
    nbytes = (s["routed_layers"] * experts_hit * expert_params(s)
              * weight_bytes)
    flops = s["routed_layers"] * assignments_held * 2 * expert_params(s)
    return flops, nbytes
