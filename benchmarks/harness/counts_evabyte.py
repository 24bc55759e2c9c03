"""Operations and bytes an EvaByte style decoder needs, from the
configuration's shapes alone (the published keys at the top level of its
configuration file, as run) and from the rows a step reads.

As ``counts.py``: two operations per multiply-add, every weight that is
needed read once, K and V of the rows the mathematics reads and of no
other; padding, a table's unread rows and a free slot are not counted, so
a share taken of these cannot pass 100 %. Matrix work only: norms, gates,
the rotary turn, the softmax and a chunk's summary (16 rows of 128 a head,
once in 16 steps) are not counted.

A layer is EVA attention (``W_q, W_k, W_v, W_o`` of ``hidden x hidden``,
``phi`` and ``mu`` of ``hidden``) and a gated MLP (three matrices at
``intermediate_size``), each behind a norm. The position ``p`` of a stream
reads ``p mod window + 1`` exact rows of its window and ``(window / chunk)
x (p div window)`` summaries, K and V of ``heads x head`` each.
"""

from __future__ import annotations


def shape(config):
    """The numbers the counts need, from a configuration file's keys."""
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    return {"vocab": int(config["vocab_size"]), "d": d, "heads": heads,
            "hd": d // heads, "f": int(config["intermediate_size"]),
            "layers": int(config["num_hidden_layers"]),
            "pred": int(config["num_pred_heads"]),
            "window": int(config["window_size"]),
            "chunk": int(config["chunk_size"])}


def layer_matrix_params(s):
    """The seven matrices of one layer."""
    return 4 * s["d"] * s["d"] + 3 * s["d"] * s["f"]


def head_params(s):
    """The untied head: every prediction head in one matrix."""
    return s["d"] * s["pred"] * s["vocab"]


def param_count(s):
    """Every parameter: the embedding, the layers (matrices, two norm
    gains, ``phi`` and ``mu``), the final norm's gain and the head."""
    return (s["vocab"] * s["d"] + s["d"] + head_params(s)
            + s["layers"] * (layer_matrix_params(s) + 4 * s["d"]))


def rows_read(s, pos):
    """``(window rows, summaries)`` that the position ``pos`` reads."""
    per_window = s["window"] // s["chunk"]
    return pos % s["window"] + 1, per_window * (pos // s["window"])


def row_flops(s):
    """Scores and mix of one query against one row, every head, a layer."""
    return 4 * s["heads"] * s["hd"]


def token_flops(s, rows):
    """Forward operations of ONE position whose attention reads ``rows``
    rows (exact and summaries together) a layer, the head excluded."""
    return s["layers"] * (2 * layer_matrix_params(s) + row_flops(s) * rows)


def head_flops(s):
    return 2 * head_params(s)


def prefill_flops(s, prompt_len):
    """A prompt pass at its true length: position ``p`` reads its own
    rows; one row of logits (the last position's)."""
    n = int(prompt_len)
    w, per_window = s["window"], s["window"] // s["chunk"]
    full, rest = divmod(n, w)
    # sum over p < n of (p mod w + 1) and of per_window * (p div w)
    near = full * w * (w + 1) // 2 + rest * (rest + 1) // 2
    far = per_window * (w * full * (full - 1) // 2 + rest * full)
    return (n * token_flops(s, 0) + s["layers"] * row_flops(s) * (near + far)
            + head_flops(s))


def decode_flops(s, pos):
    """One byte fed back at position ``pos``, with its row of logits."""
    return token_flops(s, sum(rows_read(s, int(pos)))) + head_flops(s)


def row_bytes(s, cache_bytes):
    """K and V of one row, every head, one layer."""
    return 2 * s["heads"] * s["hd"] * cache_bytes


def decode_step_need(s, live_slots, window_rows, summary_rows, chunks_closed,
                     weight_bytes, cache_bytes, logit_bytes=4):
    """What one decode step over ``live_slots`` streams has to do when
    they read ``window_rows`` exact rows and ``summary_rows`` summaries
    between them and ``chunks_closed`` of them close a chunk: ``(flops,
    bytes)``. Every weight once; K and V of the rows read; the window row
    every live stream writes and the summary row a closing one writes; one
    float32 row of every head's logits a live stream."""
    rows = window_rows + summary_rows
    nbytes = (param_count(s) * weight_bytes
              + s["layers"] * row_bytes(s, cache_bytes)
              * (rows + live_slots + chunks_closed)
              + live_slots * s["pred"] * s["vocab"] * logit_bytes)
    flops = (live_slots * (token_flops(s, 0) + head_flops(s))
             + s["layers"] * row_flops(s) * rows)
    return flops, nbytes
