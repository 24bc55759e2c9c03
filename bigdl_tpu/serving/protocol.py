"""What ``ServingEngine`` and the dense slot table ask of a model.

A model is served through these names and through nothing else of it
(``models/gpt.py``'s ``GPTForCausalLM`` and ``models/lfm2.py``'s
``LFM2ForCausalLM`` are the two implementations; docs/serving.md has the
contract in prose):

``vocab_size``, ``max_position``
    ints: the width of a row of logits, and the positions a slot holds
    (prompt plus generated tokens).
``serving_dtype(params)``
    the dtype the model is served in: its cache's, and its logits
    table's unless ``logits_dtype`` names another.
``logits_dtype``
    None, or the dtype of the logits it returns.
``init_cache(slots, dtype, sharding=None)``
    the per-slot state, a list with one dict a layer. EVERY leaf has the
    slot axis first; a layer that keeps keys and values names them
    ``"k"`` and ``"v"``, ``(slots, heads, max_position, head_dim)``; any
    other leaf is fixed-size state (a convolution's last taps). The slot
    table allocates it once, scatters a prefill's rows into it leaf by
    leaf, and donates it through every step.
``prefill(params, cache, ids, prompt_len) -> (h_last, cache)``
    ``ids`` (W, bucket) right-padded, ``prompt_len`` (W,): the final
    hidden row at each prompt's last real position, and ``cache`` (W
    rows, as ``init_cache(W, ...)`` made it) holding each row's state as
    of ITS length, whatever the padding holds.
``decode_step(params, cache, tok, pos, in_place=False, read=None) -> (h, cache)``
    one token a slot, slot ``b`` at position ``pos[b]``. ``in_place``
    is the table's word that ``ops/kv_write.py`` applies to its K/V
    leaves. ``read`` is None, or (slots,) int32 where the table also
    takes ``ops/decode_attention.py``: the positions slot ``b``'s
    attention reads, ``pos[b] + 1`` for a live slot and 0 for a free
    one, whose row then comes back as junk nobody reads. A model with
    routed experts (``experts_per_token`` > 0) also takes ``live=``
    (slots,) bool: its routed layers leave the dead
    slots out, and it returns, third, the mean over those layers of how
    many experts the live slots chose.
``logits(params, h)``
    (…, hidden) rows -> (…, vocab).
``serving_features``
    which of :data:`FEATURES` the model carries. The engine's
    constructor raises a ``TypeError`` naming any other that is asked
    for: there is no fallback.
``experts_per_token``, ``expert_product``
    0 / None for a model without routed experts; else the assignments a
    token makes in each routed layer, and the name of the grouped
    product they run as (stamped on ``serve/step``).
``check_servable()``
    optional: raise where this instance cannot be served at all.
"""

from __future__ import annotations

# the engine's optional features, by the constructor argument (or the
# family of arguments) that switches each on
FEATURES = ("paged", "spec_tokens", "lora", "int8_weights", "int8_kv",
            "tp", "kv_snapshot")

_REQUIRED = ("vocab_size", "max_position", "serving_dtype", "init_cache",
             "prefill", "decode_step", "logits", "serving_features")


def check_model(model):
    """Raise ``TypeError`` unless ``model`` speaks the protocol."""
    missing = [n for n in _REQUIRED if not hasattr(model, n)]
    if missing:
        raise TypeError(
            f"ServingEngine serves a model through the protocol of "
            f"bigdl_tpu/serving/protocol.py; {type(model).__name__} lacks "
            f"{', '.join(missing)}")
    check = getattr(model, "check_servable", None)
    if check is not None:
        check()


def need(model, feature):
    """Raise ``TypeError`` naming ``feature`` unless ``model`` carries it."""
    assert feature in FEATURES, feature
    if feature not in model.serving_features:
        has = ", ".join(sorted(model.serving_features)) or "none"
        raise TypeError(
            f"{type(model).__name__} does not carry the engine feature "
            f"{feature!r} (it carries: {has}); serve it without, there "
            f"is no fallback (docs/serving.md, the model protocol)")
