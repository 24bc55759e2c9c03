"""What ``ServingEngine`` and the dense slot table ask of a model.

A model is served through these names and through nothing else of it
(``models/gpt.py``'s ``GPTForCausalLM``, ``models/lfm2.py``'s
``LFM2ForCausalLM``, ``models/evabyte.py``'s ``EvaByteForCausalLM``,
``models/dots3.py``'s ``Dots3ForCausalLM`` and ``models/nemotron_h.py``'s
``NemotronHForCausalLM`` are the five implementations; docs/serving.md
has the contract in prose):

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
    slot axis first; the table reads nothing else off a leaf and looks
    for no leaf by name. It allocates the cache once, scatters a
    prefill's rows into it leaf by leaf (the WHOLE row of every leaf, so
    nothing of a slot's former occupant outlives an admission), and
    donates it through every step.
``cache_tables()``
    the model's own description of the leaves that a stream fills row
    by row, a tuple of :class:`RowTable` (empty for a model whose state
    is all fixed-size): which leaves, how many rows a slot has, which
    row the step at position ``pos`` writes and how many rows it reads.
    Every other leaf is fixed-size state (a convolution's last taps; a
    Mamba-2 layer's float32 S, rewritten whole at every step, of which
    the model's own kernel moves the live slots' only).
    GPT-2 and LFM2 describe one table, K and V of ``max_position`` rows
    written at ``pos`` and read up to it; EvaByte two, a window written
    at ``pos mod 2048`` and chunk summaries that gain a row every 16th
    step; Dots3 three, the index keys of every position (all read), the
    latents of every position READ BY SELECTION (``selected``: every
    row up to ``pos`` is scored, the 2048 best count, and which they are
    is decided on the device) and a ring of 513 latents, the last two
    read by the model's own kernel (``own_read``); Nemotron-H one, K and
    V of its attention layers, beside its Mamba layers' state. From the
    tables AS ALLOCATED the slot table derives whether
    ``ops/kv_write.py`` takes the step's writes (every table's leaves
    lie as the kernel needs them, rows third among it) and whether
    ``ops/decode_attention.py`` takes its read (one table, read from row
    0 under one softmax, that fits the kernel; never a table read by
    selection), and from the row counts ``serve/step``'s ``attn_blocks``
    (what the step's reads fetch: ``read_rows`` under the table's kernel,
    ``own_read``'s count under the model's, else every row held) and
    ``attn_blocks_table``.
``prefill(params, cache, ids, prompt_len) -> (h_last, cache)``
    ``ids`` (W, bucket) right-padded, ``prompt_len`` (W,): the final
    hidden row at each prompt's last real position, and ``cache`` (W
    rows, as ``init_cache(W, ...)`` made it) holding each row's state as
    of ITS length, whatever the padding holds: what the step at position
    ``prompt_len`` reads, every table's rows where its ``write_row``
    and ``read_rows`` say that step finds them.
``decode_step(params, cache, tok, pos, in_place=False, read=None, live=None) -> (h, cache)``
    one token a slot, slot ``b`` at position ``pos[b]``. ``in_place``
    is the table's word that ``ops/kv_write.py`` applies to the leaves
    of its row tables, and ``live`` (slots,) bool the table's own mask
    of the slots that hold a request: the kernel writes those and moves
    no block of another (a model that keeps the plain write takes the
    mask and leaves it unused: a free slot's row is junk nobody reads
    either way). ``read`` is None, or (slots,) int32 where the
    table also takes ``ops/decode_attention.py`` (a model of ONE row
    table): the rows slot ``b``'s attention reads, ``read_rows(pos[b])``
    for a live slot and 0 for a free one, whose row then comes back as
    junk nobody reads. A model with routed experts
    (``experts_per_token`` > 0) also leaves the dead slots out of its
    routed layers and, given ``live``, returns, third, the mean over
    those layers of how many experts the live slots chose.
``logits(params, h)``
    (…, hidden) rows -> (…, vocab).
``serving_features``
    which of :data:`FEATURES` the model carries. The engine's
    constructor raises a ``TypeError`` naming any other that is asked
    for: there is no fallback.
``experts_per_token``, ``expert_rows(width, length)``
    0 for a model without routed experts (which need not have
    ``expert_rows``); else the assignments a token makes in each routed
    layer, and how many of them one routed layer's call takes in a pass
    over ``width`` rows of ``length`` positions (a decode step: length
    1). The table asks ``nn.moe.grouped_product`` of that number, as
    the layer does of its own, and stamps the product's name on
    ``serve/step`` and ``serve/prefill``.
``step_counts(pos)``, ``prefill_counts(prompt_len)``
    optional, both or neither: host arithmetic (numpy in, a dict of
    ints out, the same keys whatever the input) on the positions the
    live slots' next step writes, and on the lengths of the prompts an
    admission prefills. The table stamps the dicts on ``serve/step``
    and ``serve/prefill`` and keeps their running sums in its
    ``stats``; nothing is read back from the device for them.
``check_servable()``
    optional: raise where this instance cannot be served at all.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

# the engine's optional features, by the constructor argument (or the
# family of arguments) that switches each on
FEATURES = ("paged", "spec_tokens", "lora", "int8_weights", "int8_kv",
            "tp", "kv_snapshot")

_REQUIRED = ("vocab_size", "max_position", "serving_dtype", "init_cache",
             "cache_tables", "prefill", "decode_step", "logits",
             "serving_features")


@dataclasses.dataclass(frozen=True)
class RowTable:
    """One group of cache leaves that a stream fills row by row.

    ``leaves`` are their names in a layer's dict, ``rows`` the rows a
    slot has. ``write_row(pos)`` is the row that the step at position
    ``pos`` writes (-1: none this step) and ``read_rows(pos)`` how many
    rows, from row 0, that step's attention reads once it has written;
    both are plain arithmetic that takes numpy on the host and traced
    arrays inside the step alike. ``row_axis`` is where the rows lie in a
    leaf: 2 is ``(slots, heads, rows, head_dim)``, the shape the two
    kernels know; a model that keeps another (1: ``(slots, rows, heads,
    head_dim)``, a row whole tiles of a head of 128; or ``(slots, rows,
    width)``, a latent with no head axis) keeps the plain write and the
    masked read.

    ``selected`` marks a table READ BY SELECTION: of the rows up to
    ``pos`` the step reads ``read_rows(pos)``, CHOSEN on the device (by
    scores against another table's leaves, the model's business), not
    the first so many. Neither kernel takes such a table whatever its
    shape (:attr:`kernel_shaped`): both move or read a slot's leading
    rows.

    ``own_read`` is None, or the model's word on a kernel of its OWN for
    this table's read: ``own_read(leaf)``, asked once with the table's
    first leaf as allocated, gives None where that kernel does not take
    it (the model's step then reads every row of every slot under a
    mask, as the slot table's masked read does) or ``fetched(pos)``, the
    rows the kernel moves of a live slot whose next step is at ``pos``
    (arithmetic on ``pos`` like the others; of a free slot it moves
    none). The model's step decides by the same test, so the slot table
    knows what is read without choosing it: ``attn_read`` says
    ``"model"`` and ``attn_blocks`` counts what is fetched.
    """

    leaves: tuple
    rows: int
    write_row: Callable
    read_rows: Callable
    row_axis: int = 2
    selected: bool = False
    own_read: Optional[Callable] = None

    @property
    def kernel_shaped(self):
        """Whether ``ops/kv_write.py`` and ``ops/decode_attention.py``
        may be asked about this table's leaves at all: rows third, and
        read from row 0 on."""
        return self.row_axis == 2 and not self.selected


def positions_table(rows):
    """The table of a model that keeps ``"k"`` and ``"v"`` of every
    position: row ``pos`` written at position ``pos``, rows ``0 .. pos``
    read."""
    return RowTable(("k", "v"), rows, lambda pos: pos, lambda pos: pos + 1)


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
