"""Mean duration of the program's ``serve/queue_wait`` spans (``submit`` to the pop for admission, one a request) that ended in the traced part."""
from benchmarks.harness import span_account


def read(ctx):
    ms, n = span_account.mean_ms(ctx.spans, "serve/queue_wait")
    ctx.notes["queue_wait_spans"] = n
    return ms
