"""Prompt tokens asked for over positions computed: the sum of ``tokens`` over the sum of ``rows`` x ``bucket`` of the program's ``serve/prefill`` spans in the traced part. One quantity under two names, because its cells report different end-to-end metrics."""
from benchmarks.harness import span_account


def read(ctx):
    return span_account.useful_share(ctx.spans)
