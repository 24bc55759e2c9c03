"""Mean duration of the program's ``serve/first_token`` spans (the pop for admission to the first token on the stream, one a request) that ended in the traced part; beside it in ``notes``, the client's side of the whole delay over the requests due in the traced part."""
from benchmarks.harness import span_account


def read(ctx):
    ms, n = span_account.mean_ms(ctx.spans, "serve/first_token")
    client_ms, due = span_account.client_first_token_ms(ctx.records, ctx.traced)
    ctx.notes["first_token_spans"] = n
    ctx.notes["client_first_token_ms_due_in_traced"] = {"mean": client_ms, "requests": due}
    return ms
