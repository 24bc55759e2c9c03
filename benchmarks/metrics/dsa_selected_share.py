"""Mean, over the program's ``serve/step`` spans inside the traced part, of the share of the positions a full layer SCORES that it then READS: ``dsa_selected_rows / dsa_context_rows``, in per cent. ``notes`` give the mean context a live stream scores. None where the program stamps none."""


def read(ctx):
    steps = [a for n, _, _, a in ctx.spans
             if n == "serve/step" and a.get("live") and a.get("dsa_context_rows") and "dsa_selected_rows" in a]
    if not steps:
        return None
    ctx.notes["dsa_context_rows_per_stream"] = sum(a["dsa_context_rows"] / a["live"] for a in steps) / len(steps)
    return 100.0 * sum(a["dsa_selected_rows"] / a["dsa_context_rows"] for a in steps) / len(steps)
