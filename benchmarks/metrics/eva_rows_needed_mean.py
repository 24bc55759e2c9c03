"""Mean, over the program's ``serve/step`` spans inside the traced part, of the rows a live stream's attention needs a layer: ``(eva_window_rows + eva_summary_rows) / live``, exact rows of its window plus summaries of closed windows. ``notes`` give the same over the rows a slot's two tables hold. None where the program stamps none."""
from benchmarks.harness import counts_evabyte


def read(ctx):
    per_stream = [(a["eva_window_rows"] + a["eva_summary_rows"]) / a["live"] for n, _, _, a in ctx.spans
                  if n == "serve/step" and a.get("live") and "eva_window_rows" in a and "eva_summary_rows" in a]
    if not per_stream:
        return None
    s = counts_evabyte.shape(ctx.config)
    held = s["window"] + int(ctx.config["constructor_kwargs"]["max_position"]) // s["chunk"]
    mean = sum(per_stream) / len(per_stream)
    ctx.notes["eva_rows_allocated"] = held
    ctx.notes["eva_rows_needed_share"] = mean / held
    return mean
