"""Mean ``experts_hit`` of the program's ``serve/step`` spans inside the traced part: of a routed layer's experts, how many at least one live slot chose in a step (mean over the routed layers). None where the program stamps none."""


def read(ctx):
    hit = [a["experts_hit"] for n, _, _, a in ctx.spans
           if n == "serve/step" and isinstance(a.get("experts_hit"), (int, float))]
    return sum(hit) / len(hit) if hit else None
