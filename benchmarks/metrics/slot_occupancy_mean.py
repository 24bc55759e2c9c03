"""Mean ``live`` of the program's ``serve/step`` spans inside the traced part: streams decoding per step."""


def read(ctx):
    live = [a["live"] for n, _, _, a in ctx.spans if n == "serve/step" and "live" in a]
    return sum(live) / len(live) if live else None
