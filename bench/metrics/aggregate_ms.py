"""Mean per round of the program's ``aggregate`` stage span, inclusive (ms)."""


def read(ctx):
    return ctx.stage_ms("aggregate")
