"""Mean per round of the program's ``objective`` stage span, inclusive (ms)."""


def read(ctx):
    return ctx.stage_ms("objective")
