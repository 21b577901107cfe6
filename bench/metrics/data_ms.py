"""Mean per round of the program's ``data`` stage span, inclusive (ms)."""


def read(ctx):
    return ctx.stage_ms("data")
