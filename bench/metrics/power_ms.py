"""Mean per round of the program's ``power`` stage span, inclusive (ms)."""


def read(ctx):
    return ctx.stage_ms("power")
