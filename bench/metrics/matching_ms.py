"""Mean per round of the program's ``matching`` stage span, inclusive (ms)."""


def read(ctx):
    return ctx.stage_ms("matching")
