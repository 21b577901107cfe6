"""Mean per round of the program's ``selection`` stage span, inclusive (ms)."""


def read(ctx):
    return ctx.stage_ms("selection")
