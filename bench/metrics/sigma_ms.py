"""Mean per round of the program's ``sigma`` stage span, inclusive (ms)."""


def read(ctx):
    return ctx.stage_ms("sigma")
