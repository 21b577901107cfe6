"""Mean per round of the program's ``local_grads`` stage span, inclusive (ms)."""


def read(ctx):
    return ctx.stage_ms("local_grads")
