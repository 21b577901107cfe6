"""Time to train, per round (ms): the window's seconds over the rounds
completed in it (closed loop, each round ended by block_until_ready)."""


def read(ctx):
    return 1e3 * ctx.window_s / len(ctx.walls) if ctx.walls else None
