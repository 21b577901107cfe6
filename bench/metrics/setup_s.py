"""Set-up time (s): process start to the first timed round — data,
weights, compilation and the warm-up rounds."""


def read(ctx):
    return ctx.setup_s
