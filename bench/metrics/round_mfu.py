"""Model FLOP utilization of the whole round (%): the CNN's model FLOPs
of the profiled rounds (``bench/flops.py``) over the sub-window's
seconds, over the chip's peak (``bench/peaks.py``)."""


def read(ctx):
    if ctx.profile is None or not ctx.peak_flops or not ctx.profile_flops:
        return None
    return 100.0 * ctx.profile_flops / ctx.profile["window_s"] \
        / ctx.peak_flops
