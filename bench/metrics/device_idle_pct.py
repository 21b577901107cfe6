"""Share of the profiled sub-window in which no operation ran on the
device (%): 100 * (1 - busy union / sub-window)."""


def read(ctx):
    return None if ctx.profile is None else ctx.profile["idle_pct"]
