"""Mean per round of the ``round`` span's time outside its stages (ms):
the round loop's own work (channel and availability draws, upload
outcomes, NaN screening, bookkeeping)."""


def read(ctx):
    if not ctx.spans:
        return None
    own = [s["round"] - sum(v for k, v in s.items() if k != "round")
           for s in ctx.spans]
    return 1e3 * sum(own) / len(own)
