"""The largest held expert's share of the scored tokens' slots over the
mean share (1 is an even load), per expert layer, averaged over the
layers and the traced window rounds: the ``sigma_slots`` of the
program's ``moe.load`` span (a slot is a (token, held expert)
assignment). ``None`` where the program records no such span."""
import numpy as np


def read(ctx):
    ratios = []
    for node in ctx.span_nodes("moe.load"):
        for row in np.asarray(node.attrs["sigma_slots"], np.float64):
            if row.size and row.mean() > 0:
                ratios.append(row.max() / row.mean())
    return float(np.mean(ratios)) if ratios else None
