"""Device ms per profiled round of the held experts' grouped products:
the device ops of the Pallas grouped matmul (``megablox.gmm``, forward
and input gradient) and its transposed form (``tgmm``, the weights'
gradient), as the chip's trace names them (PERF.md section 3).
``None`` where no such op ran."""

#: what the op names of the grouped products start with in the trace
PREFIXES = ("gmm", "tgmm")


def grouped_ops(ops_s) -> list:
    return [n for n in ops_s
            if n.lstrip("%_").split(".")[0] in PREFIXES]


def read(ctx):
    if ctx.profile is None:
        return None
    names = grouped_ops(ctx.profile["ops_s"])
    if not names:
        return None
    return sum(ctx.op_ms(n) for n in names)
