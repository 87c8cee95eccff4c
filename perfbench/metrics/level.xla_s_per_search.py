"""Device seconds per search of every op that is not a ``roomy_bitpack_*``
kernel (the expansion, the CUR test, slices and pads), from the trace."""


def read(ctx):
    if ctx.trace is None or not ctx.searches:
        return None
    return ctx.trace["other_ops_s"] / len(ctx.searches)
