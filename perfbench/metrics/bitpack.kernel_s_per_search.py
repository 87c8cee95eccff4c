"""Device seconds per search of the ``roomy_bitpack_*`` kernels, from the
trace.  Nothing to read where no such kernel ran."""


def read(ctx):
    if ctx.trace is None or not ctx.trace["n_kernel_events"]:
        return None
    return ctx.trace["kernel_s"] / len(ctx.searches)
