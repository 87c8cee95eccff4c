"""Seconds from the start of the process to the end of the warm-up: imports,
the chip, the compile cache, and one search cut to one level (host clock)."""


def read(ctx):
    return ctx.setup_s
