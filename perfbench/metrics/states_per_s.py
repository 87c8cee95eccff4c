"""States searched per second: n! for each whole search of the window, over
the wall time of the window, which runs from the first search's start to
the last one's end (host clock, tracing off)."""


def read(ctx):
    return ctx.n_states * len(ctx.searches) / ctx.window_s
