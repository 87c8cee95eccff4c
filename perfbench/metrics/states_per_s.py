"""States searched per second: the states each whole search of the window
covers (the configuration's search says how many), summed, over the wall
time of the window, which runs from the first search's start to the last
one's end (host clock, tracing off)."""


def read(ctx):
    return sum(s.states for s in ctx.searches) / ctx.window_s
