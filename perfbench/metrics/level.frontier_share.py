"""Share (%) of the states a level call expands that are in its frontier:
100 x the window's ``implicit.frontier_states`` over
``implicit.states_expanded`` (program counters).  The rest is expanded and
masked out, the work that expanding only the frontier would save."""
from perfbench import spanfold


def read(ctx):
    return spanfold.reading(ctx, "level.frontier_share")
