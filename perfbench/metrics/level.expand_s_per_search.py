"""Device self seconds per search of the ops in the level's ``expand``
scope: the neighbour rule over a block and the frontier test (device
trace, ops named by their program scope)."""
from perfbench import spanfold


def read(ctx):
    return spanfold.reading(ctx, "level.expand_s_per_search")
