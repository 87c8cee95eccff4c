"""Wall seconds per search of the search driver's ``bfs.init`` span: the
packed array made, the start marked and first counted, before the first
level (program spans on the profiler's clock, traced run)."""
from perfbench import spanfold


def read(ctx):
    return spanfold.reading(ctx, "driver.init_s_per_search")
