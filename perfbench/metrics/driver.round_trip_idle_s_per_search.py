"""Device-idle seconds of the median search in the window under the level
calls' spans (``bfs.level``, ``bfs.dispatch``, ``bfs.sync``) and between
them (``bfs.search``): the host's round trip of every level, the count
fetched and the next step handed over, while the device waits (program
spans on the profiler's clock, traced run).  A stretch that the profiler's
host and device clocks put in the wrong one of these spans stays in the
sum, and the median leaves out a search with a rare stall."""
from perfbench import spanfold


def read(ctx):
    return spanfold.reading(ctx, "driver.round_trip_idle_s_per_search")
