"""Share (%) of the HBM roofline that the array-pass kernels reach.

The least time is the bytes the searches' array pass must move over the
chip's HBM peak (``peaks.json``); the share is that over the kernels' time
in the trace.  The pass does no arithmetic to speak of, so bandwidth bounds
it.  The bytes do not depend on how the pass is implemented:

  * every level call reads and writes the packed array once:
    2 x 4 bytes x ceil(n! / 16) words;
  * every state of a frontier sends one 4-byte target per generator.
"""
import math


def search_bytes(n_states, fanout, sizes):
    """Bytes one search's array pass must move.  A search makes one level
    call per level and one that finds nothing: ``len(sizes)`` calls, which
    expand every level's states once."""
    words = math.ceil(n_states / 16)
    return len(sizes) * 2 * 4 * words + 4 * fanout * sum(sizes)


def read(ctx):
    if ctx.trace is None or not ctx.trace["n_kernel_events"]:
        return None
    need = sum(search_bytes(ctx.graph.n_states, ctx.graph.fanout, s.sizes)
               for s in ctx.searches)
    least_s = need / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / ctx.trace["kernel_s"]
