"""Seconds per search that the search driver spends in JAX's compile path:
the union of JAX's own trace, lowering and backend-compile (or cache-load)
duration events inside the window, over the number of searches."""


def read(ctx):
    if not ctx.searches:
        return None
    spans = sorted((t0, t1) for _, t0, t1 in ctx.compile_spans)
    total, edge = 0.0, float("-inf")
    for t0, t1 in spans:
        if t1 > edge:
            total += t1 - max(t0, edge)
            edge = t1
    return total / len(ctx.searches)
