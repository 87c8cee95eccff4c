"""Level counts reported per search of the window, from the ``explicit``
counters that the fixture's search file books: a fixture of the tests, a
per-layer reader of a program counter.  Nothing to read where none was
booked."""


def read(ctx):
    n = ctx.counters.get("explicit", {}).get("levels", 0)
    return n / len(ctx.searches) if n else None
