"""Device self seconds per search of the ops in the level's ``block_pad``
and ``to_table`` scopes: the packed array padded to whole blocks and the
kernels' table copies (device trace, ops named by their program scope)."""
from perfbench import spanfold


def read(ctx):
    return spanfold.reading(ctx, "level.copy_s_per_search")
