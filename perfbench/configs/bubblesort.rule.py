"""The bubble-sort graph's neighbour rule for the Tier J implicit BFS.

A state is the Myrvold-Ruskey rank of a permutation (``repro.core.
ranking``); its neighbours are the ranks of the n-1 permutations that swap
two adjacent positions.  Written like ``examples/pancake_bits.neighbor_jnp``,
the rule a user of ``implicit_bfs`` supplies for this graph.
"""
import jax.numpy as jnp

from repro.core import ranking as R


def neighbor_jnp(n: int):
    """Rank -> (n-1,) int32 neighbour ranks, single-word (n <= 12)."""
    if n > R.MAX_N_1WORD:
        raise ValueError(f"single-word ranks stop at n={R.MAX_N_1WORD}")

    def nf(i):
        perm = R.unrank_jnp(n, i.reshape(1, 1).astype(jnp.uint32))[0]
        outs = []
        for k in range(n - 1):
            swapped = jnp.concatenate([perm[:k], perm[k + 1:k + 2],
                                       perm[k:k + 1], perm[k + 2:]])
            outs.append(R.rank_jnp(swapped[None, :], width=1)[0, 0])
        return jnp.stack(outs).astype(jnp.int32)
    return nf
