"""How the harness searches a Cayley graph of S_n, and its reference.

A configuration names this file under ``"search"``; the harness builds one
``Cayley`` from the configuration and the root its paths are relative to.
A state is the rank of a permutation of ``range(n)``, ``n!`` of them; the
program searches with ``repro.core.constructs.implicit_bfs`` (Tier J, a
2-bit packed array on the device) and the configuration's neighbour rule
(``"rule"``).  The plain reference (``reference.py``) searches over the
configuration's ``"generators"``.  A Cayley graph looks the same from every
vertex, so every start has the identity's level counts: the reference runs
once and serves every start, and every search covers all ``n!`` states.

One key of the configuration, and one file beside this one, serve the
benchmark's tests alone; the harness reads neither:

  "cpu"             the overrides that cut the configuration to a size the
                    CPU tests run in seconds (here ``n`` and its
                    ``generators``); the tests apply them with
                    ``config.update(config["cpu"])``
  cayley.faults.py  the faults of this search file, named as it is with
                    ``faults`` for ``search``: its ``FAULTS`` maps a
                    fault's name to ``(module, attribute, wrapper)``.  The
                    tests replace ``module.attribute`` with ``wrapper(the
                    original)`` for one whole run, and that run must come
                    out not correct.  A wrapper receives the function of
                    the timed path that it wraps and returns one that takes
                    the same arguments; it must break the answer that path
                    gives (a state, a batch, a count), not only its timing.
                    Every faults file names ``unchanged_state``,
                    ``half_the_batch`` and ``answer_altered``, and that of
                    a search on more than one chip ``exchange_left_out``.
"""
from __future__ import annotations

import math
import os
from typing import List, Optional

from perfbench import harness, reference, spanfold


class Cayley:
    """The calls the harness makes for one Cayley-graph configuration."""

    scopes = spanfold.PROGRAM_SCOPES

    def __init__(self, config, root: str):
        self.n = config["n"]
        self.generators = config["generators"]
        self.n_states = math.factorial(self.n)
        self.fanout = len(self.generators)
        self._rule_path = os.path.join(root, config["rule"]["file"])
        self._rule_name = config["rule"]["function"]
        self._rule = None
        self._levels: Optional[List[int]] = None

    @property
    def rule(self):
        """The program's neighbour rule, built once: the level step is kept
        across searches for one rule object.  Loaded on first use, so that
        the control runs without the program."""
        if self._rule is None:
            self._rule = harness.load_file(self._rule_path,
                                           self._rule_name)(self.n)
        return self._rule

    def draw_start(self, rng) -> int:
        return int(rng.integers(0, self.n_states))

    def run(self, start: int, max_levels: Optional[int] = None):
        """One search, whole or cut to ``max_levels`` level calls: its level
        counts and the device array to wait for."""
        from repro.core import constructs as C
        kw = {} if max_levels is None else {"max_levels": max_levels}
        sizes, bits = C.implicit_bfs(self.n_states, [start], self.rule,
                                     impl="auto", **kw)
        return sizes, bits.data

    def states(self, sizes: List[int]) -> int:
        """A whole search marks every state of the (connected) graph."""
        return self.n_states

    def reference(self, start) -> List[int]:
        if self._levels is None:
            self._levels = reference.level_counts(self.n, self.generators)
        return self._levels

    def control(self, rng):
        """The reference with lost updates from a start drawn from ``rng``,
        and the counts it should have given."""
        start = rng.permutation(self.n)
        got = reference.level_counts(self.n, self.generators,
                                     lost_updates=True, start=start)
        return got, self.reference(start)
