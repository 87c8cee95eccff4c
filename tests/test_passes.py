"""Pass-planner invariants: the fused one-pass-per-level budget, both tiers.

Hypothesis-free (seeded numpy randomness) like test_sort_once.py — these
guard the streaming pass planner (disk/passes.py) and the fused BFS levels
built on it, and must run in the minimal CI image.

Covers:
  * PassPlan stage composition (producer/consumer order, write-back rules)
    and the extsort.STATS pass ledger (rw/read passes, piggybacked stages)
  * DiskBitArray.run_pass snapshot isolation: updates queued by a consumer
    stage mid-pass apply in the NEXT pass, never the current one — and the
    aborted-pass re-adoption rule extended over the sharded runtime's
    bucket dirs (cluster.py)
  * Tier D implicit BFS: exactly ONE fused read-write pass per level
    (sync/scan/rw counters), array bytes touched == one traversal per
    level to the byte, fused ≡ unfused levels AND final bit array
  * Tier J: the fused mark+rotate+count kernel ≡ the two-kernel reference,
    implicit BFS fused ≡ unfused, and the sorted engine's level budget of
    ONE lexsort + ONE scatter (the staging scatter folded into the sort)
  * fused ≡ unfused level counts on pancake n=7 for both engines
"""
import math
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitarray as BA
from repro.core import constructs as C
from repro.core import ranking as R
from repro.core import rlist as RL
from repro.core import types as T
from repro.core.disk import DiskBitArray, PassPlan, implicit_bfs
from repro.core.disk import bitarray as DBA
from repro.core.disk import extsort
from repro.core.disk.passes import record_pass

sys.path.append(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "examples"))
from pancake_bits import (neighbor_jnp as _pancake_neighbor_jnp,        # noqa: E402
                          neighbors_np as _pancake_neighbors_np)


@pytest.fixture
def wd(tmp_path):
    return str(tmp_path)


# -------------------------------------------------------------- PassPlan

class TestPassPlan:
    def test_stage_order_and_write_composition(self):
        seen = []
        plan = (PassPlan("p")
                .writes(lambda s, v: v + 1)
                .reads(lambda s, v: seen.append(("r1", s, v.copy())))
                .writes(lambda s, v: v * 2)
                .reads(lambda s, v: seen.append(("r2", s, v.copy()))))
        out = plan.apply_chunk(32, np.array([1, 2], np.uint8))
        # consumers observe the values produced by the stages BEFORE them
        assert np.array_equal(seen[0][2], [2, 3])
        assert np.array_equal(seen[1][2], [4, 6])
        assert seen[0][1] == seen[1][1] == 32
        assert np.array_equal(out, [4, 6])
        assert plan.writes_chunks and plan.forces_full_traversal

    def test_read_only_plan_does_not_write(self):
        plan = PassPlan().reads(lambda s, v: None)
        assert not plan.writes_chunks
        assert plan.forces_full_traversal
        assert PassPlan().n_stages == 0 and not PassPlan().forces_full_traversal

    def test_dirty_only_plan_visits_only_logged_chunks(self, wd):
        ba = DiskBitArray(wd, 64, chunk_elems=16)      # 4 chunks
        ba.update([17], [1])                           # only chunk 1 dirty
        seen = []
        DBA.reset_stats()
        ba.run_pass(PassPlan("seed", dirty_only=True)
                    .reads(lambda s, v: seen.append(s)))
        assert seen == [16]
        # exactly one 4-byte packed chunk read, nothing else
        assert (DBA.STATS["bytes_read"] - DBA.STATS["log_bytes_read"]) == 4
        assert ba.get([17])[0] == 1
        ba.destroy()

    def test_record_pass_ledger(self):
        extsort.reset_stats()
        record_pass(3, writes=True)
        record_pass(1, writes=False)
        assert extsort.STATS["rw_passes"] == 1
        assert extsort.STATS["read_passes"] == 1
        # 2 of the 3 fused stages rode the first traversal for free
        assert extsort.STATS["piggybacked_stages"] == 2


class TestRunPassSnapshotIsolation:
    def test_mid_pass_updates_defer_to_next_pass(self, wd):
        ba = DiskBitArray(wd, 64, chunk_elems=16)      # 4 chunks
        ba.update([0], [1])                            # chunk 0 dirty

        def echo_mark(start, vals):
            # consumer on chunk 0 queues a mark into chunk 3 (ahead of the
            # traversal) — it must NOT land in this pass
            if start == 0:
                ba.update([60], [3])

        ba.run_pass(PassPlan("iso").reads(echo_mark))
        assert ba.get([0])[0] == 1                     # this pass's op applied
        assert ba.get([60])[0] == 0                    # deferred mark absent
        ba.sync()
        assert ba.get([60])[0] == 3                    # applied by the NEXT pass
        ba.destroy()

    def test_mid_pass_update_to_earlier_chunk_defers_too(self, wd):
        ba = DiskBitArray(wd, 64, chunk_elems=16)

        def mark_back(start, vals):
            if start == 48:                            # last chunk marks chunk 0
                ba.update([1], [2])

        ba.run_pass(PassPlan().reads(mark_back))
        assert ba.get([1])[0] == 0
        ba.sync()
        assert ba.get([1])[0] == 2
        ba.destroy()

    def test_aborted_pass_snapshot_is_readopted(self, wd):
        ba = DiskBitArray(wd, 32, chunk_elems=16)
        ba.update([2], [1])

        class Boom(Exception):
            pass

        def blow_up(start, vals):
            raise Boom

        with pytest.raises(Boom):
            ba.run_pass(PassPlan().reads(blow_up))
        ba.update([3], [2])                            # newer op, same chunk
        ba.sync()                                      # must apply BOTH
        assert ba.get([2])[0] == 1 and ba.get([3])[0] == 2
        ba.destroy()


class TestShardedSnapshotReadoption:
    """The ``.pass`` re-adoption guarantee above, extended over the
    sharded runtime's bucket dirs (ISSUE 4): a sync that dies mid-pass on
    a WORKER leaves its shard-local snapshot plus (possibly) in-flight
    ``.tmp`` bucket files — the next sharded sync re-adopts the snapshot,
    ignores the strays, and loses no queued op."""

    def test_aborted_sharded_sync_loses_no_ops(self, wd):
        from repro.core.disk.cluster import (ShardRuntime,
                                             ShardedDiskBitArray)

        class Boom(Exception):
            pass

        def exploding_apply(old, agg):
            raise Boom

        rt = ShardRuntime(wd, 2, mode="inline")
        sb = ShardedDiskBitArray(rt, 64, name="bits", chunk_elems=16)
        sb.update([3], [1])                  # global idx 3 -> shard 0
        with pytest.raises(Boom):
            sb.sync(apply=exploding_apply)   # dies AFTER log promotion
        # a "killed peer" also left an in-flight .tmp bucket behind
        exch = rt.driver.exchange_dir("bits")
        with open(os.path.join(exch, "s001_d000.bin.tmp"), "wb") as f:
            f.write(np.array([[5, 3]], np.int64).tobytes())
        sb.update([40], [2])                 # global idx 40 -> shard 1
        assert sb.sync() == 0                # re-adopts, ignores the .tmp
        assert sb.get([3, 40, 5]).tolist() == [1, 2, 0]
        sb.destroy()
        assert not os.path.exists(exch)      # cleanup removed the stray


# ------------------------------------------- Tier D fused implicit BFS

def _ring_neighbors(n_states):
    def gen(idx):
        return np.stack([(idx + 1) % n_states, (idx - 1) % n_states], axis=1)
    return gen


class TestTierDFusedImplicitBFS:
    def test_one_rw_pass_per_level_exact_counters(self, wd):
        n_states = 256                                  # 4 chunks of 64
        DBA.reset_stats()
        extsort.reset_stats()
        sizes, bits = implicit_bfs(wd, n_states, [0],
                                   _ring_neighbors(n_states), chunk_elems=64)
        nbytes = bits.nbytes
        assert sum(sizes) == n_states
        passes = len(sizes) + 1        # seed pass + one per level transition
        # THE budget: one fused read-write pass per level, zero scan passes
        assert DBA.STATS["sync_passes"] == passes
        assert DBA.STATS["scan_passes"] == 0
        assert extsort.STATS["rw_passes"] == passes
        # expand+count rode every pass: ≥2 piggybacked stages per level
        assert extsort.STATS["piggybacked_stages"] >= 2 * passes
        # array bytes: exactly ONE traversal of the packed array per
        # rotate pass; the seed pass is dirty-only and touches just the
        # seed's chunk (16 packed bytes of the 64-byte array)
        arr_read = DBA.STATS["bytes_read"] - DBA.STATS["log_bytes_read"]
        assert arr_read == (passes - 1) * nbytes + 16
        arr_written = DBA.STATS["bytes_written"] - DBA.STATS["log_bytes_written"]
        assert arr_written == (passes - 1) * nbytes + 16
        bits.destroy()

    def test_unfused_pays_the_extra_scan_pass(self, wd):
        n_states = 256
        DBA.reset_stats()
        sizes, bits = implicit_bfs(wd, n_states, [0],
                                   _ring_neighbors(n_states), chunk_elems=64,
                                   fused=False)
        bits.destroy()
        # reference composition: a separate expand read pass per level
        assert DBA.STATS["scan_passes"] == len(sizes)
        assert DBA.STATS["sync_passes"] == len(sizes) + 1

    def test_fused_equals_unfused_bits_and_levels(self, wd):
        n = 6
        total = math.factorial(n)
        start = int(R.rank_np(np.arange(n)[None, :])[0])
        sizes_f, bits_f = implicit_bfs(
            os.path.join(wd, "f"), total, [start], _pancake_neighbors_np(n),
            chunk_elems=256)
        sizes_u, bits_u = implicit_bfs(
            os.path.join(wd, "u"), total, [start], _pancake_neighbors_np(n),
            chunk_elems=256, fused=False)
        assert sizes_f == sizes_u
        assert np.array_equal(bits_f.read_all(), bits_u.read_all())
        hist = bits_f.count_values()
        assert hist[0] == 0 and hist[3] == total
        bits_f.destroy()
        bits_u.destroy()

    def test_pancake_n7_level_counts(self, wd):
        # OEIS A058986: pancake diameter of n=7 is 8; fused engine must
        # reproduce the full flip-distance histogram.
        n = 7
        total = math.factorial(n)
        start = int(R.rank_np(np.arange(n)[None, :])[0])
        sizes, bits = implicit_bfs(wd, total, [start],
                                   _pancake_neighbors_np(n),
                                   chunk_elems=1 << 10)
        bits.destroy()
        assert sum(sizes) == total
        assert len(sizes) - 1 == 8
        assert sizes == [1, 6, 30, 149, 543, 1357, 1903, 1016, 35]


# ------------------------------------------- Tier J fused implicit BFS

class TestTierJFusedImplicit:
    def test_mark_rotate_count_matches_two_kernel_reference(self):
        rng = np.random.default_rng(10)
        for case in range(10):
            w = int(rng.integers(1, 12))
            packed = jnp.asarray(rng.integers(0, 1 << 32, w, dtype=np.uint64)
                                 .astype(np.uint32))
            m = int(rng.integers(1, 64))
            idx = jnp.asarray(rng.integers(-4, w * 16 + 8, m).astype(np.int32))
            n = int(rng.integers(1, w * 16 + 1))
            got, gcnt = BA.mark_rotate_count(packed, idx, n, impl="ref")
            marked = BA.mark_packed(packed, idx, impl="ref")
            want, wcnt = BA.rotate_count(marked, n, impl="ref")
            assert np.array_equal(np.asarray(got), np.asarray(want)), case
            assert int(gcnt) == int(wcnt), case

    def test_implicit_bfs_fused_equals_unfused(self):
        n = 5
        total = math.factorial(n)
        start = int(R.rank_np(np.arange(n)[None, :])[0])
        sf, bf = C.implicit_bfs(total, [start], _pancake_neighbor_jnp(n))
        su, bu = C.implicit_bfs(total, [start], _pancake_neighbor_jnp(n),
                                fused=False)
        assert sf == su
        assert np.array_equal(np.asarray(bf.data), np.asarray(bu.data))

    def test_pancake_n7_level_counts_both_engines_agree(self, wd):
        n = 7
        total = math.factorial(n)
        start = int(R.rank_np(np.arange(n)[None, :])[0])
        j_sizes, _ = C.implicit_bfs(total, [start], _pancake_neighbor_jnp(n))
        d_sizes, bits = implicit_bfs(wd, total, [start],
                                     _pancake_neighbors_np(n),
                                     chunk_elems=1 << 11)
        bits.destroy()
        assert j_sizes == d_sizes
        assert sum(j_sizes) == total

    @pytest.mark.parametrize("impl", ["ref", "interpret"])
    @pytest.mark.parametrize("fused", [True, False])
    def test_implicit_bfs_blocked_equals_one_block(self, impl, fused,
                                                   monkeypatch):
        # A block of 700 expands pancake n=7 in 8 blocks of 640 states, the
        # last one short: each block's marks land before the next block
        # expands, and the levels and the final array match a one-block
        # search.
        n = 7
        total = math.factorial(n)
        start = int(R.rank_np(np.arange(n)[None, :])[0])
        nf = _pancake_neighbor_jnp(n)
        want, wbits = C.implicit_bfs(total, [start], nf, impl="ref")
        monkeypatch.setattr(C, "IMPLICIT_BLOCK", 700)
        got, gbits = C.implicit_bfs(total, [start], nf, impl=impl,
                                    fused=fused)
        assert got == want
        assert np.array_equal(np.asarray(gbits.data), np.asarray(wbits.data))


# --------------------------------------- Tier J sorted-engine level budget

def _tiny_gen_next(n):
    def gen(row):
        code = row[0]
        perm = jnp.stack([(code >> jnp.uint32(4 * i)) & jnp.uint32(0xF)
                          for i in range(n)]).astype(jnp.int32)
        outs = []
        for k in range(2, n + 1):
            flipped = jnp.concatenate([perm[:k][::-1], perm[k:]])
            acc = jnp.uint32(0)
            for i in range(n):
                acc = acc | (flipped[i].astype(jnp.uint32)
                             << jnp.uint32(4 * i))
            outs.append(acc)
        return jnp.stack(outs)[:, None], jnp.ones((n - 1,), bool)
    return gen


class TestTierJLevelBudget:
    def test_fused_level_is_one_lexsort_one_scatter(self):
        # The expansion-scatter staging is folded into the fused lexsort:
        # a whole level traces ONE lexsort + ONE scatter (the fold into
        # the visited list).  The reference composition pays 2 + 2.
        n = 4
        cur = RL.from_rows(jnp.array([[0x3210]], jnp.uint32), capacity=4)
        all_lst = RL.from_rows(jnp.array([[0x3210]], jnp.uint32), capacity=32)
        T.reset_sort_stats()
        C._bfs_level(cur, all_lst, _tiny_gen_next(n), n - 1, 16)
        assert T.SORT_STATS == {"lexsorts": 1, "scatters": 1}
        T.reset_sort_stats()
        C._bfs_level_reference(cur, all_lst, _tiny_gen_next(n), n - 1, 16)
        assert T.SORT_STATS["lexsorts"] >= 2
        assert T.SORT_STATS["scatters"] >= 2

    def test_fused_bfs_equals_reference_pancake_n7(self):
        n = 7
        start = np.array([[sum(i << (4 * i) for i in range(n))]], np.uint32)
        total = math.factorial(n)
        res_f = C.breadth_first_search(start, _tiny_gen_next(n), fanout=n - 1,
                                       width=1, all_capacity=total + 8,
                                       level_capacity=total + 8)
        res_u = C.breadth_first_search(start, _tiny_gen_next(n), fanout=n - 1,
                                       width=1, all_capacity=total + 8,
                                       level_capacity=total + 8, fused=False)
        assert res_f.level_sizes == res_u.level_sizes
        assert sum(res_f.level_sizes) == total
