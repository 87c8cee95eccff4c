"""Implicit-BFS subsystem invariants: rank/unrank bijection, the 2-bit
delayed-update arrays on both tiers, and engine equivalence.

Hypothesis-free (seeded numpy randomness) like test_sort_once.py — these
guard the second BFS engine and must run in the minimal CI image.

Covers:
  * ranking: Myrvold–Ruskey roundtrip + bijectivity, NumPy ≡ jnp (double-
    word uint32 arithmetic), multi-word ranks for n > 12, row codec order
  * DiskBitArray: pack codec, log/sync contract vs a dict oracle, combine
    semantics, fused transform, byte-histogram counts, log spill to disk
  * RoomyBitArray: queue/sync vs oracle, packed write disjointness,
    mark_packed duplicate/OOB safety, rotate_count
  * implicit BFS ≡ sorted-list BFS level profiles on both tiers (pancake)
  * sharded_mark_sync through the bucket exchange on a fake-device mesh
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
from repro.core.disk import DiskBitArray, implicit_bfs
from repro.core.disk import bitarray as DBA

# The pancake neighbor generators and the sorted-list oracle live with the
# example CLI (benchmarks/bfs.py imports them the same way) — one copy.
sys.path.append(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "examples"))
from pancake_bits import (neighbor_jnp as _pancake_neighbor_jnp,        # noqa: E402
                          neighbors_np as _pancake_neighbors_np,
                          sorted_list_levels as _sorted_list_levels)


@pytest.fixture
def wd(tmp_path):
    return str(tmp_path)


# ------------------------------------------------------------- ranking

class TestRanking:
    def test_unrank_is_bijective_and_rank_inverts(self):
        for n in range(1, 7):
            f = math.factorial(n)
            ranks = np.arange(f, dtype=np.uint64)
            perms = R.unrank_np(n, ranks)
            assert np.all(np.sort(perms, axis=1) == np.arange(n))
            assert len({tuple(p) for p in perms.tolist()}) == f
            assert np.array_equal(R.rank_np(perms), ranks)

    def test_jnp_matches_numpy_single_word(self):
        n = 6
        ranks = np.arange(math.factorial(n), dtype=np.uint64)
        perms = R.unrank_np(n, ranks)
        rows = R.ranks_to_rows(ranks, n)
        assert rows.shape[1] == 1
        got_p = np.asarray(R.unrank_jnp(n, jnp.asarray(rows)))
        assert np.array_equal(got_p, perms)
        got_r = np.asarray(R.rank_jnp(jnp.asarray(perms)))
        assert np.array_equal(R.rows_to_ranks(got_r), ranks)

    def test_multiword_n13_and_boundary_n20(self):
        rng = np.random.default_rng(0)
        for n in (13, 20):
            f = math.factorial(n)
            ranks = (rng.integers(0, f, size=300, dtype=np.uint64)
                     if n == 20 else
                     rng.integers(0, f, size=300).astype(np.uint64))
            perms = R.unrank_np(n, ranks)
            assert np.array_equal(R.rank_np(perms), ranks)
            rows = R.ranks_to_rows(ranks, n)
            assert rows.shape[1] == 2
            assert np.array_equal(R.rows_to_ranks(rows), ranks)
            got_p = np.asarray(R.unrank_jnp(n, jnp.asarray(rows)))
            assert np.array_equal(got_p, perms)
            got_r = np.asarray(R.rank_jnp(jnp.asarray(perms)))
            assert np.array_equal(R.rows_to_ranks(got_r), ranks)

    def test_rank_rows_sort_in_rank_order(self):
        # word 0 is the high word: lexicographic (word-0-first) row order
        # must equal numeric rank order — the property the sorted-list
        # engine needs to consume rank rows directly.
        rng = np.random.default_rng(1)
        ranks = rng.integers(0, math.factorial(14), size=500).astype(np.uint64)
        rows = R.ranks_to_rows(ranks, 14)
        order = np.lexsort((rows[:, 1], rows[:, 0]))
        assert np.array_equal(R.rows_to_ranks(rows[order]), np.sort(ranks))


# -------------------------------------------------------- DiskBitArray

class TestDiskBitArray:
    def test_pack_unpack_roundtrip(self):
        rng = np.random.default_rng(0)
        vals = rng.integers(0, 4, 1001).astype(np.uint8)
        packed = DBA.pack2(vals)
        assert packed.shape[0] == -(-1001 // 4)
        assert np.array_equal(DBA.unpack2(packed, 1001), vals)

    def test_update_sync_matches_dict(self, wd):
        rng = np.random.default_rng(1)
        n = 1000
        ba = DiskBitArray(wd, n, chunk_elems=256)
        want = np.zeros(n, np.uint8)
        for _ in range(3):
            idx = rng.integers(0, n, 200)
            vals = rng.integers(0, 4, 200).astype(np.uint8)
            ba.update(idx, vals)
            for i, v in zip(idx, vals):
                want[i] |= v                 # default combine=OR …
        ba.sync(apply=lambda old, agg: old | agg)   # … apply=merge
        assert np.array_equal(ba.read_all(), want)
        assert np.array_equal(ba.get(np.arange(n)), want)
        hist = ba.count_values()
        assert hist.sum() == n
        assert np.array_equal(hist, np.bincount(want, minlength=4))
        ba.destroy()

    def test_sync_default_overwrites_with_last_combine(self, wd):
        ba = DiskBitArray(wd, 16, chunk_elems=8)
        ba.update([3, 3], [1, 2])
        # default combine=OR over both payloads, default apply=overwrite
        ba.sync()
        assert ba.get([3])[0] == 3
        ba.destroy()

    def test_transform_runs_on_logless_chunks(self, wd):
        ba = DiskBitArray(wd, 64, chunk_elems=16)   # 4 chunks
        ba.update([0], [1])                          # only chunk 0 logged
        seen = []
        ba.sync(transform=lambda start, vals: (seen.append(start), vals + 0)[1])
        assert seen == [0, 16, 32, 48]
        assert ba.get([0])[0] == 1
        ba.destroy()

    def test_log_spill_bounds_ram(self, wd):
        ba = DiskBitArray(wd, 256, chunk_elems=64, log_buf_rows=8)
        ba.update(np.arange(16) * 16 % 256, np.ones(16, np.uint8))
        # past log_buf_rows the buffered ops must hit per-chunk log files
        logs = [f for f in os.listdir(ba.path) if f.startswith("log")]
        assert logs, "expected spilled op-log files"
        ba.sync(apply=lambda old, agg: old | agg)
        assert ba.count_values()[1] == np.unique(np.arange(16) * 16 % 256).size
        ba.destroy()

    def test_stats_count_bytes(self, wd):
        DBA.reset_stats()
        ba = DiskBitArray(wd, 128, chunk_elems=64)
        ba.update([1], [2])
        ba.sync()
        assert DBA.STATS["sync_passes"] == 1
        assert DBA.STATS["bytes_read"] > 0
        assert DBA.STATS["bytes_written"] > 0
        ba.destroy()


# ------------------------------------------------------- RoomyBitArray

class TestRoomyBitArray:
    def test_pack_unpack_roundtrip(self):
        rng = np.random.default_rng(2)
        vals = jnp.asarray(rng.integers(0, 4, 250).astype(np.uint32))
        packed = BA.pack_values(vals)
        assert packed.shape[0] == BA.n_words(250)
        assert np.array_equal(np.asarray(BA.unpack_values(packed))[:250],
                              np.asarray(vals))

    def test_update_sync_matches_dict(self):
        rng = np.random.default_rng(3)
        n = 200
        ba = BA.make(n, queue_capacity=128)
        idx = rng.integers(0, n, 100)
        vals = rng.integers(0, 4, 100)
        ba, ov = BA.update(ba, jnp.asarray(idx), jnp.asarray(vals))
        assert not bool(ov)
        ba = BA.sync(ba)        # combine=OR, apply=overwrite-with-aggregate
        want = np.zeros(n, np.uint32)
        for i, v in zip(idx, vals):
            want[i] |= v
        assert np.array_equal(np.asarray(BA.get(ba, jnp.arange(n))), want)

    def test_sync_on_empty_queue_capacity_is_noop(self):
        ba = BA.make(32)                    # default queue_capacity=0
        out = BA.sync(ba)
        assert np.array_equal(np.asarray(out.data), np.asarray(ba.data))

    def test_update_queue_overflow_flag(self):
        ba = BA.make(64, queue_capacity=4)
        ba, ov = BA.update(ba, jnp.arange(3), jnp.ones(3))
        assert not bool(ov)
        ba, ov = BA.update(ba, jnp.arange(3), jnp.ones(3))
        assert bool(ov)

    def test_mark_packed_duplicates_and_oob(self):
        data = jnp.zeros((4,), jnp.uint32)          # 64 elements
        idx = jnp.asarray([5, 5, 5, 63, 64, 9999, -1], jnp.int32)
        out = BA.mark_packed(data, idx, impl="ref")
        vals = np.asarray(BA.unpack_values(out))
        want = np.zeros(64, np.uint32)
        want[[5, 63]] = BA.NEXT
        assert np.array_equal(vals, want)
        # non-UNSEEN targets absorb the mark
        out2 = BA.mark_packed(out, jnp.asarray([5], jnp.int32), impl="ref")
        assert np.array_equal(np.asarray(out2), np.asarray(out))

    def test_rotate_count(self):
        vals = jnp.asarray([BA.UNSEEN, BA.CUR, BA.NEXT, BA.DONE, BA.NEXT],
                           jnp.uint32)
        data = BA.pack_values(vals)
        new, cnt = BA.rotate_count(data, 5, impl="ref")
        got = np.asarray(BA.unpack_values(new))[:5]
        assert list(got) == [BA.UNSEEN, BA.DONE, BA.CUR, BA.DONE, BA.CUR]
        assert int(cnt) == 2

    def test_packed_write_shares_words(self):
        # two elements of the same uint32 word must update independently
        ba = BA.make(32, queue_capacity=8)
        ba, _ = BA.update(ba, jnp.asarray([0, 1, 15]), jnp.asarray([1, 2, 3]))
        ba = BA.sync(ba)
        got = np.asarray(BA.get(ba, jnp.asarray([0, 1, 2, 15])))
        assert list(got) == [1, 2, 0, 3]


# ------------------------------------------------- implicit BFS engines

class TestImplicitBFS:
    def test_tier_d_matches_sorted_list_engine(self, wd):
        n = 5
        total = math.factorial(n)
        start = int(R.rank_np(np.arange(n)[None, :])[0])
        sizes, bits = implicit_bfs(os.path.join(wd, "imp"), total, [start],
                                   _pancake_neighbors_np(n),
                                   chunk_elems=256)
        hist = bits.count_values()
        bits.destroy()
        want = _sorted_list_levels(n)
        assert sizes == want
        assert sum(sizes) == total
        assert hist[0] == 0                  # no UNSEEN left
        assert hist[3] == total              # every state ended DONE

    def test_tier_j_matches_tier_d(self, wd):
        n = 5
        total = math.factorial(n)
        start = int(R.rank_np(np.arange(n)[None, :])[0])
        d_sizes, bits = implicit_bfs(wd, total, [start],
                                     _pancake_neighbors_np(n),
                                     chunk_elems=64)
        bits.destroy()
        j_sizes, jbits = C.implicit_bfs(total, [start],
                                        _pancake_neighbor_jnp(n))
        assert j_sizes == d_sizes
        vals = np.asarray(BA.unpack_values(jbits.data))[:total]
        assert (vals == BA.DONE).all()

    def test_duplicate_seeds_collapse(self, wd):
        n = 4
        total = math.factorial(n)
        start = int(R.rank_np(np.arange(n)[None, :])[0])
        sizes, bits = implicit_bfs(wd, total, [start, start, start],
                                   _pancake_neighbors_np(n), chunk_elems=16)
        bits.destroy()
        assert sizes[0] == 1 and sum(sizes) == total


# ---------------------------------------------------------- sharded sync

class TestShardedMarkSync:
    def test_bucket_exchange_mark(self, multidev):
        multidev("""
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import AxisType, PartitionSpec as P
            from repro.core import bitarray as BA
            S, nw_local, m = 4, 2, 16          # 32 elements per shard
            mesh = jax.make_mesh((S,), ("x",), axis_types=(AxisType.Auto,))
            data = jnp.zeros((S * nw_local,), jnp.uint32)
            rng = np.random.default_rng(0)
            idx = jnp.asarray(rng.integers(0, 128, S * m).astype(np.int32))
            valid = jnp.ones((S * m,), bool)
            def f(data, idx, valid):
                return BA.sharded_mark_sync(data, idx, valid, "x", S,
                                            capacity=m)
            fs = jax.shard_map(f, mesh=mesh,
                               in_specs=(P("x"), P("x"), P("x")),
                               out_specs=(P("x"), P()))
            out, dropped = fs(data, idx, valid)
            assert int(dropped) == 0
            got = np.asarray(BA.unpack_values(out))
            want = np.zeros(128, np.uint32)
            want[np.unique(np.asarray(idx))] = BA.NEXT
            assert np.array_equal(got, want)
            print("sharded mark ok")
        """, n_devices=4)
