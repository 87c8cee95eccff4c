"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracles,
swept over shapes and dtypes (deliverable (c) of the brief)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def rand(seed, shape, dtype=jnp.float32, scale=1.0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return (x * scale).astype(dtype)


ATTN_CASES = [
    # b, hq, hkv, sq, skv, d, causal, window, softcap
    (1, 4, 4, 64, 64, 32, True, None, None),
    (2, 8, 2, 128, 128, 64, True, None, None),      # GQA 4:1
    (1, 4, 1, 96, 96, 32, True, None, None),        # MQA, unaligned seq
    (1, 4, 2, 96, 96, 32, True, 32, None),          # sliding window
    (1, 2, 2, 64, 64, 32, True, None, 50.0),        # softcap (gemma2)
    (1, 4, 2, 64, 64, 32, True, 16, 30.0),          # window+softcap
    (1, 4, 1, 48, 80, 32, False, None, None),       # cross-length, bidir
    (2, 2, 2, 33, 65, 16, True, None, None),        # odd sizes → padding
]


class TestFlashAttention:
    @pytest.mark.parametrize("case", ATTN_CASES)
    def test_kernel_matches_naive(self, case):
        b, hq, hkv, sq, skv, d, causal, window, softcap = case
        q = rand(1, (b, hq, sq, d))
        k = rand(2, (b, hkv, skv, d))
        v = rand(3, (b, hkv, skv, d))
        got = ops.flash_attention(q, k, v, causal=causal, window=window,
                                  softcap=softcap, impl="interpret",
                                  block_q=32, block_k=32)
        want = ref.attention_naive(q, k, v, causal=causal, window=window,
                                   softcap=softcap)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("case", ATTN_CASES[:4])
    def test_blocked_ref_matches_naive(self, case):
        b, hq, hkv, sq, skv, d, causal, window, softcap = case
        q = rand(4, (b, hq, sq, d))
        k = rand(5, (b, hkv, skv, d))
        v = rand(6, (b, hkv, skv, d))
        got = ref.attention_ref(q, k, v, causal=causal, window=window,
                                softcap=softcap, block_k=48)
        want = ref.attention_naive(q, k, v, causal=causal, window=window,
                                   softcap=softcap)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                            (jnp.bfloat16, 2e-2)])
    def test_dtypes(self, dtype, atol):
        q = rand(7, (1, 4, 64, 32), dtype)
        k = rand(8, (1, 2, 64, 32), dtype)
        v = rand(9, (1, 2, 64, 32), dtype)
        got = ops.flash_attention(q, k, v, impl="interpret",
                                  block_q=32, block_k=32)
        want = ref.attention_naive(q, k, v)
        assert got.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=atol, rtol=atol)

    def test_decode_ref_matches_naive_last_row(self):
        b, hq, hkv, s, d = 2, 4, 2, 48, 32
        q = rand(10, (b, hq, 1, d))
        k = rand(11, (b, hkv, s, d))
        v = rand(12, (b, hkv, s, d))
        full = ref.attention_naive(q, k, v, causal=False)
        mask = jnp.ones((b, s), bool)
        dec = ref.decode_attention_ref(
            q[:, :, 0], k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            mask)
        np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, :, 0]),
                                   atol=2e-5, rtol=2e-5)


MAMBA_CASES = [
    (2, 64, 32, 16, 16, 32),     # b, l, di, n, bd, bt
    (1, 100, 16, 8, 16, 32),     # unaligned length → padding
    (1, 128, 64, 4, 32, 64),
    (3, 32, 8, 16, 8, 16),
]


class TestMambaScan:
    @pytest.mark.parametrize("case", MAMBA_CASES)
    def test_kernel_matches_refs(self, case):
        b, l, di, n, bd, bt = case
        x = rand(1, (b, l, di))
        dt = jnp.abs(rand(2, (b, l, di))) * 0.1
        a = -jnp.abs(rand(3, (di, n)))
        bb = rand(4, (b, l, n))
        cc = rand(5, (b, l, n))
        d = rand(6, (di,))
        got = ops.mamba_scan(x, dt, a, bb, cc, d, impl="interpret",
                             block_d=bd, block_t=bt)
        want_assoc = ref.mamba_scan_ref(x, dt, a, bb, cc, d)
        want_seq = ref.mamba_scan_seq_ref(x, dt, a, bb, cc, d)
        np.testing.assert_allclose(np.asarray(want_assoc),
                                   np.asarray(want_seq), atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want_assoc),
                                   atol=1e-4, rtol=1e-4)

    def test_bfloat16(self):
        b, l, di, n = 1, 64, 16, 8
        x = rand(7, (b, l, di), jnp.bfloat16)
        dt = jnp.abs(rand(8, (b, l, di), jnp.bfloat16)) * 0.1
        a = -jnp.abs(rand(9, (di, n)))
        bb = rand(10, (b, l, n), jnp.bfloat16)
        cc = rand(11, (b, l, n), jnp.bfloat16)
        d = rand(12, (di,))
        got = ops.mamba_scan(x, dt, a, bb, cc, d, impl="interpret",
                             block_d=16, block_t=32)
        want = ref.mamba_scan_ref(x, dt, a, bb, cc, d)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=5e-2, rtol=5e-2)


SCATTER_CASES = [
    (16, 100, 8, 16),    # n_rows, m, d, block_m
    (64, 37, 4, 16),     # unaligned m → padding
    (8, 256, 16, 64),
    (32, 5, 8, 8),       # fewer ops than one block
]


class TestBucketScatter:
    @pytest.mark.parametrize("case", SCATTER_CASES)
    @pytest.mark.parametrize("sorted_idx", [True, False])
    def test_kernel_matches_ref(self, case, sorted_idx):
        n, m, d, bm = case
        tab = rand(1, (n, d))
        idx = jax.random.randint(jax.random.PRNGKey(2), (m,), 0, n + 3)
        if sorted_idx:
            idx = jnp.sort(idx)
        pay = rand(3, (m, d))
        got = ops.bucket_scatter_add(tab, idx, pay, impl="interpret",
                                     block_m=bm)
        want = ref.bucket_scatter_add_ref(tab, idx, pay)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)

    def test_out_of_range_dropped(self):
        tab = jnp.zeros((4, 2))
        idx = jnp.array([0, 4, 5, 3], jnp.int32)    # 4, 5 dropped
        pay = jnp.ones((4, 2))
        got = ops.bucket_scatter_add(tab, idx, pay, impl="interpret",
                                     block_m=4)
        want = jnp.zeros((4, 2)).at[jnp.array([0, 3])].add(1.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))


BITPACK_CASES = [
    (100, 8),            # W words, block_w
    (1024, 8),           # whole tiles
    (1300, 16),          # ragged tail
]


# The mark kernels' dead-op skip: a table of DEAD_W words, op blocks of
# DEAD_BM, each case a list of blocks, "L" all live, "D" all dead (the trash
# index, out of range or negative), "E" dead but for its first and last op.
DEAD_W, DEAD_BM = 40, 8
DEAD_MARK_CASES = {
    "all_trash": "DDDD",
    "all_live": "LLLL",
    "dead_blocks_between_live": "DLDDLDL",
    "live_across_block_edges": "EEE",
    "dead_last_block": "LLD",
    "ragged_tail": "DLD+",     # "+": a tail of 3 ops, one of them live
}


def dead_mark_ops(case):
    """The op indices of a DEAD_MARK_CASES case, with duplicates."""
    rng = np.random.default_rng(sorted(DEAD_MARK_CASES).index(case))
    cap = DEAD_W * 16
    dead = np.array([cap, cap + 5, -1, -7, cap])
    blocks = []
    for kind in DEAD_MARK_CASES[case]:
        n = 3 if kind == "+" else DEAD_BM
        ops_ = rng.choice(dead, n)
        if kind == "L":
            ops_ = rng.integers(0, cap, n)
        elif kind == "E":
            ops_[[0, -1]] = rng.integers(0, cap, 2)
        elif kind == "+":
            ops_[1] = rng.integers(0, cap)
        blocks.append(ops_)
    return np.concatenate(blocks).astype(np.int32)


class TestBitpack:
    """2-bit packed-array kernels (the implicit-BFS hot paths) vs oracles."""

    @pytest.mark.parametrize("case", BITPACK_CASES)
    def test_lut_count_matches_ref(self, case):
        w, bw = case
        packed = jax.random.randint(jax.random.PRNGKey(0), (w,), 0,
                                    1 << 30, dtype=jnp.int32).astype(jnp.uint32)
        lut = 0 | (3 << 2) | (1 << 4) | (3 << 6)    # the BFS rotate LUT
        got, gcnt = ops.bitpack_lut_count(packed, lut, 1, impl="interpret",
                                          block_w=bw)
        want, wcnt = ref.bitpack_lut_count_ref(packed, lut, 1)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        assert int(gcnt) == int(wcnt)

    def test_lut_count_pad_collision(self):
        # count_val == lut[0]: the kernel's tile padding maps to the counted
        # value and must be corrected away.
        packed = jnp.asarray([0, 0xFFFFFFFF, 5], jnp.uint32)
        lut = 0 | (0 << 2) | (2 << 4) | (1 << 6)
        got, gcnt = ops.bitpack_lut_count(packed, lut, 0, impl="interpret")
        want, wcnt = ref.bitpack_lut_count_ref(packed, lut, 0)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        assert int(gcnt) == int(wcnt)

    @pytest.mark.parametrize("bm", [4, 64])
    def test_scatter_mark_matches_ref(self, bm):
        w, m = 40, 200
        packed = jax.random.randint(jax.random.PRNGKey(1), (w,), 0,
                                    1 << 30, dtype=jnp.int32).astype(jnp.uint32)
        # duplicates, OOB high, negative — all must behave
        idx = jax.random.randint(jax.random.PRNGKey(2), (m,), -8,
                                 w * 16 + 32, dtype=jnp.int32)
        got = ops.bitpack_scatter_mark(packed, idx, impl="interpret",
                                       block_m=bm)
        want = ref.bitpack_scatter_mark_ref(packed, idx, 2, 0)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("bm", [4, 64])
    def test_mark_rotate_count_matches_ref(self, bm):
        # the fused per-level kernel ≡ scatter_mark ∘ lut_count, including
        # duplicate / OOB / negative indices landing in the trash row
        w, m = 40, 200
        packed = jax.random.randint(jax.random.PRNGKey(3), (w,), 0,
                                    1 << 30, dtype=jnp.int32).astype(jnp.uint32)
        idx = jax.random.randint(jax.random.PRNGKey(4), (m,), -8,
                                 w * 16 + 32, dtype=jnp.int32)
        lut = 0 | (3 << 2) | (1 << 4) | (3 << 6)    # the BFS rotate LUT
        got, gcnt = ops.bitpack_mark_rotate_count(
            packed, idx, lut, 1, impl="interpret", block_m=bm)
        want, wcnt = ref.bitpack_mark_rotate_count_ref(packed, idx, lut, 1,
                                                       2, 0)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        assert int(gcnt) == int(wcnt)

    def test_mark_rotate_count_pad_collision(self):
        # count_val == lut[0]: the trash row must stay out of the count and
        # the wrapper's tail-field correction must still hold
        packed = jnp.asarray([0, 0xFFFFFFFF, 5], jnp.uint32)
        lut = 0 | (0 << 2) | (2 << 4) | (1 << 6)
        idx = jnp.asarray([0, 7, 7, -1, 3 * 16 + 5], jnp.int32)
        got, gcnt = ops.bitpack_mark_rotate_count(packed, idx, lut, 0,
                                                  impl="interpret")
        want, wcnt = ref.bitpack_mark_rotate_count_ref(packed, idx, lut, 0,
                                                       2, 0)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        assert int(gcnt) == int(wcnt)

    # ------------------------------------------- dead marks skip their work

    @pytest.mark.parametrize("kernel",
                             ["scatter_mark", "mark_rotate_count", "staging"])
    @pytest.mark.parametrize("case", sorted(DEAD_MARK_CASES))
    def test_dead_marks_match_ref(self, kernel, case):
        # Ops aimed at the trash word are skipped, a whole op block at a
        # time where the block has no live op; the live ones still land.
        # The "staging" cases check what makes an op dead: staged, its
        # index is cap exactly when it was out of range or negative, or
        # padding to whole blocks.
        packed = jax.random.randint(jax.random.PRNGKey(5), (DEAD_W,), 0,
                                    1 << 30, dtype=jnp.int32).astype(jnp.uint32)
        idx = jnp.asarray(dead_mark_ops(case))
        lut = 0 | (3 << 2) | (1 << 4) | (3 << 6)    # the BFS rotate LUT
        if kernel == "staging":
            from repro.kernels import bitpack as bp
            cap = DEAD_W * 16
            raw = np.asarray(idx)
            staged = np.asarray(bp._stage_ops(idx, cap, DEAD_BM))
            assert staged.shape == (-(-raw.size // DEAD_BM) * DEAD_BM,)
            assert (staged[raw.size:] == cap).all()
            ok = (raw >= 0) & (raw < cap)
            assert np.array_equal(staged[:raw.size], np.where(ok, raw, cap))
            return
        if kernel == "scatter_mark":
            got = ops.bitpack_scatter_mark(packed, idx, impl="interpret",
                                           block_m=DEAD_BM)
            want = ref.bitpack_scatter_mark_ref(packed, idx, 2, 0)
        else:
            got, gcnt = ops.bitpack_mark_rotate_count(
                packed, idx, lut, 1, impl="interpret", block_m=DEAD_BM)
            want, wcnt = ref.bitpack_mark_rotate_count_ref(packed, idx, lut,
                                                           1, 2, 0)
            assert int(gcnt) == int(wcnt)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        if case == "all_trash" and kernel == "scatter_mark":
            assert np.array_equal(np.asarray(got), np.asarray(packed))

    # ------------------------------------------ serving-tier gather path

    @pytest.mark.parametrize("case", [
        (1000, 4096, 128, 64),   # W, M, page_words, block_m
        (64, 7, 32, 4),          # tiny, ragged page tail
        (4096, 20000, 512, 256), # defaults-shaped
    ])
    def test_gather2_matches_ref(self, case):
        # The Tier J batched-lookup acceptance pin: the paged gather
        # kernel must match the unpack-everything oracle BIT FOR BIT,
        # including OOB/negative queries (→ 0) and duplicate ranks.
        w, m, pw, bm = case
        rng = np.random.default_rng(w + m)
        packed = jnp.asarray(
            rng.integers(0, 1 << 32, w, dtype=np.uint64).astype(np.uint32))
        idx = rng.integers(-50, w * 16 + 50, m).astype(np.int64)
        got = ops.bitpack_gather2(packed, idx, impl="interpret",
                                  page_words=pw, block_m=bm)
        want = ops.bitpack_gather2(packed, idx, impl="ref")
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_gather2_empty_and_all_oob(self):
        packed = jnp.asarray(np.arange(10, dtype=np.uint32))
        for idx in (np.asarray([], np.int64), np.full(5, -3, np.int64),
                    np.full(3, 10 * 16 + 7, np.int64)):
            got = ops.bitpack_gather2(packed, idx, impl="interpret")
            want = ops.bitpack_gather2(packed, idx, impl="ref")
            assert np.array_equal(np.asarray(got), np.asarray(want))
            assert np.asarray(got).shape == idx.shape

    def test_gather2_matches_disk_packing(self):
        # Layout bridge: bytes packed by the DISK tier (4 fields/uint8,
        # field j at bits 2j), viewed little-endian as uint32 words, must
        # gather to the same fields the disk-side random read extracts —
        # the contract that lets a served oracle chunk feed the kernel.
        from repro.core.disk.bitarray import pack2
        rng = np.random.default_rng(5)
        vals = rng.integers(0, 4, 1000).astype(np.uint8)
        raw = pack2(vals)
        pad = (-raw.size) % 4
        words = jnp.asarray(np.frombuffer(
            np.concatenate([raw, np.zeros(pad, np.uint8)]).tobytes(),
            dtype="<u4"))
        idx = rng.integers(0, 1000, 500).astype(np.int64)
        got = ops.bitpack_gather2(words, idx, impl="interpret",
                                  page_words=8, block_m=16)
        assert np.array_equal(np.asarray(got), vals[idx].astype(np.int32))


class TestMamba2SSD:
    """Chunked SSD (matmul) form vs the recurrence oracles (§Perf cell C)."""

    @pytest.mark.parametrize("chunk", [8, 16, 13])
    def test_matches_mamba1_form(self, chunk):
        B, L, H, P, N = 2, 50, 3, 8, 16
        x4 = rand(1, (B, L, H, P))
        dt = jnp.abs(rand(2, (B, L, H))) * 0.1
        a = -jnp.abs(rand(3, (H,)))
        bm = rand(4, (B, L, N))
        cm = rand(5, (B, L, N))
        d = rand(6, (H,))
        y_ssd, h_ssd = ref.mamba2_ssd(x4, dt, a, bm, cm, d, chunk=chunk)
        di = H * P
        y_ref, h_ref = ref.mamba_scan_seq_stateful(
            x4.reshape(B, L, di), jnp.repeat(dt, P, axis=-1),
            jnp.broadcast_to(jnp.repeat(a, P)[:, None], (di, N)),
            bm, cm, jnp.repeat(d, P))
        np.testing.assert_allclose(np.asarray(y_ssd.reshape(B, L, di)),
                                   np.asarray(y_ref), atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(h_ssd.reshape(B, H, P, N)),
                                   np.asarray(h_ref.reshape(B, H, P, N)),
                                   atol=2e-4, rtol=2e-4)

    def test_h0_carry(self):
        """Running two halves with a state hand-off == one full pass."""
        B, L, H, P, N = 1, 64, 2, 4, 8
        x4 = rand(7, (B, L, H, P))
        dt = jnp.abs(rand(8, (B, L, H))) * 0.1
        a = -jnp.abs(rand(9, (H,)))
        bm = rand(10, (B, L, N))
        cm = rand(11, (B, L, N))
        d = rand(12, (H,))
        y_full, h_full = ref.mamba2_ssd(x4, dt, a, bm, cm, d, chunk=16)
        y1, h1 = ref.mamba2_ssd(x4[:, :32], dt[:, :32], a, bm[:, :32],
                                cm[:, :32], d, chunk=16)
        y2, h2 = ref.mamba2_ssd(x4[:, 32:], dt[:, 32:], a, bm[:, 32:],
                                cm[:, 32:], d, chunk=16, h0=h1)
        np.testing.assert_allclose(
            np.asarray(jnp.concatenate([y1, y2], axis=1)),
            np.asarray(y_full), atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                                   atol=2e-4, rtol=2e-4)


BWD_CASES = [
    # b, hq, hkv, sq, skv, d, causal, window, softcap
    (1, 2, 2, 64, 64, 32, True, None, None),
    (1, 4, 2, 64, 64, 32, True, None, None),       # GQA group-sum
    (1, 2, 2, 96, 96, 16, True, 32, None),         # window, unaligned
    (1, 2, 2, 64, 64, 32, True, None, 30.0),       # softcap derivative
    (1, 2, 1, 48, 80, 32, False, None, None),      # cross-len bidir MQA
]


class TestFlashAttentionBackward:
    """Pallas backward kernels (dkdv + dq) vs jax.grad of the naive oracle,
    plus the custom_vjp wiring in ops.flash_attention."""

    @pytest.mark.parametrize("case", BWD_CASES)
    def test_bwd_kernels_match_autograd(self, case):
        from repro.kernels.flash_attention import flash_attention as fa
        from repro.kernels.flash_attention_bwd import flash_attention_bwd
        b, hq, hkv, sq, skv, d, causal, window, softcap = case
        q = rand(1, (b, hq, sq, d))
        k = rand(2, (b, hkv, skv, d))
        v = rand(3, (b, hkv, skv, d))
        do = rand(4, (b, hq, sq, d))

        def f(q, k, v):
            o = ref.attention_naive(q, k, v, causal=causal, window=window,
                                    softcap=softcap)
            return jnp.sum(o.astype(jnp.float32) * do)
        dq_r, dk_r, dv_r = jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        o, lse = fa(q, k, v, causal=causal, window=window, softcap=softcap,
                    block_q=32, block_k=32, interpret=True, return_lse=True)
        dq, dk, dv = flash_attention_bwd(
            q, k, v, o, lse, do, causal=causal, window=window,
            softcap=softcap, block_q=32, block_k=32, interpret=True)
        g = hq // hkv
        dk = dk.reshape(b, hkv, g, skv, d).sum(2)
        dv = dv.reshape(b, hkv, g, skv, d).sum(2)
        for a_, b_ in [(dq, dq_r), (dk, dk_r), (dv, dv_r)]:
            np.testing.assert_allclose(np.asarray(a_), np.asarray(b_),
                                       atol=3e-4, rtol=3e-4)

    def test_custom_vjp_end_to_end(self):
        b, hq, hkv, sq, skv, d = 1, 4, 2, 64, 64, 32
        q = rand(5, (b, hq, sq, d))
        k = rand(6, (b, hkv, skv, d))
        v = rand(7, (b, hkv, skv, d))
        do = rand(8, (b, hq, sq, d))

        def f_kernel(q, k, v):
            o = ops.flash_attention(q, k, v, impl="interpret",
                                    block_q=32, block_k=32)
            return jnp.sum(o.astype(jnp.float32) * do)

        def f_ref(q, k, v):
            return jnp.sum(ref.attention_naive(q, k, v).astype(jnp.float32)
                           * do)
        gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a_, b_ in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a_), np.asarray(b_),
                                       atol=3e-4, rtol=3e-4)


class TestPagedDecodeKernel:
    """Flash-decoding over Roomy pages: scalar-prefetch page-table DMA
    indexing vs the contiguous-gather oracle, with SHUFFLED physical
    placement (proves the table is honored, not assumed identity)."""

    @pytest.mark.parametrize("case", [
        (2, 4, 2, 16, 4, 32, None),
        (3, 6, 2, 8, 5, 16, 30.0),      # GQA 3:1 + softcap
        (1, 4, 4, 16, 3, 32, None),     # MHA
    ])
    def test_matches_gather_oracle(self, case):
        from repro.kernels.paged_decode import paged_decode_attention
        b, hq, kvh, ps, pps, hd, softcap = case
        rng = np.random.default_rng(0)
        num_pages = b * pps + 3
        kp = jnp.asarray(rng.standard_normal((num_pages, ps, kvh, hd)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((num_pages, ps, kvh, hd)),
                         jnp.float32)
        q = jnp.asarray(rng.standard_normal((b, hq, hd)), jnp.float32)
        perm = rng.permutation(num_pages)[: b * pps]
        table = jnp.asarray(perm.reshape(b, pps), jnp.int32)
        lengths = jnp.asarray(rng.integers(1, pps * ps + 1, (b,)),
                              jnp.int32)
        got = paged_decode_attention(q, kp, vp, table, lengths,
                                     softcap=softcap, interpret=True)
        kf = kp[table].reshape(b, pps * ps, kvh, hd)
        vf = vp[table].reshape(b, pps * ps, kvh, hd)
        mask = jnp.arange(pps * ps)[None] < lengths[:, None]
        want = ref.decode_attention_ref(q, kf, vf, mask, softcap=softcap)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
