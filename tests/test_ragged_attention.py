"""Ragged paged attention (ISSUE 20): one program for mixed prefill+decode.

Oracle strategy, two levels:
- kernel: the packed ragged batch must reproduce a per-row dense masked
  softmax over the page pool (mixed decode rows, mid-prompt chunks, fresh
  prefills, empty rows in ONE call), with the interpret-mode Pallas tier
  matching the math tier — CPU tier-1 exercises the real kernel body;
- engine: a ContinuousBatchingEngine must emit the tokens of the plain
  reference (the model's no-cache forward, token by token:
  tests/_serving_reference.py) on every path that composes —
  greedy/sampled, async/sync, EOS mid-block, prefix cache, chunked long
  prompts — and, where the reference cannot say (an int8 pool rounds, an
  adapter changes the head), the tokens of the same request served alone;
  it compiles ONE mixed program per (sampling, rank).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.continuous import ContinuousBatchingEngine
from paddle_tpu.ops import ragged_paged_attention as rpa

import jax.numpy as jnp

from _serving_reference import reference_stream, reference_streams


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    paddle.seed(31)
    m = LlamaForCausalLM(llama_tiny(num_hidden_layers=2))
    m.eval()
    return m


def _mixed_case(seed=0, quantized=False):
    """One packed batch exercising every row shape at once:
    row 0 decode (q_len=1 over history), row 1 mid-prompt chunk,
    row 2 fresh full prefill, row 3 empty; 2 pad tokens."""
    rng = np.random.RandomState(seed)
    S, P_seq, bs, Hq, Hkv, D = 4, 3, 4, 4, 2, 8
    P = 1 + S * P_seq
    kp = rng.randn(Hkv, P, bs, D).astype(np.float32)
    vp = rng.randn(Hkv, P, bs, D).astype(np.float32)
    page_indices = np.arange(1, P).reshape(S, P_seq).astype(np.int32)
    q_lens = np.array([1, 6, 7, 0], np.int32)
    kv_lens = np.array([9, 11, 7, 0], np.int32)
    cu = np.zeros(S + 1, np.int32)
    cu[1:] = np.cumsum(q_lens)
    T = 16  # cu[-1] == 14 -> two pad tokens
    q = rng.randn(T, Hq, D).astype(np.float32)
    kpj, vpj = jnp.asarray(kp), jnp.asarray(vp)
    if quantized:
        from paddle_tpu.ops.paged_attention import quantize_pages

        kpj, vpj = quantize_pages(kpj), quantize_pages(vpj)
    return (jnp.asarray(q), kpj, vpj, jnp.asarray(kv_lens),
            jnp.asarray(page_indices), jnp.asarray(cu)), (
            kp, vp, page_indices, kv_lens, q_lens, cu, q, bs, Hq, Hkv, D)


def _dense_oracle(kp, vp, page_indices, kv_lens, q_lens, cu, q, bs,
                  Hq, Hkv, D):
    """Per-row dense masked softmax; limit[t] = kv - q_len + q_pos + 1."""
    T = q.shape[0]
    out = np.zeros((T, Hq, D), np.float32)
    g = Hq // Hkv
    for b in range(len(kv_lens)):
        if q_lens[b] == 0:
            continue
        kd = np.concatenate([kp[:, p] for p in page_indices[b]], axis=1)
        vd = np.concatenate([vp[:, p] for p in page_indices[b]], axis=1)
        for j in range(q_lens[b]):
            t = cu[b] + j
            limit = kv_lens[b] - q_lens[b] + j + 1
            for h in range(Hq):
                kh, vh = kd[h // g, :limit], vd[h // g, :limit]
                s = (q[t, h] @ kh.T) / np.sqrt(D)
                p_ = np.exp(s - s.max())
                p_ /= p_.sum()
                out[t, h] = p_ @ vh
    return out


class TestRaggedKernel:
    def test_mixed_rows_match_dense_oracle(self):
        args, raw = _mixed_case()
        out = rpa.ragged_paged_attention(*args, impl="math")
        ref = _dense_oracle(*raw)
        cu = raw[5]
        np.testing.assert_allclose(np.asarray(out)[:cu[-1]], ref[:cu[-1]],
                                   rtol=2e-5, atol=2e-6)

    def test_interpret_pallas_matches_math(self):
        """CPU tier-1 runs the REAL kernel body under interpret=True; it
        must agree with the math tier on the same mixed batch."""
        args, raw = _mixed_case(seed=3)
        ref = rpa.ragged_paged_attention(*args, impl="math")
        out = rpa.ragged_paged_attention(*args, impl="pallas")
        assert rpa.LAST_IMPL == "ragged-kernel-interpret"
        cu = raw[5]
        np.testing.assert_allclose(np.asarray(out)[:cu[-1]],
                                   np.asarray(ref)[:cu[-1]],
                                   rtol=1e-6, atol=1e-6)

    def test_int8_pool_pallas_matches_math(self):
        """Both tiers dequantize with the same from_int8 math; they are two
        implementations that sum in two orders (the kernel folds several
        pages per step, the math tier one), so they agree to f32 rounding
        of O(1) values, not bit for bit."""
        args, raw = _mixed_case(seed=5, quantized=True)
        ref = rpa.ragged_paged_attention(*args, impl="math")
        out = rpa.ragged_paged_attention(*args, impl="pallas")
        cu = raw[5]
        np.testing.assert_allclose(np.asarray(out)[:cu[-1]],
                                   np.asarray(ref)[:cu[-1]],
                                   rtol=0, atol=1e-6)

    @pytest.mark.parametrize("window", [3, 5, 64])
    def test_window_and_scale_match_a_dense_window(self, window):
        """A token sees its last `window` keys (its own among them) at the
        caller's scale, on both tiers: a window under a page, over one, and
        over every row."""
        args, raw = _mixed_case(seed=2)
        kp, vp, page_indices, kv_lens, q_lens, cu, q, bs, Hq, Hkv, D = raw
        want = np.zeros_like(q)
        for b in range(len(kv_lens)):
            kd = np.concatenate([kp[:, p] for p in page_indices[b]], axis=1)
            vd = np.concatenate([vp[:, p] for p in page_indices[b]], axis=1)
            for j in range(q_lens[b]):
                hi = kv_lens[b] - q_lens[b] + j + 1
                lo = max(hi - window, 0)
                for h in range(Hq):
                    s = (q[cu[b] + j, h] @ kd[h // 2, lo:hi].T) * 0.4
                    p = np.exp(s - s.max())
                    want[cu[b] + j, h] = (p / p.sum()) @ vd[h // 2, lo:hi]
        for impl in ("math", "pallas"):
            out = rpa.ragged_paged_attention(*args, scale=0.4, impl=impl,
                                             window=window)
            np.testing.assert_allclose(np.asarray(out)[:cu[-1]],
                                       want[:cu[-1]], rtol=2e-5, atol=2e-6)

    def test_a_windowed_walk_starts_at_the_pairs_first_block(self):
        """A long row and a window far shorter: the work list's fifth row
        names, for each (query block, row) pair, the kv block of its first
        token's lowest visible key; the grid's kv bound is the most blocks a
        pair walks from there; kv blocks wholly below hold NaN and are
        never read."""
        rng = np.random.RandomState(9)
        Hq, Hkv, D, bs, npages, window = 4, 2, 16, 8, 40, 24
        q_lens = np.array([1, 100, 0], np.int32)
        kv_lens = np.array([300, 260, 0], np.int32)
        cu = np.zeros(4, np.int32)
        cu[1:] = np.cumsum(q_lens)
        T = 104
        P = 1 + 3 * npages
        table = np.arange(1, P, dtype=np.int32).reshape(3, npages)
        kp = rng.randn(Hkv, P, bs, D).astype(np.float32)
        vp = rng.randn(Hkv, P, bs, D).astype(np.float32)
        lowest = [300 - window, 160 + 1 - window, 0]   # a row's first query
        for b in range(2):   # below the kv block (2 pages) of that key
            for j in range(lowest[b] // 16 * 2):
                kp[:, table[b, j]] = vp[:, table[b, j]] = np.nan
        q = jnp.asarray(rng.randn(T, Hq, D).astype(np.float32))
        tiles = rpa.RaggedTiles(tq=32, hb=Hkv, ppb=2, vmem=1 << 20)
        work, n_pairs, n_kv = rpa.ragged_work(cu, kv_lens, 32, 4, 16, xp=np,
                                              window=window)
        assert work.shape[0] == 5 and n_pairs == 5
        # pair 0: row 0's one token; row 1's span meets query blocks 0-3
        assert work[4, 0] == lowest[0] // 16
        assert work[4, 1] == lowest[1] // 16
        assert n_kv == max(-(-work[2, p] // 16) - work[4, p] for p in range(5))
        assert n_kv <= -(-(window + 32) // 16) + 1 < -(-300 // 16)
        out = rpa._ragged_pallas(q, jnp.asarray(kp), jnp.asarray(vp),
                                 jnp.asarray(kv_lens), jnp.asarray(table),
                                 jnp.asarray(cu), 0.25, interpret=True,
                                 tiles=tiles, window=window)
        ref = rpa._ragged_math(q, jnp.asarray(np.nan_to_num(kp)),
                               jnp.asarray(np.nan_to_num(vp)),
                               jnp.asarray(kv_lens), jnp.asarray(table),
                               jnp.asarray(cu), 0.25, window)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(np.asarray(out)[:101],
                                   np.asarray(ref)[:101], rtol=2e-5,
                                   atol=2e-6)

    @pytest.mark.parametrize("T,S,npages,bs", [
        (300, 3, 20, 16),   # three q blocks; kv blocks of 8 + 8 + 4 pages
        (40, 2, 9, 8),      # one q block; kv block of 9 pages (odd width)
    ])
    def test_blocked_kernel_matches_math_across_blocks(self, T, S, npages,
                                                       bs):
        """The kernel's q blocks (64 tokens), head blocks and multi-page
        kv blocks must tile a batch larger than any one of them: rows that
        straddle q blocks, GQA groups, a page table that is not a multiple
        of the kv block. Tolerance: two summation orders in f32."""
        rng = np.random.RandomState(T)
        Hq, Hkv, D = 16, 4, 32   # group 4 -> two kv heads per head block
        P = 1 + S * npages
        kp = jnp.asarray(rng.randn(Hkv, P, bs, D).astype(np.float32))
        vp = jnp.asarray(rng.randn(Hkv, P, bs, D).astype(np.float32))
        page_indices = jnp.asarray(
            rng.permutation(np.arange(1, P)).reshape(S, npages)
            .astype(np.int32))
        # a decode row over history, a long chunk over a short history
        # that straddles every q block, (an empty row); 7 pad tokens
        q_lens = np.array([1, T - 1 - 7, 0][:S], np.int32)
        kv_lens = np.where(q_lens > 0,
                           q_lens + np.array([50, 9, 0][:S]), 0)
        kv_lens = kv_lens.astype(np.int32)
        assert kv_lens.max() <= npages * bs
        cu = np.zeros(S + 1, np.int32)
        cu[1:] = np.cumsum(q_lens)
        q = jnp.asarray(rng.randn(T, Hq, D).astype(np.float32))
        args = (q, kp, vp, jnp.asarray(kv_lens), page_indices,
                jnp.asarray(cu))
        ref = rpa.ragged_paged_attention(*args, impl="math")
        out = rpa.ragged_paged_attention(*args, impl="pallas")
        np.testing.assert_allclose(np.asarray(out)[:cu[-1]],
                                   np.asarray(ref)[:cu[-1]],
                                   rtol=1e-5, atol=1e-5)

    # (T, page size, pages a row, Hq, Hkv, quantized, tiles (tq, hb, ppb),
    #  rows as (q_len, kv_len) by slot); tiles None = the rule's
    WALK_CASES = {
        # one row over two query blocks, and over three (a neighbour each side)
        "straddles-two-blocks": (64, 8, 12, 4, 4, False, (32, 4, 2),
                                 [(3, 9), (40, 40), (2, 70)]),
        "straddles-three-blocks": (96, 8, 12, 4, 4, False, (32, 2, 2),
                                   [(20, 20), (70, 90), (1, 5)]),
        "sixteen-one-token-rows": (32, 8, 6, 4, 2, False, (32, 2, 2),
                                   [(1, 3 * i + 1) for i in range(16)]),
        "dead-row-between-live": (48, 8, 6, 4, 4, False, (16, 4, 2),
                                  [(10, 10), (0, 0), (21, 30), (0, 17)]),
        "trailing-pad-blocks": (128, 8, 6, 4, 4, False, (32, 4, 2),
                                [(5, 5), (1, 33)]),
        # kv_len on a page's boundary, on a kv block's, and one past each
        "kv-on-boundaries": (64, 8, 8, 4, 4, False, (32, 4, 2),
                             [(1, 8), (1, 9), (1, 16), (1, 17), (4, 32),
                              (4, 33), (1, 64)]),
        "prefix-cache-row": (64, 8, 16, 4, 4, False, (32, 4, 4),
                             [(3, 120), (1, 7), (17, 128)]),
        "gqa-group-4": (80, 8, 10, 8, 2, False, (32, 2, 2),
                        [(1, 44), (50, 61), (0, 0), (9, 9)]),
        "gqa-group-8": (80, 8, 10, 16, 2, False, (32, 1, 4),
                        [(1, 44), (50, 61), (0, 0), (9, 9)]),
        "int8-pool": (80, 8, 10, 8, 4, True, (32, 2, 2),
                      [(1, 44), (50, 61), (0, 0), (9, 9)]),
        "rule-tiles-gqa": (300, 16, 20, 16, 4, False, None,
                           [(1, 51), (280, 300), (0, 0), (2, 2)]),
        "rule-tiles-int8": (140, 16, 9, 8, 8, True, None,
                            [(130, 130), (1, 144)]),
        "no-live-row": (32, 8, 4, 4, 4, False, (16, 4, 2),
                        [(0, 0), (0, 9)]),
    }

    @pytest.mark.parametrize("case", sorted(WALK_CASES))
    def test_live_pair_walk_matches_math(self, case):
        """The kernel's grid over its live (query block, row) pairs against
        `_ragged_math`, in interpret mode: every token a row owns agrees
        (two summation orders in f32) and every pad token, in a visited
        block or not, reads exactly zero."""
        T, bs, npages, Hq, Hkv, quantized, tiles, rows = self.WALK_CASES[case]
        rng = np.random.RandomState(len(case) + T)
        S, D = len(rows), 32
        P = 1 + S * npages
        kp = jnp.asarray(rng.randn(Hkv, P, bs, D).astype(np.float32))
        vp = jnp.asarray(rng.randn(Hkv, P, bs, D).astype(np.float32))
        if quantized:
            from paddle_tpu.ops.paged_attention import quantize_pages

            kp, vp = quantize_pages(kp), quantize_pages(vp)
        table = jnp.asarray(rng.permutation(np.arange(1, P))
                            .reshape(S, npages).astype(np.int32))
        q_lens = np.array([r[0] for r in rows], np.int32)
        kv_lens = np.array([r[1] for r in rows], np.int32)
        assert kv_lens.max() <= npages * bs and q_lens.sum() <= T
        cu = np.zeros(S + 1, np.int32)
        cu[1:] = np.cumsum(q_lens)
        q = jnp.asarray(rng.randn(T, Hq, D).astype(np.float32))
        args = (q, kp, vp, jnp.asarray(kv_lens), table, jnp.asarray(cu))
        if tiles is not None:
            tiles = rpa.RaggedTiles(*tiles, vmem=32 << 20)
        ref = np.asarray(rpa._ragged_math(*args, D ** -0.5))
        out = np.asarray(rpa._ragged_pallas(*args, D ** -0.5, interpret=True,
                                            tiles=tiles))
        n = int(cu[-1])
        np.testing.assert_allclose(out[:n], ref[:n], rtol=1e-5, atol=1e-5)
        assert not out[n:].any()

    @staticmethod
    def _enumerate_work(cu, kv_lens, tq, n_qblocks, kv_blk):
        """The work list by a plain enumeration of (query block, row)."""
        pairs = []
        for i in range(n_qblocks):
            for b in range(len(kv_lens)):
                lo, hi = max(cu[b], i * tq), min(cu[b + 1], (i + 1) * tq)
                if hi > lo:  # the row has tokens in the block
                    pairs.append((i, b, kv_lens[b] - (cu[b + 1] - hi)))
        edges = [(p == 0 or pairs[p - 1][0] != blk)
                 + 2 * (p == len(pairs) - 1 or pairs[p + 1][0] != blk)
                 for p, (blk, _, _) in enumerate(pairs)]
        longest = max([kv for q0, q1, kv in zip(cu, cu[1:], kv_lens)
                       if q1 > q0], default=0)
        return pairs, edges, -(-longest // kv_blk)

    @pytest.mark.parametrize("seed", range(6))
    def test_work_list_matches_enumeration(self, seed):
        """`ragged_work` on random spans: the pairs, their limits and edge
        bits and both grid bounds are a plain enumeration's, and the numpy
        call (the engine's counter) agrees with the `jnp` call (the
        kernel's) entry for entry."""
        rng = np.random.RandomState(seed)
        S = int(rng.choice([1, 4, 16]))
        tq = int(rng.choice([8, 16, 128]))
        n_qblocks = int(rng.randint(1, 6))
        T, kv_blk = n_qblocks * tq, int(rng.choice([16, 128]))
        for _ in range(20):
            q_lens = np.where(rng.rand(S) < 0.4, 0,
                              rng.randint(0, 2 * T // S + 2, S))
            while q_lens.sum() > T:
                q_lens[rng.randint(S)] //= 2
            kv_lens = np.where(q_lens > 0, q_lens + rng.randint(0, 300, S),
                               rng.randint(0, 50, S)).astype(np.int32)
            cu = np.zeros(S + 1, np.int32)
            cu[1:] = np.cumsum(q_lens)
            pairs, edges, n_kv = self._enumerate_work(cu, kv_lens, tq,
                                                      n_qblocks, kv_blk)
            assert len(pairs) <= n_qblocks + S - 1
            work, n_pairs, got_kv = rpa.ragged_work(cu, kv_lens, tq,
                                                    n_qblocks, kv_blk, xp=np)
            assert (int(n_pairs), int(got_kv)) == (len(pairs), n_kv)
            assert work.shape == (4, n_qblocks + S - 1)
            assert [tuple(w) for w in work[:3, :len(pairs)].T] == pairs
            assert list(work[3, :len(pairs)]) == edges
            # what the grid never visits still indexes inside its operands
            assert (work[0] >= 0).all() and (work[0] < n_qblocks).all()
            assert (work[1] >= 0).all() and (work[1] < S).all()
            jw, jn, jk = rpa.ragged_work(jnp.asarray(cu),
                                         jnp.asarray(kv_lens), tq,
                                         n_qblocks, kv_blk)
            np.testing.assert_array_equal(np.asarray(jw), work)
            assert (int(jn), int(jk)) == (len(pairs), n_kv)

    def test_tiles_follow_the_shape(self):
        """`_ragged_tiles` at the serving cell's widths and at Mistral's:
        every KV head a step, pages a block by the bytes a page holds, and
        fewer heads where the int8 pool's f32 blocks would pass the budget."""
        import jax

        def pool(hkv, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct((hkv, 2049, 16, 128), dtype)

        mha = rpa._ragged_tiles(528, 32, pool(32), 128)
        assert (mha.tq, mha.hb, mha.ppb) == (64, 32, 8)
        gqa = rpa._ragged_tiles(528, 32, pool(8), 128)
        assert (gqa.tq, gqa.hb, gqa.ppb) == (64, 8, 32)
        assert rpa._ragged_tiles(20, 32, pool(8), 5).tq == 24
        assert rpa._ragged_tiles(20, 32, pool(8), 5).ppb == 5
        for t in (mha, gqa):
            assert t.vmem <= rpa._VMEM_BUDGET
        # (walked, dense) at the cell's shape: one 256-token chunk at kv 256
        # between two decode rows at 350 -> 7 pairs x 3 kv blocks, of 9 query
        # blocks x 16 rows x 16 kv blocks
        q_lens = np.zeros(16, np.int32)
        kv_lens = np.zeros(16, np.int32)
        q_lens[[2, 5, 9]], kv_lens[[2, 5, 9]] = (1, 256, 1), (350, 256, 350)
        cu = np.concatenate([[0], np.cumsum(q_lens)])
        assert rpa.ragged_walk(cu, kv_lens, 528, 32, pool(32), 128) == (
            21, 9 * 16 * 16)

    def test_write_ragged_kv_places_tokens_and_scratches_pads(self):
        rng = np.random.RandomState(1)
        S, P_seq, bs, Hkv, D = 2, 2, 4, 2, 3
        P = 1 + S * P_seq
        pages = jnp.zeros((Hkv, P, bs, D), jnp.float32)
        page_indices = jnp.asarray(
            np.arange(1, P).reshape(S, P_seq).astype(np.int32))
        # row 0 tokens at positions 2,3,4 (page boundary crossing);
        # row 1 token at position 0; one pad token
        row_of = jnp.asarray(np.array([0, 0, 0, 1, 0], np.int32))
        token_pos = jnp.asarray(np.array([2, 3, 4, 0, 0], np.int32))
        valid = jnp.asarray(np.array([1, 1, 1, 1, 0], bool))
        new = jnp.asarray(rng.randn(5, Hkv, D).astype(np.float32))
        out = np.asarray(rpa.write_ragged_kv(pages, page_indices, row_of,
                                             token_pos, valid, new))
        new_h = np.swapaxes(np.asarray(new), 0, 1)
        np.testing.assert_array_equal(out[:, 1, 2], new_h[:, 0])
        np.testing.assert_array_equal(out[:, 1, 3], new_h[:, 1])
        np.testing.assert_array_equal(out[:, 2, 0], new_h[:, 2])
        np.testing.assert_array_equal(out[:, 3, 0], new_h[:, 3])
        # the pad token landed in scratch page 0, nowhere else
        assert np.any(out[:, 0] != 0)
        written = {(1, 2), (1, 3), (2, 0), (3, 0)}
        for pid in range(1, P):
            for off in range(bs):
                if (pid, off) not in written:
                    assert not np.any(out[:, pid, off])


def _aligned_like(a):
    """A copy of `a` in a 64-byte-aligned buffer — the case in which the
    CPU backend's jnp.asarray aliases numpy memory instead of copying."""
    buf = np.zeros(a.size + 64, a.dtype)
    off = (-buf.ctypes.data % 64) // a.itemsize
    out = buf[off:off + a.size].reshape(a.shape)
    out[...] = a
    assert out.ctypes.data % 64 == 0
    return out


def _prompts(rng, lens, vocab=100):
    return [rng.randint(1, vocab, size=n).astype(np.int32) for n in lens]


def _engine(model, **kw):
    return ContinuousBatchingEngine(
        model, **{**dict(max_seqs=4, page_size=16, max_len=160), **kw})


def _serve_pair(model, prompts, eng_kw=None, **serve_kw):
    """(reference tokens, engine tokens) for the same workload."""
    return (reference_streams(model, prompts, **serve_kw),
            _engine(model, **(eng_kw or {})).serve(prompts, **serve_kw))


def _served_alone(model, prompts, adapters=None, eng_kw=None, **serve_kw):
    """Each request by itself on a one-slot engine whose chunk budget
    holds it whole: no co-tenant, no second chunk, no prefix cache."""
    eng = _engine(model, max_seqs=1, **(eng_kw or {}))
    assert max(map(len, prompts)) <= eng._ragged_chunk
    return [eng.serve([p], adapters=ad, **serve_kw)[0]
            for p, ad in zip(prompts, adapters or [None] * len(prompts))]


class TestRaggedEngine:
    def test_bit_identical_greedy_async_and_sync(self, model):
        rng = np.random.RandomState(7)
        prompts = _prompts(rng, (3, 17, 41, 9, 28))
        for mode in ({}, {"async_decode": False}):
            want, got = _serve_pair(model, prompts, eng_kw=mode,
                                    max_new_tokens=12)
            for w, g in zip(want, got):
                np.testing.assert_array_equal(w, g)

    def test_bit_identical_sampled(self, model):
        rng = np.random.RandomState(11)
        prompts = _prompts(rng, (5, 33, 12, 20))
        want, got = _serve_pair(model, prompts, max_new_tokens=10,
                                do_sample=True, temperature=0.8, top_k=20,
                                seed=3)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)

    @pytest.mark.parametrize("max_seqs, chunk", [(4, None), (1, 16)],
                             ids=["co-tenants", "alone-chunked"])
    def test_sampled_key_scheme_under_two_schedules(self, model, max_seqs,
                                                    chunk):
        """The reference draws token i of request rid under
        fold_in(fold_in(PRNGKey(seed), rid), i) and knows no schedule: an
        engine that serves the four requests side by side and one that
        serves them one after another, 16 prompt tokens a dispatch, both
        give its streams."""
        rng = np.random.RandomState(41)
        prompts = _prompts(rng, (5, 33, 12, 20))
        kw = dict(max_new_tokens=[9, 4, 10, 6], do_sample=True,
                  temperature=0.9, top_p=0.9, seed=5)
        want, got = _serve_pair(
            model, prompts, eng_kw=dict(max_seqs=max_seqs,
                                        prefill_chunk=chunk), **kw)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)

    @pytest.mark.parametrize("decode_block", [8, 1])
    def test_dispatch_operands_do_not_alias_engine_arrays(self, model,
                                                          decode_block):
        """The race behind the token mismatches that only some processes
        showed (alignment decides, load times it), without a clock: the
        engine mutates `lengths`/`page_table` in place right after an
        ASYNC dispatch, so no program operand may share their memory — on
        the CPU backend jnp.asarray of a 64-byte-aligned numpy buffer does
        — and, read back after all the bookkeeping, each must still hold
        what the host held at dispatch."""
        eng = _engine(model, decode_block=decode_block)
        eng.lengths = _aligned_like(eng.lengths)
        eng.page_table = _aligned_like(eng.page_table)
        calls = []  # (operands that mirror engine arrays, host values then)

        def spy(builder, mirrors):
            def build(*key):
                fn = builder(*key)

                def call(*args):
                    calls.append(([(args[i], getattr(eng, name).copy())
                                   for i, name in mirrors], args))
                    return fn(*args)
                return call
            return build

        # positional layouts of the programs' operands (decode_block 1
        # dispatches the k=1 twin of the decode block)
        decode_layout = [(3, "page_table"), (4, "lengths")]
        eng._decode_block_fn = spy(eng._decode_block_fn, decode_layout)
        eng._decode = spy(eng._decode, decode_layout)
        eng._ragged_fn = spy(eng._ragged_fn, [(9, "page_table")])
        rng = np.random.RandomState(17)
        eng.serve(_prompts(rng, (5, 21)),
                  max_new_tokens=2 * eng.decode_block + 1)
        assert {len(args) for _, args in calls} == {7, 14}  # both programs
        for mirrored, args in calls:
            for a in args:
                if hasattr(a, "shape"):
                    host = np.asarray(a)
                    assert not np.shares_memory(host, eng.lengths)
                    assert not np.shares_memory(host, eng.page_table)
            for operand, then in mirrored:
                np.testing.assert_array_equal(np.asarray(operand), then)

    def test_eos_mid_block_truncates_identically(self, model):
        rng = np.random.RandomState(13)
        prompts = _prompts(rng, (6, 25, 14))
        ref = reference_streams(model, prompts, max_new_tokens=16)
        # pick an eos that really fires mid-stream for some request
        eos = int(np.asarray(ref[0])[len(prompts[0]) + 3])
        want, got = _serve_pair(model, prompts, max_new_tokens=16,
                                eos_token_id=eos)
        assert any(len(np.asarray(w)) < len(p) + 16
                   for w, p in zip(want, prompts))
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)

    def test_bit_identical_prefix_cache_and_chunked(self, model):
        rng = np.random.RandomState(17)
        shared = rng.randint(1, 100, size=24).astype(np.int32)
        prompts = [np.concatenate([shared, p])
                   for p in _prompts(rng, (3, 17, 41, 9))]
        want = reference_streams(model, prompts, max_new_tokens=6)
        eng = _engine(model, page_size=8, enable_prefix_cache=True)
        for _ in range(2):  # the second serve hits the prefix cache
            for w, g in zip(want, eng.serve(prompts, max_new_tokens=6)):
                np.testing.assert_array_equal(w, g)
        assert eng.stats["prefix_hit_pages"] > 0
        # long prompts under a chunk budget a quarter of the longest
        long_prompts = _prompts(rng, (90, 130, 5))
        want, got = _serve_pair(
            model, long_prompts, eng_kw={"prefill_chunk": 32, "max_len": 256},
            max_new_tokens=10)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)

    def test_int8_pool_is_schedule_and_chunk_independent(self, model):
        """An int8 pool rounds every K and V it stores, so the float
        reference cannot say its tokens; every token attends through the
        pool whatever chunk wrote it and whoever shares the dispatch, so
        the same request served alone can, under the default chunk budget
        and under one smaller than the prompts."""
        rng = np.random.RandomState(19)
        prompts = _prompts(rng, (3, 17, 41, 9, 28))
        kw = {"kv_cache_dtype": "int8"}
        want = _served_alone(model, prompts, eng_kw=kw, max_new_tokens=8)
        for eng_kw in (kw, {**kw, "prefill_chunk": 16}):
            got = _engine(model, **eng_kw).serve(prompts, max_new_tokens=8)
            for w, g in zip(want, got):
                np.testing.assert_array_equal(w, g)

    def test_lora_batch_rows(self, model):
        """A mixed batch: the rows without an adapter and with a zero one
        are the plain reference's; a row with an adapter (the reference
        has no adapter head) is the same request's served alone."""
        from paddle_tpu.serving.adapters import LoRAAdapter

        rng = np.random.RandomState(23)
        hidden = model.config.hidden_size
        vocab = model.config.vocab_size
        ad = LoRAAdapter("a1", rng.randn(hidden, 4).astype(np.float32) * .05,
                         rng.randn(4, vocab).astype(np.float32) * .05)
        zad = LoRAAdapter("z0", np.zeros((hidden, 4), np.float32),
                          np.zeros((4, vocab), np.float32))
        prompts = _prompts(rng, (3, 17, 41, 9))
        adapters = [ad, None, zad, ad]
        got = _engine(model).serve(prompts, max_new_tokens=8,
                                   adapters=adapters)
        alone = _served_alone(model, prompts, adapters, max_new_tokens=8)
        for rid, (p, a) in enumerate(zip(prompts, adapters)):
            want = (alone[rid] if a is ad
                    else reference_stream(model, p, 8, rid=rid))
            np.testing.assert_array_equal(want, got[rid])

    def test_warmup_covers_ragged_programs(self, model):
        """After warmup, a mixed serve (short + long prompts, two sampling
        configs) must add NO program keys and NO serve.* compile-ledger
        events — the steady-state zero-recompile contract, with a warmup
        that is one dummy serve per config whatever the prompt lengths."""
        from paddle_tpu.observability import compilemem

        eng = _engine(model)
        eng.warmup(prompt_lens=[3, 17, 41],
                   sampling=[(False, 1.0, 0, 1.0), (True, 0.8, 20, 1.0)])
        # collapsed program count: ONE mixed + one block program per
        # sampling config (plus k=1 decode only when decode_block == 1)
        assert len(eng._ragged_fns) == 2
        assert not eng._insert_fns and not eng._gather_fns
        warm_before = set(eng._warm)

        def _serve_counts():
            rep = compilemem.ledger.report(recent=0)["by_key"]
            return {k: v["count"] for k, v in rep.items()
                    if k.startswith("serve.")}

        before = _serve_counts()
        rng = np.random.RandomState(29)
        prompts = _prompts(rng, (3, 17, 41, 9, 28))
        eng.serve(prompts, max_new_tokens=12)
        eng.serve(prompts, max_new_tokens=12, do_sample=True,
                  temperature=0.8, top_k=20, seed=5)
        assert set(eng._warm) == warm_before
        assert _serve_counts() == before

    def test_devprof_ragged_row(self, model, monkeypatch):
        """The mixed dispatch banks device-seconds per token under its
        serve.ragged[...] program key (ISSUE 17 plane, new key family)."""
        from paddle_tpu.observability import devprof

        devprof._reset()
        devprof.enable(sample_every=1)
        try:
            # small chunk budget -> several mixed dispatches per prompt, so
            # warm (post-compile) dispatches exist for the cadence to time
            eng = _engine(model, max_seqs=2, prefill_chunk=16)
            rng = np.random.RandomState(31)
            eng.serve(_prompts(rng, (40, 55)), max_new_tokens=6)
            table = devprof.plane()._table()
            keys = [k for k in table if k.startswith("serve.ragged[")]
            assert keys, sorted(table)
            rec = table[keys[0]]
            assert rec["device_s"] > 0 and rec["tokens"] > 0
        finally:
            devprof._reset()

    def test_deadline_returns_partial_without_first_token(self, model):
        """Admission produces no token, so under the async pipeline an
        instant deadline may return a prompt-only partial — but the
        request must still retire cleanly with its slot freed."""
        rng = np.random.RandomState(37)
        eng = _engine(model, max_seqs=1, max_len=64, decode_block=1)
        p = _prompts(rng, (5,))[0]
        outs = eng.serve([p], max_new_tokens=30, request_timeout_s=0.0)
        assert eng.stats["timed_out_requests"] == 1
        assert outs[0] is not None
        assert len(p) <= len(np.asarray(outs[0])) < len(p) + 30
        assert eng.idle() and len(eng.free_slots) == 1
