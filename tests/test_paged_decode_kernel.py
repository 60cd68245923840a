"""The float pool's paged decode kernel (ops/paged_attention.py), in interpret
mode on the CPU, against the math tier `_paged_math`: one query token a row
over the row's pages, every KV head a grid step, live rows only.

What only the chip's compiler can refuse (tiling, VMEM, the operand count) is
tests/test_chip_compile.py's; what the kernel costs is PERF.md's.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import paged_attention as pa

D, BS, NPAGES = 64, 16, 6
FULL = NPAGES * BS
HEADS = {"mha-g1": (4, 4), "gqa-g4": (8, 2), "gqa-g8": (8, 1)}
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}
# a bf16 pool's two tiers round their f32 result once each: one step apart
TOL = {"f32": dict(rtol=2e-5, atol=2e-6), "bf16": dict(rtol=1.6e-2, atol=1.6e-2)}


def _case(heads, dtype, rows, seed=0):
    """q, K pool, V pool and a page table of `rows` rows whose pages are
    their own; page 0 is the scratch page and holds values no row may see."""
    hq, hkv = HEADS[heads]
    rng = np.random.RandomState(seed)
    P = 1 + rows * NPAGES
    kp = rng.randn(hkv, P, BS, D).astype(np.float32)
    vp = rng.randn(hkv, P, BS, D).astype(np.float32)
    kp[:, 0], vp[:, 0] = 1e3, 1e3
    table = np.arange(1, P, dtype=np.int32).reshape(rows, NPAGES)
    q = rng.randn(rows, hq, D).astype(np.float32)
    dt = DTYPES[dtype]
    return (jnp.asarray(q, dt), jnp.asarray(kp, dt), jnp.asarray(vp, dt),
            jnp.asarray(table))


def _both(q, kp, vp, lens, table):
    lens = jnp.asarray(lens, jnp.int32)
    out = pa.paged_decode_attention(q, kp, vp, lens, table, impl="pallas")
    assert pa.LAST_IMPL == "paged-kernel-interpret"
    ref = pa.paged_decode_attention(q, kp, vp, lens, table, impl="math")
    assert pa.LAST_IMPL == "paged-math"
    assert out.shape == q.shape and out.dtype == q.dtype
    return (np.asarray(out.astype(jnp.float32)),
            np.asarray(ref.astype(jnp.float32)))


@pytest.mark.parametrize("length", [1, BS, BS + 1, FULL])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_kernel_matches_math(heads, dtype, length):
    """A length of 1, a page boundary, one past it and the full table, beside
    a row of another length (so the grid's block bound is not the row's)."""
    q, kp, vp, table = _case(heads, dtype, rows=2)
    out, ref = _both(q, kp, vp, [length, 37], table)
    np.testing.assert_allclose(out, ref, **TOL[dtype])


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_dead_rows_are_zeros_and_move_no_live_row(heads, dtype):
    """Most rows dead, their table rows pointing at the scratch page as the
    engine's empty slots do: a dead row returns zeros, and a live row's output
    is bit for bit what the all-live call gives it."""
    q, kp, vp, table = _case(heads, dtype, rows=6)
    all_live = [12, 37, 20, 3, 9, FULL]
    mostly_dead = [0, 37, 0, 0, 9, 0]
    dead = np.asarray(mostly_dead) == 0
    full, _ = _both(q, kp, vp, all_live, table)
    out, ref = _both(q, kp, vp, mostly_dead,
                     jnp.where(dead[:, None], 0, table))
    assert not out[dead].any() and not ref[dead].any()
    np.testing.assert_array_equal(out[~dead], full[~dead])
    np.testing.assert_allclose(out, ref, **TOL[dtype])


@pytest.mark.parametrize("heads", sorted(HEADS))
def test_every_row_dead(heads):
    """No live row: the grid has no step, and the result is zeros."""
    q, kp, vp, table = _case(heads, "f32", rows=3)
    out, ref = _both(q, kp, vp, [0, 0, 0], jnp.zeros_like(table))
    assert not out.any() and not ref.any()


@pytest.mark.parametrize("heads", sorted(HEADS))
def test_scratch_page_is_never_seen(heads):
    """A row's pages past its length, and every page of a dead row, are page 0
    in the table (the pool's scratch page, here 1e3 everywhere): nothing of it
    reaches an output, whose rows stay convex combinations of their own V."""
    q, kp, vp, table = _case(heads, "f32", rows=4)
    lens = np.asarray([0, BS + 3, 0, 5])
    held = np.arange(NPAGES)[None] * BS < lens[:, None]
    out, ref = _both(q, kp, vp, lens, jnp.where(held, table, 0))
    np.testing.assert_allclose(out, ref, **TOL["f32"])
    assert np.abs(out).max() < 10.0


def test_blocks_that_do_not_divide_the_table():
    """7 pages a row in blocks of 4: the last block's pages past the table map
    to the scratch page and compute nothing."""
    hq, hkv = HEADS["gqa-g4"]
    rng = np.random.RandomState(3)
    P = 1 + 2 * 7
    kp = jnp.asarray(rng.randn(hkv, P, BS, D), jnp.float32)
    vp = jnp.asarray(rng.randn(hkv, P, BS, D), jnp.float32)
    table = jnp.asarray(np.arange(1, P).reshape(2, 7), jnp.int32)
    q = jnp.asarray(rng.randn(2, hq, D), jnp.float32)
    lens = jnp.asarray([7 * BS, 4 * BS + 1], jnp.int32)
    out = pa._paged_pallas(q, kp, vp, lens, table, D ** -0.5, interpret=True,
                           ppb=4)
    ref = pa._paged_math(q, kp, vp, lens, table, D ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **TOL["f32"])


def test_block_rule_follows_the_shape():
    """Pages a block come from the shape alone: never more than the table
    holds, at least one, and fewer where a page of all heads is larger."""
    def pool(hkv, bs, d, dtype=jnp.bfloat16):
        return jnp.zeros((hkv, 2, bs, d), dtype)

    cell = pa._pages_per_block(pool(32, 16, 128), 128)
    assert 1 <= pa._pages_per_block(pool(32, 16, 128), 3) <= 3
    assert pa._pages_per_block(pool(32, 128, 256, jnp.float32), 128) <= cell
    assert pa._pages_per_block(pool(8, 16, 128), 128) >= cell


def test_int8_pool_has_no_interpret_tier():
    """The int8 pool's kernel is jax's, which runs on a TPU only: asking for
    it elsewhere raises, it never becomes the math tier."""
    q, kp, vp, table = _case("mha-g1", "f32", rows=2)
    with pytest.raises(ValueError, match="int8"):
        pa.paged_decode_attention(
            q, pa.quantize_pages(kp), pa.quantize_pages(vp),
            jnp.asarray([5, 9], jnp.int32), table, impl="pallas")


# ---- a window and a scale of the caller's (models/phi4flash.py) ------------

def _dense_window(q, kp, vp, lens, table, window, scale):
    """Each row's last `window` keys by a dense softmax, in numpy."""
    q, kp, vp = (np.asarray(a, np.float32) for a in (q, kp, vp))
    out = np.zeros_like(q)
    g = q.shape[1] // kp.shape[0]
    for b, n in enumerate(lens):
        if n == 0:
            continue
        kd = np.concatenate([kp[:, p] for p in np.asarray(table[b])], axis=1)
        vd = np.concatenate([vp[:, p] for p in np.asarray(table[b])], axis=1)
        lo = max(n - window, 0)
        for h in range(q.shape[1]):
            s = (q[b, h] @ kd[h // g, lo:n].T) * scale
            p = np.exp(s - s.max())
            out[b, h] = (p / p.sum()) @ vd[h // g, lo:n]
    return out


@pytest.mark.parametrize("window, ppb", [(20, None), (20, 1), (33, 2),
                                         (FULL + 5, None)])
@pytest.mark.parametrize("heads", ["gqa-g4", "mha-g1"])
def test_window_and_scale_match_a_dense_window(heads, window, ppb):
    """Rows shorter than the window, just over it and far over it, one dead:
    both tiers see each row's last `window` keys alone, at a scale that is
    not 1/sqrt(D). Blocks of 1 and 2 pages make a row's walk start past
    block 0."""
    q, kp, vp, table = _case(heads, "f32", rows=5)
    lens = [7, window + 1 if window < FULL else FULL, 0, FULL, 50]
    scale = 0.21
    want = _dense_window(q, kp, vp, lens, table, window, scale)
    lens = jnp.asarray(lens, jnp.int32)
    out = pa._paged_pallas(q, kp, vp, lens, table, scale, interpret=True,
                           ppb=ppb, window=window)
    ref = pa.paged_decode_attention(q, kp, vp, lens, table, scale=scale,
                                    impl="math", window=window)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(ref), want, rtol=2e-5, atol=2e-6)


def test_a_windowed_walk_never_reads_below_the_window():
    """Pages wholly below every row's window hold NaN: a walk that read one
    would carry it into the softmax (0 x NaN)."""
    q, kp, vp, table = _case("gqa-g4", "f32", rows=2)
    window, lens = 20, [FULL, 70]
    kp, vp = np.array(kp), np.array(vp)
    for b, n in enumerate(lens):
        for j in range((n - window) // BS):   # pages wholly below
            kp[:, table[b, j]] = np.nan
            vp[:, table[b, j]] = np.nan
    out = pa._paged_pallas(q, jnp.asarray(kp), jnp.asarray(vp),
                           jnp.asarray(lens, jnp.int32), table, 0.125,
                           interpret=True, ppb=1, window=window)
    assert np.isfinite(np.asarray(out)).all()


def test_a_ring_of_pages_is_a_table_whose_entries_repeat():
    """`WindowRingSpec`: rows that wrote past their ring read the window
    from the ring's pages; a dead row's table is the scratch page."""
    spec = pa.WindowRingSpec(2, D, window=20)
    pool = spec.make_pool(999, BS, jnp.float32, max_seqs=3, prefill_chunk=8)
    ring = -(-(20 + 8) // BS) + 1
    assert pool[0].shape == (2, 1 + 3 * ring, BS, D) and ring == 3
    rng = np.random.RandomState(0)
    keys = rng.randn(3, 100, 2, D).astype(np.float32)
    vals = rng.randn(3, 100, 2, D).astype(np.float32)
    lens = np.array([100, 0, 61], np.int32)
    live = jnp.asarray(lens > 0)
    width = jnp.zeros((3, 8), jnp.int32)             # the row's table: 8 wide
    view = spec.paged(pool, width, jnp.zeros(3, jnp.int32), live)
    kp, vp = view.k_pages, view.v_pages
    assert (np.asarray(view.page_indices[1]) == 0).all()
    for t in range(100):                             # a token a step
        at = jnp.asarray(np.minimum(t, lens - 1).clip(0), jnp.int32)
        view = spec.paged((kp, vp), width, at, live & (t < lens))
        kp = pa.write_token_kv(kp, view.page_indices, at, keys[:, t])
        vp = pa.write_token_kv(vp, view.page_indices, at, vals[:, t])
    q = jnp.asarray(rng.randn(3, 8, D).astype(np.float32))
    view = spec.paged((kp, vp), width, jnp.asarray(lens), live)
    for impl in ("math", "pallas"):
        out = pa.paged_decode_attention(
            q, kp, vp, jnp.asarray(lens), view.page_indices, scale=0.3,
            impl=impl, window=20)
        for b, n in enumerate(lens):
            want = np.zeros((8, D), np.float32)
            for h in range(8 if n else 0):
                s = (np.asarray(q)[b, h] @ keys[b, n - 20:n, h // 4].T) * 0.3
                p = np.exp(s - s.max())
                want[h] = (p / p.sum()) @ vals[b, n - 20:n, h // 4]
            np.testing.assert_allclose(np.asarray(out[b]), want, rtol=2e-5,
                                       atol=2e-6)
    with pytest.raises(ValueError, match="prefill_chunk"):
        spec.make_pool(9, BS, jnp.float32, max_seqs=3)
    with pytest.raises(ValueError, match="float pools"):
        spec.make_pool(9, BS, jnp.float32, "int8", 3, 8)


# ---- the flat walk: one grid step a live (row, block) pair -----------------

def _blocks(n, kb, nb, window=None):
    """The blocks of `kb` keys a row of `n` keys folds, in order."""
    last = min(-(-n // kb), nb)
    first = max(n - window, 0) // kb if window is not None else 0
    return list(range(first, last))


WORK = {
    # name: (lengths, page size, pages a block, table width, window)
    "page-and-block-edges": ([1, 16, 17, 63, 64, 65, 96, 0], 16, 4, 6, None),
    "dead-rows-between": ([0, 70, 0, 0, 33, 0, 96, 0], 16, 2, 6, None),
    "one-live-row": ([0, 0, 45, 0], 16, 1, 6, None),
    "no-live-row": ([0, 0, 0], 16, 4, 6, None),
    "past-the-table": ([200, 5], 16, 4, 6, None),
    "window": ([7, 21, 0, 96, 50, 64, 65], 16, 2, 6, 20),
    "window-wider-than-a-row": ([7, 40, 96], 16, 1, 6, 64),
    "by-head": (np.array([[70, 33], [0, 0], [16, 96]]).reshape(-1), 16, 4, 6,
                None),
}


@pytest.mark.parametrize("case", sorted(WORK))
def test_the_work_list_holds_each_rows_own_blocks(case):
    """`paged_work` from numpy and from `jnp` agree; the list holds the sum
    of the rows' blocks, rows in order and a row's blocks in order, the first
    / last flags on a row's ends, the row's last held page beside each pair,
    and zeros after them. `paged_walk` counts the same steps
    beside the live rows x longest walk."""
    lens, bs, ppb, npages, window = WORK[case]
    lens = np.asarray(lens, np.int32)
    nb = -(-npages // ppb)
    work, n = pa.paged_work(lens, bs, ppb, npages, window, xp=np)
    jwork, jn = pa.paged_work(jnp.asarray(lens), bs, ppb, npages, window)
    assert all(a.dtype == jnp.int32 for a in jwork) and int(jn) == n
    work = np.stack(work)
    np.testing.assert_array_equal(work, np.stack(jwork))
    assert work.shape == (4, len(lens) * nb) and work.dtype == np.int32
    want = [(r, j, (j == blks[0]) + 2 * (j == blks[-1]),
             max(-(-int(ln) // bs) - 1, 0))
            for r, ln in enumerate(lens)
            for blks in [_blocks(int(ln), ppb * bs, nb, window)] for j in blks]
    assert n == len(want)
    assert [tuple(col) for col in work[:, :n].T] == want
    assert not work[:, n:].any()
    counts = [len(_blocks(int(ln), ppb * bs, nb, window)) for ln in lens]
    assert (n, sum(c > 0 for c in counts) * max(counts)) == _walk(
        lens, bs, ppb, npages, window)


def _walk(lens, bs, ppb, npages, window):
    """`paged_walk` with the block rule pinned to `ppb` pages."""
    first, count = pa._row_blocks(lens, bs, ppb, npages, window, np)
    return int(count.sum()), int((count > 0).sum()) * int(count.max())


def _ragged_case(hq, hkv, rows, npages, lens, seed=0, ring=None):
    """q, pools and a table for `lens`; with `ring` the table is a ring of
    that many pages a row (`WindowRingSpec.table`), else the rows' own pages
    with the scratch page past each row's length."""
    rng = np.random.RandomState(seed)
    P = 1 + rows * (ring or npages)
    kp = rng.randn(hkv, P, BS, D).astype(np.float32)
    vp = rng.randn(hkv, P, BS, D).astype(np.float32)
    kp[:, 0], vp[:, 0] = 1e3, 1e3
    if ring:
        table = np.asarray(pa.WindowRingSpec.table(
            (jnp.zeros((hkv, P, BS, D)),), jnp.zeros((rows, npages))))
        table = np.where((np.asarray(lens) > 0)[:, None], table, 0)
    else:
        table = np.arange(1, P, dtype=np.int32).reshape(rows, npages)
        held = np.arange(npages)[None] * BS < np.asarray(lens)[:, None]
        table = np.where(held, table, 0)
    q = rng.randn(rows, hq, D).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table, jnp.int32), jnp.asarray(lens, jnp.int32))


RAGGED = {
    # name: (query heads, KV heads, table width, pages a block, window, ring)
    "gqa-40-10": (40, 10, 12, 2, None, None),
    "mha-32-32": (32, 32, 12, 4, None, None),
    "window-512-ring": (8, 2, 96, 8, 512, 35),
}


@pytest.mark.parametrize("case", sorted(RAGGED))
def test_flat_walk_matches_math_on_ragged_lengths(case):
    """Rows of 1 key to the table's width, dead rows between them: every live
    row is its own softmax over its own keys whatever the others' lengths,
    and a dead row is exactly zero."""
    hq, hkv, npages, ppb, window, ring = RAGGED[case]
    full = npages * BS
    lens = [1, 0, full, BS * ppb + 1, 0, full // 3, BS * ppb, full - 1]
    q, kp, vp, table, lens = _ragged_case(hq, hkv, len(lens), npages, lens,
                                          ring=ring)
    out = pa._paged_pallas(q, kp, vp, lens, table, 0.2, interpret=True,
                           ppb=ppb, window=window)
    ref = pa._paged_math(q, kp, vp, lens, table, 0.2, window)
    dead = np.asarray(lens) == 0
    assert not np.asarray(out)[dead].any()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL["f32"])
    walked, dense = _walk(np.asarray(lens), BS, ppb, npages, window)
    assert walked < dense


def test_flat_walk_by_head_matches_math():
    """A table and a length a (row, K/V head): each pair walks its own
    blocks, a head with no key is zero beside a head of the same row that
    has some."""
    hq, hkv, npages, rows = 8, 2, 6, 3
    lens = np.array([[70, 33], [0, 0], [0, FULL]], np.int32)
    rng = np.random.RandomState(5)
    P = 1 + rows * hkv * npages
    kp = jnp.asarray(rng.randn(hkv, P, BS, D), jnp.float32)
    vp = jnp.asarray(rng.randn(hkv, P, BS, D), jnp.float32)
    table = jnp.asarray(rng.permutation(P - 1)[:rows * hkv * npages].reshape(
        rows, hkv, npages) + 1, jnp.int32)
    q = jnp.asarray(rng.randn(rows, hq, D), jnp.float32)
    out = np.asarray(pa._paged_pallas(q, kp, vp, jnp.asarray(lens), table,
                                      0.25, interpret=True, ppb=2))
    g = hq // hkv
    for h in range(hkv):  # head h alone, under its own table
        ref = pa._paged_math(q[:, h * g:(h + 1) * g], kp[h:h + 1],
                             vp[h:h + 1], jnp.asarray(lens[:, h]),
                             table[:, h], 0.25)
        np.testing.assert_allclose(out[:, h * g:(h + 1) * g],
                                   np.asarray(ref), **TOL["f32"])
    assert not out[1].any() and not out[2, :g].any()


@pytest.mark.parametrize("window", [None, 40], ids=["causal", "window-40"])
def test_flat_walk_is_the_rectangular_walk_bit_for_bit(window):
    """The walk this kernel had (every live row through the blocks of the
    longest, a step past a row's length folding nothing), rebuilt here by
    padding the list: the same bits, in fewer steps."""
    npages, ppb = 12, 2
    lens = [5, 0, npages * BS, 70, 0, 33, 150]
    q, kp, vp, table, lens = _ragged_case(8, 2, len(lens), npages, lens,
                                          seed=1)
    ln = np.asarray(lens)
    first, count = pa._row_blocks(ln, BS, ppb, npages, window, np)
    n_blocks = int(count.max())
    rect = [(r, first[r] + j, (j == 0) + 2 * (j == n_blocks - 1),
             -(-ln[r] // BS) - 1)
            for r in np.flatnonzero(ln) for j in range(n_blocks)]
    work, n_flat = pa.paged_work(ln, BS, ppb, npages, window, xp=np)
    assert n_flat == count.sum() < len(rect) <= len(work[0])
    padded = np.array(rect + [(0,) * 4] * (len(work[0]) - len(rect)),
                      np.int32).T
    flat = pa._paged_pallas(q, kp, vp, lens, table, 0.3, interpret=True,
                            ppb=ppb, window=window)
    old = pa._paged_pallas(q, kp, vp, lens, table, 0.3, interpret=True,
                           ppb=ppb, window=window,
                           work=(tuple(jnp.asarray(padded)),
                                 jnp.int32(len(rect))))
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(old))
    assert np.asarray(flat)[ln > 0].any()
