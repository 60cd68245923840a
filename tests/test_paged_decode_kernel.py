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
