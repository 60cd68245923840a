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
