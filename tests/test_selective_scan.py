"""Mamba-1's convolution and selective scan over state slots
(ops/selective_scan.py) against the literal recurrence, a token at a time in
numpy: the packed stream's spans (continuing a slot, starting fresh, a span
that starts inside the convolution's tail, one-token spans, rows of no
token), the decode step, dead rows, and the slot spec."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops import selective_scan as ssm

DI, N, K = 24, 4, 4
RNG = np.random.RandomState(0)
W = RNG.uniform(-0.5, 0.5, (K, DI)).astype(np.float32)
B0 = RNG.uniform(-0.5, 0.5, (DI,)).astype(np.float32)
A_T = -np.exp(RNG.randn(N, DI).astype(np.float32) * 0.3)
D = RNG.randn(DI).astype(np.float32)


def silu(x):
    return x / (1.0 + np.exp(-x))


def recurrence(u, proj, h, tail):
    """The literal form on one row: u [S, DI] from state h [N, DI] and the
    convolution's last inputs tail [K-1, DI]; `proj(c)` -> (dt, B, C) of a
    token. Returns (y, h, tail)."""
    ys = []
    hist = np.concatenate([tail, u])
    for t in range(len(u)):
        c = silu(B0 + sum(W[k] * hist[t + k] for k in range(K)))
        dt, bm, cm = proj(c)
        h = np.exp(dt[None, :] * A_T) * h + (dt * c)[None, :] * bm[:, None]
        ys.append((h * cm[:, None]).sum(0) + D * c)
    return np.stack(ys), h, hist[len(hist) - (K - 1):]


PROJ = RNG.randn(DI, DI + 2 * N).astype(np.float32) * 0.3


def proj(c):
    z = c @ PROJ
    return np.log1p(np.exp(z[..., :DI])) * 0.1, z[..., DI:DI + N], z[..., DI + N:]


def packed(q_lens, before, seed=1):
    rng = np.random.RandomState(seed)
    S = len(q_lens)
    cu = np.zeros(S + 1, np.int32)
    cu[1:] = np.cumsum(q_lens)
    T = int(cu[-1]) + 3                                  # three pad tokens
    u = rng.randn(T, DI).astype(np.float32)
    h = rng.randn(S, N, DI).astype(np.float32)
    tail = rng.randn(S, K - 1, DI).astype(np.float32)
    row_of = np.zeros(T, np.int32)
    for r in range(S):
        row_of[cu[r]:cu[r + 1]] = r
    kv_lens = np.asarray(before, np.int32) + np.asarray(q_lens, np.int32)
    return u, h, tail, cu, row_of, kv_lens


@pytest.mark.parametrize("q_lens, before", [
    ([1, 37, 0, 5, 1], [9, 4, 6, 0, 0]),    # decode row, chunk, none, fresh
    ([2, 3, 40], [1, 2, 0]),                # spans that start mid-tail
    ([17, 16, 1], [0, 33, 2]),              # a chunk boundary of the scan
])
def test_the_packed_stream_matches_the_recurrence(q_lens, before):
    u, h, tail, cu, row_of, kv_lens = packed(q_lens, before)
    c, tail1 = ssm.causal_conv_ragged(
        jnp.asarray(u), jnp.asarray(W), jnp.asarray(B0), jnp.asarray(tail),
        jnp.asarray(kv_lens), jnp.asarray(cu), jnp.asarray(row_of))
    dt, bm, cm = proj(np.asarray(c))
    y, h1, rows = ssm.selective_scan_ragged(
        c, jnp.asarray(dt), jnp.asarray(bm), jnp.asarray(cm),
        jnp.asarray(A_T), jnp.asarray(D), jnp.asarray(h),
        jnp.asarray(kv_lens), jnp.asarray(cu))
    assert ssm.LAST_IMPL == "ssm-xla"
    assert int(rows) == sum(n > 0 for n in q_lens)
    for r, n in enumerate(q_lens):
        if n == 0:       # a row of no token keeps its slot
            np.testing.assert_array_equal(np.asarray(h1[r]), h[r])
            np.testing.assert_array_equal(np.asarray(tail1[r]), tail[r])
            continue
        fresh = before[r] == 0   # a reused slot reads as zeros
        want_y, want_h, want_tail = recurrence(
            u[cu[r]:cu[r + 1]], proj,
            np.zeros_like(h[r]) if fresh else h[r],
            np.zeros_like(tail[r]) if fresh else tail[r])
        np.testing.assert_allclose(np.asarray(y[cu[r]:cu[r + 1]]), want_y,
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(h1[r]), want_h, rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(tail1[r]), want_tail, atol=0)


def test_decode_zeroes_a_fresh_slot_and_leaves_a_dead_row():
    u, h, tail, *_ = packed([1, 1, 1], [4, 0, 7], seed=3)
    u = u[:3]
    lengths = jnp.asarray([4, 0, 7], jnp.int32)   # row 1: a reused slot
    live = jnp.asarray([True, True, False])
    c, tail1 = ssm.causal_conv_decode(
        jnp.asarray(u), jnp.asarray(W), jnp.asarray(B0), jnp.asarray(tail),
        lengths, live)
    dt, bm, cm = proj(np.asarray(c))
    y, h1 = ssm.selective_scan_decode(
        c, jnp.asarray(dt), jnp.asarray(bm), jnp.asarray(cm),
        jnp.asarray(A_T), jnp.asarray(D), jnp.asarray(h), lengths, live)
    for r, fresh in ((0, False), (1, True)):
        want_y, want_h, want_tail = recurrence(
            u[r:r + 1], proj, np.zeros_like(h[r]) if fresh else h[r],
            np.zeros_like(tail[r]) if fresh else tail[r])
        np.testing.assert_allclose(np.asarray(y[r]), want_y[0], rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(h1[r]), want_h, rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(tail1[r]), want_tail, atol=0)
    np.testing.assert_array_equal(np.asarray(h1[2]), h[2])
    np.testing.assert_array_equal(np.asarray(tail1[2]), tail[2])
    assert not np.asarray(y[2]).any()


def test_the_state_keeps_its_slots_dtype():
    """The lower-precision control: a bfloat16 slot rounds the state after
    every token, in both forms."""
    u, h, tail, cu, row_of, kv_lens = packed([6, 1], [3, 5])
    c = jnp.asarray(u)
    dt, bm, cm = (jnp.asarray(a) for a in proj(u))
    h16 = jnp.asarray(h, jnp.bfloat16)
    y, h1, _ = ssm.selective_scan_ragged(
        c, dt, bm, cm, jnp.asarray(A_T), jnp.asarray(D), h16,
        jnp.asarray(kv_lens), jnp.asarray(cu))
    assert h1.dtype == jnp.bfloat16 and y.dtype == jnp.float32


def test_the_slot_is_a_tuple_of_arrays_with_no_pages():
    spec = ssm.ssm_slot_spec(DI, N, K)
    pool = spec.make_pool(9, 16, jnp.bfloat16, max_seqs=3, prefill_chunk=8)
    assert [(a.shape, a.dtype) for a in pool] == [
        ((3, N, DI), jnp.float32), ((3, K - 1, DI), jnp.bfloat16)]
    view = spec.paged(pool, None, jnp.zeros(3, jnp.int32),
                      jnp.ones(3, bool))
    assert isinstance(view.state, tuple) and len(view.state) == 2
    assert spec.pool_of(view) == tuple(pool)
    assert "StateSlotSpec" in spec.refuses("prefix_cache")
    assert "StateSlotSpec" in spec.refuses("handoff")
    assert spec.refuses("lora") is None and spec.has_state
    with pytest.raises(ValueError, match="max_seqs"):
        spec.make_pool(9, 16, jnp.float32)
