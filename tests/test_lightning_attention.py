"""Lightning (decayed linear) attention over state slots
(ops/lightning_attention.py) on the CPU: the chunked scan of a packed stream
equals the token recurrence and the quadratic form, for chunk sizes that do
and do not divide a span and for several rows' spans in one stream; a fresh
slot reads as zeros, a dead row's slot is left as it was."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import lightning_attention as la

H, D = 4, 8
SLOPES = la.decay_slopes(H)


def recurrence(q, k, v, s0):
    """S_t = lambda S_{t-1} + k_t^T v_t; o_t = q_t S_t / sqrt(D)."""
    lam = np.exp(-np.asarray(SLOPES))[:, None, None]
    s, out = s0.copy(), []
    for t in range(q.shape[0]):
        s = lam * s + np.einsum("hd,he->hde", k[t], v[t])
        out.append(np.einsum("hd,hde->he", q[t] / np.sqrt(D), s))
    return np.stack(out), s


def quadratic(q, k, v):
    """o_t = sum_{s<=t} lambda^(t-s) (q_t . k_s / sqrt(D)) v_s, no state."""
    n = q.shape[0]
    gap = np.arange(n)[:, None] - np.arange(n)[None, :]
    w = np.where(gap >= 0, np.exp(-np.asarray(SLOPES)[:, None, None]
                                  * np.maximum(gap, 0)), 0.0)
    s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(D)
    return np.einsum("hqk,khd->qhd", s * w, v)


def stream(seed, q_lens):
    rng = np.random.RandomState(seed)
    T = int(sum(q_lens)) + 5                     # five pad tokens trail
    mk = lambda *s: rng.randn(*s).astype(np.float32)
    return (mk(T, H, D), mk(T, H, D), mk(T, H, D),
            mk(len(q_lens), H, D, D),
            np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32))


def test_decay_slopes_are_the_geometric_sequence():
    np.testing.assert_allclose(
        np.asarray(la.decay_slopes(8)),
        [2.0 ** -(h + 1) for h in range(8)], rtol=1e-6)


@pytest.mark.parametrize("chunk", [16, 7, 64, 37])
def test_packed_chunked_scan_equals_recurrence_and_quadratic(chunk):
    """Rows: 37 tokens continuing a state, one token (the decode form), no
    token at all, 20 tokens on a fresh slot. 37 is divided by none of
    16 / 7 / 64 and is one whole chunk of 37."""
    q_lens = np.array([37, 1, 0, 20])
    before = np.array([5, 9, 3, 0])
    q, k, v, st, cu = stream(0, q_lens)
    cache = la.StateSlotRaggedCache(
        jnp.asarray(st), jnp.asarray(before + q_lens, jnp.int32),
        jnp.asarray(cu), None, None, None)
    o, new, rows = jax.jit(lambda q, k, v, c: la.lightning_ragged(
        q, k, v, c, SLOPES, chunk=chunk))(q, k, v, cache)
    assert int(rows) == 3 and la.LAST_IMPL == "lightning-xla"
    for r in range(4):
        a, b = cu[r], cu[r + 1]
        if a == b:
            np.testing.assert_array_equal(np.asarray(new[r]), st[r])
            continue
        s0 = st[r] if before[r] else np.zeros_like(st[r])
        want_o, want_s = recurrence(q[a:b], k[a:b], v[a:b], s0)
        np.testing.assert_allclose(np.asarray(o[a:b]), want_o, atol=2e-4)
        np.testing.assert_allclose(np.asarray(new[r]), want_s, atol=2e-4)
        if not before[r]:
            np.testing.assert_allclose(np.asarray(o[a:b]),
                                       quadratic(q[a:b], k[a:b], v[a:b]),
                                       atol=2e-4)
    assert not np.asarray(o[cu[-1]:]).any()      # pads: zeros


def test_a_prompt_in_chunks_carries_its_state_across_dispatches():
    """Three dispatches of one row (13 + 13 + 4 tokens) = one span of 30."""
    q, k, v, st, _ = stream(1, np.array([30]))
    state, outs, pos = jnp.zeros_like(jnp.asarray(st)), [], 0
    for take in (13, 13, 4):
        cache = la.StateSlotRaggedCache(
            state, jnp.asarray([pos + take], jnp.int32),
            jnp.asarray([0, take], jnp.int32), None, None, None)
        o, state, _ = la.lightning_ragged(
            q[pos:pos + take], k[pos:pos + take], v[pos:pos + take], cache,
            SLOPES, chunk=8)
        outs.append(np.asarray(o))
        pos += take
    want_o, want_s = recurrence(q[:30], k[:30], v[:30], np.zeros_like(st[0]))
    np.testing.assert_allclose(np.concatenate(outs), want_o, atol=2e-4)
    np.testing.assert_allclose(np.asarray(state[0]), want_s, atol=2e-4)


def test_decode_zeroes_a_fresh_slot_and_leaves_a_dead_row():
    rng = np.random.RandomState(2)
    mk = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    q, k, v, st = mk(3, H, D), mk(3, H, D), mk(3, H, D), mk(3, H, D, D)
    lengths = jnp.asarray([4, 0, 7], jnp.int32)   # row 1: a reused slot
    live = jnp.asarray([True, True, False])
    o, new = la.lightning_decode(q, k, v, st, lengths, live, SLOPES)
    want0, s0 = recurrence(*(np.asarray(a[:1]) for a in (q, k, v)),
                           np.asarray(st[0]))
    want1, s1 = recurrence(*(np.asarray(a[1:2]) for a in (q, k, v)),
                           np.zeros((H, D, D), np.float32))
    np.testing.assert_allclose(np.asarray(o[0]), want0[0], atol=1e-5)
    np.testing.assert_allclose(np.asarray(o[1]), want1[0], atol=1e-5)
    np.testing.assert_allclose(np.asarray(new[0]), s0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new[1]), s1, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(st[2]))
    assert not np.asarray(o[2]).any()


LIVE_SETS = {"none": [], "one": [5], "three_scattered": [1, 4, 6],
             "all": list(range(8))}


@pytest.mark.parametrize("form", ["walk", "every_slot"])
@pytest.mark.parametrize("live_set", sorted(LIVE_SETS))
def test_decode_walks_live_rows_and_never_touches_a_dead_slot(
        live_set, form, monkeypatch):
    """Eight slots, some live (row 4, when live, at length 0 on a slot that
    held another request's state): a live row equals the token recurrence;
    a dead row's slot is bit for bit what went in, its output zero, and its
    q, k and v (NaN here) reach nothing. Both forms of the step (the walk
    over the live rows, every slot at once past `_WALK_UP_TO` of them)."""
    monkeypatch.setattr(la, "_WALK_UP_TO", 8 if form == "walk" else -1)
    rows = LIVE_SETS[live_set]
    rng = np.random.RandomState(7)
    mk = lambda *s: rng.randn(*s).astype(np.float32)
    q, k, v, st = mk(8, H, D), mk(8, H, D), mk(8, H, D), mk(8, H, D, D)
    live = np.zeros(8, bool)
    live[rows] = True
    for a in (q, k, v):
        a[~live] = np.nan
    lengths = np.array([3, 9, 0, 1, 0, 12, 40, 2], np.int32)
    o, new = jax.jit(la.lightning_decode)(
        *(jnp.asarray(a) for a in (q, k, v, st, lengths, live)), SLOPES)
    o, new = np.asarray(o), np.asarray(new)
    for r in range(8):
        if not live[r]:
            np.testing.assert_array_equal(new[r], st[r])
            assert not o[r].any()
            continue
        s0 = st[r] if lengths[r] else np.zeros((H, D, D), np.float32)
        want, s1 = recurrence(q[r:r + 1], k[r:r + 1], v[r:r + 1], s0)
        np.testing.assert_allclose(o[r], want[0], atol=1e-5)
        np.testing.assert_allclose(new[r], s1, atol=1e-5)


def test_state_slots_have_no_pages_to_share_or_move():
    spec = la.StateSlotSpec(H, D, D)
    (state,) = spec.make_pool(9, 16, jnp.bfloat16, max_seqs=3)
    assert state.shape == (3, H, D, D) and state.dtype == jnp.float32
    assert "StateSlotSpec" in spec.refuses("prefix_cache")
    assert "StateSlotSpec" in spec.refuses("handoff")
    assert spec.refuses("lora") is None
    with pytest.raises(ValueError, match="max_seqs"):
        spec.make_pool(9, 16, jnp.float32)
