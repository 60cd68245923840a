"""Block-selected attention over K/V pages (ops/sparse_paged_attention.py)
on the CPU, against the benchmark's plain reference
(benchmarks/sala_reference.py): the selector keeps the reference's blocks
(the `dense_len` switch, the forced blocks, fewer than `topk` visible), the
writers fill the compressed-key plane across chunk boundaries, decode reads a
table a K/V head through the paged kernel's own body, and the packed prefill
attends exactly the kept set."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import sala_reference as ref
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops import sparse_decode_attention as sda
from paddle_tpu.ops import sparse_paged_attention as spa

SC = dict(kernel_size=8, kernel_stride=4, block_size=16, topk=4,
          init_blocks=1, window_size=32, dense_len=64)
SP = spa.SparseConfig(**SC)
H, HKV, D, BS = 4, 2, 16, 16


def sequence(seed, n):
    rng = np.random.RandomState(seed)
    mk = lambda *s: rng.randn(*s).astype(np.float32)
    return mk(n, H, D), mk(n, HKV, D), mk(n, HKV, D)


def paged(k, v, rows=1, row=0, extra_pages=0):
    """The sequence's K and V written through the ragged writer into row
    `row` of a fresh pool, in two chunks that split a compressed key."""
    n = k.shape[0]
    npages = -(-n // BS) + extra_pages
    pool = spa.SelectedKVSpec(HKV, D, SP).make_pool(
        1 + rows * npages, BS, jnp.float32)
    table = np.zeros((rows, npages), np.int32)
    table[row] = 1 + row * npages + np.arange(npages)
    table = jnp.asarray(table)
    cut = min(n, 2 * BS + 5)
    for lo, hi in ((0, cut), (cut, n)):
        if hi == lo:
            continue
        take = hi - lo
        kv_lens = np.zeros(rows, np.int32)
        kv_lens[row] = hi
        cu = np.zeros(rows + 1, np.int32)
        cu[row + 1:] = take
        pc = spa.SelectedRaggedLayerCache(
            *pool, table, jnp.asarray(kv_lens), jnp.asarray(cu),
            jnp.full((take + 3,), row, jnp.int32),
            jnp.asarray(np.r_[lo + np.arange(take), 0, 0, 0], jnp.int32),
            jnp.asarray(np.r_[np.ones(take, bool), [False] * 3]))
        pad = lambda a: jnp.asarray(np.r_[
            a[lo:hi], np.zeros((3,) + a.shape[1:], np.float32)])
        pool = spa.write_ragged_selected(pc, pad(k), pad(v), SP)
    return pool, table


def test_sparse_config_refuses_shapes_the_plane_cannot_hold():
    assert SP.per_page == 4 and SP.window_blocks == 2
    assert SP.table_width(100) == 4
    assert spa.SparseConfig().table_width(784) == 128
    with pytest.raises(ValueError, match="multiples of kernel_stride"):
        spa.SparseConfig(kernel_size=6, kernel_stride=4, block_size=16)
    with pytest.raises(ValueError, match="forced blocks exceed topk"):
        spa.SparseConfig(**{**SC, "topk": 2})
    with pytest.raises(ValueError, match="one selection block"):
        spa.SelectedKVSpec(HKV, D, SP).make_pool(4, 32, jnp.float32)
    with pytest.raises(ValueError, match="compressed-key plane"):
        spa.SelectedKVSpec(HKV, D, SP).make_pool(4, 16, jnp.float32, "int8")


def test_writers_fill_the_compressed_plane_across_chunks_and_tokens():
    """Chunked ragged writes then one-token writes: every whole kernel's
    mean, where the reference computes it from the sequence."""
    n = 150
    _, k, v = sequence(0, n)
    (kp, vp, cp), table = paged(k[:120], v[:120], rows=2, row=1,
                                extra_pages=2)
    for pos in range(120, n):                     # decode steps, row 1 of 2
        pc = spa.SelectedPagedLayerCache(
            kp, vp, cp, table, jnp.asarray([0, pos], jnp.int32),
            jnp.asarray([False, True]))
        new = lambda a: jnp.asarray(np.stack([np.zeros_like(a[pos]), a[pos]]))
        kp, vp, cp = spa.write_token_selected(pc, new(k), new(v), SP)
    want = np.asarray(ref.compressed_keys(jnp.asarray(k), SC))     # [J,Hkv,D]
    got = np.asarray(spa._row_compressed(cp, table[1], SP))        # [Hkv,*,D]
    np.testing.assert_allclose(got[:, :want.shape[0]],
                               np.swapaxes(want, 0, 1), atol=1e-6)
    assert not got[:, want.shape[0]:].any()       # incomplete kernels: unset


@pytest.mark.parametrize("n, what", [
    (40, "under dense_len: every visible block, fewer than topk"),
    (64, "the last dense query"),
    (65, "the first selecting query"),
    (200, "13 blocks visible, 4 kept: first, window of 2, one by score"),
])
def test_selector_keeps_the_references_blocks(n, what):
    q, k, _ = sequence(n, n)
    c = ref.compressed_keys(jnp.asarray(k), SC)
    t = np.arange(n)
    nb = -(-n // BS)
    want = np.asarray(ref.select(SC, jnp.asarray(q), c, t, nb))
    (_, _, cp), table = paged(k, np.zeros_like(k))
    got = np.asarray(spa.select_blocks(
        jnp.asarray(q), cp, table[0], jnp.asarray(t), SP, 1 / math.sqrt(D)))
    np.testing.assert_array_equal(got, want)
    last = got[-1]                                # the query at n - 1
    own = (n - 1) // BS
    if n <= SC["dense_len"]:
        assert last[:, :own + 1].all()
    else:
        assert (last.sum(-1) == SC["topk"]).all()
        assert last[:, 0].all() and last[:, own - 1:own + 1].all()
    assert not got[0, :, 1:].any()                # causal: query 0, block 0


def rows_in_one_pool(lens, seed):
    """(pool, page tables [rows, npages], the rows' (q, k, v)): a sequence
    of `lens[r]` tokens a row (none for 0), each written by `paged` and
    laid into one pool at the row's own pages."""
    rows = len(lens)
    seqs = [sequence(seed + i, max(n, 1)) for i, n in enumerate(lens)]
    npages = -(-max(lens) // BS)
    pool = spa.SelectedKVSpec(HKV, D, SP).make_pool(1 + rows * npages, BS,
                                                    jnp.float32)
    tables = np.zeros((rows, npages), np.int32)
    for r, (n, (_, k, v)) in enumerate(zip(lens, seqs)):
        if n:
            (kp, vp, cp), tb = paged(k, v, rows=rows, row=r,
                                     extra_pages=npages - -(-n // BS))
            at = np.asarray(tb[r])
            tables[r] = at
            pool = tuple(a.at[:, i].set(b[:, i]) for a, b, i in (
                (pool[0], kp, at), (pool[1], vp, at),
                (pool[2], cp, (at[:, None] * 4 + np.arange(4)).reshape(-1))))
    return pool, tables, seqs


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_decode_reads_a_table_a_head_and_equals_the_reference(impl):
    """Three rows (200 tokens selecting, 50 dense, dead) against the
    reference's masked attention; `pallas` runs the paged kernel's own body
    (interpret mode) with a page table a row and K/V head."""
    lens = [200, 50, 0]
    pool, tables, seqs = rows_in_one_pool(lens, 10)
    q = jnp.asarray(np.stack([s[0][-1] for s in seqs]))
    o, kept, seen = sda.sparse_decode_attention(
        q, *pool, jnp.asarray(tables), jnp.asarray(lens, jnp.int32), SP,
        impl=impl)
    assert sda.LAST_IMPL == (
        "sparse-decode-xla" if impl == "xla"
        else "sparse-decode-kernel-interpret")
    total = 0
    for r, (n, (qs, k, v)) in enumerate(zip(lens, seqs)):
        if not n:
            assert not np.asarray(o[r]).any()
            continue
        t = np.array([n - 1])
        mask = np.asarray(ref.select(
            SC, jnp.asarray(qs[-1:]), ref.compressed_keys(jnp.asarray(k), SC),
            t, -(-n // BS)))[0]                               # [Hkv, nb]
        keys = np.repeat(mask, BS, axis=-1)[:, :n]
        total += int(keys.sum())
        s = np.einsum("hgd,khd->hgk", qs[-1].reshape(HKV, H // HKV, D),
                      k) / math.sqrt(D)
        s = np.where(keys[:, None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("hgk,khd->hgd", p / p.sum(-1, keepdims=True), v)
        np.testing.assert_allclose(np.asarray(o[r]), want.reshape(H, D),
                                   atol=2e-5)
    assert int(kept) == total and int(seen) == sum(lens) * HKV


def all_rows_tables(q, c_keys, page_indices, lengths, sp, scale, bs):
    """The step's selector as it stood before it walked its live rows
    (PR 33's form, kept here as the reference): every row's compressed keys
    gathered, scored and sorted at once, dead rows masked afterwards.
    Returns (table [B, Hkv, width], vlen [B, Hkv])."""
    B, Hq, D = q.shape
    Hkv = c_keys.shape[0]
    npages, per = page_indices.shape[1], sp.per_page
    t = jnp.maximum(lengths - 1, 0)
    at = (page_indices[:, :, None] * per
          + jnp.arange(per)[None, None]).reshape(B, -1)
    ck = c_keys[jnp.arange(Hkv)[None, :, None], at[:, None]]
    logits = jnp.einsum("bhgd,bhjd->bhgj", q.reshape(B, Hkv, Hq // Hkv, D),
                        ck, preferred_element_type=jnp.float32) * scale
    mask = spa.block_mask(logits, t, sp, npages)
    mask = mask & (lengths > 0)[:, None, None]
    m = jnp.arange(npages)
    order = jnp.argsort(jnp.where(mask, m, m + npages), axis=-1)
    order = order[..., :sp.table_width(npages)]
    n_kept = mask.sum(axis=-1).astype(jnp.int32)
    table = jnp.take_along_axis(
        jnp.broadcast_to(page_indices[:, None], mask.shape), order, axis=-1)
    table = jnp.where(jnp.arange(order.shape[-1]) < n_kept[..., None],
                      table, 0)
    vlen = jnp.where(n_kept > 0,
                     (n_kept - 1) * bs + (t % bs + 1)[:, None], 0)
    return table, vlen


#: eight rows: selecting (past dense_len 64), dense (under it), one at the
#: edge on either side, one of a single token
ROW_LENS = [200, 1, 50, 130, 64, 65, 97, 16]
LIVE_SETS = {"none": [], "one": [3], "three_scattered": [0, 4, 6],
             "all": list(range(8)), "one_token_and_dense": [1, 2, 7]}


@pytest.fixture(scope="module")
def eight_rows():
    """(pool, page tables, the rows' last queries) of ROW_LENS, each row's
    K, V and compressed keys written by the ragged writer."""
    pool, tables, seqs = rows_in_one_pool(ROW_LENS, 40)
    return pool, jnp.asarray(tables), np.stack([q[-1] for q, _, _ in seqs])


@pytest.mark.parametrize("live_set", sorted(LIVE_SETS))
def test_decode_selector_walks_live_rows_and_keeps_the_same_tables(
        eight_rows, live_set, monkeypatch):
    """The step's selector over the live rows alone against the all-rows
    form: the kept tables and `vlen` identical, the outputs equal, dead rows
    zero (their queries NaN: never scored), the two counters equal."""
    pool, tables, qs = eight_rows
    live = np.zeros(len(ROW_LENS), bool)
    live[LIVE_SETS[live_set]] = True
    lengths = jnp.asarray(np.where(live, ROW_LENS, 0), jnp.int32)
    scale = 1.0 / math.sqrt(D)
    want_table, want_vlen = all_rows_tables(
        jnp.asarray(np.where(live[:, None, None], qs, 0.0)), pool[2], tables,
        lengths, SP, scale, BS)
    seen_by_kernel = {}

    def spy(q, k_pages, v_pages, table, vlen, scale):
        seen_by_kernel.update(table=np.asarray(table), vlen=np.asarray(vlen))
        return decode_xla(q, k_pages, v_pages, table, vlen, scale)

    decode_xla = sda._decode_xla
    monkeypatch.setattr(sda, "_decode_xla", spy)
    q = jnp.asarray(np.where(live[:, None, None], qs, np.nan))
    o, kept, seen = sda.sparse_decode_attention(q, *pool, tables, lengths,
                                                SP, impl="xla")
    np.testing.assert_array_equal(seen_by_kernel["table"],
                                  np.asarray(want_table))
    np.testing.assert_array_equal(seen_by_kernel["vlen"],
                                  np.asarray(want_vlen))
    want_o = decode_xla(jnp.asarray(qs), pool[0], pool[1], want_table,
                        want_vlen, scale)
    np.testing.assert_array_equal(np.asarray(o)[live],
                                  np.asarray(want_o)[live])
    assert not np.asarray(o)[~live].any()
    assert int(kept) == int(np.asarray(want_vlen).sum())
    assert int(seen) == int(lengths.sum()) * HKV


def test_packed_prefill_attends_exactly_the_kept_set():
    """One row's second chunk (tokens 70..199 of 200, after 70 in the pool)
    beside a one-token row: the reference's sparse layer on the whole
    sequence gives the same heads (identity projections)."""
    n, past = 200, 70
    q, k, v = sequence(20, n)
    (kp, vp, cp), table = paged(k, v, rows=2, row=0)
    take = n - past
    T = take + 1 + 4
    qq = np.zeros((T, H, D), np.float32)
    qq[:take] = q[past:]
    qq[take] = q[49]                              # row 1: one token at 49
    # row 1 reads row 0's pages as its own 50-token past (same keys)
    table = jnp.asarray(np.stack([np.asarray(table[0])] * 2))
    pc = spa.SelectedRaggedLayerCache(
        kp, vp, cp, table, jnp.asarray([n, 50], jnp.int32),
        jnp.asarray([0, take, take + 1], jnp.int32), None, None, None)
    o, kept, seen = jax.jit(lambda q, pc: spa.sparse_ragged_attention(
        q, pc, SP, impl="xla"))(jnp.asarray(qq), pc)
    assert spa.LAST_IMPL == "sparse-prefill-xla"
    masks, total = [], 0
    c = ref.compressed_keys(jnp.asarray(k), SC)
    want = np.zeros((n, H, D), np.float32)
    for t in range(n):
        mask = np.asarray(ref.select(SC, jnp.asarray(q[t:t + 1]), c,
                                     np.array([t]), -(-n // BS)))[0]
        keys = np.repeat(mask, BS, axis=-1)[:, :t + 1]
        if t >= past or t == 49:
            total += int(keys.sum())
        s = np.einsum("hgd,khd->hgk", q[t].reshape(HKV, H // HKV, D),
                      k[:t + 1]) / math.sqrt(D)
        s = np.where(keys[:, None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        want[t] = np.einsum("hgk,khd->hgd", p / p.sum(-1, keepdims=True),
                            v[:t + 1]).reshape(H, D)
    np.testing.assert_allclose(np.asarray(o[:take]), want[past:], atol=2e-5)
    np.testing.assert_allclose(np.asarray(o[take]), want[49], atol=2e-5)
    assert not np.asarray(o[take + 1:]).any()
    assert int(kept) == total
    assert int(seen) == HKV * (sum(range(past + 1, n + 1)) + 50)


def test_paged_kernel_by_head_equals_the_shared_table():
    """The decode kernel with every K/V head handed the SAME table and
    length gives what the shared-table call gives (its body is one)."""
    rng = np.random.RandomState(3)
    B, npages = 3, 5
    kp = jnp.asarray(rng.randn(HKV, 1 + B * npages, BS, D), jnp.float32)
    vp = jnp.asarray(rng.randn(HKV, 1 + B * npages, BS, D), jnp.float32)
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    table = jnp.asarray(1 + np.arange(B * npages).reshape(B, npages),
                        jnp.int32)
    lens = jnp.asarray([70, 0, 33], jnp.int32)
    shared = pa._paged_pallas(q, kp, vp, lens, table, 0.25, interpret=True)
    by_head = pa._paged_pallas(
        q, kp, vp, jnp.broadcast_to(lens[:, None], (B, HKV)),
        jnp.broadcast_to(table[:, None], (B, HKV, npages)), 0.25,
        interpret=True)
    np.testing.assert_allclose(np.asarray(by_head), np.asarray(shared),
                               atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(shared),
        np.asarray(pa._paged_math(q, kp, vp, lens, table, 0.25)), atol=1e-5)
