"""One decode token a row over the pages its selector kept (the decode half of
ops/sparse_paged_attention.py, which states the cache, the selector and the
packed prefill).

With page = selection block, the kept blocks of a decode step are a page
table a row AND K/V head of at most `SparseConfig.table_width` entries, in
position order, the row's own (partial) block last. ops/paged_attention.py's
decode kernel walks such a table as it walks a row's whole table: only the
kept pages are read. The selector in front of it (`_select_live`) makes the
tables of the rows that hold a token, one a trip of a loop to their count,
as the kernel walks its grid: a step's work grows with the rows that live,
not with the engine's slots.

Tiers (`LAST_IMPL`, at trace time; a tier that cannot run raises, it never
becomes another): `sparse-decode-kernel` (the paged kernel with a table a
K/V head; `-interpret` off the TPU under `impl="pallas"`), `sparse-decode-xla`
(one gather of the kept pages and a masked softmax: the off-TPU default and
the kernel's reference).
"""
import math

import jax
import jax.numpy as jnp

from .cache_specs import live_rows
from .paged_attention import _paged_pallas
from .sparse_paged_attention import block_mask

LAST_IMPL = None  # "sparse-decode-kernel[-interpret]" | "sparse-decode-xla"


def _decode_xla(q, k_pages, v_pages, table, vlen, scale):
    """Attention over a table a row and K/V head. q [B, Hq, D]; table
    [B, Hkv, n]; vlen [B, Hkv] keys to attend in table order."""
    B, Hq, D = q.shape
    Hkv, _, bs, _ = k_pages.shape
    n = table.shape[-1]
    h = jnp.arange(Hkv)[None, :, None]
    ks = k_pages[h, table].reshape(B, Hkv, n * bs, D).astype(jnp.float32)
    vs = v_pages[h, table].reshape(B, Hkv, n * bs, D).astype(jnp.float32)
    qs = (q * scale).astype(jnp.float32).reshape(B, Hkv, Hq // Hkv, D)
    s = jnp.einsum("bhgd,bhkd->bhgk", qs, ks)
    see = (jnp.arange(n * bs)[None, None] < vlen[..., None])[:, :, None]
    s = jnp.where(see, s, -1e30)
    p = jnp.where(see, jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
    o = jnp.einsum("bhgk,bhkd->bhgd", p, vs)
    o = o / jnp.maximum(p.sum(axis=-1), 1e-30)[..., None]
    return o.reshape(B, Hq, D).astype(q.dtype)


def _select_row(q, c_keys, pages, length, sp, scale, bs):
    """The kept pages of ONE row that holds a token. q [Hq, D]; `pages`
    [npages] the row's page table; `length` its tokens, the just-written one
    included. Returns (table [Hkv, width] int32: the kept pages in position
    order, zeros after them; vlen [Hkv] int32: the keys to attend in table
    order)."""
    Hq, D = q.shape
    Hkv = c_keys.shape[0]
    npages, per = pages.shape[0], sp.per_page
    t = jnp.maximum(length - 1, 0)[None]
    # a page's compressed keys are one window of the plane, [Hkv, per, D]:
    # the TPU keeps the plane key-major with the heads side by side, so the
    # window is 2 KB in one piece where a key and head is 256 bytes (on the
    # v5e a trip of 77 us gathered by key, 42 by page: PERF.md section 6,
    # PR 36)
    ck = jax.lax.gather(
        c_keys, (pages * per)[:, None],
        jax.lax.GatherDimensionNumbers(
            offset_dims=(1, 2, 3), collapsed_slice_dims=(),
            start_index_map=(1,)),
        (Hkv, per, D), mode="promise_in_bounds")           # [np,Hkv,per,D]
    logits = jnp.einsum("hgd,phjd->hgpj", q.reshape(Hkv, Hq // Hkv, D), ck,
                        preferred_element_type=jnp.float32)
    logits = logits.reshape(1, Hkv, Hq // Hkv, npages * per) * scale
    mask = block_mask(logits, t, sp, npages)[0]                # [Hkv,np]
    # the kept blocks in position order, a page table a head: a kept
    # block's place is the count of kept blocks before it (on the MXU: 0 / 1
    # products summed in float32 are exact), no sort (42 -> 30 us a trip)
    m = jnp.arange(npages)
    place = jnp.einsum("hm,mj->hj", mask.astype(jnp.bfloat16),
                       (m[:, None] < m[None, :]).astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    width = jnp.arange(sp.table_width(npages), dtype=jnp.float32)
    here = mask[:, None, :] & (place[:, None, :] == width[:, None])
    table = jnp.sum(jnp.where(here, pages, 0), axis=-1)
    n_kept = mask.sum(axis=-1).astype(jnp.int32)
    vlen = jnp.where(n_kept > 0, (n_kept - 1) * bs + t % bs + 1, 0)
    return table.astype(jnp.int32), vlen


def _select_live(q, c_keys, page_indices, lengths, sp, scale, bs):
    """The step's selector over the rows that hold a token, one a trip
    (ops/cache_specs.py: live_rows): a dead row is never gathered, scored or
    ordered, and its table and `vlen` stay zero. Returns (table
    [B, Hkv, width], vlen [B, Hkv]) for the kernel. One form for every live
    count: on the v5e at the cell's widths a trip is 30 us beside the
    kernel's 27 us a row, so 16 live rows of 16 cost 0.91 ms where every
    slot at once cost 1.11 (`scripts/sala_chip_checks.py sweep`, PERF.md
    section 6, PR 36)."""
    B, Hkv = q.shape[0], c_keys.shape[0]
    rows, n = live_rows(lengths > 0)

    def trip(i, carry):
        table, vlen = carry
        r = rows[i]
        tb, vl = _select_row(q[r], c_keys, page_indices[r], lengths[r], sp,
                             scale, bs)
        return (jax.lax.dynamic_update_index_in_dim(table, tb, r, 0),
                jax.lax.dynamic_update_index_in_dim(vlen, vl, r, 0))

    width = sp.table_width(page_indices.shape[1])
    return jax.lax.fori_loop(0, n, trip,
                             (jnp.zeros((B, Hkv, width), jnp.int32),
                              jnp.zeros((B, Hkv), jnp.int32)))


def sparse_decode_attention(q, k_pages, v_pages, c_keys, page_indices,
                            lengths, sp, scale=None, impl=None):
    """One query token a row over its kept pages. q [B, Hq, D]; `lengths`
    already INCLUDE the just-written token, 0 for a row that attends
    nothing. Returns (o [B, Hq, D], keys kept, keys visible), the two
    counts summed over rows and K/V heads (int32)."""
    global LAST_IMPL
    from .flash_attention import _FORCE_XLA, _on_tpu

    Hkv, _, bs, D = k_pages.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    on_tpu = _on_tpu() and not _FORCE_XLA
    impl = impl or "auto"
    # one scope for the step's selector and its attention: the benchmark
    # reads this op's device time under it (`sala.select` is the packed
    # prefill's selector alone)
    with jax.named_scope("sala.sparse.decode"):
        table, vlen = _select_live(q, c_keys, page_indices, lengths, sp,
                                   scale, bs)
        if impl == "pallas" or (impl == "auto" and on_tpu):
            o = _paged_pallas(q, k_pages, v_pages, vlen, table, scale,
                              interpret=not on_tpu)
            LAST_IMPL = ("sparse-decode-kernel" if on_tpu
                         else "sparse-decode-kernel-interpret")
        else:
            o = _decode_xla(q, k_pages, v_pages, table, vlen, scale)
            LAST_IMPL = "sparse-decode-xla"
    return o, vlen.sum(), (lengths * Hkv).sum()
