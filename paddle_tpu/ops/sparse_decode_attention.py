"""One decode token a row over the pages its selector kept (the decode half of
ops/sparse_paged_attention.py, which states the cache, the selector and the
packed prefill).

With page = selection block, the kept blocks of a decode step are a page
table a row AND K/V head of at most `SparseConfig.table_width` entries, in
position order, the row's own (partial) block last. ops/paged_attention.py's
decode kernel walks such a table as it walks a row's whole table: only the
kept pages are read.

Tiers (`LAST_IMPL`, at trace time; a tier that cannot run raises, it never
becomes another): `sparse-decode-kernel` (the paged kernel with a table a
K/V head; `-interpret` off the TPU under `impl="pallas"`), `sparse-decode-xla`
(one gather of the kept pages and a masked softmax: the off-TPU default and
the kernel's reference).
"""
import math

import jax
import jax.numpy as jnp

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


def sparse_decode_attention(q, k_pages, v_pages, c_keys, page_indices,
                            lengths, sp, scale=None, impl=None):
    """One query token a row over its kept pages. q [B, Hq, D]; `lengths`
    already INCLUDE the just-written token, 0 for a row that attends
    nothing. Returns (o [B, Hq, D], keys kept, keys visible), the two
    counts summed over rows and K/V heads (int32)."""
    global LAST_IMPL
    from .flash_attention import _FORCE_XLA, _on_tpu

    B, Hq, D = q.shape
    Hkv, _, bs, _ = k_pages.shape
    npages = page_indices.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    t = jnp.maximum(lengths - 1, 0)
    on_tpu = _on_tpu() and not _FORCE_XLA
    impl = impl or "auto"
    # one scope for the step's selector and its attention: the benchmark
    # reads this op's device time under it (`sala.select` is the packed
    # prefill's selector alone)
    with jax.named_scope("sala.sparse.decode"):
        per = sp.per_page
        at = (page_indices[:, :, None] * per
              + jnp.arange(per)[None, None]).reshape(B, -1)
        ck = c_keys[jnp.arange(Hkv)[None, :, None], at[:, None]]  # [B,Hkv,J,D]
        logits = jnp.einsum("bhgd,bhjd->bhgj",
                            q.reshape(B, Hkv, Hq // Hkv, D), ck,
                            preferred_element_type=jnp.float32) * scale
        mask = block_mask(logits, t, sp, npages)               # [B,Hkv,np]
        mask = mask & (lengths > 0)[:, None, None]
        # the kept blocks in position order: a page table a row and head
        m = jnp.arange(npages)
        order = jnp.argsort(jnp.where(mask, m, m + npages), axis=-1)
        order = order[..., :sp.table_width(npages)]
        n_kept = mask.sum(axis=-1).astype(jnp.int32)
        table = jnp.take_along_axis(
            jnp.broadcast_to(page_indices[:, None], mask.shape), order,
            axis=-1)
        table = jnp.where(jnp.arange(order.shape[-1]) < n_kept[..., None],
                          table, 0)
        vlen = jnp.where(n_kept > 0,
                         (n_kept - 1) * bs + (t % bs + 1)[:, None], 0)
        if impl == "pallas" or (impl == "auto" and on_tpu):
            o = _paged_pallas(q, k_pages, v_pages, vlen, table, scale,
                              interpret=not on_tpu)
            LAST_IMPL = ("sparse-decode-kernel" if on_tpu
                         else "sparse-decode-kernel-interpret")
        else:
            o = _decode_xla(q, k_pages, v_pages, table, vlen, scale)
            LAST_IMPL = "sparse-decode-xla"
    return o, vlen.sum(), (lengths * Hkv).sum()
