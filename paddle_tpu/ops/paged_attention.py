"""Paged KV-cache attention (reference capability: the serving engine class
of paddle/fluid/inference AnalysisPredictor + PaddleNLP's block-attention
serving; PAPERS.md ragged-paged-attention is the kernel blueprint).

TPU-native design: the KV cache is a POOL of fixed-size pages shared by all
sequences — [num_kv_heads, num_pages, page_size, head_dim], the exact layout
of jax's Pallas TPU `paged_attention` kernel — plus a per-sequence page table
(page_indices [B, pages_per_seq]) and lengths [B]. Memory is bounded by pool
occupancy (sum of actual context lengths, page-granular), not by
B × max_len as the dense fixed-shape cache is.

The pool's layout contract (stated here, for both writers and both kernels):
the pool is [Hkv, P, bs, D] in the default row-major layout, from a
program's parameter to its result, because both Mosaic kernels (jax's
`paged_attention`, `_ragged_pallas`) read it so. A write must NOT be an XLA
scatter whose window covers Hkv (`pages.at[:, page, off, :].set(...)`): the
TPU compiler lays a scatter's operand out with the window dims minor
([P, bs, Hkv, D] physically), carries the pool through the decode scan in
that layout, and copies the whole pool back before every kernel call — 55%
of the serving cell's device time when it was found (PERF.md, PR 27). So the
writers make the head a scattered index: `write_token_kv` scatters rows
(window D), `write_ragged_kv` whole pages (window bs x D).
tests/test_chip_compile.py compiles both program shapes for a described v5e
and fails if a pool-shaped copy comes back.

Two decode tiers, chosen at trace time like ops/flash_attention.py:
- kernel: `jax.experimental.pallas.ops.tpu.paged_attention` on TPU;
- math: one vectorized page-table gather plus a masked dense softmax
  (the old per-page sequential scan paid npages chained gather+dot
  round-trips — it remains the bit-exactness reference only in spirit;
  the gathered slab is B × max_len, the same footprint a dense cache
  would hold).

`PagedLayerCache` is the duck-typed per-layer cache entry the model's
attention recognizes in `past_key_values` (models/llama.py) — the third
cache protocol next to the growing-concat and fixed-shape ones.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp

LAST_IMPL = None  # "paged-kernel" | "paged-math" — set at trace time


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedLayerCache:
    """One layer's paged cache view.

    k_pages/v_pages: [num_kv_heads, num_pages, page_size, head_dim]
    page_indices:    [B, pages_per_seq] int32 rows into the pool
    lengths:         [B] int32 — valid tokens per sequence BEFORE this step
    """

    k_pages: jax.Array
    v_pages: jax.Array
    page_indices: jax.Array
    lengths: jax.Array

    def tree_flatten(self):
        return (self.k_pages, self.v_pages, self.page_indices, self.lengths), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def page_size(self):
        k = self.k_pages
        return (k.weight if is_quantized(k) else k).shape[2]


class KVCacheSpec:
    """What the serving engine asks of a model whose layers cache K and V:
    how to make the pools, how to view one as a cache entry, and how to take
    the pool back out of the entry a forward returns. A pool is the pair
    (k_pages, v_pages). The latent twin is ops.latent_pool.LatentCacheSpec;
    a model names its own through `serving_cache_spec()`."""

    latent = False

    def __init__(self, num_layers, num_kv_heads, head_dim):
        self.num_layers = num_layers
        self.num_kv_heads, self.head_dim = num_kv_heads, head_dim

    def make_pools(self, num_pages, page_size, dtype, kv_cache_dtype=None):
        shape = (self.num_kv_heads, num_pages, page_size, self.head_dim)
        if kv_cache_dtype == "int8":
            # int8 KV pool (jax paged_attention QuantizedTensor layout):
            # ~4x fewer HBM bytes per decode step vs f32, ~2x vs bf16 —
            # the decode-bandwidth lever; scales are per (head, page, row)
            from jax.experimental.pallas.ops.tpu.paged_attention import (
                quantization_utils as qu,
            )

            def zero_pool():
                return qu.QuantizedTensor(
                    weight=jnp.zeros(shape, jnp.int8),
                    scales=jnp.ones(shape[:3] + (1,), jnp.float32))
        elif kv_cache_dtype not in (None, "model"):
            raise ValueError(f"unsupported kv_cache_dtype {kv_cache_dtype!r}")
        else:
            def zero_pool():
                return jnp.zeros(shape, dtype)
        return [(zero_pool(), zero_pool()) for _ in range(self.num_layers)]

    @staticmethod
    def paged(pool, page_table, lengths, live):
        # `live` (the rows a request holds) is the latent twin's: here a
        # dead row reads its one scratch token
        return PagedLayerCache(*pool, page_table, lengths)

    @staticmethod
    def ragged(pool, page_table, kv_lens, cu, row_of, token_pos, valid):
        from .ragged_paged_attention import RaggedLayerCache

        return RaggedLayerCache(*pool, page_table, kv_lens, cu, row_of,
                                token_pos, valid)

    @staticmethod
    def pool_of(present):
        return (present.k_pages, present.v_pages)


def is_quantized(pages):
    """True for the int8 pool form: a QuantizedTensor(weight, scales) pair
    (jax's paged_attention quantization_utils layout — weight int8
    [Hkv, P, bs, D], scales [Hkv, P, bs, 1] = per-row absmax)."""
    return hasattr(pages, "weight") and hasattr(pages, "scales")


def quantize_pages(pages_f):
    """Float pool -> int8 QuantizedTensor pool (per-row absmax scales)."""
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        quantization_utils as qu,
    )

    return qu.quantize_to_int8(pages_f.astype(jnp.float32))


def _dequantize(weight, scales, dtype=jnp.float32):
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        quantization_utils as qu,
    )

    return qu.from_int8(weight, scales, dtype=dtype)


def store_kv(pages, new, put):
    """The two writers' common half: `new` [N, Hkv, D] becomes rows in the
    pool's own dtype, head-major like the pool, and every plane of the pool
    (one array, or the int8 pool's values and scales) goes through
    `put(plane, rows [Hkv, N, last])`."""
    rows = jnp.swapaxes(new, 0, 1)
    if is_quantized(pages):
        from jax.experimental.pallas.ops.tpu.paged_attention import (
            quantization_utils as qu,
        )

        qt = qu.quantize_to_int8(rows.astype(jnp.float32))
        return type(pages)(
            weight=put(pages.weight, qt.weight),
            scales=put(pages.scales, qt.scales.astype(pages.scales.dtype)),
        )
    return put(pages, rows.astype(pages.dtype))


def write_token_kv(pages, page_indices, lengths, new):
    """Scatter one new token's K or V into the pool.

    pages: [Hkv, P, bs, D] float, or QuantizedTensor for the int8 pool
    (the new row is quantized per (b, head) with its own absmax scale —
    the HBM-bandwidth lever for decode). new: [B, Hkv, D]; the token lands
    at logical position `lengths[b]` → page page_indices[b, lengths[b]//bs],
    offset lengths[b] % bs. Pages belong to exactly one sequence, so rows
    never collide (rows routed to the scratch page, only with each other).
    The head is a scattered index: layout contract, module docstring."""
    bs = (pages.weight if is_quantized(pages) else pages).shape[2]
    page_of = jnp.take_along_axis(
        page_indices, (lengths // bs)[:, None], axis=1
    )[:, 0]  # [B]
    off = lengths % bs  # [B]

    def put(plane, rows):
        h = jnp.arange(plane.shape[0])[:, None]
        return plane.at[h, page_of[None], off[None], :].set(rows)

    return store_kv(pages, new, put)


def _paged_math(q, k_pages, v_pages, lengths, page_indices, scale):
    """Masked decode attention over the paged pool; q: [B, Hq, D] (one
    decode token per row). ONE vectorized advanced-index gather pulls
    every row's pages ([B, Hkv, npages*bs, D] slab) and a masked dense
    softmax in f32 replaces the old per-page sequential scan — same math,
    one batched dot instead of npages chained gather+dot steps. The slab
    is bounded by B × pages_per_seq × page_size ≈ B × max_len, which is
    exactly the dense-cache footprint serving configs already budget for;
    int8 pools dequantize the gathered slab only."""
    B, Hq, D = q.shape
    kq, vq = is_quantized(k_pages), is_quantized(v_pages)
    Hkv, P, bs, _ = (k_pages.weight if kq else k_pages).shape
    npages = page_indices.shape[1]
    group = Hq // Hkv
    M = npages * bs

    def gather(pages, quant):
        if quant:
            full = _dequantize(
                jnp.swapaxes(pages.weight[:, page_indices], 0, 1),
                jnp.swapaxes(pages.scales[:, page_indices], 0, 1),
            )  # [B, Hkv, npages, bs, D]
        else:
            full = jnp.swapaxes(
                pages[:, page_indices], 0, 1).astype(jnp.float32)
        return full.reshape(B, Hkv, M, D)

    ks = gather(k_pages, kq)
    vs = gather(v_pages, vq)
    qs = (q * scale).astype(jnp.float32).reshape(B, Hkv, group, D)
    s = jnp.einsum("bhgd,bhkd->bhgk", qs, ks)  # [B, Hkv, group, M]
    pos = jnp.arange(M)
    s = jnp.where(pos[None, None, None, :] < lengths[:, None, None, None],
                  s, -1e30)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    out = jnp.einsum("bhgk,bhkd->bhgd", p, vs)
    out = out / jnp.maximum(p.sum(axis=-1), 1e-30)[..., None]
    return out.reshape(B, Hq, D).astype(q.dtype)


def paged_decode_attention(q, k_pages, v_pages, lengths, page_indices,
                           scale=None, pages_per_compute_block=None):
    """One-token decode attention over the paged pool.

    q: [B, Hq, D]; returns [B, Hq, D]. lengths must already INCLUDE the
    just-written token (the query attends to itself)."""
    global LAST_IMPL
    from .flash_attention import _FORCE_XLA, _on_tpu

    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    if _on_tpu() and not _FORCE_XLA:
        from jax.experimental.pallas.ops.tpu.paged_attention import (
            paged_attention as _kernel,
        )

        blk = pages_per_compute_block or min(8, page_indices.shape[1])
        while page_indices.shape[1] % blk:
            blk -= 1
        qdt = jnp.bfloat16 if is_quantized(k_pages) else k_pages.dtype
        out = _kernel((q * scale).astype(qdt), k_pages, v_pages,
                      lengths, page_indices,
                      pages_per_compute_block=max(blk, 1))
        LAST_IMPL = "paged-kernel"
        return out.astype(q.dtype)
    LAST_IMPL = "paged-math"
    return _paged_math(q, k_pages, v_pages, lengths, page_indices, scale)
