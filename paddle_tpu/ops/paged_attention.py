"""Paged KV-cache attention (reference capability: the serving engine class
of paddle/fluid/inference AnalysisPredictor + PaddleNLP's block-attention
serving; PAPERS.md ragged-paged-attention is the kernel blueprint).

TPU-native design: the KV cache is a POOL of fixed-size pages shared by all
sequences — [num_kv_heads, num_pages, page_size, head_dim], the layout jax's
Pallas TPU `paged_attention` kernel fixed — plus a per-sequence page table
(page_indices [B, pages_per_seq]) and lengths [B]. Memory is bounded by pool
occupancy (sum of actual context lengths, page-granular), not by
B × max_len as the dense fixed-shape cache is.

The pool's layout contract (stated here, for both writers and both kernels):
the pool is [Hkv, P, bs, D] in the default row-major layout, from a
program's parameter to its result, because the Mosaic kernels
(`_paged_pallas`, `_ragged_pallas`, jax's `paged_attention`) read it so. A
write must NOT be an XLA scatter whose window covers Hkv
(`pages.at[:, page, off, :].set(...)`): the
TPU compiler lays a scatter's operand out with the window dims minor
([P, bs, Hkv, D] physically), carries the pool through the decode scan in
that layout, and copies the whole pool back before every kernel call — 55%
of the serving cell's device time when it was found (PERF.md, PR 27). So the
writers make the head a scattered index: `write_token_kv` scatters rows
(window D), `write_ragged_kv` whole pages (window bs x D).
tests/test_chip_compile.py compiles both program shapes for a described v5e
and fails if a pool-shaped copy comes back.

Decode tiers, chosen at trace time like ops/flash_attention.py (`LAST_IMPL`;
a tier that cannot run raises, it never becomes another):
- `paged-kernel`, float pool: `_paged_pallas`, this module's kernel. ONE
  grid axis over the call's live (row, block of pages) pairs, on the
  pattern of `_ragged_pallas`: a work list made from the rows' lengths
  before the call (`paged_work`: the pair's row, its block, a first / last
  flag, the row's last held page) is scalar prefetch beside the lengths and
  the page table, and its length is the grid's bound, an OPERAND. Every
  page of a block is one page-indirect operand holding ALL KV heads
  ([Hkv, bs, D], one strided DMA a page), so a step folds a block into
  every head's online softmax with two batched dots (q grouped
  [Hkv, G, D]: MHA and GQA take the one path). Scores, softmax and
  accumulator are f32. Work is in proportion to the blocks the live rows
  hold: a dead row (length 0) is never visited and returns zeros, a row
  steps through its own blocks and not the longest row's (a step that
  folds nothing still cost 1.2 us: 13 rows of 1.7k-12.6k tokens walked 429
  steps for 178 live ones, PERF.md PR 38); past a row's own pages an
  operand stays on the page it held, which is not fetched again. On the
  v5e at 16 rows x 32/32 heads it reads 76-92% of the HBM roofline (28 us
  a call at 3 live rows of 350 tokens), where jax's kernel took 200-404 us
  (PERF.md PR 32: a 512-step grid, a 4 KB DMA a page and head, f32
  products, and 13 dead rows handed a length of 1).
- `paged-kernel`, int8 pool (`is_quantized`): jax's
  `jax.experimental.pallas.ops.tpu.paged_attention`, whose DMAs dequantise
  (this module's kernel has no scales operand). It skips rows of length 0
  too, but broadcasts the pool's scales to head_dim on every call (1.6 ms
  at the cell's pool, PERF.md PR 32): a kernel for that pool is ROADMAP's.
- `paged-math`: one vectorized page-table gather plus a masked dense
  softmax in f32 (the gathered slab is B × max_len, the footprint a dense
  cache would hold). The off-TPU default and the kernels' reference;
  `impl="pallas"` runs `_paged_pallas` in interpret mode for the CPU tests.

`PagedLayerCache` is the duck-typed per-layer cache entry the model's
attention recognizes in `past_key_values` (models/llama.py) — the third
cache protocol next to the growing-concat and fixed-shape ones.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

LAST_IMPL = None  # "paged-kernel[-interpret]" | "paged-math" — at trace time


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedLayerCache:
    """One layer's paged cache view.

    k_pages/v_pages: [num_kv_heads, num_pages, page_size, head_dim]
    page_indices:    [B, pages_per_seq] int32 rows into the pool
    lengths:         [B] int32 — valid tokens per sequence BEFORE this step
    live:            [B] bool — the rows a request holds. A dead row (an
                     empty slot of the engine's fixed batch, a row still in
                     prefill during a mixed step's scan) writes its token to
                     the scratch page and attends nothing.
    """

    k_pages: jax.Array
    v_pages: jax.Array
    page_indices: jax.Array
    lengths: jax.Array
    live: jax.Array

    def tree_flatten(self):
        return (self.k_pages, self.v_pages, self.page_indices, self.lengths,
                self.live), None

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
    a model names its own through `serving_cache_spec()`; one whose layers
    differ answers with a spec a layer (ops/cache_specs.py, which states
    the members the engine reads)."""

    kind = "K/V pages"
    log_pages = has_state = False
    allocator_pages = True  # as ONE layer of a ops.cache_specs.LayerCacheSpecs

    def __init__(self, num_layers, num_kv_heads, head_dim, num_heads=None):
        self.num_layers = num_layers
        self.num_kv_heads, self.head_dim = num_kv_heads, head_dim
        self.num_heads = num_heads or num_kv_heads

    @property
    def layers(self):
        return [self] * self.num_layers

    def refuses(self, plane):
        return None  # every plane of the engine was written for these pools

    def make_pools(self, num_pages, page_size, dtype, kv_cache_dtype=None,
                   max_seqs=None, prefill_chunk=None):
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

    def make_pool(self, *args, **kw):
        """This spec as ONE layer of a model whose layers differ."""
        return self.make_pools(*args, **kw)[0]

    @staticmethod
    def paged(pool, page_table, lengths, live):
        return PagedLayerCache(*pool, page_table, lengths, live)

    @staticmethod
    def ragged(pool, page_table, kv_lens, cu, row_of, token_pos, valid):
        from .ragged_paged_attention import RaggedLayerCache

        return RaggedLayerCache(*pool, page_table, kv_lens, cu, row_of,
                                token_pos, valid)

    @staticmethod
    def pool_of(present):
        return (present.k_pages, present.v_pages)

    def ragged_walk(self, pool, cu, kv_lens, n_tokens, npages):
        """(walked, dense) grid steps a layer of a mixed step's attention
        call over these spans: the step log's `ragged_walk`."""
        from .ragged_paged_attention import ragged_walk

        return ragged_walk(cu, kv_lens, n_tokens, self.num_heads, pool[0],
                           npages)

    @staticmethod
    def paged_walk(pools, lengths, npages):
        """(walked, dense) grid steps a layer and forward of a scan step's
        decode call at these lengths (the step's own token included, 0 for
        a row the scan leaves out): the step log's `paged_walk`. None for
        the int8 pool, whose kernel is jax's."""
        k_pages = pools[0][0]
        return (None if is_quantized(k_pages)
                else paged_walk(k_pages, lengths, npages))


class WindowRingSpec:
    """The cache of ONE sliding-window attention layer: a fixed RING of
    pages a row, whatever the row's length. A query sees its last `window`
    keys, so a row never needs more than the pages that hold them and the
    chunk being written: `ring_pages = ceil((window + prefill_chunk) /
    page_size) + 1` (a chunk is written before it attends, so its first
    query's `window - 1` predecessors and its last token are live together,
    on at most that many pages). The pool is `1 + max_seqs * ring_pages`
    pages whatever `num_pages` and `max_len` are; page 0 is the scratch
    page dead rows write to.

    The ring is a page TABLE of its own, computed from the row and the
    position and never touched by the allocator: logical page `j` of row
    `r` is physical page `1 + r * ring_pages + j mod ring_pages`. Writers
    and kernels take it where they take the row's table, as wide as the
    row's (the kernels are given `window` and read it at the window's pages
    alone), so the views are `PagedLayerCache` / `RaggedLayerCache` and a
    stale key a ring still holds lies either past the row's length or below
    every live query's window."""

    kind = "window ring"
    has_state = allocator_pages = False

    def __init__(self, num_kv_heads, head_dim, window):
        self.num_kv_heads, self.head_dim = num_kv_heads, head_dim
        self.window = window

    def ring_pages(self, page_size, prefill_chunk):
        return -(-(self.window + prefill_chunk) // page_size) + 1

    def make_pool(self, num_pages, page_size, dtype, kv_cache_dtype=None,
                  max_seqs=None, prefill_chunk=None):
        if max_seqs is None or prefill_chunk is None:
            raise ValueError("a window ring is sized by the rows and the "
                             "prefill chunk: make_pools needs max_seqs and "
                             "prefill_chunk")
        if kv_cache_dtype not in (None, "model"):
            raise ValueError(
                f"kv_cache_dtype={kv_cache_dtype!r}: the windowed kernels "
                f"read float pools ({type(self).__name__})")
        shape = (self.num_kv_heads,
                 1 + max_seqs * self.ring_pages(page_size, prefill_chunk),
                 page_size, self.head_dim)
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))

    def refuses(self, plane):
        if plane in ("prefix_cache", "handoff"):
            return ("shares or moves a row's pages; a window layer keeps a "
                    "ring of pages a row, which are not the allocator's "
                    f"({type(self).__name__})")
        return None

    @staticmethod
    def table(pool, page_table):
        """The rings as a table as wide as the row's own."""
        rows, width = page_table.shape
        ring = (pool[0].shape[1] - 1) // rows
        return (1 + jnp.arange(rows, dtype=jnp.int32)[:, None] * ring
                + jnp.arange(width, dtype=jnp.int32)[None, :] % ring)

    @classmethod
    def paged(cls, pool, page_table, lengths, live):
        table = jnp.where(live[:, None], cls.table(pool, page_table), 0)
        return PagedLayerCache(*pool, table, lengths, live)

    @classmethod
    def ragged(cls, pool, page_table, kv_lens, cu, row_of, token_pos, valid):
        from .ragged_paged_attention import RaggedLayerCache

        return RaggedLayerCache(*pool, cls.table(pool, page_table), kv_lens,
                                cu, row_of, token_pos, valid)

    @staticmethod
    def pool_of(present):
        return (present.k_pages, present.v_pages)


def window_walk(kv_lens, q_lens, window, page_size):
    """(walked, causal) keys of one windowed layer's attention call, summed
    over the rows that have a query (`q_lens > 0`; `kv_lens` counts the
    call's own tokens): the keys on the row's pages from the first one its
    FIRST query's window touches to its end, and the row's whole length,
    which a causal walk without a window would read."""
    first = jnp.maximum(kv_lens - q_lens + 1 - window, 0) // page_size
    has = q_lens > 0
    return (jnp.sum(jnp.where(has, kv_lens - first * page_size, 0)),
            jnp.sum(jnp.where(has, kv_lens, 0)))


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


def _paged_math(q, k_pages, v_pages, lengths, page_indices, scale,
                window=None):
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
    seen = pos[None, None, None, :] < lengths[:, None, None, None]
    if window is not None:  # the last `window` keys, the query's own among them
        seen &= pos[None, None, None, :] >= (lengths - window)[
            :, None, None, None]
    s = jnp.where(seen, s, -1e30)
    # a row of length 0 sees nothing: zeros, not the mean of its table
    p = jnp.where(seen, jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, vs)
    out = out / jnp.maximum(p.sum(axis=-1), 1e-30)[..., None]
    return out.reshape(B, Hq, D).astype(q.dtype)


_LANES = 128  # m/l scratch keep a lane-aligned last dim
_SUBLANES = 8  # q's group rows are padded to whole f32 sublane tiles


def _row_blocks(lengths, bs, ppb, npages, window, xp):
    """(first, count) [R] int32: the first block of `ppb` pages a row's walk
    folds and how many it folds. Without a `window` a row walks from block 0
    to the block of its last key (a length past the table reads no page);
    with one, from the block that holds the first of its last `window` keys.
    A dead row (length 0) walks none."""
    kb = ppb * bs
    lens = lengths.astype(xp.int32)
    last = xp.minimum(-(-lens // kb), -(-npages // ppb))
    first = (xp.zeros_like(lens) if window is None
             else xp.maximum(lens - window, 0) // kb)
    return first, xp.maximum(last - first, 0)


def paged_work(lengths, bs, ppb, npages, window=None, xp=jnp):
    """The decode kernel's work list from the rows' lengths, for `jnp`
    (before the call: no sort, no gather, no scan) and for numpy.

    Returns (work, n_steps): `work` four int32 arrays of R x ceil(npages /
    ppb) entries, one a pair p, rows in order and a row's blocks in order:
    work[0][p] the pair's row, work[1][p] its block of `ppb` pages (of the
    row's table: a windowed row's first pair stands at the block of its
    first visible key), work[2][p] bit 0 set on a row's first pair and bit 1
    on its last, work[3][p] the index of the row's last held page (the page
    maps keep an operand past it on the page it held a block before).
    `n_steps` is the sum of the rows' blocks; entries past it are zeros and
    are never visited. The list follows from `lengths` alone: the calls of
    one forward that share them share it.

    Every op between the lengths and the kernel is a launch of its own, and
    the shortest call is a few microseconds long: so the pairs come from
    masked sums over the rows (two rounds of them, the indices constants),
    four arrays and not one stacked, and nothing is computed after them."""
    lens = lengths.astype(xp.int32)
    R = lens.shape[0]
    first, count = _row_blocks(lens, bs, ppb, npages, window, xp)
    last_page = xp.maximum(-(-lens // bs) - 1, 0)
    r = np.arange(R, dtype=np.int32)
    below, own = r[None, :] < r[:, None], r[None, :] == r[:, None]
    before = xp.where(below, count[None, :], 0)
    starts = xp.sum(before, axis=1)                  # pairs before the row's
    ends = xp.sum(before + xp.where(own, count[None, :], 0), axis=1)
    # a row's block less its place among the pairs, and its last page, as
    # sums too: they then come out of the one fusion that makes `starts`
    lead = xp.sum(xp.where(own, first[None, :], 0) - before, axis=1)
    held = xp.sum(xp.where(own, last_page[None, :], 0), axis=1)
    p = np.arange(R * -(-npages // ppb), dtype=np.int32)[:, None]
    mine = (starts[None, :] <= p) & (p < ends[None, :])    # one row a pair

    def of(x):
        return xp.sum(xp.where(mine, x, 0), axis=1).astype(xp.int32)

    edge = (p == starts[None, :]) + 2 * (p + 1 == ends[None, :])
    return ((of(r[None, :]), of(lead[None, :] + p), of(edge),
             of(held[None, :])), ends[-1])


def paged_walk(k_pages, lengths, npages, window=None):
    """(walked, dense): the grid steps `_paged_pallas` takes for these
    lengths (the just-written token included, 0 for a dead row), and the
    steps of the rectangular walk it replaced (every live row through the
    blocks of the longest). Host arithmetic; `k_pages` lends its shape and
    dtype."""
    ppb = _pages_per_block(k_pages, npages)
    _, count = _row_blocks(np.asarray(lengths), k_pages.shape[2], ppb,
                           npages, window, np)
    return (int(count.sum()),
            int(np.count_nonzero(count)) * int(count.max(initial=0)))


def _decode_kernel(ppb, window, row_ref, blk_ref, edge_ref, held_ref, len_ref,
                   pt_ref, q_ref, *refs):
    """Grid (pair p of the work list): fold block `blk_ref[p]` of ppb pages,
    every KV head at once, into row `row_ref[p]`'s online softmax.
    q_ref [Hkv, Gp, D] (the group's rows padded to Gp); refs: ppb K pages
    and ppb V pages [Hkv, bs, D], then o_ref [Hkv, G, D] and the scratch acc
    [Hkv, Gp, D], m and l [Hkv, Gp, 128]. A row's pairs follow one another,
    so the scratch is the row's from its first pair to its last."""
    import jax.experimental.pallas as pl

    k_pg, v_pg = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, acc, m, l = refs[2 * ppb:]
    p = pl.program_id(0)
    j, edge = blk_ref[p], edge_ref[p]
    kb = ppb * k_pg[0].shape[1]
    length = len_ref[row_ref[p]]

    def seen(pos):
        if window is None:
            return pos < length
        return (pos < length) & (pos >= length - window)

    @pl.when((edge & 1) == 1)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, -1e30)
        l[...] = jnp.zeros_like(l)

    @pl.when(j * kb < length)
    def _fold():
        k = jnp.concatenate([pg[...] for pg in k_pg], axis=1)  # [Hkv, kb, D]
        v = jnp.concatenate([pg[...] for pg in v_pg], axis=1)
        s = jax.lax.dot_general(
            q_ref[...], k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)                # [Hkv, Gp, kb]
        pos = j * kb + jax.lax.broadcasted_iota(jnp.int32, (1, 1, kb), 2)
        s = jnp.where(seen(pos), s, -1e30)
        m_prev, l_prev = m[:, :, :1], l[:, :, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(seen(pos), jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        m[...] = jnp.broadcast_to(m_new, m.shape)
        l[...] = jnp.broadcast_to(
            l_prev * corr + p.sum(axis=-1, keepdims=True), l.shape)
        # the weights ride the MXU in the pool's dtype, as the no-cache
        # forward's softmax rounds them; the sums stay f32
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)                # [Hkv, Gp, D]
        acc[...] = acc[...] * corr + pv

    @pl.when(edge >= 2)
    def _finish():
        out = acc[...] / jnp.maximum(l[:, :, :1], 1e-30)
        o_ref[...] = out[:, :o_ref.shape[1]]


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "ppb",
                                             "window"))
def _paged_pallas(q, k_pages, v_pages, lengths, page_indices, scale,
                  interpret, ppb=None, window=None, work=None):
    """The float pool's kernel (module docstring). `ppb`, pages a block, is
    `_pages_per_block`'s and `work` (the list and its length) `paged_work`'s
    unless a test or a sweep says otherwise. The `pallas_call` is named
    `paged_attention`: once a layer and scan step, under the name the
    benchmark's reader looks for.

    A table a row AND K/V head (`page_indices [B, Hkv, n]`, `lengths
    [B, Hkv]` keys in table order: ops/sparse_paged_attention.py, whose
    kept blocks differ by K/V head) walks the same list with one (row, head)
    pair where a row stood: a page operand then holds its own head alone.

    `window` (a row sees its last `window` keys only): the row's walk starts
    at the block of its first visible key, so the cost stops growing with
    the row. The table is read at the row's LOGICAL pages: a ring of pages
    (`WindowRingSpec`) is a table whose entries repeat."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Hq, D = q.shape
    Hkv, _, bs, _ = k_pages.shape
    group = Hq // Hkv
    gp = -(-group // _SUBLANES) * _SUBLANES
    by_head = page_indices.ndim == 3
    hb = 1 if by_head else Hkv            # K/V heads a page operand holds
    if by_head:
        B, lengths = B * Hkv, lengths.reshape(-1)
        page_indices = page_indices.reshape(B, -1)
    npages = page_indices.shape[1]
    ppb = ppb or _pages_per_block(jax.ShapeDtypeStruct(
        (hb,) + k_pages.shape[1:], k_pages.dtype), npages)
    lengths = lengths.astype(jnp.int32)
    if work is None:
        work = paged_work(lengths, bs, ppb, npages, window)
    work, n_steps = work

    def page_map(pg):
        def index(p, row, blk, edge, held, lens, pt):
            # past the row's pages the operand stays on the page it held a
            # block before (the scratch page if it held none): a block
            # index that repeats between steps is not fetched again
            b, at = row[p], blk[p] * ppb + pg
            at = jnp.where(at > held[p], at - ppb, at)
            return (jax.lax.rem(b, Hkv) if by_head else 0,
                    jnp.where(at < 0, 0, pt[b, jnp.maximum(at, 0)]), 0, 0)
        return index

    def row_map(p, row, *_):
        return (row[p], 0, 0, 0)

    qs = (q * scale).astype(k_pages.dtype).reshape(B, hb, group, D)
    qs = jnp.pad(qs, ((0, 0), (0, 0), (0, gp - group), (0, 0)))
    page_bytes = hb * bs * D * k_pages.dtype.itemsize
    fn = pl.pallas_call(
        functools.partial(_decode_kernel, ppb, window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(n_steps,),
            in_specs=[pl.BlockSpec((None, hb, gp, D), row_map)]
            + [pl.BlockSpec((hb, None, bs, D), page_map(pg))
               for pg in range(ppb)] * 2,
            out_specs=pl.BlockSpec((None, hb, group, D), row_map),
            scratch_shapes=[pltpu.VMEM((hb, gp, D), jnp.float32),
                            pltpu.VMEM((hb, gp, _LANES), jnp.float32),
                            pltpu.VMEM((hb, gp, _LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, hb, group, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # every page operand twice (the pipeline's two buffers), the
            # block's K and V gathered and widened once more, and room
            vmem_limit_bytes=int(12 * ppb * page_bytes) + (16 << 20)),
        interpret=interpret,
        name="paged_attention",
    )
    out = fn(*work, lengths, page_indices.astype(jnp.int32), qs,
             *([k_pages] * ppb), *([v_pages] * ppb))
    # the list never visits a row of length 0: its block is whatever was there
    out = jnp.where((lengths > 0)[:, None, None],
                    out.reshape(B, hb * group, D), 0.0)
    return out.reshape(q.shape).astype(q.dtype)


#: K (or V) bytes a grid step folds, every KV head of its pages: 8 pages at
#: 32 heads x 16 x 128 bf16, 32 at 8 heads, where each was the sweep's best at
#: long contexts (half loses 9% there, double 15% at short ones; PERF.md PR 32)
_BLOCK_BYTES = 1 << 20
#: page operands a pool and step: 2 x 32 + q compile and ran at 7B widths
_MAX_PAGES = 32


def _pages_per_block(k_pages, npages):
    """Pages a grid step folds, from the shape alone (page size, KV heads,
    head dim, dtype): as many as fill `_BLOCK_BYTES`, at most `_MAX_PAGES`
    and the table's width. VMEM follows: the step holds each page operand
    twice and the gathered block once, ~6 `_BLOCK_BYTES` for K and V."""
    hkv, _, bs, d = k_pages.shape
    page_bytes = hkv * bs * d * k_pages.dtype.itemsize
    return max(1, min(_BLOCK_BYTES // page_bytes, _MAX_PAGES, npages))


def _jax_kernel(q, k_pages, v_pages, lengths, page_indices, scale):
    """jax's paged-attention kernel, for the int8 pool. 32 pages a compute
    block (the default 8 was 1.1-1.5x slower on either pool from 350 tokens
    a row up, PERF.md PR 32), fewer where the table's width asks."""
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        paged_attention as _kernel,
    )

    blk = min(32, page_indices.shape[1])
    while page_indices.shape[1] % blk:
        blk -= 1
    out = _kernel((q * scale).astype(jnp.bfloat16), k_pages, v_pages,
                  lengths.astype(jnp.int32), page_indices,
                  pages_per_compute_block=blk)
    # it skips a row of length 0 and leaves its output block unwritten
    return jnp.where((lengths > 0)[:, None, None], out, 0.0).astype(q.dtype)


def paged_decode_attention(q, k_pages, v_pages, lengths, page_indices,
                           scale=None, impl=None, window=None):
    """One-token decode attention over the paged pool.

    q: [B, Hq, D]; returns [B, Hq, D]. lengths must already INCLUDE the
    just-written token (the query attends to itself); a row of length 0
    attends nothing, costs nothing and returns zeros. `scale` is
    1/sqrt(D) of the STORED width unless given (a pool that stores two
    heads as one gives its own). `window`: the query sees its last `window`
    keys, itself among them, and the kernel never reads a block below them
    (float pools). impl: None/"auto"
    (a kernel on TPU, where its failure raises; the math tier elsewhere),
    "math", "pallas" (the float pool's kernel in interpret mode off TPU)."""
    global LAST_IMPL
    from .flash_attention import _FORCE_XLA, _on_tpu

    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    impl = impl or "auto"
    on_tpu = _on_tpu() and not _FORCE_XLA
    if impl == "pallas" or (impl == "auto" and on_tpu):
        if is_quantized(k_pages):
            if not on_tpu or window is not None:
                raise ValueError("the int8 pool's kernel is jax's, which "
                                 "has no interpret mode off a TPU and no "
                                 "window")
            out = _jax_kernel(q, k_pages, v_pages, lengths, page_indices,
                              scale)
        else:
            out = _paged_pallas(q, k_pages, v_pages, lengths, page_indices,
                                scale, interpret=not on_tpu, window=window)
        LAST_IMPL = "paged-kernel" if on_tpu else "paged-kernel-interpret"
        return out
    LAST_IMPL = "paged-math"
    return _paged_math(q, k_pages, v_pages, lengths, page_indices, scale,
                       window)
