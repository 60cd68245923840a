"""Block-selected sparse attention over K and V pages (reference: InfLLM-v2,
the trainable sparse attention of the MiniCPM4 family; models/minicpm_sala.py
is the block that uses it).

A layer caches K and V pages exactly as ops/paged_attention.py lays them out
(`[Hkv, P, bs, D]`, same writers, same layout contract) at a page size equal
to the selection BLOCK, plus one plane of COMPRESSED KEYS for the selector:
`c_j = mean(k[stride j : stride j + kernel))`, `bs / stride` of them a page
(those that START in the page), stored `[Hkv, P * bs/stride, D]` so that a
row's compressed keys are a gather by its page table like everything else.
The writers fill the plane as tokens complete a stride.

For a query at position t and a K/V head g (`select_blocks`):

    p   = sum over the group's query heads of softmax_j(q . c_j * scale)
          over the compressed keys complete at t (stride j + kernel - 1 <= t)
    b_m = max of p over the compressed keys that overlap block m
    block 0.. `init_blocks` and the `window_size / block_size` blocks ending
    at t's own are forced in; the `topk` highest are kept (all, if fewer are
    visible); a query with t + 1 <= `dense_len` keeps every visible block.

Attention is causal softmax over the keys of the kept blocks, shared by the
group's heads. With page = block the kept set of a decode step IS a page
table a row and K/V head, so decode (`sparse_decode_attention`) hands
ops/paged_attention.py's kernel a table per K/V head and reads the kept
pages only. The packed stream of a mixed step (`sparse_ragged_attention`)
takes its spans of two or more tokens through `sparse_prefill_attention`: a
tiled pass over the row's pages that masks what a query did not keep and
skips a tile of pages nobody in the query tile kept — exactly the kept set,
but read as a low share of the roofline, which is where a `perf_opt` starts.

Tier of the packed prefill (`LAST_IMPL`, at trace time): `sparse-prefill-xla`
on every backend; a Mosaic kernel (`sparse-prefill-kernel`) is ROADMAP's.
Decode's tiers are ops/sparse_decode_attention.py's.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp

from .cache_specs import cache_view
from .paged_attention import write_token_kv
from .ragged_paged_attention import write_ragged_kv

LAST_IMPL = None  # "sparse-prefill-xla" — at trace time


@dataclasses.dataclass(frozen=True)
class SparseConfig:
    """The family's `sparse_config` (MiniCPM4 / InfLLM-v2)."""

    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    def __post_init__(self):
        ks, st, bs = self.kernel_size, self.kernel_stride, self.block_size
        if bs % st or ks % st or ks > bs + st:
            raise ValueError(
                f"sparse_config: block_size {bs} and kernel_size {ks} must "
                f"be multiples of kernel_stride {st}, kernel_size <= "
                "block_size + kernel_stride")
        if self.window_size % bs or self.window_size < ks:
            raise ValueError("sparse_config: window_size is whole blocks "
                             "and covers a compressed key")
        if self.init_blocks + self.window_size // bs > self.topk:
            raise ValueError("sparse_config: the forced blocks exceed topk")

    @property
    def per_page(self):
        """Compressed keys that start in one block."""
        return self.block_size // self.kernel_stride

    @property
    def window_blocks(self):
        return self.window_size // self.block_size

    def table_width(self, npages):
        """Entries a decode step's kept-page table needs: `topk`, or every
        block a query under `dense_len` sees."""
        dense = -(-self.dense_len // self.block_size)
        return min(npages, max(self.topk, dense))


@cache_view("k_pages", "v_pages", "c_keys", "page_indices", "lengths", "live")
class SelectedPagedLayerCache:
    """One sparse layer's cache seen by a decode step: PagedLayerCache's
    fields (same meaning) plus the compressed-key plane
    `c_keys [Hkv, P * per_page, D]`."""

    k_pages: jax.Array
    v_pages: jax.Array
    c_keys: jax.Array
    page_indices: jax.Array
    lengths: jax.Array
    live: jax.Array


@cache_view("k_pages", "v_pages", "c_keys", "page_indices", "kv_lens",
         "cu_q_lens", "row_of", "token_pos", "valid")
class SelectedRaggedLayerCache:
    """One sparse layer's cache seen by a mixed step: RaggedLayerCache's
    fields (same meaning) plus the compressed-key plane."""

    k_pages: jax.Array
    v_pages: jax.Array
    c_keys: jax.Array
    page_indices: jax.Array
    kv_lens: jax.Array
    cu_q_lens: jax.Array
    row_of: jax.Array
    token_pos: jax.Array
    valid: jax.Array


class SelectedKVSpec:
    """The cache of ONE block-selecting attention layer (ops/cache_specs.py
    puts a model's layers together): a pool is (k_pages, v_pages, c_keys).
    K and V are KVCacheSpec's pools at `page_size == block_size`."""

    kind = "selected K/V pages"
    allocator_pages = True
    has_state = False

    def __init__(self, num_kv_heads, head_dim, sparse):
        self.num_kv_heads, self.head_dim = num_kv_heads, head_dim
        self.sparse = sparse

    def make_pool(self, num_pages, page_size, dtype, kv_cache_dtype=None,
                  max_seqs=None, prefill_chunk=None):
        if page_size != self.sparse.block_size:
            raise ValueError(
                f"page_size {page_size}: a block-selecting layer keeps "
                f"pages of one selection block ({self.sparse.block_size} "
                "tokens), so that the kept blocks are a page table")
        if kv_cache_dtype not in (None, "model"):
            raise ValueError(
                f"kv_cache_dtype={kv_cache_dtype!r}: the quantised pool "
                "plane has no compressed-key plane for the selector "
                f"({type(self).__name__})")
        shape = (self.num_kv_heads, num_pages, page_size, self.head_dim)
        plane = (self.num_kv_heads, num_pages * self.sparse.per_page,
                 self.head_dim)
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                jnp.zeros(plane, dtype))

    def refuses(self, plane):
        return None

    @staticmethod
    def paged(pool, page_table, lengths, live):
        return SelectedPagedLayerCache(*pool, page_table, lengths, live)

    @staticmethod
    def ragged(pool, page_table, kv_lens, cu, row_of, token_pos, valid):
        return SelectedRaggedLayerCache(*pool, page_table, kv_lens, cu,
                                        row_of, token_pos, valid)

    @staticmethod
    def pool_of(present):
        return (present.k_pages, present.v_pages, present.c_keys)


# ---- writers ----------------------------------------------------------------

def _write_compressed(c_keys, k_pages, page_indices, rows, pos, done, sp):
    """Compressed keys that a step's tokens completed. `rows`, `pos` [N]:
    the row and position of a token; `done` [N]: whether it completes one
    (it is a request's token at the last position of a kernel). The key's
    `kernel_size` keys are read back from the pool (they may lie in an
    earlier chunk's pages); the others write the scratch page's plane."""
    bs, ks, st = sp.block_size, sp.kernel_size, sp.kernel_stride
    done = done & (pos >= ks - 1) & ((pos - (ks - 1)) % st == 0)
    start = jnp.where(done, pos - (ks - 1), 0)
    span = start[:, None] + jnp.arange(ks)[None]                   # [N, ks]
    page = jnp.where(done[:, None], page_indices[rows[:, None], span // bs], 0)
    # the head a scattered index, as the writers make it (the pool's layout
    # contract, ops/paged_attention.py): a window over Hkv would re-lay out
    # the whole pool around every kernel call
    heads = jnp.arange(k_pages.shape[0])[:, None, None]
    keys = k_pages[heads, page[None], (span % bs)[None]]       # [Hkv,N,ks,D]
    mean = jnp.mean(keys.astype(jnp.float32), axis=2).astype(c_keys.dtype)
    at = jnp.where(done,
                   page_indices[rows, start // bs] * sp.per_page
                   + (start % bs) // st, 0)
    h = jnp.arange(c_keys.shape[0])[:, None]
    return c_keys.at[h, at[None]].set(mean)


def write_token_selected(pc, k_new, v_new, sp):
    """One new token a row (a decode step): K and V by `write_token_kv`,
    then the compressed key the token may have completed."""
    k_pages = write_token_kv(pc.k_pages, pc.page_indices, pc.lengths, k_new)
    v_pages = write_token_kv(pc.v_pages, pc.page_indices, pc.lengths, v_new)
    rows = jnp.arange(pc.lengths.shape[0])
    c_keys = _write_compressed(pc.c_keys, k_pages, pc.page_indices, rows,
                               pc.lengths, pc.live, sp)
    return k_pages, v_pages, c_keys


def write_ragged_selected(pc, k_new, v_new, sp):
    """A packed stream's tokens (a mixed step): K and V a page at a time by
    `write_ragged_kv`, then the compressed keys the stream completed — at
    most one a `kernel_stride` tokens and one more a row."""
    k_pages = write_ragged_kv(pc.k_pages, pc.page_indices, pc.row_of,
                              pc.token_pos, pc.valid, k_new)
    v_pages = write_ragged_kv(pc.v_pages, pc.page_indices, pc.row_of,
                              pc.token_pos, pc.valid, v_new)
    T = pc.row_of.shape[0]
    ks, st = sp.kernel_size, sp.kernel_stride
    ends = (pc.valid & (pc.token_pos >= ks - 1)
            & ((pc.token_pos - (ks - 1)) % st == 0))
    n = T // st + pc.page_indices.shape[0] + 1
    (at,) = jnp.nonzero(ends, size=n, fill_value=T)
    found = at < T
    at = jnp.minimum(at, T - 1)
    c_keys = _write_compressed(pc.c_keys, k_pages, pc.page_indices,
                               pc.row_of[at], pc.token_pos[at], found, sp)
    return k_pages, v_pages, c_keys


# ---- the selector -----------------------------------------------------------

def _row_compressed(c_keys, table, sp):
    """A row's compressed keys in position order: [Hkv, npages * per, D]."""
    per = sp.per_page
    at = (table[:, None] * per + jnp.arange(per)[None]).reshape(-1)
    return c_keys[jnp.arange(c_keys.shape[0])[:, None], at[None]]


def block_mask(logits, t, sp, nblocks):
    """The kept blocks. logits [N, Hkv, G, nblocks * per_page] float32
    (q . c_j * scale against the row's compressed keys in order); t [N] the
    queries' positions. Returns [N, Hkv, nblocks] bool."""
    bs, ks, st, per = (sp.block_size, sp.kernel_size, sp.kernel_stride,
                       sp.per_page)
    N, Hkv, _, J = logits.shape
    j = jnp.arange(J)
    n_c = jnp.where(t >= ks - 1, (t - (ks - 1)) // st + 1, 0)
    vis = (j[None] < n_c[:, None])[:, None, None, :]               # [N,1,1,J]
    lg = jnp.where(vis, logits, -1e30)
    e = jnp.where(vis, jnp.exp(lg - lg.max(axis=-1, keepdims=True)), 0.0)
    p = e / jnp.maximum(e.sum(axis=-1, keepdims=True), 1e-30)
    pg = jnp.where(vis[:, :, 0], p.sum(axis=2), -1.0)              # [N,Hkv,J]
    pb = pg.reshape(N, Hkv, nblocks, per)
    score = pb.max(axis=-1)
    # compressed keys that start in block m-1 and reach into block m
    spill = [r for r in range(per) if st * r + ks - 1 >= bs]
    if spill:
        over = pb[..., spill[0]:].max(axis=-1)
        score = jnp.maximum(score, jnp.concatenate(
            [jnp.full_like(over[..., :1], -1.0), over[..., :-1]], axis=-1))
    m = jnp.arange(nblocks)[None]
    own = (t // bs)[:, None]
    seen = (m <= own)[:, None, :]                                  # [N,1,nb]
    forced = ((m < sp.init_blocks) | (m > own - sp.window_blocks))[:, None, :]
    score = jnp.where(forced, 1e30, jnp.maximum(score, 0.0))
    score = jnp.where(seen, score, -1.0)
    vals, idx = jax.lax.top_k(score, min(sp.topk, nblocks))
    kept = jnp.any((idx[..., None] == m[0]) & (vals >= 0.0)[..., None],
                   axis=-2)
    dense = (t + 1 <= sp.dense_len)[:, None, None]
    return jnp.where(dense, jnp.broadcast_to(seen, kept.shape), kept)


def kept_keys(mask, t, sp):
    """Keys a query attends under `mask` [N, Hkv, nblocks] (its own block
    holds t % bs + 1 of them), summed: int32."""
    n = mask.sum(axis=-1).astype(jnp.int32)
    own = (t % sp.block_size + 1)[:, None]
    return jnp.sum(jnp.where(n > 0, (n - 1) * sp.block_size + own, 0))


def select_blocks(q, c_keys, table, t, sp, scale):
    """Kept blocks for queries of ONE row. q [N, Hq, D]; `table` the row's
    page table [npages]; t [N] positions. Returns [N, Hkv, npages] bool."""
    Hkv = c_keys.shape[0]
    N, Hq, D = q.shape
    ck = _row_compressed(c_keys, table, sp)
    logits = jnp.einsum("nhgd,hjd->nhgj", q.reshape(N, Hkv, Hq // Hkv, D), ck,
                        preferred_element_type=jnp.float32) * scale
    return block_mask(logits, t, sp, table.shape[0])


# ---- the packed stream ------------------------------------------------------

#: the packed prefill's walk: queries a tile, pages a fold (128 x 1,024 keys
#: at the cell's page of 64: a [2, 16, 128, 1024] f32 score block, 16 MB)
_Q_TILE, _KV_PAGES = 128, 16


def sparse_prefill_attention(q, k_pages, v_pages, c_keys, page_indices,
                             kv_lens, cu_q_lens, sp, scale=None):
    """The packed stream's spans of two or more tokens. q [T, Hq, D]
    (row b owns tokens cu_q_lens[b] : cu_q_lens[b+1]); kv_lens [S] the
    rows' tokens AFTER this step's writes. A span is walked a tile of
    `_Q_TILE` queries at a time: the selector gives the tile's kept blocks,
    then the row's pages go by `_KV_PAGES` at a time through an online
    softmax that masks what a query did not keep (a stretch of pages
    nobody in the tile kept is skipped). Returns (o [T, Hq, D], keys kept,
    keys visible); rows of one token or none are left zero."""
    global LAST_IMPL
    LAST_IMPL = "sparse-prefill-xla"
    T, Hq, D = q.shape
    Hkv, _, bs, _ = k_pages.shape
    G = Hq // Hkv
    npages = page_indices.shape[1]
    TQ, CP = _Q_TILE, min(_KV_PAGES, npages)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    q_lens = cu_q_lens[1:] - cu_q_lens[:-1]
    before = kv_lens - q_lens
    qp = jnp.pad(q, ((0, TQ), (0, 0), (0, 0)))
    wide = -(-npages // CP) * CP
    i = jnp.arange(TQ)
    h = jnp.arange(Hkv)[:, None]

    def tile_step(ti, carry, r, table):
        o, kept, visible = carry
        n = q_lens[r]
        at = cu_q_lens[r] + ti * TQ
        held = ti * TQ + i < n
        pos = jnp.where(held, before[r] + ti * TQ + i, 0)
        qt = jax.lax.dynamic_slice_in_dim(qp, at, TQ)
        with jax.named_scope("sala.select"):
            mask = select_blocks(qt, c_keys, table[:npages], pos, sp, scale)
            mask = mask & held[:, None, None]
        kept += kept_keys(mask, pos, sp)
        visible += jnp.sum(jnp.where(held, pos + 1, 0)) * Hkv
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, wide - npages)))
        qs = (qt * scale).astype(k_pages.dtype).reshape(TQ, Hkv, G, D)

        def kv_step(c, st):
            sel = jax.lax.dynamic_slice_in_dim(mask, c * CP, CP, axis=2)

            def fold(st):
                acc, l, mx = st
                pages = jax.lax.dynamic_slice_in_dim(table, c * CP, CP)
                kb = k_pages[h, pages[None]].reshape(Hkv, CP * bs, D)
                vb = v_pages[h, pages[None]].reshape(Hkv, CP * bs, D)
                s = jnp.einsum("nhgd,hkd->hgnk", qs, kb,
                               preferred_element_type=jnp.float32)
                kpos = c * CP * bs + jnp.arange(CP * bs)
                ok = (jnp.repeat(sel, bs, axis=-1)
                      & (kpos[None, None, :] <= pos[:, None, None]))
                ok = jnp.transpose(ok, (1, 0, 2))[:, None]     # [Hkv,1,TQ,K]
                s = jnp.where(ok, s, -1e30)
                m_new = jnp.maximum(mx, s.max(axis=-1))
                p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
                corr = jnp.exp(mx - m_new)
                l = l * corr + p.sum(axis=-1)
                acc = acc * corr[..., None] + jnp.einsum(
                    "hgnk,hkd->hgnd", p.astype(vb.dtype), vb,
                    preferred_element_type=jnp.float32)
                return acc, l, m_new

            return jax.lax.cond(jnp.any(sel), fold, lambda st: st, st)

        n_kv = (jnp.max(pos) + CP * bs) // (CP * bs)
        acc, l, _ = jax.lax.fori_loop(0, n_kv, kv_step, (
            jnp.zeros((Hkv, G, TQ, D), jnp.float32),
            jnp.zeros((Hkv, G, TQ), jnp.float32),
            jnp.full((Hkv, G, TQ), -1e30, jnp.float32)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        out = jnp.transpose(out, (2, 0, 1, 3)).reshape(TQ, Hq, D)
        old = jax.lax.dynamic_slice_in_dim(o, at, TQ)
        o = jax.lax.dynamic_update_slice_in_dim(
            o, jnp.where(held[:, None, None], out.astype(o.dtype), old),
            at, axis=0)
        return o, kept, visible

    def row_step(r, carry):
        def span(carry):
            table = jnp.pad(page_indices[r], (0, wide - npages))
            return jax.lax.fori_loop(
                0, (q_lens[r] + TQ - 1) // TQ,
                lambda ti, cr: tile_step(ti, cr, r, table), carry)

        return jax.lax.cond(q_lens[r] > 1, span, lambda c: c, carry)

    zero = jnp.zeros((), jnp.int32)
    # the whole walk under one scope (its `while` covers the selector's
    # `sala.select` inside): what the benchmark reads as this op's time
    with jax.named_scope("sala.sparse.prefill"):
        o, kept, visible = jax.lax.fori_loop(
            0, q_lens.shape[0], row_step,
            (jnp.zeros((T + TQ, Hq, D), q.dtype), zero, zero))
    return o[:T], kept, visible


def sparse_ragged_attention(q, pc, sp, scale=None, impl=None):
    """A mixed step's packed stream through one sparse layer's (already
    written) cache view `pc`: spans of two or more tokens by
    `sparse_prefill_attention`, one-token spans by
    `sparse_decode_attention` (each at its span's start). Returns
    (o [T, Hq, D], keys kept, keys visible)."""
    from .sparse_decode_attention import sparse_decode_attention

    T = q.shape[0]
    q_lens = pc.cu_q_lens[1:] - pc.cu_q_lens[:-1]
    o, kept, visible = sparse_prefill_attention(
        q, pc.k_pages, pc.v_pages, pc.c_keys, pc.page_indices, pc.kv_lens,
        pc.cu_q_lens, sp, scale)
    at = jnp.minimum(pc.cu_q_lens[:-1], T - 1)
    one = q_lens == 1
    o1, kept1, visible1 = sparse_decode_attention(
        q[at], pc.k_pages, pc.v_pages, pc.c_keys, pc.page_indices,
        jnp.where(one, pc.kv_lens, 0), sp, scale, impl)
    # (a row of no token aliases a neighbour's start: dropped)
    o = o.at[jnp.where(one, at, T)].set(o1, mode="drop")
    return o, kept + kept1, visible + visible1
