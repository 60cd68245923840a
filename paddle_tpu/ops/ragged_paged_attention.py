"""Ragged paged attention — ONE dispatch for mixed prefill+decode rows
(PAPERS.md: "Ragged Paged Attention: A High-Performance and Flexible LLM
Inference Kernel for TPU").

The paged decode kernel (ops/paged_attention.py) answers one query token
per sequence; prompts had to be prefilled by a separate dense program per
bucket, chunk-prefilled *between* decode blocks, and decode itself ran a
program per (bucket, block) rung. This kernel removes the split: a batch
step is a PACKED token stream `q: [T, Hq, D]` where row b owns the
contiguous query span `cu_q_lens[b] : cu_q_lens[b+1]` — a 3-token decode
row and a 900-token prefill chunk ride the same grid — attending over the
shared page pool through per-row page tables. One program signature per
(sampling, kv-dtype, lora-rank); the bucket ladder is gone.

Causality is per row: query i of row b (q_len = cu[b+1]-cu[b]) sees kv
positions `< kv_lens[b] - q_len + i + 1`, i.e. the row's full past plus
its own packed prefix. `kv_lens` therefore counts tokens AFTER this
step's writes (the query attends to itself), mirroring the `lengths + 1`
convention of `paged_decode_attention`.

Two tiers, same contract as the decode kernel:
- `_ragged_pallas`: a Pallas grid over the call's live work and nothing
  else, on the pattern of `_paged_pallas`. The work list (`ragged_work`,
  one small XLA fusion before the call) names the (query block, row) PAIRS
  whose row has tokens in the block, in stream order, so the pairs of one
  output block are neighbours; it rides in scalar prefetch beside
  `cu_q_lens` / `kv_lens` / the page table, and the index maps and the
  kernel read the block and the row from it. Both grid bounds are
  OPERANDS: the live pairs, and the kv blocks the longest causal limit
  among them needs. A pair whose own limit ends earlier skips, its page
  operands staying on the last page they held (not fetched again). A step
  folds one kv block of several pages, every KV head at once where VMEM
  allows, into the online softmax of the block's tokens (q grouped
  [Hkv, G x tq, D]: MHA and GQA take the one path); the scratch starts on
  a block's first pair and the output is written on its last. The tiles
  follow from the shape (`_ragged_tiles`). A query block no live row meets
  is never visited and reads zero, as every pad token does. On the v5e a
  mixed call of the serving cell takes 0.08-0.53 ms a layer (0.15 over the
  cell's prompts) where the dense walk over (head block, q block, row, kv
  block) took 5.3 whatever it held (PERF.md PR 34). On TPU a failure of this tier raises; `interpret=True`
  off-TPU so CPU tier-1 exercises the real kernel body.
- `_ragged_math`: lax.scan over page columns with a vectorized per-token
  page gather and online-softmax accumulation — the XLA reference and the
  off-TPU default. The engine's bit-exactness contract is held on this
  tier; kernel-vs-math comparisons use a tolerance (another blocking sums
  in another order).

Both handle the f32 pool and the int8 QuantizedTensor pool (weight
[Hkv, P, bs, D] int8 + per-row absmax scales). The pool's layout contract
(default layout; no write whose scatter window covers Hkv) is stated once,
in ops/paged_attention.py's docstring, and holds for `write_ragged_kv`.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.envs import env_str as _env_str
from .paged_attention import _dequantize, is_quantized, store_kv

LAST_IMPL = None  # "ragged-kernel" | "ragged-kernel-interpret" | "ragged-math"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RaggedLayerCache:
    """One layer's ragged paged cache view — the fourth cache protocol
    models/llama.py recognizes in `past_key_values` (after growing-concat,
    fixed-shape, and PagedLayerCache).

    k_pages/v_pages: [num_kv_heads, num_pages, page_size, head_dim]
                     (or QuantizedTensor pools)
    page_indices:    [S, pages_per_seq] int32 rows into the pool
    kv_lens:         [S] int32 — valid tokens per row AFTER this step's
                     writes land (post-write totals; self-attention incl.)
    cu_q_lens:       [S+1] int32 — packed query span boundaries
    row_of:          [T] int32 — owning row per packed token (pad -> any)
    token_pos:       [T] int32 — absolute kv position per packed token
    valid:           [T] bool — False for pad tokens (writes -> scratch)
    """

    k_pages: jax.Array
    v_pages: jax.Array
    page_indices: jax.Array
    kv_lens: jax.Array
    cu_q_lens: jax.Array
    row_of: jax.Array
    token_pos: jax.Array
    valid: jax.Array

    def tree_flatten(self):
        return (self.k_pages, self.v_pages, self.page_indices, self.kv_lens,
                self.cu_q_lens, self.row_of, self.token_pos, self.valid), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def page_size(self):
        k = self.k_pages
        return (k.weight if is_quantized(k) else k).shape[2]


def write_ragged_kv(pages, page_indices, row_of, token_pos, valid, new):
    """Store a packed token stream's K or V rows in the pool.

    new: [T, Hkv, D]. Token t lands at absolute position token_pos[t] of
    row row_of[t] -> page page_indices[row_of[t], token_pos[t]//bs],
    offset token_pos[t] % bs. Invalid (pad) tokens are routed to the
    scratch page 0 offset 0; they collide only with each other, and the
    scratch page is never read.

    The stream is written a page at a time (`_merge_pages`), which needs
    what the packed stream gives: a row's tokens are neighbours at
    consecutive positions, pads trail, and rows write pages of their own.
    Such a stream changes page at most T//bs + 2 S times, pads included."""
    bs = (pages.weight if is_quantized(pages) else pages).shape[2]
    page_of = jnp.where(
        valid, page_indices[row_of, token_pos // bs], 0)  # [T]
    off = jnp.where(valid, token_pos % bs, 0)             # [T]
    n_runs = row_of.shape[0] // bs + 2 * page_indices.shape[0] + 1
    return store_kv(
        pages, new,
        lambda plane, rows: _merge_pages(plane, page_of, off, rows, n_runs))


def _merge_pages(plane, page_of, off, rows, n_runs):
    """rows [Hkv, T, last] -> plane[:, page_of[t], off[t]], by whole pages.

    A run is a stretch of the stream that stays on one page. Each run's
    page is read, its new rows are laid over it, and the page is written
    back: T//bs + 2 S scatter rows of bs x last a head where a scatter by
    token has T rows of `last` (0.25 ms against 1.26 ms a write at the
    serving cell's widths, PERF.md PR 27). Two runs on one page would each
    write back the other's old rows, hence the stream contract above; on
    the scratch page that is harmless. The head is a scattered index
    (layout contract, ops/paged_attention.py)."""
    Hkv, T, last = rows.shape
    P, bs = plane.shape[1:3]
    turns = (page_of[1:] != page_of[:-1]).astype(jnp.int32)
    run_of = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(turns)])
    # unused runs point past the pool: read clipped, written nowhere
    run_page = jnp.full((n_runs,), P, jnp.int32).at[run_of].set(
        page_of, mode="drop")
    token_at = jnp.full((n_runs * bs,), T, jnp.int32).at[
        run_of * bs + off].set(jnp.arange(T), mode="drop")
    is_new = (token_at < T).reshape(1, n_runs, bs, 1)
    fresh = jnp.take(rows, token_at, axis=1, mode="clip").reshape(
        Hkv, n_runs, bs, last)
    at = plane.at[jnp.arange(Hkv)[:, None], run_page[None]]
    return at.set(jnp.where(is_new, fresh, at.get(mode="clip")), mode="drop")


def _ragged_meta(cu_q_lens, row_of, kv_lens):
    """Per-token attention limit from the packed-span boundaries.

    limit[t] = kv_lens[row] - q_len[row] + q_pos[t] + 1 — the ragged
    causal rule; 0 for pad tokens so they attend nothing (their output is
    discarded anyway, but a fully-masked softmax must stay finite)."""
    q_lens = cu_q_lens[1:] - cu_q_lens[:-1]                      # [S]
    t = jnp.arange(row_of.shape[0])
    q_pos = t - cu_q_lens[row_of]                                # [T]
    valid = t < cu_q_lens[-1]
    limit = jnp.where(
        valid, kv_lens[row_of] - q_lens[row_of] + q_pos + 1, 0)  # [T]
    return limit


def _ragged_math(q, k_pages, v_pages, kv_lens, page_indices, cu_q_lens,
                 scale, window=None):
    """Online-softmax over page columns for a packed ragged batch.

    q: [T, Hq, D]. Each scan step gathers ONE page per packed token (a
    [T, Hkv, bs, D] slab — bounded by T, never by S × pages_per_seq), so
    peak temp matches `_paged_math`'s shape generalized from one decode
    token per row to the packed stream."""
    T, Hq, D = q.shape
    kq, vq = is_quantized(k_pages), is_quantized(v_pages)
    Hkv = (k_pages.weight if kq else k_pages).shape[0]
    bs = (k_pages.weight if kq else k_pages).shape[2]
    npages = page_indices.shape[1]
    group = Hq // Hkv

    row_of = jnp.clip(
        jnp.searchsorted(cu_q_lens, jnp.arange(T), side="right") - 1,
        0, cu_q_lens.shape[0] - 2)
    limit = _ragged_meta(cu_q_lens, row_of, kv_lens)             # [T]

    qs = (q * scale).astype(jnp.float32).reshape(T, Hkv, group, D)
    o0 = jnp.zeros((T, Hkv, group, D), jnp.float32)
    l0 = jnp.zeros((T, Hkv, group), jnp.float32)
    m0 = jnp.full((T, Hkv, group), -1e30, jnp.float32)

    def gather(pages, quant, pid):
        if quant:
            return _dequantize(
                jnp.swapaxes(pages.weight[:, pid], 0, 1),
                jnp.swapaxes(pages.scales[:, pid], 0, 1),
            )
        return jnp.swapaxes(pages[:, pid], 0, 1).astype(jnp.float32)

    def body(j, carry):
        o, l, m = carry
        pid = page_indices[row_of, j]                            # [T]
        kb = gather(k_pages, kq, pid)                            # [T,Hkv,bs,D]
        vb = gather(v_pages, vq, pid)
        s = jnp.einsum("thgd,thkd->thgk", qs, kb)                # [T,Hkv,g,bs]
        pos = j * bs + jnp.arange(bs)
        seen = pos[None, None, None, :] < limit[:, None, None, None]
        if window is not None:  # the token's last `window` keys, its own too
            seen &= pos[None, None, None, :] >= (limit - window)[
                :, None, None, None]
        s = jnp.where(seen, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        o = o * corr[..., None] + jnp.einsum("thgk,thkd->thgd", p, vb)
        return (o, l, m_new)

    # dynamic trip count: pages past every live row's KV extent are fully
    # masked (p underflows to exactly 0.0), so skipping them is
    # bit-identical — and the serving page tables are max_len wide while
    # typical live KV is a few pages. fori_loop keeps ONE program
    # signature (the bound is an operand, not a shape); the TPU path never
    # sees this loop (the Pallas kernel masks blocks in-grid).
    q_lens = cu_q_lens[1:] - cu_q_lens[:-1]
    n_live = jnp.max(jnp.where(q_lens > 0, (kv_lens + bs - 1) // bs, 0))
    (o, l, _) = jax.lax.fori_loop(
        0, jnp.minimum(n_live, npages), body, (o0, l0, m0))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(T, Hq, D).astype(q.dtype)


_LANES = 128  # m/l scratch keep a lane-aligned last dim

#: query tokens a grid step holds: a row that straddles n tiles reads its K
#: and V n times, a one-token row costs a whole tile's products. On the v5e,
#: over the chat cell's prompts as mixed calls, 64 took 152 us a call, 128
#: 159 and 32 196; 16 decode rows alone 213, 341 and 162 (PERF.md PR 34)
_Q_TILE = 64
#: K (or V) bytes a grid step folds, every KV head of the step, and the
#: page operands that may take: ops/paged_attention.py's two, for the
#: reasons given there (8 pages at 32 heads x 16 x 128 bf16, 32 at 8 heads)
_BLOCK_BYTES = 1 << 20
_MAX_PAGES = 32
#: what a step may keep resident; the v5e's VMEM holds 128 MiB
_VMEM_BUDGET = 48 << 20


@dataclasses.dataclass(frozen=True)
class RaggedTiles:
    """A call's blocking: `tq` query tokens a block, `hb` KV heads a grid
    step, `ppb` pages a kv block; `vmem` bytes the step keeps resident."""

    tq: int
    hb: int
    ppb: int
    vmem: int

    def grid(self, n_tokens, page_size, npages):
        """(query blocks of the stream, kv positions a block, kv blocks of
        a row's table)."""
        return (-(-n_tokens // self.tq), self.ppb * page_size,
                -(-npages // self.ppb))


def _ragged_tiles(T, Hq, k_pages, npages):
    """The kernel's tiles from the shape alone (query heads, KV heads, page
    size, head dim, pool dtype, stream and table width), in the manner of
    `paged_attention._pages_per_block`: a query tile of `_Q_TILE` tokens,
    as many pages a kv block as fill `_BLOCK_BYTES` over all KV heads, and
    every KV head a step unless the step's resident set (blocks twice, the
    pipeline's two buffers; the gathered K and V; f32 scores, weights and
    scratch) passes `_VMEM_BUDGET`, then the largest divisor that fits."""
    kq = is_quantized(k_pages)
    kw = k_pages.weight if kq else k_pages
    Hkv, _, bs, D = kw.shape
    group = Hq // Hkv
    itemsize = jnp.dtype(kw.dtype).itemsize
    cbytes = 4 if kq else itemsize  # int8 pages dequantize to f32
    tq = min(_Q_TILE, -(-T // 8) * 8)
    ppb = max(1, min(_BLOCK_BYTES // (Hkv * bs * D * itemsize), _MAX_PAGES,
                     npages))
    kv_blk = ppb * bs

    def resident(hb):
        rows = hb * group * tq
        # an int8 page rides with its scales, a lane tile a row in VMEM
        page = hb * bs * (D * itemsize + (4 * _LANES if kq else 0))
        return (4 * rows * D * cbytes + rows * (D + 2 * _LANES) * 4
                + 4 * ppb * page + 2 * hb * kv_blk * D * cbytes
                + 3 * rows * kv_blk * 4)

    hb = next(h for h in range(Hkv, 0, -1)
              if Hkv % h == 0 and (resident(h) <= _VMEM_BUDGET or h == 1))
    return RaggedTiles(tq, hb, ppb, resident(hb))


def ragged_work(cu_q_lens, kv_lens, tq, n_qblocks, kv_blk, xp=jnp,
                window=None):
    """The kernel's work list from the packed spans, for `jnp` (before the
    call, one small fusion, no sort) and for numpy (the engine's counter).

    Returns (work [4, n_qblocks + S - 1] int32, n_pairs, n_kv). Pair p, in
    stream order: work[0, p] its query block, work[1, p] its row, work[2, p]
    the kv positions its block's last in-row token may see (the pair's
    causal limit), work[3, p] bit 0 set on a block's first pair and bit 1
    on its last. A pair is a
    (query block, row) whose row has tokens in the block: rows are
    contiguous spans, so row b owns the blocks `cu[b] // tq ..
    (cu[b+1] - 1) // tq` and there are at most n_qblocks + S - 1 pairs.
    n_kv is the kv blocks the longest live row needs. Entries past n_pairs
    repeat the last pair's block and row and are never visited.

    With a `window` (a token sees its last `window` keys, its own among
    them) the list has a fifth row, work[4, p]: the kv block that holds the
    lowest key the pair's FIRST in-row token may see, where the pair's walk
    starts; n_kv is then the most blocks any pair walks from there."""
    cu = cu_q_lens.astype(xp.int32)
    kvl = kv_lens.astype(xp.int32)
    S = kvl.shape[0]
    cu0, cu1 = cu[:-1], cu[1:]
    live = cu1 > cu0
    first = cu0 // tq
    count = xp.where(live, (cu1 - 1) // tq - first + 1, 0)       # [S]
    ends = xp.cumsum(count)                                      # [S]
    n_pairs = ends[-1]
    p = xp.arange(n_qblocks + S - 1, dtype=xp.int32)
    p_in = xp.minimum(p, xp.maximum(n_pairs - 1, 0))
    row = xp.minimum(
        xp.sum(ends[None, :] <= p_in[:, None], axis=1), S - 1
    ).astype(xp.int32)
    blk = xp.minimum(first[row] + p_in - (ends[row] - count[row]),
                     n_qblocks - 1)
    # the block's last in-row token bounds what any of its tokens may see
    lim = kvl[row] - xp.maximum(cu1[row] - (blk + 1) * tq, 0)
    edge = xp.ones((1,), bool)
    turn = blk[1:] != blk[:-1]
    is_first = xp.concatenate([edge, turn])
    is_last = xp.concatenate([turn, edge]) | (p == n_pairs - 1)
    work = [blk, row, lim, is_first.astype(xp.int32) + 2 * is_last]
    if window is not None:
        first_tok = xp.maximum(cu0[row], blk * tq)
        low = xp.maximum(kvl[row] - cu1[row] + first_tok + 1 - window, 0)
        work.append(low // kv_blk)
    work = xp.stack(work)
    n_kv = -(-xp.max(xp.where(live, kvl, 0)) // kv_blk)
    if window is not None:
        n_kv = xp.max(xp.where(p < n_pairs, -(-lim // kv_blk) - work[4], 0))
    return work.astype(xp.int32), n_pairs, n_kv


def ragged_walk(cu_q_lens, kv_lens, n_tokens, n_heads, k_pages, npages):
    """(walked, dense): the grid steps `_ragged_pallas` walks a layer for
    these spans, and the steps of the dense (query block x row x kv block)
    walk at the same tiles. Host arithmetic on the engine's own `cu_q_lens`
    (numpy); `k_pages` lends its shape and dtype."""
    tiles = _ragged_tiles(n_tokens, n_heads, k_pages, npages)
    kw = k_pages.weight if is_quantized(k_pages) else k_pages
    Hkv, _, bs, _ = kw.shape
    n_qblocks, kv_blk, nkv = tiles.grid(n_tokens, bs, npages)
    _, n_pairs, n_kv = ragged_work(
        np.asarray(cu_q_lens), np.asarray(kv_lens), tiles.tq, n_qblocks,
        kv_blk, xp=np)
    n_hb = Hkv // tiles.hb
    return (int(n_hb * n_pairs * n_kv),
            n_hb * n_qblocks * len(kv_lens) * nkv)


def _ragged_kernel(bs, group, ppb, quantized, window,
                   # scalar prefetch (order fixed by PrefetchScalarGridSpec)
                   work_ref, cu_ref, kvl_ref, pt_ref,
                   # blocked operands
                   *refs):
    """Grid (head block, live pair p, kv block j); p and j are the reduction
    axes. A step folds `ppb` pages of the pair's row into the online-softmax
    scratch of the pair's query block — tokens outside the row or past their
    causal limit are masked, and a step past the pair's own limit is
    skipped; with a `window` the pair's walk starts at the kv block
    work_ref[4, p] and keys below a token's window are masked.
    q_ref [hb, G x tq, D]: a KV head's query heads lie one after
    another along the rows, so both contractions are 3-D batched dots with
    no K or V repeated. refs: the q block, `ppb` K pages and `ppb` V pages
    [hb, bs, D] (each followed by its scales [hb, bs, 1] for the int8 pool),
    then o_ref and the scratch acc [hb, G x tq, D], m and l [.., 128]."""
    import jax.experimental.pallas as pl

    q_ref = refs[0]
    per = 2 if quantized else 1
    n_in = 1 + 2 * ppb * per
    pages = refs[1:n_in]
    o_ref, acc, m, l = refs[n_in:]
    p, step = pl.program_id(1), pl.program_id(2)
    j = step if window is None else step + work_ref[4, p]
    tq = q_ref.shape[1] // group
    kv_blk = ppb * bs
    i, b, lim_max, edge = (work_ref[0, p], work_ref[1, p], work_ref[2, p],
                           work_ref[3, p])

    @pl.when(((edge & 1) == 1) & (step == 0))
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, -1e30)
        l[...] = jnp.zeros_like(l)

    @pl.when(j * kv_blk < lim_max)
    def _accumulate():
        def load(idx):
            # ppb pages [hb, bs, D] -> one [hb, kv_blk, D] kv block
            blks = []
            for pg in range(ppb):
                w = pages[(idx * ppb + pg) * per][...]
                if quantized:
                    # from_int8: w * scales / 127.5 (per-row absmax)
                    sc = pages[(idx * ppb + pg) * per + 1][...]
                    w = (w.astype(jnp.float32)
                         * (sc.astype(jnp.float32) / 127.5))
                blks.append(w)
            return blks[0] if ppb == 1 else jnp.concatenate(blks, axis=1)

        k_blk, v_blk = load(0), load(1)
        cu0, cu1, kvl = cu_ref[b], cu_ref[b + 1], kvl_ref[b]
        s = jax.lax.dot_general(
            q_ref[...], k_blk, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)        # [hb, G*tq, kv_blk]
        t_ids = i * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
        if group > 1:
            t_ids = jnp.concatenate([t_ids] * group, axis=0)
        in_row = (t_ids >= cu0) & (t_ids < cu1)            # [G*tq, 1]
        lim = kvl - cu1 + t_ids + 1                        # [G*tq, 1]
        kv_pos = j * kv_blk + jax.lax.broadcasted_iota(
            jnp.int32, (1, kv_blk), 1)
        mask = in_row & (kv_pos < lim)
        if window is not None:
            mask &= kv_pos >= lim - window
        mask = mask[None]                              # [1, G*tq, kv_blk]
        s = jnp.where(mask, s, -1e30)
        m_prev = m[:, :, :1]                               # [hb, G*tq, 1]
        l_prev = l[:, :, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        w = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        m[...] = jnp.broadcast_to(m_new, m.shape)
        l[...] = jnp.broadcast_to(
            l_prev * corr + w.sum(axis=-1, keepdims=True), l.shape)
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            w.astype(v_blk.dtype), v_blk, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # [hb, G*tq, D]

    @pl.when((edge >= 2) & (step == pl.num_programs(2) - 1))
    def _finalize():
        o_ref[...] = (acc[...] / jnp.maximum(l[:, :, :1], 1e-30)
                      ).astype(o_ref.dtype)


def _ragged_pallas(q, k_pages, v_pages, kv_lens, page_indices, cu_q_lens,
                   scale, interpret, tiles=None, window=None):
    """The kernel tier (module docstring). `tiles` is `_ragged_tiles`'s
    unless a test or a sweep says otherwise."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, Hq, D = q.shape
    kq = is_quantized(k_pages)
    kw = k_pages.weight if kq else k_pages
    Hkv, _, bs, _ = kw.shape
    S, npages = page_indices.shape
    group = Hq // Hkv
    tiles = tiles or _ragged_tiles(T, Hq, k_pages, npages)
    tq, hb, ppb = tiles.tq, tiles.hb, tiles.ppb
    n_qblocks, kv_blk, nkv = tiles.grid(T, bs, npages)
    t_pad = n_qblocks * tq

    cu = cu_q_lens.astype(jnp.int32)
    work, n_pairs, n_kv = ragged_work(cu, kv_lens, tq, n_qblocks, kv_blk,
                                      window=window)
    n_kv = jnp.minimum(n_kv, nkv)  # a length past the table reads no page

    def page_map(pg):
        def index(h, p, j, work, cu, kvl, pt):
            # past the pair's limit the operand stays on the last page it
            # held for this pair: a block index that repeats between steps
            # is not fetched again. (`lax.div`, not `//`: seven layers of
            # 2 x ppb such maps lower in a second less without the floor's
            # sign fix-up, and nothing here is negative)
            held = jax.lax.div(work[2, p] + (bs - 1), bs)
            if window is not None:
                j = j + work[4, p]
            last = pg + jax.lax.div(jnp.maximum(held - 1 - pg, 0), ppb) * ppb
            page = pt[work[1, p], jnp.minimum(j * ppb + pg, last)]
            return (h, jnp.where(pg < held, page, 0), 0, 0)
        return index

    def q_map(h, p, j, work, cu, kvl, pt):
        return (h, work[0, p], 0, 0)

    q_spec = pl.BlockSpec((hb, None, group * tq, D), q_map)
    # [Hkv, q block, G x tq, D], in the pool's dtype (f32 for int8 pools,
    # whose pages dequantize to f32 like the math tier's)
    qs = (q * scale).astype(jnp.float32 if kq else kw.dtype)
    qs = jnp.pad(qs, ((0, t_pad - T), (0, 0), (0, 0)))
    qs = qs.reshape(n_qblocks, tq, Hkv, group, D).transpose(
        2, 0, 3, 1, 4).reshape(Hkv, n_qblocks, group * tq, D)

    in_specs, operands = [q_spec], [qs]
    for pages in (k_pages, v_pages):
        for pg in range(ppb):
            in_specs.append(pl.BlockSpec((hb, None, bs, D), page_map(pg)))
            if kq:
                in_specs.append(pl.BlockSpec((hb, None, bs, 1), page_map(pg)))
                operands += [pages.weight, pages.scales]
            else:
                operands.append(pages)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(Hkv // hb, n_pairs, n_kv),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hb, group * tq, D), jnp.float32),       # acc
            pltpu.VMEM((hb, group * tq, _LANES), jnp.float32),  # running max
            pltpu.VMEM((hb, group * tq, _LANES), jnp.float32),  # running sum
        ],
    )
    kernel = functools.partial(_ragged_kernel, bs, group, ppb, kq, window)
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qs.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=tiles.vmem + (16 << 20)),
        interpret=interpret,
        name="ragged_paged_attention",
    )
    out = fn(work, cu, kv_lens.astype(jnp.int32),
             page_indices.astype(jnp.int32), *operands)
    out = out.reshape(Hkv, n_qblocks, group, tq, D).transpose(
        1, 3, 0, 2, 4).reshape(t_pad, Hq, D)[:T]
    # the grid never visits a query block no live row meets: its tokens are
    # whatever the buffer held
    return jnp.where((jnp.arange(T) < cu[-1])[:, None, None], out, 0)


def ragged_paged_attention(q, k_pages, v_pages, kv_lens, page_indices,
                           cu_q_lens, scale=None, impl=None, window=None):
    """Mixed prefill+decode attention over the paged pool.

    q: [T, Hq, D] packed token stream; returns [T, Hq, D]. kv_lens must
    already include this step's tokens (post-write totals). Pad tokens
    (beyond cu_q_lens[-1]) return zeros-ish garbage — callers discard
    them. `scale` is 1/sqrt(D) of the STORED width unless given. `window`:
    a token sees its last `window` keys, its own among them, and the kernel
    never reads a kv block below the window of a (query block, row) pair's
    first token: the cost of a span stops growing with the row. impl: None/"auto" (kernel on TPU — a kernel failure there
    raises, it never becomes the math tier — and math elsewhere), "math",
    "pallas" (interpret-mode off TPU — the CPU tier-1 path through the
    real kernel body)."""
    global LAST_IMPL
    from .flash_attention import _FORCE_XLA, _on_tpu

    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    impl = impl or _env_str("PADDLE_RAGGED_IMPL", "auto")
    on_tpu = _on_tpu() and not _FORCE_XLA
    if impl == "pallas" or (impl == "auto" and on_tpu):
        out = _ragged_pallas(q, k_pages, v_pages, kv_lens, page_indices,
                             cu_q_lens, scale, interpret=not on_tpu,
                             window=window)
        LAST_IMPL = "ragged-kernel" if on_tpu else "ragged-kernel-interpret"
        return out
    LAST_IMPL = "ragged-math"
    return _ragged_math(q, k_pages, v_pages, kv_lens, page_indices,
                        cu_q_lens, scale, window)
