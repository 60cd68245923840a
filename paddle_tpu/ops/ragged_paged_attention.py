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
- `_ragged_pallas`: Pallas grid over (head block, q block, batch row, kv
  block of several pages); per-row scalar prefetch (`cu_q_lens` /
  `kv_lens` / page table) drives the masked block walk and the
  page-indirect BlockSpec index_maps. A step holds one [heads, q block, D]
  tile and its online-softmax scratch, so the resident set does not grow
  with T — blocked to what the chip's compiler accepts at 7B widths, not
  yet tuned. On TPU a failure of this tier raises; `interpret=True`
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
                 scale):
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
        s = jnp.where(pos[None, None, None, :] < limit[:, None, None, None],
                      s, -1e30)
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


# Kernel blocking. A grid step holds one q block of _Q_BLOCK tokens for
# _HEADS_PER_STEP query heads against one kv block of ~_KV_BLOCK positions
# (several pages), so the resident set is a few MB whatever T, Hq and the
# page-table width are — the chip's compiler refuses (or never finishes)
# a kernel that keeps the whole packed [T, Hq, D] stream resident.
_Q_BLOCK = 128
_KV_BLOCK = 128
_HEADS_PER_STEP = 8
_LANES = 128  # m/l scratch keep a lane-aligned last dim


def _ragged_kernel(bs, group, ppb, quantized,
                   # scalar prefetch (order fixed by PrefetchScalarGridSpec)
                   cu_ref, kvl_ref, pt_ref,
                   # blocked operands
                   *refs):
    """Grid (head block h, q block i, batch row b, kv block j); b and j
    are the reduction axes. A step folds `ppb` pages of row b's KV into
    the online-softmax scratch of the q block's tokens — tokens outside
    row b or past their causal limit are masked, and steps whose row
    misses the q block (or whose pages lie past the row's KV extent) are
    skipped. Heads lead every operand so both contractions are 3-D
    batched dots; the accumulators normalize into the output block on the
    q block's final reduction step."""
    import jax.experimental.pallas as pl

    q_ref = refs[0]
    n_in = 1 + ppb * (4 if quantized else 2)
    pages = refs[1:n_in]
    o_ref, acc, m, l = refs[n_in:]
    i, b, j = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    n, tq, _ = q_ref.shape
    kv_blk = ppb * bs

    @pl.when((b == 0) & (j == 0))
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, -1e30)
        l[...] = jnp.zeros_like(l)

    cu0 = cu_ref[b]
    cu1 = cu_ref[b + 1]
    kvl = kvl_ref[b]
    q_len = cu1 - cu0
    q_lo = i * tq
    # the block's last in-row token bounds what any of its tokens may see
    lim_max = kvl - q_len + (jnp.minimum(cu1, q_lo + tq) - cu0)

    @pl.when((q_len > 0) & (cu0 < q_lo + tq) & (cu1 > q_lo)
             & (j * kv_blk < lim_max))
    def _accumulate():
        def load(idx):
            # ppb pages [hb, bs, D] -> one [n, kv_blk, D] kv block, each kv
            # head repeated over its query-head group
            per = 2 if quantized else 1
            blks = []
            for pg in range(ppb):
                w = pages[(idx * ppb + pg) * per][:, 0]
                if quantized:
                    # from_int8: w * scales / 127.5 (per-row absmax)
                    sc = pages[(idx * ppb + pg) * per + 1][:, 0]
                    w = (w.astype(jnp.float32)
                         * (sc.astype(jnp.float32) / 127.5))
                blks.append(w)
            blk = blks[0] if ppb == 1 else jnp.concatenate(blks, axis=1)
            if group > 1:
                hb = blk.shape[0]
                blk = jnp.broadcast_to(
                    blk[:, None], (hb, group) + blk.shape[1:]
                ).reshape((n,) + blk.shape[1:])
            return blk

        k_blk, v_blk = load(0), load(1)
        qs = q_ref[...].astype(k_blk.dtype)
        s = jax.lax.dot_general(
            qs, k_blk, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # [n, tq, kv_blk]
        t_ids = q_lo + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
        in_row = (t_ids >= cu0) & (t_ids < cu1)            # [tq, 1]
        lim = kvl - q_len + (t_ids - cu0) + 1              # [tq, 1]
        kv_pos = j * kv_blk + jax.lax.broadcasted_iota(
            jnp.int32, (1, kv_blk), 1)
        mask = (in_row & (kv_pos < lim))[None]             # [1, tq, kv_blk]
        s = jnp.where(mask, s, -1e30)
        m_prev = m[:, :, :1]                               # [n, tq, 1]
        l_prev = l[:, :, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        m[...] = jnp.broadcast_to(m_new, m.shape)
        l[...] = jnp.broadcast_to(
            l_prev * corr + p.sum(axis=-1, keepdims=True), l.shape)
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # [n, tq, D]

    @pl.when((b == pl.num_programs(2) - 1) & (j == pl.num_programs(3) - 1))
    def _finalize():
        o_ref[...] = (acc[...] / jnp.maximum(l[:, :, :1], 1e-30)
                      ).astype(o_ref.dtype)


def _ragged_pallas(q, k_pages, v_pages, kv_lens, page_indices, cu_q_lens,
                   scale, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, Hq, D = q.shape
    kq = is_quantized(k_pages)
    kw = k_pages.weight if kq else k_pages
    Hkv, _, bs, _ = kw.shape
    S, npages = page_indices.shape
    group = Hq // Hkv

    tq = min(_Q_BLOCK, -(-T // 8) * 8)
    t_pad = -(-T // tq) * tq
    hb = max(1, min(Hkv, _HEADS_PER_STEP // group))  # kv heads per step
    while Hkv % hb:
        hb -= 1
    n = hb * group
    ppb = max(1, min(npages, _KV_BLOCK // bs))       # pages per kv block
    nkv = -(-npages // ppb)

    def page_map(pg):
        def index(h, i, b, j, cu, kvl, pt):
            # a step that will be skipped maps to scratch page 0: a block
            # index that repeats between steps is not fetched again
            page = j * ppb + pg
            live = ((cu[b + 1] > cu[b]) & (cu[b] < (i + 1) * tq)
                    & (cu[b + 1] > i * tq) & (page * bs < kvl[b]))
            return (h, jnp.where(
                live, pt[b, jnp.minimum(page, npages - 1)], 0), 0, 0)
        return index

    def q_map(h, i, b, j, cu, kvl, pt):
        return (h, i, 0)

    q_spec = pl.BlockSpec((n, tq, D), q_map)
    # heads lead: [Hq, t_pad, D], in the pool's dtype (f32 for int8 pools,
    # whose pages dequantize to f32 like the math tier's)
    qs = jnp.swapaxes(q * scale, 0, 1).astype(
        jnp.float32 if kq else kw.dtype)
    qs = jnp.pad(qs, ((0, 0), (0, t_pad - T), (0, 0)))

    in_specs, operands = [q_spec], [qs]
    for pages in (k_pages, v_pages):
        for pg in range(ppb):
            in_specs.append(pl.BlockSpec((hb, 1, bs, D), page_map(pg)))
            if kq:
                in_specs.append(pl.BlockSpec((hb, 1, bs, 1), page_map(pg)))
                operands += [pages.weight, pages.scales]
            else:
                operands.append(pages)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(Hkv // hb, t_pad // tq, S, nkv),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((n, tq, D), jnp.float32),       # acc
            pltpu.VMEM((n, tq, _LANES), jnp.float32),  # running max
            pltpu.VMEM((n, tq, _LANES), jnp.float32),  # running sum
        ],
    )
    kernel = functools.partial(_ragged_kernel, bs, group, ppb, kq)
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hq, t_pad, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=interpret,
        name="ragged_paged_attention",
    )
    out = fn(cu_q_lens.astype(jnp.int32), kv_lens.astype(jnp.int32),
             page_indices.astype(jnp.int32), *operands)
    return jnp.swapaxes(out, 0, 1)[:T]


def ragged_paged_attention(q, k_pages, v_pages, kv_lens, page_indices,
                           cu_q_lens, scale=None, impl=None):
    """Mixed prefill+decode attention over the paged pool.

    q: [T, Hq, D] packed token stream; returns [T, Hq, D]. kv_lens must
    already include this step's tokens (post-write totals). Pad tokens
    (beyond cu_q_lens[-1]) return zeros-ish garbage — callers discard
    them. impl: None/"auto" (kernel on TPU — a kernel failure there
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
                             cu_q_lens, scale, interpret=not on_tpu)
        LAST_IMPL = "ragged-kernel" if on_tpu else "ragged-kernel-interpret"
        return out
    LAST_IMPL = "ragged-math"
    return _ragged_math(q, k_pages, v_pages, kv_lens, page_indices,
                        cu_q_lens, scale)
