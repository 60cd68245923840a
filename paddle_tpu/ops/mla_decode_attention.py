"""Absorbed latent attention for decode rows: one query token a row over the
row's latent pages (ops/latent_pool.py), without ever expanding K or V.

With `W_uk,h` / `W_uv,h` the head's halves of kv_b_proj, the expanded
`q_nope . (c_kv W_uk,h)` equals `(q_nope W_uk,h^T) . c_kv`, so the caller
folds W_uk into the query (`q_lat`, kv_lora_rank wide) and W_uv into the
output; what is left is ONE shared key `[c_kv | k_rope]` (576 wide) and ONE
shared value `c_kv` (512 wide) for all query heads:

    scores = q_lat . c_kv + q_rope . k_rope ;  o_lat = softmax(scores) c_kv

That is ~121 FLOP a cache byte at 64 heads (half-way to the v5e's ridge),
and neither jax's `paged_attention` (K and V of one 128-multiple width) nor
the ragged kernel computes it.

Tiers, chosen at trace time (`LAST_IMPL`; a tier that cannot run raises, it
never becomes another):
- `mla-decode-kernel`: a Pallas kernel on TPU (`-interpret` off it, for the
  CPU tests): grid (live row, block of pages), the page table and lengths as
  scalar prefetch, every page of a block its own page-indirect operand (the
  ragged kernel's way), all heads of a row against the block in two dots,
  online softmax in VMEM scratch. Both grid bounds are OPERANDS: the rows
  that have anything to attend (the caller hands a dead row a length of 0;
  in the engine 3-5 of 16 rows are live) and the blocks of the longest of
  them; a grid step costs ~1.7 us whatever it does, and the
  static grid of 16 rows x 34 blocks spent all its time on steps that
  computed nothing (PERF.md PR 29). Blocks past a row's own length map to
  the scratch page (not fetched again) and compute nothing.
- `mla-decode-xla`: a `fori_loop` over blocks of pages with a dynamic trip
  count (the longest live row), one vectorised page gather a block and an
  online softmax in f32: every row gathers every block up to the longest
  row's (8% of the memory roofline on the chip, PERF.md PR 29). The
  off-TPU default and the kernel's reference.
"""
import functools

import jax
import jax.numpy as jnp

LAST_IMPL = None  # "mla-decode-kernel[-interpret]" | "mla-decode-xla"

#: kv positions folded a loop step of the XLA tier / a grid step of the kernel
#: (whole pages; in the kernel each page is an operand of its own, so this
#: also bounds the operand count)
KV_BLOCK = 512


def _decode_xla(q, pages, lengths, page_indices, rank, scale):
    B, H, W = q.shape
    bs = pages.shape[1]
    ppb = max(1, min(KV_BLOCK // bs, page_indices.shape[1]))
    kb = ppb * bs
    pad = -page_indices.shape[1] % ppb
    table = jnp.pad(page_indices, ((0, 0), (0, pad)))
    qs = (q.astype(jnp.float32) * scale).astype(pages.dtype)

    def body(j, carry):
        o, l, m = carry
        pid = jax.lax.dynamic_slice_in_dim(table, j * ppb, ppb, axis=1)
        lat = pages[pid].reshape(B, kb, W)
        s = jnp.einsum("bhw,bkw->bhk", qs, lat,
                       preferred_element_type=jnp.float32)
        pos = j * kb + jnp.arange(kb)
        live = pos[None, None, :] < lengths[:, None, None]
        s = jnp.where(live, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(live, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhk,bkc->bhc", p.astype(pages.dtype), lat[..., :rank],
            preferred_element_type=jnp.float32)
        return o, l, m_new

    n = jnp.minimum((jnp.max(lengths) + kb - 1) // kb, table.shape[1] // ppb)
    o, l, _ = jax.lax.fori_loop(
        0, n, body, (jnp.zeros((B, H, rank), jnp.float32),
                     jnp.zeros((B, H), jnp.float32),
                     jnp.full((B, H), -1e30, jnp.float32)))
    return o / jnp.maximum(l, 1e-30)[..., None]


_LANES = 128  # m/l scratch keep a lane-aligned last dim


def _decode_kernel(ppb, rank, row_ref, len_ref, pt_ref, q_ref, *refs):
    """Grid (i-th live row, block j of ppb pages): fold the block's latent
    rows into row `row_ref[i]`'s online softmax. q_ref [1, H, W]; refs: ppb
    pages [1, bs, W], then o_ref [1, H, rank] and the scratch acc
    [H, rank], m and l [H, 128]."""
    import jax.experimental.pallas as pl

    pages, (o_ref, acc, m, l) = refs[:ppb], refs[ppb:]
    j = pl.program_id(1)
    kb = ppb * pages[0].shape[1]
    length = len_ref[row_ref[pl.program_id(0)]]

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, -1e30)
        l[...] = jnp.zeros_like(l)

    @pl.when(j * kb < length)
    def _fold():
        k = jnp.concatenate([pg[0] for pg in pages], axis=0)      # [kb, W]
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                   # [H, kb]
        pos = j * kb + jax.lax.broadcasted_iota(jnp.int32, (1, kb), 1)
        s = jnp.where(pos < length, s, -1e30)
        m_prev, l_prev = m[:, :1], l[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(pos < length, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        m[...] = jnp.broadcast_to(m_new, m.shape)
        l[...] = jnp.broadcast_to(
            l_prev * corr + p.sum(axis=-1, keepdims=True), l.shape)
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            p.astype(k.dtype), k[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = acc[...] / jnp.maximum(l[:, :1], 1e-30)


def _decode_pallas(q, pages, lengths, page_indices, rank, scale, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, W = q.shape
    bs = pages.shape[1]
    npages = page_indices.shape[1]
    ppb = max(1, min(KV_BLOCK // bs, npages))

    def page_map(pg):
        def index(i, j, rows, lens, pt):
            # a block past the row's length maps to scratch page 0: a block
            # index that repeats between steps is not fetched again
            b, page = rows[i], j * ppb + pg
            return (jnp.where(page * bs < lens[b],
                              pt[b, jnp.minimum(page, npages - 1)], 0), 0, 0)
        return index

    def row_map(i, j, rows, lens, pt):
        return (rows[i], 0, 0)

    lengths = lengths.astype(jnp.int32)
    live = lengths > 0
    rows = jnp.argsort(~live, stable=True).astype(jnp.int32)  # live first
    n_blocks = jnp.minimum((jnp.max(lengths) + ppb * bs - 1) // (ppb * bs),
                           -(-npages // ppb))
    qs = (q.astype(jnp.float32) * scale).astype(pages.dtype)
    fn = pl.pallas_call(
        functools.partial(_decode_kernel, ppb, rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(live.sum().astype(jnp.int32), n_blocks),
            in_specs=[pl.BlockSpec((1, H, W), row_map)]
            + [pl.BlockSpec((1, bs, W), page_map(pg)) for pg in range(ppb)],
            out_specs=pl.BlockSpec((1, H, rank), row_map),
            scratch_shapes=[pltpu.VMEM((H, rank), jnp.float32),
                            pltpu.VMEM((H, _LANES), jnp.float32),
                            pltpu.VMEM((H, _LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="mla_decode_attention",
    )
    out = fn(rows, lengths, page_indices.astype(jnp.int32), qs,
             *([pages] * ppb))
    # the grid never visits a row of length 0: its block is whatever was there
    return jnp.where(live[:, None, None], out, 0.0)


def mla_decode_attention(q_lat, q_rope, pages, lengths, page_indices, scale,
                         impl=None):
    """q_lat [B, H, rank] (the query with W_uk folded in), q_rope
    [B, H, rope]; pages [P, bs, stored width] (rank + rope, then zero pad
    lanes); `lengths` INCLUDE the token just written (a row of length 0
    attends nothing and returns zeros). Returns o_lat [B, H, rank] in f32,
    for the caller to fold W_uv into. impl: None/"auto" (the kernel on TPU,
    where its failure raises; the XLA tier elsewhere), "xla", "pallas"
    (interpret mode off TPU)."""
    global LAST_IMPL
    from .flash_attention import _FORCE_XLA, _on_tpu

    q = jnp.concatenate([q_lat, q_rope], axis=-1)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, pages.shape[-1] - q.shape[-1])))
    rank = q_lat.shape[-1]
    impl = impl or "auto"
    on_tpu = _on_tpu() and not _FORCE_XLA
    if impl == "pallas" or (impl == "auto" and on_tpu):
        LAST_IMPL = ("mla-decode-kernel" if on_tpu
                     else "mla-decode-kernel-interpret")
        return _decode_pallas(q, pages, lengths, page_indices, rank, scale,
                              interpret=not on_tpu)
    LAST_IMPL = "mla-decode-xla"
    return _decode_xla(q, pages, lengths, page_indices, rank, scale)
