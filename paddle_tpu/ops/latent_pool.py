"""The pool of latent pages that latent (MLA) attention serves from, its two
cache views and its two writers (reference: DeepSeek-V2/V3 multi-head latent
attention; models/deepseek_v3.py is the block that uses it).

A token's cache entry in one layer is ONE row `[c_kv | k_rope]`
(kv_lora_rank + qk_rope_head_dim values: 576 at the published widths, 1,152
bytes in bf16) shared by every head, so a layer has ONE pool
`[num_pages, page_size, stored width]` where an MHA layer has a K and a V
pool `[Hkv, P, bs, D]` (ops/paged_attention.py). Page table, lengths and the
packed-stream fields are the engine's own and mean what they mean there.

The STORED width is the row's width rounded up to the chip's 128 lanes (576
-> 640; the pad lanes are zero and multiply nothing). A row-major tiled TPU
array pads its minor dim to 128 lanes anyway, and for a minor dim that is no
multiple of 128 the TPU's default layout is not row-major at all: a
`bf16[P, 16, 576]` pool arrives page-MINOR (`{0,2,1}`), and both programs
copied every pool to row-major on entry and back on exit (compile rehearsal,
PERF.md PR 29). Said once here so that it is a stated size, not a hidden
one: 1,280 bytes a token a layer on the device for 1,152 of content.

Layout contract (the lesson of PERF.md, PR 27): the pool stays in its default
row-major layout from a program's parameter to its result. Both writers
scatter along the leading page dim with the minor dims as the window
(`write_token_latent`: window `width`; `write_ragged_latent`: whole pages,
window `page_size x width`), which is the layout both readers
(ops/mla_prefill_attention.py, ops/mla_decode_attention.py) gather pages in.
tests/test_chip_compile.py compiles both serving programs for a described
v5e and fails if a pool-shaped copy comes back.
"""
import dataclasses

import jax
import jax.numpy as jnp

from .ragged_paged_attention import _merge_pages


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LatentPagedLayerCache:
    """One layer's latent pool seen by a decode step (the latent twin of
    PagedLayerCache).

    pages:        [num_pages, page_size, stored width]
    page_indices: [B, pages_per_seq] int32 rows into the pool
    lengths:      [B] int32 — valid tokens per sequence BEFORE this step
    live:         [B] bool — the rows a request holds. A dead row (an empty
                  slot of the engine's fixed batch, a row still in prefill
                  during a mixed step's scan) writes to the scratch page,
                  attends nothing and is routed to no expert.
    """

    pages: jax.Array
    page_indices: jax.Array
    lengths: jax.Array
    live: jax.Array

    def tree_flatten(self):
        return (self.pages, self.page_indices, self.lengths, self.live), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def page_size(self):
        return self.pages.shape[1]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LatentRaggedLayerCache:
    """One layer's latent pool seen by a mixed prefill+decode step (the
    latent twin of RaggedLayerCache; the fields after `pages` are that
    class's, with the same meaning)."""

    pages: jax.Array
    page_indices: jax.Array
    kv_lens: jax.Array
    cu_q_lens: jax.Array
    row_of: jax.Array
    token_pos: jax.Array
    valid: jax.Array

    def tree_flatten(self):
        return (self.pages, self.page_indices, self.kv_lens, self.cu_q_lens,
                self.row_of, self.token_pos, self.valid), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def page_size(self):
        return self.pages.shape[1]


def stored_width(width):
    """A latent row's width in the pool: whole 128-lane tiles."""
    return -(-width // 128) * 128


def _stored(pages, new):
    """`new` [N, width] as pool rows: the pool's dtype, zero pad lanes."""
    return jnp.pad(new.astype(pages.dtype),
                   ((0, 0), (0, pages.shape[-1] - new.shape[-1])))


def write_token_latent(pages, page_indices, lengths, new):
    """One new token a row: `new` [B, width] lands at logical position
    `lengths[b]` of row b. Rows own their pages, so rows never collide
    (rows routed to the scratch page, only with each other)."""
    bs = pages.shape[1]
    page_of = jnp.take_along_axis(
        page_indices, (lengths // bs)[:, None], axis=1)[:, 0]
    return pages.at[page_of, lengths % bs].set(_stored(pages, new))


def write_ragged_latent(pages, page_indices, row_of, token_pos, valid, new):
    """A packed token stream's rows `new` [T, width], a page at a time
    (`_merge_pages`, with the pool as its one head). Token t lands at
    position token_pos[t] of row row_of[t]; pads go to scratch page 0."""
    bs = pages.shape[1]
    page_of = jnp.where(valid, page_indices[row_of, token_pos // bs], 0)
    off = jnp.where(valid, token_pos % bs, 0)
    n_runs = row_of.shape[0] // bs + 2 * page_indices.shape[0] + 1
    return _merge_pages(pages[None], page_of, off,
                        _stored(pages, new)[None], n_runs)[0]


class LatentCacheSpec:
    """What the serving engine asks of a model whose layers cache latent
    rows: how to make the pools, how to view one as a cache entry, and how to
    take the pool back out of the entry a forward returns. The K-and-V twin
    is ops.paged_attention.KVCacheSpec; ops/cache_specs.py states the
    members the engine reads."""

    kind = "latent pages"
    log_pages, has_state = True, False

    def __init__(self, num_layers, width):
        self.num_layers, self.width = num_layers, width

    @property
    def layers(self):
        return [self] * self.num_layers

    def refuses(self, plane):
        """The prefix cache, the handoff plane and the LoRA programs were
        written for K and V pages."""
        what = {"prefix_cache": "moves K and V pages",
                "handoff": "moves K and V pages",
                "lora": "run copies of the K-and-V programs"}.get(plane)
        return what and (f"{what}; this model caches latent rows "
                         f"({type(self).__name__})")

    def make_pools(self, num_pages, page_size, dtype, kv_cache_dtype=None,
                   max_seqs=None, prefill_chunk=None):
        if kv_cache_dtype not in (None, "model"):
            raise ValueError(
                f"kv_cache_dtype={kv_cache_dtype!r}: the quantised pool "
                "plane holds K and V pages, not latent rows")
        shape = (num_pages, page_size, stored_width(self.width))
        return [(jnp.zeros(shape, dtype),) for _ in range(self.num_layers)]

    @staticmethod
    def paged(pool, page_table, lengths, live):
        return LatentPagedLayerCache(pool[0], page_table, lengths, live)

    @staticmethod
    def ragged(pool, page_table, kv_lens, cu, row_of, token_pos, valid):
        return LatentRaggedLayerCache(pool[0], page_table, kv_lens, cu,
                                      row_of, token_pos, valid)

    @staticmethod
    def pool_of(present):
        return (present.pages,)
