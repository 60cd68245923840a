"""A model's serving cache as a LIST of layer specs.

The serving engine (inference/continuous.py) asks a model what its layers
cache through `serving_cache_spec()`. A model whose layers all cache the same
thing answers with one spec for all of them (ops.paged_attention.KVCacheSpec:
K and V pages; ops.latent_pool.LatentCacheSpec: latent pages). A model whose
layers differ answers with `LayerCacheSpecs`: one spec a layer, each with
`make_pool`, `paged`, `ragged`, `pool_of` and `refuses` of its own —

- ops.sparse_paged_attention.SelectedKVSpec: K and V pages plus the
  selector's compressed-key plane;
- ops.lightning_attention.StateSlotSpec: one slot of recurrent state a row,
  no pages at all; the slot is one array (a linear-attention state) or a
  TUPLE of arrays (ops.selective_scan.ssm_slot_spec: a state-space layer's
  state and its convolution's last inputs);
- ops.paged_attention.WindowRingSpec: a fixed ring of K and V pages a row
  for a sliding-window layer, under a table of its own that the allocator
  never sees;
- `NoPoolSpec` (below): a layer that makes NO pool, because it caches
  nothing or because it reads another layer's (a cross-attention layer over
  the one K/V pool of a YOCO model).

The engine reads every spec through the same four members:

    spec.layers                       one spec a layer (views and pool_of)
    spec.make_pools(num_pages, page_size, dtype, kv_cache_dtype, max_seqs,
                    prefill_chunk)    (the chunk sizes a window ring; every
                                      other spec ignores it)
    spec.refuses(plane)               why `plane` ("prefix_cache", "handoff",
                                      "lora") cannot run over this cache, or
                                      None; the engine raises it by name
    spec.log_pages / spec.has_state   what the step log records beside a
                                      dispatch (pages held; rows with state)

and, where a mixed step's attention walks a grid sized from the spans
(KVCacheSpec alone), `spec.ragged_walk(pool, cu, kv_lens, n_tokens, npages)`:
the step log's `ragged_walk`; a spec without it logs none. Likewise
`spec.paged_walk(pools, lengths, npages)` where a scan step's decode call
walks a list made from the rows' lengths (KVCacheSpec, and `LayerCacheSpecs`
for its layers of that kind): the step log's `paged_walk`.

Pages stay the allocator's one unit: every layer whose pages the allocator
hands out shares the row's page table; a state slot and a window ring are
the row itself, and `num_pages`, the allocator's count and the step log's
`pages` are those of the layers with allocator pages alone.
"""
import dataclasses

import jax
import jax.numpy as jnp


def live_rows(live):
    """The numbers of the rows `live` [B] marks, in order, then zeros, and
    their count (both int32): what a decode form walks, so that its work
    grows with the rows that live and not with the slots. No sort (one
    fusion), as ops/paged_attention.py's kernel finds its grid's rows."""
    idx = jnp.arange(live.shape[0], dtype=jnp.int32)
    place = jnp.sum(live[None, :] & (idx[None, :] <= idx[:, None]), axis=1) - 1
    rows = jnp.sum(jnp.where(live[None, :] & (place[None, :] == idx[:, None]),
                             idx[None, :], 0), axis=1, dtype=jnp.int32)
    return rows, jnp.sum(live, dtype=jnp.int32)


def cache_view(*names):
    """Class decorator: a dataclass of arrays `names`, registered as a pytree
    in that order (a cache view crosses jit, scan and cond boundaries)."""
    def deco(cls):
        cls = dataclasses.dataclass(cls)
        cls.tree_flatten = lambda self: (
            tuple(getattr(self, n) for n in names), None)
        cls.tree_unflatten = classmethod(lambda c, aux, ch: c(*ch))
        return jax.tree_util.register_pytree_node_class(cls)
    return deco


class NoPoolSpec:
    """A layer that makes no pool: it caches nothing (`source` None), or
    reads layer `source`'s pool and never writes it. Its pool is the empty
    tuple and its view None: the MODEL's forward hands the layer its
    source's view as that layer left it in the same forward (the spec sees
    one layer's pool, and before the step's write). Every plane is the
    source's to refuse but the one that cannot run a tail."""

    kind = "no pool"
    has_state = allocator_pages = False

    def __init__(self, source=None):
        self.source = source

    def make_pool(self, num_pages, page_size, dtype, kv_cache_dtype=None,
                  max_seqs=None, prefill_chunk=None):
        return ()

    def refuses(self, plane):
        if plane == "lora" and self.source is not None:
            return ("run the whole trunk on every packed token and project "
                    "its span ends; a layer that reads another layer's pool "
                    "runs after the gather (the engine's trunk / tail "
                    f"protocol), which they do not speak "
                    f"({type(self).__name__})")
        return None

    @staticmethod
    def paged(pool, page_table, lengths, live):
        return None

    @staticmethod
    def ragged(pool, page_table, kv_lens, cu, row_of, token_pos, valid):
        return None

    @staticmethod
    def pool_of(present):
        return ()


class LayerCacheSpecs:
    def __init__(self, layers):
        self.layers = list(layers)

    @property
    def log_pages(self):
        """Whether the step log records `pages`: the allocator's, so only
        where a layer holds allocator pages (not slots, rings or nothing)."""
        return any(s.allocator_pages for s in self.layers)

    @property
    def has_state(self):
        return any(s.has_state for s in self.layers)

    def make_pools(self, num_pages, page_size, dtype, kv_cache_dtype=None,
                   max_seqs=None, prefill_chunk=None):
        return [s.make_pool(num_pages, page_size, dtype, kv_cache_dtype,
                            max_seqs, prefill_chunk) for s in self.layers]

    def paged_walk(self, pools, lengths, npages):
        """The step log's `paged_walk`, summed over the layers whose pages
        are the allocator's and whose decode call walks a row's whole table
        (KVCacheSpec); None where no layer does."""
        walks = [s.paged_walk([pool], lengths, npages)
                 for s, pool in zip(self.layers, pools)
                 if s.allocator_pages and hasattr(s, "paged_walk")]
        walks = [w for w in walks if w is not None]
        return tuple(map(sum, zip(*walks))) if walks else None

    def refuses(self, plane):
        for s in self.layers:
            why = s.refuses(plane)
            if why:
                return why
        return None
