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
  no pages at all.

The engine reads every spec through the same four members:

    spec.layers                       one spec a layer (views and pool_of)
    spec.make_pools(num_pages, page_size, dtype, kv_cache_dtype, max_seqs)
    spec.refuses(plane)               why `plane` ("prefix_cache", "handoff",
                                      "lora") cannot run over this cache, or
                                      None; the engine raises it by name
    spec.log_pages / spec.has_state   what the step log records beside a
                                      dispatch (pages held; rows with state)

and, where a mixed step's attention walks a grid sized from the spans
(KVCacheSpec alone), `spec.ragged_walk(pool, cu, kv_lens, n_tokens, npages)`:
the step log's `ragged_walk`; a spec without it logs none.

Pages stay the allocator's one unit: every paged layer of a model shares the
row's page table, and a state slot is the row itself.
"""
import dataclasses

import jax


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


class LayerCacheSpecs:
    log_pages = True

    def __init__(self, layers):
        self.layers = list(layers)

    @property
    def has_state(self):
        return any(s.has_state for s in self.layers)

    def make_pools(self, num_pages, page_size, dtype, kv_cache_dtype=None,
                   max_seqs=None):
        return [s.make_pool(num_pages, page_size, dtype, kv_cache_dtype,
                            max_seqs) for s in self.layers]

    def refuses(self, plane):
        for s in self.layers:
            why = s.refuses(plane)
            if why:
                return why
        return None
