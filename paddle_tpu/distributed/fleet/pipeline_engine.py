"""Pipeline-parallel engine — single-program GPipe over the "pp" mesh axis
(reference: fleet/meta_parallel/pipeline_parallel.py 1F1B runtime +
pp_utils/p2p_communication.py; redesigned for XLA per SURVEY.md §7.6:
collective-permute pipeline, one traced program, cf. PAPERS.md MPMD paper
for the alternative).

Mechanics:
- The N homogeneous decoder blocks are stacked: every weight leaf becomes
  [pp, layers_per_stage, ...] sharded P("pp", ...). Each pp mesh position
  owns its stage's slice — placement == stage assignment.
- Forward runs inside shard_map (manual over "pp" only; mp/dp stay GSPMD-
  automatic): lax.scan over T = M + pp - 1 ticks. Each tick every stage
  ppermutes its activation to the next stage and applies its blocks —
  exactly the reference's 1F1B steady state wave, expressed as data flow.
  Stage 0 injects micro-batch t; stage pp-1 emits outputs.
- Backward: jax.vjp through the scan (the tape records one node for the
  whole engine); per-tick remat keeps activation memory at O(M/pp).
- Bubble: 2(pp-1) ticks, amortized by micro-batch count M (same as GPipe /
  FThenB; the XLA scheduler overlaps ppermute with compute).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...framework.core import Parameter, Tensor, apply
from ...nn.layer.layers import Layer


class PipelineStack(Layer):
    """Stack of `num_layers` identical blocks, pipeline-partitioned into
    `pp_degree` stages (reference analogue: PipelineLayer's segment of
    LayerDescs, with placement replacing per-rank construction)."""

    def __init__(self, block_factory, num_layers, pp_degree, num_micro_batches=None,
                 block_kwargs=None, virtual_pp_degree=1):
        super().__init__()
        V = virtual_pp_degree
        if num_layers % (pp_degree * V) != 0:
            raise ValueError(
                f"num_layers {num_layers} not divisible by pp {pp_degree} × vpp {V}"
            )
        self.num_layers = num_layers
        self.pp_degree = pp_degree
        self.virtual_pp_degree = V
        self.layers_per_stage = num_layers // pp_degree
        self.layers_per_chunk = num_layers // (pp_degree * V)
        self.num_micro_batches = num_micro_batches or pp_degree
        # the template block is tracing machinery, NOT a registered sublayer:
        # its (dead) weights must stay out of parameters()/state_dict() —
        # only the stacked tensors below are real parameters
        object.__setattr__(self, "template", block_factory(**(block_kwargs or {})))
        blocks = [self.template] + [block_factory(**(block_kwargs or {})) for _ in range(num_layers - 1)]
        self._leaf_names = list(dict(blocks[0].named_parameters()))
        for ln in self._leaf_names:
            leaves = [dict(b.named_parameters())[ln] for b in blocks]
            base_spec = getattr(leaves[0], "partition_spec", None)
            base_entries = list(base_spec) if base_spec is not None else []
            base_entries += [None] * (len(leaves[0].shape) - len(base_entries))
            if V == 1:
                # layer l lives on stage l // Ls (contiguous segments)
                stacked = jnp.stack([l._data for l in leaves]).reshape(
                    pp_degree, self.layers_per_stage, *leaves[0].shape
                )
                spec = P("pp", None, *base_entries)
            else:
                # interleaved: visit k = v*pp + s owns layers [k*Lc, (k+1)*Lc)
                # — stage s hosts chunks {s, s+pp, ...} (reference:
                # PipelineParallelWithInterleave model-chunk placement)
                stacked = jnp.stack([l._data for l in leaves]).reshape(
                    V, pp_degree, self.layers_per_chunk, *leaves[0].shape
                )
                spec = P(None, "pp", None, *base_entries)
            p = Parameter(stacked, name=ln)
            p.partition_spec = spec
            self.add_parameter("stacked__" + ln.replace(".", "__"), p)
        self._jit_cache = {}

    def _stacked_params(self):
        return [self._parameters["stacked__" + ln.replace(".", "__")] for ln in self._leaf_names]

    def engine_leaves(self, params=None):
        """Stacked leaves in the scheduled-engine layout [V, pp, Lc, ...]."""
        params = params if params is not None else self._stacked_params()
        V = self.virtual_pp_degree
        out = []
        for p in params:
            d = p._data if hasattr(p, "_data") else p
            if V == 1:
                d = d.reshape(1, *d.shape)
            out.append(d)
        return out

    def _block_apply(self, leaf_datas, x, extra):
        """Pure: apply ONE block given its weight leaves."""
        overrides = {
            ln: Tensor(d, stop_gradient=True) for ln, d in zip(self._leaf_names, leaf_datas)
        }
        out = self.template.functional_call(overrides, Tensor(x), *extra)
        return out._data if isinstance(out, Tensor) else out[0]._data

    def forward(self, x, *extra):
        """x: [M, mb, ...] micro-batched input stream. Returns [M, mb, ...].

        `extra` entries may be static (None/python scalars) or tensor-valued
        per-micro-batch streams shaped [M, mb, ...] (attention masks,
        position ids). Streams ride the scan: each tick a stage applies the
        slice of the micro-batch it is processing (wave index t - stage).
        """
        from ..mesh import get_mesh

        mesh = get_mesh()
        pp = self.pp_degree
        M_micro = (x.shape if hasattr(x, "shape") else ())[0]
        stacked = self._stacked_params()
        # split extras into static (closed over) and tensor streams [M, ...]
        stream_idx = [
            i
            for i, e in enumerate(extra)
            if e is not None and hasattr(e, "shape") and len(e.shape) >= 1 and e.shape[0] == M_micro
        ]
        if any(
            e is not None and hasattr(e, "shape") and i not in stream_idx
            for i, e in enumerate(extra)
        ):
            raise NotImplementedError(
                "PipelineStack: tensor extras must be per-micro-batch streams [M, ...]"
            )
        streams = [Tensor(extra[i]) if not isinstance(extra[i], Tensor) else extra[i] for i in stream_idx]

        def rebuild_extra(stream_datas):
            full = list(extra)
            for i, d in zip(stream_idx, stream_datas):
                full[i] = Tensor(d, stop_gradient=True)
            return tuple(full)

        if pp == 1 or "pp" not in mesh.axis_names or mesh.shape["pp"] == 1:
            # no pipeline: plain scan over all layers on the merged micro dim
            def fn(xd, *rest):
                leaf_stacks = rest[: len(stacked)]
                stream_datas = rest[len(stacked):]
                M = xd.shape[0]
                nbatch = 3 if self.virtual_pp_degree > 1 else 2
                flat = tuple(s.reshape(self.num_layers, *s.shape[nbatch:]) for s in leaf_stacks)
                merged = xd.reshape(M * xd.shape[1], *xd.shape[2:])
                ex = rebuild_extra(
                    tuple(d.reshape(M * d.shape[1], *d.shape[2:]) for d in stream_datas)
                )

                def body(hh, per_layer):
                    return self._block_apply(list(per_layer), hh, ex), None

                out, _ = jax.lax.scan(body, merged, flat)
                return out.reshape(xd.shape)

            return apply(fn, Tensor(x) if not isinstance(x, Tensor) else x, *stacked, *streams,
                         name="layer_stack")
        if self.virtual_pp_degree > 1:
            raise NotImplementedError(
                "virtual_pp_degree > 1 runs through the scheduled engine "
                "(LlamaForCausalLMPipe(schedule='vpp') / pipeline_schedules)"
            )

        static_extra = tuple(None if i in stream_idx else e for i, e in enumerate(extra))
        cache_key = (mesh, static_extra, tuple(stream_idx))  # Mesh hashable by content
        engine_jit = self._jit_cache.get(cache_key)
        if engine_jit is not None:
            return apply(engine_jit, x if isinstance(x, Tensor) else Tensor(x), *stacked,
                         *streams, name="pipeline")

        n_leaf = len(stacked)

        def engine(xd, *rest):
            leaf_stacks = rest[:n_leaf]
            stream_datas = rest[n_leaf:]
            M = xd.shape[0]
            T = M + pp - 1
            fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]

            def shard_body(x_stream, *args):
                my_stacks = args[:n_leaf]  # leaves: [1, L_s, ...] (stage slice)
                streams_l = args[n_leaf:]
                sid = jax.lax.axis_index("pp")
                mb_shape = x_stream.shape[1:]
                def _pvary(v):
                    return jax.lax.pcast(v, ("pp",), to="varying")

                state = _pvary(jnp.zeros(mb_shape, x_stream.dtype))
                outputs = _pvary(jnp.zeros((M,) + mb_shape, x_stream.dtype))

                def apply_stage(h, *ex_mb):
                    ex = rebuild_extra(ex_mb)

                    def body(hh, per_layer):
                        return self._block_apply(list(per_layer), hh, ex), None

                    out, _ = jax.lax.scan(body, h, tuple(s[0] for s in my_stacks))
                    return out

                apply_stage = jax.checkpoint(apply_stage)

                def tick(carry, t):
                    state, outputs = carry
                    incoming = jax.lax.ppermute(state, "pp", fwd_perm)
                    inject = x_stream[jnp.minimum(t, M - 1)]
                    h_in = jnp.where(sid == 0, inject, incoming)
                    # the wave: at tick t stage s processes micro-batch t - s
                    ex_idx = jnp.clip(t - sid, 0, M - 1)
                    ex_mb = tuple(
                        jax.lax.dynamic_index_in_dim(sd, ex_idx, 0, keepdims=False)
                        for sd in streams_l
                    )
                    new_state = apply_stage(h_in, *ex_mb)
                    out_idx = jnp.clip(t - (pp - 1), 0, M - 1)
                    emit = (sid == pp - 1) & (t >= pp - 1)
                    prev = jax.lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
                    outputs = jax.lax.dynamic_update_index_in_dim(
                        outputs, jnp.where(emit, new_state, prev), out_idx, 0
                    )
                    return (new_state, outputs), None

                (state, outputs), _ = jax.lax.scan(tick, (state, outputs), jnp.arange(T))
                # broadcast results from the last stage to all stages
                mask = (sid == pp - 1).astype(outputs.dtype)
                return jax.lax.psum(outputs * mask, "pp")

            shmapped = jax.shard_map(
                shard_body,
                mesh=mesh,
                in_specs=(P(), *[P("pp") for _ in leaf_stacks], *[P() for _ in stream_datas]),
                out_specs=P(),
                axis_names={"pp"},
            )
            return shmapped(xd, *leaf_stacks, *stream_datas)

        # shard_map with inner scan requires a jit scope even when the model
        # is driven eagerly; cache the jitted engine so eager loops compile once
        from ...observability import compilemem as _compilemem

        engine_jit = _compilemem.ledgered_jit(
            engine, key=f"pp.eager_engine[pp{pp},leaves{n_leaf}]")
        self._jit_cache[cache_key] = engine_jit
        _compilemem.ledger.note_cache_size(
            "pp.eager_engine", len(self._jit_cache))
        return apply(engine_jit, x if isinstance(x, Tensor) else Tensor(x), *stacked,
                     *streams, name="pipeline")
