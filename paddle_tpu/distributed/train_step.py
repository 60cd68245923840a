"""DistributedTrainStep — the hybrid-parallel compiled step (reference
analogue: the whole Fleet meta_parallel runtime, SURVEY.md §3.3; here the
schedule/overlap/collectives are XLA's job via GSPMD shardings).

Sharding decisions, matching HybridCommunicateGroup semantics:
- weights: each Parameter's `partition_spec` ("mp" for TP layers) —
  optionally + a "sharding"-axis dim for ZeRO stage 3;
- optimizer slots (and master weights): weight spec + "sharding" axis
  (ZeRO-1; XLA's weight-update sharding makes stage-2 grad reduce-scatter
  fall out of this — PAPERS.md[4]);
- batch: first dim over (dp, sharding) — both consume distinct data shards,
  as in the reference's DP×sharding grid;
- everything else replicated.

XLA then inserts/overlaps all-reduce / reduce-scatter / all-gather over ICI
— the EagerReducer, GroupSharded*, p2p machinery of the reference collapses
into these annotations.
"""
import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..jit_api import TrainStep
from ..observability import compilemem as _compilemem
from ..observability import flightrec as _flightrec
from ..observability import goodput as _goodput
from ..observability import tracing as _tracing
from ..observability import watchdog as _watchdog
from ..testing import chaos
from .mesh import get_mesh


def _axis_in_use(spec):
    used = set()
    for e in spec:
        if e is None:
            continue
        for n in e if isinstance(e, tuple) else (e,):
            used.add(n)
    return used


def _add_axis(spec, shape, mesh, axis):
    """Add `axis` sharding on the first divisible dim not already sharded."""
    if axis not in mesh.axis_names or mesh.shape[axis] == 1:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    if axis in _axis_in_use(entries):
        return P(*entries)
    for i, (e, dim) in enumerate(zip(entries, shape)):
        cur = 1
        if e is not None:
            for n in e if isinstance(e, tuple) else (e,):
                cur *= mesh.shape[n]
        if dim % (cur * mesh.shape[axis]) == 0 and dim > 0:
            if e is None:
                entries[i] = axis
            else:
                entries[i] = (e if isinstance(e, tuple) else (e,)) + (axis,)
            return P(*entries)
    return P(*entries)


class DistributedTrainStep(TrainStep):
    """sharding_stage: 0 = pure DP/TP, 1/2 = shard optimizer state (+XLA
    grad reduce-scatter), 3 = also shard parameters (FSDP)."""

    def __init__(self, model, loss_fn, optimizer, n_labels=1, scaler=None, mesh=None,
                 sharding_stage=1, batch_axes=("dcn_dp", "dp", "sharding"), metrics_bus=None,
                 accumulate_steps=1, nonfinite_guard=None):
        self.mesh = mesh if mesh is not None else get_mesh()
        self.sharding_stage = sharding_stage
        self.batch_axes = batch_axes
        super().__init__(model, loss_fn, optimizer, n_labels=n_labels, scaler=scaler,
                         metrics_bus=metrics_bus, accumulate_steps=accumulate_steps,
                         nonfinite_guard=nonfinite_guard)
        self._place_state()
        # Tier-0 snapshot hook (distributed/checkpoint/tiers.py): detached by
        # default — the step path pays one attribute check
        self._snapshot_ring = None
        self._snapshot_replicator = None
        self._publish_thread = None

    # -- sharding construction ----------------------------------------------
    def _ns(self, spec):
        return NamedSharding(self.mesh, spec)

    def _param_spec(self, p):
        spec = p.partition_spec if getattr(p, "partition_spec", None) is not None else P()
        spec = P(*spec) if not isinstance(spec, P) else spec
        # drop axes the mesh doesn't have (e.g. mp spec on a dp-only mesh)
        entries = []
        for e in list(spec):
            if e is None:
                entries.append(None)
            else:
                names = tuple(n for n in (e if isinstance(e, tuple) else (e,)) if n in self.mesh.axis_names and self.mesh.shape[n] > 1)
                entries.append(names if len(names) > 1 else (names[0] if names else None))
        spec = P(*entries)
        if self.sharding_stage >= 3:
            spec = _add_axis(spec, tuple(p.shape), self.mesh, "sharding")
        return spec

    def _slot_spec(self, param_spec, param_shape, slot_arr):
        if np.shape(slot_arr) == tuple(param_shape) and self.sharding_stage >= 1:
            return _add_axis(param_spec, tuple(param_shape), self.mesh, "sharding")
        if np.shape(slot_arr) == tuple(param_shape):
            return param_spec
        return P()

    def _batch_spec(self, arr):
        if np.ndim(arr) == 0:
            return P()
        # context parallelism: [B, S, ...] inputs additionally shard their
        # SEQUENCE dim on the sep axis (the ring-attention island inside the
        # model consumes exactly this layout; mesh.py sep row). Keyed on the
        # MODEL's flag — a sep>1 mesh alone (e.g. Ulysses experiments) must
        # not silently re-layout inputs the model consumes replicated.
        sep = None
        if (getattr(getattr(self.model, "config", None), "context_parallel", False)
                and "sep" in self.mesh.axis_names and self.mesh.shape["sep"] > 1
                and np.ndim(arr) >= 2
                and np.shape(arr)[1] % self.mesh.shape["sep"] == 0):
            sep = "sep"
        axes = tuple(a for a in self.batch_axes if a in self.mesh.axis_names and self.mesh.shape[a] > 1)
        if not axes:
            return P(None, sep) if sep else P()
        total = int(np.prod([self.mesh.shape[a] for a in axes]))
        if np.shape(arr)[0] % total != 0:
            import warnings

            warnings.warn(
                f"batch dim {np.shape(arr)[0]} not divisible by dp×sharding={total}; "
                "falling back to replicated input (no data parallelism for this array)",
                stacklevel=3,
            )
            return P(None, sep) if sep else P()
        base = axes if len(axes) > 1 else axes[0]
        # no trailing None entry when sep is unused: a rank-1 input (e.g.
        # [B] labels) cannot carry a length-2 spec
        return P(base, sep) if sep else P(base)

    def _sharding_trees(self, batch_datas):
        p_spec = {k: self._param_spec(p) for k, p in self._trainable.items()}
        params_sh = {k: self._ns(s) for k, s in p_spec.items()}
        buffers_sh = {k: self._ns(P()) for k in self._buffers}
        frozen_sh = {k: self._ns(P()) for k in self._frozen}
        slots_sh = {}
        for name, slots in self.opt_state["slots"].items():
            pspec = p_spec.get(name, P())
            pshape = tuple(self._trainable[name].shape) if name in self._trainable else ()
            slots_sh[name] = {
                s: self._ns(self._slot_spec(pspec, pshape, arr)) for s, arr in slots.items()
            }
        opt_sh = {"step": self._ns(P()), "slots": slots_sh}
        scaler_sh = (
            {k: self._ns(P()) for k in self._scaler_state} if self._scaler_state is not None else None
        )
        batch_sh = tuple(self._ns(self._batch_spec(b)) for b in batch_datas)
        return params_sh, buffers_sh, frozen_sh, opt_sh, scaler_sh, batch_sh

    def _nf_sharding(self):
        """Replicated shardings for the non-finite sentinel counters (two
        scalars), mirroring self._nf_state's pytree — None when the guard
        is off."""
        if self._nf_state is None:
            return None
        return {k: self._ns(P()) for k in self._nf_state}

    def _dyn_sharding(self):
        """Replicated shardings for the dynamics stats carry (a handful of
        scalars + f32[G] vectors — ISSUE 13), mirroring self._dyn_state's
        pytree; None when dynamics is disabled."""
        if self._dyn_state is None:
            return None
        return {k: self._ns(P()) for k in self._dyn_state}

    def _compile(self, step_fn):
        # deferred: in_shardings depend on batch shapes; compile lazily,
        # keyed by batch shape/dtype signature
        self._jitted = {}
        return None

    def _place_state(self):
        """device_put params/opt state onto their shardings once, up front."""
        for k, p in self._trainable.items():
            p._data = jax.device_put(p._data, self._ns(self._param_spec(p)))
        for k, b in self._buffers.items():
            b._data = jax.device_put(b._data, self._ns(P()))
        p_spec = {k: self._param_spec(p) for k, p in self._trainable.items()}
        new_slots = {}
        for name, slots in self.opt_state["slots"].items():
            pshape = tuple(self._trainable[name].shape) if name in self._trainable else ()
            new_slots[name] = {
                s: jax.device_put(arr, self._ns(self._slot_spec(p_spec.get(name, P()), pshape, arr)))
                if hasattr(arr, "shape")
                else arr
                for s, arr in slots.items()
            }
        self.opt_state = {"step": self.opt_state["step"], "slots": new_slots}

    # -- multi-tier checkpointing (ISSUE 3) ---------------------------------
    def full_state_dict(self):
        """Flat ``name -> Tensor`` over everything a resume needs: trainable
        params (``p.*``), buffers (``b.*``), and the optimizer pytree
        (``opt.*``, keyed by tree path). This is the unit all checkpoint
        tiers trade in; param/buffer entries alias the live tensors, so a
        Snapshot.restore_into over this dict restores the model in place —
        follow with :meth:`load_full_state_dict` to rebuild the optimizer
        pytree from the restored leaves."""
        from ..framework.core import Tensor

        sd = {f"p.{k}": p for k, p in self._trainable.items()}
        sd.update({f"b.{k}": b for k, b in self._buffers.items()})
        flat, _ = jax.tree_util.tree_flatten_with_path(self.opt_state)
        for path, leaf in flat:
            sd[f"opt.{jax.tree_util.keystr(path)}"] = Tensor(leaf)
        return sd

    def load_full_state_dict(self, sd, step=None):
        """Adopt a restored :meth:`full_state_dict`: rebind params/buffers
        and rebuild ``opt_state`` from the ``opt.*`` leaves (which are
        detached Tensor wrappers — mutating them never wrote back). ``step``
        also restores the optimizer's python-side step counter."""
        from ..framework.core import _bump_mutation_version

        for k, p in self._trainable.items():
            key = f"p.{k}"
            if key in sd:
                p._data = sd[key]._data
        for k, b in self._buffers.items():
            key = f"b.{k}"
            if key in sd:
                b._data = sd[key]._data
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.opt_state)
        leaves = []
        for path, leaf in flat:
            key = f"opt.{jax.tree_util.keystr(path)}"
            leaves.append(sd[key]._data if key in sd else leaf)
        self.opt_state = jax.tree_util.tree_unflatten(treedef, leaves)
        _bump_mutation_version()  # rebinds must invalidate weight caches
        if step is not None:
            self.optimizer._global_step = int(step)

    def attach_snapshot_ring(self, ring, every=None, replicator=None):
        """Arm Tier-0 snapshots at step boundaries: every ``every`` steps
        (default: the ring's cadence / PADDLE_CKPT_SNAPSHOT_EVERY) the full
        state is device→host copied into ``ring``; with a ``replicator``
        the snapshot is also published for peers (Tier 1). Publication is
        asynchronous and best-effort — serialization + fsync run off the
        training thread, and a tick whose writer is still busy is skipped,
        so the newest peer-visible snapshot may lag the ring by a cadence
        tick or two (a peer restore simply replays those steps)."""
        if every is not None:
            ring.every = int(every)
        self._snapshot_ring = ring
        self._snapshot_replicator = replicator
        return ring

    def _full_state_arrays(self):
        """Raw-array variant of full_state_dict for the snapshot hot path —
        no Tensor wrapping (Snapshot copies host-side anyway)."""
        sd = {f"p.{k}": p._data for k, p in self._trainable.items()}
        sd.update({f"b.{k}": b._data for k, b in self._buffers.items()})
        flat, _ = jax.tree_util.tree_flatten_with_path(self.opt_state)
        for path, leaf in flat:
            sd[f"opt.{jax.tree_util.keystr(path)}"] = leaf
        return sd

    def _maybe_snapshot(self, step):
        # the ring owns the cadence gate; the callable defers building the
        # state mapping to the steps that actually snapshot
        snap = self._snapshot_ring.maybe_snapshot(self._full_state_arrays, step)
        if snap is not None and self._snapshot_replicator is not None:
            # publication serializes + fsyncs the full state — off the
            # training thread (the snapshot's arrays are immutable owned
            # host copies, so the writer races nothing). One in flight: a
            # still-busy writer just skips this cadence tick.
            import threading

            t = self._publish_thread
            if t is None or not t.is_alive():
                self._publish_thread = threading.Thread(
                    target=self._snapshot_replicator.publish, args=(snap,),
                    daemon=True)
                self._publish_thread.start()

    def __call__(self, *batch):
        from ..framework import random as prandom
        from ..framework.core import Tensor, to_tensor

        with _tracing.span("train.step.host_prep"):
            batch_datas = tuple(to_tensor(b)._data for b in batch)
            sig = tuple((tuple(np.shape(b)), str(np.asarray(b).dtype) if not hasattr(b, "dtype") else str(b.dtype)) for b in batch_datas)
        jitted = self._jitted.get(sig)
        first = jitted is None
        # a signature-miss call builds and compiles: one `train.step.build`
        # in the set-up log, the sharding trees and the jit wrapper its
        # child `train.step.compile_build` (a span of that name when
        # tracing is enabled), the compile the ledger's `compile` under it
        with self._build_phase() if first else _tracing._NULL:
            if first:
                with _tracing.setup_phase("train.step.compile_build"):
                    shardings = self._sharding_trees(batch_datas)
                    params_sh, buffers_sh, frozen_sh, opt_sh, scaler_sh, batch_sh = shardings
                    nf_sh = self._nf_sharding()
                    dyn_sh = self._dyn_sharding()
                    jitted = _compilemem.ledgered_jit(
                        self._step_fn, key="train.step",
                        in_shardings=(params_sh, buffers_sh, frozen_sh, opt_sh, scaler_sh, nf_sh, dyn_sh, self._ns(P()), self._ns(P()), batch_sh),
                        out_shardings=(self._ns(P()), params_sh, buffers_sh, opt_sh, scaler_sh, nf_sh, dyn_sh),
                        donate_argnums=(0, 1, 3, 4, 5, 6),
                    )
                    self._jitted[sig] = jitted
                    _compilemem.ledger.note_cache_size(
                        "train.step.signatures", len(self._jitted))
            params = {k: p._data for k, p in self._trainable.items()}
            buffers = {k: b._data for k, b in self._buffers.items()}
            frozen = {k: p._data for k, p in self._frozen.items()}
            lr = self.optimizer.get_lr()
            # a signature-miss dispatch pays XLA compile: goodput counts it
            # as init/compile, not step time (the MPMD-scaling paper's
            # bubble-vs-compute split needs the same discipline)
            with _tracing.span("train.step.dispatch"), \
                    _goodput.account("init" if first else "step"):
                with self.mesh:
                    # OOM-forensics seam (ISSUE 8) — same contract as the
                    # single-host TrainStep dispatch
                    try:
                        chaos.site("obs.oom")
                        (loss, new_params, new_buffers, self.opt_state,
                         self._scaler_state, self._nf_state,
                         self._dyn_state) = jitted(
                            params, buffers, frozen, self.opt_state,
                            self._scaler_state, self._nf_state,
                            self._dyn_state, lr, prandom.next_key(),
                            batch_datas
                        )
                    except Exception as e:
                        _compilemem.maybe_oom_report(e, program="train.step")
                        raise
        for k, v in new_params.items():
            self._trainable[k]._data = v
        for k, v in new_buffers.items():
            self._buffers[k]._data = v
        from ..framework.core import _bump_mutation_version

        _bump_mutation_version()  # direct rebinds must invalidate weight caches
        sched = self.optimizer._learning_rate_scheduler
        if sched is not None:
            sched.step()
        self.optimizer._global_step += 1
        if self._snapshot_ring is not None:
            # step BOUNDARY: params/opt-state are a consistent step; the
            # snapshot blocks only for the device→host copy
            self._maybe_snapshot(self.optimizer._global_step)
        _watchdog.maybe_beat(self.optimizer._global_step)
        self._nf_check()
        self._dyn_check()
        _flightrec.maybe_capture_step(self.optimizer._global_step)
        if self.metrics_bus is not None:
            if self.metrics_bus.tokens_per_step is None and batch_datas:
                import math

                self.metrics_bus.tokens_per_step = int(math.prod(batch_datas[0].shape))
            self.metrics_bus.on_step(loss=loss)
        return Tensor(loss)

    def run_steps(self, *batch, n, stacked=False):
        """n sharded steps in one dispatch: the same lax.scan program as
        TrainStep.run_steps, jitted with the full in/out sharding trees so
        GSPMD lays out params/opt-state/batch exactly like the single-step
        path (stacked batches carry their per-step specs shifted one dim
        right)."""
        from ..framework import random as prandom
        from ..framework.core import Tensor, to_tensor

        batch_datas = tuple(to_tensor(b)._data for b in batch)
        if stacked:
            self._check_stacked(batch_datas, n)
        sig = ("multi", n, stacked,
               tuple((tuple(np.shape(b)), str(b.dtype)) for b in batch_datas))
        jitted = self._jitted.get(sig)
        first = jitted is None
        if jitted is None:
            # per-step batch shapes decide the batch specs; stacked inputs
            # prepend a replicated scan dim
            inner = tuple(b[0] for b in batch_datas) if stacked else batch_datas
            params_sh, buffers_sh, frozen_sh, opt_sh, scaler_sh, batch_sh = (
                self._sharding_trees(inner))
            if stacked:
                batch_sh = tuple(
                    self._ns(P(None, *tuple(self._batch_spec(b)))) for b in inner)
            nf_sh = self._nf_sharding()
            dyn_sh = self._dyn_sharding()
            jitted = _compilemem.ledgered_jit(
                self._multi_fn(n, stacked),
                key=f"train.multi[n={n},stacked={stacked}]",
                in_shardings=(params_sh, buffers_sh, frozen_sh, opt_sh,
                              scaler_sh, nf_sh, dyn_sh, self._ns(P()),
                              self._ns(P()), batch_sh),
                out_shardings=(self._ns(P()), params_sh, buffers_sh, opt_sh,
                               scaler_sh, nf_sh, dyn_sh),
                donate_argnums=(0, 1, 3, 4, 5, 6),
            )
            self._jitted[sig] = jitted
            _compilemem.ledger.note_cache_size(
                "train.step.signatures", len(self._jitted))
        params = {k: p._data for k, p in self._trainable.items()}
        buffers = {k: b._data for k, b in self._buffers.items()}
        frozen = {k: p._data for k, p in self._frozen.items()}
        lr = self.optimizer.get_lr()
        # signature-miss dispatches pay XLA compile — init, not step (same
        # discipline as the single-step path)
        with _tracing.span("train.run_steps.dispatch"), \
                _goodput.account("init" if first else "step"):
            with self.mesh:
                try:
                    chaos.site("obs.oom")
                    (losses, new_params, new_buffers, self.opt_state,
                     self._scaler_state, self._nf_state,
                     self._dyn_state) = jitted(
                        params, buffers, frozen, self.opt_state,
                        self._scaler_state, self._nf_state, self._dyn_state,
                        lr, prandom.next_key(), batch_datas
                    )
                except Exception as e:
                    _compilemem.maybe_oom_report(e, program="train.multi")
                    raise
        return self._finish_run_steps(losses, new_params, new_buffers, n)
