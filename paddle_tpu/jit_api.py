"""Compiled execution (reference analogue: @paddle.jit.to_static +
dygraph-to-static, python/paddle/jit/ — but TPU-native: tracing IS jax).

Key design (SURVEY.md §3.1): the dygraph tape is built from traceable jax
ops, so wrapping a whole train step in jax.jit compiles forward + backward +
optimizer into ONE XLA program. `TrainStep` is that wrapper; `jit`/`to_static`
are the user-facing decorators.
"""
import functools
import time as _time

import jax
import jax.numpy as jnp

from .framework import random as prandom
from .framework.core import Tensor, _bump_mutation_version, to_tensor
from .observability import compilemem as _compilemem
from .observability import devprof as _devprof
from .observability import dynamics as _dynamics
from .observability import flightrec as _flightrec
from .observability import goodput as _goodput
from .observability import tracing as _tracing
from .observability import watchdog as _watchdog
from .observability.metrics import registry as _registry
from .testing import chaos
from .utils.envs import env_int as _env_int

#: consecutive non-finite (NaN/Inf loss or grads) steps tolerated before
#: the sentinel raises NonFiniteLossError; <= 0 disables the guard
NONFINITE_TOLERANCE_ENV = "PADDLE_NONFINITE_TOLERANCE"
#: host-side check cadence in dispatches (reading the device counters
#: synchronizes on the step); default max(tolerance, 16)
NONFINITE_CHECK_ENV = "PADDLE_NONFINITE_CHECK_EVERY"


class NonFiniteLossError(FloatingPointError):
    """The non-finite sentinel tripped: loss or gradients were NaN/Inf for
    PADDLE_NONFINITE_TOLERANCE consecutive steps. Every one of those
    updates was SKIPPED in-program (weights are uncorrupted) — but a model
    that cannot produce a finite step anymore is not training, so the loop
    is stopped instead of burning the rest of the job silently."""


def jit(fn=None, static_argnums=None, donate_argnums=None, backend=None):
    """Compile a Tensor->Tensor function with XLA. An implicit PRNG key is
    threaded per call so dropout stays random without retracing."""

    def deco(f):
        kw = {}
        # user indexes refer to f's positional args; inner prepends the key,
        # so shift by exactly 1 (inner takes *args positionally, not packed)
        if static_argnums is not None:
            nums = static_argnums if isinstance(static_argnums, (list, tuple)) else (static_argnums,)
            kw["static_argnums"] = tuple(a + 1 for a in nums)
        if donate_argnums is not None:
            nums = donate_argnums if isinstance(donate_argnums, (list, tuple)) else (donate_argnums,)
            kw["donate_argnums"] = tuple(a + 1 for a in nums)

        def _inner(key, *args, **kwargs):
            with prandom.rng_guard(key):
                return f(*args, **kwargs)

        # the compile-ledger wrapper (ISSUE 8): records every (re)trace
        # of this program — key'd per decorated function, so shape drift
        # on ONE function reads as churn, not as distinct programs
        inner = _compilemem.ledgered_jit(
            _inner, key=f"jit.{getattr(f, '__name__', 'fn')}", **kw)

        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            return inner(prandom.next_key(), *args, **kwargs)

        wrapper._jax_fn = inner
        return wrapper

    return deco(fn) if fn is not None else deco


def to_static(function=None, input_spec=None, build_strategy=None, backend=None, **kwargs):
    """@paddle.jit.to_static parity. If applied to a Layer, returns a wrapper
    whose __call__ runs the compiled functional forward. Honors
    jit.enable_to_static(False) (reference: ProgramTranslator.enable) — the
    object is returned unconverted for eager debugging — and skips functions
    marked @not_to_static."""
    from .nn.layer.layers import Layer

    def deco(obj):
        import importlib

        jit_ns = importlib.import_module(__package__ + ".jit")
        if not getattr(jit_ns, "_to_static_enabled", True):
            return obj
        if getattr(obj, "_not_to_static", False) or jit_ns.is_ignored(obj):
            return obj
        if isinstance(obj, Layer):
            return StaticLayer(obj)
        return jit(_convert_control_flow(obj))

    return deco(function) if function is not None else deco


def _convert_control_flow(fn):
    """Attempt the dy2static AST rewrite (data-dependent if/while/for →
    lax.cond/while_loop/fori_loop); fall back to the plain trace when the
    source is unavailable or unconvertible (reference: convert_to_static
    falling back to dygraph, python/paddle/jit/dy2static/convert_call_func.py)."""
    from .jit.dy2static import convert_control_flow

    try:
        return convert_control_flow(fn)
    except Exception:
        return fn


class StaticLayer:
    """A Layer compiled to a pure XLA callable: params/buffers become jit
    arguments via functional_call (reference: PartialProgramLayer running the
    traced program via the run_program op, python/paddle/jit/dy2static).

    The layer's forward gets the dy2static control-flow rewrite (tensor
    if/while -> lax.cond/while_loop) when convertible — same contract as
    function to_static."""

    def __init__(self, layer):
        self._layer = layer
        fwd_fn = _convert_control_flow(type(layer).forward)
        if getattr(fwd_fn, "__dy2static__", False):
            import types

            layer.forward = types.MethodType(fwd_fn, layer)

        def fwd(state, key, args, kwargs):
            with prandom.rng_guard(key):
                out = layer.functional_call(
                    {k: Tensor(v, stop_gradient=True) for k, v in state.items()}, *args, **kwargs
                )
            return out

        # per-INSTANCE key (same convention as static.exec): N compiled
        # instances of one class are N intended programs, not churn
        self._fwd = _compilemem.ledgered_jit(
            fwd, key=f"static_layer.{type(layer).__name__}"
                     f"[{id(layer) & 0xffff:x}]")

    def __call__(self, *args, **kwargs):
        state = self._layer.raw_state_dict()
        return self._fwd(state, prandom.next_key(), args, kwargs)

    def __getattr__(self, name):
        return getattr(self._layer, name)


def not_to_static(fn):
    fn._not_to_static = True
    return fn


class TrainStep:
    """One fully-compiled training step over a dygraph model.

    forward (+AMP autocast) → tape backward → grad clip → optimizer update →
    buffer (BN stats) update, all inside ONE jax.jit with donated state.
    Mirrors what the reference needed eager codegen + fused kernels +
    interpreter scheduling for (SURVEY.md §3.1 consequence).

    loss_fn(outputs, *labels) -> scalar Tensor.

    accumulate_steps=k (reference: fleet gradient_merge_optimizer.py /
    passes/auto_parallel_gradient_merge.py) runs k micro-batches through a
    lax.scan INSIDE the one compiled step: forward+backward per micro-batch,
    f32 grad accumulation, ONE optimizer update on the averaged grads. The
    batch's leading dim must be divisible by k. Composes with AMP (loss
    scale seeds each micro-backward; the finite check runs once on the
    merged grads), grad clip (applied to merged grads) and the
    DistributedTrainStep shardings (micro-split happens after sharding).
    """

    def __init__(self, model, loss_fn, optimizer, n_labels=1, scaler=None, mesh_shardings=None,
                 metrics_bus=None, accumulate_steps=1, nonfinite_guard=None):
        # the set-up log's `train.step.build` starts here and ends with the
        # first call (_build_phase)
        self._t_build0_ns = _time.monotonic_ns()
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.n_labels = n_labels
        self.scaler = scaler
        self.metrics_bus = metrics_bus
        self.accumulate_steps = int(accumulate_steps)
        if self.accumulate_steps < 1:
            raise ValueError(f"accumulate_steps must be >= 1, got {accumulate_steps}")

        self._trainable = {
            k: p for k, p in dict(model.named_parameters()).items() if not p.stop_gradient
        }
        self._frozen = {
            k: p for k, p in dict(model.named_parameters()).items() if p.stop_gradient
        }
        self._buffers = dict(model.named_buffers())
        t_opt0_ns = _time.monotonic_ns()
        self.opt_state = optimizer.init_state(self._trainable)
        # not synced: the device fills the slots while the host traces
        _tracing.setup_record(
            "train.opt_state", t_opt0_ns, _time.monotonic_ns(),
            parent="train.step.build", synced=False,
            opt_state_bytes=_compilemem.tree_nbytes(self.opt_state))
        self._scaler_state = scaler.init_state() if scaler is not None else None
        # non-finite sentinel (ISSUE 9 satellite): an in-program guard
        # skips the optimizer update when loss/grads go NaN/Inf — weights
        # never absorb a poisoned step — and device-resident counters
        # (consecutive + total skips) let the host raise after K
        # consecutive skips instead of training garbage forever. Default
        # ON without a scaler; with a DYNAMIC loss scaler the default is
        # OFF — the scaler's warm-down legitimately produces runs of
        # overflowed (skipped) steps while the scale adjusts, and killing
        # those jobs would defeat the scaler (pass nonfinite_guard=True to
        # arm it anyway, accepting that semantics).
        # PADDLE_NONFINITE_TOLERANCE<=0 or nonfinite_guard=False disables
        # it entirely (nf_state is None and the compiled program carries
        # no counters).
        self._nf_tolerance = _env_int(NONFINITE_TOLERANCE_ENV, 3)
        nf_on = (nonfinite_guard if nonfinite_guard is not None
                 else scaler is None) and self._nf_tolerance > 0
        self._nf_state = {"consec": jnp.zeros((), jnp.int32),
                          "total": jnp.zeros((), jnp.int32)} if nf_on else None
        # reading the device counters synchronizes on the dispatch, so the
        # host check is cadence-gated well above the tolerance; the consec
        # counter is monotone WHILE stuck, so a model that stopped
        # producing finite steps is still always caught at the next read
        self._nf_check_every = max(1, _env_int(NONFINITE_CHECK_ENV,
                                               max(self._nf_tolerance, 16)))
        self._nf_reported = 0     # skips already counted to the registry
        self._nf_since_check = 0  # dispatches since the last host read
        # training-dynamics telemetry (ISSUE 13): a second donated carry —
        # per-layer-group grad/param/update norms, loss EWMA + spike
        # z-score, and the non-finite PROVENANCE mask (which group went
        # NaN/Inf first) — updated in-program every step and spilled to
        # the host once per PADDLE_DYNAMICS_EVERY_STEPS window. Disabled
        # (the default), _dynamics is None: the compiled program carries
        # nothing and the epilogue pays one is-None check.
        self._dynamics = _dynamics.DynamicsMonitor.from_env(self._trainable)
        self._dyn_state = (self._dynamics.init_state()
                           if self._dynamics is not None else None)
        self._dyn_since_check = 0
        # first dispatch pays XLA compile: goodput attributes it to "init"
        self._dispatched = False
        # register with the hang watchdog BEFORE the first step: a rank that
        # wedges in its first compile/collective must still be diagnosable
        # (the init beat gets the watchdog's longer startup deadline)
        _watchdog.arm_from_env()
        # device-time profiling plane (ISSUE 17): PADDLE_DEVPROF=1 samples
        # one timed dispatch per PADDLE_DEVPROF_SAMPLE_EVERY steps;
        # disabled, the step epilogue pays one is-None check
        _devprof.arm_from_env()

        opt = optimizer
        n_lab = n_labels
        acc = self.accumulate_steps
        dyn = self._dynamics

        def fwd_bwd(params, buffers, frozen, key, batch, scale):
            """One forward+tape-backward; returns (loss, grads, new_buffers).
            Grads stay loss-scale-scaled (unscaling happens once, merged)."""
            inputs = batch[:-n_lab] if n_lab else batch
            labels = batch[-n_lab:] if n_lab else ()
            overrides = {k: Tensor(v, stop_gradient=False) for k, v in params.items()}
            buf_over = {k: Tensor(v, stop_gradient=True) for k, v in buffers.items()}
            frozen_over = {k: Tensor(v, stop_gradient=True) for k, v in frozen.items()}
            # named_scope (not host spans): fwd/bwd/opt are fused into ONE
            # XLA program, so phase attribution lives in the HLO metadata and
            # shows up in xprof device traces, where host clocks cannot reach
            with prandom.rng_guard(key), jax.named_scope("forward"):
                out = model.functional_call(
                    {**overrides, **buf_over, **frozen_over},
                    *[Tensor(b) for b in inputs],
                    training=True,
                )
                outs = out if isinstance(out, (tuple, list)) else (out,)
                loss = loss_fn(*outs, *[Tensor(b, stop_gradient=True) for b in labels])
            with jax.named_scope("backward"):
                if scale is not None:
                    # seed the cotangent with the loss scale (≡ scaling the loss)
                    loss.backward(Tensor(jnp.ones_like(loss._data) * scale))
                else:
                    loss.backward()
                grads = {k: t.grad._data for k, t in overrides.items() if t.grad is not None}
            new_buffers = {k: t._data for k, t in buf_over.items()}
            return loss._data, grads, new_buffers

        def step_fn(params, buffers, frozen, opt_state, scaler_state,
                    nf_state, dyn_state, lr, key, batch):
            scale = scaler_state["scale"] if scaler is not None else None
            if acc == 1:
                loss_data, grads, new_buffers = fwd_bwd(params, buffers, frozen, key, batch, scale)
            else:
                # micro-batch split: arrays sharing the batch leading dim are
                # scanned [acc, B/acc, ...]; everything else replicates
                bdim = jnp.shape(batch[0])[0] if batch else 0
                split = [
                    hasattr(b, "shape") and jnp.ndim(b) >= 1 and b.shape[0] == bdim and bdim % acc == 0
                    for b in batch
                ]
                if not any(split):
                    raise ValueError(
                        f"accumulate_steps={acc}: no batch array with leading dim divisible by {acc}"
                    )
                xs = tuple(
                    b.reshape(acc, b.shape[0] // acc, *b.shape[1:]) if s else None
                    for b, s in zip(batch, split)
                )
                keys = jax.random.split(key, acc)

                def micro(carry, x):
                    gacc, buf_c, loss_acc = carry
                    mkey, micro_xs = x
                    micro_batch = tuple(
                        (m if s else b) for m, b, s in zip(micro_xs, batch, split)
                    )
                    loss_m, grads_m, buf_n = fwd_bwd(params, buf_c, frozen, mkey, micro_batch, scale)
                    gacc = {
                        k: gacc[k] + grads_m[k].astype(jnp.float32) for k in gacc
                    }
                    return (gacc, buf_n, loss_acc + loss_m.astype(jnp.float32)), None

                # trace one micro to learn the grad structure (shapes static)
                g0 = jax.eval_shape(
                    lambda p, b, f, kk, bb: fwd_bwd(p, b, f, kk, bb, scale)[1],
                    params, buffers, frozen, keys[0],
                    tuple(x[0] if s else b for x, b, s in zip(xs, batch, split)),
                )
                gacc0 = {k: jnp.zeros(v.shape, jnp.float32) for k, v in g0.items()}
                (gsum, new_buffers, loss_sum), _ = jax.lax.scan(
                    micro, (gacc0, buffers, jnp.float32(0)), (keys, xs)
                )
                grads = {k: v / acc for k, v in gsum.items()}
                loss_data = loss_sum / acc
            if scaler is not None:
                grads = {k: g / scaler_state["scale"] for k, g in grads.items()}

            skip = None
            new_scaler_state = scaler_state
            finite_grads = None
            if scaler is not None or nf_state is not None:
                finite_grads = jnp.all(
                    jnp.stack([jnp.all(jnp.isfinite(g.astype(jnp.float32))) for g in grads.values()])
                ) if grads else jnp.asarray(True)
            if scaler is not None:
                skip = ~finite_grads
                new_scaler_state = scaler.update_state(scaler_state, finite_grads)
            new_nf_state = nf_state
            if nf_state is not None:
                # non-finite sentinel: a NaN/Inf loss or gradient skips the
                # whole update IN-PROGRAM (params, slots and opt step all
                # hold), and the device-resident counters let the host
                # detect a model that stopped producing finite steps
                nf_skip = ~(finite_grads
                            & jnp.all(jnp.isfinite(loss_data.astype(jnp.float32))))
                skip = nf_skip if skip is None else (skip | nf_skip)
                new_nf_state = {
                    "consec": jnp.where(nf_skip, nf_state["consec"] + 1,
                                        0).astype(jnp.int32),
                    "total": nf_state["total"] + nf_skip.astype(jnp.int32),
                }

            # dynamics reads the UNSCALED pre-clip gradients (what the
            # model actually produced); the update side brackets the
            # optimizer below, so ||delta_w|| reflects clip/decay/skip
            raw_grads = grads
            with jax.named_scope("optimizer"):
                if opt._grad_clip is not None:
                    pg = [(Tensor(params[k]), Tensor(g)) for k, g in grads.items()]
                    pg = opt._grad_clip(pg)
                    grads = {k: t._data for (k, _), (_, t) in zip(grads.items(), pg)}

                new_params, new_opt_state = opt.apply_gradients(params, grads, opt_state, lr, skip_update=skip)
            new_dyn_state = dyn_state
            if dyn_state is not None:
                with jax.named_scope("dynamics"):
                    new_dyn_state = dyn.update(dyn_state, loss_data,
                                               raw_grads, params, new_params)
            return (loss_data, new_params, new_buffers, new_opt_state,
                    new_scaler_state, new_nf_state, new_dyn_state)

        self._step_fn = step_fn
        self._compiled = self._compile(step_fn)
        self._compiled_multi = {}  # n -> jitted scan-of-step program
        # HBM budget ledger (ISSUE 8): params + optimizer state become
        # weakly-bound byte providers — the silent bf16->f32 Adam upcast
        # class of regression shows up in device.hbm_component_bytes
        # instead of as an unexplained RESOURCE_EXHAUSTED
        _compilemem.memory.register_component_provider(
            "params", self, "_hbm_params_bytes")
        _compilemem.memory.register_component_provider(
            "optimizer", self, "_hbm_optimizer_bytes")

    def _hbm_params_bytes(self):
        return _compilemem.tree_nbytes(
            [p._data for p in self._trainable.values()]
            + [p._data for p in self._frozen.values()]
            + [b._data for b in self._buffers.values()])

    def _hbm_optimizer_bytes(self):
        return _compilemem.tree_nbytes([self.opt_state, self._scaler_state])

    def _build_phase(self):
        """The set-up log's `train.step.build` round the first call that
        compiles: backdated to this step's construction the first time (the
        optimizer state, `train.opt_state`, and a sharded step's placement
        lie in between), the call's own start after that. Not synced: the
        call returns with the first step still running, and whoever reads
        the loss pays the wait."""
        t0_ns, self._t_build0_ns = self._t_build0_ns, None
        return _tracing.setup_phase("train.step.build", t0_ns=t0_ns,
                                    synced=False)

    def _compile(self, step_fn):
        # ONE logical program: recompiles mean the input signature
        # drifted, which is exactly what the churn detector watches
        return _compilemem.ledgered_jit(
            step_fn, key="train.step", donate_argnums=(0, 1, 3, 4, 5, 6))

    def _multi_fn(self, n, stacked):
        """Pure n-steps-in-one-program function (lax.scan over the step
        body). One host→device dispatch per n steps instead of per step —
        where the per-dispatch host round trip is a large share of a step
        this is the difference between measuring the host and measuring
        the chip. lr is held constant across the n steps
        (scheduler ticks once per call). stacked=True scans a [n, ...]-leading
        batch (a different micro-batch per step)."""
        step_fn = self._step_fn

        def multi_fn(params, buffers, frozen, opt_state, scaler_state,
                     nf_state, dyn_state, lr, key, batch):
            def body(carry, x):
                p, b, o, s, nf, dy = carry
                k, step_batch = (x, batch) if not stacked else x
                loss, p2, b2, o2, s2, nf2, dy2 = step_fn(
                    p, b, frozen, o, s, nf, dy, lr, k, step_batch)
                return (p2, b2, o2, s2, nf2, dy2), loss

            keys = jax.random.split(key, n)
            xs = (keys, batch) if stacked else keys
            (p, b, o, s, nf, dy), losses = jax.lax.scan(
                body, (params, buffers, opt_state, scaler_state, nf_state,
                       dyn_state), xs
            )
            return losses, p, b, o, s, nf, dy

        return multi_fn

    def _compile_multi(self, n, stacked):
        # (n, stacked) are intended program variants — each gets its own
        # ledger key so a legitimate multi-bucket run is not churn
        return _compilemem.ledgered_jit(
            self._multi_fn(n, stacked),
            key=f"train.multi[n={n},stacked={stacked}]",
            donate_argnums=(0, 1, 3, 4, 5, 6))

    def run_steps(self, *batch, n, stacked=False):
        """Run n optimizer steps in a single device dispatch. With
        stacked=False each batch array is reused for every step; with
        stacked=True each batch array carries a leading [n] dim — one
        micro-batch per step, real training in one dispatch. Returns the [n]
        per-step loss array (device-resident until read)."""
        key = (n, stacked)
        cold = key not in self._compiled_multi
        if cold:
            self._compiled_multi[key] = self._compile_multi(n, stacked)
            # the formerly-unbounded program cache (ISSUE 8 satellite):
            # size exported per cache, warn past the configured bound
            _compilemem.ledger.note_cache_size(
                "train.multi", len(self._compiled_multi))
        params = {k: p._data for k, p in self._trainable.items()}
        buffers = {k: b._data for k, b in self._buffers.items()}
        frozen = {k: p._data for k, p in self._frozen.items()}
        lr = self.optimizer.get_lr()
        batch_data = tuple(to_tensor(b)._data for b in batch)
        if stacked:
            self._check_stacked(batch_data, n)
        _dp = _devprof._PLANE
        t0 = _time.monotonic() if _dp is not None else 0.0
        try:
            chaos.site("obs.oom")
            (losses, new_params, new_buffers, self.opt_state,
             self._scaler_state, self._nf_state, self._dyn_state) = (
                self._compiled_multi[key](
                    params, buffers, frozen, self.opt_state, self._scaler_state,
                    self._nf_state, self._dyn_state, lr, prandom.next_key(),
                    batch_data,
                )
            )
        except Exception as e:
            _compilemem.maybe_oom_report(e, program="train.multi")
            raise
        if _dp is not None and not cold:
            # cold dispatches include the compile and would poison the
            # device-time table; the losses buffer completes with the
            # program, so waiting on it times the whole n-step dispatch
            _dp.tick(f"train.multi[n={n},stacked={stacked}]", t0, losses,
                     context="train")
        return self._finish_run_steps(losses, new_params, new_buffers, n)

    def _finish_run_steps(self, losses, new_params, new_buffers, n):
        """Shared run_steps epilogue (also used by DistributedTrainStep):
        write back state and keep the LR schedule ALIGNED — the dispatch ran
        n optimizer steps at the dispatch-start LR (schedule granularity is
        per dispatch), so the scheduler must tick n times, landing on the
        same schedule position as n sequential step() calls."""
        for k, v in new_params.items():
            self._trainable[k]._data = v
        for k, v in new_buffers.items():
            self._buffers[k]._data = v
        _bump_mutation_version()  # direct rebinds must invalidate weight caches
        sched = self.optimizer._learning_rate_scheduler
        if sched is not None:
            for _ in range(n):
                sched.step()
        self.optimizer._global_step += n
        _watchdog.maybe_beat(self.optimizer._global_step)
        # one dispatch covered n steps — always worth the one host read
        self._nf_check(force=True)
        # dynamics stays CADENCE-gated (counting the n covered steps):
        # forcing a spill here would put a device sync inside every
        # multi-step dispatch — exactly what bench.py's timed scan rungs
        # must not pay (they force their own spill after timing)
        self._dyn_check(n=n)
        # one dispatch covered n TRAIN steps: the capture contract counts
        # steps, so the tick burns n, not 1
        _flightrec.maybe_capture_step(self.optimizer._global_step, n=n)
        return Tensor(losses)

    def _nf_check(self, force=False):
        """Host side of the non-finite sentinel: read the device-resident
        skip counters every ``PADDLE_NONFINITE_CHECK_EVERY`` dispatches
        (the read synchronizes on the step, so it is cadence-gated), bump
        ``train.nonfinite_skips`` by the delta, and raise
        :class:`NonFiniteLossError` once the CONSECUTIVE count reaches the
        tolerance. The consecutive counter only grows while skipping, so a
        stuck model is always detected within one cadence window; a
        transient blip that recovers before the read was harmless by
        construction (every skipped update left the weights untouched)."""
        if self._nf_state is None:
            return
        self._nf_since_check += 1
        if not force and self._nf_since_check < self._nf_check_every:
            return
        self._nf_since_check = 0
        # the counter read synchronizes on the step: explicit goodput
        # phase, never silently folded into step time (ISSUE 13 satellite)
        with _goodput.account("telemetry"):
            total = int(self._nf_state["total"])
            consec = int(self._nf_state["consec"])
        if total > self._nf_reported:
            _registry.counter("train.nonfinite_skips").inc(
                total - self._nf_reported)
            self._nf_reported = total
            # non-finite provenance (ISSUE 13): the dynamics carry knows
            # WHICH layer group went NaN/Inf first — attach it to the
            # flight-record bundle (rate-limited: a skip storm commits one
            # bundle per window, not one per read)
            prov = self._nf_provenance()
            _flightrec.record(
                "nonfinite", step=self.optimizer._global_step,
                payload={"skips_total": total, "consecutive": consec,
                         "tolerance": self._nf_tolerance,
                         "provenance": prov})
        if consec >= self._nf_tolerance:
            from .utils.metrics_bus import counters as _counters

            _counters.bump("fault.train.nonfinite_exhausted")
            prov = self._nf_provenance()
            prov_msg = ""
            if prov:
                prov_msg = (
                    f"; first non-finite gradients in layer group(s) "
                    f"{', '.join(prov['first_groups']) or '<loss only>'} "
                    f"at update {prov['first_update']} "
                    f"(currently non-finite: "
                    f"{', '.join(prov['current_groups']) or '<loss only>'})")
            raise NonFiniteLossError(
                f"loss/grads non-finite for {consec} consecutive steps "
                f"(tolerance {self._nf_tolerance}, "
                f"{total} skipped updates total, global step "
                f"{self.optimizer._global_step}){prov_msg} — every skipped "
                f"update left the weights uncorrupted; lower the LR / "
                f"check the data, or raise {NONFINITE_TOLERANCE_ENV}")

    def _nf_provenance(self):
        """The dynamics carry's latched which-group-went-non-finite-first
        record (None when dynamics is off or everything stayed finite)."""
        if self._dynamics is None:
            return None
        with _goodput.account("telemetry"):
            return self._dynamics.provenance(self._dyn_state)

    def _dyn_check(self, force=False, n=1):
        """Host side of the dynamics telemetry: once per
        ``PADDLE_DYNAMICS_EVERY_STEPS`` covered steps (the read
        synchronizes on the step, so it is cadence-gated like the nf
        counters; a run_steps dispatch counts its n steps), spill the
        carry — publish the train.* gauges, extend the flight window,
        fire the loss-spike trigger. Between spills this is one counter
        increment; disabled it is the is-None check above."""
        if self._dynamics is None:
            return
        self._dyn_since_check += n
        if not force and self._dyn_since_check < self._dynamics.every:
            return
        self._dyn_since_check = 0
        with _goodput.account("telemetry"):
            self._dynamics.spill(self._dyn_state,
                                 step=self.optimizer._global_step)
            # re-arm the per-window max-z latch: each window reports its
            # own worst spike
            self._dyn_state = self._dynamics.reset_window(self._dyn_state)

    @staticmethod
    def _check_stacked(batch_data, n):
        import numpy as np

        for b in batch_data:
            if np.shape(b)[0] != n:
                raise ValueError(
                    f"stacked run_steps: leading dim {np.shape(b)[0]} != n={n}")

    def __call__(self, *batch):
        first = not self._dispatched
        with (self._build_phase() if first else _tracing._NULL), \
                _tracing.span("train.step"), \
                _goodput.account("init" if first else "step"):
            with _tracing.span("train.step.host_prep"):
                params = {k: p._data for k, p in self._trainable.items()}
                buffers = {k: b._data for k, b in self._buffers.items()}
                frozen = {k: p._data for k, p in self._frozen.items()}
                lr = self.optimizer.get_lr()
                batch_data = tuple(to_tensor(b)._data for b in batch)
            with _tracing.span("train.step.dispatch"):
                # OOM-forensics seam (ISSUE 8): a RESOURCE_EXHAUSTED out
                # of the dispatch commits telemetry/oom_report.json before
                # re-raising; the obs.oom chaos site injects one
                # deterministically for tests
                _dp = _devprof._PLANE
                t0 = _time.monotonic() if _dp is not None else 0.0
                try:
                    chaos.site("obs.oom")
                    (loss, new_params, new_buffers, self.opt_state,
                     self._scaler_state, self._nf_state,
                     self._dyn_state) = self._compiled(
                        params, buffers, frozen, self.opt_state,
                        self._scaler_state, self._nf_state, self._dyn_state,
                        lr, prandom.next_key(), batch_data
                    )
                except Exception as e:
                    _compilemem.maybe_oom_report(e, program="train.step")
                    raise
                if _dp is not None and not first:
                    # first dispatch includes the XLA compile; the loss
                    # buffer completes with the fused program, so waiting
                    # on it times the full step's device execution
                    _dp.tick("train.step", t0, loss, context="train")
        self._dispatched = True
        # write state back into the dygraph objects
        for k, v in new_params.items():
            self._trainable[k]._data = v
        for k, v in new_buffers.items():
            self._buffers[k]._data = v
        _bump_mutation_version()  # direct rebinds must invalidate weight caches
        sched = self.optimizer._learning_rate_scheduler
        if sched is not None:
            sched.step()
        self.optimizer._global_step += 1
        _watchdog.maybe_beat(self.optimizer._global_step)
        self._nf_check()
        self._dyn_check()
        _flightrec.maybe_capture_step(self.optimizer._global_step)
        if self.metrics_bus is not None:
            if self.metrics_bus.tokens_per_step is None and batch_data:
                import math

                self.metrics_bus.tokens_per_step = int(math.prod(batch_data[0].shape))
            self.metrics_bus.on_step(loss=loss)
        return Tensor(loss)
