"""Compile & HBM observability (ISSUE 8): the XLA compile ledger, the
recompile-churn detector, the HBM memory ledger, and OOM forensics.

Two failure classes cost real sessions: **compile churn** (cold
paged-serve programs were a 7.3x throughput cliff until warmup()) and
**HBM fit** (a silent bf16->f32 Adam upcast ate ~3 GB). This module
measures both instead of rediscovering them post-mortem:

- :func:`ledgered_jit` — the blessed ``jax.jit`` wrapper every compile
  site in ``paddle_tpu/`` goes through (lint-enforced by scripts/ci.sh,
  so the ledger is complete by construction, not best-effort). It detects
  (re)traces exactly — the traced Python body only runs on a jit cache
  miss — and records one :class:`CompileLedger` event per compile:
  program key, abstract input signature, wall time, and trigger
  (cold / warmup / recompile).
- :class:`CompileLedger` — the event log + the churn detector: a program
  KEY names the logical program the caller intends to be stable
  (``train.step``; serving keys embed their bucket/sampling, so bucketed
  variants are distinct programs, not churn). The same key recompiling
  under shape/dtype drift past ``churn_threshold`` distinct signatures
  raises ``compile.churn_alerts``. Program-cache sizes
  (``TrainStep._compiled_multi``, the engine's per-program dicts) are
  exported as ``compile.cache_size{cache=...}`` gauges with a warn bound.
- :class:`MemoryLedger` — harvests ``compiled.memory_analysis()``
  (arg/output/temp/code bytes) per program, **lazily**: the abstract
  signature captured at compile time lets :meth:`MemoryLedger.analyze`
  re-lower with ShapeDtypeStructs on demand (statusz /memz, OOM
  forensics, tests) instead of doubling every compile. It also keeps the
  HBM budget ledger: component byte providers (params, optimizer state,
  KV page pool) registered by the train step and the serving engine,
  summed against the device capacity into ``device.hbm_*`` gauges.
- OOM forensics — :func:`maybe_oom_report` intercepts XLA
  ``RESOURCE_EXHAUSTED`` (and the ``obs.oom`` chaos site's synthetic
  injection) at the dispatch seams and writes
  ``telemetry/oom_report.json`` — ledger snapshot, top-N programs by
  temp bytes, registered contexts (active serving slots/pages), last-N
  compile events — before the exception re-raises.

Like the rest of the package this module imports **no jax at module
scope** (the launcher and forked workers import observability); jax is
imported lazily inside the functions that need it. Compile accounting is
always-on (the metrics cost model: compiles are seconds, a ledger append
is microseconds); the per-dispatch overhead of a warm ledgered call is a
thread-local check + two clock reads, inside the PR-2 <1%-of-step bound.
"""
import functools
import itertools
import json
import os
import sys
import threading
import time
import warnings
import weakref
from collections import OrderedDict, deque
from contextlib import contextmanager, nullcontext

from ..utils.envs import env_bool, env_int, env_str
from . import tracing as _tracing
from .metrics import registry as _registry

__all__ = [
    "CompileLedger", "MemoryLedger", "ledger", "memory", "ledgered_jit",
    "record_compile", "analyze_function", "tree_nbytes", "is_oom",
    "maybe_oom_report", "write_oom_report", "register_oom_context",
    "oom_report_path", "OOM_REPORT_NAME",
]

OOM_REPORT_NAME = "oom_report.json"

# ---- compile.* metrics (always-on, the EventCounters cost model) ----------
_M_EVENTS = _registry.counter(
    "compile.events", help="XLA compiles recorded by the compile ledger")
_M_RECOMPILES = _registry.counter(
    "compile.recompiles",
    help="compiles of a program key that had already compiled before")
_M_CHURN = _registry.counter(
    "compile.churn_alerts",
    help="same logical program recompiled under shape/dtype drift past "
         "the churn threshold")
_M_WALL = _registry.histogram(
    "compile.wall_s",
    buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
             30.0, 60.0, 120.0, 300.0),
    help="per-compile wall time (trace + XLA build + first execution)")
_M_ACTIVE = _registry.gauge(
    "compile.active", help="compiles currently in flight")
_M_CACHE_WARN = _registry.counter(
    "compile.cache_warnings",
    help="program-cache size warnings past the configured bound")
_M_OOM = _registry.counter(
    "device.oom_reports", help="OOM forensics reports written")


def _rank():
    return env_str("PADDLE_TRAINER_ID",
                   os.environ.get("RANK", "0")) or "0"


def compiling_path(directory, rank):
    """The watchdog-visible mid-compile breadcrumb for ``rank``."""
    return os.path.join(directory, f"compiling.{rank}.json")


# ---- what jax says of each compile (jax.monitoring) ------------------------
# jax times a compile's parts itself and publishes them on the compiling
# thread; the ledger banks them between begin() and end() so that an event
# says where its wall time went. docs/OBSERVABILITY.md ("set-up log") has
# which of them fire on a persistent-cache hit and on a miss.
_TIMED = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
_CACHE_TIMED = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
}
_COUNTED = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
    "/jax/compilation_cache/cache_hits": "cache_hits",
}
_mon = threading.local()   # .bank: the open bank; .depth: timed parts open
_listening = False


def _new_bank():
    return {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "retrieval_s": 0.0, "cache_requests": 0, "cache_hits": 0}


def _bank(field, amount):
    bank = getattr(_mon, "bank", None)
    if bank is not None:
        bank[field] += amount
    else:
        # compiled outside a ledger event (an eager op's own program):
        # counted on whatever set-up phase is open on this thread
        _tracing.setup_count(**{"jit_" + field: amount})


def _on_enter(event, value, **kw):
    """jax says a timed part STARTED (a scalar of the same name). Parts
    nest — an inner jit traced inside an outer trace, an eager op compiled
    while tracing, a helper traced while lowering — and the inner one is
    timed inside the outer's time, so only the outermost is banked: the
    three durations of an event are disjoint and never exceed its wall."""
    if event in _TIMED:
        _mon.depth = getattr(_mon, "depth", 0) + 1


def _on_duration(event, duration_secs, **kw):
    field = _TIMED.get(event)
    if field is not None:
        _mon.depth = depth = max(0, getattr(_mon, "depth", 1) - 1)
        if depth:
            return
    else:
        field = _CACHE_TIMED.get(event)
        if field is None or _nested_compile():
            return
    _bank(field, float(duration_secs))


def _nested_compile():
    """The persistent cache's events fire INSIDE a backend compile's timed
    part (depth 1 for a program's own compile). Deeper, they are those of a
    little program compiled while the event's own was traced or lowered,
    which jax never caches: not the event's answer."""
    return getattr(_mon, "depth", 0) > 1


def _on_event(event, **kw):
    field = _COUNTED.get(event)
    if field is not None and not _nested_compile():
        _bank(field, 1)


def _listen():
    """Register the listeners, once: called where the ledger first meets
    jax (ledgered_jit, record_compile), never at import."""
    global _listening
    if _listening or "jax" not in sys.modules:
        return
    _listening = True
    from jax import monitoring

    monitoring.register_scalar_listener(_on_enter)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def _banked_fields(bank, wall_s):
    """A closed bank as an event's fields. `cache` is the persistent
    cache's answer: "off" when no compile request went to one (jax counts a
    request whenever the cache is enabled, directory or not, so the
    directory is asked for too); `other_s` is what jax did not time: the
    first execution and the dispatch."""
    req, hits = bank["cache_requests"], bank["cache_hits"]
    parts = bank["trace_s"] + bank["lower_s"] + bank["backend_s"]
    if not req or not sys.modules["jax"].config.jax_compilation_cache_dir:
        cache = "off"
    else:
        cache = "hit" if hits >= req else "miss"
    return {"trace_s": round(bank["trace_s"], 4),
            "lower_s": round(bank["lower_s"], 4),
            "backend_s": round(bank["backend_s"], 4),
            "cache": cache,
            "retrieval_s": round(bank["retrieval_s"], 4),
            "other_s": round(max(0.0, wall_s - parts), 4)}


class CompileLedger:
    """Process-wide compile event log + recompile-churn detector.

    ``begin(key)`` / ``end(token, ...)`` bracket one compile: begin fires
    at trace start (the traced shim runs only on a jit cache miss), end
    after the dispatch returns — the window covers the XLA build, so a
    rank wedged mid-compile is visible in ``active()`` and in the
    ``compiling.<rank>.json`` breadcrumb the hang watchdog reads. Nested
    begins on one thread (an inner jitted fn traced inside an outer
    trace) are suppressed: the inner body is part of the outer program.
    """

    def __init__(self, max_events=512, churn_threshold=None,
                 cache_warn_bound=None):
        self._lock = threading.Lock()
        self._events = deque(maxlen=int(max_events))
        self._by_key = {}
        self._caches = {}
        self._cache_warned = set()
        self._active = {}
        self._counter = itertools.count(1)
        self._local = threading.local()
        self.churn_threshold = (int(churn_threshold)
                                if churn_threshold is not None
                                else env_int("PADDLE_COMPILE_CHURN_THRESHOLD", 3))
        self.cache_warn_bound = (int(cache_warn_bound)
                                 if cache_warn_bound is not None
                                 else env_int("PADDLE_COMPILE_CACHE_WARN", 64))

    # ---- trigger / suppression scopes ------------------------------------
    @contextmanager
    def trigger(self, label):
        """Label every compile recorded inside the scope (``warmup``)."""
        prev = getattr(self._local, "trigger", None)
        self._local.trigger = label
        try:
            yield
        finally:
            self._local.trigger = prev

    @contextmanager
    def suppressed(self, key=None):
        """Don't record compiles inside the scope — the memory ledger's
        re-lowering for analysis must not show up as real recompiles. What
        jax traced, lowered and compiled inside it is still time spent:
        it goes to the set-up log as a record named ``lower`` (no ledger
        event, no count)."""
        prev = getattr(self._local, "suppress", False)
        prev_bank = getattr(_mon, "bank", None)
        self._local.suppress = True
        _mon.bank = bank = _new_bank()
        t0_ns = time.monotonic_ns()
        try:
            yield
        finally:
            self._local.suppress = prev
            _mon.bank = prev_bank
            t1_ns = time.monotonic_ns()
            _tracing.setup_record(
                "lower", t0_ns, t1_ns, key=None if key is None else str(key),
                **_banked_fields(bank, (t1_ns - t0_ns) / 1e9))

    # ---- the begin/end protocol ------------------------------------------
    def begin(self, key):
        """Mark a compile of ``key`` started. Returns a token for end(),
        or None when this trace is nested (or suppressed) — end(None) is
        a no-op, so callers never need to branch."""
        depth = getattr(self._local, "depth", 0)
        self._local.depth = depth + 1
        if depth or getattr(self._local, "suppress", False):
            return None
        tok = next(self._counter)
        _mon.bank = _new_bank()
        with self._lock:
            self._active[tok] = {"key": str(key), "started_at": time.time(),
                                 "tid": threading.get_ident()}
            _M_ACTIVE.set(len(self._active))
        self._write_compiling()
        return tok

    def exit_trace(self):
        """Trace-shim epilogue: the Python trace ended (the XLA build may
        still be running — the active entry stays until end())."""
        self._local.depth = max(0, getattr(self._local, "depth", 1) - 1)

    def end(self, token, key, wall_s=0.0, signature=None, trigger=None,
            error=None):
        """Close the compile ``begin()`` opened; records one event. A
        ``None`` token (nested/suppressed begin) is a no-op."""
        if token is None:
            return None
        t1_ns = time.monotonic_ns()
        bank, _mon.bank = getattr(_mon, "bank", None) or _new_bank(), None
        with self._lock:
            self._active.pop(token, None)
            _M_ACTIVE.set(len(self._active))
        self._write_compiling()
        timed = {"t0_ns": t1_ns - int(wall_s * 1e9), "t1_ns": t1_ns,
                 **_banked_fields(bank, wall_s)}
        return _ledger_record(self, key, wall_s, signature, trigger, error,
                              timed)

    # ---- cache-size accounting -------------------------------------------
    def note_cache_size(self, name, size):
        """Export a program cache's size (gauge ``compile.cache_size``,
        labeled per cache) and warn once past the configured bound — the
        ``TrainStep._compiled_multi`` unbounded-growth satellite."""
        size = int(size)
        with self._lock:
            self._caches[str(name)] = size
        _registry.gauge("compile.cache_size",
                        help="compiled-program cache sizes, per cache",
                        labels={"cache": str(name)}).set(size)
        if size > self.cache_warn_bound and name not in self._cache_warned:
            with self._lock:
                if name in self._cache_warned:
                    return
                self._cache_warned.add(name)
            _M_CACHE_WARN.inc()
            warnings.warn(
                f"program cache {name!r} holds {size} compiled programs "
                f"(bound {self.cache_warn_bound}; PADDLE_COMPILE_CACHE_WARN"
                f") — unbounded growth usually means an unstable program "
                f"key (shape/dtype drift)", RuntimeWarning, stacklevel=3)

    # ---- introspection ----------------------------------------------------
    def active(self):
        """[{key, started_at, elapsed_s, tid}] — compiles in flight."""
        now = time.time()
        with self._lock:
            return [dict(v, elapsed_s=round(now - v["started_at"], 3))
                    for v in self._active.values()]

    def counts(self):
        """Cheap scalar snapshot (bench deltas): events / wall / churn."""
        with self._lock:
            return {
                "events": sum(e["count"] for e in self._by_key.values()),
                "total_wall_s": round(sum(e["wall_s"]
                                          for e in self._by_key.values()), 4),
                "recompiles": int(_M_RECOMPILES.value),
                "churn_alerts": int(_M_CHURN.value),
            }

    def events(self, n=32):
        """The last ``n`` compile events, oldest first."""
        with self._lock:
            buf = list(self._events)
        return buf[-int(n):]

    def report(self, recent=32):
        """The /compilez payload: per-key rollup, churned keys, recent
        events, in-flight compiles, cache sizes."""
        with self._lock:
            by_key = {
                k: {"count": e["count"], "wall_s": round(e["wall_s"], 4),
                    "signatures": len(e["signatures"]),
                    "triggers": dict(e["triggers"]),
                    "churn_alerts": e["churn_alerts"],
                    "last_signature": e["last_signature"]}
                for k, e in sorted(self._by_key.items())
            }
            caches = dict(self._caches)
        churned = {k: v for k, v in by_key.items() if v["churn_alerts"]}
        counts = self.counts()
        return {
            "events": counts["events"],
            "total_wall_s": counts["total_wall_s"],
            "recompiles": counts["recompiles"],
            "churn_alerts": counts["churn_alerts"],
            "by_key": by_key,
            "churned": churned,
            "recent": self.events(recent),
            "active": self.active(),
            "caches": caches,
            "churn_threshold": self.churn_threshold,
        }

    def reset(self):
        """Test hook: forget events/keys/caches (metric objects keep their
        values — reset those via registry.reset("compile."))."""
        with self._lock:
            self._events.clear()
            self._by_key.clear()
            self._caches.clear()
            self._cache_warned.clear()
            self._active.clear()

    # ---- watchdog breadcrumb ---------------------------------------------
    def _write_compiling(self):
        """Atomic ``compiling.<rank>.json`` under PADDLE_TELEMETRY_DIR so
        the launcher-side hang watchdog can say 'rank 3 is 214 s into
        compiling train.step', cross-process. Removed when nothing is in
        flight. Never raises (a full disk must not kill a compile)."""
        d = env_str("PADDLE_TELEMETRY_DIR")
        if not d:
            return
        path = compiling_path(d, _rank())
        try:
            active = self.active()
            if not active:
                try:
                    os.unlink(path)
                except FileNotFoundError:
                    pass
                return
            os.makedirs(d, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"rank": _rank(), "pid": os.getpid(),
                           "active": active}, f)
            os.replace(tmp, path)
        except OSError:
            pass


def _ledger_record(led, key, wall_s, signature, trigger, error, timed):
    """The shared event-append + churn/trigger classification (module
    function so both ledgered_jit and record_compile use one copy).
    ``timed`` is the event's stamps and what jax said of its parts
    (``_banked_fields``); the finished event also goes to the set-up log as
    a record named ``compile``, child of the phase open on this thread."""
    key = str(key)
    sig = "?" if signature is None else str(signature)
    err = None if error is None else f"{type(error).__name__}: {error}"
    with led._lock:
        entry = led._by_key.get(key)
        first = entry is None
        if first:
            entry = led._by_key[key] = {
                "count": 0, "wall_s": 0.0, "signatures": OrderedDict(),
                "triggers": {}, "churn_alerts": 0, "last_signature": None,
                "warned": False,
            }
        resolved = (getattr(led._local, "trigger", None)
                    or trigger
                    or ("cold" if first else "recompile"))
        entry["count"] += 1
        entry["wall_s"] += float(wall_s)
        entry["triggers"][resolved] = entry["triggers"].get(resolved, 0) + 1
        new_sig = sig not in entry["signatures"]
        entry["signatures"][sig] = entry["signatures"].get(sig, 0) + 1
        while len(entry["signatures"]) > 64:  # bound per-key memory
            entry["signatures"].popitem(last=False)
        entry["last_signature"] = sig
        churned = (new_sig and err is None
                   and len(entry["signatures"]) > led.churn_threshold)
        if churned:
            entry["churn_alerts"] += 1
        rec = {"key": key, "signature": sig, "wall_s": round(float(wall_s), 4),
               "trigger": resolved, "time": time.time(), **timed}
        if err:
            rec["error"] = err
        led._events.append(rec)
    _tracing.setup_record(
        "compile", key=key, trigger=resolved, wall_s=rec["wall_s"],
        **timed, **({"error": err} if err else {}))
    _M_EVENTS.inc()
    _M_WALL.observe(wall_s)
    if not first and err is None:
        _M_RECOMPILES.inc()
    if churned:
        _M_CHURN.inc()
        if not entry["warned"]:
            entry["warned"] = True
            warnings.warn(
                f"compile churn: program {key!r} has compiled "
                f"{entry['count']} times under {len(entry['signatures'])} "
                f"distinct input signatures (threshold "
                f"{led.churn_threshold}) — shape/dtype drift is defeating "
                f"the jit cache; bucket the inputs or split the key",
                RuntimeWarning, stacklevel=4)
    return rec


#: the process-wide singleton every compile site records into
ledger = CompileLedger()


def _signature_of(args, kwargs):
    """Stable abstract-signature string for the churn detector: dtype[shape]
    per array leaf, a short repr for static leaves; hashed tail past 512
    chars so huge pytrees stay bounded. Computed only on a compile."""
    import jax

    leaves, _ = jax.tree_util.tree_flatten((args, kwargs))
    parts = []
    for l in leaves:
        shape = getattr(l, "shape", None)
        dtype = getattr(l, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{dtype}[{','.join(str(s) for s in shape)}]")
        else:
            parts.append(repr(l)[:24])
    sig = ";".join(parts)
    if len(sig) > 512:
        import hashlib

        h = hashlib.blake2b(sig.encode(), digest_size=8).hexdigest()
        sig = f"{sig[:480]}...#{h}"
    return sig


def _abstractify(args, kwargs):
    """(args, kwargs) with every array leaf replaced by a ShapeDtypeStruct —
    the handle MemoryLedger.analyze re-lowers from without holding any
    real buffers alive."""
    import jax

    def to_sds(l):
        shape = getattr(l, "shape", None)
        dtype = getattr(l, "dtype", None)
        if shape is not None and dtype is not None:
            try:
                return jax.ShapeDtypeStruct(tuple(shape), dtype)
            except TypeError:
                return l
        return l

    return jax.tree_util.tree_map(to_sds, (args, kwargs))


def ledgered_jit(fn, key=None, static_argnums=None, track_memory=True,
                 **jit_kwargs):
    """``jax.jit`` with the compile ledger wired in — the blessed wrapper
    scripts/ci.sh lints every ``paddle_tpu/`` compile site onto.

    Trace detection is exact and free: the traced shim's body only runs
    on a jit cache miss, so a warm call costs one thread-local store and
    two clock reads on top of the jitted dispatch. On a compile the
    ledger records (key, abstract signature, wall, trigger) and — when
    ``track_memory=True`` — the MemoryLedger keeps the ShapeDtypeStruct
    signature so ``compiled.memory_analysis()`` can be harvested lazily.
    Exceptions out of the dispatch pass through :func:`maybe_oom_report`,
    which makes every ledgered call site an OOM-forensics seam.
    """
    import jax

    _listen()
    if key is None:
        key = getattr(fn, "__qualname__", None) or getattr(
            fn, "__name__", "anonymous")
    led = ledger
    local = threading.local()

    @functools.wraps(fn)
    def _traced(*args, **kwargs):
        local.token = led.begin(key)
        local.traced = True
        try:
            return fn(*args, **kwargs)
        finally:
            led.exit_trace()

    if static_argnums is not None:
        jit_kwargs["static_argnums"] = static_argnums
    jitted = jax.jit(_traced, **jit_kwargs)  # compile-ledger-ok (the wrapper)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        local.traced = False
        t0 = time.perf_counter()
        try:
            out = jitted(*args, **kwargs)
        except BaseException as e:
            # BaseException, not Exception: a KeyboardInterrupt / chaos
            # SystemExit escaping mid-compile must still release the
            # active-compile token and the compiling.<rank>.json
            # breadcrumb, or every later hang report claims this rank is
            # forever 'wedged compiling <key>'
            if getattr(local, "traced", False):
                led.end(getattr(local, "token", None), key,
                        wall_s=time.perf_counter() - t0,
                        signature=_safe_signature(args, kwargs), error=e)
            if isinstance(e, Exception):
                maybe_oom_report(e, program=key)
            raise
        if getattr(local, "traced", False):
            sig = _safe_signature(args, kwargs)
            led.end(getattr(local, "token", None), key,
                    wall_s=time.perf_counter() - t0, signature=sig)
            if track_memory:
                memory.note_program(key, jitted, args, kwargs,
                                    signature=sig)
        return out

    def lower(*args, **kwargs):
        with led.suppressed(key):
            return jitted.lower(*args, **kwargs)

    wrapper._jitted = jitted
    wrapper._ledger_key = key
    wrapper.lower = lower
    return wrapper


def _safe_signature(args, kwargs):
    try:
        return _signature_of(args, kwargs)
    except Exception:
        return "?"


@contextmanager
def record_compile(key, trigger=None, signature=None):
    """Explicit compile bracket for AOT sites (``jax.export`` /
    ``.lower(...).compile()``) where :func:`ledgered_jit` can't wrap the
    callable. Times the body, records one ledger event, and routes
    exceptions through OOM forensics before re-raising."""
    _listen()
    tok = ledger.begin(key)
    t0 = time.perf_counter()
    try:
        yield
    except BaseException as e:  # incl. interrupts: never leak the token
        ledger.exit_trace()
        ledger.end(tok, key, wall_s=time.perf_counter() - t0,
                   signature=signature, trigger=trigger, error=e)
        if isinstance(e, Exception):
            maybe_oom_report(e, program=key)
        raise
    ledger.exit_trace()
    ledger.end(tok, key, wall_s=time.perf_counter() - t0,
               signature=signature, trigger=trigger)


# ---------------------------------------------------------------------------
# memory ledger
# ---------------------------------------------------------------------------
def tree_nbytes(tree):
    """Total bytes across a pytree's array leaves, from shape/dtype only —
    no host sync, no device touch."""
    import jax

    total = 0
    for l in jax.tree_util.tree_leaves(tree):
        shape = getattr(l, "shape", None)
        dtype = getattr(l, "dtype", None)
        if shape is None or dtype is None:
            continue
        n = 1
        for s in shape:
            n *= int(s)
        try:
            import numpy as np

            total += n * np.dtype(dtype).itemsize
        except TypeError:
            total += n * getattr(dtype, "itemsize", 4)
    return int(total)


def _compile_lock():
    """The serving engines' process-wide compile lock, when the module is
    loaded — re-lowering model programs walks the framework's
    thread-oblivious Tensor state, exactly what that lock exists for."""
    m = sys.modules.get("paddle_tpu.inference.continuous")
    return m._COMPILE_LOCK if m is not None else nullcontext()


def _analysis_dict(ma):
    out = {}
    for name, short in (("argument_size_in_bytes", "argument_bytes"),
                        ("output_size_in_bytes", "output_bytes"),
                        ("temp_size_in_bytes", "temp_bytes"),
                        ("generated_code_size_in_bytes", "code_bytes"),
                        ("alias_size_in_bytes", "alias_bytes")):
        v = getattr(ma, name, None)
        if v is not None:
            out[short] = int(v)
    out["peak_bytes"] = (out.get("argument_bytes", 0)
                         + out.get("output_bytes", 0)
                         + out.get("temp_bytes", 0)
                         - out.get("alias_bytes", 0))
    return out


def _cost_dict(compiled):
    """``compiled.cost_analysis()`` distilled to the devprof join keys
    ({flops, bytes, transcendentals}, floats). Defensive on purpose: the
    API has returned a dict, a list of dicts, and nothing at all across
    jax versions/backends (CPU often omits byte counts) — a missing cost
    row must degrade the roofline, never break the memory harvest."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    out = {}
    for src, short in (("flops", "flops"), ("bytes accessed", "bytes"),
                       ("transcendentals", "transcendentals")):
        try:
            v = float(ca.get(src, 0) or 0)
        except (TypeError, ValueError):
            continue
        if v > 0:
            out[short] = v
    return out or None


class MemoryLedger:
    """HBM budget ledger + lazy per-program ``memory_analysis()`` harvest.

    Components (params, optimizer state, KV page pool, ...) are
    registered as weakly-bound byte providers so N live engines sum and a
    dead one drops out. ``analyze()`` re-lowers captured programs from
    their ShapeDtypeStruct signatures — one extra (suppressed, off-device)
    compile per program, paid only when someone asks (statusz /memz with
    analyze, the OOM report, tests) rather than on every real compile.
    """

    def __init__(self, max_programs=160):
        self._lock = threading.Lock()
        self._programs = OrderedDict()
        self._max_programs = int(max_programs)
        self._providers = {}
        self._static = {}

    # ---- HBM budget components -------------------------------------------
    def set_component(self, name, nbytes):
        """A fixed component byte count (rare; prefer providers)."""
        with self._lock:
            self._static[str(name)] = int(nbytes)

    def register_component_provider(self, name, obj, method_name):
        """Register ``obj.method_name() -> bytes`` weakly under component
        ``name``; multiple live objects per name sum, dead ones vanish."""
        ref = weakref.ref(obj)
        with self._lock:
            self._providers.setdefault(str(name), []).append(
                (ref, str(method_name)))

    def components(self):
        """{component: bytes} — static entries + live provider sums."""
        with self._lock:
            static = dict(self._static)
            providers = {k: list(v) for k, v in self._providers.items()}
            # prune dead refs IN PLACE under the lock (a write-back of the
            # snapshot would clobber providers registered concurrently —
            # e.g. an engine constructed while a scrape thread reports)
            for refs in self._providers.values():
                refs[:] = [(r, m) for r, m in refs if r() is not None]
        out = dict(static)
        for name, refs in providers.items():
            total, live = 0, False
            for ref, meth in refs:
                obj = ref()
                if obj is None:
                    continue
                live = True
                try:
                    total += int(getattr(obj, meth)())
                except Exception:
                    continue
            if live or name not in out:
                out[name] = out.get(name, 0) + total
        return out

    def capacity_bytes(self):
        """Device memory capacity: ``PADDLE_HBM_CAPACITY_BYTES`` env
        override first (CPU hosts have no HBM), else the backend's
        ``memory_stats()['bytes_limit']`` when it exposes one."""
        env = env_str("PADDLE_HBM_CAPACITY_BYTES")
        if env:
            try:
                return int(float(env))
            except ValueError:
                pass
        try:
            import jax

            stats = jax.devices()[0].memory_stats()
            if stats:
                return int(stats.get("bytes_limit", 0)) or None
        except Exception:
            pass
        return None

    # ---- program capture + lazy analysis ---------------------------------
    def note_program(self, key, jitted, args, kwargs, signature=None):
        """Capture (jitted, abstract signature) at compile time so the
        analysis can run later without the real buffers. Bounded LRU."""
        try:
            abstract = _abstractify(args, kwargs)
        except Exception:
            return
        try:
            ref = weakref.ref(jitted)
        except TypeError:
            ref = lambda j=jitted: j  # noqa: E731 — unweakrefable: pin it
        with self._lock:
            self._programs[str(key)] = {
                "jitted": ref, "abstract": abstract, "signature": signature,
                "analysis": None, "cost": None, "error": None,
            }
            self._programs.move_to_end(str(key))
            while len(self._programs) > self._max_programs:
                self._programs.popitem(last=False)

    @staticmethod
    def _compile_captured(jitted, abstract, key=None):
        a, kw = abstract
        with _compile_lock(), ledger.suppressed(key):
            return jitted.lower(*a, **kw).compile()  # compile-ledger-ok (the ledger's own suppressed analysis)

    def compiled(self, key):
        """The compiled executable of one captured program, re-lowered
        from its abstract signature (suppressed in the ledger) — for
        callers that read ``as_text()``, e.g. to see the collectives of a
        sharded step. KeyError for a program never captured."""
        with self._lock:
            v = self._programs[str(key)]
        jitted = v["jitted"]()
        if jitted is None:
            raise KeyError(f"{key}: program garbage-collected")
        return self._compile_captured(jitted, v["abstract"], key)

    def analyze(self, keys=None, force=False):
        """Harvest ``memory_analysis()`` for captured programs (all, or
        the given keys). Each un-analyzed program pays one suppressed
        re-lower+compile under the serving compile lock; results are
        cached. Returns {key: analysis-or-error}."""
        with self._lock:
            todo = [(k, v) for k, v in self._programs.items()
                    if (keys is None or k in keys)
                    and (force or (v["analysis"] is None
                                   and v["error"] is None))]
        out = {}
        for k, v in todo:
            jitted = v["jitted"]()
            if jitted is None:
                err = "program garbage-collected"
                with self._lock:
                    v["error"] = err
                out[k] = {"error": err}
                continue
            try:
                compiled = self._compile_captured(jitted, v["abstract"], k)
                analysis = _analysis_dict(compiled.memory_analysis())
                cost = _cost_dict(compiled)
                with self._lock:
                    v["analysis"] = analysis
                    v["cost"] = cost
                    v["error"] = None
                out[k] = analysis
            except Exception as e:
                err = f"{type(e).__name__}: {str(e)[:200]}"
                with self._lock:
                    v["error"] = err
                out[k] = {"error": err}
        self.refresh_gauges()
        return out

    def programs(self):
        """{key: {signature, analysis|None, cost|None, error|None}} — no
        analysis is forced; un-analyzed programs show ``analysis: None``."""
        with self._lock:
            return {k: {"signature": v["signature"],
                        "analysis": v["analysis"],
                        "cost": v.get("cost"), "error": v["error"]}
                    for k, v in self._programs.items()}

    def program_cost(self, key):
        """The devprof join hook: the cached cost_analysis row for one
        program (flops + bytes), with byte counts backfilled from the
        memory analysis when cost_analysis omitted them (CPU backends
        report flops but not traffic). None until analyzed."""
        with self._lock:
            v = self._programs.get(str(key))
            if v is None:
                return None
            cost = dict(v.get("cost") or {})
            analysis = v["analysis"]
        if "bytes" not in cost and analysis and "error" not in analysis:
            nbytes = (analysis.get("argument_bytes", 0)
                      + analysis.get("output_bytes", 0))
            if nbytes > 0:
                cost["bytes"] = float(nbytes)
        return cost or None

    def top_programs_by_temp(self, n=5):
        """The analyzed programs ranked by temp bytes — the OOM report's
        'who ate the HBM' list."""
        progs = self.programs()
        ranked = sorted(
            ((k, v["analysis"]) for k, v in progs.items() if v["analysis"]),
            key=lambda kv: kv[1].get("temp_bytes", 0), reverse=True)
        return [{"key": k, **a} for k, a in ranked[:int(n)]]

    def temp_peak_bytes(self):
        progs = self.programs()
        return max((v["analysis"].get("temp_bytes", 0)
                    for v in progs.values() if v["analysis"]), default=0)

    # ---- the budget report ------------------------------------------------
    def refresh_gauges(self):
        """Publish the ``device.hbm_*`` gauges from the current ledger."""
        comps = self.components()
        used = sum(comps.values())
        cap = self.capacity_bytes()
        temp = self.temp_peak_bytes()
        for name, v in comps.items():
            _registry.gauge("device.hbm_component_bytes",
                            help="HBM budget components (params, optimizer "
                                 "state, KV page pool, ...)",
                            labels={"component": name}).set(v)
        _registry.gauge("device.hbm_used_bytes",
                        help="sum of registered HBM components").set(used)
        _registry.gauge(
            "device.hbm_temp_peak_bytes",
            help="largest analyzed per-program temp footprint").set(temp)
        if cap:
            _registry.gauge("device.hbm_capacity_bytes",
                            help="device memory capacity").set(cap)
            _registry.gauge(
                "device.hbm_headroom_bytes",
                help="capacity - components - temp high-water").set(
                max(0, cap - used - temp))
        return {"components": comps, "used_bytes": used,
                "capacity_bytes": cap, "temp_peak_bytes": temp}

    def report(self, analyze=False):
        """The /memz payload. ``analyze=True`` forces the lazy harvest
        first (an extra off-device compile per un-analyzed program)."""
        if analyze:
            self.analyze()
        budget = self.refresh_gauges()
        cap = budget["capacity_bytes"]
        used = budget["used_bytes"] + budget["temp_peak_bytes"]
        return {
            **budget,
            "headroom_bytes": (max(0, cap - used) if cap else None),
            "budget_fraction": (round(used / cap, 6) if cap else None),
            "programs": self.programs(),
            "top_programs_by_temp": self.top_programs_by_temp(),
        }

    def reset(self):
        with self._lock:
            self._programs.clear()
            self._providers.clear()
            self._static.clear()


memory = MemoryLedger()


def analyze_function(fn, *args, static_argnums=None, key=None):
    """One-off memory probe (the test_compiled_memory API, folded into the
    ledger): lower+compile ``fn`` for ``args`` and return the
    memory-analysis byte dict. Recorded in the compile ledger under
    ``probe.<name>`` with trigger ``probe`` and captured in the memory
    ledger like any other program."""
    import jax

    key = key or f"probe.{getattr(fn, '__name__', 'fn')}"
    kw = {}
    if static_argnums is not None:
        kw["static_argnums"] = static_argnums
    jitted = jax.jit(fn, **kw)  # compile-ledger-ok (recorded right below)
    with record_compile(key, trigger="probe",
                        signature=_safe_signature(args, {})):
        compiled = jitted.lower(*args).compile()  # compile-ledger-ok
    analysis = _analysis_dict(compiled.memory_analysis())
    memory.note_program(key, jitted, args, {},
                        signature=_safe_signature(args, {}))
    with memory._lock:
        if key in memory._programs:
            memory._programs[key]["analysis"] = analysis
    return analysis


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
                "Allocation failure", "OOM")
_oom_contexts = []
_oom_lock = threading.Lock()
# (id of last reported exc, report path, monotonic stamp): the double-seam
# dedup. The two seams (ledgered wrapper + engine/train-step handler) fire
# within ONE raise propagation, so the id match is time-bounded — a later
# distinct OOM whose exception object happens to reuse the freed address
# still gets its own report. (A weakref would be cleaner, but built-in
# exception types don't support weak references.)
_last_oom = [None, None, 0.0]
_OOM_DEDUP_WINDOW_S = 5.0


def is_oom(exc):
    """Is this exception an XLA device-memory exhaustion? Matches the
    RESOURCE_EXHAUSTED family by message/type name, plus the ``obs.oom``
    chaos site's synthetic injection (the deterministic test hook)."""
    if exc is None:
        return False
    try:
        from ..testing.chaos import FaultInjected

        if isinstance(exc, FaultInjected) and exc.site == "obs.oom":
            return True
    except Exception:
        pass
    text = f"{type(exc).__name__}: {exc}"
    return any(m in text for m in _OOM_MARKERS)


def register_oom_context(name, obj, method_name):
    """Register ``obj.method_name() -> dict`` (weakly bound) to be
    snapshotted into the OOM report — the serving engine registers its
    active slots / page-pool occupancy here."""
    with _oom_lock:
        _oom_contexts.append((str(name), weakref.ref(obj),
                              str(method_name)))


def _collect_oom_contexts():
    out = {}
    with _oom_lock:
        items = list(_oom_contexts)
    live = []
    for name, ref, meth in items:
        obj = ref()
        if obj is None:
            continue
        live.append((name, ref, meth))
        try:
            out.setdefault(name, []).append(getattr(obj, meth)())
        except Exception as e:
            out.setdefault(name, []).append(
                {"error": f"{type(e).__name__}: {e}"})
    with _oom_lock:
        _oom_contexts[:] = live
    return out


def oom_report_path():
    d = env_str("PADDLE_TELEMETRY_DIR") or "telemetry"
    return os.path.join(d, OOM_REPORT_NAME)


def write_oom_report(exc, program=None, path=None, analyze=None):
    """Commit ``telemetry/oom_report.json``: the error, the compile
    ledger snapshot (incl. the last-N compile events), the HBM budget
    ledger with top-N programs by temp bytes, and every registered
    context (active serving slots/pages). Atomic tmp+rename; never
    raises — forensics must not mask the original exception."""
    try:
        if analyze is None:
            analyze = env_bool("PADDLE_OOM_ANALYZE", True)
        if analyze:
            try:
                memory.analyze()
            except Exception:
                pass
        report = {
            "time": time.time(),
            "pid": os.getpid(),
            "rank": _rank(),
            "error": f"{type(exc).__name__}: {exc}",
            "program": program,
            "compile": ledger.report(recent=32),
            "memory": memory.report(),
            "top_programs_by_temp": memory.top_programs_by_temp(10),
            "contexts": _collect_oom_contexts(),
        }
        path = path or oom_report_path()
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(report, f, indent=1, default=str)
        os.replace(tmp, path)
        _M_OOM.inc()
        return path
    except Exception:
        return None


def maybe_oom_report(exc, program=None):
    """The dispatch-seam hook: no-op for non-OOM exceptions (one string
    scan, only on the error path); for RESOURCE_EXHAUSTED writes the
    forensics report once per exception object (the engine seam and the
    ledgered-jit seam both fire for one failure)."""
    if not is_oom(exc):
        return None
    if (_last_oom[0] == id(exc)
            and time.monotonic() - _last_oom[2] < _OOM_DEDUP_WINDOW_S):
        return _last_oom[1]
    path = write_oom_report(exc, program=program)
    _last_oom[0] = id(exc)
    _last_oom[1] = path
    _last_oom[2] = time.monotonic()
    return path


def _reset_for_tests():
    """Forget ledger/memory/OOM state (metrics reset separately)."""
    ledger.reset()
    memory.reset()
    with _oom_lock:
        _oom_contexts.clear()
    _last_oom[0] = _last_oom[1] = None
    _last_oom[2] = 0.0
