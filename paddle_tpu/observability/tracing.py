"""Span tracing: ``with span("name"):`` through the seams that matter.

A span is a host-side timed region with parent/child nesting (per-thread
stack). Completed spans fan out to:

- a process-wide ring buffer (``last_spans``) — what the hang watchdog dumps
  when a rank stalls;
- the profiler's chrome-trace host-event buffer, when a Profiler is
  recording — spans appear in the same timeline RecordEvent always fed;
- any registered JSONL sinks (one json object per line, crash-safe: each
  record is flushed as written);
- a per-span-name duration histogram in the metrics registry
  (``span.<name>_s``) — the per-phase step breakdown falls out of the same
  data.

Request-scoped trace records (observability/request_trace.py) ride the
same ring and sinks via :func:`emit_record`, so one ``spans.<rank>.jsonl``
file carries both streams and scripts/trace_view.py can join them.

The **step log** (:func:`new_step`, :func:`commit_step`,
:func:`step_records`) is the always-on part: one record per dispatch of a
stepping loop — the serving engine's (inference/continuous.py owns the
fields and the phase table) — with stamps on ``time.monotonic_ns()`` and
counts, in a bounded ring of its own. It is per dispatch (a few a second),
never per token, so it follows the metric registry's cost model
("publication is always on"), not the span's. While a record is built,
each phase sits inside :func:`annotation`, a
``jax.profiler.TraceAnnotation``: any xplane taken of the process shows the
dispatcher thread beside the device lines, on the profiler's own clock.
With tracing enabled the completed record also fans out as ordinary span
records, a parent and its phases.

The **set-up log** (:func:`setup_phase`, :func:`setup_record`,
:func:`setup_records`) is its sibling for the time before the first step:
one record per phase of a process's start — import, parameter creation, the
cast, the engine's pools, warm-up, the train step's build, and every compile
the ledger saw (observability/compilemem.py) — with two stamps on the same
``time.monotonic_ns()``, the phase that was open on the thread when it
started, and the phase's counts. A phase is seconds long and a process makes
a few dozen, so it is always on like the step log; an open phase sits in
:func:`annotation`, and with tracing enabled a finished record fans out as a
span of the same name. docs/OBSERVABILITY.md has the table of phases.

Cost contract (asserted in tests/test_telemetry.py like chaos.site's):
**disabled, an attr-less span is one module-global load + a None/False
check** returning a shared no-op context manager — no allocation, no clock
read. Spans called with ``**attrs`` pay the kwargs-dict build before the
check runs (Python semantics), so per-step/per-dispatch hot paths use
attr-less spans. Enable via ``enable()`` or ``PADDLE_TELEMETRY=1``.

Caveat: a span opened inside a jax trace (jit compile) measures TRACE time
once, not per-execution device time; device-side phase attribution rides
``jax.named_scope`` into xprof instead (see jit_api's fwd_bwd/optimizer
scopes and docs/OBSERVABILITY.md).
"""
import atexit
import collections
import itertools
import json
import os
import re
import sys
import threading
import time

from ..utils.envs import env_bool, env_str

__all__ = ["span", "enable", "disable", "enabled", "last_spans",
           "add_jsonl_sink", "clear_sinks", "JsonlSpanSink", "emit_record",
           "annotation", "new_step", "commit_step", "step_records",
           "span_record", "program_scopes", "note_program_scopes",
           "setup_phase", "setup_record", "setup_count", "setup_records"]

_ENABLED = None           # tri-state: None = resolve from env on first use
_RING_DEFAULT = 512
_ring = collections.deque(maxlen=_RING_DEFAULT)
#: the step log: ~27 minutes of dispatches at 5 a second
steps = collections.deque(maxlen=8192)
_step_seq = itertools.count()
_sinks = []
_local = threading.local()
_tids = {}
_tids_lock = threading.Lock()


def _small_tid():
    """Small, stable per-thread id (chrome-trace tid / span record tid).
    Unlike ``get_ident() % 100000``, cannot collide: ids are assigned
    sequentially per distinct live thread identity."""
    ident = threading.get_ident()
    tid = _tids.get(ident)
    if tid is None:
        with _tids_lock:
            tid = _tids.setdefault(ident, len(_tids) + 1)
    return tid


def _resolve_enabled():
    global _ENABLED
    _ENABLED = env_bool("PADDLE_TELEMETRY")
    if _ENABLED:
        _autoconfigure_sinks()
    return _ENABLED


def enabled():
    """True when span tracing is on (env PADDLE_TELEMETRY or enable())."""
    e = _ENABLED
    return e if e is not None else _resolve_enabled()


def enable(jsonl_path=None, ring=None):
    """Turn span tracing on programmatically; optionally attach a JSONL sink
    and resize the ring buffer. Env-configured sinks (PADDLE_TELEMETRY_DIR)
    attach here too — a launcher-spawned worker that calls obs.enable()
    itself still streams spans where the hang watchdog looks."""
    global _ENABLED, _ring
    _ENABLED = True
    if ring is not None and ring != _ring.maxlen:
        _ring = collections.deque(_ring, maxlen=int(ring))
    if jsonl_path is not None and not any(
            getattr(s, "path", None) == jsonl_path for s in _sinks):
        add_jsonl_sink(jsonl_path)  # idempotent: re-enable ≠ duplicate sink
    _autoconfigure_sinks()


def disable():
    """Turn tracing off. The ring buffer and sinks are kept (post-mortem
    inspection of what was captured while enabled)."""
    global _ENABLED
    _ENABLED = False


_autosink_path = None


def _autoconfigure_sinks():
    """Env-armed processes (launcher-spawned trainers) stream spans to
    <PADDLE_TELEMETRY_DIR>/spans.<rank>.jsonl — the file the hang watchdog
    tails for its per-rank last-N-spans report. Idempotent: repeated
    enable() calls attach the sink once."""
    global _autosink_path
    d = env_str("PADDLE_TELEMETRY_DIR")
    if not d:
        return
    rank = env_str("PADDLE_TRAINER_ID", os.environ.get("RANK", "0"))
    path = os.path.join(d, f"spans.{rank}.jsonl")
    if path == _autosink_path and any(
            getattr(s, "path", None) == path for s in _sinks):
        return
    try:
        add_jsonl_sink(path)
        _autosink_path = path
    except OSError:
        pass


class JsonlSpanSink:
    """Crash-safe JSONL span sink: every record is written + flushed
    immediately, the file handle closes idempotently at exit (atexit) or via
    the context-manager protocol — a crash loses at most the record being
    formatted, never the flushed tail."""

    def __init__(self, path):
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self.path = path
        self._f = open(path, "a")
        atexit.register(self.close)

    def __call__(self, record):
        f = self._f
        if f is None:
            return
        try:
            f.write(json.dumps(record) + "\n")
            f.flush()
        except ValueError:  # closed underneath us at interpreter teardown
            self._f = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def close(self):
        f, self._f = self._f, None
        if f is not None:
            try:
                f.close()
            except ValueError:
                pass
        try:
            atexit.unregister(self.close)
        except Exception:
            pass


def add_jsonl_sink(path):
    sink = JsonlSpanSink(path)
    _sinks.append(sink)
    return sink


def clear_sinks():
    while _sinks:
        s = _sinks.pop()
        close = getattr(s, "close", None)
        if close is not None:
            close()


def last_spans(n=64):
    """Most recent completed span records (oldest first) — the watchdog's
    'what was this rank doing' payload."""
    buf = list(_ring)
    return buf[-n:]


def clear():
    """Test hook: drop captured spans, step records and set-up records
    (sinks untouched)."""
    global _burst
    _ring.clear()
    steps.clear()
    setups.clear()
    with _setup_lock:
        _burst = None


class _NullSpan:
    """Shared no-op context manager — the entire disabled cost of span()."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _Span:
    __slots__ = ("name", "attrs", "_t0", "_parent")

    def __init__(self, name, attrs):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self._parent = stack[-1].name if stack else None
        stack.append(self)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        stack = _local.stack
        if stack and stack[-1] is self:
            stack.pop()
        dur_us = (t1 - self._t0) / 1000.0
        rec = {
            "name": self.name,
            "ts_us": self._t0 / 1000.0,   # perf_counter epoch (chrome-trace)
            "dur_us": dur_us,
            "time": time.time(),          # wall clock (cross-rank alignment)
            "pid": os.getpid(),
            "tid": _small_tid(),
            "parent": self._parent,
            "depth": len(stack),
        }
        if self.attrs:
            rec["attrs"] = self.attrs
        _emit(rec, dur_us)
        return False


def _emit(rec, dur_us):
    _ring.append(rec)
    # same timeline as RecordEvent: host spans land in the chrome trace when
    # a Profiler is recording. sys.modules probe: never trigger a jax import
    # from the telemetry layer.
    prof = sys.modules.get("paddle_tpu.profiler")
    if prof is not None:
        try:
            prof._record_host_event(rec["name"], rec["ts_us"], rec["dur_us"])
        except Exception:
            pass
    from .metrics import registry

    try:
        registry.histogram(f"span.{rec['name']}_s").observe(dur_us / 1e6)
    except ValueError:
        pass  # name collision with a non-histogram metric: skip, don't kill
    for sink in _sinks:
        try:
            sink(rec)
        except Exception:
            pass


def emit_record(rec, profiler_name=None, profiler_ts_us=None,
                profiler_dur_us=None):
    """Route an externally-built record through the same fan-out completed
    spans get — the watchdog's ring buffer, every JSONL sink, and (when the
    optional profiler args are given and a Profiler is recording) the
    chrome-trace host-event buffer. This is how request-scoped trace
    records (observability/request_trace.py) land in the SAME
    ``spans.<rank>.jsonl`` files as thread spans, so scripts/trace_view.py
    and the hang watchdog see one stream. The span-duration histograms are
    NOT fed — those are keyed by the thread-span taxonomy."""
    _ring.append(rec)
    if profiler_name is not None:
        prof = sys.modules.get("paddle_tpu.profiler")
        if prof is not None:
            try:
                prof._record_host_event(profiler_name, profiler_ts_us,
                                        profiler_dur_us)
            except Exception:
                pass
    for sink in _sinks:
        try:
            sink(rec)
        except Exception:
            pass


def span(name, **attrs):
    """``with span("train.step.dispatch", step=i):`` — free when disabled."""
    e = _ENABLED
    if not (e if e is not None else _resolve_enabled()):
        return _NULL
    return _Span(name, attrs)


# ---- the step log ----------------------------------------------------------

def annotation(name):
    """``jax.profiler.TraceAnnotation(name)``: a host event in whatever
    profiler session is open, one flag check when none is. sys.modules
    probe, as in ``_emit``: the telemetry layer never imports jax, and a
    process that has not loaded it has no device trace to sit beside."""
    prof = sys.modules.get("jax.profiler")
    return _NULL if prof is None else prof.TraceAnnotation(name)


def new_step(**fields):
    """A step record under construction: the process-wide ``seq`` plus the
    caller's identity fields. The caller fills in stamps
    (``time.monotonic_ns()``, keys ``t_*``) and counts, then
    :func:`commit_step`."""
    return {"seq": next(_step_seq), **fields}


def span_record(name, t0_ns, t1_ns, parent=None, **attrs):
    """A span record from two stamps on the step log's clock. On Linux
    ``monotonic_ns`` and the spans' ``perf_counter_ns`` read the same
    clock, so ``ts_us`` lines up with :func:`span`'s."""
    return {"name": name, "ts_us": t0_ns / 1e3,
            "dur_us": (t1_ns - t0_ns) / 1e3, "time": time.time(),
            "pid": os.getpid(), "tid": _small_tid(), "parent": parent,
            "depth": 0 if parent is None else 1, "attrs": attrs}


def commit_step(rec, phases=()):
    """Append a completed step record to the ring — always. With tracing
    enabled, also fan it out as spans, ``phases`` naming them as ``(span,
    first stamp, last stamp)`` over the record's own keys: the first is the
    parent and carries the record's counts (every field that is not a
    stamp) with ``step=seq``, the others are its children with ``step``."""
    steps.append(rec)
    if not (phases and enabled()):
        return
    seq = rec["seq"]
    counts = {k: v for k, v in rec.items()
              if k != "seq" and not k.startswith("t_")}
    (parent, a, b), *children = phases
    spans = [span_record(parent, rec[a], rec[b], step=seq, **counts)]
    spans += [span_record(name, rec[a], rec[b], parent, step=seq)
              for name, a, b in children]
    for span_rec in spans:
        _emit(span_rec, span_rec["dur_us"])


def step_records(n=None):
    """The step log, oldest first (the last ``n`` records, or all)."""
    buf = list(steps)
    return buf if n is None else buf[-n:]


# ---- the set-up log ---------------------------------------------------------

#: the set-up log: a process makes a few dozen records before its first step
setups = collections.deque(maxlen=512)
_setup_lock = threading.Lock()
#: the open burst (a record still growing), or None: parameter creation is
#: thousands of calls that read as ONE `setup.build`
_burst = None


def _open_phases():
    stack = getattr(_local, "setup_stack", None)
    if stack is None:
        stack = _local.setup_stack = []
    return stack


class _SetupPhase:
    """An open set-up phase: ``counts`` is the caller's to fill before it
    closes, ``t0_ns`` / ``t1_ns`` are its stamps afterwards."""

    __slots__ = ("name", "counts", "t0_ns", "t1_ns", "parent", "_burst",
                 "_ann")

    def __init__(self, name, t0_ns, burst, counts):
        self.name, self.t0_ns, self._burst = name, t0_ns, burst
        self.counts = counts
        self.t1_ns = None

    def __enter__(self):
        stack = _open_phases()
        # a part of a burst is no parent of its own burst
        self.parent = next((p.name for p in reversed(stack)
                            if p.name != self.name), None)
        if not self._burst:
            _close_burst()
        stack.append(self)
        self._ann = annotation(self.name)
        self._ann.__enter__()
        if self.t0_ns is None:
            self.t0_ns = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        self.t1_ns = time.monotonic_ns()
        self._ann.__exit__(*exc)
        stack = _open_phases()
        if stack and stack[-1] is self:
            stack.pop()
        if self._burst:
            _grow_burst(self)
        else:
            setup_record(self.name, self.t0_ns, self.t1_ns, self.parent,
                         **self.counts)
        return False


def setup_phase(name, t0_ns=None, burst=False, **counts):
    """``with setup_phase("engine.warmup") as ph:`` opens a set-up phase on
    this thread; ``ph.counts`` takes what the phase counted, and the record
    is appended when the block ends. ``t0_ns`` backdates the start (a phase
    that began in an earlier call: the train step's build starts at its
    construction). ``burst=True`` makes the block one part of a burst: parts
    of one name are merged into ONE record, first start to last end, their
    counts summed (a flag keeps its last value), closed when another phase
    opens, a record is appended or the log is read."""
    return _SetupPhase(name, t0_ns, burst, counts)


def setup_count(**adds):
    """Add numbers to the counts of the innermost phase open on this thread
    (nothing is open: nothing is counted). The compile ledger's listeners
    bank what jax compiles outside a ledger event here."""
    stack = _open_phases()
    if stack:
        counts = stack[-1].counts
        for k, v in adds.items():
            counts[k] = counts.get(k, 0) + v


def _grow_burst(part):
    global _burst
    done = None
    with _setup_lock:
        b = _burst
        if b is not None and b["name"] != part.name:
            done, b = b, None
        if b is None:
            b = _burst = {"name": part.name, "t0_ns": part.t0_ns,
                          "t1_ns": part.t1_ns, "parent": part.parent,
                          "tid": _small_tid()}
        b["t1_ns"] = max(b["t1_ns"], part.t1_ns)
        for k, v in part.counts.items():
            b[k] = v if isinstance(v, bool) else b.get(k, 0) + v
    if done is not None:
        _commit(done)


def _close_burst():
    global _burst
    if _burst is None:
        return
    with _setup_lock:
        b, _burst = _burst, None
    if b is not None:
        _commit(b)


_SETUP_FIELDS = ("name", "t0_ns", "t1_ns", "parent", "tid")


def _commit(rec):
    """Append a finished record; with tracing enabled it also goes out as an
    ordinary span, its counts the span's attrs."""
    setups.append(rec)
    if enabled():
        span_rec = span_record(
            rec["name"], rec["t0_ns"], rec["t1_ns"], rec["parent"],
            **{k: v for k, v in rec.items() if k not in _SETUP_FIELDS})
        span_rec["tid"] = rec["tid"]
        _emit(span_rec, span_rec["dur_us"])


_OPEN = object()


def setup_record(name, t0_ns, t1_ns, parent=_OPEN, **counts):
    """Append a finished set-up record from two ``time.monotonic_ns()``
    stamps. ``parent`` defaults to the phase open on this thread now."""
    if parent is _OPEN:
        stack = _open_phases()
        parent = stack[-1].name if stack else None
    rec = {"name": name, "t0_ns": t0_ns, "t1_ns": t1_ns, "parent": parent,
           "tid": _small_tid(), **counts}
    _close_burst()
    _commit(rec)
    return rec


def setup_records(n=None):
    """The set-up log, oldest first (the last ``n`` records, or all)."""
    _close_burst()
    buf = list(setups)
    return buf if n is None else buf[-n:]


# ---- named scopes of compiled programs --------------------------------------

#: {program ledger key: {HLO instruction name: scope}}: which of a model's
#: ``jax.named_scope``s each instruction of a compiled program lies under.
#: A device trace's op events carry instruction names and no scope; a reader
#: joins them through this table, whatever implements a scope (fusions or a
#: kernel). Filled by the serving engine's warm-up for a model that names
#: ``serving_scopes`` (inference/continuous.py).
program_scopes = {}

_OP_NAME_RX = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"", re.M)


def note_program_scopes(key, hlo_text, scopes):
    """Record, for program ``key``, every instruction of its optimised HLO
    text whose ``op_name`` lies under one of ``scopes`` (the innermost one
    wins). Returns the table."""
    rx = re.compile("/(%s)(?=/|$)" % "|".join(re.escape(s) for s in scopes))
    table = {}
    for name, op_name in _OP_NAME_RX.findall(hlo_text):
        under = rx.findall(op_name)
        if under:
            table[name] = under[-1]
    program_scopes[key] = table
    return table
