"""Readers over the program's set-up log
(`paddle_tpu.observability.tracing.setup_records()`): one record per phase
of the process's start — import, parameter creation, the cast, the engine's
pools, warm-up, the train step's build — and one per compile the ledger saw,
each with two stamps on `time.monotonic_ns()`, the clock of
`T_PROCESS_START` and of `ctx.t_window`. What these metrics split is
`setup_s`, so every record is clipped to [process start, window start].

Each reader takes the run's context and returns a number, or None when
there is nothing to read: a program without the log (the parent of the PR
that added it) or, for `setup.engine_warm_s`, a cell with no engine.

Phases nest and threads overlap, so time is counted as a UNION of
intervals, never a sum of lengths: the parts can never add up to more than
`setup_s`. A compile's four durations are jax's own (`trace_s`, `lower_s`,
`backend_s`; `other_s` is the rest of the call: first execution, dispatch),
and those are summed: compiles of one thread do not overlap."""
from benchmarks.readers.steps import _once
from benchmarks.readers.trace import merged

#: the phases whose self time is `setup.engine_warm_s`
ENGINE = ("engine.init", "engine.warmup", "frontend.start")
BUILD = ("setup.build", "setup.cast")
#: records that are a compile's or a re-lowering's own time
COMPILES = ("compile", "lower")
_STAMPS = ("name", "t0_ns", "t1_ns", "parent", "tid")


def _bounds(ctx):
    """(process start, window start) in ns on the monotonic clock."""
    return ((ctx.t_window - ctx.setup_s) * 1e9, ctx.t_window * 1e9)


def _log(ctx):
    """The set-up records that overlap [process start, window start],
    clipped to it and in order of their start; None when the program has no
    set-up log."""
    from paddle_tpu.observability import tracing

    read = getattr(tracing, "setup_records", None)
    if read is None:
        return None
    lo, hi = _bounds(ctx)
    out = []
    for r in read():
        if r["t1_ns"] <= lo or r["t0_ns"] >= hi:
            continue
        out.append({**r, "t0_ns": max(r["t0_ns"], lo),
                    "t1_ns": min(r["t1_ns"], hi),
                    "whole": lo <= r["t0_ns"] and r["t1_ns"] <= hi})
    return sorted(out, key=lambda r: (r["t0_ns"], -r["t1_ns"]))


def _union_s(records):
    return sum(e - s for s, e in merged(
        (r["t0_ns"], r["t1_ns"]) for r in records)) / 1e9


def _less_s(records, holes):
    """Seconds of the union of `records` not covered by `holes`."""
    both = _union_s(list(records) + list(holes))
    return both - _union_s(holes)


def _gaps(log, named, lo, hi, least_s=0.05):
    """The stretches of [lo, hi] that no interval of `named` covers, each
    with the records it lies between: where the unplaced time sits."""
    edges = [lo] + [x for iv in merged(named) for x in iv] + [hi]
    out = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b - a < least_s * 1e9:
            continue
        ended = [r for r in log if r["t1_ns"] <= a]
        begun = [r for r in log if r["t0_ns"] >= b]
        out.append({
            "at_s": (a - lo) / 1e9, "s": (b - a) / 1e9,
            "after": max(ended, key=lambda r: r["t1_ns"])["name"]
            if ended else "process start",
            "before": min(begun, key=lambda r: r["t0_ns"])["name"]
            if begun else "the window"})
    return out


def _compiles(log):
    """The compile events and re-lowerings that ended before the window: one
    cut by the window's start is a compile INSIDE the window, which the
    runner's `compiles_in_window` refuses by itself."""
    return [r for r in log if r["name"] in COMPILES and r["whole"]]


def _table(ctx):
    """The log, said once a run as the phase line `setup` (every phase with
    its start, length and counts; every compile with key, trigger, the four
    durations and cache; every gap no record covers with its neighbours),
    and the numbers the seven metrics share."""
    log = _log(ctx)
    if log is None:
        return None
    lo, hi = _bounds(ctx)
    setup_s = ctx.setup_s
    warm_in_s = float(ctx.traffic.get("warm_in_s", 0)
                      if ctx.result["kind"] == "serve" else 0)
    warm_in = [{"t0_ns": hi - min(warm_in_s, setup_s) * 1e9, "t1_ns": hi}]
    comp = _compiles(log)
    placed_s = _union_s(log)
    named_s = _union_s(log + warm_in)
    requests = [r for r in comp if r.get("cache") in ("hit", "miss")]
    engine = [r for r in log if r["name"] in ENGINE]
    t = {
        "setup_s": setup_s, "warm_in_s": warm_in_s, "placed_s": placed_s,
        "unplaced_s": setup_s - named_s,
        "import_s": _union_s([r for r in log if r["name"] == "setup.import"]),
        "build_s": _union_s([r for r in log if r["name"] in BUILD]),
        "trace_lower_s": sum(r.get("trace_s", 0) + r.get("lower_s", 0)
                             for r in comp),
        "backend_s": sum(r.get("backend_s", 0) for r in comp),
        "first_run_s": sum(r.get("other_s", 0) for r in comp),
        "cache_requests": len(requests),
        "cache_hits": sum(1 for r in requests if r["cache"] == "hit"),
        "engine_warm_s": _less_s(engine, comp) if engine else None,
    }
    # what jax compiled outside a ledger event (eager ops: initialisers,
    # casts, pool fills), as the phases counted it
    eager = {k: sum(r.get("jit_" + k, 0) for r in log)
             for k in ("trace_s", "lower_s", "backend_s", "cache_requests",
                       "cache_hits")}
    spans = [(r["t0_ns"], r["t1_ns"]) for r in log + warm_in]
    ctx.say(
        "setup", **t, eager_jit=eager, gaps=_gaps(log, spans, lo, hi),
        unplaced_is="the benchmark's own work before the window (reference "
                    "loss, traffic generation, the runner's warm steps and "
                    "its waits for the device) and whatever of the program "
                    "no phase names",
        phases=[{"name": r["name"], "at_s": (r["t0_ns"] - lo) / 1e9,
                 "s": (r["t1_ns"] - r["t0_ns"]) / 1e9,
                 "parent": r["parent"], "tid": r["tid"],
                 **{k: v for k, v in r.items()
                    if k not in _STAMPS and k != "whole"}}
                for r in log if r["name"] not in COMPILES],
        compiles=[{"what": r["name"], "key": r.get("key"),
                   "trigger": r.get("trigger"), "under": r["parent"],
                   "at_s": (r["t0_ns"] - lo) / 1e9,
                   "s": (r["t1_ns"] - r["t0_ns"]) / 1e9,
                   "trace_s": r.get("trace_s"), "lower_s": r.get("lower_s"),
                   "backend_s": r.get("backend_s"),
                   "other_s": r.get("other_s"), "cache": r.get("cache"),
                   "retrieval_s": r.get("retrieval_s")}
                  for r in log if r["name"] in COMPILES])
    return t


def _part(ctx, key):
    t = _once(ctx, "setup_table", lambda: _table(ctx))
    return None if t is None else t[key]


def import_s(ctx):
    """Length of `setup.import`: `import paddle_tpu`, jax's import with
    it."""
    return _part(ctx, "import_s")


def build_s(ctx):
    """Union of the `setup.build` and `setup.cast` records: parameter
    creation (the host's part: the device may still be filling them) and
    the cast to the configuration's dtype."""
    return _part(ctx, "build_s")


def trace_lower_s(ctx):
    """Sum of `trace_s + lower_s` over the compile events and re-lowerings
    before the window: host work that a warm compile cache does not
    save."""
    return _part(ctx, "trace_lower_s")


def backend_compile_s(ctx):
    """Sum of `backend_s` over the same records: the XLA compile on a miss,
    the cache's read on a hit (jax's `backend_compile_duration` holds the
    retrieval, so `retrieval_s` is not added again)."""
    return _part(ctx, "backend_s")


def cache_hit_pct(ctx):
    """Of the compile events before the window whose request went to the
    persistent cache, the share it served."""
    n = _part(ctx, "cache_requests")
    return 100.0 * _part(ctx, "cache_hits") / n if n else None


def engine_warm_s(ctx):
    """Self time of `engine.init`, `engine.warmup` and `frontend.start`:
    their union less the compile events and re-lowerings inside it (the
    pools, the dummy serves' execution, the scope tables)."""
    return _part(ctx, "engine_warm_s")


def unplaced_pct(ctx):
    """100 x (setup_s - the union of every record and the traffic file's
    warm-in) / setup_s: what still has no name."""
    t = _once(ctx, "setup_table", lambda: _table(ctx))
    return None if t is None else 100.0 * t["unplaced_s"] / t["setup_s"]
