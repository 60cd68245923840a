"""Readers over the serving engine's step log
(`paddle_tpu.observability.tracing.step_records()`): one record per engine
dispatch with stamps on `time.monotonic_ns()` — the clock of the request
stamps, of the runner's window and of `WindowTracer.t_on/t_off` — the rows
with their KV extents, the admissions and the tokens emitted. Each reader
takes the run's context and returns a number, or None when there is
nothing to read: a program without a step log (the parent of the PR that
added it), a training cell, or, for the roofline share, no device trace.

The device trace has a clock of its own. `bench.traced_window` starts when
`WindowTracer.t_on` is taken and ends when `t_off` is, so a monotonic stamp
maps to `window[0] + (t - t_on)`; `_to_profiler_ns` refuses the mapping
when the two windows differ in length by more than a millisecond."""
import bisect
import collections
import re
import statistics

from benchmarks import flops
from benchmarks.readers.trace import instr, merged
from benchmarks.traffic import percentile

#: rows that decode in the dispatch's scan and so emit tokens:
#: `d` decode rows and `g` chunks that graduate; `c` is a mid-prompt chunk
PARTICIPANT = ("d", "g")


def _log(ctx):
    """Every step record read back after the window's start (the one in
    flight at the start and the drain's too), in dispatch order; None when
    there is no log to read."""
    if ctx.result["kind"] != "serve":
        return None
    from paddle_tpu.observability import tracing

    read = getattr(tracing, "step_records", None)
    if read is None:
        return None
    lo = ctx.t_window * 1e9
    return [r for r in read() if r["t_ready"] >= lo] or None


def _window(ctx, log):
    """The records of `log` (may be None) dispatched inside the measured
    window, less the `cold` ones: a dispatch that compiled holds the
    compiler's time in its `serve.decode` phase, not the host's."""
    if not log:
        return []
    lo = ctx.t_window * 1e9
    hi = lo + ctx.result["window_s"] * 1e9
    return [r for r in log if lo <= r["t_disp0"] < hi and not r["cold"]]


def _phases():
    """The engine's own table of a dispatch's phases, (span, first stamp,
    last stamp), without their parent `serve.step`."""
    from paddle_tpu.inference.continuous import STEP_PHASES

    return STEP_PHASES[1:]


def _once(ctx, key, make):
    """`make()`, worked out (and said) once a run for the metrics that
    share it."""
    if key not in vars(ctx):
        setattr(ctx, key, make())
    return getattr(ctx, key)


def _participants(rec):
    return sum(1 for row in rec["rows"] if row[1] in PARTICIPANT)


# ---- engine -----------------------------------------------------------------

def host_busy_pct(ctx):
    """The dispatcher thread's share of time NOT blocked in the readback.
    Over the periods `t_step0(n) .. t_step0(n+1)` of consecutive chained
    dispatches (the pipeline never ran empty, so no period holds an idle
    wait for work): 100 x (1 - sum of readback waits that began inside a
    period / sum of the periods). Work of the frontend between two step()
    calls counts as the host's."""
    log = _log(ctx)
    if log is None:
        return None
    win = _window(ctx, log)
    waits = sorted((r["t_sync0"], r["t_ready"] - r["t_sync0"]) for r in log)
    starts = [w[0] for w in waits]
    period = blocked = n = 0
    for a, b in zip(win, win[1:]):
        if not (a["chained"] and b["chained"] and b["seq"] == a["seq"] + 1
                and b["engine"] == a["engine"]):
            continue
        n += 1
        period += b["t_step0"] - a["t_step0"]
        blocked += sum(w[1] for w in waits[
            bisect.bisect_left(starts, a["t_step0"]):
            bisect.bisect_left(starts, b["t_step0"])])
    if not period:
        return None
    ctx.say("steps", metric=ctx.name, periods=n, periods_s=period / 1e9,
            blocked_s=blocked / 1e9, dispatches=len(win),
            host_us_per_dispatch=(period - blocked) / 1e3 / n,
            mean_phase_us={name: sum(r[b] - r[a] for r in win) / 1e3 / len(win)
                           for name, a, b in _phases()})
    return 100.0 * (1.0 - blocked / period)


def rows_per_dispatch(ctx):
    """Mean participating rows (decode rows and graduating chunks) of the
    window's dispatches."""
    win = _window(ctx, _log(ctx))
    if not win:
        return None
    return sum(_participants(r) for r in win) / len(win)


def useful_tok_pct(ctx):
    """Tokens emitted / tokens computed (k x participating rows) over the
    window's dispatches: what the overshoot after a mid-block finish and
    frozen rows throw away."""
    win = _window(ctx, _log(ctx))
    if not win:
        return None
    made = sum(r["k"] * _participants(r) for r in win)
    kept = sum(n for r in win for _, n in r["emits"])
    ctx.say("steps", metric=ctx.name, dispatches=len(win), emitted=kept,
            computed=made, tokens_per_dispatch=kept / len(win),
            mixed=sum(1 for r in win if r["kind"] == "mixed"))
    return 100.0 * kept / made if made else None


# ---- whole request: where the time to the first token goes ----------------

def _ttft_parts(ctx):
    """Per finished window request, (queue, prefill, first_block) in ms:
    due -> admitted -> its graduating dispatch entered -> its first token
    stamped. The three telescope over the request's own stamps, so they sum
    to its TTFT whatever the log says; what the log gives is the middle
    stamp, and the join is what is checked. The runner's record joins the
    log through `t_admit` (`admits` carries the EngineRequest's own stamp,
    the record holds that value less the window's start) and the prompt's
    length, and the record found — the dispatch whose row of that `rid`
    graduates — has to be the one whose emit loop stamped the request's
    first token (`t_ready <= t_first <= t_emit1`). None (said) unless every
    finished request joins: a mean over some of them is another number."""
    log = _log(ctx)
    if log is None:
        return None
    admits = sorted((t_admit - ctx.t_window, rid, n_prompt)
                    for r in log for rid, _, t_admit, n_prompt in r["admits"])
    keys = [a[0] for a in admits]
    grad = {row[0]: r for r in log for row in r["rows"] if row[1] == "g"}
    measured = [r for r in ctx.result["requests"] if r["measured"]]
    finished = [r for r in measured
                if r["t_done"] is not None and r["t_admit"] is not None]
    parts = []
    for r in finished:
        i = bisect.bisect_left(keys, r["t_admit"] - 1e-6)
        if (i == len(keys) or abs(keys[i] - r["t_admit"]) > 1e-6
                or admits[i][2] != r["n_prompt"] or admits[i][1] not in grad):
            continue
        g = grad[admits[i][1]]
        first = (ctx.t_window + r["t_first"]) * 1e9
        if not g["t_ready"] - 1e3 <= first <= g["t_emit1"] + 1e3:
            continue
        t_disp0 = g["t_disp0"] / 1e9 - ctx.t_window
        parts.append((1e3 * (r["t_admit"] - r["due"]),
                      1e3 * (t_disp0 - r["t_admit"]),
                      1e3 * (r["t_first"] - t_disp0)))
    if not parts or len(parts) != len(finished):
        ctx.say("steps", metric="ttft.parts", refused="not every finished "
                "request joins the step log", requests=len(measured),
                finished=len(finished), joined=len(parts))
        return None
    means = [sum(p[i] for p in parts) / len(parts) for i in range(3)]
    ttft = [1e3 * (r["t_first"] - r["due"]) if r["t_done"] is not None
            else 1e3 * ctx.result["drain_timeout_s"] for r in measured]
    ctx.say("steps", metric="ttft.parts", requests=len(measured),
            joined=len(parts), queue_mean_ms=means[0],
            prefill_mean_ms=means[1], first_block_mean_ms=means[2],
            ttft_mean_ms=sum(ttft) / len(ttft),
            residual_ms=sum(ttft) / len(ttft) - sum(means))
    return means


def ttft_part_ms(ctx):
    """Mean of args.part (0 queue, 1 prefill, 2 first block) over the
    window's finished requests."""
    parts = _once(ctx, "ttft_parts", lambda: _ttft_parts(ctx))
    return None if parts is None else parts[ctx.args["part"]]


def itl_gap_ms(ctx):
    """Percentile args.q of the gaps between one request's successive
    non-empty emits (a block's tokens reach the host together, at its
    `t_ready`): what a stream feels when a long chunk joins its step."""
    log = _log(ctx)
    if log is None:
        return None
    hi = (ctx.t_window + ctx.result["window_s"]) * 1e9
    last, gaps = {}, []
    for r in sorted(log, key=lambda r: r["t_ready"]):
        for rid, n in r["emits"]:
            if not n:
                continue
            if rid in last and r["t_ready"] < hi:
                gaps.append((r["t_ready"] - last[rid]) / 1e6)
            last[rid] = r["t_ready"]
    return percentile(gaps, ctx.args["q"])


# ---- the step log on the device trace's clock -------------------------------

def _to_profiler_ns(ctx):
    """monotonic ns -> the device trace's ns, or None (said) when the
    annotation's length and `t_off - t_on` disagree by more than 1 ms."""
    lo, hi = ctx.trace.window
    t_on, t_off = ctx.tracer.t_on, ctx.tracer.t_off
    apart = (hi - lo) - (t_off - t_on) * 1e9
    if abs(apart) > 1e6:
        ctx.say("steps", metric=ctx.name, refused="the traced window's "
                "length differs between the two clocks", apart_ns=apart)
        return None
    return lambda t_ns: lo + (t_ns - t_on * 1e9)


def _traced_runs(ctx):
    """`_runs` of this run's trace and log, or None: no device trace, no
    log, or a refusal. Says the device's idle seconds by engine phase from
    the same mapping."""
    log = _log(ctx) if ctx.trace is not None else None
    to_ns = _to_profiler_ns(ctx) if log else None
    if to_ns is None:
        return None
    _idle_by_phase(ctx, log, to_ns)
    return _runs(ctx, log, to_ns)


def _kernel_ns(ctx, runs):
    """Device durations of args.kernel's events inside `runs`."""
    rx = re.compile(ctx.args["kernel"])
    return [e - s for n, s, e in ctx.trace.ops() if rx.search(instr(n)[0])
            and any(rs <= s and e <= re_ for _, rs, re_ in runs)]


def _runs(ctx, log, to_ns):
    """[(record, run start, run end)] for the `serve.*` module runs wholly
    inside the traced window, each with the first record whose tokens were
    ready (mapped) no earlier than the run ended; None (said) when a run's
    kind and its record's disagree. A run whose tokens were read back after
    the window is left out: the device's trace stops a little before the
    annotation does and cuts the run in flight short of the window's end
    (seen on the chip: 37 of its 49 paged calls). Says the skew: mapped
    `t_ready` less the run's end (the readback's own latency included)."""
    lo, hi = ctx.trace.window
    recs = sorted(log, key=lambda r: r["t_ready"])
    ready = [to_ns(r["t_ready"]) for r in recs]
    out, skew = [], []
    for name, s, e in sorted(ctx.trace.modules(), key=lambda m: m[2]):
        kind = ("mixed" if "ragged_step" in name
                else "decode" if "decode" in name else None)
        if kind is None or s <= lo or e >= hi:
            continue
        i = bisect.bisect_left(ready, e - 1e6)
        if i == len(recs) or recs[i]["kind"] != kind:
            ctx.say("steps", metric=ctx.name, refused="a module run and the "
                    "step record at its end disagree", module=name[:40],
                    record=None if i == len(recs) else recs[i]["kind"])
            return None
        if ready[i] > hi:
            continue
        out.append((recs[i], s, e))
        skew.append(ready[i] - e)
    if skew:
        ctx.say("steps", metric=ctx.name, matched_runs=len(out),
                skew_ready_minus_run_end_ms=statistics.median(skew) / 1e6,
                skew_min_ms=min(skew) / 1e6, skew_max_ms=max(skew) / 1e6)
    return out


def _idle_by_phase(ctx, log, to_ns):
    """Device idle seconds in the traced window by what the engine's thread
    was doing: each gap of the op line goes to the phase overlapping it
    most, else to `between_steps`."""
    lo, hi = ctx.trace.window
    busy = merged([(s, e) for _, s, e in ctx.trace.ops()])
    edges = [lo] + [x for b in busy for x in b] + [hi]
    spans = [(name, s, e) for r in log for name, a, b in _phases()
             for s, e in [(to_ns(r[a]), to_ns(r[b]))] if e > lo and s < hi]
    idle = collections.Counter()
    for gs, ge in zip(edges[::2], edges[1::2]):
        if ge <= gs:
            continue
        best, best_ov = "between_steps", 0.0
        for name, s, e in spans:
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = name, ov
        idle[best] += (ge - gs) / 1e9
    ctx.say("steps", metric="idle_by_phase", idle_s=dict(idle.most_common()),
            window_s=(hi - lo) / 1e9)


def ragged_call_cost(cfg, rows, itemsize=2):
    """(FLOPs, bytes) one layer's ragged attention call needs for `rows`
    of (rid, role, q_len, kv_len): QK^T and PV over the causal part of each
    row's q x kv rectangle; K and V of every row read once, q read and the
    output written."""
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    pairs = sum(q * kv - q * (q - 1) // 2 for _, _, q, kv in rows)
    kv_tok = sum(kv for _, _, _, kv in rows)
    q_tok = sum(q for _, _, q, _ in rows)
    return (4 * hq * d * pairs,
            2 * hkv * d * itemsize * kv_tok + 2 * hq * d * itemsize * q_tok)


def ragged_attn_roofline(ctx):
    """Over the whole `mixed` runs of the traced window: the least time the
    chip needs for each call's own extents (every layer's call alike;
    flops.roofline_seconds with the run's peaks), summed, over the device
    time of args.kernel's events inside those runs."""
    runs = _once(ctx, "traced_runs", lambda: _traced_runs(ctx))
    mixed = [run for run in runs or () if run[0]["kind"] == "mixed"]
    if not mixed:
        return None
    layers = ctx.cfg["num_hidden_layers"]
    least, by_bound = 0.0, collections.Counter()
    for r, _, _ in mixed:
        t, bound = flops.roofline_seconds(
            *ragged_call_cost(ctx.cfg, r["rows"]), ctx.peak)
        least += layers * t
        by_bound[bound] += layers * t
    kern = sum(_kernel_ns(ctx, mixed)) / 1e9
    if not kern:
        return None
    ctx.say("roofline", metric=ctx.name, runs=len(mixed), least_s=least,
            least_s_by_bound=dict(by_bound), kernel_s=kern)
    return 100.0 * least / kern


def paged_attn_roofline(ctx):
    """Over the whole `serve.*` runs of the traced window: the least time
    (memory-bound: K and V of every row's extent read once a scan step and
    layer, one query token a row) over the device time of args.kernel's
    events inside those runs. A mixed dispatch scans k-1 steps after its
    ragged pass, a decode block k; every one of the engine's rows goes
    through the kernel (an empty or mid-prompt row reads one scratch
    token). Returns None (said) when the trace holds another number of
    kernel events than steps x layers."""
    runs = _once(ctx, "traced_runs", lambda: _traced_runs(ctx))
    if not runs:
        return None
    cfg, layers = ctx.cfg, ctx.cfg["num_hidden_layers"]
    per_tok = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    per_q = 2 * cfg["num_attention_heads"] * cfg["head_dim"] * 2
    max_seqs = ctx.result["shape"]["max_seqs"]
    least = calls = 0
    for r, _, _ in runs:
        first = 1 if r["kind"] == "mixed" else 0
        ext = [kv for _, role, _, kv in r["rows"] if role in PARTICIPANT]
        for s in range(first, r["k"]):
            nbytes = (per_tok * (sum(kv + s for kv in ext)
                                 + max_seqs - len(ext)) + per_q * max_seqs)
            least += layers * nbytes / ctx.peak["hbm_bytes_per_s"]
        calls += layers * (r["k"] - first)
    events = _kernel_ns(ctx, runs)
    ctx.say("roofline", metric=ctx.name, runs=len(runs), bound="memory",
            least_s=least, kernel_s=sum(events) / 1e9,
            kernel_events=len(events), expected_calls=calls)
    if len(events) != calls:
        return None
    return 100.0 * least / (sum(events) / 1e9)
