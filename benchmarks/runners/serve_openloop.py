"""Runner `serve_openloop`: one ServingFrontend over one
ContinuousBatchingEngine, driven open-loop from one thread on the schedule
`traffic.open_loop` makes. Returns raw per-request records (stamps on the
host's monotonic clock, relative to the window's start) and the facts
`correct` needs; never a metric. The model and the reference are the
builder's and the reference module's that the cell's configuration file
names (`ctx.builder`, `ctx.reference`); the kernel tiers `correct` demands
are the workload file's (`ctx.tiers()`). Construction copied from
chip_smoke.serve_phase (41cde00).

A request's clock starts when it was DUE, not when submit() ran: a stalled
generator must not read as a fast server. `late` records the difference."""
import contextlib
import os
import time

import numpy as np

from benchmarks import traffic
from benchmarks.profiler import WindowTracer, annotate


@contextlib.contextmanager
def _interpret_ragged_kernel(on):
    """--rehearse drives the ragged kernel's own body off-TPU (interpret
    mode), as chip_smoke does."""
    was = os.environ.get("PADDLE_RAGGED_IMPL")
    if on and was is None:
        os.environ["PADDLE_RAGGED_IMPL"] = "pallas"
    try:
        yield
    finally:
        if on and was is None:
            del os.environ["PADDLE_RAGGED_IMPL"]


def _setup(ctx):
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine

    knobs = dict(ctx.cell["engine"])
    if ctx.rehearse:
        knobs.update(ctx.cell.get("rehearse", {}).get("engine", {}))
    model = ctx.builder.build(ctx.cfg, ctx.seed, train=False,
                              max_len=knobs["max_len"], rehearse=ctx.rehearse)
    eng = ContinuousBatchingEngine(model, **knobs)
    eng.warmup(buckets=[ctx.traffic["prompt"]["max"]])
    return model, eng, knobs


def _rel(t, t0):
    return None if t is None else t - t0


def _drive(fe, reqs, t0, seconds, tracer, drain_timeout_s):
    """Submits each request when due, then drains. Returns (records, rows):
    records hold window-relative stamps in seconds, rows the returned
    token arrays (None where none came back)."""
    from paddle_tpu.serving.scheduler import Overloaded

    handles = []
    for r in reqs:
        tracer.tick(time.monotonic() - t0)
        with annotate("bench.sleep"):
            wait = t0 + r["due"] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
        t_submit = time.monotonic()
        with annotate("bench.submit"):
            try:
                h, err = fe.submit(r["prompt"], r["max_new"]), None
            except Overloaded as e:
                h, err = None, f"shed: {getattr(e, 'step', None)}"
        handles.append((h, err, t_submit))
    with annotate("bench.sleep"):
        wait = t0 + seconds - time.monotonic()
        if wait > 0:
            time.sleep(wait)
    tracer.stop()
    deadline = time.monotonic() + drain_timeout_s
    records, rows = [], []
    with annotate("bench.collect"):
        for r, (h, err, t_submit) in zip(reqs, handles):
            row = None
            if h is not None:
                try:
                    row = np.asarray(h.result(
                        timeout=max(0.0, deadline - time.monotonic())))
                except Exception as e:  # failed, cancelled or not drained
                    err = f"{type(e).__name__}: {e}"[:200]
                    h.cancel()
            q = h._req if h is not None else None
            records.append({
                "due": r["due"], "measured": r["measured"],
                "n_prompt": len(r["prompt"]), "max_new": r["max_new"],
                "late": t_submit - t0 - r["due"],
                "t_admit": _rel(q.t_admit, t0) if q else None,
                "t_first": _rel(q.t_first_token, t0) if q else None,
                "t_done": (_rel(q.t_done, t0)
                           if q and row is not None else None),
                "n_generated": (len(row) - len(r["prompt"])
                                if row is not None else 0),
                "error": err})
            rows.append(row)
    return records, rows


def _row_problems(reqs, rows):
    """Every returned row starts with its prompt and has the asked length."""
    bad = []
    for i, (r, row) in enumerate(zip(reqs, rows)):
        n = len(r["prompt"])
        if row is not None and (len(row) != n + r["max_new"]
                                or not np.array_equal(row[:n], r["prompt"])):
            bad.append(f"request {i}: prompt {n} + {r['max_new']} asked, "
                       f"{len(row)} tokens back")
    return bad


def run(ctx):
    from paddle_tpu.observability import compilemem
    from paddle_tpu.serving import ServingFrontend

    tp = ctx.traffic
    with _interpret_ragged_kernel(ctx.rehearse):
        model, eng, knobs = _setup(ctx)
        reqs = traffic.open_loop(tp, ctx.seed, ctx.seconds,
                                 ctx.cfg["vocab_size"])
        compiles_before = compilemem.ledger.counts()["events"]
        with ServingFrontend([eng]) as fe:
            # the warm-in arrivals are set-up: the window starts after them
            t0 = ctx.mark_window_start(delay_s=tp.get("warm_in_s", 0))
            records, rows = _drive(fe, reqs, t0, ctx.seconds, ctx.tracer,
                                   tp["drain_timeout_s"])
        tiers, tier_problems = ctx.tiers()
        checks = {"compiles_in_window":
                  compilemem.ledger.counts()["events"] - compiles_before,
                  **tiers}
        problems = _row_problems(reqs, rows)

        # the shortest finished prompt (a decode row from its second step)
        # and the shortest finished prompt over one prefill chunk
        done = [i for i, (r, row) in enumerate(zip(reqs, rows))
                if r["measured"] and row is not None]
        chunk = knobs["prefill_chunk"]
        short = min(done, key=lambda i: len(reqs[i]["prompt"]), default=None)
        long_ = min((i for i in done if len(reqs[i]["prompt"]) > chunk),
                    key=lambda i: len(reqs[i]["prompt"]), default=None)
        which = [i for i in (short, long_) if i is not None]
        if long_ is None:
            problems.append("no finished prompt longer than one prefill chunk")
        else:
            try:
                checks["reference"] = ctx.reference.check_served(
                    model, [reqs[i]["prompt"] for i in which],
                    [rows[i] for i in which])
                checks["reference"]["prompt_lens"] = [
                    len(reqs[i]["prompt"]) for i in which]
            except ctx.reference.Wrong as e:
                problems.append(str(e))
    if checks["compiles_in_window"]:
        problems.append(f"{checks['compiles_in_window']} compile(s) after "
                        f"warm-up: "
                        f"{compilemem.ledger.report(recent=4)['recent']}")
    problems += tier_problems
    measured = [r for r in records if r["measured"]]
    failed = [r for r in measured if r["t_done"] is None]
    checks["errors"] = sorted({r["error"] for r in failed if r["error"]})[:5]
    return {"kind": "serve", "requests": records, "checks": checks,
            "problems": problems, "attempted": len(measured),
            "failed": len(failed), "window_s": float(ctx.seconds),
            "drain_timeout_s": tp["drain_timeout_s"],
            "shape": {"max_seqs": knobs["max_seqs"]}}


def sweep(ctx, rates, seconds):
    """One process, one engine: `seconds` of arrivals at each rate (after
    the file's warm-in), drained before the next. A rate SUSTAINS when no
    request is shed and the backlog (due and not finished) at the end of
    the window exceeds the backlog at its midpoint by no more than
    max_seqs. Stops after the first rate that does not."""
    from paddle_tpu.serving import ServingFrontend

    no_trace = WindowTracer(False, None, 0, 0)
    table = []
    with _interpret_ragged_kernel(ctx.rehearse):
        model, eng, knobs = _setup(ctx)
        with ServingFrontend([eng]) as fe:
            for rate in rates:
                reqs = traffic.open_loop(ctx.traffic, ctx.seed, seconds,
                                         ctx.cfg["vocab_size"], rate=rate)
                t0 = time.monotonic() + ctx.traffic.get("warm_in_s", 0)
                recs, _ = _drive(fe, reqs, t0, seconds, no_trace, 300.0)
                m = [r for r in recs if r["measured"]]
                ok = [r for r in m if r["t_done"] is not None]
                ttft = [1e3 * (r["t_first"] - r["due"]) for r in ok]
                tpot = [1e3 * (r["t_done"] - r["t_first"])
                        / (r["n_generated"] - 1) for r in ok
                        if r["n_generated"] > 1]
                mid, end = (traffic.backlog(recs, seconds / 2),
                            traffic.backlog(recs, seconds))
                shed = sum(1 for r in recs if r["error"]
                           and r["error"].startswith("shed"))
                row = {"rate_per_s": rate, "sent": len(m), "shed": shed,
                       "unfinished": len(m) - len(ok),
                       "backlog_mid": mid, "backlog_end": end,
                       "ttft_p50_ms": traffic.percentile(ttft, 0.5),
                       "ttft_p90_ms": traffic.percentile(ttft, 0.9),
                       "tpot_p50_ms": traffic.percentile(tpot, 0.5),
                       "tpot_p90_ms": traffic.percentile(tpot, 0.9),
                       "out_tok_per_s": sum(
                           r["n_generated"] for r in recs
                           if r["t_done"] is not None
                           and 0 <= r["t_done"] <= seconds) / seconds,
                       "late_max_ms": 1e3 * max(r["late"] for r in recs),
                       "sustains": shed == 0 and len(ok) == len(m)
                       and end - mid <= knobs["max_seqs"]}
                ctx.say("sweep", **row)
                table.append(row)
                if not row["sustains"]:
                    break
    return table
