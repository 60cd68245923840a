"""Runner `serve_pinned_schedule`: `serve_openloop` on ONE arrival schedule
for every seed. The set-up, the drive, the records and the comparison that
decides `correct` are `serve_openloop`'s own functions; only where the
requests come from differs.

`traffic.open_loop` gives every seed the same multiset of lengths and gaps
in another order, so that "runs of different seeds spread like runs of one
seed". That holds while a request is small beside the window. Where a window
holds 60 requests whose prompts span 512-16,384 tokens, the order alone moves
`ttft_mean_ms` by 40% between seeds while two runs of one seed agree to
0.3% (PERF.md section 6, PR 29), and no bound admits the cell. Here the order
is drawn once, by the same generator, for the `schedule_seed` the traffic
file names; the run's seed makes the token ids (and, in the builder, the
weights). A seed then changes what is computed on and never who meets whom.
The metrics are those of one sample path of the traffic: PERF.md says where
it lies among the paths of other orders.
"""
import numpy as np

from benchmarks import traffic
from benchmarks.runners import serve_openloop as base


def pinned_open_loop(params, seed, seconds, vocab, rate=None):
    """`traffic.open_loop`'s requests at the due times and with the lengths
    it draws for `params["schedule_seed"]`, whatever `seed`; the token ids
    are `seed`'s, uniform in [1, vocab)."""
    reqs = traffic.open_loop(params, params["schedule_seed"], seconds, 2,
                             rate=rate)
    rng = traffic.rng_for(seed, 3)
    for r in reqs:
        r["prompt"] = rng.integers(1, vocab, size=len(r["prompt"]),
                                   dtype=np.int32)
    return reqs


def run(ctx):
    """`serve_openloop.run` with `pinned_open_loop`'s requests."""
    from paddle_tpu.observability import compilemem
    from paddle_tpu.serving import ServingFrontend

    tp = ctx.traffic
    with base._interpret_ragged_kernel(ctx.rehearse):
        model, eng, knobs = base._setup(ctx)
        reqs = pinned_open_loop(tp, ctx.seed, ctx.seconds,
                                ctx.cfg["vocab_size"])
        compiles_before = compilemem.ledger.counts()["events"]
        with ServingFrontend([eng]) as fe:
            t0 = ctx.mark_window_start(delay_s=tp.get("warm_in_s", 0))
            records, rows = base._drive(fe, reqs, t0, ctx.seconds, ctx.tracer,
                                        tp["drain_timeout_s"])
        tiers, tier_problems = ctx.tiers()
        checks = {"compiles_in_window":
                  compilemem.ledger.counts()["events"] - compiles_before,
                  **tiers}
        problems = base._row_problems(reqs, rows)
        # the same two rows `serve_openloop` compares: the shortest finished
        # prompt and the shortest finished prompt over one prefill chunk
        done = [i for i, (r, row) in enumerate(zip(reqs, rows))
                if r["measured"] and row is not None]
        by_len = sorted(done, key=lambda i: len(reqs[i]["prompt"]))
        over = [i for i in by_len
                if len(reqs[i]["prompt"]) > knobs["prefill_chunk"]]
        if not over:
            problems.append("no finished prompt longer than one prefill chunk")
        else:
            which = [by_len[0], over[0]]
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


# the knee is the engine's, whatever the order: the sweep is serve_openloop's
sweep = base.sweep
