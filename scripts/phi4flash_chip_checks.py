#!/usr/bin/env python3
"""One-off comparisons on the chip for the SambaY configuration
(phi-4-mini-flash-reasoning), outside the benchmark (PERF.md section 6,
PR 35). One process, one model at the published widths, the checks named on
the command line in order:

    python3 scripts/phi4flash_chip_checks.py [--seed N] [--rehearse] CHECK...

Every check serves rows that the cell's traffic produces for this seed, ONE
AFTER THE OTHER in an engine of two slots at the cell's sizes (each lands on
a slot, a ring and pages an earlier one left its state and keys in), and
compares them by the cell's own `phi4flash_reference.check_served` with the
cell's own limits.

`clean`:      the program as it is, on the schedule's shortest prompt, its
              shortest prompt over one prefill chunk and its longest. Has to
              pass. Also says which tiers ran.
`bf16_state`: the lower-precision control of the STATE: the SSM state slots
              kept in bfloat16 (`ops.selective_scan.STATE_DTYPE`), every
              token's state rounded to it, while the reference keeps
              float32. On the last row of `--rows`. Has to FAIL
              `check_served` (by its limit on the first layer's state: the
              logits cannot see it, PERF.md section 6, PR 35).
`fp8`:        the lower-precision control of the WEIGHTS: the program's
              weights rounded to float8_e4m3 (and back to bf16) while the
              reference keeps the configuration's bf16 weights. Has to FAIL
              `check_served`. Rounds the model in place: name it last.
The planted faults (a stale slot, a ring too short, a window off by one, the
tail gathered a token early, ...) are tests/test_phi4flash.py's, on the CPU.

Prints one JSON line a check; exits 0 if every check came out as it has to."""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

HAS_TO_PASS = {"clean": True, "bf16_state": False, "fp8": False}


def say(**line):
    print(json.dumps(line), flush=True)


def _load(rehearse):
    from benchmarks import phi4flash_model

    def read(*path):
        with open(os.path.join(ROOT, "benchmarks", *path)) as f:
            return json.load(f)

    cfg = phi4flash_model.load_config(
        read("configs", "phi-4-mini-flash-reasoning.json"), rehearse)
    cell = read("workloads", "phi4flash-reasoning-steady.json")
    knobs = dict(cell["engine"])
    tp = read("traffic", "reasoning-steady.json")
    if rehearse:
        knobs.update(cell["rehearse"]["engine"])
        tp = {**tp, **tp["rehearse"]}
    return cfg, knobs, tp


def rows_for(cfg, knobs, tp, seed, seconds=50):
    """{name: (prompt, max_new)} of the schedule's measured requests: the
    shortest prompt, the shortest over one prefill chunk, the longest."""
    from benchmarks.runners.serve_pinned_schedule import pinned_open_loop

    reqs = sorted((r for r in pinned_open_loop(tp, seed, seconds,
                                               cfg["vocab_size"])
                   if r["measured"]), key=lambda r: len(r["prompt"]))
    over = [r for r in reqs if len(r["prompt"]) > knobs["prefill_chunk"]]
    pick = {"shortest": reqs[0], "over_a_chunk": (over or reqs)[0],
            "longest": reqs[-1]}
    return {k: (r["prompt"], r["max_new"]) for k, r in pick.items()}


def plant(fault):
    """Patch the program for `fault`; returns the undo."""
    import jax.numpy as jnp

    from paddle_tpu.ops import selective_scan as ssm

    was = ssm.STATE_DTYPE
    if fault == "bf16_state":
        ssm.STATE_DTYPE = jnp.bfloat16
    return lambda: setattr(ssm, "STATE_DTYPE", was)


def serve(model, knobs, rows):
    """The rows one after the other through a fresh engine of two slots;
    the engine is gone on return (the reference needs the room)."""
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine

    from paddle_tpu.observability import tracing

    tracing.clear()
    eng = ContinuousBatchingEngine(model, **{**knobs, "max_seqs": 2})
    outs = [np.asarray(eng.serve([p], max_new_tokens=n)[0]) for p, n in rows]
    walls = {}
    for r in tracing.step_records():
        if r["engine"] == eng._engine_seq and not r["cold"]:
            walls.setdefault(r["kind"], []).append(
                (r["t_ready"] - r["t_disp0"]) / 1e6)
    say(check="dispatch_wall_ms", **{
        kind: {"n": len(ms), "median": float(np.median(ms)),
               "max": float(np.max(ms))} for kind, ms in walls.items()})
    eng.pools = None
    return outs


def tiers():
    from paddle_tpu.ops import paged_attention as pa
    from paddle_tpu.ops import ragged_paged_attention as rpa
    from paddle_tpu.ops import selective_scan as ssm

    return {"ragged_impl": rpa.LAST_IMPL, "paged_impl": pa.LAST_IMPL,
            "ssm_impl": ssm.LAST_IMPL}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("checks", nargs="+", choices=sorted(HAS_TO_PASS))
    ap.add_argument("--seed", type=int, default=3500000011)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rows", default="shortest,over_a_chunk,longest",
                    help="the rows `clean` serves and compares")
    ap.add_argument("--max-new", type=int, default=None,
                    help="cap every row's served tokens (a quick look)")
    ap.add_argument("--stall-s", type=int, default=300,
                    help="dump every thread's stack when nothing is said "
                         "for this long")
    args = ap.parse_args()
    import faulthandler

    faulthandler.dump_traceback_later(args.stall_s, repeat=True)

    import jax

    from benchmarks import phi4flash_model, phi4flash_reference

    def timed(name):
        fn = getattr(phi4flash_reference, name)

        def wrapped(*a, **kw):
            t0 = time.monotonic()
            out = fn(*a, **kw)
            say(check=name, seconds=time.monotonic() - t0)
            return out
        setattr(phi4flash_reference, name, wrapped)

    for name in ("hidden_rows", "logits_of", "own_logits"):
        timed(name)
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg, knobs, tp = _load(args.rehearse)
    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit(f"no TPU: {dev}")
    model = phi4flash_model.build(cfg, args.seed, train=False,
                                  max_len=knobs["max_len"],
                                  rehearse=args.rehearse)
    rows = rows_for(cfg, knobs, tp, args.seed)
    if args.max_new:
        rows = {k: (p, min(n, args.max_new)) for k, (p, n) in rows.items()}
    say(check="setup", device=dev.device_kind, seed=args.seed,
        parameters=model.num_parameters(),
        rows={k: [len(p), n] for k, (p, n) in rows.items()})
    # what each check serves, in order; the LAST row is the one compared
    # unless the check compares them all
    plan = {"clean": args.rows.split(","),
            "bf16_state": args.rows.split(",")[-1:],
            "fp8": args.rows.split(",")[-1:]}
    if "fp8" in args.checks[:-1]:
        ap.error("fp8 rounds the model in place: name it last")
    ok = True
    for check in args.checks:
        t0 = time.monotonic()
        mine = [rows[k] for k in plan[check]]
        weights = None
        if check == "fp8":
            import jax.numpy as jnp

            weights = dict(model.raw_state_dict())
            for p in model.parameters():
                if p._data.ndim >= 2 and p._data.dtype == jnp.bfloat16:
                    p._data = p._data.astype(jnp.float8_e4m3fn).astype(
                        p._data.dtype)
        undo = plant(check)
        try:
            outs = serve(model, knobs, mine)
            ran = tiers()
            say(check=check, served_s=time.monotonic() - t0)
            compared = (range(len(mine)) if check == "clean"
                        else [len(mine) - 1])
            try:
                got = {"passed": True, **phi4flash_reference.check_served(
                    model, [mine[i][0] for i in compared],
                    [outs[i] for i in compared], weights=weights)}
            except phi4flash_reference.Wrong as e:
                got = {"passed": False, "why": str(e)[:3000]}
        finally:
            undo()
        as_it_has_to = got["passed"] == HAS_TO_PASS[check]
        ok &= as_it_has_to
        say(check=check, as_it_has_to=as_it_has_to, served=plan[check],
            seconds=time.monotonic() - t0, **ran, **got)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
