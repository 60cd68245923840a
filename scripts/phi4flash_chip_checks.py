#!/usr/bin/env python3
"""One-off comparisons on the chip for the SambaY configuration
(phi-4-mini-flash-reasoning), outside the benchmark (PERF.md section 6,
PR 35). One process, one model at the published widths, the checks named on
the command line in order:

    python3 scripts/phi4flash_chip_checks.py [--seed N] [--rehearse] CHECK...
    python3 scripts/phi4flash_chip_checks.py [--tree DIR] [--calls N] sweep

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
`sweep`:      (named alone; no model, no engine) device time of the paged
              decode kernel ALONE, off a profiler trace's module lines, at
              the cell's shape (32 slots, 40 / 10 heads of 128, pages of 64,
              a table 256 wide, bf16): 13 live rows all of 4,992 tokens
              (`u`), 13 / 4 / 32 live rows of the lengths the cell's traffic
              gives (`r`, `r4`, `r32`: `SWEEP_LENGTHS`), the window layers'
              call over rings (`w`); and at the dense cell's (16 slots,
              32 / 32 heads, pages of 16, a table 128 wide): 1 x 64, rows of
              300 and 900, 16 x 2,047. `--tree .parent` imports `paddle_tpu`
              from another checkout, so parent and change go in one chip
              call. PERF.md section 6, PR 38.
The planted faults (a stale slot, a ring too short, a window off by one, the
tail gathered a token early, ...) are tests/test_phi4flash.py's, on the CPU.

Prints one JSON line a check; exits 0 if every check came out as it has to."""
import argparse
import hashlib
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


#: 32 live extents as the cell's traffic gives them (a Monte Carlo over the
#: traffic file's two lognormals, rows in proportion to their residence, the
#: position uniform in the output; fixed here, PR 38): the first 4 average
#: 4,692, the first 13 5,078 with a longest of 12,603, all of them 4,960
SWEEP_LENGTHS = (
    2380, 11085, 2717, 2588, 2082, 3965, 4851, 3638, 12603, 12166, 3997,
    1752, 2189, 1738, 5203, 7450, 3692, 7491, 5403, 840, 4094, 2676, 4390,
    2095, 2908, 1747, 13602, 2686, 11047, 3838, 4459, 7364)


def sweep(args):
    """The `sweep` check: one JSON line a case, then the table; also left in
    chiprun_out/pr38/."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    from benchmarks.profiler import WindowTracer
    from benchmarks.readers.trace import Trace
    from paddle_tpu.ops.paged_attention import (
        WindowRingSpec, paged_decode_attention,
    )

    cfg, knobs, _ = _load(args.rehearse)
    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit(f"no TPU: {dev}")
    cut = 64 if args.rehearse else 1       # a rehearsal's rows are shorter
    window = cfg["sliding_window"]
    bf = jnp.bfloat16

    def shape(name, slots, hq, hkv, d, bs, npages, ring=None):
        """Pools, a query and a table at one cell's shape; with `ring` the
        table is the window layers' ring of that many pages a row."""
        key = jax.random.split(jax.random.PRNGKey(args.seed % 2 ** 31), 3)
        pool = (hkv, 1 + slots * (ring or npages), bs, d)
        k_pages = jax.random.normal(key[0], pool, bf)
        v_pages = jax.random.normal(key[1], pool, bf)
        q = jax.random.normal(key[2], (slots, hq, d), bf)
        own = 1 + jnp.arange(slots * npages, dtype=jnp.int32).reshape(
            slots, npages)
        table = WindowRingSpec.table((k_pages,), own) if ring else own

        def call(q, k_pages, v_pages, lengths, table):
            return paged_decode_attention(
                q, k_pages, v_pages, lengths, table,
                impl="pallas" if args.rehearse else None,
                window=window if ring else None)

        call.__name__ = f"sweep_{name}"
        return dict(fn=jax.jit(call), q=q, k=k_pages, v=v_pages, table=table,
                    slots=slots, key_bytes=2 * hkv * d * 2,
                    window=window if ring else None)

    S, bs = knobs["max_seqs"], knobs["page_size"]
    # a stored K/V head is a pair of the configuration's (differential
    # attention: `Phi4FlashModel.cache_spec`); the table as the engine's
    hq, hkv, d = (cfg["num_attention_heads"],
                  cfg["num_key_value_heads"] // 2, 2 * cfg["head_dim"])
    npages = -(-knobs["max_len"] // bs)
    chunk = knobs["prefill_chunk"]
    shapes = {
        "cross": shape("cross", S, hq, hkv, d, bs, npages),
        "window": shape("window", S, hq, hkv, d, bs, npages,
                        ring=-(-(window + chunk) // bs) + 1),
        "dense": shape("dense", 16, 32, 32, 128, 16, 128),
    }
    ragged = [max(n // cut, 1) for n in SWEEP_LENGTHS]
    cases = [("u", "cross", [4992 // cut] * 13), ("r", "cross", ragged[:13]),
             ("r4", "cross", ragged[:4]), ("r32", "cross", ragged[:S]),
             ("w", "window", ragged[:13]),
             ("d1x64", "dense", [64]), ("d300+900", "dense", [300, 900]),
             ("d16x2047", "dense", [2047] * 16)]

    def lengths_of(sh, lens):   # scattered over the slots, as an engine's are
        out = np.zeros(sh["slots"], np.int32)
        n = len(lens)
        out[(3 + np.arange(n) * sh["slots"] // n) % sh["slots"]] = lens
        return jnp.asarray(out)

    def run(sh, lens):
        lens = lengths_of(sh, lens)
        table = jnp.where((lens > 0)[:, None], sh["table"], 0)
        return sh["fn"](sh["q"], sh["k"], sh["v"], lens, table)

    # compile every shape; what each case returns, to set beside the other
    # tree's: a row's blocks fold in one order in both walks
    sha = {name: hashlib.sha1(np.asarray(
        run(shapes[which], lens).astype(jnp.float32)).tobytes()
    ).hexdigest()[:12] for name, which, lens in cases}
    out_dir = os.path.join(ROOT, "chiprun_out", "pr38")
    say(check="sweep", tree=args.name, device=dev.device_kind,
        paddle_tpu=os.path.dirname(paddle_tpu.__file__), cases=len(cases))
    # the benchmark's own hold on the profiler: on at once, the traced
    # interval under the annotation its reader clips to
    tracer = WindowTracer(True, os.path.join(out_dir, "sweep_trace."
                                             + args.name), 0.0, 0.0)
    tracer.tick(0.0)
    for name, which, lens in cases:
        for _ in range(args.calls):
            got = run(shapes[which], lens)
        jax.block_until_ready(got)
    tracer.stop()
    trace = Trace(tracer.xplane_path())
    if not trace.devices:   # a rehearsal: the CPU's trace has no device line
        return say(check="sweep", tree=args.name, cases=len(cases),
                   device_time="not measured", out_sha1=sha)
    runs = {which: trace.module_runs(f"sweep_{which}") for which in shapes}
    lines = []
    for name, which, lens in cases:
        mine, runs[which] = (runs[which][:args.calls],
                             runs[which][args.calls:])
        if len(mine) != args.calls:
            raise SystemExit(f"the trace holds {len(mine)} runs of {name}, "
                             f"not {args.calls}")
        sh = shapes[which]
        seen = [min(n, sh["window"] or n) for n in lens]
        lines.append({"check": "sweep", "tree": args.name, "case": name,
                      "live": len(lens), "keys": sum(lens),
                      "longest": max(lens), "out_sha1": sha[name],
                      "floor_us": 1e6 * sum(seen) * sh["key_bytes"] / 819e9,
                      "us_median": 1e6 * float(np.median(mine)),
                      "us_min": 1e6 * min(mine), "us_max": 1e6 * max(mine)})
        say(**lines[-1])
    with open(os.path.join(out_dir, f"sweep.{args.name}.json"), "w") as f:
        json.dump({"device": dev.device_kind, "calls": args.calls,
                   "lines": lines}, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("checks", nargs="+",
                    choices=sorted(HAS_TO_PASS) + ["sweep"])
    ap.add_argument("--tree", help="sweep: import paddle_tpu from this "
                    "checkout (the parent's) instead of the script's own")
    ap.add_argument("--calls", type=int, default=20,
                    help="sweep: runs of each case")
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
    if "sweep" in args.checks:
        if args.checks != ["sweep"]:
            ap.error("sweep runs alone")
        args.name = "change"
        if args.tree:     # ".parent" -> "parent"
            sys.path.insert(0, os.path.abspath(args.tree))
            args.name = os.path.basename(sys.path[0]).lstrip(".")
        return sweep(args)
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
