#!/usr/bin/env python3
"""One-off comparisons on the chip for the sparse + linear attention
configuration, outside the benchmark (PERF.md section 6, PR 33). One process,
one model, the checks named on the command line in order:

    python3 scripts/sala_chip_checks.py [--seed N] [--rehearse] CHECK...

Every check serves the same two requests ONE AFTER THE OTHER in an engine of
its own at the cell's sizes: a first request, then the row the cell's runner
hands the reference for this seed (the schedule's shortest prompt, which
crosses `dense_len`). The second lands on the slot and the pages the first
left its state and keys in, and is compared by the cell's own
`sala_reference.check_served` with the cell's own limits.

`clean`:       the program as it is. Has to pass. Also says what the decode
               kernel read: pages a step in its table a K/V head against the
               row's pages.
`fault_state`: planted: a row that starts at length 0 keeps the state its
               slot held (the zeroing inside the step programs is skipped).
               On the cell's own row it CANNOT show: the slowest head's decay
               is exp(-2^-8) a token, so after a prompt of 9,216 tokens the
               slot's old state is worth exp(-36) of itself when the first
               served token is made (read on the chip, PR 33: the same tokens
               to the last digit). So this check and `clean_short` compare a
               SHORT second row (96 prompt tokens) on the reused slot, where
               it has to fail `check_served`; `clean_short` has to pass.
`fault_table`: planted: a decode step's kept-block table is shifted by one
               block (it reads the neighbours of the pages the selector
               kept). Has to fail `check_served`.
`fp8`:         the lower-precision control: the program's weights rounded to
               float8_e4m3 (and back to bf16) while the reference keeps the
               configuration's bf16 weights. Has to fail `check_served`.
               Rounds the model in place: name it last.

`sweep`:       (named alone; no model, no engine) device time of ONE sparse
               layer's decode (`sparse_decode_attention`: selector and
               kernel) and ONE lightning layer's decode (`lightning_decode`,
               the slots donated) at the cell's widths and pool, contexts
               9k / 17k / 49k, 1, 2, 4, 8 and 16 live rows of 16, scattered
               over the slots: the median of `--calls` runs of each, read off
               a profiler trace's `XLA Modules` line. `--tree DIR` imports
               `paddle_tpu` from another checkout (the parent's, unpacked
               beside: both ops keep their signatures), so one chip call
               measures parent and change: the table of PERF.md section 6
               (PR 36), which set the ops' `_WALK_UP_TO`.

Prints one JSON line a check; exits 0 if every check came out as it has to."""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

HAS_TO_PASS = {"clean": True, "clean_short": True, "fault_state": False,
               "fault_table": False, "fp8": False}
#: checks that compare a short row on the reused slot (its prompt's tokens)
SHORT = {"clean_short": 96, "fault_state": 96}


def say(**line):
    print(json.dumps(line), flush=True)


def _load(rehearse):
    from benchmarks import sala_model

    def read(*path):
        with open(os.path.join(ROOT, "benchmarks", *path)) as f:
            return json.load(f)

    cfg = sala_model.load_config(read("configs", "minicpm-sala-9b.json"),
                                 rehearse)
    cell = read("workloads", "minicpm-sala-longdoc-steady.json")
    knobs = dict(cell["engine"])
    tp = read("traffic", "longdoc-steady.json")
    if rehearse:
        knobs.update(cell["rehearse"]["engine"])
        tp = {**tp, **tp["rehearse"]}
    return cfg, knobs, tp


def rows_for(cfg, tp, seed, seconds=50):
    """[(prompt, max_new)]: another request of the schedule, then the one
    the cell's runner compares (the shortest measured prompt)."""
    from benchmarks.runners.serve_pinned_schedule import pinned_open_loop

    reqs = [r for r in pinned_open_loop(tp, seed, seconds, cfg["vocab_size"])
            if r["measured"]]
    by_len = sorted(reqs, key=lambda r: len(r["prompt"]))
    first, compared = by_len[1], by_len[0]
    return [(first["prompt"], min(first["max_new"], 16)),
            (compared["prompt"], compared["max_new"])]


def plant(fault):
    """Patch the program for `fault`; returns the undo."""
    from paddle_tpu.models import minicpm_sala
    from paddle_tpu.ops import lightning_attention as la
    from paddle_tpu.ops import sparse_decode_attention as sda
    import jax.numpy as jnp

    saved = []

    def swap(mod, name, new):
        saved.append((mod, name, getattr(mod, name)))
        setattr(mod, name, new)

    if fault == "fault_state":
        decode, prefill = la.lightning_decode, la.lightning_prefill

        def stale_decode(q, k, v, state, lengths, *a, **kw):
            return decode(q, k, v, state, jnp.maximum(lengths, 1), *a, **kw)

        def stale_prefill(q, k, v, state, kv_lens, *a, **kw):
            # `before = kv_lens - q_lens` is all this argument feeds
            return prefill(q, k, v, state, kv_lens + 1, *a, **kw)

        swap(la, "lightning_decode", stale_decode)
        swap(la, "lightning_prefill", stale_prefill)
        swap(minicpm_sala, "lightning_decode", stale_decode)
    elif fault == "fault_table":
        select = sda.block_mask

        def shifted(logits, t, sp, nblocks):
            return jnp.roll(select(logits, t, sp, nblocks), -1, axis=-1)

        swap(sda, "block_mask", shifted)

    def undo():
        for mod, name, old in saved:
            setattr(mod, name, old)
    return undo


def serve(model, knobs, rows):
    """The rows one after the other through a fresh engine of two slots;
    the engine is gone on return (the reference needs the room)."""
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine
    from paddle_tpu.observability import tracing

    tracing.clear()
    eng = ContinuousBatchingEngine(model, **{**knobs, "max_seqs": 2})
    outs = [np.asarray(eng.serve([p], max_new_tokens=n)[0]) for p, n in rows]
    recs = [r for r in tracing.step_records()
            if r["engine"] == eng._engine_seq and r.get("counters")]
    eng.pools = None
    return outs, recs


def pages_read(cfg, recs):
    """Of the compared row's decode blocks: K/V pages a step the kernel's
    table held (kept keys / block, from the program's counter) against the
    pages the row had, summed over steps, K/V heads and sparse layers."""
    bs = cfg["sparse_config"]["block_size"]
    kept = seen = 0
    for r in recs:
        if r["kind"] == "decode":
            kept += -(-r["counters"]["sparse_keys_kept"] // bs)
            seen += -(-r["counters"]["sparse_keys_visible"] // bs)
    return {"decode_pages_in_tables": kept, "decode_pages_of_rows": seen}


SWEEP_CONTEXTS, SWEEP_LIVE = (9216, 17408, 49152), (1, 2, 4, 8, 16)


def sweep(args):
    """The `sweep` check: one JSON line a (op, context, live rows), then the
    table; also left in chiprun_out/pr36/."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    from benchmarks.profiler import WindowTracer
    from benchmarks.readers.trace import Trace
    from paddle_tpu.ops.lightning_attention import (
        decay_slopes, lightning_decode,
    )
    from paddle_tpu.ops.sparse_decode_attention import sparse_decode_attention
    from paddle_tpu.ops.sparse_paged_attention import SparseConfig

    cfg, knobs, _ = _load(args.rehearse)
    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit(f"no TPU: {dev}")
    sp = SparseConfig(**cfg["sparse_config"])
    B, bs = knobs["max_seqs"], knobs["page_size"]
    npages = knobs["max_len"] // bs
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    lh, ld = cfg["lightning_nh"], cfg["lightning_head_dim"]
    contexts = [c // 256 if args.rehearse else c for c in SWEEP_CONTEXTS]
    live_counts = [n for n in SWEEP_LIVE if n <= B]
    key = jax.random.split(jax.random.PRNGKey(args.seed % 2 ** 31), 8)
    bf = jnp.bfloat16
    pool = (hkv, B * npages + 1, bs, d)
    k_pages = jax.random.normal(key[0], pool, bf)
    v_pages = jax.random.normal(key[1], pool, bf)
    c_keys = jax.random.normal(key[2], (hkv, pool[1] * sp.per_page, d), bf)
    q = jax.random.normal(key[3], (B, hq, d), bf)
    table = 1 + jnp.arange(B * npages, dtype=jnp.int32).reshape(B, npages)
    lq, lk, lv = (jax.random.normal(k, (B, lh, ld), bf) for k in key[4:7])
    slopes = decay_slopes(lh)

    def sweep_sparse(q, k_pages, v_pages, c_keys, table, lengths):
        return sparse_decode_attention(q, k_pages, v_pages, c_keys, table,
                                       lengths, sp)

    def sweep_lightning(q, k, v, state, lengths, live):
        return lightning_decode(q, k, v, state, lengths, live, slopes)

    sparse = jax.jit(sweep_sparse)
    lightning = jax.jit(sweep_lightning, donate_argnums=3)

    def rows_of(n):   # scattered over the slots, as an engine's rows are
        return (3 + np.arange(n) * B // n) % B

    def lengths_of(n, ctx):
        out = np.zeros(B, np.int32)
        out[rows_of(n)] = ctx
        return jnp.asarray(out)

    state = jax.random.normal(key[7], (B, lh, ld, ld), jnp.float32)
    cases = ([("sparse", c, n) for c in contexts for n in live_counts]
             + [("lightning", contexts[0], n) for n in live_counts])
    lens = lengths_of(B, contexts[0])               # compile both
    got = sparse(q, k_pages, v_pages, c_keys, table, lens)
    _, state = lightning(lq, lk, lv, state, lens - 1, lens > 0)
    jax.block_until_ready((got, state))
    out_dir = os.path.join(ROOT, "chiprun_out", "pr36")
    say(check="sweep", tree=args.name, device=dev.device_kind,
        paddle_tpu=os.path.dirname(paddle_tpu.__file__), cases=len(cases))
    # the benchmark's own hold on the profiler: on at once, the traced
    # interval under the annotation its reader clips to
    tracer = WindowTracer(True, os.path.join(out_dir, "sweep_trace."
                                             + args.name), 0.0, 0.0)
    tracer.tick(0.0)
    for op, c, n in cases:
        lens = lengths_of(n, c)
        for _ in range(args.calls):
            if op == "sparse":
                got = sparse(q, k_pages, v_pages, c_keys, table, lens)
            else:
                got, state = lightning(lq, lk, lv, state,
                                       jnp.maximum(lens - 1, 0), lens > 0)
        jax.block_until_ready((got, state))
    tracer.stop()
    trace = Trace(tracer.xplane_path())
    if not trace.devices:   # a rehearsal: the CPU's trace has no device line
        return say(check="sweep", tree=args.name, cases=len(cases),
                   device_time="not measured")
    runs = {"sparse": trace.module_runs("sweep_sparse"),
            "lightning": trace.module_runs("sweep_lightning")}
    lines = []
    for op, c, n in cases:
        mine, runs[op] = runs[op][:args.calls], runs[op][args.calls:]
        if len(mine) != args.calls:
            raise SystemExit(f"the trace holds {len(mine)} runs of {op} at "
                             f"{c} x {n}, not {args.calls}")
        lines.append({"check": "sweep", "tree": args.name, "op": op,
                      "context": c, "live": n,
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
    ap.add_argument("--seed", type=int, default=3300000011)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--tree", help="sweep: import paddle_tpu from this "
                    "checkout (the parent's) instead of the script's own")
    ap.add_argument("--calls", type=int, default=20,
                    help="sweep: runs of each case")
    args = ap.parse_args()
    if "fp8" in args.checks[:-1]:
        ap.error("fp8 rounds the model in place: name it last")
    if "sweep" in args.checks:
        if args.checks != ["sweep"]:
            ap.error("sweep runs alone")
        args.name = "change"
        if args.tree:     # ".parent" -> "parent"
            sys.path.insert(0, os.path.abspath(args.tree))
            args.name = os.path.basename(sys.path[0]).lstrip(".")
        return sweep(args)

    import jax
    import jax.numpy as jnp

    from benchmarks import sala_model, sala_reference
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg, knobs, tp = _load(args.rehearse)
    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit(f"no TPU: {dev}")
    model = sala_model.build(cfg, args.seed, train=False,
                             max_len=knobs["max_len"], rehearse=args.rehearse)
    rows = rows_for(cfg, tp, args.seed)
    say(check="setup", device=dev.device_kind, seed=args.seed,
        prompt_lens=[len(p) for p, _ in rows], max_new=[n for _, n in rows])
    ok = True
    for check in args.checks:
        t0 = time.monotonic()
        weights = None
        if check == "fp8":
            weights = dict(model.raw_state_dict())
            for p in model.parameters():
                if p._data.ndim >= 2:
                    p._data = p._data.astype(jnp.float8_e4m3fn).astype(
                        p._data.dtype)
        mine = rows
        if check in SHORT:   # the compared row cut short: the slot's old
            mine = [rows[0],  # state has not decayed away when it is served
                    (rows[1][0][:SHORT[check]], rows[1][1])]
        undo = plant(check)
        try:
            outs, recs = serve(model, knobs, mine)
        finally:
            undo()
        try:
            got = {"passed": True, **sala_reference.check_served(
                model, [mine[1][0]], [outs[1]], weights=weights)}
        except sala_reference.Wrong as e:
            got = {"passed": False, "why": str(e)[:3000]}
        as_it_has_to = got["passed"] == HAS_TO_PASS[check]
        ok &= as_it_has_to
        say(check=check, as_it_has_to=as_it_has_to,
            seconds=time.monotonic() - t0,
            **(pages_read(cfg, recs) if check == "clean" else {}), **got)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
