"""Summarize a captured xprof trace (a `jax.profiler.trace` directory):
per-category XLA-op busy time on the device track. Usage:

    python scripts/trace_summary.py xprof_traces/tpu/<ts>

Reads the vm.trace.json.gz under plugins/profile/ and prints one JSON line
plus a human table. Categories follow the hot paths of the LLaMA proxy:
fusions (GEMM+elementwise), pallas flash fwd/bwd, while-loop control (the
chunked fused-CE loop), copy/layout.
"""
import collections
import glob
import gzip
import json
import os
import sys


def categorize(name):
    nl = name.lower()
    if nl.startswith("flash_mha_bwd"):
        return "pallas_flash_bwd"
    if nl.startswith("flash_") or "mha" in nl or "flash_attention" in nl:
        return "pallas_flash_fwd"
    if "fusion" in nl:
        return "fusion"
    if "dot" in nl or "convolution" in nl:
        return "plain_matmul"
    if "copy" in nl or "transpose" in nl or "bitcast" in nl:
        return "copy_layout"
    if "while" in nl or "condition" in nl or "body" in nl:
        return "control"
    if "broadcast" in nl:
        return "broadcast"
    return "other"


def main(root):
    paths = glob.glob(os.path.join(root, "plugins", "profile", "*", "*.trace.json.gz"))
    if not paths:
        raise SystemExit(f"no trace json under {root}")
    d = json.load(gzip.open(paths[0]))
    events = d.get("traceEvents", [])
    pids, tids = {}, {}
    for e in events:
        if e.get("ph") == "M":
            if e.get("name") == "process_name":
                pids[e["pid"]] = e["args"].get("name", "")
            if e.get("name") == "thread_name":
                tids[(e["pid"], e.get("tid"))] = e["args"].get("name", "")
    dev = {p for p, n in pids.items() if "TPU" in n}
    op_tids = {k for k, n in tids.items() if k[0] in dev and n == "XLA Ops"}
    mod_tids = {k for k, n in tids.items() if k[0] in dev and n == "XLA Modules"}
    cats = collections.Counter()
    mod_us = 0.0
    for e in events:
        if e.get("ph") != "X":
            continue
        key = (e.get("pid"), e.get("tid"))
        if key in op_tids:
            cats[categorize(e.get("name", ""))] += e.get("dur", 0)
        elif key in mod_tids:
            mod_us += e.get("dur", 0)
    total = sum(cats.values())
    out = {
        "trace": root,
        "device_busy_ms": round(total / 1e3, 1),
        "module_wall_ms": round(mod_us / 1e3, 1),
        "categories_pct": {c: round(100 * us / max(total, 1), 1)
                           for c, us in cats.most_common()},
    }
    print(json.dumps(out))
    for c, us in cats.most_common():
        print(f"  {c:18s} {us / 1e3:9.1f} ms  {100 * us / max(total, 1):5.1f}%",
              file=sys.stderr)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else sorted(
        glob.glob("xprof_traces/tpu/*"))[-1])
