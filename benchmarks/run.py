#!/usr/bin/env python3
"""benchmarks/run.py — runs ONE cell of BENCHMARK.json once, in one process.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name (see benchmarks/README.md): the
cell's file under workloads/, its configuration under configs/ with the
builder and the reference module that file names, its traffic mix under
traffic/, its runner under runners/, the kernel tiers its `correct` demands
(the workload file's `tiers`), and one file per metric under metrics/ naming
the reader that computes it. Nothing on this path knows an architecture: the
family lives in the builder, in flops.py and in the readers a cell's metrics
name. The last line of standard
output is the one JSON object the contract asks for; everything else goes
on earlier lines or into the output directory (chiprun_out/bench/).

--trace 0 reports the cell's end-to-end metrics with the profiler off;
--trace 1 reports its per-layer metrics, with jax.profiler on for the last
`trace_seconds` of the window.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result — except under --rehearse (tiny sizes, any
backend, never a result line), which exists for the sandbox rehearsals.
--check validates BENCHMARK.json against the files; --sweep finds a serving
cell's knee (one process, one engine, each rate in turn).
"""
import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

NAME_RX = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RX = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def say(phase, **fields):
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _module_file(name):
    """The file of module `name` under benchmarks/ (dots are directories)."""
    return os.path.join(HERE, *name.split(".")) + ".py"


def cell_metrics(man, cell, group):
    """The manifest's metrics of `group` that `cell` reports."""
    return [m for m in man[group]
            if "workloads" not in m or cell in m["workloads"]]


def check(man):
    """The manifest against the contract's limits that can be checked here
    and against the files it names. Returns a list of faults."""
    bad = []
    cells = {w["name"]: w for w in man["workloads"]}
    configs = {c["name"]: c for c in man["configs"]}
    e2e = {m["name"]: m for m in man["end_to_end"]}
    names = ([*cells, *configs, *e2e] + [m["name"] for m in man["per_layer"]]
             + [w["traffic"] for w in cells.values()]
             + [k for c in configs.values() for k in c["reduced"]])
    bad += [f"name {n!r} outside the allowed characters" for n in names
            if not NAME_RX.match(n)]
    if len(set(e2e) | {m["name"] for m in man["per_layer"]}) != (
            len(man["end_to_end"]) + len(man["per_layer"])):
        bad.append("two metrics share a name")
    for m in man["end_to_end"] + man["per_layer"]:
        if not UNIT_RX.match(m["unit"]):
            bad.append(f"unit {m['unit']!r} of {m['name']}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"better of {m['name']}")
        path = os.path.join(HERE, "metrics", m["name"] + ".json")
        if not os.path.exists(path):
            bad.append(f"no reader file {path}")
            continue
        spec = _load("metrics", m["name"] + ".json")
        for k in ("unit", "layer", "moves"):
            if k in m and spec.get(k) != m[k]:
                bad.append(f"{m['name']}: {k} differs between the manifest "
                           f"and its metrics file")
        mod, _, fn = spec["reader"].partition(":")
        if not os.path.exists(os.path.join(HERE, "readers", mod + ".py")):
            bad.append(f"{m['name']}: no reader module {mod}")
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"{m['name']}: unknown cell {w}")
    if "setup_s" not in e2e or e2e["setup_s"].get("workloads"):
        bad.append("setup_s must be an end-to-end metric of every cell")
    for m in man["end_to_end"]:
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"bound of {m['name']}")
    for name, w in cells.items():
        if w["config"] not in configs:
            bad.append(f"{name}: unknown config {w['config']}")
        missing = [f"{name}: no benchmarks/{part}/{stem}.json"
                   for part, stem in (("workloads", name),
                                      ("traffic", w["traffic"]))
                   if not os.path.exists(os.path.join(HERE, part,
                                                      stem + ".json"))]
        bad += missing
        if not missing:
            spec = _load("workloads", name + ".json")
            if (spec["config"], spec["traffic"], spec["chips"]) != (
                    w["config"], w["traffic"], w["chips"]):
                bad.append(f"{name}: manifest and workload file disagree")
            if not os.path.exists(os.path.join(
                    HERE, "runners", spec["runner"] + ".py")):
                bad.append(f"{name}: no runner {spec['runner']}")
            tiers = spec.get("tiers")
            if not tiers:
                bad.append(f"{name}: its workload file demands no kernel "
                           f"tiers (`tiers`): a cell may not run on a "
                           f"fallback by saying nothing")
            elif not all(isinstance(t, dict) and t.get("module")
                         and t.get("want") for t in tiers.values()):
                bad.append(f"{name}: each of `tiers` needs a `module` and "
                           f"the `want` its LAST_IMPL has to read")
        mine_e2e = {m["name"] for m in cell_metrics(man, name, "end_to_end")}
        mine_pl = cell_metrics(man, name, "per_layer")
        if len(mine_e2e) < 2 or not mine_pl:
            bad.append(f"{name}: needs setup_s, one more end-to-end metric "
                       f"and a per-layer metric")
        bad += [f"{name}: reports {m['name']} but not what it moves, "
                f"{m['moves']}" for m in mine_pl if m["moves"] not in mine_e2e]
    for c in configs.values():
        bad += _check_config(c)
        if not any(w["config"] == c["name"] for w in cells.values()):
            bad.append(f"config {c['name']} is used by no cell")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} of {len(cells)} cells ask for 4 chips")
    if any(w["chips"] not in (1, 4) for w in cells.values()):
        bad.append("chips must be 1 or 4")
    return bad


def _check_config(c):
    """One `configs` entry of the manifest against its file."""
    name = c["name"]
    if c["file"] != f"benchmarks/configs/{name}.json":
        return [f"config {name}: a cell finds its file by name, as "
                f"benchmarks/configs/{name}.json, not {c['file']}"]
    try:
        raw = _load("configs", name + ".json")
    except (OSError, ValueError) as e:
        return [f"config file {c['file']}: {e}"]
    bad = []
    for key in ("builder", "reference"):
        if not isinstance(raw.get(key), str):
            bad.append(f"config {name}: its file names no {key}")
        elif not os.path.exists(_module_file(raw[key])):
            bad.append(f"config {name}: no {key} module "
                       f"{os.path.relpath(_module_file(raw[key]), ROOT)}")
    if raw.get("source") != c["source"]:
        bad.append(f"config {name}: source differs between the manifest "
                   f"and its file")
    published = raw.get("published", {})
    for key in c["reduced"]:
        if key not in raw:
            bad.append(f"config {name}: reduced key {key} is not in its file")
        if key not in published:
            bad.append(f"config {name}: reduced key {key} lacks its "
                       f"published value (`published`)")
    return bad


class Ctx:
    """What a runner and a reader see of one run."""

    def __init__(self, args, man):
        from benchmarks import traffic
        from benchmarks.profiler import WindowTracer

        self.name = None
        self.cell_name = args.workload
        self.cell = _load("workloads", args.workload + ".json")
        self.chips = self.cell["chips"]
        self.seed, self.rehearse = args.seed, args.rehearse
        self.seconds = float(args.seconds)
        raw = _load("configs", self.cell["config"] + ".json")
        self.builder = importlib.import_module(f"benchmarks.{raw['builder']}")
        self.reference = importlib.import_module(
            f"benchmarks.{raw['reference']}")
        self.cfg = self.builder.load_config(raw, args.rehearse)
        self.traffic = traffic.sized(traffic.load(self.cell["traffic"]),
                                     args.rehearse)
        self.trace_dir = os.path.join(ROOT, ".bench_out", "trace",
                                      args.workload)
        self.tracer = WindowTracer(
            args.trace, self.trace_dir,
            float(self.traffic.get("trace_seconds", 4)), self.seconds)
        self.say = say
        self.t_window = None
        self.result = self.trace = self.peak = self.args = None
        self.traced_interval = None

    def mark_window_start(self, delay_s=0.0):
        """Set-up ends and the measured window starts `delay_s` from now;
        returns the window's start on time.monotonic()."""
        self.t_window = time.monotonic() + delay_s
        return self.t_window

    @property
    def setup_s(self):
        return self.t_window - T_PROCESS_START

    def tiers(self):
        """(checks, problems) for the kernel tiers the workload file
        demands: `tiers` maps a key of `checks` to the module whose
        LAST_IMPL has to read `want` once the timed path has run. A
        rehearsal runs off the chip, on other tiers: it records, and
        demands nothing."""
        checks, problems = {}, []
        for key, tier in self.cell["tiers"].items():
            got = importlib.import_module(tier["module"]).LAST_IMPL
            checks[key] = got
            if got != tier["want"] and not self.rehearse:
                problems.append(f"{key}: {tier['module']} ran on {got!r}, "
                                f"not {tier['want']!r}")
        return checks, problems


def _devices(ctx):
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not ctx.rehearse:
        if device["platform"] != "tpu":
            raise SystemExit(f"benchmarks/run.py: JAX found no TPU: {device}")
        if device["count"] < ctx.chips:
            raise SystemExit(f"benchmarks/run.py: the cell asks for "
                             f"{ctx.chips} chips, JAX has {device}")
        peaks = _load("peaks.json")
        if device["kind"] not in peaks:
            raise SystemExit(f"benchmarks/run.py: no peaks known for device "
                             f"kind {device['kind']!r} (peaks.json)")
        ctx.peak = peaks[device["kind"]]
    else:
        ctx.peak = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    return devs, device


def _read_metrics(ctx, man, group):
    out = {}
    for m in cell_metrics(man, ctx.cell_name, group):
        spec = _load("metrics", m["name"] + ".json")
        mod, _, fn = spec["reader"].partition(":")
        reader = getattr(importlib.import_module(f"benchmarks.readers.{mod}"),
                         fn)
        ctx.name, ctx.args = m["name"], spec.get("args", {})
        value = reader(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated request rates; serving cells only")
    ap.add_argument("--keep-trace", action="store_true")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "bench"))
    args = ap.parse_args(argv)
    man = manifest()
    if args.check:
        faults = check(man)
        for f in faults:
            print(f"benchmarks/run.py --check: {f}", file=sys.stderr)
        print(f"benchmarks/run.py --check: {len(faults)} fault(s), "
              f"{len(man['workloads'])} cell(s)")
        return 1 if faults else 0
    if not args.rehearse and args.workload not in {
            w["name"] for w in man["workloads"]}:
        raise SystemExit(f"benchmarks/run.py: no cell {args.workload!r} in "
                         f"BENCHMARK.json")
    if args.seconds is None:
        args.seconds = man["run_seconds"]

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    ctx = Ctx(args, man)
    devs, device = _devices(ctx)
    say("start", cell=ctx.cell_name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, rehearse=args.rehearse, device=device,
        compile_cache_dir=cache_dir)
    runner = importlib.import_module(
        f"benchmarks.runners.{ctx.cell['runner']}")
    os.makedirs(args.out, exist_ok=True)
    stem = os.path.join(args.out, f"{ctx.cell_name}.seed{args.seed}."
                                  f"trace{args.trace}")

    if args.sweep:
        table = runner.sweep(ctx, [float(r) for r in args.sweep.split(",")],
                             args.seconds)
        with open(os.path.join(args.out, f"{ctx.cell_name}.sweep.json"),
                  "w") as f:
            json.dump({"device": device, "seconds": args.seconds,
                       "table": table}, f, indent=1)
        return 0

    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    result = ctx.result = runner.run(ctx)
    used = devs[:ctx.chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in used]
    device["memory_peak_bytes"] = max([p for p in peaks if p] or [0])

    breakdown = None
    if args.trace:
        from benchmarks.readers.trace import Trace

        path = ctx.tracer.xplane_path()
        if path is None:
            raise SystemExit("benchmarks/run.py: --trace 1 wrote no trace")
        ctx.trace = Trace(path)
        if not ctx.trace.devices:
            if not args.rehearse:
                raise SystemExit("benchmarks/run.py: no op ran on a device "
                                 "in the traced window")
            ctx.trace = None  # the CPU backend has no device plane
    if ctx.trace is not None:
        ctx.traced_interval = (ctx.tracer.t_on - ctx.t_window,
                               ctx.tracer.t_off - ctx.t_window)
        device["busy_s"] = ctx.trace.busy_s()
        device["window_s"] = ctx.trace.window_s
        breakdown = ctx.trace.breakdown()
        say("trace", xplane_bytes=os.path.getsize(path),
            start_cost_s=ctx.tracer.start_cost_s,
            stop_cost_s=ctx.tracer.stop_cost_s, window_s=ctx.trace.window_s,
            devices=sorted(ctx.trace.devices),
            modules=sorted({re.sub(r"\(\d+\)$", "", n)
                            for n, _, _ in ctx.trace.modules()})[:20],
            host_annotations=sorted({h[0] for h in ctx.trace.host}),
            by_kind=ctx.trace.by_kind())
        if not args.keep_trace:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)

    # both groups are computed in every run (the earlier line lets a traced
    # run be set beside an untraced one); the result line carries one
    e2e = _read_metrics(ctx, man, "end_to_end")
    per_layer = _read_metrics(ctx, man, "per_layer")
    say("metrics", end_to_end=e2e, per_layer=per_layer,
        checks=result["checks"], problems=result["problems"],
        samples=result["attempted"], window_s=result["window_s"])
    with open(stem + ".json", "w") as f:
        json.dump({"device": device, "result": result, "end_to_end": e2e,
                   "per_layer": per_layer, "breakdown": breakdown},
                  f, default=str)
    if args.rehearse:
        if result["problems"]:
            print(f"benchmarks/run.py: rehearsal FAILED: "
                  f"{result['problems']}", file=sys.stderr)
            return 1
        print("benchmarks/run.py: rehearsal passed (not a chip run: no "
              "result line)", flush=True)
        return 0
    line = {"correct": not result["problems"],
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": per_layer if args.trace else e2e, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
