"""The reduction from a profiler trace (`*.xplane.pb`, read with
jax.profiler.ProfileData) to numbers. Device time is the UNION of the
intervals in which an op runs (the `XLA Ops` line nests: a `while` op
covers its body's ops, so a sum counts that time twice). Checked against
the recorded trace xprof_traces/tpu/20260731T043440 in
benchmarks/tests/test_harness.py."""
import collections
import re
import statistics

from benchmarks import flops
from benchmarks.readers.records import tokens_made_between

WINDOW_ANNOTATION = "bench.traced_window"
#: ops that only contain other ops on the same line
_CONTAINERS = {"while", "conditional", "call"}


def union_ns(intervals):
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def instr(name):
    """'%fusion.12 = bf16[..]{..} fusion(...)' -> ('%fusion.12', 'bf16[..]',
    'fusion'); a bare name comes back with empty type and opcode."""
    lhs, _, rhs = name.partition(" = ")
    m = re.search(r"\s([a-z][\w\-]*)\(", " " + rhs)
    rtype = rhs.split("{")[0].split(" ")[0].lstrip("(") if rhs else ""
    return lhs.strip(), rtype, (m.group(1) if m else "")


class Trace:
    """Device lines per chip and the benchmark's host annotations, clipped
    to the `bench.traced_window` annotation when the trace has one."""

    def __init__(self, path):
        import jax

        data = jax.profiler.ProfileData.from_file(path)
        self.devices = {}     # plane name -> {line name: [(name, s, e)]}
        self.host = []        # (name, s, e) of bench.* annotations
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                lines = {}
                for line in plane.lines:
                    if line.name in ("XLA Ops", "XLA Modules",
                                     "Async XLA Ops"):
                        lines[line.name] = [
                            (e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                if lines.get("XLA Ops"):
                    self.devices[plane.name] = lines
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("bench."):
                            self.host.append(
                                (e.name, e.start_ns,
                                 e.start_ns + e.duration_ns))
        win = [h for h in self.host if h[0] == WINDOW_ANNOTATION]
        if win:
            self.window = (win[0][1], win[0][2])
        elif not self.devices:
            return
        else:  # a trace not taken by this harness: first to last device op
            ops = [o for d in self.devices.values() for o in d["XLA Ops"]]
            self.window = (min(o[1] for o in ops), max(o[2] for o in ops))
        lo, hi = self.window
        for lines in self.devices.values():
            for k, evs in lines.items():
                lines[k] = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                            if e > lo and s < hi]

    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9

    def ops(self, device=None):
        device = device or sorted(self.devices)[0]
        return self.devices[device]["XLA Ops"]

    def modules(self, device=None):
        device = device or sorted(self.devices)[0]
        return self.devices[device].get("XLA Modules", [])

    def busy_s(self):
        """Seconds in which an op ran, averaged over the chips traced."""
        per = [union_ns([(s, e) for _, s, e in d["XLA Ops"]]) / 1e9
               for d in self.devices.values()]
        return sum(per) / len(per)

    def sum_s(self, device=None):
        return sum(e - s for _, s, e in self.ops(device)) / 1e9

    def span_s(self, device=None):
        ops = self.ops(device)
        return (max(e for _, _, e in ops) - min(s for _, s, _ in ops)) / 1e9

    def module_runs(self, pattern):
        """Durations (s) of the executions of modules matching `pattern`
        that lie wholly inside the window, on the first device."""
        lo, hi = self.window
        rx = re.compile(pattern)
        return [(e - s) / 1e9 for n, s, e in self.modules()
                if rx.search(n) and s > lo and e < hi]

    def kernel_s(self, pattern, device=None):
        rx = re.compile(pattern)
        return sum(e - s for n, s, e in self.ops(device)
                   if rx.search(instr(n)[0])) / 1e9

    def by_kind(self, n=12):
        """[kind, seconds] of the leaf ops on the first device, grouped by
        kernel name (custom calls) or opcode (everything else)."""
        by = collections.Counter()
        for nm, s, e in self.ops():
            name, _, op = instr(nm)
            if op in _CONTAINERS:
                continue
            kind = (re.sub(r"\.\d+$", "", name) if op == "custom-call"
                    else re.sub(r"[.\d]+$", "", name.lstrip("%")) or op)
            by[kind] += (e - s) / 1e9
        return [[k, v] for k, v in by.most_common(n)]

    def breakdown(self, n_ops=8, n_gaps=5):
        """{"device_ops": the leaf ops with most summed time on the first
        device (a kernel's instances counted together), "idle_gaps": the device's idle seconds by what the
        benchmark's own thread was doing (its annotation overlapping the
        gap most; `unannotated` = none)}."""
        by = collections.Counter()
        for n, s, e in self.ops():
            name, rtype, op = instr(n)
            if op in _CONTAINERS:
                continue
            if op == "custom-call":  # a kernel: its instances together
                name = re.sub(r"\.\d+$", "", name)
            by[f"{name} {rtype} {op}".strip()[:120]] += (e - s) / 1e9
        busy = merged([(s, e) for _, s, e in self.ops()])
        edges = [self.window[0]] + [x for b in busy for x in b] + [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        notes = [h for h in self.host if h[0] != WINDOW_ANNOTATION]
        idle = collections.Counter()
        for gs, ge in gaps:
            best, best_ov = "unannotated", 0.0
            for n, s, e in notes:
                ov = min(e, ge) - max(s, gs)
                if ov > best_ov:
                    best, best_ov = n, ov
            idle[best] += (ge - gs) / 1e9
        return {"device_ops": [[k, v] for k, v in by.most_common(n_ops)],
                "idle_gaps": [[k, v] for k, v in idle.most_common(n_gaps)]}


# ---- readers (each: ctx -> number or None) --------------------------------

def idle_pct(ctx):
    """100 x (1 - union of XLA Ops intervals / traced window)."""
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)


def module_ms(ctx):
    """Median device duration of the executions of args.module."""
    if ctx.trace is None:
        return None
    runs = ctx.trace.module_runs(ctx.args["module"])
    return 1e3 * statistics.median(runs) if runs else None


def kernel_busy_pct(ctx):
    """args.kernel's events' share of the device's busy time."""
    if ctx.trace is None:
        return None
    k = ctx.trace.kernel_s(ctx.args["kernel"])
    return 100.0 * k / ctx.trace.busy_s() if k else None


def tokens_per_dispatch(ctx):
    """Output tokens in the traced window / executions of args.module in
    it. Tokens leave the engine without stamps of their own, so a request's
    tokens are spread evenly between its first token and its end."""
    if ctx.trace is None or ctx.result["kind"] != "serve":
        return None
    runs = ctx.trace.module_runs(ctx.args["module"])
    if not runs:
        return None
    return tokens_made_between(ctx.result["requests"],
                               *ctx.traced_interval) / len(runs)


def attn_roofline_pct(ctx):
    """Least time for causal attention forward + backward at the cell's
    shapes (flops.py; per chip) over the summed device time of args.kernel's
    events per step. At seq 4096 the bound is compute."""
    if ctx.trace is None or ctx.result["kind"] != "train":
        return None
    # whole steps only: the kernel's events inside whole module runs
    lo, hi = ctx.trace.window
    rx_m = re.compile(ctx.args["module"])
    rx_k = re.compile(ctx.args["kernel"])
    spans = [(s, e) for n, s, e in ctx.trace.modules()
             if rx_m.search(n) and s > lo and e < hi]
    kern = sum(e - s for n, s, e in ctx.trace.ops()
               if rx_k.search(instr(n)[0])
               and any(ms <= s and e <= me for ms, me in spans)) / 1e9
    steps = len(spans)
    if not steps or not kern:
        return None
    shape = ctx.result["shape"]
    layers = ctx.cfg["num_hidden_layers"]
    fl = layers * flops.attention_flops(ctx.cfg, shape["batch"], shape["seq"])
    by = layers * flops.attention_bytes(ctx.cfg, shape["batch"], shape["seq"])
    least, bound = flops.roofline_seconds(fl / ctx.chips, by / ctx.chips,
                                          ctx.peak)
    ctx.say("roofline", metric=ctx.name, bound=bound, least_s_per_step=least,
            kernel_s_per_step=kern / steps, steps=steps)
    return 100.0 * least / (kern / steps)


_COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                         r"collective-permute")


def collective_exposed_pct(ctx):
    """Share of the traced window in which a collective is in flight on the
    first device (a synchronous op on `XLA Ops`, or the span from its -start
    to its -done on `Async XLA Ops`) and no other op runs."""
    if ctx.trace is None:
        return None
    dev = ctx.trace.devices[sorted(ctx.trace.devices)[0]]
    coll = [(s, e) for n, s, e in dev.get("Async XLA Ops", [])
            if _COLLECTIVE.search(instr(n)[0] + instr(n)[2])]
    other = []
    for n, s, e in dev["XLA Ops"]:
        name, _, op = instr(n)
        if op in _CONTAINERS:
            continue
        if _COLLECTIVE.search(name + op):
            if not op.endswith(("-start", "-done")):
                coll.append((s, e))
        else:
            other.append((s, e))
    if not coll:
        return None
    exposed = union_ns(coll + other) - union_ns(other)
    return 100.0 * exposed / (ctx.trace.window_s * 1e9)
