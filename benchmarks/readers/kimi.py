"""Readers for a latent-attention, routed-expert configuration
(`kimi_model` / `kimi_flops`): the share of its roofline that each of the
three named scopes of the serving programs reaches, the expert layer's
routing counters, the latent pool's occupancy and the whole step's share of
the peak. Each returns a number, or None when there is nothing to read (a
program without the scopes or the counters, a training cell, no trace).

A scope's DEVICE TIME is read the same way whatever implements it. The
trace's `XLA Ops` events carry HLO instruction names and no scope; the
compiled programs carry, on every instruction, the `op_name` that
`jax.named_scope` wrote (`.../mla.decode/dot_general`), and the engine's
warm-up keeps, for its two step programs, which scope each instruction lies
under (`tracing.program_scopes`, the innermost scope winning). A scope's
time is the UNION of its events' intervals inside the module runs that
`steps._traced_runs` matched to step records (`XLA Ops` nests: a scope's
`while` covers its body). The LEAST TIME is `kimi_flops`' count for
the step records' own extents (`rows`: q_len and kv_len a row; `counters`:
assignments and touched experts a dispatch) through
`flops.roofline_seconds`."""
import collections

from benchmarks import flops, kimi_flops
from benchmarks.readers import steps
from benchmarks.readers.trace import instr, union_ns

#: step-record kind -> the ledger key's stem of the program that ran it
PROGRAMS = {"mixed": "serve.ragged[", "decode": "serve.decode_block["}


def _program_scopes(ctx):
    """{kind: {instruction: scope}} of the two step programs, from the
    program's own table (`tracing.program_scopes`, filled in the engine's
    warm-up); None (said) when the program keeps none."""
    from paddle_tpu.observability import tracing

    tables = getattr(tracing, "program_scopes", None)
    out = {}
    for kind, stem in PROGRAMS.items():
        mine = [k for k in tables or () if k.startswith(stem)]
        if len(mine) != 1:
            ctx.say("kimi", metric=ctx.name, kind=kind,
                    refused=f"{len(mine)} scope tables under {stem}")
            return None
        out[kind] = tables[mine[0]]
    ctx.say("kimi", metric="scopes", instructions={
        kind: dict(collections.Counter(m.values())) for kind, m in
        out.items()})
    return out


def _scope_seconds(ctx):
    """(runs, {scope: device seconds inside those runs}) or None."""
    runs = steps._once(ctx, "traced_runs", lambda: steps._traced_runs(ctx))
    if not runs:
        return None
    scopes = steps._once(ctx, "kimi_scopes", lambda: _program_scopes(ctx))
    if not scopes:
        return None
    spans = collections.defaultdict(list)
    ops = sorted(ctx.trace.ops(), key=lambda o: o[1])
    i = 0
    for rec, lo, hi in sorted(runs, key=lambda r: r[1]):
        table = scopes[rec["kind"]]
        while i < len(ops) and ops[i][1] < lo:
            i += 1
        j = i
        while j < len(ops) and ops[j][1] < hi:
            scope = table.get(instr(ops[j][0])[0].lstrip("%"))
            if scope and ops[j][2] <= hi:
                spans[scope].append((ops[j][1], ops[j][2]))
            j += 1
        i = j
    return runs, {s: union_ns(v) / 1e9 for s, v in spans.items()}


def _dispatch_attention(rec):
    """(spans, [one-token extents a forward]) of one dispatch: the mixed
    pass's spans of two or more tokens go the expanded way, its one-token
    rows and every scan step's participants the absorbed way."""
    rows = rec["rows"]
    if rec["kind"] == "mixed":
        spans = [(q, kv) for _, _, q, kv in rows if q >= 2]
        first = [[kv for _, _, q, kv in rows if q == 1]]
        tail = [kv for _, role, _, kv in rows if role in steps.PARTICIPANT]
        return spans, first + [[kv + s for kv in tail]
                               for s in range(1, rec["k"])]
    ext = [kv for _, _, _, kv in rows]
    return [], [[kv + s for kv in ext] for s in range(rec["k"])]


def _roofline(ctx, scope, cost_of):
    got = steps._once(ctx, "kimi_scope_s", lambda: _scope_seconds(ctx))
    if not got:
        return None
    runs, seconds = got
    layers = ctx.cfg["num_hidden_layers"]
    least, by_bound = 0.0, collections.Counter()
    for rec, _, _ in runs:
        for cost, n_layers in cost_of(rec, layers):
            t, bound = flops.roofline_seconds(*cost, ctx.peak)
            least += n_layers * t
            by_bound[bound] += n_layers * t
    ctx.say("roofline", metric=ctx.name, scope=scope, runs=len(runs),
            least_s=least, least_s_by_bound=dict(by_bound),
            scope_s=seconds.get(scope), all_scopes_s=seconds)
    if not seconds.get(scope) or not least:
        return None
    return 100.0 * least / seconds[scope]


def mla_prefill_roofline(ctx):
    def cost(rec, layers):
        spans, _ = _dispatch_attention(rec)
        return [(kimi_flops.mla_prefill_cost(ctx.cfg, spans), layers)
                ] if spans else []

    return _roofline(ctx, "mla.prefill", cost)


def mla_decode_roofline(ctx):
    def cost(rec, layers):
        _, calls = _dispatch_attention(rec)
        return [(kimi_flops.mla_decode_cost(ctx.cfg, ext), layers)
                for ext in calls if ext]

    return _roofline(ctx, "mla.decode", cost)


def moe_experts_roofline(ctx):
    """The counters are sums over the dispatch's expert layers and
    forwards, so the least time is of the sums (no larger than the sum of
    the calls' own least times)."""
    def cost(rec, layers):
        c = rec.get("counters")
        return [(kimi_flops.moe_experts_cost(
            ctx.cfg, c["moe_assigned"], c["moe_hit"]), 1)] if c else []

    return _roofline(ctx, "moe.experts", cost)


# ---- program counters (every run) ------------------------------------------

def _counted(ctx):
    return [r for r in steps._window(ctx, steps._log(ctx))
            if r.get("counters")]


def _expert_forwards(ctx, rec):
    """Expert-layer forwards of one dispatch: every layer but the leading
    dense ones, once a forward (k a dispatch)."""
    return rec["k"] * (ctx.cfg["num_hidden_layers"]
                       - ctx.cfg["first_k_dense_replace"])


def experts_hit_pct(ctx):
    """Held experts that got at least one token, of those held, over the
    window's expert-layer forwards."""
    win = _counted(ctx)
    if not win:
        return None
    held = ctx.cfg["n_routed_experts"]
    return 100.0 * sum(r["counters"]["moe_hit"] for r in win) / sum(
        held * _expert_forwards(ctx, r) for r in win)


def max_load_ratio(ctx):
    """The largest held expert's load over the mean held expert's load, a
    forward: sum of the maxima x experts held / sum of the assignments."""
    win = _counted(ctx)
    assigned = sum(r["counters"]["moe_assigned"] for r in win)
    if not assigned:
        return None
    return (ctx.cfg["n_routed_experts"]
            * sum(r["counters"]["moe_max_load"] for r in win) / assigned)


def pool_used_pct(ctx):
    """Mean share of the latent pool's pages held by requests at dispatch."""
    win = [r for r in steps._window(ctx, steps._log(ctx)) if r.get("pages")]
    if not win:
        return None
    return 100.0 * sum(r["pages"][0] / r["pages"][1] for r in win) / len(win)


def serve_mfu_pct(ctx):
    """The block's operations (`kimi_flops.request_flops`) for the tokens
    the window completed, over window x peak: a request's operations are
    spread evenly over its tokens, prompt tokens counted when its first
    token came and output tokens as they were made."""
    if ctx.result["kind"] != "serve" or "router_width" not in ctx.cfg:
        return None
    window = ctx.result["window_s"]
    done = 0.0
    for r in ctx.result["requests"]:
        if r["t_first"] is None or r["t_done"] is None:
            continue
        n = r["n_prompt"] + r["n_generated"]
        per_tok = kimi_flops.request_flops(
            ctx.cfg, r["n_prompt"], r["n_generated"]) / n
        if 0 <= r["t_first"] <= window:
            done += per_tok * r["n_prompt"]
        span = max(r["t_done"] - r["t_first"], 1e-9)
        inside = max(0.0, min(r["t_done"], window) - max(r["t_first"], 0.0))
        done += per_tok * r["n_generated"] * inside / span
    return 100.0 * done / (window * ctx.peak["bf16_flops_per_s"])
