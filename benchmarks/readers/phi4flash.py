"""Readers for a configuration of state-space, window-attention and
cross-attention layers over one shared K/V pool (`phi4flash_model` /
`phi4flash_flops`): the share of its roofline that each of five named scopes
of the serving programs reaches, what the window walk and the cross-decoder's
gather save, and the whole step's share of the peak. Each returns a number,
or None when there is nothing to read (a program without the scopes or the
counters, another family's cell, no trace).

A scope's DEVICE TIME is read as `readers/kimi.py` reads it (its
`_roofline`: the union of a scope's events inside the module runs matched to
step records, whatever implements the scope). The LEAST TIME is
`phi4flash_flops`' count for the step records' own extents: `rows` (q_len
and kv_len a row). In a mixed dispatch the packed pass runs the trunk: spans
of two or more tokens go the scans' and the window layers' prefill way, its
one-token rows their decode way; the K/V layer's packed call is under
`sambay.full.prefill` (no metric reads it); the cross-decoder then reads the
pool once a cross layer for every row with a span. Each of the k - 1 scan
steps (k in a decode block) runs all layers on the participants."""
from benchmarks import phi4flash_flops as pf
from benchmarks.readers import kimi, steps


def _mine(ctx):
    return "mb_per_layer" in ctx.cfg and "sliding_window" in ctx.cfg


def ssm_prefill_roofline(ctx):
    if not _mine(ctx):
        return None
    n = pf.layers(ctx.cfg)["ssm"]

    def cost(rec, layers):
        spans, _ = kimi._dispatch_attention(rec)
        return [(pf.ssm_cost(ctx.cfg, sum(q for q, _ in spans), len(spans)),
                 n)] if spans else []

    return kimi._roofline(ctx, "sambay.ssm.prefill", cost)


def ssm_decode_roofline(ctx):
    if not _mine(ctx):
        return None
    n = pf.layers(ctx.cfg)["ssm"]

    def cost(rec, layers):
        _, calls = kimi._dispatch_attention(rec)
        return [(pf.ssm_cost(ctx.cfg, len(ext), len(ext)), n)
                for ext in calls if ext]

    return kimi._roofline(ctx, "sambay.ssm.decode", cost)


def swa_prefill_roofline(ctx):
    if not _mine(ctx):
        return None
    n = pf.layers(ctx.cfg)["swa"]

    def cost(rec, layers):
        # the ragged call holds every row of the packed pass: one-token
        # rows are spans of one
        spans = [(q, kv) for _, _, q, kv in rec["rows"] if q >= 1]
        return [(pf.swa_span_cost(ctx.cfg, spans), n)] if (
            rec["kind"] == "mixed" and spans) else []

    return kimi._roofline(ctx, "sambay.swa.prefill", cost)


def _scan_calls(rec):
    """The one-token extents of each forward of the dispatch's decode scan
    (a mixed dispatch's k - 1 steps after its packed pass; a decode block's
    k)."""
    _, calls = kimi._dispatch_attention(rec)
    return calls[1:] if rec["kind"] == "mixed" else calls


def swa_decode_roofline(ctx):
    if not _mine(ctx):
        return None
    n = pf.layers(ctx.cfg)["swa"]
    w = ctx.cfg["sliding_window"]

    def cost(rec, layers):
        return [(pf.attn_decode_cost(ctx.cfg, ext, w), n)
                for ext in _scan_calls(rec) if ext]

    return kimi._roofline(ctx, "sambay.swa.decode", cost)


def cross_decode_roofline(ctx):
    if not _mine(ctx):
        return None
    lay = pf.layers(ctx.cfg)

    def cost(rec, layers):
        out = [(pf.attn_decode_cost(ctx.cfg, ext), lay["readers"])
               for ext in _scan_calls(rec) if ext]
        if rec["kind"] == "mixed":   # the gathered span ends: cross layers
            ends = [kv for _, _, q, kv in rec["rows"] if q >= 1]
            if ends:
                out.append((pf.attn_decode_cost(ctx.cfg, ends),
                            lay["cross"]))
        return out

    return kimi._roofline(ctx, "sambay.cross.decode", cost)


# ---- program counters (every run) ------------------------------------------

def _counted(ctx, name):
    return [r for r in steps._window(ctx, steps._log(ctx))
            if name in (r.get("counters") or {})]


def keys_visited_pct(ctx):
    """Keys the window layers' kernels walk, of those a causal walk with no
    window would, over the window's dispatches."""
    win = _counted(ctx, "swa_keys_causal")
    causal = sum(r["counters"]["swa_keys_causal"] for r in win)
    if not causal:
        return None
    return 100.0 * sum(r["counters"]["swa_keys_visited"] for r in win) / causal


def tail_tok_pct(ctx):
    """Token x layer pairs the cross-decoder ran in the packed passes, of
    those it would have run had it taken every valid packed token as the
    trunk does."""
    if not _mine(ctx):
        return None
    win = _counted(ctx, "trunk_tokens")
    lay = pf.layers(ctx.cfg)
    trunk = sum(r["counters"]["trunk_tokens"] for r in win)
    if not trunk:
        return None
    return (100.0 * sum(r["counters"]["tail_tokens"] for r in win)
            / (trunk * lay["tail"] / lay["trunk"]))


def serve_mfu_pct(ctx):
    """The block's operations (`phi4flash_flops.request_flops`) for the
    tokens the window completed, over window x peak: a request's operations
    are spread evenly over its tokens, prompt tokens counted when its first
    token came and output tokens as they were made (as `kimi.serve_mfu_pct`
    counts them)."""
    if ctx.result["kind"] != "serve" or not _mine(ctx):
        return None
    window = ctx.result["window_s"]
    done = 0.0
    for r in ctx.result["requests"]:
        if r["t_first"] is None or r["t_done"] is None:
            continue
        n = r["n_prompt"] + r["n_generated"]
        per_tok = pf.request_flops(
            ctx.cfg, r["n_prompt"], r["n_generated"]) / n
        if 0 <= r["t_first"] <= window:
            done += per_tok * r["n_prompt"]
        span = max(r["t_done"] - r["t_first"], 1e-9)
        inside = max(0.0, min(r["t_done"], window) - max(r["t_first"], 0.0))
        done += per_tok * r["n_generated"] * inside / span
    return 100.0 * done / (window * ctx.peak["bf16_flops_per_s"])
