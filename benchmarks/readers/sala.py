"""Readers for a configuration of sparse-attention and linear-attention
layers (`sala_model` / `sala_flops`): the share of its roofline that each of
the four named scopes of the serving programs reaches, what the selector
keeps, how full the state slots are, and the whole step's share of the peak.
Each returns a number, or None when there is nothing to read (a program
without the scopes or the counters, another family's cell, no trace).

A scope's DEVICE TIME is read as `readers/kimi.py` reads it (its
`_roofline`: the union of a scope's events inside the module runs matched to
step records, whatever implements the scope). `sala.sparse.prefill` covers
the whole tiled walk, its selector (`sala.select`) included;
`sala.sparse.decode` covers a step's selector and its attention over the
kept pages. The LEAST TIME is `sala_flops`' count for the step records' own
extents: `rows` (q_len and kv_len a row) and `counters`
(`sparse_keys_kept`, summed over the dispatch's queries, K/V heads, sparse
layers and forwards). A one-token row's kept keys follow from its position
by the selection rule alone (`sala_flops.kept_keys`), so the counter's
remainder is the spans'."""
from benchmarks import sala_flops
from benchmarks.readers import kimi, steps


def _mine(ctx):
    return "mixer_types" in ctx.cfg and "sparse_config" in ctx.cfg


def _decode_kept(cfg, calls):
    """Kept (query, K/V head, key) triples of the one-token rows, ONE sparse
    layer: a row of kv_len tokens asks at position kv_len - 1."""
    hkv = cfg["num_key_value_heads"]
    return hkv * int(sum(sala_flops.kept_keys(cfg, kv - 1)
                         for ext in calls for kv in ext))


def sparse_prefill_roofline(ctx):
    if not _mine(ctx):
        return None
    n_sparse, _ = sala_flops.layers(ctx.cfg)

    def cost(rec, layers):
        spans, calls = kimi._dispatch_attention(rec)
        c = rec.get("counters")
        if not spans or not c or not n_sparse:
            return []
        kept = max(c["sparse_keys_kept"] / n_sparse
                   - _decode_kept(ctx.cfg, calls), 0)
        return [(sala_flops.sparse_span_cost(ctx.cfg, kept, spans), n_sparse)]

    return kimi._roofline(ctx, "sala.sparse.prefill", cost)


def sparse_decode_roofline(ctx):
    if not _mine(ctx):
        return None
    n_sparse, _ = sala_flops.layers(ctx.cfg)
    hkv = ctx.cfg["num_key_value_heads"]

    def cost(rec, layers):
        _, calls = kimi._dispatch_attention(rec)
        out = []
        for ext in calls:
            if ext:
                kept = hkv * int(sum(sala_flops.kept_keys(ctx.cfg, kv - 1)
                                     for kv in ext))
                out.append((sala_flops.sparse_cost(
                    ctx.cfg, kept, [(kv - 1, 1) for kv in ext]), n_sparse))
        return out

    return kimi._roofline(ctx, "sala.sparse.decode", cost)


def lightning_prefill_roofline(ctx):
    if not _mine(ctx):
        return None
    _, n_light = sala_flops.layers(ctx.cfg)

    def cost(rec, layers):
        spans, _ = kimi._dispatch_attention(rec)
        return [(sala_flops.lightning_cost(
            ctx.cfg, sum(q for q, _ in spans), len(spans)), n_light)
                ] if spans else []

    return kimi._roofline(ctx, "sala.lightning.prefill", cost)


def lightning_decode_roofline(ctx):
    if not _mine(ctx):
        return None
    _, n_light = sala_flops.layers(ctx.cfg)

    def cost(rec, layers):
        _, calls = kimi._dispatch_attention(rec)
        return [(sala_flops.lightning_cost(ctx.cfg, len(ext), len(ext)),
                 n_light) for ext in calls if ext]

    return kimi._roofline(ctx, "sala.lightning.decode", cost)


# ---- program counters (every run) ------------------------------------------

def _counted(ctx, name):
    return [r for r in steps._window(ctx, steps._log(ctx))
            if name in (r.get("counters") or {})]


def kept_pct(ctx):
    """Keys the sparse layers' queries attended, of those they could see,
    over the window's dispatches."""
    win = _counted(ctx, "sparse_keys_visible")
    seen = sum(r["counters"]["sparse_keys_visible"] for r in win)
    if not seen:
        return None
    return 100.0 * sum(r["counters"]["sparse_keys_kept"] for r in win) / seen


def slots_used_pct(ctx):
    """Rows whose state a forward updated, of the slots there are, over the
    window's forwards (k a dispatch)."""
    win = [r for r in _counted(ctx, "state_rows") if r.get("slots")]
    if not win:
        return None
    return 100.0 * sum(r["counters"]["state_rows"] for r in win) / sum(
        r["k"] * r["slots"][1] for r in win)


def serve_mfu_pct(ctx):
    """The block's operations (`sala_flops.request_flops`) for the tokens
    the window completed, over window x peak: a request's operations are
    spread evenly over its tokens, prompt tokens counted when its first
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
        per_tok = sala_flops.request_flops(
            ctx.cfg, r["n_prompt"], r["n_generated"]) / n
        if 0 <= r["t_first"] <= window:
            done += per_tok * r["n_prompt"]
        span = max(r["t_done"] - r["t_first"], 1e-9)
        inside = max(0.0, min(r["t_done"], window) - max(r["t_first"], 0.0))
        done += per_tok * r["n_generated"] * inside / span
    return 100.0 * done / (window * ctx.peak["bf16_flops_per_s"])
