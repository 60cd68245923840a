"""Readers over the runner's raw records (host clock, request stamps).
Each takes the run's context and returns a number, or None when there is
nothing to read (the harness then leaves the metric out)."""
from benchmarks import flops
from benchmarks.traffic import percentile


def _measured(ctx):
    if ctx.result["kind"] != "serve":
        return None
    return [r for r in ctx.result["requests"] if r["measured"]]


def _miss_ms(ctx):
    # a failed, shed or unfinished request misses every limit: it counts
    # as the drain timeout
    return 1e3 * ctx.result["drain_timeout_s"]


def tokens_made_between(requests, lo, hi):
    """Output tokens made in [lo, hi] (window-relative s), a request's tokens
    spread evenly between its first token and its end."""
    toks = 0.0
    for r in requests:
        if r["t_done"] is None or r["n_generated"] < 1:
            continue
        a, b = r["t_first"], max(r["t_done"], r["t_first"] + 1e-9)
        toks += r["n_generated"] * max(0.0, min(b, hi) - max(a, lo)) / (b - a)
    return toks


def setup_s(ctx):
    return ctx.setup_s


def ttft_ms(ctx):
    """t_first_token - t_due over the window's requests: their mean
    (args.stat == "mean") or the percentile args.q."""
    reqs = _measured(ctx)
    if not reqs:
        return None
    xs = [1e3 * (r["t_first"] - r["due"]) if r["t_done"] is not None
          else _miss_ms(ctx) for r in reqs]
    if ctx.args.get("stat") == "mean":
        return sum(xs) / len(xs)
    return percentile(xs, ctx.args["q"])


def tpot_ms(ctx):
    """Percentile args.q of (t_done - t_first_token) / (n_generated - 1)."""
    reqs = _measured(ctx)
    if not reqs:
        return None
    xs = [1e3 * (r["t_done"] - r["t_first"]) / (r["n_generated"] - 1)
          if r["t_done"] is not None else _miss_ms(ctx)
          for r in reqs if r["t_done"] is None or r["n_generated"] > 1]
    return percentile(xs, ctx.args["q"])


def serve_tok_per_s(ctx):
    """Output tokens made inside the window over the window, from every
    request (warm-in and drained ones too). Tokens carry no stamps of their
    own, so a request's tokens are spread evenly between its first token
    and its end (they leave the engine a decode block at a time). Counting
    only requests that FINISH inside the window swings by a whole request
    at each edge: +-8% between seeds on the chip (PR 24)."""
    if ctx.result["kind"] != "serve":
        return None
    w = ctx.result["window_s"]
    return tokens_made_between(ctx.result["requests"], 0.0, w) / w


def queue_wait_ms(ctx):
    """Percentile args.q of t_admit - t_due over admitted window requests."""
    reqs = _measured(ctx)
    if not reqs:
        return None
    return percentile([1e3 * (r["t_admit"] - r["due"]) for r in reqs
                       if r["t_admit"] is not None], ctx.args["q"])


def gen_late_ms(ctx):
    """Percentile args.q of (actual submit time - due time)."""
    reqs = _measured(ctx)
    if not reqs:
        return None
    return percentile([1e3 * r["late"] for r in reqs], ctx.args["q"])


def train_tok_per_s_chip(ctx):
    """Tokens of the steps whose completion was observed in the window over
    the window (its start to the last step's block_until_ready) and chips."""
    if ctx.result["kind"] != "train":
        return None
    steps = ctx.result["steps"]
    return (sum(s["tokens"] for s in steps) / ctx.result["window_s"]
            / ctx.chips)


def train_mfu_pct(ctx):
    """The run's own tokens/s/chip x FLOPs/token (flops.py) over the peak."""
    rate = train_tok_per_s_chip(ctx)
    if rate is None:
        return None
    per_tok = flops.train_flops_per_token(ctx.cfg, ctx.result["shape"]["seq"])
    return 100.0 * rate * per_tok / ctx.peak["bf16_flops_per_s"]
