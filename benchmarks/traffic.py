"""The one general traffic generator: a pure function of (traffic file, seed,
seconds). The program under test receives only what this makes.

Every seed gets the SAME multiset of lengths and inter-arrival gaps — the
stratified quantiles of the distributions the traffic file names — in
another order. A seed therefore changes which request meets which, never
how much work a run holds, so runs of different seeds spread like runs of
one seed.
"""
import json
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name):
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def sized(params, rehearse):
    """The traffic parameters, with the file's own tiny overrides under
    --rehearse."""
    p = {k: v for k, v in params.items() if k != "rehearse"}
    if rehearse:
        p.update(params.get("rehearse", {}))
    return p


def percentile(xs, q):
    """Nearest-rank on the sorted sample (copied from bench_serving.py
    `_percentile`); None for an empty sample."""
    if not xs:
        return None
    xs = sorted(xs)
    i = min(len(xs) - 1, max(0, int(round(q * (len(xs) - 1)))))
    return xs[i]


def rng_for(seed, stream=0):
    """Any whole-number seed (the driver's exceed 2**31)."""
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), int(stream)])


def lengths(n, spec):
    """n lengths: the (i+0.5)/n quantiles of the named distribution,
    rounded and clipped — the same n values for every seed."""
    p = (np.arange(n) + 0.5) / max(n, 1)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in p])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + p * (spec["max"] - spec["min"])
    elif spec["dist"] == "fixed":
        v = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec.get("min", 1),
                   spec.get("max", 1 << 30)).astype(int)


def _gaps(n):
    """n exponential inter-arrival gaps (the (i+0.5)/n quantiles) with mean
    exactly 1, in operational time."""
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g * (n / g.sum())


def _warp(op, duration, rate, bursts):
    """Operational time (one arrival per unit on average) -> seconds.
    Constant rate: op / rate. With bursts {period_s, on_s, factor}: the
    rate is `factor` times higher in the first on_s of every period, the
    mean stays `rate`."""
    if not bursts:
        return op / rate
    per, on, fac = bursts["period_s"], bursts["on_s"], bursts["factor"]
    off_rate = rate * per / (on * fac + per - on)
    t = np.linspace(0.0, duration, int(duration * 1000) + 1)
    inst = np.where((t % per) < on, off_rate * fac, off_rate)
    cum = np.concatenate([[0.0], np.cumsum((inst[1:] + inst[:-1]) / 2
                                           * np.diff(t))])
    cum *= (rate * duration) / cum[-1]
    return np.interp(op, cum, t)


def _segment(params, seed, stream, duration, rate):
    n = int(round(rate * duration))
    if n <= 0:
        return []
    rng = rng_for(seed, stream)
    gaps = rng.permutation(_gaps(n))
    op = np.cumsum(gaps) - gaps / 2
    due = _warp(op, duration, rate, params.get("bursts"))
    p_len = rng.permutation(lengths(n, params["prompt"]))
    o_len = rng.permutation(lengths(n, params["output"]))
    return [(float(t), int(p), int(o)) for t, p, o in zip(due, p_len, o_len)]


def open_loop(params, seed, seconds, vocab, rate=None):
    """The requests of one run, in due order:
    [{"due": s relative to the window's start (negative = warm-in),
      "prompt": int32 token ids, "max_new": int, "measured": bool}].
    The warm-in requests repeat the lengths and spacing of the window's last
    `warm_in_s` seconds (with token ids of their own).

    ``share`` {group, fraction}: runs of `group` consecutive requests share
    the first `fraction` of the shortest member's prompt (a prefix cache's
    traffic). Token ids are uniform in [1, vocab)."""
    rate = float(rate if rate is not None else params["rate_per_s"])
    warm = float(params.get("warm_in_s", 0))
    window = _segment(params, seed, 2, float(seconds), rate)
    # the warm-in is the window's own tail, one window earlier: what the
    # window inherits at its start is what it leaves behind at its end, so
    # the tokens made inside it are the tokens it offers, whatever the seed
    reqs = [(t - seconds, p, o, False) for t, p, o in window
            if t >= seconds - warm]
    reqs += [(t, p, o, True) for t, p, o in window]
    rng = rng_for(seed, 3)
    out = []
    share = params.get("share")
    prefix = None
    for i, (t, p, o, measured) in enumerate(reqs):
        ids = rng.integers(1, vocab, size=p, dtype=np.int32)
        if share:
            g = int(share["group"])
            if i % g == 0:
                group = reqs[i:i + g]
                n_shared = int(min(x[1] for x in group) * share["fraction"])
                prefix = ids[:n_shared].copy()
            ids[:min(len(prefix), p)] = prefix[:p]
        out.append({"due": t, "prompt": ids, "max_new": o,
                    "measured": measured})
    return out


def train_batches(params, seed, vocab, replicas=1):
    """`distinct_batches` int32 [batch, seq+1] arrays of uniform token ids;
    the runner cycles through them."""
    rng = rng_for(seed, 4)
    batch = params["batch_per_replica"] * replicas
    return [rng.integers(0, vocab, size=(batch, params["seq"] + 1),
                         dtype=np.int32)
            for _ in range(params["distinct_batches"])]


def backlog(records, t):
    """Requests due by t (window-relative s) and not finished by t."""
    return sum(1 for r in records if r["due"] <= t
               and (r["t_done"] is None or r["t_done"] > t))

