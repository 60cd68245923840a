#!/usr/bin/env python3
"""One-off comparisons on the chip for the latent-attention configuration,
outside the benchmark (PERF.md section 6, PR 29). One process, one model,
the checks named on the command line in order:

    python3 scripts/kimi_chip_checks.py [--seed N] [--rehearse] CHECK...

`long`:  one prompt of 16,384 tokens and 64 generated through the engine at
         the cell's sizes (positions past original_max_position_embeddings,
         which the runner's two short rows never reach), against the blocked
         float32 reference: `kimi_reference.check_served`, the cell's own
         comparison and limits. Has to pass.
`flip`:  where a far token comes from. The two rows the runner checks for
         this seed (the traffic file's own prompts and lengths) are served,
         then every position of both rows is compared teacher-forced: the
         program's full-forward argmax against the reference's logits, and
         the experts the program's router chose against the reference's, in
         every expert layer. Says how the gap below the reference's top
         logit divides between positions with and without a flipped HELD
         expert, names the worst positions (layer, experts in and out, the
         8th-to-9th score margin on both sides), and forces the reference
         to the program's choices to see the gap close. A far SERVED token
         is looked up at its position in the same tables.
`fault`: a planted wrong-page fault: every decode row reads its neighbour's
         pages (`page_indices` rolled by one row inside the absorbed path).
         Has to fail `check_served`.
`fp8`:   the lower-precision control: the program's weights rounded to
         float8_e4m3 (and back to bf16) while the reference keeps the
         configuration's bf16 weights. Has to fail `check_served`. Rounds the
         model in place: name it last.

`flip`, `fault` and `fp8` serve the same two rows in an otherwise idle
engine. Prints one JSON line a check (and one for the engine's warm-up);
exits 0 if every check came out as it has to."""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402


def say(**line):
    print(json.dumps(line), flush=True)


def _load(rehearse):
    from benchmarks import kimi_model

    def read(*path):
        with open(os.path.join(ROOT, "benchmarks", *path)) as f:
            return json.load(f)

    cfg = kimi_model.load_config(
        read("configs", "kimi-k2.7-code-ep32.json"), rehearse)
    cell = read("workloads", "kimi-k2.7-code-agent-steady.json")
    knobs = dict(cell["engine"])
    tp = read("traffic", "agent-code-steady.json")
    if rehearse:
        knobs.update(cell["rehearse"]["engine"])
        tp = {**tp, **tp["rehearse"]}
    return cfg, knobs, tp


def runner_rows(cfg, knobs, tp, seed, seconds=50):
    """The two requests the cell's runner hands the reference when every
    request finishes: the shortest measured prompt, and the shortest over one
    prefill chunk, of the schedule every seed runs. [(prompt, max_new)]."""
    from benchmarks.runners.serve_pinned_schedule import pinned_open_loop

    reqs = [r for r in pinned_open_loop(tp, seed, seconds, cfg["vocab_size"])
            if r["measured"]]
    short = min(reqs, key=lambda r: len(r["prompt"]))
    long_ = min((r for r in reqs if len(r["prompt"]) > knobs["prefill_chunk"]),
                key=lambda r: len(r["prompt"]))
    return [(r["prompt"], r["max_new"]) for r in (short, long_)]


def serve(model, knobs, rows, timed=False):
    """Each of `rows` [(prompt, max_new)] through a fresh engine; the engine
    is gone on return (the reference needs the room)."""
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine
    from paddle_tpu.observability import compilemem

    eng = ContinuousBatchingEngine(model, **knobs)
    if timed:
        # what the cell's set-up pays in warm-up, and what of it the scope
        # tables (`_publish_scopes`) cost
        publish, spent = eng._publish_scopes, []

        def timed_publish():
            t = time.monotonic()
            publish()
            spent.append(time.monotonic() - t)

        eng._publish_scopes = timed_publish
        t0 = time.monotonic()
        eng.warmup(buckets=[max(len(p) for p, _ in rows)])
        say(check="warmup", warmup_s=time.monotonic() - t0,
            publish_scopes_s=sum(spent),
            compiles={e["key"].split("[")[0]: e["wall_s"] for e in
                      compilemem.ledger.report(recent=8)["recent"]})
    outs = [np.asarray(o) for o in eng.serve(
        [p for p, _ in rows], max_new_tokens=[n for _, n in rows])]
    eng.pools = None
    return outs


def verdict(model, rows, outs, weights=None):
    from benchmarks import kimi_reference

    try:
        return {"passed": True, **kimi_reference.check_served(
            model, [p for p, _ in rows], outs, weights=weights)}
    except kimi_reference.Wrong as e:
        return {"passed": False, "why": str(e)[:2000]}


# ---- flip -------------------------------------------------------------------

class RouterSpy:
    """Wraps a router function `fn(...) -> (idx [T, k], weights)`: ships, a
    call, the chosen experts and the margin between the k-th and (k+1)-th
    choice score to the host, in call order; with `forced` (a list of idx
    arrays, one a call) it hands the function's caller THOSE experts, each
    weighted by this router's own score. All of it by ordered callbacks: a
    jitted layer is traced once and run for every layer and row."""

    def __init__(self, module, name, choice_of, top_k, forced=None):
        self.module, self.name, self.orig = module, name, getattr(module, name)
        self.choice_of, self.top_k = choice_of, top_k
        self.forced, self.seen = iter(forced or ()), []
        self.force = forced is not None

    def __enter__(self):
        import jax
        import jax.numpy as jnp
        from jax.experimental import io_callback

        def spy(*args, **kw):
            idx, w = self.orig(*args, **kw)
            score, choice, norm, scaling = self.choice_of(*args, **kw)
            top = jax.lax.top_k(choice, self.top_k + 1)[0]
            if self.force:
                idx = io_callback(
                    lambda: np.asarray(next(self.forced), idx.dtype),
                    jax.ShapeDtypeStruct(idx.shape, idx.dtype), ordered=True)
                w = jnp.take_along_axis(score, idx, axis=-1)
                if norm:
                    w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
                w = w * scaling
            jax.debug.callback(
                lambda i, m: self.seen.append((np.asarray(i), np.asarray(m))),
                idx, top[:, -2] - top[:, -1], ordered=True)
            return idx, w

        setattr(self.module, self.name, spy)
        return self

    def __exit__(self, *exc):
        setattr(self.module, self.name, self.orig)


def _program_choice(x, gate_w, bias, top_k, scoring="sigmoid",
                    norm_topk_prob=True, scaling=1.0):
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(jnp.einsum(
        "th,eh->te", x.astype(jnp.float32), gate_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    return s, s + bias.astype(jnp.float32), norm_topk_prob, scaling


def _reference_choice(cfg, w, pre, x):
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(
        x @ jnp.asarray(w[pre + "gate.weight"]).astype(jnp.float32).T)
    return (s, s + jnp.asarray(w[pre + "gate.e_score_correction_bias"]
                               ).astype(jnp.float32),
            cfg.get("norm_topk_prob", True), cfg["routed_scaling_factor"])


def flip(model, cfg, rows, outs):
    """See the module's docstring. The spies see the routers in call order:
    a row after the other, an expert layer after the other."""
    import jax

    from benchmarks import kimi_reference as ref
    from paddle_tpu.incubate.distributed.models.moe import dropless

    k = cfg["num_experts_per_tok"]
    first, held = cfg.get("first_expert", 0), cfg["n_routed_experts"]
    with RouterSpy(dropless, "route", _program_choice, k) as prog, \
            RouterSpy(ref, "router", _reference_choice, k) as plain:
        pairs = ref.logit_pairs(model, outs, cfg)
        jax.effects_barrier()
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    assert len(prog.seen) == len(plain.seen) == layers * len(outs)
    with RouterSpy(ref, "router", _reference_choice, k,
                   forced=[i for i, _ in prog.seen]) as forced:
        refs_forced = ref.forward_rows(cfg, model.raw_state_dict(), outs)
        jax.effects_barrier()
    report = {"rows": []}
    for r, ((prompt, _), out, (own, plain_ref), forced_ref) in enumerate(
            zip(rows, outs, pairs, refs_forced)):
        n = len(out) - 1                       # positions that predict a token
        token = own[:n].argmax(-1)             # the program's, teacher-forced
        gap = ref.gaps_below_top(plain_ref, token, np.arange(n))
        gap_forced = ref.gaps_below_top(forced_ref, token, np.arange(n))
        flipped = np.zeros((layers, n), bool)  # a HELD expert in or out
        detail = {}
        for layer in range(layers):
            (pi, pm), (ri, rm) = (s.seen[r * layers + layer]
                                  for s in (prog, plain))
            for pos in range(n):
                odd = set(pi[pos].tolist()) ^ set(ri[pos].tolist())
                mine = sorted(e for e in odd if first <= e < first + held)
                if mine:
                    flipped[layer, pos] = True
                    detail[layer, pos] = {
                        "layer": layer + cfg["first_k_dense_replace"],
                        "held_experts_in_or_out": mine,
                        "program_margin": float(pm[pos]),
                        "reference_margin": float(rm[pos])}
        any_flip = flipped.any(axis=0)
        served = np.arange(len(prompt) - 1, n)
        served_gap = ref.gaps_below_top(plain_ref, out[served + 1], served)
        worst = np.argsort(gap)[::-1][:4]
        # each far SERVED token: under the reference, under the reference
        # routed as the program's full forward routed, and under the
        # program's own full forward (0: it is that forward's argmax too)
        far_served = [{
            "position": int(p), "gap": float(g),
            "gap_reference_forced": float(ref.gaps_below_top(
                forced_ref, out[p + 1:p + 2], [p])[0]),
            "gap_own_full_forward": float(ref.gaps_below_top(
                own, out[p + 1:p + 2], [p])[0]),
            "flips": [detail[layer, int(p)] for layer in range(layers)
                      if flipped[layer, p]]}
            for p, g in zip(served, served_gap) if g > ref.SERVED_GAP_REL][:4]
        report["rows"].append({
            "positions": n, "prompt": len(prompt),
            "with_held_flip": int(any_flip.sum()),
            "flips_by_layer": flipped.sum(axis=1).tolist(),
            "far": int((gap > ref.SERVED_GAP_REL).sum()),
            "far_with_held_flip": int((gap[any_flip]
                                       > ref.SERVED_GAP_REL).sum()),
            "max_gap_no_flip": float(gap[~any_flip].max(initial=0.0)),
            "max_gap_flip": float(gap[any_flip].max(initial=0.0)),
            "over_0.2_no_flip": int((gap[~any_flip] > 0.2).sum()),
            "over_0.2_flip": int((gap[any_flip] > 0.2).sum()),
            "max_gap_reference_forced": float(gap_forced.max(initial=0.0)),
            "served": {"tokens": len(served),
                       "worst_gap": float(served_gap.max(initial=0.0)),
                       "far": int((served_gap > ref.SERVED_GAP_REL).sum()),
                       "far_at_a_held_flip": int(
                           (served_gap[any_flip[served]]
                            > ref.SERVED_GAP_REL).sum()),
                       "far_tokens": far_served},
            "worst": [{"position": int(p), "gap": float(gap[p]),
                       "gap_reference_forced": float(gap_forced[p]),
                       "flips": [detail[layer, int(p)]
                                 for layer in range(layers)
                                 if flipped[layer, p]]} for p in worst]})
    return report


# ---- fault ------------------------------------------------------------------

def planted_wrong_pages():
    """Context: inside, every row of the absorbed path reads the pages of
    the row before it."""
    import contextlib

    import jax.numpy as jnp

    from paddle_tpu.models import deepseek_v3

    @contextlib.contextmanager
    def plant():
        real = deepseek_v3.mla_decode_attention

        def wrong(q_lat, q_rope, pages, lengths, page_indices, scale):
            return real(q_lat, q_rope, pages, lengths,
                        jnp.roll(page_indices, 1, axis=0), scale)

        deepseek_v3.mla_decode_attention = wrong
        try:
            yield
        finally:
            deepseek_v3.mla_decode_attention = real

    return plant()


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("checks", nargs="+",
                    choices=("long", "flip", "fault", "fp8"))
    ap.add_argument("--seed", type=int, default=2900000029)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on the CPU: proves the script, no more")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks import kimi_model
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg, knobs, tp = _load(args.rehearse)
    model = kimi_model.build(cfg, args.seed, train=False,
                             max_len=knobs["max_len"], rehearse=args.rehearse)
    rows = runner_rows(cfg, knobs, tp, args.seed,
                       seconds=3 if args.rehearse else 50)
    ok, outs = True, None
    for n, which in enumerate(args.checks):
        line = {"check": which, "seed": args.seed,
                "device": jax.devices()[0].device_kind}
        if which == "long":
            rng = np.random.default_rng(args.seed)
            n_long = knobs["max_len"] - (16 if args.rehearse else 1024)
            mine = [(rng.integers(1, cfg["vocab_size"], n_long,
                                  dtype=np.int32), 8 if args.rehearse else 64)]
            line.update(verdict(model, mine,
                                serve(model, knobs, mine, timed=n == 0)))
            good = line["passed"]
        elif which == "flip":
            outs = serve(model, knobs, rows, timed=n == 0)
            line.update(flip(model, cfg, rows, outs))
            good = True
        elif which == "fault":
            with planted_wrong_pages():
                line.update(verdict(model, rows, serve(model, knobs, rows)))
            good = not line["passed"]
        else:
            # the configuration's weights go to the host: both sets do not fit
            exact = {k: np.asarray(v)
                     for k, v in model.raw_state_dict().items()}
            for p in model.state_dict().values():
                if p._data.dtype == jnp.bfloat16 and p._data.ndim >= 2:
                    p._data = p._data.astype(jnp.float8_e4m3fn).astype(
                        jnp.bfloat16)
            line.update(verdict(model, rows, serve(model, knobs, rows),
                                weights=exact))
            good = not line["passed"] or args.rehearse
        say(**line, as_it_has_to=good)
        ok &= good
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
