"""Run by hand: `pytest benchmarks/tests -q` (tier-1 collects tests/ only).

The readers of the engine's step log (benchmarks/readers/steps.py) on a
synthetic log and a synthetic device trace whose numbers can be worked out
by hand, and the serving cell's rehearsal printing what needs no trace.

The synthetic pipeline: block n is read back at 0.1 + 0.2 n s after the
window's start; the step() call that dispatches block n starts 4 ms after
block n-2 was read back, spends 2 ms before the jitted call and 1 ms in it,
then waits for block n-1 from 3 ms after its own start."""
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.readers import steps  # noqa: E402
from benchmarks.readers.trace import Trace  # noqa: E402

T_WINDOW = 1000.0
CELL = "deepseek7b-chat-steady"
CFG = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
       "num_hidden_layers": 2}
PEAK = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}
#: rows (rid, role, q_len, kv_len), emits and kind of blocks 0..8; request
#: 1 decodes throughout, 10 (600 tokens, 12 out) and 11 (100 tokens, 20
#: out) arrive in the window
ROWS = {
    2: ("mixed", [(1, "d", 1, 50), (10, "c", 512, 512)], [(1, 8)]),
    3: ("mixed", [(1, "d", 1, 58), (10, "g", 88, 600), (11, "g", 100, 100)],
        [(1, 8), (10, 8), (11, 8)]),
    4: ("decode", [(1, "d", 1, 66), (10, "d", 1, 608), (11, "d", 1, 108)],
        [(1, 8), (10, 4), (11, 8)]),
    5: ("decode", [(1, "d", 1, 74), (10, "d", 1, 612), (11, "d", 1, 116)],
        [(1, 8), (10, 0), (11, 4)]),
    6: ("decode", [(1, "d", 1, 82), (11, "d", 1, 120)], [(1, 8), (11, 0)]),
}


def _ns(rel_s):
    return round((T_WINDOW + rel_s) * 1e9)


def _ready(n):
    return 0.1 + 0.2 * n


def _step0(n):
    return _ready(n - 2) + 0.004


def _record(n):
    kind, rows, emits = ROWS.get(n, ("decode", [(1, "d", 1, 34 + 8 * n)],
                                     [(1, 8)]))
    admits = {2: [(10, T_WINDOW + 0.04, T_WINDOW + 0.10, 600)],
              3: [(11, T_WINDOW + 0.30, T_WINDOW + 0.302, 100)]}
    return {"seq": 100 + n, "engine": 3, "kind": kind, "k": 8,
            "chained": n > 0, "cold": False, "t_step0": _ns(_step0(n)),
            "t_pack0": _ns(_step0(n) + 0.0001),
            "t_disp0": _ns(_step0(n) + 0.002),
            "t_disp1": _ns(_step0(n) + 0.003),
            "t_sync0": _ns(_step0(n + 1) + 0.003), "t_ready": _ns(_ready(n)),
            "t_emit1": _ns(_ready(n) + 0.001), "rows": rows,
            "admits": admits.get(n, []), "emits": emits}


def _request(due, t_admit_abs, n_prompt, t_first, t_done, n_gen,
             measured=True):
    return {"due": due, "measured": measured, "n_prompt": n_prompt,
            "max_new": n_gen, "late": 0.0, "t_admit": t_admit_abs - T_WINDOW,
            "t_first": t_first, "t_done": t_done, "n_generated": n_gen,
            "error": None}


def _kernel(name, i, s, e):
    return (f"%{name}.{i} = f32[16,4,1,8]{{3,2,1,0}} custom-call(f32[1] %p), "
            f"custom_call_target=\"tpu_custom_call\"", s, e)


def _trace(drop_paged=0, late_run4=0.003, device_stops_at=None):
    """Blocks 1..5 on the device: block n runs from ready(n) - 0.2 s to
    0.5 ms before ready(n), block 4 starting `late_run4` late; traced from
    0.25 to 0.95 s, the device's own trace to `device_stops_at` if given.
    Profiler clock: 5 s at the trace's start."""
    lo = 5_000_000_000

    def at(rel_s):
        return lo + round((rel_s - 0.25) * 1e9)

    t = Trace.__new__(Trace)
    mods, ops, paged = [], [], 0
    for n in range(1, 6):
        kind = ROWS.get(n, ("decode",))[0]
        s = at(_ready(n) - 0.2 + (late_run4 if n == 4 else 0))
        e = at(_ready(n) - 0.0005)
        mods.append((f"jit_{'ragged_step' if kind == 'mixed' else 'decode_block'}"
                     f"({n})", s, e))
        ops.append((f"%fusion.{n} = bf16[8]{{0}} fusion(bf16[8]{{0}} %x)",
                    s, e))
        if kind == "mixed":  # a layer's ragged call: 1 ms in block 2, 2 in 3
            for layer in range(2):
                a = s + 1_000_000 + layer * 5_000_000
                ops.append(_kernel("ragged_paged_attention", layer, a,
                                   a + (n - 1) * 1_000_000))
        for i in range(2 * (7 if kind == "mixed" else 8)):
            a = s + 20_000_000 + i * 1_000_000
            ops.append(_kernel("paged_attention", paged, a, a + 100_000))
            paged += 1
    if drop_paged:
        whole = [i for i, o in enumerate(ops) if "%paged_attention" in o[0]
                 and o[1] > at(0.3)]
        del ops[whole[0]]
    hi = at(0.95)
    if device_stops_at is not None:
        stop = at(device_stops_at)
        ops = [(n, s, min(e, stop)) for n, s, e in ops if s < stop]
        mods = [(n, s, min(e, stop)) for n, s, e in mods if s < stop]
    t.window = (lo, hi)
    t.host = []
    t.devices = {"/device:TPU:0": {
        "XLA Ops": [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                    if e > lo and s < hi],
        "XLA Modules": [(n, max(s, lo), min(e, hi)) for n, s, e in mods
                        if e > lo and s < hi]}}
    return t


@pytest.fixture
def ctx(monkeypatch):
    from paddle_tpu.observability import tracing

    log = [_record(n) for n in range(9)]
    # a warm-up dispatch long before the window is never read
    log.insert(0, dict(_record(0), seq=1, t_disp0=_ns(-50), t_ready=_ns(-49.8)))
    monkeypatch.setattr(tracing, "step_records", lambda n=None: list(log),
                        raising=False)
    said = []
    c = types.SimpleNamespace(
        name="m", args={}, cfg=CFG, peak=PEAK, t_window=T_WINDOW, said=said,
        say=lambda phase, **kw: said.append({"phase": phase, **kw}),
        trace=_trace(), tracer=types.SimpleNamespace(
            t_on=T_WINDOW + 0.25, t_off=T_WINDOW + 0.95),
        result={"kind": "serve", "window_s": 1.0, "drain_timeout_s": 60,
                "shape": {"max_seqs": 4}, "requests": [
                    _request(-0.5, T_WINDOW - 0.45, 30, -0.1, 2.0, 200,
                             measured=False),
                    _request(0.05, T_WINDOW + 0.10, 600, _ready(3), 0.9, 12),
                    _request(0.30, T_WINDOW + 0.302, 100, _ready(3), 1.1,
                             20)]})
    return c


def test_window_holds_the_dispatches_made_in_it(ctx):
    log = steps._log(ctx)
    assert [r["seq"] for r in log] == list(range(100, 109))
    # block 2 is the first dispatched inside the window (0.106 s), block 6
    # the last (0.906 s); 0 and 1 were in flight at its start
    assert [r["seq"] for r in steps._window(ctx, log)] == [102, 103, 104,
                                                          105, 106]


def test_host_busy_share(ctx):
    # four periods of 0.2 s, each holding a wait of 0.193 s for the block
    # ahead: the host is busy for 7 ms of every 200
    assert steps.host_busy_pct(ctx) == pytest.approx(3.5, abs=1e-6)
    assert ctx.said[-1]["periods_s"] == pytest.approx(0.8)
    assert ctx.said[-1]["host_us_per_dispatch"] == pytest.approx(7e3)
    # of which 1.9 ms packing, 1 ms in the jitted call, 1 ms emitting
    assert ctx.said[-1]["mean_phase_us"] == pytest.approx({
        "serve.pack": 1900, "serve.decode": 1000, "serve.decode.sync": 193e3,
        "serve.emit": 1000})


def test_host_busy_skips_periods_after_an_empty_pipeline(ctx):
    from paddle_tpu.observability import tracing

    log = tracing.step_records()
    log[5]["chained"] = False  # block 4 found the pipeline empty
    # periods (3,4) and (4,5) go; (2,3) and (5,6) stay
    assert steps.host_busy_pct(ctx) == pytest.approx(3.5, abs=1e-6)
    assert ctx.said[-1]["periods_s"] == pytest.approx(0.4)
    for r in log:
        r["chained"] = False
    assert steps.host_busy_pct(ctx) is None  # a sync engine has no pipeline


def test_rows_and_useful_tokens(ctx):
    # participants 1 + 3 + 3 + 3 + 2 over 5 dispatches; 72 of 96 tokens kept
    assert steps.rows_per_dispatch(ctx) == pytest.approx(12 / 5)
    assert steps.useful_tok_pct(ctx) == pytest.approx(75.0)
    said = ctx.said[-1]
    assert (said["emitted"], said["computed"], said["mixed"]) == (72, 96, 2)
    # the identity the chip run is held to
    assert (steps.rows_per_dispatch(ctx) * 8 * steps.useful_tok_pct(ctx)
            / 100) == pytest.approx(said["tokens_per_dispatch"])


def test_ttft_parts_sum_to_the_mean(ctx):
    parts = []
    for part in range(3):
        ctx.args = {"part": part}
        parts.append(steps.ttft_part_ms(ctx))
    # request 10: due 0.05, admitted 0.10, graduates in block 3 (jitted
    # call entered at 0.306), first token 0.7; request 11: due 0.30,
    # admitted 0.302, same block
    assert parts == pytest.approx([(50 + 2) / 2, (206 + 4) / 2,
                                   (394 + 394) / 2], abs=1e-3)
    said = [s for s in ctx.said if s.get("metric") == "ttft.parts"]
    assert len(said) == 1  # worked out once for the three metrics
    assert said[0]["joined"] == said[0]["requests"] == 2
    assert said[0]["ttft_mean_ms"] == pytest.approx((650 + 400) / 2)
    assert abs(said[0]["residual_ms"]) < 1e-6


def test_a_failed_request_shows_as_the_residual(ctx):
    ctx.result["requests"][2]["t_done"] = None
    ctx.args = {"part": 0}
    assert steps.ttft_part_ms(ctx) == pytest.approx(50)
    said = ctx.said[-1]
    assert said["joined"] == 1 and said["requests"] == 2
    assert said["residual_ms"] == pytest.approx((650 + 60e3) / 2 - 650)


@pytest.mark.parametrize("key, off", [("t_admit", 1e-3), ("n_prompt", 1),
                                      ("t_first", 2e-3), ("t_first", -1e-3)])
def test_a_finished_request_that_does_not_join_refuses_the_split(ctx, key,
                                                                 off):
    """Its admit stamp or prompt length is in no record, or its first token
    was stamped outside the emit phase of the dispatch the log names."""
    ctx.result["requests"][1][key] += off
    ctx.args = {"part": 1}
    assert steps.ttft_part_ms(ctx) is None
    said = ctx.said[-1]
    assert "refused" in said and (said["finished"], said["joined"]) == (2, 1)


def test_a_cold_dispatch_is_left_out_of_the_window(ctx):
    from paddle_tpu.observability import tracing

    log = tracing.step_records()
    log[5]["cold"] = True  # block 4 compiled
    assert [r["seq"] for r in steps._window(ctx, steps._log(ctx))] == [
        102, 103, 105, 106]
    # and breaks the chain of periods: (2,3) and (5,6) stay
    assert steps.host_busy_pct(ctx) == pytest.approx(3.5, abs=1e-6)
    assert ctx.said[-1]["periods_s"] == pytest.approx(0.4)


def test_inter_token_gaps(ctx):
    # in the window every stream gets a block each 0.2 s; request 10's and
    # 11's first emits start their streams and give no gap
    ctx.args = {"q": 0.9}
    assert steps.itl_gap_ms(ctx) == pytest.approx(200.0)


def _ragged_least(rows):
    """By hand, for CFG and PEAK: one layer's call."""
    pairs = sum(q * kv - q * (q - 1) // 2 for _, _, q, kv in rows)
    fl = 4 * 4 * 8 * pairs
    by = (2 * 2 * 8 * 2 * sum(r[3] for r in rows)
          + 2 * 4 * 8 * 2 * sum(r[2] for r in rows))
    return max(fl / 1e9, by / 1e9), ("compute" if fl >= by else "memory")


def test_ragged_roofline_share(ctx):
    ctx.args = {"kernel": "ragged_paged_attention"}
    # whole mixed runs in the traced window: blocks 2 and 3 (block 1 is cut
    # by its start); two layers each; kernel time 2 x 1 ms + 2 x 2 ms
    l2, b2 = _ragged_least(ROWS[2][1])
    l3, b3 = _ragged_least(ROWS[3][1])
    assert (b2, b3) == ("compute", "compute")
    assert steps.ragged_attn_roofline(ctx) == pytest.approx(
        100 * 2 * (l2 + l3) / 0.006)
    roof = [s for s in ctx.said if s["phase"] == "roofline"][-1]
    assert roof["runs"] == 2 and roof["kernel_s"] == pytest.approx(0.006)
    skew = [s for s in ctx.said if "skew_ready_minus_run_end_ms" in s][-1]
    assert skew["matched_runs"] == 3
    assert skew["skew_ready_minus_run_end_ms"] == pytest.approx(0.5)
    # the device idles for 0.5 ms before blocks 2, 3 and 5 while the host
    # waits in the readback, and for 3.5 ms before block 4, most of which
    # the host spends in block 3's emit loop
    idle = [s for s in ctx.said if s.get("metric") == "idle_by_phase"][-1]
    assert idle["idle_s"] == pytest.approx({"serve.decode.sync": 0.0015,
                                            "serve.emit": 0.0035})


def test_a_run_cut_by_the_end_of_the_device_trace_is_left_out(ctx):
    """The device's trace stops 10 ms before the annotation: block 5's run
    then ends inside the window though it is not whole."""
    ctx.trace = _trace(device_stops_at=0.94)
    assert [m[2] < ctx.trace.window[1] for m in ctx.trace.modules()][-1]
    ctx.args = {"kernel": "%paged_attention"}
    assert steps.paged_attn_roofline(ctx) is not None
    assert ctx.said[-1]["runs"] == 3 and ctx.said[-1]["expected_calls"] == 44
    assert ctx.said[-2]["skew_max_ms"] == pytest.approx(0.5)


def test_memory_bound_calls_are_said(ctx):
    ctx.args = {"kernel": "ragged_paged_attention"}
    ctx.peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert steps.ragged_attn_roofline(ctx) is not None
    roof = [s for s in ctx.said if s["phase"] == "roofline"][-1]
    assert set(roof["least_s_by_bound"]) == {"memory"}


def test_the_mapping_is_refused_when_the_windows_disagree(ctx):
    ctx.args = {"kernel": "ragged_paged_attention"}
    ctx.tracer.t_off += 0.002
    assert steps.ragged_attn_roofline(ctx) is None
    assert "refused" in ctx.said[-1] and ctx.said[-1]["apart_ns"] < -1e6


def test_a_run_that_ends_at_another_kind_of_record_is_refused(ctx):
    ctx.args = {"kernel": "ragged_paged_attention"}
    ctx.tracer.t_on += 0.2   # the same length, a fifth of a second off:
    ctx.tracer.t_off += 0.2  # every run now ends at the record before
    assert steps.ragged_attn_roofline(ctx) is None
    assert any("refused" in s for s in ctx.said)


def test_paged_roofline_share(ctx):
    ctx.args = {"kernel": "%paged_attention"}
    # blocks 2, 3 (7 scan steps after the ragged pass) and 4 (8 steps), two
    # layers: 44 calls of 0.1 ms; a row outside the scan reads one token
    per_tok, per_q = 2 * 2 * 8 * 2, 2 * 4 * 8 * 2
    least = 0
    for n, first in ((2, 1), (3, 1), (4, 0)):
        ext = [kv for _, role, _, kv in ROWS[n][1] if role in "dg"]
        for s in range(first, 8):
            least += 2 * (per_tok * (sum(kv + s for kv in ext) + 4 - len(ext))
                          + per_q * 4) / 1e9
    assert steps.paged_attn_roofline(ctx) == pytest.approx(
        100 * least / (44 * 1e-4))
    assert ctx.said[-1]["expected_calls"] == 44
    ctx.trace = _trace(drop_paged=1)
    del ctx.traced_runs  # worked out once a run: a new trace, a new run
    assert steps.paged_attn_roofline(ctx) is None
    assert (ctx.said[-1]["kernel_events"], ctx.said[-1]["expected_calls"]) \
        == (43, 44)


def test_none_without_a_device_plane_or_a_log(ctx, monkeypatch):
    from paddle_tpu.observability import tracing

    ctx.trace = None
    ctx.args = {"kernel": "x"}
    assert steps.ragged_attn_roofline(ctx) is None
    assert steps.paged_attn_roofline(ctx) is None
    assert steps.host_busy_pct(ctx) is not None  # needs no trace
    # the parent of the PR that added the log has no step_records
    monkeypatch.delattr(tracing, "step_records")
    ctx.trace = _trace()
    del ctx.traced_runs
    for reader, args in ((steps.host_busy_pct, {}),
                         (steps.rows_per_dispatch, {}),
                         (steps.useful_tok_pct, {}),
                         (steps.ttft_part_ms, {"part": 0}),
                         (steps.itl_gap_ms, {"q": 0.9}),
                         (steps.ragged_attn_roofline, {"kernel": "x"}),
                         (steps.paged_attn_roofline, {"kernel": "x"})):
        ctx.args = args
        assert reader(ctx) is None
    ctx.result = {"kind": "train"}
    assert steps.rows_per_dispatch(ctx) is None


def test_the_rehearsal_prints_what_needs_no_trace():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py")]
    r = subprocess.run(run + ["--check"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0 and "0 fault(s)" in r.stdout, r.stdout + r.stderr
    r = subprocess.run(run + ["--workload", CELL, "--seed", "3000000019",
                              "--seconds", "4", "--trace", "0", "--rehearse"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = [json.loads(x) for x in r.stdout.splitlines()
            if x.startswith('{"phase": "metrics"')][0]
    got = line["per_layer"]
    for name in ("engine.host_busy_pct", "engine.rows_per_dispatch",
                 "engine.useful_tok_pct", "ttft.queue_mean_ms",
                 "ttft.prefill_mean_ms", "ttft.first_block_mean_ms"):
        assert name in got, (name, sorted(got))
    assert "ragged_attn_roofline" not in got  # the CPU has no device plane
    parts = sum(got[f"ttft.{p}_mean_ms"]["value"]
                for p in ("queue", "prefill", "first_block"))
    assert parts == pytest.approx(line["end_to_end"]["ttft_mean_ms"]["value"],
                                  abs=1e-6)
    assert 0 < got["engine.useful_tok_pct"]["value"] <= 100
