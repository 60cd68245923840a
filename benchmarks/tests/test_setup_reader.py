"""Run by hand: `pytest benchmarks/tests -q` (tier-1 collects tests/ only).

The readers of the program's set-up log (benchmarks/readers/setup.py) on a
hand-made log whose numbers can be worked out by hand. The process starts
at 1000 s on the monotonic clock and the window at 1030 s, 10 s of warm-in
before it:

    1000.0 - 1002.0  setup.import
    1003.0 - 1005.0  setup.build        1005.0 - 1006.0  setup.cast
    1006.0 - 1006.5  engine.init  >  1006.1 - 1006.4  engine.init.pools
    1006.5 - 1016.5  engine.warmup  >  1006.5 - 1016.0  engine.warmup.serve
        1007.0 - 1012.0  compile (trace 1, lower 0.5, backend 3, other 0.5)
        1012.0 - 1014.0  compile (trace 0.5, lower 0.25, backend 1)
        1016.0 - 1016.5  engine.warmup.scopes  >  1016.1 - 1016.4  lower
    1019.0 - 1019.5  frontend.start (another thread)
    1029.0 - 1032.0  a compile cut by the window's start

so 26 s are named (17 s of records, 10 s of warm-in that cover the cut
compile's second), 4 s are not, and the engine's self time is 11 s of phases
less the 7.3 s of compiles and re-lowering inside them."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.readers import setup  # noqa: E402

T0, T_WINDOW = 1000.0, 1030.0


def _rec(name, a, b, parent=None, tid=1, **counts):
    return {"name": name, "t0_ns": round(a * 1e9), "t1_ns": round(b * 1e9),
            "parent": parent, "tid": tid, **counts}


def _compile(a, b, key, cache, trace_s, lower_s, backend_s, other_s,
             name="compile", parent="engine.warmup.serve"):
    return _rec(name, a, b, parent, key=key, trigger="warmup",
                wall_s=b - a, trace_s=trace_s, lower_s=lower_s,
                backend_s=backend_s, other_s=other_s, cache=cache,
                retrieval_s=0.0)


LOG = [
    _rec("early", 990.0, 999.0),               # before the process's stamp
    _rec("setup.import", 1000.0, 1002.0),
    _rec("setup.build", 1003.0, 1005.0, params=291, init_calls=517),
    _rec("setup.cast", 1005.0, 1006.0, arrays=291),
    _rec("engine.init.pools", 1006.1, 1006.4, "engine.init"),
    _rec("engine.init", 1006.0, 1006.5, pool_bytes=1 << 30),
    _compile(1007.0, 1012.0, "serve.ragged[k8]", "hit", 1.0, 0.5, 3.0, 0.5),
    _compile(1012.0, 1014.0, "serve.decode_block[k8]", "miss", 0.5, 0.25,
             1.0, 0.25),
    _rec("engine.warmup.serve", 1006.5, 1016.0, "engine.warmup"),
    _compile(1016.1, 1016.4, "serve.ragged[k8]", "off", 0.1, 0.2, 0.0, 0.0,
             name="lower", parent="engine.warmup.scopes"),
    _rec("engine.warmup.scopes", 1016.0, 1016.5, "engine.warmup", scopes=40),
    _rec("engine.warmup", 1006.5, 1016.5, programs=2),
    _rec("frontend.start", 1019.0, 1019.5, tid=2, replicas=1),
    _compile(1029.0, 1032.0, "serve.late", "miss", 1.0, 1.0, 1.0, 0.0,
             parent=None),
    _rec("in.the.window", 1031.0, 1033.0),
]


@pytest.fixture
def ctx(monkeypatch):
    from paddle_tpu.observability import tracing

    monkeypatch.setattr(tracing, "setup_records", lambda n=None: list(LOG),
                        raising=False)
    said = []
    return types.SimpleNamespace(
        t_window=T_WINDOW, setup_s=T_WINDOW - T0, name=None, args={},
        traffic={"warm_in_s": 10}, result={"kind": "serve"}, said=said,
        say=lambda phase, **kw: said.append((phase, kw)))


def test_union_not_sum_over_nested_phases(ctx):
    # engine.init + its pools, warm-up + its serve and scopes: 11 s of
    # phases (0.5 + 10 + 0.5), not the 21.3 s their lengths add up to
    assert setup.engine_warm_s(ctx) == pytest.approx(11.0 - 7.3)
    assert setup.build_s(ctx) == pytest.approx(3.0)
    assert setup.import_s(ctx) == pytest.approx(2.0)


def test_compile_parts_are_summed_over_whole_events(ctx):
    # the compile cut by the window's start is left out of all three
    assert setup.trace_lower_s(ctx) == pytest.approx(1.5 + 0.75 + 0.3)
    assert setup.backend_compile_s(ctx) == pytest.approx(4.0)
    assert setup.cache_hit_pct(ctx) == pytest.approx(50.0)   # "off" is none


def test_clipping_at_the_window_and_the_unplaced_share(ctx):
    # named: import 2, build + cast 3, engine 10.5, frontend 0.5, and the
    # warm-in's 10 s, which cover the late compile's second before the window
    assert setup.unplaced_pct(ctx) == pytest.approx(100 * (30 - 26) / 30)
    (phase, line), = ctx.said                  # said once for all seven
    assert phase == "setup"
    assert line["placed_s"] == pytest.approx(17.0)
    assert line["unplaced_s"] == pytest.approx(4.0)
    assert line["first_run_s"] == pytest.approx(0.75)
    names = [p["name"] for p in line["phases"]]
    assert "early" not in names and "in.the.window" not in names
    assert names[:2] == ["setup.import", "setup.build"]
    assert line["phases"][0]["at_s"] == 0 and line["phases"][0]["s"] == 2
    late = [c for c in line["compiles"] if c["key"] == "serve.late"][0]
    assert late["s"] == pytest.approx(1.0)     # clipped at the window
    first = line["compiles"][0]
    assert (first["key"], first["trigger"], first["cache"], first["under"],
            first["trace_s"], first["lower_s"], first["backend_s"],
            first["other_s"]) == ("serve.ragged[k8]", "warmup", "hit",
                                  "engine.warmup.serve", 1.0, 0.5, 3.0, 0.5)
    assert len(line["compiles"]) == 4
    # the four unplaced seconds, by where they lie
    assert [(g["after"], g["before"], round(g["s"], 3))
            for g in line["gaps"]] == [
        ("setup.import", "setup.build", 1.0),
        ("engine.warmup", "frontend.start", 2.5),
        ("frontend.start", "compile", 0.5)]


def test_a_training_cell_reads_six(ctx):
    global LOG
    ctx.result = {"kind": "train"}
    ctx.traffic = {"seq": 4096}
    keep, LOG = LOG, [r for r in LOG if not r["name"].startswith(
        ("engine.", "frontend."))]
    try:
        assert setup.engine_warm_s(ctx) is None
        got = [f(ctx) for f in (setup.import_s, setup.build_s,
                                setup.trace_lower_s, setup.backend_compile_s,
                                setup.cache_hit_pct, setup.unplaced_pct)]
    finally:
        LOG = keep
    assert all(v is not None for v in got)
    # no warm-in: import 2, build + cast 3, compiles 7 + 0.3, the late one 1
    assert got[-1] == pytest.approx(100 * (30 - 13.3) / 30)


def test_none_without_the_log(ctx, monkeypatch):
    from paddle_tpu.observability import tracing

    monkeypatch.delattr(tracing, "setup_records")
    for read in (setup.import_s, setup.build_s, setup.trace_lower_s,
                 setup.backend_compile_s, setup.cache_hit_pct,
                 setup.engine_warm_s, setup.unplaced_pct):
        assert read(ctx) is None
    assert ctx.said == []
