"""Run by hand: `pytest benchmarks/tests -q` (tier-1 collects tests/ only).

The yardstick's own checks: the trace reducer on a recorded trace, the
traffic generator as a pure function of seed and file, the percentile, the
manifest, and end-to-end rehearsals of both runners on the CPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import traffic  # noqa: E402

RUN = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py")]
XPLANE = os.path.join(ROOT, "xprof_traces", "tpu", "20260731T043440",
                      "plugins", "profile", "2026_07_31_04_34_44",
                      "vm.xplane.pb")


def _run(*args, devices=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if devices:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(RUN + list(args), cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)


def test_trace_reducer_on_the_recorded_trace():
    """The planner's four numbers for plane /device:TPU:0, line XLA Ops,
    15,505 events (ISSUE 24): sum 2,530.80 ms, union 2,322.76 ms,
    first-to-last span 2,324.13 ms; XLA Modules 2,323.40 ms."""
    if not os.path.exists(XPLANE):
        pytest.skip("the recorded trace is not in this checkout")
    from benchmarks.readers.trace import Trace, union_ns

    t = Trace(XPLANE)
    assert len(t.ops()) == 15505
    assert round(t.sum_s() * 1e3, 2) == 2530.80
    assert round(t.busy_s() * 1e3, 2) == 2322.76
    assert round(t.span_s() * 1e3, 2) == 2324.13
    assert t.busy_s() <= t.span_s() <= t.sum_s()
    mods = union_ns([(s, e) for _, s, e in t.modules()]) / 1e6
    # the modules line is clipped to the window (here first to last op)
    assert 2323.40 - 0.1 < mods <= 2323.40
    b = t.breakdown()
    assert len(b["device_ops"]) == 8 and b["device_ops"][0][1] > 0
    assert all(op.split(" ")[-1] not in ("while", "conditional", "call")
               for op, _ in b["device_ops"])


def test_union_of_intervals():
    from benchmarks.readers.trace import merged, union_ns

    iv = [(0, 10), (5, 12), (20, 30), (22, 25), (30, 31)]
    assert union_ns(iv) == 23
    assert merged(iv) == [[0, 12], [20, 31]]
    assert union_ns([]) == 0


def test_instruction_names():
    from benchmarks.readers.trace import instr

    name = ("%fusion.16 = (u32[1]{0:T(128)}, u32[1]{0:T(128)}) "
            "fusion(u32[2]{0:T(128)} %key.1), kind=kLoop")
    assert instr(name) == ("%fusion.16", "u32[1]", "fusion")
    assert instr("%while.7 = (s32[]{:T(128)}) while((s32[]) %t), body=%b")[2] \
        == "while"
    assert instr("bare_name") == ("bare_name", "", "")


def test_traffic_is_a_pure_function_of_seed_and_file():
    p = traffic.sized(traffic.load("chat-steady"), False)
    a = traffic.open_loop(p, 3000000019, 40, 102400)
    b = traffic.open_loop(p, 3000000019, 40, 102400)
    c = traffic.open_loop(p, 7, 40, 102400)
    assert [x["due"] for x in a] == [x["due"] for x in b]
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    assert [x["due"] for x in a] != [x["due"] for x in c]
    # another seed: the window holds the same multiset of lengths and the
    # same count, so the same work in another order
    m, mc = ([x for x in r if x["measured"]] for r in (a, c))
    assert sorted(x["max_new"] for x in m) == sorted(x["max_new"] for x in mc)
    assert (sorted(len(x["prompt"]) for x in m)
            == sorted(len(x["prompt"]) for x in mc))
    assert len(m) == round(p["rate_per_s"] * 40)
    assert all(0 <= x["due"] < 40 for x in m)
    warm = [x for x in a if not x["measured"]]
    assert all(-p["warm_in_s"] <= x["due"] < 0 for x in warm)
    # the warm-in repeats the window's tail, one window earlier
    tail = [x for x in m if x["due"] >= 40 - p["warm_in_s"]]
    assert [(round(x["due"] + 40, 9), len(x["prompt"]), x["max_new"])
            for x in warm] == [(round(x["due"], 9), len(x["prompt"]),
                                x["max_new"]) for x in tail]
    assert not any(np.array_equal(x["prompt"], y["prompt"])
                   for x, y in zip(warm, tail))
    assert [x["due"] for x in a] == sorted(x["due"] for x in a)
    lo, hi = p["prompt"]["min"], p["prompt"]["max"]
    assert all(lo <= len(x["prompt"]) <= hi for x in a)
    assert all(len(x["prompt"]) + x["max_new"] < 2048 for x in a)
    med = sorted(len(x["prompt"]) for x in m)[len(m) // 2]
    assert abs(med - p["prompt"]["median"]) <= 8


def test_bursts_keep_the_mean_rate():
    p = dict(traffic.sized(traffic.load("chat-steady"), False),
             bursts={"period_s": 8, "on_s": 2, "factor": 3}, warm_in_s=0,
             rate_per_s=20)
    r = traffic.open_loop(p, 1, 40, 1000)
    assert len(r) == 800
    on = sum(1 for x in r if x["due"] % 8 < 2)
    assert 0.46 < on / len(r) < 0.54  # 3x the rate in a quarter of the time


def test_shared_prefixes():
    p = dict(traffic.sized(traffic.load("chat-steady"), False),
             share={"group": 4, "fraction": 0.5}, warm_in_s=0)
    r = traffic.open_loop(p, 1, 10, 1000)
    n = int(min(len(x["prompt"]) for x in r[:4]) * 0.5)
    assert n > 0
    assert all(np.array_equal(x["prompt"][:n], r[0]["prompt"][:n])
               for x in r[:4])


def test_percentile_and_backlog():
    assert traffic.percentile([], 0.9) is None
    assert traffic.percentile([5], 0.9) == 5
    assert traffic.percentile(list(range(101)), 0.9) == 90
    assert traffic.percentile([3, 1, 2], 0.5) == 2
    recs = [{"due": 0.0, "t_done": 1.0}, {"due": 0.5, "t_done": None},
            {"due": 2.0, "t_done": 3.0}]
    assert traffic.backlog(recs, 0.7) == 2
    assert traffic.backlog(recs, 1.5) == 1


def test_flops_from_shapes():
    from benchmarks import flops, model

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mistral-7b-v0.3.json")) as f:
        cfg = model.load_config(json.load(f))
    # 218.1 M a layer + 134.2 M head (ISSUE 24's count, less the embedding)
    assert round(flops.matmul_params(cfg) / 1e6, 1) == round(
        4 * 218.1 + 134.2, 1)
    assert round(flops.total_params(cfg) / 1e9, 2) == 1.14
    full = dict(cfg, num_hidden_layers=1)
    # causal fwd+bwd of one layer at batch 1: 6 * S^2 * h / ... = 3 matmul
    # pairs of 2*S*S*h flops, halved
    assert flops.attention_flops(full, 1, 4096) == 6 * 2 * 4096 ** 2 * 4096 / 2
    t, bound = flops.roofline_seconds(
        flops.attention_flops(full, 2, 4096), flops.attention_bytes(full, 2, 4096),
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and 0.004 < t < 0.005


def test_manifest_check_passes():
    r = _run("--check")
    assert r.returncode == 0, r.stdout + r.stderr


def test_no_tpu_no_result_line():
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    r = _run("--workload", man["workloads"][0]["name"], "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert '"correct"' not in r.stdout


@pytest.mark.parametrize("cell,seconds", [("mistral7b-pretrain-4k", "2"),
                                          ("deepseek7b-chat-steady", "4")])
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_on_the_cpu(cell, seconds, trace):
    r = _run("--workload", cell, "--seed", "3000000019", "--seconds", seconds,
             "--trace", trace, "--rehearse")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "rehearsal passed" in r.stdout
    assert '"correct"' not in r.stdout  # a rehearsal prints no result line


def test_mesh_rehearsal_on_four_virtual_devices(tmp_path):
    """The four-chip cell's path (DistributedTrainStep on mp2 x sharding2),
    from a workload file of its own if the manifest has one, else from the
    one-chip training cell's file with a mesh added (files are data)."""
    name = "mistral7b-pretrain-4k-mp2z2"
    if not os.path.exists(os.path.join(ROOT, "benchmarks", "workloads",
                                       name + ".json")):
        pytest.skip("the four-chip cell is not in this benchmark yet")
    r = _run("--workload", name, "--seed", "5", "--seconds", "2",
             "--trace", "0", "--rehearse", devices=4)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "rehearsal passed" in r.stdout


def test_collective_exposed_share():
    """A collective counts while nothing else runs: sync op 10-20 alone,
    async span 30-60 overlapped by compute 40-60 -> 10 + 10 of 100."""
    import types

    from benchmarks.readers import trace as tr

    t = types.SimpleNamespace(
        window_s=100 / 1e9,
        devices={"/device:TPU:0": {
            "XLA Ops": [
                ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0, 10),
                ("%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %x)", 10, 20),
                ("%all-gather-start.1 = (f32[8]{0}) all-gather-start(f32[4]{0} %y)", 30, 31),
                ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q)", 40, 60),
                ("%all-gather-done.1 = f32[8]{0} all-gather-done((f32[8]{0}) %s)", 60, 61),
            ],
            "Async XLA Ops": [
                ("%all-gather-start.1 = (f32[8]{0}) all-gather-start(f32[4]{0} %y)", 30, 60),
            ]}})
    ctx = types.SimpleNamespace(trace=t)
    assert round(tr.collective_exposed_pct(ctx), 6) == 20.0
    assert tr.collective_exposed_pct(types.SimpleNamespace(trace=None)) is None
