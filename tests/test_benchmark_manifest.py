"""The benchmark's manifest and its newest cell, guarded by tier-1:
`benchmarks/run.py --check` (BENCHMARK.json against the contract's limits
and against every file it names) and a CPU rehearsal of the training cell
and of each cell of a family other than Llama's (latent attention and routed
experts; sparse and linear attention; state-space, window and
cross-attention layers over one shared K/V pool) through the harness's own
entry point (tiny widths, 3 s
window; it prints no result line and measures nothing). The harness's
own unit tests stay in benchmarks/tests (run by hand). The cell's runner
pins one arrival schedule for every seed: that is guarded here too."""
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)


def test_manifest_checks_clean():
    done = _run("--check", timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert "0 fault(s), 5 cell(s)" in done.stdout


#: the set-up log's metrics (ISSUE 37): on the metrics line of every run
SETUP = ("setup.import_s", "setup.build_s", "setup.trace_lower_s",
         "setup.backend_compile_s", "setup.cache_hit_pct",
         "setup.engine_warm_s", "setup.unplaced_pct")

# cell -> what has to reach its metrics line: the family's counters, the
# numbers of the comparison that decides `correct`, and the set-up log's seven
REHEARSED = {
    # the training cell has no engine: six of the seven (10 s in the sandbox)
    "mistral7b-pretrain-4k": (
        "train.mfu_pct", "reference_loss",
        *(name for name in SETUP if name != "setup.engine_warm_s")),
    "kimi-k2.7-code-agent-steady": (
        "moe.experts_hit_pct", "moe.max_load_ratio", "latent_pool.used_pct",
        "serve.mfu_pct", "full_forward_rel_rms", "far_share", *SETUP),
    "minicpm-sala-longdoc-steady": (
        "sparse.kept_pct", "state_slots.used_pct", "sala.serve_mfu_pct",
        "full_forward_rel_rms", "far_share", "blocks_selected_alike",
        "lightning-xla", "sparse-prefill-xla", "sparse-decode-xla", *SETUP),
    "phi4flash-reasoning-steady": (
        "swa.keys_visited_pct", "yoco.tail_tok_pct",
        "phi4flash.serve_mfu_pct", "full_forward_rel_rms", "far_share",
        "mismatch_share", "first_state_rel_rms", "ragged-kernel-interpret",
        "ssm-xla", *SETUP),
}


@pytest.mark.parametrize("cell", sorted(REHEARSED))
def test_cell_rehearses(cell, tmp_path):
    done = _run("--workload", cell, "--seconds", "3", "--trace", "0",
                "--rehearse", "--seed", "3000000019", "--out", str(tmp_path),
                timeout=600)
    assert done.returncode == 0, (done.stdout[-3000:], done.stderr[-3000:])
    assert "rehearsal passed" in done.stdout
    for name in REHEARSED[cell]:
        assert name in done.stdout, name
    for name in set(SETUP) - set(REHEARSED[cell]):
        assert name not in done.stdout, name


def test_pinned_schedule_is_one_order_for_every_seed():
    """`serve_pinned_schedule`: the due times and lengths are the ones the
    harness's generator draws for the traffic file's `schedule_seed`, for
    any seed (the driver's exceed 2**31); the token ids are the seed's."""
    sys.path.insert(0, ROOT)
    try:
        from benchmarks import traffic
        from benchmarks.runners.serve_pinned_schedule import pinned_open_loop
    finally:
        sys.path.remove(ROOT)
    params = traffic.sized(traffic.load("agent-code-steady"), False)
    vocab = 20480
    a, b, again = (pinned_open_loop(params, seed, 50, vocab)
                   for seed in (7, 2 ** 31 + 77, 7))
    drawn = traffic.open_loop(params, params["schedule_seed"], 50, vocab)

    def shape(reqs):
        return [(r["due"], len(r["prompt"]), r["max_new"], r["measured"])
                for r in reqs]

    assert shape(a) == shape(b) == shape(drawn)
    assert sum(r["measured"] for r in a) == 60
    assert all(np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, again))
    assert any(not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, b))
    assert all(1 <= r["prompt"].min() and r["prompt"].max() < vocab
               and r["prompt"].dtype == np.int32 for r in a)
    # another order than the seed's own, which serve_openloop would run
    assert shape(a) != shape(traffic.open_loop(params, 7, 50, vocab))
