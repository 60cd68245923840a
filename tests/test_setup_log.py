"""The always-on set-up log (ISSUE 37): one record per phase of a process's
start in ``tracing.setups`` — import, parameter creation, the cast, the
engine's pools, warm-up, the train step's build — and one per compile the
ledger saw, split into what jax says of its parts (trace, lowering, backend
compile or cache read) and the rest."""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.inference.continuous import ContinuousBatchingEngine
from paddle_tpu.jit_api import TrainStep
from paddle_tpu.models.llama import (LlamaForCausalLM,
                                     LlamaPretrainingCriterion, llama_tiny)
from paddle_tpu.observability import compilemem, registry, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = 3
#: per Llama layer: seven projections (each initialised by Linear and again
#: by `_mk_linear`) and two norms; besides, the embedding (initialised
#: twice), the final norm and the head (twice)
PARAMS = 9 * LAYERS + 3
INIT_CALLS = PARAMS + 7 * LAYERS + 2


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("PADDLE_TELEMETRY", raising=False)
    monkeypatch.delenv("PADDLE_TELEMETRY_DIR", raising=False)
    tracing.disable()
    tracing.clear_sinks()
    tracing.clear()
    yield
    tracing.disable()
    tracing.clear_sinks()
    tracing.clear()


def _named(name, records=None):
    return [r for r in (tracing.setup_records() if records is None
                        else records) if r["name"] == name]


def _tiny(**kw):
    paddle.seed(11)
    return LlamaForCausalLM(llama_tiny(num_hidden_layers=LAYERS,
                                       max_position_embeddings=128, **kw))


# ---- the log itself ---------------------------------------------------------

def test_on_with_telemetry_disabled():
    assert not tracing.enabled()
    with tracing.setup_phase("t.phase", rows=3) as ph:
        ph.counts["pages"] = 7
    (rec,) = tracing.setup_records()
    assert (rec["name"], rec["rows"], rec["pages"]) == ("t.phase", 3, 7)
    assert rec["parent"] is None and rec["tid"] >= 1
    assert tracing.last_spans() == []          # no fan-out while disabled


def test_fans_out_as_spans_when_enabled():
    tracing.enable()
    hist = registry.histogram("span.t.fan_s")
    n0 = hist.count
    with tracing.setup_phase("t.fan", rows=2):
        tracing.setup_record("t.fan.child", time.monotonic_ns() - 1000,
                             time.monotonic_ns(), pages=1)
    spans = {s["name"]: s for s in tracing.last_spans()}
    assert spans["t.fan"]["attrs"] == {"rows": 2}
    assert spans["t.fan.child"]["parent"] == "t.fan"
    assert spans["t.fan.child"]["attrs"] == {"pages": 1}
    assert hist.count == n0 + 1
    rec = _named("t.fan")[0]
    assert spans["t.fan"]["ts_us"] == rec["t0_ns"] / 1e3
    assert spans["t.fan"]["dur_us"] == (rec["t1_ns"] - rec["t0_ns"]) / 1e3


def test_stamps_are_monotonic_ns_and_nest():
    before = time.monotonic_ns()
    with tracing.setup_phase("t.outer") as outer:
        with tracing.setup_phase("t.inner"):
            time.sleep(0.002)
        tracing.setup_record("t.stamped", outer.t0_ns, time.monotonic_ns())
    after = time.monotonic_ns()
    inner, stamped, out = (_named(n)[0]
                           for n in ("t.inner", "t.stamped", "t.outer"))
    assert before <= out["t0_ns"] <= inner["t0_ns"]
    assert inner["t1_ns"] - inner["t0_ns"] >= 2_000_000
    assert inner["t1_ns"] <= out["t1_ns"] <= after
    assert (inner["parent"], stamped["parent"], out["parent"]) == (
        "t.outer", "t.outer", None)
    assert (outer.t0_ns, outer.t1_ns) == (out["t0_ns"], out["t1_ns"])
    # the step log's clock: a step record stamped now compares directly
    rec = tracing.new_step(t_now=time.monotonic_ns())
    assert rec["t_now"] >= out["t1_ns"]


def test_a_backdated_phase_starts_where_it_is_told():
    t0 = time.monotonic_ns() - 5_000_000
    with tracing.setup_phase("t.late", t0_ns=t0):
        pass
    assert _named("t.late")[0]["t0_ns"] == t0


def test_ring_is_bounded():
    for i in range(tracing.setups.maxlen + 40):
        tracing.setup_record("t.flood", i, i + 1)
    recs = tracing.setup_records()
    assert len(recs) == tracing.setups.maxlen
    assert recs[-1]["t0_ns"] == tracing.setups.maxlen + 39
    assert len(tracing.setup_records(5)) == 5


def test_a_burst_is_one_record_closed_by_the_next_phase():
    for i in range(4):
        with tracing.setup_phase("t.burst", burst=True, n=1, flag=False):
            with tracing.setup_phase("t.burst", burst=True, inner=2):
                pass
    with tracing.setup_phase("t.after"):
        pass
    for _ in range(2):
        with tracing.setup_phase("t.burst", burst=True, n=1):
            pass
    first, second = _named("t.burst")          # reading closes the second
    assert (first["n"], first["inner"], first["flag"]) == (4, 8, False)
    assert second["n"] == 2 and "inner" not in second
    assert first["parent"] is None
    assert first["t1_ns"] <= _named("t.after")[0]["t0_ns"] <= second["t0_ns"]


def test_setup_count_lands_on_the_innermost_open_phase():
    tracing.setup_count(lost=1)                # nothing open: nothing kept
    with tracing.setup_phase("t.a"):
        with tracing.setup_phase("t.b"):
            tracing.setup_count(x=1.5)
            tracing.setup_count(x=1)
        tracing.setup_count(y=1)
    assert _named("t.b")[0]["x"] == 2.5
    assert _named("t.a")[0]["y"] == 1 and "x" not in _named("t.a")[0]
    assert not any("lost" in r for r in tracing.setup_records())


def test_import_leaves_a_record():
    code = ("import json, time; t0 = time.monotonic_ns(); "
            "import paddle_tpu; "
            "from paddle_tpu.observability import tracing; "
            "print(json.dumps([t0, time.monotonic_ns(), "
            "tracing.setup_records()]))")
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    t0, t1, recs = json.loads(done.stdout.strip().splitlines()[-1])
    assert [r["name"] for r in recs] == ["setup.import"]
    assert t0 <= recs[0]["t0_ns"] < recs[0]["t1_ns"] <= t1
    assert recs[0]["t1_ns"] - recs[0]["t0_ns"] > 0.5 * (t1 - t0)


# ---- build and cast ---------------------------------------------------------

def test_build_is_one_record_and_cast_another():
    model = _tiny()
    params = list(model.parameters())
    f32 = sum(p._data.size * 4 for p in params)
    model.bfloat16()
    (build,) = _named("setup.build")
    (cast,) = _named("setup.cast")
    assert len(params) == PARAMS
    assert (build["params"], build["init_calls"]) == (PARAMS, INIT_CALLS)
    assert build["bytes"] == f32
    assert 0 < build["init_self_s"] <= (build["t1_ns"] - build["t0_ns"]) / 1e9
    assert build["synced"] is False and cast["synced"] is False
    floats = PARAMS + sum(
        1 for b in model.buffers() if jnp.issubdtype(b.dtype, jnp.floating))
    assert cast["arrays"] == floats
    assert cast["bytes_in"] >= f32
    assert cast["bytes_out"] * 2 == cast["bytes_in"]
    assert build["t1_ns"] <= cast["t0_ns"]
    assert all(p.dtype == paddle.bfloat16 for p in model.parameters())


def test_eager_compiles_are_counted_on_the_open_phase():
    before = compilemem.ledger.counts()["events"]
    with tracing.setup_phase("t.eager"):
        jnp.arange(977.0).reshape(1, 977).sum().block_until_ready()
    rec = _named("t.eager")[0]
    assert rec["jit_backend_s"] > 0 and rec["jit_cache_requests"] >= 1
    assert compilemem.ledger.counts()["events"] == before   # no ledger event


# ---- the engine -------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """A tiny engine warmed once: (engine, the set-up records of its
    construction and warm-up, the warm-up histogram's sum before and
    after, the ledger's event count before and after)."""
    tracing.clear()
    model = _tiny()
    model.eval()
    hist = registry.histogram("serve.compile_warmup_s")
    sum0, events0 = hist.sum, compilemem.ledger.counts()["events"]
    eng = ContinuousBatchingEngine(model, max_seqs=4, page_size=16,
                                   max_len=128, prefill_chunk=32,
                                   decode_block=4)
    eng.warmup(buckets=[16])
    return (eng, tracing.setup_records(), hist.sum - sum0,
            compilemem.ledger.counts()["events"] - events0)


def test_engine_init_holds_its_pools(served):
    eng, recs, _, _ = served
    (init,) = _named("engine.init", recs)
    (pools,) = _named("engine.init.pools", recs)
    assert pools["parent"] == "engine.init"
    assert init["t0_ns"] <= pools["t0_ns"] <= pools["t1_ns"] <= init["t1_ns"]
    assert init["pool_bytes"] == eng.pool_bytes() > 0


def test_warmup_holds_a_compile_child_a_program(served):
    eng, recs, _, events = served
    (warm,) = _named("engine.warmup", recs)
    (serve,) = _named("engine.warmup.serve", recs)
    compiles = _named("compile", recs)
    assert serve["parent"] == "engine.warmup" and warm["synced"] is True
    assert warm["programs"] == events == len(compiles) == 2
    assert sorted(c["key"].split("[")[0] for c in compiles) == [
        "serve.decode_block", "serve.ragged"]
    for c in compiles:
        assert c["parent"] == "engine.warmup.serve"
        assert c["trigger"] == "warmup"
        assert serve["t0_ns"] <= c["t0_ns"] < c["t1_ns"] <= serve["t1_ns"]
        assert min(c["trace_s"], c["lower_s"], c["backend_s"]) > 0
        # each field is rounded to 1e-4
        assert (c["trace_s"] + c["lower_s"] + c["backend_s"]
                <= c["wall_s"] + 3e-4)
        assert c["other_s"] >= 0 and c["cache"] in ("off", "hit", "miss")
        assert abs((c["t1_ns"] - c["t0_ns"]) / 1e9 - c["wall_s"]) < 1e-3


def test_warmup_histogram_reads_the_phase_stamps(served):
    _, recs, observed, _ = served
    (warm,) = _named("engine.warmup", recs)
    assert observed == pytest.approx((warm["t1_ns"] - warm["t0_ns"]) / 1e9,
                                     abs=1e-9)


def test_ledger_events_carry_the_parts_and_count_as_before(served):
    eng, recs, _, _ = served
    events = {e["key"]: e for e in compilemem.ledger.events(64)}
    for c in _named("compile", recs):
        e = events[c["key"]]
        for k in ("t0_ns", "t1_ns", "trace_s", "lower_s", "backend_s",
                  "cache", "retrieval_s", "other_s", "wall_s", "trigger"):
            assert e[k] == c[k], k
    assert compilemem.ledger.report()["recent"][-1]["other_s"] >= 0
    # a warm serve compiles nothing: what `compiles_in_window` counts
    before = compilemem.ledger.counts()["events"]
    n_records = len(_named("compile"))
    eng.serve([np.ones(5, np.int32)], max_new_tokens=6)
    assert compilemem.ledger.counts()["events"] == before
    assert len(_named("compile")) == n_records


def test_frontend_start_ends_when_its_dispatcher_serves(served):
    from paddle_tpu.serving import ServingFrontend

    eng = served[0]
    t0 = time.monotonic_ns()
    with ServingFrontend([eng]) as fe:
        fe.submit(np.ones(3, np.int32), 2).result(timeout=120)
        t1 = time.monotonic_ns()
    (rec,) = _named("frontend.start")
    assert rec["replicas"] == 1 and rec["parent"] is None
    assert t0 <= rec["t0_ns"] <= rec["t1_ns"] <= t1


# ---- the compile ledger's parts ---------------------------------------------

def _program(x):
    return jnp.tanh(x @ x).sum()


def test_a_lower_leaves_a_record_and_no_event():
    fn = compilemem.ledgered_jit(_program, key="t.lowered")
    before = compilemem.ledger.counts()
    fn.lower(jax.ShapeDtypeStruct((8, 8), jnp.float32))
    assert compilemem.ledger.counts() == before
    (rec,) = _named("lower")
    assert rec["key"] == "t.lowered"
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0
    assert rec["backend_s"] == 0 and not _named("compile")


def test_nested_jits_are_timed_once():
    inner = jax.jit(lambda x: jnp.sin(x) * 2)

    def outer(x):
        for _ in range(20):
            x = inner(x + 1)
        return x

    fn = compilemem.ledgered_jit(outer, key="t.nested")
    fn(jnp.ones((4, 4)))
    (rec,) = _named("compile")
    assert rec["key"] == "t.nested"
    assert rec["trace_s"] + rec["lower_s"] + rec["backend_s"] <= (
        rec["wall_s"] + 3e-4)


def test_a_nested_compile_does_not_answer_for_the_event():
    """An eager op compiled while the event's program is traced is timed
    inside `trace_s`, and its cache request (never served: jax keeps no
    program that compiled in under a second) is not the event's."""
    pre = "/jax/core/compile/"
    trace, backend = pre + "jaxpr_trace_duration", pre + (
        "backend_compile_duration")
    cache = "/jax/compilation_cache/"
    compilemem._mon.bank = bank = compilemem._new_bank()
    try:
        compilemem._on_enter(trace, 0.0)
        compilemem._on_enter(backend, 0.0)         # the eager op's compile
        compilemem._on_event(cache + "compile_requests_use_cache")
        compilemem._on_duration(backend, 0.5)
        compilemem._on_duration(trace, 2.0)
        compilemem._on_enter(backend, 0.0)         # the program's own
        compilemem._on_event(cache + "compile_requests_use_cache")
        compilemem._on_event(cache + "cache_hits")
        compilemem._on_duration(cache + "cache_retrieval_time_sec", 0.3)
        compilemem._on_duration(backend, 0.4)
    finally:
        compilemem._mon.bank = None
    assert bank == {"trace_s": 2.0, "lower_s": 0.0, "backend_s": 0.4,
                    "retrieval_s": 0.3, "cache_requests": 1, "cache_hits": 1}


def test_second_build_hits_the_persistent_cache(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs,
           jax.config.jax_persistent_cache_min_entry_size_bytes)
    try:
        cc.reset_cache()
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        x = jnp.ones((16, 16))
        seen = []
        for _ in range(2):
            compilemem.ledger.reset()
            compilemem.ledgered_jit(_program, key="t.cached")(x)
            (event,) = compilemem.ledger.events()
            seen.append(event)
        if not os.listdir(tmp_path):
            pytest.skip("this backend wrote nothing to the persistent "
                        "compile cache: no hit to read")
        assert [e["cache"] for e in seen] == ["miss", "hit"]
        assert seen[0]["retrieval_s"] == 0
        # jax times the cache's read inside the backend compile
        assert 0 < seen[1]["retrieval_s"] <= seen[1]["backend_s"] + 1e-4
        assert [r["cache"] for r in _named("compile")] == ["miss", "hit"]
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was[1])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", was[2])
        cc.reset_cache()
        compilemem.ledger.reset()


def test_no_cache_directory_reads_off():
    assert not jax.config.jax_compilation_cache_dir
    compilemem.ledgered_jit(_program, key="t.off")(jnp.ones((12, 12)))
    assert _named("compile")[0]["cache"] == "off"


# ---- the train step ---------------------------------------------------------

def _train_step(model, cls=TrainStep, **kw):
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    crit = LlamaPretrainingCriterion()
    return cls(model, lambda out, labels: crit(out, labels), opt, **kw)


def test_train_step_build_runs_from_construction_to_the_first_call():
    model = _tiny()
    tracing.clear()
    t0 = time.monotonic_ns()
    step = _train_step(model)
    t_built = time.monotonic_ns()
    opt_bytes = compilemem.tree_nbytes(step.opt_state)
    x = paddle.to_tensor(np.ones((2, 16), np.int32))
    step(x, x)
    t1 = time.monotonic_ns()
    step(x, x)
    (build,) = _named("train.step.build")
    (opt,) = _named("train.opt_state")
    (comp,) = _named("compile")
    assert t0 <= build["t0_ns"] <= opt["t0_ns"] <= opt["t1_ns"] <= t_built
    assert t_built <= comp["t0_ns"] <= comp["t1_ns"] <= build["t1_ns"] <= t1
    assert opt["parent"] == comp["parent"] == "train.step.build"
    assert comp["key"] == "train.step" and build["synced"] is False
    assert opt["opt_state_bytes"] == opt_bytes > 0


def test_sharded_compile_build_has_one_instrument():
    from paddle_tpu.distributed import mesh as M
    from paddle_tpu.distributed.train_step import DistributedTrainStep

    model = _tiny()
    tracing.clear()
    tracing.enable()
    x = paddle.to_tensor(np.ones((4, 16), np.int32))
    with M.mesh_guard(M.build_mesh(dp=2)):
        step = _train_step(model, DistributedTrainStep)
        step(x, x)
    (build,) = _named("train.step.build")
    (cb,) = _named("train.step.compile_build")
    assert cb["parent"] == "train.step.build"
    assert build["t0_ns"] <= cb["t0_ns"] <= cb["t1_ns"] <= build["t1_ns"]
    assert any(c["key"] == "train.step" and c["parent"] == "train.step.build"
               for c in _named("compile"))
    spans = [s for s in tracing.last_spans(512)
             if s["name"] == "train.step.compile_build"]
    assert len(spans) == 1 and spans[0]["parent"] == "train.step.build"
