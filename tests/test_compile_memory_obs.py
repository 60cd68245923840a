"""Compile & HBM observability (ISSUE 8).

Tentpole coverage: the compile ledger is complete across the blessed
sites (TrainStep, run_steps multi-cache, the serving engine's program
dicts, warmup), the churn detector fires on a deliberately shape-unstable
loop and stays silent on bucketed shapes, the chaos-injected
RESOURCE_EXHAUSTED produces a complete ``telemetry/oom_report.json``,
``/compilez`` and ``/memz`` serve live data, the hang watchdog diagnoses
a rank wedged mid-compile, and the disabled-telemetry overhead stays
inside the PR-2 <1%-of-step bound.
"""
import json
import os
import time
import urllib.request
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.jit_api import TrainStep
from paddle_tpu.observability import compilemem as cm
from paddle_tpu.observability import tracing, watchdog
from paddle_tpu.observability.metrics import registry
from paddle_tpu.observability.statusz import StatusServer
from paddle_tpu.testing import chaos

import jax.numpy as jnp


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("PADDLE_TELEMETRY_DIR", raising=False)
    monkeypatch.delenv("PADDLE_HBM_CAPACITY_BYTES", raising=False)
    chaos.disarm()
    cm._reset_for_tests()
    registry.reset("compile.")
    registry.reset("device.")
    yield
    chaos.disarm()
    cm._reset_for_tests()
    registry.reset("compile.")
    registry.reset("device.")


def _tiny_engine(model, **kw):
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine

    kw.setdefault("max_seqs", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_len", 64)
    kw.setdefault("decode_block", 2)
    return ContinuousBatchingEngine(model, **kw)


@pytest.fixture(scope="module")
def tiny_model():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    paddle.seed(11)
    m = LlamaForCausalLM(llama_tiny(num_hidden_layers=2))
    m.eval()
    return m


def _make_step(in_f=4, out_f=2):
    paddle.seed(3)
    model = nn.Sequential(nn.Linear(in_f, 8), nn.Tanh(), nn.Linear(8, out_f))
    opt = optimizer.AdamW(learning_rate=0.01, parameters=model.parameters())
    loss_fn = lambda out, lab: ((out - lab) ** 2).mean()  # noqa: E731
    return TrainStep(model, loss_fn, opt)


def _t(a):
    return paddle.to_tensor(np.asarray(a, np.float32))


# ---------------------------------------------------------------------------
# ledgered_jit + CompileLedger unit behavior
# ---------------------------------------------------------------------------
class TestLedgeredJit:
    def test_compile_recorded_once_warm_silent(self):
        f = cm.ledgered_jit(lambda x: x + 1, key="t.one")
        f(jnp.ones(3))
        c1 = cm.ledger.counts()
        f(jnp.ones(3))
        f(jnp.ones(3))
        c2 = cm.ledger.counts()
        assert c1["events"] == 1
        assert c2 == c1, "warm calls must record nothing"
        rep = cm.ledger.report()
        assert rep["by_key"]["t.one"]["count"] == 1
        assert rep["by_key"]["t.one"]["triggers"] == {"cold": 1}

    def test_recompile_and_signature_capture(self):
        f = cm.ledgered_jit(lambda x: x * 2, key="t.re")
        f(jnp.ones(3))
        f(jnp.ones((2, 3)))
        rep = cm.ledger.report()
        e = rep["by_key"]["t.re"]
        assert e["count"] == 2 and e["signatures"] == 2
        assert e["triggers"] == {"cold": 1, "recompile": 1}
        assert "float32[2,3]" in e["last_signature"]
        assert cm.ledger.counts()["recompiles"] == 1

    def test_churn_alert_fires_on_shape_unstable_loop(self):
        f = cm.ledgered_jit(lambda x: x.sum(), key="t.churn")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            for n in range(1, 7):  # 6 distinct signatures, one key
                f(jnp.ones(n))
        c = cm.ledger.counts()
        assert c["churn_alerts"] >= 1
        assert any("compile churn" in str(x.message) for x in w)
        assert "t.churn" in cm.ledger.report()["churned"]

    def test_churn_silent_on_bucketed_keys(self):
        # bucketed variants carry their bucket in the KEY (the serving /
        # generate convention) — many programs, each compiled once
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            for n in (8, 16, 32, 64, 128):
                cm.ledgered_jit(lambda x: x.sum(), key=f"t.bucket[b{n}]")(
                    jnp.ones(n))
        assert cm.ledger.counts()["churn_alerts"] == 0
        assert not any("compile churn" in str(x.message) for x in w)
        assert cm.ledger.counts()["events"] == 5

    def test_trigger_scope_labels_warmup(self):
        f = cm.ledgered_jit(lambda x: x - 1, key="t.warm")
        with cm.ledger.trigger("warmup"):
            f(jnp.ones(2))
        assert cm.ledger.report()["by_key"]["t.warm"]["triggers"] == {
            "warmup": 1}

    def test_nested_trace_suppressed(self):
        inner = cm.ledgered_jit(lambda x: x + 1, key="t.inner")
        outer = cm.ledgered_jit(lambda x: inner(x) * 3, key="t.outer")
        outer(jnp.ones(2))
        rep = cm.ledger.report()
        assert "t.outer" in rep["by_key"]
        assert "t.inner" not in rep["by_key"], \
            "an inner jit traced inside an outer trace is the outer program"

    def test_error_during_trace_recorded_and_active_cleared(self):
        def boom(x):
            raise ValueError("trace-time failure")

        f = cm.ledgered_jit(boom, key="t.err")
        with pytest.raises(ValueError):
            f(jnp.ones(2))
        assert cm.ledger.active() == []
        recent = cm.ledger.events()
        assert recent and recent[-1]["key"] == "t.err"
        assert "ValueError" in recent[-1]["error"]
        # the ledger stays usable afterwards (depth bookkeeping intact)
        g = cm.ledgered_jit(lambda x: x, key="t.after_err")
        g(jnp.ones(2))
        assert cm.ledger.report()["by_key"]["t.after_err"]["count"] == 1

    def test_record_compile_bracket(self):
        with cm.record_compile("t.aot", trigger="aot"):
            pass
        e = cm.ledger.report()["by_key"]["t.aot"]
        assert e["count"] == 1 and e["triggers"] == {"aot": 1}

    def test_cache_size_gauge_and_warn_bound(self):
        old = cm.ledger.cache_warn_bound
        cm.ledger.cache_warn_bound = 3
        try:
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                cm.ledger.note_cache_size("t.cache", 2)
                g = registry.get("compile.cache_size",
                                 labels={"cache": "t.cache"})
                assert g is not None and g.value == 2
                assert not w
                cm.ledger.note_cache_size("t.cache", 5)
                assert any("program cache" in str(x.message) for x in w)
                # warned once, not per update
                cm.ledger.note_cache_size("t.cache", 6)
                assert sum("program cache" in str(x.message)
                           for x in w) == 1
        finally:
            cm.ledger.cache_warn_bound = old


# ---------------------------------------------------------------------------
# train-step ledger completeness + steady state
# ---------------------------------------------------------------------------
class TestTrainStepLedger:
    def test_train_step_compile_recorded_and_warm_zero_recompiles(self):
        step = _make_step()
        x, y = np.random.rand(8, 4), np.random.rand(8, 2)
        step(_t(x), _t(y))
        rep = cm.ledger.report()
        assert rep["by_key"]["train.step"]["count"] == 1
        mark = cm.ledger.counts()
        for _ in range(3):  # warm steps: the steady-state assertion
            step(_t(x), _t(y))
        assert cm.ledger.counts()["events"] == mark["events"], \
            "warm train steps must trigger zero recompiles"

    def test_train_step_shape_drift_is_churn(self):
        step = _make_step()
        y = np.random.rand(4, 2)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            for b in (4, 5, 6, 7, 8):  # deliberately shape-unstable loop
                step(_t(np.random.rand(b, 4)),
                     _t(np.random.rand(b, 2)))
        e = cm.ledger.report()["by_key"]["train.step"]
        assert e["count"] == 5 and e["signatures"] == 5
        assert cm.ledger.counts()["churn_alerts"] >= 1
        assert any("train.step" in str(x.message) for x in w
                   if "compile churn" in str(x.message))

    def test_run_steps_multi_cache_growth_tracked(self):
        old = cm.ledger.cache_warn_bound
        cm.ledger.cache_warn_bound = 2
        try:
            step = _make_step()
            x, y = np.random.rand(8, 4), np.random.rand(8, 2)
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                for n in (1, 2, 3):  # n-key growth path
                    step.run_steps(_t(x), _t(y), n=n)
                g = registry.get("compile.cache_size",
                                 labels={"cache": "train.multi"})
                assert g is not None and g.value == 3
                assert any("train.multi" in str(x.message) for x in w)
            # each (n, stacked) is its own intended program — no churn
            assert cm.ledger.counts()["churn_alerts"] == 0
            for n in (1, 2, 3):
                assert (cm.ledger.report()["by_key"]
                        [f"train.multi[n={n},stacked=False]"]["count"] == 1)
        finally:
            cm.ledger.cache_warn_bound = old

    def test_hbm_components_registered(self):
        step = _make_step()
        comps = cm.memory.components()
        assert comps.get("params", 0) > 0
        assert comps.get("optimizer", 0) > 0
        # AdamW: 2 f32 moments per f32 param (+ lr/step scalars) — the
        # optimizer component is the same order as params, and a dtype
        # upcast would show up here
        assert comps["optimizer"] >= comps["params"]
        del step
        import gc

        gc.collect()
        assert cm.memory.components().get("params", 0) == 0, \
            "a dead TrainStep's bytes must drop out of the budget"


# ---------------------------------------------------------------------------
# serving-engine ledger completeness + warm-path assertions
# ---------------------------------------------------------------------------
class TestEngineLedger:
    def test_serve_records_every_program_and_warm_serve_is_silent(
            self, tiny_model):
        eng = _tiny_engine(tiny_model)
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 100, size=n).astype(np.int32)
                   for n in (5, 9)]
        eng.serve(prompts, max_new_tokens=4)
        rep = cm.ledger.report()
        keys = set(rep["by_key"])
        # ledger completeness: every compiled program the engine holds has
        # a ledger entry with the matching key family
        assert len([k for k in keys if k.startswith("serve.ragged[")]) \
            == len(eng._ragged_fns) > 0
        assert len([k for k in keys if k.startswith("serve.insert[")]) \
            == len(eng._insert_fns) == 0
        n_dec = (len([k for k in keys if k.startswith("serve.decode[")])
                 + len([k for k in keys
                        if k.startswith("serve.decode_block[")]))
        assert n_dec == len(eng._decode_fns) + len(eng._decode_block_fns)
        mark = cm.ledger.counts()["events"]
        eng.serve(prompts, max_new_tokens=4)  # warm: same buckets
        assert cm.ledger.counts()["events"] == mark, \
            "warm serving dispatch must trigger zero recompiles"

    def test_warmup_compiles_are_labeled_and_cover_serve(self, tiny_model):
        eng = _tiny_engine(tiny_model)
        eng.warmup(prompt_lens=[5, 9])
        rep = cm.ledger.report()
        warm_events = sum(e["triggers"].get("warmup", 0)
                          for e in rep["by_key"].values())
        assert warm_events == cm.ledger.counts()["events"] > 0, \
            "every warmup compile carries the warmup trigger"
        mark = cm.ledger.counts()["events"]
        rng = np.random.RandomState(1)
        eng.serve([rng.randint(1, 100, size=5).astype(np.int32),
                   rng.randint(1, 100, size=9).astype(np.int32)],
                  max_new_tokens=3)
        assert cm.ledger.counts()["events"] == mark, \
            "a warmed engine serves its vocabulary without compiling"

    def test_pool_frag_gauges_and_kv_component(self, tiny_model):
        eng = _tiny_engine(tiny_model, enable_prefix_cache=True)
        assert cm.memory.components().get("kv_pool", 0) == eng.pool_bytes()
        rng = np.random.RandomState(2)
        p = rng.randint(1, 100, size=17).astype(np.int32)
        eng.serve([p], max_new_tokens=3)
        free = registry.get("serve.pool_frag_free_pages").value
        evict = registry.get("serve.pool_frag_evictable_pages").value
        used = registry.get("serve.pool_frag_used_pages").value
        assert used == 0  # everything retired
        assert evict > 0  # prefix cache holds the prompt's full pages
        assert free + evict == eng.num_pages - 1
        frag = registry.get("serve.pool_frag_ratio").value
        assert frag == pytest.approx(evict / (free + evict))


# ---------------------------------------------------------------------------
# memory ledger
# ---------------------------------------------------------------------------
class TestMemoryLedger:
    def test_lazy_analysis_from_captured_signature(self):
        f = cm.ledgered_jit(lambda a, b: (a @ b).sum(), key="t.mm")
        f(jnp.zeros((32, 16)), jnp.zeros((16, 8)))
        progs = cm.memory.programs()
        assert progs["t.mm"]["analysis"] is None  # lazy: nothing forced yet
        mark = cm.ledger.counts()["events"]
        out = cm.memory.analyze()
        assert cm.ledger.counts()["events"] == mark, \
            "analysis re-lowering must not pollute the compile ledger"
        assert out["t.mm"]["argument_bytes"] == (32 * 16 + 16 * 8) * 4
        assert out["t.mm"]["output_bytes"] == 4
        assert cm.memory.programs()["t.mm"]["analysis"] is not None

    def test_analyze_function_probe(self):
        res = cm.analyze_function(lambda x: (x @ x.T).sum(),
                                  jnp.zeros((64, 64)))
        assert res["argument_bytes"] == 64 * 64 * 4
        assert res["temp_bytes"] > 0
        e = cm.ledger.report()["by_key"]
        probe = [k for k in e if k.startswith("probe.")]
        assert probe and e[probe[0]]["triggers"] == {"probe": 1}

    def test_budget_report_against_env_capacity(self, monkeypatch):
        monkeypatch.setenv("PADDLE_HBM_CAPACITY_BYTES", str(1 << 30))
        step = _make_step()
        rep = cm.memory.report()
        assert rep["capacity_bytes"] == 1 << 30
        assert rep["used_bytes"] == sum(rep["components"].values()) > 0
        assert rep["headroom_bytes"] == (1 << 30) - rep["used_bytes"] \
            - rep["temp_peak_bytes"]
        assert 0 <= rep["budget_fraction"] < 1
        assert rep["budget_fraction"] == round(
            (rep["used_bytes"] + rep["temp_peak_bytes"]) / (1 << 30), 6)
        assert registry.get("device.hbm_capacity_bytes").value == 1 << 30
        assert registry.get(
            "device.hbm_component_bytes",
            labels={"component": "params"}).value > 0
        del step

    def test_provider_registered_during_report_is_kept(self):
        class Obj:
            def nbytes(self):
                return 100

        a = Obj()
        cm.memory.register_component_provider("t.comp", a, "nbytes")
        assert cm.memory.components()["t.comp"] == 100
        # registering another provider between two reports must not be
        # clobbered by the dead-ref prune (the prune is in place, not a
        # snapshot write-back)
        b = Obj()
        cm.memory.register_component_provider("t.comp", b, "nbytes")
        assert cm.memory.components()["t.comp"] == 200
        del a
        import gc

        gc.collect()
        assert cm.memory.components()["t.comp"] == 100

    def test_tree_nbytes(self):
        tree = {"a": jnp.zeros((4, 4), jnp.float32),
                "b": [jnp.zeros(8, jnp.int8), None, 3]}
        assert cm.tree_nbytes(tree) == 4 * 4 * 4 + 8

    def test_top_programs_by_temp_ranked(self):
        cm.analyze_function(lambda x: (x @ x.T).sum(),
                            jnp.zeros((128, 128)), key="probe.big")
        cm.analyze_function(lambda x: x.sum(), jnp.zeros(8),
                            key="probe.small")
        top = cm.memory.top_programs_by_temp(5)
        assert top[0]["key"] == "probe.big"
        assert top[0]["temp_bytes"] >= top[-1]["temp_bytes"]


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------
class TestOOMForensics:
    def test_is_oom_classification(self):
        assert cm.is_oom(RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 3221225472 bytes"))
        assert cm.is_oom(chaos.FaultInjected("obs.oom", 1))
        assert not cm.is_oom(chaos.FaultInjected("serve.decode", 1))
        assert not cm.is_oom(ValueError("shape mismatch"))
        assert not cm.is_oom(None)

    def test_train_step_chaos_oom_writes_report_and_reraises(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_TELEMETRY_DIR", str(tmp_path))
        step = _make_step()
        x, y = np.random.rand(8, 4), np.random.rand(8, 2)
        step(_t(x), _t(y))  # warm + fill the ledger
        with chaos.FaultPlan().fail("obs.oom"):
            with pytest.raises(chaos.FaultInjected):
                step(_t(x), _t(y))
        path = os.path.join(str(tmp_path), "oom_report.json")
        assert os.path.exists(path)
        rep = json.load(open(path))
        assert rep["program"] == "train.step"
        assert "obs.oom" in rep["error"]
        assert rep["compile"]["by_key"]["train.step"]["count"] == 1
        assert rep["compile"]["recent"], "last-N compile events present"
        assert rep["memory"]["components"].get("params", 0) > 0
        assert registry.get("device.oom_reports").value == 1

    def test_serve_chaos_oom_report_with_engine_context(
            self, tiny_model, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_TELEMETRY_DIR", str(tmp_path))
        eng = _tiny_engine(tiny_model)
        rng = np.random.RandomState(3)
        prompts = [rng.randint(1, 100, size=5).astype(np.int32)]
        eng.serve(prompts, max_new_tokens=2)  # warm
        with chaos.FaultPlan().fail("obs.oom"):
            outs = eng.serve(prompts, max_new_tokens=2)
        # degradation contract: the OOM'd request failed ALONE ...
        assert outs == [None]
        assert eng.stats["failed_requests"] == 1
        # ... and forensics committed before the isolation handler ate it
        rep = json.load(open(os.path.join(str(tmp_path),
                                          "oom_report.json")))
        ctxs = rep["contexts"]["serving_engine"]
        assert any(c["num_pages"] == eng.num_pages and "stats" in c
                   for c in ctxs)
        assert rep["memory"]["components"].get("kv_pool", 0) > 0
        assert any(k.startswith("serve.") for k in rep["compile"]["by_key"])

    def test_oom_report_includes_top_programs_when_analyzable(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_TELEMETRY_DIR", str(tmp_path))
        f = cm.ledgered_jit(lambda x: (x @ x.T).sum(), key="t.fat")
        f(jnp.zeros((64, 64)))
        path = cm.write_oom_report(RuntimeError("RESOURCE_EXHAUSTED: boom"))
        rep = json.load(open(path))
        assert any(p["key"] == "t.fat" and p["temp_bytes"] > 0
                   for p in rep["top_programs_by_temp"])

    def test_maybe_oom_report_dedups_and_ignores_non_oom(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_TELEMETRY_DIR", str(tmp_path))
        assert cm.maybe_oom_report(ValueError("nope")) is None
        e = RuntimeError("RESOURCE_EXHAUSTED")
        p1 = cm.maybe_oom_report(e)
        p2 = cm.maybe_oom_report(e)  # second seam, same exception object
        assert p1 == p2
        assert registry.get("device.oom_reports").value == 1
        # a LATER OOM reports again even if CPython recycled the freed
        # exception's address: the id dedup is time-bounded to one raise
        # propagation (simulate the window expiring)
        cm._last_oom[2] -= 2 * cm._OOM_DEDUP_WINDOW_S
        del e
        cm.maybe_oom_report(RuntimeError("RESOURCE_EXHAUSTED: again"))
        assert registry.get("device.oom_reports").value == 2
        rep = json.load(open(os.path.join(str(tmp_path),
                                          "oom_report.json")))
        assert "again" in rep["error"]


# ---------------------------------------------------------------------------
# /compilez + /memz
# ---------------------------------------------------------------------------
class TestStatusz:
    def test_payload_builders(self):
        f = cm.ledgered_jit(lambda x: x + 1, key="t.sz")
        f(jnp.ones(2))
        srv = StatusServer()
        cz = srv.compilez()
        assert cz["events"] >= 1 and "t.sz" in cz["by_key"]
        mz = srv.memz()
        assert "components" in mz and "t.sz" in mz["programs"]
        assert mz["programs"]["t.sz"]["analysis"] is None
        mz = srv.memz(analyze=True)
        assert mz["programs"]["t.sz"]["analysis"]["output_bytes"] == 8

    def test_http_routes_live(self):
        f = cm.ledgered_jit(lambda x: x * 2, key="t.http")
        f(jnp.ones(3))
        srv = StatusServer(port=0).start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            cz = json.load(urllib.request.urlopen(f"{base}/compilez"))
            assert "t.http" in cz["by_key"]
            mz = json.load(urllib.request.urlopen(f"{base}/memz"))
            assert "t.http" in mz["programs"]
            mz = json.load(urllib.request.urlopen(f"{base}/memz?analyze=1"))
            assert mz["programs"]["t.http"]["analysis"] is not None
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{base}/nope")
            body = json.loads(ei.value.read())
            assert "/compilez" in body["routes"] and "/memz" in body["routes"]
        finally:
            srv.stop()

    def test_serving_report_carries_compile_and_memory(self, tiny_model):
        from paddle_tpu.serving import ServingFrontend

        eng = _tiny_engine(tiny_model)
        with ServingFrontend([eng]) as fe:
            rng = np.random.RandomState(5)
            h = fe.submit(rng.randint(1, 100, size=5).astype(np.int32), 3)
            h.result(timeout=60)
            rep = fe.serving_report()
        assert rep["compile"]["events"] > 0
        assert any(k.startswith("serve.") for k in rep["compile"]["by_key"])
        assert rep["memory"]["components"].get("kv_pool", 0) > 0


# ---------------------------------------------------------------------------
# hang watchdog: mid-compile diagnosis
# ---------------------------------------------------------------------------
class TestWatchdogMidCompile:
    def test_ledger_writes_compiling_breadcrumb(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_TELEMETRY_DIR", str(tmp_path))
        path = cm.compiling_path(str(tmp_path), "0")
        tok = cm.ledger.begin("train.step")
        try:
            rec = json.load(open(path))
            assert rec["active"][0]["key"] == "train.step"
            assert rec["pid"] == os.getpid()
        finally:
            cm.ledger.exit_trace()
            cm.ledger.end(tok, "train.step", wall_s=0.1)
        assert not os.path.exists(path), "breadcrumb removed at compile end"

    def test_hang_report_says_wedged_mid_compile(self, tmp_path):
        d = str(tmp_path)
        # rank 0 = THIS process with the SIGUSR1 faulthandler installed —
        # the watchdog signals every rank pid for stack dumps, and an
        # unhandled SIGUSR1 would kill the test process (same setup as
        # test_telemetry's watchdog tests)
        hb0 = watchdog.Heartbeat(d, 0)
        try:
            # a stalled rank 1 with a live pid ...
            with open(watchdog.heartbeat_path(d, 1), "w") as f:
                json.dump({"rank": 1, "pid": os.getpid(), "step": 3,
                           "time": time.time() - 120}, f)
            # ... that is 90s into compiling train.step
            with open(cm.compiling_path(d, 1), "w") as f:
                json.dump({"rank": "1", "pid": os.getpid(), "active": [
                    {"key": "train.step",
                     "started_at": time.time() - 90}]}, f)
            wd = watchdog.HangWatchdog(d, deadline_s=1.0,
                                       signal_grace_s=0.05)
            wd._start_time = time.time() - 300
            report_path = wd.scan_once()
            assert report_path
            rep = json.load(open(report_path))
            comp = rep["ranks"]["1"]["compiling"]
            assert comp["active"][0]["key"] == "train.step"
            assert comp["active"][0]["elapsed_s"] >= 89
            # the rank without a breadcrumb has no compiling block
            assert "compiling" not in rep["ranks"]["0"]
        finally:
            hb0.close()


# ---------------------------------------------------------------------------
# disabled-overhead bound (the PR-2 contract, with the ledger compiled in)
# ---------------------------------------------------------------------------
class TestDisabledOverhead:
    @staticmethod
    def _best_of(runs, fn):
        return min(fn() for _ in range(runs))

    def test_oom_seam_disabled_cost(self):
        chaos.site("obs.oom")  # settle the env probe
        n = 100_000

        def measure():
            t0 = time.perf_counter()
            for _ in range(n):
                chaos.site("obs.oom")
            return (time.perf_counter() - t0) / n

        per_call = self._best_of(3, measure)
        assert per_call < 2e-6, f"obs.oom seam costs {per_call * 1e9:.0f}ns"

    def test_warm_ledgered_dispatch_overhead_under_one_percent(self):
        """A warm ledgered call adds a thread-local store + two clock
        reads on top of the jitted dispatch. Bound the DELTA vs a raw
        jitted call at 100µs — 1% of a 10ms step, same contract as the
        PR-2 instrumentation bound (measured: ~1µs)."""
        import jax

        raw = jax.jit(lambda x: x)  # compile-ledger-ok (the baseline under measurement)
        led = cm.ledgered_jit(lambda x: x, key="t.overhead")
        x = jnp.ones(4)
        raw(x), led(x)  # warm both
        n = 2_000

        def measure(fn):
            def run():
                t0 = time.perf_counter()
                for _ in range(n):
                    fn(x)
                return (time.perf_counter() - t0) / n
            return run

        t_raw = self._best_of(5, measure(raw))
        t_led = self._best_of(5, measure(led))
        assert t_led - t_raw < 100e-6, (
            f"ledgered dispatch adds {(t_led - t_raw) * 1e6:.1f}µs/call")
