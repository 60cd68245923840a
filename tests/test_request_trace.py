"""Request-scoped distributed tracing + /statusz + SLO burn rates (ISSUE 7).

Covers the tentpole end to end — trace contexts minted at submit(),
propagated through scheduler/router/engine across threads, reconstructed
as ONE rooted tree per request by scripts/trace_view.py even across a
mid-stream replica kill (failed attempt + reroute edge + replay, no
orphans, no duplicated trace ids) — plus the satellites: Prometheus
exposition correctness against a strict text-format parser, the serving
goodput split, the live /statusz//varz//tracez//healthz endpoints, and
multi-window SLO burn-rate alerts firing on a violated interactive TTFT
objective. The disabled-overhead contract (PR 2) is asserted with request
tracing compiled in.
"""
import importlib.util
import json
import os
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import goodput, request_trace as rtrace
from paddle_tpu.observability import slo as slo_mod
from paddle_tpu.observability import tracing
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.observability.statusz import StatusServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_trace_view():
    spec = importlib.util.spec_from_file_location(
        "trace_view", os.path.join(REPO, "scripts", "trace_view.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


trace_view = _load_trace_view()


@pytest.fixture(autouse=True)
def _clean_telemetry(monkeypatch):
    monkeypatch.delenv("PADDLE_TELEMETRY", raising=False)
    monkeypatch.delenv("PADDLE_TELEMETRY_DIR", raising=False)
    tracing.disable()
    tracing.clear_sinks()
    tracing.clear()
    rtrace.clear()
    obs.registry.reset()
    goodput.reset()
    goodput.serving.reset()
    yield
    tracing.disable()
    tracing.clear_sinks()
    tracing.clear()
    rtrace.clear()


def _tiny_model(layers=2, seed=41):
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    paddle.seed(seed)
    m = LlamaForCausalLM(llama_tiny(num_hidden_layers=layers))
    m.eval()
    return m


# ---------------------------------------------------------------------------
# trace context core
# ---------------------------------------------------------------------------
class TestTraceCore:
    def test_disabled_start_is_none_and_cheap(self):
        assert rtrace.start(1) is None
        n = 50_000
        t0 = time.perf_counter()
        for i in range(n):
            rtrace.start(i)
        per_call = (time.perf_counter() - t0) / n
        # same bound class as the disabled span: a flag check, no allocation
        assert per_call < 2e-6, f"disabled start() costs {per_call*1e9:.0f}ns"

    def test_tree_structure_and_sink(self, tmp_path):
        path = str(tmp_path / "spans.0.jsonl")
        tracing.enable(jsonl_path=path)
        tr = rtrace.start(7, slo="interactive")
        att = tr.root.child("attempt", n=0, replica="replica0")
        att.event("place", replica="replica0")
        q = att.child("queue")
        q.end()
        tr.finish("ok", n_generated=3)
        recs = [json.loads(l) for l in open(path)]
        byname = {r["name"]: r for r in recs}
        assert set(byname) == {"request", "attempt", "place", "queue"}
        assert byname["request"]["parent"] is None
        assert byname["attempt"]["parent"] == byname["request"]["span"]
        assert byname["queue"]["parent"] == byname["attempt"]["span"]
        assert all(r["trace"] == tr.trace_id and r["rid"] == 7 for r in recs)
        assert byname["place"]["dur_s"] == 0.0
        assert byname["request"]["status"] == "ok"
        assert byname["request"]["attrs"]["n_generated"] == 3

    def test_finish_sweeps_open_spans_once(self):
        tracing.enable()
        tr = rtrace.start(1)
        tr.root.child("attempt")  # left open on purpose
        tr.finish("error", error="boom")
        tr.finish("ok")  # idempotent: second terminal transition loses
        [summary] = rtrace.recent()
        assert summary["status"] == "error"
        names = {r["name"]: r for r in summary["records"]}
        # the sweep closed the straggler with the terminal status
        assert names["attempt"]["status"] == "error"
        assert len(rtrace.recent()) == 1

    def test_cross_thread_close(self):
        tracing.enable()
        tr = rtrace.start(2)
        q = tr.root.child("queue")
        t = threading.Thread(target=lambda: q.end("ok"))
        t.start()
        t.join()
        tr.finish("ok")
        names = {r["name"]: r["status"] for r in rtrace.recent()[0]["records"]}
        assert names["queue"] == "ok"

    def test_span_bound_and_dropped_counter(self, monkeypatch):
        monkeypatch.setattr(rtrace, "MAX_SPANS_PER_TRACE", 4)
        tracing.enable()
        before = obs.registry.get("rtrace.dropped_spans").value
        tr = rtrace.start(3)
        for i in range(10):
            tr.root.child(f"s{i}").end()
        tr.finish("ok")
        [summary] = rtrace.recent()
        assert summary["n_spans"] == 4
        assert summary["dropped"] == 7  # 6 overflow spans + the root close
        assert obs.registry.get("rtrace.dropped_spans").value - before == 7

    def test_truncated_trace_stays_well_formed(self, monkeypatch):
        """Suppression happens at span CREATION, so a trace that blows the
        bound (a 4k-token request) still emits its root/attempt closes —
        trace_view sees a well-formed (truncated) tree, not orphans."""
        monkeypatch.setattr(rtrace, "MAX_SPANS_PER_TRACE", 6)
        tracing.enable()
        tr = rtrace.start(9)
        att = tr.root.child("attempt")
        for _ in range(20):
            s = att.child("decode_block")
            s.end()
            s.event("emit")  # children of suppressed spans stay suppressed
        att.end()
        tr.finish("ok")
        [summary] = rtrace.recent()
        assert summary["dropped"] > 0
        roots, problems = trace_view.build_tree(summary["records"])
        assert problems == []
        names = {r["name"] for r in summary["records"]}
        assert {"request", "attempt"} <= names

    def test_slowest_and_errored_views(self):
        tracing.enable()
        for i, (status, sleep_s) in enumerate(
                [("ok", 0.0), ("error", 0.0), ("ok", 0.02)]):
            tr = rtrace.start(i)
            if sleep_s:
                time.sleep(sleep_s)
            tr.finish(status)
        slowest = rtrace.slowest(1)
        assert slowest[0]["rid"] == 2
        assert [t["rid"] for t in rtrace.errored()] == [1]


# ---------------------------------------------------------------------------
# Prometheus exposition correctness (satellite)
# ---------------------------------------------------------------------------
_SAMPLE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?P<labels>\{[^{}]*\})? (?P<value>[^ ]+)$")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text):
    """Strict-enough text-format parser: validates comment syntax, sample
    syntax, TYPE-before-samples, label quoting/escaping. Returns
    {family: {"type": t, "help": h, "samples": [(name, labels, value)]}}."""
    families, cur = {}, None
    for ln, line in enumerate(text.splitlines(), 1):
        if not line:
            continue
        if line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            fam = rest.split(" ", 1)[0]
            families.setdefault(fam, {"type": None, "help": None,
                                      "samples": []})["help"] = rest
            continue
        if line.startswith("# TYPE "):
            parts = line.split(" ")
            assert len(parts) >= 4, f"line {ln}: malformed TYPE: {line!r}"
            fam, kind = parts[2], parts[3]
            assert kind in ("counter", "gauge", "histogram", "summary",
                            "untyped"), f"line {ln}: bad type {kind}"
            cur = families.setdefault(fam, {"type": None, "help": None,
                                            "samples": []})
            assert cur["type"] is None, f"line {ln}: duplicate TYPE {fam}"
            cur["type"] = kind
            continue
        assert not line.startswith("#"), f"line {ln}: bad comment {line!r}"
        m = _SAMPLE.match(line)
        assert m, f"line {ln}: unparseable sample {line!r}"
        float(m.group("value"))  # must be a number
        labels = {}
        if m.group("labels"):
            body = m.group("labels")[1:-1]
            pairs = _LABEL.findall(body)
            consumed = ",".join(f'{k}="{v}"' for k, v in pairs)
            assert consumed == body, f"line {ln}: bad labels {body!r}"
            unescape = (lambda v: re.sub(
                r"\\(.)",
                lambda mm: {"n": "\n"}.get(mm.group(1), mm.group(1)), v))
            labels = {k: unescape(v) for k, v in pairs}
        name = m.group("name")
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        fam = base if base in families else name
        assert fam in families, f"line {ln}: sample {name} before TYPE"
        families[fam]["samples"].append((name, labels, m.group("value")))
    return families


class TestPrometheusExposition:
    def test_full_registry_passes_strict_parser(self):
        # everything the process registered so far — the real payload /varz
        # serves — must parse
        obs.registry.counter("t.reqs", help="requests").inc(3)
        obs.registry.histogram("t.lat_s", buckets=(0.1, 1.0)).observe(0.5)
        parse_prometheus(obs.registry.to_prometheus())

    def test_labels_grouped_escaped_and_cumulative(self):
        r = MetricsRegistry()
        r.histogram("srv.wait_s", buckets=(0.1, 1.0),
                    labels={"slo_class": "interactive"}).observe(0.05)
        h2 = r.histogram("srv.wait_s", buckets=(0.1, 1.0),
                         labels={"slo_class": 'we"ird\\cls'})
        h2.observe(0.5)
        h2.observe(5.0)
        r.gauge("srv.depth", help="queue depth",
                labels={"replica": "r0"}).set(4)
        text = r.to_prometheus()
        fams = parse_prometheus(text)
        assert fams["srv_wait_s"]["type"] == "histogram"
        # ONE TYPE header for the family, samples for both label sets
        assert text.count("# TYPE srv_wait_s histogram") == 1
        assert "# HELP srv_depth queue depth" in text
        buckets = [(n, l, v) for n, l, v in fams["srv_wait_s"]["samples"]
                   if n == "srv_wait_s_bucket"]
        by_cls = {}
        for _, labels, v in buckets:
            by_cls.setdefault(labels["slo_class"], []).append(
                (labels["le"], int(v)))
        # escaping round-trips through the parser
        assert 'we"ird\\cls' in by_cls
        for cls, series in by_cls.items():
            les = [le for le, _ in series]
            counts = [c for _, c in series]
            assert les[-1] == "+Inf"
            assert counts == sorted(counts), "buckets must be cumulative"
        # +Inf count equals the series _count sample
        count = next(int(v) for n, l, v in fams["srv_wait_s"]["samples"]
                     if n == "srv_wait_s_count"
                     and l["slo_class"] == 'we"ird\\cls')
        assert by_cls['we"ird\\cls'][-1][1] == count == 2
        # gauges: hwm is its own typed family
        assert fams["srv_depth_hwm"]["type"] == "gauge"

    def test_family_type_conflict_raises(self):
        r = MetricsRegistry()
        r.counter("x.y", labels={"a": "1"})
        with pytest.raises(ValueError, match="family"):
            r.gauge("x.y", labels={"a": "2"})


# ---------------------------------------------------------------------------
# SLO burn-rate accounting
# ---------------------------------------------------------------------------
class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


class TestSLOMonitor:
    def test_objective_validation(self):
        with pytest.raises(ValueError, match="threshold_s"):
            slo_mod.SLOObjective("interactive", "ttft")
        with pytest.raises(ValueError, match="unknown SLO metric"):
            slo_mod.SLOObjective("interactive", "nope", 1.0)
        obj = slo_mod.SLOObjective("interactive", "ttft", 1.0, 0.99)
        assert obj.error_budget == pytest.approx(0.01)
        assert obj.is_bad(value=2.0) and not obj.is_bad(value=0.5)

    def test_burn_rate_math(self):
        clock = FakeClock()
        m = slo_mod.SLOMonitor(
            objectives=[slo_mod.SLOObjective("i", "ttft", 1.0, 0.99)],
            clock=clock)
        for _ in range(99):
            m.observe("i", "ttft", 0.1)
        m.observe("i", "ttft", 5.0)  # 1% bad = exactly the budget
        rates = m.burn_rates()["i.ttft<1.0s"]
        assert rates["fast"] == pytest.approx(1.0)
        assert rates["slow"] == pytest.approx(1.0)
        assert rates["fast_n"] == 100

    def test_multiwindow_alert_needs_both_windows(self):
        clock = FakeClock()
        m = slo_mod.SLOMonitor(
            objectives=[slo_mod.SLOObjective("i", "ttft", 1.0, 0.99)],
            fast_window_s=300, slow_window_s=3600, alert_burn_rate=10.0,
            clock=clock)
        # an hour of healthy traffic...
        for _ in range(60):
            m.observe("i", "ttft", 0.1)
            clock.t += 55.0
        # ...then a fast-window burst of violations: fast burns hot, the
        # slow window still holds an hour of mostly-good samples
        for _ in range(5):
            m.observe("i", "ttft", 9.0)
        r = m.burn_rates()["i.ttft<1.0s"]
        assert r["fast"] >= 10.0 > r["slow"]
        assert m.alerts() == []  # blip: no page
        # sustained violations push the slow window past the bar too
        for _ in range(200):
            m.observe("i", "ttft", 9.0)
        alerts = m.alerts()
        assert len(alerts) == 1 and alerts[0]["metric"] == "ttft"
        assert obs.registry.get("slo.alerts_fired").value == 1
        rep = m.report()
        assert rep["objectives"]["i.ttft<1.0s"]["alerting"] is True
        g = obs.registry.get("slo.burn_rate",
                             labels={"objective": "i.ttft<1.0s",
                                     "window": "fast"})
        assert g is not None and g.value >= 10.0

    def test_default_objectives_from_scheduler_classes(self):
        from paddle_tpu.serving.scheduler import BATCH, INTERACTIVE

        objs = slo_mod.default_objectives([INTERACTIVE, BATCH])
        kinds = {(o.slo_class, o.metric) for o in objs}
        assert ("interactive", "ttft") in kinds
        assert ("interactive", "deadline_miss") in kinds
        assert ("batch", "tpot") in kinds


# ---------------------------------------------------------------------------
# trace_view reconstruction
# ---------------------------------------------------------------------------
def _rec(trace, span, parent, name, t0, dur=0.001, **attrs):
    r = {"trace": trace, "span": span, "parent": parent, "name": name,
         "rid": 0, "t0": t0, "dur_s": dur, "time": t0 + dur,
         "pid": 1, "status": "ok"}
    if attrs:
        r["attrs"] = attrs
    return r


class TestTraceView:
    def test_merges_files_and_builds_tree(self, tmp_path):
        # one request whose records landed in TWO files (submit process +
        # a second replica's sink), plus a duplicate record (two sinks)
        a = [_rec("t1", "t1/1", None, "request", 10.0, 0.5),
             _rec("t1", "t1/2", "t1/1", "attempt", 10.0, 0.2)]
        b = [_rec("t1", "t1/2", "t1/1", "attempt", 10.0, 0.2),  # dup
             _rec("t1", "t1/3", "t1/2", "queue", 10.01, 0.01)]
        for fn, recs in (("spans.0.jsonl", a), ("spans.1.jsonl", b)):
            with open(tmp_path / fn, "w") as f:
                f.write("\n".join(json.dumps(r) for r in recs) + "\n")
        traces = trace_view.load_traces([str(tmp_path)])
        assert set(traces) == {"t1"}
        assert len(traces["t1"]) == 3  # duplicate collapsed
        roots, problems = trace_view.build_tree(traces["t1"])
        assert problems == []
        assert len(roots) == 1 and roots[0]["rec"]["name"] == "request"
        assert roots[0]["children"][0]["children"][0]["rec"]["name"] == "queue"

    def test_divergent_duplicate_span_ids_flagged(self, tmp_path):
        """Exact duplicates (one record, two sinks) collapse; two DIFFERENT
        records sharing a span id are corruption and must be flagged."""
        recs = [_rec("t3", "t3/1", None, "request", 1.0),
                _rec("t3", "t3/2", "t3/1", "a", 1.0),
                _rec("t3", "t3/2", "t3/1", "b", 1.1)]
        p = tmp_path / "spans.jsonl"
        with open(p, "w") as f:
            f.write("\n".join(json.dumps(r) for r in recs) + "\n")
        traces = trace_view.load_traces([str(p)])
        assert len(traces["t3"]) == 3
        _, problems = trace_view.build_tree(traces["t3"])
        assert any("duplicate" in x for x in problems)
        assert trace_view.main([str(p), "--check"]) == 2

    def test_detects_orphans_and_check_exit(self, tmp_path, capsys):
        recs = [_rec("t2", "t2/1", None, "request", 1.0),
                _rec("t2", "t2/9", "t2/404", "ghost", 1.1)]
        p = tmp_path / "spans.jsonl"
        with open(p, "w") as f:
            f.write("\n".join(json.dumps(r) for r in recs) + "\n")
        _, problems = trace_view.build_tree(
            trace_view.load_traces([str(p)])["t2"])
        assert any("orphan" in x for x in problems)
        assert trace_view.main([str(p), "--check"]) == 2
        assert trace_view.main([str(p)]) == 0  # report-only mode
        out = capsys.readouterr().out
        assert "orphan" in out and "trace t2" in out


# ---------------------------------------------------------------------------
# serving integration: traces, goodput split, statusz, SLO alert
# ---------------------------------------------------------------------------
class TestServingIntegration:
    @pytest.fixture(scope="class")
    def model(self):
        return _tiny_model()

    def _engines(self, model, n=1, prefill_chunk=16, **kw):
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        return [ContinuousBatchingEngine(
            model, max_seqs=2, page_size=8, max_len=64, decode_block=2,
            prefill_chunk=prefill_chunk, **kw) for _ in range(n)]

    def test_traced_request_tree_and_goodput_split(self, model, tmp_path):
        from paddle_tpu.serving import ServingFrontend

        sink = str(tmp_path / "spans.0.jsonl")
        tracing.enable(jsonl_path=sink)
        rng = np.random.RandomState(0)
        with ServingFrontend(self._engines(model)) as fe:
            # two rounds of one short prompt (one chunk) + one long (three
            # chunks of 16): the first round compiles (goodput 'compile'),
            # the second hits warm programs so the decode slice is
            # populated too
            for _ in range(2):
                hs = [fe.submit(rng.randint(1, 100, (n,)).astype(np.int32),
                                4, slo_class="interactive")
                      for n in (6, 40)]
                for h in hs:
                    assert h.result(timeout=120) is not None
            rep = fe.serving_report()
        # the full lifecycle reconstructs: queue -> place -> admit ->
        # first token -> decode blocks -> emit, one rooted tree each.
        # Admission does no device work, so a request's trace holds no
        # prefill span: its chunks ride the mixed dispatches, which the
        # step log records (tests/test_step_log.py)
        traces = trace_view.load_traces([sink])
        assert len(traces) == 4
        all_names = set()
        for recs in traces.values():
            roots, problems = trace_view.build_tree(recs)
            assert problems == []
            assert len(roots) == 1
            all_names.update(r["name"] for r in recs)
        assert {"request", "attempt", "place", "queue", "admit",
                "first_token", "decode_block", "emit"} <= all_names
        assert not {"prefill", "prefill_chunk"} & all_names
        # tracez carries them too
        assert len(rtrace.slowest(5)) == 4
        # serving goodput split (satellite): engine wall classified
        # (every dispatch, mixed steps included, is booked under decode)
        cats = rep["goodput"]["categories"]
        assert cats.get("compile", 0) > 0
        assert cats.get("decode", 0) > 0
        assert cats.get("host_emit", 0) > 0
        assert rep["goodput"]["goodput_fraction"] == pytest.approx(
            (cats.get("prefill", 0) + cats.get("decode", 0))
            / rep["goodput"]["wall_s"], rel=1e-6)
        # SLO section present with per-objective burn rates
        assert "interactive.ttft<1.0s" in rep["slo"]["objectives"]

    def test_untraced_serving_emits_nothing(self, model):
        from paddle_tpu.serving import ServingFrontend

        rng = np.random.RandomState(1)
        with ServingFrontend(self._engines(model)) as fe:
            h = fe.submit(rng.randint(1, 100, (6,)).astype(np.int32), 3)
            assert h.result(timeout=120) is not None
        assert rtrace.recent() == []
        assert obs.registry.get("rtrace.traces").value == 0

    def test_slo_alert_fires_on_violated_interactive_ttft(self, model):
        """Acceptance: burn-rate alerts fire in a test that violates the
        interactive TTFT objective — a 1µs target every real request
        breaks, through the REAL frontend observation path."""
        from paddle_tpu.serving import ServingFrontend

        monitor = slo_mod.SLOMonitor(
            objectives=[slo_mod.SLOObjective(
                "interactive", "ttft", threshold_s=1e-6, objective=0.99)],
            alert_burn_rate=5.0)
        rng = np.random.RandomState(2)
        with ServingFrontend(self._engines(model),
                             slo_monitor=monitor) as fe:
            for _ in range(3):
                fe.submit(rng.randint(1, 100, (6,)).astype(np.int32), 2,
                          slo_class="interactive").result(timeout=120)
            rep = fe.serving_report()
        [alert] = rep["slo"]["alerts"]
        assert alert["slo_class"] == "interactive"
        assert alert["metric"] == "ttft"
        assert alert["burn_fast"] >= 5.0 and alert["burn_slow"] >= 5.0

    def test_statusz_endpoints_live(self, model, tmp_path):
        from paddle_tpu.serving import ServingFrontend

        tracing.enable(jsonl_path=str(tmp_path / "spans.jsonl"))
        rng = np.random.RandomState(3)
        with ServingFrontend(self._engines(model), statusz_port=0) as fe:
            fe.submit(rng.randint(1, 100, (6,)).astype(np.int32), 3,
                      slo_class="interactive").result(timeout=120)
            base = f"http://127.0.0.1:{fe.statusz.port}"
            varz = urllib.request.urlopen(f"{base}/varz")
            assert varz.status == 200
            assert "text/plain" in varz.headers["Content-Type"]
            fams = parse_prometheus(varz.read().decode())
            assert "serving_ttft_s" in fams  # labeled family made it out
            sz = json.load(urllib.request.urlopen(f"{base}/statusz"))
            assert sz["telemetry_enabled"] is True
            assert sz["serving"]["replicas"]["replica0"]["state"] == "LIVE"
            assert "slo" in sz["serving"] and "goodput" in sz["serving"]
            tz = json.load(urllib.request.urlopen(f"{base}/tracez"))
            assert tz["slowest"] and tz["slowest"][0]["records"]
            hz = urllib.request.urlopen(f"{base}/healthz")
            assert hz.status == 200
            assert json.load(hz)["status"] == "ok"
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(f"{base}/nope")
            assert err.value.code == 404
        # shutdown stopped the server
        with pytest.raises(OSError):
            urllib.request.urlopen(f"{base}/healthz", timeout=2)

    def test_healthz_degrades_with_dead_replica(self, model):
        from paddle_tpu.serving import ServingFrontend

        with ServingFrontend(self._engines(model, n=2)) as fe:
            srv = StatusServer(frontend=fe)
            fe.kill("replica0", reason="test")
            code, payload = srv.healthz()
            assert code == 200 and payload["status"] == "degraded"
            fe.kill("replica1", reason="test")
            code, payload = srv.healthz()
            assert code == 503 and payload["status"] == "unhealthy"

    def test_statusz_heartbeat_files(self, tmp_path):
        from paddle_tpu.observability import watchdog

        d = str(tmp_path)
        watchdog.Heartbeat(d, 0, install_faulthandler=False).beat(step=5)
        srv = StatusServer(telemetry_dir=d, heartbeat_stale_s=60.0)
        code, payload = srv.healthz()
        assert code == 200 and payload["status"] == "ok"
        assert payload["heartbeat_age_s"]["0"] < 60.0


# ---------------------------------------------------------------------------
# chaos: replica killed mid-stream -> ONE trace with the reroute edge
# ---------------------------------------------------------------------------
class TestChaosTracePropagation:
    def test_replica_kill_yields_single_tree_with_reroute(self, tmp_path):
        """Satellite acceptance: a replica killed mid-flight (PR-4 chaos
        harness) yields ONE trace per request whose tree shows the failed
        attempt, the reroute edge, and the successful replay — no orphan
        spans, no duplicated trace_ids."""
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine
        from paddle_tpu.serving import RequestFailed, ServingFrontend
        from paddle_tpu.serving.router import DEAD
        from paddle_tpu.testing import chaos

        sink = str(tmp_path / "spans.0.jsonl")
        tracing.enable(jsonl_path=sink)
        model = _tiny_model()
        engines = [ContinuousBatchingEngine(
            model, max_seqs=2, page_size=8, max_len=64, decode_block=2)
            for _ in range(2)]
        rng = np.random.RandomState(7)
        fe = ServingFrontend(engines, heartbeat_deadline_s=120.0)
        try:
            handles = [fe.submit(
                rng.randint(1, 100, (8 + (i % 3),)).astype(np.int32), 6,
                slo_class="interactive" if i % 2 else "batch")
                for i in range(10)]
            with chaos.FaultPlan().fail("serving.replica_kill", times=1):
                deadline = time.monotonic() + 60
                while (not any(r.state == DEAD for r in fe.replicas)
                       and time.monotonic() < deadline):
                    time.sleep(0.005)
            assert any(r.state == DEAD for r in fe.replicas)
            done = failed = 0
            for h in handles:
                try:
                    assert h.result(timeout=120) is not None
                    done += 1
                except RequestFailed:
                    failed += 1
            assert done + failed == len(handles) and done > 0
        finally:
            fe.shutdown()

        traces = trace_view.load_traces([sink])
        # one trace per submitted request, no duplicated trace ids
        assert len(traces) == len(handles)
        rids = [recs[0]["rid"] for recs in traces.values()]
        assert sorted(rids) == sorted(h.rid for h in handles)
        rerouted = 0
        for tid, recs in traces.items():
            roots, problems = trace_view.build_tree(recs)
            assert problems == [], (tid, problems)
            assert len(roots) == 1
            names = [r["name"] for r in recs]
            if "reroute" in names:
                rerouted += 1
                by_t0 = sorted(recs, key=lambda r: (r["t0"], r["span"]))
                attempts = [r for r in by_t0 if r["name"] == "attempt"]
                edge = next(r for r in by_t0 if r["name"] == "reroute")
                root = roots[0]["rec"]
                # the failed attempt precedes the edge; if the replay
                # succeeded, a later attempt carries the ok status
                assert any(a["status"] in ("failed", "rerouted")
                           for a in attempts)
                assert edge["attrs"]["from_replica"]
                if root["status"] == "ok":
                    assert len(attempts) >= 2
                    assert any(a["status"] == "ok" for a in attempts)
        # the kill happened while work was queued/in flight: something
        # actually exercised the reroute path
        assert rerouted > 0


# ---------------------------------------------------------------------------
# the PR-2 disabled-overhead contract, with request tracing compiled in
# ---------------------------------------------------------------------------
class TestDisabledOverheadWithTracing:
    def test_submit_path_probe_is_flag_check_only(self):
        """The frontend's per-submit telemetry when disabled: one
        request_trace.start() flag check. Bounded like the PR-2 span
        contract (generous 2µs so CI load can't flake it)."""
        n = 20_000

        def measure():
            t0 = time.perf_counter()
            for i in range(n):
                if rtrace.start(i) is not None:  # the submit-path guard
                    raise AssertionError("tracing unexpectedly on")
            return (time.perf_counter() - t0) / n

        per_call = min(measure() for _ in range(3))
        assert per_call < 2e-6, f"{per_call * 1e9:.0f}ns per disabled probe"
