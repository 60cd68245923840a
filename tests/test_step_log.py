"""The serving engine's always-on step log (ISSUE 25): one record per
dispatch in ``tracing.steps``, stamps on ``time.monotonic_ns()``, the rows
with their KV extents, admissions and emitted tokens — and the repaired
``serve.tpot_s``, which times one block and not two."""
import inspect
import time
from collections import deque

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.continuous import (ContinuousBatchingEngine,
                                             EngineRequest)
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.observability import registry, tracing

STAMPS = ("t_step0", "t_pack0", "t_disp0", "t_disp1", "t_sync0", "t_ready",
          "t_emit1")
PROMPT_LENS = (100, 9, 70, 33, 5, 41)
MAX_NEW = (10, 7, 3, 12, 1, 9)


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    m = LlamaForCausalLM(llama_tiny(num_hidden_layers=2,
                                    max_position_embeddings=256))
    m.eval()
    return m


def _engine(model, async_decode, **kw):
    return ContinuousBatchingEngine(model, max_seqs=4, page_size=16,
                                    max_len=256, prefill_chunk=64,
                                    decode_block=4,
                                    async_decode=async_decode, **kw)


def _drive(eng, reqs):
    """The frontend's loop without the frontend: admit what fits, step."""
    queue, dispatches = deque(reqs), 0
    inner = eng._dispatch_ragged

    def counting(chain=None):
        nonlocal dispatches
        out = inner(chain=chain)
        dispatches += out is not None
        return out

    eng._dispatch_ragged = counting
    try:
        while queue or not eng.idle():
            eng._admit_from(queue)
            eng.step()
    finally:
        del eng._dispatch_ragged
    return dispatches


def _requests(seed=0):
    rng = np.random.RandomState(seed)
    return [EngineRequest(rid, rng.randint(1, 128, (n,)).astype(np.int32), m)
            for rid, (n, m) in enumerate(zip(PROMPT_LENS, MAX_NEW))]


@pytest.fixture(scope="module", params=[True, False], ids=["async", "sync"])
def served(request, model):
    """One drive of six requests through a tiny ragged engine, with tracing
    off: its records, requests and counter deltas."""
    assert not tracing.enabled()
    eng = _engine(model, request.param)
    reqs = _requests()
    tokens0 = registry.get("serve.tokens_out").value
    tpot = registry.get("serve.tpot_s")
    tpot0 = (tpot.count, tpot.sum)
    spans0 = len(tracing.last_spans(10_000))
    dispatches = _drive(eng, reqs)
    return {"async": request.param, "reqs": reqs, "dispatches": dispatches,
            "records": [r for r in tracing.step_records()
                        if r["engine"] == eng._engine_seq],
            "stats": dict(eng.stats),
            "tokens_out": registry.get("serve.tokens_out").value - tokens0,
            "tpot": (tpot.count - tpot0[0], tpot.sum - tpot0[1]),
            "new_spans": len(tracing.last_spans(10_000)) - spans0}


def test_one_record_per_dispatch(served):
    recs = served["records"]
    assert len(recs) == served["dispatches"] > 0
    assert [r["seq"] for r in recs] == sorted({r["seq"] for r in recs})
    assert {r["kind"] for r in recs} == {"mixed", "decode"}
    assert all(r["k"] == 4 for r in recs)
    # the pipeline is empty at the first dispatch and, in sync mode, always
    assert not recs[0]["chained"]
    assert any(r["chained"] for r in recs) == served["async"]
    assert recs[0]["cold"] and not recs[-1]["cold"]


def test_stamps_are_ordered(served):
    recs = served["records"]
    for r in recs:
        ts = [r[k] for k in STAMPS]
        assert ts == sorted(ts), r
    ready = [r["t_ready"] for r in recs]
    assert ready == sorted(ready)
    # the log's clock is the request stamps' clock
    now = time.monotonic_ns()
    assert all(0 < now - r["t_emit1"] < 600e9 for r in recs)


def test_emits_account_for_every_token(served):
    recs, reqs = served["records"], served["reqs"]
    emitted = sum(n for r in recs for _, n in r["emits"])
    assert emitted == served["tokens_out"] == sum(MAX_NEW)
    assert emitted == sum(len(q.result) - len(q.prompt) for q in reqs)
    per = {q.rid: sum(n for r in recs for rid, n in r["emits"]
                      if rid == q.rid) for q in reqs}
    assert per == {q.rid: q.max_new_tokens for q in reqs}
    # every row of a dispatch that decodes has an emits entry, 0 included
    for r in recs:
        assert sorted(rid for rid, _ in r["emits"]) == sorted(
            row[0] for row in r["rows"] if row[1] in "dg")
        assert all(0 <= n <= r["k"] for _, n in r["emits"])


def test_rows_carry_the_kernels_extents(served):
    recs, reqs = served["records"], served["reqs"]
    chunks = [row for r in recs for row in r["rows"] if row[1] in "cg"]
    assert sum(q for _, _, q, _ in chunks) == sum(PROMPT_LENS)
    # a prompt graduates once, with its whole length as the KV extent
    assert sorted((rid, kv) for rid, role, _, kv in chunks if role == "g") \
        == [(q.rid, len(q.prompt)) for q in reqs]
    assert all(r["kind"] == "mixed" for r in recs
               if any(row[1] in "cg" for row in r["rows"]))
    for rid in range(len(reqs)):
        # a request's KV extent after the dispatch's first write grows by
        # its chunk while it prefills, then by k a dispatch (less at its cap)
        mine = [(role, q, kv) for r in recs for x, role, q, kv in r["rows"]
                if x == rid]
        fed = 0
        for role, q, kv in mine:
            if role in "cg":
                fed += q
                assert kv == fed
        decode = [kv for role, _, kv in mine if role == "d"]
        assert all(0 <= b - a <= 4 for a, b in zip(decode, decode[1:]))
        assert all(q == 1 for role, q, _ in mine if role == "d")
    admits = [a for r in recs for a in r["admits"]]
    assert [(a[0], a[3]) for a in admits] == [
        (q.rid, len(q.prompt)) for q in reqs]


def test_mixed_dispatches_log_the_ragged_walk(served):
    """A mixed dispatch's record carries `ragged_walk = (walked, dense)`,
    the ragged kernel's grid steps a layer reckoned on the host from the
    dispatch's own spans, and `engine.stats` sums them. At this size a row's
    table is one kv block, so the kernel walks one step a (query block, row)
    pair: a step a row with tokens and one more for each block edge a row
    straddles, of (query blocks x `max_seqs`) dense."""
    from paddle_tpu.ops.ragged_paged_attention import _Q_TILE

    recs = served["records"]
    mixed = [r for r in recs if r["kind"] == "mixed"]
    assert mixed
    assert not any("ragged_walk" in r for r in recs if r["kind"] == "decode")
    n_qblocks = -(-(64 + 4) // _Q_TILE)  # prefill_chunk + max_seqs tokens
    for r in mixed:
        walked, dense = r["ragged_walk"]
        assert dense == n_qblocks * 4, r
        assert len(r["rows"]) <= walked <= len(r["rows"]) + n_qblocks - 1, r
    assert served["stats"]["ragged_walk"] == tuple(
        sum(r["ragged_walk"][i] for r in mixed) for i in (0, 1))


def test_scanning_dispatches_log_the_paged_walk(served):
    """Every dispatch that scans (decode, and mixed at `decode_block` > 1)
    carries `paged_walk = (walked, dense)`: the decode kernel's grid steps a
    layer and forward at the first scan step's lengths, beside the live rows
    x longest row's blocks walk it replaced. `walked <= dense`, and they are
    equal when the scan's rows walk as many blocks each (at this size a
    table is one block: a step a row either way)."""
    recs = served["records"]
    assert all("paged_walk" in r for r in recs)
    for r in recs:
        walked, dense = r["paged_walk"]
        scanning = [row for row in r["rows"] if row[1] in "dg"]
        assert 0 < walked <= dense, r
        assert walked == dense == len(scanning), r
    assert served["stats"]["paged_walk"] == tuple(
        sum(r["paged_walk"][i] for r in recs) for i in (0, 1))


@pytest.mark.parametrize("lens, want", [
    ((300, 300, 300), (9, 9)),        # one length: the rectangle is the list
    ((300, 20, 129), (3 + 1 + 2, 9)),  # each row to its own last block
    ((0, 257, 0), (3, 3)),             # dead rows between: no step
    ((0, 0, 0), (0, 0)),
])
def test_paged_walk_counts_each_rows_own_blocks(lens, want):
    """`KVCacheSpec.paged_walk` on the host: 8 pages of 16 a block here, so
    a row of n keys walks ceil(n / 128) steps; `LayerCacheSpecs` sums the
    layers whose pages are the allocator's and leaves rings and slots out."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.cache_specs import LayerCacheSpecs, NoPoolSpec
    from paddle_tpu.ops.paged_attention import (
        KVCacheSpec, WindowRingSpec, _pages_per_block,
    )

    spec = KVCacheSpec(2, 32, 128)
    pool = (jax.ShapeDtypeStruct((32, 99, 16, 128), jnp.bfloat16),) * 2
    assert _pages_per_block(pool[0], 32) == 8
    assert spec.paged_walk([pool, pool], np.asarray(lens), 32) == want
    layered = LayerCacheSpecs([KVCacheSpec(1, 32, 128), NoPoolSpec(0),
                               WindowRingSpec(32, 128, 64),
                               KVCacheSpec(1, 32, 128)])
    assert layered.paged_walk([pool, (), pool, pool], np.asarray(lens),
                              32) == tuple(2 * w for w in want)
    assert LayerCacheSpecs([NoPoolSpec()]).paged_walk(
        [()], np.asarray(lens), 32) is None


def test_ttft_parts_sum_to_the_request_stamps(served):
    recs = served["records"]
    admits = {a[0]: a for r in recs for a in r["admits"]}
    grad = {row[0]: r for r in recs for row in r["rows"] if row[1] == "g"}
    for q in served["reqs"]:
        rid, t_enqueue, t_admit, _ = admits[q.rid]
        assert (t_enqueue, t_admit) == (q.t_enqueue, q.t_admit)
        g = grad[q.rid]
        # the first token is stamped in the graduating block's emit loop
        assert g["t_ready"] / 1e9 <= q.t_first_token <= g["t_emit1"] / 1e9
        assert (q.rid, ) == tuple(x for x, n in g["emits"]
                                  if x == q.rid and n > 0)
        parts = (t_admit - t_enqueue, g["t_disp0"] / 1e9 - t_admit,
                 q.t_first_token - g["t_disp0"] / 1e9)
        assert all(p >= 0 for p in parts)
        assert sum(parts) == pytest.approx(q.t_first_token - q.t_enqueue,
                                           abs=1e-9)


def test_tpot_times_one_block(served):
    """serve.tpot_s observes t_ready(n) - max(t_disp1(n), t_ready(n-1)):
    pairwise disjoint intervals. Dispatch-to-readback (the old definition)
    overlaps each chained block's with the one before it."""
    recs = served["records"]
    own = [(max(r["t_disp1"], p["t_ready"] if p else 0), r["t_ready"])
           for p, r in zip([None] + recs, recs)]
    assert all(a < b for a, b in own)
    assert all(own[i][1] <= own[i + 1][0] for i in range(len(own) - 1))
    count, total = served["tpot"]
    assert count == len(recs)
    assert total == pytest.approx(
        sum((b - a) / 1e9 / r["k"] for (a, b), r in zip(own, recs)),
        rel=1e-6)
    old = [(r["t_disp0"], r["t_ready"]) for r in recs]
    overlapping = [i for i in range(1, len(old))
                   if old[i][0] < old[i - 1][1]]
    chained = [i for i, r in enumerate(recs) if r["chained"]]
    assert overlapping == chained
    assert bool(chained) == served["async"]


def test_tracing_off_the_log_fills_and_no_span_is_recorded(served):
    assert served["records"] and served["new_spans"] == 0


def test_tracing_on_fans_the_record_out_as_spans(model):
    # a test before this one may have left the span ring at a few entries
    kept = tracing._ring.maxlen
    tracing.enable(ring=4096)
    try:
        eng = _engine(model, True)
        _drive(eng, _requests(1))
        spans = tracing.last_spans(10_000)
    finally:
        tracing.disable()
        tracing.clear()
        tracing.enable(ring=kept)
        tracing.disable()
    steps = [s for s in spans if s["name"] == "serve.step"
             and s["attrs"]["engine"] == eng._engine_seq]
    assert steps and all(s["parent"] is None for s in steps)
    seqs = {s["attrs"]["step"] for s in steps}
    for name in ("serve.pack", "serve.decode", "serve.decode.sync",
                 "serve.emit"):
        mine = [s for s in spans if s["name"] == name
                and s["attrs"]["step"] in seqs]
        assert len(mine) == len(steps), name
        assert all(s["parent"] == "serve.step" and s["dur_us"] >= 0
                   for s in mine)
    admits = [s for s in spans if s["name"] == "serve.admit"
              and s.get("attrs", {}).get("step") in seqs]
    assert sorted(s["attrs"]["rid"] for s in admits) == list(range(6))
    one = steps[0]["attrs"]
    assert one["kind"] == "mixed" and one["rows"] and one["emits"]
    # the parent carries the record's counts and none of its stamps
    assert sorted(one) == ["admits", "chained", "cold", "emits", "engine",
                           "k", "kind", "paged_walk", "ragged_walk", "rows",
                           "step"]
    # a phase lies inside its step's life
    by_step = {s["attrs"]["step"]: s for s in steps}
    for s in spans:
        if s["name"].startswith("serve.") and s.get("parent") == "serve.step" \
                and s["name"] != "serve.admit" and s["attrs"]["step"] in seqs:
            p = by_step[s["attrs"]["step"]]
            assert p["ts_us"] <= s["ts_us"]
            assert s["ts_us"] + s["dur_us"] <= p["ts_us"] + p["dur_us"] + 1e-3
    assert registry.get("span.serve.decode.sync_s").count >= len(steps)


def test_the_ring_is_bounded():
    assert tracing.steps.maxlen == 8192
    kept = tracing.step_records()
    try:
        for i in range(tracing.steps.maxlen + 10):
            tracing.commit_step({"seq": -1, "i": i})
        assert len(tracing.steps) == tracing.steps.maxlen
        assert tracing.step_records(3)[-1]["i"] == tracing.steps.maxlen + 9
        assert len(tracing.step_records(3)) == 3
    finally:
        tracing.steps.clear()
        tracing.steps.extend(kept)


def test_seq_is_process_wide():
    a, b = tracing.new_step(engine=0), tracing.new_step(engine=1)
    assert b["seq"] == a["seq"] + 1 and a["engine"] == 0


def test_annotation_is_the_profilers_own():
    import jax

    assert isinstance(tracing.annotation("serve.step"),
                      jax.profiler.TraceAnnotation)
    src = inspect.getsource(tracing)
    assert "import jax" not in src and "from jax" not in src


def test_a_raise_in_a_phase_closes_its_annotation(model, monkeypatch):
    eng = _engine(model, True)
    seen = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("in", self.name))

        def __exit__(self, *exc):
            seen.append(("out", self.name))

    monkeypatch.setattr(tracing, "annotation", Ann)
    queue = deque(_requests(3)[:1])
    eng._admit_from(queue)

    def boom(*a, **k):
        raise RuntimeError("dispatch died")

    monkeypatch.setattr(eng.retry_policy, "run", boom)
    with pytest.raises(RuntimeError, match="dispatch died"):
        eng.step()
    assert eng._phase_ann is None
    opened = sorted(n for d, n in seen if d == "in")
    assert opened == sorted(n for d, n in seen if d == "out")
    assert ("in", "serve.decode") in seen and ("out", "serve.decode") in seen


def test_per_record_host_cost(model):
    """Everything the log adds to one dispatch — the record, seven stamps,
    four phase annotations, 16 rows and emits, the commit — against a
    dispatch of tens of milliseconds. Measured ~15 us; bound 500 us."""
    eng = _engine(model, True)
    rows = [(i, "d", 1, 100 + i) for i in range(16)]
    kept = tracing.step_records()
    n = 2_000

    def measure():
        t0 = time.perf_counter()
        for _ in range(n):
            eng._t_step0 = time.monotonic_ns()
            with tracing.annotation("serve.step"):
                step = eng._begin_step("decode", 8, None)
                eng._phase("serve.decode", step, "t_disp0")
                eng._phase(None, step, "t_disp1")
                eng._dispatched(step, False, list(rows))
                step["t_sync0"] = time.monotonic_ns()
                with tracing.annotation("serve.decode.sync"):
                    pass
                step["t_ready"] = time.monotonic_ns()
                with tracing.annotation("serve.emit"):
                    step["emits"] = [(rid, 8) for rid, _, _, _ in rows]
                step["t_emit1"] = time.monotonic_ns()
                tracing.commit_step(step)
        return (time.perf_counter() - t0) / n

    try:
        per_record = min(measure() for _ in range(3))
    finally:
        tracing.steps.clear()
        tracing.steps.extend(kept)
    assert per_record < 500e-6, (
        f"the step log costs {per_record * 1e6:.1f} us a dispatch")


def test_the_frontend_path_logs_and_reports(model):
    """Driven by ServingFrontend's dispatcher thread: the log joins a
    handle's request through `t_admit`."""
    from paddle_tpu.serving import ServingFrontend

    eng = _engine(model, True)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
               for n in (70, 12, 33)]
    with ServingFrontend([eng]) as fe:
        handles = [fe.submit(p, 6) for p in prompts]
        rows = [h.result(timeout=120) for h in handles]
    assert [len(r) - len(p) for r, p in zip(rows, prompts)] == [6, 6, 6]
    recs = [r for r in tracing.step_records()
            if r["engine"] == eng._engine_seq]
    admits = {a[2]: a for r in recs for a in r["admits"]}
    for h, p in zip(handles, prompts):
        q = h._req
        assert admits[q.t_admit] == (q.rid, q.t_enqueue, q.t_admit, len(p))
    assert sum(n for r in recs for _, n in r["emits"]) == 18


def test_admissions_that_never_reach_a_dispatch_stay_bounded(model):
    """Requests admitted and gone again before any dispatch (cancelled mid
    prefill; a prefill-role replica's, detached) are never drained into a
    record: the engine keeps the last 4 x max_seqs of them."""
    eng = _engine(model, True)
    rng = np.random.RandomState(9)
    rid = 0
    for _ in range(6):
        queue = deque(EngineRequest(rid + i, rng.randint(
            1, 128, (9,)).astype(np.int32), 2) for i in range(4))
        rid += 4
        eng._admit_from(queue)
        assert not queue
        for slot in list(eng._prefilling):
            eng._abort_prefill(slot)
    assert len(eng._admits) == eng._admits.maxlen == 16
    last = EngineRequest(rid, rng.randint(1, 128, (9,)).astype(np.int32), 2)
    _drive(eng, [last])
    recs = [r for r in tracing.step_records()
            if r["engine"] == eng._engine_seq]
    assert [a[0] for a in recs[0]["admits"]] == list(range(9, 25))
    assert not any(r["admits"] for r in recs[1:]) and not eng._admits
