"""Phi-4-mini-flash (models/phi4flash.py) at a tiny size on the CPU, against
the benchmark's plain float32 reference (benchmarks/phi4flash_reference.py,
independent of paddle_tpu): the full forward, chunked prefill then decode
through state slots, window rings and the one K/V pool, the serving engine's
mixed step (trunk on the packed stream, cross-decoder on the span ends) and
decode block, what the cache spec refuses, and planted faults that each have
to fail the comparison aimed at them.

Tiny = eight layers (Mamba, window, Mamba, window, memory, full, GMU, cross:
every kind), 8 query heads over 4 K/V heads of 16, a window of 32 keys, pages
of 16."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from benchmarks import phi4flash_reference as ref
from paddle_tpu.framework.core import Tensor
from paddle_tpu.inference.continuous import (
    ContinuousBatchingEngine, EngineRequest,
)
from paddle_tpu.models import phi4flash as pf
from paddle_tpu.observability import tracing
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops import selective_scan as ssm
from paddle_tpu.ops.cache_specs import LayerCacheSpecs

REF_KEYS = ("num_hidden_layers", "mb_per_layer", "hidden_size",
            "num_attention_heads", "num_key_value_heads", "sliding_window",
            "layer_norm_eps", "mamba_d_state")
PAGE = 16


def ref_cfg(cfg):
    return {k: getattr(cfg, k) for k in REF_KEYS}


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    m = pf.Phi4FlashForCausalLM(pf.phi4flash_tiny())
    m.eval()
    return m


def engine(model, **kw):
    return ContinuousBatchingEngine(
        model, **{**dict(max_seqs=4, page_size=PAGE, max_len=512,
                         prefill_chunk=32, decode_block=4), **kw})


def reference(model, ids):
    return ref.forward(ref_cfg(model.config), model.raw_state_dict(), ids)


def test_config_derives_the_published_layer_table():
    cfg = pf.Phi4FlashConfig(num_hidden_layers=32, model_type="phi4flash",
                             sliding_window=512)
    kinds = cfg.layer_kinds
    assert [kinds.count(k) for k in (pf.MAMBA, pf.SWA, pf.MEMORY, pf.FULL,
                                     pf.GMU, pf.CROSS)] == [8, 8, 1, 1, 7, 7]
    assert kinds[:2] == [pf.MAMBA, pf.SWA] and kinds[16:20] == [
        pf.MEMORY, pf.FULL, pf.GMU, pf.CROSS]
    assert (cfg.kv_layer, cfg.tail_start, cfg.dt_rank) == (17, 18, 8)
    assert kinds == [{"memory": pf.MEMORY}.get(k, k) for k in ref.layer_kinds(
        {"num_hidden_layers": 32, "mb_per_layer": 2})]
    assert abs(cfg.lambda_init(17) - ref.lambda_init(17)) < 1e-12
    with pytest.raises(ValueError, match="layer table"):
        pf.Phi4FlashConfig(num_hidden_layers=6)
    with pytest.raises(ValueError, match="published switches"):
        pf.Phi4FlashConfig(mlp_bias=True)


def test_seeded_init_is_mambas_and_peaks_the_scores(model):
    w = model.raw_state_dict()
    a_log = np.asarray(w["model.layers.0.attn.A_log"])
    np.testing.assert_allclose(np.exp(a_log[0]), np.arange(1, 17), rtol=1e-6)
    assert (np.asarray(w["model.layers.0.attn.D"]) == 1).all()
    dt = np.log1p(np.exp(np.asarray(w["model.layers.0.attn.dt_proj.bias"])))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 0.1 * 1.01
    wqkv = np.asarray(w["model.layers.1.attn.Wqkv.weight"])
    cfg = model.config
    qk = cfg.hidden_size + cfg.num_key_value_heads * cfg.head_dim
    assert abs(wqkv[:, :qk].std() / cfg.qk_init_std - 1) < 0.05
    assert abs(wqkv[:, qk:].std() / cfg.initializer_range - 1) < 0.05
    assert w["model.layers.7.attn.Wqkv.weight"].shape == (128, 128)  # q alone


def test_full_forward_matches_the_reference(model):
    """A batch is rows of one packed stream: each sequence on its own."""
    two = np.random.RandomState(1).randint(1, 512, (2, 150)).astype(np.int32)
    own = np.asarray(model(Tensor(jnp.asarray(two)))._data)
    for got, ids in zip(own, two):
        np.testing.assert_allclose(got, reference(model, ids), atol=3e-5)


def test_the_padded_query_form_is_the_four_product_form(model):
    """`[q1 | 0]`, `[0 | q2]` over stored `[k1 | k2]`, `[v1 | v2]` by plain
    grouped-query attention (dense, no kernel) equals the reference's
    four products: the trick is tested, not assumed."""
    cfg = model.config
    attn = model.model.layers[cfg.kv_layer].attn
    w = {k.split("attn.", 1)[1]: v for k, v in model.raw_state_dict().items()
         if k.startswith(f"model.layers.{cfg.kv_layer}.attn.")}
    x = jnp.asarray(np.random.RandomState(7).randn(40, cfg.hidden_size),
                    jnp.float32)
    H, Hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    qkv = x @ w["Wqkv.weight"]
    q = attn._padded_queries(qkv[:, :H * d])                  # [S, H, 2d]
    k = qkv[:, H * d:(H + Hkv) * d].reshape(40, Hkv // 2, 2 * d)
    v = qkv[:, (H + Hkv) * d:].reshape(40, Hkv // 2, 2 * d)
    g = H // (Hkv // 2)
    s = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, g, axis=1)) * d ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((40, 40), bool))[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khe->qhe", jax.nn.softmax(s, axis=-1),
                   jnp.repeat(v, g, axis=1))
    got = attn._combine(a) @ w["out_proj.weight"]
    want, _ = ref.diff_attention(
        ref_cfg(cfg), {"attn." + k: v for k, v in w.items()},
        ref.lambda_init(cfg.kv_layer), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def _through_the_caches(model, ids, n_prompt, chunk, ring_chunk=None,
                        first=None):
    """Logits at every position of `ids`, made as the engine makes them:
    the prompt in chunks (the first of `first` tokens) through each layer's
    ragged view, then one token a step through its decode view
    (teacher-forced), row 1 of 2; row 0 is dead and its slots must come back
    as they went in. The live row's slots start marked too: a row at length
    0 reads them as zeros. `ring_chunk`: the chunk the rings are sized for."""
    spec = model.serving_cache_spec()
    assert isinstance(spec, LayerCacheSpecs)
    n_pages = -(-len(ids) // PAGE)
    pools = spec.make_pools(1 + n_pages, PAGE, jnp.float32, max_seqs=2,
                            prefill_chunk=chunk if ring_chunk is None else ring_chunk)
    marked = [tuple(jnp.full_like(a, 7.0) for a in pool) if s.has_state
              else pool for s, pool in zip(spec.layers, pools)]
    table = np.zeros((2, n_pages), np.int32)
    table[1] = 1 + np.arange(n_pages)
    table = jnp.asarray(table)
    T = chunk + 2

    @jax.jit
    def prefill(pools, tok, pos, take):
        token_pos = jnp.where(jnp.arange(T) < take, pos + jnp.arange(T), 0)
        caches = [s.ragged(
            pool, table, jnp.stack([0, pos + take]),
            jnp.stack([0, 0, take]), jnp.ones(T, jnp.int32), token_pos,
            jnp.arange(T) < take) for s, pool in zip(spec.layers, pools)]
        logits, presents = model(Tensor(tok[None]),
                                 position_ids=Tensor(token_pos[None]),
                                 past_key_values=caches)
        return logits._data[0], [s.pool_of(p)
                                 for s, p in zip(spec.layers, presents)]

    @jax.jit
    def decode(pools, tok, pos):
        caches = [s.paged(pool, table, jnp.stack([0, pos]),
                          jnp.asarray([False, True]))
                  for s, pool in zip(spec.layers, pools)]
        logits, presents = model(
            Tensor(jnp.stack([0, tok])[:, None]),
            position_ids=Tensor(jnp.stack([0, pos])[:, None]),
            past_key_values=caches)
        return logits._data[1, 0], [s.pool_of(p)
                                    for s, p in zip(spec.layers, presents)]

    out, pools, pos = [], marked, 0
    while pos < n_prompt:
        take = min(first or chunk, chunk, n_prompt - pos)
        first = None
        tok = np.zeros(T, np.int32)
        tok[:take] = ids[pos:pos + take]
        logits, pools = prefill(pools, jnp.asarray(tok), jnp.int32(pos),
                                jnp.int32(take))
        out.append(np.asarray(logits[:take]))
        pos += take
    for pos in range(n_prompt, len(ids)):
        logits, pools = decode(pools, jnp.int32(ids[pos]), jnp.int32(pos))
        out.append(np.asarray(logits)[None])
    for s, pool in zip(spec.layers, pools):
        if s.has_state:   # the dead row's slot, through every step
            assert all((np.asarray(a[0]) == 7.0).all() for a in pool)
    return np.concatenate(out)


@pytest.fixture(scope="module")
def row(model):
    """A row of 190 tokens (150 of prompt): its ring of 5 pages wraps more
    than twice; and the reference's logits."""
    ids = np.random.RandomState(2).randint(1, 512, (190,)).astype(np.int32)
    return ids, reference(model, ids)


@pytest.mark.parametrize("chunk, first", [
    (20, None),   # under the window
    (32, 2),      # the window; the second span starts mid convolution tail
    (50, None),   # over the window
])
def test_chunked_prefill_then_decode_through_slots_rings_and_pool(
        model, row, chunk, first):
    ids, want = row
    got = _through_the_caches(model, ids, 150, chunk, first=first)
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_engine_end_to_end_greedy_tokens_and_step_log(model):
    """The mixed step's trunk / tail / head and the decode block, against
    the reference's argmax on the served rows; the 300-token row's ring of
    5 pages (80 keys) wraps more than three times."""
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 512, (n,)).astype(np.int32)
               for n in (270, 9, 70, 1)]
    tracing.clear()
    eng = engine(model)
    outs = eng.serve(prompts, max_new_tokens=30)
    for prompt, out in zip(prompts, outs):
        out = np.asarray(out)
        want = reference(model, out)[len(prompt) - 1:-1].argmax(-1)
        np.testing.assert_array_equal(out[len(prompt):], want)
    recs = [r for r in tracing.step_records()
            if r["engine"] == eng._engine_seq]
    assert recs and all(set(r["counters"]) == set(
        model.serving_counter_names) for r in recs)
    n_tail = 2                                   # the tiny tail: GMU, cross
    for r in recs:
        c, (used, total), (held, slots) = r["counters"], r["pages"], r["slots"]
        assert 0 < c["swa_keys_visited"] <= c["swa_keys_causal"]
        assert 0 < c["state_rows"] <= r["k"] * slots
        assert 0 < used <= total == eng.num_pages - 1
        assert 0 < held <= slots == 4
        if r["kind"] == "mixed":   # one tail token a row with a span
            spans = sum(1 for _, _, q, _ in r["rows"] if q > 0)
            tokens = sum(q for _, _, q, _ in r["rows"])
            assert c["tail_tokens"] == n_tail * spans
            assert c["trunk_tokens"] == 6 * tokens
        else:
            assert c["tail_tokens"] == c["trunk_tokens"] == 0
    # the long prompt's later chunks: the window walk stops growing
    assert any(2 * r["counters"]["swa_keys_visited"]
               < r["counters"]["swa_keys_causal"] for r in recs)
    # layer 5's K and V pages alone are the allocator's; 2 rings of
    # 1 + 4 x 5 pages; 3 slots of state and convolution tail; nothing above
    hp, d2, f = 2, 32, 4
    assert eng.pool_bytes() == (
        2 * hp * eng.num_pages * PAGE * d2 * f
        + 2 * 2 * hp * (1 + 4 * 5) * PAGE * d2 * f
        + 3 * 4 * (16 + 3) * 256 * f)
    assert [len(p) for p in eng.pools] == [2, 2, 2, 2, 2, 2, 0, 0]
    # both step programs hold every scope the model names
    eng.warmup(buckets=[64])
    scopes = {name for key in ("serve.ragged[", "serve.decode_block[")
              for prog, table in tracing.program_scopes.items()
              if prog.startswith(key) for name in set(table.values())}
    assert set(model.serving_scopes) <= scopes


def test_the_mixed_step_runs_the_cross_decoder_on_the_rows_alone(model):
    """In the mixed program the cross-decoder's matmuls have max_seqs rows,
    not T: no GMU or cross projection of the packed stream's width."""
    eng = engine(model)
    eng.warmup(buckets=[8])
    from paddle_tpu.observability import compilemem

    (key,) = [k for k in compilemem.memory.programs()
              if k.startswith("serve.ragged[")][-1:]
    text = compilemem.memory.compiled(key).as_text()
    T, S = eng._ragged_tokens, eng.max_seqs
    gmu = [l for l in text.splitlines() if "sambay.gmu" in l and "dot" in l]
    assert gmu and all(f"f32[{S}," in l and f"f32[{T}," not in l
                       for l in gmu), gmu[:3]


def test_a_reused_slot_and_ring_serve_like_a_fresh_engine(model):
    """Two slots, five requests one after the other: every later request
    lands on a slot and a ring an earlier one left its state and keys in."""
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, 512, (n,)).astype(np.int32)
               for n in (90, 2, 75, 21, 66)]
    used = engine(model, max_seqs=2).serve(prompts, max_new_tokens=6)
    # five slots: every request on a slot and a ring nothing has touched
    fresh = engine(model, max_seqs=5).serve(prompts, max_new_tokens=6)
    for out, alone in zip(used, fresh):
        np.testing.assert_array_equal(np.asarray(out), np.asarray(alone))


# ---- planted faults: each has to fail the comparison aimed at it ----------

def _fault_window(delta):
    def plant(mp, model):
        for layer in model.model.layers:
            if layer.kind == pf.SWA:
                mp.setattr(layer.attn, "window", layer.attn.window + delta)
    return plant


def _fault_lambda(mp, model):
    attn = model.model.layers[model.config.kv_layer].attn
    mp.setattr(attn, "lambda_init", model.config.lambda_init(1))


def _fault_memory_after_gate(mp, model):
    mamba = model.model.layers[model.config.kv_layer - 1].attn
    forward = type(mamba).forward

    def gated(self, x, pc):
        out, y, present = forward(self, x, pc)
        z = jnp.split(x @ self.in_proj.weight._data, 2, axis=-1)[1]
        return out, y * jax.nn.silu(z), present
    mp.setattr(type(mamba), "forward", gated)


def _fault_cross_reads_own_pool(mp, model):
    """A cross layer over pages of its own, which nothing ever wrote."""
    tail = pf.Phi4FlashModel._tail

    def own_pool(self, h, memory, kv):
        empty = type(kv)(jnp.zeros_like(kv.k_pages), jnp.zeros_like(kv.v_pages),
                         *jax.tree_util.tree_leaves(kv)[2:])
        return tail(self, h, memory, empty)
    mp.setattr(pf.Phi4FlashModel, "_tail", own_pool)


def _fault_stale_slot(mp, model):
    """A slot that is not zeroed when its row starts: the marked state and
    convolution tail of the driver are read."""
    # the reset reads `kv_lens - q_lens == 0`: hide it
    mp.setattr(pf, "causal_conv_ragged", _unfresh(ssm.causal_conv_ragged, 4))
    mp.setattr(pf, "selective_scan_ragged",
               _unfresh(ssm.selective_scan_ragged, 7))


def _unfresh(fn, kv_lens_at):
    """`fn` told that every row had a token before its span."""
    def wrapped(*args, **kw):
        args = list(args)
        cu = args[kv_lens_at + 1]
        started = (cu[1:] - cu[:-1]) == args[kv_lens_at]
        args[kv_lens_at] = args[kv_lens_at] + started   # before: 0 -> 1
        return fn(*args, **kw)
    return wrapped


@pytest.mark.parametrize("fault", [
    _fault_window(-1), _fault_window(+1), _fault_lambda,
    _fault_memory_after_gate, _fault_cross_reads_own_pool, _fault_stale_slot,
], ids=["window-31", "window-33", "lambda0-of-layer-1", "memory-after-gate",
        "cross-reads-own-pool", "stale-slot"])
def test_a_planted_fault_in_the_model_fails_the_comparison(
        model, row, monkeypatch, fault):
    ids, want = row
    fault(monkeypatch, model)
    got = _through_the_caches(model, ids[:120], 90, 32)
    assert np.abs(got - want[:120]).max() > 1e-3


def test_a_ring_one_page_short_of_the_keys_fails_the_comparison(model, row):
    """A span of 32 tokens and its first query's 31 predecessors are 63
    keys. `WindowRingSpec.ring_pages` holds 5 pages for them, one more than
    their 4 (the writers lay rows over a page, not pages, so a ring of 64
    tokens still holds all 63: the fifth is slack the formula leaves for a
    writer of whole pages). At 3 pages a span's last keys lie over keys its
    first queries still see."""
    ids, want = row
    got = _through_the_caches(model, ids[:120], 90, 32, ring_chunk=32 - PAGE,
                              first=2)
    np.testing.assert_allclose(got, want[:120], atol=5e-5)
    got = _through_the_caches(model, ids[:120], 90, 32,
                              ring_chunk=32 - 2 * PAGE, first=2)
    assert np.abs(got - want[:120]).max() > 1e-3


def test_the_tail_gathered_one_token_early_serves_other_tokens(
        model, monkeypatch):
    prompt = np.random.RandomState(6).randint(1, 512, (45,)).astype(np.int32)
    good = np.asarray(engine(model).serve([prompt], max_new_tokens=8)[0])
    tail = model.serving_tail
    monkeypatch.setattr(
        model, "serving_tail",
        lambda ov, out, at, presents: tail(ov, out, at - 1, presents),
        raising=False)
    bad = np.asarray(engine(model).serve([prompt], max_new_tokens=8)[0])
    want = reference(model, good)[len(prompt) - 1:-1].argmax(-1)
    np.testing.assert_array_equal(good[len(prompt):], want)
    assert (bad[len(prompt):] != want).any()


# ---- what the spec takes and refuses ---------------------------------------

def test_cache_spec_sizes_rings_by_rows_and_chunk_alone(model):
    spec = model.serving_cache_spec()
    assert [s.kind for s in spec.layers] == [
        "state slots", "window ring", "state slots", "window ring",
        "state slots", "K/V pages", "no pool", "no pool"]
    assert spec.log_pages and spec.has_state
    for num_pages in (9, 900):
        pools = spec.make_pools(num_pages, PAGE, jnp.float32, max_seqs=3,
                                prefill_chunk=64)
        ring = -(-(32 + 64) // PAGE) + 1
        assert pools[1][0].shape == (2, 1 + 3 * ring, PAGE, 32)
        assert pools[5][0].shape == (2, num_pages, PAGE, 32)
        assert pools[0][0].shape == (3, 16, 256) and pools[0][1].shape == (
            3, 3, 256)
        assert pools[6] == pools[7] == ()
    table = pa.WindowRingSpec.table(pools[1], jnp.zeros((3, 40), jnp.int32))
    assert table.shape == (3, 40)
    np.testing.assert_array_equal(
        np.asarray(table[1, :9]), 1 + ring + np.arange(9) % ring)
    with pytest.raises(ValueError, match="prefill_chunk"):
        spec.make_pools(9, PAGE, jnp.float32, max_seqs=3)


@pytest.mark.parametrize("plane, kwargs", [
    ("prefix cache", dict(enable_prefix_cache=True)),
    ("kv_cache_dtype", dict(kv_cache_dtype="int8")),
])
def test_a_plane_that_cannot_take_these_layers_refuses(model, plane, kwargs):
    with pytest.raises(ValueError, match=plane):
        engine(model, **kwargs)


def test_handoff_and_lora_refuse_by_name(model):
    eng = engine(model)
    with pytest.raises(ValueError, match="export_pages.*StateSlotSpec"):
        eng.export_pages(0)
    with pytest.raises(ValueError, match="adopt_request.*state slot"):
        eng.adopt_request(EngineRequest(0, np.ones(3, np.int32), 2), {})
    assert "trunk / tail" in eng._cache_spec.refuses("lora")
    assert "WindowRingSpec" in pa.WindowRingSpec(2, 32, 32).refuses("handoff")
    with pytest.raises(ValueError, match="state slot"):
        model(Tensor(jnp.ones((1, 1), jnp.int32)),
              past_key_values=[(jnp.zeros(1), jnp.zeros(1))] * 8)
