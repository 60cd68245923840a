"""MiniCPM-SALA (models/minicpm_sala.py) at a tiny size on the CPU, against
the benchmark's plain float32 reference (benchmarks/sala_reference.py,
independent of paddle_tpu.models): the full forward, chunked prefill then
decode through selected K/V pages and state slots, the serving engine's one
ragged step and one decode block over a cache spec a LAYER, and what that
spec refuses.

Tiny = four layers (one `minicpm4`, three `lightning-attn`), blocks of 16
keys, compressed keys of 8 by 4, top 4 with a window of 2 blocks, dense up to
64 keys: every sequence here runs past `dense_len`, so selection is live."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from benchmarks import sala_reference as ref
from paddle_tpu.framework.core import Tensor
from paddle_tpu.inference.continuous import (
    ContinuousBatchingEngine, EngineRequest,
)
from paddle_tpu.models.minicpm_sala import (
    MinicpmSalaConfig, MinicpmSalaForCausalLM, minicpm_sala_tiny,
)
from paddle_tpu.observability import tracing
from paddle_tpu.ops.cache_specs import LayerCacheSpecs

REF_KEYS = ("num_hidden_layers", "mixer_types", "hidden_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "lightning_nh", "lightning_nkv", "lightning_head_dim",
            "rms_norm_eps", "rope_theta", "scale_emb", "scale_depth",
            "dim_model_base", "tie_word_embeddings")
PAGE = 16


def ref_cfg(cfg):
    out = {k: getattr(cfg, k) for k in REF_KEYS}
    out["published"] = {"num_hidden_layers": cfg.residual_depth}
    out["sparse_config"] = {
        k: getattr(cfg.sparse, k) for k in (
            "kernel_size", "kernel_stride", "block_size", "topk",
            "init_blocks", "window_size", "dense_len")}
    return out


@pytest.fixture(scope="module")
def model():
    """Layers 9-12 of a published depth of 32 (the residual scale reads the
    published depth, not the slice's)."""
    paddle.seed(3)
    m = MinicpmSalaForCausalLM(minicpm_sala_tiny(residual_depth=32))
    m.eval()
    return m


def engine(model, **kw):
    return ContinuousBatchingEngine(
        model, **{**dict(max_seqs=4, page_size=PAGE, max_len=256,
                         prefill_chunk=32, decode_block=4), **kw})


def test_config_takes_the_published_keys_and_refuses_other_switches():
    cfg = MinicpmSalaConfig(num_hidden_layers=8, residual_depth=32,
                            mup_denominator=32, model_type="minicpm_sala",
                            rand_init=False)
    assert cfg.mixer_types == (["minicpm4"] + ["lightning-attn"] * 3) * 2
    assert abs(cfg.residual_scale - 1.4 / 32 ** 0.5) < 1e-12
    with pytest.raises(ValueError, match="mixer_types"):
        MinicpmSalaConfig(num_hidden_layers=2, mixer_types=["mamba", "x"])
    with pytest.raises(ValueError, match="published switches"):
        MinicpmSalaConfig(attn_use_rope=True)


def test_full_forward_matches_the_reference(model):
    ids = np.random.RandomState(1).randint(1, 512, (150,)).astype(np.int32)
    own = np.asarray(model(Tensor(jnp.asarray(ids[None])))._data[0])
    want = ref.forward(ref_cfg(model.config), model.raw_state_dict(), ids)
    np.testing.assert_allclose(own, want, atol=2e-5)
    # a batch is rows of one packed stream: each sequence on its own
    two = np.stack([ids[:90], ids[60:]])
    own2 = np.asarray(model(Tensor(jnp.asarray(two)))._data)
    np.testing.assert_allclose(own2[0], want[:90], atol=2e-5)
    np.testing.assert_allclose(own2[1], ref.forward(
        ref_cfg(model.config), model.raw_state_dict(), ids[60:]), atol=2e-5)


def _through_the_caches(model, ids, n_prompt, chunk):
    """Logits at every position of `ids`, made as the engine makes them:
    the prompt in chunks through each layer's ragged view, then one token a
    step through its decode view (teacher-forced), row 1 of 2; row 0 is
    dead and its state slot must come back as it went in."""
    spec = model.serving_cache_spec()
    assert isinstance(spec, LayerCacheSpecs)
    n_pages = -(-len(ids) // PAGE)
    pools = spec.make_pools(1 + n_pages, PAGE, jnp.float32, max_seqs=2)
    marked = [tuple(a.at[0].set(7.0) if s.has_state else a for a in pool)
              for s, pool in zip(spec.layers, pools)]
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

    out, pools = [], marked
    for pos in range(0, n_prompt, chunk):
        take = min(chunk, n_prompt - pos)
        tok = np.zeros(T, np.int32)
        tok[:take] = ids[pos:pos + take]
        logits, pools = prefill(pools, jnp.asarray(tok), jnp.int32(pos),
                                jnp.int32(take))
        out.append(np.asarray(logits[:take]))
    for pos in range(n_prompt, len(ids)):
        logits, pools = decode(pools, jnp.int32(ids[pos]), jnp.int32(pos))
        out.append(np.asarray(logits)[None])
    for s, pool in zip(spec.layers, pools):
        if s.has_state:   # the dead row's slot, through every step
            assert (np.asarray(pool[0][0]) == 7.0).all()
    return np.concatenate(out)


@pytest.mark.parametrize("chunk", [40, 1])
def test_chunked_prefill_then_decode_through_pages_and_state(model, chunk):
    """Logits at EVERY position, past `dense_len` of 64; chunk 1 sends the
    whole prompt down the decode forms as one-token spans. The live row's
    slot starts marked too: a row at length 0 reads its state as zeros."""
    ids = np.random.RandomState(2).randint(1, 512, (140,)).astype(np.int32)
    got = _through_the_caches(model, ids, n_prompt=110, chunk=chunk)
    want = ref.forward(ref_cfg(model.config), model.raw_state_dict(), ids)
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_engine_end_to_end_greedy_tokens_and_step_log(model):
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 512, (n,)).astype(np.int32)
               for n in (150, 9, 70, 1)]
    tracing.clear()
    eng = engine(model)
    outs = eng.serve(prompts, max_new_tokens=12)
    cfg, w = ref_cfg(model.config), model.raw_state_dict()
    for prompt, out in zip(prompts, outs):
        out = np.asarray(out)
        want = ref.forward(cfg, w, out)[len(prompt) - 1:-1].argmax(-1)
        np.testing.assert_array_equal(out[len(prompt):], want)
    recs = [r for r in tracing.step_records()
            if r["engine"] == eng._engine_seq]
    assert recs and all(set(r["counters"]) == {
        "sparse_keys_kept", "sparse_keys_visible", "state_rows"}
        for r in recs)
    for r in recs:
        c, (used, total), (held, slots) = r["counters"], r["pages"], r["slots"]
        assert 0 < c["sparse_keys_kept"] <= c["sparse_keys_visible"]
        assert 0 < c["state_rows"] <= r["k"] * slots
        assert 0 < used <= total == eng.num_pages - 1
        assert 0 < held <= slots == 4
    # the 150-token prompt's later chunks select: fewer keys kept than seen
    assert any(r["counters"]["sparse_keys_kept"]
               < r["counters"]["sparse_keys_visible"] for r in recs)
    # one sparse layer's K, V and compressed plane; three layers' slots
    assert eng.pool_bytes() == (
        eng.num_pages * 2 * (2 * PAGE + 4) * 32 * 4 + 3 * 4 * 4 * 32 * 32 * 4)
    # both step programs hold every scope the model names
    eng.warmup(buckets=[64])
    scopes = {name for key in ("serve.ragged[", "serve.decode_block[")
              for prog, table in tracing.program_scopes.items()
              if prog.startswith(key) for name in set(table.values())}
    assert set(model.serving_scopes) <= scopes


def test_a_reused_slot_serves_like_a_fresh_engine(model):
    """Two slots, five requests one after the other: every later request
    lands on a slot (and pages) an earlier one left its state in."""
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, 512, (n,)).astype(np.int32)
               for n in (90, 40, 75, 21, 66)]
    used = engine(model, max_seqs=2).serve(prompts, max_new_tokens=6)
    for prompt, out in zip(prompts, used):
        fresh = engine(model, max_seqs=2).serve([prompt], max_new_tokens=6)[0]
        np.testing.assert_array_equal(np.asarray(out), np.asarray(fresh))


def test_dead_rows_leave_their_state_untouched(model):
    """One request in an engine of 4 rows: the three empty slots ride every
    scan step and the mixed step's pad tokens its stream; their slots stay
    as they were, and each dispatch counts only the live row."""
    prompt = np.random.RandomState(5).randint(1, 512, (80,)).astype(np.int32)
    tracing.clear()
    eng = engine(model)
    mark = lambda pool, spec: (tuple(a.at[1:].set(7.0) for a in pool)
                               if spec.has_state else pool)
    eng.pools = [mark(p, s) for p, s in zip(eng.pools, eng._layer_specs)]
    eng.free_slots = [3, 2, 1, 0]                 # the request takes slot 0
    eng.serve([prompt], max_new_tokens=9)
    for pool, spec in zip(eng.pools, eng._layer_specs):
        if spec.has_state:
            assert (np.asarray(pool[0][1:]) == 7.0).all()
            assert not (np.asarray(pool[0][0]) == 7.0).all()
    recs = [r for r in tracing.step_records()
            if r["engine"] == eng._engine_seq]
    assert len(recs) >= 3
    for r in recs:
        assert 0 < r["counters"]["state_rows"] <= r["k"]
        assert r["slots"] == (1, 4)


@pytest.mark.parametrize("plane, kwargs", [
    ("prefix cache", dict(enable_prefix_cache=True)),
    ("kv_cache_dtype", dict(kv_cache_dtype="int8")),
    ("page_size", dict(page_size=32)),
])
def test_a_plane_that_cannot_take_these_layers_refuses(model, plane, kwargs):
    with pytest.raises(ValueError, match=plane):
        engine(model, **kwargs)


def test_handoff_refuses_state_slots_by_name(model):
    eng = engine(model)
    with pytest.raises(ValueError, match="export_pages.*StateSlotSpec"):
        eng.export_pages(0)
    with pytest.raises(ValueError, match="adopt_request.*state slot"):
        eng.adopt_request(EngineRequest(0, np.ones(3, np.int32), 2), {})
    # the LoRA planes run the same views a layer: taken, and they compile
    assert eng._cache_spec.refuses("lora") is None
    eng.warmup(buckets=[8], lora_ranks=(2,))
    assert len(eng._lora_ragged_fns) == len(eng._lora_block_fns) == 1
    with pytest.raises(ValueError, match="selected K/V pages"):
        model(Tensor(jnp.ones((1, 1), jnp.int32)),
              past_key_values=[(jnp.zeros(1), jnp.zeros(1))] * 4)
    assert [s.kind for s in model.serving_cache_spec().layers] == [
        "selected K/V pages"] + ["state slots"] * 3
