"""The DeepSeek-V3 / Kimi-K2 block (models/deepseek_v3.py) at a tiny size on
the CPU, against the benchmark's plain float32 reference
(benchmarks/kimi_reference.py, independent of paddle_tpu.models): latent
attention through the pool of latent pages (ops/latent_pool.py), both
attention paths, the dropless expert layer and its shares, and the serving
engine's model protocol.

Tiny = two heads' worth of every width, 16 experts top 4, YaRN factor 4
over an original context of 32, so every sequence here runs past it."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from benchmarks import kimi_reference as ref
from paddle_tpu.framework.core import Tensor
from paddle_tpu.incubate.distributed.models.moe import dropless
from paddle_tpu.inference.continuous import ContinuousBatchingEngine
from paddle_tpu.models.deepseek_v3 import (
    DeepseekV3ForCausalLM, deepseek_v3_tiny,
)
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.observability import tracing
from paddle_tpu.ops import latent_pool
from paddle_tpu.ops.mla_decode_attention import mla_decode_attention
from paddle_tpu.ops.mla_prefill_attention import mla_prefill_attention

REF_KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "kv_lora_rank", "v_head_dim", "rms_norm_eps", "rope_theta",
            "rope_scaling", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "num_hidden_layers",
            "first_k_dense_replace", "tie_word_embeddings", "first_expert")


def ref_cfg(cfg):
    return {k: getattr(cfg, k) for k in REF_KEYS}


def build(seed=3, **kw):
    """A tiny model with the gates' selection biases seeded non-zero."""
    paddle.seed(seed)
    model = DeepseekV3ForCausalLM(deepseek_v3_tiny(**kw))
    model.eval()
    rng = np.random.RandomState(seed)
    for name, p in model.state_dict().items():
        if name.endswith("e_score_correction_bias"):
            p.set_value(Tensor(jnp.asarray(rng.normal(0, 0.05, p.shape),
                                           jnp.float32)))
    return model


@pytest.fixture(scope="module")
def share_model():
    """Holds experts [4, 12) of 16: a share, so absent experts are left out
    by program and reference alike."""
    return build(first_expert=4, n_held_experts=8)


def test_full_forward_matches_the_reference(share_model):
    ids = np.random.RandomState(1).randint(1, 512, (100,)).astype(np.int32)
    own = np.asarray(share_model(Tensor(jnp.asarray(ids[None])))._data[0])
    want = ref.forward(ref_cfg(share_model.config),
                       share_model.raw_state_dict(), ids)
    np.testing.assert_allclose(own, want, atol=2e-5)


def _through_the_pool(model, ids, n_prompt, chunk, page=16):
    """Logits at every position of `ids`, made as the engine makes them:
    the prompt in chunks through LatentRaggedLayerCache, then one token a
    step through LatentPagedLayerCache (teacher-forced), row 0 of 2. Two
    jitted steps, lengths as operands, like the engine's two programs."""
    spec = model.serving_cache_spec()
    n_pages = -(-len(ids) // page)
    pools = spec.make_pools(1 + n_pages, page, jnp.float32)
    table = np.zeros((2, n_pages), np.int32)
    table[0] = 1 + np.arange(n_pages)
    table = jnp.asarray(table)
    T = chunk + 2

    @jax.jit
    def prefill(pools, tok, pos, take):
        token_pos = jnp.where(jnp.arange(T) < take, pos + jnp.arange(T), 0)
        caches = [spec.ragged(
            pool, table, jnp.stack([pos + take, 0]),
            jnp.stack([0, take, take]), jnp.zeros(T, jnp.int32), token_pos,
            jnp.arange(T) < take) for pool in pools]
        logits, presents = model(Tensor(tok[None]),
                                 position_ids=Tensor(token_pos[None]),
                                 past_key_values=caches)
        return logits._data[0], [spec.pool_of(p) for p in presents]

    @jax.jit
    def decode(pools, tok, pos):
        caches = [spec.paged(pool, table, jnp.stack([pos, 0]),
                             jnp.asarray([True, False]))
                  for pool in pools]
        logits, presents = model(
            Tensor(jnp.stack([tok, 0])[:, None]),
            position_ids=Tensor(jnp.stack([pos, 0])[:, None]),
            past_key_values=caches)
        return logits._data[0], [spec.pool_of(p) for p in presents]

    out = []
    for pos in range(0, n_prompt, chunk):
        take = min(chunk, n_prompt - pos)
        tok = np.zeros(T, np.int32)
        tok[:take] = ids[pos:pos + take]
        logits, pools = prefill(pools, jnp.asarray(tok), jnp.int32(pos),
                                jnp.int32(take))
        out.append(np.asarray(logits[:take]))
    for pos in range(n_prompt, len(ids)):
        logits, pools = decode(pools, jnp.int32(ids[pos]), jnp.int32(pos))
        out.append(np.asarray(logits))
    return np.concatenate(out)


@pytest.mark.parametrize("chunk", [24, 1])
def test_chunked_prefill_then_decode_through_the_latent_pool(share_model,
                                                             chunk):
    """Logits at EVERY position, past the original context of 32; chunk 1
    sends the whole prompt down the absorbed path as one-token spans."""
    ids = np.random.RandomState(2).randint(1, 512, (90,)).astype(np.int32)
    got = _through_the_pool(share_model, ids, n_prompt=70, chunk=chunk)
    want = ref.forward(ref_cfg(share_model.config),
                       share_model.raw_state_dict(), ids)
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_engine_end_to_end_greedy_tokens(share_model):
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 512, (n,)).astype(np.int32)
               for n in (150, 9, 70, 33)]
    tracing.clear()
    eng = ContinuousBatchingEngine(share_model, max_seqs=4, page_size=16,
                                   max_len=256, prefill_chunk=32,
                                   decode_block=4)
    outs = eng.serve(prompts, max_new_tokens=12)
    cfg, w = ref_cfg(share_model.config), share_model.raw_state_dict()
    for prompt, out in zip(prompts, outs):
        out = np.asarray(out)
        want = ref.forward(cfg, w, out)[len(prompt) - 1:-1].argmax(-1)
        np.testing.assert_array_equal(out[len(prompt):], want)
    # the step log carries the routing and pool counters of every dispatch
    recs = [r for r in tracing.step_records() if r["engine"] == eng._engine_seq]
    assert recs and all(set(r["counters"]) == {"moe_hit", "moe_assigned",
                                               "moe_max_load"} for r in recs)
    layers = 2  # expert layers of the tiny model
    for r in recs:
        c, (used, total) = r["counters"], r["pages"]
        assert 0 < c["moe_hit"] <= 8 * layers * r["k"]
        assert c["moe_max_load"] <= c["moe_assigned"]
        assert 0 < used <= total == eng.num_pages - 1
    assert eng.pool_bytes() == 3 * eng.num_pages * 16 * 128 * 4


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_absorbed_equals_expanded(impl):
    """One span through the expanded path, and each of its tokens alone
    through the absorbed path at the length it sees: the same numbers, on
    the XLA tier and through the kernel's own body (interpret mode)."""
    rng = np.random.RandomState(5)
    H, dn, dr, dv, C, bs = 2, 32, 16, 32, 32, 16
    past, n = 37, 21
    pages = np.zeros((8, bs, latent_pool.stored_width(C + dr)), np.float32)
    pages[1:6, :, :C + dr] = rng.normal(0, 1, (5, bs, C + dr))
    pages = jnp.asarray(pages)
    table = jnp.asarray([[1, 2, 3, 4, 5, 0], [0] * 6], jnp.int32)
    q_nope = jnp.asarray(rng.normal(0, 1, (n + 3, H, dn)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(0, 1, (n + 3, H, dr)), jnp.float32)
    w_uk = jnp.asarray(rng.normal(0, 0.2, (H, dn, C)), jnp.float32)
    w_uv = jnp.asarray(rng.normal(0, 0.2, (H, C, dv)), jnp.float32)
    expanded = mla_prefill_attention(
        q_nope, q_rope, pages, w_uk, w_uv,
        jnp.asarray([past + n, 0], jnp.int32), table,
        jnp.asarray([0, n, n], jnp.int32), 0.3)
    q_lat = jnp.einsum("bhd,hdc->bhc", q_nope[:n], w_uk)
    o_lat = mla_decode_attention(
        q_lat, q_rope[:n], pages, past + 1 + jnp.arange(n, dtype=jnp.int32),
        jnp.broadcast_to(table[0], (n, 6)), 0.3, impl=impl)
    absorbed = jnp.einsum("bhc,hcd->bhd", o_lat, w_uv)
    np.testing.assert_allclose(np.asarray(expanded[:n]), np.asarray(absorbed),
                               atol=2e-5)
    assert not np.asarray(expanded[n:]).any()    # pads and no span: zeros


# ---- the router and the expert layer ---------------------------------------

def _scores(x, gate_w):
    return 1.0 / (1.0 + np.exp(-(x @ gate_w.T)))


def test_bias_changes_the_choice_and_not_the_weight():
    rng = np.random.RandomState(7)
    x = rng.normal(0, 1, (12, 24)).astype(np.float32)
    gate_w = rng.normal(0, 0.3, (16, 24)).astype(np.float32)
    s = _scores(x, gate_w)
    bias = np.zeros(16, np.float32)
    bias[5] = 10.0                       # expert 5 is chosen by every token
    idx0, _ = dropless.route(jnp.asarray(x), jnp.asarray(gate_w),
                             jnp.zeros(16), 4, scaling=2.5)
    idx, w = dropless.route(jnp.asarray(x), jnp.asarray(gate_w),
                            jnp.asarray(bias), 4, scaling=2.5)
    idx, w = np.asarray(idx), np.asarray(w)
    assert (idx == 5).any(axis=1).all()
    assert not (np.asarray(idx0) == 5).any(axis=1).all()
    # the weights are the scores WITHOUT the bias, normalised over the four
    # chosen and scaled: each token's sum to the scaling factor
    chosen = np.take_along_axis(s, idx, axis=1)
    np.testing.assert_allclose(
        w, 2.5 * chosen / chosen.sum(axis=1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(w.sum(axis=1), 2.5, rtol=1e-5)
    # without normalisation the weights are the scores themselves
    _, raw = dropless.route(jnp.asarray(x), jnp.asarray(gate_w),
                            jnp.asarray(bias), 4, norm_topk_prob=False)
    np.testing.assert_allclose(np.asarray(raw), chosen, rtol=1e-5)


def test_every_token_to_one_expert_and_none_dropped():
    """The worst imbalance: all T tokens choose expert 3 first. A layer
    with a capacity would drop most of them; this one computes all."""
    rng = np.random.RandomState(8)
    T, h, m, n = 40, 24, 16, 4
    x = jnp.asarray(rng.normal(0, 1, (T, h)), jnp.float32)
    g, u = (jnp.asarray(rng.normal(0, 0.2, (n, h, m)), jnp.float32)
            for _ in range(2))
    d = jnp.asarray(rng.normal(0, 0.2, (n, m, h)), jnp.float32)
    idx = jnp.tile(jnp.asarray([[3, 9]], jnp.int32), (T, 1))   # 9: not held
    w = jnp.asarray(rng.uniform(0.2, 1.0, (T, 2)), jnp.float32)
    y, counters = dropless.held_experts(x, idx, w, g, u, d, first=0)
    want = w[:, :1] * ((jax.nn.silu(x @ g[3]) * (x @ u[3])) @ d[3])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    assert np.asarray(counters).tolist() == [1, T, T]
    # a token no request holds (a pad, a dead row) keeps no assignment: zeros
    # back, not counted, whatever its input
    mask = jnp.arange(T) % 4 != 0
    y, counters = dropless.held_experts(
        jnp.where(mask[:, None], x, jnp.nan), idx, w, g, u, d, first=0,
        token_mask=mask)
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        jnp.where(mask[:, None], want, 0.0)), atol=1e-5)
    assert np.asarray(counters).tolist() == [1, T - T // 4, T - T // 4]


def test_dead_rows_attend_nothing_and_are_routed_nowhere(share_model):
    """One request in an engine of 4 rows: every dispatch's counters are
    bounded by what ITS tokens can be assigned (the three empty slots ride
    every scan step and the mixed step's pad tokens ride its stream), and a
    dead row hands the absorbed path a length of 0."""
    prompt = np.random.RandomState(5).randint(1, 512, (40,)).astype(np.int32)
    tracing.clear()
    eng = ContinuousBatchingEngine(share_model, max_seqs=4, page_size=16,
                                   max_len=256, prefill_chunk=32,
                                   decode_block=4)
    eng.serve([prompt], max_new_tokens=9)
    recs = [r for r in tracing.step_records() if r["engine"] == eng._engine_seq]
    top_k, layers = share_model.config.num_experts_per_tok, 2
    assert len(recs) >= 3
    for r in recs:
        tokens = sum(q for _, _, q, _ in r["rows"]) + (r["k"] - 1) * sum(
            role in "dg" for _, role, _, _ in r["rows"])
        assert r["counters"]["moe_assigned"] <= tokens * top_k * layers
    seen = []
    real = latent_pool.LatentCacheSpec.paged
    attn = share_model.model.layers[0].self_attn
    absorbed = attn._absorbed
    attn._absorbed = lambda qn, qr, pages, lens, *a: (
        seen.append(lens), absorbed(qn, qr, pages, lens, *a))[1]
    try:
        spec = share_model.serving_cache_spec()
        pools = spec.make_pools(3, 16, jnp.float32)
        caches = [real(pool, jnp.asarray([[1], [0]]), jnp.asarray([5, 0]),
                       jnp.asarray([True, False])) for pool in pools]
        share_model(Tensor(jnp.zeros((2, 1), jnp.int32)),
                    position_ids=Tensor(jnp.asarray([[5], [0]])),
                    past_key_values=caches)
    finally:
        del attn._absorbed
    assert np.asarray(seen[0]).tolist() == [6, 0]


def test_the_shares_add_up():
    """Four shares of four experts each, the shared expert counted once,
    sum to the reference's uncut layer over all 16."""
    paddle.seed(11)
    make = lambda first, held: dropless.DroplessMoE(
        64, 32, 16, 4, first_expert=first, n_held=held, scaling=2.5)
    whole = make(0, 16)
    bias = np.random.RandomState(3).normal(0, 0.05, 16).astype(np.float32)
    whole.gate.e_score_correction_bias.set_value(Tensor(jnp.asarray(bias)))
    w = {"mlp." + k: v for k, v in whole.raw_state_dict().items()}
    x = jnp.asarray(np.random.RandomState(4).normal(0, 1, (50, 64)),
                    jnp.float32)
    cfg = {"num_experts_per_tok": 4, "routed_scaling_factor": 2.5}
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(cfg, w, "mlp.", x, first=0, n_held=16)
        shared = ref.expert_layer(cfg, {**w, **{
            "mlp.experts." + p: w["mlp.experts." + p][:0]
            for p in ("gate_proj", "up_proj", "down_proj")}},
            "mlp.", x, first=0, n_held=0)
    total = -3 * np.asarray(shared)      # four parts carry it four times
    for share in range(4):
        part = make(4 * share, 4)
        state = dict(whole.raw_state_dict())
        for p in ("gate_proj", "up_proj", "down_proj"):
            state["experts." + p] = state["experts." + p][4 * share:][:4]
        part.load_raw_state_dict(state)
        total = total + np.asarray(part(Tensor(x))._data)
        # and each part is the reference's own part
        with jax.default_matmul_precision("highest"):
            mine = ref.expert_layer(
                cfg, {**w, **{"mlp." + k: v for k, v in state.items()
                              if k.startswith("experts.")}},
                "mlp.", x, first=4 * share, n_held=4)
        np.testing.assert_allclose(np.asarray(part(Tensor(x))._data),
                                   np.asarray(mine), atol=2e-5)
    np.testing.assert_allclose(total, np.asarray(want), atol=5e-5)


# ---- the engine's model protocol --------------------------------------------

def test_llama_serves_through_the_same_protocol():
    paddle.seed(7)
    model = LlamaForCausalLM(llama_tiny(max_position_embeddings=128))
    model.eval()
    trunk, prefix = model.serving_trunk()
    assert trunk is model.llama and prefix == "llama."
    spec = model.serving_cache_spec()
    assert spec.kind == "K/V pages" and spec.refuses("handoff") is None
    (k, v), = spec.make_pools(3, 16, jnp.float32)[:1]
    assert k.shape == v.shape == (4, 3, 16, 16)
    eng = ContinuousBatchingEngine(model, max_seqs=2, page_size=16,
                                   max_len=64, prefill_chunk=16)
    assert [s.kind for s in eng._layer_specs] == ["K/V pages"] * 2
    ids = np.arange(1, 20, dtype=np.int32)
    out = eng.serve([ids], max_new_tokens=4)[0]
    logits = np.asarray(model(Tensor(jnp.asarray(out[None])))._data[0])
    np.testing.assert_array_equal(out[len(ids):],
                                  logits[len(ids) - 1:-1].argmax(-1))
    h = jnp.ones((2, 64), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(model.serving_head(h, model.raw_state_dict())),
        np.asarray(model.lm_head(Tensor(h))._data))


def test_a_model_without_the_serving_protocol_is_refused_by_name():
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    model = GPTForCausalLM(gpt_tiny())  # a decoder with generate(), no trunk
    with pytest.raises(TypeError, match="GPTForCausalLM lacks serving_trunk "
                                        "and serving_head"):
        ContinuousBatchingEngine(model, max_seqs=2, page_size=16, max_len=64)


@pytest.mark.parametrize("call, kwargs, error", [
    ("__init__", dict(ragged=False), (ValueError, "bucket-ladder plane")),
    ("warmup", dict(shared_prefix_lens=(16,)),
     (TypeError, "unexpected keyword")),
])
def test_the_deleted_planes_options_select_nothing(call, kwargs, error):
    """One dispatch plane, no option selects it (PR 31): the bucket ladder's
    warm-up argument went with it, and its switch is refused by name.
    `ragged=True` is still taken, and does nothing, because the benchmark's
    workload files pass it (`benchmarks/workloads/*.json`)."""
    with pytest.raises(error[0], match=error[1]):
        getattr(ContinuousBatchingEngine, call)(None, None, **kwargs)


def test_the_benchmarks_engine_knobs_are_taken():
    """Every key of a serving workload file's `engine` block (what
    `benchmarks/runners/serve_openloop.py` hands the constructor) is a
    parameter of the engine."""
    import glob
    import inspect
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    taken = set(inspect.signature(ContinuousBatchingEngine).parameters)
    cells = [json.load(open(p)) for p in sorted(glob.glob(
        os.path.join(root, "benchmarks", "workloads", "*.json")))]
    blocks = [c["engine"] for c in cells if "engine" in c]
    assert blocks and all(set(b) <= taken for b in blocks)


@pytest.mark.parametrize("plane, kwargs", [
    ("kv_cache_dtype", dict(kv_cache_dtype="int8")),
    ("prefix cache", dict(enable_prefix_cache=True)),
])
def test_a_plane_that_cannot_take_a_latent_pool_refuses(share_model, plane,
                                                        kwargs):
    with pytest.raises(ValueError, match=plane):
        ContinuousBatchingEngine(share_model, max_seqs=2, page_size=16,
                                 max_len=64, **kwargs)


def test_handoff_and_lora_planes_refuse_a_latent_pool(share_model):
    from paddle_tpu.inference.continuous import EngineRequest

    eng = ContinuousBatchingEngine(share_model, max_seqs=2, page_size=16,
                                   max_len=64)
    with pytest.raises(ValueError, match="export_pages"):
        eng.export_pages(0)
    with pytest.raises(ValueError, match="adopt_request"):
        eng.adopt_request(EngineRequest(0, np.ones(3, np.int32), 2), {})
    assert "LoRA" in str(eng._lora_reject(None))
    with pytest.raises(ValueError, match="latent pages"):
        share_model(Tensor(jnp.ones((1, 1), jnp.int32)),
                    past_key_values=[(jnp.zeros(1), jnp.zeros(1))] * 3)
