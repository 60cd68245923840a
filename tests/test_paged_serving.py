"""Paged KV pool + continuous batching (reference capability: AnalysisPredictor
serving / PaddleNLP block-attention; PAPERS.md ragged-paged-attention).

Oracle strategy: the paged decode path must reproduce the dense fixed-cache
`generate()` token-for-token (greedy), while the pool stays smaller than the
dense cache the same workload would need — memory is the point of paging.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.continuous import ContinuousBatchingEngine
from paddle_tpu.ops.paged_attention import (
    PagedLayerCache,
    paged_decode_attention,
    write_token_kv,
)


def _tiny_model():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    paddle.seed(31)
    m = LlamaForCausalLM(llama_tiny(num_hidden_layers=2))
    m.eval()
    return m, m.config


class TestPagedAttentionOp:
    def test_matches_dense_attention(self):
        rng = np.random.RandomState(0)
        B, Hq, Hkv, D, bs, npages_seq = 3, 4, 2, 8, 4, 3
        P = 1 + B * npages_seq
        lens = np.array([5, 9, 12], np.int32)
        kp = jnp.asarray(rng.randn(Hkv, P, bs, D).astype(np.float32))
        vp = jnp.asarray(rng.randn(Hkv, P, bs, D).astype(np.float32))
        pt = jnp.asarray(
            np.arange(1, P).reshape(B, npages_seq).astype(np.int32))
        q = jnp.asarray(rng.randn(B, Hq, D).astype(np.float32))

        out = paged_decode_attention(q, kp, vp, jnp.asarray(lens), pt)

        # dense oracle: reassemble each row's contiguous KV from its pages
        for b in range(B):
            kd = np.concatenate([np.asarray(kp[:, p]) for p in np.asarray(pt[b])],
                                axis=1)  # [Hkv, npages*bs, D]
            vd = np.concatenate([np.asarray(vp[:, p]) for p in np.asarray(pt[b])],
                                axis=1)
            kd, vd = kd[:, :lens[b]], vd[:, :lens[b]]
            g = Hq // Hkv
            for h in range(Hq):
                kh, vh = kd[h // g], vd[h // g]
                s = (np.asarray(q[b, h]) @ kh.T) / np.sqrt(D)
                p_ = np.exp(s - s.max())
                p_ /= p_.sum()
                ref = p_ @ vh
                np.testing.assert_allclose(np.asarray(out[b, h]), ref,
                                           rtol=2e-5, atol=2e-6)

    def test_write_token_kv_lands_in_right_page(self):
        Hkv, P, bs, D, B = 2, 5, 4, 3, 2
        pages = jnp.zeros((Hkv, P, bs, D), jnp.float32)
        pt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        lens = jnp.asarray([5, 2], jnp.int32)  # row0 -> page 2 off 1; row1 -> page 3 off 2
        new = jnp.ones((B, Hkv, D)) * jnp.asarray([[[1.0]], [[2.0]]])
        out = write_token_kv(pages, pt, lens, new)
        assert float(out[0, 2, 1, 0]) == 1.0
        assert float(out[0, 3, 2, 0]) == 2.0
        # nothing else written
        assert float(jnp.abs(out).sum()) == pytest.approx(
            float(jnp.abs(new).sum()), rel=1e-6)


class TestContinuousBatching:
    def _model(self):
        return _tiny_model()

    def test_matches_dense_generate_mixed_lengths(self):
        """5 mixed-length requests through 2 slots and a small pool must
        reproduce per-prompt dense generate() exactly (greedy)."""
        m, cfg = self._model()
        rng = np.random.RandomState(5)
        lens = [5, 11, 7, 16, 3]
        prompts = [rng.randint(1, cfg.vocab_size, (l,)).astype(np.int32)
                   for l in lens]
        new = 6
        eng = ContinuousBatchingEngine(m, max_seqs=2, page_size=16,
                                       num_pages=9, max_len=64)
        outs = eng.serve(prompts, max_new_tokens=new)
        assert eng.stats["decode_steps"] > 0
        for p, o in zip(prompts, outs):
            ref = m.generate(p[None], max_new_tokens=new).numpy()[0]
            np.testing.assert_array_equal(o, ref)
        # continuous batching really interleaved: fewer decode steps than
        # serial per-request decoding would need
        assert eng.stats["decode_steps"] < len(prompts) * (new - 1)

    def test_warmup_compiles_both_programs_and_preserves_streams(self):
        """warmup() must compile exactly the mixed step and the decode
        block, a post-warmup serve must add no program, and its streams
        must be token-identical to a fresh engine's (warmup mutates no
        state the scheduler depends on)."""
        m, cfg = self._model()
        rng = np.random.RandomState(9)
        lens = [5, 11, 37]
        prompts = [rng.randint(1, cfg.vocab_size, (l,)).astype(np.int32)
                   for l in lens]
        new = 7
        mk = lambda: ContinuousBatchingEngine(  # noqa: E731
            m, max_seqs=2, page_size=16, num_pages=12, max_len=64,
            decode_block=4)
        warm, cold = mk(), mk()
        warm.warmup(lens)
        # every program the serve loop can hit is already compiled
        sampling = (False, 1.0, 0, 1.0)

        def programs():
            return {name: set(getattr(warm, name)) for name in (
                "_ragged_fns", "_decode_block_fns", "_decode_fns",
                "_insert_fns", "_gather_fns")}

        before = programs()
        assert before == {"_ragged_fns": {sampling},
                          "_decode_block_fns": {(sampling, 4)},
                          "_decode_fns": set(), "_insert_fns": set(),
                          "_gather_fns": set()}
        outs = warm.serve(prompts, max_new_tokens=new)
        refs = cold.serve(prompts, max_new_tokens=new)
        for o, r in zip(outs, refs):
            np.testing.assert_array_equal(o, r)
        # the timed serve added no new programs
        assert programs() == before

    def test_pool_smaller_than_dense_and_admission_defers(self):
        """The memory contract: pool bytes < the dense fixed-shape caches the
        same 5 concurrent requests would allocate, and a tight pool defers
        admissions instead of failing."""
        m, cfg = self._model()
        rng = np.random.RandomState(6)
        prompts = [rng.randint(1, cfg.vocab_size, (l,)).astype(np.int32)
                   for l in [5, 9, 6, 12, 4]]
        new = 4
        # page_size=4: the 5-token prompt + 4 new needs 3 pages and the
        # 9-token one 4; 6 usable pages cannot hold both -> the second defers
        eng = ContinuousBatchingEngine(m, max_seqs=2, page_size=4,
                                       num_pages=7, max_len=64)
        outs = eng.serve(prompts, max_new_tokens=new)
        for p, o in zip(prompts, outs):
            ref = m.generate(p[None], max_new_tokens=new).numpy()[0]
            np.testing.assert_array_equal(o, ref)
        assert eng.stats["deferred_admissions"] > 0
        dtype_bytes = 2 if "bfloat16" in str(next(iter(m.parameters())).dtype) else 4
        dense_bytes = (len(prompts) * eng.max_len * cfg.num_key_value_heads
                       * cfg.head_dim * dtype_bytes * 2 * cfg.num_hidden_layers)
        assert eng.pool_bytes() < dense_bytes, (eng.pool_bytes(), dense_bytes)

    def test_page_size_larger_than_prompt(self):
        """A prompt shorter than a page (page_size=32) must still land its
        KV (regression: npg floored to 0 and silently dropped the prompt)."""
        m, cfg = self._model()
        rng = np.random.RandomState(8)
        prompts = [rng.randint(1, cfg.vocab_size, (l,)).astype(np.int32)
                   for l in [5, 11]]
        eng = ContinuousBatchingEngine(m, max_seqs=2, page_size=32,
                                       num_pages=5, max_len=64)
        outs = eng.serve(prompts, max_new_tokens=4)
        for p, o in zip(prompts, outs):
            ref = m.generate(p[None], max_new_tokens=4).numpy()[0]
            np.testing.assert_array_equal(o, ref)

    def test_predictor_serve_auto_max_len_admits_the_longest_prompt(self):
        """Predictor.serve sizes max_len to the longest prompt plus its new
        tokens, in whole pages, and the engine admits it (regression: the
        bucket ladder asked for the prompt's BUCKET and raised)."""
        from paddle_tpu.inference import Predictor

        m, cfg = self._model()
        rng = np.random.RandomState(9)
        # len 17 + 1 = 18 tokens: two pages of 16
        prompts = [rng.randint(1, cfg.vocab_size, (17,)).astype(np.int32)]
        outs = Predictor(m).serve(prompts, max_new_tokens=1, page_size=16,
                                  max_seqs=1)
        ref = m.generate(prompts[0][None], max_new_tokens=1).numpy()[0]
        np.testing.assert_array_equal(outs[0], ref)

    def test_eos_stops_early_and_frees_pages(self):
        m, cfg = self._model()
        rng = np.random.RandomState(7)
        prompts = [rng.randint(1, cfg.vocab_size, (6,)).astype(np.int32)]
        # pick eos = the greedy first token so the request retires immediately
        ref = m.generate(prompts[0][None], max_new_tokens=2).numpy()[0]
        eos = int(ref[6])
        eng = ContinuousBatchingEngine(m, max_seqs=2, page_size=16,
                                       num_pages=9, max_len=64)
        outs = eng.serve(prompts, max_new_tokens=8, eos_token_id=eos)
        assert len(outs[0]) == 7  # prompt + the eos token, stopped early
        assert len(eng.free_pages) == eng.num_pages - 1  # all pages back
        assert sorted(eng.free_slots) == [0, 1]

    def test_sampling_reproducible_and_schedule_independent(self):
        """Sampled serving: per-request key streams make a request's output
        identical whether it ran alone or co-scheduled with others, and
        reproducible across serve() calls with the same seed."""
        m, cfg = self._model()
        rng = np.random.RandomState(10)
        prompts = [rng.randint(1, cfg.vocab_size, (l,)).astype(np.int32)
                   for l in [5, 9, 7]]
        kw = dict(max_new_tokens=6, do_sample=True, temperature=0.9,
                  top_k=20, seed=123)
        eng = ContinuousBatchingEngine(m, max_seqs=2, page_size=16,
                                       num_pages=9, max_len=64)
        outs = eng.serve(prompts, **kw)
        outs2 = eng.serve(prompts, **kw)
        for a, b in zip(outs, outs2):
            np.testing.assert_array_equal(a, b)  # same seed -> same draw
        # request 1 alone (different co-scheduling, different request_id
        # base would change things — so serve it with its original index)
        alone = eng.serve(prompts[:2], **kw)
        np.testing.assert_array_equal(alone[1], outs[1])
        # all tokens valid; temperature path actually sampled (greedy differs)
        greedy = eng.serve(prompts, max_new_tokens=6)
        assert any((a[len(p):] != g[len(p):]).any()
                   for a, g, p in zip(outs, greedy, prompts))
        assert all(int(o.max()) < cfg.vocab_size for o in outs)

    def test_decode_program_temp_memory_bounded(self):
        """The jitted decode step must not materialize per-sequence dense
        cache views: its temps stay below the pool itself."""
        m, cfg = self._model()
        eng = ContinuousBatchingEngine(m, max_seqs=4, page_size=16,
                                       num_pages=17, max_len=64)
        state = m.raw_state_dict()
        toks = jnp.zeros((4, 1), jnp.int32)
        keys = jnp.stack([jax.random.PRNGKey(0)] * 4)
        decode = eng._decode((False, 1.0, 0, 1.0))
        caps = jnp.full((4,), 63, jnp.int32)  # per-row length caps (ISSUE 6)
        lowered = decode.lower(
            state, toks, tuple(eng.pools),
            jnp.asarray(eng.page_table), jnp.asarray(eng.lengths), caps,
            keys)
        temp = lowered.compile().memory_analysis().temp_size_in_bytes
        # with donated pools the aliased outputs count toward temp in XLA's
        # accounting, so allow up to ~1.5x the pool itself; the failure mode
        # being guarded (per-sequence dense cache views gathered per layer)
        # would show up as a multiple of this
        assert temp < 1.5 * eng.pool_bytes(), (temp, eng.pool_bytes())


class TestInt8KVPool:
    def test_op_parity_with_float_pool(self):
        """int8 pool decode attention tracks the float-pool result within
        quantization tolerance (per-row absmax scales)."""
        rng = np.random.RandomState(11)
        B, Hq, Hkv, D, bs, nps = 2, 4, 2, 16, 4, 3
        P = 1 + B * nps
        from paddle_tpu.ops.paged_attention import quantize_pages

        kp = jnp.asarray(rng.randn(Hkv, P, bs, D).astype(np.float32))
        vp = jnp.asarray(rng.randn(Hkv, P, bs, D).astype(np.float32))
        pt = jnp.asarray(np.arange(1, P).reshape(B, nps).astype(np.int32))
        lens = jnp.asarray([7, 11], jnp.int32)
        q = jnp.asarray(rng.randn(B, Hq, D).astype(np.float32))
        ref = paged_decode_attention(q, kp, vp, lens, pt)
        out = paged_decode_attention(q, quantize_pages(kp), quantize_pages(vp),
                                     lens, pt)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=0.1, atol=0.05)

    def test_engine_serves_and_pool_is_smaller(self):
        m, _ = _tiny_model()
        rng = np.random.RandomState(12)
        prompts = [rng.randint(1, m.config.vocab_size, (l,)).astype(np.int32)
                   for l in [5, 9]]
        f32_eng = ContinuousBatchingEngine(m, max_seqs=2, page_size=16,
                                           num_pages=9, max_len=64)
        i8_eng = ContinuousBatchingEngine(m, max_seqs=2, page_size=16,
                                          num_pages=9, max_len=64,
                                          kv_cache_dtype="int8")
        ref = f32_eng.serve(prompts, max_new_tokens=4)
        outs = i8_eng.serve(prompts, max_new_tokens=4)
        # int8 weight bytes + per-row scales must undercut the float pool
        assert i8_eng.pool_bytes() < f32_eng.pool_bytes(), (
            i8_eng.pool_bytes(), f32_eng.pool_bytes())
        for p, o, r in zip(prompts, outs, ref):
            assert len(o) == len(r) == len(p) + 4
            assert int(np.max(o)) < m.config.vocab_size
            # the FIRST generated token comes from the exact dense prefill
            # (before any int8 round-trip) — must match the float engine
            assert o[len(p)] == r[len(p)], (o, r)


class TestServingFuzz:
    def test_random_request_storms_match_dense(self):
        """Fuzz the scheduler: random prompt lengths, request counts,
        max_new, eos on/off, page sizes — every request's greedy output must
        equal its dense generate() regardless of queueing/retire order."""
        m, _ = _tiny_model()
        V = m.config.vocab_size
        rng = np.random.RandomState(99)
        for trial in range(4):
            n_req = int(rng.randint(1, 7))
            prompts = [rng.randint(1, V, (int(rng.randint(3, 20)),)).astype(np.int32)
                       for _ in range(n_req)]
            new = int(rng.randint(1, 7))
            eos = int(rng.randint(1, V)) if trial % 2 else None
            eng = ContinuousBatchingEngine(
                m, max_seqs=int(rng.randint(1, 4)),
                page_size=int(rng.choice([4, 8, 16])),
                max_len=64)
            outs = eng.serve(prompts, max_new_tokens=new, eos_token_id=eos)
            for i, (p, o) in enumerate(zip(prompts, outs)):
                full = m.generate(p[None], max_new_tokens=new,
                                  eos_token_id=eos).numpy()[0]
                # dense generate pads AFTER eos; the engine stops — compare
                # up to the engine's (possibly shorter) length
                np.testing.assert_array_equal(
                    o, full[:len(o)], err_msg=f"trial {trial} req {i}")
                if eos is None:
                    # no early stop possible: the engine must deliver every
                    # requested token (prefix-match alone would let silent
                    # truncation pass)
                    assert len(o) == len(p) + new, (trial, i, len(o))
                elif len(o) < len(full):
                    assert o[-1] == eos  # engine stopped exactly at eos
            # no leaks after every storm
            assert len(eng.free_pages) == eng.num_pages - 1
            assert sorted(eng.free_slots) == list(range(eng.max_seqs))


def test_on_token_streams_every_token_in_order():
    """The streaming callback delivers each request's tokens in generation
    order, and exactly the tokens the final outputs contain."""
    m, _ = _tiny_model()
    rng = np.random.RandomState(13)
    prompts = [rng.randint(1, m.config.vocab_size, (l,)).astype(np.int32)
               for l in [5, 9, 7]]
    streamed = {}
    eng = ContinuousBatchingEngine(m, max_seqs=2, page_size=16, max_len=64)
    outs = eng.serve(prompts, max_new_tokens=5,
                     on_token=lambda rid, t: streamed.setdefault(rid, []).append(t))
    for rid, (p, o) in enumerate(zip(prompts, outs)):
        assert streamed[rid] == list(o[len(p):]), rid


def test_raising_on_token_does_not_leak_warm_engine():
    """A raising callback must not strand pages/slots: the engine stays
    reusable after the exception (warm-engine contract)."""
    m, _ = _tiny_model()
    rng = np.random.RandomState(14)
    prompts = [rng.randint(1, m.config.vocab_size, (l,)).astype(np.int32)
               for l in [5, 9]]
    eng = ContinuousBatchingEngine(m, max_seqs=2, page_size=16, max_len=64)

    def boom(rid, tok):
        raise RuntimeError("client disconnected")

    with pytest.raises(RuntimeError, match="client disconnected"):
        eng.serve(prompts, max_new_tokens=4, on_token=boom)
    assert len(eng.free_pages) == eng.num_pages - 1
    assert sorted(eng.free_slots) == [0, 1]
    # and the warm engine still serves correctly afterwards
    outs = eng.serve(prompts, max_new_tokens=4)
    for p, o in zip(prompts, outs):
        ref = m.generate(p[None], max_new_tokens=4).numpy()[0]
        np.testing.assert_array_equal(o, ref)


def test_interpret_kernel_serves_the_reference_with_half_the_rows_dead(
        monkeypatch):
    """2 requests in an engine of 4 rows, GQA, through the float pool's
    decode kernel in interpret mode (steered here, not by an option of the
    program): the two empty slots reach the kernel at length 0 in every scan
    step, and the served tokens are the no-cache reference's."""
    import functools

    from _serving_reference import reference_streams

    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    from paddle_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "paged_decode_attention", functools.partial(
        pa.paged_decode_attention, impl="pallas"))
    paddle.seed(31)
    m = LlamaForCausalLM(llama_tiny(num_key_value_heads=2))
    m.eval()
    rng = np.random.RandomState(17)
    prompts = [rng.randint(1, m.config.vocab_size, (l,)).astype(np.int32)
               for l in [19, 5]]
    eng = ContinuousBatchingEngine(m, max_seqs=4, page_size=16, max_len=64,
                                   decode_block=4)
    pa.LAST_IMPL = None
    outs = eng.serve(prompts, max_new_tokens=7)
    assert pa.LAST_IMPL == "paged-kernel-interpret"
    assert eng.stats["decode_steps"] > 0
    for o, r in zip(outs, reference_streams(m, prompts, 7)):
        np.testing.assert_array_equal(o, r)


def test_block_decode_matches_per_token():
    """decode_block=8 (k steps per dispatch) must produce exactly the same
    streams as decode_block=1 (per-token dispatch), across mixed lengths,
    eos retirement and queued admissions."""
    m, _ = _tiny_model()
    rng = np.random.RandomState(21)
    prompts = [rng.randint(1, m.config.vocab_size, (l,)).astype(np.int32)
               for l in [5, 11, 3, 17, 8]]
    eos = int(m.generate(prompts[0][None], max_new_tokens=1).numpy()[0, -1])
    outs = {}
    for block in (1, 8):
        eng = ContinuousBatchingEngine(m, max_seqs=2, page_size=8, max_len=64,
                                       decode_block=block)
        outs[block] = eng.serve(prompts, max_new_tokens=12, eos_token_id=eos)
        if block > 1:
            # the block path must actually have fused steps
            assert eng.stats["decode_steps"] > 0
    for a, b in zip(outs[1], outs[8]):
        np.testing.assert_array_equal(a, b)


class TestPrefixCache:
    """Automatic prefix caching: content-addressed shared pages, refcounts,
    LRU eviction, suffix-only prefill (vLLM-class capability)."""

    def _model(self):
        return _tiny_model()

    def test_shared_system_prompt_matches_dense_and_hits(self):
        """Requests sharing a long system prefix must produce EXACTLY the
        no-cache outputs while reusing the prefix pages."""
        m, cfg = self._model()
        rng = np.random.RandomState(7)
        sys_prompt = rng.randint(1, cfg.vocab_size, (33,)).astype(np.int32)
        prompts = [np.concatenate([sys_prompt,
                                   rng.randint(1, cfg.vocab_size, (k,))
                                   .astype(np.int32)])
                   for k in (4, 9, 2, 6)]
        new = 5
        base = ContinuousBatchingEngine(m, max_seqs=2, page_size=8,
                                        num_pages=32, max_len=96)
        want = base.serve(prompts, max_new_tokens=new)
        eng = ContinuousBatchingEngine(m, max_seqs=2, page_size=8,
                                       num_pages=32, max_len=96,
                                       enable_prefix_cache=True)
        got = eng.serve(prompts, max_new_tokens=new)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        # 33-token shared prefix @ page 8 = 4 full shared pages. A prompt's
        # pages are indexed when it graduates, so the two requests admitted
        # together miss each other and requests 3 and 4, admitted after a
        # graduation, each hit all four
        assert eng.stats["prefix_hit_pages"] == 2 * 4, eng.stats

    def test_identical_prompts_second_serve_hits_cache(self):
        """Cache persists across serve() calls on a warm engine."""
        m, cfg = self._model()
        rng = np.random.RandomState(8)
        p = rng.randint(1, cfg.vocab_size, (20,)).astype(np.int32)
        eng = ContinuousBatchingEngine(m, max_seqs=1, page_size=8,
                                       num_pages=16, max_len=64,
                                       enable_prefix_cache=True)
        first = eng.serve([p], max_new_tokens=4)[0]
        hits0 = eng.stats["prefix_hit_pages"]
        second = eng.serve([p], max_new_tokens=4)[0]
        np.testing.assert_array_equal(first, second)
        # 20 tokens @ page 8 -> pages covering [0,8), [8,16) shareable
        # ((20-1)//8 = 2 full-page cap)
        assert eng.stats["prefix_hit_pages"] - hits0 == 2, eng.stats

    def test_page_accounting_invariant_and_eviction(self):
        """free + evictable + in-use = num_pages - 1 at every quiet point;
        a tight pool evicts cached pages instead of deadlocking."""
        m, cfg = self._model()
        rng = np.random.RandomState(9)
        eng = ContinuousBatchingEngine(m, max_seqs=1, page_size=8,
                                       num_pages=8, max_len=64,
                                       enable_prefix_cache=True)

        def check():
            in_use = len(eng._page_refs)
            assert in_use + len(eng.free_pages) + len(eng._evictable) \
                == eng.num_pages - 1
            assert 0 not in eng._page_refs and 0 not in eng._evictable

        for i in range(4):  # distinct prompts large enough to force evictions
            p = rng.randint(1, cfg.vocab_size, (24,)).astype(np.int32)
            eng.serve([p], max_new_tokens=4)
            check()
        assert eng.stats["prefix_evictions"] > 0, eng.stats

    def test_sampling_stream_independent_of_cache(self):
        """Sampled outputs depend only on (seed, request id, token index) —
        prefix-cache on/off must not change them."""
        m, cfg = self._model()
        rng = np.random.RandomState(10)
        sys_prompt = rng.randint(1, cfg.vocab_size, (17,)).astype(np.int32)
        prompts = [np.concatenate([sys_prompt,
                                   rng.randint(1, cfg.vocab_size, (k,))
                                   .astype(np.int32)]) for k in (3, 5)]
        kw = dict(max_new_tokens=4, do_sample=True, temperature=0.9,
                  top_p=0.9, seed=3)
        off = ContinuousBatchingEngine(m, max_seqs=2, page_size=8,
                                       num_pages=24, max_len=64)
        on = ContinuousBatchingEngine(m, max_seqs=2, page_size=8,
                                      num_pages=24, max_len=64,
                                      enable_prefix_cache=True)
        for w, g in zip(off.serve(prompts, **kw), on.serve(prompts, **kw)):
            np.testing.assert_array_equal(w, g)

    def test_shared_evictable_pages_not_double_counted(self):
        """Admission must not count a request's own shared pages (sitting in
        _evictable) as allocatable — regression for a KeyError crash in
        _alloc_pages on a warm tight pool."""
        m, cfg = self._model()
        rng = np.random.RandomState(11)
        x = rng.randint(1, cfg.vocab_size, (24,)).astype(np.int32)
        y = rng.randint(1, cfg.vocab_size, (24,)).astype(np.int32)
        eng = ContinuousBatchingEngine(m, max_seqs=2, page_size=8,
                                       num_pages=8, max_len=64,
                                       enable_prefix_cache=True)
        eng.serve([x], max_new_tokens=4)  # X's 2 indexed pages -> evictable
        outs = eng.serve([y, x], max_new_tokens=4)  # must not crash
        ref_eng = ContinuousBatchingEngine(m, max_seqs=2, page_size=8,
                                           num_pages=8, max_len=64)
        for o, r in zip(outs, ref_eng.serve([y, x], max_new_tokens=4)):
            np.testing.assert_array_equal(o, r)

    def test_hit_plus_suffix_bucket_fits_page_table_row(self):
        """A prefix hit whose independently-rounded suffix bucket would
        overflow pages_per_seq must shrink the hit — regression for a
        page-table row broadcast crash."""
        m, cfg = self._model()
        rng = np.random.RandomState(12)
        seed_p = rng.randint(1, cfg.vocab_size, (24,)).astype(np.int32)
        big = np.concatenate([seed_p[:8],
                              rng.randint(1, cfg.vocab_size, (65,))
                              .astype(np.int32)])  # 73 tokens, shares page 1
        eng = ContinuousBatchingEngine(m, max_seqs=1, page_size=8,
                                       num_pages=40, max_len=128,
                                       enable_prefix_cache=True)
        eng.serve([seed_p], max_new_tokens=2)
        out = eng.serve([big], max_new_tokens=2)[0]  # must not crash
        ref = ContinuousBatchingEngine(m, max_seqs=1, page_size=8,
                                       num_pages=40, max_len=128)
        np.testing.assert_array_equal(out, ref.serve([big], max_new_tokens=2)[0])

    def test_warmup_bypasses_prefix_cache(self):
        """warmup() with the prefix cache on compiles the mixed step and
        the decode block alone — the gather and insert programs are the KV
        handoff plane's, compiled by a handoff — and its all-ones dummy
        prompt must not leave junk pages indexed."""
        m, cfg = self._model()
        eng = ContinuousBatchingEngine(m, max_seqs=1, page_size=8,
                                       num_pages=40, max_len=256,
                                       enable_prefix_cache=True)
        eng.warmup([20, 70])
        assert len(eng._ragged_fns) == len(eng._decode_block_fns) == 1
        assert not eng._insert_fns and not eng._gather_fns
        assert not eng._prefix_index and not eng._evictable
        assert eng.enable_prefix_cache  # restored

    def test_int8_pool_refuses_prefix_cache(self):
        m, cfg = self._model()
        with pytest.raises(ValueError, match="int8"):
            ContinuousBatchingEngine(m, max_seqs=1, kv_cache_dtype="int8",
                                     enable_prefix_cache=True)
