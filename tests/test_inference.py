"""paddle.inference Predictor tests (reference model: inference zero-copy
handle API)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import inference, nn


def test_predictor_handles_and_run():
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 3))
    pred = inference.create_predictor(net, input_names=["x"])
    x = np.random.RandomState(0).rand(2, 4).astype(np.float32)

    # v2 positional style
    (out,) = pred.run([x])
    assert out.shape == (2, 3)

    # handle style
    h = pred.get_input_handle("x")
    h.copy_from_cpu(x)
    pred.run()
    out2 = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
    np.testing.assert_allclose(out, out2, rtol=1e-6)

    # parity with direct eager forward
    net.eval()
    ref = np.asarray(net(paddle.to_tensor(x)).numpy())
    np.testing.assert_allclose(out, ref, rtol=1e-5)


def test_jit_save_load_roundtrip(tmp_path):
    paddle.seed(1)
    net = nn.Linear(3, 2)
    path = str(tmp_path / "model")
    paddle.jit.save(net, path)
    art = paddle.jit.load(path)
    net2 = nn.Linear(3, 2)
    net2.set_state_dict(art["state_dict"])
    x = paddle.to_tensor(np.ones((1, 3), np.float32))
    np.testing.assert_allclose(
        np.asarray(net(x).numpy()), np.asarray(net2(x).numpy()), rtol=1e-6
    )


class TestKVCacheDecode:
    """Decode-path invariant (reference: AnalysisPredictor decode loop):
    incremental cached logits == full-context logits."""

    def _model(self, seed=21):
        paddle.seed(seed)
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        cfg = llama_tiny(num_hidden_layers=2)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return m, cfg

    def test_incremental_matches_full_context(self):
        import jax.numpy as jnp

        m, cfg = self._model()
        rng = np.random.RandomState(0)
        ids = rng.randint(0, cfg.vocab_size, (2, 10)).astype(np.int32)
        full = m(paddle.to_tensor(ids))  # [2, 10, V]

        caches = [
            (paddle.Tensor(k), paddle.Tensor(v)) for k, v in m.init_cache(2, 16)
        ]
        # prefill on the first 6 tokens, then decode 4 one at a time
        logits, caches = m(paddle.to_tensor(ids[:, :6]), past_key_values=caches,
                           cache_position=paddle.to_tensor(np.int32(0)), use_cache=True)
        steps = [logits.numpy()[:, i] for i in range(6)]
        for t in range(6, 10):
            logits, caches = m(
                paddle.to_tensor(ids[:, t:t + 1]), past_key_values=caches,
                cache_position=paddle.to_tensor(np.int32(t)), use_cache=True,
            )
            steps.append(logits.numpy()[:, 0])
        inc = np.stack(steps, axis=1)
        assert np.allclose(full.numpy(), inc, atol=2e-4), np.abs(full.numpy() - inc).max()

    def test_generate_greedy_matches_manual_argmax(self):
        m, cfg = self._model()
        rng = np.random.RandomState(1)
        ids = rng.randint(0, cfg.vocab_size, (2, 5)).astype(np.int32)
        out = m.generate(paddle.to_tensor(ids), max_new_tokens=6)
        out = out.numpy()
        assert out.shape == (2, 11)
        assert (out[:, :5] == ids).all()
        # manual greedy rollout through the plain (uncached) forward
        cur = ids
        for _ in range(6):
            lg = m(paddle.to_tensor(cur)).numpy()
            nxt = lg[:, -1].argmax(-1).astype(np.int32)
            cur = np.concatenate([cur, nxt[:, None]], axis=1)
        assert (out == cur).all(), (out, cur)

    def test_generate_sampling_reproducible_and_eos(self):
        m, cfg = self._model()
        ids = np.array([[1, 2, 3]], dtype=np.int32)
        a = m.generate(paddle.to_tensor(ids), max_new_tokens=8, do_sample=True,
                       temperature=0.8, seed=7).numpy()
        b = m.generate(paddle.to_tensor(ids), max_new_tokens=8, do_sample=True,
                       temperature=0.8, seed=7).numpy()
        assert (a == b).all()
        # eos: force every token to be eos by using argmax token as eos
        g = m.generate(paddle.to_tensor(ids), max_new_tokens=4)
        eos = int(g.numpy()[0, 3])
        out = m.generate(paddle.to_tensor(ids), max_new_tokens=6, eos_token_id=eos,
                         pad_token_id=0).numpy()
        hit = np.where(out[0] == eos)[0]
        if len(hit) and hit[0] < out.shape[1] - 1:
            assert (out[0, hit[0] + 1:] == 0).all()


class TestGPTDecode:
    """The KV-cache generation path is model-agnostic: GPT (learned
    positions, tied wte head) serves through the same GenerationMixin."""

    def _model(self):
        paddle.seed(5)
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

        cfg = gpt_tiny(hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
        m = GPTForCausalLM(cfg)
        m.eval()
        return m, cfg

    def test_incremental_matches_full_context(self):
        m, cfg = self._model()
        rng = np.random.RandomState(1)
        ids = rng.randint(0, cfg.vocab_size, (2, 8)).astype(np.int32)
        full = m(paddle.to_tensor(ids))
        caches = [(paddle.Tensor(k), paddle.Tensor(v)) for k, v in m.init_cache(2, 12)]
        logits, caches = m(paddle.to_tensor(ids[:, :5]), past_key_values=caches,
                           cache_position=paddle.to_tensor(np.int32(0)), use_cache=True)
        steps = [logits.numpy()[:, i] for i in range(5)]
        for t in range(5, 8):
            logits, caches = m(
                paddle.to_tensor(ids[:, t:t + 1]), past_key_values=caches,
                cache_position=paddle.to_tensor(np.int32(t)), use_cache=True,
            )
            steps.append(logits.numpy()[:, 0])
        inc = np.stack(steps, axis=1)
        assert np.allclose(full.numpy(), inc, atol=2e-4), np.abs(full.numpy() - inc).max()

    def test_generate_matches_full_context_greedy(self):
        m, cfg = self._model()
        ids = np.random.RandomState(2).randint(0, cfg.vocab_size, (2, 9)).astype(np.int32)
        out = m.generate(ids, max_new_tokens=5)
        assert out.shape == [2, 14]
        full = m(paddle.to_tensor(out.numpy()[:, :-1]))
        nxt = full.numpy()[:, -1].argmax(-1)
        assert (nxt == out.numpy()[:, -1]).all()


class TestRaggedBatchGenerate:
    """generate(attention_mask=...) serves per-row prompt lengths in one
    batch (internal left-alignment): each row's continuation must equal the
    single-row generate() of that prompt alone."""

    def _check_ragged_pair(self, m, V, l0, l1, new):
        rng = np.random.RandomState(7)
        r0 = rng.randint(0, V, (l0,)).astype(np.int32)
        r1 = rng.randint(0, V, (l1,)).astype(np.int32)
        S = max(l0, l1)
        ids = np.zeros((2, S), np.int32)
        mask = np.zeros((2, S), np.int32)
        ids[0, :l0], ids[1, :l1] = r0, r1
        mask[0, :l0], mask[1, :l1] = 1, 1
        out = m.generate(ids, max_new_tokens=new, attention_mask=mask).numpy()
        ref0 = m.generate(r0[None], max_new_tokens=new).numpy()[0, l0:]
        ref1 = m.generate(r1[None], max_new_tokens=new).numpy()[0, l1:]
        assert (out[0, S:] == ref0).all(), (out[0, S:], ref0)
        assert (out[1, S:] == ref1).all(), (out[1, S:], ref1)

    def test_llama_rows_match_single(self):
        paddle.seed(17)
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        m = LlamaForCausalLM(llama_tiny(num_hidden_layers=2))
        m.eval()
        self._check_ragged_pair(m, 128, 5, 9, 5)

    def test_left_padded_mask_matches_right_padded(self):
        """Callers pad on either side: the prompt must be gathered by the
        mask, not prefix-sliced (ADVICE r4)."""
        paddle.seed(21)
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        m = LlamaForCausalLM(llama_tiny(num_hidden_layers=2))
        m.eval()
        rng = np.random.RandomState(11)
        V, l0, l1, new = 128, 5, 9, 4
        r0 = rng.randint(0, V, (l0,)).astype(np.int32)
        r1 = rng.randint(0, V, (l1,)).astype(np.int32)
        S = max(l0, l1)
        ids = np.zeros((2, S), np.int32)
        mask = np.zeros((2, S), np.int32)
        ids[0, S - l0:], ids[1, S - l1:] = r0, r1  # LEFT padded
        mask[0, S - l0:], mask[1, S - l1:] = 1, 1
        out = m.generate(ids, max_new_tokens=new, attention_mask=mask).numpy()
        ref0 = m.generate(r0[None], max_new_tokens=new).numpy()[0, l0:]
        ref1 = m.generate(r1[None], max_new_tokens=new).numpy()[0, l1:]
        assert (out[0, S:] == ref0).all(), (out[0, S:], ref0)
        assert (out[1, S:] == ref1).all(), (out[1, S:], ref1)

    def test_gpt_rows_match_single(self):
        paddle.seed(18)
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

        m = GPTForCausalLM(gpt_tiny(hidden_dropout_prob=0.0,
                                    attention_probs_dropout_prob=0.0))
        m.eval()
        self._check_ragged_pair(m, 128, 4, 7, 4)

    def test_ragged_with_repetition_penalty(self):
        """Penalty composes with the ragged path: per-row parity against
        single-row generate() with the same penalty."""
        paddle.seed(19)
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        m = LlamaForCausalLM(llama_tiny(num_hidden_layers=1))
        m.eval()
        rng = np.random.RandomState(9)
        l0, l1 = 3, 6
        r0 = rng.randint(0, 128, (l0,)).astype(np.int32)
        r1 = rng.randint(0, 128, (l1,)).astype(np.int32)
        ids = np.zeros((2, 6), np.int32)
        mask = np.zeros((2, 6), np.int32)
        ids[0, :l0], ids[1, :l1] = r0, r1
        mask[0, :l0], mask[1, :l1] = 1, 1
        out = m.generate(ids, max_new_tokens=6, attention_mask=mask,
                         repetition_penalty=4.0).numpy()
        ref0 = m.generate(r0[None], max_new_tokens=6, repetition_penalty=4.0).numpy()[0, l0:]
        ref1 = m.generate(r1[None], max_new_tokens=6, repetition_penalty=4.0).numpy()[0, l1:]
        assert (out[0, 6:] == ref0).all(), (out[0, 6:], ref0)
        assert (out[1, 6:] == ref1).all(), (out[1, 6:], ref1)

    def test_ragged_min_length_suppresses_eos(self):
        paddle.seed(20)
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        m = LlamaForCausalLM(llama_tiny(num_hidden_layers=1))
        m.eval()
        ids = np.array([[1, 2, 3, 0], [4, 5, 6, 7]], np.int32)
        mask = np.array([[1, 1, 1, 0], [1, 1, 1, 1]], np.int32)
        # eos = the first greedily generated token of row 0 -> without
        # min_length it would terminate immediately
        first = int(m.generate(ids, max_new_tokens=1,
                               attention_mask=mask).numpy()[0, -1])
        out = m.generate(ids, max_new_tokens=6, attention_mask=mask,
                         eos_token_id=first, min_length=4,
                         pad_token_id=0).numpy()
        gen0 = out[0, 4:]
        assert first not in gen0[:4].tolist(), gen0


class TestBeamSearch:
    def test_full_width_beam_is_exhaustive_for_two_steps(self):
        """With num_beams == V and max_new=2, beam search IS exhaustive
        search: its result must equal the brute-force argmax of
        logp(v1) + logp(v2 | v1) over all (v1, v2)."""
        paddle.seed(11)
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

        cfg = gpt_tiny(vocab_size=32, hidden_dropout_prob=0.0,
                       attention_probs_dropout_prob=0.0)
        m = GPTForCausalLM(cfg)
        m.eval()
        V = cfg.vocab_size
        ids = np.random.RandomState(3).randint(0, V, (1, 6)).astype(np.int32)

        out = m.generate(ids, max_new_tokens=2, decode_strategy="beam_search",
                         num_beams=V).numpy()

        # brute force: one batched forward per step
        lp1 = _log_softmax(m(paddle.to_tensor(ids)).numpy()[0, -1])
        seqs = np.concatenate(
            [np.repeat(ids, V, axis=0), np.arange(V, dtype=np.int32)[:, None]], axis=1
        )
        lp2 = _log_softmax(m(paddle.to_tensor(seqs)).numpy()[:, -1])  # [V, V]
        joint = lp1[:, None] + lp2
        v1, v2 = np.unravel_index(np.argmax(joint), joint.shape)
        assert out[0, -2] == v1 and out[0, -1] == v2, (out[0, -2:], (v1, v2))

    def test_beam_beats_or_matches_greedy_logprob(self):
        paddle.seed(12)
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        cfg = llama_tiny(num_hidden_layers=2)
        m = LlamaForCausalLM(cfg)
        m.eval()
        ids = np.random.RandomState(4).randint(0, cfg.vocab_size, (2, 7)).astype(np.int32)

        def seq_logprob(full_ids, s0, n):
            lg = m(paddle.to_tensor(full_ids[:, :-1])).numpy()
            lp = np.stack([_log_softmax(lg[:, t]) for t in range(lg.shape[1])], axis=1)
            tot = np.zeros(full_ids.shape[0])
            for t in range(s0 - 1, s0 - 1 + n):
                tot += np.take_along_axis(lp[:, t], full_ids[:, t + 1:t + 2], -1)[:, 0]
            return tot

        greedy = m.generate(ids, max_new_tokens=4).numpy()
        beam = m.generate(ids, max_new_tokens=4, decode_strategy="beam_search",
                          num_beams=4).numpy()
        g = seq_logprob(greedy, 7, 4)
        b = seq_logprob(beam, 7, 4)
        assert (b >= g - 1e-4).all(), (b, g)

    def test_top_p_nucleus(self):
        paddle.seed(14)
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        m = LlamaForCausalLM(llama_tiny(num_hidden_layers=1))
        m.eval()
        ids = np.array([[1, 2, 3]], np.int32)
        a = m.generate(ids, max_new_tokens=6, do_sample=True, top_p=0.9, seed=3).numpy()
        b = m.generate(ids, max_new_tokens=6, do_sample=True, top_p=0.9, seed=3).numpy()
        assert (a == b).all()
        # top_p -> 0 keeps only the argmax token: degenerates to greedy
        g = m.generate(ids, max_new_tokens=6).numpy()
        p0 = m.generate(ids, max_new_tokens=6, do_sample=True, top_p=1e-6, seed=9).numpy()
        assert (g == p0).all()

    def test_repetition_penalty_reduces_repeats(self):
        paddle.seed(15)
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        m = LlamaForCausalLM(llama_tiny(num_hidden_layers=1))
        m.eval()
        ids = np.array([[5, 6, 7]], np.int32)
        plain = m.generate(ids, max_new_tokens=12).numpy()[0, 3:]
        pen = m.generate(ids, max_new_tokens=12, repetition_penalty=5.0).numpy()[0, 3:]
        assert len(set(pen.tolist())) >= len(set(plain.tolist()))
        # penalty=1.0 is exactly the plain path
        same = m.generate(ids, max_new_tokens=12, repetition_penalty=1.0).numpy()[0, 3:]
        assert (same == plain).all()

    def test_min_length_suppresses_eos(self):
        paddle.seed(16)
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        m = LlamaForCausalLM(llama_tiny(num_hidden_layers=1))
        m.eval()
        ids = np.array([[1, 2, 3]], np.int32)
        # pick the greedy first-token as eos: without min_length generation
        # would end immediately
        first = int(m.generate(ids, max_new_tokens=1).numpy()[0, -1])
        out = m.generate(ids, max_new_tokens=6, eos_token_id=first,
                         min_length=4, pad_token_id=0).numpy()[0, 3:]
        assert first not in out[:4].tolist(), out

    def test_strategy_routing(self):
        paddle.seed(13)
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        m = LlamaForCausalLM(llama_tiny(num_hidden_layers=1))
        m.eval()
        ids = np.zeros((1, 4), np.int32)
        with pytest.raises(ValueError):
            m.generate(ids, decode_strategy="beam_search", num_beams=1)
        out = m.generate(ids, max_new_tokens=2, decode_strategy="sampling", seed=7)
        assert out.shape == [1, 6]


def _log_softmax(x):
    x = x.astype(np.float64)
    x = x - x.max(-1, keepdims=True)
    return x - np.log(np.exp(x).sum(-1, keepdims=True))


class TestAotExport:
    def test_export_roundtrip(self, tmp_path):
        from paddle_tpu.inference.predictor import Predictor
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        paddle.seed(5)
        m = LlamaForCausalLM(llama_tiny(num_hidden_layers=2))
        m.eval()
        p = Predictor(m)
        ids = np.random.RandomState(0).randint(0, 128, (2, 8)).astype(np.int32)
        path = str(tmp_path / "llama.stablehlo")
        nbytes = p.export_aot(path, ids)
        assert nbytes > 0
        aot = Predictor.load_aot(path)
        out = aot.run(m.raw_state_dict(), ids)
        direct = m(paddle.to_tensor(ids)).numpy()
        assert np.allclose(out[0], direct, atol=1e-5)


class TestDecodeBucketing:
    """Prompt-length bucketing (reference: AnalysisPredictor shape
    bucketing): generate() compiles one program per power-of-two bucket,
    not per prompt length, and padded prompts decode identically."""

    def _model(self, seed=29):
        paddle.seed(seed)
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        cfg = llama_tiny(num_hidden_layers=2)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return m, cfg

    def test_bucket_function(self):
        from paddle_tpu.generation import prompt_bucket

        assert prompt_bucket(1) == 16
        assert prompt_bucket(16) == 16
        assert prompt_bucket(17) == 32
        assert prompt_bucket(33) == 64

    def test_compile_count_is_per_bucket(self):
        m, cfg = self._model()
        rng = np.random.RandomState(0)
        for s0 in (5, 9, 13, 16):  # one bucket (16)
            ids = rng.randint(0, cfg.vocab_size, (1, s0)).astype(np.int32)
            m.generate(paddle.to_tensor(ids), max_new_tokens=3)
        assert len(m._gen_cache) == 1, list(m._gen_cache)
        ids = rng.randint(0, cfg.vocab_size, (1, 20)).astype(np.int32)  # bucket 32
        m.generate(paddle.to_tensor(ids), max_new_tokens=3)
        assert len(m._gen_cache) == 2

    def test_bucketed_continuation_matches_manual_argmax(self):
        import jax.numpy as jnp

        m, cfg = self._model(seed=31)
        rng = np.random.RandomState(1)
        s0 = 11  # padded to 16 inside generate
        ids = rng.randint(0, cfg.vocab_size, (2, s0)).astype(np.int32)
        out = np.asarray(m.generate(paddle.to_tensor(ids), max_new_tokens=4).numpy())
        assert out.shape == (2, s0 + 4)
        np.testing.assert_array_equal(out[:, :s0], ids)
        # manual greedy roll-forward through full-context forward
        cur = ids
        for _ in range(4):
            logits = m(paddle.to_tensor(cur))
            nxt = np.asarray(logits.numpy())[:, -1].argmax(-1).astype(np.int32)
            cur = np.concatenate([cur, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(out, cur)

    def test_generate_on_mp_sharded_model(self):
        """Decode on a TP-sharded model: params placed over the mp axis,
        same tokens as the unsharded model (the KV cache inherits the
        head-dim sharding through GSPMD propagation)."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.distributed import mesh as M

        m, cfg = self._model(seed=37)
        rng = np.random.RandomState(2)
        ids = rng.randint(0, cfg.vocab_size, (2, 9)).astype(np.int32)
        ref = np.asarray(m.generate(paddle.to_tensor(ids), max_new_tokens=4).numpy())

        mesh = M.build_mesh(mp=2)
        with M.mesh_guard(mesh):
            for _, p in m.named_parameters():
                spec = getattr(p, "partition_spec", None) or P()
                entries = [
                    e if e in mesh.axis_names and mesh.shape.get(e, 1) > 1 else None
                    for e in (list(spec) + [None] * (len(p.shape) - len(spec)))
                ]
                p._data = jax.device_put(p._data, NamedSharding(mesh, P(*entries)))
            m._gen_cache = {}
            out = np.asarray(m.generate(paddle.to_tensor(ids), max_new_tokens=4).numpy())
        M.reset_mesh()
        np.testing.assert_array_equal(out, ref)
