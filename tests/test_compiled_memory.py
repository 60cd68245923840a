"""Compiled-program memory evidence for the perf-critical paths
(BASELINE.md claims, verifiable without TPU hardware via XLA's
CompiledMemoryStats on the CPU backend — absolute numbers differ on TPU,
but the asymptotics asserted here are backend-independent properties of
the HLO).

1. fused_linear_cross_entropy never materializes the [N, V] logits;
2. recompute (remat) shrinks a deep net's live activation footprint;
3. the full 7B north-star-shaped program TRACES abstractly (eval_shape) —
   shape correctness at scale without allocating 7B params.

The probes flow through the compile/memory ledger's
``compilemem.analyze_function`` (ISSUE 8) — the same
``memory_analysis()`` harvest /memz and the OOM report use, so these
asymptotic assertions and the live HBM ledger can never diverge.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import compilemem


def _temp_bytes(fn, *args):
    return compilemem.analyze_function(fn, *args)["temp_bytes"]


class TestFusedCEMemory:
    def test_no_logits_materialization(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.incubate.nn import functional as inf

        N, H, V = 8192, 256, 32000
        h = jnp.zeros((N, H), jnp.bfloat16)
        w = jnp.zeros((H, V), jnp.bfloat16)
        y = jnp.zeros((N,), jnp.int32)

        def fused(h, w, y):
            out = inf.fused_linear_cross_entropy(h, w, y, chunk_size=1024)
            return (out._data if hasattr(out, "_data") else out).mean()

        def naive(h, w, y):
            logits = (h @ w).astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
            return (lse - ll).mean()

        grad_f = jax.grad(fused, argnums=(0, 1))
        grad_n = jax.grad(naive, argnums=(0, 1))
        tb_fused = _temp_bytes(grad_f, h, w, y)
        tb_naive = _temp_bytes(grad_n, h, w, y)
        logits_bytes = N * V * 4
        # naive pays the full f32 logits (forward + cotangent); fused must
        # stay well under ONE logits materialization
        assert tb_naive >= logits_bytes, (tb_naive, logits_bytes)
        assert tb_fused < 0.6 * logits_bytes, (
            f"fused-CE temp {tb_fused / 1e6:.1f}MB vs logits {logits_bytes / 1e6:.1f}MB"
        )


class TestRematRecompute:
    def test_checkpoint_recomputes_in_backward(self):
        """CPU XLA's temp accounting doesn't expose the remat saving (it
        schedules both variants to the same peak), but the RECOMPUTATION is
        a property of the HLO itself: the remat'd backward re-runs the
        block forward, so the compiled module holds strictly more tanh ops
        than the plain one (which reuses the saved activations)."""
        import jax
        import jax.numpy as jnp

        D, L, B = 512, 16, 256
        ws = [jnp.zeros((D, D), jnp.float32) for _ in range(L)]
        x = jnp.zeros((B, D), jnp.float32)

        def block(x, w):
            return jnp.tanh(x @ w)

        def plain(x, ws):
            for w in ws:
                x = block(x, w)
            return x.sum()

        def remat(x, ws):
            f = jax.checkpoint(block)
            for w in ws:
                x = f(x, w)
            return x.sum()

        def tanh_count(f):
            return jax.jit(jax.grad(f)).lower(x, ws).compile().as_text().count("tanh")

        n_plain, n_remat = tanh_count(plain), tanh_count(remat)
        assert n_remat > n_plain, (n_remat, n_plain)


class TestNorthStarAbstractTrace:
    def test_7b_train_loss_traces(self):
        """The REAL LLaMA-7B shape (h4096, L32, v32000, s2048) through
        construction + forward + fused loss — abstractly. eval_shape
        allocates nothing, so this catches shape/dtype bugs at the
        north-star scale that tiny-model tests cannot."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.framework.core import Tensor
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=4096, intermediate_size=11008,
            num_hidden_layers=32, num_attention_heads=32,
            max_position_embeddings=2048, dtype="bfloat16",
            use_recompute=True, fuse_linear_cross_entropy=True,
        )

        def full(ids, labels):
            paddle.seed(0)
            m = LlamaForCausalLM(cfg)
            n_params = m.num_parameters()
            assert 6.5e9 < n_params < 7.5e9, f"not 7B-shaped: {n_params / 1e9:.2f}B"
            out = m(Tensor(ids), labels=Tensor(labels))
            return out._data

        ids = jax.ShapeDtypeStruct((1, 2048), jnp.int32)
        labels = jax.ShapeDtypeStruct((1, 2048), jnp.int32)
        res = jax.eval_shape(full, ids, labels)
        assert res.shape == (), res.shape
        assert res.dtype == jnp.float32, res.dtype
