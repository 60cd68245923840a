"""Ragged/varlen flash attention (reference: flash_attn_unpadded /
flash_attn_varlen): packed [total, H, D] layout + cumulative offsets must
equal per-sequence dense attention, for causal and full, MHA and GQA.
The TPU tier proves the splash SegmentIds kernel path is O(total·block)
memory, not O(total²)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nn.functional.flash_attention import flash_attn_unpadded
from paddle_tpu.ops import flash_attention as fa


def _pack(seqs_q, seqs_k=None):
    seqs_k = seqs_k if seqs_k is not None else seqs_q
    cu_q = np.cumsum([0] + [s.shape[0] for s in seqs_q]).astype(np.int32)
    cu_k = np.cumsum([0] + [s.shape[0] for s in seqs_k]).astype(np.int32)
    return (np.concatenate(seqs_q), np.concatenate(seqs_k), cu_q, cu_k)


def _ref_attention(q, k, v, causal, scale):
    # [S, H, D] single sequence dense reference
    logits = np.einsum("qhd,khd->hqk", q, k).astype(np.float64) * scale
    if causal:
        sq, sk = q.shape[0], k.shape[0]
        mask = np.tril(np.ones((sq, sk), bool), k=sk - sq)
        logits = np.where(mask[None], logits, -np.inf)
    logits -= logits.max(-1, keepdims=True)
    p = np.exp(logits)
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hqk,khd->qhd", p, v)


class TestVarlenSegments:
    def test_segment_ids_from_offsets(self):
        import jax.numpy as jnp

        seg = fa.varlen_segment_ids(jnp.asarray([0, 3, 5], jnp.int32), 5)
        np.testing.assert_array_equal(np.asarray(seg), [0, 0, 0, 1, 1])
        # padded total: trailing tokens fall into the next segment
        seg = fa.varlen_segment_ids(jnp.asarray([0, 3, 5], jnp.int32), 7)
        np.testing.assert_array_equal(np.asarray(seg), [0, 0, 0, 1, 1, 2, 2])


class TestVarlenParity:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_per_sequence_dense(self, causal):
        rng = np.random.RandomState(0)
        H, D = 2, 16
        lens = [5, 9, 3]
        seqs = [rng.randn(L, H, D).astype(np.float32) for L in lens]
        qp, kp, cu_q, cu_k = _pack(seqs)
        out, _ = flash_attn_unpadded(
            paddle.to_tensor(qp), paddle.to_tensor(kp), paddle.to_tensor(kp),
            paddle.to_tensor(cu_q), paddle.to_tensor(cu_k),
            max(lens), max(lens), causal=causal,
        )
        scale = 1.0 / np.sqrt(D)
        ref = np.concatenate([_ref_attention(s, s, s, causal, scale) for s in seqs])
        np.testing.assert_allclose(np.asarray(out.numpy()), ref, rtol=2e-4, atol=1e-5)

    def test_gqa_varlen(self):
        rng = np.random.RandomState(1)
        HQ, HK, D = 4, 2, 8
        lens = [4, 6]
        qs = [rng.randn(L, HQ, D).astype(np.float32) for L in lens]
        ks = [rng.randn(L, HK, D).astype(np.float32) for L in lens]
        qp = np.concatenate(qs)
        kp = np.concatenate(ks)
        cu = np.cumsum([0] + lens).astype(np.int32)
        out, _ = flash_attn_unpadded(
            paddle.to_tensor(qp), paddle.to_tensor(kp), paddle.to_tensor(kp),
            paddle.to_tensor(cu), paddle.to_tensor(cu), max(lens), max(lens),
            causal=True,
        )
        scale = 1.0 / np.sqrt(D)
        refs = []
        for q, k in zip(qs, ks):
            ke = np.repeat(k, HQ // HK, axis=1)
            refs.append(_ref_attention(q, ke, ke, True, scale))
        np.testing.assert_allclose(
            np.asarray(out.numpy()), np.concatenate(refs), rtol=2e-4, atol=1e-5
        )

    def test_gradients_flow(self):
        rng = np.random.RandomState(2)
        lens = [4, 4]
        seqs = [rng.randn(L, 2, 8).astype(np.float32) for L in lens]
        qp, kp, cu_q, cu_k = _pack(seqs)
        q = paddle.to_tensor(qp, stop_gradient=False)
        out, _ = flash_attn_unpadded(
            q, paddle.to_tensor(kp), paddle.to_tensor(kp),
            paddle.to_tensor(cu_q), paddle.to_tensor(cu_k), 4, 4, causal=True,
        )
        out.sum().backward()
        g = np.asarray(q.grad.numpy())
        assert np.isfinite(g).all() and np.abs(g).sum() > 0


@pytest.mark.tpu
class TestVarlenSplashOnTPU:
    def test_splash_varlen_matches_dense_and_is_subquadratic(self):
        import jax
        import jax.numpy as jnp

        assert jax.devices()[0].platform == "tpu"
        rng = np.random.RandomState(0)
        H, D = 4, 64
        lens = [512, 768, 256, 512]  # total 2048
        total = sum(lens)
        seqs = [0.1 * rng.randn(L, H, D).astype(np.float32) for L in lens]
        qp = np.concatenate(seqs)
        cu = np.cumsum([0] + lens).astype(np.int32)

        q = jnp.asarray(qp)
        cu_j = jnp.asarray(cu)
        scale = 1.0 / np.sqrt(D)

        out = fa.flash_attention_varlen_fwd(q, q, q, cu_j, cu_j, causal=True, scale=scale)
        assert fa.LAST_IMPL == "splash-varlen", fa.LAST_IMPL
        ref = fa._dense_varlen(q, q, q, cu_j, cu_j, True, scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-2, atol=2e-3)

        # memory: the compiled kernel's temporaries stay well under the
        # dense [H, total, total] f32 score matrix
        fn = jax.jit(lambda a: fa._splash_varlen(a, a, a, cu_j, cu_j, True, scale))
        mem = fn.lower(q).compile().memory_analysis()
        dense_bytes = H * total * total * 4
        assert mem.temp_size_in_bytes < dense_bytes / 4, (
            mem.temp_size_in_bytes, dense_bytes,
        )


def test_splash_kernel_construction_is_trace_safe():
    """Regression (round-5 TPU gqa_splash rung): make_splash_mha tree_maps
    jnp.array over its MaskInfo; constructed inside a jit trace WITHOUT
    ensure_compile_time_eval those become ambient-trace tracers, get cached,
    and leak into the separately-traced custom-vjp backward as
    UnexpectedTracerError. Construction is backend-independent, so assert on
    CPU that a cache-miss inside a trace yields only concrete mask arrays."""
    import jax
    import jax.numpy as jnp

    built = {}

    def f(x):
        # unique shape so the cache misses inside THIS trace
        built["k"] = fa._splash_kernel(2, 384, 384, 128, True,
                                       cache_tag="regress")
        return x * 2

    jax.jit(f)(jnp.ones(()))
    kernel = built["k"]
    from jax.core import Tracer

    leaves = []
    for info in (kernel.fwd_mask_info, kernel.dq_mask_info, kernel.dkv_mask_info):
        if info is not None:
            leaves += [l for l in jax.tree_util.tree_leaves(info)]
    assert leaves, "expected mask-info arrays"
    bad = [l for l in leaves if isinstance(l, Tracer)]
    assert not bad, f"tracer leaked out of splash kernel construction: {bad[:2]}"
