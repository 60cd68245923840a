"""incubate fused functionals (reference: python/paddle/incubate/nn/functional/
— fused_rotary_position_embedding, fused_rms_norm, fused_linear...).

On TPU these are jnp compositions XLA fuses into adjacent matmuls; rope gets
a Pallas kernel upgrade path in paddle_tpu/ops/.
"""
import jax
import jax.numpy as jnp

from ...framework.core import Tensor, apply, to_tensor


@jax.custom_vjp
def _barrier_diff(xs):
    return jax.lax.optimization_barrier(xs)


def _barrier_diff_fwd(xs):
    return jax.lax.optimization_barrier(xs), None


def _barrier_diff_bwd(_, cts):
    # upstream's rule exactly: the transpose is a barrier on the cotangents,
    # which is what sequences the unrolled backward chunks
    return (jax.lax.optimization_barrier(cts),)


_barrier_diff.defvjp(_barrier_diff_fwd, _barrier_diff_bwd)
_OPT_BARRIER = None  # resolved on first use


def _opt_barrier(xs):
    """lax.optimization_barrier with a differentiation fallback: releases
    before ~0.5 ship the primitive without a grad rule, so the unrolled
    fused-CE chain (differentiable chunk-loss token) would fail to
    transpose there. The custom_vjp twin is semantically identical."""
    global _OPT_BARRIER
    if _OPT_BARRIER is None:
        try:
            jax.grad(lambda x: jax.lax.optimization_barrier((x,))[0].sum())(
                jnp.ones((1,), jnp.float32))
            _OPT_BARRIER = jax.lax.optimization_barrier
        except NotImplementedError:
            _OPT_BARRIER = _barrier_diff
    return _OPT_BARRIER(xs)


def _t(x):
    return x if isinstance(x, Tensor) else to_tensor(x)


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    from ...nn import functional as F
    from ...tensor import linalg

    if transpose_weight:
        weight = linalg.t(weight)
    return F.linear(x, weight, bias)


def fused_rms_norm(x, norm_weight, norm_bias=None, epsilon=1e-6, begin_norm_axis=-1, **kw):
    from ...nn.functional.norm import rms_norm

    out = rms_norm(x, norm_weight, epsilon)
    if norm_bias is not None:
        out = out + _t(norm_bias)
    return out


def fused_layer_norm(x, norm_weight, norm_bias, epsilon=1e-5, **kw):
    from ...nn.functional.norm import layer_norm

    return layer_norm(x, [_t(x).shape[-1]], norm_weight, norm_bias, epsilon)


def fused_bias_dropout_residual_layer_norm(x, residual, bias=None, ln_scale=None, ln_bias=None,
                                           dropout_rate=0.0, ln_epsilon=1e-5, training=True):
    from ...nn import functional as F

    y = x if bias is None else x + _t(bias)
    y = F.dropout(y, dropout_rate, training=training)
    y = y + residual
    return F.layer_norm(y, [y.shape[-1]], ln_scale, ln_bias, ln_epsilon)


def rope_rotate(x, cos, sin):
    """Rotate-half rope application on [B, S, H, D]."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rotated * sin


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None, position_ids=None,
                                    use_neox_rotary_style=True, rotary_emb_base=10000.0):
    """reference: incubate fused_rope (phi/kernels/fusion/gpu/fused_rope*). Computes
    sin/cos on the fly if not given. Layout [batch, seq, heads, head_dim]."""
    q = _t(q)
    B, S, H, D = q.shape
    if sin is None or cos is None:
        inv = 1.0 / (rotary_emb_base ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
        pos = jnp.arange(S, dtype=jnp.float32)
        freqs = jnp.outer(pos, inv)  # S, D/2
        emb = jnp.concatenate([freqs, freqs], axis=-1)
        cos_a = jnp.cos(emb)[None, :, None, :]
        sin_a = jnp.sin(emb)[None, :, None, :]
    else:
        cos_a = _t(cos)._data
        sin_a = _t(sin)._data
        if cos_a.ndim == 2:
            cos_a = cos_a[None, :, None, :]
            sin_a = sin_a[None, :, None, :]
    if position_ids is not None:
        pid = _t(position_ids)._data  # B, S
        cos_a = jnp.take(cos_a[0, :, 0, :], pid, axis=0)[:, :, None, :]
        sin_a = jnp.take(sin_a[0, :, 0, :], pid, axis=0)[:, :, None, :]

    outs = []
    for t in (q, k, v):
        if t is None:
            outs.append(None)
            continue
        t = _t(t)
        outs.append(apply(lambda a: rope_rotate(a.astype(jnp.float32), cos_a, sin_a).astype(a.dtype), t, name="fused_rope"))
    return tuple(outs)


def fused_dropout_add(x, y, p=0.0, training=True, mode="upscale_in_train"):
    from ...nn import functional as F

    return F.dropout(x, p, training=training, mode=mode) + _t(y)


def swiglu(x, y=None, name=None):
    """LLaMA MLP gate: silu(x) * y (reference: phi swiglu fusion kernel)."""
    if y is None:
        a, b = jnp.split(_t(x)._data, 2, axis=-1)
        return apply(lambda v: jax.nn.silu(v[..., : v.shape[-1] // 2]) * v[..., v.shape[-1] // 2 :], _t(x), name="swiglu")
    return apply(lambda a, b: jax.nn.silu(a) * b, _t(x), _t(y), name="swiglu")


def fused_multi_head_attention(x, qkv_weight, linear_weight, pre_layer_norm=False,
                               pre_ln_scale=None, pre_ln_bias=None, ln_scale=None,
                               ln_bias=None, pre_ln_epsilon=1e-5, qkv_bias=None,
                               linear_bias=None, cache_kv=None, attn_mask=None,
                               dropout_rate=0.5, attn_dropout_rate=0.5,
                               ln_epsilon=1e-5, training=True,
                               mode="upscale_in_train", ring_id=-1,
                               add_residual=True, name=None):
    """reference: incubate.nn.functional.fused_multi_head_attention
    (fused_attention CUDA kernel): [pre-LN ->] qkv matmul -> MHA (+mask,
    attn dropout) -> out proj -> dropout -> [+residual] [-> post-LN], in
    the reference's weight layout qkv_weight [3, H, Dh, D], qkv_bias
    [3, H, Dh]. One traced expression here — XLA produces the fusion the
    reference hand-wrote.
    """
    from ...nn import functional as NF
    from ...tensor import linalg, manipulation

    if cache_kv is not None:
        raise NotImplementedError(
            "decode caches are served by GenerationMixin.generate (generation.py)"
        )
    three, H, Dh, D = qkv_weight.shape
    if three != 3 or D != x.shape[-1]:
        raise ValueError(f"qkv_weight must be [3, H, Dh, D={x.shape[-1]}], got {qkv_weight.shape}")
    B, S = x.shape[0], x.shape[1]
    residual = x
    h = x
    if pre_layer_norm:
        h = NF.layer_norm(h, [D], weight=pre_ln_scale, bias=pre_ln_bias,
                          epsilon=pre_ln_epsilon)
    w2d = manipulation.transpose(manipulation.reshape(qkv_weight, [3 * H * Dh, D]), [1, 0])
    qkv = linalg.matmul(h, w2d)  # [B, S, 3*H*Dh]
    if qkv_bias is not None:
        qkv = qkv + manipulation.reshape(qkv_bias, [3 * H * Dh])
    qkv = manipulation.reshape(qkv, [B, S, 3, H, Dh])
    q = qkv[:, :, 0]
    k = qkv[:, :, 1]
    v = qkv[:, :, 2]
    out = NF.scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask, dropout_p=attn_dropout_rate,
        is_causal=False, training=training,
    )
    out = manipulation.reshape(out, [B, S, H * Dh])
    out = linalg.matmul(out, linear_weight)
    if linear_bias is not None:
        out = out + linear_bias
    out = NF.dropout(out, p=dropout_rate, training=training, mode=mode)
    if add_residual:
        out = residual + out
    if not pre_layer_norm:
        out = NF.layer_norm(out, [D], weight=ln_scale, bias=ln_bias, epsilon=ln_epsilon)
    return out


def fused_linear_cross_entropy(hidden, weight, labels, ignore_index=-100,
                               chunk_size=None, reduction="mean",
                               checkpoint_chunks=True, name=None):
    """Cross-entropy straight from hidden states — the [N, vocab] logits
    tensor is never materialized (reference analogue: fused softmax-CE
    kernels in paddle/phi/kernels/fusion/ + PaddleNLP's parallel CE; here the
    memory win matters most: O(chunk·vocab) live instead of O(N·vocab)).

    hidden [..., H] (any leading dims), weight [H, V], labels [...] int.
    chunk_size (default 4096, or FLAGS_fused_ce_chunk_size) trades peak
    memory against loop count; a single-chunk call skips the loop entirely
    so XLA sees one fused matmul+softmax. checkpoint_chunks=False keeps
    chunk logits live for the backward (faster when memory allows); True
    recomputes them, so peak is one chunk of logits fwd + one bwd.
    Chunked matmuls stay MXU-sized for chunk_size ≥ 512.

    When the static chunk count is ≤ FLAGS_fused_ce_unroll (default 0 =
    disabled) the chunk loop is unrolled into the trace instead of lowered
    to an XLA while-loop: the r5 xprof trace of the headline training shape
    billed 8.2% of device-busy time to while-loop control for a 3-iteration
    CE loop (xprof_traces/tpu/20260731T043440). Each unrolled chunk is
    chained through `lax.optimization_barrier` on the previous chunk's loss
    so both the forward and the transposed backward schedule sequentially,
    preserving the one-chunk live-logits bound. OPT-IN until measured on
    chip: XLA *CPU* strips opt-barrier during optimization (verified — the
    barriers are in the StableHLO but absent from the optimized module, and
    unconstrained unrolled chunks overlap to 2.5× the loop's temp at the
    8192×32000 probe shape), so the memory bound is only enforceable on
    TPU, where opt-barrier is honored. Not measured on the chip yet.
    """
    import os

    if chunk_size is None:
        chunk_size = int(os.environ.get("FLAGS_fused_ce_chunk_size", 4096))
    hidden = _t(hidden)
    weight = _t(weight)
    labels = _t(labels)

    def fn(h, w, lab):
        hs = h.reshape(-1, h.shape[-1])
        ls = lab.reshape(-1).astype(jnp.int32)
        n, hd = hs.shape
        c = min(chunk_size, n)

        def chunk_fn(args):
            hc, lc = args
            logits = jnp.matmul(hc, w, preferred_element_type=jnp.float32)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            safe = jnp.clip(lc, 0, logits.shape[-1] - 1)
            ll = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
            valid = lc != ignore_index
            return jnp.where(valid, lse - ll, 0.0), valid

        if c >= n:
            body = jax.checkpoint(chunk_fn) if checkpoint_chunks else chunk_fn
            losses, valids = body((hs, ls))
        else:
            pad = (-n) % c
            if pad:
                hs = jnp.concatenate([hs, jnp.zeros((pad, hd), hs.dtype)], 0)
                ls = jnp.concatenate([ls, jnp.full((pad,), ignore_index, ls.dtype)], 0)
            hs = hs.reshape(-1, c, hd)
            ls = ls.reshape(-1, c)
            body = jax.checkpoint(chunk_fn) if checkpoint_chunks else chunk_fn
            unroll_limit = int(os.environ.get("FLAGS_fused_ce_unroll", 0))
            if hs.shape[0] <= unroll_limit:
                # Unrolled chunks alone let XLA overlap them, holding several
                # chunk-logits buffers live at once (measured 2.5x the loop's
                # temp at the 8192x32000 probe shape — worse than the full
                # logits fused-CE exists to avoid). Chaining each chunk's
                # input through an optimization_barrier with the previous
                # chunk's output forces sequential scheduling: while-loop
                # gone, same one-chunk live-memory bound.
                # The chain token must be DIFFERENTIABLE (the chunk loss):
                # the barrier's transpose then also sequences the backward —
                # chunk i's cotangent chain completes only after chunk i+1's
                # remat+grad, which is where the peak actually lives.
                outs = []
                token = jnp.zeros((1,), jnp.float32)
                for i in range(hs.shape[0]):
                    hc, _ = _opt_barrier((hs[i], token))
                    li, vi = body((hc, ls[i]))
                    token = li[:1]
                    outs.append((li, vi))
                losses = jnp.stack([o[0] for o in outs])
                valids = jnp.stack([o[1] for o in outs])
            else:
                losses, valids = jax.lax.map(body, (hs, ls))
        total = jnp.sum(losses)
        count = jnp.sum(valids)
        if reduction == "mean":
            return total / jnp.maximum(count, 1)
        if reduction == "sum":
            return total
        return losses.reshape(-1)[: lab.size].reshape(lab.shape)

    return apply(fn, hidden, weight, labels, name="fused_linear_cross_entropy")


def segment_sum(data, segment_ids, name=None):
    """reference: incubate.segment_sum — jax.ops.segment_sum, the TPU-native
    lowering of the phi segment kernels."""
    import jax

    d, s = _t(data), _t(segment_ids)
    n = int(jnp.max(s._data)) + 1 if s._data.size else 0
    return apply(lambda a, i: jax.ops.segment_sum(a, i, num_segments=n), d, s,
                 name="segment_sum")


def _segment_reduce(reducer):
    import jax

    def op(data, segment_ids, name=None):
        d, s = _t(data), _t(segment_ids)
        n = int(jnp.max(s._data)) + 1 if s._data.size else 0

        def fn(a, i):
            out = reducer(a, i, n)
            # empty segments → 0 (paddle semantics), detected by COUNT so
            # integer sentinels and legitimate ±inf values both survive
            cnt = jax.ops.segment_sum(jnp.ones(i.shape, jnp.int32), i, num_segments=n)
            cnt = cnt.reshape(cnt.shape + (1,) * (out.ndim - 1))
            return jnp.where(cnt > 0, out, jnp.zeros((), out.dtype))

        return apply(fn, d, s, name="segment_reduce")

    return op


def _seg_mean(a, i, n):
    import jax

    tot = jax.ops.segment_sum(a, i, num_segments=n)
    cnt = jax.ops.segment_sum(jnp.ones(a.shape[:1], a.dtype), i, num_segments=n)
    cnt = cnt.reshape(cnt.shape + (1,) * (a.ndim - 1))
    return tot / jnp.maximum(cnt, 1)


def _seg_max(a, i, n):
    import jax

    return jax.ops.segment_max(a, i, num_segments=n)


def _seg_min(a, i, n):
    import jax

    return jax.ops.segment_min(a, i, num_segments=n)


segment_mean = _segment_reduce(_seg_mean)
segment_max = _segment_reduce(_seg_max)
segment_min = _segment_reduce(_seg_min)


def softmax_mask_fuse(x, mask, name=None):
    """reference: incubate.softmax_mask_fuse — additive mask + softmax in
    one fused expression (XLA fuses into adjacent matmuls)."""
    return apply(
        lambda a, m: jax.nn.softmax(a.astype(jnp.float32) + m.astype(jnp.float32), axis=-1).astype(a.dtype),
        _t(x), _t(mask), name="softmax_mask_fuse",
    )


def softmax_mask_fuse_upper_triangle(x, name=None):
    def fn(a):
        s = a.shape[-1]
        mask = jnp.tril(jnp.ones((a.shape[-2], s), bool), k=s - a.shape[-2])
        logits = jnp.where(mask, a.astype(jnp.float32), jnp.finfo(jnp.float32).min)
        return jax.nn.softmax(logits, axis=-1).astype(a.dtype)

    return apply(fn, _t(x), name="softmax_mask_fuse_upper_triangle")


def graph_send_recv(x, src_index, dst_index, reduce_op="sum", out_size=None, name=None):
    """reference: incubate.graph_send_recv — gather messages at src, reduce
    at dst (segment reduction over edges)."""
    import jax

    if reduce_op not in ("sum", "max", "min", "mean"):
        raise ValueError(f"graph_send_recv: unsupported reduce_op {reduce_op!r}")
    xd, si, di = _t(x), _t(src_index), _t(dst_index)
    n = out_size or int(xd.shape[0])
    red = {"sum": jax.ops.segment_sum, "max": jax.ops.segment_max,
           "min": jax.ops.segment_min}.get(reduce_op)

    def fn(a, s, d):
        msgs = a[s]
        cnt = jax.ops.segment_sum(jnp.ones(d.shape, jnp.int32), d, num_segments=n)
        cshape = cnt.reshape(cnt.shape + (1,) * (a.ndim - 1))
        if red is not None:
            out = red(msgs, d, num_segments=n)
            if reduce_op in ("max", "min"):
                out = jnp.where(cshape > 0, out, jnp.zeros((), out.dtype))
            return out
        tot = jax.ops.segment_sum(msgs, d, num_segments=n)
        return tot / jnp.maximum(cshape, 1).astype(tot.dtype)

    return apply(fn, xd, si, di, name="graph_send_recv")
