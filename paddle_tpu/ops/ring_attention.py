"""Ring attention over the ICI ring (reference capability: context/ring
parallelism — ecosystem RingFlashAttention atop core sep groups, SURVEY.md
§5 long-context; here first-class).

Blockwise flash attention with the KV blocks rotating around the mesh axis
by `lax.ppermute` while Q stays resident: each of the N steps computes one
Q-block × KV-block tile with online-softmax accumulation (running max m,
normalizer l, unnormalized output o — the flash-attention recurrence), so
the sequence scales with the number of chips on the ring. Causal masking is
by GLOBAL positions (block skew): q_pos = q_shard·S + i, k_pos = src_shard·S
+ j, mask q_pos ≥ k_pos.

Two inner-tile tiers (VERDICT r4 item 3 — no [S_local, S_local] f32 scores
buffer in either):

- kernel ("ring-splash"): on TPU the resident Pallas flash kernel consumes
  the visiting KV shard with proper VMEM tiling (`_flash_attention(...,
  save_residuals=True)` → per-shard (o, l, m), merged across ring steps by
  the online-softmax combine). Fully-masked visits (causal, src > my) skip
  compute entirely. Backward recomputes through the blockwise math path via
  custom_vjp — flash-style recompute, never a dense score matrix.
- blockwise math ("ring-block"): the visiting KV shard is consumed in
  `block_k`-sized chunks inside a lax.scan, peaking at [B, H, S_local,
  block_k] f32 instead of [B, H, S_local, S_local]. Runs on every backend
  and is the AD path.

Use inside shard_map with the sequence dim sharded on a mesh axis (canonical:
"sep"). Layout: [B, H, S_local, D].
"""
import functools
import math

import jax
import jax.numpy as jnp

# which tier the last trace selected ("ring-splash" | "ring-block"); bench
# and tests read it the way flash_attention.LAST_IMPL is read
LAST_IMPL = None


def _pick_block_k(S, block_k=None):
    from .flash_attention import _BLOCK_CONFIG

    bk = min(block_k or _BLOCK_CONFIG["block_k"] or 512, S)
    while S % bk:
        bk //= 2
    return max(bk, 1)


def _online_merge(o, l, m, o2, l2, m2):
    """Merge accumulated (o: unnormalized f32, l, m) with one block's
    NORMALIZED kernel output o2 and its softmax stats (l2 = sum-exp,
    m2 = row max), all stats [..., S]."""
    m_new = jnp.maximum(m, m2)
    ca = jnp.exp(m - m_new)
    cb = jnp.exp(m2 - m_new) * l2
    l_new = l * ca + cb
    o_new = o * ca[..., None] + o2.astype(jnp.float32) * cb[..., None]
    return o_new, l_new, m_new


def _ring_block_impl(q, k, v, axis_name, causal, scale, block_k):
    """Blockwise-math ring: every backend, AD-compatible, O(S·block_k) scores.
    GQA-aware: k/v may carry fewer (kv) heads than q — the ring messages
    move the UNEXPANDED kv shard (Hkv heads of ICI bytes, not Hq)."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, S, D)
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    bk = _pick_block_k(S, block_k)
    nblk = S // bk

    o0 = jnp.zeros((B, Hkv, G, S, D), jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, S, 1), jnp.float32)
    m0 = jnp.full((B, Hkv, G, S, 1), -1e30, jnp.float32)
    back_perm = [(j, (j - 1) % n) for j in range(n)]  # kv block walks the ring

    qpos = my * S + jnp.arange(S)[:, None]

    def body(carry, i):
        o, l, m, k_cur, v_cur = carry
        src = (my + i) % n  # whose kv block we hold at step i

        def consume(olm):
            o, l, m = olm

            def blk(carry2, j):
                o, l, m = carry2
                kb = jax.lax.dynamic_slice_in_dim(k_cur, j * bk, bk, axis=2)
                vb = jax.lax.dynamic_slice_in_dim(v_cur, j * bk, bk, axis=2)
                s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kb).astype(jnp.float32) * scale
                if causal:
                    kpos = src * S + j * bk + jnp.arange(bk)[None, :]
                    s = jnp.where(qpos >= kpos, s, -1e30)
                m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m - m_new)
                l = l * corr + p.sum(axis=-1, keepdims=True)
                o = o * corr + jnp.einsum(
                    "bhgqk,bhkd->bhgqd", p, vb.astype(jnp.float32)
                )
                return (o, l, m_new), None

            (o, l, m), _ = jax.lax.scan(blk, (o, l, m), jnp.arange(nblk))
            return (o, l, m)

        if causal:
            # a visit with src > my is fully masked (global-position skew):
            # skip its matmuls entirely — ~half the ring FLOPs on average
            o, l, m = jax.lax.cond(src <= my, consume, lambda olm: olm, (o, l, m))
        else:
            o, l, m = consume((o, l, m))
        k_cur = jax.lax.ppermute(k_cur, axis_name, back_perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, back_perm)
        return (o, l, m, k_cur, v_cur), None

    # scan (not fori_loop): reverse-mode AD flows through it, and n is a
    # static mesh-axis size so the ring unrolls to a fixed trip count
    (o, l, m, _, _), _ = jax.lax.scan(body, (o0, l0, m0, k, v), jnp.arange(n))
    return (o / jnp.maximum(l, 1e-30)).reshape(B, H, S, D).astype(q.dtype)


def _ring_kernel_fwd_impl(q, k, v, axis_name, causal, scale):
    """Kernel-tier forward: the Pallas flash kernel eats each visiting KV
    shard whole (VMEM-tiled inside), (o, l, m) merged across visits."""
    from jax.experimental.pallas.ops.tpu import flash_attention as _fa

    from .flash_attention import _block_sizes

    B, H, S, D = q.shape
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    bq, bk = _block_sizes(S, S)
    sizes = _fa.BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq,
    )

    Hkv = k.shape[1]

    def fa_call(k_cur, v_cur, causal_flag):
        if Hkv != H:  # GQA: expand at the kernel call only — the ring
            # messages carry the unexpanded Hkv heads
            k_cur = jnp.repeat(k_cur, H // Hkv, axis=1)
            v_cur = jnp.repeat(v_cur, H // Hkv, axis=1)
        # save_residuals=True: (normalized o, l = sum-exp, m = row max)
        return _fa._flash_attention(
            q, k_cur, v_cur, None, None, True, causal_flag, scale, sizes, False
        )

    o0 = jnp.zeros((B, H, S, D), jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    m0 = jnp.full((B, H, S), -1e30, jnp.float32)
    back_perm = [(j, (j - 1) % n) for j in range(n)]

    def body(carry, i):
        o, l, m, k_cur, v_cur = carry
        src = (my + i) % n

        def full(olm):
            o2, l2, m2 = fa_call(k_cur, v_cur, False)
            return _online_merge(*olm, o2, l2.reshape(B, H, S), m2.reshape(B, H, S))

        def diag(olm):
            o2, l2, m2 = fa_call(k_cur, v_cur, True)
            return _online_merge(*olm, o2, l2.reshape(B, H, S), m2.reshape(B, H, S))

        def skip(olm):
            return olm

        if causal:
            idx = jnp.where(src == my, 1, jnp.where(src < my, 0, 2))
            o, l, m = jax.lax.switch(idx, (full, diag, skip), (o, l, m))
        else:
            o, l, m = full((o, l, m))
        k_cur = jax.lax.ppermute(k_cur, axis_name, back_perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, back_perm)
        return (o, l, m, k_cur, v_cur), None

    (o, l, m, _, _), _ = jax.lax.scan(body, (o0, l0, m0, k, v), jnp.arange(n))
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_kernel(q, k, v, axis_name, causal, scale, block_k):
    return _ring_kernel_fwd_impl(q, k, v, axis_name, causal, scale)


def _ring_kernel_vjp_fwd(q, k, v, axis_name, causal, scale, block_k):
    return _ring_kernel_fwd_impl(q, k, v, axis_name, causal, scale), (q, k, v)


def _ring_kernel_vjp_bwd(axis_name, causal, scale, block_k, res, g):
    # flash-style recompute: grads through the blockwise math ring (no dense
    # score matrix); the fwd kernel's residuals beyond q/k/v are not needed
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: _ring_block_impl(q, k, v, axis_name, causal, scale, block_k),
        q, k, v,
    )
    return vjp(g)


_ring_kernel.defvjp(_ring_kernel_vjp_fwd, _ring_kernel_vjp_bwd)


def ring_attention(q, k, v, axis_name="sep", causal=False, scale=None,
                   block_k=None, impl=None):
    """q/k/v: [B, H, S_local, D] local shards inside shard_map; the logical
    sequence is S_local × axis_size(axis_name). Returns [B, H, S_local, D].

    impl: None (auto: Pallas kernel tier on TPU when shapes allow, else
    blockwise math), "kernel", or "block"."""
    global LAST_IMPL
    B, H, S, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    from .flash_attention import _FORCE_XLA, _on_tpu

    dim_ok = D % 128 == 0 or D in (64, 96, 128, 256)
    auto_kernel = _on_tpu() and S % 128 == 0 and dim_ok and not _FORCE_XLA
    if impl == "kernel" or (impl is None and auto_kernel):
        out = _ring_kernel(q, k, v, axis_name, causal, scale, block_k)
        LAST_IMPL = "ring-splash"
        return out
    LAST_IMPL = "ring-block"
    return _ring_block_impl(q, k, v, axis_name, causal, scale, block_k)


def ulysses_attention(q, k, v, axis_name="sep", causal=False, scale=None, attn_impl=None):
    """Ulysses/sep segment parallelism (reference: meta_parallel/
    segment_parallel.py sep axis — all-to-all head↔seq exchange around
    attention). q/k/v: [B, S_local, H, D] with H divisible by axis size.

    all_to_all swaps the sharded dim: seq-sharded → head-sharded, runs FULL
    sequence attention on H/N heads, then swaps back. Two all_to_alls over
    ICI replace the reference's global_scatter-style exchange.
    """
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name, tiled=True)
    # [B, S_loc, H, D] -> [B, S, H_loc, D]
    q_f = a2a(q, split_axis=2, concat_axis=1)
    k_f = a2a(k, split_axis=2, concat_axis=1)
    v_f = a2a(v, split_axis=2, concat_axis=1)
    if attn_impl is None:
        def attn_impl(qq, kk, vv):
            B, Sq, Hh, Dd = qq.shape
            sc = scale if scale is not None else 1.0 / math.sqrt(Dd)
            s = jnp.einsum("bqhd,bkhd->bhqk", qq, kk).astype(jnp.float32) * sc
            if causal:
                mask = jnp.tril(jnp.ones((Sq, kk.shape[1]), bool))
                s = jnp.where(mask, s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, vv.astype(jnp.float32)).astype(qq.dtype)
    out = attn_impl(q_f, k_f, v_f)
    # [B, S, H_loc, D] -> [B, S_loc, H, D]
    return a2a(out, split_axis=1, concat_axis=2)
