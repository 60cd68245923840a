"""Expanded latent attention for the prefill spans of a packed token stream:
K and V are expanded from the latent pages (ops/latent_pool.py) a block of
pages at a time, never a whole context (16,384 tokens x 64 heads x 320 x 2 B
would be 0.67 GB a layer).

    [k_nope | v] = c_kv W_kvb (per head) ;  k = [k_nope | k_rope]
    o = softmax(scale . q k^T, causal per row) v

The stream is the ragged plane's (ops/ragged_paged_attention.py): row b owns
tokens `cu_q_lens[b] : cu_q_lens[b+1]`, and query i of the row sees kv
positions `< kv_lens[b] - q_len + i + 1`. Only rows with TWO OR MORE query
tokens are computed here; a one-token row costs its whole context's
expansion on this path and a 576-wide dot product a cached token on the
absorbed one (ops/mla_decode_attention.py), so the caller sends those there
(same mathematics). Their rows of the result are zeros.

Tiers, chosen at trace time (`LAST_IMPL`; a tier that cannot run raises):
- `mla-prefill-xla`: per row with a span, a `fori_loop` over blocks of pages
  (expand once a block) and inside it over the row's query blocks that the
  block is causal for, online softmax in f32. Trip counts are operands, so
  one program serves every packing. Every backend.
"""
import jax
import jax.numpy as jnp

LAST_IMPL = None  # "mla-prefill-xla"

Q_BLOCK = 256
KV_BLOCK = 512


def _prefill_xla(q, pages, w_uk, w_uv, kv_lens, page_indices, cu, rank,
                 scale):
    T, H, Dq = q.shape
    dn, dv = w_uk.shape[1], w_uv.shape[2]
    bs = pages.shape[1]
    S = kv_lens.shape[0]
    qb = min(Q_BLOCK, -(-T // 8) * 8)
    nq_max = -(-T // qb)
    Tq = nq_max * qb                      # a row's window of the stream
    ppb = max(1, min(KV_BLOCK // bs, page_indices.shape[1]))
    kb = ppb * bs
    table = jnp.pad(page_indices,
                    ((0, 0), (0, -page_indices.shape[1] % ppb)))
    dt = pages.dtype
    # heads lead; padded so that a window starting at any cu[b] <= T fits
    qs = jnp.swapaxes((q.astype(jnp.float32) * scale).astype(dt), 0, 1)
    qs = jnp.pad(qs, ((0, 0), (0, Tq), (0, 0)))            # [H, T + Tq, Dq]
    out0 = jnp.zeros((H, T + Tq, dv), dt)

    def row(b, out):
        start, q_len, kv_len = cu[b], cu[b + 1] - cu[b], kv_lens[b]

        def span(out):
            past = kv_len - q_len      # tokens of the row before its span
            win = jax.lax.dynamic_slice_in_dim(qs, start, Tq, axis=1)
            win = win.reshape(H, nq_max, qb, Dq)

            def kv_block(j, carry):
                pid = jax.lax.dynamic_slice_in_dim(table[b], j * ppb, ppb)
                lat = pages[pid].reshape(kb, -1)
                c, k_rope = lat[:, :rank], lat[:, rank:rank + Dq - dn]
                k_nope = jnp.einsum("kc,hdc->hkd", c, w_uk,
                                    preferred_element_type=jnp.float32
                                    ).astype(dt)
                v = jnp.einsum("kc,hcd->hkd", c, w_uv,
                               preferred_element_type=jnp.float32).astype(dt)
                kv_pos = j * kb + jnp.arange(kb)

                def q_block(i, carry):
                    o, l, m = carry
                    qi = win[:, i]                          # [H, qb, Dq]
                    s = (jnp.einsum("hqd,hkd->hqk", qi[..., :dn], k_nope,
                                    preferred_element_type=jnp.float32)
                         + jnp.einsum("hqr,kr->hqk", qi[..., dn:], k_rope,
                                      preferred_element_type=jnp.float32))
                    q_pos = i * qb + jnp.arange(qb)
                    see = ((kv_pos[None, :] < (past + q_pos + 1)[:, None])
                           & (q_pos < q_len)[:, None])[None]
                    s = jnp.where(see, s, -1e30)
                    m_i, l_i, o_i = m[:, i], l[:, i], o[:, i]
                    m_new = jnp.maximum(m_i, s.max(axis=-1))
                    p = jnp.where(see, jnp.exp(s - m_new[..., None]), 0.0)
                    corr = jnp.exp(m_i - m_new)
                    l_i = l_i * corr + p.sum(axis=-1)
                    o_i = o_i * corr[..., None] + jnp.einsum(
                        "hqk,hkd->hqd", p.astype(dt), v,
                        preferred_element_type=jnp.float32)
                    return (o.at[:, i].set(o_i), l.at[:, i].set(l_i),
                            m.at[:, i].set(m_new))

                # the first query block whose last token sees this kv block
                first = jnp.maximum(j * kb - past, 0) // qb
                return jax.lax.fori_loop(first, -(-q_len // qb), q_block,
                                         carry)

            o, l, _ = jax.lax.fori_loop(
                0, -(-kv_len // kb), kv_block,
                (jnp.zeros((H, nq_max, qb, dv), jnp.float32),
                 jnp.zeros((H, nq_max, qb), jnp.float32),
                 jnp.full((H, nq_max, qb), -1e30, jnp.float32)))
            o = (o / jnp.maximum(l, 1e-30)[..., None]).reshape(H, Tq, dv)
            mine = (jnp.arange(Tq) < q_len)[None, :, None]
            old = jax.lax.dynamic_slice_in_dim(out, start, Tq, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(
                out, jnp.where(mine, o.astype(dt), old), start, axis=1)

        return jax.lax.cond(q_len >= 2, span, lambda out: out, out)

    out = jax.lax.fori_loop(0, S, row, out0)
    return jnp.swapaxes(out[:, :T], 0, 1)


def mla_prefill_attention(q_nope, q_rope, pages, w_uk, w_uv, kv_lens,
                          page_indices, cu_q_lens, scale):
    """q_nope [T, H, nope], q_rope [T, H, rope]: the packed stream; pages
    [P, bs, stored width] (rank + rope, then zero pad lanes); w_uk
    [H, nope, rank] and w_uv [H, rank, v]: the
    head's halves of kv_b_proj; kv_lens [S] AFTER this step's writes.
    Returns [T, H, v] in the pool's dtype (zeros for pad tokens and for
    rows of one query token)."""
    global LAST_IMPL
    LAST_IMPL = "mla-prefill-xla"
    return _prefill_xla(jnp.concatenate([q_nope, q_rope], axis=-1), pages,
                        w_uk, w_uv, kv_lens, page_indices, cu_q_lens,
                        w_uk.shape[-1], scale)
