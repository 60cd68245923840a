"""Lightning (decayed linear) attention over recurrent-state slots
(reference: Lightning Attention-2, arXiv:2401.04658; models/minicpm_sala.py
is the block that uses it).

A head keeps ONE matrix of state a row, `S [key dim, value dim]` in float32,
where an attention head keeps a key and a value a token:

    S_t = lambda_h S_{t-1} + k_t^T v_t        o_t = (q_t * scale) S_t

with a fixed decay a head, `lambda_h = exp(-slope_h)`. Equivalently
`o_t = sum_{s<=t} lambda_h^(t-s) (q_t . k_s) scale v_s`: causal attention with
no softmax, which is why a prompt can be folded in chunk by chunk.

The cache of a layer is a set of STATE SLOTS `[max_seqs, heads, key dim,
value dim]`, indexed by the serving engine's row: it has no length and no
pages (`StateSlotSpec`; the K-and-V twin is ops.paged_attention.KVCacheSpec).
A slot is zeroed INSIDE the step program when its row starts at length 0, so
a reused slot costs the host nothing, and a dead row (an empty slot, a row
still in prefill during a mixed step's scan) leaves its slot as it was.

Two forms, as the engine's two cache views ask:
- `lightning_decode`: one token a row (memory-bound: a live row's state is
  read and written once, 4 MiB at 32 heads of 128 x 128, in place; a dead
  row's is not touched while at most `_WALK_UP_TO` rows live).
- `lightning_prefill`: the packed stream of a mixed step. Each row's span of
  two or more tokens continues the row's state in chunks of `chunk` tokens:
  inside a chunk the decayed scores `q k^T` ride the MXU, between chunks the
  state carries everything earlier, so a span never costs O(S^2). One-token
  spans (decode rows, a prompt's lone last token) take the decode form.

Tier (`LAST_IMPL`, at trace time): `lightning-xla`, plain `jax.numpy` under
`lax` loops whose trip counts are operands (the spans' lengths), on every
backend. A Mosaic kernel (`lightning-kernel`) is ROADMAP's: the chunk loop
already runs on the MXU and the benchmark's roofline share says what a
kernel would have to beat.
"""
import math

import jax
import jax.numpy as jnp

from .cache_specs import cache_view, live_rows

LAST_IMPL = None  # "lightning-xla" — at trace time

_HI = jax.lax.Precision.HIGHEST  # products that read the float32 state


def decay_slopes(num_heads):
    """The per-head decay slopes `2^(-8 (h+1) / H)` (ALiBi's geometric
    sequence, as Lightning Attention sets them): lambda_h = exp(-slope_h)."""
    return jnp.asarray([2.0 ** (-8.0 * (h + 1) / num_heads)
                        for h in range(num_heads)], jnp.float32)


@cache_view("state", "lengths", "live")
class StateSlotCache:
    """One layer's state slots seen by a decode step.

    state:   [S, heads, key dim, value dim] float32 (a layer whose slot is
             several arrays: the tuple of them, each [S, ...])
    lengths: [S] int32 — the row's tokens BEFORE this step (0: a fresh slot,
             its state reads as zeros)
    live:    [S] bool — the rows a request holds; a dead row's slot is left
             as it was
    """

    state: jax.Array
    lengths: jax.Array
    live: jax.Array


@cache_view("state", "kv_lens", "cu_q_lens", "row_of", "token_pos", "valid")
class StateSlotRaggedCache:
    """One layer's state slots seen by a mixed prefill+decode step: the
    packed-stream fields are RaggedLayerCache's, with the same meaning
    (`kv_lens` counts a row's tokens AFTER this step; a row of no token is
    left as it was)."""

    state: jax.Array
    kv_lens: jax.Array
    cu_q_lens: jax.Array
    row_of: jax.Array
    token_pos: jax.Array
    valid: jax.Array


class StateSlotSpec:
    """The cache of ONE recurrent layer, as the serving engine asks for it
    (ops/cache_specs.py puts a model's layers together): a slot a row, with
    no length and no pages, so the planes that share or move pages refuse
    it by name. A slot is ONE array (`StateSlotSpec(heads, key, value)`: a
    linear-attention layer's state; the views' `state` is that array) or a
    layer's TUPLE of arrays (`StateSlotSpec(shape, shape, ...,
    dtypes=...)`: a state-space layer's state and its convolution's last
    inputs, ops/selective_scan.py; the views' `state` is the tuple). An
    array is float32 (a running sum) unless its entry of `dtypes` says
    otherwise; None there is the model's dtype."""

    kind = "state slots"
    has_state = True
    allocator_pages = False

    def __init__(self, *shapes, dtypes=None):
        self.one = isinstance(shapes[0], int)
        self.shapes = [tuple(shapes)] if self.one else [
            tuple(s) for s in shapes]
        self.dtypes = list(dtypes or [jnp.float32] * len(self.shapes))

    def make_pool(self, num_pages, page_size, dtype, kv_cache_dtype=None,
                  max_seqs=None, prefill_chunk=None):
        if max_seqs is None:
            raise ValueError("state slots are a row each: make_pools needs "
                             "max_seqs")
        return tuple(jnp.zeros((max_seqs,) + shape, d or dtype)
                     for shape, d in zip(self.shapes, self.dtypes))

    def refuses(self, plane):
        if plane in ("prefix_cache", "handoff"):
            return ("shares or moves a row's pages; a recurrent layer "
                    "keeps a state slot a row, which has none "
                    f"({type(self).__name__})")
        return None

    def _state(self, pool):
        return pool[0] if self.one else tuple(pool)

    def paged(self, pool, page_table, lengths, live):
        return StateSlotCache(self._state(pool), lengths, live)

    def ragged(self, pool, page_table, kv_lens, cu, row_of, token_pos, valid):
        return StateSlotRaggedCache(self._state(pool), kv_lens, cu, row_of,
                                    token_pos, valid)

    def pool_of(self, present):
        return (present.state,) if self.one else tuple(present.state)


#: `lightning_decode` walks its live rows one a trip up to this many of them
#: and past that updates every slot at once. The number is the sweep's
#: (`scripts/sala_chip_checks.py sweep` on the v5e, 16 slots of 32 x 128 x
#: 128; PERF.md section 6, PR 36): the walk costs 17 us and 11.3 us a row
#: more (95.5 at 8 rows, 107 at 9), every slot at once 105 whatever lives.
_WALK_UP_TO = 8


def _decode_slots(q, k, v, state, lengths, live, lam):
    """Every slot at once: a dead row's slot is read and written back as it
    was. q (scaled), k and v are float32."""
    s0 = jnp.where((lengths > 0)[:, None, None, None], state, 0.0)
    s1 = lam * s0 + jnp.einsum("bhd,bhe->bhde", k, v)
    o = jnp.einsum("bhd,bhde->bhe", q, s1, precision=_HI)
    return (jnp.where(live[:, None, None], o, 0.0),
            jnp.where(live[:, None, None, None], s1, state))


def _decode_live(q, k, v, state, lengths, rows, n, lam):
    """The live rows alone (`rows[:n]`), one a trip: a row's slot is read,
    updated and written back in place; a dead row's slot is neither read nor
    written."""
    def trip(i, carry):
        o, st = carry
        r = rows[i]
        s0 = jnp.where(lengths[r] > 0,
                       jax.lax.dynamic_index_in_dim(st, r, 0, False), 0.0)
        s1 = lam[0] * s0 + jnp.einsum("hd,he->hde", k[r], v[r])
        o_r = jnp.einsum("hd,hde->he", q[r], s1, precision=_HI)
        return (jax.lax.dynamic_update_index_in_dim(o, o_r, r, 0),
                jax.lax.dynamic_update_index_in_dim(st, s1, r, 0))

    return jax.lax.fori_loop(
        0, n, trip, (jnp.zeros(q.shape[:2] + v.shape[-1:], jnp.float32),
                     state))


def lightning_decode(q, k, v, state, lengths, live, slopes, scale=None):
    """One token a row. q, k, v: [B, H, D*]; state [B, H, Dk, Dv] float32;
    `lengths` the rows' tokens BEFORE this one. Returns (o [B, H, Dv] in
    q's dtype, the new state): a live row's state decays, takes `k^T v`
    and answers `q`; a row at length 0 starts from zeros; a dead row keeps
    its slot and returns zeros."""
    global LAST_IMPL
    LAST_IMPL = "lightning-xla"
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    lam = jnp.exp(-slopes)[None, :, None, None]
    qkv = (jnp.asarray(q, jnp.float32) * scale, jnp.asarray(k, jnp.float32),
           jnp.asarray(v, jnp.float32))
    rows, n = live_rows(live)
    o, state = jax.lax.cond(
        n <= _WALK_UP_TO,
        lambda: _decode_live(*qkv, state, lengths, rows, n, lam),
        lambda: _decode_slots(*qkv, state, lengths, live, lam))
    return o.astype(q.dtype), state


def lightning_prefill(q, k, v, state, kv_lens, cu_q_lens, slopes, scale=None,
                      chunk=128):
    """The packed stream's spans of two or more tokens. q, k, v: [T, H, D*]
    (row b owns tokens cu_q_lens[b] : cu_q_lens[b+1]); state
    [S, H, Dk, Dv] float32; kv_lens [S] the rows' tokens AFTER this step.
    Returns (o [T, H, Dv], the new state). Rows of one token or none are not
    touched here (their o stays zero): `lightning_decode` has them."""
    global LAST_IMPL
    LAST_IMPL = "lightning-xla"
    T, H, Dk = q.shape
    Dv = v.shape[-1]
    C = chunk
    scale = scale if scale is not None else 1.0 / math.sqrt(Dk)
    q_lens = cu_q_lens[1:] - cu_q_lens[:-1]
    before = kv_lens - q_lens
    pad = ((0, C), (0, 0), (0, 0))
    qp, kp, vp = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    i = jnp.arange(C)
    # within a chunk: lambda^(i-j) for j <= i, else 0   [H, C, C]
    gap = (i[:, None] - i[None, :]).astype(jnp.float32)
    causal = (i[:, None] >= i[None, :])[None]
    decay = jnp.where(causal,
                      jnp.exp(-slopes[:, None, None] * jnp.maximum(gap, 0.0)),
                      0.0)
    # what the carried state is worth to token i: lambda^(i+1)   [C, H]
    carry_w = jnp.exp(-slopes[None, :] * (i[:, None] + 1.0))

    def chunk_step(c, carry, start, n):
        o, s = carry
        at = start + c * C
        m = jnp.minimum(C, n - c * C)                 # the chunk's tokens
        held = (i < m)[:, None, None]

        def take(a):
            return jnp.where(held, jax.lax.dynamic_slice_in_dim(a, at, C), 0)

        qc, kc, vc = take(qp), take(kp), take(vp)
        a = jnp.einsum("ihd,jhd->hij", qc, kc,
                       preferred_element_type=jnp.float32) * decay
        intra = jnp.einsum("hij,jhe->ihe", a.astype(vc.dtype), vc,
                           preferred_element_type=jnp.float32)
        inter = jnp.einsum("ihd,hde->ihe",
                           qc.astype(jnp.float32) * carry_w[:, :, None], s,
                           precision=_HI)
        oc = ((intra + inter) * scale).astype(o.dtype)
        old = jax.lax.dynamic_slice_in_dim(o, at, C)
        o = jax.lax.dynamic_update_slice_in_dim(
            o, jnp.where(held, oc, old), at, axis=0)
        # the chunk's own keys, each decayed to the chunk's end (a token
        # past the span is zero and adds nothing)
        left = jnp.exp(-slopes[None, :] * jnp.maximum(
            (m - 1 - i)[:, None].astype(jnp.float32), 0.0))       # [C, H]
        s = (jnp.exp(-slopes * m.astype(jnp.float32))[:, None, None] * s
             + jnp.einsum("jhd,jhe->hde",
                          kc.astype(jnp.float32) * left[:, :, None],
                          vc.astype(jnp.float32), precision=_HI))
        return o, s

    def row_step(r, carry):
        o, st = carry
        n = q_lens[r]

        def span(args):
            o, st = args
            s0 = jnp.where(before[r] > 0, st[r], 0.0)
            o, s = jax.lax.fori_loop(
                0, (n + C - 1) // C,
                lambda c, cr: chunk_step(c, cr, cu_q_lens[r], n), (o, s0))
            return o, jax.lax.dynamic_update_index_in_dim(st, s, r, axis=0)

        return jax.lax.cond(n > 1, span, lambda args: args, (o, st))

    o0 = jnp.zeros((T + C, H, Dv), q.dtype)
    o, state = jax.lax.fori_loop(0, q_lens.shape[0], row_step, (o0, state))
    return o[:T], state


def lightning_ragged(q, k, v, cache, slopes, scale=None, chunk=128):
    """A mixed step's packed stream through one layer's state slots: spans
    of two or more tokens by `lightning_prefill`, one-token spans by
    `lightning_decode` (each at its span's start). Returns (o [T, H, Dv],
    the new state, the rows whose state was updated)."""
    T = q.shape[0]
    q_lens = cache.cu_q_lens[1:] - cache.cu_q_lens[:-1]
    with jax.named_scope("sala.lightning.prefill"):
        o, state = lightning_prefill(q, k, v, cache.state, cache.kv_lens,
                                     cache.cu_q_lens, slopes, scale, chunk)
    at = jnp.minimum(cache.cu_q_lens[:-1], T - 1)
    one = q_lens == 1
    with jax.named_scope("sala.lightning.decode"):
        o1, state = lightning_decode(q[at], k[at], v[at], state,
                                     cache.kv_lens - q_lens, one, slopes,
                                     scale)
    # (a row of no token aliases a neighbour's start: dropped)
    o = o.at[jnp.where(one, at, T)].set(o1, mode="drop")
    return o, state, jnp.sum(q_lens > 0).astype(jnp.int32)
