"""Mamba-1's causal depthwise convolution and selective scan over
recurrent-state slots (reference: Mamba, arXiv:2312.00752;
models/phi4flash.py is the block that uses them).

A state-space layer mixes tokens through a state `h [d_inner, d_state]` a
row, where an attention layer keeps a key and a value a token. Per token t,
with `c_t` the convolved input and `dt_t`, `B_t`, `C_t` projected from it:

    c_t = silu(conv_b + sum_k conv_w[k] * u_{t-(K-1)+k})     (zeros before the row)
    h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * c_t) (x) B_t    A = -exp(A_log)
    y_t = h_t C_t + D * c_t

`h`, `A`, `dt` and the recurrence are float32, as the published kernel keeps
them. The cache of a layer is a STATE SLOT a row (`ssm_slot_spec`, built on
ops.lightning_attention.StateSlotSpec): the state, stored `[d_state,
d_inner]` so that the wide axis lies along the lanes (a minor dimension of 16
would be padded to 128), and the convolution's last `K - 1` inputs
`[K - 1, d_inner]` in the model's dtype. A slot has no length and no pages.
It reads as zeros when its row starts at length 0, INSIDE the step program,
so a slot another request just left costs the host nothing and shows
nothing; a dead row (an empty slot, a row still in prefill during a mixed
step's scan) leaves its slot as it was.

The forms, as the engine's two cache views ask:
- `causal_conv_ragged` / `selective_scan_ragged`: the packed stream of a
  mixed step. The convolution is one vectorised pass over the stream (a
  token's predecessors come from its own span, then from the slot's tail).
  The scan continues each span of two or more tokens from the row's slot in
  chunks of `chunk` tokens under `lax` loops whose trip counts are operands
  (the spans' lengths): a chunk's `dt`, `c`, `B`, `C` are sliced from the
  stream, its steps are unrolled on the `[d_state, d_inner]` state, and
  only `y` goes back, so no `[T, d_inner, d_state]` tensor ever exists.
  One-token spans (decode rows, a prompt's lone last token) take the decode
  form, every row at once.
- `causal_conv_decode` / `selective_scan_decode`: one token a row, every
  row at once (memory-bound: a row's state is read and written once).

Tier (`LAST_IMPL`, at trace time): `ssm-xla`, plain `jax.numpy`, on every
backend. A Mosaic kernel (`ssm-kernel`) that keeps the state in VMEM across
a span is ROADMAP's; the benchmark's roofline shares say what it has to
beat.
"""
import jax
import jax.numpy as jnp

from .lightning_attention import StateSlotSpec

LAST_IMPL = None  # "ssm-xla" — at trace time

#: the dtype a slot keeps the state in. float32, as the published kernel; the
#: benchmark's lower-precision control sets bfloat16 here and has to fail
STATE_DTYPE = jnp.float32


def ssm_slot_spec(d_inner, d_state, d_conv):
    """The slot of ONE state-space layer: (state [d_state, d_inner] in
    `STATE_DTYPE`, the convolution's last inputs [d_conv - 1, d_inner] in
    the model's dtype)."""
    return StateSlotSpec((d_state, d_inner), (d_conv - 1, d_inner),
                         dtypes=(STATE_DTYPE, None))


def _conv_out(taps, w, b, dtype):
    """silu(b + sum_k w[k] * taps[k]) in float32, back in `dtype`."""
    acc = b.astype(jnp.float32)
    for k, tap in enumerate(taps):
        acc = acc + w[k].astype(jnp.float32) * tap.astype(jnp.float32)
    return jax.nn.silu(acc).astype(dtype)


def causal_conv_ragged(u, w, b, tail, kv_lens, cu_q_lens, row_of):
    """The packed stream through one layer's convolution. u [T, Di] (row r
    owns tokens cu_q_lens[r] : cu_q_lens[r+1]); w [K, Di], tap k multiplying
    the input K-1-k tokens back; b [Di]; tail [S, K-1, Di], a row's last
    K-1 inputs, oldest first; kv_lens [S] the rows' tokens AFTER this step.
    Returns (c [T, Di], the new tail): a token's predecessors are its own
    span's, then the slot's tail, which reads as zeros for a row that starts
    at length 0; a row of no token keeps its tail."""
    T, K = u.shape[0], w.shape[0]
    q_lens = cu_q_lens[1:] - cu_q_lens[:-1]
    fresh = (kv_lens - q_lens) == 0
    old = jnp.where(fresh[:, None, None], 0, tail)             # [S, K-1, Di]

    def back(rows, at, q_pos, j):
        """The input j tokens before position `q_pos` of `rows`' spans,
        which start `q_pos` tokens before stream index `at`."""
        own = u[jnp.clip(at - j, 0, T - 1)]
        kept = old[rows, jnp.clip(K - 1 + q_pos - j, 0, K - 2)]
        return jnp.where((q_pos >= j)[:, None], own, kept)

    t = jnp.arange(T)
    q_pos = t - cu_q_lens[row_of]
    c = _conv_out([back(row_of, t, q_pos, K - 1 - k) for k in range(K - 1)]
                  + [u], w, b, u.dtype)
    # the tail after the span: what a token at the span's end would look
    # back on
    rows = jnp.arange(q_lens.shape[0])
    new = jnp.stack([back(rows, cu_q_lens[1:], q_lens, K - 1 - i)
                     for i in range(K - 1)], axis=1)
    return c, jnp.where((q_lens > 0)[:, None, None], new.astype(tail.dtype),
                        tail)


def causal_conv_decode(u, w, b, tail, lengths, live):
    """One token a row. u [B, Di]; tail [B, K-1, Di]; `lengths` the rows'
    tokens BEFORE this one. Returns (c [B, Di], the new tail); a row at
    length 0 starts from zeros, a dead row keeps its tail."""
    old = jnp.where((lengths > 0)[:, None, None], tail, 0)
    K = w.shape[0]
    c = _conv_out([old[:, k] for k in range(K - 1)] + [u], w, b, u.dtype)
    new = jnp.concatenate([old[:, 1:], u[:, None].astype(tail.dtype)], axis=1)
    return c, jnp.where(live[:, None, None], new, tail)


def _step(h, dt, c, Bm, Cm, A_T, D):
    """One token of the recurrence on h [..., N, Di] (float32 inside; the
    state comes back in ITS dtype, rounded to it after EVERY token: a
    narrower slot says so with `reduce_precision`, which the compiler may
    not fuse away as it may a pair of converts inside an unrolled chunk):
    (y [..., Di] float32, the new h)."""
    a = jnp.exp(dt[..., None, :] * A_T)
    hn = (a * h.astype(jnp.float32)
          + (dt * c)[..., None, :] * Bm[..., :, None])
    if h.dtype != jnp.float32:
        info = jnp.finfo(h.dtype)
        hn = jax.lax.reduce_precision(hn, info.nexp, info.nmant)
    hn = hn.astype(h.dtype)
    y = jnp.sum(hn.astype(jnp.float32) * Cm[..., :, None], axis=-2) + D * c
    return y, hn


def selective_scan_decode(c, dt, Bm, Cm, A_T, D, h, lengths, live):
    """One token a row. c [B, Di]; dt [B, Di] float32 (after softplus);
    Bm, Cm [B, N]; A_T [N, Di] = -exp(A_log) transposed, D [Di], float32;
    h [B, N, Di]; `lengths` the rows' tokens BEFORE this one. Returns
    (y [B, Di] in c's dtype, the new state): a row at length 0 starts from
    zeros; a dead row keeps its slot and returns zeros."""
    global LAST_IMPL
    LAST_IMPL = "ssm-xla"
    f32 = jnp.float32
    h0 = jnp.where((lengths > 0)[:, None, None], h, 0)
    y, h1 = _step(h0, dt, c.astype(f32), Bm.astype(f32), Cm.astype(f32),
                  A_T, D)
    return (jnp.where(live[:, None], y, 0.0).astype(c.dtype),
            jnp.where(live[:, None, None], h1, h))


def selective_scan_prefill(c, dt, Bm, Cm, A_T, D, h, kv_lens, cu_q_lens,
                           chunk=16):
    """The packed stream's spans of two or more tokens, each continuing its
    row's slot. c, dt [T, Di]; Bm, Cm [T, N]; h [S, N, Di]; kv_lens [S] the
    rows' tokens AFTER this step. Returns (y [T, Di] in c's dtype, the new
    state). Rows of one token or none are not touched here (their y stays
    zero): `selective_scan_decode` has them."""
    global LAST_IMPL
    LAST_IMPL = "ssm-xla"
    T = c.shape[0]
    f32 = jnp.float32
    q_lens = cu_q_lens[1:] - cu_q_lens[:-1]
    before = kv_lens - q_lens
    pad = ((0, chunk), (0, 0))
    cp, dtp = jnp.pad(c, pad), jnp.pad(dt, pad)
    Bp, Cp = jnp.pad(Bm, pad), jnp.pad(Cm, pad)
    i = jnp.arange(chunk)

    def chunk_step(k, carry, start, n):
        y, hs = carry
        at = start + k * chunk
        m = jnp.minimum(chunk, n - k * chunk)             # the chunk's tokens

        def take(a):
            return jax.lax.dynamic_slice_in_dim(a, at, chunk).astype(f32)

        cc, dd, bb, ee = take(cp), take(dtp), take(Bp), take(Cp)
        ys = []
        for s in range(chunk):                            # unrolled
            ys_s, hn = _step(hs, dd[s], cc[s], bb[s], ee[s], A_T, D)
            hs = jnp.where(s < m, hn, hs)   # a token past the span: no step
            ys.append(ys_s)
        old = jax.lax.dynamic_slice_in_dim(y, at, chunk)
        y = jax.lax.dynamic_update_slice_in_dim(
            y, jnp.where((i < m)[:, None], jnp.stack(ys).astype(y.dtype),
                         old), at, axis=0)
        return y, hs

    def row_step(r, carry):
        y, st = carry
        n = q_lens[r]

        def span(args):
            y, st = args
            h0 = jnp.where(before[r] > 0, st[r], 0)
            y, hs = jax.lax.fori_loop(
                0, (n + chunk - 1) // chunk,
                lambda k, cr: chunk_step(k, cr, cu_q_lens[r], n), (y, h0))
            return y, jax.lax.dynamic_update_index_in_dim(st, hs, r, axis=0)

        return jax.lax.cond(n > 1, span, lambda args: args, (y, st))

    y0 = jnp.zeros((T + chunk, c.shape[1]), c.dtype)
    y, h = jax.lax.fori_loop(0, q_lens.shape[0], row_step, (y0, h))
    return y[:T], h


def selective_scan_ragged(c, dt, Bm, Cm, A_T, D, h, kv_lens, cu_q_lens,
                          scopes=("ssm.prefill", "ssm.decode"), chunk=16):
    """A mixed step's packed stream through one layer's state: spans of two
    or more tokens by `selective_scan_prefill`, one-token spans by
    `selective_scan_decode` (each at its span's start), under the caller's
    two `jax.named_scope`s. Returns (y [T, Di], the new state, the rows
    whose state was updated)."""
    T = c.shape[0]
    q_lens = cu_q_lens[1:] - cu_q_lens[:-1]
    with jax.named_scope(scopes[0]):
        y, h = selective_scan_prefill(c, dt, Bm, Cm, A_T, D, h, kv_lens,
                                      cu_q_lens, chunk)
    at = jnp.minimum(cu_q_lens[:-1], T - 1)
    one = q_lens == 1
    with jax.named_scope(scopes[1]):
        y1, h = selective_scan_decode(c[at], dt[at], Bm[at], Cm[at], A_T, D,
                                      h, kv_lens - q_lens, one)
    # (a row of no token aliases a neighbour's start: dropped)
    y = y.at[jnp.where(one, at, T)].set(y1, mode="drop")
    return y, h, jnp.sum(q_lens > 0).astype(jnp.int32)
