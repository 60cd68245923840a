"""The plain reference of the Phi-4-mini-flash block (SambaY,
arXiv:2507.06607), and the comparison that decides a serving cell's `correct`.

Independent of `paddle_tpu`: plain `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`, no kernels, no cache, no state
slots, no packing. It reads the program's weights by name (`[in, out]`) and
upcasts one layer's at a time, attends a block of queries at a time and
makes logits a block of the vocabulary at a time, so that a row of several
thousand tokens fits beside the served model on the chip.

Layer i of L, x the residual stream, LN = LayerNorm with weight and bias:

    x += Mixer_i(LN1(x));   x += W2(silu(g) * u),  [g | u] = W1 LN2(x)
    logits = E LNf(x)       (E the embedding, tied; no scale, no positions)

Mixer by i (m = mb_per_layer = 2): Mamba for even i <= L/2, attention with a
window of `sliding_window` keys for odd i < L/2, full attention at L/2 + 1;
from L/2 + 2 on, GMU for even i and cross-attention for odd i.

Mamba (a literal `lax.scan` over tokens, the state `[d_inner, d_state]`):
    [u | z] = Win x;  c_t = silu(b + sum_k w[k] u_{t-3+k})
    [delta | B | C] = Wx c_t;  dt = softplus(Wdt delta + b_dt);  A = -exp(A_log)
    h_t = exp(dt (x) A) h_{t-1} + (dt c_t) (x) B;  y_t = h_t C + D c_t
    out = Wout(y_t silu(z));  the MEMORY of layer L/2 is y_t, before the gate.
GMU: Wout(silu(Win x) * memory), the memory of the same token.
Differential attention (the literal four-product form; pair p = heads 2p,
2p+1 of q, of k and of v; query pair p reads K/V pair p // (H / Hkv)):
    a1 = softmax(q1 k1^T / sqrt(d)) [v1 | v2];  a2 = softmax(q2 k2^T / sqrt(d)) [v1 | v2]
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0_i,  lam0_i = 0.8 - 0.6 exp(-0.3 i)
    o = RMSNorm_2d(a1 - lam a2; weight) (1 - lam0_i)
Cross-attention projects q alone and reads layer L/2 + 1's k and v.
"""
import functools
import math

import numpy as np

from benchmarks.phi4flash_flops import layer_kinds


class Wrong(Exception):
    """The program's output is not what the reference says it should be."""


# ---- the limits of `check_served`, each with its reason and its readings
# (PERF.md section 6, PR 35; my chip runs on the v5e;
# `scripts/phi4flash_chip_checks.py` for the bfloat16-state control, which
# has to fail, and the planted faults) -------------------------------------
#
# (a) the program's own full forward against this reference, same row, same
# bf16 weights, at `compared_positions` of the row (every served position and
# a stride of the prompt's). The program rounds every activation to bf16
# between matmuls through 32 layers and keeps the residual stream in bf16;
# the reference keeps f32. Compared: the root-mean-square of the logit
# difference, relative to the root-mean-square of the reference's logits.
# Readings: the program 0.0787-0.0811 over eight seeds (17 rows of 789-4,017
# tokens); the float8-weight control 0.817; the bfloat16-state control 0.086
# (it cannot fail here: see (c)).
FULL_FORWARD_REL_RMS = 0.12
# (b) every served token (chunked prefill through slots, rings and the one
# K/V pool, then decode) against the reference's teacher-forced logits at
# its position. A served token is FAR when its logit lies more than
# SERVED_GAP_REL of the reference's RMS logit below the reference's top
# logit. Random weights give near-flat logits, so near-ties are common and a
# served token is then the program's argmax by a gap the two sides' rounding
# bounds; the limits are on SHARES of a row's served tokens. Readings: far
# tokens, the program 0 of 1,182-2,915 on every row (worst gap 0.20-0.37 of
# the RMS), the float8-weight control 89.0%; tokens that differ from the
# reference's argmax, the program 14.1-19.7%, the control 96.6%.
SERVED_GAP_REL = 0.5
MAX_FAR_SHARE = 0.05
MAX_MISMATCH_SHARE = 0.30
# (c) the SSM state of the FIRST Mamba layer (layer 0: its input is the
# embedding, so both sides scan the same tokens through one bf16 projection)
# after the program's own scan of the row, against this reference's float32
# recurrence: relative RMS of the difference. The logits cannot see the
# state's precision: through 32 bf16 layers the program's own rounding reads
# 0.080 in (a), a state rounded to bfloat16 after every token adds 0.0004 to
# it. Here it shows: the slow channels (A = -1, dt ~ 1e-3) sum thousands of
# roundings. Readings: the program 0.0012-0.0040 over seven seeds (14 rows);
# the bfloat16-state control 0.0368; the float8-weight control 0.068. (With
# the rounding left to a pair of converts the chip's compiler kept float32
# inside each unrolled chunk of 16 tokens and the control read 0.0043:
# ops/selective_scan.py says `reduce_precision` for a narrower slot.)
FIRST_STATE_REL_RMS = 0.010
#: prompt positions whose logits (a) compares, beside every served position
PROMPT_STRIDE = 64
#: vocabulary rows a logits block holds (float32 beside the served model)
VOCAB_BLOCK = 16384
#: the program's own forward runs on rows padded to a multiple of this many
#: tokens (causal: the pad changes nothing before it), so that rows of
#: different lengths share a compile
OWN_FORWARD_PAD = 1024


def _jnp():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def _f32(w):
    _, jnp = _jnp()
    return jnp.asarray(w).astype(jnp.float32)


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def layer_norm(x, w, b, eps):
    jax, jnp = _jnp()
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(w) + _f32(b)


def mamba(cfg, w, x, state_dtype=None):
    """One sequence x [S, h] through a Mamba-1 mixer, token by token.
    Returns (out [S, h], y before the gate [S, d_inner]). `state_dtype`:
    the control's, the state rounded to it after every token."""
    jax, jnp = _jnp()
    n = cfg.get("mamba_d_state", 16)
    r = cfg.get("mamba_dt_rank", "auto")
    r = math.ceil(cfg["hidden_size"] / 16) if r == "auto" else r
    u, z = jnp.split(x @ _f32(w["attn.in_proj.weight"]), 2, axis=-1)
    cw, cb = _f32(w["attn.conv1d.weight"]), _f32(w["attn.conv1d.bias"])
    K, S = cw.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1])), u])
    c = jax.nn.silu(cb + sum(cw[k] * padded[k:k + S] for k in range(K)))
    dbc = c @ _f32(w["attn.x_proj.weight"])
    dt = jax.nn.softplus(dbc[:, :r] @ _f32(w["attn.dt_proj.weight"])
                         + _f32(w["attn.dt_proj.bias"]))
    Bm, Cm = dbc[:, r:r + n], dbc[:, r + n:]
    A = -jnp.exp(_f32(w["attn.A_log"]))                     # [Di, N]

    def step(h, t):
        dt_t, c_t, b_t, c_out = t
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * c_t)[:, None] * b_t[None]
        if state_dtype is not None:
            h = h.astype(state_dtype).astype(jnp.float32)
        return h, h @ c_out

    h, y = jax.lax.scan(step, jnp.zeros(A.shape, jnp.float32),
                        (dt, c, Bm, Cm))
    y = y + _f32(w["attn.D"]) * c
    return (y * jax.nn.silu(z)) @ _f32(w["attn.out_proj.weight"]), y, h


def diff_attention(cfg, w, lam0, x, kv=None, window=None, q_block=256):
    """Differential attention of a layer whose lambda_init is `lam0` on one
    sequence x [S, h], in the four-product form, `q_block` queries at a
    time. `kv`: another layer's (k, v) to read (cross-attention); else this
    layer projects its own. Returns (out [S, h], (k, v))."""
    jax, jnp = _jnp()
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, S = cfg["hidden_size"] // H, x.shape[0]
    qkv = x @ _f32(w["attn.Wqkv.weight"])
    q = qkv[:, :H * d].reshape(S, H // 2, 2, d)
    if kv is None:
        kv = (qkv[:, H * d:(H + Hkv) * d].reshape(S, Hkv // 2, 2, d),
              qkv[:, (H + Hkv) * d:].reshape(S, Hkv // 2, 2, d))
    k, v = kv
    g = H // Hkv                                   # query pairs a K/V pair
    k1, k2 = (jnp.repeat(k[:, :, s], g, axis=1) for s in (0, 1))
    vv = jnp.repeat(v.reshape(S, Hkv // 2, 2 * d), g, axis=1)  # [v1 | v2]
    lam = (jnp.exp(jnp.sum(_f32(w["attn.lambda_q1"])
                           * _f32(w["attn.lambda_k1"])))
           - jnp.exp(jnp.sum(_f32(w["attn.lambda_q2"])
                             * _f32(w["attn.lambda_k2"]))) + lam0)
    # a block of queries at a time against every key of the row, masked
    # (`lax.map`: one compile whatever the row's length)
    n_blocks = -(-S // q_block)
    qp = jnp.pad(q, ((0, n_blocks * q_block - S), (0, 0), (0, 0), (0, 0)))
    j = jnp.arange(S)[None, :]

    def block(lo):
        qs = jax.lax.dynamic_slice_in_dim(qp, lo, q_block, axis=0)
        t = lo + jnp.arange(q_block)[:, None]
        see = j <= t
        if window is not None:
            see &= j >= t - (window - 1)

        def attend(qs, ks):
            s = jnp.einsum("qpd,kpd->pqk", qs, ks) / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("pqk,kpe->qpe", p, vv)

        a = attend(qs[:, :, 0], k1) - lam * attend(qs[:, :, 1], k2)
        o = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True)
                              + cfg["layer_norm_eps"])
        return (o * _f32(w["attn.subln.weight"]) * (1.0 - lam0)
                ).reshape(q_block, H * d)

    out = jax.lax.map(block, jnp.arange(n_blocks) * q_block)
    return (out.reshape(n_blocks * q_block, H * d)[:S]
            @ _f32(w["attn.out_proj.weight"])), kv


def mlp(x, w, rows=2048):
    jax, jnp = _jnp()
    fc1, fc2 = _f32(w["mlp.fc1.weight"]), _f32(w["mlp.fc2.weight"])
    out = []
    for lo in range(0, x.shape[0], rows):
        g, u = jnp.split(x[lo:lo + rows] @ fc1, 2, axis=-1)
        out.append((jax.nn.silu(g) * u) @ fc2)
    return jnp.concatenate(out)


def _layer(cfg, kind, state_dtype, x, w, lam0, read):
    """One decoder layer of `kind` on x [S, h]; `w` holds the layer's own
    weights, `read` what it reads of an earlier layer (a GMU the memory, a
    cross layer (k, v)). Returns (x, what it hands on: a Mamba layer its y
    before the gate, an attention layer its (k, v))."""
    jax, jnp = _jnp()
    eps = cfg["layer_norm_eps"]
    xn = layer_norm(x, w["input_layernorm.weight"],
                    w["input_layernorm.bias"], eps)
    if kind in ("mamba", "memory"):
        a, made, _ = mamba(cfg, w, xn, state_dtype)
    elif kind == "gmu":
        a, made = (jax.nn.silu(xn @ _f32(w["attn.in_proj.weight"])) * read
                   ) @ _f32(w["attn.out_proj.weight"]), None
    else:
        a, made = diff_attention(
            cfg, w, lam0, xn, kv=read,
            window=cfg["sliding_window"] if kind == "swa" else None)
    x = x + a
    xn = layer_norm(x, w["post_attention_layernorm.weight"],
                    w["post_attention_layernorm.bias"], eps)
    return x + mlp(xn, w), made


def hidden_rows(cfg, w, rows, at=None, state_dtype=None):
    """The final-normed hidden state of each of `rows` (token-id sequences,
    each on its own) under the weights `w` (name -> array in the program's
    dtype), one layer's weights upcast at a time: float32 [len(row), h], or
    only the positions `at[i]` of row i."""
    jax, jnp = _jnp()
    kinds = layer_kinds(cfg)
    # one compile a kind and row length: the layer's index is an operand
    step = {kind: jax.jit(functools.partial(_layer, cfg, kind, state_dtype))
            for kind in set(kinds)}
    out = []
    with jax.default_matmul_precision("highest"):
        for n, row in enumerate(rows):
            ids = np.asarray(row, np.int32)
            x = _f32(jnp.asarray(w["model.embed_tokens.weight"])[ids])
            memory = kv = None
            for i, kind in enumerate(kinds):
                pre = f"model.layers.{i}."
                x, made = step[kind](
                    x, {k[len(pre):]: v for k, v in w.items()
                        if k.startswith(pre)}, jnp.float32(lambda_init(i)),
                    memory if kind == "gmu" else
                    kv if kind == "cross" else None)
                memory = made if kind == "memory" else memory
                kv = made if kind == "full" else kv
            if at is not None:
                x = x[np.asarray(at[n])]
            out.append(layer_norm(x, w["model.final_layernorm.weight"],
                                  w["model.final_layernorm.bias"],
                                  cfg["layer_norm_eps"]))
    return out


def logits_of(h, embed, block=VOCAB_BLOCK):
    """float32 logits `h E^T` of h [n, hidden] on the HOST, a block of the
    vocabulary at a time (E in the program's dtype, upcast a block)."""
    jax, jnp = _jnp()
    embed = jnp.asarray(embed)

    @jax.jit
    def part(h, e):
        return h.astype(jnp.float32) @ e.astype(jnp.float32).T

    with jax.default_matmul_precision("highest"):
        return np.concatenate(
            [np.asarray(part(h, embed[lo:lo + block]))
             for lo in range(0, embed.shape[0], block)], axis=1)


def forward(cfg, w, ids, state_dtype=None):
    """float32 logits [len(ids), vocab] of one sequence."""
    (h,) = hidden_rows(cfg, w, [ids], state_dtype=state_dtype)
    return logits_of(h, w["model.embed_tokens.weight"])


def first_state(cfg, w, ids):
    """The float32 SSM state [d_inner, d_state] of layer 0 after the tokens
    `ids`: embedding, LayerNorm, the Mamba recurrence."""
    jax, jnp = _jnp()
    pre = "model.layers.0."
    lw = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}

    @jax.jit
    def run(x, lw):
        return mamba(cfg, lw, layer_norm(
            x, lw["input_layernorm.weight"], lw["input_layernorm.bias"],
            cfg["layer_norm_eps"]))[2]

    with jax.default_matmul_precision("highest"):
        return np.asarray(run(_f32(jnp.asarray(
            w["model.embed_tokens.weight"])[np.asarray(ids, np.int32)]), lw))


def own_first_state(model, ids, page=64):
    """Layer 0's state slot after the program's own trunk has run `ids` as
    one span from an empty slot, through the views its cache spec makes (the
    serving engine's protocol): float32 [d_inner, d_state] on the host."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework.core import Tensor

    state = model.raw_state_dict()
    inner, prefix = model.serving_trunk()
    spec = model.serving_cache_spec()
    n = len(ids)
    npages = -(-n // page)
    dtype = state["model.embed_tokens.weight"].dtype

    @jax.jit
    def run(state, ids):
        pools = spec.make_pools(1 + npages, page, dtype, max_seqs=1,
                                prefill_chunk=n)
        table = 1 + jnp.arange(npages, dtype=jnp.int32)[None]
        pos = jnp.arange(n, dtype=jnp.int32)
        views = [s.ragged(pool, table, jnp.full((1,), n, jnp.int32),
                          jnp.asarray([0, n], jnp.int32),
                          jnp.zeros((n,), jnp.int32), pos,
                          jnp.ones((n,), bool))
                 for s, pool in zip(spec.layers, pools)]
        _, presents = inner.functional_call(
            {k[len(prefix):]: Tensor(v, stop_gradient=True)
             for k, v in state.items() if k.startswith(prefix)},
            Tensor(ids[None]), position_ids=Tensor(pos[None]),
            past_key_values=views, use_cache=True, training=False)
        return presents[0].state[0][0].astype(jnp.float32).T

    return np.asarray(run(state, jnp.asarray(ids, jnp.int32)))


def _rms(a):
    return float(np.sqrt(np.mean(np.square(a, dtype=np.float64))))


def compared_positions(n_prompt, n_total):
    """The positions whose logits are compared: every one that predicts a
    served token, and every PROMPT_STRIDE-th of the prompt before them."""
    served = np.arange(n_prompt - 1, n_total - 1)
    return np.concatenate([np.arange(0, n_prompt - 1, PROMPT_STRIDE),
                           served]).astype(np.int64), served


def own_logits(model, out, at):
    """The program's own cacheless forward of the row `out` at positions
    `at`, float32 on the host: its trunk and cross-decoder on the row padded
    to a multiple of OWN_FORWARD_PAD, then its head a block of positions at
    a time."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework.core import Tensor

    state = model.raw_state_dict()
    inner, prefix = model.serving_trunk()
    n = -(-len(out) // OWN_FORWARD_PAD) * OWN_FORWARD_PAD
    ids = np.zeros((1, n), np.int32)
    ids[0, :len(out)] = out

    @jax.jit
    def hidden(state, ids):
        return inner.functional_call(
            {k[len(prefix):]: Tensor(v, stop_gradient=True)
             for k, v in state.items() if k.startswith(prefix)},
            Tensor(ids), training=False)._data[0]

    @jax.jit
    def head(state, h):
        return model.serving_head(h, state).astype(jnp.float32)

    h = hidden(state, jnp.asarray(ids))[jnp.asarray(at)]
    return np.concatenate([np.asarray(head(state, h[lo:lo + 512]))
                           for lo in range(0, len(at), 512)])


def check_served(model, prompts, outs, cfg=None, weights=None,
                 state_dtype=None):
    """The comparison that decides `correct` for a serving cell. For each
    distinct (prompt, served row):

    (a) the program's own full forward of the row (no cache) against this
        reference's at the compared positions: relative RMS logit error
        <= FULL_FORWARD_REL_RMS;
    (b) every served token against the reference's teacher-forced logits at
        its position: at most MAX_FAR_SHARE of a row's lie more than
        SERVED_GAP_REL of the reference's RMS logit below its top logit, and
        at most MAX_MISMATCH_SHARE differ from its argmax at all.

    (c) layer 0's SSM state after the program's own scan of the row against
        this reference's recurrence: relative RMS <= FIRST_STATE_REL_RMS.

    Returns each number compared beside its limit; raises Wrong. The
    reference reads the program's own weights unless a control hands it
    others (`weights`), in float32 state unless a control says otherwise
    (`state_dtype`)."""
    cfg = cfg or model.benchmark_cfg
    w = model.raw_state_dict() if weights is None else weights
    seen, pairs = set(), []
    for p, o in zip(prompts, outs):   # a runner may hand one row twice
        key = np.asarray(o).tobytes()
        if key not in seen:
            seen.add(key)
            pairs.append((np.asarray(p), np.asarray(o)))
    rows = []
    for prompt, out in pairs:
        at, _ = compared_positions(len(prompt), len(out))
        (h,) = hidden_rows(cfg, w, [out], [at], state_dtype)
        ref = logits_of(h, w["model.embed_tokens.weight"])
        own = own_logits(model, out, at)
        ref_rms = _rms(ref)
        h_ref = first_state(cfg, w, out)
        h_own = own_first_state(model, out)
        n_served = len(out) - len(prompt)
        tail = ref[len(at) - n_served:]       # the rows that predict served
        gaps = (tail.max(axis=-1)
                - tail[np.arange(n_served), out[len(prompt):]]) / ref_rms
        rows.append({
            "tokens": int(len(out)), "prompt": int(len(prompt)),
            "positions_compared": int(len(at)),
            "full_forward_rel_rms": _rms(own - ref) / ref_rms,
            "limit_full_forward_rel_rms": FULL_FORWARD_REL_RMS,
            "served": int(n_served),
            "far_share": float((gaps > SERVED_GAP_REL).mean()),
            "limit_far_share": MAX_FAR_SHARE,
            "far_is_gap_rel_over": SERVED_GAP_REL,
            "mismatch_share": float((gaps > 0).mean()),
            "limit_mismatch_share": MAX_MISMATCH_SHARE,
            "worst_served_gap_rel": float(gaps.max(initial=0.0)),
            "first_state_rel_rms": _rms(h_own - h_ref) / _rms(h_ref),
            "limit_first_state_rel_rms": FIRST_STATE_REL_RMS,
            "ref_logit_rms": ref_rms})
        del ref, own, tail
    result = {"rows": rows, "checked": sum(r["served"] for r in rows)}
    for i, r in enumerate(rows):
        for name in ("full_forward_rel_rms", "far_share", "mismatch_share",
                     "first_state_rel_rms"):
            if not r[name] <= r["limit_" + name]:
                raise Wrong(
                    f"row {i} ({r['prompt']} prompt + {r['served']} served "
                    f"tokens): {name} {r[name]:.4f} over its limit "
                    f"{r['limit_' + name]}: {result}")
    return result
