"""The plain reference of the MiniCPM-SALA block, and the comparison that
decides a serving cell's `correct`.

Independent of `paddle_tpu.models`: plain `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`, no kernels, no cache, no state, no
batching. It reads the program's weights by name (the checkpoint's names,
`[in, out]`) and upcasts one layer's at a time, so it fits beside the program
on the chip.

    h0 = scale_emb E[ids];  r = scale_depth / sqrt(published depth)
    h += r Mixer(RMSNorm(h));  h += r W_down(silu(W_gate x) * W_up x)
    logits = W_head RMSNorm(h) / (hidden_size / dim_model_base)

`lightning-attn` (per head h, lambda_h = exp(-2^(-8(h+1)/H))), in its
QUADRATIC form, a block of queries at a time:
    q = RoPE(RMSNorm_head(x W_q)), k likewise, v = x W_v
    o_t = sum_{s<=t} lambda_h^(t-s) (q_t . k_s / sqrt(d)) v_s
    out = (RMSNorm_head(o) * sigmoid(x W_g)) W_o
`minicpm4` (no positional encoding), by an EXPLICIT mask from its own
selector:
    q, k = RMSNorm_head(.), v = x W_v
    c_j = mean(k[stride j : stride j + kernel))
    p = sum over the K/V head's query heads of softmax_j(q_t . c_j / sqrt(d))
        over the j with stride j + kernel - 1 <= t
    b_m = max of p over the c_j whose keys overlap block m
    kept = the `init_blocks` first blocks, the window_size / block_size
        blocks ending at t's own, then the highest b_m up to `topk` in all
        (ties to the earlier block); every visible block if t + 1 <= dense_len
    attn = causal softmax over the keys of the kept blocks
    out = (attn * sigmoid(x W_g)) W_o

Rope layout: half-split (`rotate_half`), as the published modelling code.
"""
import functools
import math

import numpy as np

from benchmarks.sala_flops import sparse as sparse_keys


class Wrong(Exception):
    """The program's output is not what the reference says it should be."""


# ---- the limits of `check_served`, each with its reason and its readings
# (PERF.md section 6, PR 33; my chip runs on the v5e: fifteen runs of the
# program over fifteen seeds; `scripts/sala_chip_checks.py` for the
# float8-weight control, which has to fail, and the planted faults) ---------
#
# (a) the program's own full forward against this reference, same row, same
# bf16 weights, at `compared_positions` of the row (every served position and
# a stride of the prompt's: the whole row's logits over 73,448 ids would be
# 2.8 GB a side). The program rounds every activation to bf16 between matmuls
# through 8 layers and keeps the residual stream in bf16; the reference keeps
# f32. Compared: the root-mean-square of the logit difference, relative to
# the root-mean-square of the reference's logits. Readings: the program
# 0.027-0.048 over its seeds; the control 0.270.
FULL_FORWARD_REL_RMS = 0.10
# (b) every served token (chunked prefill, then decode through kept pages and
# state slots) against the reference's teacher-forced logits at its position.
# A served token is FAR when its logit lies more than SERVED_GAP_REL of the
# reference's RMS logit below the reference's top logit. Random weights give
# near-flat logits, so near-ties are common and a served token is then the
# program's argmax by a gap the two sides' rounding bounds. A top-k near-tie
# at rank `topk` (two blocks' scores within rounding: program and reference
# keep 99.7-99.9% of blocks alike) makes the program keep another block than
# the reference, which moves the token by a whole block's contribution, as a
# router near-tie does in the latent cell: the worst token has a tail no
# rounding bound holds, so the limits are on SHARES. The runner compares the
# schedule's shortest row, 46 served tokens on schedule 33: one token is
# 2.2%. Readings: far tokens, the program 0 of 46 in fourteen runs and 0 of
# 277 in one (worst gap 0.02-0.47 of the RMS); the control 17.4%; the
# kept-block table shifted by one block 56.5%. Tokens that differ from the
# reference's argmax: the program 4.3-17.4%; the control 45.7%; the shifted
# table 82.6%.
SERVED_GAP_REL = 0.5
MAX_FAR_SHARE = 0.05
MAX_MISMATCH_SHARE = 0.30
#: prompt positions whose logits (a) compares, beside every served position
PROMPT_STRIDE = 64


def _jnp():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def _f32(w):
    _, jnp = _jnp()
    return jnp.asarray(w).astype(jnp.float32)


def rms_norm(x, w, eps):
    jax, jnp = _jnp()
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def rope(x, positions, theta):
    """x [S, heads, d], half-split pairs (i, i + d/2)."""
    _, jnp = _jnp()
    d = x.shape[-1]
    inv = 1.0 / float(theta) ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.asarray(positions, np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _qkv(cfg, w, pre, x, heads, kv_heads, d):
    eps = cfg["rms_norm_eps"]
    S = x.shape[0]
    q = (x @ _f32(w[pre + "q_proj.weight"])).reshape(S, heads, d)
    k = (x @ _f32(w[pre + "k_proj.weight"])).reshape(S, kv_heads, d)
    v = (x @ _f32(w[pre + "v_proj.weight"])).reshape(S, kv_heads, d)
    return (rms_norm(q, w[pre + "q_norm.weight"], eps),
            rms_norm(k, w[pre + "k_norm.weight"], eps), v)


def _gated(w, pre, o, x):
    jax, _ = _jnp()
    gate = jax.nn.sigmoid(x @ _f32(w[pre + "o_gate.weight"]))
    return (o.reshape(o.shape[0], -1) * gate) @ _f32(w[pre + "o_proj.weight"])


def lightning(cfg, w, pre, x, positions, q_block=256):
    """Decayed linear attention of one sequence x [S, h], quadratic form,
    `q_block` queries at a time."""
    _, jnp = _jnp()
    H, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    S = x.shape[0]
    q, k, v = _qkv(cfg, w, pre, x, H, cfg["lightning_nkv"], d)
    q = rope(q, positions, cfg["rope_theta"])
    k = rope(k, positions, cfg["rope_theta"])
    slopes = jnp.asarray([2.0 ** (-8.0 * (h + 1) / H) for h in range(H)],
                         jnp.float32)
    outs = []
    for lo in range(0, S, q_block):
        hi = min(lo + q_block, S)
        gap = (jnp.arange(lo, hi)[:, None] - jnp.arange(hi)[None, :])
        weight = jnp.where(gap >= 0, jnp.exp(
            -slopes[:, None, None] * jnp.maximum(gap, 0)[None]), 0.0)
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) / math.sqrt(d)
        outs.append(jnp.einsum("hqk,khd->qhd", s * weight, v[:hi]))
    o = rms_norm(jnp.concatenate(outs, axis=0), w[pre + "o_norm.weight"],
                 cfg["rms_norm_eps"])
    return _gated(w, pre, o, x)


def compressed_keys(k, sc):
    """c_j = mean(k[stride j : stride j + kernel)) for every whole kernel:
    [J, Hkv, d] (J = 0 rows for a sequence shorter than one kernel)."""
    _, jnp = _jnp()
    ks, st = sc["kernel_size"], sc["kernel_stride"]
    n = max((k.shape[0] - ks) // st + 1, 0)
    if n == 0:
        return jnp.zeros((0,) + k.shape[1:], k.dtype)
    return k[st * np.arange(n)[:, None] + np.arange(ks)[None, :]].mean(axis=1)


def select(sc, q, c, t, n_blocks):
    """The kept blocks of queries q [N, H, d] at positions t [N] against
    the compressed keys c [J, Hkv, d]: bool [N, Hkv, n_blocks]."""
    jax, jnp = _jnp()
    ks, st, bs = sc["kernel_size"], sc["kernel_stride"], sc["block_size"]
    N, H, d = q.shape
    J, Hkv, _ = c.shape
    t = jnp.asarray(t)
    m = jnp.arange(n_blocks)
    own = t // bs
    seen = m[None, :] <= own[:, None]                              # [N, nb]
    forced = seen & ((m[None, :] < sc["init_blocks"])
                     | (m[None, :] > own[:, None]
                        - sc["window_size"] // bs))
    score = jnp.zeros((N, Hkv, n_blocks), jnp.float32)
    if J:
        j = jnp.arange(J)
        vis = (st * j[None, :] + ks - 1) <= t[:, None]             # [N, J]
        lg = jnp.einsum("nhgd,jhd->nhgj", q.reshape(N, Hkv, H // Hkv, d),
                        c) / math.sqrt(d)
        lg = jnp.where(vis[:, None, None, :], lg, -jnp.inf)
        p = jnp.where(vis[:, None, None, :],
                      jnp.exp(lg - jnp.max(jnp.where(
                          vis[:, None, None, :], lg, -1e30), axis=-1,
                          keepdims=True)), 0.0)
        p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
        pg = p.sum(axis=2)                                         # [N,Hkv,J]
        overlap = ((st * j[:, None] + ks - 1 >= bs * m[None, :])
                   & (st * j[:, None] <= bs * m[None, :] + bs - 1))  # [J, nb]
        use = overlap[None, None] & vis[:, None, :, None]
        score = jnp.max(jnp.where(use, pg[..., None], 0.0), axis=2)
    score = jnp.where(forced[:, None, :], jnp.inf, score)
    score = jnp.where(seen[:, None, :], score, -1.0)
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    kept = (rank < sc["topk"]) & seen[:, None, :]
    dense = (t + 1 <= sc["dense_len"])[:, None, None]
    return jnp.where(dense, jnp.broadcast_to(seen[:, None, :], kept.shape),
                     kept)


def sparse(cfg, w, pre, x, positions, q_block=128, masks=None):
    """Block-selected attention of one sequence x [S, h] by an explicit
    mask, `q_block` queries at a time. `masks` (a list) receives each
    block's kept-block mask."""
    jax, jnp = _jnp()
    sc = sparse_keys(cfg)
    H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    bs = sc["block_size"]
    S = x.shape[0]
    n_blocks = -(-S // bs)
    q, k, v = _qkv(cfg, w, pre, x, H, Hkv, d)
    c = compressed_keys(k, sc)
    outs = []
    for lo in range(0, S, q_block):
        hi = min(lo + q_block, S)
        t = np.arange(lo, hi)
        kept = select(sc, q[lo:hi], c, t, n_blocks)                # [N,Hkv,nb]
        if masks is not None:
            masks.append(kept)
        keys = jnp.repeat(kept, bs, axis=-1)[..., :hi]             # [N,Hkv,hi]
        see = keys & (jnp.arange(hi)[None, None, :] <= t[:, None, None])
        s = jnp.einsum("qhgd,khd->qhgk",
                       q[lo:hi].reshape(hi - lo, Hkv, H // Hkv, d),
                       k[:hi]) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(see[:, :, None, :], s, -jnp.inf),
                           axis=-1)
        outs.append(jnp.einsum("qhgk,khd->qhgd", p, v[:hi]
                               ).reshape(hi - lo, H, d))
    return _gated(w, pre, jnp.concatenate(outs, axis=0), x)


def swiglu(x, gate, up, down, rows=2048):
    jax, jnp = _jnp()
    gate, up, down = _f32(gate), _f32(up), _f32(down)
    return jnp.concatenate([
        (jax.nn.silu(x[lo:lo + rows] @ gate) * (x[lo:lo + rows] @ up)) @ down
        for lo in range(0, x.shape[0], rows)])


def residual_scale(cfg):
    depth = (cfg.get("published") or {}).get("num_hidden_layers",
                                             cfg["num_hidden_layers"])
    return cfg["scale_depth"] / math.sqrt(depth)


def _layer(cfg, kind, positions, x, w):
    """One decoder layer on x [S, h]; `w` holds the layer's own weights."""
    eps, r = cfg["rms_norm_eps"], residual_scale(cfg)
    mixer = lightning if kind == "lightning-attn" else sparse
    x = x + r * mixer(cfg, w, "self_attn.",
                      rms_norm(x, w["input_layernorm.weight"], eps),
                      positions)
    h = rms_norm(x, w["post_attention_layernorm.weight"], eps)
    return x + r * swiglu(h, w["mlp.gate_proj.weight"],
                          w["mlp.up_proj.weight"], w["mlp.down_proj.weight"])


def forward_rows(cfg, w, rows, at=None):
    """f32 logits of each of `rows` (token-id sequences, each on its own)
    under the weights `w` (name -> array in the program's dtype), one
    layer's weights upcast at a time: [len(row), vocab], or only the
    positions `at[i]` of row i. Every row runs at its own length (the
    selector's blocks and compressed keys depend on it)."""
    jax, jnp = _jnp()
    out = []
    with jax.default_matmul_precision("highest"):
        head = (_f32(w["model.embed_tokens.weight"]).T
                if cfg.get("tie_word_embeddings")
                else _f32(w["lm_head.weight"]))
        for n, row in enumerate(rows):
            ids = np.asarray(row, np.int32)
            layer = {kind: jax.jit(functools.partial(
                _layer, cfg, kind, np.arange(len(ids))))
                for kind in set(cfg["mixer_types"])}
            x = _f32(w["model.embed_tokens.weight"][ids]) * cfg["scale_emb"]
            for i, kind in enumerate(cfg["mixer_types"]):
                pre = f"model.layers.{i}."
                x = layer[kind](x, {k[len(pre):]: v for k, v in w.items()
                                    if k.startswith(pre)})
            if at is not None:
                x = x[np.asarray(at[n])]
            x = rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"])
            out.append(np.asarray(
                x @ head / (cfg["hidden_size"] / cfg["dim_model_base"])))
    return out


def forward(cfg, w, ids):
    """`forward_rows` of one sequence."""
    return forward_rows(cfg, w, [ids])[0]


def _rms(a):
    return float(np.sqrt(np.mean(np.square(a, dtype=np.float64))))


def compared_positions(n_prompt, n_total):
    """The positions whose logits are compared: every one that predicts a
    served token, and every PROMPT_STRIDE-th of the prompt before them."""
    served = np.arange(n_prompt - 1, n_total - 1)
    return np.concatenate([np.arange(0, n_prompt - 1, PROMPT_STRIDE),
                           served]).astype(np.int64), served


def logit_pairs(model, outs, prompts, cfg=None, weights=None):
    """[(the program's own full-forward logits, this reference's, the
    positions compared, which of them predict served tokens)] of each row.
    The reference reads the program's own weights unless a control hands it
    others (`weights`)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework.core import Tensor

    cfg = cfg or model.benchmark_cfg
    state = model.raw_state_dict()
    at = [compared_positions(len(p), len(o))[0]
          for p, o in zip(prompts, outs)]
    refs = forward_rows(cfg, state if weights is None else weights, outs, at)
    inner, prefix = model.serving_trunk()

    @jax.jit
    def own_forward(state, ids, at):
        h = inner.functional_call(
            {k[len(prefix):]: Tensor(v, stop_gradient=True)
             for k, v in state.items() if k.startswith(prefix)},
            Tensor(ids), training=False)
        return model.serving_head(h._data[0, at], state).astype(jnp.float32)

    pairs = []
    for out, ref, a in zip(outs, refs, at):
        own = own_forward(state, jnp.asarray(out[None], jnp.int32),
                          jnp.asarray(a))
        pairs.append((np.asarray(own), ref, a))
    return pairs


def selection_agreement(model, row, cfg=None, stride=16):
    """Share of blocks the program's selector and this reference's keep
    alike, on the FIRST sparse layer of `row` (its inputs differ by rounding
    alone): the reference's own q and compressed keys in float32 through
    `select`, and rounded to the program's dtype through the program's
    `ops.sparse_paged_attention.block_mask`; every `stride`-th query past
    `dense_len`. |kept by both| / |kept by either|, or None when no query of
    the row selects."""
    jax, jnp = _jnp()
    from paddle_tpu.ops import sparse_paged_attention as spa

    cfg = cfg or model.benchmark_cfg
    sc = sparse_keys(cfg)
    w = model.raw_state_dict()
    first = cfg["mixer_types"].index("minicpm4")
    ids = np.asarray(row, np.int32)
    t = np.arange(sc["dense_len"], len(ids), stride)
    if not len(t):
        return None
    with jax.default_matmul_precision("highest"):
        x = _f32(w["model.embed_tokens.weight"][ids]) * cfg["scale_emb"]
        for i, kind in enumerate(cfg["mixer_types"][:first]):
            pre = f"model.layers.{i}."
            x = _layer(cfg, kind, np.arange(len(ids)), x,
                       {k[len(pre):]: v for k, v in w.items()
                        if k.startswith(pre)})
        pre = f"model.layers.{first}."
        lw = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
        q, k, _ = _qkv(cfg, lw, "self_attn.",
                       rms_norm(x, lw["input_layernorm.weight"],
                                cfg["rms_norm_eps"]),
                       cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"])
        c = compressed_keys(k, sc)
        n_blocks = -(-len(ids) // sc["block_size"])
        want = np.asarray(select(sc, q[t], c, t, n_blocks))
    dtype = w[pre + "self_attn.q_proj.weight"].dtype
    sp = spa.SparseConfig(**sc)
    per = sp.per_page
    cpad = jnp.zeros((n_blocks * per,) + c.shape[1:], dtype).at[
        :c.shape[0]].set(c.astype(dtype))
    N, H, d = q[t].shape
    logits = jnp.einsum(
        "nhgd,jhd->nhgj",
        q[t].astype(dtype).reshape(N, c.shape[1], H // c.shape[1], d), cpad,
        preferred_element_type=jnp.float32) / math.sqrt(d)
    got = np.asarray(spa.block_mask(logits, jnp.asarray(t), sp, n_blocks))
    return float((got & want).sum() / max((got | want).sum(), 1))


def check_served(model, prompts, outs, cfg=None, weights=None):
    """The comparison that decides `correct` for a serving cell. For each
    distinct (prompt, served row):

    (a) the program's own full forward of the row (no cache) against this
        reference's at the compared positions: relative RMS logit error
        <= FULL_FORWARD_REL_RMS;
    (b) every served token against the reference's teacher-forced logits at
        its position: at most MAX_FAR_SHARE of them lie more than
        SERVED_GAP_REL of the reference's RMS logit below its top logit, and
        at most MAX_MISMATCH_SHARE differ from its argmax at all.

    Returns each number compared beside its limit, and the share of blocks
    program and reference select alike; raises Wrong."""
    seen, pairs = set(), []
    for p, o in zip(prompts, outs):   # a runner may hand one row twice
        key = np.asarray(o).tobytes()
        if key not in seen:
            seen.add(key)
            pairs.append((np.asarray(p), np.asarray(o)))
    prompts, outs = [p for p, _ in pairs], [o for _, o in pairs]
    rows = []
    checked = mismatched = far = 0
    for prompt, out, (own, ref, at) in zip(
            prompts, outs, logit_pairs(model, outs, prompts, cfg, weights)):
        ref_rms = _rms(ref)
        n_served = len(out) - len(prompt)
        tail = ref[len(at) - n_served:]       # the rows that predict served
        tokens = out[len(prompt):]
        gaps = (tail.max(axis=-1)
                - tail[np.arange(n_served), tokens]) / ref_rms
        checked += n_served
        mismatched += int((gaps > 0).sum())
        far += int((gaps > SERVED_GAP_REL).sum())
        rows.append({"tokens": int(len(out)), "prompt": int(len(prompt)),
                     "positions_compared": int(len(at)),
                     "full_forward_rel_rms": _rms(own - ref) / ref_rms,
                     "limit_full_forward_rel_rms": FULL_FORWARD_REL_RMS,
                     "worst_served_gap_rel": float(gaps.max(initial=0.0)),
                     "blocks_selected_alike": selection_agreement(
                         model, out, cfg),
                     "ref_logit_rms": ref_rms})
    result = {"rows": rows, "checked": checked,
              "exact": checked - mismatched,
              "far_share": far / max(checked, 1),
              "limit_far_share": MAX_FAR_SHARE,
              "far_is_gap_rel_over": SERVED_GAP_REL,
              "mismatch_share": mismatched / max(checked, 1),
              "limit_mismatch_share": MAX_MISMATCH_SHARE}
    for i, r in enumerate(rows):
        if not r["full_forward_rel_rms"] <= FULL_FORWARD_REL_RMS:
            raise Wrong(f"row {i}: the program's full forward differs from "
                        f"the reference by {r['full_forward_rel_rms']:.4f} of "
                        f"the logits' RMS (limit {FULL_FORWARD_REL_RMS}): "
                        f"{result}")
    if result["far_share"] > MAX_FAR_SHARE:
        raise Wrong(f"{far}/{checked} served tokens lie more than "
                    f"{SERVED_GAP_REL} of the logits' RMS below the "
                    f"reference's top logit (limit {MAX_FAR_SHARE:.0%}): "
                    f"{result}")
    if result["mismatch_share"] > MAX_MISMATCH_SHARE:
        raise Wrong(f"{mismatched}/{checked} served tokens differ from the "
                    f"reference argmax (limit {MAX_MISMATCH_SHARE:.0%}): "
                    f"{result}")
    return result
