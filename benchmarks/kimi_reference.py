"""The plain reference of the DeepSeek-V3 block that Kimi-K2 publishes, and
the comparison that decides a serving cell's `correct`.

Independent of `paddle_tpu.models`: plain `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`, no kernels, no cache, no batching,
the EXPANDED form of latent attention only, a Python loop over a token's
chosen experts masked to the share it is given. It reads the program's
weights by name (the checkpoint's names, `[in, out]`) and upcasts them one
use at a time, so it fits beside the program on the chip.

    x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))
    c_q = RMSNorm(x W_qa); [q_nope | q_rope] = c_q W_qb          (per head)
    [c_kv | k_r] = x W_kva; c_kv = RMSNorm(c_kv); k_rope = RoPE(k_r)
    [k_nope | v] = c_kv W_kvb (per head); k = [k_nope | k_rope]
    o = softmax(scale q k^T, causal) v; out = concat(o) W_o
    s = sigmoid(x W_g^T); top-k by s + b; w = s / (sum + 1e-20) * factor
    FFN = sum_{k: e_k held} w_k E_{e_k}(x) + S(x)      (layer 0: dense SwiGLU)

Rope layout: interleaved pairs (2i, 2i+1), as the checkpoints store the rope
dims (the published code permutes to half-split before rotating; the scores
are equal). YaRN as published: inverse frequencies blended by the linear ramp
between the correction dims of beta_fast and beta_slow, cos/sin multiplier
mscale(factor, mscale) / mscale(factor, mscale_all_dim), softmax scale
(nope + rope)^-1/2 * mscale(factor, mscale_all_dim)^2.

What the experts outside `[first, first + n_held)` would add is left out,
here as in the program: the configuration is one chip's share, and the
partial result is what goes on to the next layer.
"""
import math

import numpy as np


class Wrong(Exception):
    """The program's output is not what the reference says it should be."""


# ---- the limits of `check_served`, each with its reason and its readings
# (PERF.md section 6, PR 29: the largest the program gave on the chip over its
# seeds; what the lower-precision control gave, which has to fail; what a
# planted wrong-page fault gave) ----------------------------------------------
#
# (a) the program's own full forward against this reference, same rows, same
# bf16 weights. The program rounds every activation to bf16 between matmuls
# (8 mantissa bits) through 7 layers and keeps the residual stream in bf16;
# the reference keeps f32. Compared: the root-mean-square of the logit
# difference over a row, relative to the root-mean-square of the reference's
# logits. Readings: the program 0.028-0.035 over its seeds; with its weights
# rounded to float8_e4m3 (the control) 0.262 / 0.271.
FULL_FORWARD_REL_RMS = 0.08
# (b) every served token (chunked prefill, then absorbed decode through the
# latent pool) against the reference's teacher-forced logits at its position.
# A served token is FAR when its logit lies more than SERVED_GAP_REL of the
# reference's RMS logit below the reference's top logit. Random weights give
# near-flat logits (the top few of 20,480 lie within a few rounding errors of
# each other), so near-ties occur and a served token is then the program's
# argmax, not the reference's, by a gap the two logits' own errors bound:
# measured, no position whose experts the program chose as the reference did
# lies more than 0.073 below (3,474 positions of two rows, teacher-forced).
# Where the 8th and 9th router scores lie within rounding (margins of 2e-4
# to 1e-2 there) the program holds another expert than the reference: 5% of
# positions have such a flip of a HELD expert in some layer, it moves the
# logits by that expert's whole contribution (gaps up to 0.28 teacher-forced)
# and with the reference routed as the program routed the gaps close to
# 0.072. One served token of 676 read 0.61 (the same rows served again gave
# it again): it sits at such a position, and the served path resolved it
# otherwise than the program's own full forward too. So the worst token has
# a heavy tail that no rounding bound holds, and the limit is on the SHARE
# of far tokens. Readings
# (scripts/kimi_chip_checks.py): the program at most 1 far token of 676
# (0.15%); the control 10.4% (8.3% on two shorter rows); every decode row
# reading its neighbour's pages (the planted fault) 99.7%.
SERVED_GAP_REL = 0.5
MAX_FAR_SHARE = 0.02
# at most this share of served tokens may differ from the reference argmax
# (the program: 3-6%; the control 51%; the planted wrong pages 99.7%)
MAX_MISMATCH_SHARE = 0.25


def yarn_mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_tables(cfg, positions):
    """(cos, sin [S, rope/2], softmax scale) at integer `positions`."""
    d, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    inv = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    scale = (cfg["qk_nope_head_dim"] + d) ** -0.5
    mult = 1.0
    rs = cfg.get("rope_scaling")
    if rs:
        factor = rs["factor"]
        orig = rs["original_max_position_embeddings"]

        def correction_dim(rot):
            return d * math.log(orig / (rot * 2 * math.pi)) / (
                2 * math.log(base))

        low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(rs["beta_slow"])), d - 1)
        ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3),
                       0.0, 1.0)
        inv = inv / factor * ramp + inv * (1.0 - ramp)
        mult = (yarn_mscale(factor, rs["mscale"])
                / yarn_mscale(factor, rs["mscale_all_dim"]))
        if rs["mscale_all_dim"]:
            scale *= yarn_mscale(factor, rs["mscale_all_dim"]) ** 2
    ang = np.asarray(positions, np.float64)[:, None] * inv[None, :]
    return ((np.cos(ang) * mult).astype(np.float32),
            (np.sin(ang) * mult).astype(np.float32), scale)


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


def rope(x, cos, sin):
    """x [S, ..., rope], interleaved pairs; cos/sin [S, rope/2]."""
    _, jnp = _jnp()
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def attention(cfg, w, pre, x, positions, q_block=256, head_block=16):
    """Latent attention of one sequence x [S, h], expanded form, causal;
    `head_block` heads and `q_block` queries at a time (the same sums, in
    blocks so that a 16k-token row fits beside the program)."""
    jax, jnp = _jnp()
    H, dn, dr = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                 cfg["qk_rope_head_dim"])
    C, dv, eps = cfg["kv_lora_rank"], cfg["v_head_dim"], cfg["rms_norm_eps"]
    S = x.shape[0]
    cos, sin, scale = rope_tables(cfg, positions)
    cq = rms_norm(x @ _f32(w[pre + "q_a_proj.weight"]),
                  w[pre + "q_a_layernorm.weight"], eps)
    kv = x @ _f32(w[pre + "kv_a_proj_with_mqa.weight"])
    c = rms_norm(kv[:, :C], w[pre + "kv_a_layernorm.weight"], eps)
    k_rope = rope(kv[:, C:], cos, sin)                          # [S, dr]
    w_qb = w[pre + "q_b_proj.weight"].reshape(-1, H, dn + dr)
    w_kvb = w[pre + "kv_b_proj.weight"].reshape(C, H, dn + dv)
    heads = []
    for h0 in range(0, H, head_block):
        hs = slice(h0, min(h0 + head_block, H))
        q = jnp.einsum("sr,rhd->shd", cq, _f32(w_qb[:, hs]))
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], cos, sin)],
                            axis=-1)
        kvb = jnp.einsum("sc,chd->shd", c, _f32(w_kvb[:, hs]))
        k = jnp.concatenate(
            [kvb[..., :dn],
             jnp.broadcast_to(k_rope[:, None], kvb.shape[:2] + (dr,))],
            axis=-1)
        v = kvb[..., dn:]
        outs = []
        for lo in range(0, S, q_block):
            hi = min(lo + q_block, S)
            s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) * scale
            see = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
            p = jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", p, v[:hi]))
        heads.append(jnp.concatenate(outs, axis=0))
    o = jnp.concatenate(heads, axis=1).reshape(S, H * dv)
    return o @ _f32(w[pre + "o_proj.weight"])


def swiglu(x, gate, up, down, rows=4096):
    """(silu(x gate) * (x up)) down, `rows` tokens at a time (per-token
    work, blocked so that a 16k-token row's intermediate fits)."""
    jax, jnp = _jnp()
    gate, up, down = _f32(gate), _f32(up), _f32(down)
    return jnp.concatenate([
        (jax.nn.silu(x[lo:lo + rows] @ gate) * (x[lo:lo + rows] @ up)) @ down
        for lo in range(0, x.shape[0], rows)])


def router(cfg, w, pre, x):
    """(chosen experts [S, k], their weights [S, k]) over ALL experts."""
    jax, jnp = _jnp()
    s = jax.nn.sigmoid(x @ _f32(w[pre + "gate.weight"]).T)
    _, idx = jax.lax.top_k(
        s + _f32(w[pre + "gate.e_score_correction_bias"]),
        cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        chosen = chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-20)
    return idx, chosen * cfg["routed_scaling_factor"]


def expert_layer(cfg, w, pre, x, first, n_held, shared=True):
    """sum over a token's chosen experts that lie in [first, first + n_held)
    of w_k E_k(x), plus the shared expert when `shared`. The held experts'
    weights are stacked from `first` on under `experts.*`. Every held
    expert is evaluated on every token and masked to the tokens that chose
    it (a scan over the held experts, so that one expert's f32 weights
    and products are live at a time): no sorting, no capacity."""
    jax, jnp = _jnp()
    idx, weights = router(cfg, w, pre, x)

    def add_expert(y, held):       # one held expert after the other
        e, gate, up, down = held
        mine = jnp.where(idx == e, weights, 0.0).sum(axis=-1)
        return y + mine[:, None] * swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (first + jnp.arange(n_held), w[pre + "experts.gate_proj"],
         w[pre + "experts.up_proj"], w[pre + "experts.down_proj"]))
    if shared:
        y = y + swiglu(x, w[pre + "shared_experts.gate_proj.weight"],
                       w[pre + "shared_experts.up_proj.weight"],
                       w[pre + "shared_experts.down_proj.weight"])
    return y


def _layer(cfg, dense, first, positions, x, w):
    """One decoder layer on x [S, h]; `w` holds the layer's own weights."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, w, "self_attn.",
                      rms_norm(x, w["input_layernorm.weight"], eps),
                      positions)
    h = rms_norm(x, w["post_attention_layernorm.weight"], eps)
    if dense:
        return x + swiglu(h, w["mlp.gate_proj.weight"],
                          w["mlp.up_proj.weight"], w["mlp.down_proj.weight"])
    return x + expert_layer(cfg, w, "mlp.", h, first,
                            w["mlp.experts.gate_proj"].shape[0])


def forward_rows(cfg, w, rows):
    """f32 logits [len(row), vocab] of each of `rows` (token-id sequences,
    each on its own: no batching) under the weights `w` (name -> array in
    the program's dtype), one layer's weights upcast at a time. Rows are
    right-padded to one width (causal: padding cannot reach earlier
    positions), so a layer compiles once. `cfg` holds the published keys
    plus `first_expert` (default 0); the held experts are those stacked in
    `w`."""
    import functools

    jax, jnp = _jnp()
    width = -(-max(len(r) for r in rows) // 128) * 128
    first = cfg.get("first_expert", 0)
    layer = {dense: jax.jit(functools.partial(_layer, cfg, dense, first,
                                              np.arange(width)))
             for dense in (True, False)}
    out = []
    with jax.default_matmul_precision("highest"):
        head = (_f32(w["model.embed_tokens.weight"]).T
                if cfg.get("tie_word_embeddings") else _f32(w["lm_head.weight"]))
        for row in rows:
            ids = np.zeros(width, np.int32)
            ids[:len(row)] = row
            x = _f32(w["model.embed_tokens.weight"][ids])
            for i in range(cfg["num_hidden_layers"]):
                pre = f"model.layers.{i}."
                x = layer[i < cfg["first_k_dense_replace"]](
                    x, {k[len(pre):]: v for k, v in w.items()
                        if k.startswith(pre)})
            x = rms_norm(x[:len(row)], w["model.norm.weight"],
                         cfg["rms_norm_eps"])
            out.append(np.asarray(x @ head))
    return out


def forward(cfg, w, ids):
    """`forward_rows` of one sequence."""
    return forward_rows(cfg, w, [ids])[0]


def _rms(a):
    return float(np.sqrt(np.mean(np.square(a, dtype=np.float64))))


def logit_pairs(model, outs, cfg=None, weights=None):
    """[(the program's own full-forward logits, this reference's)] of each
    of the rows `outs`, both f32 [len(row), vocab]. The reference reads the
    program's own weights unless a control hands it others (`weights`: the
    configuration's, while the program runs rounded ones)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework.core import Tensor

    cfg = cfg or model.benchmark_cfg
    state = model.raw_state_dict()
    refs = forward_rows(cfg, state if weights is None else weights, outs)

    @jax.jit
    def own_forward(state, ids):
        logits = model.functional_call(
            {k: Tensor(v, stop_gradient=True) for k, v in state.items()},
            Tensor(ids), training=False)
        return logits._data[0].astype(jnp.float32)

    width = -(-max(len(o) for o in outs) // 128) * 128
    pairs = []
    for out, ref in zip(outs, refs):
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(out)] = out
        pairs.append((np.asarray(own_forward(state, jnp.asarray(ids)))
                      [:len(out)], ref))
    return pairs


def gaps_below_top(ref, tokens, at):
    """How far below the reference's top logit each of `tokens` lies at the
    positions `at` whose logits predict it, in units of the reference's RMS
    logit (0: the reference's own argmax)."""
    rows = ref[np.asarray(at)]
    return (rows.max(axis=-1)
            - rows[np.arange(len(rows)), np.asarray(tokens)]) / _rms(ref)


def check_served(model, prompts, outs, cfg=None, weights=None):
    """The comparison that decides `correct` for a serving cell. For each
    (prompt, served row):

    (a) the program's own full forward of the row (no cache) against this
        reference's: relative RMS logit error <= FULL_FORWARD_REL_RMS;
    (b) every served token against the reference's teacher-forced logits
        at its position: at most MAX_FAR_SHARE of them lie more than
        SERVED_GAP_REL of the reference's RMS logit below its top logit,
        and at most MAX_MISMATCH_SHARE differ from its argmax at all.

    Returns each number compared beside its limit; raises Wrong."""
    outs = [np.asarray(o) for o in outs]
    rows = []
    checked = mismatched = far = 0
    for prompt, out, (own, ref) in zip(
            prompts, outs, logit_pairs(model, outs, cfg, weights)):
        ref_rms = _rms(ref)
        served = np.arange(len(prompt), len(out))
        gaps = gaps_below_top(ref, out[served], served - 1)
        checked += len(gaps)
        mismatched += int((gaps > 0).sum())
        far += int((gaps > SERVED_GAP_REL).sum())
        rows.append({"tokens": int(len(out)), "prompt": int(len(prompt)),
                     "full_forward_rel_rms": _rms(own - ref) / ref_rms,
                     "limit_full_forward_rel_rms": FULL_FORWARD_REL_RMS,
                     "worst_served_gap_rel": float(gaps.max(initial=0.0)),
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
