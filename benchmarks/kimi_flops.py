"""Operations and bytes the DeepSeek-V3 / Kimi-K2 block needs, from shapes
and the step log's own extents alone: the counts behind `serve.mfu_pct`,
`mla_prefill_roofline`, `mla_decode_roofline` and `moe_experts_roofline`.
Kept with the benchmark so that no later PR can change the yardstick, and
the SAME count whatever implements a scope (XLA fusions or a kernel).

`cfg` is the builder's (`kimi_model.load_config`): `n_routed_experts` is the
number of experts HELD, `router_width` the published number routed over. A
multiply-add is 2 operations; weights and cache rows are bf16 (2 bytes).
Not counted: norms, rope, softmax's exponentials, the embedding gather,
anything recomputed or padded.
"""

ITEM = 2  # bytes of a bf16 value


def widths(cfg):
    H = cfg["num_attention_heads"]
    return (H, cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"],
            cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def attention_proj_params(cfg):
    """q_a, q_b, kv_a, kv_b and o projections of one layer."""
    H, dn, dr, dv, C, W = widths(cfg)
    h, q = cfg["hidden_size"], cfg["q_lora_rank"]
    return h * q + q * H * (dn + dr) + h * W + C * H * (dn + dv) + H * dv * h


def expert_params(cfg):
    """One SwiGLU expert (routed or shared)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_share(cfg):
    """Expected assignments a token makes on the held experts."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_width"])


def matmul_flops_per_token(cfg):
    """Matmul operations one token needs through the whole block as this
    chip runs it (its held experts at their expected share), the head over
    the vocabulary slice included; attention's score and value products
    are `mla_*_cost`'s."""
    h = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    sparse = cfg["num_hidden_layers"] - dense
    per_sparse = (h * cfg["router_width"]
                  + (cfg["n_shared_experts"] + held_share(cfg))
                  * expert_params(cfg))
    params = (cfg["num_hidden_layers"] * attention_proj_params(cfg)
              + dense * 3 * h * cfg["intermediate_size"]
              + sparse * per_sparse + h * cfg["vocab_size"])
    return 2 * params


def mla_prefill_cost(cfg, spans):
    """(FLOPs, bytes) of ONE layer's expanded attention over `spans` of
    (q_len, kv_len), kv_len counting the span itself: K and V expanded once
    from the row's latent rows, QK^T over nope + rope and PV over the causal
    part of each q x kv rectangle; latent rows, q and kv_b_proj read once, o
    written."""
    H, dn, dr, dv, C, W = widths(cfg)
    pairs = sum(q * kv - q * (q - 1) // 2 for q, kv in spans)
    kv_tok = sum(kv for _, kv in spans)
    q_tok = sum(q for q, _ in spans)
    flops = 2 * H * (dn + dr + dv) * pairs + 2 * C * H * (dn + dv) * kv_tok
    nbytes = ITEM * (W * kv_tok + H * (dn + dr + dv) * q_tok
                     + (C * H * (dn + dv) if spans else 0))
    return flops, nbytes


def mla_decode_cost(cfg, kv_lens):
    """(FLOPs, bytes) of ONE layer's absorbed attention for one query token
    a row over `kv_lens` cached tokens each: W_uk folded into the query and
    W_uv out of the result (kv_b_proj read once a call), scores over the 576
    wide shared key and the value sum over the 512 wide latent; every latent
    row read once."""
    H, dn, dr, dv, C, W = widths(cfg)
    n, kv_tok = len(kv_lens), sum(kv_lens)
    flops = 2 * H * (W + C) * kv_tok + 2 * H * C * (dn + dv) * n
    nbytes = ITEM * (W * kv_tok + H * (W + C) * n
                     + (C * H * (dn + dv) if n else 0))
    return flops, nbytes


def moe_experts_cost(cfg, assigned, touched):
    """(FLOPs, bytes) of the held routed experts over `assigned` (token,
    expert) pairs that fell on `touched` expert-layers: three matmuls a
    pair; each touched expert's weights read once, a pair's input read and
    output written."""
    flops = 2 * expert_params(cfg) * assigned
    nbytes = ITEM * (expert_params(cfg) * touched
                     + 2 * cfg["hidden_size"] * assigned)
    return flops, nbytes


def request_flops(cfg, n_prompt, n_generated):
    """Operations the block needs to serve one request whole: every token's
    matmuls, and causal expanded attention over prompt + output (the least
    form at these lengths: a decode token's absorbed products cost more per
    cached token, which is the kernel's choice, not the algorithm's)."""
    H, dn, dr, dv, _, _ = widths(cfg)
    n = n_prompt + n_generated
    attn = 2 * H * (dn + dr + dv) * (n * (n + 1) // 2)
    return (n * matmul_flops_per_token(cfg)
            + cfg["num_hidden_layers"] * attn)
