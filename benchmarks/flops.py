"""Operations and bytes the algorithm needs, from shapes alone. Kept with
the benchmark so that no later PR can change the yardstick.

Counted: every matmul of the decoder layers and the output head, forward
and backward (6 per weight per token), and causal attention (the half of
the score matrix a causal kernel has to compute). Not counted: the
embedding gather, elementwise work, and anything recomputed (a flash
backward recomputes the scores; that is the kernel's cost, not the
algorithm's).
"""


def matmul_params(cfg):
    """Weights that multiply an activation once per token."""
    h, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    d = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    layer = h * q + 2 * h * kv + q * h + 3 * h * ffn
    return cfg["num_hidden_layers"] * layer + h * cfg["vocab_size"]


def total_params(cfg):
    h = cfg["hidden_size"]
    norms = (2 * cfg["num_hidden_layers"] + 1) * h
    embed = 0 if cfg.get("tie_word_embeddings") else h * cfg["vocab_size"]
    return matmul_params(cfg) + embed + norms


def attention_flops(cfg, batch, seq, causal=True, backward=True):
    """One layer's attention core over [batch, seq]: QK^T and PV forward
    (2 matmuls), dV, dP, dQ, dK backward (4), each 2*S*S*D per head, halved
    when causal."""
    per_matmul = (2 * batch * cfg["num_attention_heads"] * seq * seq
                  * cfg["head_dim"])
    n = 6 if backward else 2
    return n * per_matmul / (2 if causal else 1)


def attention_bytes(cfg, batch, seq, backward=True, itemsize=2):
    """One layer's least HBM traffic: forward reads q,k,v and writes o and
    the f32 row statistics; backward reads q,k,v,o,do and the statistics
    and writes dq,dk,dv."""
    d = cfg["head_dim"]
    q = batch * cfg["num_attention_heads"] * seq * d * itemsize
    kv = batch * cfg["num_key_value_heads"] * seq * d * itemsize
    stats = batch * cfg["num_attention_heads"] * seq * 4
    fwd = 2 * q + 2 * kv + stats
    bwd = 4 * q + 4 * kv + stats
    return fwd + (bwd if backward else 0)


def train_flops_per_token(cfg, seq):
    """Forward + backward operations per trained token at sequence length
    `seq` (causal)."""
    attn = (cfg["num_hidden_layers"] * attention_flops(cfg, 1, seq)) / seq
    return 6 * matmul_params(cfg) + attn


def roofline_seconds(flops, nbytes, peak):
    """(least seconds, which bound) on a chip with these peaks."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
