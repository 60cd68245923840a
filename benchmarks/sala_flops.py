"""Operations and bytes the MiniCPM-SALA block needs, from shapes and the
step log's own extents alone: the counts behind `sala.serve_mfu_pct` and the
four `sparse_*` / `lightning_*` roofline shares. Kept with the benchmark so
that no later PR can change the yardstick, and the SAME count whatever
implements a scope (XLA loops or a kernel).

`cfg` is the builder's (`sala_model.load_config`). A multiply-add is 2
operations; weights, K, V and compressed keys are bf16 (2 bytes), a state
slot float32. Not counted: norms, rope, gates' sigmoids, softmax's
exponentials, the top-k, the embedding gather, anything recomputed, padded
or masked.

LEAST work of the two mixers (one layer):
- sparse attention: 4 * heads * head_dim operations a (query, kept key)
  pair, plus the selector's 2 * heads * head_dim a (query, visible compressed
  key) pair; bytes: the kept K and V once a row, K/V head and forward, plus
  the visible compressed keys once, plus q and o of the token;
- lightning attention: 4 * heads * d * d operations a token (k^T v into the
  state and q times the state); bytes: q, k, v, o of the token plus the state
  read and written once a span (prefill) or a row and step (decode).
"""

import numpy as np

ITEM = 2  # bytes of a bf16 value


def sparse(cfg):
    sc = dict(kernel_size=32, kernel_stride=16, block_size=64, topk=64,
              init_blocks=1, window_size=2048, dense_len=8192)
    sc.update(cfg.get("sparse_config") or {})
    return sc


def layers(cfg):
    """(sparse layers, lightning layers) held."""
    kinds = cfg["mixer_types"]
    return kinds.count("minicpm4"), kinds.count("lightning-attn")


def kept_keys(cfg, t):
    """Keys the query at position t (an int or an array of them) attends a
    K/V head: all t + 1 up to `dense_len`; past it `topk` blocks (the forced
    ones among them), its own holding t % block + 1 keys — the selection
    rule alone fixes the count."""
    sc = sparse(cfg)
    t = np.asarray(t)
    bs = sc["block_size"]
    blocks = np.minimum(sc["topk"], t // bs + 1)
    return np.where(t + 1 <= sc["dense_len"], t + 1,
                    (blocks - 1) * bs + t % bs + 1)


def compressed_visible(cfg, t):
    """Compressed keys complete at position t (an int or an array)."""
    sc = sparse(cfg)
    t = np.asarray(t)
    return np.maximum(0, (t - sc["kernel_size"] + 1) // sc["kernel_stride"]
                      + 1)


def matmul_flops_per_token(cfg):
    """Matmul operations one token needs through the held layers and the
    head (the embedding is a gather); the mixers' own products are
    `request_flops`'s."""
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    hd, kv = cfg["head_dim"], cfg["num_key_value_heads"]
    n_sparse, n_light = layers(cfg)
    ffn = 3 * h * m
    sparse_proj = 3 * h * h + 2 * h * kv * hd          # q, o, gate; k, v
    light_proj = 5 * h * h                              # q, k, v, o, gate
    params = (n_sparse * (sparse_proj + ffn) + n_light * (light_proj + ffn)
              + h * cfg["vocab_size"])
    return 2 * params


def sparse_cost(cfg, kept, queries):
    """(FLOPs, bytes) of ONE sparse layer for `queries` = [(t, n)]: n
    queries at position t (one a row and forward), `kept` = the (query, K/V
    head, kept key) triples among them (the program's counter, or
    `kept_keys` summed). Every query reads its own kept K and V."""
    H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    comp = int(sum(n * compressed_visible(cfg, t) for t, n in queries))
    n_q = sum(n for _, n in queries)
    flops = 4 * (H // Hkv) * d * kept + 2 * H * d * comp
    nbytes = ITEM * (2 * d * kept + Hkv * d * comp + 2 * H * d * n_q)
    return flops, nbytes


def sparse_span_cost(cfg, kept, spans):
    """(FLOPs, bytes) of ONE sparse layer for prefill `spans` of (q_len,
    kv_len), kv_len counting the span: `kept` as above. A span's queries
    share what they read: at least the keys from the first query's window to
    the span's end and block 0, once a K/V head, and the row's compressed
    keys once."""
    H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    sc = sparse(cfg)
    comp = int(sum(compressed_visible(cfg, np.arange(kv - q, kv)).sum()
                   for q, kv in spans))
    read = sum(min(kv, q + sc["window_size"] + sc["block_size"])
               for q, kv in spans)
    n_q = sum(q for q, _ in spans)
    flops = 4 * (H // Hkv) * d * kept + 2 * H * d * comp
    nbytes = ITEM * (2 * Hkv * d * read
                     + Hkv * d * int(sum(compressed_visible(cfg, kv - 1)
                                         for _, kv in spans))
                     + 2 * H * d * n_q)
    return flops, nbytes


def lightning_cost(cfg, tokens, states):
    """(FLOPs, bytes) of ONE lightning layer over `tokens` tokens that read
    and write `states` state slots (a span: one; a decode step: one a
    row)."""
    H, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    flops = 4 * H * d * d * tokens
    nbytes = ITEM * 4 * H * d * tokens + 2 * 4 * H * d * d * states
    return flops, nbytes


def request_flops(cfg, n_prompt, n_generated):
    """Operations the block needs to serve one request whole: every token's
    matmuls, each sparse layer's kept pairs and selector, each lightning
    layer's state products."""
    H, d = cfg["num_attention_heads"], cfg["head_dim"]
    n = n_prompt + n_generated
    n_sparse, n_light = layers(cfg)
    t = np.arange(n)
    kept = int(kept_keys(cfg, t).sum())
    comp = int(compressed_visible(cfg, t).sum())
    attn = 4 * H * d * kept + 2 * H * d * comp
    light = 4 * cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2 * n
    return (n * matmul_flops_per_token(cfg) + n_sparse * attn
            + n_light * light)
