"""Operations and bytes the Phi-4-mini-flash block needs, from shapes and the
step log's own extents alone: the counts behind `phi4flash.serve_mfu_pct` and
the five `ssm_*` / `swa_*` / `cross_*` roofline shares. Kept with the
benchmark so that no later PR can change the yardstick, and the SAME count
whatever implements a scope (XLA loops or a kernel).

`cfg` is the builder's (`phi4flash_model.load_config`). A multiply-add is 2
operations; weights, K, V and activations are bf16 (2 bytes), the SSM state
and dt float32. Not counted: norms, gates' silus outside the scan, softmax's
exponentials, the embedding gather, anything recomputed, padded or masked —
never a key outside a window, never the zeros the padded-query form
multiplies, never a materialised [T, d_inner, d_state] tensor.

LEAST work of the mixers (one layer):
- selective scan, a token: per (channel, state) element exp(dt A) is 2
  operations (a multiply, an exponential), a h 1, (dt c) B added 2, h C summed
  2: SSM_OPS = 7 d_inner d_state; the convolution 2 d_conv d_inner, D c
  2 d_inner. Bytes a token: u read, c written and read back (the projections
  that make dt, B, C stand between the convolution and the scan, outside the
  scope), dt read, y written: (2 + 2 + 2 + 4 + 2) d_inner + 4 d_state; the
  slot (state float32, convolution tail bf16) read and written once a span
  (prefill) or a row and forward (decode);
- differential attention, a (query, visible key): 20 pairs x (2 x 2 x 64 for
  the two scores + 2 x 2 x 128 for the two value products) = 15,360 by the
  four-product count. Bytes: K and V of the visible keys once a span or row
  (5,120 B a key), q read and o written (2 x 5,120 B a token).
"""

import numpy as np

ITEM = 2  # bytes of a bf16 value

KINDS = ("mamba", "swa", "memory", "full", "gmu", "cross")


def layer_kinds(cfg):
    L, m = cfg["num_hidden_layers"], cfg["mb_per_layer"]
    half = L // 2
    return [("mamba" if i % m == 0 else "swa") if i < half
            else "memory" if i == half else "full" if i == half + 1
            else ("gmu" if i % m == 0 else "cross") for i in range(L)]


def layers(cfg):
    """{kind: layers of it}, plus `ssm` (Mamba layers, the memory layer
    among them), `trunk` and `tail` (layers below and from L/2 + 2) and
    `readers` (layers that read the one K/V pool at decode)."""
    kinds = layer_kinds(cfg)
    n = {k: kinds.count(k) for k in KINDS}
    n["ssm"] = n["mamba"] + n["memory"]
    n["tail"] = n["gmu"] + n["cross"]
    n["trunk"] = len(kinds) - n["tail"]
    n["readers"] = n["full"] + n["cross"]
    return n


def _dims(cfg):
    h = cfg["hidden_size"]
    d = h // cfg["num_attention_heads"]
    di = cfg.get("mamba_expand", 2) * h
    n = cfg.get("mamba_d_state", 16)
    r = cfg.get("mamba_dt_rank", "auto")
    r = -(-h // 16) if r == "auto" else r
    return h, d, di, n, r, cfg.get("mamba_d_conv", 4)


def mixer_params(cfg):
    """{kind: matmul weights of one layer's mixer}."""
    h, d, di, n, r, _ = _dims(cfg)
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    mamba = h * 2 * di + di * (r + 2 * n) + r * di + di * h
    attn = h * (H + 2 * Hkv) * d + H * d * h
    return {"mamba": mamba, "memory": mamba, "swa": attn, "full": attn,
            "gmu": 2 * h * di, "cross": 2 * h * H * d}


def matmul_flops_per_token(cfg):
    """(trunk, tail + head): matmul operations one token needs through
    layers 0 .. L/2+1, and through the cross-decoder and the head (the
    embedding is a gather); the mixers' own products are `request_flops`'s."""
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    per, n = mixer_params(cfg), layers(cfg)
    ffn = 3 * h * m
    trunk = sum(n[k] * (per[k] + ffn)
                for k in ("mamba", "swa", "memory", "full"))
    tail = sum(n[k] * (per[k] + ffn) for k in ("gmu", "cross"))
    return 2 * trunk, 2 * (tail + h * cfg["vocab_size"])


def ssm_ops_per_token(cfg):
    _, _, di, n, _, k = _dims(cfg)
    return 7 * di * n + 2 * k * di + 2 * di


def _slot_bytes(cfg):
    _, _, di, n, _, k = _dims(cfg)
    return 4 * n * di + ITEM * (k - 1) * di


def ssm_cost(cfg, tokens, slots):
    """(operations, bytes) of ONE Mamba layer's convolution and scan over
    `tokens` tokens that read and write `slots` state slots (a span: one; a
    decode forward: one a row)."""
    _, _, di, n, _, _ = _dims(cfg)
    return (ssm_ops_per_token(cfg) * tokens,
            (12 * di + 2 * ITEM * n) * tokens + 2 * _slot_bytes(cfg) * slots)


def attn_ops_per_pair(cfg):
    """Operations a (query, visible key) by the four-product count."""
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    return (cfg["num_attention_heads"] // 2) * (2 * 2 * d + 2 * 2 * 2 * d)


def kv_bytes_per_key(cfg):
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_key_value_heads"] * d * ITEM


def _qo_bytes(cfg):
    return 2 * cfg["hidden_size"] * ITEM


def swa_span_cost(cfg, spans):
    """(operations, bytes) of ONE window layer over prefill `spans` of
    (q_len, kv_len), kv_len counting the span: a query at position t sees
    min(t + 1, window) keys; the span reads the keys from its first query's
    window to its end once."""
    w = cfg["sliding_window"]
    pairs = sum(int(np.minimum(np.arange(kv - q, kv) + 1, w).sum())
                for q, kv in spans)
    keys = sum(min(kv, q + w - 1) for q, kv in spans)
    toks = sum(q for q, _ in spans)
    return (attn_ops_per_pair(cfg) * pairs,
            kv_bytes_per_key(cfg) * keys + _qo_bytes(cfg) * toks)


def attn_decode_cost(cfg, extents, window=None):
    """(operations, bytes) of ONE attention layer for one query a row over
    rows of `extents` keys (the query's own among them), the last `window`
    of them where there is one."""
    keys = sum(min(kv, window) if window else kv for kv in extents)
    return (attn_ops_per_pair(cfg) * keys,
            kv_bytes_per_key(cfg) * keys + _qo_bytes(cfg) * len(extents))


def request_flops(cfg, n_prompt, n_generated):
    """Operations the block needs to serve one request whole. Every token
    but the last generated runs the trunk (its matmuls, the scans, the window
    layers' visible pairs, the K/V layer's causal pairs); the tokens that
    yield a logit (the last prompt token and every generated one but the
    last) run the cross-decoder and the head too: each cross layer's query
    over the whole row."""
    n = n_prompt + n_generated - 1                # tokens processed
    trunk, tail = matmul_flops_per_token(cfg)
    lay = layers(cfg)
    t = np.arange(n)
    swa_pairs = int(np.minimum(t + 1, cfg["sliding_window"]).sum())
    full_pairs = int((t + 1).sum())
    cross_pairs = int((t[n_prompt - 1:] + 1).sum())
    pair = attn_ops_per_pair(cfg)
    return (n * trunk + n_generated * tail
            + lay["ssm"] * ssm_ops_per_token(cfg) * n
            + pair * (lay["swa"] * swa_pairs + lay["full"] * full_pairs
                      + lay["cross"] * cross_pairs))
