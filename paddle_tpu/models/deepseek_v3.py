"""The DeepSeek-V3 decoder block, which Kimi-K2 publishes unchanged
(`model_type: kimi_k2`): multi-head latent attention (MLA) with YaRN rope,
`first_k_dense_replace` leading dense SwiGLU layers, then expert layers with
a sigmoid `noaux_tc` router, routed experts and shared experts.

Built for serving: every parameter is created in the configuration's dtype
(a 5 B-parameter model built in float32 and cast does not fit a 16 GB chip),
the forward runs on raw arrays and keeps no tape (inference-only), and the
serving engine's cache protocol is the latent pool's (ops/latent_pool.py):

- no cache: plain causal attention on expanded K and V (the full forward the
  benchmark's reference is compared with);
- `LatentPagedLayerCache` (one token a row): write the row `[c_kv | k_rope]`,
  then ABSORBED attention (ops/mla_decode_attention.py);
- `LatentRaggedLayerCache` (a packed mixed stream): write the stream's rows
  a page at a time, EXPANDED attention for the spans of two or more tokens
  (ops/mla_prefill_attention.py) and the absorbed path for one-token rows.

An expert layer holds `n_held_experts` of `n_routed_experts` from
`first_expert` on (incubate/distributed/models/moe/dropless.py): one chip's
share of an expert-parallel deployment, the whole bank by default.

Parameter names follow the published checkpoints (`model.layers.N.self_attn.
q_a_proj.weight`, `kv_a_proj_with_mqa`, `kv_b_proj`, `mlp.gate.weight`,
`mlp.gate.e_score_correction_bias`, `mlp.shared_experts.*`); weights are
stored `[in, out]` as everywhere in this framework, the held experts stacked
`[n_held, ...]`. Rope layout: interleaved pairs `(2i, 2i+1)`, as the
checkpoints store `q_rope` / `k_rope` (the published code permutes to the
half-split layout first; the scores are the same).
"""
import math

import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..incubate.distributed.models.moe.dropless import DroplessMoE
from ..nn import initializer as I
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..ops.latent_pool import (
    LatentCacheSpec, LatentPagedLayerCache, LatentRaggedLayerCache,
    write_ragged_latent, write_token_latent,
)
from ..ops.mla_decode_attention import mla_decode_attention
from ..ops.mla_prefill_attention import mla_prefill_attention


class DeepseekV3Config:
    def __init__(self, vocab_size=512, hidden_size=128, intermediate_size=256,
                 moe_intermediate_size=64, num_hidden_layers=3,
                 num_attention_heads=2, q_lora_rank=48, kv_lora_rank=32,
                 qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                 n_routed_experts=16, num_experts_per_tok=4,
                 n_shared_experts=1, first_k_dense_replace=1,
                 routed_scaling_factor=2.5, scoring_func="sigmoid",
                 norm_topk_prob=True, n_group=1, topk_group=1,
                 rms_norm_eps=1e-5, rope_theta=50000.0, rope_scaling=None,
                 max_position_embeddings=4096, tie_word_embeddings=False,
                 first_expert=0, n_held_experts=None, dtype="float32",
                 initializer_range=0.02):
        if n_group != 1 or topk_group != 1:
            raise ValueError("group-limited routing (n_group > 1) is not "
                             "implemented: the gate picks over all experts")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.q_lora_rank, self.kv_lora_rank = q_lora_rank, kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_shared_experts = n_shared_experts
        self.first_k_dense_replace = first_k_dense_replace
        self.routed_scaling_factor = routed_scaling_factor
        self.scoring_func, self.norm_topk_prob = scoring_func, norm_topk_prob
        self.rms_norm_eps, self.rope_theta = rms_norm_eps, rope_theta
        self.rope_scaling = rope_scaling
        self.max_position_embeddings = max_position_embeddings
        self.tie_word_embeddings = tie_word_embeddings
        self.first_expert = first_expert
        self.n_held_experts = (n_routed_experts if n_held_experts is None
                               else n_held_experts)
        self.dtype = dtype
        self.initializer_range = initializer_range

    @property
    def latent_width(self):
        """Values a token caches a layer: `[c_kv | k_rope]`."""
        return self.kv_lora_rank + self.qk_rope_head_dim


def deepseek_v3_tiny(**kw):
    """Two heads' worth of every width, 16 experts top 4, YaRN factor 4 over
    an original context of 32: the CPU tests' size."""
    kw.setdefault("rope_scaling", {
        "type": "yarn", "factor": 4, "original_max_position_embeddings": 32,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1})
    return DeepseekV3Config(**kw)


def _yarn_mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_parameters(cfg):
    """(inverse frequencies [rope/2], the cos/sin multiplier, the softmax
    scale) of the configuration's rope: plain, or YaRN (inverse frequencies
    blended between `1 / theta^(2i/d)` and that over `factor` by the linear
    ramp between the two correction dims)."""
    d, base = cfg.qk_rope_head_dim, cfg.rope_theta
    inv = 1.0 / base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    rs = cfg.rope_scaling
    if not rs:
        return inv, 1.0, scale
    if rs.get("type", rs.get("rope_type")) != "yarn":
        raise ValueError(f"unknown rope_scaling {rs!r}")
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (d * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(rs.get("beta_slow", 1))), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    inv = inv / factor * ramp + inv * (1.0 - ramp)
    all_dim = rs.get("mscale_all_dim", 0)
    mult = _yarn_mscale(factor, rs.get("mscale", 1)) / _yarn_mscale(
        factor, all_dim)
    if all_dim:
        scale *= _yarn_mscale(factor, all_dim) ** 2
    return inv, mult, scale


def apply_rope(x, positions, inv_freq, mult):
    """Rotate the interleaved pairs of x [..., S, heads, rope] (or
    [..., S, rope]) by `positions` [..., S]; f32 inside."""
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(ang) * mult, jnp.sin(ang) * mult
    if x.ndim == ang.ndim + 1:
        cos, sin = cos[..., None, :], sin[..., None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _rms(x, weight, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * weight


class _Weight(Layer):
    """One `weight` (a bias-free projection `[in, out]`, or a norm's scale),
    so that the state dict reads `<name>.weight` as the checkpoints do."""

    def __init__(self, shape, dtype, init):
        super().__init__()
        self.weight = self.create_parameter(list(shape), dtype=dtype,
                                            default_initializer=init)


class DeepseekV3Attention(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        h, H = cfg.hidden_size, cfg.num_attention_heads
        dq = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        init = I.Normal(0.0, cfg.initializer_range)
        one = I.Constant(1.0)

        def proj(i, o):
            return _Weight((i, o), cfg.dtype, init)

        self.q_a_proj = proj(h, cfg.q_lora_rank)
        self.q_a_layernorm = _Weight((cfg.q_lora_rank,), cfg.dtype, one)
        self.q_b_proj = proj(cfg.q_lora_rank, H * dq)
        self.kv_a_proj_with_mqa = proj(h, cfg.latent_width)
        self.kv_a_layernorm = _Weight((cfg.kv_lora_rank,), cfg.dtype, one)
        self.kv_b_proj = proj(cfg.kv_lora_rank,
                              H * (cfg.qk_nope_head_dim + cfg.v_head_dim))
        self.o_proj = proj(H * cfg.v_head_dim, h)

    def _up(self):
        """kv_b_proj's halves a head: w_uk [H, nope, rank] (folded into the
        query on the absorbed path) and w_uv [H, rank, v]."""
        cfg = self.cfg
        w = self.kv_b_proj.weight._data.reshape(
            cfg.kv_lora_rank, cfg.num_attention_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim)
        return (jnp.transpose(w[..., :cfg.qk_nope_head_dim], (1, 2, 0)),
                jnp.transpose(w[..., cfg.qk_nope_head_dim:], (1, 0, 2)))

    def _absorbed(self, q_nope, q_rope, pages, lengths, table, scale):
        """One query token a row, [B, H, *] -> [B, H, v]."""
        w_uk, w_uv = self._up()
        q_lat = jnp.einsum("bhd,hdc->bhc", q_nope, w_uk,
                           preferred_element_type=jnp.float32)
        o_lat = mla_decode_attention(q_lat.astype(q_nope.dtype), q_rope,
                                     pages, lengths, table, scale)
        return jnp.einsum("bhc,hcd->bhd", o_lat.astype(q_nope.dtype), w_uv,
                          preferred_element_type=jnp.float32
                          ).astype(q_nope.dtype)

    def forward(self, x, position_ids=None, past_key_value=None):
        cfg = self.cfg
        x = x._data
        B, S, _ = x.shape
        H, dn, C = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                    cfg.kv_lora_rank)
        pos = (jnp.broadcast_to(jnp.arange(S), (B, S)) if position_ids is None
               else position_ids._data)
        inv, mult, scale = rope_parameters(cfg)
        eps = cfg.rms_norm_eps
        q = _rms(x @ self.q_a_proj.weight._data,
                 self.q_a_layernorm.weight._data, eps)
        q = (q @ self.q_b_proj.weight._data).reshape(B, S, H, -1)
        q_nope, q_rope = q[..., :dn], apply_rope(q[..., dn:], pos, inv, mult)
        kv = x @ self.kv_a_proj_with_mqa.weight._data
        latent = jnp.concatenate(
            [_rms(kv[..., :C], self.kv_a_layernorm.weight._data, eps),
             apply_rope(kv[..., C:], pos, inv, mult)], axis=-1)  # [B, S, W]
        pc = past_key_value
        if isinstance(pc, LatentPagedLayerCache):
            if S != 1:
                raise ValueError("paged cache is decode-only: expected S == 1")
            pages = write_token_latent(pc.pages, pc.page_indices, pc.lengths,
                                       latent[:, 0])
            # a dead row's token went to the scratch page: nothing to attend
            lens = jnp.where(pc.live, pc.lengths + 1, 0)
            with jax.named_scope("mla.decode"):
                o = self._absorbed(q_nope[:, 0], q_rope[:, 0], pages, lens,
                                   pc.page_indices, scale)
            o = o[:, None]
            present = LatentPagedLayerCache(pages, pc.page_indices,
                                            pc.lengths, pc.live)
        elif isinstance(pc, LatentRaggedLayerCache):
            if B != 1:
                raise ValueError("ragged cache packs every row into one "
                                 "stream: expected B == 1")
            pages = write_ragged_latent(pc.pages, pc.page_indices, pc.row_of,
                                        pc.token_pos, pc.valid, latent[0])
            w_uk, w_uv = self._up()
            with jax.named_scope("mla.prefill"):
                o = mla_prefill_attention(
                    q_nope[0], q_rope[0], pages, w_uk, w_uv, pc.kv_lens,
                    pc.page_indices, pc.cu_q_lens, scale)
            # one-token rows (decode rows; a prompt's last lone token) take
            # the absorbed path, each at its span's start
            q_lens = pc.cu_q_lens[1:] - pc.cu_q_lens[:-1]
            at = jnp.minimum(pc.cu_q_lens[:-1], S - 1)
            with jax.named_scope("mla.decode"):
                o1 = self._absorbed(
                    q_nope[0, at], q_rope[0, at], pages,
                    jnp.where(q_lens == 1, pc.kv_lens, 0), pc.page_indices,
                    scale)
            # (a row of no token aliases a neighbour's start: dropped)
            o = o.at[jnp.where(q_lens == 1, at, S)].set(o1, mode="drop")[None]
            present = LatentRaggedLayerCache(
                pages, pc.page_indices, pc.kv_lens, pc.cu_q_lens, pc.row_of,
                pc.token_pos, pc.valid)
        elif pc is not None:
            raise ValueError(
                f"latent attention caches latent pages (ops/latent_pool.py), "
                f"not {type(pc).__name__}")
        else:
            o, present = self._full(q_nope, q_rope, latent, scale), None
        out = o.reshape(B, S, -1) @ self.o_proj.weight._data
        return Tensor(out, stop_gradient=True), present

    def _full(self, q_nope, q_rope, latent, scale):
        """Causal attention over the call's own tokens on expanded K and V,
        a block of queries at a time ([H, block, S] scores)."""
        B, S, H, dn = q_nope.shape
        C = self.cfg.kv_lora_rank
        w_uk, w_uv = self._up()
        c, k_rope = latent[..., :C], latent[..., C:]
        k_nope = jnp.einsum("bkc,hdc->bhkd", c, w_uk)
        v = jnp.einsum("bkc,hcd->bhkd", c, w_uv)
        qb = min(256, S)
        pad = -S % qb
        qn = jnp.pad(q_nope, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qr = jnp.pad(q_rope, ((0, 0), (0, pad), (0, 0), (0, 0)))

        def block(i):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * qb, qb, axis=1)
            s = (jnp.einsum("bqhd,bhkd->bhqk", sl(qn), k_nope,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bqhr,bkr->bhqk", sl(qr), k_rope,
                              preferred_element_type=jnp.float32)) * scale
            see = (jnp.arange(S)[None, :]
                   <= (i * qb + jnp.arange(qb))[:, None])
            p = jax.nn.softmax(jnp.where(see, s, -1e30), axis=-1)
            return jnp.einsum("bhqk,bhkd->bqhd", p.astype(v.dtype), v)

        o = jax.lax.map(block, jnp.arange((S + pad) // qb))  # [n, B, qb, H, v]
        return jnp.moveaxis(o, 0, 1).reshape(B, S + pad, H, -1)[:, :S]


class DeepseekV3MLP(Layer):
    def __init__(self, cfg):
        super().__init__()
        init = I.Normal(0.0, cfg.initializer_range)
        h, m = cfg.hidden_size, cfg.intermediate_size
        self.gate_proj = _Weight((h, m), cfg.dtype, init)
        self.up_proj = _Weight((h, m), cfg.dtype, init)
        self.down_proj = _Weight((m, h), cfg.dtype, init)

    def forward(self, x):
        x = x._data
        y = (jax.nn.silu(x @ self.gate_proj.weight._data)
             * (x @ self.up_proj.weight._data)) @ self.down_proj.weight._data
        return Tensor(y, stop_gradient=True)


def _token_mask(pc):
    """Which tokens of a cached call a request holds ([B, S] bool, or None
    for all): the packed stream's `valid`, a decode step's `live` rows. The
    others (pad tokens, dead rows) are routed to no expert."""
    if isinstance(pc, LatentRaggedLayerCache):
        return pc.valid[None]
    if isinstance(pc, LatentPagedLayerCache):
        return pc.live[:, None]
    return None


class DeepseekV3DecoderLayer(Layer):
    def __init__(self, cfg, index):
        super().__init__()
        self.cfg = cfg
        one = I.Constant(1.0)
        self.self_attn = DeepseekV3Attention(cfg)
        if index < cfg.first_k_dense_replace:
            self.mlp = DeepseekV3MLP(cfg)
        else:
            self.mlp = DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                n_shared_experts=cfg.n_shared_experts,
                first_expert=cfg.first_expert, n_held=cfg.n_held_experts,
                scoring=cfg.scoring_func, norm_topk_prob=cfg.norm_topk_prob,
                scaling=cfg.routed_scaling_factor, dtype=cfg.dtype,
                std=cfg.initializer_range)
        self.input_layernorm = _Weight((cfg.hidden_size,), cfg.dtype, one)
        self.post_attention_layernorm = _Weight((cfg.hidden_size,),
                                                cfg.dtype, one)

    def forward(self, h, position_ids=None, past_key_value=None):
        eps = self.cfg.rms_norm_eps
        a, present = self.self_attn(
            Tensor(_rms(h._data, self.input_layernorm.weight._data, eps)),
            position_ids, past_key_value)
        h = h._data + a._data
        m = Tensor(_rms(h, self.post_attention_layernorm.weight._data, eps))
        m = (self.mlp(m, _token_mask(past_key_value))
             if isinstance(self.mlp, DroplessMoE) else self.mlp(m))
        return Tensor(h + m._data, stop_gradient=True), present


class DeepseekV3Model(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        self.embed_tokens = _Weight(
            (cfg.vocab_size, cfg.hidden_size), cfg.dtype,
            I.Normal(0.0, cfg.initializer_range))
        self.layers = LayerList([DeepseekV3DecoderLayer(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = _Weight((cfg.hidden_size,), cfg.dtype, I.Constant(1.0))

    def forward(self, input_ids, attention_mask=None, position_ids=None,
                past_key_values=None, cache_position=None, use_cache=False):
        if attention_mask is not None or cache_position is not None:
            raise ValueError("the latent-attention decoder takes position_ids "
                             "and latent caches only (no padding mask, no "
                             "fixed-shape cache)")
        h = Tensor(self.embed_tokens.weight._data[input_ids._data],
                   stop_gradient=True)
        presents = []
        for i, layer in enumerate(self.layers):
            h, present = layer(
                h, position_ids,
                None if past_key_values is None else past_key_values[i])
            presents.append(present)
        out = Tensor(_rms(h._data, self.norm.weight._data,
                          self.config.rms_norm_eps), stop_gradient=True)
        return (out, presents) if past_key_values is not None else out

    def moe_counters(self):
        """The expert layers' counters of the LAST forward, summed over
        layers (int32 [3], see dropless.held_experts), or None for a model
        with no expert layer. Valid inside the forward's own trace."""
        per = [layer.mlp.last_counters for layer in self.layers
               if getattr(layer.mlp, "last_counters", None) is not None]
        return sum(per[1:], per[0]) if per else None


class DeepseekV3ForCausalLM(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        self.model = DeepseekV3Model(cfg)
        self.lm_head = (None if cfg.tie_word_embeddings else _Weight(
            (cfg.hidden_size, cfg.vocab_size), cfg.dtype,
            I.Normal(0.0, cfg.initializer_range)))

    def forward(self, input_ids, attention_mask=None, position_ids=None,
                past_key_values=None, cache_position=None, use_cache=False):
        out = self.model(input_ids, attention_mask, position_ids,
                         past_key_values, cache_position, use_cache)
        h, presents = out if past_key_values is not None else (out, None)
        logits = Tensor(self._head(h._data, self._head_weight()._data),
                        stop_gradient=True)
        return (logits, presents) if past_key_values is not None else logits

    def _head_weight(self):
        return (self.model.embed_tokens if self.lm_head is None
                else self.lm_head).weight

    def _head(self, h, w):
        return h @ (jnp.swapaxes(w, -1, -2) if self.lm_head is None else w)

    # ---- the serving engine's model protocol (inference/continuous.py) ----
    def serving_trunk(self):
        return self.model, "model."

    def serving_head(self, h, state):
        return self._head(h, state["model.embed_tokens.weight"
                                   if self.lm_head is None
                                   else "lm_head.weight"])

    def serving_cache_spec(self):
        return LatentCacheSpec(self.config.num_hidden_layers,
                               self.config.latent_width)

    #: the `jax.named_scope`s this model opens inside the step programs
    serving_scopes = ("mla.prefill", "mla.decode", "moe.route", "moe.experts",
                      "moe.shared")
    #: what `serving_counters()` counts, each summed over the expert layers
    #: (and by the engine over a dispatch's forwards)
    serving_counter_names = ("moe_hit", "moe_assigned", "moe_max_load")

    def serving_counters(self):
        return self.model.moe_counters()

    def num_parameters(self):
        return int(sum(math.prod(p.shape) for p in self.parameters()))
