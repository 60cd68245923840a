"""MiniCPM-SALA (`model_type: minicpm_sala`): a MiniCPM decoder whose layers
mix tokens in one of two ways, named a layer by `mixer_types` —

- `minicpm4`: grouped-query attention with NO positional encoding, q/k norm
  and an output gate, made sparse by InfLLM-v2 block selection (each query
  keeps at most `topk` blocks of `block_size` keys, chosen by scores against
  compressed keys; ops/sparse_paged_attention.py);
- `lightning-attn`: decayed linear attention with RoPE, q/k norm, an output
  norm and an output gate, whose cache is a `head_dim x head_dim` state a
  head and no keys at all (ops/lightning_attention.py).

The block is the MiniCPM family's: `h0 = scale_emb * E[ids]`; every residual
branch is scaled by `scale_depth / sqrt(published depth)`; the logits are
divided by `hidden_size / dim_model_base`. The depth in that scale is the
PUBLISHED one (`residual_depth`), whatever slice of the layers is held here.

Built for serving, as models/deepseek_v3.py is: every parameter is created in
the configuration's dtype, the forward runs on raw arrays and keeps no tape,
and the serving engine's cache protocol is a spec a LAYER
(ops/cache_specs.py): selected K/V pages for a `minicpm4` layer, a state slot
a row for a `lightning-attn` layer. A forward with no cache runs the same
ops over a scratch cache of its own (every sequence of the batch a row of
one packed stream), so there is one implementation of each mixer.

Parameter names follow the family's checkpoints (`model.layers.N.self_attn.
{q,k,v,o}_proj.weight`, `q_norm`, `k_norm`; the output gate is `o_gate`, the
lightning layer's output norm `o_norm`); weights are stored `[in, out]`.
Rope layout: the half-split `rotate_half` of the published modelling code.
"""
import math

import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..nn import initializer as I
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..ops.cache_specs import LayerCacheSpecs
from ..ops.lightning_attention import (
    StateSlotCache, StateSlotRaggedCache, StateSlotSpec, decay_slopes,
    lightning_decode, lightning_ragged,
)
from ..ops.sparse_decode_attention import sparse_decode_attention
from ..ops.sparse_paged_attention import (
    SelectedKVSpec, SelectedPagedLayerCache, SelectedRaggedLayerCache,
    SparseConfig, sparse_ragged_attention, write_ragged_selected,
    write_token_selected,
)
from .deepseek_v3 import _rms, _Weight

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


class MinicpmSalaConfig:
    """The published `config.json` keys under their own names, the family's
    `sparse_config` (MiniCPM4), and what this framework adds (`dtype`,
    `initializer_range`, `residual_depth`: the depth the residual scale
    reads, for a slice of the published layers)."""

    def __init__(self, vocab_size=512, hidden_size=128, intermediate_size=256,
                 num_hidden_layers=4, mixer_types=None,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                 lightning_nh=4, lightning_nkv=4, lightning_head_dim=32,
                 lightning_scale="1/sqrt(d)", lightning_use_rope=True,
                 attn_use_rope=False, qk_norm=True, use_output_gate=True,
                 use_output_norm=True, attn_use_output_gate=True,
                 attention_bias=False, hidden_act="silu", rms_norm_eps=1e-6,
                 rope_theta=10000.0, scale_emb=12, scale_depth=1.4,
                 dim_model_base=256, mup_denominator=32,
                 max_position_embeddings=4096, tie_word_embeddings=False,
                 sparse_config=None, residual_depth=None, dtype="float32",
                 initializer_range=0.02, **unread):
        mixer_types = list(mixer_types or
                           ([SPARSE] + [LIGHTNING] * 3) * num_hidden_layers
                           )[:num_hidden_layers]
        if len(mixer_types) != num_hidden_layers or \
                set(mixer_types) - {SPARSE, LIGHTNING}:
            raise ValueError(f"mixer_types {mixer_types!r}: one of "
                             f"{SPARSE!r} / {LIGHTNING!r} a layer")
        if lightning_nkv != lightning_nh:
            raise ValueError("lightning layers with grouped K/V heads are "
                             "not implemented (published: nh == nkv)")
        if attn_use_rope or not lightning_use_rope or attention_bias or \
                hidden_act != "silu" or lightning_scale != "1/sqrt(d)" or \
                not (qk_norm and use_output_gate and use_output_norm
                     and attn_use_output_gate):
            raise ValueError("only the published switches are implemented: "
                             "no rope and an output gate on minicpm4 layers, "
                             "rope, output norm and gate on lightning layers, "
                             "q/k norm on both, silu, no bias")
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.mixer_types = mixer_types
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads, self.head_dim = num_key_value_heads, head_dim
        self.lightning_nh, self.lightning_nkv = lightning_nh, lightning_nkv
        self.lightning_head_dim = lightning_head_dim
        self.rms_norm_eps, self.rope_theta = rms_norm_eps, rope_theta
        self.scale_emb, self.scale_depth = scale_emb, scale_depth
        self.dim_model_base = dim_model_base
        self.mup_denominator = mup_denominator  # carried; nothing reads it
        self.max_position_embeddings = max_position_embeddings
        self.tie_word_embeddings = tie_word_embeddings
        self.sparse = SparseConfig(**(sparse_config or {}))
        self.residual_depth = residual_depth or num_hidden_layers
        self.dtype, self.initializer_range = dtype, initializer_range

    @property
    def residual_scale(self):
        return self.scale_depth / math.sqrt(self.residual_depth)


def minicpm_sala_tiny(**kw):
    """Four layers (one sparse, three lightning), a `sparse_config` shrunk
    with the widths (blocks of 16 keys, compressed keys of 8 by 4, top 4
    with a window of 2 blocks, dense up to 64 keys): the CPU tests' size."""
    kw.setdefault("sparse_config", dict(
        kernel_size=8, kernel_stride=4, block_size=16, topk=4, init_blocks=1,
        window_size=32, dense_len=64))
    return MinicpmSalaConfig(**kw)


def apply_rope(x, positions, theta):
    """Rotate the half-split pairs (i, i + D/2) of x [T, heads, D] by
    `positions` [T]; f32 inside."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


class _Mixer(Layer):
    """What the two mixers share: the projections, q/k norm, the output
    gate, and the flattening of a call into the packed stream."""

    def __init__(self, cfg, heads, kv_heads, head_dim):
        super().__init__()
        self.cfg = cfg
        self.heads, self.kv_heads, self.d = heads, kv_heads, head_dim
        init, one = I.Normal(0.0, cfg.initializer_range), I.Constant(1.0)
        h = cfg.hidden_size
        self.q_proj = _Weight((h, heads * head_dim), cfg.dtype, init)
        self.k_proj = _Weight((h, kv_heads * head_dim), cfg.dtype, init)
        self.v_proj = _Weight((h, kv_heads * head_dim), cfg.dtype, init)
        self.o_gate = _Weight((h, heads * head_dim), cfg.dtype, init)
        self.o_proj = _Weight((heads * head_dim, h), cfg.dtype, init)
        self.q_norm = _Weight((head_dim,), cfg.dtype, one)
        self.k_norm = _Weight((head_dim,), cfg.dtype, one)
        self.last_counters = None

    def _qkv(self, x):
        """x [N, hidden] -> q [N, heads, d], k and v [N, kv_heads, d], q and
        k normed a head."""
        eps, n = self.cfg.rms_norm_eps, x.shape[0]
        q = (x @ self.q_proj.weight._data).reshape(n, self.heads, self.d)
        k = (x @ self.k_proj.weight._data).reshape(n, self.kv_heads, self.d)
        v = (x @ self.v_proj.weight._data).reshape(n, self.kv_heads, self.d)
        return (_rms(q, self.q_norm.weight._data, eps),
                _rms(k, self.k_norm.weight._data, eps), v)

    def _out(self, o, x):
        """The mixer's heads [N, heads, d] through gate and o_proj."""
        gate = jax.nn.sigmoid(
            (x @ self.o_gate.weight._data).astype(jnp.float32))
        y = (o.reshape(o.shape[0], -1).astype(jnp.float32) * gate)
        return y.astype(x.dtype) @ self.o_proj.weight._data


class SparseAttention(_Mixer):
    """A `minicpm4` layer: no rope, block-selected attention."""

    def __init__(self, cfg):
        super().__init__(cfg, cfg.num_attention_heads,
                         cfg.num_key_value_heads, cfg.head_dim)

    def forward(self, x, positions, pc):
        sp = self.cfg.sparse
        B, S, _ = x.shape
        x = x.reshape(B * S, -1)
        q, k, v = self._qkv(x)
        if isinstance(pc, SelectedPagedLayerCache):
            pools = write_token_selected(pc, k, v, sp)
            # a dead row's token went to the scratch page: nothing to attend
            lens = jnp.where(pc.live, pc.lengths + 1, 0)
            o, kept, seen = sparse_decode_attention(
                q, *pools, pc.page_indices, lens, sp)
            present = SelectedPagedLayerCache(*pools, pc.page_indices,
                                              pc.lengths, pc.live)
        elif isinstance(pc, SelectedRaggedLayerCache):
            pools = write_ragged_selected(pc, k, v, sp)
            present = SelectedRaggedLayerCache(
                *pools, pc.page_indices, pc.kv_lens, pc.cu_q_lens, pc.row_of,
                pc.token_pos, pc.valid)
            o, kept, seen = sparse_ragged_attention(q, present, sp)
        else:
            raise ValueError(
                "a minicpm4 layer caches selected K/V pages "
                f"(ops/sparse_paged_attention.py), not {type(pc).__name__}")
        self.last_counters = jnp.stack([kept, seen, jnp.zeros_like(kept)])
        return self._out(o, x).reshape(B, S, -1), present


class LightningAttention(_Mixer):
    """A `lightning-attn` layer: rope, decayed linear attention over a
    state slot, an output norm a head."""

    def __init__(self, cfg):
        super().__init__(cfg, cfg.lightning_nh, cfg.lightning_nkv,
                         cfg.lightning_head_dim)
        self.o_norm = _Weight((cfg.lightning_head_dim,), cfg.dtype,
                              I.Constant(1.0))

    def forward(self, x, positions, pc):
        cfg = self.cfg
        B, S, _ = x.shape
        x = x.reshape(B * S, -1)
        q, k, v = self._qkv(x)
        pos = positions.reshape(-1)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
        slopes = decay_slopes(self.heads)
        if isinstance(pc, StateSlotCache):
            with jax.named_scope("sala.lightning.decode"):
                o, state = lightning_decode(q, k, v, pc.state, pc.lengths,
                                            pc.live, slopes)
            rows = jnp.sum(pc.live).astype(jnp.int32)
            present = StateSlotCache(state, pc.lengths, pc.live)
        elif isinstance(pc, StateSlotRaggedCache):
            o, state, rows = lightning_ragged(q, k, v, pc, slopes)
            present = StateSlotRaggedCache(
                state, pc.kv_lens, pc.cu_q_lens, pc.row_of, pc.token_pos,
                pc.valid)
        else:
            raise ValueError(
                "a lightning-attn layer caches a state slot a row "
                f"(ops/lightning_attention.py), not {type(pc).__name__}")
        zero = jnp.zeros_like(rows)
        self.last_counters = jnp.stack([zero, zero, rows])
        o = _rms(o, self.o_norm.weight._data, cfg.rms_norm_eps)
        return self._out(o, x).reshape(B, S, -1), present


class MinicpmSalaMLP(Layer):
    def __init__(self, cfg):
        super().__init__()
        init = I.Normal(0.0, cfg.initializer_range)
        h, m = cfg.hidden_size, cfg.intermediate_size
        self.gate_proj = _Weight((h, m), cfg.dtype, init)
        self.up_proj = _Weight((h, m), cfg.dtype, init)
        self.down_proj = _Weight((m, h), cfg.dtype, init)

    def forward(self, x):
        y = (jax.nn.silu(x @ self.gate_proj.weight._data)
             * (x @ self.up_proj.weight._data))
        return y @ self.down_proj.weight._data


class MinicpmSalaDecoderLayer(Layer):
    def __init__(self, cfg, index):
        super().__init__()
        self.cfg = cfg
        one = I.Constant(1.0)
        self.mixer_type = cfg.mixer_types[index]
        self.self_attn = (SparseAttention(cfg) if self.mixer_type == SPARSE
                          else LightningAttention(cfg))
        self.mlp = MinicpmSalaMLP(cfg)
        self.input_layernorm = _Weight((cfg.hidden_size,), cfg.dtype, one)
        self.post_attention_layernorm = _Weight((cfg.hidden_size,),
                                                cfg.dtype, one)

    def forward(self, h, positions, pc):
        cfg = self.cfg
        eps, scale = cfg.rms_norm_eps, cfg.residual_scale
        a, present = self.self_attn(
            _rms(h, self.input_layernorm.weight._data, eps), positions, pc)
        h = h + (a * scale).astype(h.dtype)
        m = self.mlp(_rms(h, self.post_attention_layernorm.weight._data, eps))
        return h + (m * scale).astype(h.dtype), present


class MinicpmSalaModel(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        self.embed_tokens = _Weight(
            (cfg.vocab_size, cfg.hidden_size), cfg.dtype,
            I.Normal(0.0, cfg.initializer_range))
        self.layers = LayerList([MinicpmSalaDecoderLayer(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = _Weight((cfg.hidden_size,), cfg.dtype, I.Constant(1.0))

    def cache_spec(self):
        cfg = self.config
        return LayerCacheSpecs(
            SelectedKVSpec(cfg.num_key_value_heads, cfg.head_dim, cfg.sparse)
            if kind == SPARSE else
            StateSlotSpec(cfg.lightning_nh, cfg.lightning_head_dim,
                          cfg.lightning_head_dim)
            for kind in cfg.mixer_types)

    def _scratch_caches(self, B, S, dtype):
        """A forward with no cache: every sequence a row of one packed
        stream over pools of its own, each row starting at length 0."""
        bs = self.config.sparse.block_size
        npages = -(-S // bs)
        spec = self.cache_spec()
        pools = spec.make_pools(1 + B * npages, bs, dtype, max_seqs=B)
        table = 1 + jnp.arange(B * npages, dtype=jnp.int32).reshape(B, npages)
        cu = jnp.arange(B + 1, dtype=jnp.int32) * S
        row_of = jnp.repeat(jnp.arange(B, dtype=jnp.int32), S)
        pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), B)
        return [s.ragged(pool, table, jnp.full((B,), S, jnp.int32), cu,
                         row_of, pos, jnp.ones((B * S,), bool))
                for s, pool in zip(spec.layers, pools)], pos

    def forward(self, input_ids, attention_mask=None, position_ids=None,
                past_key_values=None, cache_position=None, use_cache=False):
        if attention_mask is not None or cache_position is not None:
            raise ValueError("this decoder takes position_ids and its layers' "
                             "own caches only (no padding mask, no "
                             "fixed-shape cache)")
        cfg = self.config
        ids = input_ids._data
        B, S = ids.shape
        h = (self.embed_tokens.weight._data[ids] * cfg.scale_emb
             ).astype(self.embed_tokens.weight._data.dtype)
        caches = past_key_values
        if caches is None:
            caches, pos = self._scratch_caches(B, S, h.dtype)
            h = h.reshape(1, B * S, -1)
        else:
            pos = (jnp.broadcast_to(jnp.arange(S), (B, S))
                   if position_ids is None else position_ids._data)
        presents = []
        for layer, pc in zip(self.layers, caches):
            h, present = layer(h, pos, pc)
            presents.append(present)
        out = Tensor(_rms(h, self.norm.weight._data, cfg.rms_norm_eps
                          ).reshape(B, S, -1), stop_gradient=True)
        return (out, presents) if past_key_values is not None else out

    def counters(self):
        """(keys kept, keys visible) summed over the sparse layers and the
        rows whose state the FIRST lightning layer updated (every lightning
        layer updates the same rows), of the LAST forward: int32 [3], valid
        inside the forward's own trace."""
        per = [l.self_attn.last_counters for l in self.layers]
        kinds = self.config.mixer_types
        sparse = [c for c, kind in zip(per, kinds) if kind == SPARSE]
        light = [c for c, kind in zip(per, kinds) if kind == LIGHTNING]
        total = sum(sparse[1:], sparse[0]) if sparse else jnp.zeros(
            (3,), jnp.int32)
        return total + light[0] if light else total


class MinicpmSalaForCausalLM(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        self.model = MinicpmSalaModel(cfg)
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
        cfg = self.config
        logits = h @ (jnp.swapaxes(w, -1, -2) if self.lm_head is None else w)
        return logits / (cfg.hidden_size / cfg.dim_model_base)

    # ---- the serving engine's model protocol (inference/continuous.py) ----
    def serving_trunk(self):
        return self.model, "model."

    def serving_head(self, h, state):
        return self._head(h, state["model.embed_tokens.weight"
                                   if self.lm_head is None
                                   else "lm_head.weight"])

    def serving_cache_spec(self):
        return self.model.cache_spec()

    #: the `jax.named_scope`s this model opens inside the step programs
    serving_scopes = ("sala.select", "sala.sparse.prefill",
                      "sala.sparse.decode", "sala.lightning.prefill",
                      "sala.lightning.decode")
    #: what `serving_counters()` counts (summed by the engine over a
    #: dispatch's forwards): keys the sparse layers' queries kept and could
    #: see, over queries, K/V heads and sparse layers; rows whose state a
    #: lightning layer updated
    serving_counter_names = ("sparse_keys_kept", "sparse_keys_visible",
                             "state_rows")

    def serving_counters(self):
        return self.model.counters()

    def num_parameters(self):
        return int(sum(math.prod(p.shape) for p in self.parameters()))
