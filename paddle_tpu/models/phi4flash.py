"""Phi-4-mini-flash (`model_type: phi4flash`): the SambaY
decoder-hybrid-decoder (arXiv:2507.06607). A self-decoder of Mamba-1 layers
(arXiv:2312.00752) alternating with sliding-window attention, ONE
full-attention layer whose K and V are the model's only long cache (YOCO,
arXiv:2405.05254), and a cross-decoder whose layers alternate gated memory
units (GMU) with cross-attention over that one cache. Attention is
differential (arXiv:2410.05258, the `multihead_flashdiff_2` form). LayerNorm
with bias, no rotary embedding anywhere (positions are used by no layer), a
tied head.

Layer i of L (`mb_per_layer` m, derived as the source derives it):

    i % m == 0, i <  L/2      Mamba                         a state slot
    i % m != 0, i <  L/2      window attention              a ring of pages
    i == L/2                  Mamba, hands on its memory    a state slot
    i == L/2 + 1              full attention                K/V pages
    i >= L/2 + 2, i % m == 0  GMU: Wout(silu(Win x) * mem)  nothing
    i >= L/2 + 2, i % m != 0  cross-attention over L/2+1's  nothing (borrowed)

Differential attention on the repo's kernels, exactly: query heads (2p,
2p+1) and K/V heads (2p, 2p+1) are pair p's (q1, q2), (k1, k2), (v1, v2). A
K/V pair is STORED as one head of twice the width, `[k1 | k2]`, `[v1 | v2]`
(a plain reshape of adjacent heads), and the queries are zero-padded, `[q1 |
0]` and `[0 | q2]`: then `softmax(q1 k1^T / sqrt(d)) [v1 | v2]` and its twin
are plain grouped-query attention at the stored width with scale
`1/sqrt(d)` of the PUBLISHED head, K and V read once.

Built for serving, as models/minicpm_sala.py is: every parameter is created
in the configuration's dtype, the forward runs on raw arrays and keeps no
tape, and the cache is a spec a LAYER (ops/cache_specs.py). The serving
engine's mixed step runs the trunk (layers 0 .. L/2+1) on the packed stream
and the cross-decoder on the gathered span ends alone (`serving_tail`): a
prompt token that yields no logit never runs layers L/2+2 .. L-1, which cache
nothing. A forward with no cache runs the same ops over a scratch cache of
its own (every sequence a row of one packed stream).

Parameter names follow the family's checkpoints (`model.layers.N.attn.*`,
`mlp.fc1` / `fc2`, `input_layernorm`, `post_attention_layernorm`,
`final_layernorm`); weights are stored `[in, out]`, `conv1d.weight`
`[d_conv, d_inner]`.
"""
import math

import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..nn import initializer as I
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..ops.cache_specs import LayerCacheSpecs, NoPoolSpec
from ..ops.lightning_attention import StateSlotCache, StateSlotRaggedCache
from ..ops.paged_attention import (
    KVCacheSpec, PagedLayerCache, WindowRingSpec, paged_decode_attention,
    window_walk, write_token_kv,
)
from ..ops.ragged_paged_attention import (
    RaggedLayerCache, ragged_paged_attention, write_ragged_kv,
)
from ..ops.selective_scan import (
    causal_conv_decode, causal_conv_ragged, selective_scan_decode,
    selective_scan_ragged, ssm_slot_spec,
)
from .deepseek_v3 import _rms, _Weight

MAMBA, SWA, MEMORY, FULL, GMU, CROSS = (
    "mamba", "swa", "mamba-memory", "full", "gmu", "cross")


class Phi4FlashConfig:
    """The published `config.json` keys under their own names, Mamba's
    (`mamba_d_state`, `mamba_d_conv`, `mamba_expand`, `mamba_dt_rank`) and
    what this framework adds (`dtype`, the seeded init's three scales)."""

    def __init__(self, vocab_size=512, hidden_size=128, intermediate_size=256,
                 num_hidden_layers=8, num_attention_heads=8,
                 num_key_value_heads=4, mb_per_layer=2, sliding_window=32,
                 layer_norm_eps=1e-5, hidden_act="silu",
                 tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False,
                 embd_pdrop=0, resid_pdrop=0, max_position_embeddings=4096,
                 mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
                 mamba_dt_rank="auto", dtype="float32",
                 initializer_range=0.02, qk_init_std=None,
                 lambda_init_std=0.1, **unread):
        L = num_hidden_layers
        if mb_per_layer != 2 or L % 4 or L < 8:
            raise ValueError(
                "the layer table is the published one: mb_per_layer 2 and a "
                f"depth that is a multiple of 4, at least 8 (got "
                f"{mb_per_layer}, {L})")
        if hidden_act != "silu" or not tie_word_embeddings or mlp_bias \
                or lm_head_bias or embd_pdrop or resid_pdrop:
            raise ValueError("only the published switches are implemented: "
                             "silu, a tied head, no bias in MLP or head, no "
                             "dropout")
        if hidden_size % num_attention_heads or num_attention_heads % 2 \
                or num_key_value_heads % 2 \
                or num_attention_heads % num_key_value_heads:
            raise ValueError("differential attention pairs adjacent heads: "
                             "even counts of query and K/V heads, the one a "
                             "multiple of the other, heads dividing hidden")
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = L
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = hidden_size // num_attention_heads
        self.mb_per_layer, self.sliding_window = mb_per_layer, sliding_window
        self.layer_norm_eps = layer_norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.tie_word_embeddings = True
        self.mamba_d_state, self.mamba_d_conv = mamba_d_state, mamba_d_conv
        self.mamba_expand = mamba_expand
        self.d_inner = mamba_expand * hidden_size
        self.dt_rank = (math.ceil(hidden_size / 16)
                        if mamba_dt_rank == "auto" else mamba_dt_rank)
        self.dtype, self.initializer_range = dtype, initializer_range
        # a score q . k / sqrt(d) of LayerNorm'd input has a standard
        # deviation of hidden_size * std^2: 3 at sqrt(3 / hidden_size), where
        # a softmax over thousands of random keys is still peaked (at the
        # usual 0.02 it is flat and no logit tells a right page from a wrong)
        self.qk_init_std = qk_init_std or math.sqrt(3.0 / hidden_size)
        self.lambda_init_std = lambda_init_std
        half, m = L // 2, mb_per_layer
        self.layer_kinds = [
            (MAMBA if i % m == 0 else SWA) if i < half
            else MEMORY if i == half else FULL if i == half + 1
            else (GMU if i % m == 0 else CROSS) for i in range(L)]
        self.kv_layer = half + 1          # the one K/V pool's layer
        self.tail_start = half + 2        # the cross-decoder's first layer

    def lambda_init(self, index):
        return 0.8 - 0.6 * math.exp(-0.3 * index)


def phi4flash_tiny(**kw):
    """Eight layers (Mamba, window, Mamba, window, memory, full, GMU, cross:
    every kind), 8 query heads over 4 K/V heads of 16, a window of 32: the
    CPU tests' size."""
    return Phi4FlashConfig(**kw)


def _layer_norm(x, weight, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


class _Columns(I.Initializer):
    """Normal(0, std) on the first `n` output columns, Normal(0, rest) on the
    others: the query and key columns of a fused `Wqkv`."""

    def __init__(self, n, std, rest):
        self.n, self.std, self.rest = n, std, rest

    def __call__(self, shape, dtype):
        w = I.Normal(0.0, 1.0)(shape, dtype)
        scale = jnp.where(jnp.arange(shape[-1]) < self.n, self.std, self.rest)
        return w * scale.astype(dtype)


class _DtBias(I.Initializer):
    """Mamba's: the inverse softplus of dt, dt log-uniform in [lo, hi]."""

    def __init__(self, lo=1e-3, hi=0.1):
        self.lo, self.hi = lo, hi

    def __call__(self, shape, dtype):
        u = I.Uniform(0.0, 1.0)(shape, jnp.float32)
        dt = jnp.exp(u * (math.log(self.hi) - math.log(self.lo))
                     + math.log(self.lo))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class _ALog(I.Initializer):
    """Mamba's: A = -(1 .. d_state) a channel. A random A_log would forget
    at once or never, and the state would test nothing."""

    def __call__(self, shape, dtype):
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[-1] + 1, dtype=jnp.float32)), shape).astype(dtype)


class _Biased(Layer):
    """`weight` and `bias` (a LayerNorm's, the convolution's, `dt_proj`'s)."""

    def __init__(self, shape, bias_shape, dtype, init, bias_init):
        super().__init__()
        self.weight = self.create_parameter(list(shape), dtype=dtype,
                                            default_initializer=init)
        self.bias = self.create_parameter(list(bias_shape), dtype=dtype,
                                          default_initializer=bias_init)


def _norm(cfg):
    return _Biased((cfg.hidden_size,), (cfg.hidden_size,), cfg.dtype,
                   I.Constant(1.0), I.Constant(0.0))


class Mamba(Layer):
    """A Mamba-1 mixer over a state slot. `memory` (layer L/2): the forward
    also returns y before its gate, the cross-decoder's memory."""

    def __init__(self, cfg, scopes):
        super().__init__()
        self.cfg, self.scopes = cfg, scopes
        h, di = cfg.hidden_size, cfg.d_inner
        n, r, k = cfg.mamba_d_state, cfg.dt_rank, cfg.mamba_d_conv
        init = I.Normal(0.0, cfg.initializer_range)
        self.in_proj = _Weight((h, 2 * di), cfg.dtype, init)
        self.conv1d = _Biased((k, di), (di,), cfg.dtype,
                              I.Uniform(-k ** -0.5, k ** -0.5),
                              I.Uniform(-k ** -0.5, k ** -0.5))
        self.x_proj = _Weight((di, r + 2 * n), cfg.dtype, init)
        self.dt_proj = _Biased((r, di), (di,), cfg.dtype,
                               I.Uniform(-r ** -0.5, r ** -0.5), _DtBias())
        # float32 whatever the model's dtype, as the published kernel reads
        self.A_log = self.create_parameter([di, n], dtype="float32",
                                           default_initializer=_ALog())
        self.D = self.create_parameter([di], dtype="float32",
                                       default_initializer=I.Constant(1.0))
        self.out_proj = _Weight((di, h), cfg.dtype, init)
        self.last_rows = None

    def forward(self, x, pc):
        """x [N, hidden]; pc a state-slot view. Returns (out [N, hidden],
        y before the gate [N, d_inner], the view with the new state)."""
        cfg = self.cfg
        ragged = isinstance(pc, StateSlotRaggedCache)
        if not ragged and not isinstance(pc, StateSlotCache):
            raise ValueError(
                "a Mamba layer caches a state slot a row "
                f"(ops/selective_scan.py), not {type(pc).__name__}")
        n, r = cfg.mamba_d_state, cfg.dt_rank
        u, z = jnp.split(x @ self.in_proj.weight._data, 2, axis=-1)
        w, b = self.conv1d.weight._data, self.conv1d.bias._data
        A_T = -jnp.exp(self.A_log._data.astype(jnp.float32)).T
        D = self.D._data.astype(jnp.float32)
        h0, tail0 = pc.state
        with jax.named_scope(self.scopes[0 if ragged else 1]):
            if ragged:
                c, tail = causal_conv_ragged(u, w, b, tail0, pc.kv_lens,
                                             pc.cu_q_lens, pc.row_of)
            else:
                c, tail = causal_conv_decode(u, w, b, tail0, pc.lengths,
                                             pc.live)
        dbc = c @ self.x_proj.weight._data
        dt = jax.nn.softplus(
            (dbc[:, :r] @ self.dt_proj.weight._data).astype(jnp.float32)
            + self.dt_proj.bias._data.astype(jnp.float32))
        Bm, Cm = dbc[:, r:r + n], dbc[:, r + n:]
        if ragged:
            y, h1, rows = selective_scan_ragged(
                c, dt, Bm, Cm, A_T, D, h0, pc.kv_lens, pc.cu_q_lens,
                scopes=self.scopes)
            present = StateSlotRaggedCache(
                (h1, tail), pc.kv_lens, pc.cu_q_lens, pc.row_of,
                pc.token_pos, pc.valid)
        else:
            with jax.named_scope(self.scopes[1]):
                y, h1 = selective_scan_decode(c, dt, Bm, Cm, A_T, D, h0,
                                              pc.lengths, pc.live)
            rows = jnp.sum(pc.live).astype(jnp.int32)
            present = StateSlotCache((h1, tail), pc.lengths, pc.live)
        self.last_rows = rows
        gated = (y.astype(jnp.float32)
                 * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
        return gated @ self.out_proj.weight._data, y, present


class DiffAttention(Layer):
    """Differential attention of one layer. `kind` SWA / FULL: projects q, k
    and v, writes K and V to its own pool and attends; CROSS: projects q
    alone and attends over the view it is handed, writing nothing."""

    def __init__(self, cfg, index, kind):
        super().__init__()
        self.cfg, self.kind = cfg, kind
        h, d = cfg.hidden_size, cfg.head_dim
        H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
        self.lambda_init = cfg.lambda_init(index)
        self.window = cfg.sliding_window if kind == SWA else None
        init = I.Normal(0.0, cfg.initializer_range)
        qk = H * d + (0 if kind == CROSS else Hkv * d)
        self.Wqkv = _Weight(
            (h, H * d + (0 if kind == CROSS else 2 * Hkv * d)), cfg.dtype,
            _Columns(qk, cfg.qk_init_std, cfg.initializer_range))
        self.out_proj = _Weight((H * d, h), cfg.dtype, init)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, name, self.create_parameter(
                [d], dtype="float32",
                default_initializer=I.Normal(0.0, cfg.lambda_init_std)))
        self.subln = _Weight((2 * d,), cfg.dtype, I.Constant(1.0))

    def _padded_queries(self, q):
        """q [N, H * d] -> [N, H, 2d]: pair p's q1 as head 2p `[q1 | 0]`,
        its q2 as head 2p + 1 `[0 | q2]`; heads 4g .. 4g+3 (two pairs) share
        stored K/V head g when H / Hkv = 2."""
        cfg = self.cfg
        N, d = q.shape[0], cfg.head_dim
        q = q.reshape(N, cfg.num_attention_heads // 2, 2, d)
        zero = jnp.zeros_like(q[:, :, 0])
        return jnp.stack(
            [jnp.concatenate([q[:, :, 0], zero], axis=-1),
             jnp.concatenate([zero, q[:, :, 1]], axis=-1)],
            axis=2).reshape(N, cfg.num_attention_heads, 2 * d)

    def _combine(self, a):
        """a [N, H, 2d] (heads 2p, 2p+1 = a1, a2 of pair p) -> the layer's
        heads [N, H * d]: RMSNorm(a1 - lambda a2) (1 - lambda_init)."""
        cfg = self.cfg
        lam = (jnp.exp(jnp.sum(self.lambda_q1._data * self.lambda_k1._data))
               - jnp.exp(jnp.sum(self.lambda_q2._data * self.lambda_k2._data))
               + self.lambda_init)
        N = a.shape[0]
        a = a.reshape(N, cfg.num_attention_heads // 2, 2, 2 * cfg.head_dim
                      ).astype(jnp.float32)
        o = _rms(a[:, :, 0] - lam * a[:, :, 1],
                 self.subln.weight._data.astype(jnp.float32),
                 cfg.layer_norm_eps) * (1.0 - self.lambda_init)
        return o.reshape(N, -1)

    def forward(self, x, pc, scope):
        """x [N, hidden]; pc this layer's own view (SWA, FULL) or the K/V
        layer's, as that layer left it (CROSS). Returns (out, the view)."""
        cfg = self.cfg
        d, H, Hkv = cfg.head_dim, cfg.num_attention_heads, \
            cfg.num_key_value_heads
        N = x.shape[0]
        qkv = x @ self.Wqkv.weight._data
        q = self._padded_queries(qkv[:, :H * d])
        scale = d ** -0.5
        ragged = isinstance(pc, RaggedLayerCache)
        if not ragged and not isinstance(pc, PagedLayerCache):
            raise ValueError(
                "an attention layer caches K and V pages or reads another "
                f"layer's (ops/paged_attention.py), not {type(pc).__name__}")
        with jax.named_scope(scope):
            if self.kind != CROSS:
                # adjacent heads are a pair: [k1 | k2] is a reshape
                k = qkv[:, H * d:(H + Hkv) * d].reshape(N, Hkv // 2, 2 * d)
                v = qkv[:, (H + Hkv) * d:].reshape(N, Hkv // 2, 2 * d)
                if ragged:
                    pools = [write_ragged_kv(pg, pc.page_indices, pc.row_of,
                                             pc.token_pos, pc.valid, new)
                             for pg, new in ((pc.k_pages, k), (pc.v_pages, v))]
                    pc = RaggedLayerCache(
                        *pools, pc.page_indices, pc.kv_lens, pc.cu_q_lens,
                        pc.row_of, pc.token_pos, pc.valid)
                else:
                    pools = [write_token_kv(pg, pc.page_indices, pc.lengths,
                                            new)
                             for pg, new in ((pc.k_pages, k), (pc.v_pages, v))]
                    pc = PagedLayerCache(*pools, pc.page_indices, pc.lengths,
                                         pc.live)
            if ragged:
                a = ragged_paged_attention(
                    q, pc.k_pages, pc.v_pages, pc.kv_lens, pc.page_indices,
                    pc.cu_q_lens, scale=scale, window=self.window)
            else:
                # a dead row's token went to the scratch page: nothing there
                a = paged_decode_attention(
                    q, pc.k_pages, pc.v_pages,
                    jnp.where(pc.live, pc.lengths + 1, 0), pc.page_indices,
                    scale=scale, window=self.window)
        o = self._combine(a).astype(x.dtype)
        return o @ self.out_proj.weight._data, pc


class GatedMemory(Layer):
    """A GMU: `Wout(silu(Win x) * m)`, m the memory layer's y of the SAME
    token. It caches nothing."""

    def __init__(self, cfg):
        super().__init__()
        init = I.Normal(0.0, cfg.initializer_range)
        self.in_proj = _Weight((cfg.hidden_size, cfg.d_inner), cfg.dtype,
                               init)
        self.out_proj = _Weight((cfg.d_inner, cfg.hidden_size), cfg.dtype,
                                init)

    def forward(self, x, memory):
        with jax.named_scope("sambay.gmu"):
            g = jax.nn.silu((x @ self.in_proj.weight._data
                             ).astype(jnp.float32))
            y = (g * memory.astype(jnp.float32)).astype(x.dtype)
        return y @ self.out_proj.weight._data


class Phi4FlashMLP(Layer):
    def __init__(self, cfg):
        super().__init__()
        init = I.Normal(0.0, cfg.initializer_range)
        h, m = cfg.hidden_size, cfg.intermediate_size
        self.fc1 = _Weight((h, 2 * m), cfg.dtype, init)   # [gate | up]
        self.fc2 = _Weight((m, h), cfg.dtype, init)

    def forward(self, x):
        g, u = jnp.split(x @ self.fc1.weight._data, 2, axis=-1)
        return (jax.nn.silu(g) * u) @ self.fc2.weight._data


class Phi4FlashDecoderLayer(Layer):
    def __init__(self, cfg, index):
        super().__init__()
        self.cfg = cfg
        self.kind = kind = cfg.layer_kinds[index]
        if kind in (MAMBA, MEMORY):
            self.attn = Mamba(cfg, ("sambay.ssm.prefill",
                                    "sambay.ssm.decode"))
        elif kind == GMU:
            self.attn = GatedMemory(cfg)
        else:
            self.attn = DiffAttention(cfg, index, kind)
        self.mlp = Phi4FlashMLP(cfg)
        self.input_layernorm = _norm(cfg)
        self.post_attention_layernorm = _norm(cfg)

    def forward(self, h, mix):
        """h [N, hidden]; `mix(attn, normed x)` -> (the mixer's output, what
        it hands on). Returns (h, what it handed on)."""
        eps = self.cfg.layer_norm_eps
        n1, n2 = self.input_layernorm, self.post_attention_layernorm
        a, aux = mix(self.attn, _layer_norm(h, n1.weight._data,
                                            n1.bias._data, eps))
        h = h + a.astype(h.dtype)
        m = self.mlp(_layer_norm(h, n2.weight._data, n2.bias._data, eps))
        return h + m.astype(h.dtype), aux


class Phi4FlashModel(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        self.embed_tokens = _Weight(
            (cfg.vocab_size, cfg.hidden_size), cfg.dtype,
            I.Normal(0.0, cfg.initializer_range))
        self.layers = LayerList([Phi4FlashDecoderLayer(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.final_layernorm = _norm(cfg)
        self.last_counters = None

    def cache_spec(self):
        cfg = self.config
        d2 = 2 * cfg.head_dim   # a stored K/V pair
        hp = cfg.num_key_value_heads // 2

        def spec(kind):
            if kind in (MAMBA, MEMORY):
                return ssm_slot_spec(cfg.d_inner, cfg.mamba_d_state,
                                     cfg.mamba_d_conv)
            if kind == SWA:
                return WindowRingSpec(hp, d2, cfg.sliding_window)
            if kind == FULL:
                return KVCacheSpec(1, hp, d2, cfg.num_attention_heads)
            return NoPoolSpec(cfg.kv_layer if kind == CROSS else None)

        return LayerCacheSpecs(spec(kind) for kind in cfg.layer_kinds)

    def _scratch_caches(self, B, S, dtype, page=64):
        """A forward with no cache: every sequence a row of one packed
        stream over pools of its own, each row starting at length 0 (the
        rings sized for a chunk of the whole sequence)."""
        npages = -(-S // page)
        spec = self.cache_spec()
        pools = spec.make_pools(1 + B * npages, page, dtype, max_seqs=B,
                                prefill_chunk=S)
        table = 1 + jnp.arange(B * npages, dtype=jnp.int32).reshape(B, npages)
        cu = jnp.arange(B + 1, dtype=jnp.int32) * S
        row_of = jnp.repeat(jnp.arange(B, dtype=jnp.int32), S)
        pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), B)
        return [s.ragged(pool, table, jnp.full((B,), S, jnp.int32), cu,
                         row_of, pos, jnp.ones((B * S,), bool))
                for s, pool in zip(spec.layers, pools)]

    # ---- the two halves ---------------------------------------------------
    def _trunk(self, h, caches):
        """Layers 0 .. L/2+1 on h [N, hidden] through their own views.
        Returns (h, the memory [N, d_inner], the views back)."""
        cfg = self.config
        ragged = isinstance(caches[cfg.kv_layer], RaggedLayerCache)
        memory, presents = None, []
        for layer, pc in zip(self.layers[:cfg.tail_start], caches):
            if layer.kind in (MAMBA, MEMORY):
                def mix(attn, x, pc=pc):
                    out, y, present = attn(x, pc)
                    return out, (y, present)
                h, (y, present) = layer(h, mix)
                if layer.kind == MEMORY:
                    memory = y
            else:
                # the K/V layer's one-token rows read the pool as the
                # cross layers do: one scope for the pool's decode reads
                scope = (f"sambay.swa.{'prefill' if ragged else 'decode'}"
                         if layer.kind == SWA else
                         "sambay.full.prefill" if ragged else
                         "sambay.cross.decode")
                h, present = layer(
                    h, lambda attn, x, pc=pc, scope=scope: attn(x, pc, scope))
            presents.append(present)
        self._count_trunk(caches, presents)
        return h, memory, presents

    def _tail(self, h, memory, kv):
        """Layers L/2+2 .. L-1 and the final norm on h [N, hidden], with the
        memory of the same N tokens and the K/V layer's view `kv` (ragged:
        N the packed stream; paged: N one query a row)."""
        cfg = self.config
        scope = ("sambay.cross.decode" if isinstance(kv, PagedLayerCache)
                 else "sambay.cross.prefill")
        for layer in self.layers[cfg.tail_start:]:
            if layer.kind == GMU:
                h, _ = layer(h, lambda attn, x: (attn(x, memory), None))
            else:
                h, _ = layer(h, lambda attn, x: attn(x, kv, scope))
        f = self.final_layernorm
        return _layer_norm(h, f.weight._data, f.bias._data,
                           cfg.layer_norm_eps)

    def _count_trunk(self, caches, presents):
        """int32 [5] of `Phi4FlashForCausalLM.serving_counter_names`, the
        tail's tokens still 0: keys the window layers' kernels walk and a
        full causal walk would (a row, page granular), token x layer pairs
        of a packed pass, rows whose state the first Mamba layer updated."""
        cfg = self.config
        kinds = cfg.layer_kinds
        kv = caches[cfg.kv_layer]
        n_swa = kinds.count(SWA)
        if isinstance(kv, RaggedLayerCache):
            q_lens = kv.cu_q_lens[1:] - kv.cu_q_lens[:-1]
            lens = jnp.where(q_lens > 0, kv.kv_lens, 0)
            trunk = kv.cu_q_lens[-1] * cfg.tail_start
        else:
            q_lens = kv.live.astype(jnp.int32)
            lens = jnp.where(kv.live, kv.lengths + 1, 0)
            trunk = jnp.zeros((), jnp.int32)
        walked, causal = window_walk(lens, q_lens, cfg.sliding_window,
                                     kv.page_size)
        self.last_counters = jnp.stack([
            n_swa * walked, n_swa * causal, jnp.zeros((), jnp.int32), trunk,
            self.layers[0].attn.last_rows]).astype(jnp.int32)

    def forward(self, input_ids, attention_mask=None, position_ids=None,
                past_key_values=None, cache_position=None, use_cache=False,
                tail=None):
        """`past_key_values` None: the whole model over a scratch cache,
        the normed stream back. A list of decode views (one token a row):
        all layers, (the normed stream, the views). A list of ragged views
        (a packed stream): the TRUNK alone, ((h, memory), the views): the
        cross-decoder is `tail`'s, on whichever tokens yield a logit.
        `tail` = ((h, memory), positions `at` of the stream [1, T], the
        trunk's views): the cross-decoder and the final norm on those
        tokens, one a row, [len(at), hidden]."""
        if attention_mask is not None or cache_position is not None:
            raise ValueError("this decoder takes its layers' own caches only "
                             "(no padding mask, no fixed-shape cache)")
        cfg = self.config
        if tail is not None:
            (h, memory), at, presents = tail
            return self._tail_at(h[0, at], memory[at], presents)
        ids = input_ids._data
        B, S = ids.shape
        h = self.embed_tokens.weight._data[ids].reshape(B * S, -1)
        caches = past_key_values
        if caches is None:
            caches = self._scratch_caches(B, S, h.dtype)
        h, memory, presents = self._trunk(h, caches)
        kv = presents[cfg.kv_layer]
        presents += list(caches[cfg.tail_start:])   # poolless: handed back
        if past_key_values is not None and isinstance(kv, RaggedLayerCache):
            return (h.reshape(B, S, -1), memory), presents
        out = Tensor(self._tail(h, memory, kv).reshape(B, S, -1),
                     stop_gradient=True)
        return (out, presents) if past_key_values is not None else out

    def _count_tail(self, tokens):
        """`tokens` of a packed pass ran the cross-decoder's layers."""
        cfg = self.config
        self.last_counters = self.last_counters.at[2].set(
            tokens * (cfg.num_hidden_layers - cfg.tail_start))

    def _tail_at(self, h, memory, presents):
        """The cross-decoder on ONE token a row (a mixed step's span ends):
        the K/V layer's packed-stream view becomes the decode view of a row
        whose query is its last token. A row of no token reads nothing."""
        cfg = self.config
        kv = presents[cfg.kv_layer]
        q_lens = kv.cu_q_lens[1:] - kv.cu_q_lens[:-1]
        live = q_lens > 0
        self._count_tail(jnp.sum(live))
        return self._tail(h, memory, PagedLayerCache(
            kv.k_pages, kv.v_pages, kv.page_indices, kv.kv_lens - 1, live))

    def counters(self):
        return self.last_counters


class Phi4FlashForCausalLM(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        self.model = Phi4FlashModel(cfg)

    def forward(self, input_ids, attention_mask=None, position_ids=None,
                past_key_values=None, cache_position=None, use_cache=False):
        out = self.model(input_ids, attention_mask, position_ids,
                         past_key_values, cache_position, use_cache)
        if past_key_values is None:
            return Tensor(self._head(out._data), stop_gradient=True)
        h, presents = out
        if isinstance(h, tuple):   # ragged views: the tail on EVERY token
            (h, memory), kv = h, presents[self.config.kv_layer]
            self.model._count_tail(kv.cu_q_lens[-1])
            h = self.model._tail(h[0], memory, kv)[None]
        else:
            h = h._data
        return Tensor(self._head(h), stop_gradient=True), presents

    def _head(self, h, w=None):
        w = self.model.embed_tokens.weight._data if w is None else w
        return h @ jnp.swapaxes(w, -1, -2)

    # ---- the serving engine's model protocol (inference/continuous.py) ----
    def serving_trunk(self):
        return self.model, "model."

    def serving_tail(self, overrides, trunk_out, at, presents):
        """What a mixed step runs between the trunk and the head: the
        cross-decoder on the span ends `at` alone (one token a row)."""
        return self.model.functional_call(
            overrides, None, tail=(trunk_out, at, presents), training=False)

    def serving_head(self, h, state):
        return self._head(h, state["model.embed_tokens.weight"])

    def serving_cache_spec(self):
        return self.model.cache_spec()

    #: the `jax.named_scope`s this model opens inside the step programs
    serving_scopes = ("sambay.ssm.prefill", "sambay.ssm.decode",
                      "sambay.swa.prefill", "sambay.swa.decode",
                      "sambay.full.prefill", "sambay.cross.decode",
                      "sambay.gmu")
    #: what `serving_counters()` counts (summed by the engine over a
    #: dispatch's forwards): keys the window layers' kernels walk and keys a
    #: full causal walk would, over rows, window layers and forwards (a row's
    #: pages from its first query's window to its end; its whole length);
    #: token x layer pairs the packed pass ran in the cross-decoder and in
    #: the trunk; rows whose state a Mamba layer updated
    serving_counter_names = ("swa_keys_visited", "swa_keys_causal",
                             "tail_tokens", "trunk_tokens", "state_rows")

    def serving_counters(self):
        return self.model.counters()

    def num_parameters(self):
        return int(sum(math.prod(p.shape) for p in self.parameters()))
