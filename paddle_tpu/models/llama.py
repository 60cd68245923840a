"""LLaMA family — the flagship model (BASELINE configs 4/5; reference
analogue: PaddleNLP llama modeling on top of fleet meta_parallel layers).

TPU-first design:
- every weight carries a PartitionSpec (mp for tensor parallel, sharding for
  ZeRO) consumed by DistributedTrainStep's pjit shardings;
- attention lowers to the Pallas flash kernel on TPU (ops/flash_attention);
- rope/swiglu/rms_norm are the fused incubate functionals (XLA fuses);
- optional jax.checkpoint recompute per decoder layer;
- homogeneous decoder blocks so the pipeline engine can stack/scan them.
"""
import math

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..framework import dtype as dtypes
from ..framework.core import Tensor
from ..incubate.nn.functional import fused_rotary_position_embedding, swiglu
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..distributed.fleet.pp_layers import PipelineModule
from ..generation import GenerationMixin
from ..nn.layer.norm import RMSNorm
from ..tensor import manipulation


class LlamaConfig:
    def __init__(
        self,
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=11008,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=None,
        max_position_embeddings=4096,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        use_recompute=False,
        recompute_policy="full",
        sequence_parallel=False,
        fuse_linear_cross_entropy=False,
        ce_chunk_size=None,
        dtype="float32",
        seq_length=2048,
        num_experts=0,
        moe_top_k=2,
        moe_gate="gshard",
        moe_aux_loss_weight=0.01,
        context_parallel=False,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.tie_word_embeddings = tie_word_embeddings
        self.use_recompute = use_recompute
        self.recompute_policy = recompute_policy
        self.sequence_parallel = sequence_parallel
        self.fuse_linear_cross_entropy = fuse_linear_cross_entropy
        self.ce_chunk_size = ce_chunk_size
        self.dtype = dtype
        self.seq_length = seq_length
        # Mixtral-class sparse-MoE variant (reference ecosystem:
        # incubate.distributed.models.moe atop the fleet EP axis): every
        # decoder layer's MLP becomes num_experts SwiGLU experts behind a
        # gshard/switch gate; the load-balance aux loss joins the CE loss.
        self.num_experts = num_experts
        self.moe_top_k = moe_top_k
        self.moe_gate = moe_gate
        self.moe_aux_loss_weight = moe_aux_loss_weight
        # context/sequence parallelism over the sep mesh axis (SURVEY §5
        # long-context): True/"ring" = ring attention (KV shards rotate by
        # ppermute, blockwise tiles); "ulysses" = DeepSpeed-Ulysses style
        # (two all_to_alls swap seq-sharding for head-sharding around
        # flash-tier attention — needs per-mp-rank Q heads divisible by
        # sep; GQA kv heads ride the a2a unexpanded when also divisible).
        # DistributedTrainStep shards [B, S] inputs' seq dim on sep
        # automatically either way.
        if context_parallel not in (False, True, "ring", "ulysses"):
            raise ValueError(
                f"context_parallel must be False/True/'ring'/'ulysses', "
                f"got {context_parallel!r}")
        self.context_parallel = context_parallel

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


# canonical sizes (LLaMA-2 family) — BASELINE configs 4 (7B) and 5 (70B)
def llama2_7b(**kw):
    return LlamaConfig(hidden_size=4096, intermediate_size=11008, num_hidden_layers=32,
                       num_attention_heads=32, **kw)


def llama2_13b(**kw):
    return LlamaConfig(hidden_size=5120, intermediate_size=13824, num_hidden_layers=40,
                       num_attention_heads=40, **kw)


def llama2_70b(**kw):
    return LlamaConfig(hidden_size=8192, intermediate_size=28672, num_hidden_layers=80,
                       num_attention_heads=64, num_key_value_heads=8, **kw)


def llama_tiny(**kw):
    """test-scale config"""
    kw.setdefault("vocab_size", 128)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("intermediate_size", 128)
    kw.setdefault("num_hidden_layers", 2)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("max_position_embeddings", 128)
    return LlamaConfig(**kw)


def _mk_linear(in_f, out_f, spec, std=0.02, bias=False):
    """TP-annotated Linear. bias=False for LLaMA-style projections; BERT/
    ERNIE pass bias=True — a column-parallel ("mp" output dim) bias shards
    on "mp", a row-parallel one replicates."""
    l = Linear(in_f, out_f, weight_attr=None, bias_attr=None if bias else False)
    l.weight._data = I.Normal(0.0, std)((in_f, out_f), l.weight.dtype)
    l.weight.partition_spec = spec
    l.weight.is_distributed = True
    if bias:
        l.bias.partition_spec = P("mp") if spec[-1] == "mp" else P(None)
        l.bias.is_distributed = True
    return l


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        # column-parallel qkv (heads split over mp), row-parallel output
        self.q_proj = _mk_linear(h, self.num_heads * self.head_dim, P(None, "mp"))
        self.k_proj = _mk_linear(h, self.num_kv_heads * self.head_dim, P(None, "mp"))
        self.v_proj = _mk_linear(h, self.num_kv_heads * self.head_dim, P(None, "mp"))
        self.o_proj = _mk_linear(self.num_heads * self.head_dim, h, P("mp", None))

    def forward(self, hidden_states, attention_mask=None, position_ids=None,
                past_key_value=None, cache_position=None, segment_ids=None):
        """past_key_value:
        - None: plain causal attention;
        - (k, v) without cache_position: legacy growing-concat cache (eager);
        - (k_cache, v_cache) [B, S_max, hk, D] WITH cache_position: the
          fixed-shape decode cache (XLA-friendly — dynamic_update_slice at
          the write offset, full-cache attention under a position mask);
        - ops.paged_attention.PagedLayerCache: the paged serving cache
          (page-pool scatter write + paged decode attention; kernel-backed
          on TPU — reference: PaddleNLP block-attention serving /
          PAPERS.md ragged-paged-attention). Decode-only (S == 1),
          inference-only (no tape);
        - ops.ragged_paged_attention.RaggedLayerCache: the ragged serving
          cache — S is a PACKED mixed prefill+decode token stream (B == 1)
          whose per-row spans/page tables ride in the cache entry; one
          ragged kernel dispatch covers every row. Inference-only."""
        import jax

        from ..framework.core import apply
        from ..ops.paged_attention import PagedLayerCache
        from ..ops.ragged_paged_attention import RaggedLayerCache

        B, S = hidden_states.shape[0], hidden_states.shape[1]
        q = manipulation.reshape(self.q_proj(hidden_states), [B, S, self.num_heads, self.head_dim])
        k = manipulation.reshape(self.k_proj(hidden_states), [B, S, self.num_kv_heads, self.head_dim])
        v = manipulation.reshape(self.v_proj(hidden_states), [B, S, self.num_kv_heads, self.head_dim])
        paged = isinstance(past_key_value, PagedLayerCache)
        ragged = isinstance(past_key_value, RaggedLayerCache)
        if segment_ids is not None and (past_key_value is not None
                                        or cache_position is not None):
            raise ValueError("packed segment_ids do not compose with a "
                             "decode cache — packing is a training path")
        rope_kw = {}
        if cache_position is not None or paged or ragged:
            if position_ids is None and cache_position is not None:
                pos0 = cache_position if hasattr(cache_position, "_data") else Tensor(jnp.asarray(cache_position))
                position_ids = apply(
                    lambda p: jnp.broadcast_to(p + jnp.arange(S), (B, S)), pos0, name="cache_pos"
                )
            # rope table must cover absolute positions up to the cache end
            # (the default table is sized to the CURRENT q length — one row
            # during decode)
            if paged or ragged:
                S_tab = past_key_value.page_indices.shape[1] * past_key_value.page_size
            elif past_key_value is not None:
                S_tab = past_key_value[0].shape[1]
            else:
                S_tab = self.config.max_position_embeddings
            D = self.head_dim
            inv = 1.0 / (self.config.rope_theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
            emb = jnp.concatenate([o := jnp.outer(jnp.arange(S_tab, dtype=jnp.float32), inv), o], axis=-1)
            rope_kw = dict(cos=Tensor(jnp.cos(emb)), sin=Tensor(jnp.sin(emb)))
        q, k, _ = fused_rotary_position_embedding(
            q, k, None, position_ids=position_ids, rotary_emb_base=self.config.rope_theta,
            **rope_kw,
        )
        if paged:
            from ..ops.paged_attention import paged_decode_attention, write_token_kv

            if S != 1:
                raise ValueError("paged cache is decode-only: expected S == 1")
            pc = past_key_value
            k_pages = write_token_kv(pc.k_pages, pc.page_indices, pc.lengths,
                                     k._data[:, 0])
            v_pages = write_token_kv(pc.v_pages, pc.page_indices, pc.lengths,
                                     v._data[:, 0])
            # a dead row's token went to the scratch page: nothing to attend
            out = paged_decode_attention(
                q._data[:, 0], k_pages, v_pages,
                jnp.where(pc.live, pc.lengths + 1, 0), pc.page_indices
            )
            out = Tensor(out.reshape(B, 1, self.num_heads * self.head_dim),
                         stop_gradient=True)
            present = PagedLayerCache(k_pages, v_pages, pc.page_indices,
                                      pc.lengths, pc.live)
            return self.o_proj(out), present
        if ragged:
            from ..ops.ragged_paged_attention import (
                ragged_paged_attention, write_ragged_kv,
            )

            if B != 1:
                raise ValueError(
                    "ragged cache packs every row into one stream: "
                    "expected B == 1")
            rc = past_key_value
            k_pages = write_ragged_kv(rc.k_pages, rc.page_indices, rc.row_of,
                                      rc.token_pos, rc.valid, k._data[0])
            v_pages = write_ragged_kv(rc.v_pages, rc.page_indices, rc.row_of,
                                      rc.token_pos, rc.valid, v._data[0])
            out = ragged_paged_attention(
                q._data[0], k_pages, v_pages, rc.kv_lens, rc.page_indices,
                rc.cu_q_lens,
            )
            out = Tensor(out.reshape(B, S, self.num_heads * self.head_dim),
                         stop_gradient=True)
            present = RaggedLayerCache(
                k_pages, v_pages, rc.page_indices, rc.kv_lens, rc.cu_q_lens,
                rc.row_of, rc.token_pos, rc.valid)
            return self.o_proj(out), present
        if past_key_value is not None and cache_position is not None:
            k_cache, v_cache = past_key_value
            pos_a = (cache_position._data if hasattr(cache_position, "_data")
                     else jnp.asarray(cache_position))

            def write(cache, new):
                return jax.lax.dynamic_update_slice(
                    cache, new.astype(cache.dtype), (0, pos_a, 0, 0)
                )

            k_cache = apply(write, k_cache, k, name="kv_cache_write")
            v_cache = apply(write, v_cache, v, name="kv_cache_write")
            present = (k_cache, v_cache)
            S_max = k_cache.shape[1]
            # absolute-position causal mask over the full fixed cache:
            # query row i (absolute pos p+i) may see cache cols j <= p+i
            def build_mask(p):
                rows = p + jnp.arange(S)[:, None]
                cols = jnp.arange(S_max)[None, :]
                m = jnp.where(cols <= rows, 0.0, jnp.float32(-1e9))
                return m[None, None]  # [1, 1, S, S_max]

            mask = apply(build_mask, Tensor(pos_a), name="cache_mask")
            if attention_mask is not None and attention_mask.ndim == 2:
                pad = (1.0 - manipulation.unsqueeze(attention_mask.astype("float32"), [1, 2])) * -1e9
                mask = mask + pad
            out = F.scaled_dot_product_attention(q, k_cache, v_cache, attn_mask=mask,
                                                 is_causal=False, training=self.training)
            out = manipulation.reshape(out, [B, S, self.num_heads * self.head_dim])
            return self.o_proj(out), present
        if past_key_value is not None:
            k = manipulation.concat([past_key_value[0], k], axis=1)
            v = manipulation.concat([past_key_value[1], v], axis=1)
        present = (k, v)
        if segment_ids is not None:
            if attention_mask is not None:
                raise ValueError(
                    "packed segment_ids and attention_mask are exclusive — "
                    "give padding its own segment id instead")
            from ..framework.core import apply
            from ..ops.flash_attention import flash_attention_packed

            out = apply(
                lambda qd, kd, vd: flash_attention_packed(
                    qd, kd, vd, segment_ids._data if hasattr(segment_ids, "_data")
                    else segment_ids, causal=True),
                q, k, v, name="flash_attention_packed")
            out = manipulation.reshape(out, [B, S, self.num_heads * self.head_dim])
            return self.o_proj(out), present
        if self._use_context_parallel(past_key_value):
            if attention_mask is not None:
                raise ValueError(
                    "context_parallel attention is causal-only: padding "
                    "masks are not supported on the ring path (pack "
                    "sequences instead)")
            out = self._ring_attention(q, k, v)
            out = manipulation.reshape(out, [B, S, self.num_heads * self.head_dim])
            return self.o_proj(out), present
        # causal ALWAYS holds for the decoder; a user mask only adds padding.
        # [B, S] padding masks become additive [B, 1, 1, S].
        mask = attention_mask
        if mask is not None and mask.ndim == 2:
            mask = (1.0 - manipulation.unsqueeze(mask.astype("float32"), [1, 2])) * -1e9
        out = F.scaled_dot_product_attention(q, k, v, attn_mask=mask,
                                             is_causal=True, training=self.training)
        out = manipulation.reshape(out, [B, S, self.num_heads * self.head_dim])
        return self.o_proj(out), present

    def _use_context_parallel(self, past_key_value):
        if not self.config.context_parallel or past_key_value is not None:
            return False
        from ..distributed.mesh import get_mesh, has_mesh

        if not has_mesh():
            return False
        mesh = get_mesh()
        if "sep" not in mesh.axis_names or mesh.shape["sep"] <= 1:
            return False
        from ..distributed.mesh import inside_manual_pp

        if inside_manual_pp():
            # inside the scheduled pipeline engine the pp axis is manual and
            # a nested sep shard_map cannot apply — refuse loudly rather
            # than silently computing non-CP attention on CP-sharded inputs
            raise ValueError(
                "context_parallel does not compose with the scheduled "
                "pipeline engine yet — run CP on the GSPMD path "
                "(dp/mp/sharding x sep) or pipeline without CP")
        return True

    def _ring_attention(self, q, k, v):
        """Context-parallel attention island: the surrounding program is
        GSPMD-global with the sequence dim sharded on sep
        (DistributedTrainStep._batch_spec); this shard_map runs either the
        blockwise ring (ops/ring_attention — Pallas tier on TPU, causal by
        GLOBAL positions) or the Ulysses all-to-all pair on the local
        shards. q/k/v: [B, S, H(kv), D]."""
        import functools

        import jax

        from ..distributed.mesh import get_mesh
        from ..framework.core import apply
        from ..ops.ring_attention import ring_attention, ulysses_attention

        mesh = get_mesh()
        sep = mesh.shape["sep"]
        if q.shape[1] % sep:
            raise ValueError(
                f"context_parallel: sequence length {q.shape[1]} is not "
                f"divisible by the sep axis size {sep} — pad the sequence "
                "or change the mesh")
        ulysses = self.config.context_parallel == "ulysses"
        # keep the batch axes and TP sharding INSIDE the island's layout:
        # declaring them replicated would make GSPMD all-gather full-batch,
        # all-head q/k/v and redo identical attention on every dp/mp rank
        batch = tuple(a for a in ("dcn_dp", "dp", "sharding")
                      if a in mesh.axis_names and mesh.shape[a] > 1)
        bspec = batch if len(batch) != 1 else batch[0]
        mp = mesh.shape.get("mp", 1) if "mp" in mesh.axis_names else 1
        hspec = "mp" if mp > 1 else None
        if ulysses:
            hq_local = q.shape[2] // mp
            hkv_local = k.shape[2] // mp
            if hq_local % sep:
                raise ValueError(
                    f"context_parallel='ulysses' needs per-mp-rank head "
                    f"count divisible by sep={sep} (got {hq_local}) — use "
                    "'ring' instead (which keeps kv heads unexpanded)")
            # GQA: keep kv UNEXPANDED through the a2a when its head count
            # splits over sep (flash_attention_fwd handles hq != hk natively
            # — splash kernel on TPU); pre-expand only as the fallback,
            # which costs group x the KV a2a bytes
            group = q.shape[2] // k.shape[2]
            pre_expand = group > 1 and hkv_local % sep != 0
            # ulysses layout is [B, S, H, D]: seq on dim 1, heads on dim 2.
            # attn_impl: the flash tier (Pallas kernel on TPU), NOT the
            # dense default — full-sequence scores per head-group at long
            # context is exactly what CP exists to avoid
            from ..ops.flash_attention import flash_attention_fwd

            island = jax.shard_map(
                functools.partial(
                    ulysses_attention, axis_name="sep", causal=True,
                    attn_impl=lambda qq, kk, vv: flash_attention_fwd(
                        qq, kk, vv, causal=True),
                ),
                mesh=mesh,
                in_specs=(P(bspec if batch else None, "sep", hspec, None),) * 3,
                out_specs=P(bspec if batch else None, "sep", hspec, None),
                check_vma=False,
            )

            def fn(qd, kd, vd):
                if pre_expand:
                    kd = jnp.repeat(kd, group, axis=2)
                    vd = jnp.repeat(vd, group, axis=2)
                return island(qd, kd, vd)
        else:
            spec = P(bspec if batch else None, hspec, "sep", None)
            island = jax.shard_map(
                functools.partial(ring_attention, axis_name="sep", causal=True),
                mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
            )

            def fn(qd, kd, vd):
                out = island(jnp.swapaxes(qd, 1, 2), jnp.swapaxes(kd, 1, 2),
                             jnp.swapaxes(vd, 1, 2))
                return jnp.swapaxes(out, 1, 2)  # back to [B, S, H, D]

        return apply(fn, q, k, v, name="ulysses_cp" if ulysses else "ring_attention_cp")


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        self.gate_proj = _mk_linear(h, m, P(None, "mp"))
        self.up_proj = _mk_linear(h, m, P(None, "mp"))
        self.down_proj = _mk_linear(m, h, P("mp", None))

    def forward(self, x):
        return self.down_proj(swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.self_attn = LlamaAttention(config)
        if config.num_experts > 1:
            # Mixtral-class sparse MoE: SwiGLU expert bank behind a
            # gshard/switch gate, experts sharded on the expert mesh axis
            from ..incubate.distributed.models.moe import (
                MoELayer,
                SwiGLUExpertStack,
            )

            self.mlp = MoELayer(
                config.hidden_size,
                experts=SwiGLUExpertStack(
                    config.num_experts, config.hidden_size,
                    config.intermediate_size),
                gate={"type": config.moe_gate,
                      "num_expert": config.num_experts,
                      "top_k": config.moe_top_k},
            )
        else:
            self.mlp = LlamaMLP(config)
        self.input_layernorm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, hidden_states, attention_mask=None, position_ids=None,
                past_key_value=None, cache_position=None, segment_ids=None):
        residual = hidden_states
        h, present = self.self_attn(
            self.input_layernorm(hidden_states), attention_mask, position_ids,
            past_key_value=past_key_value, cache_position=cache_position,
            segment_ids=segment_ids,
        )
        h = residual + h
        residual = h
        h = residual + self.mlp(self.post_attention_layernorm(h))
        if past_key_value is not None:
            return h, present
        return h


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        self.embed_tokens.weight._data = I.Normal(0.0, 0.02)(
            (config.vocab_size, config.hidden_size), self.embed_tokens.weight.dtype
        )
        self.embed_tokens.weight.partition_spec = P("mp", None)
        self.layers = LayerList([LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, attention_mask=None, position_ids=None,
                past_key_values=None, cache_position=None, use_cache=False,
                segment_ids=None):
        if segment_ids is not None and position_ids is None:
            # rope restarts at every packed segment boundary
            from ..framework.core import Tensor as _T
            from ..ops.flash_attention import packed_position_ids

            raw = segment_ids._data if hasattr(segment_ids, "_data") else segment_ids
            position_ids = _T(packed_position_ids(raw), stop_gradient=True)
        h = self.embed_tokens(input_ids)
        if self.config.sequence_parallel:
            h = _seq_shard(h)
        presents = [] if (use_cache or past_key_values is not None) else None
        for i, layer in enumerate(self.layers):
            pkv = past_key_values[i] if past_key_values is not None else None
            if pkv is not None:
                h, present = layer(h, attention_mask, position_ids,
                                   past_key_value=pkv, cache_position=cache_position)
                presents.append(present)
            elif (self.config.use_recompute and self.training
                  and self.config.num_experts <= 1):
                # MoE layers skip block-level remat: the gate's aux loss is
                # read off the layer afterwards (moe_aux_loss) and must stay
                # on the primal tape; expert remat is MoELayer's own
                # recompute_interval
                from ..distributed.fleet.recompute import recompute

                h = recompute(layer, h, attention_mask, position_ids,
                              policy=self.config.recompute_policy,
                              segment_ids=segment_ids)
            else:
                h = layer(h, attention_mask, position_ids,
                          segment_ids=segment_ids)
        out = self.norm(h)
        if presents is not None and past_key_values is not None:
            return out, presents
        return out

    def moe_aux_loss(self):
        """Sum of the gates' load-balance losses from the LAST forward
        (None when the model has no MoE layers).

        Trace-scope contract: l_aux is a forward side-channel, so this is
        valid only (a) eagerly, right after an eager forward, or (b) INSIDE
        the same trace as the forward — which is exactly how a TrainStep
        loss_fn runs (forward and loss trace as one program; see
        LlamaForCausalLM.make_loss_fn). Reading it eagerly after a JITTED
        forward raises jax's UnexpectedTracerError rather than returning a
        stale value."""
        total = None
        for layer in self.layers:
            aux = getattr(layer.mlp, "l_aux", None)
            if aux is not None:
                total = aux if total is None else total + aux
        return total


def _seq_shard(h):
    """Megatron-SP equivalent: constrain the activation's seq dim onto the mp
    axis (reference: sequence_parallel_utils.py ScatterOp). Under GSPMD this
    single constraint induces the scatter/gather pattern."""
    import jax

    from ..distributed.mesh import get_mesh, has_mesh
    from ..framework.core import apply

    if not has_mesh():
        return h
    mesh = get_mesh()
    if "mp" not in mesh.axis_names or mesh.shape["mp"] == 1:
        return h
    from ..distributed.mesh import inside_manual_pp

    if inside_manual_pp():
        # inside the scheduled engine's shard_map the pp axis is manual and
        # a GSPMD constraint cannot apply to pp-varying values — SP sharding
        # there is GSPMD's job via the weight specs, so skip the hint
        return h
    sharding = jax.sharding.NamedSharding(mesh, P(None, "mp", None))
    return apply(lambda a: jax.lax.with_sharding_constraint(a, sharding), h, name="seq_shard")


class LlamaPretrainingCriterion(Layer):
    """reference: PaddleNLP LlamaPretrainingCriterion (TP-aware CE)."""

    def __init__(self, config=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index
        self.ce_chunk_size = getattr(config, "ce_chunk_size", None)

    def forward(self, logits, *rest):
        if len(rest) == 2:
            # fused form: (hidden, lm_weight, labels) — chunked CE, no full
            # logits tensor (incubate.nn.functional.fused_linear_cross_entropy)
            from ..incubate.nn.functional import fused_linear_cross_entropy

            weight, labels = rest
            return fused_linear_cross_entropy(
                logits, weight, labels, ignore_index=self.ignore_index,
                chunk_size=self.ce_chunk_size
            )
        (labels,) = rest
        return F.cross_entropy(
            logits.astype("float32"), labels, ignore_index=self.ignore_index, reduction="mean"
        )


class LlamaEmbeddingPipe(Embedding):
    """Pipe head desc (reference: LlamaEmbeddingPipe in PaddleNLP's pipe
    model): 0.02-std init, mp-sharded rows; applies the Megatron-SP
    activation constraint when config.sequence_parallel."""

    def __init__(self, config: LlamaConfig):
        super().__init__(config.vocab_size, config.hidden_size)
        self.weight._data = I.Normal(0.0, 0.02)(
            (config.vocab_size, config.hidden_size), self.weight.dtype
        )
        self.weight.partition_spec = P("mp", None)
        self._sp = bool(config.sequence_parallel)

    def forward(self, input_ids):
        h = super().forward(input_ids)
        if self._sp:
            h = _seq_shard(h)
        return h


class LlamaForCausalLMPipe(PipelineModule):
    """Pipeline-parallel LLaMA (reference analogue: PaddleNLP
    LlamaForCausalLMPipe built from PipelineLayer LayerDescs, run by
    PipelineParallel / PipelineParallelWithInterleave).

    Assembled ONLY from the generic desc API (pp_layers.PipelineModule):
    embedding desc + N x LlamaDecoderLayer + RMSNorm + head. Tied
    embeddings (config.tie_word_embeddings) use SharedLayerDesc("embed"):
    ONE parameter, both gradient contributions summed by the module.

    schedule:
    - "fthenb" (default): differentiable GPipe (shard_map+ppermute engine,
      autodiff backward, embed/norm/head GSPMD);
    - "1f1b" / "vpp": the scheduled engine (pipeline_schedules) with
      hand-interleaved forward/backward per static tick tables (activation
      memory O(pp), not O(M)); "vpp" needs virtual_pp_degree >= 2."""

    SCHEDULES = ("fthenb", "1f1b", "vpp")

    def __init__(self, config: LlamaConfig, pp_degree=1, num_micro_batches=None,
                 schedule="fthenb", virtual_pp_degree=1):
        from ..distributed.fleet.pp_layers import LayerDesc, SharedLayerDesc

        if schedule not in self.SCHEDULES:
            raise ValueError(f"schedule must be one of {self.SCHEDULES}, got {schedule!r}")
        if config.num_experts > 1 and config.moe_aux_loss_weight:
            import warnings

            warnings.warn(
                "pipelined MoE trains the CE objective only: the gate "
                "load-balance aux loss is not threaded through the "
                "scheduled engine's hand-built loss yet (eager/GSPMD paths "
                "include it via make_loss_fn)", stacklevel=2)
        if schedule == "fthenb" and virtual_pp_degree > 1:
            raise ValueError("virtual_pp_degree > 1 needs schedule '1f1b' or 'vpp'")
        tied = config.tie_word_embeddings
        descs = [
            SharedLayerDesc("embed", LlamaEmbeddingPipe, config,
                            shared_weight_attr="weight")
            if tied else LayerDesc(LlamaEmbeddingPipe, config)
        ]
        descs += [LayerDesc(LlamaDecoderLayer, config)
                  for _ in range(config.num_hidden_layers)]
        descs += [LayerDesc(RMSNorm, config.hidden_size, epsilon=config.rms_norm_eps)]
        descs += [SharedLayerDesc("embed") if tied
                  else LayerDesc(_mk_linear, config.hidden_size, config.vocab_size,
                                 P(None, "mp"))]
        super().__init__(descs, pp_degree=pp_degree,
                         num_micro_batches=num_micro_batches,
                         schedule=schedule, virtual_pp_degree=virtual_pp_degree,
                         body=(1, 1 + config.num_hidden_layers))
        self.config = config

    @property
    def embed_tokens(self):
        return self._head_entries[0][1]

    @property
    def norm(self):
        return self._tail_entries[0][1]

    @property
    def lm_head(self):
        kind, obj, _ = self._tail_entries[1]
        return obj if kind == "layer" else None

    def forward(self, input_ids, labels=None, attention_mask=None, position_ids=None):
        return super().forward(input_ids, labels, attention_mask, position_ids)

    def load_from_causal_lm(self, src):
        """Copy weights from a same-config LlamaForCausalLM into the pipe
        (stacked [V, pp, Lc, ...] body layout via load_body_from)."""
        sd = {k: v for k, v in src.named_parameters()}
        self.embed_tokens.weight.set_value(sd["llama.embed_tokens.weight"])
        self.norm.weight.set_value(sd["llama.norm.weight"])
        if self.lm_head is not None:
            self.lm_head.weight.set_value(sd["lm_head.weight"])
        self.load_body_from(list(src.llama.layers))
        return self



class LlamaForCausalLM(GenerationMixin, Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = _mk_linear(config.hidden_size, config.vocab_size, P(None, "mp"))

    def _apply_moe_aux(self, loss):
        """Add the same-trace gate load-balance loss (reference: moe_layer
        l_aux consumed by the trainer) — the ONE implementation shared by
        the labeled forward and make_loss_fn."""
        aux = self.llama.moe_aux_loss()
        if aux is None or not self.config.moe_aux_loss_weight:
            return loss
        return loss + self.config.moe_aux_loss_weight * aux

    def make_loss_fn(self):
        """loss_fn for TrainStep/DistributedTrainStep (loss_fn(logits,
        labels)) that INCLUDES the MoE gate aux loss. The compiled step
        traces the model forward and this closure in one program, so
        reading moe_aux_loss() here sees the same-trace gate losses — the
        supported way to train a num_experts>1 model through the compiled
        paths (the bare criterion would silently drop the load-balance
        pressure and let routing collapse)."""
        crit = LlamaPretrainingCriterion(self.config)

        def loss_fn(logits, labels):
            return self._apply_moe_aux(crit(logits, labels))

        return loss_fn

    def forward(self, input_ids, attention_mask=None, position_ids=None, labels=None,
                past_key_values=None, cache_position=None, use_cache=False,
                segment_ids=None):
        if past_key_values is not None:
            if segment_ids is not None:
                raise ValueError("packed segment_ids do not compose with a "
                                 "decode cache — packing is a training path")
            h, presents = self.llama(
                input_ids, attention_mask, position_ids,
                past_key_values=past_key_values, cache_position=cache_position,
                use_cache=True,
            )
            if self.lm_head is not None:
                logits = self.lm_head(h)
            else:
                from ..tensor import linalg

                logits = linalg.matmul(h, self.llama.embed_tokens.weight, transpose_y=True)
            return logits, presents
        h = self.llama(input_ids, attention_mask, position_ids,
                       segment_ids=segment_ids)
        with_aux = self._apply_moe_aux
        if self.config.fuse_linear_cross_entropy and (labels is not None or self.training):
            # hand (hidden, lm weight) to the fused CE so [B,S,vocab] logits
            # are never materialized (incubate fused_linear_cross_entropy);
            # eval/generation calls (labels=None, not training) fall through
            # to the logits path below
            if self.lm_head is not None:
                w = self.lm_head.weight
            else:
                from ..tensor import linalg

                w = linalg.t(self.llama.embed_tokens.weight)
            if labels is not None:
                return with_aux(LlamaPretrainingCriterion(self.config)(h, w, labels))
            return h, w
        if self.lm_head is not None:
            logits = self.lm_head(h)
        else:
            from ..tensor import linalg

            logits = linalg.matmul(h, self.llama.embed_tokens.weight, transpose_y=True)
        if labels is not None:
            return with_aux(LlamaPretrainingCriterion(self.config)(logits, labels))
        return logits

    # ---- the serving engine's model protocol (inference/continuous.py):
    # the trunk that returns hidden states and its state-dict prefix, the
    # head as raw-array ops (the SAME ops forward() runs: F.linear /
    # matmul(transpose_y=True)), and the kind of pool the layers cache in
    def serving_trunk(self):
        return self.llama, "llama."

    def serving_head(self, h, state):
        if self.lm_head is None:
            return h @ jnp.swapaxes(state["llama.embed_tokens.weight"], -1, -2)
        return h @ state["lm_head.weight"]

    def serving_cache_spec(self):
        from ..ops.paged_attention import KVCacheSpec

        cfg = self.config
        return KVCacheSpec(cfg.num_hidden_layers, cfg.num_key_value_heads,
                           cfg.head_dim, cfg.num_attention_heads)

    def num_parameters(self):
        import numpy as np

        return int(sum(np.prod(p.shape) for p in self.parameters()))

    @staticmethod
    def flops_per_token(config, seq_len=None, causal=True):
        """Training matmul FLOPs per token: 6*N (GQA-aware) plus the
        attention quadratic term 12*L*h*s (halved when causal — that is
        what the flash/splash kernels actually compute)."""
        h = config.hidden_size
        kv_heads = getattr(config, "num_key_value_heads", None) or config.num_attention_heads
        head_dim = h // config.num_attention_heads
        kv_dim = kv_heads * head_dim
        n = (
            config.vocab_size * h * (1 if config.tie_word_embeddings else 2)
            + config.num_hidden_layers
            * (
                2 * h * h  # q + o projections
                + 2 * h * kv_dim  # k + v projections (GQA-reduced)
                + 3 * h * config.intermediate_size  # gate/up/down
            )
        )
        flops = 6 * n
        if seq_len is not None:
            attn = 12.0 * config.num_hidden_layers * h * seq_len
            flops += attn * (0.5 if causal else 1.0)
        return flops
