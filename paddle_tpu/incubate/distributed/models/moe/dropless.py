"""A dropless expert layer that is told which experts it holds (reference:
DeepSeek-V3 / Kimi-K2 `MoE` with the `noaux_tc` gate; MegaBlocks'
dropless grouped matmul).

Beside `MoELayer` (dense `[T, E, C]` dispatch tensors, a static capacity,
overflow tokens dropped), which stays as it is. Here nothing has a capacity:

- the router scores ALL `num_experts` experts (sigmoid or softmax), picks
  `top_k` by score + `e_score_correction_bias`, weighs by the score WITHOUT
  the bias, normalises over all `top_k` chosen (`norm_topk_prob`) and scales
  by `routed_scaling_factor` — whatever this layer holds;
- the layer holds experts `[first, first + n_held)` (an expert-parallel
  share; the whole bank when `n_held == num_experts`) and computes

      y = sum_{k: first <= e_k < first + n_held} w_k E_{e_k}(x)  +  S(x)

  What the absent experts would add is the other shares' to compute; on one
  chip the layer runs without its exchange and nothing stands in for it.
  Summed over all shares, with the shared expert `S` counted once, the parts
  are the whole layer (tests/test_deepseek_v3.py).
- the held assignments are sorted by expert and go through ONE grouped
  matmul a projection. Shapes are static at the worst case `T * top_k` rows
  (a token's `top_k` experts are distinct, so no tighter bound holds without
  dropping); rows past the held assignments are not computed by the kernel
  tier and are masked out of the sum on every tier.

Tiers (`LAST_IMPL`, chosen at trace time; a tier that cannot run raises):
- `gmm-kernel`: `jax.experimental.pallas.ops.tpu.megablox.gmm` on TPU, which
  visits only the row tiles that hold assignments;
- `ragged-dot`: `jax.lax.ragged_dot` elsewhere.
"""
import jax
import jax.numpy as jnp

from .....framework.core import Tensor
from .....nn import initializer as I
from .....nn.layer.layers import Layer

LAST_IMPL = None  # "gmm-kernel" | "ragged-dot"

#: megablox tiles (rows, contraction, columns): 128 rows is a decode step's
#: whole sorted batch; 1024 x 1024 weight tiles divide the published widths
GMM_TILING = (128, 1024, 1024)


def route(x, gate_w, bias, top_k, scoring="sigmoid", norm_topk_prob=True,
          scaling=1.0):
    """(expert ids [T, top_k] int32, weights [T, top_k] f32) of tokens x
    [T, h] under gate_w [E, h] and the selection bias [E] (or None). The
    scores are computed in f32 at the highest matmul precision: a choice
    between two experts is a comparison of two sums over h."""
    logits = jnp.einsum("th,eh->te", x.astype(jnp.float32),
                        gate_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown scoring_func {scoring!r}")
    choice = s if bias is None else s + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk_prob:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scaling


def _grouped(lhs, rhs, sizes, kernel):
    if kernel:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        tm, tk, tn = GMM_TILING
        tiling = (min(tm, lhs.shape[0]), min(tk, lhs.shape[1]),
                  min(tn, rhs.shape[2]))
        return gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype,
                   tiling=tiling)
    return jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=lhs.dtype)


def held_experts(x, idx, w, gate_proj, up_proj, down_proj, first,
                 token_mask=None):
    """The held experts' part of the routed sum, and its counters.

    x [T, h]; idx, w [T, K] from `route`; gate_proj / up_proj [n, h, m] and
    down_proj [n, m, h]: the SwiGLU experts `first .. first + n`;
    token_mask [T] bool: the tokens a request holds (None: all). A masked
    token (a pad of a packed stream, a dead row of a fixed batch) keeps no
    assignment: it sorts with the absent ones, gets zeros and is not
    counted. Returns (y [T, h], counters int32 [3]: held experts with at
    least one token, assignments that fell on held experts, the largest
    held expert's load)."""
    global LAST_IMPL
    from .....ops.flash_attention import _FORCE_XLA, _on_tpu

    kernel = _on_tpu() and not _FORCE_XLA
    LAST_IMPL = "gmm-kernel" if kernel else "ragged-dot"
    T, K = idx.shape
    n = gate_proj.shape[0]
    local = idx - first
    held = (local >= 0) & (local < n)
    if token_mask is not None:
        held &= token_mask[:, None]
    key = jnp.where(held, local, n).reshape(-1)        # absent ones sort last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((n + 1,), jnp.int32).at[key].add(1)[:n]
    rows = T * K
    pad = -rows % GMM_TILING[0] if kernel else 0
    xs = jnp.pad(x[order // K], ((0, pad), (0, 0)))    # [rows, h], by expert
    a = (jax.nn.silu(_grouped(xs, gate_proj, sizes, kernel))
         * _grouped(xs, up_proj, sizes, kernel))
    ys = _grouped(a, down_proj, sizes, kernel)[:rows]
    back = jnp.zeros((rows,), jnp.int32).at[order].set(jnp.arange(rows))
    y_k = ys[back].reshape(T, K, -1).astype(jnp.float32)
    y = jnp.where(held[..., None], w[..., None] * y_k, 0.0).sum(axis=1)
    counters = jnp.stack([(sizes > 0).sum(), sizes.sum(), sizes.max()])
    return y.astype(x.dtype), counters.astype(jnp.int32)


class _Params(Layer):
    """Named parameters (or sub-holders of them), so that the state dict
    reads as the checkpoints do."""

    def __init__(self, **members):
        super().__init__()
        for name, member in members.items():
            setattr(self, name, member)


class DroplessMoE(Layer):
    """Router + the held routed experts + the shared expert(s); parameter
    names follow the published checkpoints (`gate.weight`,
    `gate.e_score_correction_bias`, `shared_experts.*`), the held experts
    stacked `[n_held, ...]` under `experts.*`. Inference-only (no tape).

    After a forward `last_counters` holds that call's `held_experts`
    counters, valid inside the same trace (the side-channel contract of
    `MoELayer.l_aux`)."""

    def __init__(self, hidden_size, moe_intermediate_size, num_experts,
                 top_k, n_shared_experts=1, first_expert=0, n_held=None,
                 scoring="sigmoid", norm_topk_prob=True, scaling=1.0,
                 dtype="float32", std=0.02):
        super().__init__()
        n_held = num_experts if n_held is None else n_held
        if not 0 <= first_expert <= first_expert + n_held <= num_experts:
            raise ValueError(
                f"held experts [{first_expert}, {first_expert + n_held}) are "
                f"not a share of {num_experts}")
        self.num_experts, self.top_k = num_experts, top_k
        self.first_expert, self.n_held = first_expert, n_held
        self.scoring, self.norm_topk_prob = scoring, norm_topk_prob
        self.scaling = scaling
        h, m = hidden_size, moe_intermediate_size

        def param(*shape, init=I.Normal(0.0, std), dt=dtype):
            return self.create_parameter(list(shape), dtype=dt,
                                         default_initializer=init)

        ms = m * n_shared_experts
        self.gate = _Params(
            weight=param(num_experts, h),
            e_score_correction_bias=param(num_experts, init=I.Constant(0.0),
                                          dt="float32"))
        self.experts = _Params(gate_proj=param(n_held, h, m),
                               up_proj=param(n_held, h, m),
                               down_proj=param(n_held, m, h))
        self.shared_experts = _Params(
            gate_proj=_Params(weight=param(h, ms)),
            up_proj=_Params(weight=param(h, ms)),
            down_proj=_Params(weight=param(ms, h)))
        self.last_counters = None

    def forward(self, x, token_mask=None):
        """x [..., h]; token_mask (x's leading shape, bool): the tokens a
        request holds, see `held_experts`."""
        xd = x._data
        flat = xd.reshape(-1, xd.shape[-1])
        with jax.named_scope("moe.route"):
            idx, w = route(flat, self.gate.weight._data,
                           self.gate.e_score_correction_bias._data,
                           self.top_k, self.scoring, self.norm_topk_prob,
                           self.scaling)
        e = self.experts
        with jax.named_scope("moe.experts"):
            y, self.last_counters = held_experts(
                flat, idx, w, e.gate_proj._data, e.up_proj._data,
                e.down_proj._data, self.first_expert,
                None if token_mask is None else token_mask.reshape(-1))
        s = self.shared_experts
        with jax.named_scope("moe.shared"):
            y = y + (jax.nn.silu(flat @ s.gate_proj.weight._data)
                     * (flat @ s.up_proj.weight._data)
                     ) @ s.down_proj.weight._data
        return Tensor(y.reshape(xd.shape), stop_gradient=True)
