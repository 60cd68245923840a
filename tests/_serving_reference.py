"""The plain reference the serving tests hold an engine to: the model's own
no-cache forward over the whole row, one token at a time, in float32.
Greedy takes the argmax; a sampled stream applies the engine's row sampler
under the documented key ``fold_in(fold_in(PRNGKey(seed), rid), i)``
(``ContinuousBatchingEngine.serve``). No pool, no pages, no schedule."""
import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.framework.core import Tensor
from paddle_tpu.inference.continuous import _row_sampler, canonical_sampling

_FORWARDS = {}  # (id(model), width) -> jitted full forward


def _last_logits(model, state, row):
    """f32 logits after ``row``; right-padded to a multiple of 64 (causal:
    padding cannot reach an earlier position), so few widths compile."""
    width = -(-len(row) // 64) * 64
    fwd = _FORWARDS.get((id(model), width))
    if fwd is None:
        fwd = _FORWARDS[(id(model), width)] = jax.jit(
            lambda state, ids: model.functional_call(
                {k: Tensor(v, stop_gradient=True) for k, v in state.items()},
                Tensor(ids), training=False)._data)
    ids = np.zeros((1, width), np.int32)
    ids[0, :len(row)] = row
    logits = fwd(state, jnp.asarray(ids))
    return logits[:, len(row) - 1].astype(jnp.float32)


def reference_stream(model, prompt, max_new_tokens, rid=0, eos_token_id=None,
                     do_sample=False, temperature=1.0, top_k=0, top_p=1.0,
                     seed=0):
    """prompt + generated tokens, as ``serve()`` returns request ``rid``."""
    sampler = _row_sampler(
        *canonical_sampling(do_sample, temperature, top_k, top_p))
    base = jax.random.fold_in(jax.random.PRNGKey(seed), rid)
    state = model.raw_state_dict()
    row = [int(t) for t in prompt]
    for i in range(int(max_new_tokens)):
        key = jax.random.fold_in(base, i)[None]
        row.append(int(sampler(_last_logits(model, state, row), key)[0]))
        if row[-1] == eos_token_id:
            break
    return np.asarray(row, np.int32)


def reference_streams(model, prompts, max_new_tokens, **kw):
    """``reference_stream`` of every request of a ``serve()`` batch."""
    new = (max_new_tokens if isinstance(max_new_tokens, (list, tuple))
           else [max_new_tokens] * len(prompts))
    return [reference_stream(model, p, n, rid=rid, **kw)
            for rid, (p, n) in enumerate(zip(prompts, new))]
