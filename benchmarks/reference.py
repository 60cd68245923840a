"""The plain reference each cell's `correct` is decided against: the model's
ordinary full forward — no KV cache, XLA attention, unfused cross-entropy.
Copied from chip_smoke.py (`_reference_logits`, `_check_against_reference`,
41cde00) so that the program may change and the yardstick may not."""
import contextlib

import numpy as np

# A served token may differ from the reference argmax only where the
# reference itself is this close to a tie, counted in bf16 steps at the top
# logit's magnitude. The served path re-reads KV from bf16 pages and sums
# its blockwise softmax in another order, and the layers' bf16 rounding
# carries that to the logits; random weights give near-flat logits, so ties
# this close do occur. The chip showed flips up to 3 steps (PR 22, 4 of 64
# tokens); a wrong mask or page moves logits by whole units.
ARGMAX_GAP_BF16_STEPS = 8
# at most this share of checked tokens may use that allowance at all
MAX_NEAR_TIE_SHARE = 0.25
# |step-0 training loss - reference loss|. Both run the same bf16 weights;
# the train step's splash kernel and fused chunked CE sum in another order
# than XLA attention and a whole-logits logsumexp. At random init the loss
# is ~ln(vocab)+0.8 and a per-token loss has a spread of ~1.3, so two
# UNRELATED forwards over 8,192 tokens differ by ~0.014 (1.3/sqrt(8192)):
# the tolerance sits well under that, and well over bf16 summation noise
# (measured on the chip: see PERF.md, PR 24).
TRAIN_LOSS_TOL = 5e-3


class Wrong(Exception):
    """The program's output is not what the reference says it should be."""


@contextlib.contextmanager
def _xla_attention():
    from paddle_tpu.ops import flash_attention as fa

    fa.force_xla(True)
    try:
        yield
    finally:
        fa.force_xla(False)


def _forward_fn(model):
    from paddle_tpu.framework.core import Tensor

    def fwd(state, ids):
        out = model.functional_call(
            {k: Tensor(v, stop_gradient=True) for k, v in state.items()},
            Tensor(ids), training=False)
        return out._data

    return fwd


def reference_logits(model, rows):
    """f32 logits of right-padded rows (causal: padding cannot reach
    earlier positions); width padded to a multiple of 128."""
    import paddle_tpu as paddle
    from paddle_tpu.observability import compilemem

    width = -(-max(len(r) for r in rows) // 128) * 128
    ids = np.zeros((len(rows), width), np.int32)
    for i, r in enumerate(rows):
        ids[i, :len(r)] = r
    with _xla_attention():
        logits = compilemem.ledgered_jit(
            _forward_fn(model), key="bench.reference")(
                model.raw_state_dict(), paddle.to_tensor(ids)._data)
        return np.asarray(logits.astype("float32"))


def check_served(model, prompts, outs):
    """For each (prompt, served row): the reference argmax must be the
    served token at every generated position, a mismatch being allowed only
    within ARGMAX_GAP_BF16_STEPS of the reference's own top logit."""
    logits = reference_logits(model, outs)
    checked = near_tie = 0
    worst = 0.0  # in bf16 steps
    for row, (prompt, out) in enumerate(zip(prompts, outs)):
        for pos in range(len(prompt), len(out)):
            ref = logits[row, pos - 1]
            served = int(out[pos])
            checked += 1
            if int(ref.argmax()) == served:
                continue
            top = float(ref.max())
            step = 2.0 ** (np.floor(np.log2(max(abs(top), 1e-30))) - 7)
            gap = (top - float(ref[served])) / step
            worst = max(worst, gap)
            near_tie += 1
            if gap > ARGMAX_GAP_BF16_STEPS:
                raise Wrong(
                    f"row {row} position {pos}: served token {served} is "
                    f"{gap:.1f} bf16 steps below the reference argmax "
                    f"{int(ref.argmax())} (tolerance {ARGMAX_GAP_BF16_STEPS})")
    if near_tie > MAX_NEAR_TIE_SHARE * checked:
        raise Wrong(f"{near_tie}/{checked} served tokens needed the near-tie "
                    f"allowance (limit {MAX_NEAR_TIE_SHARE:.0%})")
    return {"checked": checked, "exact": checked - near_tie,
            "near_tie": near_tie, "worst_gap_bf16_steps": worst}


def reference_loss(model, ids):
    """Mean next-token cross-entropy of int32 [batch, seq+1] `ids` under
    the reference forward, one sequence at a time (XLA attention holds a
    whole [heads, seq, seq] score matrix), logsumexp in f32."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.observability import compilemem

    fwd = _forward_fn(model)

    def seq_loss(state, x, y):
        logits = fwd(state, x).astype(jnp.float32)[0]
        picked = jnp.take_along_axis(logits, y[0][:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    with _xla_attention():
        f = compilemem.ledgered_jit(seq_loss, key="bench.reference_loss")
        state = model.raw_state_dict()
        total = sum(float(f(state, jnp.asarray(row[None, :-1]),
                            jnp.asarray(row[None, 1:]))) for row in ids)
    return total / (ids.shape[0] * (ids.shape[1] - 1))
