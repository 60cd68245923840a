"""One-off: XLA CPU temp-memory accounting of the fused-CE chunk loop vs the
barrier-chained unroll (FLAGS_fused_ce_unroll). Motivates why the unroll is
OPT-IN: on CPU the opt-barrier chain is stripped during XLA optimization, so
the unrolled chunks overlap and temp grows well past the loop's bound (and
past the full-logits buffer fused-CE exists to avoid). On TPU opt-barrier is
honored, so the chain should hold the one-chunk bound — that needs a chip
run, not this script.

Recorded result (8192×256×32000, chunk 2048 → 4 chunks, bf16 inputs):
  loop (unroll=0):      568 MB temp, 1 pre-opt barrier (remat's own)
  unrolled (unroll=4): 1350 MB temp, 12 pre-opt barriers, 0 post-opt —
                       present in StableHLO, stripped by CPU optimization
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import jax.numpy as jnp

from paddle_tpu.incubate.nn import functional as inf

N, H, V, CHUNK = 8192, 256, 32000, 2048
h = jnp.zeros((N, H), jnp.bfloat16)
w = jnp.zeros((H, V), jnp.bfloat16)
y = jnp.zeros((N,), jnp.int32)

logits_bytes = N * V * 4
for unroll in [0, 4]:
    os.environ["FLAGS_fused_ce_unroll"] = str(unroll)

    def fused(h, w, y):
        out = inf.fused_linear_cross_entropy(h, w, y, chunk_size=CHUNK)
        return (out._data if hasattr(out, "_data") else out).mean()

    g = jax.grad(fused, argnums=(0, 1))
    low = jax.jit(g).lower(h, w, y)
    comp = low.compile()
    tb = comp.memory_analysis().temp_size_in_bytes
    n_bar_pre = low.as_text().count("optimization_barrier")
    n_bar_post = comp.as_text().count("opt-barrier")
    print(
        f"unroll={unroll}: temp={tb/1e6:.1f}MB ratio_vs_logits={tb/logits_bytes:.3f} "
        f"barriers pre-opt={n_bar_pre} post-opt={n_bar_post}",
        flush=True,
    )
