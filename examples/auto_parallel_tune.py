"""Semi-auto parallel: planner + profiling tuner + Engine.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/auto_parallel_tune.py

Demonstrates: enumerate_plans (closed-form cost model), ProfilingTuner
measuring the top candidates with the real compiled step, and Engine.fit
consuming the measured winner via Strategy.tuning.
"""
import numpy as np

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.distributed import mesh as M
from paddle_tpu.distributed.auto_parallel.engine import Engine, Strategy
from paddle_tpu.distributed.auto_parallel.planner import enumerate_plans
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
import paddle_tpu.nn.functional as F


def loss_fn(out, labels):
    return F.cross_entropy(
        out.reshape([-1, out.shape[-1]]), labels.reshape([-1]).unsqueeze(-1)
    ).mean()


def main():
    import jax

    n = len(jax.devices())
    print("modeled candidates for a 1B-param model on", n, "devices:")
    for p in enumerate_plans(1e9, n, hidden_size=2048, num_layers=16)[:5]:
        print(f"  dp{p.dp}-mp{p.mp}-pp{p.pp}-sh{p.sharding}: {p.reason}")

    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny(num_hidden_layers=2, hidden_dropout_prob=0.0,
                                    attention_probs_dropout_prob=0.0))
    st = Strategy()
    st.tuning.enable = True
    st.tuning.top_k = 3
    st.tuning.steps = 2
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    eng = Engine(model=model, loss=loss_fn, optimizer=opt, strategy=st)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, 128, (8, 17)).astype(np.int32)
    ds = [(ids[i, :-1], ids[i, 1:]) for i in range(8)]
    M.reset_mesh()
    hist = eng.fit(ds, batch_size=8, epochs=2, verbose=0)
    print("tuner trials:", eng._tuning_result.summary())
    b = eng._plan
    print(f"measured winner: dp{b.dp}-mp{b.mp}-pp{b.pp}-sh{b.sharding}")
    print(f"losses: first {hist['loss'][0]:.4f} last {hist['loss'][-1]:.4f}")


if __name__ == "__main__":
    main()
