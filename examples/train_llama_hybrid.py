"""Hybrid-parallel LLaMA pretraining (the north-star shape, scaled tiny).

Runs anywhere: on a real TPU slice the mesh maps onto ICI; on CPU it runs on
a virtual 8-device mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_llama_hybrid.py

Demonstrates: mesh construction (pp x mp x sharding), the scheduled 1F1B
pipeline engine behind the LayerDesc API, ZeRO-2 optimizer-state sharding,
and the fully-compiled hybrid train step.
"""
import numpy as np

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import logging

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import optimizer
from paddle_tpu.distributed import mesh as M
from paddle_tpu.distributed.train_step import DistributedTrainStep
from paddle_tpu.models.llama import LlamaForCausalLMPipe, llama_tiny
from paddle_tpu.utils.metrics_bus import StepMetricsBus, stdout_logger


def main():
    import jax

    n = len(jax.devices())
    pp = 2 if n % 2 == 0 else 1
    mp = 2 if (n // pp) % 2 == 0 else 1
    sharding = n // (pp * mp)
    print(f"devices={n} -> pp={pp} mp={mp} sharding={sharding}")

    # telemetry on: per-phase spans, goodput split, and the metrics bus
    # (tokens/sec + MFU) — the observable-by-default flagship (ISSUE 2)
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    obs.enable()

    paddle.seed(0)
    cfg = llama_tiny(num_hidden_layers=2 * pp, sequence_parallel=mp > 1)
    mesh = M.build_mesh(pp=pp, mp=mp, sharding=sharding)
    with M.mesh_guard(mesh):
        model = LlamaForCausalLMPipe(cfg, pp_degree=pp, num_micro_batches=max(pp, 2),
                                     schedule="1f1b" if pp > 1 else "fthenb")
        opt = optimizer.AdamW(learning_rate=3e-4, parameters=model.parameters(),
                              weight_decay=0.01)
        n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
        # MFU = achieved / peak FLOPs: ~6*params FLOPs per trained token;
        # peak comes from the accelerator (env override for real slices,
        # e.g. PADDLE_PEAK_FLOPS=1.97e14 for a v5p chip). On CPU the
        # default keeps the field present without pretending it means much.
        peak_flops = float(os.environ.get("PADDLE_PEAK_FLOPS", "0")) or 1e12
        bus = StepMetricsBus(flops_per_token=6 * n_params, peak_flops=peak_flops,
                             log_every=3, skip_first=1)
        bus.subscribe(stdout_logger())
        step = DistributedTrainStep(model, lambda loss: loss, opt, n_labels=0,
                                    sharding_stage=2, metrics_bus=bus)
        rng = np.random.RandomState(0)
        bs = max(4, 2 * sharding * max(pp, 2))
        for i in range(10):
            ids = rng.randint(0, cfg.vocab_size, (bs, 33)).astype(np.int32)
            loss = step(paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:]))
            print(f"step {i}: loss {float(loss.numpy()):.4f}")

    summary = bus.summary()
    print(f"summary: {summary}")
    gp = obs.goodput.report()
    print("goodput: {:.1%} of wall clock in steps "
          "(init/compile {:.1%}, untracked {:.1%})".format(
              gp["goodput_fraction"],
              gp["fractions"].get("init", 0.0),
              gp["untracked_s"] / gp["wall_s"] if gp["wall_s"] else 0.0))
    print("per-phase step breakdown (host spans, mean):")
    for name in obs.registry.names("span.train."):
        h = obs.registry.get(name)
        if h.count:
            print(f"  {name}: {h.mean * 1000:.2f} ms x {h.count}")


if __name__ == "__main__":
    main()
