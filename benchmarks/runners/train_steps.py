"""Runner `train_steps`: whole optimizer steps of the program's jitted train
step (TrainStep, or DistributedTrainStep on the cell's mesh) until the
window is up. Returns raw per-step records and the facts `correct` needs;
never a metric. The model, its loss and the reference are the builder's
and the reference module's that the cell's configuration file names
(`ctx.builder`, `ctx.reference`); the kernel tiers `correct` demands are the
workload file's (`ctx.tiers()`). Construction copied from chip_smoke.py
(`_make_train_step`, `_train_losses`, `four_chip_phase`, 41cde00)."""
import contextlib
import time

import numpy as np

from benchmarks import traffic
from benchmarks.profiler import annotate

WARM_STEPS = 3  # compile, DistributedTrainStep's known second compile, one steady


def run(ctx):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.observability import compilemem

    cfg, tp, knobs = ctx.cfg, ctx.traffic, ctx.cell["step"]
    mesh_spec = ctx.cell.get("mesh")
    replicas = 1
    guard = contextlib.nullcontext()
    if mesh_spec:
        from paddle_tpu.distributed import mesh as mesh_mod

        replicas = mesh_spec.get("sharding", 1) * mesh_spec.get("dp", 1)
        guard = mesh_mod.mesh_guard(mesh_mod.build_mesh(
            devices=jax.devices()[:ctx.chips], **mesh_spec))
    batches = traffic.train_batches(tp, ctx.seed, cfg["vocab_size"], replicas)
    n_tok = batches[0].shape[0] * tp["seq"]

    def feed(i):
        b = batches[i % len(batches)]
        return paddle.to_tensor(b[:, :-1]), paddle.to_tensor(b[:, 1:])

    checks, steps = {}, []
    with guard:
        model = ctx.builder.build(cfg, ctx.seed, train=True, max_len=tp["seq"],
                                  rehearse=ctx.rehearse,
                                  recompute=knobs.get("recompute", False))
        ref_loss = ctx.reference.reference_loss(model, batches[0])
        opt = optimizer.AdamW(learning_rate=knobs["lr"],
                              parameters=model.parameters(),
                              weight_decay=knobs["weight_decay"])

        criterion = ctx.builder.criterion()

        def loss_fn(*a):
            return criterion(*a)

        if mesh_spec:
            from paddle_tpu.distributed.train_step import DistributedTrainStep

            step = DistributedTrainStep(
                model, loss_fn, opt,
                sharding_stage=knobs.get("sharding_stage", 2))
        else:
            from paddle_tpu.jit_api import TrainStep

            step = TrainStep(model, loss_fn, opt)

        losses = []
        for i in range(WARM_STEPS):
            loss = step(*feed(i))
            loss._data.block_until_ready()
            losses.append(loss)
        compiles_before = compilemem.ledger.counts()["events"]

        t0 = ctx.mark_window_start()
        i = WARM_STEPS
        while True:
            ctx.tracer.tick(time.monotonic() - t0)
            with annotate("bench.feed"):
                x, y = feed(i)
            t_fed = time.monotonic() - t0
            with annotate("bench.step"):
                loss = step(x, y)
                t_dispatched = time.monotonic() - t0
                loss._data.block_until_ready()
            t = time.monotonic() - t0
            steps.append({"t_fed": t_fed, "t_dispatched": t_dispatched,
                          "t_done": t, "tokens": n_tok})
            losses.append(loss)
            i += 1
            if t >= ctx.seconds:
                break
        ctx.tracer.stop()
        checks["compiles_in_window"] = (
            compilemem.ledger.counts()["events"] - compiles_before)
        if mesh_spec:
            checks["devices_with_shards"] = len(
                {s.device for p in model.parameters()
                 for s in p._data.addressable_shards})
            text = compilemem.memory.compiled("train.step").as_text()
            checks["collectives"] = sorted(
                op for op in ("all-reduce", "all-gather", "reduce-scatter",
                              "all-to-all", "collective-permute")
                if op in text)

    losses = [float(l.numpy()) for l in losses]
    # where the slowest step's time went: feeding, the host side of the
    # dispatch, or waiting for the device
    ends = [0.0] + [s["t_done"] for s in steps]
    slow = max(range(len(steps)), key=lambda j: ends[j + 1] - ends[j])
    checks["slowest_step_s"] = {
        "feed": steps[slow]["t_fed"] - ends[slow],
        "dispatch": steps[slow]["t_dispatched"] - steps[slow]["t_fed"],
        "device_wait": steps[slow]["t_done"] - steps[slow]["t_dispatched"],
        "median_step": float(np.median(np.diff(ends)))}
    tiers, problems = ctx.tiers()
    checks.update(losses_first=losses[0], losses_last5=losses[-5:],
                  reference_loss=ref_loss, **tiers,
                  params=model.num_parameters())
    if not np.all(np.isfinite(losses)):
        problems.append(f"non-finite loss: {losses}")
    if not np.mean(losses[-5:]) < losses[0]:
        problems.append(f"loss did not fall: {losses[0]} -> {losses[-5:]}")
    if abs(losses[0] - ref_loss) > ctx.reference.TRAIN_LOSS_TOL:
        problems.append(f"step-0 loss {losses[0]} vs reference {ref_loss} "
                        f"(tolerance {ctx.reference.TRAIN_LOSS_TOL})")
    if checks["compiles_in_window"]:
        problems.append(f"{checks['compiles_in_window']} compile(s) inside "
                        f"the window: "
                        f"{compilemem.ledger.report(recent=4)['recent']}")
    if mesh_spec:
        if checks["devices_with_shards"] != ctx.chips:
            problems.append(f"parameters live on "
                            f"{checks['devices_with_shards']} devices")
        if not checks["collectives"]:
            problems.append("the sharded step compiled without collectives")
    return {"kind": "train", "steps": steps, "checks": checks,
            "problems": problems, "attempted": len(steps), "failed": 0,
            "window_s": steps[-1]["t_done"],
            "shape": {"batch": batches[0].shape[0], "seq": tp["seq"]}}
