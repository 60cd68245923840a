"""Auto-parallel planner v1 (reference: auto_parallel/static/cost_model.py,
auto_parallel/static/cluster.py, auto_parallel/static/tuner/ — the
Completer/Partitioner cost search collapses on TPU to choosing the MESH
SHAPE; GSPMD handles per-op propagation once the mesh + param specs exist).

The planner enumerates factorizations n_devices = dp × mp × pp × sharding,
rejects shapes that do not fit HBM, and scores the rest with a per-step
communication-cost model (bytes moved over ICI):

- dp / sharding grad sync: ring all-reduce 2·P·(w-1)/w bytes (reduce-scatter
  + all-gather for sharding — same wire bytes, less memory);
- mp (Megatron TP): per layer, two activation all-reduces fwd + two bwd
  over B·S·H activations: 8·L·B·S·H·(mp-1)/mp bytes;
- pp: per boundary, micro-batched activation p2p: 2·B·S·H bytes, plus a
  bubble term charged as equivalent-bytes: bubble_frac · compute_bytes.

This is intentionally a closed-form v1 (the reference's tuner profiles
candidates; rungs of that ladder can replace the constants later).
"""
import dataclasses

import numpy as np

HBM_BYTES_DEFAULT = 16e9  # v5e
# resident optimizer bytes/param: AdamW f32 moments (8) + f32 master (4);
# grads are transient inside the donated jitted step
OPT_BYTES_PER_PARAM = 12.0
# With full recompute, the only per-layer residency is the checkpointed
# block input (one activation of B_micro·S·H at each layer boundary);
# the transient working set of the layer being recomputed is charged
# separately as RECOMPUTE_WORKING_LAYERS extra layer-activations.
RECOMPUTE_WORKING_LAYERS = 8.0
# Latency constants: a scheduled-pipeline tick is a lockstep ppermute
# (global sync + dispatch), a collective has a latency floor per hop.
TICK_LATENCY_S = 1e-5
COLL_LATENCY_S = 5e-6
# Cross-slice data-center network: ~25 GB/s per chip vs ~400 GB/s ICI —
# the reason ONLY the dcn_dp grad sync may cross slices (mesh.py).
DCN_BW_DEFAULT = 2.5e10
DCN_LATENCY_S = 5e-5

# Mutable cost-model constants, refittable from measured bench rungs
# (reference: auto_parallel/static/cluster.py reads measured cluster specs;
# here `calibrate_from_bench` fits them from bench.py result lines instead).
# compute_efficiency is the measured MFU of the best real-TPU training rung:
# the planner's compute term uses achievable FLOP/s, not datasheet peak, so
# the compute/communication tradeoff reflects this chip as measured.
CALIBRATION = {
    "peak_flops": 197e12,  # bf16 FLOP/s per chip (v5e datasheet)
    "ici_bw": 4e11,  # v5e aggregate per-chip ICI ≈ 400 GB/s
    "compute_efficiency": 1.0,
    "source": None,
}


def calibrate(records):
    """Fit CALIBRATION from bench result dicts (bench.py rung results or
    its final contract line). Uses the best real-TPU training
    rung's measured MFU as the achievable-compute efficiency. Returns the
    updated CALIBRATION, or None if no TPU evidence exists (constants kept)."""
    best = None
    for r in records:
        if not isinstance(r, dict):
            continue
        extra = r.get("extra") or {}
        mfu = extra.get("mfu")
        if extra.get("backend") == "tpu" and isinstance(mfu, (int, float)) and mfu > 0:
            if best is None or mfu > best[0]:
                best = (float(mfu), extra.get("config"))
    if best is None:
        return None
    CALIBRATION["compute_efficiency"] = best[0]
    CALIBRATION["source"] = best[1]
    return dict(CALIBRATION)


def calibrate_from_bench(path, save_path=None):
    """Load a bench artifact (JSONL of rung results, or one JSON document —
    possibly pretty-printed) and refit the cost-model constants. With
    `save_path`, persist the fitted constants as JSON so other processes can
    pick them up via `load_calibration` (or the PADDLE_TPU_CALIBRATION env
    var at import). Returns the updated CALIBRATION or None."""
    import json
    import os

    if not os.path.exists(path):
        return None
    with open(path) as f:
        text = f.read().strip()
    records = []
    try:
        # whole-file parse first: a single-document artifact may be pretty-printed
        whole = json.loads(text)
        records = whole if isinstance(whole, list) else [whole]
    except json.JSONDecodeError:
        for line in text.splitlines():
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    fitted = calibrate(records)
    if fitted is not None and save_path:
        with open(save_path, "w") as f:
            json.dump(fitted, f, indent=1)
    return fitted


def load_calibration(path):
    """Adopt previously fitted constants (calibrate_from_bench save_path).
    Returns the updated CALIBRATION, or None if the file is absent/invalid."""
    import json
    import os

    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            data = json.load(f)
    except (json.JSONDecodeError, OSError):
        return None
    for k in ("peak_flops", "ici_bw", "compute_efficiency"):
        if isinstance(data.get(k), (int, float)) and data[k] > 0:
            CALIBRATION[k] = float(data[k])
    CALIBRATION["source"] = data.get("source")
    return dict(CALIBRATION)


def _autoload_calibration():
    from ...utils.envs import env_str

    p = env_str("PADDLE_TPU_CALIBRATION")
    if p:
        load_calibration(p)


_autoload_calibration()


@dataclasses.dataclass
class Plan:
    dp: int
    mp: int
    pp: int
    sharding: int
    cost: float
    mem_per_device: float
    reason: str
    sharding_stage: int = 1  # 3 = params ZeRO-sharded too (needed to fit)
    # micro-batches per replica the memory model assumed (grad accumulation
    # keeps the live working set micro-batch-sized); the Engine must run
    # with at least this many accumulate steps or the act estimate is void
    accumulate_steps: int = 1
    dcn_dp: int = 1  # slice-crossing data-parallel ways (multi-slice)

    def mesh_shape(self):
        return dict(dp=self.dp, mp=self.mp, pp=self.pp, sharding=self.sharding,
                    dcn_dp=self.dcn_dp)


def _divisor_tuples(n):
    """All (dp, mp, pp, sharding) with product n."""
    outs = []
    for mp in _divisors(n):
        for pp in _divisors(n // mp):
            rem = n // (mp * pp)
            for sh in _divisors(rem):
                outs.append((rem // sh, mp, pp, sh))
    return outs


def _divisors(n):
    return [d for d in range(1, n + 1) if n % d == 0]


def plan_mesh(
    n_params,
    n_devices,
    seq_len=2048,
    batch_per_device=1,
    hidden_size=None,
    num_layers=None,
    hbm_bytes=HBM_BYTES_DEFAULT,
    max_mp=8,
    dtype_bytes=2,
    min_axes=None,
    n_slices=1,
    dcn_bw=DCN_BW_DEFAULT,
    vocab_size=None,
):
    """Pick (dp, mp, pp, sharding) for `n_params` on `n_devices` chips.

    Returns the lowest-communication Plan that fits memory; raises if none
    fits. hidden_size/num_layers refine the mp/pp activation terms when
    known (else estimated from n_params, LLaMA-ish shape assumptions).
    n_slices > 1 splits n_devices over that many TPU slices: the inner
    factorization stays within a slice (ICI) and an extra grad all-reduce
    over the dcn_dp axis is charged at DCN bandwidth.
    """
    cands = enumerate_plans(
        n_params, n_devices, seq_len=seq_len, batch_per_device=batch_per_device,
        hidden_size=hidden_size, num_layers=num_layers, hbm_bytes=hbm_bytes,
        max_mp=max_mp, dtype_bytes=dtype_bytes, min_axes=min_axes,
        n_slices=n_slices, dcn_bw=dcn_bw, vocab_size=vocab_size,
    )
    if not cands:
        raise ValueError(
            f"no mesh shape fits {n_params / 1e9:.2f}B params on {n_devices} devices "
            f"with {hbm_bytes / 1e9:.0f}GB HBM — add devices or enable offload"
        )
    return cands[0]


def enumerate_plans(
    n_params,
    n_devices,
    seq_len=2048,
    batch_per_device=1,
    hidden_size=None,
    num_layers=None,
    hbm_bytes=HBM_BYTES_DEFAULT,
    max_mp=8,
    dtype_bytes=2,
    min_axes=None,
    n_slices=1,
    dcn_bw=DCN_BW_DEFAULT,
    vocab_size=None,
):
    """All memory-feasible Plans, best modeled cost first (the candidate
    ladder the ProfilingTuner measures — reference: tuner/ enumerating
    Partitioner candidates before profiling)."""
    if n_slices > 1:
        if n_devices % n_slices:
            raise ValueError(f"{n_devices} devices not divisible by {n_slices} slices")
        n_devices = n_devices // n_slices
    if hidden_size is None:
        # n ≈ 12 L h² and L ≈ h/128 → h ≈ (128 n / 12)^(1/3)
        hidden_size = int((128 * n_params / 12) ** (1 / 3))
    if num_layers is None:
        num_layers = max(1, hidden_size // 128)

    mins = min_axes or {}
    candidates = []
    for dp, mp, pp, sh in _divisor_tuples(n_devices):
        if mp > max_mp:
            continue  # TP wants the high-bandwidth ICI neighborhood
        axes = dict(dp=dp, mp=mp, pp=pp, sharding=sh)
        if any(axes[a] < v for a, v in mins.items()):
            continue
        model_shard = mp * pp  # ways the params themselves are split
        state_shard = model_shard * sh  # optimizer state additionally ZeRO-sharded
        for zero3 in (False, True):
            if zero3 and sh == 1:
                continue
            param_bytes = n_params * dtype_bytes / (state_shard if zero3 else model_shard)
            opt_bytes = n_params * OPT_BYTES_PER_PARAM / state_shard
            # constant GLOBAL batch across candidates (fair cost comparison);
            # each dcn x dp x sharding replica sees B / (dcn*dp*sh) samples,
            # processed as micro-batches of batch_per_device (grad
            # accumulation keeps the live working set micro-batch-sized)
            B = batch_per_device * n_devices * n_slices
            replica_b = max(B // max(n_slices * dp * sh, 1), 1)
            micro_b = batch_per_device
            n_micro = max(replica_b // micro_b, 1)
            # full-recompute residency: one dtype-sized boundary activation
            # per local layer (split over mp inside the layer), plus the
            # transient working set of the one layer being recomputed.
            # A 1F1B stage keeps up to pp in-flight micro-batches resident
            # during the steady state, so the boundary term scales with
            # min(n_micro, pp).
            layers_local = max(-(-num_layers // pp), 1)  # ceil
            in_flight = min(n_micro, pp)
            act_bytes = (
                micro_b * seq_len * hidden_size * dtype_bytes
                * (in_flight * layers_local / max(mp, 1) + RECOMPUTE_WORKING_LAYERS)
            )
            mem = param_bytes + opt_bytes + act_bytes
            if mem > hbm_bytes * 0.92:
                continue

            # ---- per-step cost in SECONDS: comm bytes / ICI bandwidth,
            # bubble and per-tick latency charged against the step
            ICI_BW = CALIBRATION["ici_bw"]
            # achievable (not datasheet) FLOP/s: datasheet peak × measured MFU
            PEAK = CALIBRATION["peak_flops"] * CALIBRATION["compute_efficiency"]
            tokens = B * seq_len
            compute_s = 6.0 * n_params * tokens / (n_devices * n_slices * PEAK)
            P = n_params * dtype_bytes
            grad_sync_ways = dp * sh
            cost = 0.0
            if grad_sync_ways > 1:
                cost += 2.0 * P / model_shard * (grad_sync_ways - 1) / grad_sync_ways / ICI_BW
                cost += COLL_LATENCY_S * np.log2(grad_sync_ways)
            if n_slices > 1:
                # cross-slice grad all-reduce over the dcn_dp axis — the one
                # collective allowed to ride the DCN
                cost += (2.0 * P / model_shard * (n_slices - 1) / n_slices / dcn_bw
                         + DCN_LATENCY_S * np.log2(n_slices))
            if zero3:
                # per-step weight all-gather (XLA weight-update sharding)
                cost += P / model_shard * (sh - 1) / sh / ICI_BW
            if mp > 1:
                # 2 activation all-reduces fwd + 2 bwd per layer per
                # micro-batch (Megatron TP), bytes summed over the replica
                # batch, plus the per-collective latency floor
                cost += (
                    8.0 * num_layers / pp * replica_b * seq_len * hidden_size
                    * dtype_bytes * (mp - 1) / mp / ICI_BW
                )
                cost += 4.0 * num_layers / pp * n_micro * COLL_LATENCY_S
            if pp > 1:
                # micro-batched boundary p2p: every micro-batch crosses each
                # of the pp-1 boundaries forward and backward
                act = micro_b * seq_len * hidden_size * dtype_bytes
                cost += 2.0 * n_micro * act * (pp - 1) / ICI_BW
                # the scheduled engine runs in lockstep ticks (one global
                # ppermute sync each): 2·(M + pp − 1) ticks per step — this
                # fixed latency is what makes pipelining a loss for models
                # whose compute does not dwarf it
                ticks = 2.0 * (n_micro + pp - 1)
                cost += ticks * TICK_LATENCY_S
                # bubble as lost compute: (pp−1)/(M + pp − 1) of the step,
                # plus the tail-imbalance tax: the last stage's fused
                # B_LAST tick costs bwd+head while peers' steady tick costs
                # fwd+bwd; in forward-units (fwd=1, bwd=3) the lockstep
                # gate pays max(0, 3·head_ratio − 1)/4 of compute on steady
                # ticks (pipeline_schedules.Schedule.tick_flops model).
                # Falls back to 2%/stage when vocab (head size) is unknown.
                bubble = (pp - 1) / (n_micro + pp - 1.0)
                if vocab_size is not None and pp > 1:
                    layers_per_stage = max(num_layers / pp, 1e-9)
                    # FLOP units on both sides: fwd flops/token ≈ 2×params,
                    # per-layer params ≈ 12h² → 24h² flops/layer; head
                    # matmul = 2·h·vocab flops/token
                    stage_fwd = layers_per_stage * 24.0 * hidden_size * hidden_size
                    head_ratio = 2.0 * hidden_size * vocab_size / stage_fwd
                    imbalance_tax = max(0.0, (3.0 * head_ratio - 1.0) / 4.0)
                else:
                    imbalance_tax = 0.02 * (pp - 1)
                cost += (bubble + imbalance_tax) * compute_s
            candidates.append(
                Plan(dp, mp, pp, sh, cost, mem,
                     reason=f"mem {mem / 1e9:.1f}GB of {hbm_bytes / 1e9:.0f}GB, "
                            f"cost {cost * 1e3:.2f}ms/step" + (", zero3" if zero3 else ""),
                     sharding_stage=3 if zero3 else (2 if sh > 1 else 1),
                     # pp>1: the pipe engine micro-batches internally (the
                     # in_flight term models it); only plain-path plans ask
                     # the Engine for gradient accumulation
                     accumulate_steps=1 if pp > 1 else n_micro,
                     dcn_dp=n_slices)
            )
    candidates.sort(key=lambda c: (c.cost, c.mp * c.pp))
    return candidates


def plan_for_model(model, n_devices=None, seq_len=None, batch_per_device=1, **kw):
    """Plan from a live model: reads num_parameters()/config when present."""
    import jax

    n_devices = n_devices if n_devices is not None else len(jax.devices())
    if hasattr(model, "num_parameters"):
        n_params = model.num_parameters()
    else:
        n_params = int(sum(np.prod(p.shape) for p in model.parameters()))
    cfg = getattr(model, "config", None)
    hid = getattr(cfg, "hidden_size", None)
    layers = getattr(cfg, "num_hidden_layers", None)
    seq = seq_len or getattr(cfg, "seq_length", 2048)
    kw.setdefault("vocab_size", getattr(cfg, "vocab_size", None))
    return plan_mesh(n_params, n_devices, seq_len=seq, batch_per_device=batch_per_device,
                     hidden_size=hid, num_layers=layers, **kw)


def build_planned_mesh(plan, devices=None):
    """Materialize the plan as the global Mesh (mp fastest-varying for ICI
    locality — mesh.build_mesh axis order)."""
    from ..mesh import build_mesh, set_mesh

    mesh = build_mesh(dp=plan.dp, mp=plan.mp, pp=plan.pp, sharding=plan.sharding,
                      dcn_dp=plan.dcn_dp, devices=devices)
    set_mesh(mesh)
    return mesh
