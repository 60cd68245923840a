"""Benchmark driver contract: ONE JSON line
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

Measures training tokens/sec/chip on a LLaMA-2-shaped proxy sized for one
chip's HBM, and reports MFU against the BASELINE north star (45% MFU —
BASELINE.md). MFU accounting includes the causal-attention quadratic term:
flops/token = 6*N_params + 12*L*h*s*0.5 (fwd+bwd, causal halves the matrix).

Needs a TPU: a measurement path that finds no chip fails (exit code
NO_TPU_RC), it never substitutes a CPU run or an earlier result. Each
ladder rung runs in a child process with a wall-clock budget and one
process holds the chip at a time: this parent never touches JAX. The ladder
runs SMALLEST PROGRAM FIRST and appends every completed rung to
BENCH_rungs.jsonl as it completes; the final JSON line is the best training
rung of THIS run, with the kernel/serving rungs attached under extra.
"""
import json
import os
import subprocess
import sys
import time

NO_TPU_RC = 3  # a rung child found no TPU: the whole bench exits with it
RUNG_TIMEOUT_S = [600, 420, 420, 420, 360, 300, 600, 600, 600, 600, 600]  # per-rung wall clock (compile+run)
GQA_RUNG_TIMEOUT_S = 420

# GQA rung (kv_heads < heads): exercises the splash kernel on record —
# run additionally after the primary rung, result attached as extra.gqa.
# b8/recompute=full: the config measured to fit one v5e chip's HBM with
# AdamW f32 state (b4/dots RESOURCE_EXHAUSTEDs);
# matches big_b8_full for a direct GQA-vs-MHA comparison.
GQA_RUNG = dict(hidden=2048, layers=12, heads=16, kv_heads=4, inter=5504,
                seq=2048, batch=8, recompute="full")
# MoE rung: Mixtral-class 8-expert top-2 at a size whose expert banks +
# AdamW f32 state fit one chip — the only rung exercising the gated
# expert-dispatch compute path (capacity dispatch + SwiGLU expert bank
# einsums) on hardware. MFU uses the dense-equivalent 6N accounting, so it
# understates achieved utilization by ~the (1 - top_k/num_experts) unused-
# expert fraction; tokens/s is the honest headline for this rung.
MOE_RUNG = dict(hidden=1024, layers=8, heads=16, inter=2816, seq=1024,
                batch=8, recompute="none", num_experts=8)
# Frontier GQA rung: same knobs as the b6-none headline rung so splash-vs-
# pallas MFU is apples-to-apples (the rfull GQA rung exists for the direct
# big_b8_full comparison; its 29.9% vs 62.0% gap is mostly the recompute +
# batch config, not the kernel)
GQA_FRONTIER_RUNG = dict(hidden=2048, layers=12, heads=16, kv_heads=4,
                         inter=5504, seq=2048, batch=6, recompute="none")
DECODE_RUNG_TIMEOUT_S = 420

LADDER = [
    # Preference-ordered: the first rung that fits the chip is reported.
    # recompute="dots" saves matmul outputs and recomputes elementwise only
    # (≈0 extra FLOPs); "full" re-runs the layer forward (+1/3 FLOPs) and is
    # the deep fallback for memory; "none" keeps everything live.
    dict(hidden=2048, layers=12, heads=16, inter=5504, seq=2048, batch=8,
         recompute="dots"),
    dict(hidden=2048, layers=12, heads=16, inter=5504, seq=2048, batch=4,
         recompute="none"),
    dict(hidden=2048, layers=12, heads=16, inter=5504, seq=2048, batch=4,
         recompute="dots"),
    dict(hidden=2048, layers=12, heads=16, inter=5504, seq=2048, batch=8,
         recompute="full"),
    dict(hidden=1024, layers=8, heads=16, inter=2816, seq=1024, batch=8,
         recompute="none"),
    # deliberately tiny last rung: the compile-helper failure mode is
    # program-size-correlated; this is the "any TPU number at all" rung
    dict(hidden=512, layers=4, heads=8, inter=1408, seq=512, batch=8,
         recompute="none"),
    # idx 6: the big rung with N steps per dispatch (lax.scan over the step)
    # — measures on-chip throughput with the per-dispatch host latency
    # amortized away; recompute=full is the config that fits HBM
    dict(hidden=2048, layers=12, heads=16, inter=5504, seq=2048, batch=8,
         recompute="full", scan_steps=True),
    # idx 7/8: recompute-free / dots at b4 in scan mode. Pre-bf16-fix these
    # OOMed because Adam silently upcast params to f32 (+~3GB); with true
    # bf16 their compiled peaks (12.95 / 10.34 GB) fit the ~15.7 GB chip —
    # no recompute tax means these are the north-star-MFU candidates.
    dict(hidden=2048, layers=12, heads=16, inter=5504, seq=2048, batch=4,
         recompute="none", scan_steps=True),
    dict(hidden=2048, layers=12, heads=16, inter=5504, seq=2048, batch=4,
         recompute="dots", scan_steps=True),
    # idx 9: b6 is the largest no-recompute batch that fits HBM
    dict(hidden=2048, layers=12, heads=16, inter=5504, seq=2048, batch=6,
         recompute="none", scan_steps=True),
    # idx 10: long-context rung — same tokens/step at 4x the sequence
    # length
    dict(hidden=2048, layers=12, heads=16, inter=5504, seq=8192, batch=1,
         recompute="none", scan_steps=True),
]


def peak_flops_per_chip():
    """bf16 peak of this chip from the one table (devprof.DEVICE_PEAKS);
    an unknown device kind raises."""
    from paddle_tpu.observability.devprof import device_peaks

    return device_peaks()[1]


def run(hidden=2048, layers=12, heads=16, inter=5504, vocab=32000, seq=2048, batch=8,
        steps=12, recompute="dots", kv_heads=None, scan_steps=False, ce_chunk=None,
        num_experts=0):
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.jit_api import TrainStep
    from paddle_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
        LlamaPretrainingCriterion,
    )

    # training-dynamics telemetry rides every bench rung (ISSUE 13
    # satellite): in-program, near-free, and the spill cadence (default 32)
    # sits above the timed loop — extra.dynamics records grad norm /
    # loss-z / non-finite evidence next to the perf number. Each rung is
    # its own child process, so the env write is rung-scoped.
    os.environ.setdefault("PADDLE_DYNAMICS", "1")

    paddle.seed(0)
    cfg = LlamaConfig(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
        num_hidden_layers=layers, num_attention_heads=heads,
        num_key_value_heads=kv_heads,
        max_position_embeddings=seq,
        use_recompute=recompute != "none",
        recompute_policy=recompute if recompute != "none" else "full",
        dtype="bfloat16",
        fuse_linear_cross_entropy=True,
        **({"ce_chunk_size": ce_chunk} if ce_chunk else {}),
        **({"num_experts": num_experts} if num_experts else {}),
    )
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    n_params = model.num_parameters()
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters(), weight_decay=0.01)
    # throughput/MFU flows through the framework's step-metrics bus (SURVEY §5)
    from paddle_tpu.utils.metrics_bus import StepMetricsBus

    bus = StepMetricsBus(
        tokens_per_step=batch * seq,
        flops_per_token=LlamaForCausalLM.flops_per_token(cfg, seq_len=seq),
        peak_flops=peak_flops_per_chip(),
        log_every=steps, skip_first=2,
    )
    step = TrainStep(model, lambda *a: LlamaPretrainingCriterion()(*a), opt, metrics_bus=bus)

    from paddle_tpu.observability import compilemem as _compilemem

    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, seq + 1)).astype(np.int32)
    x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])

    # warmup / compile. Sync EVERY dispatch: two in-flight steps overlap the
    # next step's uploaded args with the previous step's working set (~+4.4GB
    # transient at this size) — measured to OOM configs
    # whose single-step peak fits comfortably (b4-dots: 10.3GB predicted,
    # RESOURCE_EXHAUSTED only when dispatches overlap).
    if not scan_steps:
        for _ in range(2):
            loss = step(x, y)
            float(loss.numpy())

    if scan_steps:
        # n steps per dispatch: measures the CHIP, not the per-dispatch
        # host round trip.
        # stacked=True feeds a DIFFERENT batch to every scanned step — real
        # training steps, not one batch repeated.
        sids = rng.randint(0, vocab, (steps, batch, seq + 1)).astype(np.int32)
        xs = paddle.to_tensor(sids[:, :, :-1])
        ys = paddle.to_tensor(sids[:, :, 1:])
        losses = step.run_steps(xs, ys, n=steps, stacked=True)  # compile
        losses.numpy()
        comp_warm = _compilemem.ledger.counts()
        t0 = time.perf_counter()
        losses = step.run_steps(xs, ys, n=steps, stacked=True)
        loss_arr = losses.numpy()
        dt = (time.perf_counter() - t0) / steps
        loss = paddle.to_tensor(loss_arr[-1])
    else:
        # Sync every timed dispatch too — overlapping async dispatches carry
        # the same ~+4.4GB upload/working-set transient that OOMs b4-class
        # configs in warmup, and the timed loop runs 12x longer. This
        # measures sequential step latency (what a logging training loop
        # pays); the scan rungs measure the chip with overlap-free dispatch.
        comp_warm = _compilemem.ledger.counts()
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(x, y)
            float(loss.numpy())
        dt = (time.perf_counter() - t0) / steps

    # steady-state compile contract (ISSUE 8 satellite): warm train steps
    # must trigger ZERO recompiles — a nonzero delta means the timed number
    # measured the compiler, not the chip, and the perf trajectory can't
    # distinguish "slower code" from "compiling more"
    comp_end = _compilemem.ledger.counts()
    warm_recompiles = comp_end["events"] - comp_warm["events"]
    if warm_recompiles:
        raise RuntimeError(
            f"steady-state compile contract violated: {warm_recompiles} "
            f"compile(s) fired during the warm timed loop "
            f"(ledger: {_compilemem.ledger.report(recent=4)['recent']})")

    # one forced spill AFTER the timed loop: the summary reflects the run
    # without a mid-loop device sync perturbing the measurement
    dyn_block = {"enabled": False}
    if step._dynamics is not None:
        s = step._dynamics.spill(step._dyn_state,
                                 step=step.optimizer._global_step) or {}
        dyn_block = {
            "enabled": True,
            "groups": len(step._dynamics.group_names),
            "grad_norm": s.get("grad_norm"),
            "loss_z": round(s.get("loss_z", 0.0), 4),
            "nonfinite_steps": s.get("nonfinite_steps"),
            "nonfinite_first": s.get("nonfinite_first"),
        }

    # device-time attribution (ISSUE 17): per-program roofline rows next
    # to the perf number. Armed AFTER the timed loop — sample_every=1
    # blocks on every dispatch, which would serialize exactly what the
    # rungs measure — and the cost harvest is a suppressed re-lower, so
    # neither the headline nor the compile contract sees it.
    from paddle_tpu.observability import devprof as _devprof

    dev_block = {}
    try:
        _devprof.enable(sample_every=1)
        if scan_steps:
            step.run_steps(xs, ys, n=steps, stacked=True).numpy()
        else:
            for _ in range(2):
                float(step(x, y).numpy())
        _compilemem.memory.analyze()
        rep = _devprof.report()
        dev_block = {k: {f: r[f] for f in
                         ("device_s_mean", "device_s_per_token", "mfu",
                          "arith_intensity", "verdict") if r.get(f)
                         is not None}
                     for k, r in rep.get("programs", {}).items()}
    except Exception as e:  # noqa: BLE001 — profiling must not kill the rung
        dev_block = {"error": f"{type(e).__name__}: {str(e)[:120]}"}
    finally:
        _devprof.disable()

    from paddle_tpu.ops import flash_attention as fa

    tokens_per_sec = batch * seq / dt
    # one authoritative flops/token accounting (GQA-aware 6N + causal
    # attention quadratic term) — same formula the bus uses
    flops_per_token = LlamaForCausalLM.flops_per_token(cfg, seq_len=seq)
    mfu = flops_per_token * tokens_per_sec / peak_flops_per_chip()
    return {
        "metric": "tokens_per_sec_per_chip_llama_proxy",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {
            "mfu": round(mfu, 4),
            "params": n_params,
            "step_time_s": round(dt, 4),
            "config": (f"h{hidden}-L{layers}-a{heads}-i{inter}-v{vocab}-s{seq}-b{batch}"
                       f"-r{recompute}" + (f"-kv{kv_heads}" if kv_heads else "")
                       + (f"-e{num_experts}" if num_experts else "")),
            "backend": jax.default_backend(),
            "attn_impl": fa.LAST_IMPL or "math-xla",
            "final_loss": round(float(loss.numpy()), 4),
            "steps_per_dispatch": steps if scan_steps else 1,
            # compile ledger block (ISSUE 8 satellite): the perf
            # trajectory can now split "slower code" from "compiling more"
            "compile": {
                "events": comp_end["events"],
                "total_wall_s": comp_end["total_wall_s"],
                "churn_alerts": comp_end["churn_alerts"],
                "warm_recompiles": warm_recompiles,
            },
            # training-dynamics block (ISSUE 13 satellite): numerics
            # evidence lands next to the perf number on every rung
            "dynamics": dyn_block,
            # per-program device-time/roofline rows (ISSUE 17): the
            # trajectory guard compares these key by key across rounds
            "devprof": dev_block,
            **({} if scan_steps else
               {"bus": {k: round(v, 4) for k, v in bus.summary().items()}}),
        },
    }


def run_decode(hidden=2048, layers=12, heads=16, kv_heads=None, inter=5504,
               vocab=32000, batch=8, prompt_len=512, new_tokens=256,
               quantize=None):
    """Serving-path rung: jitted generate() with the fixed-shape KV cache
    (generation.py). Reports decode tokens/s/chip = B*new_tokens / wall after
    the compile is warm (a second call on the same bucket reuses the program)."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
        num_hidden_layers=layers, num_attention_heads=heads,
        num_key_value_heads=kv_heads, max_position_embeddings=prompt_len + new_tokens,
        dtype="bfloat16",
    )
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    if quantize:
        # weight-only int8/int4: the HBM-bandwidth lever for decode
        from paddle_tpu.nn.quant import quantize_for_inference

        model.eval()
        quantize_for_inference(model, quantize, skip=lambda n, l: "lm_head" in n)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, prompt_len)).astype(np.int32)
    out = model.generate(ids, max_new_tokens=new_tokens)  # compile + warm
    out.numpy()
    t0 = time.perf_counter()
    out = model.generate(ids, max_new_tokens=new_tokens)
    out.numpy()
    dt = time.perf_counter() - t0
    tps = batch * new_tokens / dt
    # decode is HBM-bandwidth-bound: each decode step streams every weight
    # byte once per batch row group. steps/s × weight bytes / peak BW is the
    # utilization diagnostic (v5e ≈ 819 GB/s).
    n_params = model.num_parameters()
    bytes_per_param = {"int8": 1, "int4": 0.5}.get(quantize, 2)
    hbm_util = (tps / batch) * n_params * bytes_per_param / 819e9
    return {
        "metric": "decode_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": 0.0,
        "extra": {
            "config": (f"h{hidden}-L{layers}-a{heads}-b{batch}-p{prompt_len}-n{new_tokens}"
                       + (f"-w{quantize}" if quantize else "")),
            "backend": jax.default_backend(),
            "wall_s": round(dt, 3),
            "hbm_bw_util": round(hbm_util, 4),
        },
    }


def run_spec_decode(hidden=2048, layers=12, heads=16, kv_heads=None, inter=5504,
                    vocab=32000, batch=8, prompt_len=512, new_tokens=256,
                    gamma=4):
    """Speculative decoding rung: target vs a quarter-depth draft; the
    output is exactly the target's greedy stream, the wall-clock gain is
    the acceptance rate's doing."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    def mk(nl):
        cfg = LlamaConfig(
            vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
            num_hidden_layers=nl, num_attention_heads=heads,
            num_key_value_heads=kv_heads,
            max_position_embeddings=prompt_len + new_tokens + gamma + 1,
            dtype="bfloat16")
        m = LlamaForCausalLM(cfg)
        m.bfloat16(); m.eval()
        return m
    model, draft = mk(layers), mk(max(layers // 4, 1))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, prompt_len)).astype(np.int32)
    out = model.generate_speculative(ids, draft, max_new_tokens=new_tokens, gamma=gamma)
    out.numpy()  # compile + warm
    t0 = time.perf_counter()
    out = model.generate_speculative(ids, draft, max_new_tokens=new_tokens, gamma=gamma)
    out.numpy()
    dt = time.perf_counter() - t0
    return {
        "metric": "speculative_decode_tokens_per_sec_per_chip",
        "value": round(batch * new_tokens / dt, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": 0.0,
        "extra": {
            "config": f"h{hidden}-L{layers}-d{max(layers // 4, 1)}-g{gamma}-b{batch}-n{new_tokens}",
            "backend": jax.default_backend(),
            "wall_s": round(dt, 3),
        },
    }


def run_paged_serve(hidden=2048, layers=12, heads=16, kv_heads=None, inter=5504,
                    vocab=32000, n_requests=12, max_seqs=4, max_new=128):
    """Continuous-batching serving rung: mixed-length prompts through the
    paged KV pool (kernel-backed paged attention on TPU). Reports decode
    tokens/s/chip across the whole workload."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
        num_hidden_layers=layers, num_attention_heads=heads,
        num_key_value_heads=kv_heads, max_position_embeddings=1024,
        dtype="bfloat16",
    )
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    model.eval()
    rng = np.random.RandomState(0)
    lens = rng.randint(32, 512, n_requests)
    prompts = [rng.randint(1, vocab, (l,)).astype(np.int32) for l in lens]
    # decode_block=32: fusing 32 decode steps per dispatch amortizes the
    # per-dispatch host round trip at the cost of admitting new requests
    # every 32 tokens instead of every 8 (streams stay token-identical —
    # tested).
    eng = ContinuousBatchingEngine(model, max_seqs=max_seqs, page_size=64,
                                   max_len=1024, decode_block=32)
    # compile warm: every prefill bucket in the workload + the full
    # power-of-two block-decode ladder (found on chip: the k=32/16/8 block
    # programs otherwise compile inside the timed loop, ~1.5 s each)
    eng.warmup([len(p) for p in prompts])
    t0 = time.perf_counter()
    outs = eng.serve(prompts, max_new_tokens=max_new)
    dt = time.perf_counter() - t0
    gen_tokens = sum(len(o) - len(p) for o, p in zip(outs, prompts))
    from paddle_tpu.ops import paged_attention as pa

    # prefix-cache A/B: a system-prompt workload (every request shares a
    # long prefix — the RAG/chat serving shape) served with the cache on;
    # the win is suffix-only prefill + page dedup (hit pages reported)
    # system prefix = an exact page multiple, so its pages never straddle a
    # request-specific suffix and every request shares the full prefix
    pc_page = 64
    sys_len = 4 * pc_page
    sysp = rng.randint(1, vocab, (sys_len,)).astype(np.int32)
    pc_prompts = [np.concatenate([sysp, rng.randint(1, vocab, (8,)).astype(np.int32)])
                  for _ in range(n_requests)]
    pc_new = 8
    pc = {}
    for label, flag in (("off", False), ("on", True)):
        e2 = ContinuousBatchingEngine(
            model, max_seqs=max_seqs, page_size=pc_page,
            max_len=1024,
            decode_block=8, enable_prefix_cache=flag)
        e2.warmup([len(p) for p in pc_prompts])
        if flag:
            # seed the cache so the timed serve hits it
            e2.serve([pc_prompts[0]], max_new_tokens=1)
        hits_before = e2.stats["prefix_hit_pages"]
        t1 = time.perf_counter()
        pc_outs = e2.serve(pc_prompts, max_new_tokens=pc_new)
        pc[label] = {
            "wall_s": round(time.perf_counter() - t1, 3),
            "hit_pages": e2.stats["prefix_hit_pages"] - hits_before,
        }
        pc.setdefault("outputs", [o.tolist() for o in pc_outs])
        # soft compare: a TPU bf16 argmax tie between the two program
        # shapes must not abort the whole harvested bench — report the rate
        pc["output_match"] = round(
            sum(a == b for a, b in zip(pc["outputs"],
                                       [o.tolist() for o in pc_outs]))
            / len(pc_outs), 3)
    pc.pop("outputs")
    pc["speedup"] = round(pc["off"]["wall_s"] / max(pc["on"]["wall_s"], 1e-9), 2)

    return {
        "metric": "paged_serve_tokens_per_sec_per_chip",
        "value": round(gen_tokens / dt, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": 0.0,
        "extra": {
            "config": f"h{hidden}-L{layers}-req{n_requests}-slots{max_seqs}-n{max_new}",
            "backend": jax.default_backend(),
            "attn_impl": pa.LAST_IMPL,
            "wall_s": round(dt, 3),
            "decode_steps": eng.stats["decode_steps"],
            "pool_mb": round(eng.pool_bytes() / 1e6, 1),
            "prefix_cache": pc,
        },
    }


def _child_main(rung_idx):
    """Run one ladder rung and print its JSON line. Exits NO_TPU_RC when
    JAX finds no TPU: a CPU run is not a measurement of the chip."""
    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print(f"[bench] no TPU: jax.devices() = {jax.devices()}",
              file=sys.stderr, flush=True)
        sys.exit(NO_TPU_RC)
    try:
        if rung_idx == -5:
            res = run_spec_decode()
        elif rung_idx == -4:
            res = run_paged_serve()
        elif rung_idx == -3:
            res = run_decode(quantize="int8")
        elif rung_idx == -7:
            res = run_decode(quantize="int4")
        elif rung_idx == -2:
            res = run_decode()
        elif rung_idx == -6:
            res = run(**GQA_RUNG, scan_steps=True)
        elif rung_idx == -8:
            res = run(**GQA_FRONTIER_RUNG, scan_steps=True)
        elif rung_idx == -9:
            res = run(**MOE_RUNG, scan_steps=True)
        else:
            res = run(**(LADDER[rung_idx] if rung_idx >= 0 else GQA_RUNG))
    except Exception as e:  # noqa: BLE001 — report, never crash silently
        res = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
    print(json.dumps(res), flush=True)


def _run_rung(rung_idx, timeout_s):
    """Spawn a rung child; returns (result_dict | None, timed_out)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--rung", str(rung_idx)]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout_s,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired:
        return None, True
    if proc.returncode == NO_TPU_RC:
        sys.stderr.write(proc.stderr)
        sys.exit(NO_TPU_RC)
    for line in reversed(proc.stdout.strip().splitlines() or []):
        try:
            return json.loads(line), False
        except json.JSONDecodeError:
            continue
    tail = (proc.stderr or "")[-200:]
    return {"error": f"rung exited rc={proc.returncode} with no JSON; stderr tail: {tail}"}, False


RUNGS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_rungs.jsonl")
TRAJECTORY_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_trajectory.jsonl")


def _last_banked_headline():
    """The newest BENCH_r<N>.json driver artifact (None when none exist) —
    the perf-trajectory baseline this run's headline is compared against."""
    import glob
    import re

    cands = []
    here = os.path.dirname(os.path.abspath(__file__))
    for p in glob.glob(os.path.join(here, "BENCH_r*.json")):
        m = re.match(r"BENCH_r(\d+)\.json$", os.path.basename(p))
        if m:
            cands.append((int(m.group(1)), p))
    if not cands:
        return None, None
    _, path = max(cands)
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return None, None
    # the driver artifact wraps the contract line under "parsed"
    if isinstance(rec.get("parsed"), dict) and "metric" in rec["parsed"]:
        rec = rec["parsed"]
    if not isinstance(rec, dict) or "metric" not in rec:
        return None, None
    return os.path.basename(path), rec


def _trajectory_guard(res):
    """Perf-trajectory guard (ISSUE 13 satellite): compare this run's
    headline tokens/s against the last banked BENCH_r*.json and flag >10%
    regressions IN THE CONTRACT LINE (extra.trajectory + a note), then
    append the datapoint to BENCH_trajectory.jsonl so the trajectory is a
    recorded series, not an empty promise. Same-backend, same-metric
    comparisons only. Never raises: the contract line lands regardless."""
    try:
        name, prev = _last_banked_headline()
        traj = None
        if (prev is not None and prev.get("value")
                and prev.get("metric") == res.get("metric")
                and (prev.get("extra") or {}).get("backend")
                == (res.get("extra") or {}).get("backend")
                and res.get("value")):
            delta = res["value"] / prev["value"] - 1.0
            # rung CONFIGS must match for the delta to mean anything: a
            # smaller-config run is legitimately slower, not a
            # regression — record the mismatch, never flag it
            same_config = ((prev.get("extra") or {}).get("config")
                           == (res.get("extra") or {}).get("config"))
            traj = {
                "baseline_file": name,
                "baseline_value": prev["value"],
                "baseline_config": (prev.get("extra") or {}).get("config"),
                "delta": round(delta, 4),
                "comparable": same_config,
                "regression": same_config and delta < -0.10,
            }
            res.setdefault("extra", {})["trajectory"] = traj
            if traj["regression"]:
                note = (f"PERF REGRESSION: headline {res['value']} is "
                        f"{-delta:.1%} below banked {name} "
                        f"({prev['value']})")
                prior = res["extra"].get("note")
                res["extra"]["note"] = ((prior + "; " + note) if prior
                                        else note)[:600]
            # per-program mode (ISSUE 17): name WHICH program regressed,
            # not just that the headline moved. Device-time rows are only
            # comparable between same-config runs — config changes move
            # per-program time legitimately.
            if same_config:
                prev_prog = (prev.get("extra") or {}).get("devprof") or {}
                cur_prog = (res.get("extra") or {}).get("devprof") or {}
                regressed = []
                for key, row in sorted(cur_prog.items()):
                    base = prev_prog.get(key)
                    if not (isinstance(row, dict) and isinstance(base, dict)):
                        continue
                    b = base.get("device_s_mean")
                    c = row.get("device_s_mean")
                    if b and c and c / b - 1.0 > 0.10:
                        regressed.append(
                            {"program": key, "delta": round(c / b - 1.0, 4),
                             "device_s_mean": c,
                             "baseline_device_s_mean": b})
                if regressed:
                    traj["program_regressions"] = regressed
                    names = ", ".join(f"{r['program']} +{r['delta']:.1%}"
                                      for r in regressed)
                    note = f"PERF REGRESSION (device time): {names}"
                    prior = res["extra"].get("note")
                    res["extra"]["note"] = ((prior + "; " + note) if prior
                                            else note)[:600]
        rec = {
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "metric": res.get("metric"),
            "value": res.get("value"),
            "mfu": (res.get("extra") or {}).get("mfu"),
            "config": (res.get("extra") or {}).get("config"),
            "backend": (res.get("extra") or {}).get("backend"),
            # per-program device-time rows so the NEXT round's guard has a
            # baseline to compare key by key (ISSUE 17)
            "programs": (res.get("extra") or {}).get("devprof") or None,
            "baseline": traj,
        }
        with open(TRAJECTORY_PATH, "a") as f:
            f.write(json.dumps(rec) + "\n")
    except Exception as e:  # noqa: BLE001 — the contract line must land
        res.setdefault("extra", {})["trajectory"] = {
            "error": f"{type(e).__name__}: {str(e)[:120]}"}

# Smallest-compile-first harvest order (VERDICT r4 item 1a). The kernel rungs
# that differentiate the framework (splash GQA, KV-cache decode, int8 decode)
# run BEFORE the big training compiles so they get on record before the
# programs most likely to run out their budget.
HARVEST = [
    ("tiny_h512", 5),
    ("small_h1024", 4),
    ("gqa_splash", -1),
    ("gqa_splash_scan", -6),
    ("gqa_b6_none_scan", -8),
    ("moe_e8_scan", -9),
    ("decode", -2),
    ("decode_int8", -3),
    ("decode_int4", -7),
    ("decode_speculative", -5),
    ("paged_serve", -4),
    ("big_b8_full", 3),
    ("big_b8_full_scan", 6),
    ("b4_none_scan", 7),
    ("b4_dots_scan", 8),
    ("b6_none_scan", 9),
    ("long_s8192_scan", 10),
    ("mid_b4_dots", 2),
    ("big_b8_dots", 0),
]
# Only tried if the big rung fails without a timeout (e.g. OOM): trade FLOPs or
# batch for memory.
MEM_FALLBACKS = [("mid_b4_none", 1)]
# Final reported training rung: the best measured MFU among banked standard
# (MHA) training rungs — they are the same model family, only
# batch/recompute/dispatch mode differ (recorded in extra.config).
PREFERENCE = [9, 7, 8, 6, 0, 3, 2, 1, 4, 5]  # idx 10 (long-context) is evidence, not the headline


def _timeout_for(idx):
    if idx in (-1, -6, -8, -9):
        return GQA_RUNG_TIMEOUT_S
    if idx in (-2, -3, -4, -5, -7):
        return DECODE_RUNG_TIMEOUT_S
    return RUNG_TIMEOUT_S[idx]


def _bank(name, result):
    """Append one completed rung to BENCH_rungs.jsonl IMMEDIATELY — a
    mid-ladder timeout must not lose the record of rungs that already
    ran. A log only: nothing reads it back into a result."""
    rec = {"rung": name, "ts": time.strftime("%Y-%m-%dT%H:%M:%S")}
    rec.update(result or {"error": "no output"})
    with open(RUNGS_PATH, "a") as f:
        f.write(json.dumps(rec) + "\n")


def main():
    errors = []
    banked = {}  # ladder idx -> successful result of THIS run
    for name, idx in HARVEST:
        print(f"[bench] rung {name} (idx {idx})", file=sys.stderr, flush=True)
        out, timed_out = _run_rung(idx, _timeout_for(idx))
        if timed_out:
            errors.append(f"{name}: timeout>{_timeout_for(idx)}s; ladder stopped")
            _bank(name, {"error": f"timeout>{_timeout_for(idx)}s"})
            break  # later rungs are bigger compiles; keep what completed
        _bank(name, out)
        if out is not None and "error" not in out:
            banked[idx] = out
            continue
        errors.append(f"{name}: {(out or {}).get('error', 'unknown')[:160]}")
        if idx == 0:  # big rung failed without a timeout (likely OOM) — memory ladder
            for fname, fidx in MEM_FALLBACKS:
                print(f"[bench] mem fallback {fname}", file=sys.stderr, flush=True)
                fout, ft = _run_rung(fidx, _timeout_for(fidx))
                if ft:
                    errors.append(f"{fname}: timeout")
                    _bank(fname, {"error": "timeout"})
                    break
                _bank(fname, fout)
                if fout is not None and "error" not in fout:
                    banked[fidx] = fout
                    break
                errors.append(f"{fname}: {(fout or {}).get('error', 'unknown')[:160]}")
    # primary = best measured MFU among this run's training rungs
    # (PREFERENCE order breaks ties / missing-mfu cases)
    res = None
    candidates = [i for i in PREFERENCE if i in banked]
    if candidates:
        best = max(candidates,
                   key=lambda i: (banked[i].get("extra", {}).get("mfu") or 0.0,
                                  -PREFERENCE.index(i)))
        res = banked[best]
        if errors:
            res.setdefault("extra", {})["note"] = "; ".join(errors)[:400]
    if res is None:
        res = {
            "metric": "tokens_per_sec_per_chip_llama_proxy",
            "value": 0.0,
            "unit": "tokens/s/chip",
            "vs_baseline": 0.0,
            "error": " | ".join(errors),
        }
    # kernel-rung results attach to whatever final line ships: the
    # splash/decode numbers must reach the driver artifact even when every
    # training rung failed
    if -8 in banked or -6 in banked or -1 in banked:
        g = banked.get(-8) or banked.get(-6) or banked[-1]
        res.setdefault("extra", {})["gqa"] = {
            "tokens_per_sec": g["value"],
            "mfu": g.get("extra", {}).get("mfu"),
            "attn_impl": g.get("extra", {}).get("attn_impl"),
            "config": g.get("extra", {}).get("config"),
        }
    if -2 in banked:
        d = banked[-2]
        res.setdefault("extra", {})["decode"] = {
            "tokens_per_sec": d["value"],
            "config": d.get("extra", {}).get("config"),
        }
        if -3 in banked:
            res["extra"]["decode"]["int8_tokens_per_sec"] = banked[-3]["value"]
        if -7 in banked:
            res["extra"]["decode"]["int4_tokens_per_sec"] = banked[-7]["value"]
    if -5 in banked:
        sp = banked[-5]
        res.setdefault("extra", {})["speculative"] = {
            "tokens_per_sec": sp["value"],
            "config": sp.get("extra", {}).get("config"),
        }
    if -4 in banked:
        ps = banked[-4]
        res.setdefault("extra", {})["paged_serve"] = {
            "tokens_per_sec": ps["value"],
            "attn_impl": ps.get("extra", {}).get("attn_impl"),
            "config": ps.get("extra", {}).get("config"),
        }
    # cluster health per run (ISSUE 11 satellite): snapshot count, worst
    # cross-rank phase skew, straggler verdicts from the fleet plane
    try:
        from paddle_tpu.observability import fleet as _fleet

        res.setdefault("extra", {})["fleet"] = _fleet.bench_block()
    except Exception as e:  # noqa: BLE001 — the bench line must still land
        res.setdefault("extra", {})["fleet"] = {
            "error": f"{type(e).__name__}: {str(e)[:160]}"}
    # perf-trajectory guard (ISSUE 13 satellite): flag >10% headline
    # regressions vs the last driver artifact and record the series
    _trajectory_guard(res)
    print(json.dumps(res), flush=True)
    if "error" in res:
        sys.exit(1)


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--rung":
        _child_main(int(sys.argv[2]))
    else:
        main()
