"""Cross-feature composition parity (SURVEY §4: every new axis/feature must
compose with the existing ones, proven by single-device loss parity on the
8-device virtual mesh — the matrix the per-feature tests don't cover)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.distributed import mesh as M
from paddle_tpu.distributed.train_step import DistributedTrainStep
from paddle_tpu.models.llama import (
    LlamaForCausalLM,
    LlamaPretrainingCriterion,
    llama_tiny,
)


def _model_and_batch(seq=16, bs=8, seed=61, **cfg_kw):
    paddle.seed(seed)
    cfg = llama_tiny(num_hidden_layers=2, **cfg_kw)
    m = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, (bs, seq + 1)).astype(np.int32)
    return m, paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])


def test_moe_composes_with_zero_sharding():
    """MoE (experts on dp) × ZeRO-2 (optimizer state on sharding): first
    compiled step equals the eager labeled forward, incl. the aux loss."""
    m, x, y = _model_and_batch(num_experts=4, moe_top_k=2)
    ref = float(m(x, labels=y).numpy())
    with M.mesh_guard(M.build_mesh(dp=2, sharding=4)):
        opt = optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
        step = DistributedTrainStep(m, m.make_loss_fn(), opt, sharding_stage=2)
        loss = step(x, y)
    np.testing.assert_allclose(float(loss.numpy()), ref, rtol=2e-5, atol=2e-6)


def test_moe_composes_with_tp():
    """MoE × TP: expert weights carry BOTH the expert axis (dp) and mp
    sharding on the hidden dim."""
    m, x, y = _model_and_batch(num_experts=4, num_attention_heads=4)
    ref = float(m(x, labels=y).numpy())
    with M.mesh_guard(M.build_mesh(dp=4, mp=2)):
        opt = optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
        step = DistributedTrainStep(m, m.make_loss_fn(), opt)
        loss = step(x, y)
    np.testing.assert_allclose(float(loss.numpy()), ref, rtol=2e-5, atol=2e-6)


def test_cp_composes_with_zero_sharding():
    """Ring CP × ZeRO: seq on sep, optimizer state on sharding."""
    m, x, y = _model_and_batch(context_parallel=True)
    ref = float(m(x, labels=y).numpy())
    with M.mesh_guard(M.build_mesh(sharding=2, sep=4)):
        opt = optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
        step = DistributedTrainStep(
            m, lambda o, l: LlamaPretrainingCriterion()(o, l), opt,
            sharding_stage=2)
        loss = step(x, y)
    np.testing.assert_allclose(float(loss.numpy()), ref, rtol=2e-5, atol=2e-6)


def test_cp_composes_with_recompute_bf16():
    """Ring CP × jax.checkpoint recompute × bf16 weights: trains to
    descent, every step finite (the north-star memory recipe at long
    context)."""
    m, x, y = _model_and_batch(context_parallel=True, use_recompute=True,
                               recompute_policy="dots", dtype="bfloat16")
    m.bfloat16()
    with M.mesh_guard(M.build_mesh(sep=4)):
        opt = optimizer.AdamW(learning_rate=3e-3, parameters=m.parameters(),
                              multi_precision=True)
        step = DistributedTrainStep(
            m, lambda o, l: LlamaPretrainingCriterion()(o, l), opt)
        losses = [float(step(x, y).numpy()) for _ in range(6)]
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses


def test_16dev_cp_hybrid_no_deadlock():
    """CP at 16 devices with mp>1 and sharding>1 (mp2 x sep4 x sharding2):
    the device count where GSPMD reshard-in-divergent-branch deadlocks have
    bitten before (test_pipeline_composition 16dev regression). Fresh
    subprocess for its own 16-device virtual mesh."""
    import os
    import subprocess
    import sys
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=16",
           "JAX_PLATFORMS": "cpu"}
    code = textwrap.dedent("""
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu import optimizer
        from paddle_tpu.distributed import mesh as M
        from paddle_tpu.distributed.train_step import DistributedTrainStep
        from paddle_tpu.models.llama import (
            LlamaForCausalLM, LlamaPretrainingCriterion, llama_tiny)
        paddle.seed(61)
        cfg = llama_tiny(num_hidden_layers=2, context_parallel=True,
                         num_attention_heads=8, num_key_value_heads=4)
        m = LlamaForCausalLM(cfg)
        rng = np.random.RandomState(61)
        ids = rng.randint(0, cfg.vocab_size, (8, 17)).astype(np.int32)
        x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])
        ref = float(m(x, labels=y).numpy())
        with M.mesh_guard(M.build_mesh(mp=2, sep=4, sharding=2)):
            opt = optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
            step = DistributedTrainStep(
                m, lambda o, l: LlamaPretrainingCriterion()(o, l), opt,
                sharding_stage=2)
            val = float(step(x, y).numpy())
        delta = abs(val - ref)
        assert delta < 1e-4, (val, ref)
        print(f"cp16 parity_delta={delta:.2e}")
    """)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=540, cwd=repo, env=env)
    assert p.returncode == 0, p.stderr[-800:]
    assert "parity_delta" in p.stdout, p.stdout


def test_moe_cp_together():
    """MoE experts (dp) and ring CP (sep) in ONE model/mesh: the expert
    all-to-alls and the KV ring ride different axes."""
    m, x, y = _model_and_batch(num_experts=2, context_parallel=True)
    ref = float(m(x, labels=y).numpy())
    with M.mesh_guard(M.build_mesh(dp=2, sep=4)):
        opt = optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
        step = DistributedTrainStep(m, m.make_loss_fn(), opt)
        loss = step(x, y)
    np.testing.assert_allclose(float(loss.numpy()), ref, rtol=2e-5, atol=2e-6)


def test_moe_pipe_ce_parity_and_aux_warning():
    """MoE layers run INSIDE the scheduled 1F1B engine (stacked expert
    banks scan like any homogeneous block): CE loss parity vs the plain
    MoE model; the un-threaded gate aux loss is a documented warning."""
    import warnings as _w

    from paddle_tpu.models.llama import LlamaForCausalLMPipe

    paddle.seed(62)
    cfg = llama_tiny(num_hidden_layers=4, num_experts=2,
                     moe_aux_loss_weight=0.0)
    plain = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(62)
    ids = rng.randint(0, cfg.vocab_size, (4, 13)).astype(np.int32)
    x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])
    ref = float(plain(x, labels=y).numpy())  # CE only (aux weight 0)

    with M.mesh_guard(M.build_mesh(pp=2)):
        pipe = LlamaForCausalLMPipe(cfg, pp_degree=2, schedule="1f1b")
        pipe.load_from_causal_lm(plain)
        val = float(pipe(x, y).numpy())
    np.testing.assert_allclose(val, ref, rtol=2e-5, atol=2e-6)

    cfg2 = llama_tiny(num_hidden_layers=4, num_experts=2)  # default aux weight
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        LlamaForCausalLMPipe(cfg2, pp_degree=2, schedule="1f1b")
    assert any("aux loss" in str(r.message) for r in rec)


def test_cp_inside_pipe_engine_raises():
    """context_parallel cannot ride inside the scheduled pipe's manual pp
    axis — must refuse loudly, not silently run non-CP attention."""
    from paddle_tpu.models.llama import LlamaForCausalLMPipe

    paddle.seed(63)
    cfg = llama_tiny(num_hidden_layers=4, context_parallel=True)
    rng = np.random.RandomState(63)
    ids = rng.randint(0, cfg.vocab_size, (4, 17)).astype(np.int32)
    x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])
    with M.mesh_guard(M.build_mesh(pp=2, sep=4)):
        pipe = LlamaForCausalLMPipe(cfg, pp_degree=2, schedule="1f1b")
        with pytest.raises(Exception, match="context_parallel does not compose"):
            pipe(x, y)
