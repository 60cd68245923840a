"""The main path's Pallas kernels compile for the chip, at real widths.

This sandbox has no accelerator, but the TPU's compiler is installed and
compiles for a chip that is *described* (a v5e 2x2 topology), not attached:
what Mosaic refuses on the chip it refuses here — a slice off the tiling, a
resident set past VMEM, an API the installed jax no longer has — at no chip
time. Interpret-mode tests cannot see any of that. Each case lowers one
kernel exactly as the ops module calls it, at LLaMA-7B widths (h4096 = 32
heads x 128, page 16, T = prefill_chunk + max_seqs), and asserts the
compiled program holds a Mosaic kernel (`tpu_custom_call`).

The pool cases guard a layout, not a kernel: the KV-pool writers and the
two paged kernels in the serving engine's two program shapes (a decode block:
writer -> paged kernel in an 8-step `lax.scan`; a mixed step: ragged writer ->
ragged kernel, then the 7-step scan), two layers at the benchmark cell's
widths (32 / 32 heads) and at 32 / 8, pools donated. A write expressed as an
XLA scatter whose window covers the head axis makes the TPU compiler keep
the pool in another layout than the kernels read, and re-lay out the whole
pool around every kernel call (55% of device time when it was found, PERF.md
PR 27). A CPU compile shows nothing of it, so these are the only guard a CPU
suite can have against the layout coming back: no pool-shaped `copy` in the
compiled text, temporaries under one pool.

The latent-attention configuration (benchmarks/configs/kimi-k2.7-code-ep32)
adds its kernels to the first group (the absorbed decode kernel, and jax's
grouped matmul through the dropless expert layer's own call) and, as its own
case, the serving engine's two real programs lowered at the cell's sizes from
an ABSTRACT model (4.85 B parameters as shapes): no copy shaped like the
latent pool in either, and arguments + temporaries as the configuration file
states them.

The SambaY configuration (benchmarks/configs/phi-4-mini-flash-reasoning) adds
the two kernels with a window and a scale of the caller's, and the engine's
two real programs at the cell's sizes from an abstract model (3.85 B
parameters as shapes): no copy shaped like layer 17's pool, a ring or a slot
array, and the mixed program's cross-decoder on max_seqs rows.

This is the ONLY test file that describes a topology, and it does so inside
a module-scoped fixture: only one process may load the TPU library, so the
call must not run while any module is imported (pytest-xdist workers all
import every test file) nor in a child process.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

H, HKV_GQA, D, SEQ = 32, 8, 128, 2048
PAGE, MAX_LEN, MAX_SEQS, PREFILL_CHUNK = 16, 2048, 4, 512
T = PREFILL_CHUNK + MAX_SEQS       # the engine's packed token-stream width
NPAGES = MAX_LEN // PAGE           # page-table width per sequence
POOL = 1 + MAX_SEQS * NPAGES       # the engine's default pool (+ scratch)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    env = pytest.MonkeyPatch()
    if "TPU_LOG_DIR" not in os.environ:
        env.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        env.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile can be written to the persistent cache but not read
    # back without a chip (the next one warns and compiles again): keep
    # the cache off around these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
    env.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _ragged(quantized, hkv=H):
    """The ragged kernel through its own call at the serving cell's heads
    (32 / 32), or at `hkv` KV heads (Mistral's 32 / 8: the rule takes other
    tiles there)."""
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        quantization_utils as qu,
    )

    from paddle_tpu.ops.ragged_paged_attention import _ragged_pallas

    def fn(q, k, v, kv_lens, page_indices, cu):
        return _ragged_pallas(q, k, v, kv_lens, page_indices, cu,
                              D ** -0.5, interpret=False)

    def args(sds):
        if quantized:
            pool = qu.QuantizedTensor(
                weight=sds((hkv, POOL, PAGE, D), jnp.int8),
                scales=sds((hkv, POOL, PAGE, 1), jnp.float32))
        else:
            pool = sds((hkv, POOL, PAGE, D), jnp.bfloat16)
        return (sds((T, H, D), jnp.bfloat16), pool, pool,
                sds((MAX_SEQS,), jnp.int32),
                sds((MAX_SEQS, NPAGES), jnp.int32),
                sds((MAX_SEQS + 1,), jnp.int32))

    return fn, args


def _flash(grad):
    from paddle_tpu.ops.flash_attention import _pallas_flash

    def fwd(q, k, v):
        return _pallas_flash(q, k, v, True, D ** -0.5)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    def args(sds):
        return (sds((2, H, SEQ, D), jnp.bfloat16),) * 3

    return (bwd if grad else fwd), args


def _splash_gqa():
    from paddle_tpu.ops.flash_attention import _splash_impl

    def fn(q, k, v):
        return _splash_impl(q, k, v, True, D ** -0.5)

    def args(sds):
        kv = sds((2, HKV_GQA, SEQ, D), jnp.bfloat16)
        return (sds((2, H, SEQ, D), jnp.bfloat16), kv, kv)

    return fn, args


def _paged_decode(hkv, quantized=False):
    """The paged decode tier the pool's type takes (the repo's kernel for a
    float pool, jax's for the int8 pool) through ops/paged_attention.py's own
    call, at the serving cell's shape: 16 rows of 128 pages (the test steers
    the platform predicate to the described chip)."""
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        quantization_utils as qu,
    )

    from paddle_tpu.ops.paged_attention import paged_decode_attention

    def fn(q, k, v, lengths, page_indices):
        return paged_decode_attention(q, k, v, lengths, page_indices)

    def args(sds):
        shape = (hkv,) + CELL_POOL[1:]
        if quantized:
            pool = qu.QuantizedTensor(
                weight=sds(shape, jnp.int8),
                scales=sds(shape[:3] + (1,), jnp.float32))
        else:
            pool = sds(shape, jnp.bfloat16)
        return (sds((CELL_ROWS, H, D), jnp.bfloat16), pool, pool,
                sds((CELL_ROWS,), jnp.int32),
                sds((CELL_ROWS, NPAGES), jnp.int32))

    return fn, args


# the latent-attention cell's widths
# (benchmarks/workloads/kimi-k2.7-code-agent-steady.json)
MLA_H, MLA_RANK, MLA_ROPE, MLA_STORED = 64, 512, 64, 640
MLA_ROWS, MLA_MAX_LEN, MLA_CHUNK, MLA_K = 16, 17408, 2048, 8
MLA_NPAGES = MLA_MAX_LEN // PAGE
MLA_POOL = (1 + MLA_ROWS * MLA_NPAGES, PAGE, MLA_STORED)


def _mla_decode():
    from paddle_tpu.ops.mla_decode_attention import mla_decode_attention

    def fn(q_lat, q_rope, pages, lengths, page_indices):
        return mla_decode_attention(q_lat, q_rope, pages, lengths,
                                    page_indices, 0.1)

    def args(sds):
        return (sds((MLA_ROWS, MLA_H, MLA_RANK), jnp.bfloat16),
                sds((MLA_ROWS, MLA_H, MLA_ROPE), jnp.bfloat16),
                sds(MLA_POOL, jnp.bfloat16), sds((MLA_ROWS,), jnp.int32),
                sds((MLA_ROWS, MLA_NPAGES), jnp.int32))

    return fn, args


def _moe_gmm(tokens):
    """jax's megablox grouped matmul through the dropless expert layer's own
    call: 12 held experts of 7168 x 2048, worst-case `tokens x 8` rows."""
    from paddle_tpu.incubate.distributed.models.moe.dropless import (
        held_experts,
    )

    def fn(x, idx, w, gate, up, down):
        return held_experts(x, idx, w, gate, up, down, first=0)

    def args(sds):
        return (sds((tokens, 7168), jnp.bfloat16), sds((tokens, 8), jnp.int32),
                sds((tokens, 8), jnp.float32),
                sds((12, 7168, 2048), jnp.bfloat16),
                sds((12, 7168, 2048), jnp.bfloat16),
                sds((12, 2048, 7168), jnp.bfloat16))

    return fn, args


# the sparse + linear attention cell's widths
# (benchmarks/workloads/minicpm-sala-longdoc-steady.json)
SALA_HKV, SALA_PAGE, SALA_ROWS, SALA_K = 2, 64, 16, 8
SALA_MAX_LEN, SALA_CHUNK = 50176, 4096
SALA_NPAGES = SALA_MAX_LEN // SALA_PAGE
SALA_POOL = (SALA_HKV, 1 + SALA_ROWS * SALA_NPAGES, SALA_PAGE, D)


def _sparse_decode():
    """Decode over the kept pages through ops/sparse_decode_attention.py's
    own call: the selector over the compressed-key plane, then the paged
    kernel with a table a row and K/V head (32 query heads over 2)."""
    from paddle_tpu.ops.sparse_decode_attention import sparse_decode_attention
    from paddle_tpu.ops.sparse_paged_attention import SparseConfig

    def fn(q, k, v, c, lengths, page_indices):
        return sparse_decode_attention(q, k, v, c, page_indices, lengths,
                                       SparseConfig())

    def args(sds):
        pool = sds(SALA_POOL, jnp.bfloat16)
        return (sds((SALA_ROWS, H, D), jnp.bfloat16), pool, pool,
                sds((SALA_HKV, SALA_POOL[1] * 4, D), jnp.bfloat16),
                sds((SALA_ROWS,), jnp.int32),
                sds((SALA_ROWS, SALA_NPAGES), jnp.int32))

    return fn, args


def _windowed(decode):
    """Either kernel at the SambaY cell's window layers: 40 padded query
    heads over 10 stored K/V pairs of 128, pages of 64, a ring table 256
    wide, window 512 and the published head's scale (1/8)."""
    from paddle_tpu.ops.paged_attention import paged_decode_attention
    from paddle_tpu.ops.ragged_paged_attention import _ragged_pallas

    rows, width, pool = 32, 256, (10, 1 + 32 * 25, 64, 128)

    def fn(q, k, v, lens, table, cu):
        if decode:
            return paged_decode_attention(q, k, v, lens, table, scale=0.125,
                                          window=512)
        return _ragged_pallas(q, k, v, lens, table, cu, 0.125,
                              interpret=False, window=512)

    def args(sds):
        return (sds((rows if decode else 1024 + rows, 40, 128),
                    jnp.bfloat16),
                sds(pool, jnp.bfloat16), sds(pool, jnp.bfloat16),
                sds((rows,), jnp.int32), sds((rows, width), jnp.int32),
                sds((rows + 1,), jnp.int32))

    return fn, args


CASES = {
    "paged-decode-window-512": lambda: _windowed(decode=True),
    "ragged-window-512": lambda: _windowed(decode=False),
    "sparse-decode-by-head": _sparse_decode,
    "mla-decode": _mla_decode,
    "moe-gmm-decode-rows": lambda: _moe_gmm(MLA_ROWS),
    "moe-gmm-mixed-stream": lambda: _moe_gmm(MLA_CHUNK + MLA_ROWS),
    "ragged-bf16-pool": lambda: _ragged(quantized=False),
    "ragged-int8-pool": lambda: _ragged(quantized=True),
    "ragged-gqa-kv8-pool": lambda: _ragged(quantized=False, hkv=HKV_GQA),
    "ragged-gqa-kv8-int8-pool": lambda: _ragged(quantized=True, hkv=HKV_GQA),
    "flash-fwd-s2048": lambda: _flash(grad=False),
    "flash-bwd-s2048": lambda: _flash(grad=True),
    "splash-gqa-kv8": _splash_gqa,
    "paged-decode-mha": lambda: _paged_decode(H),
    "paged-decode-gqa-kv8": lambda: _paged_decode(HKV_GQA),
    "paged-decode-int8-pool": lambda: _paged_decode(H, quantized=True),
}


# the serving cell's widths (benchmarks/workloads/deepseek7b-chat-steady.json)
CELL_HKV, CELL_ROWS, CELL_K, CELL_LAYERS = 32, 16, 8, 2
CELL_T = PREFILL_CHUNK + CELL_ROWS
CELL_POOL = (CELL_HKV, 1 + CELL_ROWS * NPAGES, PAGE, D)


def _decode_scan(pools, q, new, table, lengths, live, steps):
    """`steps` decode steps as the engine's scan body runs them: each layer
    writes one token a row into K and V, then reads both pools; a dead row
    (`live` false) attends nothing."""
    from paddle_tpu.ops.paged_attention import (
        paged_decode_attention, write_token_kv,
    )

    def body(carry, _):
        pools_c, lens = carry
        acc, written = jnp.zeros_like(q), []
        for kp, vp in pools_c:
            kp = write_token_kv(kp, table, lens, new[:, :kp.shape[0]])
            vp = write_token_kv(vp, table, lens, new[:, :vp.shape[0]])
            acc += paged_decode_attention(
                q, kp, vp, jnp.where(live, lens + 1, 0), table)
            written.append((kp, vp))
        return (tuple(written), lens + 1), acc

    (pools, _), outs = jax.lax.scan(body, (pools, lengths), None,
                                    length=steps)
    return outs, pools


def _decode_block_shape(hkv):
    def fn(pools, q, new, table, lengths, live):
        return _decode_scan(pools, q, new, table, lengths, live, CELL_K)

    def args(sds):
        pool = sds((hkv,) + CELL_POOL[1:], jnp.bfloat16)
        q = sds((CELL_ROWS, H, D), jnp.bfloat16)
        return (((pool, pool),) * CELL_LAYERS, q, q,
                sds((CELL_ROWS, NPAGES), jnp.int32),
                sds((CELL_ROWS,), jnp.int32), sds((CELL_ROWS,), jnp.bool_))

    return fn, args


def _mixed_step_shape(hkv):
    from paddle_tpu.ops.ragged_paged_attention import (
        _ragged_pallas, write_ragged_kv,
    )

    def fn(pools, q_t, new_t, q, new, table, lengths, live, cu, row_of,
           token_pos, valid):
        kv_lens = lengths + cu[1:] - cu[:-1]
        acc, written = jnp.zeros_like(q_t), []
        for kp, vp in pools:
            kp = write_ragged_kv(kp, table, row_of, token_pos, valid,
                                 new_t[:, :hkv])
            vp = write_ragged_kv(vp, table, row_of, token_pos, valid,
                                 new_t[:, :hkv])
            acc += _ragged_pallas(q_t, kp, vp, kv_lens, table, cu,
                                  D ** -0.5, interpret=False)
            written.append((kp, vp))
        return acc, _decode_scan(tuple(written), q, new, table, kv_lens,
                                 live, CELL_K - 1)

    def args(sds):
        pool = sds((hkv,) + CELL_POOL[1:], jnp.bfloat16)
        q_t = sds((CELL_T, H, D), jnp.bfloat16)
        q = sds((CELL_ROWS, H, D), jnp.bfloat16)
        per_token = sds((CELL_T,), jnp.int32)
        return (((pool, pool),) * CELL_LAYERS, q_t, q_t, q, q,
                sds((CELL_ROWS, NPAGES), jnp.int32),
                sds((CELL_ROWS,), jnp.int32), sds((CELL_ROWS,), jnp.bool_),
                sds((CELL_ROWS + 1,), jnp.int32), per_token, per_token,
                sds((CELL_T,), jnp.bool_))

    return fn, args


POOL_CASES = {
    "decode-block-mha": (_decode_block_shape, CELL_HKV),
    "decode-block-gqa-kv8": (_decode_block_shape, HKV_GQA),
    "mixed-step-mha": (_mixed_step_shape, CELL_HKV),
    "mixed-step-gqa-kv8": (_mixed_step_shape, HKV_GQA),
}


def _compile_for_chip(shape, one_chip, monkeypatch, **jit_kw):
    from paddle_tpu.ops import flash_attention

    # jax.devices() still says CPU here: the tier choice follows the
    # described chip in these tests only, not through an option of the program
    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    fn, args = shape()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(fn, **jit_kw).lower(*args(sds)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, monkeypatch):
    _compile_for_chip(CASES[case], one_chip, monkeypatch)


# the training cell's attention call (benchmarks/workloads/
# mistral7b-pretrain-4k.json: batch 2 x seq 4096), and three shapes that hold
# the rule's caps: the widest head at the full tile, a head past it (where
# VMEM refuses 1024 rows), and the longest context, the last two with the
# three-kernel backward (for one row of 32768 the fused one's dq partials
# alone would need 8 GiB)
SPLASH_GRAD_CASES = {
    "cell-b2-s4096-d128": (2, 4096, D, 1024, True),
    "b2-s4096-d256": (2, 4096, 256, 1024, True),
    "b2-s4096-d512": (2, 4096, 512, 512, False),
    "b1-s32768-d128": (1, 32768, D, 1024, False),
}


@pytest.mark.parametrize("case", sorted(SPLASH_GRAD_CASES))
def test_splash_grad_runs_on_the_rules_tiles(case, one_chip, monkeypatch):
    """`jax.grad` of `_splash_impl`, 32 / 8 heads, causal, lowered for the
    described v5e: the Mosaic kernels are in, and their scratch is shaped by
    the rule's query tile, not by the library's 128 x 128 default (9x slower
    at the cell's shape, PERF.md PR 30). The only guard a CPU suite can have
    against a tile the chip's VMEM refuses."""
    from paddle_tpu.ops.flash_attention import _splash_impl

    batch, seq, head_dim, bq, fused = SPLASH_GRAD_CASES[case]

    def fn(q, k, v):
        def loss(q, k, v):
            out = _splash_impl(q, k, v, True, head_dim ** -0.5)
            return out.astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def args(sds):
        kv = sds((batch, HKV_GQA, seq, head_dim), jnp.bfloat16)
        return (sds((batch, H, seq, head_dim), jnp.bfloat16), kv, kv)

    compiled = _compile_for_chip(lambda: (fn, args), one_chip, monkeypatch)
    # '%splash_mha_fwd_residuals.1 = (f32[2,1024,128]{..}, ..) custom-call(':
    # the kernel's name and its results, its f32 scratch first: the forward's
    # (bq, 128) statistics, the backward's (bkv, head_dim) accumulators
    calls = re.findall(r"%splash_mha_(fwd|dkv|dq)\w*[.\d]* = (.*?) custom-call\(",
                       compiled.as_text())
    assert {kind for kind, _ in calls} == (
        {"fwd", "dkv"} if fused else {"fwd", "dkv", "dq"})
    lead = f"{batch}," if batch > 1 else ""   # vmap over one row is no dim
    for kind, results in calls:
        assert f"f32[{lead}128," not in results, (kind, results)
        tile = f"f32[{lead}{bq},{128 if kind == 'fwd' else head_dim}]"
        assert tile in results, (kind, results)
    # the fused backward's dq partials, or none: under 1 GiB either way
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("case", sorted(POOL_CASES))
def test_pool_stays_in_the_kernels_layout(case, one_chip, monkeypatch):
    shape, hkv = POOL_CASES[case]
    compiled = _compile_for_chip(lambda: shape(hkv), one_chip, monkeypatch,
                                 donate_argnums=(0,))
    text = compiled.as_text()
    assert "%paged_attention" in text  # the name the benchmark's reader finds
    pool = ",".join(map(str, (hkv,) + CELL_POOL[1:]))
    copies = re.findall(rf"= bf16\[{pool}\][^ ]* copy\(", text)
    assert not copies, f"{len(copies)} whole-pool re-layout copies"
    pool_bytes = 2 * hkv * CELL_POOL[1] * PAGE * D
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes



# ---- the latent-attention cell's two programs, at the cell's sizes ---------

def _abstract_kimi():
    """(model, state shapes, configuration) of the benchmark's
    kimi-k2.7-code-ep32 at its full depth, no parameter materialised: the
    layers are built inside `jax.eval_shape`, and the programs below take
    every parameter as an operand."""
    import json

    from benchmarks import kimi_model
    from paddle_tpu.framework import random as prandom
    from paddle_tpu.models.deepseek_v3 import DeepseekV3ForCausalLM

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "kimi-k2.7-code-ep32.json")) as f:
        raw = json.load(f)
    cfg = kimi_model.load_config(raw)
    made = {}

    def make():
        with prandom.rng_guard(jax.random.PRNGKey(0)):
            made["model"] = DeepseekV3ForCausalLM(
                kimi_model.model_config(cfg, MLA_MAX_LEN, "bfloat16"))
        return made["model"].raw_state_dict()

    return made, jax.eval_shape(make), raw


def test_latent_programs_at_the_cells_sizes(one_chip, monkeypatch):
    """`serve.decode_block` and `serve.ragged` of the real engine over the
    latent pool, lowered for the described v5e: the kernels are in, no copy
    is shaped like a pool, and arguments + temporaries are what the
    configuration file's `compile_memory_gib` says (under 15.0 GiB)."""
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine
    from paddle_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    made, state, raw = _abstract_kimi()
    depth = raw["num_hidden_layers"]
    eng = ContinuousBatchingEngine(
        made["model"], max_seqs=MLA_ROWS, page_size=PAGE,
        max_len=MLA_MAX_LEN, prefill_chunk=MLA_CHUNK, decode_block=MLA_K,
        num_pages=2)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    st = {n: sds(v.shape, v.dtype) for n, v in state.items()}
    pools = tuple((sds(MLA_POOL, jnp.bfloat16),) for _ in range(depth))
    S, T = MLA_ROWS, MLA_CHUNK + MLA_ROWS
    i32, greedy = jnp.int32, (False, 1.0, 0, 1.0)
    table, per_row, keys = (sds((S, MLA_NPAGES), i32), sds((S,), i32),
                            sds((MLA_K, S, 2), jnp.uint32))
    lowered = {
        "decode_block": eng._decode_block_fn(greedy, MLA_K)._jitted.lower(
            st, sds((S, 1), i32), pools, table, per_row, per_row, keys),
        "ragged": eng._ragged_fn(greedy)._jitted.lower(
            st, sds((T,), i32), sds((S + 1,), i32), sds((T,), i32),
            sds((T,), i32), sds((T,), jnp.bool_), sds((S, 1), jnp.bool_),
            sds((S, 1), i32), pools, table, table, per_row, per_row, keys),
    }
    pool = ",".join(map(str, MLA_POOL))
    for name, low in lowered.items():
        compiled = low.compile()
        text = compiled.as_text()
        assert "mla_decode_attention" in text and "gmm" in text, name
        copies = re.findall(rf"= bf16\[{pool}\][^ ]* copy\(", text)
        assert not copies, f"{name}: {len(copies)} pool-shaped copies"
        ma = compiled.memory_analysis()
        gib = (ma.argument_size_in_bytes + ma.temp_size_in_bytes) / 2 ** 30
        stated = raw["compile_memory_gib"][name]
        assert gib < 15.0 and abs(gib - stated) < 0.05, (name, gib, stated)


# ---- the sparse + linear attention cell's two programs, at the cell's sizes -

def _abstract_sala():
    """(model, state shapes, configuration) of the benchmark's
    minicpm-sala-9b at its held depth, no parameter materialised."""
    import json

    from benchmarks import sala_model
    from paddle_tpu.framework import random as prandom
    from paddle_tpu.models.minicpm_sala import MinicpmSalaForCausalLM

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "minicpm-sala-9b.json")) as f:
        raw = json.load(f)
    cfg = sala_model.load_config(raw)
    made = {}

    def make():
        with prandom.rng_guard(jax.random.PRNGKey(0)):
            made["model"] = MinicpmSalaForCausalLM(
                sala_model.model_config(cfg, SALA_MAX_LEN, "bfloat16"))
        return made["model"].raw_state_dict()

    return made, jax.eval_shape(make), raw


def test_sala_programs_at_the_cells_sizes(one_chip, monkeypatch):
    """`serve.decode_block` and `serve.ragged` of the real engine over
    selected K/V pages and state slots, lowered for the described v5e: the
    paged kernel is in (decode reads a table a K/V head), no copy is shaped
    like a K/V pool or a layer's state slots, no instruction makes every
    slot's compressed keys at once (the step's selector walks its live rows
    since PR 36: the all-slots gather was two fusions `bf16[100352,128]`,
    the second and third ops of the cell's trace), and arguments +
    temporaries are what the configuration file's `compile_memory_gib` says
    (under 15.0 GiB)."""
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine
    from paddle_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    made, state, raw = _abstract_sala()
    eng = ContinuousBatchingEngine(
        made["model"], max_seqs=SALA_ROWS, page_size=SALA_PAGE,
        max_len=SALA_MAX_LEN, prefill_chunk=SALA_CHUNK, decode_block=SALA_K,
        num_pages=2)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    st = {n: sds(v.shape, v.dtype) for n, v in state.items()}
    slots = (SALA_ROWS, 32, D, D)
    kv = (sds(SALA_POOL, jnp.bfloat16), sds(SALA_POOL, jnp.bfloat16),
          sds((SALA_HKV, SALA_POOL[1] * 4, D), jnp.bfloat16))
    pools = tuple(kv if kind == "minicpm4" else (sds(slots, jnp.float32),)
                  for kind in raw["mixer_types"])
    S, T = SALA_ROWS, SALA_CHUNK + SALA_ROWS
    i32, greedy = jnp.int32, (False, 1.0, 0, 1.0)
    table, per_row, keys = (sds((S, SALA_NPAGES), i32), sds((S,), i32),
                            sds((SALA_K, S, 2), jnp.uint32))
    lowered = {
        "decode_block": eng._decode_block_fn(greedy, SALA_K)._jitted.lower(
            st, sds((S, 1), i32), pools, table, per_row, per_row, keys),
        "ragged": eng._ragged_fn(greedy)._jitted.lower(
            st, sds((T,), i32), sds((S + 1,), i32), sds((T,), i32),
            sds((T,), i32), sds((T,), jnp.bool_), sds((S, 1), jnp.bool_),
            sds((S, 1), i32), pools, table, table, per_row, per_row, keys),
    }
    shapes = [f"bf16[{','.join(map(str, SALA_POOL))}]",
              f"f32[{','.join(map(str, slots))}]"]
    keys = SALA_NPAGES * 4            # compressed keys a row's table spans
    every_slot = [f"bf16[{SALA_ROWS * SALA_HKV * keys},{D}]",
                  f"bf16[{SALA_ROWS},{SALA_HKV},{keys},{D}]"]
    for name, low in lowered.items():
        compiled = low.compile()
        text = compiled.as_text()
        assert "%paged_attention" in text, name
        for shape in shapes:
            copies = re.findall(rf"= {re.escape(shape)}[^ ]* copy\(", text)
            assert not copies, f"{name}: {len(copies)} copies of {shape}"
        for shape in every_slot:
            made = re.findall(rf"= {re.escape(shape)}[^ ]* \w", text)
            assert not made, f"{name}: {len(made)} results of {shape}"
        ma = compiled.memory_analysis()
        gib = (ma.argument_size_in_bytes + ma.temp_size_in_bytes) / 2 ** 30
        stated = raw["compile_memory_gib"][name]
        assert abs(gib - stated) < 0.05 and gib < 15.0, (
            name, gib, stated, ma.argument_size_in_bytes / 2 ** 30,
            ma.temp_size_in_bytes / 2 ** 30)


# ---- the SambaY cell's two programs, at the cell's sizes -------------------

def _abstract_phi4flash():
    """(model, state shapes, configuration, the cell's engine knobs) of the
    benchmark's phi-4-mini-flash-reasoning, no parameter materialised."""
    import json

    from benchmarks import phi4flash_model
    from paddle_tpu.framework import random as prandom
    from paddle_tpu.models.phi4flash import (
        Phi4FlashConfig, Phi4FlashForCausalLM,
    )

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def read(*path):
        with open(os.path.join(here, "benchmarks", *path)) as f:
            return json.load(f)

    raw = read("configs", "phi-4-mini-flash-reasoning.json")
    knobs = read("workloads", "phi4flash-reasoning-steady.json")["engine"]
    cfg = phi4flash_model.load_config(raw)
    made = {}

    def make():
        with prandom.rng_guard(jax.random.PRNGKey(0)):
            made["model"] = Phi4FlashForCausalLM(Phi4FlashConfig(
                **{k: cfg[k] for k in phi4flash_model.MODEL_KEYS},
                max_position_embeddings=knobs["max_len"], dtype="bfloat16"))
        return made["model"].raw_state_dict()

    return made, jax.eval_shape(make), raw, knobs


def test_phi4flash_programs_at_the_cells_sizes(one_chip, monkeypatch):
    """`serve.decode_block` and `serve.ragged` of the real engine over state
    slots, window rings, ONE K/V pool and 14 layers with no pool, lowered
    for the described v5e, whole (32 layers, 3.85 B parameters): both
    kernels are in, no copy is shaped like layer 17's pool, a ring or a slot
    array, the mixed program's cross-decoder runs on max_seqs rows, and
    arguments + temporaries are what the configuration file's
    `compile_memory_gib` says (under 15.0 GiB)."""
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine
    from paddle_tpu.ops import flash_attention
    from paddle_tpu.ops.cache_specs import LayerCacheSpecs

    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    made, state, raw, kn = _abstract_phi4flash()
    assert sum(v.size for v in state.values()) == 3_852_457_984
    make_pools = LayerCacheSpecs.make_pools
    with monkeypatch.context() as mp:   # no 4.9 GB of zeros on this CPU
        mp.setattr(LayerCacheSpecs, "make_pools",
                   lambda self, *a, **k: [() for _ in self.layers])
        eng = ContinuousBatchingEngine(
            made["model"], **{k: kn[k] for k in (
                "max_seqs", "page_size", "max_len", "prefill_chunk",
                "decode_block")})

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S, K = kn["max_seqs"], kn["decode_block"]
    T = kn["prefill_chunk"] + S
    shapes = jax.eval_shape(lambda: make_pools(
        eng._cache_spec, eng.num_pages, kn["page_size"], jnp.bfloat16, None,
        max_seqs=S, prefill_chunk=kn["prefill_chunk"]))
    ring = -(-(512 + kn["prefill_chunk"]) // kn["page_size"]) + 1
    assert [tuple(a.shape for a in p) for p in shapes[:2]] == [
        ((S, 16, 5120), (S, 3, 5120)),
        ((10, 1 + S * ring, 64, 128),) * 2]
    assert shapes[17][0].shape == (10, eng.num_pages, 64, 128)
    assert all(p == () for p in shapes[18:])
    pools = tuple(tuple(sds(a.shape, a.dtype) for a in p) for p in shapes)
    st = {n: sds(v.shape, v.dtype) for n, v in state.items()}
    i32, greedy = jnp.int32, (False, 1.0, 0, 1.0)
    table, per_row, keys = (sds((S, kn["max_len"] // kn["page_size"]), i32),
                            sds((S,), i32), sds((K, S, 2), jnp.uint32))
    lowered = {
        "decode_block": eng._decode_block_fn(greedy, K)._jitted.lower(
            st, sds((S, 1), i32), pools, table, per_row, per_row, keys),
        "ragged": eng._ragged_fn(greedy)._jitted.lower(
            st, sds((T,), i32), sds((S + 1,), i32), sds((T,), i32),
            sds((T,), i32), sds((T,), jnp.bool_), sds((S, 1), jnp.bool_),
            sds((S, 1), i32), pools, table, table, per_row, per_row, keys),
    }
    pooled = sorted({
        f"{'bf16' if a.dtype == jnp.bfloat16 else 'f32'}"
        f"[{','.join(map(str, a.shape))}]" for p in shapes for a in p})
    for name, low in lowered.items():
        compiled = low.compile()
        text = compiled.as_text()
        assert "%paged_attention" in text, name
        assert ("%ragged_paged_attention" in text) == (name == "ragged")
        for shape in pooled:
            copies = re.findall(rf"= {re.escape(shape)}[^ ]* copy\(", text)
            assert not copies, f"{name}: {len(copies)} copies of {shape}"
        gmu = [line for line in text.splitlines()
               if "sambay.gmu" in line and " convolution(" in line]
        assert gmu and all(f"bf16[{S},5120]" in line for line in gmu), name
        ma = compiled.memory_analysis()
        gib = (ma.argument_size_in_bytes + ma.temp_size_in_bytes) / 2 ** 30
        stated = raw["compile_memory_gib"][name]
        assert abs(gib - stated) < 0.05 and gib < 15.0, (
            name, gib, stated, ma.argument_size_in_bytes / 2 ** 30,
            ma.temp_size_in_bytes / 2 ** 30)
