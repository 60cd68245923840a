"""chip_smoke.py's own paths, rehearsed on the CPU.

The script is the standing proof that the system starts on the chip, so a
wrong argument or import in it costs a chip call. These tests run its
rehearsal mode in-process at the tiny size (both one-chip phases, and the
--chips 4 phase on four of conftest's virtual devices), and pin the three
things its verdict rests on: a non-TPU platform or a math-tier kernel fails
the real (non-rehearsal) path, a kernel that raises on a TPU is fatal in
every ops module, and the compile cache sits where it was placed from
outside.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

from paddle_tpu.ops import flash_attention as fa  # noqa: E402
from paddle_tpu.ops import paged_attention as pa  # noqa: E402
from paddle_tpu.ops import ragged_paged_attention as rpa  # noqa: E402
from paddle_tpu.ops import ring_attention as ra  # noqa: E402
from paddle_tpu.utils import compile_cache  # noqa: E402


@pytest.fixture(autouse=True)
def _no_cache_left_on():
    """main() turns the persistent compile cache on; the rest of the suite
    (this worker's later files too) runs with it as it was."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def _phases(out):
    return {rec["phase"]: rec for rec in map(json.loads, (
        line for line in out.splitlines() if line.startswith("{")))}


class TestRehearsal:
    def test_one_chip_phases_run_tiny_on_cpu(self, capsys):
        assert chip_smoke.main(["--rehearse"]) == 0
        out = capsys.readouterr().out
        assert '"ok"' not in out  # a rehearsal is never a result
        ph = _phases(out)
        train, serve = ph["train"], ph["serve"]
        assert len(train["losses"]) >= 3
        assert train["losses"][-1] < train["losses"][0]
        assert serve["requests"] >= 4 and serve["compiles_after_warmup"] == 0
        assert max(serve["prompt_lens"]) > chip_smoke.TINY.prefill_chunk
        # the rehearsal drives the real kernel body in interpret mode
        assert serve["ragged"] == "ragged-kernel-interpret"
        ref = serve["reference"]
        assert ref["checked"] == 2 * chip_smoke.TINY.new_tokens
        assert ref["exact"] == ref["checked"]  # f32 at tiny size: no ties

    def test_four_chip_phase_on_virtual_devices(self, capsys, monkeypatch):
        """--chips 4 on four virtual devices, with the Pallas tier stood in
        by the XLA math so the shard_map island around the kernel (GSPMD
        cannot partition a Mosaic kernel) runs here too: batch over
        sharding, heads over mp, loss parity with one device through three
        optimizer steps."""
        shapes = []

        def stand_in(q, k, v, causal, scale):
            shapes.append(q.shape)
            return fa._xla_attention(q, k, v, causal, scale)

        monkeypatch.setattr(fa, "_on_tpu", lambda: True)
        monkeypatch.setattr(fa, "_pallas_flash", stand_in)
        assert chip_smoke.main(["--rehearse", "--chips", "4"]) == 0
        out = capsys.readouterr().out
        assert '"ok"' not in out
        four = _phases(out)["four_chips"]
        assert set(_phases(out)) == {"start", "four_chips", "done"}
        assert four["devices_with_shards"] == 4
        assert four["collectives"]
        assert max(four["parity_deltas"]) < 1e-5  # f32 at tiny size
        b, h = chip_smoke.TINY.batch, 4
        assert (b, h) == shapes[0][:2]            # the one-device step
        assert (b // 2, h // 2) == shapes[-1][:2]  # per shard: sharding2, mp2


class TestVerdict:
    def test_no_tpu_fails_the_real_path(self, capsys):
        assert chip_smoke.main([]) != 0
        cap = capsys.readouterr()
        assert '"ok"' not in cap.out and "no TPU" in cap.err

    def test_math_tier_fails_the_real_path(self, capsys):
        """On the CPU attention lowers to the XLA math tier: with the
        kernel-tier checks on (what a run without --rehearse does) the
        train phase must refuse it."""
        with pytest.raises(chip_smoke.SmokeFailure, match="'xla'"):
            chip_smoke.train_phase(chip_smoke.TINY, seed=0, strict=True)
        assert '"ok"' not in capsys.readouterr().out

    @pytest.mark.parametrize("impl,allowed", [
        ("ragged-math", {"ragged-kernel"}),
        ("ragged-kernel-interpret", {"ragged-kernel"}),
        ("paged-math", {"paged-kernel"}),
        (None, {"pallas", "splash"}),
    ])
    def test_require_tier(self, impl, allowed):
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke._require_tier("attention", impl, allowed, strict=True)
        chip_smoke._require_tier("attention", impl, allowed, strict=False)


class _KernelRefused(Exception):
    pass


def _refuse(*a, **k):
    raise _KernelRefused("Mosaic refused the kernel")


def _call_flash():
    x = jnp.zeros((1, 128, 2, 64), jnp.float32)
    return fa.flash_attention_fwd(x, x, x, causal=True)


def _call_splash():
    q = jnp.zeros((1, 128, 4, 64), jnp.float32)
    kv = jnp.zeros((1, 128, 2, 64), jnp.float32)
    return fa.flash_attention_fwd(q, kv, kv, causal=True)


def _call_packed():
    x = jnp.zeros((1, 128, 2, 64), jnp.float32)
    return fa.flash_attention_packed(x, x, x, jnp.zeros((1, 128), jnp.int32))


def _call_varlen():
    x = jnp.zeros((128, 2, 64), jnp.float32)
    cu = jnp.asarray([0, 128], jnp.int32)
    return fa.flash_attention_varlen_fwd(x, x, x, cu, cu, same_offsets=True)


def _call_paged():
    q = jnp.zeros((2, 2, 64), jnp.float32)
    pool = jnp.zeros((2, 5, 8, 64), jnp.float32)
    return pa.paged_decode_attention(
        q, pool, pool, jnp.ones((2,), jnp.int32),
        jnp.zeros((2, 2), jnp.int32))


def _call_paged_int8():
    q = jnp.zeros((2, 2, 64), jnp.float32)
    pool = pa.quantize_pages(jnp.zeros((2, 5, 8, 64), jnp.float32))
    return pa.paged_decode_attention(
        q, pool, pool, jnp.ones((2,), jnp.int32),
        jnp.zeros((2, 2), jnp.int32))


def _call_ragged():
    q = jnp.zeros((8, 2, 64), jnp.float32)
    pool = jnp.zeros((2, 5, 8, 64), jnp.float32)
    return rpa.ragged_paged_attention(
        q, pool, pool, jnp.ones((2,), jnp.int32),
        jnp.zeros((2, 2), jnp.int32), jnp.asarray([0, 1, 2], jnp.int32))


def _call_ring():
    x = jnp.zeros((1, 2, 128, 64), jnp.float32)
    return ra.ring_attention(x, x, x, axis_name="sep", causal=True)


class TestKernelFailureIsFatalOnTpu:
    """No site in paddle_tpu/ops catches a kernel exception when
    `_on_tpu()`: a kernel the chip's compiler refuses must fail the run, not
    become the XLA math path with the run still 'passing'."""

    @pytest.mark.parametrize("call,kernel", [
        (_call_flash, "paddle_tpu.ops.flash_attention._pallas_flash"),
        (_call_splash, "paddle_tpu.ops.flash_attention._splash_impl"),
        (_call_packed, "paddle_tpu.ops.flash_attention._splash_kernel"),
        (_call_varlen, "paddle_tpu.ops.flash_attention._splash_varlen"),
        (_call_paged, "paddle_tpu.ops.paged_attention._paged_pallas"),
        (_call_paged_int8, "jax.experimental.pallas.ops.tpu.paged_attention"
                           ".paged_attention"),
        (_call_ragged,
         "paddle_tpu.ops.ragged_paged_attention._ragged_pallas"),
        (_call_ring, "paddle_tpu.ops.ring_attention._ring_kernel"),
    ], ids=["flash", "splash", "packed", "varlen", "paged", "paged-int8", "ragged",
            "ring"])
    def test_kernel_exception_propagates(self, call, kernel, monkeypatch):
        monkeypatch.setattr(fa, "_on_tpu", lambda: True)
        monkeypatch.setattr(kernel, _refuse)
        with pytest.raises(_KernelRefused):
            call()

    def test_on_tpu_does_not_swallow(self, monkeypatch):
        def broken():
            raise RuntimeError("backend failed to initialize")

        monkeypatch.setattr(jax, "devices", broken)
        with pytest.raises(RuntimeError, match="failed to initialize"):
            fa._on_tpu()


class TestCompileCachePlacement:
    def test_env_var_wins_and_code_sets_nothing(self, monkeypatch, tmp_path):
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_a_fixed_path_in_the_checkout(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert compile_cache.enable_compile_cache() == want  # never moves
        assert jax.config.jax_compilation_cache_dir == want
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_import_does_not_place_a_cache(self):
        """`import paddle_tpu` must not turn the cache on: only the entry
        points do."""
        import subprocess

        code = ("import jax, paddle_tpu; "
                "print(jax.config.jax_compilation_cache_dir)")
        env = {k: v for k, v in os.environ.items()
               if k != compile_cache.ENV_VAR}
        out = subprocess.run(
            [sys.executable, "-c", code], env=env, text=True,
            capture_output=True, timeout=120,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr[-400:]
        assert out.stdout.strip().splitlines()[-1] == "None"


def test_reference_check_rejects_a_wrong_token(monkeypatch):
    """The serve phase's comparison itself: a served token far below the
    reference argmax fails, one within a few bf16 steps of a tie passes
    and is counted."""
    vocab, n_prompt = 16, 3
    logits = np.zeros((1, 128, vocab), np.float32)
    logits[0, :, 5] = 4.0           # the reference wants token 5 everywhere
    logits[0, :, 6] = 4.0 - 2 ** -5  # token 6: one bf16 step below
    monkeypatch.setattr(chip_smoke, "_reference_logits",
                        lambda model, rows: logits)
    prompts = [np.arange(n_prompt)]
    ok = chip_smoke._check_against_reference(
        None, prompts, [np.array([0, 1, 2, 5, 5, 5, 5, 5, 5, 5, 6])], [0])
    assert ok["checked"] == 8 and ok["near_tie"] == 1
    with pytest.raises(chip_smoke.SmokeFailure, match="bf16 steps below"):
        chip_smoke._check_against_reference(
            None, prompts, [np.array([0, 1, 2, 5, 9])], [0])
    with pytest.raises(chip_smoke.SmokeFailure, match="near-tie allowance"):
        chip_smoke._check_against_reference(
            None, prompts, [np.array([0, 1, 2, 6, 6, 5])], [0])
