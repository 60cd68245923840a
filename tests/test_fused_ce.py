"""fused_linear_cross_entropy tests — value/grad parity with full-logits CE
(oracle pattern per SURVEY.md §4: kernel vs reference impl + grad check)."""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.incubate.nn.functional import fused_linear_cross_entropy
from paddle_tpu.models.llama import LlamaForCausalLM, LlamaPretrainingCriterion, llama_tiny
from paddle_tpu.nn import functional as F
from paddle_tpu.tensor import linalg


def _setup(n=37, h=16, v=50, seed=0, ignore_head=5):
    rng = np.random.RandomState(seed)
    hid = paddle.to_tensor(rng.randn(2, n, h).astype(np.float32), stop_gradient=False)
    w = paddle.to_tensor(rng.randn(h, v).astype(np.float32), stop_gradient=False)
    labels = rng.randint(0, v, (2, n))
    labels[0, :ignore_head] = -100
    y = paddle.to_tensor(labels.astype(np.int64))
    return hid, w, y


class TestFusedLinearCE:
    def test_matches_full_logits_value_and_grads(self):
        hid, w, y = _setup()
        loss = fused_linear_cross_entropy(hid, w, y, chunk_size=8)
        loss.backward()
        gh, gw = np.asarray(hid.grad.numpy()), np.asarray(w.grad.numpy())

        h2 = paddle.to_tensor(np.asarray(hid.numpy()), stop_gradient=False)
        w2 = paddle.to_tensor(np.asarray(w.numpy()), stop_gradient=False)
        ref = F.cross_entropy(linalg.matmul(h2, w2), y, ignore_index=-100)
        ref.backward()
        np.testing.assert_allclose(float(loss.numpy()), float(ref.numpy()), rtol=1e-5)
        np.testing.assert_allclose(gh, np.asarray(h2.grad.numpy()), rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(gw, np.asarray(w2.grad.numpy()), rtol=2e-4, atol=1e-6)

    def test_chunk_size_invariance(self):
        hid, w, y = _setup(n=24)
        vals = [
            float(fused_linear_cross_entropy(hid, w, y, chunk_size=c).numpy())
            for c in (4, 16, 48, 1024)
        ]
        np.testing.assert_allclose(vals, vals[0], rtol=1e-6)

    def test_all_ignored_is_finite(self):
        hid, w, _ = _setup()
        y = paddle.to_tensor(np.full((2, 37), -100, np.int64))
        loss = float(fused_linear_cross_entropy(hid, w, y).numpy())
        assert np.isfinite(loss) and loss == 0.0

    def test_llama_fused_flag_matches_unfused(self):
        paddle.seed(11)
        cfg = llama_tiny(fuse_linear_cross_entropy=True)
        model = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion()
        ids = np.random.RandomState(1).randint(0, cfg.vocab_size, (2, 17)).astype(np.int32)
        x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:].astype(np.int64))
        out = model(x)
        assert isinstance(out, tuple) and len(out) == 2
        fused = float(crit(*out, y).numpy())
        model.config.fuse_linear_cross_entropy = False
        logits = model(x)
        unfused = float(crit(logits.astype("float32"), y).numpy())
        np.testing.assert_allclose(fused, unfused, rtol=1e-4)


class TestChunkLoopUnroll:
    """The opt-in unroll path (FLAGS_fused_ce_unroll): same numerics as the
    while-loop path, no while op in the compiled HLO (the r5 xprof trace
    billed 8.2% of device time to while-loop control for a 3-iteration CE
    loop), and the barrier chain that sequences chunks on TPU present in
    the lowered program. The memory bound itself is TPU-only (XLA CPU
    strips opt-barrier) — not measured on the chip yet."""

    def _grad_fn(self, n=1024, h=64, v=8000, chunk=256):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.incubate.nn import functional as inf

        def fused(hid, w, y):
            out = inf.fused_linear_cross_entropy(hid, w, y, chunk_size=chunk)
            return (out._data if hasattr(out, "_data") else out).mean()

        rng = np.random.RandomState(3)
        hid = jnp.asarray(rng.randn(n, h).astype(np.float32))
        w = jnp.asarray(rng.randn(h, v).astype(np.float32))
        y = jnp.asarray(rng.randint(0, v, (n,)).astype(np.int32))
        return jax.grad(fused, argnums=(0, 1)), (hid, w, y)

    def test_unrolled_hlo_has_no_while_and_barrier_chain(self, monkeypatch):
        import jax

        def lowered(unroll):
            # fresh fn per lowering: jax's jit cache is keyed on the function
            # object and would otherwise reuse the first unroll's trace
            g, args = self._grad_fn()
            monkeypatch.setenv("FLAGS_fused_ce_unroll", str(unroll))
            return jax.jit(g).lower(*args)

        low_l, low_u = lowered(0), lowered(4)
        txt_l = low_l.compile().as_text()
        txt_u = low_u.compile().as_text()
        # the CHUNK loop must be gone from the unrolled lowering. Older
        # XLA:CPU additionally lowers the scatter-add inside
        # take_along_axis's transpose as its own while-loop (absent on newer
        # backends, and emitted once PER UNROLLED CHUNK here) — that is not
        # the loop this knob eliminates, so filter whiles by their op
        # metadata before asserting.
        def chunk_whiles(txt):
            return sum(1 for line in txt.splitlines()
                       if (" while(" in line or "while (" in line)
                       and "scatter" not in line)

        assert chunk_whiles(txt_l) >= 1
        assert chunk_whiles(txt_u) == 0, txt_u[:2000]
        # the sequencing chain must be in the lowered program (TPU honors it;
        # CPU strips it during optimization, hence asserting pre-optimization).
        # The loop path also carries a barrier or two from remat's own
        # lowering — assert the chunk chain on top of that floor. Floor is
        # loop+8: 4 forward chain barriers AND 4 transpose barriers — the
        # backward ones enforce the one-chunk bound where the peak lives, and
        # would be the first casualty if a JAX upgrade short-circuited the
        # barrier transpose on symbolic-zero cotangents.
        assert low_u.as_text().count("optimization_barrier") >= low_l.as_text().count(
            "optimization_barrier"
        ) + 8

    def test_unrolled_matches_loop_numerics(self, monkeypatch):
        g, args = self._grad_fn()
        monkeypatch.setenv("FLAGS_fused_ce_unroll", "0")
        gl_h, gl_w = g(*args)
        monkeypatch.setenv("FLAGS_fused_ce_unroll", "4")
        gu_h, gu_w = g(*args)
        np.testing.assert_allclose(np.asarray(gl_h), np.asarray(gu_h), rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(gl_w), np.asarray(gu_w), rtol=1e-6, atol=1e-7)
