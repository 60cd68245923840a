"""The splash GQA kernel's tiles are a function of the shape (PR 30).

Three things a CPU suite can hold: the rule's own contract (every tile a
multiple of 128 that divides its sequence, under the caps the v5e's VMEM
set, the backward fused only while its dq partials stay small, no
environment variable in it); and the kernel's numerics at the rule's tiles
in interpret mode, dense-causal and with SegmentIds, against plain dense
attention — off the chip the ops take the math path, so nothing else in
tier-1 runs this kernel. That the chip's compiler takes the tiles is
tests/test_chip_compile.py's."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as sk,
    splash_attention_mask as sm,
)

from paddle_tpu.ops import flash_attention as fa

SEQS = (128, 384, 1024, 2048, 4096, 32768)
HEAD_DIMS = (64, 128, 256, 512)


def _tiles(bs):
    return {n: getattr(bs, n) for n in (
        "block_q", "block_kv", "block_kv_compute", "block_q_dkv",
        "block_kv_dkv", "block_kv_dkv_compute", "block_q_dq", "block_kv_dq",
        "use_fused_bwd_kernel")}


@pytest.mark.parametrize("head_dim", HEAD_DIMS)
@pytest.mark.parametrize("seq", SEQS)
def test_rule_tiles_divide_and_stay_under_the_caps(seq, head_dim):
    bs = fa._splash_block_sizes(seq, seq, head_dim)
    t = _tiles(bs)
    fused = t.pop("use_fused_bwd_kernel")
    q_tiles = [t["block_q"], t["block_q_dkv"]]
    kv_tiles = [t["block_kv"], t["block_kv_dkv"]]
    if fused:  # the fused backward takes no dq tiles
        assert t["block_q_dq"] is None and t["block_kv_dq"] is None
        # one partial of dq per key block, in HBM: bounded
        assert (seq // t["block_kv_dkv"]) * seq * head_dim <= 8 << 20
    else:
        q_tiles.append(t["block_q_dq"])
        kv_tiles.append(t["block_kv_dq"])
    assert bs.has_backward_blocks
    cap = 1024 if head_dim <= 256 else 512   # VMEM: tile x head_dim
    for b in q_tiles + kv_tiles:
        assert b % 128 == 0 and seq % b == 0 and b <= min(cap, seq)
    for mem, comp in (("block_kv", "block_kv_compute"),
                      ("block_kv_dkv", "block_kv_dkv_compute")):
        assert t[comp] % 128 == 0 and t[comp] <= 512
        assert t[mem] % t[comp] == 0
    # the library's 128 x 128 default only where the sequence is that short
    assert t["block_q"] == min(cap, seq) or seq % cap


def test_rule_at_the_training_cells_shape():
    """mistral7b-pretrain-4k: the sweep's winner (PERF.md §6, PR 30)."""
    assert _tiles(fa._splash_block_sizes(4096, 4096, 128)) == {
        "block_q": 1024, "block_kv": 1024, "block_kv_compute": 512,
        "block_q_dkv": 1024, "block_kv_dkv": 1024,
        "block_kv_dkv_compute": 512, "block_q_dq": None, "block_kv_dq": None,
        "use_fused_bwd_kernel": True}
    # a sequence no power of two divides: the largest tile that does
    odd = fa._splash_block_sizes(640, 1536, 128)
    assert (odd.block_q, odd.block_kv, odd.block_kv_compute) == (640, 768, 384)


def test_environment_does_not_reach_the_tiles(monkeypatch):
    """FLAGS_splash_block_q/kv are gone, not renamed: the built kernel's
    tiles are the rule's whatever the environment says."""
    want = fa._splash_block_sizes(1024, 1024, 128)
    monkeypatch.setenv("FLAGS_splash_block_q", "128")
    monkeypatch.setenv("FLAGS_splash_block_kv", "128")
    kernel = fa._splash_kernel(2, 1024, 1024, 128, True, cache_tag="env-test")
    built = kernel.kwargs["block_sizes"]
    assert built == want and built.block_q == 1024
    # the mask tables were cut to the same tiles
    assert kernel.fwd_mask_info.block_mask.shape[-2:] == (1, 1)


def _dense(q, k, v, seg, scale):
    g = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, g, axis=0), jnp.repeat(v, g, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q, k, precision="highest") * scale
    n = q.shape[1]
    mask = jnp.tril(jnp.ones((n, n), bool))
    if seg is not None:
        mask = mask & (seg[:, None] == seg[None, :])
    p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p, v, precision="highest")


@pytest.mark.parametrize("segmented", [False, True],
                         ids=["causal", "segment-ids"])
@pytest.mark.parametrize("seq", [384, 1024, 2048])
def test_kernel_at_the_rules_tiles_matches_dense(seq, segmented):
    """GQA 4 / 2, head 128, f32, interpret mode: forward and the three
    gradients (the fused backward at these lengths) against dense."""
    hq, hkv, d = 4, 2, 128
    bs = fa._splash_block_sizes(seq, seq, d)
    assert bs.use_fused_bwd_kernel
    mask = sm.MultiHeadMask([sm.CausalMask((seq, seq)) for _ in range(hq)])
    kernel = sk.make_splash_mha(mask=mask, head_shards=1, q_seq_shards=1,
                                block_sizes=bs, interpret=True)
    rng = np.random.RandomState(seq)
    q, k, v, w = (jnp.asarray(rng.standard_normal((h, seq, d)), jnp.float32)
                  for h in (hq, hkv, hkv, hq))
    seg = None
    if segmented:  # three documents, cut off the tile lattice
        cuts = (seq // 3 + 5, 2 * seq // 3 - 7)
        seg = jnp.asarray(np.searchsorted(cuts, np.arange(seq), side="right"),
                          jnp.int32)
    scale = d ** -0.5

    def splash(q, k, v):
        ids = sk.SegmentIds(q=seg, kv=seg) if segmented else None
        return kernel(q * scale, k, v, segment_ids=ids)

    def loss(f):
        return lambda *a: (f(*a) * w).sum()

    ref = _dense(q, k, v, seg, scale)
    np.testing.assert_allclose(splash(q, k, v), ref, atol=2e-5, rtol=0)
    got = jax.grad(loss(splash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda *a: _dense(*a, seg, scale)),
                    argnums=(0, 1, 2))(q, k, v)
    for name, g, r in zip("qkv", got, want):
        np.testing.assert_allclose(g, r, atol=2e-3, rtol=0,
                                   err_msg=f"d{name}")
