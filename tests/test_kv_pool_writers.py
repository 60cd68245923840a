"""The two KV-pool writers store what a plain loop over tokens stores.

`write_token_kv` (one token a row, the decode scan's writer) and
`write_ragged_kv` (the packed stream's writer) are compared bit for bit
with a numpy loop that puts each token's row of every head at
`pool[h, page, off]`. The int8 pool's rows are quantised by jax's own
`quantize_to_int8` on both sides: what is under test is where a row lands,
in which dtype, and that nothing else is touched. How the write compiles
for the chip (no whole-pool re-layout) is tests/test_chip_compile.py's.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu.paged_attention import (
    quantization_utils as qu,
)

from paddle_tpu.ops.paged_attention import quantize_pages, write_token_kv
from paddle_tpu.ops.ragged_paged_attention import write_ragged_kv

HKV, BS, D, ROWS, PAGES_ROW = 3, 4, 8, 5, 3
P = 1 + ROWS * PAGES_ROW
TABLE = np.arange(1, P, dtype=np.int32).reshape(ROWS, PAGES_ROW)


def _pool(kind, rng):
    start = rng.randn(HKV, P, BS, D).astype(np.float32)
    if kind == "int8":
        return quantize_pages(jnp.asarray(start))
    return jnp.asarray(start, jnp.bfloat16)


def _planes(pool):
    """The pool's arrays as numpy, in a fixed order."""
    if hasattr(pool, "weight"):
        return [np.asarray(pool.weight), np.asarray(pool.scales)]
    return [np.asarray(pool)]


def _rows(kind, new):
    """new [N, Hkv, D] -> per plane, the [N, Hkv, last] rows to store."""
    if kind == "int8":
        qt = qu.quantize_to_int8(jnp.asarray(new, jnp.float32))
        return [np.asarray(qt.weight), np.asarray(qt.scales)]
    return [np.asarray(jnp.asarray(new).astype(jnp.bfloat16))]


def _loop_write(planes, kind, page_of, off, new):
    """The reference: token by token, head by head."""
    for plane, rows in zip(planes, _rows(kind, new)):
        for n in range(len(page_of)):
            for h in range(HKV):
                plane[h, page_of[n], off[n]] = rows[n, h]


def _check(pool, want, pads):
    """Bit identity everywhere but the scratch slot (0, 0), where pads
    collide with each other in no stated order: it holds one of them."""
    for got, ref, rows in zip(_planes(pool), want, pads):
        keep = np.ones(got.shape[1:3], bool)
        keep[0, 0] = False
        np.testing.assert_array_equal(got[:, keep], ref[:, keep])
        assert any(np.array_equal(got[:, 0, 0], r) for r in rows)


def _decode_steps():
    """(table, lengths) per write: rows 0-1 live, rows 2 and 4 routed to
    scratch (a scan-table row of zeros and cap 0, as the engine sends an
    empty slot), row 3 frozen at its cap, so it writes one slot twice."""
    table = TABLE.copy()
    table[[2, 4]] = 0
    lens = np.array([BS - 2, 2 * BS - 1, 0, 5, 0], np.int32)
    steps = []
    for _ in range(3):          # row 0 and row 1 both cross a page boundary
        steps.append((table, lens.copy()))
        lens = lens + np.array([1, 1, 0, 0, 0], np.int32)
    return steps


def _ragged_steps():
    """(row_of, token_pos, valid) per write. First: row 0 a chunk that
    crosses two page boundaries, row 1 no token at all (q_len 0), row 2 one
    decode token, three pads. Then row 2 again at the same position (a row
    frozen at its cap) beside a chunk for row 3, and more pads. Last: every
    row writes two tokens across a page boundary, the most page changes a
    stream of that length can hold (the page-wise writer's bound)."""
    first = (np.array([0] * 7 + [2] + [0] * 3, np.int32),
             np.array(list(range(2, 9)) + [6] + [0] * 3, np.int32),
             np.array([True] * 8 + [False] * 3))
    second = (np.array([2] + [3] * 5 + [1] * 5, np.int32),
              np.array([6] + list(range(3, 8)) + [9] * 5, np.int32),
              np.array([True] * 6 + [False] * 5))
    third = (np.array([r for r in range(ROWS) for _ in range(2)] + [0, 0],
                      np.int32),
             np.array([BS - 1, BS] * ROWS + [0, 0], np.int32),
             np.array([True] * (2 * ROWS) + [False] * 2))
    return [first, second, third]


@pytest.mark.parametrize("writer", ["decode", "ragged"])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_writer_matches_token_loop(kind, writer):
    rng = np.random.RandomState(7)
    pool = _pool(kind, rng)
    want = [p.copy() for p in _planes(pool)]
    for step in (_decode_steps() if writer == "decode" else _ragged_steps()):
        if writer == "decode":
            table, lens = step
            new = rng.randn(ROWS, HKV, D).astype(np.float32)
            page_of, off = table[np.arange(ROWS), lens // BS], lens % BS
            is_pad = page_of == 0
            pool = write_token_kv(pool, jnp.asarray(table), jnp.asarray(lens),
                                  jnp.asarray(new))
        else:
            row_of, pos, valid = step
            new = rng.randn(len(row_of), HKV, D).astype(np.float32)
            page_of = np.where(valid, TABLE[row_of, pos // BS], 0)
            off = np.where(valid, pos % BS, 0)
            is_pad = ~valid
            pool = write_ragged_kv(pool, jnp.asarray(TABLE),
                                   jnp.asarray(row_of), jnp.asarray(pos),
                                   jnp.asarray(valid), jnp.asarray(new))
        assert is_pad.sum() > 1 and (off[is_pad] == 0).all()
        _loop_write(want, kind, page_of[~is_pad], off[~is_pad], new[~is_pad])
        _check(pool, want, [r[is_pad] for r in _rows(kind, new)])
        # the next write starts from what the program left in the scratch slot
        for ref, got in zip(want, _planes(pool)):
            ref[:, 0, 0] = got[:, 0, 0]
    if kind == "int8":
        assert _planes(pool)[0].dtype == np.int8
        assert _planes(pool)[1].dtype == np.float32
