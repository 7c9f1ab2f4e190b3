"""The comparison that decides ``correct``, one block part at a time: on
the GQA family's two equal parts it gives, float for float, what it gave
when it knew only K and V, and on parts of unequal width it reads each
part alone."""

import numpy as np
import pytest

from bench import check

BLOCK = 16


def _kv_only(got, ref_rows, first_block, layers, block):
    """The comparison as it was written for two equal K|V halves."""
    worst = 0.0
    for i, blk in enumerate(got):
        lo = (first_block + i) * block
        ref = ref_rows[lo:lo + block].reshape(block, 2, layers, -1)
        if blk is None:
            worst = max(worst, 1.0)
            continue
        diff = blk.astype(np.float32).reshape(ref.shape) - ref
        err = np.linalg.norm(diff, axis=(0, 3)) / np.linalg.norm(ref, axis=(0, 3))
        worst = max(worst, float(err.max()))
    return worst


def _blocks(rows, first, n):
    return [rows[i * BLOCK:(i + 1) * BLOCK].astype(np.float16) for i in range(first, first + n)]


# (layers, KV heads x d_head): the rehearsal's, qwen3-14b's and glm4-9b's
@pytest.mark.parametrize("layers,width", [(2, 32), (8, 1024), (20, 256)])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gqa_parts_equal_the_kv_comparison_bit_for_bit(layers, width, seed):
    rng = np.random.default_rng(seed)
    cols = 2 * layers * width
    rows = (rng.standard_normal((4 * BLOCK, cols))
            * rng.uniform(0.05, 5.0, cols)).astype(np.float32)
    noisy = (rows * (1 + 0.02 * rng.standard_normal(rows.shape))).astype(np.float32)
    blocks = _blocks(noisy, 1, 3)
    blocks[1][:, rng.integers(cols)] *= np.float16(1.3)  # one column of one block further off
    parts = [("k", width), ("v", width)]
    got = check.worst_block_error(blocks, rows, 1, parts, layers, BLOCK)
    assert got == _kv_only(blocks, rows, 1, layers, BLOCK)
    assert 0 < got < 1
    assert check.worst_block_error(blocks[:1] + [None], rows, 1, parts, layers, BLOCK) == 1.0


LAYERS = 3
LATENT = [("c", 512), ("kr", 64)]  # a latent and its rotary key, as MLA caches them


def _latent_rows():
    rng = np.random.default_rng(7)
    return rng.standard_normal((2 * BLOCK, LAYERS * (512 + 64))).astype(np.float32)


def test_unequal_parts_exact_blocks_read_rounding_only():
    rows = _latent_rows()
    assert check.worst_block_error(_blocks(rows, 0, 2), rows, 0, LATENT, LAYERS, BLOCK) < 1e-3


KR = LAYERS * 512  # first column of the second part


def _zero_kr(b):
    b[:, KR:] = 0
    return b


def _scale_kr_layer_1(b):
    b[:, KR + 64:KR + 128] *= np.float16(1.5)
    return b


def _zero_c_layer_2(b):
    b[:, 2 * 512:3 * 512] = 0
    return b


FAULTS = {
    "kr_zeroed": (_zero_kr, 1.0),
    "kr_of_one_layer_scaled": (_scale_kr_layer_1, 0.5),
    "c_of_one_layer_zeroed": (_zero_c_layer_2, 1.0),
    "kr_left_out": (lambda b: b[:, :KR], 1.0),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_unequal_parts_a_part_at_fault_reads_its_own_error(fault):
    make, expect = FAULTS[fault]
    rows = _latent_rows()
    blocks = _blocks(rows, 0, 2)
    blocks[1] = make(blocks[1].copy())
    got = check.worst_block_error(blocks, rows, 0, LATENT, LAYERS, BLOCK)
    assert got == pytest.approx(expect, abs=1e-3)
