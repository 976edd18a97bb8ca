"""Fused Pallas GF kernel vs the jnp path (interpret mode on CPU):
bit-identity across codemodes, odd lengths (padding), batched stripes,
and the engine registration."""

import numpy as np
import pytest

from cubefs_tpu.ops import gf256, pallas_gf, rs_kernel


@pytest.mark.parametrize("n,m", [(12, 4), (6, 3), (24, 8)])
def test_pallas_encode_bit_identical(n, m, rng):
    data = rng.integers(0, 256, (n, 512)).astype(np.uint8)
    pm = gf256.parity_matrix(n, m)
    got = np.asarray(pallas_gf.gf_matrix_apply_pallas(pm, data, tile=256))
    expect = np.asarray(rs_kernel.gf_matrix_apply(pm, data))
    assert np.array_equal(got, expect)


def test_pallas_padding_path(rng):
    n, m = 6, 3
    data = rng.integers(0, 256, (n, 777)).astype(np.uint8)  # not a tile multiple
    pm = gf256.parity_matrix(n, m)
    got = np.asarray(pallas_gf.gf_matrix_apply_pallas(pm, data, tile=256))
    assert np.array_equal(got, gf256.gf_matmul(pm, data))


def test_pallas_batched_reconstruct(rng):
    n, total = 12, 16
    enc = gf256.encode_matrix(n, total)
    data = rng.integers(0, 256, (3, n, 256)).astype(np.uint8)
    shards = np.stack([gf256.gf_matmul(enc, d) for d in data])
    bad = [1, 7]
    present = [i for i in range(total) if i not in bad]
    rows = rs_kernel.reconstruct_rows(n, total, present, bad)
    got = np.asarray(pallas_gf.gf_matrix_apply_pallas(
        rows, shards[:, present[:n]], tile=256))
    assert np.array_equal(got, shards[:, bad])
