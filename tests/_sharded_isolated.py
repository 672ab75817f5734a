"""Sharded (multi-chip) extension tests on the virtual 8-device CPU mesh.

Validates that the shard_map pipeline (row-sharded RS extension, column
parity on whole columns exchanged by all_to_all, column NMT trees hashed
whole on the chip that holds them) is bit-identical to the
single-device path — the consensus-safety requirement of SURVEY.md §2.3.
"""

import numpy as np
import pytest
import jax

from celestia_tpu.da import dah as dah_mod
from celestia_tpu.ops import nmt, rs
from celestia_tpu.parallel import sharded


def _roots_ref(eds_ref):
    return np.asarray(jax.jit(nmt.eds_nmt_roots)(eds_ref))


@pytest.mark.parametrize(
    "row_shards, k, batch",
    [
        pytest.param(2, 8, None, id="2"),
        pytest.param(4, 8, None, id="4"),
        pytest.param(8, 8, None, id="8"),
        # the catch-up cell's factoring (data 1 x row 4), batched
        pytest.param(4, 16, 2, id="1x4-batch2-k16"),
        # BASELINE config #5 / VERDICT r2 #9: the production 128x128
        # square, whose tile shapes (2k/R column blocks, the exchanged
        # (k/R, 2k, 512) blocks) k=8 cannot exercise
        pytest.param(8, 128, None, id="full_size-1x8"),
        pytest.param(4, 128, 2, id="full_size-1x4-batch2"),
    ],
)
def test_sharded_matches_single_device(row_shards, k, batch):
    mesh = sharded.make_mesh(jax.devices()[:row_shards], data=1, row=row_shards)
    rng = np.random.default_rng((k, row_shards))
    if batch is None:
        sqs = rng.integers(0, 256, (1, k, k, 512), dtype=np.uint8)
        outs = [sharded.extend_and_roots_sharded(sqs[0], mesh)]
    else:
        sqs = rng.integers(0, 256, (batch, k, k, 512), dtype=np.uint8)
        eds_b, rr_b, cc_b, dr_b = sharded.extend_and_roots_sharded_batch(sqs, mesh)
        outs = [(eds_b[i], rr_b[i], cc_b[i], dr_b[i]) for i in range(batch)]
    for sq, (eds, rr, cc, droot) in zip(sqs, outs):
        eds_ref = np.asarray(rs.extend_square(sq))
        assert np.array_equal(eds, eds_ref)
        roots = _roots_ref(eds_ref)
        assert np.array_equal(rr, roots[0])
        assert np.array_equal(cc, roots[1])
        want = dah_mod.DataAvailabilityHeader.compute_hash(
            [roots[0][i].tobytes() for i in range(2 * k)],
            [roots[1][i].tobytes() for i in range(2 * k)],
        )
        assert droot.tobytes() == want


def test_sharded_batched_data_axis():
    mesh = sharded.make_mesh(data=2, row=4)
    rng = np.random.default_rng(9)
    k = 8
    sqs = rng.integers(0, 256, (4, k, k, 512), dtype=np.uint8)
    eds_b, rr_b, cc_b, dr_b = sharded.extend_and_roots_sharded_batch(sqs, mesh)
    for i in range(4):
        ref = np.asarray(rs.extend_square(sqs[i]))
        assert np.array_equal(eds_b[i], ref)
        roots = _roots_ref(ref)
        assert np.array_equal(rr_b[i], roots[0])
        assert np.array_equal(cc_b[i], roots[1])


def test_mesh_validation():
    with pytest.raises(ValueError):
        sharded.make_mesh(jax.devices(), data=3, row=4)
    mesh = sharded.make_mesh(data=1, row=8)
    with pytest.raises(ValueError, match="divisible"):
        from celestia_tpu.ops import gf256

        # k=4 rows over 8 shards (codec is a required cache key)
        sharded._sharded_fn(mesh, 4, False, gf256.active_codec())
