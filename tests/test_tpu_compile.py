"""The chip's programs compile for a described TPU v5e, with no chip.

The TPU compiler is installed here and compiles for a chip that is
described and not attached (on-chip-measurement guide, section 2): what
the chip's compiler would refuse — a tiling, a memory bound, a
partitioning — fails here at no chip time.  These are the pieces of the
served path at validator sizes, a few seconds each.  The fused k=128
programs (device_plane._extend_levels_fn and friends) take about a
minute each, so they stay in the rehearsal before a chip call.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this
file.  All tests live in this one file so that one worker loads it.
"""

import os
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from celestia_tpu.ops import gf256, rs
from celestia_tpu.ops import nmt as nmt_ops

K = 128  # the square-size cap (appconsts.py)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype=jnp.uint8):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_rs_extend_k128(shape):
    G = jnp.asarray(gf256.encode_matrix_bits(K, gf256.active_codec()))
    c = _compile(partial(rs._extend, G=G), shape((K, K, 512)))
    assert c.memory_analysis().output_size_in_bytes == (2 * K) ** 2 * 512


def test_nmt_leaf_digests_k128(shape):
    # one axis of the EDS: 2k rows of 2k namespace-prefixed leaves
    c = _compile(nmt_ops.leaf_digests, shape((2 * K, 2 * K, 29 + 512)))
    out = (2 * K) * (2 * K) * nmt_ops.NMT_DIGEST_SIZE
    assert c.memory_analysis().output_size_in_bytes == out


def test_nmt_combine_level_k128(shape):
    c = _compile(
        nmt_ops.combine_level,
        shape((2, 2 * K, 2 * K, nmt_ops.NMT_DIGEST_SIZE)),
    )
    out = 2 * (2 * K) * K * nmt_ops.NMT_DIGEST_SIZE
    assert c.memory_analysis().output_size_in_bytes == out


def test_rfc6962_level_combine_k128(shape):
    # the first inner level of the data-root tree over the 4k axis roots
    def level(nodes):
        return nmt_ops.rfc6962_inner(nodes[0::2], nodes[1::2])

    c = _compile(level, shape((4 * K, 32)))
    assert c.memory_analysis().output_size_in_bytes == 2 * K * 32


def test_repair_verify_k32(shape):
    # decode + both byzantine checks; the roots leg is the NMT code the
    # tests above compile at k=128
    k = 32
    n2 = 2 * k
    avail = np.random.default_rng(0).random((n2, n2)) >= 0.25
    rk, rm, ck, cm = rs._simulate_schedule(avail, k)
    fn = rs._repair_verify_fn(
        k, rk.shape[0], min(n2, max(1, 8192 // k)), False,
        gf256.active_codec(),
    )
    c = fn.lower(
        shape((n2, n2, 512)), shape(avail.shape, jnp.bool_), shape((2, n2, 90)),
        shape(rk.shape), shape(rm.shape, jnp.bool_),
        shape(ck.shape), shape(cm.shape, jnp.bool_),
    ).compile()
    assert c.memory_analysis().temp_size_in_bytes > 0
