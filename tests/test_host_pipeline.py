"""The pooled host DA pipeline at tiny k with an explicit 2-thread pool.

Covers the hostpool configuration chain (``--cpu-threads``), the
overlapped native extend->roots pipeline, the pooled host repair, the
host DAH fast path and the no-native numpy fallbacks, each asserted
byte-identical to its reference construction.
"""

import numpy as np
import pytest

from celestia_tpu.ops import gf256, rs
from celestia_tpu.ops import nmt as nmt_ops
from celestia_tpu.utils import hostpool, native


@pytest.fixture
def two_thread_pool():
    """Pin the process pool to 2 workers for the duration of a test."""
    hostpool.set_cpu_threads(2)
    yield
    hostpool.set_cpu_threads(None)


@pytest.fixture
def leopard_codec():
    prev = gf256.active_codec()
    gf256.set_active_codec(gf256.CODEC_LEOPARD)
    yield
    gf256.set_active_codec(prev)


def test_hostpool_resolution_chain(monkeypatch):
    """explicit set > env var > os.cpu_count, and the executor tracks
    the resolved size."""
    monkeypatch.delenv("CELESTIA_TPU_CPU_THREADS", raising=False)
    hostpool.set_cpu_threads(None)
    import os

    assert hostpool.cpu_threads() == (os.cpu_count() or 1)
    monkeypatch.setenv("CELESTIA_TPU_CPU_THREADS", "3")
    assert hostpool.cpu_threads() == 3
    monkeypatch.setenv("CELESTIA_TPU_CPU_THREADS", "bogus")
    assert hostpool.cpu_threads() == (os.cpu_count() or 1)
    hostpool.set_cpu_threads(2)
    try:
        assert hostpool.cpu_threads() == 2
        assert hostpool.get_pool()._max_workers == 2
        assert hostpool.run_sharded(lambda x: x * x, range(5)) == [
            0, 1, 4, 9, 16,
        ]
        with pytest.raises(ValueError):
            hostpool.set_cpu_threads(0)
    finally:
        hostpool.set_cpu_threads(None)


def test_cli_cpu_threads_flag():
    """--cpu-threads routes to the process pool (and is cleaned up)."""
    from celestia_tpu import cli

    parser = cli.build_parser()
    args = parser.parse_args(["--cpu-threads", "2", "keys", "list"])
    assert args.cpu_threads == 2
    try:
        hostpool.set_cpu_threads(args.cpu_threads)
        assert hostpool.cpu_threads() == 2
    finally:
        hostpool.set_cpu_threads(None)


@pytest.mark.skipif(not native.available(), reason="native lib unavailable")
def test_threaded_extend_repair_dah_smoke(two_thread_pool, leopard_codec):
    """One pass of every new threaded path at k=8 with the pool at 2."""
    k = 8
    rng = np.random.default_rng(42)
    sq = rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    # overlapped native pipeline, pool default (2 threads)
    eds, roots, droot = native.extend_block_leopard_cpu(sq)
    ref = native.extend_block_leopard_cpu(sq, nthreads=1)
    assert np.array_equal(eds, ref[0])
    assert np.array_equal(roots, ref[1])
    assert np.array_equal(droot, ref[2])
    # pooled host repair (bench _host_repair_ms path), root-verified
    avail = rng.random((2 * k, 2 * k)) >= 0.25
    damaged = eds.copy()
    damaged[~avail] = 0
    fixed = rs.repair_square(
        damaged, avail, row_roots=roots[: 2 * k], col_roots=roots[2 * k :]
    )
    assert np.array_equal(fixed, eds)
    # host-regime DAH fast path (tests pin the CPU backend, so
    # extend_and_header routes through the native pipeline here)
    from celestia_tpu.da import dah as dah_mod

    eds2, dah = dah_mod.extend_and_header(sq)
    assert np.array_equal(eds2.shares, eds)
    assert dah.row_roots == tuple(roots[i].tobytes() for i in range(2 * k))
    assert dah.hash == dah_mod.DataAvailabilityHeader.compute_hash(
        dah.row_roots, dah.col_roots
    )
    dah.validate_basic()
    # pooled standalone root shard == the overlapped pipeline's roots
    assert np.array_equal(
        nmt_ops.eds_nmt_roots_host(eds),
        roots.reshape(2, 2 * k, 90),
    )


def test_numpy_fallbacks_match_native(two_thread_pool, monkeypatch):
    """The no-native pool fallbacks (hashlib SHA shards, numpy NMT
    reduction) must be byte-identical to the reference paths."""
    import hashlib

    from celestia_tpu.ops import sha256 as sha_ops

    rng = np.random.default_rng(7)
    msgs = rng.integers(0, 256, (9, 91), dtype=np.uint8)
    want = np.stack(
        [
            np.frombuffer(hashlib.sha256(m.tobytes()).digest(), dtype=np.uint8)
            for m in msgs
        ]
    )
    if native.available():
        assert np.array_equal(sha_ops.sha256_batch_host(msgs), want)
    k = 2
    eds = np.asarray(rs.extend_square(rng.integers(0, 256, (k, k, 512), dtype=np.uint8)))
    want_roots = np.asarray(nmt_ops.eds_nmt_roots(eds))
    monkeypatch.setattr(native, "available", lambda: False)
    assert np.array_equal(sha_ops.sha256_batch_host(msgs), want)
    got = nmt_ops.eds_nmt_roots_host(eds, nthreads=2)
    assert np.array_equal(got, want_roots)
