"""Concurrency/race harness: threaded clients against shared node state.

VERDICT r1 coverage gap #47 (race detection): the signer holds its lock
across sign -> broadcast -> sequence-increment, and the node service lock
serialises app access; these tests hammer both from many threads and assert
the invariants that would break under a race (unique sequences, no lost or
double-spent txs, consistent balances).  Reference analogue: `make
test-race` + the signer mutex held across broadcastTx
(pkg/user/signer.go:44-55).
"""

import threading

import pytest

from celestia_tpu.client.signer import Signer
from celestia_tpu.da.blob import Blob
from celestia_tpu.da.namespace import Namespace
from celestia_tpu.node.server import NodeServer
from celestia_tpu.node.testnode import TestNode
from celestia_tpu.state.tx import MsgSend
from celestia_tpu.utils.secp256k1 import PrivateKey

N_THREADS = 8
TX_PER_THREAD = 4


def test_shared_signer_concurrent_submits():
    """One signer, many threads: every tx must land with a unique sequence
    and every transfer must be applied exactly once."""
    alice = PrivateKey.from_seed(b"race-alice")
    sink = PrivateKey.from_seed(b"race-sink").public_key().address()
    node = TestNode(funded_accounts=[(alice, 10**12)])
    signer = Signer(node, alice)
    errors = []
    results = []
    lock = threading.Lock()

    def worker(i):
        try:
            for j in range(TX_PER_THREAD):
                res = signer.submit_tx(
                    [MsgSend(signer.address, sink, 1000)]
                )
                with lock:
                    results.append(res)
        except Exception as e:  # noqa: BLE001
            with lock:
                errors.append(e)

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(N_THREADS)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors[:3]
    n = N_THREADS * TX_PER_THREAD
    assert len(results) == n
    assert all(r.code == 0 for r in results), [
        r.log for r in results if r.code
    ][:3]
    # exactly n transfers applied — no lost or doubled sends
    assert node.app.bank.balance(sink) == 1000 * n
    acc = node.app.accounts.get_or_create(signer.address)
    assert acc.sequence == n
    # all tx hashes unique (unique sequences -> unique sign bytes)
    hashes = {r.tx_hash for r in results}
    assert len(hashes) == n


def test_concurrent_grpc_clients_distinct_accounts():
    """Many RemoteNode clients with their own accounts through one server:
    the node service lock must serialise state access without deadlock."""
    keys = [PrivateKey.from_seed(b"race-client-%d" % i) for i in range(4)]
    node = TestNode(
        funded_accounts=[(k, 10**12) for k in keys], auto_produce=False
    )
    # warm jit caches so the producer never holds the lock across a compile
    from celestia_tpu.da import dah as dah_mod
    import numpy as np

    for k in (1, 2, 4):
        dah_mod.extend_and_header(np.zeros((k, k, 512), dtype=np.uint8))
    from celestia_tpu.client.remote import RemoteNode

    errors = []
    with NodeServer(node, block_interval_s=0.1) as server:

        def worker(i):
            try:
                remote = RemoteNode(server.address, timeout_s=120.0)
                signer = Signer(remote, keys[i])
                ns = Namespace.v0(b"race-%d" % i)
                res = signer.submit_pay_for_blob([Blob(ns, b"\x01" * 600)])
                assert res.code == 0, res.log
                res2 = signer.submit_tx(
                    [MsgSend(signer.address, keys[(i + 1) % 4].public_key().address(), 5)]
                )
                assert res2.code == 0, res2.log
                remote.close()
            except Exception as e:  # noqa: BLE001
                errors.append((i, e))

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    assert not errors, errors
    # every account sent exactly 2 txs
    for k in keys:
        acc = node.app.accounts.get_or_create(k.public_key().address())
        assert acc.sequence == 2


def test_nonce_recovery_under_external_interference():
    """A second signer for the SAME account invalidates the first's local
    sequence; the first must recover via nonce-mismatch parsing."""
    alice = PrivateKey.from_seed(b"race-dup")
    sink = PrivateKey.from_seed(b"race-dup-sink").public_key().address()
    node = TestNode(funded_accounts=[(alice, 10**12)])
    s1 = Signer(node, alice)
    s2 = Signer(node, alice)
    assert s1.submit_tx([MsgSend(s1.address, sink, 10)]).code == 0
    # s2's cached sequence is now stale; recovery re-signs with the node's
    # expected sequence
    res = s2.submit_tx([MsgSend(s2.address, sink, 20)])
    assert res.code == 0, res.log
    assert node.app.bank.balance(sink) == 30


def test_commitment_cache_concurrent_hammer():
    """Regression for the celint R1 founding bug: _COMMITMENT_CACHE shipped
    as an UNLOCKED plain dict mutated from pooled threads (warm_commitments
    batches + per-blob lookups during FilterTxs/ProcessProposal).
    Hammer the shared LRU from many threads with a tiny cap so eviction
    churns constantly, over both keys (the tx-digest key the validator
    paths warm and read, the content key of create_commitment), and
    assert every commitment returned under contention equals the serial
    recompute."""
    import hashlib

    from celestia_tpu.da.inclusion import (
        _COMMITMENT_CACHE,
        create_commitment,
        tx_commitment,
        warm_commitments,
    )

    blobs = [
        Blob(Namespace.v0(b"hammer-%02d" % i), bytes([i + 1]) * (300 + 37 * i))
        for i in range(24)
    ]
    # one single-blob tx per blob; any fixed digest stands in for its raw
    txs = [(hashlib.sha256(b"hammer-tx-%d" % i).digest(), (b,)) for i, b in enumerate(blobs)]
    old_cap = _COMMITMENT_CACHE.max_entries
    _COMMITMENT_CACHE.clear()
    try:
        expected = [create_commitment(b) for b in blobs]
        _COMMITMENT_CACHE.clear()
        _COMMITMENT_CACHE.set_max_entries(6)  # force eviction under load
        errors = []
        barrier = threading.Barrier(N_THREADS)

        def worker(tid):
            try:
                barrier.wait(timeout=30)
                for rep in range(6):
                    if (tid + rep) % 3 == 0:
                        # the batch path pooled proposal legs use
                        warm_commitments(txs)
                    order = list(range(len(blobs)))
                    # deterministic per-thread order, distinct across threads
                    off = (tid * 5 + rep) % len(order)
                    for i in order[off:] + order[:off]:
                        if (tid + i) % 2:
                            got = create_commitment(blobs[i])
                        else:
                            got, _missed = tx_commitment(txs[i][0], 0, blobs[i])
                        assert got == expected[i], (
                            f"thread {tid} rep {rep} blob {i}: commitment "
                            f"diverged under concurrency"
                        )
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=worker, args=(t,))
            for t in range(N_THREADS)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errors, errors[:3]
        assert len(_COMMITMENT_CACHE) <= 6
        stats = _COMMITMENT_CACHE.stats()
        assert stats["evictions"] > 0  # the cap really churned
    finally:
        _COMMITMENT_CACHE.set_max_entries(old_cap)
        _COMMITMENT_CACHE.clear()
