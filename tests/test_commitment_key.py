"""Share commitments keyed by the tx digest on the validator's paths.

CheckTx, FilterTxs and ProcessProposal cache each blob's commitment under
(sha256(raw BlobTx), blob index, threshold), the digest those paths take
anyway.  These tests hold that key to what the content key gave: an
altered blob is still caught, identical blobs in two txs do not share an
entry, ProcessProposal computes each blob once in the warm pass, and no
blob is hashed to find its entry.
"""

import hashlib

import numpy as np
import pytest

from celestia_tpu.appconsts import DEFAULT_SUBTREE_ROOT_THRESHOLD
from celestia_tpu.da import eds_cache, inclusion
from celestia_tpu.da.blob import Blob, BlobTx, unmarshal_blob_tx
from celestia_tpu.da.namespace import Namespace
from celestia_tpu.state.app import App
from celestia_tpu.state.modules.blob import estimate_gas, validate_blob_tx
from celestia_tpu.state.tx import Fee, MsgPayForBlobs, Tx
from celestia_tpu.utils import native
from celestia_tpu.utils.secp256k1 import PrivateKey
from celestia_tpu.utils.telemetry import Telemetry

THRESHOLD = DEFAULT_SUBTREE_ROOT_THRESHOLD


def _app(*keys):
    app = App()
    app.init_chain({
        "accounts": [
            {"address": k.public_key().address().hex(), "balance": 10**12}
            for k in keys
        ]
    })
    return app


def _pfb(app, key, blobs, sequence=0):
    """A signed BlobTx paying for ``blobs``, raw bytes."""
    addr = key.public_key().address()
    acc = app.accounts.get_or_create(addr)
    msg = MsgPayForBlobs(
        signer=addr,
        namespaces=tuple(b.namespace.raw for b in blobs),
        blob_sizes=tuple(len(b.data) for b in blobs),
        share_commitments=tuple(inclusion.create_commitment(b) for b in blobs),
        share_versions=tuple(b.share_version for b in blobs),
    )
    gas = estimate_gas([len(b.data) for b in blobs])
    tx = Tx(
        (msg,), Fee(int(gas * 0.002) + 1, gas), key.public_key().compressed(),
        sequence, acc.account_number,
    ).signed(key, app.chain_id)
    return BlobTx(tx=tx.marshal(), blobs=tuple(blobs)).marshal()


def _blob(rng, tag, nbytes):
    return Blob(
        Namespace.v0(tag), rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes()
    )


def _cold(app):
    """A foreign block: every proposal-lifecycle cache misses."""
    eds_cache.clear()
    app._sig_cache.clear()
    app._decoded_cache.clear()
    inclusion._COMMITMENT_CACHE.clear()


def _tx_key(raw, index):
    return (hashlib.sha256(raw).digest(), index, THRESHOLD)


def test_altered_blob_rejected_after_honest_twin_warmed_by_checktx():
    key = PrivateKey.from_seed(b"commitment-key-alter")
    app = _app(key)
    rng = np.random.default_rng(1)
    honest = _pfb(app, key, [_blob(rng, b"alter-a", 3000), _blob(rng, b"alter-b", 700)])
    _cold(app)
    assert app.check_tx(honest).code == 0
    assert _tx_key(honest, 1) in inclusion._COMMITMENT_CACHE
    prep = app.prepare_proposal([honest])
    assert len(prep.block_txs) == 1

    # the same signed PFB with one byte of blob 1 changed after signing
    btx = unmarshal_blob_tx(honest)
    changed = bytearray(btx.blobs[1].data)
    changed[100] ^= 0xFF
    altered = BlobTx(
        tx=btx.tx, blobs=(btx.blobs[0], Blob(btx.blobs[1].namespace, bytes(changed)))
    ).marshal()
    ok, reason = app.process_proposal([altered], prep.square_size, prep.data_root)
    assert not ok
    assert "blob 1: share commitment mismatch" in reason
    # the honest twin's entry is still there, and the altered tx has its own
    assert _tx_key(honest, 1) in inclusion._COMMITMENT_CACHE
    assert inclusion._COMMITMENT_CACHE.get(_tx_key(altered, 1)) != (
        inclusion._COMMITMENT_CACHE.get(_tx_key(honest, 1))
    )
    ok, reason = app.process_proposal(prep.block_txs, prep.square_size, prep.data_root)
    assert ok, reason


def test_identical_blobs_in_two_txs_get_their_own_entries():
    keys = [PrivateKey.from_seed(b"commitment-key-twin-%d" % i) for i in range(2)]
    app = _app(*keys)
    blob = _blob(np.random.default_rng(2), b"twin", 1500)
    raws = [_pfb(app, k, [blob]) for k in keys]
    prep = app.prepare_proposal(raws)
    assert len(prep.block_txs) == 2
    _cold(app)
    ok, reason = app.process_proposal(prep.block_txs, prep.square_size, prep.data_root)
    assert ok, reason
    assert inclusion._COMMITMENT_CACHE.puts == 2
    want = inclusion.create_commitment(blob)
    for raw in raws:
        assert inclusion._COMMITMENT_CACHE.get(_tx_key(raw, 0)) == want


def test_process_proposal_puts_one_commitment_per_blob_and_hashes_no_blob(monkeypatch):
    keys = [PrivateKey.from_seed(b"commitment-key-once-%d" % i) for i in range(3)]
    app = _app(*keys)
    rng = np.random.default_rng(3)
    raws = [
        _pfb(app, k, [_blob(rng, b"once-%d-%d" % (i, j), 200 + 900 * j) for j in range(i + 1)])
        for i, k in enumerate(keys)
    ]
    prep = app.prepare_proposal(raws)
    assert len(prep.block_txs) == 3
    _cold(app)
    content_keys = []
    real_key = inclusion._commitment_key

    def counting_key(blob, threshold):
        content_keys.append(len(blob.data))
        return real_key(blob, threshold)

    monkeypatch.setattr(inclusion, "_commitment_key", counting_key)
    ok, reason = app.process_proposal(prep.block_txs, prep.square_size, prep.data_root)
    assert ok, reason
    assert content_keys == []
    assert inclusion._COMMITMENT_CACHE.puts == 1 + 2 + 3
    assert app.telemetry.summary()["counters"].get("commitment_warm_miss", 0) == 0


def test_validate_blob_tx_counts_what_no_warm_pass_computed():
    key = PrivateKey.from_seed(b"commitment-key-miss")
    app = _app(key)
    rng = np.random.default_rng(4)
    raw = _pfb(app, key, [_blob(rng, b"miss-a", 900), _blob(rng, b"miss-b", 90)])
    digest = hashlib.sha256(raw).digest()
    btx = unmarshal_blob_tx(raw)
    telemetry = Telemetry()
    inclusion._COMMITMENT_CACHE.clear()
    validate_blob_tx(btx, app.chain_id, digest, telemetry)
    assert telemetry.summary()["counters"]["commitment_warm_miss"] == 2
    inclusion._COMMITMENT_CACHE.clear()
    assert inclusion.warm_commitments([(digest, btx.blobs)]) >= 1
    assert inclusion._COMMITMENT_CACHE.puts == 2
    validate_blob_tx(btx, app.chain_id, digest, telemetry)
    assert telemetry.summary()["counters"]["commitment_warm_miss"] == 2


def test_warm_pass_skips_malformed_blobs():
    ns = Namespace.v0(b"malformed")
    good = Blob(ns, b"\x05" * 600)
    digest = hashlib.sha256(b"malformed-tx").digest()
    inclusion._COMMITMENT_CACHE.clear()
    tasks = inclusion.warm_commitments(
        [(digest, (Blob(ns, b""), Blob(ns, b"\x01", share_version=3), good))]
    )
    assert tasks >= 1
    assert inclusion._COMMITMENT_CACHE.puts == 1
    assert inclusion._COMMITMENT_CACHE.get((digest, 2, THRESHOLD)) == (
        inclusion.create_commitment(good)
    )
    with pytest.raises(ValueError):
        inclusion.tx_commitment(digest, 0, Blob(ns, b""))


def test_host_fallback_gives_the_native_bytes(monkeypatch):
    """Without the native library, the warm pass computes the same
    commitments with hashlib (one layout for a consensus value)."""
    if not native.available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(5)
    blobs = tuple(_blob(rng, b"fallback-%d" % i, n) for i, n in enumerate((1, 479, 40000)))
    digest = hashlib.sha256(b"fallback-tx").digest()
    inclusion._COMMITMENT_CACHE.clear()
    inclusion.warm_commitments([(digest, blobs)])
    want = [inclusion._COMMITMENT_CACHE.get((digest, i, THRESHOLD)) for i in range(3)]
    inclusion._COMMITMENT_CACHE.clear()
    monkeypatch.setattr(native, "available", lambda: False)
    assert inclusion.warm_commitments([(digest, blobs)]) == 3
    got = [inclusion._COMMITMENT_CACHE.get((digest, i, THRESHOLD)) for i in range(3)]
    assert got == want and None not in got
