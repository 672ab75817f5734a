"""Proposal-lifecycle DA caching: the content-addressed EDS/DAH cache and
the decode-once pipeline.

The safety-critical properties pinned here:

* cache keys commit to the FULL tx bytes (+ square size, app version,
  codec) — never to the claimed data root; a byzantine proposer cannot
  launder a bad square through a cache hit;
* cached and uncached paths are byte-identical (DAH hash equality for
  both codecs, single- and multi-threaded);
* the codec is pinned once at genesis: switching after first native use
  hard-fails outside tests.
"""

import threading

import numpy as np
import pytest

from celestia_tpu.da import dah as dah_mod
from celestia_tpu.da import eds_cache
from celestia_tpu.ops import gf256
from celestia_tpu.state.app import App
from celestia_tpu.state.tx import Fee, MsgSend, Tx
from celestia_tpu.utils import hostpool
from celestia_tpu.utils.secp256k1 import PrivateKey


@pytest.fixture(autouse=True)
def _fresh_caches():
    eds_cache.clear()
    yield
    eds_cache.clear()


def _funded_app(seed=b"eds-cache", codec=None, chain_id="edscache-1"):
    key = PrivateKey.from_seed(seed)
    app = App(chain_id=chain_id)
    genesis = {
        "chain_id": chain_id,
        "genesis_time_ns": 1,
        "accounts": [
            {"address": key.public_key().address().hex(), "balance": 10**12}
        ],
    }
    if codec is not None:
        genesis["codec"] = codec
    app.init_chain(genesis)
    return app, key


def _send_txs(app, key, n=3, start_seq=0):
    addr = key.public_key().address()
    acc = app.accounts.peek(addr)
    txs = []
    for i in range(n):
        tx = Tx(
            (MsgSend(addr, b"\x42" * 20, 1 + i),),
            Fee(200_000, 100_000),
            key.public_key().compressed(),
            sequence=start_seq + i,
            account_number=acc.account_number,
        )
        txs.append(tx.signed(key, app.chain_id).marshal())
    return txs


# ---------------------------------------------------------------------------
# key construction
# ---------------------------------------------------------------------------


def test_key_commits_to_tx_bytes_not_data_root():
    txs = [b"\x01\x02\x03", b"\x04\x05"]
    base = eds_cache.make_key(txs, 4, 1, gf256.CODEC_LEOPARD)
    # any byte mutation re-keys
    mutated = [b"\x01\x02\x04", b"\x04\x05"]
    assert eds_cache.make_key(mutated, 4, 1, gf256.CODEC_LEOPARD) != base
    # shifting bytes across tx boundaries re-keys (length prefixes)
    shifted = [b"\x01\x02", b"\x03\x04\x05"]
    assert eds_cache.make_key(shifted, 4, 1, gf256.CODEC_LEOPARD) != base
    # square size / app version / codec are all part of the key
    assert eds_cache.make_key(txs, 8, 1, gf256.CODEC_LEOPARD) != base
    assert eds_cache.make_key(txs, 4, 2, gf256.CODEC_LEOPARD) != base
    assert eds_cache.make_key(txs, 4, 1, gf256.CODEC_LAGRANGE) != base


def test_lru_bound_and_eviction():
    cache = eds_cache.EdsCache(max_entries=2)
    cache.put(b"a", "eds-a", "dah-a")
    cache.put(b"b", "eds-b", "dah-b")
    assert cache.get(b"a") == ("eds-a", "dah-a")  # refresh a
    cache.put(b"c", "eds-c", "dah-c")  # evicts b (LRU)
    assert cache.get(b"b") is None
    assert cache.get(b"a") is not None
    assert cache.get(b"c") is not None
    assert cache.stats()["evictions"] == 1


# ---------------------------------------------------------------------------
# lifecycle: proposer's second extend is a lookup; checks still run
# ---------------------------------------------------------------------------


def test_proposer_process_leg_hits_cache_and_matches_cold_run():
    app, key = _funded_app()
    txs = _send_txs(app, key)
    prop = app.prepare_proposal(txs)
    assert app.telemetry.counters.get("eds_cache_miss_prepare") == 1
    ok, reason = app.process_proposal(
        prop.block_txs, prop.square_size, prop.data_root
    )
    assert ok, reason
    assert app.telemetry.counters.get("eds_cache_hit_process") == 1
    # byte-identical to a fully cold validator on the same genesis
    eds_cache.clear()
    cold, _ = _funded_app()
    ok, reason = cold.process_proposal(
        prop.block_txs, prop.square_size, prop.data_root
    )
    assert ok, reason
    assert cold.telemetry.counters.get("eds_cache_miss_process") == 1


def test_mutated_tx_bytes_miss_cache_and_reject():
    app, key = _funded_app(b"mutate")
    txs = _send_txs(app, key)
    prop = app.prepare_proposal(txs)
    assert app.process_proposal(
        prop.block_txs, prop.square_size, prop.data_root
    )[0]
    hits_before = eds_cache.stats()["hits"]
    bad_txs = list(prop.block_txs)
    bad_txs[0] = bad_txs[0][:-1] + bytes([bad_txs[0][-1] ^ 1])
    ok, reason = app.process_proposal(
        bad_txs, prop.square_size, prop.data_root
    )
    assert not ok
    # the mutated block never reached a cache hit
    assert eds_cache.stats()["hits"] == hits_before


def test_same_data_root_different_txs_rejected_despite_cached_entry():
    """A byzantine proposer advertises the data root of a block this node
    ALREADY validated (hot in the cache), but ships different txs.  The
    key is the tx bytes, so the forged proposal cannot hit the honest
    entry; the recompute exposes the root mismatch."""
    app, key = _funded_app(b"launder")
    txs = _send_txs(app, key, n=3)
    prop = app.prepare_proposal(txs)
    ok, _ = app.process_proposal(
        prop.block_txs, prop.square_size, prop.data_root
    )
    assert ok  # honest entry now cached and hot
    other_txs = _send_txs(app, key, n=2)
    forged = app.prepare_proposal(other_txs)  # valid OTHER block
    ok, reason = app.process_proposal(
        forged.block_txs, forged.square_size, prop.data_root  # lying root
    )
    assert not ok
    assert "data root mismatch" in reason


def test_ante_rejection_happens_before_any_cache_consult():
    """Validity checks are not skippable: garbage txs must reject even
    when the cache is warm with unrelated entries."""
    app, key = _funded_app(b"garbage")
    txs = _send_txs(app, key)
    prop = app.prepare_proposal(txs)
    app.process_proposal(prop.block_txs, prop.square_size, prop.data_root)
    misses_before = eds_cache.stats()["misses"]
    hits_before = eds_cache.stats()["hits"]
    ok, reason = app.process_proposal(
        [b"\xde\xad\xbe\xef"], 1, prop.data_root
    )
    assert not ok and "invalid tx" in reason
    # rejected before reaching the extend: no cache traffic at all
    assert eds_cache.stats()["hits"] == hits_before
    assert eds_cache.stats()["misses"] == misses_before


# ---------------------------------------------------------------------------
# byte identity: cached vs cold, both codecs, 1 and N threads
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("codec", [gf256.CODEC_LEOPARD, gf256.CODEC_LAGRANGE])
@pytest.mark.parametrize("threads", [1, None])
def test_cache_hit_dah_byte_identical_to_cold(codec, threads):
    prev_codec = gf256.active_codec()
    prev_threads = hostpool._override
    try:
        gf256.set_active_codec(codec)
        hostpool.set_cpu_threads(threads)
        app, key = _funded_app(b"ident-" + codec.encode(), codec=codec,
                               chain_id=f"ident-{codec}")
        txs = _send_txs(app, key)
        prop = app.prepare_proposal(txs)  # populates the cache
        cached_entry = eds_cache.get(
            eds_cache.make_key(
                prop.block_txs, prop.square_size, app.app_version, codec
            )
        )
        assert cached_entry is not None
        eds_hit, dah_hit = cached_entry
        # cold recompute: every cache emptied
        eds_cache.clear()
        prop_cold = app.prepare_proposal(txs)
        assert prop_cold.dah.hash == dah_hit.hash
        assert prop_cold.dah.row_roots == dah_hit.row_roots
        assert prop_cold.dah.col_roots == dah_hit.col_roots
        assert np.array_equal(prop_cold.eds.shares, eds_hit.shares)
    finally:
        hostpool.set_cpu_threads(prev_threads)
        gf256.set_active_codec(prev_codec)


# ---------------------------------------------------------------------------
# min DAH: locked, codec-aware, first resident of the cache
# ---------------------------------------------------------------------------


def test_min_dah_codec_aware_and_thread_safe():
    prev = gf256.active_codec()
    try:
        gf256.set_active_codec(gf256.CODEC_LEOPARD)
        leo = dah_mod.min_data_availability_header().hash
        gf256.set_active_codec(gf256.CODEC_LAGRANGE)
        lag = dah_mod.min_data_availability_header().hash
        # at k=1 the RS code is a constant polynomial: parity == data in
        # BOTH field representations, so the VALUES agree — but the cache
        # must still key them separately (a k>1 analogue would differ)
        assert leo == lag
        assert eds_cache.CACHE.peek(
            eds_cache.min_dah_key(gf256.CODEC_LEOPARD)
        ) is not None
        assert eds_cache.CACHE.peek(
            eds_cache.min_dah_key(gf256.CODEC_LAGRANGE)
        ) is not None
        gf256.set_active_codec(gf256.CODEC_LEOPARD)
        assert dah_mod.min_data_availability_header().hash == leo
        # hammer it from threads against a cleared cache: one value
        eds_cache.clear()
        results = []
        errs = []

        def worker():
            try:
                results.append(dah_mod.min_data_availability_header().hash)
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert set(results) == {leo}
    finally:
        gf256.set_active_codec(prev)


# ---------------------------------------------------------------------------
# decode-once pipeline
# ---------------------------------------------------------------------------


def test_deliver_reuses_decoded_txs_read_only():
    app, key = _funded_app(b"deliver")
    txs = _send_txs(app, key)
    prop = app.prepare_proposal(txs)
    assert app.process_proposal(
        prop.block_txs, prop.square_size, prop.data_root
    )[0]
    results, _end, _hash = app.finalize_block(
        prop.block_txs, 2, 10, prop.data_root
    )
    assert all(r.code == 0 for r in results)
    assert app.telemetry.counters.get("decoded_cache_hit_deliver") == len(
        prop.block_txs
    )
    # read-only: delivering bytes the proposal legs never saw must not
    # seed the cache (the cache implies full BlobTx validation)
    app._decoded_cache.clear()
    fresh = _send_txs(app, key, n=1, start_seq=len(txs))
    app.deliver_tx(fresh[0])
    assert len(app._decoded_cache) == 0


def test_app_version_change_invalidates_decoded_cache():
    app, key = _funded_app(b"upgrade")
    txs = _send_txs(app, key)
    app.prepare_proposal(txs)
    assert len(app._decoded_cache) > 0
    app._set_app_version(app.app_version)
    assert len(app._decoded_cache) == 0


# ---------------------------------------------------------------------------
# codec pin-once guard (ROADMAP r5 follow-up)
# ---------------------------------------------------------------------------


def test_set_active_codec_refuses_switch_after_native_use(monkeypatch):
    prev_codec = gf256.active_codec()
    prev_used = gf256._codec_used
    try:
        gf256.set_active_codec(gf256.CODEC_LEOPARD)
        gf256.mark_codec_used()
        # outside a pytest session the switch must hard-fail...
        monkeypatch.delenv("PYTEST_CURRENT_TEST", raising=False)
        with pytest.raises(RuntimeError, match="pinned at genesis"):
            gf256.set_active_codec(gf256.CODEC_LAGRANGE)
        # ...re-pinning the SAME codec stays a no-op...
        gf256.set_active_codec(gf256.CODEC_LEOPARD)
        # ...and force=True is the explicit escape hatch
        gf256.set_active_codec(gf256.CODEC_LAGRANGE, force=True)
        assert gf256.active_codec() == gf256.CODEC_LAGRANGE
        gf256.set_active_codec(gf256.CODEC_LEOPARD, force=True)
    finally:
        gf256._codec_used = prev_used
        gf256.set_active_codec(prev_codec, force=True)


# ---------------------------------------------------------------------------
# cache poisoning under injected lru.put faults (PR 7 chaos satellite)
# ---------------------------------------------------------------------------


def test_lost_cache_writes_force_recompute_never_partial(chaos):
    """With lru.put faults armed every insert is a LOST WRITE: the
    EDS/DAH cache must simply miss and recompute — an entry
    is either absent or complete, and the recomputed bytes match a
    fault-free run exactly."""
    app, key = _funded_app(b"chaos-lru")
    txs = _send_txs(app, key)
    prop_clean = app.prepare_proposal(txs)

    eds_cache.clear()
    chaos.arm("lru.put", "fail_rate", rate=1.0, seed=13)
    app2, key2 = _funded_app(b"chaos-lru")
    txs2 = _send_txs(app2, key2)
    prop = app2.prepare_proposal(txs2)
    assert prop.data_root == prop_clean.data_root
    # the prepare-leg insert was dropped: nothing resident
    assert len(eds_cache.CACHE) == 0
    # process re-validates from scratch (a MISS, not a poisoned hit) and
    # still accepts — byte identity survives the lost writes
    ok, reason = app2.process_proposal(
        prop.block_txs, prop.square_size, prop.data_root
    )
    assert ok, reason
    assert app2.telemetry.counters.get("eds_cache_miss_process") == 1
    assert app2.telemetry.counters.get("eds_cache_hit_process") is None

    # disarmed: the same flow caches and hits again (no lingering damage)
    chaos.disarm()
    eds_cache.clear()
    app2._decoded_cache.clear()
    prop = app2.prepare_proposal(txs2)
    ok, _ = app2.process_proposal(
        prop.block_txs, prop.square_size, prop.data_root
    )
    assert ok
    assert app2.telemetry.counters.get("eds_cache_hit_process") == 1


def test_dropped_batch_insert_is_all_or_nothing(chaos):
    """put_many under an armed lru.put fault drops the WHOLE batch: a
    half-landed row-memo batch would be exactly the partial state the
    chaos suite exists to rule out."""
    from celestia_tpu.utils.lru import LruCache

    chaos.arm("lru.put", "fail_rate", rate=1.0, seed=3)
    c = LruCache("chaos_batch", 16)
    c.put_many([(i, i) for i in range(8)])
    assert len(c) == 0
    assert c.get_many(range(8)) == [None] * 8
    chaos.disarm()
    c.put_many([(i, i) for i in range(8)])
    assert c.get_many(range(8)) == list(range(8))
