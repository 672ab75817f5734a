"""ProcessProposal launches the extend before the ante and awaits it after.

On a cache miss that the single-device plane extends, App.process_proposal
builds the square, launches ``extend_levels`` inside a
``da/dah.extend_block_ahead`` block (da/device_plane.py ``begin_extend``),
runs the ante while the program computes, and then extends the square as
before: ``device_plane.extend_and_header`` finds the launch and only
awaits it (``finish_extend``).  Pinned here, with the plane forced on
over the CPU backend at k=4 and k=8 for both codecs:

* the split is byte-identical to ``extend_and_header`` called alone, and
  so is the overlapped ProcessProposal's DAH and device entry;
* the overlapped ProcessProposal still extends through
  ``device_plane.extend_and_header``, launching once;
* nothing waits on the launched program before the ante starts;
* every verdict and reason is the one the ante-first order gave: the
  ante's reason wins over a square that does not build or does not
  match the header's size;
* a proposal the ante rejects leaves the EDS cache, its counters and
  the device-handle budget as they were;
* a fault in the finishing half poisons the plane and the block still
  gets the same root from the host legs;
* ``extend_overlap_process`` counts each launch: once per miss, never on
  a hit.
"""

import numpy as np
import pytest

from celestia_tpu.client import txsim
from celestia_tpu.da import dah as dah_mod
from celestia_tpu.da import device_plane, eds_cache, inclusion
from celestia_tpu.da.blob import BlobTx
from celestia_tpu.da.inclusion import create_commitment
from celestia_tpu.da.square import Square
from celestia_tpu.ops import gf256
from celestia_tpu.state.app import App
from celestia_tpu.state.modules.blob import estimate_gas
from celestia_tpu.state.tx import Fee, MsgPayForBlobs, Tx
from celestia_tpu.utils import devprof, faults, native
from celestia_tpu.utils.secp256k1 import PrivateKey

CODECS = [gf256.CODEC_LEOPARD, gf256.CODEC_LAGRANGE]
# per square size: the blob sizes of each of three PFBs, which lay out
# to exactly that square
BLOBS = {4: (400, 100), 8: (4000, 3000)}


@pytest.fixture(autouse=True)
def _clean_plane():
    prev = gf256.active_codec()
    device_plane.clear_poison(force=True)
    eds_cache.clear()
    yield
    device_plane.clear_poison(force=True)
    native.clear_poison(force=True)
    faults.reset_stats()
    eds_cache.clear()
    gf256.set_active_codec(prev, force=True)


def _square(k: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    sq = rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    sq[:, :, :29] = 0
    sq[:, :, 28] = rng.integers(1, 200, (k, k), dtype=np.uint8)
    return sq


def _pfb(app: App, key: PrivateKey, seq: int, blobs) -> bytes:
    addr = key.public_key().address()
    msg = MsgPayForBlobs(
        signer=addr,
        namespaces=tuple(b.namespace.raw for b in blobs),
        blob_sizes=tuple(len(b.data) for b in blobs),
        share_commitments=tuple(create_commitment(b) for b in blobs),
        share_versions=tuple(b.share_version for b in blobs),
    )
    gas = estimate_gas([len(b.data) for b in blobs])
    tx = Tx(
        (msg,),
        Fee(amount=gas, gas_limit=gas),
        key.public_key().compressed(),
        sequence=seq,
        account_number=app.accounts.peek(addr).account_number,
    )
    return BlobTx(tx=tx.signed(key, app.chain_id).marshal(), blobs=tuple(blobs)).marshal()


def _proposal(codec: str, k: int):
    """(app, prepared proposal of exactly k): three signed PFBs, laid
    out and extended by the proposer on the host legs."""
    key = PrivateKey.from_seed(b"overlap-" + codec.encode())
    app = App(chain_id="overlap-1")
    app.init_chain({
        "chain_id": "overlap-1",
        "genesis_time_ns": 1,
        "codec": codec,
        "accounts": [{"address": key.public_key().address().hex(), "balance": 10**12}],
    })
    rng = np.random.default_rng(k)
    txs = [
        _pfb(app, key, i, [txsim.random_blob(rng, 2 * i + j, size) for j, size in enumerate(BLOBS[k])])
        for i in range(3)
    ]
    with device_plane.forced("off"):
        prep = app.prepare_proposal(txs)
    assert prep.square_size == k and len(prep.block_txs) == 3
    return app, prep


def _foreign(app: App) -> None:
    """Empty every proposal-lifecycle cache: the validator never saw
    the block."""
    eds_cache.clear()
    app._sig_cache.clear()
    app._decoded_cache.clear()
    inclusion._COMMITMENT_CACHE.clear()


def _counter(app: App, name: str) -> int:
    return app.telemetry.summary()["counters"].get(name, 0)


def _assert_same_entry(a, b) -> None:
    assert a.k == b.k and a.data_root == b.data_root and a.nbytes == b.nbytes
    assert np.array_equal(np.asarray(a.eds), np.asarray(b.eds))
    for x, y in zip(a.levels + a.root_levels, b.levels + b.root_levels):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def _count_launches(monkeypatch) -> list:
    launches = []
    begin = device_plane.begin_extend

    def counted(square):
        launches.append(square)
        return begin(square)

    monkeypatch.setattr(device_plane, "begin_extend", counted)
    return launches


@pytest.mark.parametrize("k", [4, 8])
@pytest.mark.parametrize("codec", CODECS)
def test_begin_finish_byte_identical_to_extend_and_header(codec, k, monkeypatch):
    """begin+finish, and an extend_block inside extend_block_ahead of the
    same square, give extend_and_header's bytes; the block's extend
    awaits its launch (one launch), and another square inside the block
    launches its own."""
    gf256.set_active_codec(codec, force=True)
    sq = _square(k, seed=50 + k)
    square = Square(array=sq.reshape(k * k, 512), size=k)
    launches = _count_launches(monkeypatch)
    with device_plane.forced("on"):
        eds_a, dah_a = device_plane.extend_and_header(sq)
        entry_a = eds_cache.get_device_entry(dah_a.hash)
        eds_cache.clear()
        pending = device_plane.begin_extend(sq)
        assert eds_cache.get_device_entry(dah_a.hash) is None  # nothing before finish
        eds_b, dah_b = device_plane.finish_extend(pending)
        entry_b = eds_cache.get_device_entry(dah_b.hash)
        del launches[:]
        with dah_mod.extend_block_ahead(square) as launched:
            assert launched and len(launches) == 1
            _, dah_other = dah_mod.extend_and_header(_square(k, seed=51 + k))
            assert len(launches) == 2
            eds_c, dah_c = dah_mod.extend_block(square)
        assert len(launches) == 2
        assert device_plane.poisoned() is None, device_plane.poisoned()
    assert dah_other.hash != dah_a.hash
    for dah in (dah_b, dah_c):
        assert (dah.hash, dah.row_roots, dah.col_roots) == (
            dah_a.hash, dah_a.row_roots, dah_a.col_roots,
        )
    for eds in (eds_b, eds_c):
        assert np.array_equal(np.asarray(eds.shares), np.asarray(eds_a.shares))
    _assert_same_entry(entry_b, entry_a)


def test_a_launch_never_awaited_registers_nothing():
    sq = _square(4, seed=60)
    square = Square(array=sq.reshape(16, 512), size=4)
    with device_plane.forced("on"):
        with dah_mod.extend_block_ahead(square) as launched:
            assert launched
        assert eds_cache.device_handle_stats()["puts"] == 0
        _, dah = dah_mod.extend_block(square)  # launches afresh
    assert eds_cache.device_handle_stats()["puts"] == 1
    assert eds_cache.get_device_entry(dah.hash) is not None


@pytest.mark.parametrize("k", [4, 8])
@pytest.mark.parametrize("codec", CODECS)
def test_overlapped_process_matches_extend_alone(codec, k, monkeypatch):
    """A cold ProcessProposal on the plane launches once, extends through
    device_plane.extend_and_header and accepts with the DAH and device
    entry that extend_and_header gives alone; the same block again is a
    hit and launches nothing."""
    app, prep = _proposal(codec, k)
    _foreign(app)
    launches = _count_launches(monkeypatch)
    extends = []
    extend_and_header = device_plane.extend_and_header

    def spied(square):
        extends.append(square)
        return extend_and_header(square)

    monkeypatch.setattr(device_plane, "extend_and_header", spied)
    with device_plane.forced("on"):
        ok, reason = app.process_proposal(prep.block_txs, prep.square_size, prep.data_root)
        assert ok, reason
        assert len(launches) == 1 and len(extends) == 1
        assert _counter(app, "extend_overlap_process") == 1
        assert _counter(app, "eds_cache_miss_process") == 1
        ok, reason = app.process_proposal(prep.block_txs, prep.square_size, prep.data_root)
        assert ok, reason
        assert _counter(app, "eds_cache_hit_process") == 1
        assert _counter(app, "extend_overlap_process") == 1

        key = eds_cache.make_key(prep.block_txs, k, app.app_version, codec)
        _, dah = eds_cache.CACHE.peek(key)
        entry = eds_cache.get_device_entry(prep.data_root)
        assert len(launches) == 1 and len(extends) == 1
        eds_cache.clear()
        eds_alone, dah_alone = dah_mod.extend_and_header(prep.square.to_array().reshape(k, k, 512))
        entry_alone = eds_cache.get_device_entry(dah_alone.hash)
        assert device_plane.poisoned() is None, device_plane.poisoned()
    assert (dah.hash, dah.row_roots, dah.col_roots) == (
        dah_alone.hash, dah_alone.row_roots, dah_alone.col_roots,
    )
    assert dah.hash == prep.data_root
    _assert_same_entry(entry, entry_alone)


def _cache_state():
    s = eds_cache.stats()
    return (len(eds_cache.CACHE), s["hits"], s["misses"], s["puts"],
            eds_cache.device_handle_stats()["puts"], len(eds_cache._DEVICE_CACHE))


def test_ante_reason_wins_and_leaves_caches_as_they_were():
    """An invalid tx beside a wrong square size, or beside a square that
    cannot be built, is rejected for the invalid tx, as when the ante
    ran first; a size mismatch or a square error alone keeps its own
    reason.  None of them caches anything, nor does an ante rejection
    whose extend was already launched."""
    app, prep = _proposal(gf256.CODEC_LEOPARD, 8)
    bad = list(prep.block_txs) + [b"\xde\xad\xbe\xef"]
    _foreign(app)
    with device_plane.forced("on"):
        before = _cache_state()
        ok, ante_reason = app.process_proposal(bad, 8, prep.data_root)
        assert not ok and ante_reason.startswith("invalid tx in proposal: ")
        assert _counter(app, "extend_overlap_process") == 1  # launched, then dropped
        assert app.process_proposal(bad, 4, prep.data_root) == (False, ante_reason)
        ok, reason = app.process_proposal(prep.block_txs, 4, prep.data_root)
        assert (ok, reason) == (False, "square size mismatch: computed 8, header says 4")

        app.params.set("blob", "GovMaxSquareSize", 4)  # the block no longer fits
        assert app.process_proposal(bad, 8, prep.data_root) == (False, ante_reason)
        ok, reason = app.process_proposal(prep.block_txs, 8, prep.data_root)
        assert not ok and reason.startswith("proposal rejected: square construction overflow")
        assert _cache_state() == before
        assert _counter(app, "extend_overlap_process") == 1
        assert _counter(app, "eds_cache_miss_process") == 0
        assert device_plane.poisoned() is None, device_plane.poisoned()


def test_finish_fault_poisons_and_accepts_the_same_root(monkeypatch):
    app, prep = _proposal(gf256.CODEC_LEOPARD, 4)
    _foreign(app)

    def boom(pending):
        raise RuntimeError("injected device loss while the ante ran")

    monkeypatch.setattr(device_plane, "finish_extend", boom)
    with device_plane.forced("on"):
        ok, reason = app.process_proposal(prep.block_txs, prep.square_size, prep.data_root)
    assert ok, reason
    assert device_plane.poisoned() is not None
    assert _counter(app, "extend_overlap_process") == 1
    key = eds_cache.make_key(prep.block_txs, 4, app.app_version, gf256.CODEC_LEOPARD)
    _, dah = eds_cache.CACHE.peek(key)
    assert (dah.hash, dah.row_roots, dah.col_roots) == (
        prep.data_root, prep.dah.row_roots, prep.dah.col_roots,
    )
    assert eds_cache.get_device_entry(prep.data_root) is None  # the host legs ran


def test_nothing_waits_on_the_launch_before_the_ante(monkeypatch):
    """The launch returns without waiting for the program: no device
    value is awaited between it and the start of the ante, and the
    wait for the roots comes after the ante."""
    import jax

    app, prep = _proposal(gf256.CODEC_LEOPARD, 4)
    _foreign(app)
    events = []

    def spy(name, fn):
        def wrapped(*args, **kwargs):
            if name == "ante":
                events.append(name)
            out = fn(*args, **kwargs)
            if name != "ante":
                events.append(name)
            return out
        return wrapped

    monkeypatch.setattr(device_plane, "begin_extend", spy("launch", device_plane.begin_extend))
    monkeypatch.setattr(App, "_decode_proposal_txs", spy("ante", App._decode_proposal_txs))
    for mod, name in ((jax, "block_until_ready"), (jax, "device_get"), (devprof, "fetch")):
        monkeypatch.setattr(mod, name, spy("wait", getattr(mod, name)))
    with devprof.collect(), device_plane.forced("on"):
        ok, reason = app.process_proposal(prep.block_txs, prep.square_size, prep.data_root)
    assert ok, reason
    assert events[:2] == ["launch", "ante"], events
    assert "wait" in events[2:], events
