"""TestNode.repair_block: a full node rebuilds a block from the shares it
gathered, checked against the plain reference.

The node is handed what a full node has — the data root, the DAH, the
shares and the mask of those it holds — and never the txs.  Pinned here
at k = 4, 8 and 16, with the device plane forced on over the CPU
backend, against ``benchmark/configs/celestia_da_reference.py`` (which
imports nothing of the program) on seeded blocks of signed PFBs:

* 25% of the cells withheld uniformly, and a mask that peels in two
  phases: the registered EDS, axis roots and data root are the
  reference's byte for byte, and the recovered txs are the block's;
* DAS proofs are then served from the registered device entry and
  verify against the data root;
* a flipped provided share, a DAH that is not the data root's, a DAH
  of another block and a mask that cannot repair are each rejected with
  their counter, and nothing is registered;
* a mask deeper than ``rs._MAX_DEVICE_PHASES`` repairs on the host and
  registers the same entry, and a device fault poisons the plane and
  repairs the block on the host;
* traced, the call is a ``repair_block`` trace with its spans and args;
* ``deconstruct`` gives back the txs ``construct`` laid out.
"""

import importlib.util
import os

import numpy as np
import pytest

from celestia_tpu.client.signer import Signer
from celestia_tpu.da import das, device_plane, eds_cache
from celestia_tpu.da import square as square_mod
from celestia_tpu.da.blob import Blob, BlobTx
from celestia_tpu.da.namespace import Namespace
from celestia_tpu.node.testnode import TestNode
from celestia_tpu.ops import gf256, rs
from celestia_tpu.utils import faults, tracing
from celestia_tpu.utils.secp256k1 import PrivateKey

_REF = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmark", "configs", "celestia_da_reference.py",
)
_spec = importlib.util.spec_from_file_location("celestia_da_reference", _REF)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

KEYS = [PrivateKey.from_seed(b"repair-block-%d" % i) for i in range(4)]
# share of the k*k square each PFB's blobs fill (the last PFB carries two)
FILL = ((0.30,), (0.15,), (0.08, 0.05))


@pytest.fixture(autouse=True)
def _plane(monkeypatch):
    monkeypatch.setenv(device_plane.ENV_MODE, "on")
    device_plane.clear_poison(force=True)
    eds_cache.clear()
    yield
    device_plane.clear_poison(force=True)
    faults.reset_stats()
    eds_cache.clear()


def _nodes():
    funded = [(key, 10**15) for key in KEYS]
    return (
        TestNode(funded_accounts=funded, auto_produce=False),
        TestNode(funded_accounts=funded, auto_produce=False),
    )


def _block(proposer, k: int, seed: int):
    """A block of signed PFBs laid out at exactly k, from the proposer's
    PrepareProposal (on the host legs: the full node is the one on the
    plane): (block_txs, data_root, dah, eds uint8[2k, 2k, 512])."""
    rng = np.random.default_rng(seed)
    txs = []
    for key, fills in zip(KEYS, FILL):
        blobs = [
            Blob(Namespace.v0(b"rb%d" % rng.integers(0, 4)), rng.bytes(int(f * k * k * 478)))
            for f in fills
        ]
        txs.append(Signer(proposer, key).pay_for_blob_tx(blobs)(sequence=0))
    with device_plane.forced("off"):
        prop = proposer.app.prepare_proposal(txs)
        eds = np.asarray(prop.eds.shares)
    assert prop.square_size == k and len(prop.block_txs) == len(txs)
    eds_cache.clear()
    return list(prop.block_txs), prop.data_root, prop.dah, eds


def _withheld(n2: int, share: float, seed: int) -> np.ndarray:
    """bool[n2, n2], True where a cell is held: ``share`` of the cells
    withheld, drawn uniformly."""
    avail = np.ones(n2 * n2, dtype=bool)
    rng = np.random.default_rng(seed)
    avail[rng.choice(n2 * n2, int(share * n2 * n2), replace=False)] = False
    return avail.reshape(n2, n2)


def _phases(avail: np.ndarray) -> int:
    schedule = rs._simulate_schedule(avail, avail.shape[0] // 2)
    return 0 if schedule is None else schedule[0].shape[0]


def _two_phase_mask(n2: int) -> np.ndarray:
    """Rows 0..k withhold columns 0..k-1 and one column past k each, and
    so do columns 0..k-1 (k+1 cells missing: unsolvable); the columns
    past k miss two cells at most, so the first phase solves them, and
    the second then solves rows 0..k."""
    k = n2 // 2
    avail = np.ones((n2, n2), dtype=bool)
    avail[: k + 1, :k] = False
    for r in range(k + 1):
        avail[r, k + 1 + r % (k - 1)] = False
    return avail


def _masked(eds: np.ndarray, avail: np.ndarray) -> np.ndarray:
    """The shares a full node holds, with junk where it holds none."""
    return np.where(avail[:, :, None], eds, np.uint8(0xA5))


def _registered(block_txs, k: int, full):
    key = eds_cache.make_key(block_txs, k, full.app.app_version, gf256.active_codec())
    return eds_cache.CACHE.peek(key)


def _assert_reference(hit, block_txs, data_root):
    ref = reference.Reference(block_txs)
    eds, dah = hit
    assert np.array_equal(np.asarray(eds.shares), ref.eds)
    assert list(dah.row_roots) == ref.row_roots and list(dah.col_roots) == ref.col_roots
    assert dah.hash == ref.data_root == data_root


def _counters(node) -> dict:
    return node.app.telemetry.summary()["counters"]


@pytest.mark.parametrize("k", [4, 8, 16])
def test_a_quarter_withheld_repairs_to_the_reference(k):
    proposer, full = _nodes()
    block_txs, data_root, dah, eds = _block(proposer, k, seed=k)
    avail = _withheld(2 * k, 0.25, seed=k)
    assert _phases(avail) == 1
    assert full.repair_block(data_root, dah, _masked(eds, avail), avail) == (True, "")
    hit = _registered(block_txs, k, full)
    assert hit is not None  # only under the key of txs equal to the block's
    entry = eds_cache.get_device_entry(data_root)
    assert entry is not None and entry.eds is hit[0]._shares
    _assert_reference(hit, block_txs, data_root)
    assert _counters(full)["repair_device"] == 1


def test_a_two_phase_mask_repairs_to_the_reference():
    k = 4
    proposer, full = _nodes()
    block_txs, data_root, dah, eds = _block(proposer, k, seed=40)
    avail = _two_phase_mask(2 * k)
    assert _phases(avail) == 2
    assert full.repair_block(data_root, dah, _masked(eds, avail), avail) == (True, "")
    _assert_reference(_registered(block_txs, k, full), block_txs, data_root)
    assert _counters(full)["repair_device"] == 1


def test_the_node_runs_the_program_repair_program_hands_out(monkeypatch):
    """The node path, ``rs.repair_square_device`` and the ahead-of-time
    handle ``rs.repair_program`` reach one compile-cache entry."""
    k = 4
    proposer, full = _nodes()
    block_txs, data_root, dah, eds = _block(proposer, k, seed=46)
    avail = _withheld(2 * k, 0.25, seed=k)
    fn, args = rs.repair_program(avail)
    seen = []
    real = rs._repair_verify_fn

    def spy(*key):
        program = real(*key)

        def call(*a):
            seen.append((program, [(x.shape, x.dtype) for x in a]))
            return program(*a)

        return call

    monkeypatch.setattr(rs, "_repair_verify_fn", spy)
    assert full.repair_block(data_root, dah, _masked(eds, avail), avail)[0]
    assert seen == [(fn, [(a.shape, a.dtype) for a in args])]


def test_samples_are_served_from_the_repaired_device_entry(monkeypatch):
    k = 4
    proposer, full = _nodes()
    block_txs, data_root, dah, eds = _block(proposer, k, seed=41)
    avail = _withheld(2 * k, 0.25, seed=k)
    assert full.repair_block(data_root, dah, _masked(eds, avail), avail)[0]
    served = []
    real = device_plane.sample_proofs_batch

    def spy(entry, header, coords):
        served.append(entry)
        return real(entry, header, coords)

    monkeypatch.setattr(device_plane, "sample_proofs_batch", spy)
    eds_obj, header = _registered(block_txs, k, full)
    coords = [(0, 0), (1, 2 * k - 1), (2 * k - 1, 3), (k, k)]
    proofs = das.sample_proofs_batch(eds_obj, header, coords)
    assert served == [eds_cache.get_device_entry(data_root)]
    assert all(p.verify(data_root) for p in proofs)
    assert [p.share for p in proofs] == [eds[r, c].tobytes() for r, c in coords]


def _assert_nothing_registered(full, block_txs, data_root, k):
    assert _registered(block_txs, k, full) is None
    assert eds_cache.get_device_entry(data_root) is None
    assert len(eds_cache.CACHE) == 0


def test_bad_shares_headers_and_masks_are_rejected():
    k = 4
    n2 = 2 * k
    proposer, full = _nodes()
    block_txs, data_root, dah, eds = _block(proposer, k, seed=42)
    _other_txs, other_root, other_dah, _other_eds = _block(proposer, k, seed=43)
    avail = _withheld(n2, 0.25, seed=k)
    shares = _masked(eds, avail)
    flipped = shares.copy()
    r, c = np.argwhere(avail)[5]
    flipped[r, c, 100] ^= 0x10
    cases = [
        ("repair_rejected_byzantine", (data_root, dah, flipped, avail)),
        ("repair_rejected_dah", (data_root, other_dah, shares, avail)),
        ("repair_rejected_roots", (other_root, other_dah, shares, avail)),
    ]
    unrepairable = avail.copy()
    unrepairable[: k + 1, : k + 1] = False
    cases.append(("repair_rejected_mask", (data_root, dah, _masked(eds, unrepairable), unrepairable)))
    for counter, args in cases:
        ok, reason = full.repair_block(*args)
        assert not ok and reason, counter
        assert _counters(full).get(counter) == 1, (counter, _counters(full))
        _assert_nothing_registered(full, block_txs, data_root, k)
    assert "repair_device" not in _counters(full)


def test_a_mask_deeper_than_the_device_program_repairs_on_the_host(monkeypatch):
    k = 4
    proposer, full = _nodes()
    block_txs, data_root, dah, eds = _block(proposer, k, seed=44)
    avail = _two_phase_mask(2 * k)
    monkeypatch.setattr(rs, "_MAX_DEVICE_PHASES", 1)
    launched = []
    monkeypatch.setattr(rs, "finish_repair", lambda *a, **kw: launched.append(a))
    assert full.repair_block(data_root, dah, _masked(eds, avail), avail) == (True, "")
    assert launched == []
    _assert_reference(_registered(block_txs, k, full), block_txs, data_root)
    # the same device entry the extend route registers for this square
    entry = eds_cache.get_device_entry(data_root)
    assert entry is not None and entry.k == k
    assert np.array_equal(np.asarray(entry.eds), eds)
    assert _counters(full)["repair_host"] == 1 and "repair_device" not in _counters(full)


def test_a_device_fault_poisons_the_plane_and_repairs_on_the_host(monkeypatch):
    k = 4
    proposer, full = _nodes()
    block_txs, data_root, dah, eds = _block(proposer, k, seed=47)
    avail = _withheld(2 * k, 0.25, seed=k)

    def fault(*_a, **_kw):
        raise RuntimeError("injected device fault")

    monkeypatch.setattr(rs, "finish_repair", fault)
    assert full.repair_block(data_root, dah, _masked(eds, avail), avail) == (True, "")
    assert device_plane.poisoned()
    _assert_reference(_registered(block_txs, k, full), block_txs, data_root)
    assert _counters(full)["repair_host"] == 1 and "repair_device" not in _counters(full)


def test_the_repair_trace_has_its_spans_and_args():
    k = 4
    proposer, full = _nodes()
    block_txs, data_root, dah, eds = _block(proposer, k, seed=45)
    avail = _withheld(2 * k, 0.25, seed=k)
    tracing.enable()
    try:
        tracing.clear()
        assert full.repair_block(data_root, dah, _masked(eds, avail), avail)[0]
        (trace,) = [t for t in tracing.block_traces() if t.name == "repair_block"]
    finally:
        tracing.disable()
    spans = {s.name: dict(s.args) for s in trace.spans}
    assert spans["repair_block"] | {"k": k, "available": int(avail.sum()), "withheld": int((~avail).sum()),
                                    "phases": 1, "leg": "device"} == spans["repair_block"]
    for name in ("repair.dah_check", "repair.plan", "repair.launch", "repair.wait", "repair.register"):
        assert name in spans
    assert spans["repair.upload"]["bytes"] >= (2 * k) ** 2 * 512
    assert spans["repair.fetch_verdicts"]["bytes"] >= k * k * 512
    assert spans["repair.deconstruct"] == {"txs": len(block_txs), "blobs": 4}


def test_deconstruct_gives_back_what_construct_laid_out():
    rng = np.random.default_rng(7)
    normal = [b"normal-%d-" % i + rng.bytes(int(rng.integers(10, 900))) for i in range(5)]
    pfbs = [
        BlobTx(
            b"pfb-%d" % i,
            tuple(Blob(Namespace.v0(b"ns%d" % rng.integers(0, 6)), rng.bytes(int(rng.integers(1, 3000))))
                  for _ in range(1 + i % 3)),
        ).marshal()
        for i in range(7)
    ]
    square, block_txs, _wrappers = square_mod.construct(normal + pfbs)
    assert block_txs == normal + pfbs
    again = square_mod.Square(size=square.size, array=square.to_array().copy())
    assert square_mod.deconstruct(again) == block_txs
    assert square_mod.deconstruct(square_mod.construct(pfbs)[0]) == pfbs
    assert square_mod.deconstruct(square_mod.construct(normal)[0]) == normal
