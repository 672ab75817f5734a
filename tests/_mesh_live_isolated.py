"""Live mesh-path tests — run subprocess-isolated (tests/test_mesh_live.py).

The multi-chip sharded extension wired into the LIVE proposal lifecycle
(ISSUE 14): prepare/process on a forced multi-host-device virtual mesh
must produce data roots byte-identical to the single-device path, the
content-addressed EDS cache must interoperate across both legs, the
batched multi-block leg must equal the per-block loop, and squares the
row axis cannot divide must fall back cleanly.

Isolated for the same jaxlib fragility as tests/_sharded_isolated.py
(late shard_map compiles in a long-lived process).  COST DISCIPLINE: a
shard_map compile on the virtual CPU mesh costs tens of seconds of XLA
wall (structure-bound, not size-bound — k=4 compiles no faster than
k=8), so the suite is split into two groups that each compile exactly
ONE sharded program (the wrapper runs them in separate children, each
with `--xla_backend_optimization_level=0` — integer-only programs, so
the optimization level cannot change bytes, and the byte-identity
assertions would catch it if it did):

* group "rowmesh": the 1x2 pure-row mesh, single-square program —
  live-path identity, EDS-cache interop both directions, laundering,
  fallback and the degradation ladder (the last three compile nothing).
* group "datamesh": the 2x2 mixed data x row mesh, batched program —
  batched-vs-loop root equality, the warm-only catch-up leg, and the
  catch-up leg's trace and bytes against the benchmark's reference.

Between them both factorings are covered.
"""

import numpy as np
import pytest

from celestia_tpu.appconsts import CONTINUATION_SPARSE_SHARE_CONTENT_SIZE
from celestia_tpu.client.signer import Signer
from celestia_tpu.da import dah as dah_mod
from celestia_tpu.da import eds_cache
from celestia_tpu.da.blob import Blob, BlobTx
from celestia_tpu.da.inclusion import create_commitment
from celestia_tpu.da.namespace import Namespace
from celestia_tpu.node.testnode import TestNode
from celestia_tpu.parallel import mesh as mesh_mod
from celestia_tpu.parallel import sharded
from celestia_tpu.state.tx import MsgPayForBlobs
from celestia_tpu.utils.secp256k1 import PrivateKey


@pytest.fixture(autouse=True)
def _clean_mesh():
    mesh_mod._reset_for_tests()
    eds_cache.clear()
    yield
    mesh_mod._reset_for_tests()
    eds_cache.clear()


def _funded_node(seed: bytes):
    key = PrivateKey.from_seed(seed)
    node = TestNode(funded_accounts=[(key, 10**14)], auto_produce=False)
    return node, Signer(node, key)


def _blob_txs(signer, n_tx: int, k: int, tag: int = 0):
    """n signed BlobTxs sized so the square lands around k (sequences
    restart at 0: nothing here is ever delivered)."""
    per_tx = max(
        1,
        ((k * k // 2) // n_tx - 4) * CONTINUATION_SPARSE_SHARE_CONTENT_SIZE,
    )
    raws = []
    for i in range(n_tx):
        ns = Namespace.v0(bytes([tag * 16 + i + 1]) * 10)
        blob = Blob(ns, bytes([tag * 16 + i]) * per_tx)
        msg = MsgPayForBlobs(
            signer=signer.address,
            namespaces=(ns.raw,),
            blob_sizes=(len(blob.data),),
            share_commitments=(create_commitment(blob),),
            share_versions=(0,),
        )
        tx = signer.sign_tx([msg], gas_limit=2_000_000, sequence=i)
        raws.append(BlobTx(tx.marshal(), [blob]).marshal())
    return raws


# ---------------------------------------------------------------------------
# group "rowmesh": one single-square program on the 1x2 pure-row mesh
# ---------------------------------------------------------------------------


def test_rowmesh_live_path_interop_launder_fallback():
    node, signer = _funded_node(b"mesh-live")
    app = node.app
    raws = _blob_txs(signer, 2, 8)

    # single-device baseline
    mesh_mod.configure("off")
    prop_off = app.prepare_proposal(raws)
    root = prop_off.data_root
    assert prop_off.square_size >= 4

    # live mesh path: byte-identical root, sharded leg actually ran
    mesh_mod._reset_for_tests()
    mesh_mod.configure("1x2")
    eds_cache.clear()
    before = app.telemetry.counters.get("extend_sharded", 0)
    prop_on = app.prepare_proposal(raws)
    assert prop_on.data_root == root
    assert app.telemetry.counters.get("extend_sharded", 0) == before + 1
    assert prop_on.dah.row_roots == prop_off.dah.row_roots
    assert prop_on.dah.col_roots == prop_off.dah.col_roots
    assert np.array_equal(prop_on.eds.shares, prop_off.eds.shares)
    prop_on.dah.validate_basic()

    # interop leg A: mesh-produced warm entry serves the unsharded leg
    mesh_mod.configure("off")
    hits = app.telemetry.counters.get("eds_cache_hit_process", 0)
    ok, why = app.process_proposal(
        prop_on.block_txs, prop_on.square_size, prop_on.data_root
    )
    assert ok, why
    assert app.telemetry.counters.get("eds_cache_hit_process", 0) == hits + 1

    # interop leg B: unsharded warm entry serves the mesh leg (no new
    # sharded dispatch — the content key is identical by construction)
    eds_cache.clear()
    app.prepare_proposal(raws)  # unsharded (mesh still off)
    mesh_mod._reset_for_tests()
    mesh_mod.configure("1x2")
    n_sharded = app.telemetry.counters.get("extend_sharded", 0)
    ok, why = app.process_proposal(
        prop_on.block_txs, prop_on.square_size, prop_on.data_root
    )
    assert ok, why
    assert app.telemetry.counters.get("extend_sharded", 0) == n_sharded

    # laundering: different (valid, same-signer) txs claiming the warm
    # entry's root must recompute and be rejected on the root compare —
    # the key commits to the tx bytes, never the claimed root
    evil = _blob_txs(signer, 2, 8, tag=3)
    ok, why = app.process_proposal(
        evil, prop_on.square_size, prop_on.data_root
    )
    assert not ok
    assert "mismatch" in why

    # fallback: a square the row axis cannot divide (and the k=1 min
    # DAH) take the single-device path, byte-identical, counted —
    # compiles nothing (this mesh's program is already built)
    mesh_mod._reset_for_tests()
    mesh_mod.configure("1x8")  # 8-way rows over a small square
    small = _blob_txs(signer, 1, 2, tag=5)
    eds_cache.clear()
    n_sharded = app.telemetry.counters.get("extend_sharded", 0)
    prop_small = app.prepare_proposal(small)
    assert prop_small.square_size < 8
    assert app.telemetry.counters.get("extend_sharded", 0) == n_sharded
    assert mesh_mod.stats()["fallback_squares"] >= 1
    mesh_mod.configure("off")
    eds_cache.clear()
    assert app.prepare_proposal(small).data_root == prop_small.data_root
    dah_mod.min_data_availability_header()
    assert mesh_mod.poisoned() is None


def test_rowmesh_sharded_failure_degrades_to_single_device():
    """The robustness ladder: a sharded fault poisons the mesh one-way
    and the SAME call falls through to the single-device path with the
    same root.  The injected fault fires before any dispatch, so this
    test compiles nothing."""
    node, signer = _funded_node(b"mesh-degrade")
    app = node.app
    raws = _blob_txs(signer, 2, 8)
    mesh_mod.configure("off")
    root = app.prepare_proposal(raws).data_root

    mesh_mod._reset_for_tests()
    mesh_mod.configure("1x2")
    eds_cache.clear()
    import celestia_tpu.parallel.sharded as sharded_mod

    orig = sharded_mod.extend_block_sharded

    def boom(square, mesh):
        raise RuntimeError("injected sharded fault")

    sharded_mod.extend_block_sharded = boom
    try:
        prop = app.prepare_proposal(raws)
    finally:
        sharded_mod.extend_block_sharded = orig
    assert prop.data_root == root
    assert mesh_mod.poisoned() is not None
    assert app.telemetry.counters.get("extend_mesh_degraded", 0) == 1
    # poisoned: later squares go single-device without retrying the mesh
    eds_cache.clear()
    before = app.telemetry.counters.get("extend_sharded", 0)
    assert app.prepare_proposal(raws).data_root == root
    assert app.telemetry.counters.get("extend_sharded", 0) == before


# ---------------------------------------------------------------------------
# group "datamesh": one batched program on the 2x2 mixed data x row mesh
# ---------------------------------------------------------------------------


def test_datamesh_batched_equals_loop_and_warm_cache():
    """validate_blocks_batched on the mixed factoring: one batched
    dispatch, verdicts equal the per-block loop, warm entries carry the
    exact per-block roots, and the warm-only leg (the state-sync
    catch-up path) fills the cache without validating."""
    node, signer = _funded_node(b"mesh-batch")
    app = node.app

    # three distinct same-k blocks (same blob shape, different bytes →
    # same square size, different roots); single-device baselines first
    # (no compile: the host-native leg)
    blocks = [_blob_txs(signer, 2, 8, tag=t) for t in (0, 1, 2)]
    mesh_mod.configure("off")
    proposals = []
    for txs in blocks:
        eds_cache.clear()
        p = app.prepare_proposal(txs)
        proposals.append((p.block_txs, p.square_size, p.data_root))
    assert len({root for _t, _s, root in proposals}) == 3

    # batched leg: 3 blocks pad to the data axis (4), ONE dispatch
    eds_cache.clear()
    mesh_mod._reset_for_tests()
    mesh_mod.configure("2x2")
    before = app.telemetry.counters.get("extend_batched_blocks", 0)
    verdicts = app.validate_blocks_batched(
        [(list(t), s, r) for t, s, r in proposals]
    )
    assert [ok for ok, _ in verdicts] == [True, True, True], verdicts
    assert app.telemetry.counters.get("extend_batched_blocks", 0) == before + 3
    assert mesh_mod.stats()["batched_dispatches"] == 1

    # warm-only leg (what bft_catchup_batch calls): cache filled, no
    # verdicts; the per-block validations that follow all hit warm
    eds_cache.clear()
    assert (
        app.validate_blocks_batched(
            [(list(t), s, r) for t, s, r in proposals], warm_only=True
        )
        == []
    )
    hits = app.telemetry.counters.get("eds_cache_hit_process", 0)
    for txs, size, root in proposals:
        ok, why = app.process_proposal(list(txs), size, root)
        assert ok, why
    assert app.telemetry.counters.get("eds_cache_hit_process", 0) == hits + 3

    # the batched entry's (EDS, DAH) pairs are byte-identical to the
    # single-device per-square path (same program as above — no compile)
    rng = np.random.default_rng(11)
    sqs = rng.integers(0, 256, (3, 8, 8, 512), dtype=np.uint8)
    m = mesh_mod.device_mesh()
    arr = np.concatenate([sqs, sqs[-1:]])  # pad to the data axis
    pairs = sharded.extend_and_headers_sharded_batch(arr, m)
    for i in range(3):
        ref_eds, ref_dah = dah_mod.extend_and_header(sqs[i])
        assert np.array_equal(pairs[i][0].shares, ref_eds.shares)
        assert pairs[i][1].hash == ref_dah.hash
        assert pairs[i][1].row_roots == ref_dah.row_roots


def _reference():
    """benchmark/configs/celestia_da_reference.py, loaded by path: a
    plain implementation that imports nothing of the program."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "configs", "celestia_da_reference.py",
    )
    spec = importlib.util.spec_from_file_location("celestia_da_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_datamesh_catchup_trace_and_reference_bytes(monkeypatch):
    """The catch-up leg on the real batched program (the one compiled
    above): one ``extend_batch`` trace with the dispatch's children,
    then a cache-hit ``process_proposal`` per block; the warmed EDS,
    axis roots and data roots equal the plain reference's byte for
    byte on a seeded pool."""
    from celestia_tpu.utils import devprof, tracing

    node, signer = _funded_node(b"mesh-catchup")
    app = node.app
    rng = np.random.default_rng(2025)
    size = (8 * 8 // 4 - 4) * CONTINUATION_SPARSE_SHARE_CONTENT_SIZE
    mesh_mod.configure("off")
    proposals = []
    for b in range(3):
        blobs = [Blob(Namespace.v0(rng.bytes(10)), rng.bytes(size)) for _ in range(2)]
        eds_cache.clear()
        p = app.prepare_proposal([signer.pay_for_blob_tx(blobs)(sequence=0)])
        proposals.append((p.block_txs, p.square_size, p.data_root))
    assert {s for _t, s, _r in proposals} == {8}

    eds_cache.clear()
    mesh_mod._reset_for_tests()
    mesh_mod.configure("2x2")
    # the profiling build of the program would be a second CPU compile
    monkeypatch.setattr(devprof, "note_compile", lambda *a, **k: None)
    tracing.disable()
    tracing.clear()
    tracing.enable(16)
    try:
        verdicts = app.validate_blocks_batched(
            [(list(t), s, r) for t, s, r in proposals]
        )
        traces = tracing.block_traces()
    finally:
        tracing.disable()
        tracing.clear()
    assert verdicts == [(True, "")] * 3
    assert [t.name for t in traces] == ["extend_batch"] + ["process_proposal"] * 3
    batch = traces[0]
    by_id = {s.span_id: s for s in batch.spans}
    root = by_id[batch.root_id]
    assert (root.args["k"], root.args["blocks"], root.args["pad"]) == (8, 3, 1)
    parent = {s.name: by_id[s.parent_id].name for s in batch.spans if s.parent_id in by_id}
    for child in ("upload", "launch", "wait", "fetch_roots", "assemble"):
        assert parent[f"extend.sharded.{child}"] == "extend.sharded"
    assert parent["extend.sharded"] == "extend_batch"
    for t in traces[1:]:
        extend = next(s for s in t.spans if s.name == "extend")
        assert extend.args["eds_cache"] == "hit"
    assert app.telemetry.counters.get("extend_batch_pad", 0) == 1

    ref_mod = _reference()
    matrices: dict = {}
    for txs, size, root_claimed in proposals:
        key = eds_cache.make_key(txs, size, app.app_version, "leopard-ff8")
        eds, dah = eds_cache.CACHE.peek(key)
        ref = ref_mod.Reference(txs, _matrices=matrices)
        assert ref.data_root == dah.hash == root_claimed
        assert list(dah.row_roots) == ref.row_roots
        assert list(dah.col_roots) == ref.col_roots
        assert np.array_equal(np.asarray(eds.shares), ref.eds)
