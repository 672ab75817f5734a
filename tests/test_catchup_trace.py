"""The catch-up warm leg's trace (state/app.py ``warm_extends_batched``),
with no shard_map compile.

The batched sharded program is replaced by one that answers with the
single-device extend in the program's row-shard layout, so everything
around it runs as on the mesh: the per-block square rebuilds and EDS
keys, the stack and its padding, the sharded upload onto a 1x4 mesh of
virtual CPU devices, the launch, the wait, the root fetch and the
device-side reassembly.  The real program's spans and bytes are pinned
in the slow ``datamesh`` child (tests/_mesh_live_isolated.py).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from celestia_tpu.appconsts import CONTINUATION_SPARSE_SHARE_CONTENT_SIZE
from celestia_tpu.client.signer import Signer
from celestia_tpu.da import dah as dah_mod
from celestia_tpu.da import eds_cache
from celestia_tpu.da.blob import Blob
from celestia_tpu.da.namespace import Namespace
from celestia_tpu.node.testnode import TestNode
from celestia_tpu.ops import gf256
from celestia_tpu.parallel import mesh as mesh_mod
from celestia_tpu.parallel import sharded
from celestia_tpu.utils import devprof, tracing
from celestia_tpu.utils.secp256k1 import PrivateKey

DISPATCH = {"extend.sharded.upload", "extend.sharded.launch", "extend.sharded.wait",
            "extend.sharded.fetch_roots", "extend.sharded.assemble"}


def _single_device_fn(x):
    """The batched program's outputs, from the single-device extend:
    (eds_local[n, k, 2, 2k, B], row_roots, col_roots, data_roots)."""
    parts = [dah_mod.extend_and_header(np.asarray(sq)) for sq in np.asarray(x)]
    k = x.shape[1]

    def roots(rs):
        return np.stack([np.frombuffer(r, np.uint8) for r in rs])

    return (
        jnp.asarray(np.stack([np.stack([e.shares[:k], e.shares[k:]], axis=1) for e, _d in parts])),
        jnp.asarray(np.stack([roots(d.row_roots) for _e, d in parts])),
        jnp.asarray(np.stack([roots(d.col_roots) for _e, d in parts])),
        jnp.asarray(np.stack([np.frombuffer(d.hash, np.uint8) for _e, d in parts])),
    )


@pytest.fixture
def catchup(monkeypatch):
    """A node, three same-k proposals made with the mesh off, and the
    1x4 mesh on with the stand-in program."""
    mesh_mod._reset_for_tests()
    eds_cache.clear()
    key = PrivateKey.from_seed(b"catchup-trace")
    node = TestNode(funded_accounts=[(key, 10**14)], auto_produce=False)
    signer = Signer(node, key)
    rng = np.random.default_rng(25)
    size = (8 * 8 // 4 - 4) * CONTINUATION_SPARSE_SHARE_CONTENT_SIZE
    mesh_mod.configure("off")
    proposals = []
    for b in range(3):
        blobs = [Blob(Namespace.v0(bytes([b * 4 + i + 1]) * 10), rng.bytes(size)) for i in range(2)]
        p = node.app.prepare_proposal([signer.pay_for_blob_tx(blobs)(sequence=0)])
        proposals.append((p.block_txs, p.square_size, p.data_root))
    assert {s for _t, s, _r in proposals} == {8}
    eds_cache.clear()
    mesh_mod._reset_for_tests()
    mesh_mod.configure("1x4")
    monkeypatch.setattr(sharded, "_sharded_fn", lambda *a: _single_device_fn)
    monkeypatch.setattr(devprof, "note_compile", lambda *a, **k: None)
    tracing.disable()
    tracing.clear()
    yield node.app, proposals
    tracing.disable()
    tracing.clear()
    mesh_mod._reset_for_tests()
    eds_cache.clear()


def test_one_extend_batch_trace_then_a_cache_hit_per_block(catchup):
    app, proposals = catchup
    tracing.enable(64)
    verdicts = app.validate_blocks_batched([(list(t), s, r) for t, s, r in proposals])
    assert verdicts == [(True, "")] * 3
    traces = tracing.block_traces()
    assert [t.name for t in traces] == ["extend_batch"] + ["process_proposal"] * 3
    batch = traces[0]
    root = next(s for s in batch.spans if s.span_id == batch.root_id)
    # 3 blocks pad to 4 chunks of the 1x4 mesh's data axis of 1
    assert {k: root.args[k] for k in ("k", "blocks", "pad", "mesh", "warmed")} == {
        "k": 8, "blocks": 3, "pad": 1, "mesh": "1x4", "warmed": 3}
    by_id = {s.span_id: s for s in batch.spans}
    parent = {s.name: by_id[s.parent_id].name for s in batch.spans if s.parent_id in by_id}
    assert parent["extend.sharded"] == parent["extend_batch.stack"] == "extend_batch"
    assert all(parent[name] == "extend.sharded" for name in DISPATCH)
    names = [s.name for s in batch.spans]
    assert names.count("square_build") == names.count("eds_cache.key") == 3
    assert {s.args["leg"] for s in batch.spans if s.name == "eds_cache.key"} == {"warm"}
    stack = next(s for s in batch.spans if s.name == "extend_batch.stack")
    assert stack.args == {"squares": 3, "pad": 1}
    dispatch = next(s for s in batch.spans if s.name == "extend.sharded")
    # 4 squares on one data row: per square, two all_to_alls each send 3
    # of the 4 (k/4, 2k, 512) column blocks a chip holds
    assert dispatch.args["ici_bytes"] == 4 * 2 * (8 // 4 * 16 * 512) * 3 // 4
    upload = next(s for s in batch.spans if s.name == "extend.sharded.upload")
    assert upload.args["bytes"] == 4 * 8 * 8 * 512
    fetch = next(s for s in batch.spans if s.name == "extend.sharded.fetch_roots")
    assert fetch.args["bytes"] == 4 * (4 * 8 * 90 + 32)
    for t in traces[1:]:
        extend = next(s for s in t.spans if s.name == "extend")
        assert extend.args["eds_cache"] == "hit"
    assert app.telemetry.counters["extend_batch_pad"] == 1
    assert app.telemetry.counters["extend_batched_blocks"] == 3


def test_the_untraced_warm_leg_gives_the_same_entries(catchup):
    app, proposals = catchup
    assert app.warm_extends_batched([(t, s) for t, s, _r in proposals]) == 3
    assert tracing.block_traces() == []
    for txs, size, root in proposals:
        hit = eds_cache.CACHE.peek(eds_cache.make_key(txs, size, app.app_version, gf256.active_codec()))
        assert hit is not None and hit[1].hash == root


def test_no_mesh_no_trace(catchup):
    app, proposals = catchup
    mesh_mod.configure("off")
    tracing.enable(8)
    assert app.warm_extends_batched([(t, s) for t, s, _r in proposals]) == 0
    assert tracing.block_traces() == []
