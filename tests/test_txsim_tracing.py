"""txsim over the network + store tracing.

- run_remote: the reference txsim CLI shape (test/cmd/txsim/cli.go,
  test/txsim/run.go): a master account funds derived sub-accounts over the
  network, then sequences drive load against the node's gRPC service.
- store tracing: SetCommitMultiStoreTracer parity (app/app.go:243) — every
  write through the multistore is observable with its store name and key.
"""

import numpy as np

from celestia_tpu.client.remote import RemoteNode
from celestia_tpu.client.signer import Signer
from celestia_tpu.client import txsim
from celestia_tpu.node.server import NodeServer
from celestia_tpu.node.testnode import TestNode
from celestia_tpu.state.store import MultiStore
from celestia_tpu.utils.secp256k1 import PrivateKey


def test_signed_pfb_txs_pass_the_filter():
    """txsim.signed_pfb_txs (chip_smoke.py): round-robin
    signers at consecutive sequences, one random blob each, every tx
    kept by the proposer's FilterTxs."""
    keys = [PrivateKey.from_seed(b"pfb-gen-%d" % i) for i in range(2)]
    node = TestNode(
        funded_accounts=[(key, 10**15) for key in keys], auto_produce=False
    )
    txs = txsim.signed_pfb_txs(
        node, keys, 5, 1000, np.random.default_rng(3), first_namespace=7
    )
    assert len(set(txs)) == 5
    assert node.app._filter_txs(txs) == txs


def test_txsim_remote_blob_and_send():
    master = PrivateKey.from_seed(b"txsim-master")
    node = TestNode(
        funded_accounts=[(master, 10**13)], auto_produce=False
    )
    from celestia_tpu.da import dah as dah_mod

    for k in (1, 2, 4):
        dah_mod.extend_and_header(np.zeros((k, k, 512), dtype=np.uint8))
    with NodeServer(node, block_interval_s=0.1) as server:
        remote = RemoteNode(server.address, timeout_s=120.0)
        signer = Signer(remote, master)
        results = txsim.run_remote(
            remote,
            signer,
            [txsim.BlobSequence(size_max=2000), txsim.SendSequence()],
            iterations=3,
            funding=10**9,
        )
        remote.close()
    assert len(results) == 6
    assert all(r["code"] == 0 for r in results), [
        r for r in results if r["code"]
    ]
    kinds = {r["type"] for r in results}
    assert kinds == {"blob", "send"}
    # load actually landed in blocks
    assert node.height > 1
    total_txs = sum(len(b.txs) for b in node.blocks)
    assert total_txs >= 7  # 1 multi-msg funding tx + 6 sequence txs


def test_cli_txsim_command(tmp_path):
    """The celestia-tpu txsim command end-to-end against a served node."""
    import json as _json

    from celestia_tpu.cli import main

    master = PrivateKey.from_seed(b"cli-txsim-master")
    home = tmp_path / "home"
    kd = home / "keyring"
    kd.mkdir(parents=True)
    (kd / "master.json").write_text(
        _json.dumps(
            {
                "priv": f"{master.d:064x}",
                "address": master.public_key().address().hex(),
            }
        )
    )
    node = TestNode(funded_accounts=[(master, 10**13)], auto_produce=False)
    from celestia_tpu.da import dah as dah_mod

    for k in (1, 2, 4):
        dah_mod.extend_and_header(np.zeros((k, k, 512), dtype=np.uint8))
    with NodeServer(node, block_interval_s=0.1) as server:
        rc = main(
            [
                "--home", str(home),
                "txsim",
                "--node", server.address,
                "--from", "master",
                "--blob", "1",
                "--send", "1",
                "--iterations", "2",
                "--blob-size-max", "1500",
            ]
        )
    assert rc == 0


def test_store_tracer_observes_writes():
    """Store writes route through the ONE tracing surface
    (utils/tracing.trace_store_writes): each write/delete is captured on
    the bridge AND lands as an instant event on the active span trace."""
    from celestia_tpu.utils import tracing

    ms = MultiStore(["bank", "auth"])
    tracing.disable()
    tracing.clear()
    tracing.enable(4)
    try:
        with tracing.block_span("deliver_block", height=1):
            with tracing.trace_store_writes(ms) as tracer_bridge:
                ms.store("bank").set(b"k1", b"v1")
                ms.store("auth").delete(b"k2")
                # branches created after installation trace to the same sink
                branch = ms.branch()
                branch.store("bank").set(b"k3", b"v3")
        assert tracer_bridge.events == [
            ("write", "bank", b"k1"),
            ("delete", "auth", b"k2"),
            ("write", "bank", b"k3"),
        ]
        # outside the bridge nothing is captured (tracer uninstalled)
        ms.store("bank").set(b"k4", b"v4")
        assert len(tracer_bridge.events) == 3
        # the same writes are instant events on the block trace, so a
        # trace reader sees state mutations inline with the phase spans
        tr = tracing.block_traces()[0]
        store_events = [
            ev for ev in tr.instants if ev["name"] == "store.write"
        ]
        assert [
            (ev["args"]["op"], ev["args"]["store"]) for ev in store_events
        ] == [("write", "bank"), ("delete", "auth"), ("write", "bank")]
    finally:
        tracing.disable()
        tracing.clear()


def test_store_tracer_nesting_restores_previous():
    """An inner bridge chains to and then RESTORES the outer one: the
    outer observer keeps seeing writes during and after the inner
    context (review fix: exit used to uninstall unconditionally)."""
    from celestia_tpu.utils import tracing

    ms = MultiStore(["bank"])
    with tracing.trace_store_writes(ms) as outer:
        with tracing.trace_store_writes(ms) as inner:
            ms.store("bank").set(b"a", b"1")
        ms.store("bank").set(b"b", b"2")  # outer must still observe
    ms.store("bank").set(b"c", b"3")  # nobody observes
    assert [(op, k) for op, _s, k in inner.events] == [("write", b"a")]
    assert [(op, k) for op, _s, k in outer.events] == [
        ("write", b"a"), ("write", b"b"),
    ]


def test_store_tracer_nesting_emits_one_instant_per_write():
    """With tracing ON, a nested bridge chain still emits exactly ONE
    store.write instant per mutation (review fix: the chained outer
    bridge used to re-emit, double-counting writes on the trace)."""
    from celestia_tpu.utils import tracing

    ms = MultiStore(["bank"])
    tracing.disable()
    tracing.clear()
    tracing.enable(2)
    try:
        with tracing.block_span("deliver_block", height=1):
            with tracing.trace_store_writes(ms) as outer:
                with tracing.trace_store_writes(ms) as inner:
                    ms.store("bank").set(b"a", b"1")
        assert len(inner.events) == 1 and len(outer.events) == 1
        tr = tracing.block_traces()[0]
        writes = [ev for ev in tr.instants if ev["name"] == "store.write"]
        assert len(writes) == 1, writes
    finally:
        tracing.disable()
        tracing.clear()


def test_tracer_can_follow_a_block():
    """Trace every store write made by one block's execution — the
    debugging workflow SetCommitMultiStoreTracer exists for, through the
    unified tracer surface."""
    from celestia_tpu.utils import tracing

    alice = PrivateKey.from_seed(b"trace-alice")
    node = TestNode(funded_accounts=[(alice, 10**12)])
    signer = Signer(node, alice)
    from celestia_tpu.state.tx import MsgSend

    with tracing.trace_store_writes(node.app.store) as bridge:
        res = signer.submit_tx(
            [MsgSend(signer.address, b"\x11" * 20, 1000)]
        )
    assert res.code == 0
    stores_touched = {s for _, s, _ in bridge.events}
    # fee deduction + transfer touch bank; sequence bump touches auth
    assert "bank" in stores_touched and "auth" in stores_touched
