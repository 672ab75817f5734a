"""Disk-backed persistence + crash recovery.

VERDICT r2 next-round #2: persist committed state diffs + blocks + the tx
index per height; `start` recovers from the data dir without a snapshot;
kill -9 a node mid-chain, restart, identical app hashes; memory stays flat
over long chains.  Reference: /root/reference/app/app.go:657-661
(LoadLatestVersion), cmd/celestia-appd/cmd/root.go:219-250 (data dir).
"""

import hashlib
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

from celestia_tpu.client.signer import Signer
from celestia_tpu.node.testnode import TestNode
from celestia_tpu.state.disk import BlockLog, StateLog, _Log, _T_STATE
from celestia_tpu.utils.secp256k1 import PrivateKey

REPO = Path(__file__).resolve().parents[1]


def _make_node(data_dir, **kw):
    alice = PrivateKey.from_seed(b"persist-alice")
    node = TestNode(
        funded_accounts=[(alice, 10**13)],
        genesis_time_ns=1_700_000_000_000_000_000,
        data_dir=str(data_dir),
        **kw,
    )
    return node, alice


def test_restart_resumes_chain_with_identical_state(tmp_path):
    node, alice = _make_node(tmp_path / "d1")
    signer = Signer(node, alice)
    bob = b"\x07" * 20
    for i in range(5):
        from celestia_tpu.state.tx import MsgSend

        res = signer.submit_tx([MsgSend(signer.address, bob, 1000 * (i + 1))])
        assert res.code == 0, res.log
        node.produce_block()
    h = node.height
    ah = node.app.store.committed_hash(h)
    balances = (
        node.app.bank.balance(signer.address),
        node.app.bank.balance(bob),
    )
    tx_hash = next(iter(node._tx_index))
    node.close()

    # a brand-new process-equivalent: same data dir, no snapshot, no state
    node2, _ = _make_node(tmp_path / "d1")
    assert node2.height == h
    assert node2.app.store.committed_hash(h) == ah
    assert node2.app.bank.balance(signer.address) == balances[0]
    assert node2.app.bank.balance(bob) == balances[1]
    # tx index rebuilt from the block log
    assert node2.get_tx(tx_hash) is not None
    # the chain continues producing identical-shape blocks
    signer2 = Signer(node2, alice)
    from celestia_tpu.state.tx import MsgSend

    res = signer2.submit_tx([MsgSend(signer2.address, bob, 7)])
    assert res.code == 0, res.log
    assert node2.height > h  # confirm-poll produced the next block(s)
    assert node2.app.bank.balance(bob) == balances[1] + 7
    node2.close()


def test_recovery_is_deterministic_across_replicas(tmp_path):
    """Two nodes executing the same blocks, one restarted from disk
    mid-chain, converge to the same app hash (the crash-recovery analogue
    of state-machine replication)."""
    from celestia_tpu.state.tx import MsgSend

    node_a, alice = _make_node(tmp_path / "a")
    node_b, _ = _make_node(tmp_path / "b")
    bob = b"\x08" * 20

    def _advance(node, n):
        s = Signer(node, alice)
        for _ in range(n):
            res = s.submit_tx([MsgSend(s.address, bob, 500)])
            assert res.code == 0, res.log
            node.produce_block()

    _advance(node_a, 3)
    _advance(node_b, 3)
    node_b.close()
    node_b2, _ = _make_node(tmp_path / "b")  # restart b from disk
    _advance(node_a, 2)
    _advance(node_b2, 2)
    assert (
        node_a.app.store.committed_hash(node_a.height)
        == node_b2.app.store.committed_hash(node_b2.height)
    )
    node_a.close()
    node_b2.close()


def test_torn_tail_write_is_discarded(tmp_path):
    """A partial record at the end of state.log (crash mid-append) is
    truncated; the node restarts at the last intact height."""
    node, alice = _make_node(tmp_path / "d")
    signer = Signer(node, alice)
    from celestia_tpu.state.tx import MsgSend

    for _ in range(3):
        res = signer.submit_tx([MsgSend(signer.address, b"\x09" * 20, 10)])
        assert res.code == 0
        node.produce_block()
    h = node.height
    ah = node.app.store.committed_hash(h)
    node.close()
    # simulate a torn write on BOTH logs
    for name in ("state.log", "blocks.log"):
        with open(tmp_path / "d" / name, "ab") as f:
            f.write(b"CTL1\x01\xff\xff")  # header cut off mid-field
    node2, _ = _make_node(tmp_path / "d")
    assert node2.height == h
    assert node2.app.store.committed_hash(h) == ah
    node2.close()


def test_state_log_ahead_of_block_log_rolls_back(tmp_path):
    """Crash between the state fsync and the block fsync: the state log
    has one commit more than the block log.  Recovery replays only up to
    the last fully-persisted block."""
    node, alice = _make_node(tmp_path / "d")
    signer = Signer(node, alice)
    from celestia_tpu.state.tx import MsgSend

    for _ in range(4):
        res = signer.submit_tx([MsgSend(signer.address, b"\x0a" * 20, 10)])
        assert res.code == 0
        node.produce_block()
    h = node.height
    node.close()
    # drop the LAST block record, keeping the state diff for its height
    blocks = BlockLog.recover(str(tmp_path / "d"))
    assert blocks[-1].header.height == h
    path = tmp_path / "d" / "blocks.log"
    offsets = [off for _, _, off in _Log.scan(str(path))]
    _Log.truncate_to(str(path), offsets[-2])

    node2, _ = _make_node(tmp_path / "d")
    assert node2.height == h - 1
    node2.close()


def test_orphan_state_log_without_blocks_resets_cleanly(tmp_path):
    """Crash inside the first block's fsync window: state.log has records
    but blocks.log has none.  The stale state records must be discarded —
    a fresh chain starts and keeps working across a further restart
    (regression: duplicate genesis records used to brick recovery with a
    hash mismatch)."""
    node, alice = _make_node(tmp_path / "d")
    signer = Signer(node, alice)
    from celestia_tpu.state.tx import MsgSend

    res = signer.submit_tx([MsgSend(signer.address, b"\x0b" * 20, 10)])
    assert res.code == 0
    node.produce_block()
    node.close()
    os.remove(tmp_path / "d" / "blocks.log")  # blocks never hit disk

    node2, _ = _make_node(tmp_path / "d")
    assert node2.height == 1  # fresh genesis, not a corrupted resume
    signer2 = Signer(node2, alice)
    res = signer2.submit_tx([MsgSend(signer2.address, b"\x0b" * 20, 20)])
    assert res.code == 0
    node2.produce_block()
    h = node2.height
    ah = node2.app.store.committed_hash(h)
    node2.close()
    node3, _ = _make_node(tmp_path / "d")  # and recovery still works
    assert node3.height == h
    assert node3.app.store.committed_hash(h) == ah
    node3.close()


def test_snapshot_restore_adopts_data_dir(tmp_path):
    """A node restored from a state-sync snapshot with a data_dir seeds a
    base checkpoint and logs new blocks; the NEXT restart recovers from
    disk, past the snapshot height."""
    from celestia_tpu.state.tx import MsgSend

    snap_dir = str(tmp_path / "snaps")
    node, alice = _make_node(
        tmp_path / "d1", snapshot_dir=snap_dir, snapshot_interval=2
    )
    signer = Signer(node, alice)
    for _ in range(4):
        res = signer.submit_tx([MsgSend(signer.address, b"\x0c" * 20, 5)])
        assert res.code == 0
        node.produce_block()
    node.close()

    node2 = TestNode.from_snapshot(
        snap_dir, auto_produce=True, data_dir=str(tmp_path / "d2")
    )
    s = node2.app.store.last_height
    signer2 = Signer(node2, alice)
    res = signer2.submit_tx([MsgSend(signer2.address, b"\x0c" * 20, 5)])
    assert res.code == 0, res.log
    node2.produce_block()
    h = node2.height
    assert h > s
    ah = node2.app.store.committed_hash(h)
    node2.close()

    node3, _ = _make_node(tmp_path / "d2")
    assert node3.height == h
    assert node3.app.store.committed_hash(h) == ah
    node3.close()


def test_memory_stays_flat_over_long_chain(tmp_path):
    """No per-height full-state copies: committed history is bounded by
    the store's history window regardless of chain length."""
    node, alice = _make_node(tmp_path / "d")
    node.app.store.history_keep = 16
    for _ in range(120):
        node.produce_block()
    store = node.app.store
    assert len(store._meta) <= 16
    assert len(store._reverse_diffs) <= 16
    # merkle garbage is collected: node count is O(live state), not O(chain)
    live = len(store._nodes)
    for _ in range(64):
        node.produce_block()
    assert len(store._nodes) < live * 2
    node.close()


@pytest.mark.slow
def test_kill9_cli_node_restarts_and_catches_up(tmp_path):
    """The real thing: `celestia-tpu start` as an OS process, kill -9 it
    mid-chain, start again — it recovers from the data dir (no snapshot)
    and keeps producing from where it crashed."""
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "TF_CPP_MIN_LOG_LEVEL": "3",
    }
    home = tmp_path / "home"

    def cli(*args, timeout=420):
        return subprocess.run(
            [sys.executable, "-m", "celestia_tpu.cli", "--home", str(home), *args],
            capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env,
        )

    out = cli("keys", "add", "alice", timeout=60)
    assert out.returncode == 0, out.stderr
    alice = json.loads(out.stdout)["address"]
    out = cli("init", "--chain-id", "crashnet-1", "--fund-keyring", str(10**12),
              timeout=60)
    assert out.returncode == 0, out.stderr

    def start():
        proc = subprocess.Popen(
            [sys.executable, "-m", "celestia_tpu.cli", "--home", str(home),
             "start", "--grpc-address", "127.0.0.1:0",
             "--block-interval", "0.5"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            cwd=REPO, env=env,
        )
        line = proc.stdout.readline()
        info = json.loads(line)
        return proc, info["grpc"]

    proc, grpc_addr = start()
    try:
        out = cli("tx", "--node", grpc_addr, "--from", "alice",
                  "send", "0" * 40, "12345")
        assert out.returncode == 0, out.stderr + out.stdout
        # let a few empty blocks commit, then SIGKILL with no warning
        time.sleep(3)
    finally:
        proc.kill()
        proc.wait()

    blocks_before = BlockLog.recover(str(home / "data"))
    assert blocks_before, "no blocks persisted before the crash"
    h_before = blocks_before[-1].header.height

    proc, grpc_addr = start()
    try:
        out = cli("query", "--node", grpc_addr, "balance", alice, timeout=120)
        assert out.returncode == 0, out.stderr + out.stdout
        bal = json.loads(out.stdout)
        assert int(bal["balance"]) < 10**12  # the pre-crash transfer survived
        # the chain keeps growing past the crash height
        deadline = time.time() + 60
        while time.time() < deadline:
            out = cli("status", "--node", grpc_addr, timeout=60)
            if out.returncode == 0 and json.loads(out.stdout)["height"] > h_before:
                break
            time.sleep(1)
        else:
            pytest.fail(f"chain did not grow past crash height {h_before}")
    finally:
        proc.kill()
        proc.wait()
