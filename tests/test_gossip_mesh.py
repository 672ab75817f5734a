"""P2P gossip mesh: consensus without any relay (VERDICT r3 #4).

The star bft-relay was a single point of failure/censorship; the mesh
(node/gossip.py) floods consensus messages peer-to-peer with dedup,
runs node-local round timers, and gossips txs by want/have — so killing
the relay mid-run must not stop the chain, and a tx submitted to ONE
validator must land in a block via gossip hops only.

Reference role: celestia-core p2p (SURVEY §2.2), CAT pool
(specs/cat_pool.md).
"""

import time

import numpy as np
import pytest

from celestia_tpu.client.remote import RemoteNode
from celestia_tpu.client.signer import Signer
from celestia_tpu.node.coordinator import BFTRelay, PeerValidator
from celestia_tpu.node.gossip import GossipEngine
from celestia_tpu.node.server import NodeServer
from celestia_tpu.node.testnode import TestNode
from celestia_tpu.state.tx import MsgSend
from celestia_tpu.utils.secp256k1 import PrivateKey


def _valset(keys, power=100):
    return [
        {
            "address": k.public_key().address().hex(),
            "pubkey": k.public_key().compressed().hex(),
            "power": power,
        }
        for k in keys
    ]


def _genesis(keys, chain_id, funded=None):
    return {
        "chain_id": chain_id,
        "genesis_time_ns": 1_700_000_000_000_000_000,
        "accounts": [
            {"address": k.public_key().address().hex(), "balance": 10**12}
            for k in keys
        ]
        + [
            {"address": key.public_key().address().hex(), "balance": bal}
            for key, bal in (funded or [])
        ],
        "validators": [
            {
                "address": k.public_key().address().hex(),
                "self_delegation": 100_000_000,
            }
            for k in keys
        ],
    }


def _warm():
    from celestia_tpu.da import dah as dah_mod

    for k in (1, 2):
        dah_mod.extend_and_header(np.zeros((k, k, 512), dtype=np.uint8))


def _mesh(chain_id, n=3, funded=None):
    """n BFT validators + servers + fully-connected gossip engines."""
    keys = [
        PrivateKey.from_seed(b"%s-val-%d" % (chain_id.encode(), i))
        for i in range(n)
    ]
    genesis = _genesis(keys, chain_id, funded=funded)
    valset = _valset(keys)
    nodes, servers = [], []
    for i in range(n):
        node = TestNode(
            chain_id=chain_id, genesis=genesis,
            validator_key=keys[i], auto_produce=False,
        )
        node.enable_bft(valset)
        server = NodeServer(node, block_interval_s=None)
        server.start()
        nodes.append(node)
        servers.append(server)
    engines = []
    for i, node in enumerate(nodes):
        peers = [s.address for j, s in enumerate(servers) if j != i]
        engines.append(GossipEngine(node, peers, block_gap_s=0.05))
    return keys, nodes, servers, engines


def _wait_height(nodes, h, timeout_s=90.0):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if all(n.height >= h for n in nodes):
            return
        time.sleep(0.05)
    raise TimeoutError(
        f"mesh stuck below height {h}: {[n.height for n in nodes]}"
    )


def _teardown(servers, engines, remotes=()):
    for e in engines:
        try:
            e.stop()
        except Exception:
            pass
    for s in servers:
        try:
            s.stop()
        except Exception:
            pass
    for r in remotes:
        try:
            r.close()
        except Exception:
            pass


def test_pex_bootstrap_from_one_seed_with_bounded_fanout():
    """VERDICT r4 #6: six validators, each configured with ONLY the seed
    validator's address and flood fanout 3, must discover each other via
    PEX and commit; killing the seed mid-run must not stop the mesh
    (comet p2p/addrbook role, cmd/root.go:141)."""
    _warm()
    chain_id = "gossip-pex-1"
    n = 6
    keys = [
        PrivateKey.from_seed(b"%s-val-%d" % (chain_id.encode(), i))
        for i in range(n)
    ]
    genesis = _genesis(keys, chain_id)
    valset = _valset(keys)
    nodes, servers = [], []
    for i in range(n):
        node = TestNode(
            chain_id=chain_id, genesis=genesis,
            validator_key=keys[i], auto_produce=False,
        )
        node.enable_bft(valset)
        server = NodeServer(node, block_interval_s=None)
        server.start()
        nodes.append(node)
        servers.append(server)
    seed_addr = servers[0].address
    engines = []
    for i, node in enumerate(nodes):
        peers = [] if i == 0 else [seed_addr]  # one seed only
        engines.append(
            GossipEngine(
                node, peers, block_gap_s=0.05, fanout=3,
                pex_interval_s=0.2,
            )
        )
    seed_stopped = False
    try:
        for e in engines:
            e.start()
        _wait_height(nodes, 3, timeout_s=120.0)
        # PEX actually spread the addresses (not just seed-relayed):
        for e in engines[1:]:
            assert len(e._peers_snapshot()) >= n - 2, (
                f"PEX did not propagate: {e._peers_snapshot()}"
            )
        # the status RPC surfaces the mesh's operational stats
        r = RemoteNode(servers[1].address, timeout_s=30)
        try:
            st = r.status()
            assert st["gossip"]["peers"] >= n - 2
            assert st["gossip"]["fanout"] == 3
            assert st["gossip"]["pex_learned"] >= n - 3
        finally:
            r.close()
        # kill the seed: > 2/3 power remains, mesh must keep committing
        engines[0].stop()
        servers[0].stop()
        seed_stopped = True
        target = max(node.height for node in nodes[1:]) + 3
        _wait_height(nodes[1:], target, timeout_s=120.0)
    finally:
        if seed_stopped:
            _teardown(servers[1:], engines[1:])
        else:
            _teardown(servers, engines)


def test_state_sync_rejoin_past_decided_window(tmp_path):
    """VERDICT r4 #4 (network state-sync): a validator stopped while the
    net advances PAST the decided-log window cannot replay certificates
    one-by-one — it must fetch a served snapshot over gRPC, verify the
    anchoring certificate (2/3-signed block at snapshot height + 1 whose
    prev_app_hash commits to the snapshot state), swap the state in, and
    resume.  Reference: snapshot store wiring root.go:227-243,
    interval/keep-recent defaults default_overrides.go:296-297."""
    _warm()
    chain_id = "gossip-sync-1"
    n = 4
    keys = [
        PrivateKey.from_seed(b"%s-val-%d" % (chain_id.encode(), i))
        for i in range(n)
    ]
    genesis = _genesis(keys, chain_id)
    valset = _valset(keys)
    nodes, servers = [], []
    for i in range(n):
        node = TestNode(
            chain_id=chain_id, genesis=genesis,
            validator_key=keys[i], auto_produce=False,
            snapshot_dir=str(tmp_path / f"snap-{i}"),
            snapshot_interval=4,
        )
        node.bft_decided_log_max = 6  # shrunken window (512 in prod)
        node.enable_bft(valset)
        server = NodeServer(node, block_interval_s=None)
        server.start()
        nodes.append(node)
        servers.append(server)
    engines = []
    for i, node in enumerate(nodes):
        peers = [s.address for j, s in enumerate(servers) if j != i]
        engines.append(GossipEngine(node, peers, block_gap_s=0.05))
    eng3 = srv3 = None
    try:
        for e in engines:
            e.start()
        _wait_height(nodes, 2, timeout_s=90.0)
        # validator 3 goes offline
        engines[3].stop()
        servers[3].stop()
        offline_at = nodes[3].height
        # the live 3/4-power mesh advances far past the decided window
        _wait_height(nodes[:3], offline_at + 14, timeout_s=180.0)
        live = nodes[0]
        assert live._bft_decided_log, "decided log unexpectedly empty"
        assert min(live._bft_decided_log) > offline_at + 1, (
            "window did not prune past the laggard: test premise broken"
        )
        assert live.snapshots.latest() is not None
        # rejoin: fresh server (new port) + engine seeded with the peers
        srv3 = NodeServer(nodes[3], block_interval_s=None)
        srv3.start()
        eng3 = GossipEngine(
            nodes[3], [servers[i].address for i in range(3)],
            block_gap_s=0.05,
        )
        eng3.start()
        target = max(node.height for node in nodes[:3]) + 3
        _wait_height(nodes, target, timeout_s=180.0)
    finally:
        # engines[3]/servers[3] included: stop() is idempotent, and an
        # early failure (before the offline step) must not leak them
        _teardown(
            servers + ([srv3] if srv3 else []),
            engines + ([eng3] if eng3 else []),
        )


def test_mesh_commits_without_any_relay():
    """Three meshed validators produce blocks autonomously — no relay
    process exists at any point."""
    _warm()
    keys, nodes, servers, engines = _mesh("mesh-solo")
    try:
        for e in engines:
            e.start()
        _wait_height(nodes, 4)
        # identical state everywhere at a common height
        h = min(n.height for n in nodes)
        hashes = {n.app.store.committed_hash(h) for n in nodes}
        assert len(hashes) == 1
        # every node decided from a certificate it verified itself
        for n in nodes:
            d = n._bft.decided.get(h) or n._bft.decided[max(n._bft.decided)]
            power = sum(
                n._bft.validators[v.validator] for v in d.precommits
            )
            assert power * 3 >= n._bft.total_power * 2
    finally:
        _teardown(servers, engines)


def test_tx_submitted_to_one_validator_lands_via_gossip():
    """want/have tx gossip: a tx broadcast to ONE node propagates to the
    proposer (whoever it is) and commits; all replicas apply it."""
    _warm()
    alice = PrivateKey.from_seed(b"mesh-tx-alice")
    keys, nodes, servers, engines = _mesh(
        "mesh-tx", funded=[(alice, 10**12)]
    )
    remotes = [RemoteNode(s.address, timeout_s=30.0) for s in servers]
    try:
        for e in engines:
            e.start()
        _wait_height(nodes, 2)
        signer = Signer(remotes[0], alice)
        bob = b"\x61" * 20
        raw = signer.sign_tx([MsgSend(signer.address, bob, 5_500)]).marshal()
        res = remotes[0].broadcast_tx(raw)  # ONE validator only
        assert res.code == 0, res.log
        deadline = time.time() + 90
        while time.time() < deadline:
            if all(n.app.bank.balance(bob) == 5_500 for n in nodes):
                break
            time.sleep(0.1)
        assert all(n.app.bank.balance(bob) == 5_500 for n in nodes), (
            "tx did not replicate through gossip hops"
        )
    finally:
        _teardown(servers, engines, remotes)


def test_relay_killed_mid_run_network_keeps_committing():
    """Bootstrap with the dumb relay, then kill it: the meshed
    validators keep deciding new heights without it."""
    _warm()
    keys, nodes, servers, engines = _mesh("mesh-relaykill")
    remotes = [RemoteNode(s.address, timeout_s=30.0) for s in servers]
    try:
        # phase 1: the legacy relay drives one block (bootstrap role)
        relay = BFTRelay(
            [
                PeerValidator(name=f"val-{i}", client=r)
                for i, r in enumerate(remotes)
            ]
        )
        relay.produce_block()
        assert all(n.height == 2 for n in nodes)
        del relay  # the relay is gone for good
        # phase 2: the mesh takes over and the chain keeps moving
        for e in engines:
            e.start()
        _wait_height(nodes, 5)
        h = min(n.height for n in nodes)
        hashes = {n.app.store.committed_hash(h) for n in nodes}
        assert len(hashes) == 1
    finally:
        _teardown(servers, engines, remotes)


def test_mesh_survives_one_dead_validator_and_catches_it_up():
    """2/3 power keeps committing while one validator's server is down;
    on revival the mesh's certificate-verified catch-up pulls it level."""
    _warm()
    keys, nodes, servers, engines = _mesh("mesh-crash")
    try:
        for e in engines:
            e.start()
        _wait_height(nodes, 3)
        # kill validator 2 entirely (engine + server)
        engines[2].stop()
        servers[2].stop()
        h_dead = nodes[2].height
        _wait_height(nodes[:2], h_dead + 2)
        # revive: new server on the same node + a fresh engine
        revived = NodeServer(nodes[2], block_interval_s=None)
        revived.start()
        servers.append(revived)
        peers = [servers[0].address, servers[1].address]
        e2 = GossipEngine(nodes[2], peers, block_gap_s=0.05)
        # the live validators must learn the revived address: their peer
        # lists pointed at the OLD (dead) server address, so re-point
        for i in (0, 1):
            engines[i].peer_addrs = [
                servers[1 - i].address, revived.address
            ]
        engines.append(e2)
        e2.start()
        target = max(n.height for n in nodes[:2]) + 2
        _wait_height(nodes, target)
        h = min(n.height for n in nodes)
        hashes = {n.app.store.committed_hash(h) for n in nodes}
        assert len(hashes) == 1
    finally:
        _teardown(servers, engines)


def test_bft_catchup_batch_adopts_window_and_stops_on_bad_wire():
    """The batched catch-up entry (node.bft_catchup_batch, ISSUE 14):
    a laggard adopts a whole window of decided blocks in one call —
    the extends warm as a batch when a mesh is active (exercised in
    tests/_mesh_live_isolated.py; here the mesh is off, proving the
    plain degradation path adopts identically) — and a tampered wire
    mid-window stops adoption exactly where per-block replay would."""
    _warm()
    keys, nodes, servers, engines = _mesh("mesh-batchcatch", n=3)
    try:
        for e in engines:
            e.start()
        _wait_height(nodes, 5)
        for e in engines:
            e.stop()
        src = nodes[0]
        wires = []
        for h in range(2, src.height + 1):
            d = src.bft_decided(h)
            if d is None:
                break
            wires.append(d)
        assert len(wires) >= 3
        # a fresh laggard on the same chain (height 1 after genesis)
        laggard = TestNode(
            chain_id="mesh-batchcatch",
            genesis=_genesis(keys, "mesh-batchcatch"),
            validator_key=keys[0],
            auto_produce=False,
        )
        laggard.enable_bft(_valset(keys))
        adopted, why = laggard.bft_catchup_batch(wires)
        assert adopted == len(wires), why
        assert laggard.height == 1 + len(wires)
        assert laggard.app.store.committed_hash(
            laggard.height
        ) == src.app.store.committed_hash(laggard.height)

        # tampered certificate mid-window: adoption stops at the bad wire
        laggard2 = TestNode(
            chain_id="mesh-batchcatch",
            genesis=_genesis(keys, "mesh-batchcatch"),
            validator_key=keys[0],
            auto_produce=False,
        )
        laggard2.enable_bft(_valset(keys))
        import copy

        bad = copy.deepcopy(wires)
        bad[1]["precommits"] = bad[1]["precommits"][:1]  # below 2/3
        adopted, why = laggard2.bft_catchup_batch(bad)
        assert adopted == 1
        assert why
        assert laggard2.height == 2
    finally:
        _teardown(servers, engines)


@pytest.mark.slow
def test_mesh_three_os_processes(tmp_path_factory):
    """Full dress: three ``start --bft-valset --peers`` OS processes and
    NO relay process at any point — the mesh self-paces, and a tx
    submitted to one process replicates everywhere."""
    import json
    import os
    import signal
    import socket
    import subprocess
    import sys
    from pathlib import Path

    REPO = Path(__file__).resolve().parents[1]
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "TF_CPP_MIN_LOG_LEVEL": "3",
    }
    base = tmp_path_factory.mktemp("meshprocnet")
    val_keys = [PrivateKey.from_seed(b"meshproc-val-%d" % i) for i in range(3)]
    alice = PrivateKey.from_seed(b"meshproc-alice")
    genesis = _genesis(val_keys, "meshproc-3", funded=[(alice, 10**12)])
    shared = base / "genesis.json"
    shared.write_text(json.dumps(genesis))
    valset_file = base / "valset.json"
    valset_file.write_text(json.dumps(_valset(val_keys)))

    # pre-assign ports so each process can name its peers at startup
    ports = []
    socks = []
    for _ in range(3):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    addrs = [f"127.0.0.1:{p}" for p in ports]

    def _cli(home, *args, timeout=420):
        return subprocess.run(
            [sys.executable, "-m", "celestia_tpu.cli", "--home", str(home),
             *args],
            capture_output=True, text=True, timeout=timeout, cwd=REPO,
            env=env,
        )

    procs = []
    try:
        for i in range(3):
            home = base / f"val{i}"
            out = _cli(home, "init", "--chain-id", "meshproc-3",
                       "--genesis", str(shared), timeout=60)
            assert out.returncode == 0, out.stderr
            key_file = home / "config" / "priv_validator_key.json"
            key_file.write_text(
                json.dumps({"priv_key": f"{val_keys[i].d:064x}"})
            )
            peers = ",".join(a for j, a in enumerate(addrs) if j != i)
            proc = subprocess.Popen(
                [
                    sys.executable, "-m", "celestia_tpu.cli",
                    "--home", str(home), "start",
                    "--bft-valset", str(valset_file),
                    "--grpc-address", addrs[i],
                    "--peers", peers,
                    "--block-interval", "0.2",
                ],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True, cwd=REPO, env=env,
            )
            line = proc.stdout.readline()
            assert proc.poll() is None, f"validator {i} died at startup"
            assert json.loads(line)["grpc"] == addrs[i]
            procs.append(proc)

        remotes = [RemoteNode(a, timeout_s=30.0) for a in addrs]
        deadline = time.time() + 300
        while time.time() < deadline:
            try:
                if all(r.height >= 4 for r in remotes):
                    break
            except Exception:
                pass
            time.sleep(0.5)
        heights = [r.height for r in remotes]
        assert all(h >= 4 for h in heights), f"mesh stalled: {heights}"

        # one-submission tx replication through the process mesh
        signer = Signer(remotes[0], alice)
        bob = b"\x71" * 20
        raw = signer.sign_tx([MsgSend(signer.address, bob, 3_300)]).marshal()
        assert remotes[0].broadcast_tx(raw).code == 0
        deadline = time.time() + 120
        ok = False
        while time.time() < deadline and not ok:
            try:
                ok = all(
                    int(r.abci_query(
                        "store/bank/balance", {"address": bob.hex()}
                    )) == 3_300
                    for r in remotes
                )
            except Exception:
                ok = False
            time.sleep(0.5)
        assert ok, "tx did not replicate across the process mesh"
        for r in remotes:
            r.close()
    finally:
        for proc in procs:
            proc.send_signal(signal.SIGINT)
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def test_unsigned_junk_gossip_rejected_and_harmless():
    """Unauthenticated garbage sent to the GossipMsg RPC is neither
    delivered nor re-flooded, and a sky-high claimed height cannot wedge
    the mesh into a catch-up loop — the chain keeps committing."""
    _warm()
    keys, nodes, servers, engines = _mesh("mesh-junk")
    remotes = [RemoteNode(s.address, timeout_s=30.0) for s in servers]
    try:
        for e in engines:
            e.start()
        _wait_height(nodes, 3)
        # structurally invalid junk
        assert remotes[0].gossip_msg(
            {"wire": {"kind": "vote", "garbage": True}, "sender": "evil"}
        ) is False
        # structurally valid but unsigned vote with an absurd height
        junk_vote = {
            "kind": "vote", "vtype": "precommit", "height": 10**12,
            "round": 0, "block_id": "00" * 32,
            "validator": keys[0].public_key().address().hex(),
            "signature": "00" * 64,
        }
        assert remotes[0].gossip_msg(
            {"wire": junk_vote, "sender": "evil"}
        ) is False
        # the mesh keeps deciding new heights regardless
        h0 = min(n.height for n in nodes)
        _wait_height(nodes, h0 + 2)
    finally:
        _teardown(servers, engines, remotes)
