"""Process-level e2e: real OS processes for node and clients.

The testground-style tier of the reference's test strategy (SURVEY §4 #5:
leader/follower processes coordinated externally —
test/testground/network/entry_point.go, test/e2e): a LEADER process runs
``celestia-tpu start`` (full node + gRPC service); FOLLOWER processes drive
it through the CLI — tx submission, queries, txsim load — over a real
network boundary, with nothing shared but the port.
"""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

_CHILD_ENV = {
    **os.environ,
    # followers must not contend with the parent pytest process (or the
    # leader) for the single TPU device
    "JAX_PLATFORMS": "cpu",
    "TF_CPP_MIN_LOG_LEVEL": "3",
}


def _cli(home, *args, timeout=420):
    proc = subprocess.run(
        [sys.executable, "-m", "celestia_tpu.cli", "--home", str(home), *args],
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=REPO,
        env=_CHILD_ENV,
    )
    return proc


@pytest.fixture(scope="module")
def leader(tmp_path_factory):
    home = tmp_path_factory.mktemp("leader-home")
    out = _cli(home, "keys", "add", "alice", timeout=60)
    assert out.returncode == 0, out.stderr
    alice = json.loads(out.stdout)["address"]
    out = _cli(
        home, "init", "--chain-id", "procnet-1",
        "--fund-keyring", str(10**12), timeout=60,
    )
    assert out.returncode == 0, out.stderr

    node = subprocess.Popen(
        [
            sys.executable, "-m", "celestia_tpu.cli", "--home", str(home),
            "start", "--grpc-address", "127.0.0.1:0",  # ephemeral port
            "--block-interval", "0.3",
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
        cwd=REPO,
        env=_CHILD_ENV,
    )
    # the startup JSON line carries the bound address
    line = node.stdout.readline()
    assert node.poll() is None, "leader process died at startup"
    address = json.loads(line)["grpc"]
    yield home, alice, address
    node.send_signal(signal.SIGINT)
    try:
        node.wait(timeout=10)
    except subprocess.TimeoutExpired:
        node.kill()


def test_follower_submits_and_queries(leader):
    home, alice, addr = leader
    # follower 1: PFB submission, confirmed over the wire
    out = _cli(
        home, "tx", "--node", "%s" % addr, "--from", "alice",
        "pay-for-blob", "6d756c746970726f63", "ab" * 600,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["code"] == 0 and res["height"] >= 1

    # follower 2 (separate process): sees the tx and the balance change
    out = _cli(home, "query", "--node", "%s" % addr,
               "tx", res["txhash"])
    assert out.returncode == 0, out.stderr
    info = json.loads(out.stdout.strip().splitlines()[-1])
    assert info["code"] == 0 and info["height"] == res["height"]

    out = _cli(home, "query", "--node", "%s" % addr, "balance", alice)
    assert out.returncode == 0, out.stderr
    bal = json.loads(out.stdout.strip().splitlines()[-1])["balance"]
    assert bal < 10**12  # fees deducted

    # chain keeps progressing underneath the followers
    out = _cli(home, "status", "--node", "%s" % addr)
    h1 = json.loads(out.stdout.strip().splitlines()[-1])["height"]
    time.sleep(1.5)
    out = _cli(home, "status", "--node", "%s" % addr)
    h2 = json.loads(out.stdout.strip().splitlines()[-1])["height"]
    assert h2 > h1


def test_follower_txsim_load(leader):
    home, _alice, addr = leader
    out = _cli(
        home, "txsim", "--node", "%s" % addr, "--from", "alice",
        "--blob", "1", "--send", "1", "--iterations", "2",
        "--blob-size-max", "1200", "--funding", str(10**9),
    )
    assert out.returncode == 0, out.stdout + out.stderr
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["submitted"] == 4 and rep["failed"] == 0


def test_three_process_validator_net(tmp_path_factory):
    """Three validator PROCESSES + the coordinator CLI: replication with
    nothing shared but a genesis file and gRPC addresses."""
    from celestia_tpu.utils.secp256k1 import PrivateKey

    base = tmp_path_factory.mktemp("procnet")
    val_keys = [PrivateKey.from_seed(b"procnet-val-%d" % i) for i in range(3)]
    genesis = {
        "chain_id": "procnet-3",
        "genesis_time_ns": 1_700_000_000_000_000_000,
        "accounts": [
            {"address": k.public_key().address().hex(), "balance": 10**12}
            for k in val_keys
        ],
        "validators": [
            {
                "address": k.public_key().address().hex(),
                "self_delegation": 100_000_000,
            }
            for k in val_keys
        ],
    }
    shared = base / "genesis.json"
    shared.write_text(json.dumps(genesis))

    nodes, addrs = [], []
    try:
        for i in range(3):
            home = base / f"val{i}"
            out = _cli(home, "init", "--chain-id", "procnet-3",
                       "--genesis", str(shared), timeout=60)
            assert out.returncode == 0, out.stderr
            key_file = home / "config" / "priv_validator_key.json"
            key_file.write_text(
                json.dumps({"priv_key": f"{val_keys[i].d:064x}"})
            )
            proc = subprocess.Popen(
                [
                    sys.executable, "-m", "celestia_tpu.cli",
                    "--home", str(home), "start", "--validator",
                    "--grpc-address", "127.0.0.1:0",
                ],
                stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL,
                text=True,
                cwd=REPO,
                env=_CHILD_ENV,
            )
            line = proc.stdout.readline()
            assert proc.poll() is None, f"validator {i} died at startup"
            addrs.append(json.loads(line)["grpc"])
            nodes.append(proc)

        out = subprocess.run(
            [
                sys.executable, "-m", "celestia_tpu.cli", "coordinator",
                "--peers", ",".join(addrs), "--blocks", "4",
                "--block-interval", "0.1",
            ],
            capture_output=True,
            text=True,
            timeout=420,
            cwd=REPO,
            env=_CHILD_ENV,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        lines = [json.loads(l) for l in out.stdout.strip().splitlines()]
        assert [b["height"] for b in lines] == [2, 3, 4, 5]
        # every committed block reports one agreed app hash; proposers rotate
        assert len({b["proposer"] for b in lines}) == 3
        # all three validator processes report the same chain state
        statuses = []
        for addr in addrs:
            out = _cli(base / "val0", "status", "--node", addr)
            statuses.append(json.loads(out.stdout.strip().splitlines()[-1]))
        assert {s["height"] for s in statuses} == {5}
        assert len({s["app_hash"] for s in statuses}) == 1
        assert len({s["data_root"] for s in statuses}) == 1
    finally:
        for proc in nodes:
            proc.send_signal(signal.SIGINT)
        for proc in nodes:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
