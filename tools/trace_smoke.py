"""trace-smoke: the observability plane's boot gate (`make trace-smoke`).

Leg 1 (single node): runs ONE tiny-k testnode block with tracing
enabled and asserts:

* the ring holds a prepare + process trace for the block,
* the prepare tree contains square_build and an extend phase with a
  roots child (the acceptance shape),
* the Chrome trace document is schema-valid (validate_chrome_trace) and
  JSON-serializable — i.e. it opens in Perfetto as-is,
* the Prometheus exposition of the same run parses line by line.

Leg 2 (two nodes, PR 9): spins TWO traced validator processes sharing a
genesis, drives one block through the process coordinator, fans
TraceDump + clock probes out, merges the dumps (node/cluster.py) and
asserts the merged document is schema-valid with both node tracks and a
non-empty cross-node parent/flow link between the proposer's prepare
and the validator's process spans.

Exit 0 + one summary JSON line per leg on success; non-zero with the
reason on any failure.  Runs on the CPU backend (no device required).
"""

import json
import os
import signal
import subprocess
import sys
import tempfile

# runnable as `python tools/trace_smoke.py` from the repo root
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")

    from celestia_tpu.client.signer import Signer
    from celestia_tpu.da import eds_cache
    from celestia_tpu.node.testnode import TestNode
    from celestia_tpu.state.tx import MsgSend
    from celestia_tpu.utils import tracing
    from celestia_tpu.utils.secp256k1 import PrivateKey

    tracing.enable(4)
    eds_cache.clear()
    key = PrivateKey.from_seed(b"trace-smoke")
    node = TestNode(funded_accounts=[(key, 10**12)], auto_produce=False)
    signer = Signer(node, key)
    res = signer._broadcast(
        lambda: signer.sign_tx(
            [MsgSend(signer.address, b"\x11" * 20, 1000)]
        ).marshal()
    )
    if res.code != 0:
        print(f"trace-smoke: broadcast failed: {res.log}", file=sys.stderr)
        return 1
    node.produce_block()

    traces = tracing.block_traces()
    names = {tr.name for tr in traces}
    if not {"prepare_proposal", "process_proposal"} <= names:
        print(f"trace-smoke: missing block traces, got {names}", file=sys.stderr)
        return 1
    prep = [t for t in traces if t.name == "prepare_proposal"][-1]
    if not prep.spans:
        print("trace-smoke: prepare trace has no spans", file=sys.stderr)
        return 1

    def flat(node):
        out = {node["name"]}
        for c in node["children"]:
            out |= flat(c)
        return out

    tree_names = flat(prep.tree())
    for required in ("square_build", "extend", "roots"):
        if required not in tree_names:
            print(
                f"trace-smoke: span {required!r} missing from the prepare "
                f"tree {sorted(tree_names)}",
                file=sys.stderr,
            )
            return 1

    dump = tracing.trace_dump()
    problems = tracing.validate_chrome_trace(dump)
    if problems:
        print(f"trace-smoke: invalid trace JSON: {problems}", file=sys.stderr)
        return 1
    encoded = json.dumps(dump)  # must serialize for Perfetto

    # the metrics side of the plane: every exposition line must parse
    # (ONE validator, shared with tests/test_tracing.py)
    from celestia_tpu.utils.telemetry import validate_exposition

    bad = validate_exposition(node.app.telemetry.export_prometheus())
    if bad:
        print(
            f"trace-smoke: malformed exposition lines: {bad[:3]!r}",
            file=sys.stderr,
        )
        return 1

    print(
        json.dumps(
            {
                "trace_smoke": "ok",
                "height": node.height,
                "blocks_traced": len(traces),
                "prepare_spans": len(prep.spans),
                "trace_bytes": len(encoded),
                "prepare_breakdown": tracing.TRACER.phase_breakdown(prep),
            }
        )
    )
    return 0


def _readline_deadline(proc, timeout_s: float = 180.0):
    """One stdout line from a subprocess, bounded: a validator that
    hangs before printing its startup JSON must fail the gate loudly,
    never hang it (stderr goes to DEVNULL, so a silent hang would be
    undebuggable in CI).  A daemon reader thread + join timeout — NOT
    select() on the pipe: proc.stdout is a buffered text stream, and
    polling its fd after a partial read misses data already slurped
    into the Python-level buffer."""
    import threading

    out = []
    t = threading.Thread(
        target=lambda: out.append(proc.stdout.readline()), daemon=True
    )
    t.start()
    t.join(timeout_s)
    if not out or not out[0]:
        return None
    return out[0]


def two_node_leg() -> int:
    """Spin two traced validator processes, drive one block, merge the
    dumps and gate on the cross-node link (the PR-9 acceptance shape)."""
    from celestia_tpu.client.remote import RemoteNode
    from celestia_tpu.node import cluster
    from celestia_tpu.node.coordinator import (
        PeerValidator,
        ProcessCoordinator,
    )
    from celestia_tpu.utils import tracing
    from celestia_tpu.utils.secp256k1 import PrivateKey

    base = tempfile.mkdtemp(prefix="trace-smoke-2node-")
    keys = [PrivateKey.from_seed(b"trace-smoke-val-%d" % i) for i in range(2)]
    genesis = {
        "chain_id": "trace-smoke-2",
        "genesis_time_ns": 1_700_000_000_000_000_000,
        "accounts": [
            {"address": k.public_key().address().hex(), "balance": 10**12}
            for k in keys
        ],
        "validators": [
            {
                "address": k.public_key().address().hex(),
                "self_delegation": 100_000_000,
            }
            for k in keys
        ],
    }
    shared = os.path.join(base, "genesis.json")
    with open(shared, "w") as f:
        json.dump(genesis, f)

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "TF_CPP_MIN_LOG_LEVEL": "3",
        "CELESTIA_TPU_TRACE": "1",
    }
    procs, clients = [], []
    try:
        for i in range(2):
            home = os.path.join(base, f"val{i}")
            r = subprocess.run(
                [
                    sys.executable, "-m", "celestia_tpu.cli",
                    "--home", home, "init",
                    "--chain-id", "trace-smoke-2", "--genesis", shared,
                ],
                capture_output=True, text=True, timeout=120,
                cwd=REPO, env=env,
            )
            if r.returncode != 0:
                print(f"trace-smoke-2node: init failed: {r.stderr}",
                      file=sys.stderr)
                return 1
            with open(
                os.path.join(home, "config", "priv_validator_key.json"), "w"
            ) as f:
                json.dump({"priv_key": f"{keys[i].d:064x}"}, f)
            proc = subprocess.Popen(
                [
                    sys.executable, "-m", "celestia_tpu.cli",
                    "--home", home, "start", "--validator",
                    "--grpc-address", "127.0.0.1:0",
                    "--warm-squares", "",
                ],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True, cwd=REPO,
                env={**env, "CELESTIA_TPU_NODE_ID": f"val-{i}"},
            )
            line = _readline_deadline(proc)
            if line is None or proc.poll() is not None:
                why = "died" if proc.poll() is not None else "hung"
                proc.kill()
                print(
                    f"trace-smoke-2node: validator {i} {why} at startup",
                    file=sys.stderr,
                )
                return 1
            procs.append(proc)
            clients.append(
                RemoteNode(json.loads(line)["grpc"], timeout_s=120.0)
            )

        coord = ProcessCoordinator(
            [
                PeerValidator(name=f"val-{i}", client=c)
                for i, c in enumerate(clients)
            ]
        )
        coord.produce_block()

        merged = cluster.cluster_trace(clients)
        problems = tracing.validate_chrome_trace(merged)
        if problems:
            print(f"trace-smoke-2node: invalid merged trace: {problems[:5]}",
                  file=sys.stderr)
            return 1
        node_ids = {n["node_id"] for n in merged["otherData"]["nodes"]}
        if node_ids != {"val-0", "val-1"}:
            print(f"trace-smoke-2node: wrong node tracks: {node_ids}",
                  file=sys.stderr)
            return 1
        flows = merged["otherData"]["cross_node_flows"]
        if flows < 1:
            print("trace-smoke-2node: no cross-node flow links in the merge",
                  file=sys.stderr)
            return 1
        by_pid = {}
        for ev in merged["traceEvents"]:
            if ev.get("ph") == "X":
                by_pid.setdefault(ev["pid"], set()).add(ev["name"])
        prep_pids = {p for p, names in by_pid.items()
                     if "prepare_proposal" in names}
        proc_pids = {p for p, names in by_pid.items()
                     if "process_proposal" in names}
        if not prep_pids or not (proc_pids - prep_pids):
            print(
                "trace-smoke-2node: prepare/process spans not on separate "
                f"node tracks (prepare pids {prep_pids}, process pids "
                f"{proc_pids})",
                file=sys.stderr,
            )
            return 1
        print(
            json.dumps(
                {
                    "trace_smoke_2node": "ok",
                    "nodes": sorted(node_ids),
                    "cross_node_flows": flows,
                    "events": len(merged["traceEvents"]),
                }
            )
        )
        return 0
    finally:
        for c in clients:
            c.close()
        for proc in procs:
            proc.send_signal(signal.SIGINT)
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


if __name__ == "__main__":
    rc = main()
    if rc == 0:
        rc = two_node_leg()
    sys.exit(rc)
