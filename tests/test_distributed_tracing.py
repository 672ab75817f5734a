"""Cross-node distributed tracing + the cluster observability plane (PR 9).

Covers:

* the 2-node e2e: two traced validator PROCESSES, one block through the
  process coordinator — the proposer's prepare and the validator's
  process spans merge into one schema-valid Chrome trace on separate
  node tracks with an explicit cross-node parent/flow link and aligned
  clocks;
* wire-envelope versioning: a ``_tc``-bearing request against an
  un-upgraded (legacy) handler is accepted silently — no error, no
  span leak — and a context-free request against an upgraded handler
  degrades to "no remote parent";
* merge semantics (node/cluster.py): per-node pids, offset application,
  flow resolution, unresolvable links skipped;
* the chaos rider: ``gossip.fetch`` faults armed — fault instants land
  in the armed node's dump and merge onto ITS track;
* the clock-offset midpoint probe (ClockProbe RPC + estimator);
* cluster-health aggregation over live nodes (heights, breakers,
  caches, RPC byte/call counters).
"""

import json
import os
import signal
import subprocess
import sys
from pathlib import Path

import pytest

from celestia_tpu.node import cluster
from celestia_tpu.utils import faults, tracing

REPO = Path(__file__).resolve().parents[1]

_CHILD_ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "TF_CPP_MIN_LOG_LEVEL": "3",
    "CELESTIA_TPU_TRACE": "1",
}


@pytest.fixture
def tracer():
    tracing.disable()
    tracing.clear()
    tracing.enable(8)
    yield tracing
    tracing.disable()
    tracing.clear()


# ---------------------------------------------------------------------------
# merge semantics (no processes)
# ---------------------------------------------------------------------------


def _dump(nid, spans, offset_events=()):
    """A minimal per-node Chrome doc: spans = [(span_id, name, ts_us,
    dur_us, extra_args)]."""
    events = [
        {"ph": "M", "name": "process_name", "pid": 1, "args": {"name": nid}}
    ]
    for sid, name, ts, dur, extra in spans:
        events.append(
            {
                "ph": "X", "name": name, "cat": "block", "ts": ts,
                "dur": dur, "pid": 1, "tid": 5,
                "args": {"span_id": sid, "parent_id": 0, **extra},
            }
        )
    events.extend(offset_events)
    return {
        "displayTimeUnit": "ms",
        "traceEvents": events,
        "otherData": {"node_id": nid, "blocks": []},
    }


def test_merge_assigns_node_tracks_and_applies_offsets():
    parts = [
        {
            "node_id": "val-A",
            "clock_offset_s": 0.0,
            "trace": _dump("val-A", [(7, "prepare_proposal", 1000.0, 400.0, {})]),
        },
        {
            "node_id": "val-B",
            "clock_offset_s": 2.0,  # val-B's clock runs 2 s ahead
            "trace": _dump(
                "val-B",
                [(9, "process_proposal", 2_001_500.0, 300.0,
                  {"remote_node": "val-A", "remote_span": 7})],
            ),
        },
    ]
    merged = cluster.merge_node_dumps(parts)
    assert tracing.validate_chrome_trace(merged) == []
    xs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert {e["pid"] for e in xs} == {1, 2}
    b = [e for e in xs if e["pid"] == 2][0]
    # 2_001_500 us - 2 s offset = 1500 us on the collector timeline
    assert b["ts"] == pytest.approx(1500.0)
    names = [
        e["args"]["name"] for e in merged["traceEvents"]
        if e.get("ph") == "M" and e["name"] == "process_name"
    ]
    assert names == ["val-A", "val-B"]


def test_merge_emits_flow_links_and_skips_unresolvable():
    parts = [
        {
            "node_id": "val-A",
            "trace": _dump("val-A", [(7, "prepare_proposal", 1000.0, 400.0, {})]),
        },
        {
            "node_id": "val-B",
            "trace": _dump(
                "val-B",
                [
                    # resolvable: val-A span 7 exists
                    (9, "process_proposal", 2000.0, 300.0,
                     {"remote_node": "val-A", "remote_span": 7}),
                    # unresolvable: no such span in any collected dump
                    (10, "rpc.cons_commit", 2500.0, 50.0,
                     {"remote_node": "val-Z", "remote_span": 999}),
                ],
            ),
        },
    ]
    merged = cluster.merge_node_dumps(parts)
    assert tracing.validate_chrome_trace(merged) == []
    assert merged["otherData"]["cross_node_flows"] == 1
    s = [e for e in merged["traceEvents"] if e.get("ph") == "s"][0]
    f = [e for e in merged["traceEvents"] if e.get("ph") == "f"][0]
    assert s["pid"] == 1 and f["pid"] == 2 and s["id"] == f["id"]
    # the s event binds inside the source span's interval
    assert 1000.0 <= s["ts"] <= 1400.0


def test_merge_missing_or_zero_offset_defaults_to_unshifted():
    """A part with no ``clock_offset_s`` at all (an old collector, or a
    probe that failed) merges with its timestamps UNSHIFTED — identical
    to an explicit zero — and the merged nodes table still carries the
    node so downstream consumers (critpath, mesh_waterfall) resolve
    its pid."""
    from celestia_tpu.utils import critpath

    spans = [(7, "prepare_proposal", 1000.0, 400.0, {"height": 5})]
    with_zero = cluster.merge_node_dumps([
        {"node_id": "val-A", "clock_offset_s": 0.0,
         "trace": _dump("val-A", spans)},
    ])
    without = cluster.merge_node_dumps([
        {"node_id": "val-A", "trace": _dump("val-A", spans)},
    ])
    for merged in (with_zero, without):
        assert tracing.validate_chrome_trace(merged) == []
        (x,) = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
        assert x["ts"] == pytest.approx(1000.0)
        _, offsets = critpath.extract_spans(merged)
        assert offsets.get("val-A", 0.0) == 0.0


def test_critpath_over_merge_with_mixed_resolvable_links():
    """One resolvable cross-node link (the rpc envelope) next to an
    UNRESOLVABLE one on the anchor root (the origin's dump was not
    collected): the merge emits exactly one flow, and the analyzer
    still attributes the anchor's flow edge off the raw send ts while
    reporting the dangling link."""
    from celestia_tpu.utils import critpath

    parts = [
        {
            "node_id": "val-A",
            "trace": _dump("val-A", [(7, "gossip.push", 1000.0, 400.0, {})]),
        },
        {
            "node_id": "val-B",
            "trace": _dump(
                "val-B",
                [
                    # resolvable: val-A span 7 exists in the collection
                    (9, "rpc.das_sample", 2000.0, 300.0,
                     {"remote_node": "val-A", "remote_span": 7}),
                    # the ANCHOR's link is unresolvable: val-C was never
                    # collected, but its send ts still rides the args
                    (10, "process_proposal", 2500.0, 500.0,
                     {"height": 3, "remote_node": "val-C",
                      "remote_span": 555, "remote_send_ts": 0.0021}),
                ],
            ),
        },
    ]
    merged = cluster.merge_node_dumps(parts)
    assert tracing.validate_chrome_trace(merged) == []
    assert merged["otherData"]["cross_node_flows"] == 1
    report = critpath.critical_path(merged)
    assert report["root"]["name"] == "process_proposal"
    assert report["unresolved_links"] == 1
    # flow edge = anchor start (2500 us) - send ts (2100 us) = 0.4 ms;
    # val-C has no offset row, so the raw send ts rides unshifted
    assert report["propagation_delay_ms"] == pytest.approx(0.4, abs=0.01)
    assert report["attribution_ms"]["flow"] == pytest.approx(0.4, abs=0.01)


def test_merge_tolerates_zero_span_dump():
    """A node that was up but never traced a block contributes a dump
    with NO X events: the merge must keep its track (pid + process
    name), count zero flows from it, and the analyzer must anchor off
    the other node unbothered."""
    from celestia_tpu.utils import critpath

    merged = cluster.merge_node_dumps([
        {"node_id": "val-A",
         "trace": _dump("val-A",
                        [(7, "prepare_proposal", 1000.0, 400.0,
                          {"height": 2})])},
        {"node_id": "val-quiet", "trace": _dump("val-quiet", [])},
    ])
    assert tracing.validate_chrome_trace(merged) == []
    names = [
        e["args"]["name"] for e in merged["traceEvents"]
        if e.get("ph") == "M" and e["name"] == "process_name"
    ]
    assert names == ["val-A", "val-quiet"]
    assert {n["node_id"] for n in merged["otherData"]["nodes"]} == {
        "val-A", "val-quiet"
    }
    report = critpath.critical_path(merged)
    assert report["root"] == {
        "name": "prepare_proposal", "node": "val-A", "span_id": 7,
    }
    assert report["root_wall_ms"] == pytest.approx(0.4, abs=0.001)


def test_wire_context_shape_and_malformed_tolerance(tracer):
    tracing.set_node_id("ctx-node", force=True)
    with tracing.block_span("prepare_proposal", height=3):
        ctx = tracing.wire_context(height=3)
    assert ctx["n"] == "ctx-node" and ctx["h"] == 3 and ctx["s"] > 0
    assert ctx["t"] > 0
    # malformed / hostile / old-version contexts fold to no-remote-args
    for junk in (None, "junk", 42, [], {"n": "", "s": 1},
                 {"n": "x", "s": "zz"}, {"n": 0}):
        assert tracing._context_args(junk) == {}
    # a parentless context (gossip flood drained outside any span) still
    # attributes the ORIGIN node; only a valid span id is flow-linkable
    assert tracing._context_args({"n": "x"}) == {"remote_node": "x"}
    assert tracing._context_args({"n": "x", "s": -5}) == {
        "remote_node": "x"
    }
    # a good context decorates the span; block roots inherit it
    with tracing.rpc_span("rpc.cons_process", ctx):
        with tracing.block_span("process_proposal", height=3):
            pass
    tr = [t for t in tracing.block_traces() if t.name == "process_proposal"][0]
    root = [s for s in tr.spans if s.span_id == tr.root_id][0]
    assert root.args["remote_node"] == "ctx-node"
    assert root.args["remote_span"] == ctx["s"]


def test_clock_offset_estimator_midpoint(tracer):
    from celestia_tpu.utils.telemetry import clock

    est = tracing.estimate_clock_offset(lambda: clock() + 3.0, samples=4)
    assert est["offset_s"] == pytest.approx(3.0, abs=0.05)
    assert est["samples"] == 4
    assert est["rtt_s"] >= 0.0


# ---------------------------------------------------------------------------
# wire-envelope versioning (mixed-version mesh, in-process)
# ---------------------------------------------------------------------------


def _make_served_node(seed: bytes):
    from celestia_tpu.client.signer import Signer
    from celestia_tpu.node.testnode import TestNode
    from celestia_tpu.state.tx import MsgSend
    from celestia_tpu.utils.secp256k1 import PrivateKey

    key = PrivateKey.from_seed(seed)
    node = TestNode(
        funded_accounts=[(key, 10**12)],
        genesis_time_ns=1_700_000_000_000_000_000,
        auto_produce=False,
    )
    signer = Signer(node, key)
    raw = signer._broadcast(
        lambda: signer.sign_tx(
            [MsgSend(signer.address, b"\x21" * 20, 50)]
        ).marshal()
    )
    assert raw.code == 0, raw.log
    return node


def test_old_peer_drops_context_silently(tracer, monkeypatch):
    """New sender -> un-upgraded receiver: a ``_tc``-bearing request hits
    a legacy handler that only knows the named keys.  The round must
    succeed, and the receiver must record neither an rpc span nor a
    remote parent (dropped context, no error, no span leak)."""
    from celestia_tpu.client.remote import RemoteNode
    from celestia_tpu.node.server import NodeServer, NodeService

    def legacy_cons_process(self, req: bytes, ctx) -> bytes:
        q = json.loads(req)  # ignores every key it does not know
        ok, reason = self.node.cons_process(
            [bytes.fromhex(t) for t in q["block_txs"]],
            int(q["square_size"]),
            bytes.fromhex(q["data_root"]),
        )
        return json.dumps({"accept": ok, "reason": reason}).encode()

    monkeypatch.setattr(NodeService, "cons_process", legacy_cons_process)
    node = _make_served_node(b"mixed-version-old")
    with NodeServer(node) as server:
        remote = RemoteNode(server.address, timeout_s=60.0)
        p = remote.cons_prepare()
        assert p.get("_tc"), "upgraded prepare should return a context"
        ok, reason = remote.cons_process(
            p["block_txs"], p["square_size"], p["data_root"], tc=p["_tc"]
        )
        remote.close()
    assert ok, reason
    names = {s.name for tr in tracing.block_traces() for s in tr.spans}
    assert "process_proposal" in names
    # the legacy handler opened no rpc span and the block root carries
    # no remote parent: the context was DROPPED, not half-applied
    dump = tracing.trace_dump()
    evs = [e for e in dump["traceEvents"] if e.get("ph") == "X"]
    assert not any(e["name"] == "rpc.cons_process" for e in evs)
    proc_roots = [
        e for e in evs
        if e["name"] == "process_proposal" and e["args"].get("parent_id") == 0
    ]
    assert proc_roots and all(
        "remote_node" not in e["args"] for e in proc_roots
    )


def test_new_peer_accepts_contextless_and_garbage_context(tracer):
    """Old sender -> upgraded receiver: no ``_tc`` at all, and a hostile
    garbage ``_tc``, must both process normally (remote parent absent)."""
    from celestia_tpu.client.remote import RemoteNode
    from celestia_tpu.node.server import NodeServer

    node = _make_served_node(b"mixed-version-new")
    with NodeServer(node) as server:
        remote = RemoteNode(server.address, timeout_s=60.0)
        p = remote.cons_prepare()
        # raw call WITHOUT _tc (the old client's envelope, byte-identical
        # to the pre-context wire format)
        out = remote._call_json(
            "ConsProcess",
            {
                "block_txs": [t.hex() for t in p["block_txs"]],
                "square_size": p["square_size"],
                "data_root": p["data_root"].hex(),
            },
        )
        assert out["accept"], out.get("reason")
        # hostile context: junk types must not error the RPC
        out = remote._call_json(
            "ConsProcess",
            {
                "block_txs": [t.hex() for t in p["block_txs"]],
                "square_size": p["square_size"],
                "data_root": p["data_root"].hex(),
                "_tc": {"n": 123, "s": "not-an-int", "t": []},
            },
        )
        assert out["accept"], out.get("reason")
        remote.close()
    dump = tracing.trace_dump()
    evs = [e for e in dump["traceEvents"] if e.get("ph") == "X"]
    rpc_spans = [e for e in evs if e["name"] == "rpc.cons_process"]
    assert rpc_spans, "upgraded receiver records its rpc spans"
    assert all("remote_node" not in e["args"] for e in rpc_spans)


def test_rpc_byte_and_call_counters(tracer):
    """Satellite: rpc_{method}_bytes_{in,out} + call counters on both
    sides, exported through the Prometheus plane."""
    from celestia_tpu.client import remote as remote_mod
    from celestia_tpu.client.remote import RemoteNode
    from celestia_tpu.node.server import NodeServer
    from celestia_tpu.utils.telemetry import validate_exposition

    node = _make_served_node(b"rpc-telemetry")
    with NodeServer(node) as server:
        remote = RemoteNode(server.address, timeout_s=60.0)
        remote.status()
        text = remote.metrics()
        remote.close()
    assert validate_exposition(text) == []
    samples = dict(
        (name, value)
        for name, labels, value in cluster.parse_exposition(text)
        if not labels
    )
    assert samples.get("celestia_tpu_rpc_status_calls_total", 0) >= 1
    assert samples.get("celestia_tpu_rpc_status_bytes_out_total", 0) > 0
    assert samples.get("celestia_tpu_rpc_metrics_calls_total", 0) >= 1
    # client-side counters exist in this process (we just made calls)
    client_lines = remote_mod.client_rpc_exposition()
    assert any("rpc_client_status_calls_total" in ln for ln in client_lines)
    # and the fault/degradation totals ride the same exposition
    assert "celestia_tpu_fault_notes_total" in text
    assert "celestia_tpu_degradations_total" in text


# ---------------------------------------------------------------------------
# chaos rider: fault instants attributed to the right node
# ---------------------------------------------------------------------------


def test_gossip_fetch_fault_attributed_to_armed_node(tracer):
    from celestia_tpu.node.gossip import GossipEngine
    from celestia_tpu.node.testnode import TestNode

    tracing.set_node_id("chaos-val-0", force=True)
    node = TestNode(auto_produce=False,
                    genesis_time_ns=1_700_000_000_000_000_000)
    eng = GossipEngine(node, [])  # not started: we drive _pull_rpc directly
    faults.disarm()
    faults.arm("gossip.fetch", "fail_rate", rate=1.0, seed=99)
    try:
        def status_pull():
            return {"height": 1}

        with pytest.raises(faults.InjectedFault) as exc:
            eng._pull_rpc(status_pull)
        # what _catch_up does with the failure: recorded, never silent
        faults.note("gossip.fetch", exc.value)
    finally:
        faults.disarm()
    dump = tracing.trace_dump()
    fetch = [
        e for e in dump["traceEvents"]
        if e.get("ph") == "X" and e["name"] == "gossip.fetch"
    ]
    assert fetch and "error" in fetch[-1]["args"]
    notes = [
        e for e in dump["traceEvents"]
        if e.get("ph") == "i" and e["name"] == "fault.note"
    ]
    assert notes, "the swallowed failure must appear as a trace instant"
    assert all(e["args"]["node_id"] == "chaos-val-0" for e in fetch + notes)
    # merged with a healthy peer's dump, the instants stay on the armed
    # node's track
    merged = cluster.merge_node_dumps(
        [
            {"node_id": "chaos-val-0", "trace": dump},
            {"node_id": "chaos-val-1",
             "trace": _dump("chaos-val-1", [(3, "gossip.deliver", 10.0, 5.0, {})])},
        ]
    )
    assert tracing.validate_chrome_trace(merged) == []
    merged_notes = [
        e for e in merged["traceEvents"]
        if e.get("ph") == "i" and e["name"] == "fault.note"
    ]
    assert merged_notes and all(e["pid"] == 1 for e in merged_notes)


# ---------------------------------------------------------------------------
# the 2-process e2e (real network boundary, separate tracers)
# ---------------------------------------------------------------------------


def _cli(home, *args, timeout=420, env=_CHILD_ENV):
    return subprocess.run(
        [sys.executable, "-m", "celestia_tpu.cli", "--home", str(home), *args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env,
    )


@pytest.fixture(scope="module")
def traced_pair(tmp_path_factory):
    """Two traced validator processes sharing a genesis, plus RemoteNode
    clients: the smallest real mesh a cross-node trace can span."""
    from celestia_tpu.client.remote import RemoteNode
    from celestia_tpu.utils.secp256k1 import PrivateKey

    base = tmp_path_factory.mktemp("traced-pair")
    keys = [PrivateKey.from_seed(b"traced-pair-%d" % i) for i in range(2)]
    genesis = {
        "chain_id": "traced-pair",
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
    shared = base / "genesis.json"
    shared.write_text(json.dumps(genesis))
    procs, clients = [], []
    try:
        for i in range(2):
            home = base / f"val{i}"
            out = _cli(home, "init", "--chain-id", "traced-pair",
                       "--genesis", str(shared), timeout=120)
            assert out.returncode == 0, out.stderr
            (home / "config" / "priv_validator_key.json").write_text(
                json.dumps({"priv_key": f"{keys[i].d:064x}"})
            )
            proc = subprocess.Popen(
                [
                    sys.executable, "-m", "celestia_tpu.cli",
                    "--home", str(home), "start", "--validator",
                    "--grpc-address", "127.0.0.1:0", "--warm-squares", "",
                ],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True, cwd=REPO,
                env={**_CHILD_ENV, "CELESTIA_TPU_NODE_ID": f"val-{i}"},
            )
            line = proc.stdout.readline()
            assert proc.poll() is None, f"validator {i} died at startup"
            procs.append(proc)
            clients.append(
                RemoteNode(json.loads(line)["grpc"], timeout_s=120.0)
            )
        yield clients
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


def test_two_node_block_merges_with_cross_node_link(traced_pair):
    """THE acceptance shape: one block across two traced processes —
    prepare on the proposer's track, process on the validator's, one
    schema-valid merged document, explicit cross-node parent + flow."""
    from celestia_tpu.node.coordinator import (
        PeerValidator,
        ProcessCoordinator,
    )

    clients = traced_pair
    coord = ProcessCoordinator(
        [PeerValidator(name=f"val-{i}", client=c)
         for i, c in enumerate(clients)]
    )
    coord.produce_block()
    height = coord.height

    parts = [cluster.collect_trace(c) for c in clients]
    assert [p["node_id"] for p in parts] == ["val-0", "val-1"]
    assert all(p["enabled"] for p in parts)
    # clocks probed per peer; same host, so offsets are tiny but REAL
    assert all(abs(p["clock_offset_s"]) < 2.0 for p in parts)

    # the validator's process root carries the proposer's prepare root
    # as its explicit cross-node parent
    val_events = [
        e for e in parts[1]["trace"]["traceEvents"] if e.get("ph") == "X"
    ]
    proc_roots = [
        e for e in val_events
        if e["name"] == "process_proposal"
        and e["args"].get("height") == height
        and e["args"].get("parent_id") == 0
    ]
    assert proc_roots, "validator must hold a process trace for the height"
    args = proc_roots[-1]["args"]
    assert args.get("remote_node") == "val-0"
    assert isinstance(args.get("remote_span"), int) and args["remote_span"] > 0
    prep_roots = [
        e for e in parts[0]["trace"]["traceEvents"]
        if e.get("ph") == "X" and e["name"] == "prepare_proposal"
        and e["args"].get("height") == height
    ]
    assert prep_roots, "proposer must hold a prepare trace for the height"
    assert args["remote_span"] == prep_roots[-1]["args"]["span_id"]

    merged = cluster.merge_node_dumps(parts)
    assert tracing.validate_chrome_trace(merged) == []
    json.dumps(merged)  # Perfetto-openable as-is
    assert {n["node_id"] for n in merged["otherData"]["nodes"]} == {
        "val-0", "val-1"
    }
    assert merged["otherData"]["cross_node_flows"] >= 1
    by_pid = {}
    for ev in merged["traceEvents"]:
        if ev.get("ph") == "X":
            by_pid.setdefault(ev["pid"], set()).add(ev["name"])
    prep_pids = {p for p, n in by_pid.items() if "prepare_proposal" in n}
    proc_pids = {p for p, n in by_pid.items() if "process_proposal" in n}
    assert prep_pids and (proc_pids - prep_pids), (
        "prepare and process must sit on separate node tracks"
    )
    # at least one flow arrow connects the two tracks
    s_events = {e["id"]: e for e in merged["traceEvents"] if e.get("ph") == "s"}
    f_events = {e["id"]: e for e in merged["traceEvents"] if e.get("ph") == "f"}
    assert any(
        s_events[i]["pid"] != f_events[i]["pid"]
        for i in s_events if i in f_events
    )


def test_cluster_health_over_live_pair(traced_pair):
    clients = traced_pair
    health = cluster.cluster_health(clients)
    assert health["reachable"] == 2 and health["unreachable"] == 0
    assert health["height_spread"] == 0
    assert health["app_hash_agree"] is True
    for peer in health["peers"]:
        assert peer["node_id"] in ("val-0", "val-1")
        assert peer["height"] >= 1
        assert peer["clock_offset_s"] is not None
        assert "rpc" in peer and "server" in peer["rpc"]
        calls = peer["rpc"]["server"]
        assert calls.get("status", {}).get("calls", 0) >= 1
        assert calls.get("status", {}).get("bytes_out", 0) > 0
        # a scrape counts its own bytes_out only after responding, so
        # the metrics method shows calls first, bytes on the NEXT scrape
        assert calls.get("metrics", {}).get("calls", 0) >= 1
        # the registry always holds the node's built-in caches; which
        # extras exist (e.g. eds) depends on what ran before, so assert
        # presence + shape, not a workload-dependent name
        assert peer["caches"], "cache registry rollup must not be empty"
        assert all(
            {"hits", "misses", "hit_rate"} <= set(c)
            for c in peer["caches"].values()
        )


def test_clock_probe_rpc_over_live_pair(traced_pair):
    clients = traced_pair
    for i, c in enumerate(clients):
        probe = c.clock_probe()
        assert probe["node_id"] == f"val-{i}"
        assert probe["ts"] > 0
        est = c.clock_offset(samples=3)
        assert abs(est["offset_s"]) < 2.0
        assert est["rtt_s"] > 0.0
