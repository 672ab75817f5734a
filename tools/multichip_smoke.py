"""multichip-smoke: the live mesh path's boot gate (`make multichip-smoke`).

Self-provisions a virtual multi-device CPU mesh in a child process
(``--xla_force_host_platform_device_count`` only takes effect before
jax initialises, and the parent never touches jax) and drives ONE REAL BLOCK through the live
prepare→process proposal lifecycle with the mesh configured
(CELESTIA_TPU_MESH) and tracing armed.  Asserts:

* the block committed through the SHARDED path: the prepare trace
  carries the ``extend.sharded`` host span with the mesh factoring in
  its args, and the EDS cache (content-addressed, leg-agnostic) served
  the process leg warm;
* the merged Chrome trace is schema-valid and contains the sharded
  dispatch span (``device.extend_sharded``) on >= 2 DISTINCT per-chip
  device tracks (``device:<platform>:<id>`` thread_name metadata) —
  device occupancy across chips is a measured number, not a guess;
* the mesh provider reports the sharded extend in its stats.

Exit 0 + one summary JSON line on success; non-zero with the reason on
any failure.  Runs entirely on the CPU backend (no device required).
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_DEVICES = 4
MESH_SPEC = "1x2"  # 2 row shards -> 2 distinct device tracks


def parent() -> int:
    from celestia_tpu.utils.device import force_host_devices_env

    env = force_host_devices_env(dict(os.environ), N_DEVICES)
    # opt level 0: the shard_map compile is structure-bound XLA wall;
    # the programs are integer-only, so the level cannot change bytes
    # (and the dryrun/byte-identity gates would catch it if it could)
    if "--xla_backend_optimization_level" not in env["XLA_FLAGS"]:
        env["XLA_FLAGS"] += " --xla_backend_optimization_level=0"
    env["CELESTIA_TPU_MESH"] = MESH_SPEC
    env["_MULTICHIP_SMOKE_CHILD"] = "1"
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], cwd=REPO, env=env,
        timeout=600,
    )
    return proc.returncode


def child() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")

    from celestia_tpu.client.signer import Signer
    from celestia_tpu.da import eds_cache
    from celestia_tpu.da.blob import Blob, BlobTx
    from celestia_tpu.da.inclusion import create_commitment
    from celestia_tpu.da.namespace import Namespace
    from celestia_tpu.node.testnode import TestNode
    from celestia_tpu.parallel import mesh as mesh_mod
    from celestia_tpu.state.tx import MsgPayForBlobs
    from celestia_tpu.utils import tracing
    from celestia_tpu.utils.secp256k1 import PrivateKey

    if len(jax.devices()) < N_DEVICES:
        print(
            f"multichip-smoke: device provisioning failed: "
            f"{jax.devices()}",
            file=sys.stderr,
        )
        return 1
    m = mesh_mod.device_mesh()
    if m is None:
        print(
            f"multichip-smoke: mesh did not resolve: {mesh_mod.stats()}",
            file=sys.stderr,
        )
        return 1

    tracing.enable(4)
    tracing.clear()
    eds_cache.clear()
    key = PrivateKey.from_seed(b"multichip-smoke")
    node = TestNode(funded_accounts=[(key, 10**12)], auto_produce=False)
    signer = Signer(node, key)
    # a small blob: the square must land at k >= 2 so the row axis can
    # shard it (a bare MsgSend block is the k=1 min square — the
    # fallback path, deliberately NOT what this gate proves)
    ns = Namespace.v0(b"\x33" * 10)
    blob = Blob(ns, b"\x42" * 600)
    msg = MsgPayForBlobs(
        signer=signer.address,
        namespaces=(ns.raw,),
        blob_sizes=(len(blob.data),),
        share_commitments=(create_commitment(blob),),
        share_versions=(0,),
    )
    tx = signer.sign_tx([msg], gas_limit=2_000_000, sequence=0)
    res = node.broadcast_tx(BlobTx(tx.marshal(), [blob]).marshal())
    if res.code != 0:
        print(f"multichip-smoke: broadcast failed: {res.log}", file=sys.stderr)
        return 1
    # one REAL block: reap -> PrepareProposal -> ProcessProposal ->
    # commit, with the extend routed through the mesh
    node.produce_block()

    app = node.app
    if app.telemetry.counters.get("extend_sharded", 0) < 1:
        print(
            f"multichip-smoke: no sharded extend on the live path "
            f"(counters: {dict(app.telemetry.counters)}, "
            f"mesh: {mesh_mod.stats()})",
            file=sys.stderr,
        )
        return 1
    if app.telemetry.counters.get("eds_cache_hit_process", 0) < 1:
        print(
            "multichip-smoke: process leg did not hit the mesh-warmed "
            "EDS cache",
            file=sys.stderr,
        )
        return 1

    traces = tracing.block_traces()
    prep = [t for t in traces if t.name == "prepare_proposal"]
    if not prep:
        print("multichip-smoke: no prepare trace", file=sys.stderr)
        return 1
    prep = prep[-1]
    sharded_spans = [s for s in prep.spans if s.name == "extend.sharded"]
    if not sharded_spans:
        print(
            f"multichip-smoke: no extend.sharded span "
            f"(spans: {sorted({s.name for s in prep.spans})})",
            file=sys.stderr,
        )
        return 1
    args = getattr(sharded_spans[0], "args", {}) or {}
    if args.get("mesh_row") != 2:
        print(
            f"multichip-smoke: extend.sharded span lacks mesh args: {args}",
            file=sys.stderr,
        )
        return 1
    dispatch_spans = [
        s for s in prep.spans
        if s.cat == "device" and s.name == "device.extend_sharded"
    ]
    tracks = {s.thread_name for s in dispatch_spans}
    if len(tracks) < 2:
        print(
            f"multichip-smoke: sharded dispatch on {len(tracks)} device "
            f"track(s), need >= 2 ({sorted(tracks)})",
            file=sys.stderr,
        )
        return 1

    # the merged doc must stay a valid Chrome trace with the per-chip
    # tracks surfacing as named Perfetto threads
    dump = tracing.trace_dump()
    problems = tracing.validate_chrome_trace(dump)
    if problems:
        print(
            f"multichip-smoke: invalid trace JSON: {problems[:5]}",
            file=sys.stderr,
        )
        return 1
    thread_names = {
        ev["args"]["name"]
        for ev in dump["traceEvents"]
        if ev.get("ph") == "M" and ev.get("name") == "thread_name"
    }
    device_tracks = sorted(
        n for n in thread_names if n.startswith("device:")
    )
    if len(device_tracks) < 2:
        print(
            f"multichip-smoke: merged trace has {len(device_tracks)} "
            f"device track(s), need >= 2 ({sorted(thread_names)})",
            file=sys.stderr,
        )
        return 1

    # join the background AOT cost-compile before interpreter teardown:
    # a daemon thread still inside XLA at exit dies on a GIL check
    from celestia_tpu.utils import devprof

    devprof.flush_compiles(timeout_s=120.0)
    print(
        json.dumps(
            {
                "multichip_smoke": "ok",
                "height": node.height,
                "mesh": mesh_mod.stats(),
                "sharded_dispatch_spans": len(dispatch_spans),
                "device_tracks": device_tracks,
            }
        )
    )
    return 0


def main() -> int:
    if os.environ.get("_MULTICHIP_SMOKE_CHILD") == "1":
        return child()
    return parent()


if __name__ == "__main__":
    sys.exit(main())
