#!/usr/bin/env python3
"""chip_smoke: the validator block path on a TPU, through the node.

``python chip_smoke.py`` (one chip) drives the node's main path once, at
the square sizes validators run: the governance default k=64 and the cap
k=128 (appconsts.py).  A funded ``TestNode`` is served by a
``NodeServer`` and reached over gRPC through ``RemoteNode`` + ``Signer``;
signed PayForBlob traffic fills a k=64 square and then a k=128 square; a
second ``TestNode`` built from the same genesis runs ``process_proposal``
on every committed block cold (EDS cache cleared) and commits it to the
same app hash; every data root and all 4k axis roots are checked byte
for byte against the native host reference; share proofs fetched over
gRPC are verified against the data root; one k=128 repair with 25% of
the cells withheld runs through ``rs.repair_square_device``.  The run
fails unless every extend went through ``extend.device_plane`` on a
``tpu`` device track and no poison or degradation was recorded.

``python chip_smoke.py --chips 4`` runs only the sharded path of a
four-chip host: one k=128 block through ``App._extend_square_routed`` on
the 1x4 mesh and a batch of 4 k=128 blocks through
``App.validate_blocks_batched``, compared with the one-chip program on
chip 0 and with the host reference.

With no TPU the script exits non-zero and names what it found; it never
falls back to the CPU.  Times printed here are smoke timings of one run,
not benchmark numbers.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

import argparse
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 21
TRAFFIC_FILL = 0.7  # fraction of a k x k square the blob traffic fills


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(f"chip_smoke: {msg}", flush=True)


# ---------------------------------------------------------------------------
# compile accounting (JAX's own monitoring events, per program name)
# ---------------------------------------------------------------------------

_compile_lock = threading.Lock()
# fun_name -> [programs, seconds, persistent-cache hits, retrieval seconds]
_compiles = {}
_thread = threading.local()  # a hit's retrieval time, until its compile event


def _on_duration(event, duration, **kw):
    # JAX times the compile of a program (backend_compile_duration) around
    # its persistent-cache read; a hit first records its retrieval
    # (cache_retrieval_time_sec, no program name) on the same thread
    if event == "/jax/compilation_cache/cache_retrieval_time_sec":
        _thread.retrieval = float(duration)
    elif event == "/jax/core/compile/backend_compile_duration":
        retrieval = getattr(_thread, "retrieval", None)
        _thread.retrieval = None
        with _compile_lock:
            rec = _compiles.setdefault(kw.get("fun_name", "?"), [0, 0.0, 0, 0.0])
            rec[0] += 1
            rec[1] += float(duration)
            if retrieval is not None:
                rec[2] += 1
                rec[3] += retrieval


def install_compile_listeners():
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def report_compiles():
    """Per program: compile events and seconds, and how many of them were
    persistent-cache hits with the seconds spent retrieving those."""
    with _compile_lock:
        items = sorted(_compiles.items(), key=lambda kv: -kv[1][1])
    small = [rec for _n, rec in items if rec[1] < 0.5]
    for name, (count, secs, hits, retrieval) in items:
        if secs >= 0.5:
            log(
                f"compile {name}: {count} program(s), {secs:.3f} s; "
                f"{hits} persistent-cache hit(s), retrieval {retrieval:.3f} s"
            )
    log(
        f"compile (programs under 0.5 s each): {sum(r[0] for r in small)} "
        f"program(s), {sum(r[1] for r in small):.3f} s; "
        f"{sum(r[2] for r in small)} persistent-cache hit(s); "
        f"all programs: {sum(r[2] for _n, r in items)} hit(s)"
    )


def precompile(jobs):
    """AOT-compile independent programs concurrently: XLA compiles one
    program on about one core, and the persistent compile cache hands
    each executable to the jitted call that later runs it.  Prints each
    program's compile seconds and memory analysis."""

    def one(job):
        label, fn, args = job
        t0 = time.perf_counter()
        mem = fn.lower(*args).compile().memory_analysis()
        log(
            f"precompile {label}: {time.perf_counter() - t0:.1f} s; memory "
            f"argument {mem.argument_size_in_bytes} B, output "
            f"{mem.output_size_in_bytes} B, temp {mem.temp_size_in_bytes} B, "
            f"generated code {mem.generated_code_size_in_bytes} B"
        )

    with ThreadPoolExecutor(max_workers=len(jobs)) as pool:
        list(pool.map(one, jobs))


def timed(label, fn, *a, **kw):
    t0 = time.perf_counter()
    out = fn(*a, **kw)
    log(f"smoke timing {label}: {(time.perf_counter() - t0) * 1000.0:.1f} ms")
    return out


# ---------------------------------------------------------------------------
# the host reference
# ---------------------------------------------------------------------------


def square_array(square):
    k = square.size
    return square.to_array().reshape(k, k, 512)


def reference_extend(block_txs, square_size):
    """The plain host reference for one block: the square rebuilt from
    the block's txs, extended by the native C++ pipeline.  Returns
    (square, eds, row_roots, col_roots, data_root)."""
    from celestia_tpu.da.square import construct as construct_square
    from celestia_tpu.utils import native

    square, _txs, wrappers = construct_square(list(block_txs), square_size)
    check(square.size == square_size, f"reference square {square.size} != {square_size}")
    eds, roots, droot = native.extend_block_cpu(square_array(square))
    k = square_size
    return square, wrappers, eds, roots[: 2 * k], roots[2 * k :], droot.tobytes()


def check_dah(dah, row_roots, col_roots, data_root, what):
    k2 = row_roots.shape[0]
    check(len(dah.row_roots) == k2 and len(dah.col_roots) == k2, f"{what}: root count")
    for i in range(k2):
        check(dah.row_roots[i] == row_roots[i].tobytes(), f"{what}: row root {i} differs")
        check(dah.col_roots[i] == col_roots[i].tobytes(), f"{what}: col root {i} differs")
    check(dah.hash == data_root, f"{what}: data root differs")


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------


def fill_square(remote, signers, rng, k, n_tx):
    """Submit ``n_tx`` signed PayForBlobs (one per signer, concurrently,
    right after a block was committed, so they land in one block) whose
    blobs fill ~TRAFFIC_FILL of a k x k square; returns the heights they
    were committed at."""
    from celestia_tpu.client.txsim import random_blob

    blob_bytes = int(TRAFFIC_FILL * k * k * 478) // n_tx
    blobs = [random_blob(rng, i, blob_bytes) for i in range(n_tx)]
    h0 = remote.height
    while remote.height == h0:
        time.sleep(0.01)
    with ThreadPoolExecutor(max_workers=n_tx) as pool:
        results = list(
            pool.map(lambda sb: sb[0].submit_pay_for_blob([sb[1]]), zip(signers, blobs))
        )
    for r in results:
        check(r.code == 0, f"PayForBlob failed: code={r.code} log={r.log}")
    return sorted({r.height for r in results})


# ---------------------------------------------------------------------------
# one chip: the node path
# ---------------------------------------------------------------------------


def run_one_chip(ks=(64, 128), platform="tpu", block_interval_s=2.0):
    import jax
    import jax.numpy as jnp

    from celestia_tpu.client.signer import Signer
    from celestia_tpu.da import dah as dah_mod
    from celestia_tpu.da import device_plane, eds_cache
    from celestia_tpu.da.proof import ShareInclusionProof
    from celestia_tpu.node.remote import RemoteNode
    from celestia_tpu.node.server import NodeServer
    from celestia_tpu.node.testnode import TestNode
    from celestia_tpu.ops import gf256, rs
    from celestia_tpu.utils import devprof, tracing
    from celestia_tpu.utils.secp256k1 import PrivateKey

    k_default, k_cap = ks
    rng = np.random.default_rng(SEED)
    tracing.enable()

    # the big programs of the run, compiled side by side: the extend at
    # every size a traffic block can take (k/2 too: traffic split over
    # two blocks can leave one that small) and the repair at the cap.
    # Uncommitted shapes, as the node passes its arrays: the same cache
    # key as the jitted calls that follow.
    codec = gf256.active_codec()
    warm = sorted({1, k_default // 2, k_default, k_cap})
    n2 = 2 * k_cap
    avail = np.random.default_rng(SEED + 1).random((n2, n2)) >= 0.25
    jobs = [
        (f"extend_levels k={s}", device_plane._extend_levels_fn(s, codec),
         (jax.ShapeDtypeStruct((s, s, 512), jnp.uint8),))
        for s in warm if s > 1
    ]
    jobs.append((f"repair_verify k={k_cap}", *rs.repair_program(avail)))
    timed("precompile (wall, programs in parallel)", precompile, jobs)

    # warm every size the run will extend BEFORE serving (cli start's
    # --warm-squares): the producer holds the service lock across an
    # extend, and a cold compile there would stall every RPC
    for s in warm:
        timed(
            f"warm extend k={s} (compile included)",
            dah_mod.extend_and_header,
            np.zeros((s, s, 512), dtype=np.uint8),
        )

    keys = [PrivateKey.from_seed(b"chip-smoke-%d" % i) for i in range(16)]
    vkey = PrivateKey.from_seed(b"chip-smoke-validator")
    genesis = {
        "genesis_time_ns": 1_700_000_000 * 10**9,
        "accounts": [
            {"address": vkey.public_key().address().hex(), "balance": 10**12}
        ]
        + [
            {"address": key.public_key().address().hex(), "balance": 10**15}
            for key in keys
        ],
        "validators": [
            {"address": vkey.public_key().address().hex(), "self_delegation": 10**11}
        ],
        # the cap block needs the governance bound raised to k=128
        "params": {"blob": {"GovMaxSquareSize": k_cap}},
    }
    node = TestNode(
        chain_id="chip-smoke", genesis=genesis, validator_key=vkey,
        auto_produce=False,
    )
    server = NodeServer(node, block_interval_s=block_interval_s)
    server.start()
    try:
        remote = RemoteNode(server.address, timeout_s=120)
        signers = [Signer(remote, key) for key in keys]
        t0 = time.perf_counter()
        h_default = fill_square(remote, signers[:8], rng, k_default, 8)
        log(f"smoke timing k={k_default} traffic submit->confirm: {(time.perf_counter() - t0) * 1000.0:.1f} ms")
        t0 = time.perf_counter()
        h_cap = fill_square(remote, signers, rng, k_cap, 16)
        log(f"smoke timing k={k_cap} traffic submit->confirm: {(time.perf_counter() - t0) * 1000.0:.1f} ms")
        sizes = {h: remote.block(h)["square_size"] for h in h_default + h_cap}
        log(f"traffic blocks (height: k): {sizes}")
        check(any(sizes[h] == k_default for h in h_default), f"no k={k_default} block committed")
        check(any(sizes[h] == k_cap for h in h_cap), f"no k={k_cap} block committed")
        # let the producer commit a few more (empty) blocks
        target = remote.height + 2
        while remote.height < target:
            time.sleep(block_interval_s / 4)

        # share proofs over gRPC, verified against the data root: two
        # 2-share ranges inside one row of a blob, per traffic block
        n_proofs = 0
        for h in sorted(sizes):
            blk = node.block(h)
            k = blk.header.square_size
            _sq, wrappers, _e, _r, _c, droot = reference_extend(blk.txs, k)
            starts = [w.share_indexes[0] for w in wrappers[:2]]
            for start in (s - 2 if s % k == k - 1 else s for s in starts):
                out = timed(
                    f"share proof k={k} height={h}",
                    remote.abci_query,
                    "custom/proof/share",
                    {"height": h, "start": start, "end": start + 2},
                )
                proof = ShareInclusionProof.from_dict(out["proof"])
                root = bytes.fromhex(out["data_root"])
                check(root == droot, f"proof data root differs at height {h}")
                check(proof.verify(root), f"share proof at height {h} does not verify")
                n_proofs += 1
        log(f"{n_proofs} share proofs fetched over gRPC verified against the data root")
    finally:
        server.stop()

    # every committed block: host reference vs the proposer's DAH, then a
    # second node processes it cold and commits it to the same app hash
    follower = TestNode(
        chain_id="chip-smoke", genesis=genesis, validator_key=vkey,
        auto_produce=False,
    )
    checked = {}
    for blk in node.blocks:
        h, k = blk.header.height, blk.header.square_size
        _sq, _w, ref_eds, rr, cc, droot = reference_extend(blk.txs, k)
        check(blk.header.data_hash == droot, f"height {h}: committed data root differs from the reference")
        art = node._eds_cache.get(h)
        if art is not None:
            check_dah(art["dah"], rr, cc, droot, f"proposer height {h}")
        eds_cache.clear()  # a foreign block: no EDS-cache hit
        label = f"cold process_proposal k={k} height={h}"
        ok, reason = (
            timed(label, follower.cons_process, blk.txs, k, blk.header.data_hash)
            if k in (k_default, k_cap)
            else follower.cons_process(blk.txs, k, blk.header.data_hash)
        )
        check(ok, f"follower rejected height {h}: {reason}")
        key = eds_cache.make_key(blk.txs, k, follower.app.app_version, codec)
        hit = eds_cache.get(key)
        check(hit is not None, f"height {h}: follower extend left no EDS entry")
        check_dah(hit[1], rr, cc, droot, f"follower height {h}")
        app_hash = follower.cons_commit(
            blk.txs, h, blk.header.time_ns, blk.header.data_hash, k,
            proposer=blk.proposer, votes=blk.votes,
        )
        check(app_hash == blk.header.app_hash, f"height {h}: follower app hash differs")
        checked[k] = checked.get(k, 0) + 1
        if k == k_cap:
            cap_block = (ref_eds, rr, cc)
    counters = follower.app.telemetry.summary()["counters"]
    check(counters.get("eds_cache_hit_process", 0) == 0, "follower hit the EDS cache")
    log(
        f"{len(node.blocks)} blocks committed (k: count {checked}); every data root and "
        f"all 4k axis roots byte-identical to the host reference on both nodes; "
        f"follower app hashes agree"
    )

    # repair at the cap with 25% of the cells withheld
    ref_eds, rr, cc = cap_block
    for label in ("cold (compile included)", "warm"):
        repaired = timed(
            f"repair k={n2 // 2} 25% withheld {label}",
            rs.repair_square_device, ref_eds, avail, rr, cc,
        )
        check(np.array_equal(np.asarray(repaired), ref_eds), "repaired square differs from the reference")
    log(f"repair k={n2 // 2} with {int((~avail).sum())} of {n2 * n2} cells withheld: square and roots verified")
    n_repair = devprof.device_profile()["dispatches"].get("rs_repair_verify", 0)
    check(n_repair == 2, f"{n_repair} of 2 repairs ran on the device")

    check_device_path(platform)


def check_device_path(platform):
    """Every extend went through the device plane, on the expected
    platform's device tracks, and nothing degraded."""
    from celestia_tpu.utils import devprof, tracing

    spans = tracing.span_summary()
    n_plane = spans.get("extend.device_plane", {}).get("count", 0)
    host_legs = {
        n: spans[n]["count"]
        for n in ("extend.native", "extend.jax", "extend.memo", "extend.sharded")
        if spans.get(n, {}).get("count", 0)
    }
    check(n_plane > 0, "no extend went through extend.device_plane")
    check(not host_legs, f"extends left the device plane: {host_legs}")
    prof = devprof.device_profile()
    n_disp = prof["dispatches"].get("extend_levels", 0)
    check(n_disp == n_plane, f"{n_plane} device-plane extends but {n_disp} device dispatches")
    tracks = sorted(prof["device_busy_ms"])
    check(
        tracks and all(t.startswith(platform + ":") for t in tracks),
        f"device tracks {tracks} are not all {platform}",
    )
    log(f"{n_plane} extends through extend.device_plane on device tracks {tracks}")


# ---------------------------------------------------------------------------
# four chips: the sharded path only
# ---------------------------------------------------------------------------


def run_four_chips(k=128, batch=4, n_chips=4):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from celestia_tpu.client.txsim import signed_pfb_txs
    from celestia_tpu.da import device_plane, eds_cache
    from celestia_tpu.da.square import construct as construct_square
    from celestia_tpu.node.testnode import TestNode
    from celestia_tpu.ops import gf256
    from celestia_tpu.parallel import mesh as mesh_mod
    from celestia_tpu.parallel import sharded
    from celestia_tpu.utils import native
    from celestia_tpu.utils.secp256k1 import PrivateKey

    devs = jax.devices()
    check(mesh_mod.mesh_shape() == (1, n_chips), f"mesh is {mesh_mod.stats()}")
    rng = np.random.default_rng(SEED)
    keys = [PrivateKey.from_seed(b"chip-smoke-4-%d" % i) for i in range(4 * (batch + 1))]
    node = TestNode(funded_accounts=[(key, 10**15) for key in keys], auto_produce=False)
    node.app.params.set("blob", "GovMaxSquareSize", k)
    blocks = []
    for b in range(batch + 1):
        # ~TRAFFIC_FILL of a k x k square, 4 fresh signers at sequence 0
        txs = signed_pfb_txs(
            node, keys[4 * b : 4 * b + 4], 4, int(TRAFFIC_FILL * k * k * 478) // 4,
            rng, first_namespace=4 * b,
        )
        square, block_txs, _w = construct_square(txs, k)
        check(square.size == k, f"block {b} built k={square.size}, want {k}")
        blocks.append((square, block_txs))
    codec = gf256.active_codec()
    one_chip = device_plane._extend_levels_fn(k, codec)
    mesh = mesh_mod.device_mesh()
    row = NamedSharding(mesh, P("row", None, None))
    batch_rows = NamedSharding(mesh, P("data", "row", None, None))
    timed("precompile (wall, programs in parallel)", precompile, [
        (f"sharded extend 1x{n_chips} k={k}", sharded._sharded_fn(mesh, k, False, codec),
         (jax.ShapeDtypeStruct((k, k, 512), jnp.uint8, sharding=row),)),
        (f"sharded extend 1x{n_chips} batch={batch} k={k}", sharded._sharded_fn(mesh, k, True, codec),
         (jax.ShapeDtypeStruct((batch, k, k, 512), jnp.uint8, sharding=batch_rows),)),
        (f"one-chip extend_levels k={k} on chip 0", one_chip,
         (jax.ShapeDtypeStruct((k, k, 512), jnp.uint8, sharding=SingleDeviceSharding(devs[0])),)),
    ])

    def one_chip_roots(arr):
        _eds, levels, root_levels = one_chip(jax.device_put(arr, devs[0]))
        roots = np.asarray(levels[-1])[:, :, 0, :]
        return roots[0], roots[1], np.asarray(root_levels[-1])[0].tobytes()

    def compare(dah, eds, square, what):
        arr = square_array(square)
        _eds, roots, droot = native.extend_block_cpu(arr)
        rr, cc = roots[: 2 * k], roots[2 * k :]
        check_dah(dah, rr, cc, droot.tobytes(), f"{what} vs host reference")
        orr, occ, odr = timed(f"one-chip program on chip 0 ({what})", one_chip_roots, arr)
        check_dah(dah, orr, occ, odr, f"{what} vs one-chip program")
        shards = eds._shares.addressable_shards
        shard_devs = {s.device for s in shards}
        check(len(shard_devs) == n_chips, f"{what}: EDS on {len(shard_devs)} device(s), want {n_chips}")
        check(
            all(s.data.shape[0] < 2 * k for s in shards),
            f"{what}: EDS replicated, not sharded: {[s.data.shape for s in shards]}",
        )
        log(f"{what}: roots byte-identical to the host reference and the one-chip program; EDS on {len(shard_devs)} distinct devices")

    # one block through the live routing on the 1x4 mesh
    square, _txs = blocks[0]
    before = mesh_mod.stats()["sharded_extends"]
    eds, dah = timed(f"sharded extend k={k} (compile included)", node.app._extend_square_routed, square)
    eds, dah = timed(f"sharded extend k={k} warm", node.app._extend_square_routed, square)
    check(mesh_mod.stats()["sharded_extends"] == before + 2, "extend did not route through the mesh")
    compare(dah, eds, square, f"sharded k={k}")

    # a batch of blocks through validate_blocks_batched (one dispatch)
    proposals = []
    for square, block_txs in blocks[1:]:
        _e, _roots, droot = native.extend_block_cpu(square_array(square))
        proposals.append((block_txs, k, droot.tobytes()))
    eds_cache.clear()
    disp = mesh_mod.stats()["batched_dispatches"]
    verdicts = timed(
        f"validate_blocks_batched {batch}x k={k} (compile included)",
        node.app.validate_blocks_batched, proposals,
    )
    check(all(ok for ok, _ in verdicts), f"batched verdicts {verdicts}")
    check(mesh_mod.stats()["batched_dispatches"] == disp + 1, "batch was not one sharded dispatch")
    for i, (square, block_txs) in enumerate(blocks[1:]):
        hit = eds_cache.get(eds_cache.make_key(block_txs, k, node.app.app_version, codec))
        check(hit is not None, f"batch block {i} left no EDS entry")
        compare(hit[1], hit[0], square, f"batch block {i}")
    eds_cache.clear()
    verdicts = timed(f"validate_blocks_batched {batch}x k={k} warm", node.app.validate_blocks_batched, proposals)
    check(all(ok for ok, _ in verdicts), f"batched verdicts {verdicts}")


# ---------------------------------------------------------------------------


def final_checks():
    from celestia_tpu.da import device_plane
    from celestia_tpu.parallel import mesh as mesh_mod
    from celestia_tpu.utils import faults, native

    for name, reason in (
        ("device_plane", device_plane.poisoned()),
        ("mesh", mesh_mod.poisoned()),
        ("native", native.poisoned()),
    ):
        check(reason is None, f"{name} poisoned: {reason}")
    degraded = faults.fault_stats()["degradations"]
    check(not degraded, f"degradations recorded: {degraded}")
    log("no poison and no degradation recorded")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1 (default): the node path on one chip; 4: the sharded path only",
    )
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "celestia_tpu")):
        print(
            f"chip_smoke: FAIL: the celestia_tpu package is not beside {__file__}; "
            "run from a checkout of the repo",
            file=sys.stderr,
        )
        return 2
    # one chip: pin the live path to the single-device program even on a
    # multi-chip host; four chips: the 1x4 mesh (parallel/mesh.py)
    os.environ["CELESTIA_TPU_MESH"] = "off" if args.chips == 1 else f"1x{args.chips}"
    # libtpu's own logs would land under /tmp, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from celestia_tpu.utils.device import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: FAIL: no TPU found — jax platform is {dev.platform!r} "
            f"({len(devs)} device(s): {dev.device_kind}); nothing falls back to it",
            file=sys.stderr,
        )
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: FAIL: --chips {args.chips} but {len(devs)} device(s) visible", file=sys.stderr)
        return 1
    install_compile_listeners()
    log(f"device {dev.device_kind} x{len(devs)}; compile cache {cache_dir}")
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            run_one_chip()
        else:
            run_four_chips(n_chips=args.chips)
        final_checks()
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    finally:
        report_compiles()
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')} on {dev.device_kind}")
    log(f"smoke timing whole run: {time.perf_counter() - t0:.1f} s")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
