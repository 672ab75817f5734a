"""Benchmark: the block-extension hot path against an honest CPU leg.

Covers the BASELINE.md configs:

- #3 (headline): 128x128 ExtendBlock — fused 2D GF(256) RS extension + all
  4k NMT axis roots + RFC-6962 data root — device-amortized ms, plus a
  single-shot end-to-end call (host array in -> roots fetched back, i.e.
  including transfer), plus the full PrepareProposal path over a square's
  worth of signed PFBs (ante + native batch sig verify + square build +
  device pipeline).
- #4: Repair of a 128x128 EDS from 25% withheld cells (DAS decode), with
  committed-root verification.
- #5: batched 8x128x128 squares on one chip (batch dim; per-square ms).

CPU comparison legs, both at FULL size with no extrapolation:

- `leopard_cpu` (the honest baseline, vs_baseline denominator): the
  in-tree Leopard codec — O(n log n) LCH FFT with the pshufb 4-bit-split
  SIMD multiply kernel real Leopard uses (native leo_encode,
  byte-identical to the device path, ADR-012) + the same threaded
  SHA-256/NMT stage.  This is the algorithm class of the reference's
  codec (pkg/da/data_availability_header.go:44-75), so the ≥10x
  BASELINE.md target is finally measured, not extrapolated.
- `table_gf_cpu`: the O(k^2) table-method pipeline, kept for continuity
  with earlier rounds' numbers.

Device timing uses dependent-chain amortization where transfer is excluded:
chained R-iteration jits isolate the marginal per-iteration device cost;
the e2e metric is a plain single call and therefore *includes* the
host<->device transfer (recorded separately in extras as transfer
overhead).

The bench runs on the chip or not at all: with no accelerator backend,
or when a device-plane / mesh / native degradation fires during the run,
it exits non-zero.  Everything runs in this one process (a chip belongs
to one process at a time).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extras"}.
vs_baseline = cpu_ms / device_ms (speedup; >1 is faster than the CPU leg).
"""

import json
import os
import sys
import time

import numpy as np

K = int(os.environ.get("BENCH_K", "128"))
BATCH = int(os.environ.get("BENCH_BATCH", "8"))


def _chain_fn(k: int, r: int, batch: int = 0):
    import jax

    from celestia_tpu.ops import nmt as nmt_ops
    from celestia_tpu.ops import rs
    from celestia_tpu.ops.gf256 import encode_matrix_bits
    import jax.numpy as jnp

    G = jnp.asarray(encode_matrix_bits(k))

    def step(square):
        eds = rs._extend(square, G)
        roots = nmt_ops.eds_nmt_roots(eds)
        all_roots = roots.reshape(4 * k, nmt_ops.NMT_DIGEST_SIZE)
        return eds, nmt_ops.rfc6962_root_pow2(all_roots)

    if batch:
        step_single = step
        step = lambda sq: jax.vmap(step_single)(sq)  # noqa: E731

    @jax.jit
    def f(x):
        def body(i, x):
            _, droot = step(x)
            if batch:
                return x.at[0, 0, 0, 0].set(droot[0, 0])
            return x.at[0, 0, 0].set(droot[0])

        return jax.lax.fori_loop(0, r, body, x)

    return f


def _amortized_device_ms(k: int, batch: int = 0, r_lo: int = 10, r_hi: int = 60):
    """Marginal per-iteration device time via dependent-chain subtraction.

    The iteration gap must be large enough that the true signal
    ((r_hi - r_lo) x per-iteration ms) dominates the per-call
    jitter (tens of ms); the median of several deltas rejects the
    remaining outliers.
    """
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    shape = (batch, k, k, 512) if batch else (k, k, 512)
    sq = jax.device_put(jnp.asarray(rng.integers(0, 256, shape, dtype=np.uint8)))
    f_lo, f_hi = _chain_fn(k, r_lo, batch), _chain_fn(k, r_hi, batch)
    np.asarray(f_lo(sq)).ravel()[0]
    np.asarray(f_hi(sq)).ravel()[0]
    reps = []
    for _ in range(5):
        t0 = time.time()
        np.asarray(f_lo(sq)).ravel()[0]
        t_lo = time.time() - t0
        t0 = time.time()
        np.asarray(f_hi(sq)).ravel()[0]
        t_hi = time.time() - t0
        reps.append((t_hi - t_lo) / (r_hi - r_lo) * 1000.0)
    return max(float(np.median(reps)), 1e-3)


def _e2e_extend_ms(k: int):
    """Single-call ExtendBlock: host uint8 array in, DAH roots fetched out.

    Includes host->device transfer of the ~8 MiB square and device->host
    fetch of roots + data root (the PrepareProposal transfer budget,
    SURVEY.md §7 hard part c).
    """
    from celestia_tpu.da import dah as dah_mod

    rng = np.random.default_rng(2)
    raw = rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    # warm the jit caches
    dah_mod.extend_and_header(raw)
    times = []
    for _ in range(5):
        t0 = time.time()
        dah_mod.extend_and_header(raw)
        times.append((time.time() - t0) * 1000.0)
    return float(np.median(times))


def _cpu_threads() -> int:
    """The ACTUAL host worker count the CPU legs ran with (the pool
    size: --cpu-threads / CELESTIA_TPU_CPU_THREADS / os.cpu_count) —
    r05 recorded os.cpu_count() while the legs threaded independently."""
    from celestia_tpu.utils import hostpool

    return hostpool.cpu_threads()


def _cpu_ms(k: int):
    """Native threaded C++ pipeline at full size (no extrapolation)."""
    from celestia_tpu.utils import native

    if not native.available():
        return None
    rng = np.random.default_rng(1)
    sq = rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    times = []
    for _ in range(3):
        t0 = time.time()
        native.extend_block_cpu(sq)
        times.append((time.time() - t0) * 1000.0)
    return float(np.median(times))


def _leopard_cpu_ms(k: int):
    """The HONEST CPU baseline (BASELINE.md ≥10x target, unmeasured
    through r04): full ExtendBlock via the in-tree Leopard codec — the
    O(n log n) LCH FFT with the same pshufb 4-bit-split SIMD multiply
    kernel real Leopard uses (native/celestia_native.cpp leo_encode,
    byte-identical to the device path per tests/test_leopard_codec.py) —
    plus the same SHA/NMT stage as the table leg.  Returns
    (full_pipeline_ms, extension_only_ms)."""
    from celestia_tpu.utils import native

    if not native.available():
        return None, None
    rng = np.random.default_rng(1)
    sq = np.ascontiguousarray(
        rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    )
    native.extend_block_leopard_cpu(sq)  # warm tables
    times = []
    for _ in range(3):
        t0 = time.time()
        native.extend_block_leopard_cpu(sq)
        times.append((time.time() - t0) * 1000.0)
    ext_times = []
    for _ in range(3):
        t0 = time.time()
        native.leo_extend_square(sq)
        ext_times.append((time.time() - t0) * 1000.0)
    return float(np.median(times)), float(np.median(ext_times))


def _leopard_scaling_ms(k: int, pool_ms: float = None):
    """Thread-scaling of the full leopard host pipeline at 1/2/N worker
    threads (N = the pool size) — the evidence that the multi-threaded
    host DA path actually fans out.  Returns {"t1": ms, "t2": ms,
    "tN": ms} (keys deduplicated when N <= 2).  ``pool_ms`` reuses the
    pool-width median _leopard_cpu_ms already measured instead of
    re-running the full pipeline three more times."""
    from celestia_tpu.utils import native

    if not native.available():
        return None
    rng = np.random.default_rng(1)
    sq = np.ascontiguousarray(
        rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    )
    native.extend_block_leopard_cpu(sq, nthreads=1)  # warm tables
    out = {}
    n = _cpu_threads()
    for t in sorted({1, min(2, n), n}):
        if t == n and pool_ms is not None:
            out[f"t{t}"] = round(float(pool_ms), 1)
            continue
        times = []
        for _ in range(3):
            t0 = time.time()
            native.extend_block_leopard_cpu(sq, nthreads=t)
            times.append((time.time() - t0) * 1000.0)
        out[f"t{t}"] = round(float(np.median(times)), 1)
    return out


def _repair_ms(k: int):
    """BASELINE config #4: repair from 25% withheld cells, root-verified,
    on the DEVICE (ops/rs.py repair_square_device: host peels the boolean
    mask, the accelerator runs decode matmuls + byzantine verification).
    Warm-started: the jit cache is keyed by (k, phases, chunk), and a 25%
    random mask resolves in one phase, so real DAS repairs hit the cache."""
    from celestia_tpu.ops import rs

    from celestia_tpu.utils import native

    rng = np.random.default_rng(3)
    sq = rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    if native.available():
        eds, roots, _ = native.extend_block_cpu(sq)
    else:
        eds = np.asarray(rs.extend_square(sq))
        from celestia_tpu.ops import nmt as nmt_ops

        r = np.asarray(nmt_ops.eds_nmt_roots(eds))
        roots = r.reshape(4 * k, 90)
    row_roots, col_roots = roots[: 2 * k], roots[2 * k :]
    # withhold 25% of cells (random mask, reproducible)
    avail = rng.random((2 * k, 2 * k)) >= 0.25
    damaged = np.array(eds)
    damaged[~avail] = 0
    # warm the (k, phases, chunk) jit cache with a DIFFERENT mask of the
    # same phase count, then time the real repair
    warm_avail = rng.random((2 * k, 2 * k)) >= 0.25
    warm = np.array(eds)
    warm[~warm_avail] = 0
    rs.repair_square_device(
        warm, warm_avail, row_roots=row_roots, col_roots=col_roots
    )
    # the DAS-server regime is the common path (VERDICT r3 #6): shares
    # are re-served straight from device memory, so the bulk fetch is
    # NOT part of the repair budget — it is measured once separately
    times, breakdowns = [], []
    for _ in range(3):
        bd = {}
        t0 = time.time()
        fixed_dev = rs.repair_square_device(
            damaged, avail, row_roots=row_roots, col_roots=col_roots,
            breakdown=bd, return_device=True,
        )
        times.append((time.time() - t0) * 1000.0)
        breakdowns.append(bd)
    t0 = time.time()
    fixed = np.asarray(fixed_dev)
    bulk_fetch_ms = (time.time() - t0) * 1000.0
    assert np.array_equal(fixed, eds), "repair produced a wrong square"
    mid = sorted(range(len(times)), key=lambda i: times[i])[len(times) // 2]
    bd_out = {
        n: (round(v, 1) if isinstance(v, float) else v)
        for n, v in breakdowns[mid].items()
    }
    bd_out["bulk_fetch_ms"] = round(bulk_fetch_ms, 1)
    return float(np.median(times)), bd_out


def _amortized_repair_device_ms(k: int, r_lo: int = 3, r_hi: int = 9):
    """Marginal per-repair device time (decode phases + re-extension
    check + axis roots) via dependent-chain subtraction — the per-call
    fixed transfer cost cancels, leaving the device compute."""
    import jax
    import jax.numpy as jnp

    from celestia_tpu.ops import rs

    rng = np.random.default_rng(7)
    sq = rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    eds = np.asarray(rs.extend_square(sq))
    avail = rng.random((2 * k, 2 * k)) >= 0.25
    masked = np.where(avail[:, :, None], eds, 0).astype(np.uint8)
    rk, rm, ck, cm = rs._simulate_schedule(avail, k)
    chunk = min(2 * k, max(1, 8192 // k))
    G = jnp.asarray(__import__("celestia_tpu.ops.gf256", fromlist=["x"]).encode_matrix_bits(k))
    from celestia_tpu.ops import nmt as nmt_ops

    rkj, rmj = jnp.asarray(rk), jnp.asarray(rm)
    ckj, cmj = jnp.asarray(ck), jnp.asarray(cm)

    def chain(r):
        @jax.jit
        def f(x):
            def body(i, x):
                rep = rs._repair_phases(
                    x, rkj, rmj, ckj, cmj, k=k, chunk=chunk
                )
                rec = rs._extend(rep[:k, :k], G)
                roots = nmt_ops.eds_nmt_roots(rep)
                # fold verdict bytes back in: keeps the chain dependent
                return rep.at[0, 0, 0].set(
                    rec[0, 0, 0] ^ roots[0, 0, 0]
                )

            return jax.lax.fori_loop(0, r, body, x)

        return f

    x = jax.device_put(jnp.asarray(masked))
    f_lo, f_hi = chain(r_lo), chain(r_hi)
    np.asarray(f_lo(x)).ravel()[0]
    np.asarray(f_hi(x)).ravel()[0]
    reps = []
    for _ in range(3):
        t0 = time.time()
        np.asarray(f_lo(x)).ravel()[0]
        t_lo = time.time() - t0
        t0 = time.time()
        np.asarray(f_hi(x)).ravel()[0]
        t_hi = time.time() - t0
        reps.append((t_hi - t_lo) / (r_hi - r_lo) * 1000.0)
    return max(float(np.median(reps)), 1e-3)


def _make_pfb_node_and_txs(
    n_tx: int, blob_bytes: int, seed: int, max_square: int, key_prefix: bytes
):
    """A funded TestNode plus n signed single-blob PFBs (shared by the
    FilterTxs and PrepareProposal benches)."""
    from celestia_tpu.client.txsim import signed_pfb_txs
    from celestia_tpu.node.testnode import TestNode
    from celestia_tpu.utils.secp256k1 import PrivateKey

    keys = [PrivateKey.from_seed(key_prefix + b"-%d" % i) for i in range(8)]
    node = TestNode(
        funded_accounts=[(key, 10**15) for key in keys], auto_produce=False
    )
    node.app.params.set("blob", "GovMaxSquareSize", max_square)
    txs = signed_pfb_txs(
        node, keys, n_tx, blob_bytes, np.random.default_rng(seed)
    )
    return node, txs


def _filter_txs_ms(n_tx: int = 512):
    """FilterTxs (ante + native batch sig verify + commitment recompute)
    over n signed single-blob PFBs — the VERDICT r1 #5 'fast signature
    verification' acceptance metric, isolated from square build and the
    device pipeline."""
    from celestia_tpu.da import inclusion

    node, txs = _make_pfb_node_and_txs(n_tx, 2000, 6, 128, b"filt")
    times = []
    for _ in range(3):
        # measure the COLD paths: tx construction warmed the commitment
        # cache and a prior iteration the signature/decoded-tx caches —
        # any of them would hide codec/EC regressions
        inclusion._COMMITMENT_CACHE.clear()
        node.app._sig_cache.clear()
        node.app._decoded_cache.clear()
        t0 = time.time()
        kept = node.app._filter_txs(txs)
        times.append((time.time() - t0) * 1000.0)
    assert len(kept) == n_tx, f"filter kept {len(kept)}/{n_tx}"
    return float(np.median(times))


def _prepare_proposal_ms(k: int):
    """Full PrepareProposal over a square's worth of signed PFBs, with the
    phase breakdown (filter / square build / device extension incl.
    transfer) and a separate upload/compute/fetch attribution of the
    extension call, so the transfer is isolated from host-side work
    (VERDICT r2 #7)."""
    from celestia_tpu.da import dah as dah_mod

    n_tx = max(2, k)  # ~k txs with blobs sized to fill a k x k square
    blob_bytes = max(478, (k * k * 478) // max(1, n_tx) - 4 * 478)
    node, txs = _make_pfb_node_and_txs(n_tx, blob_bytes, 4, k, b"bench")
    # warm device caches for this square size
    node.app.prepare_proposal(txs[:2])
    times, breakdowns = [], []
    for _ in range(3):
        # This measures the PROPOSER regime: pooled txs passed CheckTx,
        # which computes blob commitments and records the decoded-tx
        # verdicts (warm _COMMITMENT_CACHE + _decoded_cache — kept) but
        # verifies signatures inline without touching the batch-path
        # sig cache (cold — cleared).  _filter_txs_ms below measures the
        # fully cold validator-receiving-a-foreign-proposal regime.
        node.app._sig_cache.clear()
        t0 = time.time()
        prop = node.app.prepare_proposal(txs)
        times.append((time.time() - t0) * 1000.0)
        breakdowns.append(dict(node.app.last_prepare_breakdown))
    assert prop.square_size >= k // 2, (
        f"bench square too small: {prop.square_size} (want ~{k})"
    )
    mid = sorted(range(len(times)), key=lambda i: times[i])[len(times) // 2]
    breakdown = {n: round(v, 1) for n, v in breakdowns[mid].items()}
    # attribute the extension call's transfer vs compute (extra syncs, so
    # only for attribution — the hot path stays one fused call)
    sq = prop.square.to_array().reshape(
        prop.square.size, prop.square.size, -1
    )
    _, _, xfer = dah_mod.extend_and_header_breakdown(sq)
    breakdown.update({n: round(v, 1) for n, v in xfer.items()})
    return float(np.median(times)), prop.square_size, len(txs), breakdown


def _prepare_host_legs_ms(k: int = 128):
    """The HOST components of the <50 ms PrepareProposal gate at ~k PFBs
    (proposer regime: decoded/commitment caches warm, signature cache
    cold — same as _prepare_proposal_ms), measurable without a device:
    the gate total is filter + build + the amortized device extension.
    Returns (filter_ms, build_ms, n_tx)."""
    from celestia_tpu.da.square import build as build_square

    n_tx = max(2, k)
    blob_bytes = max(478, (k * k * 478) // max(1, n_tx) - 4 * 478)
    node, txs = _make_pfb_node_and_txs(n_tx, blob_bytes, 4, k, b"bench")
    max_size = node.app.max_effective_square_size()
    kept = node.app._filter_txs(txs)  # warm decoded/commitment caches
    f_times, b_times = [], []
    for _ in range(3):
        node.app._sig_cache.clear()
        t0 = time.time()
        kept = node.app._filter_txs(txs)
        f_times.append((time.time() - t0) * 1000.0)
        t0 = time.time()
        build_square(kept, max_size)
        b_times.append((time.time() - t0) * 1000.0)
    assert len(kept) == n_tx
    return float(np.median(f_times)), float(np.median(b_times)), n_tx


def _prepare_then_process_ms(k: int):
    """The per-block proposer lifecycle — PrepareProposal immediately
    followed by ProcessProposal of the SAME block (the reference runs
    ExtendBlock twice per block per validator) — cold vs warm.

    Cold: every proposal-lifecycle cache cleared (EDS/DAH cache, row
    memo, signature + decoded-tx caches) — a validator seeing a foreign
    block for the first time.  Warm: the immediately repeated round —
    the proposer's own process leg / a round-restart re-proposal — where
    the content-addressed EDS cache eliminates the re-extend.  Returns
    (cold_ms, warm_ms, extras)."""
    from celestia_tpu.da import dah as dah_mod, eds_cache, inclusion

    n_tx = max(2, k)
    blob_bytes = max(478, (k * k * 478) // max(1, n_tx) - 4 * 478)
    node, txs = _make_pfb_node_and_txs(n_tx, blob_bytes, 8, k, b"ptp")
    app = node.app

    def run_once():
        t0 = time.time()
        prop = app.prepare_proposal(txs)
        ok, reason = app.process_proposal(
            prop.block_txs, prop.square_size, prop.data_root
        )
        assert ok, f"prepare_then_process rejected its own block: {reason}"
        return (time.time() - t0) * 1000.0, prop

    # warm any jit/program caches for this square size with a DIFFERENT
    # square so the cold figure measures recompute, not compile
    rng = np.random.default_rng(9)
    dah_mod.extend_and_header(
        rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    )
    eds_cache.clear()
    dah_mod.clear_row_memo()
    app._sig_cache.clear()
    app._decoded_cache.clear()
    inclusion._COMMITMENT_CACHE.clear()
    cold_ms, prop = run_once()
    warm_times = [run_once()[0] for _ in range(3)]
    warm_ms = float(np.median(warm_times))
    stats = eds_cache.stats()
    memo = dah_mod.row_memo_stats()
    hit_proc = app.telemetry.counters.get("eds_cache_hit_process", 0)
    extras = {
        "cold_ms": round(cold_ms, 1),
        "warm_ms": round(warm_ms, 1),
        "warm_speedup": round(cold_ms / warm_ms, 2) if warm_ms else 0.0,
        "square": prop.square_size,
        "txs": len(txs),
        "eds_cache_hit_rate": round(stats["hit_rate"], 3),
        "eds_cache_process_hits": hit_proc,
        "row_memo_reuse_pct": round(memo["reuse_pct"], 1),
    }
    return cold_ms, warm_ms, extras


def _row_memo_reuse(k: int):
    """Consecutive-heights row reuse, isolated from the EDS cache: height
    H+1 keeps 75% of height H's rows (unchanged blobs / padding) and
    changes the rest.  Measures the warm extend of the overlapping
    square vs a cold extend of the same square, plus the memo's observed
    reuse percentage — the direct evidence of redundant row-extension
    elimination (the EDS cache can't help here: the squares differ).

    Under leopard+native the production policy keeps the memo OFF (the
    fused C++ pipeline beats Python-orchestrated reuse even at 100%
    coverage — da/dah.py measured note), so the memo is force-enabled
    for this measurement and the result carries ``engaged_by_policy`` so
    the trajectory distinguishes the two regimes."""
    from celestia_tpu.da import dah as dah_mod
    from celestia_tpu.utils.device import host_regime

    if not host_regime():
        # device regime: extend_and_header bypasses the memo by design
        # (see da/dah.py) — the reuse figure is a host-regime metric
        return {"note": "device regime: row memo serves host legs only"}
    engaged = dah_mod._row_memo_applicable()
    rng = np.random.default_rng(11)
    a = rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    b = a.copy()
    b[: max(1, k // 4)] = rng.integers(
        0, 256, (max(1, k // 4), k, 512), dtype=np.uint8
    )
    prev_applicable = dah_mod._row_memo_applicable
    dah_mod._row_memo_applicable = lambda: True
    try:
        dah_mod.clear_row_memo()
        dah_mod.extend_and_header(a)  # height H: populates the memo
        before = dah_mod.row_memo_stats()  # exclude height H's cold misses
        t0 = time.time()
        _, dah_warm = dah_mod.extend_and_header(b)  # height H+1: 75% row hits
        warm_ms = (time.time() - t0) * 1000.0
        after = dah_mod.row_memo_stats()
        lookups = after["lookups"] - before["lookups"]
        stats = {
            "reuse_pct": (
                100.0 * (after["hits"] - before["hits"]) / lookups
                if lookups
                else 0.0
            ),
            "assembled": after["assembled"],
        }
        dah_mod.clear_row_memo()
    finally:
        dah_mod._row_memo_applicable = prev_applicable
    t0 = time.time()
    _, dah_cold = dah_mod.extend_and_header(b)
    cold_ms = (time.time() - t0) * 1000.0
    assert dah_warm.hash == dah_cold.hash, "row memo changed bytes"
    return {
        "row_memo_reuse_pct": round(stats["reuse_pct"], 1),
        "assembled": stats["assembled"],
        "engaged_by_policy": engaged,
        "warm_shared_rows_ms": round(warm_ms, 1),
        "cold_ms": round(cold_ms, 1),
    }


def _trace_summary(k: int) -> dict:
    """extras.trace_summary: per-phase ms of ONE cold prepare -> warm
    process round at k, read mechanically from the block-lifecycle
    tracer (utils/tracing.py) instead of hand-inserted clocks.  Each
    block entry is the tracer's phase_breakdown: direct-child span
    durations under the per-height root plus ``total_ms`` and
    ``untraced_ms`` — the untraced remainder of the extend phase is the
    pipeline-tail figure the ROADMAP previously described only in prose.
    Tracing is enabled only for this leg and fully torn down after, so
    every other bench number stays a tracer-off measurement."""
    from celestia_tpu.utils import tracing

    n_tx = max(2, k)
    blob_bytes = max(478, (k * k * 478) // max(1, n_tx) - 4 * 478)
    # a seed no other leg uses: the EDS cache is content-addressed, so
    # fresh tx bytes guarantee the traced prepare extends COLD (real
    # extension work in the phase split, then the warm EDS-cache hit on
    # the process leg — both regimes in one trace) WITHOUT clearing the
    # process-wide caches, whose accumulated counters the
    # unified_caches extras snapshot still has to report
    node, txs = _make_pfb_node_and_txs(n_tx, blob_bytes, 12, k, b"trace")
    node.app.prepare_proposal(txs[:2])  # warm programs/caches off-trace
    tracing.enable(4)
    tracing.clear()
    try:
        prop = node.app.prepare_proposal(txs)
        ok, reason = node.app.process_proposal(
            prop.block_txs, prop.square_size, prop.data_root
        )
        assert ok, f"trace_summary round rejected its own block: {reason}"
        out: dict = {"square": prop.square_size, "txs": len(txs)}
        for tr in tracing.block_traces():
            out[tr.name] = tracing.TRACER.phase_breakdown(tr)
            out[tr.name]["spans"] = len(tr.spans)
        return out
    finally:
        tracing.disable()
        tracing.clear()


def _critpath_extras(k: int) -> dict:
    """extras.critpath (BASELINE.md): the critical-path analyzer
    (utils/critpath.py) over ONE traced cold prepare -> warm process
    round at k.  The proposer's trace context is threaded into the
    process leg exactly the way the consensus RPC surface does it
    (rpc.cons_process wrapping the process root), so the process root
    carries a real ``_tc`` send timestamp and the report includes a
    propagation hop even on the in-process testnode (same clock —
    offset 0, clamped at 0).  k-stamped lower-is-better series: the
    analyzed critical-path wall, the unattributed gap on the path and
    the testnode-leg propagation delay.  Tracing is enabled only for
    this leg and fully torn down after."""
    from celestia_tpu.utils import critpath, tracing

    n_tx = max(2, k)
    blob_bytes = max(478, (k * k * 478) // max(1, n_tx) - 4 * 478)
    # a dedicated seed (content-addressed EDS cache): the analyzed
    # prepare must extend COLD so the path covers real extension work
    node, txs = _make_pfb_node_and_txs(n_tx, blob_bytes, 12, k, b"critpath")
    node.app.prepare_proposal(txs[:2])  # warm programs/caches off-trace
    tracing.enable(4)
    tracing.clear()
    try:
        prop = node.app.prepare_proposal(txs)
        tc = tracing.last_block_context("prepare_proposal")
        if tc is not None and not tc.get("n"):
            # the bench process has no node id; a context with an empty
            # origin is (correctly) dropped by the tracing plane, so
            # stamp the synthetic proposer identity the report shows
            tc = dict(tc, n="bench-proposer")
        with tracing.rpc_span("rpc.cons_process", tc):
            ok, reason = node.app.process_proposal(
                prop.block_txs, prop.square_size, prop.data_root
            )
        assert ok, f"critpath round rejected its own block: {reason}"
        report = None
        for tr in tracing.block_traces():
            if tr.name == "process_proposal":
                report = critpath.critical_path(tr)
        assert report is not None, "no process trace captured"
        out = {
            "square": prop.square_size,
            f"critical_path_ms_k{k}": report["total_ms"],
            f"unattributed_gap_ms_k{k}": report["attribution_ms"]["gap"],
            "clock_skew_clamped": report["clock_skew_clamped"],
        }
        delay = report["propagation_delay_ms"]
        if delay is not None:
            out[f"propagation_delay_ms_k{k}"] = delay
        return out
    finally:
        tracing.disable()
        tracing.clear()


def _host_profile_extras(k: int) -> dict:
    """extras.host_profile (BASELINE.md): the HOST half of the profile
    — the wall-clock sampling profiler (utils/hostprof.py) armed around
    one cold prepare -> warm process leg at k.  Reports the top-N
    self-time frames (leaf-frame sample counts: where the host CPU
    actually was, including the untraced tails no span names), the
    sampler's achieved samples/sec and its measured self-overhead as a
    percent of the leg wall (tools/bench_check.py alarms when that
    figure exceeds 2%).  The sampler is armed only for this leg and
    fully torn down after."""
    from celestia_tpu.utils import hostprof

    n_tx = max(2, k)
    blob_bytes = max(478, (k * k * 478) // max(1, n_tx) - 4 * 478)
    # a dedicated seed (content-addressed EDS cache): the profiled
    # prepare must extend COLD so the samples cover real extension work
    node, txs = _make_pfb_node_and_txs(n_tx, blob_bytes, 17, k, b"hostprof")
    node.app.prepare_proposal(txs[:2])  # warm programs/caches unprofiled
    hostprof.clear()
    hostprof.start(200.0)
    t0 = time.time()
    try:
        prop = node.app.prepare_proposal(txs)
        # one deterministic mid-leg sample: a tiny-k leg can finish
        # inside a single sampler tick, and an empty profile would read
        # as "sampler broken" to the watchdog (its cost is measured
        # into overhead_pct like any tick — nothing is hidden)
        hostprof.sample_once()
        ok, reason = node.app.process_proposal(
            prop.block_txs, prop.square_size, prop.data_root
        )
        assert ok, f"host_profile round rejected its own block: {reason}"
        leg_wall_ms = (time.time() - t0) * 1000.0
    finally:
        hostprof.stop()
    st = hostprof.stats()
    out = {
        "k": k,
        "square": prop.square_size,
        "hz": st["hz"],
        "leg_wall_ms": round(leg_wall_ms, 1),
        "samples_total": st["samples_total"],
        "samples_per_s": st["samples_per_s"],
        "sampler_overhead_pct": st["overhead_pct"],
        "folded_unique": st["folded_unique"],
        "top_frames": hostprof.top_frames(10),
    }
    hostprof.clear()
    return out


def _device_profile_extras(k: int) -> dict:
    """extras.device_profile (BASELINE.md): per-kernel XLA FLOPs /
    bytes-accessed / measured compile ms, per-dispatch counts + busy ms,
    device-occupancy percent over the leg's window and the device-memory
    watermark — collected by utils/devprof.py around three fused
    extend+roots dispatches.  The same leg runs on a host-only round
    (XLA CPU backend at a tiny k): platform gaps (memory_stats None,
    cost_analysis absent) degrade to the profile's ``notes`` section,
    never an exception."""
    import jax.numpy as jnp

    from celestia_tpu.da import dah as dah_mod
    from celestia_tpu.ops.gf256 import active_codec
    from celestia_tpu.utils import devprof

    rng = np.random.default_rng(5)
    sq = rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    fn = dah_mod._extend_and_roots_fn(k, active_codec())
    arr = jnp.asarray(sq)
    # warm the executable OUTSIDE the occupancy window so the reported
    # occupancy is dispatch time, not compile time (the compile figure
    # is note_compile's own measured AOT build below)
    import jax as _jax

    _jax.block_until_ready(fn(arr))
    with devprof.collect():
        # cost/compile accounting FIRST (flushed — the build runs on a
        # background thread), then restart the occupancy window: the
        # one-time AOT compile contributes wall time but zero busy
        # time, and leaving it in the window would turn the
        # HIGHER-is-better occupancy headline into compile-noise.
        # 10 dispatches amortize per-dispatch Python/memory_stats
        # overhead so the occupancy figure is stable enough to trend.
        devprof.note_compile("extend_and_roots", fn, (arr,))
        devprof.flush_compiles()
        devprof.restart_window()
        for _ in range(10):
            d = devprof.dispatch("extend_and_roots", k=k)
            d.done(fn(arr))
        prof = devprof.device_profile()
    prof["k"] = k
    return prof


def _transfer_accounting_extras(k: int) -> dict:
    """extras.transfer_accounting (BASELINE.md): per-leg H2D/D2H bytes,
    ms and event counts through the device-resident plane
    (da/device_plane.py), recorded by the devprof transfer ledger around
    one cold extend and one device-warm batched DAS serve.

    The plane is FORCED on for the leg (on the CPU fallback round it
    would otherwise stay off), so the figures always describe the
    device-resident wiring: the extend phase should charge one square
    upload (h2d) plus the data-root + axis-roots fetches (d2h), and the
    warm serve phase should charge ONLY the batched proof-path gather —
    ``hot_path_d2h_legs`` lists every leg that crossed, which is how
    bench_check sees a new unplanned transfer sneak onto the hot path."""
    from celestia_tpu.da import dah as dah_mod
    from celestia_tpu.da import das as das_mod
    from celestia_tpu.da import device_plane, eds_cache
    from celestia_tpu.utils import devprof

    rng = np.random.default_rng(7)
    sq = rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    sq[:, :, :29] = 0
    sq[:, :, 28] = rng.integers(1, 200, (k, k), dtype=np.uint8)
    n2 = 2 * k
    coord_rng = np.random.default_rng(8)
    coords = [
        (int(r), int(c))
        for r, c in zip(
            coord_rng.integers(0, n2, 64), coord_rng.integers(0, n2, 64)
        )
    ]
    with device_plane.forced("on"):
        if device_plane.poisoned() is not None:
            return {"skipped": f"plane poisoned: {device_plane.poisoned()}"}
        # warm the executables OUTSIDE the ledger window: the one-time
        # compile is not a per-call transfer
        eds_w, dah_w = dah_mod.extend_and_header(sq.copy())
        das_mod.sample_proofs_batch(eds_w, dah_w, coords)
        devprof.reset()
        with devprof.collect():
            t0 = time.time()
            eds, dah = dah_mod.extend_and_header(sq.copy())
            extend_ms = (time.time() - t0) * 1000.0
            extend_legs = devprof.transfer_accounting()
            t0 = time.time()
            proofs = das_mod.sample_proofs_batch(eds, dah, coords)
            serve_ms = (time.time() - t0) * 1000.0
            all_legs = devprof.transfer_accounting()
        if device_plane.poisoned() is not None:
            return {"skipped": f"plane poisoned: {device_plane.poisoned()}"}
        # byte-identity spot check: the ledger must never be the cost of
        # a wrong proof (full cross-product pinned by the tier-1 tests)
        ref = das_mod._sample_proof_uncached(eds, dah, *coords[0])
        assert proofs[0] == ref, "device-served proof diverged"
    serve_legs = {
        leg: rec for leg, rec in all_legs.items()
        if rec != extend_legs.get(leg)
    }
    out = {
        "k": k,
        "cells": len(coords),
        "extend_cold_ms": round(extend_ms, 2),
        "proof_serve_warm_ms": round(serve_ms, 2),
        "legs": all_legs,
        "hot_path_d2h_legs": sorted(
            leg for leg, rec in all_legs.items() if rec["d2h_events"]
        ),
        "extend_d2h_bytes": sum(
            rec["d2h_bytes"] for rec in extend_legs.values()
        ),
        "proof_serve_d2h_bytes": sum(
            rec["d2h_bytes"] - extend_legs.get(leg, {}).get("d2h_bytes", 0)
            for leg, rec in serve_legs.items()
        ),
        "total_d2h_bytes": sum(
            rec["d2h_bytes"] for rec in all_legs.values()
        ),
        "total_h2d_bytes": sum(
            rec["h2d_bytes"] for rec in all_legs.values()
        ),
        "device_cache": eds_cache.device_handle_stats(),
    }
    return out


def _multichip_extras() -> dict:
    """extras.multichip: sharded vs unsharded extend + the batched
    multi-block leg on THIS process's mesh (parallel/mesh.py resolves it
    from the visible chips; a one-chip host records a skip).  Runs in the
    bench's own process — a chip belongs to one process, so no child
    could reach it.  Root byte-identity vs the unsharded native
    reference is asserted on both legs, so a wrong number can never be
    recorded as a fast one."""
    import jax

    from celestia_tpu.parallel import mesh as mesh_mod
    from celestia_tpu.parallel import sharded
    from celestia_tpu.utils import native

    mesh = mesh_mod.device_mesh()
    if mesh is None:
        return {"skipped": f"no multi-chip mesh: {mesh_mod.stats()}"}
    k = int(os.environ.get("BENCH_MULTICHIP_K", "128"))
    batch = int(os.environ.get("BENCH_MULTICHIP_BATCH", "8"))
    data_ax, row_ax = mesh_mod.mesh_shape()
    out = {
        "platform": str(jax.default_backend()),
        "devices": int(jax.local_device_count()),
        "mesh": f"{data_ax}x{row_ax}",
        "k": k,
        "batch": batch,
    }
    rng = np.random.default_rng(42)
    sq = rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    # unsharded reference: the pooled native host pipeline (byte-identical
    # to the device path per the golden-vector pins)
    times = []
    for _ in range(3):
        t0 = time.time()
        _e0, _r0, droot_ref = native.extend_block_leopard_cpu(sq)
        times.append((time.time() - t0) * 1000.0)
    out[f"unsharded_extend_{k}_ms"] = round(float(np.median(times)), 1)
    out["unsharded_leg"] = "leopard_cpu"
    ref_root = droot_ref.tobytes()

    t0 = time.time()
    _eds, _rr, _cc, droot = sharded.extend_and_roots_sharded(sq, mesh)
    out[f"sharded_extend_{k}_cold_ms"] = round((time.time() - t0) * 1000.0, 1)
    # explicit raise, not assert: `python -O` must not be able to record
    # a diverged root as a fast number
    if droot.tobytes() != ref_root:
        raise RuntimeError("sharded data root diverged from the reference")
    times = []
    for _ in range(2):
        t0 = time.time()
        sharded.extend_and_roots_sharded(sq, mesh)
        times.append((time.time() - t0) * 1000.0)
    out[f"sharded_extend_{k}_ms"] = round(float(np.median(times)), 1)

    # batched multi-block leg (BASELINE config #5): square 0 IS the
    # single leg's square, so the batch is checked against the same root
    sqs = rng.integers(0, 256, (batch, k, k, 512), dtype=np.uint8)
    sqs[0] = sq
    t0 = time.time()
    _be, _br, _bc, bdroots = sharded.extend_and_roots_sharded_batch(sqs, mesh)
    out[f"batched_{batch}x{k}_cold_ms"] = round((time.time() - t0) * 1000.0, 1)
    if bdroots[0].tobytes() != ref_root:
        raise RuntimeError("batched sharded data root diverged from the reference")
    t0 = time.time()
    sharded.extend_and_roots_sharded_batch(sqs, mesh)
    warm_s = time.time() - t0
    out[f"batched_{batch}x{k}_per_square_ms"] = round(warm_s * 1000.0 / batch, 1)
    out[f"batched_{batch}x{k}_blocks_per_s"] = round(batch / warm_s, 2)
    return out


def _unified_cache_stats() -> dict:
    """Process-wide view of every bounded cache (utils/lru.py registry):
    per-cache hit rate / evictions / approximate resident bytes plus the
    summed footprint against the CELESTIA_TPU_CACHE_BUDGET_MB advisory
    budget — the LRU-consolidation telemetry BENCH_r06 captures.  The
    legacy eds_cache_* keys above are produced by the domain wrapper and
    stay byte-for-byte compatible; this section is additive."""
    from celestia_tpu.utils import lru

    stats = lru.registry_stats()
    caches = {}
    for name, agg in sorted(stats["caches"].items()):
        caches[name] = {
            "instances": agg["instances"],
            "entries": agg["entries"],
            "hit_rate": round(agg["hit_rate"], 3),
            "evictions": agg["evictions"],
            "approx_bytes": agg["approx_bytes"],
        }
    return {
        "caches": caches,
        "total_approx_bytes": stats["total_approx_bytes"],
        "budget_bytes": stats["budget_bytes"],
        "over_budget": stats["over_budget"],
    }


def _fault_recovery_stats() -> dict:
    """Injected-fault recovery latency (PR 7 robustness trajectory): a
    simulated gossip fetch driven through the unified RetryPolicy with
    the gossip.fetch point armed at a 10% fail rate — p50/p99 of the
    per-fetch wall time INCLUDING the seeded backoff sleeps, so the
    number is the latency an actual catch-up pull pays when one peer in
    ten flakes.  Fully seeded: the schedule and the jitter reproduce."""
    from celestia_tpu.utils import faults

    rate = 0.10
    n = 400
    faults.arm("gossip.fetch", "fail_rate", rate=rate, seed=1234)
    lat = []
    recovered = 0
    try:
        for i in range(n):
            policy = faults.RetryPolicy(
                attempts=6, base_s=0.001, cap_s=0.01, seed=i
            )
            t0 = time.perf_counter()
            policy.run(lambda: faults.fire("gossip.fetch"))
            lat.append((time.perf_counter() - t0) * 1000.0)
        armed = faults.armed_points()["gossip.fetch"]
        recovered = armed["injected"]
    finally:
        faults.disarm("gossip.fetch")
    lat.sort()
    return {
        "fault_rate": rate,
        "fetches": n,
        "injected_faults_recovered": recovered,
        "gossip_fetch_p50_ms": round(lat[len(lat) // 2], 3),
        "gossip_fetch_p99_ms": round(lat[int(len(lat) * 0.99)], 3),
    }


def _fault_stats_extras() -> dict:
    """extras.fault_stats: recovery-latency leg + the process-wide
    injection/swallow/degradation counters (BASELINE.md)."""
    from celestia_tpu.utils import faults

    out = {"recovery": _fault_recovery_stats()}
    s = faults.fault_stats()
    out["notes"] = s["notes"]
    out["degradations"] = s["degradations"]
    return out


def _lint_stats_extras() -> dict:
    """extras.lint_stats: one full-tree celint run with per-rule wall
    timing — the whole-program pass (R6 builds a cross-module lock graph)
    is a growing cost that bench_check watches for drift the same way it
    watches latency legs."""
    from celestia_tpu.lint import LintStats, failing, run_lint

    stats = LintStats()
    findings = run_lint(stats=stats)
    d = stats.to_dict()
    return {
        "wall_ms": d["total_wall_ms"],
        "files": d["files"],
        "failing": len(failing(findings)),
        "suppressed": sum(1 for f in findings if f.suppressed),
        "rules": {
            rid: {"wall_ms": rec["wall_ms"], "findings": rec["findings"]}
            for rid, rec in d["rules"].items()
        },
    }


def _das_serving_extras(k: int, n_samples: int = 256) -> dict:
    """extras.das_serving (BASELINE.md): the vectorized DA serving plane
    at k x k — samples/sec for the per-cell prover loop (the pre-batch
    serving cost, uncached by construction) vs the batched prover cold
    (row stacks built once per row) and warm (das_rows cache serving
    pure proof-path extraction).  Keys are k-stamped so rounds at
    different square sizes never cross-compare in bench_check.  The leg
    ASSERTS batch-vs-scalar proof byte-identity — a faster prover that
    changes one proof byte is a failed leg, not a better number."""
    from celestia_tpu.da import dah as dah_mod
    from celestia_tpu.da import das as das_mod

    rng = np.random.default_rng(12)
    square = rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    square[:, :, :29] = 0
    square[:, :, 28] = rng.integers(1, 200, (k, k), dtype=np.uint8)
    eds, dah = dah_mod.extend_and_header(square)
    n2 = 2 * k
    n = min(int(n_samples), n2 * n2)
    flat = np.random.default_rng(13).choice(n2 * n2, size=n, replace=False)
    coords = [(int(f) // n2, int(f) % n2) for f in flat]

    # per-cell loop: every sample rebuilds its row stack + the 4k-root
    # tree (the serving cost before this plane existed)
    t0 = time.perf_counter()
    scalar = [das_mod._sample_proof_uncached(eds, dah, r, c) for r, c in coords]
    scalar_s = time.perf_counter() - t0

    das_mod.rows_cache().clear()
    t0 = time.perf_counter()
    cold = das_mod.sample_proofs_batch(eds, dah, coords)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = das_mod.sample_proofs_batch(eds, dah, coords)
    warm_s = time.perf_counter() - t0

    # explicit raise, not assert: python -O must not be able to record
    # a faster-but-wrong prover's figures as byte_identical
    if cold != scalar or warm != scalar:
        raise RuntimeError(
            "batch prover output diverged from the per-cell prover"
        )
    stats = das_mod.rows_cache().stats()
    out = {
        "k": k,
        "samples": n,
        "rows_touched": len({r for r, _ in coords}),
        f"scalar_k{k}_samples_per_s": round(n / scalar_s, 1),
        f"batch_cold_k{k}_samples_per_s": round(n / cold_s, 1),
        f"batch_warm_k{k}_samples_per_s": round(n / warm_s, 1),
        f"warm_batch_vs_scalar_k{k}_speedup": round(scalar_s / warm_s, 2),
        "byte_identical": True,
        "das_rows": {
            "hits": stats["hits"],
            "misses": stats["misses"],
            "hit_rate": stats["hit_rate"],
            "approx_bytes": stats["approx_bytes"],
        },
    }
    return out


def _swarm_extras() -> dict:
    """extras.swarm (BASELINE.md): the light-client swarm legs against
    one live QoS-enabled node over the real gRPC boundary.  Two seeded
    legs: an HONEST crowd (no over-askers — the per-tier latency tails
    and the Jain fairness index bench_check judges against the 0.8
    absolute floor) and a HOSTILE MIX (the same crowd plus over-askers,
    pinning the light tier's p99 while the flood is demoted and shed).
    Percentile keys are k-stamped with the SERVED square size, so
    rounds at different block shapes never cross-compare.  Wall-clock
    concurrency makes shed counts load-dependent — the recorded figures
    are tails and rates, never exact schedules.  A leg that hits its
    hard deadline reports {"error": ...} instead of partial numbers."""
    from celestia_tpu.client.signer import Signer
    from celestia_tpu.client.swarm import SwarmConfig, run_swarm
    from celestia_tpu.da import das as das_mod
    from celestia_tpu.da.blob import Blob
    from celestia_tpu.da.namespace import Namespace
    from celestia_tpu.node.server import NodeServer
    from celestia_tpu.node.testnode import TestNode
    from celestia_tpu.utils.secp256k1 import PrivateKey

    key = PrivateKey.from_seed(b"bench-swarm")
    node = TestNode(funded_accounts=[(key, 10**12)])
    signer = Signer(node, key)
    rng = np.random.default_rng(23)
    heights = []
    for i in range(2):
        data = bytes(rng.integers(0, 256, 4000, dtype=np.uint8))
        res = signer.submit_pay_for_blob(
            [Blob(Namespace.v0(bytes([0x41 + i]) * 10), data)]
        )
        if res.code != 0:
            return {"error": f"blob submit failed: {res.log[:120]}"}
        heights.append(res.height)
    blocks = [(h, node.block(h).header.square_size) for h in heights]
    k = max(s for _, s in blocks)

    das_mod.rows_cache().clear()
    server = NodeServer(
        node,
        block_interval_s=None,
        das_max_inflight=4,
        das_qos=True,
        timeseries_interval_s=None,
    )
    server.start()
    try:
        honest = run_swarm(server.address, blocks, SwarmConfig(
            clients=24, hostile=0, rounds=2, samples_per_round=1,
            batch_sizes=(4, 8), seed=5, workers=8,
            retry_attempts=4, request_deadline_s=5.0, deadline_s=30.0,
        ))
        mix = run_swarm(server.address, blocks, SwarmConfig(
            clients=24, hostile=4, rounds=2, samples_per_round=1,
            hostile_multiplier=8, batch_sizes=(4, 8), seed=6, workers=8,
            retry_attempts=4, request_deadline_s=5.0, deadline_s=30.0,
        ))
    finally:
        server.stop()

    out = {"k": k, "clients": 24, "blocks": len(blocks)}
    for name, rep, cfg_rounds in (
        ("honest", honest, 2), ("hostile_mix", mix, 2),
    ):
        if rep["deadline_hit"] or rep["rounds_run"] < cfg_rounds:
            out[name] = {
                "error": f"deadline hit after {rep['rounds_run']} rounds"
            }
            continue
        leg = {
            "requests": rep["requests"],
            "samples_per_s": rep["samples_per_s"],
            f"light_p50_k{k}_ms": rep["latency"]["light"]["p50_ms"],
            f"light_p99_k{k}_ms": rep["latency"]["light"]["p99_ms"],
            "light_shed_rate": rep["groups"]["light"]["shed_rate"],
        }
        if rep["hostile"]:
            leg[f"hostile_p99_k{k}_ms"] = (
                rep["latency"]["hostile"]["p99_ms"]
            )
            leg["hostile_shed_rate"] = (
                rep["groups"]["hostile"]["shed_rate"]
            )
        out[name] = leg
    # the floor-judged contract figure is the HONEST crowd's fairness:
    # with no over-askers a QoS-healthy plane serves near-uniformly
    if isinstance(out.get("honest"), dict) and "error" not in out["honest"]:
        out["fairness_index"] = honest["fairness_index"]
    return out


def _host_repair_ms(k: int):
    """Host-only repair (the light-client/DAS path — no accelerator):
    25% withheld, root-verified.  Under the leopard codec this runs the
    O(n log n) FFT erasure decode + FFT re-extension
    (native leo_decode_axes / extend_block_leopard_cpu)."""
    from celestia_tpu.ops import rs
    from celestia_tpu.utils import native

    if not native.available():
        return None
    rng = np.random.default_rng(3)
    sq = rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    eds, roots, _ = native.extend_block_leopard_cpu(sq)
    rr, cc = roots[: 2 * k], roots[2 * k :]
    avail = rng.random((2 * k, 2 * k)) >= 0.25
    damaged = eds.copy()
    damaged[~avail] = 0
    times = []
    for _ in range(3):
        t0 = time.time()
        fixed = rs.repair_square(
            damaged, avail, row_roots=rr, col_roots=cc
        )
        times.append((time.time() - t0) * 1000.0)
    assert np.array_equal(fixed, eds), "host repair produced a wrong square"
    return float(np.median(times))


def _glv_us_per_sig(n: int = 256, precomp=None):
    """Native batched ECDSA verify, µs per signature (ADR-011 host leg) —
    8 distinct senders so the pubkey-decompression cache behaves like a
    proposal (senders repeat).  Raises when the native kernel is absent:
    verify_batch would silently fall back to pure Python there, and that
    figure must never be recorded under the GLV key.

    precomp routes the table strategy (native.ecmul_double_glv_batch):
    False = legacy Jacobian-table symbol, True = the batched
    precomputed-affine-table symbol, None = production auto-routing."""
    from celestia_tpu.utils import native
    from celestia_tpu.utils.secp256k1 import PrivateKey, verify_batch

    if not (native.available() and native.has_glv()):
        raise RuntimeError("native GLV kernel unavailable")
    if precomp and not native.has_glv_pre():
        raise RuntimeError("native GLV precomp symbol unavailable")

    keys = [PrivateKey.from_seed(b"bench-glv-%d" % (i % 8)) for i in range(n)]
    msgs = [b"bench-glv-msg-%d" % i for i in range(n)]
    sigs = [key.sign(m) for key, m in zip(keys, msgs)]
    pubs = [key.public_key().compressed() for key in keys]
    out = verify_batch(msgs, sigs, pubs, precomp=precomp)  # warm
    times = []
    for _ in range(5):
        t0 = time.time()
        out = verify_batch(msgs, sigs, pubs, precomp=precomp)
        times.append((time.time() - t0) * 1e6 / n)
    assert all(out), "bench GLV verify failed on valid signatures"
    return float(np.median(times))


def _tx_ingress_extras(n: int = 512) -> dict:
    """extras.tx_ingress: the batched admission plane end to end.

    Sustained CheckTx tx/s at batch {1, 64, 512} in the cold regime
    (empty caches — first sight of the bytes) and at batch 512 in the
    warm regime (a twin node re-admitting bytes whose signature/decode
    verdicts are already cached: the gossip-replay shape).  Then the
    FilterTxs pair the acceptance criterion names: the sequential
    cold leg (the r05 ``filter_512_pfb_ms`` regime) vs the batched
    plane (admission through check_txs_batch pre-pays signatures and
    decodes, filter runs admission-warmed), with the kept-tx lists
    asserted BYTE-IDENTICAL in-leg.  Finally GLV µs/sig with and
    without the precomputed-table symbol.  All figures are batch- and
    regime-stamped for tools/bench_check.py (tx/s and speedup series
    are higher-is-better)."""
    from celestia_tpu.da import inclusion
    from celestia_tpu.node.testnode import TestNode
    from celestia_tpu.utils.secp256k1 import PrivateKey

    out = {}
    node, txs = _make_pfb_node_and_txs(n, 2000, 6, 128, b"ingress")
    app = node.app

    def _twin():
        # fresh node with the IDENTICAL genesis (same seeds/accounts), so
        # the one signed tx set stays valid and each drain starts from a
        # clean check state
        keys = [PrivateKey.from_seed(b"ingress-%d" % i) for i in range(8)]
        t = TestNode(
            funded_accounts=[(key, 10**15) for key in keys],
            auto_produce=False,
        )
        t.app.params.set("blob", "GovMaxSquareSize", 128)
        return t

    def _clear(a):
        inclusion._COMMITMENT_CACHE.clear()
        a._sig_cache.clear()
        a._decoded_cache.clear()

    # -- sequential FilterTxs, cold (the r05 baseline regime) ----------
    seq_times = []
    for _ in range(3):
        _clear(app)
        t0 = time.time()
        kept_seq = app._filter_txs(txs, parallel=False)
        seq_times.append((time.time() - t0) * 1000.0)
    assert len(kept_seq) == n, f"filter kept {len(kept_seq)}/{n}"
    out["filter_seq_cold_512_ms"] = round(float(np.median(seq_times)), 1)

    # -- sustained CheckTx tx/s, cold, batch {1, 64, 512} --------------
    for batch in (1, 64, 512):
        tnode = _twin()
        _clear(tnode.app)
        t0 = time.time()
        if batch == 1:
            results = [tnode.app.check_tx(raw) for raw in txs]
        else:
            results = []
            for i in range(0, n, batch):
                results.extend(tnode.app.check_txs_batch(txs[i : i + batch]))
        wall = time.time() - t0
        assert [r.code for r in results] == [0] * n, "bench admission failed"
        out[f"check_b{batch}_cold_tx_per_s"] = round(n / wall, 1)
        if batch == 64:
            # in-leg verdict identity: the batched drain must match a
            # per-tx CheckTx loop result-for-result
            loop_node = _twin()
            _clear(loop_node.app)
            loop = [loop_node.app.check_tx(raw) for raw in txs]
            assert [(r.code, r.log) for r in loop] == [
                (r.code, r.log) for r in results
            ], "batched CheckTx verdicts diverged from the sequential loop"
        if batch == 512:
            warmed_sig, warmed_dec = tnode.app._sig_cache, tnode.app._decoded_cache
    # warm regime: a twin re-admits the same bytes with the verdict
    # caches attached (gossip replay / node restart shape)
    wnode = _twin()
    wnode.app._sig_cache = warmed_sig
    wnode.app._decoded_cache = warmed_dec
    t0 = time.time()
    results = wnode.app.check_txs_batch(txs)
    wall = time.time() - t0
    assert [r.code for r in results] == [0] * n
    out["check_b512_warm_tx_per_s"] = round(n / wall, 1)

    # -- the batched admission plane's FilterTxs ----------------------
    # production path: every proposal tx arrived through CheckTx, which
    # pre-paid its signature + decode verdicts; filter then runs
    # admission-warmed (and through the parallel leg on multi-core
    # hosts).  Verdict identity with the cold sequential leg is the
    # acceptance assert.
    bnode = _twin()
    _clear(bnode.app)
    bnode.app.check_txs_batch(txs)  # admission warms the plane
    bat_times = []
    for _ in range(3):
        t0 = time.time()
        kept_bat = bnode.app._filter_txs(txs)
        bat_times.append((time.time() - t0) * 1000.0)
    assert kept_bat == kept_seq, "batched-plane filter verdicts diverged"
    out["filter_batched_512_ms"] = round(float(np.median(bat_times)), 1)
    out["filter_512_speedup"] = round(
        out["filter_seq_cold_512_ms"] / max(out["filter_batched_512_ms"], 1e-3),
        2,
    )

    # -- GLV µs/sig with and without table precomputation -------------
    try:
        out["glv_nopre_us_per_sig"] = round(_glv_us_per_sig(precomp=False), 1)
        out["glv_pre_us_per_sig"] = round(_glv_us_per_sig(precomp=True), 1)
    except Exception as e:
        out["glv_pre_error"] = repr(e)[:200]
    return out


def _dah_128_fixture_match() -> bool:
    """Run the Go stack's 128x128 fixture through the DEVICE pipeline and
    compare against the pinned hash (VERDICT r4 weak #4: the test suite
    only ties the 128 vector to the native C++ leg because XLA CPU takes
    minutes to compile it; on the real chip the compile is seconds, so
    the bench asserts the fixture on-device every round).  Vector + share
    construction live in celestia_tpu.da.golden, shared with the tests."""
    from celestia_tpu.da import dah as dah_mod
    from celestia_tpu.da.golden import DAH_128_HASH, fixture_shares

    eds = dah_mod.extend_shares(fixture_shares(128 * 128))
    dah = dah_mod.new_data_availability_header(eds)
    return dah.hash == DAH_128_HASH


def _degradations() -> list:
    """Every degradation the process recorded — a poisoned device plane,
    mesh or native library records one (faults.record_degradation).  The
    bench's numbers are device numbers only when this is empty."""
    from celestia_tpu.utils import faults

    return [
        f"{d['subsystem']}: {d['reason']}"
        for d in faults.fault_stats()["degradations"]
    ]


def main():
    from celestia_tpu.utils.device import enable_compile_cache

    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        sys.exit(
            "bench.py: no accelerator found (jax platform 'cpu'); the bench "
            "measures the chip and has no host fallback"
        )
    k = K
    extras = {
        "device": {
            "platform": str(dev.platform),
            "kind": str(dev.device_kind),
            "count": len(jax.devices()),
        }
    }
    device_ms = _amortized_device_ms(k)
    extras[f"extend_block_{k}_device_ms"] = round(device_ms, 3)
    cpu_ms = _cpu_ms(k)
    if cpu_ms is not None:
        extras[f"extend_block_{k}_table_gf_cpu_ms"] = round(cpu_ms, 1)
        extras["cpu_threads"] = _cpu_threads()
    try:
        leo_ms, leo_ext_ms = _leopard_cpu_ms(k)
    except Exception as e:  # never let a CPU leg kill the device evidence
        leo_ms, leo_ext_ms = None, None
        extras["leopard_error"] = repr(e)[:200]
    if leo_ms is not None:
        # the honest baseline leg (BASELINE ≥10x target): Leopard-class
        # O(n log n) FFT + pshufb SIMD multiply, full pipeline at full
        # size on this host; extension_only isolates the codec itself
        extras["cpu_leg"] = "leopard_cpu"
        extras[f"extend_block_{k}_leopard_cpu_ms"] = round(leo_ms, 1)
        extras["leopard_extension_only_ms"] = round(leo_ext_ms, 1)
        cpu_ms = leo_ms  # vs_baseline compares against the leopard leg
    elif cpu_ms is not None:
        extras["cpu_leg"] = "table_gf_cpu"
    try:
        scaling = _leopard_scaling_ms(k, leo_ms)
        if scaling is not None:
            extras["extend_block_thread_scaling_ms"] = scaling
    except Exception as e:
        extras["scaling_error"] = repr(e)[:200]
    e2e_ms = _e2e_extend_ms(k)
    extras[f"extend_block_{k}_e2e_single_call_ms"] = round(e2e_ms, 2)
    extras["transfer_overhead_ms"] = round(e2e_ms - device_ms, 2)
    try:
        prep_ms, sq_size, n_tx, breakdown = _prepare_proposal_ms(k)
        extras[f"prepare_proposal_{k}_e2e_ms"] = round(prep_ms, 1)
        extras["prepare_proposal_square"] = sq_size
        extras["prepare_proposal_txs"] = n_tx
        extras["prepare_breakdown"] = breakdown
        # what PrepareProposal costs without its transfers: host filter
        # + host build + the AMORTIZED device compute (the breakdown's
        # upload/compute/fetch each carry extra syncs, so the
        # chained-iteration device_ms is the honest compute figure).
        # SURVEY §7 hard part c budget: < 50 ms.
        extras["prepare_minus_transfer_ms"] = round(
            breakdown.get("filter_ms", 0.0)
            + breakdown.get("build_ms", 0.0)
            + device_ms,
            1,
        )
    except Exception as e:  # keep the headline even if the app path trips
        extras["prepare_proposal_error"] = repr(e)[:200]
    try:
        # the redundant-work elimination headline: one block's prepare ->
        # process lifecycle, cold vs warm (EDS cache + row memo + sig/
        # decode caches) — the warm leg is the proposer's own process
        # re-extend collapsing to a content-addressed lookup
        cold_ms, warm_ms, ptp = _prepare_then_process_ms(k)
        extras[f"prepare_then_process_{k}tx_ms"] = ptp
    except Exception as e:
        extras["prepare_then_process_error"] = repr(e)[:200]
    try:
        # host-regime leg even on a device round: the row memo serves
        # host-only deployments, so its reuse evidence is a host figure
        extras["row_memo"] = _row_memo_reuse(k)
    except Exception as e:
        extras["row_memo_error"] = repr(e)[:200]
    try:
        repair_ms, repair_bd = _repair_ms(k)
        # DAS-serving regime: verified repair with the square kept in
        # device memory (return_device=True) — the upload overlaps the
        # host scheduling, the verdicts come back in one batched fetch,
        # and the bulk fetch (only paid by host-side consumers) is the
        # separate bulk_fetch_ms line in the breakdown
        extras[f"repair_{k}_25pct_ms"] = round(repair_ms, 1)
        extras["repair_breakdown"] = repair_bd
        # NOTE: the old repair_minus_transfer_ms key is intentionally
        # gone — with the upload overlapped into the dispatch window the
        # "e2e minus transfers" split no longer exists; the RTT-free
        # on-chip figure is repair_{k}_device_amortized_ms below, and
        # repair_{k}_25pct_ms IS the serving-regime e2e (no bulk fetch).
        # RTT-free device figure: chained-iteration marginal cost of the
        # full verified repair program (decode + re-extension + roots) —
        # what the <500 ms BASELINE #4 budget means on attached hardware
        extras[f"repair_{k}_device_amortized_ms"] = round(
            _amortized_repair_device_ms(k), 1
        )
    except Exception as e:
        extras["repair_error"] = repr(e)[:200]
    try:
        extras["filter_512_pfb_ms"] = round(_filter_txs_ms(512), 1)
    except Exception as e:
        extras["filter_error"] = repr(e)[:200]
    try:
        extras["tx_ingress"] = _tx_ingress_extras()
    except Exception as e:
        extras["tx_ingress_error"] = repr(e)[:200]
    try:
        batch_ms = _amortized_device_ms(k, batch=BATCH)
        extras[f"batch{BATCH}x{k}_per_square_ms"] = round(batch_ms / BATCH, 3)
    except Exception as e:
        extras["batch_error"] = repr(e)[:200]
    try:
        extras["glv_us_per_sig"] = round(_glv_us_per_sig(), 1)
    except Exception as e:
        extras["glv_error"] = repr(e)[:200]
    try:
        host_repair = _host_repair_ms(k)
        if host_repair is not None:
            extras[f"repair_{k}_host_25pct_ms"] = round(host_repair, 1)
    except Exception as e:
        extras["host_repair_error"] = repr(e)[:200]
    try:
        # Go-fixture gate on the DEVICE path (only meaningful at k=128)
        if k == 128:
            extras["dah_128_fixture_match"] = bool(_dah_128_fixture_match())
    except Exception as e:
        extras["dah_128_fixture_error"] = repr(e)[:200]
    try:
        # robustness trajectory: injected-fault recovery latency + the
        # process-wide injection/swallow/degradation counters
        extras["fault_stats"] = _fault_stats_extras()
    except Exception as e:
        extras["fault_stats_error"] = repr(e)[:200]
    try:
        # per-phase span breakdown of one prepare->process round (the
        # observability plane's mechanical phase pin, BASELINE.md)
        extras["trace_summary"] = _trace_summary(k)
    except Exception as e:
        extras["trace_summary_error"] = repr(e)[:200]
    try:
        # critical-path attribution of the same lifecycle (k-stamped
        # lower-is-better series the watchdog tracks)
        extras["critpath"] = _critpath_extras(k)
    except Exception as e:
        extras["critpath_error"] = repr(e)[:200]
    try:
        # device-side truth (PR 11): XLA cost/compile accounting,
        # dispatch occupancy and the device-memory watermark around the
        # fused extend+roots kernel at full k
        extras["device_profile"] = _device_profile_extras(k)
    except Exception as e:
        extras["device_profile_error"] = repr(e)[:200]
    try:
        # multi-chip sharded series: the live mesh path's sharded-vs-
        # unsharded extend + the batched multi-block leg (this process)
        extras["multichip"] = _multichip_extras()
    except Exception as e:
        extras["multichip_error"] = repr(e)[:200]
    try:
        # vectorized DA serving plane: batched multi-sample prover vs
        # the per-cell loop, cold vs warm (byte-identity asserted)
        extras["das_serving"] = _das_serving_extras(k)
    except Exception as e:
        extras["das_serving_error"] = repr(e)[:200]
    try:
        # light-client swarm legs: honest crowd + hostile mix against a
        # live QoS-enabled node (per-tier tails, fairness vs 0.8 floor)
        extras["swarm"] = _swarm_extras()
    except Exception as e:
        extras["swarm_error"] = repr(e)[:200]
    try:
        # device-resident plane ledger: per-leg H2D/D2H bytes + ms for
        # extend vs device-warm proof serving (bench_check watches the
        # byte/ms figures like compute regressions)
        extras["transfer_accounting"] = _transfer_accounting_extras(k)
    except Exception as e:
        extras["transfer_accounting_error"] = repr(e)[:200]
    try:
        # LAST: snapshot after every leg has exercised its caches
        extras["unified_caches"] = _unified_cache_stats()
    except Exception as e:
        extras["unified_caches_error"] = repr(e)[:200]
    try:
        # static-analysis cost trajectory: celint whole-tree wall ms +
        # per-rule split (bench_check watches lint_stats.wall_ms)
        extras["lint_stats"] = _lint_stats_extras()
    except Exception as e:
        extras["lint_stats_error"] = repr(e)[:200]

    degraded = _degradations()
    if degraded:
        sys.exit("bench.py: the device path degraded: " + "; ".join(degraded))
    vs = round(cpu_ms / device_ms, 1) if cpu_ms else 0.0
    print(
        json.dumps(
            {
                "metric": f"extend_block_{k}x{k}_p50_device_ms",
                "value": round(device_ms, 3),
                "unit": "ms",
                "vs_baseline": vs,
                "extras": extras,
            }
        )
    )


if __name__ == "__main__":
    main()
