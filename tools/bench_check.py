"""bench_check: the bench-regression watchdog.

Reads a BENCH_r*.json trajectory (``--dir``; the repo holds no records
until the cell benchmark writes them) and compares every headline metric's
LATEST recorded value against the best value any EARLIER round recorded
for the same metric name, with a stated tolerance.  Exits loud (rc 1,
one line per regression) when the latest value is worse than
best-so-far by more than the tolerance; rc 0 with a summary JSON line
otherwise.

What counts as a headline metric (see BASELINE.md for meanings):

* ``parsed.value`` under its ``parsed.metric`` name (the round's
  headline figure — device and CPU legs are DIFFERENT metric names, so
  a round that ran without a device never "regresses" the device
  series),
* flat ``extras`` entries matching the latency families
  (``extend_block_*_ms``, ``prepare_*_ms``, ``filter_*_ms``,
  ``repair_*_ms``, ``transfer_overhead_ms``, ``glv_us_per_sig``,
  ``leopard_extension_only_ms``) — lower is better,
* nested ``prepare_then_process_*`` blocks: ``warm_speedup`` (HIGHER is
  better) and ``cold_ms``/``warm_ms`` (lower),
* nested ``extras.trace_summary`` per-phase ms (every ``*_ms`` figure
  under the ``prepare_proposal``/``process_proposal`` breakdowns —
  lower is better; the span counts are structure, not latency, and are
  skipped),
* ``extras.device_profile.device_occupancy_pct`` (HIGHER is better —
  falling occupancy at equal work means growing dispatch gaps),
* ``extras.das_serving``: every k-stamped ``*_samples_per_s`` figure and
  the ``warm_batch_vs_scalar_*_speedup`` (HIGHER is better — the
  serving plane's throughput trajectory),
* ``extras.multichip`` (the sharded mesh series): every warm ``*_ms``
  figure (lower is better; ``*_cold_ms`` compile walls are recorded but
  not watched — single-run XLA compile is host-load noise) and every
  ``*_blocks_per_s`` throughput (HIGHER is better).  Metric names are
  prefixed with the recording platform + mesh factoring AND carry the
  k/batch config, so a reduced virtual-CPU-mesh round, a full-size
  device round, and rounds on differently-provisioned chip counts can
  never cross-compare,
* ``extras.host_profile.sampler_overhead_pct`` — judged against an
  ABSOLUTE 2% ceiling on the latest round (the continuous-profiling
  cost contract: the sampler must stay under 2% of the leg wall it
  measures), never against best-so-far,
* ``extras.swarm`` (the light-client swarm legs): every per-tier
  ``*_p50_ms``/``*_p99_ms`` figure under the ``honest``/``hostile_mix``
  leg blocks (lower is better; names carry the k stamp from bench so
  different square sizes never cross-compare), and the honest-crowd
  ``fairness_index`` — judged against an ABSOLUTE 0.8 FLOOR on the
  latest round only (the QoS fairness contract: an honest crowd must
  see a near-uniform served distribution; a lucky 0.99 round must not
  turn every later 0.95 into a failure, so no best-so-far trend),
* ``extras.tx_ingress`` (the batched admission plane): every
  ``*_tx_per_s`` sustained-throughput figure and the FilterTxs
  ``*_speedup`` (HIGHER is better), plus the ``*_ms`` /
  ``*_us_per_sig`` latency figures (lower).  Names carry the batch
  size and cache regime (``check_b512_cold_tx_per_s``), so cold and
  warm drains at different batch sizes never cross-compare.

Rounds whose ``parsed`` is null (a crashed bench run) contribute no
values; they are counted and reported, never treated as zeros.

Usage:
    python tools/bench_check.py [--dir REPO] [--tolerance 0.25] [files...]
"""

import argparse
import glob
import json
import os
import re
import sys

LOWER_IS_BETTER = tuple(
    re.compile(p)
    for p in (
        r"^extend_block_.*_ms$",
        r"^prepare_.*_ms$",
        r"^filter_.*_ms$",
        r"^repair_.*_ms$",
        r"^transfer_overhead_ms$",
        r"^glv_us_per_sig$",
        r"^leopard_extension_only_ms$",
    )
)

# metric name -> True when HIGHER values are better
_HIGHER = {"warm_speedup"}

# per-metric tolerance overrides: occupancy is a busy/wall ratio of a
# short dispatch loop — inherently noisier than the latency medians the
# default 25% was calibrated for, so it gets a documented wider band
# instead of silently regressing the shared tolerance
TOLERANCE_OVERRIDE = {
    "device_profile.device_occupancy_pct": 0.60,
    # lint wall time is host-load-noisy single-run wall clock; 2x over
    # best-so-far is the alarm, not the 25% latency band
    "lint_stats.wall_ms": 1.00,
}

# metrics judged against an ABSOLUTE ceiling on the LATEST round only
# (no best-so-far comparison: the host sampler's overhead budget is a
# contract — "continuous profiling costs under 2% of the work it
# measures" — not a trajectory to trend)
ABSOLUTE_CEILING = {
    "host_profile.sampler_overhead_pct": 2.0,
}

# metrics judged against an ABSOLUTE floor on the LATEST round only —
# the mirror of ABSOLUTE_CEILING for contract metrics where LOW is the
# failure: the swarm's honest-crowd Jain fairness index must stay at or
# above the serving plane's DAS_FAIRNESS_FLOOR (the same 0.8 the stock
# das_fairness_floor alert rule watches server-side)
ABSOLUTE_FLOOR = {
    "swarm.fairness_index": 0.8,
}


def _flat_headlines(parsed: dict):
    """Yield (metric, value, higher_is_better) from one round's parsed
    bench document."""
    metric = parsed.get("metric")
    value = parsed.get("value")
    if isinstance(metric, str) and isinstance(value, (int, float)):
        yield metric, float(value), False
    extras = parsed.get("extras") or {}
    for key, val in extras.items():
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            if any(p.match(key) for p in LOWER_IS_BETTER):
                yield key, float(val), False
        elif isinstance(val, dict) and key.startswith("prepare_then_process"):
            for sub in ("warm_speedup", "cold_ms", "warm_ms"):
                v = val.get(sub)
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    yield f"{key}.{sub}", float(v), sub in _HIGHER
        elif key == "trace_summary" and isinstance(val, dict):
            # per-phase ms of the traced prepare->process round: every
            # *_ms figure in the two breakdowns is a latency headline
            for block in ("prepare_proposal", "process_proposal"):
                phases = val.get(block)
                if not isinstance(phases, dict):
                    continue
                for pk, pv in phases.items():
                    if (
                        pk.endswith("_ms")
                        and isinstance(pv, (int, float))
                        and not isinstance(pv, bool)
                    ):
                        yield f"trace_summary.{block}.{pk}", float(pv), False
        elif key == "critpath" and isinstance(val, dict):
            # critical-path attribution of the traced lifecycle: the
            # path wall, the unattributed gap and the testnode-leg
            # propagation delay are all latency series (names carry the
            # k stamp, so square sizes never cross-compare)
            for mk, mv in sorted(val.items()):
                if (
                    "_ms_k" in mk
                    and isinstance(mv, (int, float))
                    and not isinstance(mv, bool)
                ):
                    yield f"critpath.{mk}", float(mv), False
        elif key == "multichip" and isinstance(val, dict):
            # platform AND mesh factoring in the name: the same k on a
            # different chip count is a different series (a 1x4 round
            # must not alarm against a 1x8 best-so-far)
            platform = val.get("platform", "unknown")
            series = f"multichip.{platform}.{val.get('mesh', 'nomesh')}"
            for mk, mv in sorted(val.items()):
                if isinstance(mv, bool) or not isinstance(mv, (int, float)):
                    continue
                if mk.endswith("_blocks_per_s"):
                    yield f"{series}.{mk}", float(mv), True
                elif mk.endswith("_ms") and not mk.endswith("_cold_ms"):
                    yield f"{series}.{mk}", float(mv), False
        elif key == "das_serving" and isinstance(val, dict):
            # the serving plane's throughput series: samples/sec figures
            # and the warm-batch-vs-scalar speedup are HIGHER-is-better;
            # names carry the k stamp, so rounds at different square
            # sizes never cross-compare
            for mk, mv in sorted(val.items()):
                if isinstance(mv, bool) or not isinstance(mv, (int, float)):
                    continue
                if mk.endswith("_samples_per_s") or mk.endswith("_speedup"):
                    yield f"das_serving.{mk}", float(mv), True
        elif key == "device_profile" and isinstance(val, dict):
            occ = val.get("device_occupancy_pct")
            if isinstance(occ, (int, float)) and not isinstance(occ, bool):
                yield "device_profile.device_occupancy_pct", float(occ), True
        elif key == "host_profile" and isinstance(val, dict):
            # continuous-profiling cost: judged against the 2% absolute
            # ceiling (ABSOLUTE_CEILING), not best-so-far — a lucky
            # 0.1% round must not turn every later 0.5% into a failure
            ov = val.get("sampler_overhead_pct")
            if isinstance(ov, (int, float)) and not isinstance(ov, bool):
                yield "host_profile.sampler_overhead_pct", float(ov), False
        elif key == "transfer_accounting" and isinstance(val, dict):
            # the device-resident plane's transfer ledger: residual
            # bytes over the wire and the two phase walls are watched
            # like compute regressions (a new hot-path D2H shows up as
            # a byte jump before it shows up as latency); the k stamp
            # keeps host-fallback tiny-k rounds off the full-k series
            kk = val.get("k", "nok")
            for mk in (
                "extend_cold_ms",
                "proof_serve_warm_ms",
                "extend_d2h_bytes",
                "proof_serve_d2h_bytes",
                "total_d2h_bytes",
                "total_h2d_bytes",
            ):
                mv = val.get(mk)
                if isinstance(mv, (int, float)) and not isinstance(mv, bool):
                    yield f"transfer_accounting.k{kk}.{mk}", float(mv), False
        elif key == "swarm" and isinstance(val, dict):
            # the light-client swarm series: per-tier latency tails
            # under each leg (k-stamped by bench — a k=4 honest crowd
            # never alarms against a k=8 best) plus the honest-crowd
            # fairness index, which check() judges against the 0.8
            # ABSOLUTE_FLOOR instead of best-so-far
            fi = val.get("fairness_index")
            if isinstance(fi, (int, float)) and not isinstance(fi, bool):
                yield "swarm.fairness_index", float(fi), True
            for leg in ("honest", "hostile_mix"):
                block = val.get(leg)
                if not isinstance(block, dict):
                    continue
                for mk, mv in sorted(block.items()):
                    if isinstance(mv, bool) or not isinstance(
                        mv, (int, float)
                    ):
                        continue
                    # tier percentile keys carry the k stamp between the
                    # tag and the unit: light_p99_k4_ms
                    if mk.endswith("_ms") and (
                        "_p50_" in mk or "_p99_" in mk
                    ):
                        yield f"swarm.{leg}.{mk}", float(mv), False
        elif key == "tx_ingress" and isinstance(val, dict):
            # the batched admission plane: sustained tx/s (HIGHER) at
            # each batch size/regime, the FilterTxs speedup over the
            # sequential leg (HIGHER), and the latency/µs-per-sig
            # figures (lower).  Names carry batch size and regime, so
            # a cold batch-1 round never cross-compares a warm batch-512
            for mk, mv in sorted(val.items()):
                if isinstance(mv, bool) or not isinstance(mv, (int, float)):
                    continue
                if mk.endswith("_tx_per_s") or mk.endswith("_speedup"):
                    yield f"tx_ingress.{mk}", float(mv), True
                elif mk.endswith("_ms") or mk.endswith("_us_per_sig"):
                    yield f"tx_ingress.{mk}", float(mv), False
        elif key == "lint_stats" and isinstance(val, dict):
            # celint whole-tree wall time: the R6 whole-program pass is
            # the only tier-1 gate whose cost grows with the TREE, so
            # its drift is watched like a latency leg
            wall = val.get("wall_ms")
            if isinstance(wall, (int, float)) and not isinstance(wall, bool):
                yield "lint_stats.wall_ms", float(wall), False


def load_trajectory(paths):
    """[(round_name, {metric: (value, higher_better)})] in round order,
    plus the list of rounds whose bench run produced no parse."""
    rounds, unparsed = [], []
    for path in paths:
        name = os.path.splitext(os.path.basename(path))[0]
        with open(path) as f:
            doc = json.load(f)
        parsed = doc.get("parsed")
        if not isinstance(parsed, dict):
            unparsed.append(name)
            continue
        metrics = {}
        for metric, value, higher in _flat_headlines(parsed):
            metrics[metric] = (value, higher)
        rounds.append((name, metrics))
    return rounds, unparsed


def check(rounds, tolerance: float):
    """Compare each metric's last recorded value vs its best-so-far.
    Returns (regressions, series) where series maps metric ->
    {"best", "best_round", "last", "last_round", "ratio"}."""
    series = {}
    for rnd, metrics in rounds:
        for metric, (value, higher) in metrics.items():
            series.setdefault(metric, []).append((rnd, value, higher))
    regressions = []
    summary = {}
    for metric, points in sorted(series.items()):
        *earlier, (last_round, last, higher) = points
        ceiling = ABSOLUTE_CEILING.get(metric)
        if ceiling is not None:
            # absolute-budget metric: the latest round alone decides
            summary[metric] = {
                "last": last, "last_round": last_round,
                "ceiling": ceiling,
                "ratio": round(last / ceiling, 3) if ceiling else 1.0,
            }
            if last > ceiling:
                regressions.append(
                    {
                        "metric": metric,
                        "direction": "ceiling",
                        "best": ceiling,
                        "best_round": "(absolute ceiling)",
                        "last": last,
                        "last_round": last_round,
                        "ratio": round(last / ceiling, 3),
                        "tolerance": 0.0,
                    }
                )
            continue
        floor = ABSOLUTE_FLOOR.get(metric)
        if floor is not None:
            # absolute-floor metric: the latest round alone decides —
            # the symmetric twin of the ceiling branch above, alarming
            # when the contract value FALLS BELOW the floor
            ratio = round(last / floor, 3) if floor else 1.0
            summary[metric] = {
                "last": last, "last_round": last_round,
                "floor": floor, "ratio": ratio,
            }
            if last < floor:
                regressions.append(
                    {
                        "metric": metric,
                        "direction": "floor",
                        "best": floor,
                        "best_round": "(absolute floor)",
                        "last": last,
                        "last_round": last_round,
                        "ratio": ratio,
                        "tolerance": 0.0,
                    }
                )
            continue
        if not earlier:
            summary[metric] = {
                "last": last, "last_round": last_round,
                "best": last, "best_round": last_round, "ratio": 1.0,
            }
            continue
        values = [v for _, v, _ in earlier]
        tol = TOLERANCE_OVERRIDE.get(metric, tolerance)
        if higher:
            best_i = max(range(len(values)), key=values.__getitem__)
            best = values[best_i]
            # a HIGHER metric regresses when the latest falls below
            # best * (1 - tolerance)
            bad = last < best * (1.0 - tol)
            ratio = (last / best) if best else 1.0
        else:
            best_i = min(range(len(values)), key=values.__getitem__)
            best = values[best_i]
            bad = last > best * (1.0 + tol)
            ratio = (last / best) if best else 1.0
        summary[metric] = {
            "last": last, "last_round": last_round,
            "best": best, "best_round": earlier[best_i][0],
            "ratio": round(ratio, 3),
        }
        if bad:
            regressions.append(
                {
                    "metric": metric,
                    "direction": "higher" if higher else "lower",
                    "best": best,
                    "best_round": earlier[best_i][0],
                    "last": last,
                    "last_round": last_round,
                    "ratio": round(ratio, 3),
                    "tolerance": tol,
                }
            )
    return regressions, summary


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="bench_check")
    p.add_argument("files", nargs="*",
                   help="BENCH json files in round order (default: "
                        "--dir/BENCH_r*.json sorted)")
    p.add_argument("--dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    p.add_argument("--tolerance", type=float, default=0.25,
                   help="allowed fractional slack vs best-so-far "
                        "(default 0.25 = 25%%)")
    args = p.parse_args(argv)
    paths = args.files or sorted(
        glob.glob(os.path.join(args.dir, "BENCH_r*.json"))
    )
    if len(paths) < 2:
        print(f"bench_check: need >= 2 rounds, found {len(paths)}",
              file=sys.stderr)
        return 2
    rounds, unparsed = load_trajectory(paths)
    if len(rounds) < 2:
        print(
            f"bench_check: only {len(rounds)} parseable rounds "
            f"({len(unparsed)} unparsed: {unparsed})",
            file=sys.stderr,
        )
        return 2
    regressions, summary = check(rounds, args.tolerance)
    if regressions:
        for r in regressions:
            print(
                "bench_check: REGRESSION %s: %s=%s (%s) vs best %s (%s), "
                "ratio %s > tolerance %s"
                % (
                    r["direction"], r["metric"], r["last"], r["last_round"],
                    r["best"], r["best_round"], r["ratio"], r["tolerance"],
                ),
                file=sys.stderr,
            )
        return 1
    print(
        json.dumps(
            {
                "bench_check": "ok",
                "rounds": [r for r, _ in rounds],
                "unparsed_rounds": unparsed,
                "metrics_checked": len(summary),
                "tolerance": args.tolerance,
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
