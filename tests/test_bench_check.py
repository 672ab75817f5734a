"""The bench regression watchdog (tools/bench_check.py): a synthetic
regressed round must fail loudly, and the comparison semantics
(per-metric series, best-so-far, direction, tolerance, unparsed rounds)
are pinned here on synthetic rounds."""

import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

_spec = importlib.util.spec_from_file_location(
    "bench_check", REPO / "tools" / "bench_check.py"
)
bench_check = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_check)


def _round(n, metric=None, value=None, extras=None, parsed=True):
    doc = {"n": n, "cmd": "bench", "rc": 0, "tail": ""}
    if not parsed:
        doc["parsed"] = None
    else:
        doc["parsed"] = {
            "metric": metric or "extend_block_128x128_p50_device_ms",
            "value": value if value is not None else 10.0,
            "unit": "ms",
            "extras": extras or {},
        }
    return doc


def _write_rounds(tmp_path, rounds):
    for i, doc in enumerate(rounds, start=1):
        (tmp_path / f"BENCH_r{i:02d}.json").write_text(json.dumps(doc))


def test_synthetic_regression_fails_loud(tmp_path):
    """Acceptance: a regressed round must exit non-zero and NAME the
    regressed metric."""
    _write_rounds(tmp_path, [
        _round(1, value=10.5, extras={"filter_512_pfb_ms": 153.5}),
        _round(2, value=8.4, extras={"filter_512_pfb_ms": 152.7}),
        _round(3, parsed=False),  # a crashed round contributes nothing
        _round(4, value=8.6, extras={"filter_512_pfb_ms": 83.3}),
        _round(
            5,
            value=40.0,  # best so far is 8.4 ms
            extras={"filter_512_pfb_ms": 500.0},  # best so far 83.3 ms
        ),
    ])
    out = subprocess.run(
        [sys.executable, "tools/bench_check.py", "--dir", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert out.returncode == 1
    assert "REGRESSION" in out.stderr
    assert "extend_block_128x128_p50_device_ms" in out.stderr
    assert "filter_512_pfb_ms" in out.stderr


def test_lower_is_better_tolerance_boundary(tmp_path):
    _write_rounds(tmp_path, [
        _round(1, value=10.0),
        _round(2, value=12.4),  # within the 25% tolerance of best=10
    ])
    assert bench_check.main(["--dir", str(tmp_path)]) == 0
    _write_rounds(tmp_path, [
        _round(1, value=10.0),
        _round(2, value=12.6),  # past the tolerance
    ])
    assert bench_check.main(["--dir", str(tmp_path)]) == 1


def test_warm_speedup_higher_is_better(tmp_path, capsys):
    extras_good = {"prepare_then_process_128tx_ms": {
        "cold_ms": 300.0, "warm_ms": 80.0, "warm_speedup": 4.0}}
    extras_bad = {"prepare_then_process_128tx_ms": {
        "cold_ms": 300.0, "warm_ms": 290.0, "warm_speedup": 1.05}}
    _write_rounds(tmp_path, [
        _round(1, extras=extras_good),
        _round(2, extras=extras_bad),
    ])
    assert bench_check.main(["--dir", str(tmp_path)]) == 1
    err = capsys.readouterr().err
    assert "warm_speedup" in err and "higher" in err
    # an IMPROVED speedup passes
    _write_rounds(tmp_path, [
        _round(1, extras=extras_bad),
        _round(2, extras=extras_good),
    ])
    assert bench_check.main(["--dir", str(tmp_path)]) == 0


def test_multichip_series_watched(tmp_path, capsys):
    """extras.multichip: warm _ms figures regress lower-is-better,
    blocks_per_s higher-is-better, cold compile walls are NOT watched,
    and the platform prefix keeps cpu/device rounds apart."""
    good = {"multichip": {
        "platform": "cpu", "mesh": "2x4", "k": 32, "batch": 8,
        "sharded_extend_32_ms": 200.0,
        "sharded_extend_32_cold_ms": 60000.0,
        "batched_8x32_blocks_per_s": 8.0,
    }}
    bad = {"multichip": {
        "platform": "cpu", "mesh": "2x4", "k": 32, "batch": 8,
        "sharded_extend_32_ms": 900.0,        # regressed (lower better)
        "sharded_extend_32_cold_ms": 1.0,      # ignored either way
        "batched_8x32_blocks_per_s": 2.0,      # regressed (higher better)
    }}
    _write_rounds(tmp_path, [_round(1, extras=good), _round(2, extras=bad)])
    assert bench_check.main(["--dir", str(tmp_path)]) == 1
    err = capsys.readouterr().err
    assert "multichip.cpu.2x4.sharded_extend_32_ms" in err
    assert "multichip.cpu.2x4.batched_8x32_blocks_per_s" in err
    assert "cold_ms" not in err
    # a platform switch is a NEW series, never a regression
    dev = {"multichip": {
        "platform": "tpu", "mesh": "1x8", "k": 128, "batch": 8,
        "sharded_extend_128_ms": 5.0,
        "batched_8x128_blocks_per_s": 400.0,
    }}
    _write_rounds(tmp_path, [_round(1, extras=good), _round(2, extras=dev)])
    assert bench_check.main(["--dir", str(tmp_path)]) == 0
    # ... and so is a mesh-factoring switch at the same platform and k
    # (fewer chips are legitimately slower, not a regression)
    refit = {"multichip": {
        "platform": "cpu", "mesh": "1x2", "k": 32, "batch": 8,
        "sharded_extend_32_ms": 900.0,
        "batched_8x32_blocks_per_s": 2.0,
    }}
    _write_rounds(tmp_path, [_round(1, extras=good), _round(2, extras=refit)])
    assert bench_check.main(["--dir", str(tmp_path)]) == 0


def test_critpath_series_watched(tmp_path, capsys):
    """extras.critpath: the k-stamped critical-path figures are watched
    lower-is-better; a regressed round fails and NAMES the series."""
    extras_good = {"critpath": {
        "square": 128,
        "critical_path_ms_k128": 40.0,
        "unattributed_gap_ms_k128": 2.0,
        "propagation_delay_ms_k128": 0.5,
        "clock_skew_clamped": 0,
    }}
    extras_bad = {"critpath": {
        "square": 128,
        "critical_path_ms_k128": 120.0,  # 3x the best: past tolerance
        "unattributed_gap_ms_k128": 2.0,
        "propagation_delay_ms_k128": 0.5,
        "clock_skew_clamped": 0,
    }}
    _write_rounds(tmp_path, [
        _round(1, extras=extras_good),
        _round(2, extras=extras_bad),
    ])
    assert bench_check.main(["--dir", str(tmp_path)]) == 1
    err = capsys.readouterr().err
    assert "critpath.critical_path_ms_k128" in err
    # steady figures pass; the non-ms clock_skew_clamped is NOT a series
    _write_rounds(tmp_path, [
        _round(1, extras=extras_good),
        _round(2, extras={"critpath": dict(
            extras_good["critpath"], clock_skew_clamped=5)}),
    ])
    assert bench_check.main(["--dir", str(tmp_path)]) == 0


def test_unparsed_rounds_are_skipped_not_zeroed(tmp_path):
    _write_rounds(tmp_path, [
        _round(1, value=10.0),
        _round(2, parsed=False),  # crashed bench run
        _round(3, value=9.0),
    ])
    assert bench_check.main(["--dir", str(tmp_path)]) == 0


def test_different_metric_names_never_cross_compare(tmp_path):
    """A device round followed by a CPU-leg round (different headline
    metric names) is NOT a regression — the r05 situation."""
    _write_rounds(tmp_path, [
        _round(1, metric="extend_block_128x128_p50_device_ms", value=8.4),
        _round(2, metric="extend_block_128x128_leopard_cpu_ms", value=127.5),
    ])
    assert bench_check.main(["--dir", str(tmp_path)]) == 0


def test_needs_two_parseable_rounds(tmp_path):
    _write_rounds(tmp_path, [_round(1), _round(2, parsed=False)])
    assert bench_check.main(["--dir", str(tmp_path)]) == 2


def test_host_profile_overhead_absolute_ceiling(tmp_path, capsys):
    """The sampler-overhead budget is an ABSOLUTE 2% ceiling on the
    latest round — never a best-so-far comparison (a lucky 0.1% round
    must not make every later 0.5% round a failure)."""
    ok = {"host_profile": {"sampler_overhead_pct": 0.1}}
    still_ok = {"host_profile": {"sampler_overhead_pct": 1.9}}
    bad = {"host_profile": {"sampler_overhead_pct": 2.5}}
    # 0.1% -> 1.9% is a 19x jump but UNDER the ceiling: passes
    _write_rounds(tmp_path, [_round(1, extras=ok), _round(2, extras=still_ok)])
    assert bench_check.main(["--dir", str(tmp_path)]) == 0
    # over the ceiling fails loudly and names the metric
    _write_rounds(tmp_path, [_round(1, extras=ok), _round(2, extras=bad)])
    assert bench_check.main(["--dir", str(tmp_path)]) == 1
    err = capsys.readouterr().err
    assert "host_profile.sampler_overhead_pct" in err
    assert "ceiling" in err
    # a single round over the ceiling still fails (no baseline needed)
    _write_rounds(tmp_path, [_round(1), _round(2, extras=bad)])
    assert bench_check.main(["--dir", str(tmp_path)]) == 1


def test_swarm_fairness_absolute_floor_and_tier_p99(tmp_path, capsys):
    """extras.swarm: the honest-crowd fairness index is judged against
    the ABSOLUTE 0.8 floor on the latest round only (the mirror of the
    sampler-overhead ceiling — a lucky 0.99 round must not fail every
    later 0.95), and the k-stamped per-tier p99 figures regress
    lower-is-better like any latency series."""
    good = {"swarm": {
        "k": 4, "fairness_index": 0.97,
        "honest": {"light_p50_k4_ms": 3.0, "light_p99_k4_ms": 20.0,
                   "samples_per_s": 4000.0},
        "hostile_mix": {"light_p99_k4_ms": 30.0,
                        "hostile_p99_k4_ms": 90.0},
    }}
    still_good = {"swarm": {
        "k": 4, "fairness_index": 0.81,  # far below best 0.97, over floor
        "honest": {"light_p50_k4_ms": 3.1, "light_p99_k4_ms": 21.0},
        "hostile_mix": {"light_p99_k4_ms": 31.0},
    }}
    unfair = {"swarm": {
        "k": 4, "fairness_index": 0.55,  # below the 0.8 floor
        "honest": {"light_p99_k4_ms": 20.0},
    }}
    slow = {"swarm": {
        "k": 4, "fairness_index": 0.97,
        "honest": {"light_p99_k4_ms": 200.0},  # 10x the best p99
    }}
    # a big fairness DROP that stays over the floor passes (latest-only)
    _write_rounds(tmp_path, [_round(1, extras=good),
                             _round(2, extras=still_good)])
    assert bench_check.main(["--dir", str(tmp_path)]) == 0
    # under the floor fails loudly, names the metric and the direction
    _write_rounds(tmp_path, [_round(1, extras=good),
                             _round(2, extras=unfair)])
    assert bench_check.main(["--dir", str(tmp_path)]) == 1
    err = capsys.readouterr().err
    assert "swarm.fairness_index" in err
    assert "floor" in err
    # only the LATEST round is judged: an old under-floor round with a
    # recovered latest passes
    _write_rounds(tmp_path, [_round(1, extras=unfair),
                             _round(2, extras=good)])
    assert bench_check.main(["--dir", str(tmp_path)]) == 0
    # the per-tier p99 series regresses like any latency headline
    _write_rounds(tmp_path, [_round(1, extras=good),
                             _round(2, extras=slow)])
    assert bench_check.main(["--dir", str(tmp_path)]) == 1
    err = capsys.readouterr().err
    assert "swarm.honest.light_p99_k4_ms" in err
    # throughput/aux figures under the legs are recorded, not watched
    assert "samples_per_s" not in err


def test_check_series_semantics():
    rounds = [
        ("r1", {"m_ms": (10.0, False), "only_r1_ms": (5.0, False)}),
        ("r2", {"m_ms": (8.0, False)}),
        ("r3", {"m_ms": (8.5, False)}),
    ]
    regressions, summary = bench_check.check(rounds, tolerance=0.25)
    assert regressions == []
    assert summary["m_ms"]["best"] == 8.0
    assert summary["m_ms"]["best_round"] == "r2"
    assert summary["m_ms"]["last"] == 8.5
    # single-occurrence metrics have no baseline to regress against
    assert summary["only_r1_ms"]["ratio"] == 1.0
    regressions, _ = bench_check.check(
        [("r1", {"m_ms": (8.0, False)}), ("r2", {"m_ms": (11.0, False)})],
        tolerance=0.25,
    )
    assert len(regressions) == 1
    assert regressions[0]["metric"] == "m_ms"
    assert regressions[0]["ratio"] == pytest.approx(1.375)
