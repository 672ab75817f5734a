"""Test configuration: run all tests on a virtual 8-device CPU mesh.

The tests run on the CPU (``JAX_PLATFORMS=cpu``); sharding correctness
is validated on XLA's host platform with 8 virtual devices.  The chip is
reached only through ``chip_smoke.py`` (see README.md).  The env is set
before jax is imported, so spawned subprocesses inherit it too.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# no persistent compile cache in tier-1, for this process or the nodes it
# spawns: the run stays hermetic (nothing written into the checkout, no
# executables shared between xdist workers)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

assert len(jax.devices()) == 8, (
    f"tests expect 8 virtual CPU devices, got {jax.devices()}"
)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running process-level e2e tests"
    )


# ---------------------------------------------------------------------------
# tier-1 wall-time budget (tools/t1_budget.py)
#
# The 870 s tier-1 run TRUNCATES (memory/tier1-timeout-budget): every
# second a test burns is a test at the tail that never runs.  The
# session reports its 10 slowest tests at the end, and writes the full
# per-test duration table to a JSON file tools/t1_budget.py judges
# (loud failure when any single non-slow test exceeds its 30 s budget).
# Set CELESTIA_TPU_T1_DURATIONS to move the file; empty default lands
# it in the system tempdir.
# ---------------------------------------------------------------------------

_t1_by_test: dict = {}
_t1_durations = []  # same entries, in completion order (tests import this)


def _t1_durations_path() -> str:
    import tempfile

    return os.environ.get("CELESTIA_TPU_T1_DURATIONS", "").strip() or (
        os.path.join(tempfile.gettempdir(), "celestia_tpu_t1_durations.json")
    )


def pytest_runtest_logreport(report):
    # SUM setup + call + teardown: a 100 s fixture burns the tier-1
    # budget exactly like a 100 s test body, and recording only the
    # call phase would hide it from the guard
    entry = _t1_by_test.get(report.nodeid)
    if entry is None:
        entry = {
            "test": report.nodeid,
            "duration_s": 0.0,
            "slow": "slow" in getattr(report, "keywords", {}),
            "outcome": report.outcome,
        }
        _t1_by_test[report.nodeid] = entry
        _t1_durations.append(entry)
    entry["duration_s"] = round(
        entry["duration_s"] + float(report.duration), 3
    )
    if report.when == "call":
        entry["outcome"] = report.outcome


def pytest_terminal_summary(terminalreporter):
    if not _t1_durations:
        return
    top = sorted(
        _t1_durations, key=lambda e: -e["duration_s"]
    )[:10]
    terminalreporter.write_line("")
    terminalreporter.write_line(
        "tier-1 wall budget — 10 slowest tests "
        "(tools/t1_budget.py fails non-slow tests over 30 s):"
    )
    for e in top:
        mark = " [slow]" if e["slow"] else ""
        terminalreporter.write_line(
            f"  {e['duration_s']:8.2f}s  {e['test']}{mark}"
        )
    import json as _json

    try:
        with open(_t1_durations_path(), "w") as f:
            _json.dump(
                {"durations": sorted(
                    _t1_durations, key=lambda e: -e["duration_s"]
                )},
                f,
            )
    except OSError as e:
        terminalreporter.write_line(f"  (durations file not written: {e})")


import pytest  # noqa: E402


@pytest.fixture
def chaos():
    """The chaos harness handle: yields celestia_tpu.utils.faults with a
    clean slate and GUARANTEES teardown — every armed fault point is
    disarmed, stats are reset, and a native poison pin left by a
    degradation test is force-cleared so later tests see the real
    library.  Arm points with ``chaos.arm(...)`` (seeded; same seed =>
    same schedule) and reproduce any chaos failure by re-arming with the
    seed the failing test printed.

    When the lock-order shadow checker's factories are installed
    (CELESTIA_TPU_LOCKWATCH runs — `make lockwatch`), the fixture also
    arms recording for the test body, so chaos scenarios execute with
    lock-order observation on."""
    from celestia_tpu.utils import faults, lockwatch, native

    faults.disarm()
    faults.reset_stats()
    rearm = lockwatch.installed() and not lockwatch.armed()
    if rearm:
        lockwatch.arm()
    yield faults
    if rearm:
        lockwatch.disarm()
    faults.disarm()
    faults.reset_stats()
    if native.poisoned() is not None:
        native.clear_poison(force=True)


@pytest.fixture(autouse=True, scope="session")
def _lockwatch_gate():
    """`make lockwatch` contract: when the shadow checker was armed from
    the environment, the WHOLE session fails if any lock-order inversion
    was observed — with both acquisition stacks in the failure."""
    yield
    if not os.environ.get("CELESTIA_TPU_LOCKWATCH", "").strip():
        return
    from celestia_tpu.utils import lockwatch

    print("\n" + lockwatch.report())
    if lockwatch.inversions():
        pytest.fail(
            "lock-order inversions observed at runtime:\n"
            + lockwatch.report(),
            pytrace=False,
        )
    # static cross-check: an observed order that CONTRADICTS the derived
    # lock hierarchy fails even when no thread raced the reverse order
    from celestia_tpu.lint.lockorder import runtime_crosscheck

    problems = runtime_crosscheck(lockwatch.observed_pairs())
    if problems:
        pytest.fail(
            "runtime lock orders contradict the static lock graph:\n"
            + "\n".join(problems),
            pytrace=False,
        )


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Drop compiled XLA executables at module boundaries.

    jaxlib's CPU plugin segfaults inside backend_compile_and_load once
    enough executables accumulate in one long-lived process (observed at
    ~65% of a full-suite run after ADR-012 doubled the per-size program
    variants).  Clearing per module keeps the live set small; the few extra
    small-k recompiles are seconds each."""
    yield
    jax.clear_caches()


@pytest.fixture(autouse=True, scope="module")
def _reset_degradation_state_per_module():
    """Start every module with no recorded degradation and no poison pin.

    Degradations and the device-plane / mesh / native poison pins are
    process-global and one-way by contract, so a module that trips one
    (test_device_plane.py's fault legs) would otherwise leave it set for
    every later module in the same xdist worker: the stock `degradations`
    alert then fires in an unrelated node and `alerts_firing == []`
    fails, depending only on how --dist loadfile assigned the files."""
    from celestia_tpu.da import device_plane
    from celestia_tpu.parallel import mesh
    from celestia_tpu.utils import faults, native

    faults.reset_stats()
    for plane in (device_plane, mesh, native):
        if plane.poisoned() is not None:
            plane.clear_poison(force=True)
    yield
