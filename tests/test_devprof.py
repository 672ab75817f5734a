"""Device-observability plane (utils/devprof.py): dispatch bracketing,
the per-chip device track, XLA cost accounting — and above all the
CPU-ONLY DEGRADATION CONTRACT: ``memory_stats()`` returning None,
``cost_analysis()`` raising/absent on the platform, the profiler flag
set without a TPU — all must fold to telemetry notes, never exceptions
(the ISSUE-11 satellite this file pins)."""

import numpy as np
import pytest

from celestia_tpu.utils import devprof, tracing
from celestia_tpu.utils.telemetry import validate_exposition


@pytest.fixture(autouse=True)
def _clean_devprof():
    devprof.reset()
    yield
    devprof.reset()


@pytest.fixture
def tracer():
    tracing.enable(4)
    tracing.clear()
    yield
    tracing.disable()
    tracing.clear()


def test_disabled_dispatch_is_shared_noop():
    assert not devprof.active()
    d = devprof.dispatch("anything", k=1)
    assert d is devprof.NULL_DISPATCH
    sentinel = object()
    assert d.done(sentinel) is sentinel
    # note_compile is equally free when inactive
    devprof.note_compile("anything", None, ())
    assert devprof.device_profile()["kernels"] == {}


def test_collect_window_records_dispatch_and_cost():
    from celestia_tpu.ops import rs

    sq = np.random.default_rng(0).integers(0, 256, (2, 2, 512), dtype=np.uint8)
    with devprof.collect():
        np.asarray(rs.extend_square(sq))
        devprof.flush_compiles()  # the cost build runs on a daemon thread
        prof = devprof.device_profile()
    assert prof["dispatches"].get("rs_extend", 0) >= 1
    assert prof["device_busy_ms_total"] >= 0.0
    assert 0.0 <= prof["device_occupancy_pct"] <= 100.0
    # the cost row landed (XLA CPU answers cost_analysis for tiny
    # programs; if a platform cannot, the row simply lacks the field —
    # but compile_ms is OUR measurement and always present)
    assert "rs_extend" in prof["kernels"]
    assert prof["kernels"]["rs_extend"]["compile_ms"] > 0.0
    # leaving the collect window disarms the bracket again
    assert not devprof.active()


def test_device_track_span_inside_block_trace(tracer):
    from celestia_tpu.ops import rs

    sq = np.random.default_rng(1).integers(0, 256, (2, 2, 512), dtype=np.uint8)
    with tracing.block_span("prepare_proposal", height=7):
        np.asarray(rs.extend_square(sq))
    tr = tracing.block_traces()[-1]
    dev = [s for s in tr.spans if s.cat == "device"]
    assert dev, [s.name for s in tr.spans]
    s = dev[0]
    assert s.name == "device.rs_extend"
    assert s.tid >= devprof.DEVICE_TID_BASE
    assert s.thread_name.startswith("device:")
    assert "enqueue_ms" in s.args and s.args["enqueue_ms"] >= 0.0
    # the dump names the device track and stays schema-valid
    dump = tracing.trace_dump()
    assert tracing.validate_chrome_trace(dump) == []
    names = {
        ev["args"]["name"]
        for ev in dump["traceEvents"]
        if ev.get("ph") == "M" and ev.get("name") == "thread_name"
    }
    assert any(n.startswith("device:") for n in names), names


def test_launched_dispatch_charges_the_host_work_after_the_launch(tracer):
    """A program launched, then left to run under other host work before
    ``done``: busy time and the device span cover it from the launch's
    return, and ``enqueue_ms`` leaves the host work out."""
    import time

    import jax.numpy as jnp

    x = jnp.arange(8)
    (x + 1).block_until_ready()  # compiled outside the bracket
    with devprof.collect(), tracing.block_span("process_proposal", height=1):
        d = devprof.dispatch("overlapped", k=1)
        out = x + 1
        d.launched()
        time.sleep(0.05)  # the host's own work while the device runs
        d.done(out)
        prof = devprof.device_profile()
    assert prof["device_busy_ms_total"] >= 50.0, prof["device_busy_ms_total"]
    (span,) = [s for s in tracing.block_traces()[-1].spans if s.name == "device.overlapped"]
    assert span.duration_ms >= 50.0
    assert span.args["enqueue_ms"] < 50.0


class _NoneMemDevice:
    platform = "cpu"
    id = 0

    def memory_stats(self):
        return None


class _RaisingMemDevice:
    platform = "tpu"
    id = 0

    def memory_stats(self):
        raise RuntimeError("no memory stats on this platform")


def test_memory_stats_none_degrades_to_note():
    assert devprof._sample_memory_of(_NoneMemDevice()) is None
    assert devprof._sample_memory_of(_RaisingMemDevice()) is None
    notes = devprof.device_profile()["notes"]
    assert "memory_stats" in notes and notes["memory_stats"]["count"] == 2
    # a CPU backend's sample_memory is the same contract end to end
    out = devprof.sample_memory()
    assert out is None or isinstance(out, dict)


def test_memory_stats_real_dict_is_folded():
    class Dev:
        platform = "tpu"
        id = 3

        def memory_stats(self):
            return {
                "bytes_in_use": 100,
                "peak_bytes_in_use": 900,
                "bytes_limit": 1000,
            }

    out = devprof._sample_memory_of(Dev())
    assert out == {
        "bytes_in_use": 100,
        "peak_bytes_in_use": 900,
        "bytes_limit": 1000,
        # frac = CURRENT usage (alertable); peak_frac = lifetime
        # high-water mark (informational — jax never lowers it)
        "frac": 0.1,
        "peak_frac": 0.9,
    }
    assert devprof.device_profile()["mem"]["peak_frac"] == 0.9


class _LowerRaises:
    def lower(self, *args):
        raise NotImplementedError("AOT lowering unsupported here")


class _CostRaises:
    class _Compiled:
        def cost_analysis(self):
            raise NotImplementedError("cost_analysis absent on this platform")

        def memory_analysis(self):
            raise NotImplementedError("ditto")

    class _Lowered:
        def compile(self):
            return _CostRaises._Compiled()

    def lower(self, *args):
        return self._Lowered()


def test_cost_analysis_raising_degrades_to_note():
    with devprof.collect():
        devprof.note_compile("broken_lower", _LowerRaises(), ())
        devprof.note_compile("broken_cost", _CostRaises(), ())
        devprof.flush_compiles()
        prof = devprof.device_profile()
    # lowering failure: no row, a note
    assert "broken_lower" not in prof["kernels"]
    assert "compile.broken_lower" in prof["notes"]
    # cost failure AFTER a successful compile: the row keeps the
    # measured compile time, the gaps are notes
    assert prof["kernels"]["broken_cost"].keys() == {"compile_ms"}
    assert "cost_analysis" in prof["notes"]
    assert "memory_analysis" in prof["notes"]


def test_note_compile_dedups_per_shape():
    calls = []

    class Fn:
        class _Lowered:
            def compile(self):
                class C:
                    def cost_analysis(self):
                        return {"flops": 1.0}

                    def memory_analysis(self):
                        return None

                return C()

        def lower(self, *args):
            calls.append(args)
            return self._Lowered()

    fn = Fn()
    a = np.zeros((2, 2), dtype=np.uint8)
    with devprof.collect():
        devprof.note_compile("dedup", fn, (a,))
        devprof.note_compile("dedup", fn, (a,))  # same shape: skipped
        devprof.note_compile("dedup", fn, (np.zeros((4, 4), np.uint8),))
        devprof.flush_compiles()
    assert len(calls) == 2


def test_profiler_flag_without_tpu_never_raises(tmp_path):
    # the ISSUE-11 satellite: --device-profile on a CPU-only box must be
    # a note (or a working CPU capture), NEVER an exception
    ok = devprof.start_profiler(str(tmp_path / "prof"))
    stopped = devprof.stop_profiler()
    if ok:
        assert stopped == str(tmp_path / "prof")
    else:
        assert "profiler.start" in devprof.device_profile()["notes"]
        assert stopped is None
    # stop without start is a quiet no-op
    assert devprof.stop_profiler() is None


def test_exposition_lines_parse():
    with devprof.collect():
        devprof._sample_memory_of(_NoneMemDevice())  # a note
        from celestia_tpu.ops import sha256 as sha_ops

        sha_ops.sha256_np(np.zeros((3, 65), dtype=np.uint8))
        devprof.flush_compiles()
        lines = devprof.exposition_lines()
    assert lines, "device plane must always emit at least the notes total"
    assert validate_exposition("\n".join(lines) + "\n") == []
    text = "\n".join(lines)
    assert "celestia_tpu_devprof_notes_total" in text
    assert 'celestia_tpu_xla_compile_ms{kernel="sha256_batch"}' in text


def test_dispatch_bracketing_matches_byte_identity(tracer):
    """Profiling must never change bytes: the same extension with and
    without the bracket armed."""
    from celestia_tpu.ops import rs

    sq = np.random.default_rng(2).integers(0, 256, (2, 2, 512), dtype=np.uint8)
    with_track = np.asarray(rs.extend_square(sq))
    tracing.disable()
    without = np.asarray(rs.extend_square(sq))
    assert np.array_equal(with_track, without)


def test_multi_device_dispatch_records_every_chip():
    """dispatch(multi_device=True) on a sharded output charges the
    t1->t2 interval to EVERY chip the array spans (one busy entry per
    device — the cross-chip occupancy accounting the sharded extension
    path relies on); a non-sharded output degrades to the single-device
    bracket."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from celestia_tpu.parallel.sharded import make_mesh

    mesh = make_mesh(jax.devices()[:2], data=1, row=2)
    x = jax.device_put(
        jnp.zeros((4, 4), dtype=jnp.int32),
        NamedSharding(mesh, P("row", None)),
    )
    with devprof.collect():
        d = devprof.dispatch("multi_test", multi_device=True)
        d.done(x)
        prof = devprof.device_profile()
    busy = prof["device_busy_ms"]
    assert len(busy) == 2, busy
    assert prof["dispatches"]["multi_test"] == 1  # counted once, not per chip

    # single-device output under the same flag: one busy key
    y = jnp.zeros((4,), dtype=jnp.int32)
    with devprof.collect():
        d = devprof.dispatch("single_test", multi_device=True)
        d.done(y)
        prof = devprof.device_profile()
    assert len(prof["device_busy_ms"]) == 1
