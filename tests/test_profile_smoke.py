"""Tier-1 wiring of `make profile-smoke` (tools/profile_smoke.py) —
the same assertions the gate's single-process leg makes, run in-process
at tiny k (the same way the trace-smoke assertions live in
tests/test_tracing.py): one traced block must yield a merged HOST +
per-chip DEVICE-track Chrome trace, an XLA cost row for the fused
kernel, a >= 2-snapshot time-series dump with computed rates, one
deliberately-tripped alert rule firing, and a line-parse-valid
exposition carrying the new device/alert sections."""

import json
import time

import pytest

from celestia_tpu.utils import devprof, tracing


@pytest.fixture
def traced_jax_node():
    """A tiny funded TestNode whose extension is FORCED through the
    device plane (the leg the chip runs): without it the native fused
    pipeline would satisfy the square host-side and no device dispatch
    would happen on the CPU backend."""
    from celestia_tpu.da import device_plane, eds_cache
    from celestia_tpu.node.testnode import TestNode
    from celestia_tpu.utils.secp256k1 import PrivateKey

    tracing.enable(4)
    tracing.clear()
    devprof.reset()
    eds_cache.clear()
    with device_plane.forced("on"):
        key = PrivateKey.from_seed(b"test-profile-smoke")
        node = TestNode(funded_accounts=[(key, 10**12)], auto_produce=False)
        yield node, key
    tracing.disable()
    tracing.clear()
    devprof.reset()
    eds_cache.clear()


def _produce_send_block(node, key):
    from celestia_tpu.client.signer import Signer
    from celestia_tpu.state.tx import MsgSend

    signer = Signer(node, key)
    res = signer._broadcast(
        lambda: signer.sign_tx(
            [MsgSend(signer.address, b"\x33" * 20, 1000)]
        ).marshal()
    )
    assert res.code == 0, res.log
    node.produce_block()


def test_traced_block_has_host_and_device_tracks(traced_jax_node):
    node, key = traced_jax_node
    _produce_send_block(node, key)
    prep = [
        t for t in tracing.block_traces() if t.name == "prepare_proposal"
    ][-1]
    host = [s for s in prep.spans if s.cat != "device"]
    device = [s for s in prep.spans if s.cat == "device"]
    assert host and device, sorted({s.name for s in prep.spans})
    # the device span is the fused extend_levels dispatch, on a
    # synthetic per-chip track, parented under the block's extend leg
    assert any(s.name == "device.extend_levels" for s in device)
    for s in device:
        assert s.tid >= devprof.DEVICE_TID_BASE
        assert s.thread_name.startswith("device:")
    # merged doc: schema-valid, device track named for Perfetto
    dump = tracing.trace_dump()
    assert tracing.validate_chrome_trace(dump) == []
    thread_names = {
        ev["args"]["name"]
        for ev in dump["traceEvents"]
        if ev.get("ph") == "M" and ev.get("name") == "thread_name"
    }
    assert any(n.startswith("device:") for n in thread_names), thread_names
    # the XLA cost table recorded the fused kernel (compile_ms is ours
    # and always present; flops/bytes only where the platform answers);
    # the build runs on a background thread — join it first
    devprof.flush_compiles()
    prof = devprof.device_profile()
    assert "extend_levels" in prof["kernels"], prof["notes"]
    assert prof["kernels"]["extend_levels"]["compile_ms"] > 0.0
    assert prof["dispatches"].get("extend_levels", 0) >= 1


def test_timeseries_alert_and_exposition_over_live_node(traced_jax_node):
    from celestia_tpu.node.server import NodeService
    from celestia_tpu.utils import faults
    from celestia_tpu.utils import timeseries as ts_mod
    from celestia_tpu.utils.telemetry import validate_exposition

    node, key = traced_jax_node
    _produce_send_block(node, key)
    base = len(faults.fault_stats()["degradations"])
    series = ts_mod.TimeSeries(16)
    series.record(ts_mod.collect_node_sample(node))
    try:
        faults.record_degradation("test_profile_smoke", "deliberate trip")
        time.sleep(0.02)
        series.record(ts_mod.collect_node_sample(node))
        snapshots = series.samples()
        assert len(snapshots) >= 2
        rates = series.rates()
        assert "height" in rates
        json.loads(json.dumps({"snapshots": snapshots, "rates": rates}))
        engine = ts_mod.AlertEngine(
            [
                ts_mod.AlertRule(
                    "degradations_above_base", metric="degradations",
                    op=">", threshold=float(base), for_s=0.0,
                )
            ]
        )
        firing = engine.firing(series)
        assert [a["name"] for a in firing] == ["degradations_above_base"]
        # the served exposition carries the device + alert + trace-ring
        # sections and every line parses (join the background cost
        # build so the xla_compile_ms line is deterministically there)
        devprof.flush_compiles()
        service = NodeService(node)
        service.timeseries = series
        service.alert_engine = engine
        text = service.metrics_text()
        assert validate_exposition(text) == []
        assert 'celestia_tpu_xla_compile_ms{kernel="extend_levels"}' in text
        assert "celestia_tpu_trace_span_drops_total" in text
        assert (
            'celestia_tpu_alert_firing{rule="degradations_above_base"} 1'
            in text
        )
        assert "celestia_tpu_alerts_firing_total 1" in text
    finally:
        faults.reset_stats()
