"""The catch-up cell's readers, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Synthetic block traces and a reduced device dict pin each reader's
arithmetic: the roofline's byte count and divisor, the n=8, k=128
figure, and None on a program without the ``extend_batch`` trace.  A
tiny catch-up run with tracing on (4 CPU devices, windows of 4) checks
that the program leaves the spans the readers read.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [HERE, BENCH, os.path.join(BENCH, "metrics"), ROOT]

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import catchup_validate_ms  # noqa: E402
import catchup_warm_host_ms  # noqa: E402
import catchup_warm_ms  # noqa: E402
import run  # noqa: E402
import sharded_batch_device_ms  # noqa: E402
import sharded_batch_roofline  # noqa: E402
import tiny  # noqa: E402

NEW = ("catchup_warm_ms", "catchup_warm_host_ms", "catchup_validate_ms",
       "sharded_batch_device_ms", "sharded_batch_roofline")
PEAK = {"hbm_bytes_per_s": 819e9}


class Ctx:
    def __init__(self, blocks, device, k=128, chips=4):
        self.blocks, self.device, self.k, self.peak, self.chips = blocks, device, k, PEAK, chips


def _batch(t0, root_s, wait_s, warmed=8):
    return {"name": "extend_batch", "spans": [
        ("square_build", t0, t0 + 0.005, {"txs": 32}),
        ("extend.sharded.wait", t0 + 0.01, t0 + 0.01 + wait_s, {}),
        ("extend_batch", t0, t0 + root_s, {"k": 128, "blocks": 8, "pad": 0, "warmed": warmed}),
    ]}


def _block(t0, root_s):
    return {"name": "process_proposal", "spans": [
        ("extend", t0 + 0.001, t0 + 0.002, {"leg": "process", "eds_cache": "hit"}),
        ("process_proposal", t0, t0 + root_s, {"height": 1}),
    ]}


def _device(sharded_s, other_s, runs=2):
    return {"programs": {
        "_sharded_extend_and_roots": {"runs": runs, "device_s": dict(enumerate(sharded_s))},
        "concatenate": {"runs": runs, "device_s": dict(enumerate(other_s))},
    }}


def test_span_readers_per_window_call_and_per_block():
    blocks = [_batch(0.0, 0.100, 0.030), _block(1.0, 0.020), _block(2.0, 0.030),
              _batch(3.0, 0.120, 0.050), _block(4.0, 0.025)]
    ctx = Ctx(blocks, _device([0.04] * 4, [0.0] * 4))
    assert catchup_warm_ms.read(ctx) == pytest.approx(110.0)
    assert catchup_warm_host_ms.read(ctx) == pytest.approx(70.0)
    assert catchup_validate_ms.read(ctx) == pytest.approx(25.0)


def test_roofline_bytes_divisor_and_the_k128_window_figure():
    assert sharded_batch_roofline.least_bytes(8, 128) == 335_912_960
    least_s = 335_912_960 / (4 * 819e9)
    assert least_s == pytest.approx(102.5e-6, rel=1e-3)
    # busiest chip: chip 1, 0.084 + 0.010 s over 2 runs of the batched program
    device = _device([0.080, 0.084, 0.081, 0.080], [0.008, 0.010, 0.004, 0.004])
    ctx = Ctx([_batch(0.0, 0.3, 0.05)], device)
    assert sharded_batch_roofline.read(ctx) == pytest.approx(100.0 * least_s / 0.047)
    assert sharded_batch_device_ms.read(ctx) == pytest.approx(42.0)
    # the reassembly's time counts: moving work there cannot raise the share
    moved = _device([0.070, 0.074, 0.071, 0.070], [0.018, 0.020, 0.014, 0.014])
    assert sharded_batch_roofline.read(Ctx([_batch(0.0, 0.3, 0.05)], moved)) == pytest.approx(
        sharded_batch_roofline.read(ctx))
    # pads are not work: 6 real squares of a padded 8
    six = sharded_batch_roofline.read(Ctx([_batch(0.0, 0.3, 0.05, warmed=6)], device))
    assert six == pytest.approx(sharded_batch_roofline.read(ctx) * 6 / 8)


def test_none_on_a_program_without_the_catch_up_spans():
    blocks = [_block(1.0, 0.020), _block(2.0, 0.030)]
    ctx = Ctx(blocks, {"programs": {"extend_levels": {"runs": 2, "device_s": {0: 0.02}}}})
    for reader in (catchup_warm_ms, catchup_warm_host_ms, catchup_validate_ms,
                   sharded_batch_device_ms, sharded_batch_roofline):
        assert reader.read(ctx) is None, reader.__name__
    # the batched program ran but no extend_batch trace says how many squares
    ctx = Ctx(blocks, _device([0.04] * 4, [0.0] * 4))
    assert sharded_batch_roofline.read(ctx) is None
    # an extend_batch trace without the wait span (an older program)
    ctx = Ctx([{"name": "extend_batch", "spans": [("extend_batch", 0.0, 0.1, {})]}], {"programs": {}})
    assert catchup_warm_ms.read(ctx) == pytest.approx(100.0)
    assert catchup_warm_host_ms.read(ctx) is None


def test_the_new_metrics_are_declared_for_the_catch_up_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == ["catchup-k128-x4.window8"] and m["moves"] == "blocks_per_s"
    cell = next(w for w in spec["workloads"] if w["name"] == "catchup-k128-x4.window8")
    assert cell["chips"] == 4 and cell["traffic"] == "window8" and len(cell["why"]) <= 200


def test_the_catch_up_control_is_not_correct(tmp_path, capsys):
    """The program's own lagrange-gf256 codec through the batched
    sharded program, where the configuration states Leopard."""
    from celestia_tpu.parallel import mesh

    mesh.configure(None)
    try:
        args = run.parse(["--workload", tiny.CELL, "--seed", "3000000013", "--seconds", "1", "--trace", "0"])
        root = tiny.make_root(str(tmp_path), batched=True)
        assert run.run_cell(args, platform=None, root=root, overrides={"codec": "lagrange-gf256"}) == 0
    finally:
        mesh.configure(None)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is False
    assert out["checks"]["root_mismatches"]["value"] > 0
    assert out["checks"]["eds_share_mismatches"]["value"] > 0


def test_a_tiny_traced_catch_up_run_reads_every_span_metric(tmp_path, capsys, monkeypatch):
    """Tracing on: one extend_batch trace per window call, then one
    process_proposal trace per block, each a cache hit.  The profile of
    a CPU run has no TPU planes, so the device dict is a reduced one
    naming the batched program, and the peak is the v5e's."""
    from celestia_tpu.parallel import mesh
    from celestia_tpu.utils import devprof, tracing

    seen = {}

    def fake_trace_device(r, profile_dir):
        seen["traces"] = [bt["name"] for bt in r.block_traces]
        seen["spans"] = {s[0] for bt in r.block_traces for s in bt["spans"]}
        return {"window_s": 1.0, "chips": [0, 1, 2, 3], "busy_s": {c: 0.1 for c in range(4)},
                "programs": {"_sharded_extend_and_roots": {"runs": 2, "device_s": {c: 0.02 for c in range(4)}}},
                "top_ops": [], "idle_gaps": []}

    monkeypatch.setattr(run, "trace_device", fake_trace_device)

    class Context(run.Context):  # a CPU has no row in peaks.json
        def __init__(self, *a):
            super().__init__(*a)
            self.peak = PEAK

    monkeypatch.setattr(run, "Context", Context)
    # the profiling build of the sharded program: a second CPU compile
    monkeypatch.setattr(devprof, "note_compile", lambda *a, **k: None)
    mesh.configure(None)
    try:
        args = run.parse(["--workload", tiny.CELL, "--seed", "3000000007", "--seconds", "1", "--trace", "1"])
        assert run.run_cell(args, platform=None, root=tiny.make_root(str(tmp_path), batched=True)) == 0
    finally:
        tracing.disable()
        tracing.clear()
        mesh.configure(None)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    names = seen["traces"]
    calls = names.count("extend_batch")
    assert calls >= 2 and names.count("process_proposal") == 4 * calls
    assert names[:5] == ["extend_batch"] + ["process_proposal"] * 4
    assert {"extend.sharded", "extend.sharded.upload", "extend.sharded.launch", "extend.sharded.wait",
            "extend.sharded.fetch_roots", "extend.sharded.assemble", "extend_batch.stack"} <= seen["spans"]
    for name in NEW:
        assert out["metrics"][name]["value"] > 0, name
