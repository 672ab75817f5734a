"""The repairing full node's cell (``fullnode-k128.repair25``), driven on
the CPU at k=8 with the look for a chip skipped, as test_harness.py
drives the others: a tiny run reads correct, while the codec control, a
repaired share altered before registration and a program with no
``TestNode.repair_block`` do not.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_repair_cell.py -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [HERE, BENCH, os.path.join(BENCH, "metrics"), os.path.join(BENCH, "configs"), ROOT]

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ["CELESTIA_TPU_DEVICE_PLANE"] = "on"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import run  # noqa: E402
import tiny  # noqa: E402

CELL = "fullnode-k128.repair25"
SEED = 3000000001


def test_the_cell_is_in_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("fullnode-k128", "repair25", 1)
    with open(os.path.join(ROOT, "benchmark", "configs", "fullnode-k128.json")) as f:
        config = json.load(f)
    assert config["gov_max_square_size"] == 128 and config["codec"] == "leopard-ff8"
    ours = [m for m in spec["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in ours} == {
        "repair_ms", "repair_host_ms", "repair_deconstruct_ms", "repair_device_ms", "repair_roofline"}


@pytest.fixture(scope="module")
def repair_root(tmp_path_factory):
    """tiny.py's k=8 root with the cell's traffic and entry."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("repair")))
    with open(os.path.join(BENCH, "traffic", "repair25.json")) as f:
        traffic = json.load(f)
    traffic.update(pool_blocks=4, reference_blocks=2, fill=tiny.TINY_TRAFFIC["fill"],
                   namespaces=tiny.TINY_TRAFFIC["namespaces"], groups=tiny.TINY_TRAFFIC["groups"])
    with open(os.path.join(root, "benchmark", "traffic", "tiny.json"), "w") as f:
        json.dump(traffic, f)
    return root


def _tiny_run(root, capsys, overrides=None):
    args = run.parse(["--workload", tiny.CELL, "--seed", str(SEED), "--seconds", "1", "--trace", "0"])
    assert run.run_cell(args, platform=None, root=root, overrides=overrides) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    return out


def test_a_tiny_repair_run_is_correct(repair_root, capsys):
    out = _tiny_run(repair_root, capsys)
    assert out["correct"] is True, out["checks"]
    checks = {n: c["value"] for n, c in out["checks"].items()}
    assert checks["repairs_off_device"] == 0 and checks["extends_off_device_plane"] == 0
    assert checks["blocks_compared"] > 0 and checks["eds_compared"] > 0
    assert out["attempted"] > 4 and out["failed"] == 0


def test_the_control_is_not_correct(repair_root, capsys):
    """The program's own lagrange-gf256 codec in place of Leopard."""
    out = _tiny_run(repair_root, capsys, overrides={"codec": "lagrange-gf256"})
    assert out["correct"] is False
    assert out["checks"]["root_mismatches"]["value"] > 0


def test_a_share_altered_before_registration_is_not_correct(repair_root, capsys, monkeypatch):
    from celestia_tpu.ops import rs

    real = rs.finish_repair

    def altered(*args, **kwargs):
        out = real(*args, **kwargs)
        out.eds = out.eds.at[-1, -1, -1].set(out.eds[-1, -1, -1] ^ 1)
        return out

    monkeypatch.setattr(rs, "finish_repair", altered)
    out = _tiny_run(repair_root, capsys)
    assert out["correct"] is False
    assert out["checks"]["eds_share_mismatches"]["value"] > 0


def test_a_program_with_no_repair_fails_in_set_up(repair_root, monkeypatch):
    from celestia_tpu.node.testnode import TestNode

    monkeypatch.delattr(TestNode, "repair_block")
    args = run.parse(["--workload", tiny.CELL, "--seed", str(SEED), "--seconds", "1", "--trace", "0"])
    with pytest.raises(RuntimeError, match="repair_block"):
        run.run_cell(args, platform=None, root=repair_root)
