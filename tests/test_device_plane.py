"""Device-resident DA plane (da/device_plane.py): byte-identity against
the host pipeline for every leg, the eds_cache device-handle budget,
and the one-way degradation ladder.

Everything runs with the plane FORCED on over the CPU backend at a tiny
k (the XLA CPU compile wall rules out full size in tier-1) — same
wiring, same programs, host-scale buffers.  The consensus-safety
contract under test: a plane-extended block commits the SAME roots and
serves the SAME proof bytes as the host pipeline, and losing the device
(eviction, fault) degrades to the host paths without changing a byte.
The extend route's three legs (device plane, native host leg, jax last
rung) are each checked against the benchmark's independent reference,
and their order is pinned.
"""

import numpy as np
import pytest

from celestia_tpu.da import dah as dah_mod
from celestia_tpu.da import das as das_mod
from celestia_tpu.da import device_plane, eds_cache
from celestia_tpu.ops import gf256
from celestia_tpu.utils import devprof, faults, native, tracing

K = 4


def _square(k: int = K, seed: int = 12) -> np.ndarray:
    rng = np.random.default_rng(seed)
    sq = rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    sq[:, :, :29] = 0
    sq[:, :, 28] = rng.integers(1, 200, (k, k), dtype=np.uint8)
    return sq


@pytest.fixture(autouse=True)
def _clean_plane():
    """Every test starts unpoisoned with an empty device-handle cache
    and leaves the process the same way (the plane state is global):
    no poison pin and no degradation record outlives its test."""
    device_plane.clear_poison(force=True)
    eds_cache.clear()
    yield
    device_plane.clear_poison(force=True)
    native.clear_poison(force=True)
    faults.reset_stats()
    eds_cache.clear()


def _extend_both(sq: np.ndarray):
    """(device-plane result, host-pipeline result) for one square."""
    with device_plane.forced("on"):
        eds_d, dah_d = dah_mod.extend_and_header(sq.copy())
        assert device_plane.poisoned() is None, device_plane.poisoned()
    with device_plane.forced("off"):
        eds_h, dah_h = dah_mod.extend_and_header(sq.copy())
    return (eds_d, dah_d), (eds_h, dah_h)


# ---------------------------------------------------------------------------
# byte identity: extend + header, both codecs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "codec", [gf256.CODEC_LEOPARD, gf256.CODEC_LAGRANGE]
)
def test_extend_and_header_byte_identical(codec):
    prev = gf256.active_codec()
    try:
        gf256.set_active_codec(codec)
        sq = _square(seed=21)
        (eds_d, dah_d), (eds_h, dah_h) = _extend_both(sq)
        assert dah_d.hash == dah_h.hash
        assert dah_d.row_roots == dah_h.row_roots
        assert dah_d.col_roots == dah_h.col_roots
        assert np.array_equal(
            np.asarray(eds_d.shares), np.asarray(eds_h.shares)
        )
    finally:
        gf256.set_active_codec(prev, force=True)


# ---------------------------------------------------------------------------
# byte identity: device-gathered DAS proofs vs the host reference,
# both codecs, full cross-product of cells (all four quadrants)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "codec", [gf256.CODEC_LEOPARD, gf256.CODEC_LAGRANGE]
)
def test_device_proofs_byte_identical_to_host_reference(codec):
    prev = gf256.active_codec()
    try:
        gf256.set_active_codec(codec)
        sq = _square(seed=22)
        (eds_d, dah_d), (eds_h, dah_h) = _extend_both(sq)
        coords = [(r, c) for r in range(2 * K) for c in range(2 * K)]
        with device_plane.forced("on"):
            assert eds_cache.get_device_entry(dah_d.hash) is not None
            proofs = das_mod.sample_proofs_batch(eds_d, dah_d, coords)
            assert device_plane.poisoned() is None, device_plane.poisoned()
        for (r, c), p in zip(coords, proofs):
            ref = das_mod._sample_proof_uncached(eds_h, dah_h, r, c)
            assert p == ref, (r, c)
            assert p.verify(dah_h.hash)
    finally:
        gf256.set_active_codec(prev, force=True)


def test_rfc6962_level_stack_matches_host_tree():
    """The traceable root-tree twin: every level byte-identical to
    da/proof.py merkle_level_tree over the same leaves."""
    from celestia_tpu.da.proof import merkle_level_tree
    from celestia_tpu.ops import nmt as nmt_ops

    rng = np.random.default_rng(5)
    leaves = rng.integers(0, 256, (16, 90), dtype=np.uint8)
    dev = nmt_ops.rfc6962_level_stack(np.asarray(leaves))
    host = merkle_level_tree([leaves[i].tobytes() for i in range(16)])
    assert len(dev) == len(host)
    for d, h in zip(dev, host):
        assert np.array_equal(np.asarray(d), h)


# ---------------------------------------------------------------------------
# eviction / device loss: the host fallback serves identical proofs
# ---------------------------------------------------------------------------


def test_eviction_mid_stream_falls_back_byte_identical():
    """Dropping the device entry between two batches of one serving
    stream must be invisible in the proof bytes: the second batch comes
    off the host path, identical."""
    sq = _square(seed=23)
    with device_plane.forced("on"):
        eds, dah = dah_mod.extend_and_header(sq.copy())
        coords = [(0, 0), (1, 5), (7, 2), (4, 4)]
        first = das_mod.sample_proofs_batch(eds, dah, coords)
        # mid-stream eviction (byte-budget pressure, device loss, admin
        # clear — the cause does not matter to the serving contract)
        assert eds_cache.drop_device_entry(dah.hash)
        assert eds_cache.get_device_entry(dah.hash) is None
        second = das_mod.sample_proofs_batch(eds, dah, coords)
    assert first == second
    for (r, c), p in zip(coords, second):
        assert p == das_mod._sample_proof_uncached(eds, dah, r, c)


def test_device_fault_poisons_and_falls_back_byte_identical(monkeypatch):
    """A gather that dies mid-batch poisons the plane one-way; the SAME
    call returns host-path proofs, byte-identical, and later extends
    route straight to the host legs."""
    sq = _square(seed=24)
    with device_plane.forced("on"):
        eds, dah = dah_mod.extend_and_header(sq.copy())
        coords = [(0, 1), (6, 3)]
        expected = [
            das_mod._sample_proof_uncached(eds, dah, r, c)
            for r, c in coords
        ]

        def boom(*a, **kw):
            raise RuntimeError("injected device loss")

        monkeypatch.setattr(device_plane, "sample_proofs_batch", boom)
        got = das_mod.sample_proofs_batch(eds, dah, coords)
        assert got == expected
        assert device_plane.poisoned() is not None
        assert not device_plane.enabled()  # poisoned wins over forced-on
        # a poisoned plane routes extends to the host legs too
        eds2, dah2 = dah_mod.extend_and_header(_square(seed=25))
        assert eds_cache.get_device_entry(dah2.hash) is None


def test_poison_is_one_way():
    device_plane.poison("first fault")
    device_plane.poison("second fault")  # first reason wins
    assert device_plane.poisoned() == "first fault"
    with pytest.raises(RuntimeError):
        device_plane.clear_poison()
    device_plane.clear_poison(force=True)
    assert device_plane.poisoned() is None


def test_extend_fault_poisons_and_same_call_falls_back(monkeypatch):
    """A device fault inside the fused extend must not lose the block:
    the very same extend_and_header call falls through to the host legs
    and returns the identical header."""
    with device_plane.forced("off"):
        _, dah_ref = dah_mod.extend_and_header(_square(seed=26))
    device_plane.clear_poison(force=True)

    def boom(square):
        raise RuntimeError("injected extend fault")

    monkeypatch.setattr(device_plane, "extend_and_header", boom)
    with device_plane.forced("on"):
        _, dah_got = dah_mod.extend_and_header(_square(seed=26))
    assert device_plane.poisoned() is not None
    assert dah_got.hash == dah_ref.hash
    assert dah_got.row_roots == dah_ref.row_roots


# ---------------------------------------------------------------------------
# byte budget + transfer ledger
# ---------------------------------------------------------------------------


def test_device_handle_budget_evicts_lru():
    """The device-handle cache honors its entry budget: inserting past
    capacity evicts the least-recently-used handle, and the stats
    surface reports the byte accounting."""
    max_entries = eds_cache._DEVICE_CACHE.max_entries
    roots = []
    with device_plane.forced("on"):
        for i in range(max_entries + 1):
            _, dah = dah_mod.extend_and_header(_square(seed=100 + i))
            roots.append(dah.hash)
    assert eds_cache.get_device_entry(roots[0]) is None  # LRU evicted
    assert eds_cache.get_device_entry(roots[-1]) is not None
    stats = eds_cache.device_handle_stats()
    assert stats["evictions"] >= 1
    assert stats["approx_bytes"] > 0


def test_transfer_ledger_records_only_contract_legs():
    """With the ledger armed, one extend + one warm batch charge
    exactly the contract legs: extend_levels (h2d), data_root, roots
    and proof_gather (d2h) — nothing else crosses."""
    sq = _square(seed=27)
    with device_plane.forced("on"):
        devprof.reset()
        with devprof.collect():
            eds, dah = dah_mod.extend_and_header(sq.copy())
            das_mod.sample_proofs_batch(eds, dah, [(0, 0), (3, 7)])
            ledger = devprof.transfer_accounting()
    d2h = {leg for leg, rec in ledger.items() if rec["d2h_events"]}
    assert d2h == {"data_root", "roots", "proof_gather"}
    assert ledger["data_root"]["d2h_bytes"] == 32
    assert ledger["roots"]["d2h_bytes"] == 4 * K * 90
    assert ledger["extend_levels"]["h2d_bytes"] == K * K * 512
    assert ledger["extend_levels"]["d2h_events"] == 0


def test_mode_env_routing(monkeypatch):
    monkeypatch.setenv(device_plane.ENV_MODE, "off")
    assert not device_plane.enabled()
    monkeypatch.setenv(device_plane.ENV_MODE, "on")
    assert device_plane.enabled()
    device_plane.poison("fault")
    assert not device_plane.enabled()
    device_plane.clear_poison(force=True)
    # auto on the CPU backend (host regime): plane stays off — tier-1
    # and the node default path are unchanged
    monkeypatch.setenv(device_plane.ENV_MODE, "auto")
    from celestia_tpu.utils.device import host_regime

    if host_regime():
        assert not device_plane.enabled()


# ---------------------------------------------------------------------------
# the extend route: three legs, each against the independent reference,
# and the order they are tried in
# ---------------------------------------------------------------------------

_LEG_SPANS = ("extend.device_plane", "extend.native", "extend.jax")


def _reference():
    """benchmark/configs/celestia_da_reference.py, loaded by path: a
    plain implementation that imports nothing of the program."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "configs", "celestia_da_reference.py",
    )
    spec = importlib.util.spec_from_file_location("celestia_da_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _sorted_ns_square(k: int, seed: int) -> np.ndarray:
    """Random shares whose namespaces ascend in row-major order (so down
    every column too), as a built square's do: the NMT min/max rules of
    the program and the reference agree only on ordered leaves."""
    rng = np.random.default_rng(seed)
    sq = rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    ns = rng.integers(0, 256, (k * k, 29), dtype=np.uint8)
    ns[:, 0] = 0  # version 0, never the parity namespace
    sq[:, :, :29] = ns[np.lexsort(ns.T[::-1])].reshape(k, k, 29)
    return sq


def _traced_extend(sq: np.ndarray):
    """(eds, dah, names of the extend legs that ran) for one square."""
    tracing.disable()
    tracing.clear()
    tracing.enable(4)
    try:
        with tracing.block_span("extend_leg", height=1):
            eds, dah = dah_mod.extend_and_header(sq.copy())
        ran = {s.name for s in tracing.block_traces()[-1].spans}
    finally:
        tracing.disable()
        tracing.clear()
    return eds, dah, ran & set(_LEG_SPANS)


@pytest.mark.parametrize(
    "leg,k",
    [("native", k) for k in (1, 2, 4, 8, 16, 32, 64)]
    + [("plane", k) for k in (1, 2, 4)]
    + [("jax", k) for k in (1, 2, 4)],
)
def test_extend_legs_match_reference(leg, k):
    """Each leg, forced, gives the reference's EDS bytes, all 4k axis
    roots and the data root, at Leopard."""
    if leg == "native" and not native.available():
        pytest.skip("native library not built")
    ref_mod = _reference()
    sq = _sorted_ns_square(k, seed=40 + k)
    prev = gf256.active_codec()
    try:
        gf256.set_active_codec(gf256.CODEC_LEOPARD)
        with device_plane.forced("on" if leg == "plane" else "off"):
            if leg == "jax":
                native.poison("test: force the last rung")
            eds, dah, ran = _traced_extend(sq)
    finally:
        native.clear_poison(force=True)
        gf256.set_active_codec(prev, force=True)
    assert ran == {f"extend.{'device_plane' if leg == 'plane' else leg}"}
    ref_eds = ref_mod.extend(sq, ref_mod.leopard_bit_matrix(k))
    ref_rows, ref_cols = ref_mod.axis_roots(ref_eds)
    assert np.array_equal(np.asarray(eds.shares), ref_eds)
    assert list(dah.row_roots) == ref_rows
    assert list(dah.col_roots) == ref_cols
    assert dah.hash == ref_mod.rfc6962_root(ref_rows + ref_cols)


@pytest.mark.parametrize(
    "codec", [gf256.CODEC_LEOPARD, gf256.CODEC_LAGRANGE]
)
@pytest.mark.parametrize(
    "state,expect",
    [
        ("clean", "extend.device_plane"),
        ("plane_poisoned", "extend.native"),
        ("plane_off_on_chip", "extend.native"),
        ("plane_and_native_poisoned", "extend.jax"),
    ],
)
def test_extend_ladder_takes_next_leg(state, expect, codec, monkeypatch):
    """The route tries the device plane, then the native host leg, then
    the jax last rung — and a chip host whose plane is off takes the
    host leg, not a second device program.  Every state's DAH equals the
    clean state's."""
    from celestia_tpu.utils import device

    if not native.available():
        pytest.skip("native library not built")
    sq = _square(seed=30)
    prev = gf256.active_codec()
    try:
        gf256.set_active_codec(codec)
        with device_plane.forced("on"):
            _, dah_ref, _ = _traced_extend(sq)
            if state in ("plane_poisoned", "plane_and_native_poisoned"):
                device_plane.poison("test: injected device fault")
            if state == "plane_and_native_poisoned":
                native.poison("test: injected native fault")
        mode = "off" if state == "plane_off_on_chip" else "on"
        if state == "plane_off_on_chip":
            monkeypatch.setattr(device, "host_regime", lambda: False)
        with device_plane.forced(mode):
            _, dah, ran = _traced_extend(sq)
    finally:
        native.clear_poison(force=True)
        gf256.set_active_codec(prev, force=True)
    assert ran == {expect}, ran
    assert dah.hash == dah_ref.hash
    assert dah.row_roots == dah_ref.row_roots
    assert dah.col_roots == dah_ref.col_roots
