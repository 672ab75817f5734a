"""Device-resident DA plane: EDS, NMT levels and the root tree stay on-chip.

Every earlier stage of the pipeline re-crossed the PCIe wall: the fused
extend+roots program fetched its roots per array, the standalone device
root pass (ops/nmt.py eds_nmt_roots_device) DISCARDED the inner NMT
levels so DAS serving re-hashed whole rows host-side, and proof
generation re-uploaded shares it had just fetched.  This module makes
the proposal->commit->serve lifecycle device-resident end to end
("On the Encoding Process in Decentralized Systems", arxiv 2408.15203:
the encode pipeline should produce its downstream artifacts in place,
not round-trip them through a host barrier):

* ONE program (:func:`_extend_levels_fn`) takes the
  original square and emits the EDS, the full per-row/per-column NMT
  level stacks and the RFC-6962 root-tree levels — no intermediate host
  fetch.  The only eager D2H on the proposal path is the 32-byte data
  root; the 4k axis roots follow in one lazily-issued tuple fetch (the
  DAH is a host object), and the shares/levels never cross at all.
* The device buffers ride a :class:`DevicePlaneEntry` handle cached in
  da/eds_cache.py beside the content-addressed (EDS, DAH) entry, with
  explicit byte-budget accounting from array SHAPES (weighing an entry
  must never force a transfer).
* DAS proofs become pure gathers (:func:`sample_proofs_batch`): proof-
  path indices are host integer arithmetic, the digests are gathered on
  the device, and ONE batched ``device_get`` fetches every proof node +
  share of the batch — never a re-hash.  Byte-identity with the host
  prover (da/das.py ``_sample_proof_uncached``) is pinned by
  tests/test_device_plane.py for both codecs.

Degradation ladder (specs/robustness.md): any device fault poisons the
plane ONE-WAY for the rest of the process — same contract as
utils/native.py — and every caller falls back to the byte-identical
host paths (da/dah.py legs, da/das.py host prover).  An entry evicted
from the byte budget is just a miss: the host fallback serves identical
proofs (pinned by the eviction test).

Activation (``CELESTIA_TPU_DEVICE_PLANE``): ``auto`` (default) enables
the plane exactly when a real accelerator backend is attached
(utils/device.host_regime() false); ``on`` forces it even on the CPU
backend (tests, the device-resident smoke); ``off`` disables it.
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from functools import lru_cache
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from celestia_tpu.ops import nmt as nmt_ops
from celestia_tpu.ops import rs
from celestia_tpu.ops.gf256 import active_codec as _active_codec
from celestia_tpu.ops.gf256 import encode_matrix_bits
from celestia_tpu.utils import devprof, tracing
from celestia_tpu.utils.telemetry import clock as _clock

ENV_MODE = "CELESTIA_TPU_DEVICE_PLANE"

# One-way degradation pin, same ladder as utils/native.py: a device
# fault mid-run (device loss, OOM, a gather that dies) poisons the plane
# for the REST OF THE PROCESS and every caller falls back to the byte-
# identical host legs.  Deliberately one-way — a chip that faulted once
# under load cannot silently come back, and a mid-chain flap between
# legs would make perf numbers unreadable.  Only clear_poison(force=True)
# (tests, operator intervention) clears it.
_poison_lock = threading.Lock()
_poison_reason: Optional[str] = None  # celint: guarded-by(_poison_lock)


def poison(reason: str) -> None:
    """Pin the device-resident plane OFF after a fault (loud, one-way).
    The in-flight block still commits identical roots: every fallback
    leg is byte-identical by construction."""
    global _poison_reason
    from celestia_tpu.utils import faults
    from celestia_tpu.utils.logging import Logger

    with _poison_lock:
        if _poison_reason is not None:
            return  # already degraded; first reason wins
        _poison_reason = reason
    faults.record_degradation("device_plane", reason)
    Logger(level="warn").warn(
        "device-resident DA plane poisoned: falling back to the host "
        "extend/serve paths for the rest of the process (byte-identical, "
        "more transfers)",
        reason=reason[:200],
    )


def poisoned() -> Optional[str]:
    """The poison reason, or None while the plane is trusted."""
    with _poison_lock:
        return _poison_reason


def clear_poison(force: bool = False) -> None:
    """Un-pin the degradation.  Refuses without ``force=True``: the pin
    exists precisely so nothing switches back silently."""
    global _poison_reason
    with _poison_lock:
        if _poison_reason is None:
            return
        if not force:
            raise RuntimeError(
                "the device-resident plane was poisoned "
                f"({_poison_reason!r}) and the degradation pin is one-way; "
                "pass force=True only if you KNOW the fault is resolved"
            )
        _poison_reason = None


def _mode() -> str:
    return os.environ.get(ENV_MODE, "auto").strip().lower()


def enabled() -> bool:
    """True when the device-resident extend/serve legs should run: the
    mode allows it (``on`` anywhere, ``auto`` only with a real
    accelerator backend) and the plane is not poisoned."""
    mode = _mode()
    if mode == "off":
        return False
    if poisoned() is not None:
        return False
    if mode == "on":
        return True
    from celestia_tpu.utils.device import host_regime

    return not host_regime()


@contextmanager
def forced(mode: str = "on"):
    """Temporarily pin the mode env (bench transfer-accounting legs, the
    device-resident smoke, tests) — restores the previous value even on
    error.  Process-global, like the env it sets."""
    prev = os.environ.get(ENV_MODE)
    os.environ[ENV_MODE] = mode
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop(ENV_MODE, None)
        else:
            os.environ[ENV_MODE] = prev


@lru_cache(maxsize=None)
def _extend_levels_fn(k: int, codec: str):
    """The fused device-resident program for square size k:

    square uint8[k,k,512] -> (eds uint8[2k,2k,512],
                              nmt levels tuple[(2, 2k, 2k>>j, 90)],
                              root levels tuple[(4k>>j, 32)])

    One XLA executable produces every downstream artifact of the
    proposal lifecycle — extension, all inner NMT nodes of the 4k axis
    trees (axis 0 of each level: 0=row trees, 1=column trees) and the
    complete RFC-6962 tree over the 4k roots (whose last level is the
    data root) — with zero host round trips between stages."""
    G = jnp.asarray(encode_matrix_bits(k, codec))

    def extend_levels(square: jnp.ndarray):
        eds = rs._extend(square, G)
        leaves = nmt_ops.eds_prefixed_leaves(eds)  # (2, 2k, 2k, 541)
        levels = nmt_ops.nmt_level_stack(leaves)
        roots = levels[-1][:, :, 0, :]  # (2, 2k, 90)
        all_roots = roots.reshape(4 * k, nmt_ops.NMT_DIGEST_SIZE)
        root_levels = nmt_ops.rfc6962_level_stack(all_roots)
        return eds, tuple(levels), tuple(root_levels)

    return jax.jit(extend_levels)


class DevicePlaneEntry:
    """The device-buffer handle cached beside an eds_cache entry: the
    EDS shares, every NMT level and the root-tree levels, all still on
    their chip.  ``nbytes`` is computed from shapes — weighing an entry
    in the byte budget never forces a transfer."""

    __slots__ = ("k", "data_root", "eds", "levels", "root_levels", "nbytes")

    def __init__(self, k, data_root, eds, levels, root_levels):
        self.k = int(k)
        self.data_root = data_root
        self.eds = eds
        self.levels = tuple(levels)
        self.root_levels = tuple(root_levels)
        self.nbytes = int(
            int(eds.nbytes)
            + sum(int(a.nbytes) for a in self.levels)
            + sum(int(a.nbytes) for a in self.root_levels)
        )


def extend_and_header(square):
    """The device-resident twin of da/dah.extend_and_header: square
    uint8[k,k,512] -> (ExtendedDataSquare, DataAvailabilityHeader),
    byte-identical to the host pipeline (the consensus-safety
    requirement, pinned by tests/test_device_plane.py).

    D2H budget: 32 bytes (data root, eager) + 4k x 90 bytes (axis roots,
    one lazily-issued tuple fetch for the host DAH object).  The EDS and
    the level stacks stay on the device inside the returned
    :class:`DevicePlaneEntry`, registered in eds_cache's device-handle
    budget so process/commit and DAS serving find the block device-warm.
    """
    from celestia_tpu.da import eds_cache
    from celestia_tpu.da.dah import DataAvailabilityHeader, ExtendedDataSquare

    k = int(square.shape[0])
    codec = _active_codec()
    with tracing.span("extend.device_plane", k=k, codec=codec):
        fn = _extend_levels_fn(k, codec)
        t0 = _clock()
        arr = jnp.asarray(square)
        # h2d charge: jnp.asarray ENQUEUES the upload — the recorded ms
        # is scheduling cost, the wire time overlaps the dispatch below
        devprof.record_transfer(
            "extend_levels", "h2d", k * k * 512, (_clock() - t0) * 1000.0
        )
        d = devprof.dispatch("extend_levels", k=k, codec=codec)
        eds_d, levels, root_levels = d.done(fn(arr))
        # the ONE eager hot-path D2H: the 32-byte data root
        data_root = bytes(devprof.fetch("data_root", root_levels[-1])[0])
        # axis roots, lazily fetched relative to the dispatch (they are
        # only needed to assemble the host DAH object) — ONE tuple fetch
        axis_roots = devprof.fetch("roots", levels[-1])  # (2, 2k, 1, 90)
        rr = axis_roots[0, :, 0, :]
        cc = axis_roots[1, :, 0, :]
        dah = DataAvailabilityHeader(
            tuple(rr[i].tobytes() for i in range(rr.shape[0])),
            tuple(cc[i].tobytes() for i in range(cc.shape[0])),
            data_root,
        )
    # cost accounting OUTSIDE the traced span (da/dah.py placement
    # contract)
    devprof.note_compile("extend_levels", fn, (arr,))
    entry = DevicePlaneEntry(k, data_root, eds_d, levels, root_levels)
    eds_cache.put_device_entry(data_root, entry)
    return ExtendedDataSquare(eds_d), dah


@lru_cache(maxsize=4096)
def _cell_node_indices(n: int, col: int, n_levels: int) -> tuple:
    """(level, index) of every sibling digest of the single-cell NMT
    range proof [col, col+1), in the EXACT traversal order
    da/proof.py nmt_range_proof_from_levels records them."""
    out: List[Tuple[int, int]] = []
    start, end = col, col + 1

    def walk(lo: int, hi: int, level: int) -> None:
        if lo >= end or hi <= start:
            out.append((level, lo >> level))
            return
        if hi - lo == 1:
            return
        mid = (lo + hi) // 2
        walk(lo, mid, level - 1)
        walk(mid, hi, level - 1)

    walk(0, n, n_levels - 1)
    return tuple(out)


def sample_proofs_batch(
    entry: DevicePlaneEntry, dah, coords: Sequence[Tuple[int, int]]
) -> list:
    """Serve n DAS proofs as pure gathers from the cached device level
    stacks: host integer arithmetic picks the proof-path indices, the
    digests and shares are gathered ON the device, and ONE batched
    ``device_get`` fetches everything — no re-hash, no row rebuild.
    Proofs are byte-identical to the host prover (coords order kept).

    Raises on any device fault — the caller (da/das.py) poisons the
    plane and falls back to the host prover for the same batch."""
    from celestia_tpu.da.das import SampleProof
    from celestia_tpu.da.proof import MerkleProof, NmtRangeProof

    k = entry.k
    n2 = 2 * k
    L = len(entry.levels)
    RL = len(entry.root_levels)
    total_roots = 4 * k
    # host-side index computation: per-level gather requests, filled per
    # coord in traversal order (the assembly below re-walks coords in
    # the same order, so per-level cursors reproduce the exact ordering)
    nmt_rows: List[List[int]] = [[] for _ in range(L)]
    nmt_idxs: List[List[int]] = [[] for _ in range(L)]
    for row, col in coords:
        for level, idx in _cell_node_indices(n2, col, L):
            nmt_rows[level].append(row)
            nmt_idxs[level].append(idx)
    root_idxs: List[List[int]] = [[] for _ in range(RL - 1)]
    for row, _col in coords:
        for j in range(RL - 1):
            root_idxs[j].append((row >> j) ^ 1)
    with tracing.span("das.device_gather", cells=len(coords), k=k):
        gathers = []
        used_levels = []
        for level in range(L):
            if not nmt_rows[level]:
                continue
            used_levels.append(level)
            r_a = jnp.asarray(nmt_rows[level], dtype=jnp.int32)
            i_a = jnp.asarray(nmt_idxs[level], dtype=jnp.int32)
            gathers.append(entry.levels[level][0, r_a, i_a])  # row trees
        for j in range(RL - 1):
            gathers.append(
                entry.root_levels[j][jnp.asarray(root_idxs[j], dtype=jnp.int32)]
            )
        rows_a = jnp.asarray([r for r, _ in coords], dtype=jnp.int32)
        cols_a = jnp.asarray([c for _, c in coords], dtype=jnp.int32)
        gathers.append(entry.eds[rows_a, cols_a])  # (n, 512) shares
        d = devprof.dispatch("das_proof_gather", cells=len(coords), k=k)
        gathered = d.done(tuple(gathers))
        # the proof path crosses in ONE batched fetch — the only D2H of
        # warm device-resident serving
        host = devprof.fetch("proof_gather", gathered)
    nmt_host = dict(zip(used_levels, host[: len(used_levels)]))
    root_host = host[len(used_levels) : len(used_levels) + (RL - 1)]
    shares_host = host[-1]
    cursors = [0] * L
    root_cursor = 0
    out = []
    for i, (row, col) in enumerate(coords):
        nodes = []
        for level, _idx in _cell_node_indices(n2, col, L):
            nodes.append(nmt_host[level][cursors[level]].tobytes())
            cursors[level] += 1
        aunts = tuple(
            root_host[j][root_cursor].tobytes() for j in range(RL - 1)
        )
        root_cursor += 1
        out.append(
            SampleProof(
                row=row,
                col=col,
                square_size=k,
                share=shares_host[i].tobytes(),
                nmt_proof=NmtRangeProof(col, col + 1, tuple(nodes)),
                row_root=dah.row_roots[row],
                root_proof=MerkleProof(
                    index=row, total=total_roots, aunts=aunts
                ),
            )
        )
    return out
