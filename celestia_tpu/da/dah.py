"""Extended data square + DataAvailabilityHeader: the block-extension hot path.

Behavioral parity with /root/reference/pkg/da/data_availability_header.go
(ExtendShares :65-75, NewDataAvailabilityHeader :44-63, Hash :92-108,
ValidateBasic :134-177, MinDataAvailabilityHeader :179) and
app/extend_block.go:14-32 — redesigned as one fused, jit-compiled device
program: RS-extend (ops/rs.py bit-matmuls) -> all 4k NMT axis roots
(ops/nmt.py level-synchronous reduction) -> RFC-6962 data root, in a single
XLA executable per square size.  This runs twice per block per validator
(PrepareProposal / ProcessProposal) and is the BASELINE.json north star.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass
from functools import lru_cache
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from celestia_tpu.appconsts import (
    DEFAULT_SQUARE_SIZE_UPPER_BOUND,
    SHARE_SIZE,
    is_power_of_two,
)
from celestia_tpu.da.square import Square
from celestia_tpu.ops import nmt as nmt_ops
from celestia_tpu.ops import rs
from celestia_tpu.ops.gf256 import active_codec as _active_codec
from celestia_tpu.ops.gf256 import encode_matrix_bits
from celestia_tpu.utils import tracing

NMT_ROOT_SIZE = nmt_ops.NMT_DIGEST_SIZE  # 90
DATA_ROOT_SIZE = 32


class ExtendedDataSquare:
    """A 2k x 2k erasure-extended share square (rsmt2d.ExtendedDataSquare parity).

    Holds the share tensor uint8[2k, 2k, 512]; Q0 (top-left k x k) is the
    original data square.
    """

    def __init__(self, shares):
        # Accepts a host array OR a device (jax) array.  A device-resident
        # EDS stays on the device until something actually reads the share
        # bytes (proof generation, gossip): PrepareProposal/ProcessProposal
        # only consume the roots, so the ~8-33 MiB device->host transfer
        # drops out of the block hot path (SURVEY §7 hard part c).
        if isinstance(shares, np.ndarray) or not hasattr(shares, "shape"):
            # host-coercible input (ndarray, list, tuple, ...)
            shares = np.asarray(shares, dtype=np.uint8)
        elif shares.dtype != np.uint8:  # device array with wrong dtype
            raise ValueError(f"EDS shares must be uint8, got {shares.dtype}")
        n = shares.shape[0]
        if shares.shape != (n, n, SHARE_SIZE) or n % 2 or not is_power_of_two(n // 2):
            raise ValueError(f"invalid EDS shape {shares.shape}")
        self._shares = shares

    @property
    def shares(self) -> np.ndarray:
        if not isinstance(self._shares, np.ndarray):
            self._shares = np.asarray(self._shares).astype(np.uint8, copy=False)
        return self._shares

    @property
    def width(self) -> int:
        # shape is metadata — never forces a device->host transfer
        return self._shares.shape[0]

    @property
    def square_size(self) -> int:
        """Original (unextended) square width k."""
        return self.width // 2

    def row(self, r: int) -> np.ndarray:
        return self.shares[r]

    def col(self, c: int) -> np.ndarray:
        return self.shares[:, c]

    def quadrant(self, q: int) -> np.ndarray:
        k = self.square_size
        r, c = divmod(q, 2)
        return self.shares[r * k : (r + 1) * k, c * k : (c + 1) * k]

    def flattened_original(self) -> np.ndarray:
        """Q0 as uint8[k*k, 512] (row-major original shares)."""
        k = self.square_size
        return self.quadrant(0).reshape(k * k, SHARE_SIZE)


@lru_cache(maxsize=None)
def _extend_and_roots_fn(k: int, codec: str):
    """Jitted fused pipeline for square size k:
    square uint8[k,k,512] -> (eds, row_roots[2k,90], col_roots[2k,90], data_root[32])."""
    G = jnp.asarray(encode_matrix_bits(k, codec))

    def run(square: jnp.ndarray):
        eds = rs._extend(square, G)
        roots = nmt_ops.eds_nmt_roots(eds)  # (2, 2k, 90)
        all_roots = roots.reshape(4 * k, NMT_ROOT_SIZE)
        data_root = nmt_ops.rfc6962_root_pow2(all_roots)
        return eds, roots[0], roots[1], data_root

    return jax.jit(run)


@dataclass(frozen=True)
class DataAvailabilityHeader:
    """Row/column NMT roots + memoized hash (= the block's data root)."""

    row_roots: Tuple[bytes, ...]
    col_roots: Tuple[bytes, ...]
    _hash: bytes

    @property
    def hash(self) -> bytes:
        return self._hash

    @property
    def square_size(self) -> int:
        return len(self.row_roots) // 2

    def validate_basic(self) -> None:
        """dah ValidateBasic parity: extended square bounds + root shapes +
        hash consistency (data_availability_header.go:134-177)."""
        n = len(self.row_roots)
        if n == 0 or n != len(self.col_roots):
            raise ValueError("row/col root counts must match and be non-empty")
        k = n // 2
        if n % 2 or not is_power_of_two(k):
            raise ValueError(f"extended square width {n} must be 2 * power-of-two")
        if k > DEFAULT_SQUARE_SIZE_UPPER_BOUND:
            raise ValueError(
                f"square size {k} exceeds upper bound {DEFAULT_SQUARE_SIZE_UPPER_BOUND}"
            )
        for r in (*self.row_roots, *self.col_roots):
            if len(r) != NMT_ROOT_SIZE:
                raise ValueError(f"NMT root must be {NMT_ROOT_SIZE} bytes")
        if self.compute_hash(self.row_roots, self.col_roots) != self._hash:
            raise ValueError("DAH hash does not match its roots")

    @staticmethod
    def compute_hash(row_roots, col_roots) -> bytes:
        return nmt_ops.rfc6962_root_np(list(row_roots) + list(col_roots)).tobytes()

    def to_bytes(self) -> bytes:
        """Deterministic wire form: counts + concatenated roots."""
        out = bytearray()
        out += len(self.row_roots).to_bytes(4, "big")
        for r in self.row_roots:
            out += r
        out += len(self.col_roots).to_bytes(4, "big")
        for c in self.col_roots:
            out += c
        return bytes(out)

    @classmethod
    def from_bytes(cls, raw: bytes) -> "DataAvailabilityHeader":
        n_rows = int.from_bytes(raw[:4], "big")
        pos = 4
        rows = []
        for _ in range(n_rows):
            rows.append(raw[pos : pos + NMT_ROOT_SIZE])
            pos += NMT_ROOT_SIZE
        n_cols = int.from_bytes(raw[pos : pos + 4], "big")
        pos += 4
        cols = []
        for _ in range(n_cols):
            cols.append(raw[pos : pos + NMT_ROOT_SIZE])
            pos += NMT_ROOT_SIZE
        if pos != len(raw):
            raise ValueError("trailing bytes in DAH encoding")
        dah = cls(tuple(rows), tuple(cols), cls.compute_hash(rows, cols))
        dah.validate_basic()
        return dah


def extend_shares(shares: np.ndarray) -> ExtendedDataSquare:
    """da.ExtendShares parity: uint8[n, 512] (n a perfect power-of-4 count)
    -> ExtendedDataSquare."""
    shares = np.asarray(shares, dtype=np.uint8)
    n = shares.shape[0]
    k = int(round(n**0.5))
    if k * k != n or not is_power_of_two(k):
        raise ValueError(f"share count {n} must be a square of a power of two")
    square = shares.reshape(k, k, SHARE_SIZE)
    eds = np.asarray(rs.extend_square(square))
    return ExtendedDataSquare(eds)


def _host_native_available() -> bool:
    """True when :func:`new_data_availability_header` hashes on the host
    pool: the default backend is the CPU (a host-only deployment) and
    the native pooled pipeline is present."""
    from celestia_tpu.utils import native
    from celestia_tpu.utils.device import host_regime

    return host_regime() and native.available()


def _extend_and_header_host(
    square: np.ndarray,
) -> Tuple[ExtendedDataSquare, "DataAvailabilityHeader"]:
    """The host ExtendBlock leg: the pooled native C++ pipeline with the
    extend->roots overlap (byte-identical to the device program — pinned
    by tests/test_leopard_codec.py / test_golden_vectors.py)."""
    from celestia_tpu.ops import gf256
    from celestia_tpu.utils import hostpool, native

    codec = gf256.active_codec()
    # the fused C++ call computes extension AND all 4k roots in its
    # 3-phase overlapped pipeline (row extend -> columns interleaved
    # with top-row roots -> remaining roots); the span args record the
    # fusion so the trace reader knows the roots phase below is the
    # Python-side DAH assembly, not the hashing itself.  Args (incl. the
    # cpu_threads() lock+env read) are built only when the tracer is on
    # — this is the per-block host hot path.
    span = (
        tracing.span(
            "extend.native",
            codec=codec,
            fused_roots=True,
            nthreads=hostpool.cpu_threads(),
            phases=3,
        )
        if tracing.enabled()
        else tracing.NULL_SPAN
    )
    with span:
        if codec == gf256.CODEC_LEOPARD:
            eds, roots, data_root = native.extend_block_leopard_cpu(square)
        else:
            eds, roots, data_root = native.extend_block_cpu(square)
    n2 = 2 * square.shape[0]
    with tracing.span("roots", stage="assemble", fused_native=True):
        dah = DataAvailabilityHeader(
            tuple(roots[i].tobytes() for i in range(n2)),
            tuple(roots[n2 + i].tobytes() for i in range(n2)),
            data_root.tobytes(),
        )
    return ExtendedDataSquare(eds), dah


def extend_and_header(
    square: np.ndarray,
) -> Tuple[ExtendedDataSquare, "DataAvailabilityHeader"]:
    """The fused hot path: original square uint8[k,k,512] -> (EDS, DAH).

    One program computes extension, 4k NMT roots and the data root (the
    reference does this as ExtendShares + NewDataAvailabilityHeader,
    app/prepare_proposal.go:65-77).  Three byte-identical legs, tried in
    this order:

    1. the device plane (da/device_plane.py, XLA program
       ``extend_levels``) when ``device_plane.enabled()``;
    2. the pooled native C++ pipeline (:func:`_extend_and_header_host`)
       when the native library is loaded, whatever the backend;
    3. the jitted :func:`_extend_and_roots_fn`, the last rung, only when
       native code is missing or poisoned.

    A fault in leg 1 or 2 poisons that leg one-way (loud) and this very
    call falls through to the next, so the block being extended still
    commits the same data root.  Inside :func:`extend_block_ahead` of the
    same square, leg 1 awaits the program launched there.
    """
    from celestia_tpu.da import device_plane
    from celestia_tpu.utils import devprof, native

    square = np.asarray(square, dtype=np.uint8)
    k = square.shape[0]
    if device_plane.enabled():
        # one program emits EDS + NMT level stacks + root tree; only the
        # data root and the 4k axis roots cross to the host, and the
        # level stacks stay cached device-side for DAS serving
        try:
            return device_plane.extend_and_header(square)
        except Exception as e:
            device_plane.poison(f"device-resident extend failed: {e!r}")
    if native.available():
        try:
            return _extend_and_header_host(square)
        except Exception as e:
            native.poison(f"extend_and_header native leg failed: {e!r}")
    codec = _active_codec()
    with tracing.span("extend.jax", codec=codec, k=k, fused_roots=True):
        fn = _extend_and_roots_fn(k, codec)
        arr = jnp.asarray(square)
        # devprof bracket: device-track span (enqueue vs device-drain,
        # per chip).  Inactive, the dispatch is a shared no-op and the
        # result stays ASYNC.
        d = devprof.dispatch("extend_and_roots", k=k, codec=codec)
        eds_d, row_roots, col_roots, data_root = d.done(fn(arr))
    # cost accounting OUTSIDE both the device bracket and the traced
    # extend.jax span: the one-time AOT compile must inflate neither
    devprof.note_compile("extend_and_roots", fn, (arr,))
    eds = ExtendedDataSquare(eds_d)  # stays on device until shares are read
    with tracing.span("roots", stage="fetch"):
        # materializing the root arrays forces the (async) device values
        # to host
        rr = np.asarray(row_roots)
        cc = np.asarray(col_roots)
        dah = DataAvailabilityHeader(
            tuple(rr[i].tobytes() for i in range(rr.shape[0])),
            tuple(cc[i].tobytes() for i in range(cc.shape[0])),
            np.asarray(data_root).tobytes(),
        )
    return eds, dah


@contextmanager
def extend_block_ahead(square: Square):
    """Start :func:`extend_block`'s device leg for ``square`` now, so the
    device computes while the caller's block runs on the host: an
    :func:`extend_block` of the same square inside the block awaits this
    launch (da/device_plane.py ``ahead``).  Off the device plane nothing
    starts early, and the host legs run where the caller asks for the
    extend.  A launch fault poisons the plane, as in
    :func:`extend_and_header`.  Yields whether a program was launched."""
    from celestia_tpu.da import device_plane

    pending = None
    if device_plane.enabled():
        try:
            pending = device_plane.begin_extend(_original_array(square))
        except Exception as e:
            device_plane.poison(f"device-resident extend failed: {e!r}")
    if pending is None:
        yield False
        return
    with device_plane.ahead(pending):
        yield True


def new_data_availability_header(eds: ExtendedDataSquare) -> DataAvailabilityHeader:
    """da.NewDataAvailabilityHeader parity: roots + hash from an existing EDS.

    Host regime: the 4k independent NMT trees shard across the process
    worker pool (ops/nmt.py eds_nmt_roots_host) instead of compiling the
    XLA CPU program — same bytes, minutes less latency at k=128."""
    roots = None
    if _host_native_available():
        try:
            with tracing.span("roots", stage="host_pool", trees=2 * eds.width):
                roots = nmt_ops.eds_nmt_roots_host(eds.shares)
        except Exception as e:
            # same one-way degradation as extend_and_header: poison the
            # native leg and recompute on the jax path (identical bytes)
            from celestia_tpu.utils import native as _native

            _native.poison(f"eds_nmt_roots native leg failed: {e!r}")
    if roots is None:
        with tracing.span("roots", stage="jax"):
            # the standalone devprof-instrumented device entry
            # (ops/nmt.py): device-track timing + XLA cost accounting
            # when profiling is armed, a plain jitted call otherwise
            roots = nmt_ops.eds_nmt_roots_device(eds.shares)
    rows = tuple(roots[0, i].tobytes() for i in range(roots.shape[1]))
    cols = tuple(roots[1, i].tobytes() for i in range(roots.shape[1]))
    return DataAvailabilityHeader(
        rows, cols, DataAvailabilityHeader.compute_hash(rows, cols)
    )


def extend_block(square: Square) -> Tuple[ExtendedDataSquare, DataAvailabilityHeader]:
    """app.ExtendBlock parity (extend_block.go:14-26): square -> (EDS, DAH)."""
    return extend_and_header(_original_array(square))


def _original_array(square: Square) -> np.ndarray:
    """uint8[k,k,512], a view of the square's own (cached) array."""
    k = square.size
    return square.to_array().reshape(k, k, SHARE_SIZE)


# serializes the first computation of the min DAH; the PR 4 worker pool
# made the old bare module global racy (two threads could both see None
# and compute concurrently — benign for the value, but the unsynchronized
# write was a data race by contract)
_min_dah_lock = threading.Lock()


def min_data_availability_header() -> DataAvailabilityHeader:
    """DAH of the minimal (empty) square: one tail-padding share
    (data_availability_header.go:179).

    Cached as the first resident of the content-addressed EDS cache
    (da/eds_cache.py) — codec-aware by key, so a test that switches the
    active codec can never read the other codec's min DAH, and lock-
    guarded so pool workers race neither the computation nor the insert."""
    from celestia_tpu.da import eds_cache

    key = eds_cache.min_dah_key(_active_codec())
    hit = eds_cache.CACHE.peek(key)  # peek: keep hit-rate stats about blocks
    if hit is not None:
        return hit[1]
    with _min_dah_lock:
        hit = eds_cache.CACHE.peek(key)
        if hit is not None:
            return hit[1]
        from celestia_tpu.da.square import build

        square, _, _ = build([])
        eds, dah = extend_block(square)
        eds_cache.put(key, eds, dah)
        return dah
