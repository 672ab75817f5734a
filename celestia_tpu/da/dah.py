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
from dataclasses import dataclass
from functools import lru_cache
from typing import Dict, List, Optional, Tuple

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
from celestia_tpu.utils.lru import LruCache

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
    """True when the host-regime fast path applies: the default backend
    is the CPU (a host-only deployment) and the native pooled pipeline
    is present."""
    from celestia_tpu.utils import native
    from celestia_tpu.utils.device import host_regime

    return host_regime() and native.available()


def _extend_and_header_host(
    square: np.ndarray,
) -> Tuple[ExtendedDataSquare, "DataAvailabilityHeader"]:
    """Host-regime ExtendBlock: the pooled native C++ pipeline with the
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


# ---------------------------------------------------------------------------
# Row-level extension memoization (host regime).
#
# Consecutive heights share rows whose bytes have not changed — tail-padding
# rows, namespace-padding rows, unchanged blob rows — and within one square
# the padding rows are all identical.  Extension and the ROW tree are pure
# per-row functions: parity row r depends only on row r's bytes, and the
# NMT prefix rule (own ns for c < k, parity ns for c >= k) is the same for
# every original row index, so digest(row bytes) fully determines both the
# parity row and the extended row's NMT root.  Column extension and column
# roots depend on the whole square and always recompute.
#
# The memo serves the HOST regime legs only (native fused pipeline + the
# jax-on-CPU fallback).  The device leg deliberately bypasses it: a partial
# hit cannot shrink the fused XLA program, populating parity would force a
# ~32 MiB device->host fetch onto the hot path, and the device regime's
# redundant work is already eliminated one level up by the content-addressed
# EDS cache (da/eds_cache.py).
#
# MEASURED scoping (k=128, 2-core host, this PR): the leopard-native fused
# pipeline (FFT + overlapped extend->roots in C++) finishes in ~191 ms,
# while Python-orchestrated selective reuse costs ~250 ms even with 100%
# of rows memoized — the column FFT + full native roots + assembly copies
# alone exceed the fused total.  The memo therefore engages only where it
# measurably wins: the table-method (lagrange) native pipeline (~3.9 s
# fused at k=128 -> ~3x faster with 75% row reuse) and the no-native
# pure-Python fallback (proportional savings on every skipped row).  For
# leopard+native the memo is fully disabled — not even digests are
# computed — so the default host hot path carries zero overhead.
# ---------------------------------------------------------------------------


class _RowMemo:
    """(k, codec, sha256(row bytes)) -> (parity row bytes, row root bytes).

    Domain wrapper over the unified utils/lru.py cache; the batch API and
    the legacy stats keys (lookups/inserted/reuse_pct) are preserved for
    bench.py's BENCH_r0x series."""

    def __init__(self, max_entries: int):
        self._lru = LruCache(
            "row_memo", max_entries, weigher=_row_memo_weigher
        )
        # assembled is memo-path bookkeeping, not a cache counter; int
        # += is atomic enough for a stats field under CPython
        self.assembled = 0  # squares served by the memoized assembly path

    @property
    def max_entries(self) -> int:
        return self._lru.max_entries

    def lookup_many(self, k: int, codec: str, digests: List[bytes]):
        return self._lru.get_many((k, codec, d) for d in digests)

    def insert_many(self, k: int, codec: str, items) -> None:
        """items: iterable of (digest, parity_bytes, root_bytes)."""
        self._lru.put_many(
            ((k, codec, d), (parity, root)) for d, parity, root in items
        )

    def mark_assembled(self) -> None:
        self.assembled += 1

    def clear(self) -> None:
        self._lru.clear()
        self.assembled = 0

    def stats(self) -> dict:
        s = self._lru.stats()
        lookups = s["hits"] + s["misses"]
        return {
            "entries": s["entries"],
            "lookups": lookups,
            "hits": s["hits"],
            "inserted": s["puts"],
            "assembled": self.assembled,
            "reuse_pct": (100.0 * s["hits"] / lookups) if lookups else 0.0,
            "approx_bytes": s["approx_bytes"],
        }


def _row_memo_weigher(key, value) -> int:
    parity, root = value
    return len(parity) + len(root) + 64


def _row_memo_max_entries() -> int:
    import os

    # one entry holds a k x 512 B parity row (64 KiB at k=128): 512
    # entries bound the memo around 32 MiB worst case
    return int(os.environ.get("CELESTIA_TPU_ROW_MEMO", "512"))


_ROW_MEMO = _RowMemo(_row_memo_max_entries())


def row_memo_stats() -> dict:
    return _ROW_MEMO.stats()


def clear_row_memo() -> None:
    _ROW_MEMO.clear()


def _row_digests(square: np.ndarray) -> List[bytes]:
    """sha256 per original row (the memo keys), threaded when native."""
    from celestia_tpu.utils import native

    k = square.shape[0]
    flat = np.ascontiguousarray(square.reshape(k, -1))
    if native.available():
        d = native.sha256_batch(flat)
        return [d[i].tobytes() for i in range(k)]
    import hashlib

    return [hashlib.sha256(flat[i].tobytes()).digest() for i in range(k)]


def _row_memo_applicable() -> bool:
    """True when the memoized assembly can beat the fused pipeline for
    the active codec (see the measured scoping note above)."""
    from celestia_tpu.ops import gf256
    from celestia_tpu.utils import native

    return (not native.available()) or (
        _active_codec() == gf256.CODEC_LAGRANGE
    )


def _gf_encode_axis(X: np.ndarray) -> np.ndarray:
    """E(k) @ X over GF(256) in the active codec: uint8[k, B'] -> uint8[k, B'].

    The single primitive both memo phases need — row parity for missed
    rows and the full column extension are the same encode matrix applied
    along axis 0 (Q3 = E @ Q1 == row-extension of Q2 for a linear code,
    the rsmt2d quadrant consistency property).  The native table matmul
    threads across axes, so the byte dimension is chunked over the pool
    (zero-padding is exact: GF matmul is column-independent)."""
    from celestia_tpu.ops.gf256 import encode_matrix, encode_shares_ref
    from celestia_tpu.utils import hostpool, native

    k, Bp = X.shape
    if not native.available():
        return encode_shares_ref(X)
    E = np.ascontiguousarray(encode_matrix(k))
    T = max(1, min(hostpool.cpu_threads(), Bp // 4096))
    if T == 1:
        return native.gf_matmul_axes(E[None], np.ascontiguousarray(X)[None])[0]
    chunk = -(-Bp // T)
    pad = T * chunk - Bp
    if pad:
        X = np.concatenate(
            [X, np.zeros((k, pad), dtype=np.uint8)], axis=1
        )
    Xc = np.ascontiguousarray(
        X.reshape(k, T, chunk).transpose(1, 0, 2)
    )
    D = np.ascontiguousarray(np.broadcast_to(E, (T, k, k)))
    out = native.gf_matmul_axes(D, Xc)  # (T, k, chunk)
    out = out.transpose(1, 0, 2).reshape(k, T * chunk)
    return np.ascontiguousarray(out[:, :Bp])


def _try_memoized_extend(
    square: np.ndarray, digests: List[bytes]
) -> Optional[Tuple[ExtendedDataSquare, "DataAvailabilityHeader"]]:
    """Assemble (EDS, DAH) from the row memo, or None when coverage is too
    thin to beat the fused pipeline.

    Engages when at least a quarter of the k row-extensions are saved —
    via memo hits from earlier heights or via intra-square duplicates
    (identical padding rows extend once).  Byte-identical to the fused
    path by construction: same encode matrix, same field tables, same
    NMT/RFC-6962 reductions (pinned by tests/test_eds_cache.py)."""
    k, B = square.shape[0], square.shape[2]
    codec = _active_codec()
    entries = _ROW_MEMO.lookup_many(k, codec, digests)
    missing: "Dict[bytes, int]" = {}  # digest -> representative row
    for r, (d, e) in enumerate(zip(digests, entries)):
        if e is None and d not in missing:
            missing[d] = r
    if k - len(missing) < max(1, k // 4):
        return None
    n2 = 2 * k
    with tracing.span(
        "extend.memo", k=k, memo_hits=k - len(missing), memo_misses=len(missing)
    ):
        top = np.empty((k, n2, B), dtype=np.uint8)
        top[:, :k] = square
        parity_by_digest: "Dict[bytes, np.ndarray]" = {}
        if missing:
            reps = list(missing.values())
            data = square[reps]  # (m, k, B)
            P = _gf_encode_axis(data.transpose(1, 0, 2).reshape(k, -1))
            par = P.reshape(k, len(reps), B).transpose(1, 0, 2)  # (m, k, B)
            for i, d in enumerate(missing):
                parity_by_digest[d] = par[i]
        for r, (d, e) in enumerate(zip(digests, entries)):
            if e is not None:
                top[r, k:] = np.frombuffer(e[0], dtype=np.uint8).reshape(k, B)
            else:
                top[r, k:] = parity_by_digest[d]
        bottom = _gf_encode_axis(top.reshape(k, -1)).reshape(k, n2, B)
        eds = np.concatenate([top, bottom], axis=0)
    from celestia_tpu.utils import native

    if native.available():
        # the threaded C++ root pass over all 4k trees beats a selective
        # Python-orchestrated reduction even with most row roots memoized
        # (measured: selective batch over 3k+ trees is ~2.5x slower than
        # the full native pass) — reuse the extension, recompute roots
        with tracing.span("roots", stage="native_full_pass", trees=4 * k):
            all_roots = native.eds_nmt_roots(eds)
        row_roots = [all_roots[i].tobytes() for i in range(n2)]
        col_roots = [all_roots[n2 + i].tobytes() for i in range(n2)]
        root_by_digest = {d: row_roots[r] for d, r in missing.items()}
    else:
        # pure-Python fallback: every skipped tree is hashlib work saved —
        # memoized original rows come from the table; changed rows (deduped
        # by digest), all parity rows and all columns reduce in one batch
        own_ns = eds[..., : nmt_ops.NAMESPACE_SIZE]
        parity_ns = np.broadcast_to(nmt_ops._PARITY_NS, own_ns.shape)
        r_idx = np.arange(n2)
        in_q0 = (r_idx[:, None] < k) & (r_idx[None, :] < k)
        prefix = np.where(in_q0[..., None], own_ns, parity_ns)
        row_leaves = np.concatenate([prefix, eds], axis=-1)
        col_leaves = row_leaves.transpose(1, 0, 2)
        sel = list(missing.values()) + list(range(k, n2))
        trees = np.concatenate([row_leaves[sel], col_leaves], axis=0)
        with tracing.span("roots", stage="host_batch", trees=len(trees)):
            roots = nmt_ops.nmt_roots_host_batch(trees)
        m = len(missing)
        root_by_digest = {d: roots[i].tobytes() for i, d in enumerate(missing)}
        row_roots = []
        for d, e in zip(digests, entries):
            row_roots.append(e[1] if e is not None else root_by_digest[d])
        row_roots.extend(roots[m + j].tobytes() for j in range(k))
        col_roots = [roots[m + k + c].tobytes() for c in range(n2)]
    dah = DataAvailabilityHeader(
        tuple(row_roots),
        tuple(col_roots),
        DataAvailabilityHeader.compute_hash(row_roots, col_roots),
    )
    _ROW_MEMO.insert_many(
        k,
        codec,
        (
            (d, top[r, k:].tobytes(), root_by_digest[d])
            for d, r in missing.items()
        ),
    )
    _ROW_MEMO.mark_assembled()
    return ExtendedDataSquare(eds), dah


def _memo_populate(
    k: int, digests: List[bytes], eds_shares: np.ndarray, row_roots
) -> None:
    """Record every distinct original row of a freshly extended square."""
    codec = _active_codec()
    seen = set()
    items = []
    for r, d in enumerate(digests):
        if d in seen:
            continue
        seen.add(d)
        items.append((d, eds_shares[r, k:].tobytes(), row_roots[r]))
    _ROW_MEMO.insert_many(k, codec, items)


def extend_and_header(
    square: np.ndarray,
) -> Tuple[ExtendedDataSquare, "DataAvailabilityHeader"]:
    """The fused hot path: original square uint8[k,k,512] -> (EDS, DAH).

    One device program computes extension, 4k NMT roots and the data root
    (the reference does this as ExtendShares + NewDataAvailabilityHeader,
    app/prepare_proposal.go:65-77).  In the host regime (CPU backend, a
    host-only deployment) the same pipeline
    runs on the pooled native C++ legs instead: identical bytes, no
    multi-minute XLA CPU compile — and the row memo above skips the
    per-row work for rows whose bytes this process has extended before.
    """
    from celestia_tpu.utils.device import host_regime

    square = np.asarray(square, dtype=np.uint8)
    k = square.shape[0]
    from celestia_tpu.da import device_plane

    if device_plane.enabled():
        # device-resident plane (specs/device_pipeline.md): one program
        # emits EDS + NMT level stacks + root tree; only
        # the data root and the 4k axis roots cross to the host, and the
        # level stacks stay cached device-side for DAS serving.  First in
        # the routing order so forcing the plane on (tests, smoke) wins
        # over the host-regime fast paths; any fault poisons the plane
        # one-way and THIS call falls through to the byte-identical legs
        # below.
        try:
            return device_plane.extend_and_header(square)
        except Exception as e:
            device_plane.poison(f"device-resident extend failed: {e!r}")
    digests: Optional[List[bytes]] = None
    if host_regime() and _row_memo_applicable():
        with tracing.span("row_digests", k=k):
            digests = _row_digests(square)
        memoized = _try_memoized_extend(square, digests)
        if memoized is not None:
            return memoized
    if _host_native_available():
        try:
            eds, dah = _extend_and_header_host(square)
        except Exception as e:
            # graceful degradation (specs/robustness.md): a native fault
            # mid-run pins the library OFF (one-way; loud) and this very
            # call falls through to the table-GF jax path below — byte-
            # identical output, so the block being extended still commits
            # the same data root it would have cold
            from celestia_tpu.utils import native as _native

            _native.poison(f"extend_and_header native leg failed: {e!r}")
        else:
            if digests is not None:
                _memo_populate(k, digests, eds.shares, dah.row_roots)
            return eds, dah
    from celestia_tpu.utils import devprof

    with tracing.span("extend.jax", codec=_active_codec(), k=k, fused_roots=True):
        fn = _extend_and_roots_fn(k, _active_codec())
        arr = jnp.asarray(square)
        # devprof bracket: device-track span (enqueue vs device-drain,
        # per chip).  Inactive, the dispatch is a shared no-op and the
        # result stays ASYNC — the hot path keeps its fire-and-forget
        # shape.
        d = devprof.dispatch("extend_and_roots", k=k, codec=_active_codec())
        eds_d, row_roots, col_roots, data_root = d.done(fn(arr))
    # cost accounting OUTSIDE both the device bracket and the traced
    # extend.jax span: the one-time AOT compile must inflate neither
    # the device span nor the phase ms bench_check now watches
    devprof.note_compile("extend_and_roots", fn, (arr,))
    eds = ExtendedDataSquare(eds_d)  # stays on device until shares are read
    with tracing.span("roots", stage="fetch"):
        # materializing the root arrays forces the (async) device values
        # to host — on an attached chip this span IS the root fetch
        rr = np.asarray(row_roots)
        cc = np.asarray(col_roots)
        dah = DataAvailabilityHeader(
            tuple(rr[i].tobytes() for i in range(rr.shape[0])),
            tuple(cc[i].tobytes() for i in range(cc.shape[0])),
            np.asarray(data_root).tobytes(),
        )
    if digests is not None:
        # host-regime jax fallback: the "device" array is CPU-backed, so
        # materializing the shares is a host copy, not a device transfer
        _memo_populate(k, digests, eds.shares, dah.row_roots)
    return eds, dah


def extend_and_header_breakdown(square: np.ndarray):
    """extend_and_header with the transfer budget split out: returns
    (eds, dah, {"upload_ms", "compute_ms", "fetch_ms"}).

    Three device syncs instead of one fused call, so the total is a few
    RTTs WORSE than extend_and_header — use it to attribute time (bench
    breakdown, SURVEY §7 hard part c), never on the hot path."""
    from celestia_tpu.utils.telemetry import clock as _clock

    square = np.asarray(square, dtype=np.uint8)
    k = square.shape[0]
    from celestia_tpu.utils import devprof

    t0 = _clock()
    dev = jax.device_put(jnp.asarray(square))
    dev.block_until_ready()
    t1 = _clock()
    fn = _extend_and_roots_fn(k, _active_codec())
    out = fn(dev)
    jax.block_until_ready(out)
    t2 = _clock()
    eds_d, row_roots, col_roots, data_root = out
    rr = np.asarray(row_roots)
    cc = np.asarray(col_roots)
    droot = np.asarray(data_root).tobytes()
    t3 = _clock()
    # cost accounting after the LAST timestamp: the one-time AOT
    # compile must not leak into any breakdown window
    devprof.note_compile("extend_and_roots", fn, (dev,))
    dah = DataAvailabilityHeader(
        tuple(rr[i].tobytes() for i in range(rr.shape[0])),
        tuple(cc[i].tobytes() for i in range(cc.shape[0])),
        droot,
    )
    return ExtendedDataSquare(eds_d), dah, {
        "upload_ms": (t1 - t0) * 1000.0,
        "compute_ms": (t2 - t1) * 1000.0,
        "fetch_ms": (t3 - t2) * 1000.0,
    }


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
    k = square.size
    arr = square.to_array().reshape(k, k, SHARE_SIZE)
    return extend_and_header(arr)


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
