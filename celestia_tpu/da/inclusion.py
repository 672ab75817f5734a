"""Blob share commitments: merkle-mountain-range subtree roots (ADR-013).

Parity with go-square/inclusion.CreateCommitment as used by
/root/reference/x/blob/types/payforblob.go:49-56 (commitment creation) and
x/blob/types/blob_tx.go:98-107 (re-verification in ProcessProposal), and
with pkg/inclusion's commitment-from-EDS path conceptually: a blob's
commitment is the RFC-6962 merkle root over the NMT roots of its aligned
subtrees, whose widths form a merkle mountain range bounded by
SubtreeWidth(blob) — making the commitment independent of the square size
and equal to the subtree roots that appear in the row NMTs.

Cache key.  ``_COMMITMENT_CACHE`` holds commitments under two keys:

* ``(sha256(raw BlobTx), blob index, threshold)`` on the validator's paths
  (CheckTx, FilterTxs, ProcessProposal).  The caller has already hashed
  the raw envelope for its decoded-tx and signature caches, and the
  deterministic unmarshal fixes every blob's namespace, share version and
  bytes from those raw bytes: the key is still content-addressed and
  consensus-safe, and no blob is hashed again to find its entry.
  ``warm_commitments`` fills these entries for a batch of txs,
  ``tx_commitment`` reads one (computing it on a miss).
* ``(sha256(ns || version || data), threshold)`` for
  ``create_commitment(blob)``, which has no tx around it (the signer,
  tests).

Leaves.  The native pass (``native.blob_commitments``) lays each NMT
leaf, ``0x00 || ns || share``, out in a stack buffer straight from the
blob's bytes as it hashes it; no share array is built.  Without the
native library the same leaves come from ``shares.blob_shares_array``
and are hashed with hashlib: a consensus value keeps one layout, and the
tests pin the two paths to each other.

Threading.  The native pass runs on ``hostpool.cpu_threads()`` threads
(fewer for a small batch) through the native work queue, largest tasks
first; its helper threads stay parked between calls, since starting
threads per call cost more than the hashing on a TPU host.  The grain
comes from the input: a blob larger than the grain (the batch's leaves
over eight tasks per thread) is one task per mountain, and smaller blobs
are whole and share a task with their neighbours, so a block of hundreds
of one-share blobs runs tens of tasks.  The thread that finishes a blob's
last mountain folds its roots.  Subtree roots are host work: the
mountains are small trees, and per-blob device dispatches would cost a
round-trip and a shape-specific compile each.  The device keeps the big
batched work (the 4k axis trees).
"""

from __future__ import annotations

import hashlib
from itertools import chain
from typing import Iterable, List, Sequence, Tuple

import numpy as np

from celestia_tpu.appconsts import (
    DEFAULT_SUBTREE_ROOT_THRESHOLD,
    NAMESPACE_SIZE,
    SUPPORTED_SHARE_VERSIONS,
    round_down_power_of_two,
)
from celestia_tpu.da.blob import Blob
from celestia_tpu.da.shares import blob_shares_array, sparse_shares_needed
from celestia_tpu.da.square import subtree_width
from celestia_tpu.ops import nmt as nmt_ops
from celestia_tpu.utils import native
from celestia_tpu.utils.lru import LruCache


def _commitment_weigher(key, value) -> int:
    """Either key (its first item a 32-byte digest) -> a 32-byte commitment."""
    return len(key[0]) + len(value) + 64


# the same blob's commitment is needed in CheckTx, FilterTxs AND
# ProcessProposal (the reference recomputes it at each of those points);
# a content-addressed key makes a hit deterministic and consensus-safe.
# The thread-safe bounded LRU: every read/insert is atomic.
_COMMITMENT_CACHE = LruCache(
    "commitment", 8192, weigher=_commitment_weigher
)


def merkle_mountain_range_sizes(total: int, max_tree_size: int) -> List[int]:
    """Decompose ``total`` leaves into descending power-of-two mountains
    capped at ``max_tree_size``."""
    sizes: List[int] = []
    while total:
        if total >= max_tree_size:
            sizes.append(max_tree_size)
            total -= max_tree_size
        else:
            p = round_down_power_of_two(total)
            sizes.append(p)
            total -= p
    return sizes


def _check_blob(blob: Blob) -> None:
    """The blobs a commitment exists for (blob_shares_array's rules)."""
    if blob.share_version not in SUPPORTED_SHARE_VERSIONS:
        raise ValueError(f"unsupported share version {blob.share_version}")
    if not blob.data:
        raise ValueError("blob data must be non-empty")
    if len(blob.data) >= 1 << 32:
        raise ValueError("blob longer than a sequence length can state")


def _mountains(blob: Blob, subtree_root_threshold: int) -> List[int]:
    n = sparse_shares_needed(len(blob.data))
    return merkle_mountain_range_sizes(n, subtree_width(n, subtree_root_threshold))


def _blob_leaves(blob: Blob) -> np.ndarray:
    """ns-prefixed NMT leaves uint8[n, 541] of one blob: the layout the
    native pass writes leaf by leaf."""
    arr = blob_shares_array(blob.namespace, blob.data, blob.share_version)
    ns = np.broadcast_to(
        np.frombuffer(blob.namespace.raw, dtype=np.uint8),
        (arr.shape[0], NAMESPACE_SIZE),
    )
    return np.ascontiguousarray(np.concatenate([ns, arr], axis=1))


def _nmt_root_host(leaves: np.ndarray) -> bytes:
    """Root of one small NMT with hashlib."""
    level = [nmt_ops.leaf_digest_np(leaves[i].tobytes()) for i in range(len(leaves))]
    while len(level) > 1:
        level = [
            nmt_ops.combine_digests_np(level[2 * i], level[2 * i + 1])
            for i in range(len(level) // 2)
        ]
    return level[0]


def _commitment_host(blob: Blob, sizes: List[int]) -> bytes:
    """The hashlib path: per-mountain NMT roots, then the RFC-6962 fold."""
    leaves = _blob_leaves(blob)
    roots: List[bytes] = []
    offset = 0
    for s in sizes:
        roots.append(_nmt_root_host(leaves[offset : offset + s]))
        offset += s
    return nmt_ops.rfc6962_root_np(roots).tobytes()


def _compute_commitments(
    blobs: Sequence[Blob], subtree_root_threshold: int, nthreads=None
) -> Tuple[List[bytes], int]:
    """(commitments, tasks run) of checked blobs, bypassing the cache."""
    sizes = [_mountains(b, subtree_root_threshold) for b in blobs]
    if not native.available():
        # serial, one blob at a time
        return [_commitment_host(b, s) for b, s in zip(blobs, sizes)], len(blobs)
    out, tasks = native.blob_commitments(
        [b.data if isinstance(b.data, bytes) else bytes(b.data) for b in blobs],
        b"".join(b.namespace.raw for b in blobs),
        bytes(b.share_version for b in blobs),
        np.fromiter(chain.from_iterable(sizes), dtype=np.int32),
        np.cumsum([0] + [len(s) for s in sizes]),
        nthreads,
    )
    return [row.tobytes() for row in out], tasks


def _commitment_key(blob: Blob, subtree_root_threshold: int):
    return (
        hashlib.sha256(
            blob.namespace.raw + (blob.share_version & 0xFF).to_bytes(1, "big")
            + blob.data
        ).digest(),
        subtree_root_threshold,
    )


def create_commitment(
    blob: Blob, subtree_root_threshold: int = DEFAULT_SUBTREE_ROOT_THRESHOLD
) -> bytes:
    """32-byte share commitment of a blob, cached under its content key."""
    key = _commitment_key(blob, subtree_root_threshold)
    cached = _COMMITMENT_CACHE.get(key)
    if cached is not None:
        return cached
    _check_blob(blob)
    out = _compute_commitments([blob], subtree_root_threshold)[0][0]
    # concurrent misses on one key race benignly: both compute the SAME
    # bytes (the commitment is a pure function of the key), last put wins
    _COMMITMENT_CACHE.put(key, out)
    return out


def tx_commitment(
    tx_digest: bytes,
    index: int,
    blob: Blob,
    subtree_root_threshold: int = DEFAULT_SUBTREE_ROOT_THRESHOLD,
) -> Tuple[bytes, bool]:
    """Commitment of blob ``index`` of the BlobTx whose raw bytes hash
    to ``tx_digest`` (``blob`` is that blob, as unmarshalled from them).
    The second value is True when no warm pass had cached it and it was
    computed here."""
    key = (tx_digest, index, subtree_root_threshold)
    cached = _COMMITMENT_CACHE.get(key)
    if cached is not None:
        return cached, False
    _check_blob(blob)
    out = _compute_commitments([blob], subtree_root_threshold)[0][0]
    _COMMITMENT_CACHE.put(key, out)
    return out, True


def warm_commitments(
    txs: Iterable[Tuple[bytes, Sequence[Blob]]],
    subtree_root_threshold: int = DEFAULT_SUBTREE_ROOT_THRESHOLD,
) -> int:
    """Compute the commitments of every blob of ``txs`` in ONE native
    pass and cache each under its tx key, so the ``tx_commitment``
    lookups of tx validation all hit.  ``txs`` holds (tx digest, blobs)
    pairs, the digest being SHA-256 of the raw BlobTx the blobs were
    unmarshalled from.  Blobs already cached are skipped, and so are
    malformed ones (callers pass unvalidated envelopes; validate_blob_tx
    reports them).  Returns the number of tasks the pass ran."""
    keys: List[tuple] = []
    blobs: List[Blob] = []
    seen = set()
    for digest, tx_blobs in txs:
        for i, blob in enumerate(tx_blobs):
            key = (digest, i, subtree_root_threshold)
            if key in seen or key in _COMMITMENT_CACHE:
                continue
            try:
                _check_blob(blob)
            except ValueError:
                continue
            seen.add(key)
            keys.append(key)
            blobs.append(blob)
    if not blobs:
        return 0
    out, tasks = _compute_commitments(blobs, subtree_root_threshold)
    _COMMITMENT_CACHE.put_many(zip(keys, out))
    return tasks
