"""Namespaced Merkle Tree as a batched, level-synchronous device reduction.

TPU-native equivalent of celestiaorg/nmt as used by the reference's
``pkg/wrapper.ErasuredNamespacedMerkleTree`` (nmt_wrapper.go:26-114) and the
hasher specified in test/util/malicious/hasher.go:1-71 (the de-validated copy
that documents the exact digest format):

* leaf digest  = ns || ns || sha256(0x00 || ns || data)
* node digest  = minNs || maxNs || sha256(0x01 || left || right)
  with minNs = left.min and, because IgnoreMaxNamespace=true, maxNs =
  left.max when right.min == 0xFF..FF (an all-parity right subtree), else
  right.max.
* empty root   = zeros(29) || zeros(29) || sha256("")

Digests are 29+29+32 = 90 bytes.  Instead of per-leaf Push calls into one
tree object at a time, all 4k axis trees of the extended square reduce
together: one leaf-hash batch of shape [n_axes, n_leaves] then log2(n_leaves)
pairwise-combine levels — each level one fused sha256 batch.

The leaf prefix rule mirrors nmt_wrapper.go:93-114: Q0 cells are prefixed
with their own namespace, every cell outside Q0 with the parity namespace.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from celestia_tpu.appconsts import (
    NAMESPACE_SIZE,
    PARITY_SHARE_NAMESPACE_RAW,
    SHARE_SIZE,
)
from celestia_tpu.ops.sha256 import sha256

NMT_DIGEST_SIZE = 2 * NAMESPACE_SIZE + 32  # 90

_PARITY_NS = np.frombuffer(PARITY_SHARE_NAMESPACE_RAW, dtype=np.uint8)


def leaf_digests(leaves: jnp.ndarray) -> jnp.ndarray:
    """Hash namespaced leaves: uint8[..., L] -> uint8[..., 90].

    ``leaves`` already carry their namespace prefix (ns || data).  Ops
    carry the ``nmt_leaves`` name scope.
    """
    with jax.named_scope("nmt_leaves"):
        ns = leaves[..., :NAMESPACE_SIZE]
        prefix = jnp.zeros(leaves.shape[:-1] + (1,), dtype=jnp.uint8)  # 0x00
        h = sha256(jnp.concatenate([prefix, leaves], axis=-1))
        return jnp.concatenate([ns, ns, h], axis=-1)


def combine_level(nodes: jnp.ndarray) -> jnp.ndarray:
    """One reduction level: uint8[..., m, 90] -> uint8[..., m//2, 90].
    Ops carry the ``nmt_inner`` name scope."""
    with jax.named_scope("nmt_inner"):
        left = nodes[..., 0::2, :]
        right = nodes[..., 1::2, :]
        l_min = left[..., :NAMESPACE_SIZE]
        l_max = left[..., NAMESPACE_SIZE : 2 * NAMESPACE_SIZE]
        r_min = right[..., :NAMESPACE_SIZE]
        r_max = right[..., NAMESPACE_SIZE : 2 * NAMESPACE_SIZE]
        parity = jnp.asarray(_PARITY_NS)
        r_is_parity = jnp.all(r_min == parity, axis=-1, keepdims=True)  # IgnoreMaxNamespace
        max_ns = jnp.where(r_is_parity, l_max, r_max)
        prefix = jnp.ones(left.shape[:-1] + (1,), dtype=jnp.uint8)  # 0x01
        h = sha256(jnp.concatenate([prefix, left, right], axis=-1))
        return jnp.concatenate([l_min, max_ns, h], axis=-1)


def nmt_roots(leaves: jnp.ndarray) -> jnp.ndarray:
    """Full NMT reduction: uint8[..., n, L] namespaced leaves -> uint8[..., 90].

    n must be a power of two (EDS axes always are).
    """
    n = leaves.shape[-2]
    if n & (n - 1):
        raise ValueError(f"leaf count must be a power of two, got {n}")
    nodes = leaf_digests(leaves)
    while nodes.shape[-2] > 1:
        nodes = combine_level(nodes)
    return nodes[..., 0, :]


def eds_prefixed_leaves(eds: jnp.ndarray) -> jnp.ndarray:
    """Build the namespace-prefixed leaves of all row and column trees.

    eds: uint8[2k, 2k, SHARE_SIZE] -> uint8[2, 2k, 2k, 29+SHARE_SIZE]
    (axis 0: 0=row trees, 1=column trees; leaves ordered along each axis).

    Prefix = the share's own namespace inside Q0, the parity namespace
    everywhere else (nmt_wrapper.go:93-114).
    """
    n2 = eds.shape[0]
    k = n2 // 2
    own_ns = eds[..., :NAMESPACE_SIZE]  # (2k, 2k, 29)
    parity = jnp.broadcast_to(jnp.asarray(_PARITY_NS), own_ns.shape)
    r = jnp.arange(n2)
    in_q0 = (r[:, None] < k) & (r[None, :] < k)  # (2k, 2k)
    prefix = jnp.where(in_q0[..., None], own_ns, parity)
    rows = jnp.concatenate([prefix, eds], axis=-1)  # (2k rows, 2k leaves, 541)
    cols = rows.transpose(1, 0, 2)  # column trees
    return jnp.stack([rows, cols], axis=0)


def eds_nmt_roots(eds: jnp.ndarray) -> jnp.ndarray:
    """All 4k NMT axis roots of an EDS: uint8[2k,2k,512] -> uint8[2, 2k, 90]."""
    return nmt_roots(eds_prefixed_leaves(eds))


# one jitted whole-EDS root program shared by every eager caller; the
# race on first assignment is benign (two identical jit wrappers, one
# survives, the XLA executable cache is shared anyway)
_EDS_ROOTS_JIT = None


def eds_nmt_roots_device(eds) -> np.ndarray:
    """Jitted, devprof-instrumented DEVICE entry for the whole-EDS root
    pass: uint8[2k,2k,B] (host or device) -> uint8[2, 2k, 90] on the
    host.  The eager :func:`eds_nmt_roots` stays the traceable form for
    fused callers; this wrapper is the standalone dispatch
    (da/dah.new_data_availability_header's jax leg), bracketed with
    device timing + XLA cost accounting (utils/devprof.py)."""
    global _EDS_ROOTS_JIT
    from celestia_tpu.utils import devprof

    if _EDS_ROOTS_JIT is None:
        _EDS_ROOTS_JIT = jax.jit(eds_nmt_roots)
    arr = jnp.asarray(eds)
    d = devprof.dispatch("eds_nmt_roots", n2=int(arr.shape[0]))
    out = d.done(_EDS_ROOTS_JIT(arr))
    devprof.note_compile("eds_nmt_roots", _EDS_ROOTS_JIT, (arr,))
    return np.asarray(out)


def _nmt_roots_np_batch(leaves: np.ndarray) -> np.ndarray:
    """Host reduction of a batch of NMTs: uint8[T, n, L] -> uint8[T, 90].

    Mirror of :func:`nmt_roots` in numpy — the no-native fallback of
    :func:`eds_nmt_roots_host`.  Hashing runs SERIALLY (nthreads=1):
    this executes inside a pool worker, and fanning out again onto the
    same executor would deadlock it (all workers blocked on futures only
    they could run)."""
    from celestia_tpu.ops.sha256 import sha256_batch_host

    T, n, L = leaves.shape
    ns = leaves[:, :, :NAMESPACE_SIZE]
    prefix = np.zeros((T, n, 1), dtype=np.uint8)
    h = sha256_batch_host(
        np.concatenate([prefix, leaves], axis=-1).reshape(T * n, L + 1),
        # celint: allow(hostpool-discipline) — deliberate serial: this
        # runs INSIDE a pool worker; fanning out onto the same executor
        # would deadlock it (all workers blocked on futures only they
        # could run)
        nthreads=1,
    ).reshape(T, n, 32)
    nodes = np.concatenate([ns, ns, h], axis=-1)
    while nodes.shape[1] > 1:
        left, right = nodes[:, 0::2], nodes[:, 1::2]
        l_max = left[..., NAMESPACE_SIZE : 2 * NAMESPACE_SIZE]
        r_min = right[..., :NAMESPACE_SIZE]
        r_max = right[..., NAMESPACE_SIZE : 2 * NAMESPACE_SIZE]
        r_is_parity = np.all(r_min == _PARITY_NS, axis=-1, keepdims=True)
        max_ns = np.where(r_is_parity, l_max, r_max)
        one = np.ones(left.shape[:-1] + (1,), dtype=np.uint8)
        h = sha256_batch_host(
            np.concatenate([one, left, right], axis=-1).reshape(
                -1, 1 + 2 * NMT_DIGEST_SIZE
            ),
            # celint: allow(hostpool-discipline) — same nested-pool
            # deadlock avoidance as the leaf pass above
            nthreads=1,
        ).reshape(left.shape[:-1] + (32,))
        nodes = np.concatenate(
            [left[..., :NAMESPACE_SIZE], max_ns, h], axis=-1
        )
    return nodes[:, 0]


def eds_nmt_roots_host(eds: np.ndarray, nthreads=None) -> np.ndarray:
    """All 4k NMT axis roots on the HOST worker pool (no device, no XLA
    compile): uint8[2k, 2k, B] -> uint8[2, 2k, 90].

    The 4k trees are embarrassingly parallel; the native C++ entry
    shards them across the pool, and the numpy fallback shards
    tree-chunks across the same pool.  Byte-identical to
    :func:`eds_nmt_roots` (pinned by tests/test_sha_nmt.py and the
    thread-scaling tests in tests/test_leopard_codec.py)."""
    from celestia_tpu.utils import hostpool, native

    eds = np.ascontiguousarray(eds, dtype=np.uint8)
    n2 = eds.shape[0]
    if native.available():
        return native.eds_nmt_roots(eds, nthreads=nthreads).reshape(
            2, n2, NMT_DIGEST_SIZE
        )
    # numpy fallback: build the prefixed leaves, then reduce tree-chunks
    # on the shared pool
    k = n2 // 2
    own_ns = eds[..., :NAMESPACE_SIZE]
    parity = np.broadcast_to(_PARITY_NS, own_ns.shape)
    r = np.arange(n2)
    in_q0 = (r[:, None] < k) & (r[None, :] < k)
    prefix = np.where(in_q0[..., None], own_ns, parity)
    rows = np.concatenate([prefix, eds], axis=-1)
    trees = np.concatenate([rows, rows.transpose(1, 0, 2)], axis=0)
    workers = nthreads if nthreads is not None else hostpool.cpu_threads()
    workers = max(1, min(int(workers), trees.shape[0]))
    bounds = np.linspace(0, trees.shape[0], workers + 1).astype(int)
    chunks = hostpool.run_sharded(
        lambda t: _nmt_roots_np_batch(trees[bounds[t] : bounds[t + 1]]),
        range(workers),
    )
    return np.concatenate(chunks, axis=0).reshape(2, n2, NMT_DIGEST_SIZE)


def empty_root_np() -> np.ndarray:
    """EmptyRoot: zeros ns range + sha256 of the empty string."""
    import hashlib

    return np.frombuffer(
        b"\x00" * (2 * NAMESPACE_SIZE) + hashlib.sha256(b"").digest(), dtype=np.uint8
    )


# ---------------------------------------------------------------------------
# RFC-6962-style binary Merkle tree (tendermint/go-square merkle parity)
# used for the data root over the 4k NMT axis roots
# (pkg/da/data_availability_header.go:92-108) and share commitments.
# ---------------------------------------------------------------------------


def rfc6962_leaf_hashes(leaves: jnp.ndarray) -> jnp.ndarray:
    """uint8[..., n, L] -> uint8[..., n, 32]: sha256(0x00 || leaf)."""
    prefix = jnp.zeros(leaves.shape[:-1] + (1,), dtype=jnp.uint8)
    return sha256(jnp.concatenate([prefix, leaves], axis=-1))


def rfc6962_inner(left: jnp.ndarray, right: jnp.ndarray) -> jnp.ndarray:
    prefix = jnp.ones(left.shape[:-1] + (1,), dtype=jnp.uint8)
    return sha256(jnp.concatenate([prefix, left, right], axis=-1))


def rfc6962_root_pow2(leaves: jnp.ndarray) -> jnp.ndarray:
    """Merkle root of a power-of-two number of equal-length leaves.

    uint8[..., n, L] -> uint8[..., 32].  Matches tendermint's simple merkle
    for power-of-two counts (split point = n/2 at every level).
    """
    n = leaves.shape[-2]
    if n & (n - 1):
        raise ValueError(f"leaf count must be a power of two, got {n}")
    nodes = rfc6962_leaf_hashes(leaves)
    while nodes.shape[-2] > 1:
        nodes = rfc6962_inner(nodes[..., 0::2, :], nodes[..., 1::2, :])
    return nodes[..., 0, :]


def rfc6962_level_stack(leaves: jnp.ndarray) -> list:
    """All levels of the RFC-6962 tree over a power-of-two leaf count:
    ``[leaf hashes (..., n, 32), (..., n/2, 32), ..., root (..., 1, 32)]``.

    Traceable twin of da/proof.py's host ``merkle_level_tree`` (pinned
    byte-identical by tests/test_device_plane.py) — the device-resident
    plane keeps this stack on-chip so a data-root membership proof is a
    gather of ``levels[j][(index >> j) ^ 1]``, never a re-hash.  Ops
    carry the ``root_tree`` name scope.
    """
    n = leaves.shape[-2]
    if n & (n - 1):
        raise ValueError(f"leaf count must be a power of two, got {n}")
    with jax.named_scope("root_tree"):
        levels = [rfc6962_leaf_hashes(leaves)]
        while levels[-1].shape[-2] > 1:
            nodes = levels[-1]
            levels.append(
                rfc6962_inner(nodes[..., 0::2, :], nodes[..., 1::2, :])
            )
    return levels


def rfc6962_root_np(leaves: list) -> np.ndarray:
    """Host reference for arbitrary leaf counts (tendermint split rule:
    largest power of two strictly less than n)."""
    import hashlib

    def rec(items):
        if len(items) == 0:
            return hashlib.sha256(b"").digest()
        if len(items) == 1:
            return hashlib.sha256(b"\x00" + items[0]).digest()
        split = 1
        while split * 2 < len(items):
            split *= 2
        left = rec(items[:split])
        right = rec(items[split:])
        return hashlib.sha256(b"\x01" + left + right).digest()

    return np.frombuffer(rec([bytes(x) for x in leaves]), dtype=np.uint8)


def nmt_level_stack(leaves: jnp.ndarray) -> list:
    """All levels of the NMT: [leaf digests (n), level1 (n/2), ..., root (1)].

    The level stack is what proof generation needs (inner nodes at every
    aligned span) — the reference gets these via the NodeVisitor cache
    (pkg/inclusion/nmt_caching.go:80-124); here they fall out of the
    level-synchronous reduction for free.
    """
    n = leaves.shape[-2]
    if n & (n - 1):
        raise ValueError(f"leaf count must be a power of two, got {n}")
    levels = [leaf_digests(leaves)]
    while levels[-1].shape[-2] > 1:
        levels.append(combine_level(levels[-1]))
    return levels


def combine_digests_np(left: bytes, right: bytes) -> bytes:
    """Host-side NMT node combine (for proof verification)."""
    import hashlib

    l_min, l_max = left[:NAMESPACE_SIZE], left[NAMESPACE_SIZE : 2 * NAMESPACE_SIZE]
    r_min, r_max = right[:NAMESPACE_SIZE], right[NAMESPACE_SIZE : 2 * NAMESPACE_SIZE]
    max_ns = l_max if r_min == bytes(_PARITY_NS) else r_max
    h = hashlib.sha256(b"\x01" + left + right).digest()
    return l_min + max_ns + h


def leaf_digest_np(ns_prefixed_leaf: bytes) -> bytes:
    """Host-side NMT leaf digest (for proof verification)."""
    import hashlib

    ns = ns_prefixed_leaf[:NAMESPACE_SIZE]
    return ns + ns + hashlib.sha256(b"\x00" + ns_prefixed_leaf).digest()
