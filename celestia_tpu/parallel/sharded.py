"""Multi-chip sharded block extension: shard_map over a jax.sharding.Mesh.

The TPU-native replacement for the reference's intra-block parallelism
(rsmt2d's goroutine row/col fan-out, SURVEY.md §2.3): rows of the original
square are sharded across the ``row`` mesh axis (ICI), whole squares are
batched across the ``data`` axis (multi-block validator catch-up,
BASELINE.json config #5).

Communication pattern (all XLA collectives over ICI):

* Q1 (row parity): fully local — each device encodes its own row shard.
* Q2/Q3 (column parity): the GF(2) contraction runs over the sharded row
  axis, so each device computes a partial bit-matmul against its slice of
  the encode matrix, reduced with ``psum_scatter`` so every device ends up
  holding only its shard of the parity rows (a reduce-scatter, not an
  all-reduce — 1/R the traffic).
* Row-tree NMT roots: local.  Column-tree NMT roots: each device reduces its
  local rows of every column to one subtree node, then an ``all_gather`` of
  those (tiny: R x 2k x 90 bytes) finishes the top log2(R) levels
  replicated on every device.
* Data root: row/col roots are all-gathered (2 x 2k x 90 bytes) and the
  RFC-6962 reduction is computed replicated — every device holds the same
  data root, the sharded analogue of the DAH hash at
  /root/reference/pkg/da/data_availability_header.go:92-108.
"""

from __future__ import annotations

from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from celestia_tpu.appconsts import NAMESPACE_SIZE, SHARE_SIZE
from celestia_tpu.ops import nmt as nmt_ops
from celestia_tpu.ops import rs
from celestia_tpu.ops.gf256 import active_codec as _active_codec
from celestia_tpu.ops.gf256 import encode_matrix_bits
from celestia_tpu.ops.nmt import NMT_DIGEST_SIZE, _PARITY_NS
from celestia_tpu.utils import devprof, tracing
from celestia_tpu.utils.lru import LruCache


def make_mesh(devices=None, data: int = 1, row: int = None) -> Mesh:
    """Build a ("data", "row") mesh over the given (or all) devices."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if row is None:
        row = n // data
    if data * row != n:
        raise ValueError(f"data*row = {data}*{row} != device count {n}")
    arr = np.asarray(devices).reshape(data, row)
    return Mesh(arr, ("data", "row"))


def _extend_rows_local(q_top: jnp.ndarray, G: jnp.ndarray) -> jnp.ndarray:
    """Row parity for the local row shard: (r, k, B) -> (r, k, B)."""
    return rs.pack_bits(rs.matmul_gf2(G, rs.unpack_bits(q_top)))


def _sharded_extend_and_roots(square_shard: jnp.ndarray, G: jnp.ndarray, k: int,
                              n_row_shards: int):
    """shard_map body: square_shard (k/R, k, 512) local rows -> per-device
    outputs (local EDS rows slice, replicated roots + data root)."""
    R = n_row_shards
    rows_local = k // R
    shard_id = jax.lax.axis_index("row")

    # --- Q1: local row extension ------------------------------------------
    q1 = _extend_rows_local(square_shard, G)  # (k/R, k, B)
    top = jnp.concatenate([square_shard, q1], axis=1)  # (k/R, 2k, B)

    # --- Q2/Q3: column parity via sharded contraction ---------------------
    # Columns hold k values spread across the row shards; the encode matrix
    # contracts over all 8k bit-rows.  Device d multiplies its (8k/R)-slice
    # of G's columns with its local bits, then psum_scatter sums partials
    # and scatters the 8k output bit-rows back across the row axis.
    bits_local = rs.unpack_bits(top.transpose(1, 0, 2))  # (2k, 8*k/R, B)
    g_cols = jax.lax.dynamic_slice_in_dim(
        G, shard_id * (8 * rows_local), 8 * rows_local, axis=1
    )  # (8k, 8k/R)
    partial = jnp.matmul(g_cols, bits_local, preferred_element_type=jnp.int32)
    # (2k, 8k, B) partial sums; reduce-scatter over the output bit-row axis.
    partial = partial.transpose(1, 0, 2)  # (8k, 2k, B)
    summed = jax.lax.psum_scatter(partial, "row", scatter_dimension=0, tiled=True)
    bot_bits = (summed & 1).astype(jnp.int8)  # (8k/R, 2k, B)
    bot = rs.pack_bits(
        bot_bits.reshape(rows_local, 8, 2 * k, SHARE_SIZE)
        .transpose(2, 0, 1, 3)
        .reshape(2 * k, 8 * rows_local, SHARE_SIZE)
    ).transpose(1, 0, 2)  # (k/R, 2k, B) local parity rows
    # Note: psum_scatter gives contiguous slices in shard order, so device d
    # holds parity rows [d*k/R, (d+1)*k/R) — same contiguous layout as Q0.

    # --- NMT leaves with namespace prefixes --------------------------------
    # Global row indexes of this device's rows: top half r0+i, bottom half
    # k + r0 + i; Q0 membership needs global (row, col) coordinates.
    r0 = shard_id * rows_local
    col_idx = jnp.arange(2 * k)
    parity_ns = jnp.asarray(_PARITY_NS)

    def prefixed(rows, global_row_offset):
        own = rows[..., :NAMESPACE_SIZE]
        grow = global_row_offset + jnp.arange(rows.shape[0])
        in_q0 = (grow[:, None] < k) & (col_idx[None, :] < k)
        pref = jnp.where(in_q0[..., None], own, jnp.broadcast_to(parity_ns, own.shape))
        return jnp.concatenate([pref, rows], axis=-1)

    top_leaves = prefixed(top, r0)  # (k/R, 2k, 541)
    bot_leaves = prefixed(bot, k + r0)

    # --- row-tree roots: fully local ---------------------------------------
    top_row_roots = nmt_ops.nmt_roots(top_leaves)  # (k/R, 90)
    bot_row_roots = nmt_ops.nmt_roots(bot_leaves)
    row_roots = jnp.concatenate(
        [
            jax.lax.all_gather(top_row_roots, "row", axis=0, tiled=True),
            jax.lax.all_gather(bot_row_roots, "row", axis=0, tiled=True),
        ],
        axis=0,
    )  # (2k, 90) replicated

    # --- column-tree roots: local subtree reduce + gathered finish ---------
    # Column-tree leaves are ordered by global row: [top rows..., bottom
    # rows...].  Device d holds two contiguous leaf blocks per column (its Q0
    # /Q1 rows and its Q2/Q3 rows); reduce each block to one subtree node,
    # all_gather the 2R nodes per column (in global order), finish locally.
    col_leaves_top = top_leaves.transpose(1, 0, 2)  # (2k cols, k/R, 541)
    col_leaves_bot = bot_leaves.transpose(1, 0, 2)

    def reduce_block(leaves):
        nodes = nmt_ops.leaf_digests(leaves)
        while nodes.shape[-2] > 1:
            nodes = nmt_ops.combine_level(nodes)
        return nodes[..., 0, :]  # (2k, 90)

    sub_top = reduce_block(col_leaves_top)
    sub_bot = reduce_block(col_leaves_bot)
    # gather per-device subtree nodes in global row order
    g_top = jax.lax.all_gather(sub_top, "row", axis=0)  # (R, 2k, 90)
    g_bot = jax.lax.all_gather(sub_bot, "row", axis=0)
    nodes = jnp.concatenate([g_top, g_bot], axis=0)  # (2R, 2k, 90)
    nodes = nodes.transpose(1, 0, 2)  # (2k cols, 2R, 90)
    while nodes.shape[-2] > 1:
        nodes = nmt_ops.combine_level(nodes)
    col_roots = nodes[..., 0, :]  # (2k, 90) replicated

    # --- data root ----------------------------------------------------------
    all_roots = jnp.concatenate([row_roots, col_roots], axis=0)  # (4k, 90)
    data_root = nmt_ops.rfc6962_root_pow2(all_roots)  # (32,) replicated

    eds_local = jnp.concatenate([top[:, None], bot[:, None]], axis=1)
    # (k/R, 2, 2k, B): [:, 0] = top-half rows, [:, 1] = bottom-half rows
    return eds_local, row_roots, col_roots, data_root


# program-handle cache on the unified LRU (celint R2's sanctioned
# surface): one jitted shard_map program per (mesh, k, batched, codec).
# 64 entries cover every power-of-two k x 2 legs x a few factorings; an
# eviction only costs a retrace, never wrong bytes.
_FN_CACHE = LruCache("sharded_fns", 64)


def _build_sharded_fn(mesh: Mesh, k: int, batched: bool, codec: str):
    R = mesh.shape["row"]
    if k % R:
        raise ValueError(f"square size {k} not divisible by row shards {R}")
    G = jnp.asarray(encode_matrix_bits(k, codec))
    body = partial(_sharded_extend_and_roots, G=G, k=k, n_row_shards=R)

    if not batched:
        fn = shard_map(
            body,
            mesh=mesh,
            in_specs=P("row", None, None),
            out_specs=(P("row", None, None, None), P(), P(), P()),
            check_vma=False,
        )
        return jax.jit(fn)

    vbody = jax.vmap(body)
    fn = shard_map(
        vbody,
        mesh=mesh,
        in_specs=P("data", "row", None, None),
        out_specs=(
            P("data", "row", None, None, None),
            P("data"),
            P("data"),
            P("data"),
        ),
        check_vma=False,
    )
    return jax.jit(fn)


def _sharded_fn(mesh: Mesh, k: int, batched: bool, codec: str):
    key = (mesh, k, batched, codec)
    fn = _FN_CACHE.get(key)
    if fn is None:
        # built OUTSIDE the cache lock (encode_matrix_bits is real work);
        # a racing double-build puts identical handles — last writer wins
        fn = _build_sharded_fn(mesh, k, batched, codec)
        _FN_CACHE.put(key, fn)
    return fn


def _extend_and_roots_sharded_device(
    square: np.ndarray, mesh: Mesh, *, record_stats: bool = True
):
    """Sharded fused hot path on a mesh, DEVICE-RESIDENT results:
    square uint8[k,k,512] -> (eds_dev uint8[2k,2k,512], row_roots,
    col_roots, data_root) — all four still on their chips.  The
    reassembly from the (k, 2, 2k, B) row-shard layout happens with a
    device-side concatenate, so the header paths below never pull the
    shares host-side at all (da/device_plane.py contract: the only D2H
    of the proposal path is the roots).

    Instrumented: an ``extend.sharded`` span with the mesh shape as args
    (the live-path trace names the factoring) and a devprof dispatch
    bracket that records the t1→t2 interval on EVERY chip the output is
    sharded across — device occupancy across chips is a measured number
    on the merged Perfetto timeline, not a guess.  ``record_stats=False``
    keeps warm-up extends (cli boot) out of the mesh provider's
    sharded-extends counter — the exposition reports LIVE extends."""
    square = np.asarray(square, dtype=np.uint8)
    k = square.shape[0]
    codec = _active_codec()
    data_ax, row_ax = int(mesh.shape["data"]), int(mesh.shape["row"])
    with tracing.span(
        "extend.sharded", k=k, mesh_data=data_ax, mesh_row=row_ax,
        codec=codec,
    ):
        sharding = NamedSharding(mesh, P("row", None, None))
        x = jax.device_put(jnp.asarray(square), sharding)
        devprof.record_transfer("extend_sharded", "h2d", int(square.nbytes))
        fn = _sharded_fn(mesh, k, False, codec)
        d = devprof.dispatch(
            "extend_sharded", multi_device=True,
            k=k, mesh=f"{data_ax}x{row_ax}", codec=codec,
        )
        out = d.done(fn(x))
        eds_local, row_roots, col_roots, data_root = out
        # device-side reassembly of the row-shard layout (the old host
        # np.concatenate reassembly cost two PCIe crossings per square)
        eds_dev = jnp.concatenate(
            [eds_local[:, 0], eds_local[:, 1]], axis=0
        )
    # cost accounting OUTSIDE the traced span (same placement contract
    # as da/dah.py): the one-time AOT compile lands in the
    # celestia_tpu_xla_* kernel table, never in the phase ms
    devprof.note_compile("extend_sharded", fn, (x,))
    if record_stats:
        from celestia_tpu.parallel import mesh as mesh_mod

        mesh_mod.record_sharded_extend()
    return eds_dev, row_roots, col_roots, data_root


def extend_and_roots_sharded(
    square: np.ndarray, mesh: Mesh, *, record_stats: bool = True
):
    """Sharded fused hot path on a mesh: square uint8[k,k,512] ->
    (eds uint8[2k,2k,512], row_roots, col_roots, data_root) as HOST
    arrays (the legacy contract).  All four results cross in ONE
    batched ``device_get`` — callers that can keep the EDS on device
    should use :func:`extend_and_header_sharded` instead, which fetches
    only the roots."""
    eds_dev, row_roots, col_roots, data_root = (
        _extend_and_roots_sharded_device(
            square, mesh, record_stats=record_stats
        )
    )
    with tracing.span("roots", stage="fetch", sharded=True):
        return devprof.fetch(
            "sharded_results", (eds_dev, row_roots, col_roots, data_root)
        )


def _batch_span(squares: np.ndarray, mesh: Mesh):
    """The ``extend.sharded`` span of one batched dispatch: the caller
    opens it, :func:`_extend_and_roots_sharded_batch_device` and the
    caller's own fetch open its children."""
    return tracing.span(
        "extend.sharded", k=squares.shape[1], batch=squares.shape[0],
        mesh_data=int(mesh.shape["data"]), mesh_row=int(mesh.shape["row"]),
        codec=_active_codec(),
    )


def _rows_to_eds(eds_local):
    """(n, k, 2, 2k, B) row-shard layout -> (n, 2k, 2k, B), on device."""
    return jnp.concatenate([eds_local[:, :, 0], eds_local[:, :, 1]], axis=1)


def _extend_and_roots_sharded_batch_device(
    squares: np.ndarray, mesh: Mesh, *, count_squares: int = None
):
    """Batched sharded path, DEVICE-RESIDENT results: uint8[n,k,k,512],
    n divisible by the data axis -> (eds_local[n,k,2,2k,512] in the
    row-shard layout (:func:`_rows_to_eds` reassembles it on device),
    row_roots[n,2k,90], col_roots[n,2k,90], data_roots[n,32]) with all
    four still on their chips.  One device dispatch for the whole batch
    — the state-sync catch-up leg (BASELINE.json config #5).  Runs
    inside the caller's :func:`_batch_span`; traced, the host waits on
    the device in ``extend.sharded.wait``.

    ``count_squares``: how many of the n inputs are REAL squares (the
    rest are data-axis padding the caller will drop) — only the real
    ones land in the mesh provider's sharded-extends counter."""
    n, k = squares.shape[0], squares.shape[1]
    codec = _active_codec()
    data_ax, row_ax = int(mesh.shape["data"]), int(mesh.shape["row"])
    with tracing.span("extend.sharded.upload", bytes=int(squares.nbytes)):
        sharding = NamedSharding(mesh, P("data", "row", None, None))
        x = jax.device_put(jnp.asarray(squares), sharding)
        devprof.record_transfer(
            "extend_sharded_batch", "h2d", int(squares.nbytes)
        )
    fn = _sharded_fn(mesh, k, True, codec)
    d = devprof.dispatch(
        "extend_sharded_batch", multi_device=True,
        k=k, batch=n, mesh=f"{data_ax}x{row_ax}", codec=codec,
    )
    with tracing.span("extend.sharded.launch"):
        out = fn(x)
    with tracing.span("extend.sharded.wait"):
        out = d.done(out)
    devprof.note_compile("extend_sharded_batch", fn, (x,))
    from celestia_tpu.parallel import mesh as mesh_mod

    mesh_mod.record_sharded_extend(
        batched=True, squares=n if count_squares is None else count_squares
    )
    return out


def extend_and_roots_sharded_batch(
    squares: np.ndarray, mesh: Mesh, *, count_squares: int = None
):
    """Batched sharded path with the legacy HOST-array contract (see
    :func:`_extend_and_roots_sharded_batch_device`): all four results
    cross in ONE batched ``device_get``."""
    squares = np.asarray(squares, dtype=np.uint8)
    with _batch_span(squares, mesh):
        eds_local, row_roots, col_roots, data_roots = (
            _extend_and_roots_sharded_batch_device(
                squares, mesh, count_squares=count_squares
            )
        )
        eds_dev = _rows_to_eds(eds_local)
    with tracing.span("roots", stage="fetch", sharded=True):
        return devprof.fetch(
            "sharded_results", (eds_dev, row_roots, col_roots, data_roots)
        )


# ---------------------------------------------------------------------------
# (EDS, DAH) entries for the live proposal lifecycle (state/app.py)
# ---------------------------------------------------------------------------


def _header_from_roots(row_roots: np.ndarray, col_roots: np.ndarray,
                       data_root: np.ndarray):
    """Fold sharded root arrays into a DataAvailabilityHeader whose hash
    IS the replicated data root the mesh computed (cross-checked: the
    sharded RFC-6962 fold and the host fold agree byte-for-byte per
    tests/_sharded_isolated.py, so this trusts the device fold)."""
    from celestia_tpu.da.dah import DataAvailabilityHeader

    n2 = row_roots.shape[0]
    return DataAvailabilityHeader(
        tuple(row_roots[i].tobytes() for i in range(n2)),
        tuple(col_roots[i].tobytes() for i in range(n2)),
        np.asarray(data_root).tobytes(),
    )


def extend_and_header_sharded(square: np.ndarray, mesh: Mesh):
    """The mesh twin of da/dah.extend_and_header: square uint8[k,k,512]
    -> (ExtendedDataSquare, DataAvailabilityHeader), byte-identical to
    the single-device path (the consensus-safety requirement)."""
    from celestia_tpu.da.dah import ExtendedDataSquare

    eds_dev, row_roots, col_roots, data_root = (
        _extend_and_roots_sharded_device(square, mesh)
    )
    # only the roots cross (one batched fetch, ~4k x 90 + 32 bytes);
    # the EDS stays sharded on its chips until .shares is actually read
    rr, cc, dr = devprof.fetch(
        "sharded_roots", (row_roots, col_roots, data_root)
    )
    return ExtendedDataSquare(eds_dev), _header_from_roots(rr, cc, dr)


def extend_block_sharded(square, mesh: Mesh):
    """The mesh twin of da/dah.extend_block: a da.square.Square in, one
    sharded dispatch, (EDS, DAH) out."""
    k = square.size
    arr = square.to_array().reshape(k, k, SHARE_SIZE)
    return extend_and_header_sharded(arr, mesh)


def extend_and_headers_sharded_batch(
    squares: np.ndarray, mesh: Mesh, *, count_squares: int = None
) -> List[Tuple[object, object]]:
    """Batched (EDS, DAH) list for n same-k squares in ONE dispatch.

    The caller pads the batch to a multiple of the ``data`` axis (the
    shard_map leading dim must divide it) and drops the pad results; the
    state-sync warm path (state/app.py warm_extends_batched) does both
    and passes ``count_squares`` so pads never inflate the counter.
    Traced, ``extend.sharded`` holds ``.upload``, ``.launch``, ``.wait``,
    ``.fetch_roots`` and ``.assemble`` (the device-side reassembly, the
    per-square slices and the DAHs).
    """
    from celestia_tpu.da.dah import ExtendedDataSquare

    squares = np.asarray(squares, dtype=np.uint8)
    n, k = squares.shape[0], squares.shape[1]
    with _batch_span(squares, mesh):
        eds_local, row_roots, col_roots, data_roots = (
            _extend_and_roots_sharded_batch_device(
                squares, mesh, count_squares=count_squares
            )
        )
        # one batched root fetch for the WHOLE warm batch; each square's
        # shares stay device-resident until someone reads them
        with tracing.span(
            "extend.sharded.fetch_roots",
            bytes=n * (4 * k * NMT_DIGEST_SIZE + 32),
        ):
            rr, cc, drs = devprof.fetch(
                "sharded_roots", (row_roots, col_roots, data_roots)
            )
        with tracing.span("extend.sharded.assemble"):
            eds_dev = _rows_to_eds(eds_local)
            out: List[Tuple[object, object]] = [
                (
                    ExtendedDataSquare(eds_dev[i]),
                    _header_from_roots(rr[i], cc[i], drs[i]),
                )
                for i in range(n)
            ]
    return out
