"""Multi-chip sharded block extension: shard_map over a jax.sharding.Mesh.

The TPU-native replacement for the reference's intra-block parallelism
(rsmt2d's goroutine row/col fan-out, SURVEY.md §2.3): rows of the original
square are sharded across the ``row`` mesh axis (ICI), whole squares are
batched across the ``data`` axis (multi-block validator catch-up,
BASELINE.json config #5).

Communication pattern (all XLA collectives over ICI; no reduction):

* Q1 (row parity): fully local — each device encodes its own row shard.
* Q2/Q3 (column parity): the top half is transposed across the row axis
  instead of contracted over it.  One ``all_to_all`` of the packed bytes
  hands device d all k rows of its 2k/R columns; it encodes those columns
  locally with the full encode matrix (the same GF(2) product as Q1), and
  a second ``all_to_all`` returns the parity rows row-sharded.  Each moves
  (k/R) x 2k x 512 bytes per device per square, (R-1)/R of it off-chip
  (:func:`ici_bytes`).
* Row-tree NMT roots: local.  Column-tree NMT roots: each device holds
  whole columns (both halves) for its 2k/R columns after the exchange, so
  it hashes them as whole trees and an ``all_gather`` of the roots
  (2k x 90 bytes) assembles them replicated.
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
    """Parity of each of r local rows (or, transposed, columns) of k
    shares: (r, k, B) -> (r, k, B)."""
    return rs.pack_bits(rs.matmul_gf2(G, rs.unpack_bits(q_top)))


def ici_bytes(k: int, n_row_shards: int, squares_per_device: int = 1) -> int:
    """Bytes each device sends to the others in the column-parity
    exchange of one dispatch: two all_to_alls of a (k/R, 2k, 512) uint8
    block per square, of which (R-1)/R leaves the device (0 at R=1)."""
    R = n_row_shards
    block = (k // R) * 2 * k * SHARE_SIZE
    return squares_per_device * 2 * block * (R - 1) // R


def _sharded_extend_and_roots(square_shard: jnp.ndarray, G: jnp.ndarray, k: int,
                              n_row_shards: int):
    """shard_map body: square_shard (k/R, k, 512) local rows -> per-device
    outputs (local EDS rows slice, replicated roots + data root)."""
    R = n_row_shards
    rows_local = k // R
    cols_local = 2 * k // R
    shard_id = jax.lax.axis_index("row")

    # --- Q1: local row extension ------------------------------------------
    q1 = _extend_rows_local(square_shard, G)  # (k/R, k, B)
    top = jnp.concatenate([square_shard, q1], axis=1)  # (k/R, 2k, B)

    # --- Q2/Q3: column parity on whole local columns ------------------------
    # Device d sends column block j of its rows to device j and receives
    # all k rows (in global row order) of its own columns
    # [d*2k/R, (d+1)*2k/R); it encodes them with the full G, and the
    # reverse exchange hands back parity rows [d*k/R, (d+1)*k/R).
    top_cols = jax.lax.all_to_all(
        top, "row", split_axis=1, concat_axis=0, tiled=True
    ).transpose(1, 0, 2)  # (2k/R, k, B)
    bot_cols = _extend_rows_local(top_cols, G)  # (2k/R, k, B)
    bot = jax.lax.all_to_all(
        bot_cols.transpose(1, 0, 2), "row", split_axis=0, concat_axis=1,
        tiled=True,
    )  # (k/R, 2k, B) local parity rows

    # --- NMT leaves with namespace prefixes --------------------------------
    # Q0 membership needs the global indexes of a block's two cell axes
    # (rows then columns, or columns then rows: the test is symmetric).
    parity_ns = jnp.asarray(_PARITY_NS)

    def prefixed(cells, idx0, idx1):
        own = cells[..., :NAMESPACE_SIZE]
        in_q0 = (idx0[:, None] < k) & (idx1[None, :] < k)
        pref = jnp.where(in_q0[..., None], own, jnp.broadcast_to(parity_ns, own.shape))
        return jnp.concatenate([pref, cells], axis=-1)

    whole_axis = jnp.arange(2 * k)
    own_rows = shard_id * rows_local + jnp.arange(rows_local)
    own_cols = shard_id * cols_local + jnp.arange(cols_local)

    # --- row-tree roots: fully local ---------------------------------------
    top_row_roots = nmt_ops.nmt_roots(prefixed(top, own_rows, whole_axis))
    bot_row_roots = nmt_ops.nmt_roots(prefixed(bot, k + own_rows, whole_axis))
    row_roots = jnp.concatenate(
        [
            jax.lax.all_gather(top_row_roots, "row", axis=0, tiled=True),
            jax.lax.all_gather(bot_row_roots, "row", axis=0, tiled=True),
        ],
        axis=0,
    )  # (2k, 90) replicated

    # --- column-tree roots: whole local columns, gathered roots ------------
    cols = jnp.concatenate([top_cols, bot_cols], axis=1)
    # (2k/R cols, 2k leaves in global row order, B)
    col_roots = jax.lax.all_gather(
        nmt_ops.nmt_roots(prefixed(cols, own_cols, whole_axis)),
        "row", axis=0, tiled=True,
    )  # (2k, 90) replicated

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

    Instrumented: an ``extend.sharded`` span with the mesh shape and the
    exchange's :func:`ici_bytes` as args (the live-path trace names the
    factoring) and a devprof dispatch
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
        codec=codec, ici_bytes=ici_bytes(k, row_ax),
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
    n, k = squares.shape[0], squares.shape[1]
    data_ax, row_ax = int(mesh.shape["data"]), int(mesh.shape["row"])
    return tracing.span(
        "extend.sharded", k=k, batch=n, mesh_data=data_ax, mesh_row=row_ax,
        codec=_active_codec(), ici_bytes=ici_bytes(k, row_ax, n // data_ax),
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
