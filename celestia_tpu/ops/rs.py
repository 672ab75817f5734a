"""Device-side 2D Reed-Solomon extension and repair (JAX, MXU matmuls).

TPU-native equivalent of ``rsmt2d.ComputeExtendedDataSquare`` /
``rsmt2d.Repair`` as invoked by the reference at
/root/reference/pkg/da/data_availability_header.go:65-75 (encode) and its DAS
reconstruction surface (SURVEY.md §2.2).  Everything is integer arithmetic —
bit-exact across TPU/CPU backends and compiler versions, which is a consensus
-safety requirement (SURVEY.md §2.3 "determinism").

Representation: a square is ``uint8[k, k, 512]`` (row, column, byte).  GF(256)
linear maps are lifted to GF(2) bit-matrices (ops/gf256.py): shares are
unpacked to bit-planes, multiplied with an int8 0/1 matrix on the MXU with
int32 accumulation, reduced mod 2, and packed back to bytes.  The extension
is three batched matmuls (row parity, column parity, diagonal parity) fused
under one ``jit``.

Quadrant layout of the extended square (2k x 2k):

    Q0 | Q1        Q0 = original, Q1 = row parity,
    -------        Q2 = column parity, Q3 = parity of parity
    Q2 | Q3        (row- and column-extension commute; tested)
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from celestia_tpu.appconsts import SHARE_SIZE, is_power_of_two
from celestia_tpu.ops import gf256


def unpack_bits(x: jnp.ndarray) -> jnp.ndarray:
    """uint8[..., n, B] -> int8 bits[..., 8n, B]; bit row j*8+t = bit t of byte row j."""
    t = jnp.arange(8, dtype=jnp.uint8)
    bits = (x[..., :, None, :] >> t[None, :, None]) & 1  # (..., n, 8, B)
    shape = x.shape[:-2] + (8 * x.shape[-2], x.shape[-1])
    return bits.reshape(shape).astype(jnp.int8)


def pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """int bits[..., 8n, B] -> uint8[..., n, B] (inverse of unpack_bits)."""
    shape = bits.shape[:-2] + (bits.shape[-2] // 8, 8, bits.shape[-1])
    b = bits.reshape(shape).astype(jnp.int32)
    t = jnp.arange(8, dtype=jnp.int32)
    return (b << t[None, :, None]).sum(axis=-2).astype(jnp.uint8)


def matmul_gf2(G: jnp.ndarray, bits: jnp.ndarray) -> jnp.ndarray:
    """(G @ bits) mod 2 with int32 MXU accumulation; operands int8 0/1."""
    acc = jnp.matmul(G, bits, preferred_element_type=jnp.int32)
    return (acc & 1).astype(jnp.int8)


def _row_parity(square: jnp.ndarray, G: jnp.ndarray) -> jnp.ndarray:
    """(r, k, B) uint8 -> (r, k, B) uint8 parity of each row."""
    bits = unpack_bits(square)  # (r, 8k, B)
    return pack_bits(matmul_gf2(G, bits))


def _extend(square: jnp.ndarray, G: jnp.ndarray) -> jnp.ndarray:
    """Core extension: uint8[k, k, B] -> uint8[2k, 2k, B]."""
    q0 = square
    q1 = _row_parity(q0, G)  # row parity
    q2 = _row_parity(q0.transpose(1, 0, 2), G).transpose(1, 0, 2)  # col parity
    q3 = _row_parity(q1.transpose(1, 0, 2), G).transpose(1, 0, 2)  # parity of parity
    top = jnp.concatenate([q0, q1], axis=1)
    bottom = jnp.concatenate([q2, q3], axis=1)
    return jnp.concatenate([top, bottom], axis=0)


@lru_cache(maxsize=None)
def _extend_fn(k: int, codec: str):
    # codec required — see _repair_verify_fn
    G = jnp.asarray(gf256.encode_matrix_bits(k, codec))
    return jax.jit(partial(_extend, G=G))


def extend_square(square) -> jnp.ndarray:
    """Extend an original square uint8[k, k, 512] to its EDS uint8[2k, 2k, 512].

    Device entry point: carries the devprof dispatch bracket (device
    track + cost accounting; a no-op when profiling is inactive — the
    result stays ASYNC then, exactly as before)."""
    from celestia_tpu.utils import devprof

    square = jnp.asarray(square, dtype=jnp.uint8)
    k = square.shape[0]
    if square.shape[1] != k or not is_power_of_two(k):
        raise ValueError(f"square must be (k, k, B) with k a power of two, got {square.shape}")
    fn = _extend_fn(k, gf256.active_codec())
    d = devprof.dispatch("rs_extend", k=k)
    out = d.done(fn(square))
    devprof.note_compile("rs_extend", fn, (square,))
    return out


@lru_cache(maxsize=None)
def _extend_batched_fn(k: int, codec: str):
    G = jnp.asarray(gf256.encode_matrix_bits(k, codec))
    return jax.jit(jax.vmap(partial(_extend, G=G)))


def extend_squares_batched(squares) -> jnp.ndarray:
    """Extend a batch uint8[n, k, k, 512] -> uint8[n, 2k, 2k, 512]."""
    from celestia_tpu.utils import devprof

    squares = jnp.asarray(squares, dtype=jnp.uint8)
    k = squares.shape[1]
    if squares.ndim != 4 or squares.shape[2] != k or not is_power_of_two(k):
        raise ValueError(
            f"batch must be (n, k, k, B) with k a power of two, got {squares.shape}"
        )
    fn = _extend_batched_fn(k, gf256.active_codec())
    d = devprof.dispatch("rs_extend_batched", k=k, n=int(squares.shape[0]))
    out = d.done(fn(squares))
    devprof.note_compile("rs_extend_batched", fn, (squares,))
    return out


# ---------------------------------------------------------------------------
# Device-side repair (rsmt2d.Repair on the MXU)
#
# Key observation: which axes become solvable in which order depends ONLY on
# the boolean availability mask, never on share values.  So the host
# simulates the peeling schedule on bools (microseconds), uploads the tiny
# per-phase index tensors (known positions + update masks, ~KB), and the
# device runs the entire data path: Lagrange decode-matrix construction in
# the log domain, the GF(2) bit-lift, and the batched decode as int8 MXU
# matmuls — the same arithmetic as the encode path, so it is bit-exact with
# the host reference.  No share byte crosses the PCIe/ICI link between
# phases.
# ---------------------------------------------------------------------------

def _gf_tables_dev(codec: str = None):
    # created per call, NOT cached: importing this module must not
    # initialize a jax backend, and a cached array captured inside a
    # traced scope would leak a tracer into later traces.  XLA folds
    # the repeated constants, so per-call creation costs nothing.
    exp, log = gf256.field_tables(codec)
    return (
        jnp.asarray(exp, dtype=jnp.int32),
        jnp.asarray(log, dtype=jnp.int32),
    )


def _decode_matrices_dev(
    known: jnp.ndarray, k: int, codec: str = None
) -> jnp.ndarray:
    """Device port of gf256.decode_matrices_batch: known uint8[n, k]
    (distinct POSITIONS per row — guaranteed by the host scheduler) ->
    D uint8[n, 2k, k].  Position -> field-point mapping is XOR with k
    under the leopard codec (gf256.position_points)."""
    codec = gf256._resolve(codec)
    exp, log = _gf_tables_dev(codec)
    xor_const = k if codec == gf256.CODEC_LEOPARD else 0
    src = known.astype(jnp.int32) ^ xor_const  # [n, k]
    dst = jnp.arange(2 * k, dtype=jnp.int32) ^ xor_const
    diff_ss = src[:, None, :] ^ src[:, :, None]  # [n, j, m]
    diff_ss = diff_ss.at[:, jnp.arange(k), jnp.arange(k)].set(1)
    denom_log = log[diff_ss].sum(axis=2) % 255  # [n, j]
    diff_ds = dst[None, :, None] ^ src[:, None, :]  # [n, i, m]
    zero_mask = diff_ds == 0
    safe = jnp.where(zero_mask, 1, diff_ds)
    log_all = log[safe]  # [n, i, m]
    total_log = log_all.sum(axis=2)  # [n, i]
    has_zero = zero_mask.any(axis=2)  # [n, i]
    num_log = (total_log[:, :, None] - log_all) % 255  # [n, i, j]
    lagrange = exp[(num_log - denom_log[:, None, :]) % 255]
    return jnp.where(
        has_zero[:, :, None], zero_mask.astype(jnp.uint8), lagrange
    ).astype(jnp.uint8)


@lru_cache(maxsize=None)
def _bit_basis(codec: str):
    """B[u, s, t] = bit s of gf_mul(2^u, 2^t) — the GF(2) lift is LINEAR
    in the operand's bits: M(a)[s,t] = XOR_u a_u * B[u,s,t].  Expanding a
    matrix therefore needs no table gathers (slow on TPU), just one tiny
    contraction over u against this 8x8x8 constant.  Holds in both codec
    representations (the Cantor-index map is GF(2)-linear)."""
    powers = np.uint8(1) << np.arange(8, dtype=np.uint8)
    prod = gf256.gf_mul(powers[:, None], powers[None, :], codec)  # [u, t]
    s = np.arange(8, dtype=np.uint8)
    return ((prod[:, None, :] >> s[None, :, None]) & 1).astype(np.int8)


def _bit_expand_dev(D: jnp.ndarray, codec: str = None) -> jnp.ndarray:
    """Device port of gf256.bit_expand_matrix, batched: uint8[n, m, c] ->
    int8 0/1 [n, 8m, 8c].  Gather-free: unpack D's bits, contract with
    the constant bit basis, mod 2."""
    n, m, c = D.shape
    u = jnp.arange(8, dtype=jnp.uint8)
    a_bits = ((D[:, :, :, None] >> u) & 1).astype(jnp.int8)  # [n, m, c, u]
    B = jnp.asarray(_bit_basis(gf256._resolve(codec)))  # [u, s, t]
    acc = jnp.einsum(
        "nmcu,ust->nmsct", a_bits, B, preferred_element_type=jnp.int32
    )
    out = (acc & 1).astype(jnp.int8)
    return out.reshape(n, 8 * m, 8 * c)


def _decode_axes_dev(
    data: jnp.ndarray, known: jnp.ndarray, k: int, chunk: int,
    codec: str = None,
) -> jnp.ndarray:
    """Decode ALL 2k axes of one orientation: data uint8[2k, 2k, B]
    (axis-major), known uint8[2k, k] -> decoded uint8[2k, 2k, B].
    Chunked over axes to bound the D_bits working set."""
    codec = gf256._resolve(codec)
    n2 = 2 * k
    B = data.shape[2]
    X = jnp.take_along_axis(data, known[:, :, None].astype(jnp.int32), axis=1)

    def one_chunk(args):
        Xc, knownc = args  # [chunk, k, B], [chunk, k]
        D = _decode_matrices_dev(knownc, k, codec)  # [chunk, 2k, k]
        D_bits = _bit_expand_dev(D, codec)  # [chunk, 16k, 8k]
        X_bits = unpack_bits(Xc)  # [chunk, 8k, B]
        out_bits = matmul_gf2(D_bits, X_bits)  # [chunk, 16k, B]
        return pack_bits(out_bits)  # [chunk, 2k, B]

    n_chunks = max(1, n2 // chunk)
    chunk = n2 // n_chunks
    Xr = X.reshape(n_chunks, chunk, k, B)
    Kr = known.reshape(n_chunks, chunk, k)
    decoded = jax.lax.map(one_chunk, (Xr, Kr))  # [n_chunks, chunk, 2k, B]
    return decoded.reshape(n2, n2, B)


def _repair_phases(
    eds: jnp.ndarray,
    row_known: jnp.ndarray,  # [P, 2k, k]
    row_mask: jnp.ndarray,  # [P, 2k] bool
    col_known: jnp.ndarray,
    col_mask: jnp.ndarray,
    k: int,
    chunk: int,
    codec: str = None,
) -> jnp.ndarray:
    """P peeling phases (rows then columns each), fully on device."""
    codec = gf256._resolve(codec)
    P = row_known.shape[0]
    for p in range(P):  # P is static: unrolled into one XLA program
        decoded = _decode_axes_dev(eds, row_known[p], k, chunk, codec)
        eds = jnp.where(row_mask[p][:, None, None], decoded, eds)
        edsT = eds.transpose(1, 0, 2)
        decodedT = _decode_axes_dev(edsT, col_known[p], k, chunk, codec)
        edsT = jnp.where(col_mask[p][:, None, None], decodedT, edsT)
        eds = edsT.transpose(1, 0, 2)
    return eds


def _repair_verify(
    eds, avail, row_known, row_mask, col_known, col_mask, *, k: int,
    chunk: int, with_roots: bool, codec: str = None,
):
    """Phases + BOTH byzantine checks (+ axis roots) fused into ONE
    device program — a repairing light/full node pays a single round trip
    for everything except the (optional) bulk fetch of the square.

    eds arrives with unavailable cells zeroed, so comparing the repaired
    square against it AT AVAILABLE CELLS is exactly the provided-share
    consistency check (rsmt2d ErrByzantine for shares the peeling
    schedule overwrote)."""
    codec = gf256._resolve(codec)
    repaired = _repair_phases(
        eds, row_known, row_mask, col_known, col_mask, k=k, chunk=chunk,
        codec=codec,
    )
    G = jnp.asarray(gf256.encode_matrix_bits(k, codec))
    recomputed = _extend(repaired[:k, :k], G)
    mismatch = jnp.any(repaired != recomputed, axis=2)  # [2k, 2k] bool
    provided_mismatch = avail & jnp.any(repaired != eds, axis=2)
    if with_roots:
        from celestia_tpu.ops import nmt as nmt_ops

        roots = nmt_ops.eds_nmt_roots(repaired)  # [2, 2k, 90]
    else:
        roots = jnp.zeros((2, 2 * k, 90), dtype=jnp.uint8)
    return repaired, mismatch, provided_mismatch, roots


# Honest DAS masks peel in 1-2 phases; each extra phase unrolls another
# full decode pipeline into the XLA program.  Bounding the device path (and
# the executable cache) stops an adversarial staircase mask from forcing
# unbounded multi-second recompiles — deeper peels take the host path.
_MAX_DEVICE_PHASES = 4


@lru_cache(maxsize=8)
def _repair_verify_fn(
    k: int, phases: int, chunk: int, with_roots: bool, codec: str
):
    # codec is REQUIRED (resolved by the caller): a None default resolved
    # in here would cache the first-build codec under key None and serve
    # a stale program after a codec switch
    return jax.jit(
        partial(
            _repair_verify, k=k, chunk=chunk, with_roots=with_roots,
            codec=codec,
        )
    )


def _simulate_schedule(avail: np.ndarray, k: int):
    """Peel the availability mask on the host (bools only): returns the
    per-phase (row_known, row_mask, col_known, col_mask) tensors the
    device program consumes.  Raises if the mask cannot reconstruct."""
    n2 = 2 * k
    avail = avail.copy()
    row_known, row_mask, col_known, col_mask = [], [], [], []

    def plan(mask2d):
        counts = mask2d.sum(axis=1)
        solvable = (counts >= k) & (counts < n2)
        # first k available positions per axis (arbitrary valid points for
        # unsolvable axes — their results are masked out)
        order = np.argsort(~mask2d, axis=1, kind="stable")
        known = np.sort(order[:, :k], axis=1).astype(np.uint8)
        known[~solvable] = np.arange(k, dtype=np.uint8)[None, :]
        return known, solvable

    while not avail.all():
        rk, rm = plan(avail)
        avail[rm] = True
        ck, cm = plan(avail.T)
        avail[:, cm] = True
        if not (rm.any() or cm.any()):
            raise ValueError(
                "repair stalled: insufficient available cells to reconstruct"
            )
        row_known.append(rk)
        row_mask.append(rm)
        col_known.append(ck)
        col_mask.append(cm)
    if not row_known:  # nothing missing: zero phases
        return None
    return (
        np.stack(row_known),
        np.stack(row_mask),
        np.stack(col_known),
        np.stack(col_mask),
    )


def _repair_plan(avail: np.ndarray, with_roots: bool):
    """The host peel of ``avail`` and the device program that consumes
    it: ``(fn, (row_known, row_mask, col_known, col_mask))``, with ``fn``
    None when the mask needs more than _MAX_DEVICE_PHASES phases (the
    host path takes those)."""
    n2 = avail.shape[0]
    k = n2 // 2
    schedule = _simulate_schedule(avail, k)  # bools only, ~1 ms at k=128
    if schedule is None:  # nothing missing: zero phases
        rk = np.zeros((0, n2, k), dtype=np.uint8)
        rm = np.zeros((0, n2), dtype=bool)
        schedule = (rk, rm, rk.copy(), rm.copy())
    phases = schedule[0].shape[0]
    if phases > _MAX_DEVICE_PHASES:
        return None, schedule
    chunk = min(n2, max(1, 8192 // k))  # ~bounded D_bits working set
    # codec resolved HERE (not inside the lru_cached builder) so a codec
    # switch can never serve a stale cached program
    fn = _repair_verify_fn(k, phases, chunk, with_roots, gf256.active_codec())
    return fn, schedule


def repair_program(available: np.ndarray, with_roots: bool = True):
    """The program ``repair_square_device`` dispatches for this
    availability mask, with its arguments as ``jax.ShapeDtypeStruct``s:
    ``(fn, args)`` for an ahead-of-time ``fn.lower(*args).compile()``
    that lands on the same compile-cache entry."""
    avail = np.asarray(available, dtype=bool)
    fn, schedule = _repair_plan(avail, with_roots)
    if fn is None:
        raise ValueError(
            "this mask peels in more than "
            f"{_MAX_DEVICE_PHASES} phases: it repairs on the host"
        )
    n2 = avail.shape[0]
    args = (
        jax.ShapeDtypeStruct((n2, n2, SHARE_SIZE), jnp.uint8),
        jax.ShapeDtypeStruct(avail.shape, jnp.bool_),
    ) + tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in schedule)
    return fn, args


def repair_square_device(
    eds: np.ndarray,
    available: np.ndarray,
    row_roots: np.ndarray = None,
    col_roots: np.ndarray = None,
    breakdown: dict = None,
    return_device: bool = False,
) -> np.ndarray:
    """rsmt2d.Repair on the accelerator (VERDICT r2 #6 / BASELINE #4).

    Same contract as :func:`repair_square` — reconstruct, then prove the
    result is the unique codeword matching everything the caller provided
    (ByzantineError otherwise) and the committed DAH roots when given —
    but the decode matmuls, BOTH byzantine checks (codeword consistency
    AND provided-share agreement) and the NMT roots all run as ONE fused
    device program; the host only peels the boolean mask and ships index
    tensors, then fetches the small verdicts in one batched round trip.
    The bulk upload is kicked asynchronously before the host peel, so
    the transfer streams while the schedule is computed.

    ``return_device=True`` is the DOCUMENTED DEFAULT for DAS-serving
    callers: the repaired square stays in device memory (shares are
    re-served from there) with no loss of verification, skipping the
    bulk device->host fetch entirely.  Fetch only when the caller
    actually consumes the bytes host-side.

    breakdown (optional dict) receives schedule (overlapped with the
    upload) / upload_compute / verdict_fetch millisecond attributions,
    plus bulk_fetch_ms when the square is fetched."""
    import time as _t

    provided = np.asarray(eds, dtype=np.uint8)
    avail = np.asarray(available, dtype=bool)
    n2 = provided.shape[0]
    k = n2 // 2
    if provided.shape[:2] != (n2, n2) or avail.shape != (n2, n2):
        raise ValueError("eds must be (2k, 2k, B) with matching availability mask")
    masked = np.where(avail[:, :, None], provided, 0).astype(np.uint8)

    with_roots = row_roots is not None or col_roots is not None
    t0 = _t.time()
    fn, (rk, rm, ck, cm) = _repair_plan(avail, with_roots)
    if fn is None:
        # degenerate (adversarial) masks: don't let each one compile its
        # own P-phase device program — the host path handles any depth.
        # (The bulk upload is dispatched AFTER this check so the
        # fallback never pays a wasted 8 MiB transfer.)
        out = repair_square(eds, available, row_roots, col_roots)
        return jnp.asarray(out) if return_device else out
    # dispatch the bulk upload asynchronously (jnp.asarray starts the
    # transfer; nothing blocks on it) so the ~8 MiB square streams while
    # the index tensors upload and the program dispatches (VERDICT r3 #6)
    masked_dev = jnp.asarray(masked)
    t1 = _t.time()
    from celestia_tpu.utils import devprof

    fn_args = (
        masked_dev, jnp.asarray(avail),
        jnp.asarray(rk), jnp.asarray(rm),
        jnp.asarray(ck), jnp.asarray(cm),
    )
    d = devprof.dispatch("rs_repair_verify", k=k, phases=rk.shape[0])
    out = fn(*fn_args)
    d.done(out)
    repaired_dev, mismatch_dev, provided_mismatch_dev, roots_dev = out
    # celint: allow(host-sync) — t2 is the compute/fetch timing boundary of the repair breakdown; d.done() above only drains when profiling is armed, this sync must hold either way
    jax.block_until_ready(repaired_dev)
    t2 = _t.time()
    # ONE batched fetch of every verdict: per-array np.asarray pays a
    # full round trip each; device_get dispatches them together
    fetched = jax.device_get(
        (mismatch_dev, provided_mismatch_dev)
        + ((roots_dev,) if with_roots else ())
    )
    mismatch_axes, provided_mismatch = fetched[0], fetched[1]
    roots = fetched[2] if with_roots else None
    t3 = _t.time()
    # cost accounting after the LAST timestamp: the one-time AOT
    # compile must not be misattributed to upload/compute/fetch
    devprof.note_compile("rs_repair_verify", fn, fn_args)
    if breakdown is not None:
        breakdown.update(
            schedule_ms=(t1 - t0) * 1000.0,  # overlapped with the upload
            upload_compute_ms=(t2 - t1) * 1000.0,
            verdict_fetch_ms=(t3 - t2) * 1000.0,
            upload_overlapped=True,
        )
    if mismatch_axes.any():
        bad = np.nonzero(mismatch_axes)
        raise ByzantineError(
            f"inconsistent erasure coding at cells {list(zip(*bad))[:8]}"
        )
    if provided_mismatch.any():
        bad = np.nonzero(provided_mismatch)
        raise ByzantineError(
            f"provided shares disagree with the reconstructed codeword at "
            f"cells {list(zip(*bad))[:8]}"
        )
    if with_roots:
        for name, axis_roots, got in (
            ("row", row_roots, roots[0]),
            ("col", col_roots, roots[1]),
        ):
            if axis_roots is None:
                continue
            axis_roots = np.asarray(axis_roots, dtype=np.uint8)
            if axis_roots.shape != got.shape:
                raise ValueError(
                    f"{name}_roots must be {got.shape}, got {axis_roots.shape}"
                )
            bad = np.nonzero((axis_roots != got).any(axis=1))[0]
            if len(bad):
                raise ByzantineError(
                    f"reconstructed {name} axes {bad.tolist()[:8]} do not "
                    f"match the committed NMT roots"
                )
    if return_device:
        # all verification already ran on device; the caller keeps the
        # square in device memory (no bulk fetch)
        return repaired_dev
    t5 = _t.time()
    repaired = np.asarray(repaired_dev)
    if breakdown is not None:
        breakdown["bulk_fetch_ms"] = (_t.time() - t5) * 1000.0
    return repaired


# ---------------------------------------------------------------------------
# Repair (rsmt2d.Repair parity): iterative row/column reconstruction
# ---------------------------------------------------------------------------


def _gf_matmul_axes_host(
    D: np.ndarray, X: np.ndarray, nthreads=None
) -> np.ndarray:
    """out[i] = D[i] x X[i] over GF(256): threaded native C++ when
    available (sharded across the host pool), vectorized numpy log-table
    fallback otherwise."""
    from celestia_tpu.utils import native

    if native.available():
        return native.gf_matmul_axes(D, X, nthreads=nthreads)
    exp, log = gf256.field_tables()  # active codec's representation
    n, R, k = D.shape
    B = X.shape[2]
    out = np.zeros((n, R, B), dtype=np.uint8)
    logX = log[X.astype(np.int32)]  # [n, k, B]
    for i in range(n):
        acc = out[i]
        for j in range(k):
            col = D[i, :, j]
            nz = col != 0
            if not nz.any():
                continue
            prod = exp[
                (log[col[nz].astype(np.int32)][:, None] + logX[i, j][None, :])
                % 255
            ].astype(np.uint8)
            prod[:, X[i, j] == 0] = 0
            acc[nz] ^= prod
    return out


class ByzantineError(ValueError):
    """The available shares are not a consistent Reed-Solomon codeword
    (rsmt2d ErrByzantine parity): a malicious proposer published shares that
    disagree with the polynomial through the rest of their row/column."""


def repair_square(
    eds: np.ndarray,
    available: np.ndarray,
    row_roots: np.ndarray = None,
    col_roots: np.ndarray = None,
    nthreads: int = None,
) -> np.ndarray:
    """Reconstruct a full EDS from a partial one (rsmt2d.Repair parity).

    eds: uint8[2k, 2k, B] with garbage in unavailable cells;
    available: bool[2k, 2k] marking cells present;
    row_roots / col_roots: optional uint8[2k, 90] committed NMT axis roots
    from the block's DAH.  When given, every axis of the reconstructed
    square is re-hashed and checked against its commitment — without this,
    a malicious provider supplying k internally-consistent but *wrong*
    shares per axis would yield a "successful" reconstruction that does not
    match the block (rsmt2d.Repair verifies rebuilt axes against the
    committed roots for exactly this reason).

    Iteratively solves every row/column with >= k available cells, batching
    axes that share an availability mask into one device matmul, until the
    square is complete.  Raises ValueError if reconstruction stalls
    (insufficient data — fewer than k cells in every incomplete axis), and
    :class:`ByzantineError` if the provided shares are not a consistent
    codeword: after completion the square is re-extended from Q0 and every
    originally-available cell must match what was provided (this also
    catches inconsistent fully-available axes that need no solving), then
    checked against the committed roots when supplied.

    ``nthreads`` (None = the process pool size, ``--cpu-threads``) fans
    the per-phase decode, the re-extension and the NMT root verification
    out over the host worker pool: within a phase every solvable axis is
    independent, so the decode batch, the verify extension and the 4k
    root trees all shard cleanly.  Threaded and single-threaded repairs
    are byte-identical (tests/test_leopard_codec.py).
    """
    from celestia_tpu.utils import native as _nat

    # LAZY snapshot of the provided shares: the leopard decoder only
    # ever writes ERASED cells, so provided bytes survive in eds and the
    # final eds == recomputed check subsumes the provided-share check.
    # Only the generic matrix path overwrites whole axes (recomputed
    # bytes over provided ones) — it snapshots before its first write.
    # Skipping the eager copy saves a full square memcpy per repair.
    original_eds: np.ndarray = None
    eds = np.array(eds, dtype=np.uint8, copy=True)
    avail = np.array(available, dtype=bool, copy=True)
    n2 = eds.shape[0]
    k = n2 // 2
    if eds.shape[:2] != (n2, n2) or avail.shape != (n2, n2):
        raise ValueError("eds must be (2k, 2k, B) with matching availability mask")
    # Zero out unavailable cells so "garbage" can't leak through masks.
    eds[~avail] = 0

    while not avail.all():
        progress = False
        for axis in (0, 1):  # rows then columns
            data = eds if axis == 0 else eds.transpose(1, 0, 2)
            mask = avail if axis == 0 else avail.T
            counts = mask.sum(axis=1)
            solvable = np.nonzero((counts >= k) & (counts < n2))[0]
            if len(solvable) == 0:
                continue
            # Decode ALL solvable axes in one batched host call: under a
            # random DAS withholding pattern every axis carries a distinct
            # availability mask, so per-mask grouping degenerates to one
            # dispatch per axis — hundreds of device round-trips.
            idxs = solvable
            if (
                gf256.active_codec() == gf256.CODEC_LEOPARD
                and _nat.available()
            ):
                # leopard codec: the O(n log n) FFT erasure decode
                # (native leo_decode_axes, Forney over the novel basis)
                # — ~0.3 ms/axis at k=128 vs several ms for the
                # matrix path; bit-identical (tests/test_leopard_codec)
                if axis == 0 and bool((counts >= k).all()):
                    # fast host path (the common honest-DAS shape: every
                    # row has >= k cells): decode IN PLACE on the whole
                    # contiguous square — rows ARE the axes, complete
                    # rows are no-ops inside the decoder — skipping the
                    # ~2x33 MiB gather/scatter the index path pays
                    ok = _nat.leo_decode_axes(
                        eds, avail.astype(np.uint8), nthreads=nthreads
                    )
                    if not ok.all():
                        raise RuntimeError(
                            "leo_decode_axes rejected a solvable axis"
                        )
                    avail[:, :] = True
                    progress = True
                    continue
                block = np.ascontiguousarray(data[idxs])
                ok = _nat.leo_decode_axes(
                    block, mask[idxs].astype(np.uint8), nthreads=nthreads
                )
                if not ok.all():  # solvable==True guarantees >= k rows
                    raise RuntimeError("leo_decode_axes rejected a solvable axis")
                decoded = block
            else:
                # generic path: one Lagrange decode matrix per axis
                # (vectorized) + one threaded native GF matmul.  This
                # path overwrites whole axes, so snapshot the provided
                # bytes first (still intact in eds at this point)
                if original_eds is None:
                    original_eds = eds.copy()
                order = np.argsort(~mask[idxs], axis=1, kind="stable")
                known_idx = np.sort(order[:, :k], axis=1)  # [n_axes, k]
                D = gf256.decode_matrices_batch(known_idx.astype(np.uint8), k)
                X = np.take_along_axis(
                    data[idxs], known_idx[:, :, None], axis=1
                )  # [n_axes, k, B]
                decoded = _gf_matmul_axes_host(D, X, nthreads)  # [n_axes, 2k, B]
            if axis == 0:
                eds[idxs] = decoded
                avail[idxs] = True
            else:
                eds[:, idxs] = decoded.transpose(1, 0, 2)
                avail[:, idxs] = True
            progress = True
        if not progress:
            raise ValueError(
                "repair stalled: insufficient available cells to reconstruct"
            )

    # Byzantine check: the completed square must be the unique codeword
    # extending its Q0, and every share the caller actually provided must
    # agree with it.  (rsmt2d returns ErrByzantine from Repair here.)
    # When no generic pass ran, provided bytes are still in place in eds
    # (the leopard decoder never touches received cells), so the
    # provided-share check below is subsumed by eds == recomputed.
    orig_avail = np.asarray(available, dtype=bool)
    provided = (
        np.array(original_eds, dtype=np.uint8, copy=False)
        if original_eds is not None
        else eds
    )
    # Repair is a DAS/light-client operation: verify on the host (threaded
    # native pipeline, bit-identical to the device kernels) so repairing a
    # square never requires an accelerator or pays a cold device compile;
    # the device path remains the fallback where the native lib is absent.
    _native = _nat

    use_native = _native.available()
    use_leo = use_native and gf256.active_codec() == gf256.CODEC_LEOPARD
    need_roots = row_roots is not None or col_roots is not None
    native_roots = None
    if use_native and need_roots:
        # one threaded pass computes both the re-extension and the axis
        # roots needed for the commitment check below; the leopard codec
        # takes the O(n log n) FFT extension (same bytes, ~60x less GF
        # work than the table method at k=128)
        if use_leo:
            recomputed, native_roots, _ = _native.extend_block_leopard_cpu(
                eds[:k, :k], nthreads=nthreads
            )
        else:
            recomputed, native_roots, _ = _native.extend_block_cpu(
                eds[:k, :k], nthreads=nthreads
            )
    elif use_leo:
        recomputed = _native.leo_extend_square(eds[:k, :k], nthreads=nthreads)
    elif use_native:
        recomputed = _native.rs_extend_square(eds[:k, :k])
    else:
        recomputed = np.asarray(extend_square(eds[:k, :k]))
    if not np.array_equal(eds, recomputed):
        bad = np.nonzero((eds != recomputed).any(axis=2))
        raise ByzantineError(
            f"inconsistent erasure coding at cells {list(zip(*bad))[:8]}"
        )
    mismatch = orig_avail & (provided != recomputed).any(axis=2)
    if mismatch.any():
        bad = np.nonzero(mismatch)
        raise ByzantineError(
            f"provided shares disagree with the reconstructed codeword at "
            f"cells {list(zip(*bad))[:8]}"
        )
    if row_roots is not None or col_roots is not None:
        if native_roots is not None:
            # eds == recomputed at this point, so the pipeline's roots ARE
            # the repaired square's roots
            roots = native_roots.reshape(2, n2, 90)
        else:
            from celestia_tpu.ops import nmt as nmt_ops

            # pooled host reduction (numpy fallback when native is absent)
            roots = nmt_ops.eds_nmt_roots_host(eds, nthreads=nthreads)
        for name, axis_roots, got in (
            ("row", row_roots, roots[0]),
            ("col", col_roots, roots[1]),
        ):
            if axis_roots is None:
                continue
            axis_roots = np.asarray(axis_roots, dtype=np.uint8)
            if axis_roots.shape != got.shape:
                raise ValueError(
                    f"{name}_roots must be {got.shape}, got {axis_roots.shape}"
                )
            bad = np.nonzero((axis_roots != got).any(axis=1))[0]
            if len(bad):
                raise ByzantineError(
                    f"reconstructed {name} axes {bad.tolist()[:8]} do not "
                    f"match the committed NMT roots"
                )
    return eds


# ---------------------------------------------------------------------------
# Host reference (numpy) for bit-exactness tests
# ---------------------------------------------------------------------------


def extend_square_ref(square: np.ndarray) -> np.ndarray:
    """Pure-numpy reference of extend_square; the device must match exactly."""
    square = np.asarray(square, dtype=np.uint8)
    k = square.shape[0]
    B = square.shape[2]
    out = np.zeros((2 * k, 2 * k, B), dtype=np.uint8)
    out[:k, :k] = square
    for r in range(k):  # row parity
        out[r, k:] = gf256.encode_shares_ref(square[r])
    for c in range(2 * k):  # column parity (over the top half)
        out[k:, c] = gf256.encode_shares_ref(out[:k, c])
    return out
