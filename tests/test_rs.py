"""Reed-Solomon kernel tests: bit-exactness device-vs-reference, quadrant
commutativity, repair from partial data (rsmt2d parity, SURVEY.md §2.2)."""

import numpy as np
import pytest

from celestia_tpu.ops import gf256, rs


def test_gf_mul_basics():
    assert gf256.gf_mul(0, 5) == 0
    assert gf256.gf_mul(1, 173) == 173
    # x * x^7 reduces — a property of the standard polynomial basis, so
    # pin the lagrange codec explicitly (the default leopard codec works
    # in the Cantor-index representation where this identity changes)
    assert gf256.gf_mul(2, 0x80, gf256.CODEC_LAGRANGE) == (
        (0x100 ^ 0x11D) & 0xFF
    )
    a = np.arange(256, dtype=np.uint8)
    nz = a[1:]
    assert np.all(gf256.gf_mul(nz, gf256.gf_inv(nz)) == 1)


def test_gf_mul_distributes():
    rng = np.random.default_rng(0)
    a, b, c = (rng.integers(0, 256, 100, dtype=np.uint8) for _ in range(3))
    left = gf256.gf_mul(a, b ^ c)
    right = gf256.gf_mul(a, b) ^ gf256.gf_mul(a, c)
    assert np.array_equal(left, right)


def test_lagrange_identity_rows():
    # dst overlapping src gives unit rows.
    src = np.array([0, 1, 2, 3], dtype=np.uint8)
    M = gf256.lagrange_matrix(src, src)
    assert np.array_equal(M, np.eye(4, dtype=np.uint8))


def test_encode_matrix_k1_is_repetition():
    E = gf256.encode_matrix(1)
    assert E.shape == (1, 1) and E[0, 0] == 1


def test_bit_expand_matches_gf_mul():
    rng = np.random.default_rng(1)
    A = rng.integers(0, 256, (4, 4), dtype=np.uint8)
    x = rng.integers(0, 256, (4, 16), dtype=np.uint8)
    # reference GF matmul
    want = np.zeros((4, 16), dtype=np.uint8)
    for j in range(4):
        want ^= gf256.gf_mul(A[:, j : j + 1], x[j : j + 1, :])
    # bit-domain
    Ab = gf256.bit_expand_matrix(A).astype(np.int32)
    xb = np.stack([(x >> t) & 1 for t in range(8)], axis=1).reshape(32, 16).astype(np.int32)
    yb = (Ab @ xb) % 2
    got = np.zeros((4, 16), dtype=np.uint8)
    for t in range(8):
        got |= (yb.reshape(4, 8, 16)[:, t, :] << t).astype(np.uint8)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("k", [1, 2, 4, 8, 16])
def test_extend_square_matches_reference(k):
    rng = np.random.default_rng(k)
    square = rng.integers(0, 256, (k, k, 64), dtype=np.uint8)
    want = rs.extend_square_ref(square)
    got = np.asarray(rs.extend_square(square))
    assert got.dtype == np.uint8
    assert np.array_equal(got, want), f"device/reference mismatch at k={k}"


def test_extend_commutativity_q3():
    # Q3 via columns-of-Q1 must equal Q3 via rows-of-Q2.
    rng = np.random.default_rng(9)
    k = 8
    square = rng.integers(0, 256, (k, k, 32), dtype=np.uint8)
    eds = np.asarray(rs.extend_square(square))
    q2 = eds[k:, :k]
    q3 = eds[k:, k:]
    # row-extend Q2 and compare with Q3
    q3_alt = np.zeros_like(q3)
    for r in range(k):
        q3_alt[r] = gf256.encode_shares_ref(q2[r])
    assert np.array_equal(q3, q3_alt)


def test_extend_batched():
    rng = np.random.default_rng(2)
    squares = rng.integers(0, 256, (3, 4, 4, 32), dtype=np.uint8)
    got = np.asarray(rs.extend_squares_batched(squares))
    for i in range(3):
        assert np.array_equal(got[i], rs.extend_square_ref(squares[i]))


def test_systematic_property():
    # Q0 of the EDS is the original square, untouched.
    rng = np.random.default_rng(3)
    square = rng.integers(0, 256, (8, 8, 16), dtype=np.uint8)
    eds = np.asarray(rs.extend_square(square))
    assert np.array_equal(eds[:8, :8], square)


@pytest.mark.parametrize("k", [2, 4, 8])
def test_repair_withheld_rows_cols(k):
    """DAS case: withhold 25% (half the rows and half the cols of the EDS)."""
    rng = np.random.default_rng(k * 7)
    square = rng.integers(0, 256, (k, k, 32), dtype=np.uint8)
    eds = np.asarray(rs.extend_square(square))
    avail = np.ones((2 * k, 2 * k), dtype=bool)
    withheld_rows = rng.choice(2 * k, k, replace=False)
    withheld_cols = rng.choice(2 * k, k, replace=False)
    avail[withheld_rows, :] = False
    avail[:, withheld_cols] = False
    # exactly k rows and k cols remain -> every missing axis still has k cells
    corrupted = eds.copy()
    corrupted[~avail] = 0xAA  # garbage must not leak
    repaired = rs.repair_square(corrupted, avail)
    assert np.array_equal(repaired, eds)


def test_repair_random_cells():
    rng = np.random.default_rng(11)
    k = 4
    square = rng.integers(0, 256, (k, k, 16), dtype=np.uint8)
    eds = np.asarray(rs.extend_square(square))
    avail = rng.random((2 * k, 2 * k)) < 0.7
    # ensure solvable start: keep at least k cells per row
    for r in range(2 * k):
        if avail[r].sum() < k:
            avail[r, rng.choice(2 * k, k, replace=False)] = True
    repaired = rs.repair_square(eds.copy(), avail)
    assert np.array_equal(repaired, eds)


def test_repair_insufficient_raises():
    k = 2
    square = np.zeros((k, k, 8), dtype=np.uint8)
    eds = np.asarray(rs.extend_square(square))
    avail = np.zeros((2 * k, 2 * k), dtype=bool)
    avail[0, 0] = True
    with pytest.raises(ValueError, match="stalled"):
        rs.repair_square(eds, avail)


def test_repair_detects_byzantine_shares():
    """A tampered available share that breaks codeword consistency must raise
    ByzantineError (rsmt2d ErrByzantine parity), not silently 'repair'."""
    rng = np.random.default_rng(21)
    k = 4
    square = rng.integers(0, 256, (k, k, 16), dtype=np.uint8)
    eds = np.asarray(rs.extend_square(square))
    avail = np.ones((2 * k, 2 * k), dtype=bool)
    avail[0, :k] = False  # force row 0 to be solved from its parity half
    bad = eds.copy()
    bad[0, k] ^= 1  # tamper an available parity share in the solved row
    with pytest.raises(rs.ByzantineError):
        rs.repair_square(bad, avail)


def test_repair_detects_byzantine_full_row():
    """Inconsistent but fully-available axes (never solved) are also caught."""
    rng = np.random.default_rng(22)
    k = 4
    square = rng.integers(0, 256, (k, k, 16), dtype=np.uint8)
    eds = np.asarray(rs.extend_square(square))
    avail = np.ones((2 * k, 2 * k), dtype=bool)
    avail[1, 0] = False  # something to repair so the loop runs
    bad = eds.copy()
    bad[k + 1, k + 1] ^= 0x10  # tamper a fully-available parity cell
    with pytest.raises(rs.ByzantineError):
        rs.repair_square(bad, avail)


def test_repair_verifies_committed_roots():
    """Internally-consistent but *wrong* shares (a valid codeword for a
    different square) must fail against the block's committed NMT roots —
    rsmt2d.Repair checks every rebuilt axis against the DAH for this."""
    from celestia_tpu.ops import nmt as nmt_ops

    rng = np.random.default_rng(23)
    k = 2
    sq_good = rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    sq_evil = rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    eds_good = np.asarray(rs.extend_square(sq_good))
    eds_evil = np.asarray(rs.extend_square(sq_evil))
    roots_good = np.asarray(nmt_ops.eds_nmt_roots(eds_good))
    avail = np.ones((2 * k, 2 * k), dtype=bool)
    avail[0, 0] = False  # something to solve so repair actually runs
    # correct roots accept the true square
    repaired = rs.repair_square(
        eds_good.copy(), avail, row_roots=roots_good[0], col_roots=roots_good[1]
    )
    assert np.array_equal(repaired, eds_good)
    # the evil square is a perfectly consistent codeword — only the committed
    # roots expose it
    rs.repair_square(eds_evil.copy(), avail)  # passes without roots
    with pytest.raises(rs.ByzantineError, match="committed NMT roots"):
        rs.repair_square(
            eds_evil.copy(), avail,
            row_roots=roots_good[0], col_roots=roots_good[1],
        )


def test_extend_batched_validates_shape():
    with pytest.raises(ValueError, match="power of two"):
        rs.extend_squares_batched(np.zeros((2, 3, 3, 16), dtype=np.uint8))


# ---------------------------------------------------------------------------
# Device-resident repair (VERDICT r2 #6): same contract as repair_square,
# decode matmuls + byzantine verification on the accelerator
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [2, 4, 8])
def test_repair_device_matches_host(k):
    rng = np.random.default_rng(k * 13)
    square = rng.integers(0, 256, (k, k, 32), dtype=np.uint8)
    eds = np.asarray(rs.extend_square(square))
    avail = np.ones((2 * k, 2 * k), dtype=bool)
    withheld_rows = rng.choice(2 * k, k, replace=False)
    withheld_cols = rng.choice(2 * k, k, replace=False)
    avail[withheld_rows, :] = False
    avail[:, withheld_cols] = False
    corrupted = eds.copy()
    corrupted[~avail] = 0x55
    dev = rs.repair_square_device(corrupted, avail)
    host = rs.repair_square(corrupted, avail)
    assert np.array_equal(dev, eds)
    assert np.array_equal(dev, host)


def test_repair_device_random_cells_and_roots():
    from celestia_tpu.ops import nmt as nmt_ops

    rng = np.random.default_rng(31)
    k = 4
    square = rng.integers(0, 256, (k, k, 512), dtype=np.uint8)
    eds = np.asarray(rs.extend_square(square))
    roots = np.asarray(nmt_ops.eds_nmt_roots(eds))
    avail = rng.random((2 * k, 2 * k)) < 0.7
    for r in range(2 * k):
        if avail[r].sum() < k:
            avail[r, rng.choice(2 * k, k, replace=False)] = True
    repaired = rs.repair_square_device(
        eds.copy(), avail, row_roots=roots[0], col_roots=roots[1]
    )
    assert np.array_equal(repaired, eds)


def test_repair_device_detects_byzantine():
    rng = np.random.default_rng(33)
    k = 4
    square = rng.integers(0, 256, (k, k, 16), dtype=np.uint8)
    eds = np.asarray(rs.extend_square(square))
    avail = np.ones((2 * k, 2 * k), dtype=bool)
    avail[0, :k] = False
    bad = eds.copy()
    bad[0, k] ^= 1
    with pytest.raises(rs.ByzantineError):
        rs.repair_square_device(bad, avail)
    # wrong committed roots are caught too (full-size shares: the NMT
    # leaf format needs the 29-byte namespace prefix)
    k2 = 2
    square2 = rng.integers(0, 256, (k2, k2, 512), dtype=np.uint8)
    eds2 = np.asarray(rs.extend_square(square2))
    avail2 = np.ones((2 * k2, 2 * k2), dtype=bool)
    avail2[1, 0] = False
    fake_roots = np.zeros((2 * k2, 90), dtype=np.uint8)
    with pytest.raises(rs.ByzantineError):
        rs.repair_square_device(
            eds2.copy(), avail2, row_roots=fake_roots
        )


def test_repair_program_is_the_program_repair_dispatches(monkeypatch):
    """rs.repair_program hands an AOT caller (chip_smoke.py's precompile)
    the very program, and argument shapes, that repair_square_device
    then dispatches — so its compile lands on the same cache entry."""
    from celestia_tpu.ops import nmt as nmt_ops

    rng = np.random.default_rng(37)
    k = 4
    eds = np.asarray(
        rs.extend_square(rng.integers(0, 256, (k, k, 512), dtype=np.uint8))
    )
    roots = np.asarray(nmt_ops.eds_nmt_roots(eds))
    avail = rng.random((2 * k, 2 * k)) >= 0.25
    fn, args = rs.repair_program(avail)
    fn.lower(*args).compile()
    seen = []
    real = rs._repair_verify_fn

    def spy(*key):
        program = real(*key)

        def call(*a):
            seen.append((program, [(x.shape, x.dtype) for x in a]))
            return program(*a)

        return call

    monkeypatch.setattr(rs, "_repair_verify_fn", spy)
    repaired = rs.repair_square_device(
        eds.copy(), avail, row_roots=roots[0], col_roots=roots[1]
    )
    assert np.array_equal(repaired, eds)
    assert seen == [(fn, [(a.shape, a.dtype) for a in args])]


def test_repair_device_insufficient_raises():
    k = 2
    square = np.zeros((k, k, 8), dtype=np.uint8)
    eds = np.asarray(rs.extend_square(square))
    avail = np.zeros((2 * k, 2 * k), dtype=bool)
    avail[0, 0] = True
    with pytest.raises(ValueError, match="stalled"):
        rs.repair_square_device(eds, avail)


def test_repair_device_nothing_missing():
    rng = np.random.default_rng(35)
    k = 2
    square = rng.integers(0, 256, (k, k, 8), dtype=np.uint8)
    eds = np.asarray(rs.extend_square(square))
    avail = np.ones((2 * k, 2 * k), dtype=bool)
    assert np.array_equal(rs.repair_square_device(eds, avail), eds)


def test_repair_device_return_device_still_catches_byzantine():
    """Regression (review finding): return_device=True must not skip the
    provided-share consistency check — it now runs on device."""
    rng = np.random.default_rng(41)
    k = 4
    square = rng.integers(0, 256, (k, k, 16), dtype=np.uint8)
    eds = np.asarray(rs.extend_square(square))
    avail = np.ones((2 * k, 2 * k), dtype=bool)
    # row 0 has k+1 available cells: the first k solve it, the LAST one
    # is overwritten by the decode — tampering it leaves the codeword
    # intact and is only caught by the provided-share comparison
    avail[0, : k - 1] = False
    bad = eds.copy()
    bad[0, 2 * k - 1] ^= 0x04
    with pytest.raises(rs.ByzantineError, match="provided shares"):
        rs.repair_square_device(bad, avail, return_device=True)
    # clean input round-trips on device
    out = rs.repair_square_device(eds.copy(), avail, return_device=True)
    assert np.array_equal(np.asarray(out), eds)
