"""Cross-implementation golden vectors (VERDICT r1 item #6).

Every fixture here is a constant from outside this repository:

- SHA-256: FIPS 180-4 / NIST test vectors.
- RFC-6962 binary Merkle roots: the Certificate Transparency reference test
  corpus (certificate-transparency-go merkle tests) — the same hash rule the
  reference uses for the data root (`specs/src/specs/data_structures.md:184-204`
  cites RFC-6962 and pins the empty root literal).
- RFC-6979 deterministic ECDSA on secp256k1: the community test vectors for
  (privkey 1, "Satoshi Nakamoto"), etc., reproduced across bitcoin-core,
  trezor, and python-ecdsa test suites.
- NMT empty root: the literal in the reference spec
  (`specs/src/specs/data_structures.md:231-235`).

A shared misreading of a spec by this repo's device kernels AND its host
reference implementations cannot survive these pins.
"""

import hashlib

import numpy as np
import pytest

from celestia_tpu.ops import nmt as nmt_ops
from celestia_tpu.ops import sha256 as sha_ops
from celestia_tpu.utils import native
from celestia_tpu.utils.secp256k1 import N, PrivateKey

# --------------------------------------------------------------------------
# SHA-256 (FIPS 180-4)
# --------------------------------------------------------------------------

SHA_VECTORS = [
    (b"", "e3b0c44298fc1c149afbf4c8996fb92427ae41e4649b934ca495991b7852b855"),
    (b"abc", "ba7816bf8f01cfea414140de5dae2223b00361a396177a9cb410ff61f20015ad"),
    (
        b"abcdbcdecdefdefgefghfghighijhijkijkljklmklmnlmnomnopnopq",
        "248d6a61d20638b8e5c026930c3e6039a33ce45964ff2167f6ecedd419db06c1",
    ),
    (
        b"abcdefghbcdefghicdefghijdefghijkefghijklfghijklmghijklmn"
        b"hijklmnoijklmnopjklmnopqklmnopqrlmnopqrsmnopqrstnopqrstu",
        "cf5b16a778af8380036ce59e7b0492370b249b11e8f07a51afac45037afee9d1",
    ),
]


def test_sha256_device_fips_vectors():
    for msg, want in SHA_VECTORS:
        arr = np.frombuffer(msg, dtype=np.uint8).reshape(1, -1)
        got = bytes(np.asarray(sha_ops.sha256(arr))[0])
        assert got.hex() == want, msg


def test_sha256_native_fips_vectors():
    if not native.available():
        pytest.skip("native library unavailable")
    for msg, want in SHA_VECTORS:
        arr = np.frombuffer(msg, dtype=np.uint8).reshape(1, -1)
        got = bytes(native.sha256_batch(arr)[0])
        assert got.hex() == want, msg


# --------------------------------------------------------------------------
# RFC-6962 binary Merkle tree (Certificate Transparency test corpus)
# --------------------------------------------------------------------------

CT_LEAVES = [
    bytes.fromhex(h)
    for h in [
        "",
        "00",
        "10",
        "2021",
        "3031",
        "40414243",
        "5051525354555657",
        "606162636465666768696a6b6c6d6e6f",
    ]
]
CT_ROOTS = [
    "6e340b9cffb37a989ca544e6bb780a2c78901d3fb33738768511a30617afa01d",
    "fac54203e7cc696cf0dfcb42c92a1d9dbaf70ad9e621f4bd8d98662f00e3c125",
    "aeb6bcfe274b70a14fb067a5e5578264db0fa9b51af5e0ba159158f329e06e77",
    "d37ee418976dd95753c1c73862b9398fa2a2cf9b4ff0fdfe8b30cd95209614b7",
    "4e3bbb1f7b478dcfe71fb631631519a3bca12c9aefca1612bfce4c13a86264d4",
    "76e67dadbcdf1e10e1b74ddc608abd2f98dfb16fbce75277b5232a127f2087ef",
    "ddb89be403809e325750d3d263cd78929c2942b7942a34b77e122c9594a74c8c",
    "5dc9da79a70659a9ad559cb701ded9a2ab9d823aad2f4960cfe370eff4604328",
]
EMPTY_ROOT = "e3b0c44298fc1c149afbf4c8996fb92427ae41e4649b934ca495991b7852b855"


def test_rfc6962_host_ct_corpus():
    assert bytes(nmt_ops.rfc6962_root_np([])).hex() == EMPTY_ROOT
    for n in range(1, 9):
        got = bytes(nmt_ops.rfc6962_root_np(CT_LEAVES[:n])).hex()
        assert got == CT_ROOTS[n - 1], f"CT corpus size {n}"


def test_rfc6962_device_matches_ct_at_pow2():
    # the device path requires equal-length leaves and power-of-two counts;
    # pad the CT corpus to a uniform length and pin against the host rule
    # (itself pinned against the CT corpus above), plus the single-leaf and
    # pair cases directly against CT constants where lengths allow.
    one = np.frombuffer(CT_LEAVES[0], dtype=np.uint8).reshape(1, 0)
    got = bytes(np.asarray(nmt_ops.rfc6962_root_pow2(one.reshape(1, 0))))
    assert got.hex() == CT_ROOTS[0]
    uniform = np.stack(
        [np.frombuffer(b"%16d" % i, dtype=np.uint8) for i in range(8)]
    )
    want = bytes(nmt_ops.rfc6962_root_np([bytes(x) for x in uniform]))
    got = bytes(np.asarray(nmt_ops.rfc6962_root_pow2(uniform)))
    assert got == want


# --------------------------------------------------------------------------
# NMT empty root (reference spec literal)
# --------------------------------------------------------------------------


def test_nmt_empty_root_spec_literal():
    root = bytes(nmt_ops.empty_root_np())
    ns = nmt_ops.NAMESPACE_SIZE
    assert root[: 2 * ns] == b"\x00" * (2 * ns)
    assert root[2 * ns :].hex() == EMPTY_ROOT


# --------------------------------------------------------------------------
# RFC-6979 deterministic ECDSA (secp256k1 community vectors)
# --------------------------------------------------------------------------

ECDSA_VECTORS = [
    (
        1,
        b"Satoshi Nakamoto",
        "934b1ea10a4b3c1757e2b0c017d0b6143ce3c9a7e6a4a49860d7a6ab210ee3d8"
        "2442ce9d2b916064108014783e923ec36b49743e2ffa1c4496f01a512aafd9e5",
    ),
    (
        1,
        b"All those moments will be lost in time, like tears in rain. "
        b"Time to die...",
        "8600dbd41e348fe5c9465ab92d23e3db8b98b873beecd930736488696438cb6b"
        "547fe64427496db33bf66019dacbf0039c04199abb0122918601db38a72cfc21",
    ),
    (
        N - 1,
        b"Satoshi Nakamoto",
        "fd567d121db66e382991534ada77a6bd3106f0a1098c231e47993447cd6af2d0"
        "6b39cd0eb1bc8603e159ef5c20a5c8ad685a45b06ce9bebed3f153d10d93bed5",
    ),
]


def test_rfc6979_ecdsa_vectors():
    for d, msg, want in ECDSA_VECTORS:
        sk = PrivateKey(d)
        assert sk.sign(msg).hex() == want
        pk = sk.public_key()
        assert pk.verify(msg, bytes.fromhex(want))


# --------------------------------------------------------------------------
# NMT node rule recomputed inline from the spec formula
# (specs/src/specs/data_structures.md:255-263 + malicious/hasher.go:271-310)
# --------------------------------------------------------------------------


def test_nmt_node_rule_from_spec_formula():
    ns = nmt_ops.NAMESPACE_SIZE
    parity = b"\xff" * ns
    ns_a = bytes([0] * (ns - 1) + [1])
    ns_b = bytes([0] * (ns - 1) + [2])
    leaf_a = ns_a + b"payload-a"
    leaf_b = ns_b + b"payload-b"
    leaf_p = parity + b"parity-share"

    # leaf: n_min = n_max = namespace, v = h(0x00, ns || data)
    for leaf in (leaf_a, leaf_b, leaf_p):
        d = nmt_ops.leaf_digest_np(leaf)
        assert d[:ns] == leaf[:ns]
        assert d[ns : 2 * ns] == leaf[:ns]
        assert d[2 * ns :] == hashlib.sha256(b"\x00" + leaf).digest()

    da = nmt_ops.leaf_digest_np(leaf_a)
    db = nmt_ops.leaf_digest_np(leaf_b)
    dp = nmt_ops.leaf_digest_np(leaf_p)

    # ordinary node: min = left.min, max = right.max
    node = nmt_ops.combine_digests_np(da, db)
    assert node[:ns] == ns_a
    assert node[ns : 2 * ns] == ns_b
    assert node[2 * ns :] == hashlib.sha256(b"\x01" + da + db).digest()

    # ignore-max rule: right child entirely parity -> parent max = left.max
    node = nmt_ops.combine_digests_np(db, dp)
    assert node[:ns] == ns_b
    assert node[ns : 2 * ns] == ns_b, "IgnoreMaxNamespace must drop parity ns"
    assert node[2 * ns :] == hashlib.sha256(b"\x01" + db + dp).digest()

    # both parity (Q3): range stays parity
    node = nmt_ops.combine_digests_np(dp, dp)
    assert node[:ns] == parity
    assert node[ns : 2 * ns] == parity


def test_native_commitment_matches_python_path():
    """The one-call native create_commitment must be bit-identical to the
    per-subtree host path across mountain-range shapes (incl. non-power-of-2
    mountain counts, where RFC-6962's uneven split kicks in)."""
    import numpy as np

    from celestia_tpu.appconsts import (
        DEFAULT_SUBTREE_ROOT_THRESHOLD,
        NAMESPACE_SIZE,
    )
    from celestia_tpu.da import inclusion
    from celestia_tpu.da.blob import Blob
    from celestia_tpu.da.namespace import Namespace
    from celestia_tpu.da.shares import shares_to_array, split_blob_into_shares
    from celestia_tpu.da.square import subtree_width
    from celestia_tpu.ops import nmt as nmt_ops
    from celestia_tpu.utils import native

    if not native.available():
        import pytest

        pytest.skip("native library unavailable")
    rng = np.random.default_rng(7)
    for nbytes in (1, 478, 479, 5000, 57000, 200000):
        blob = Blob(
            Namespace.v0(b"\x07" * 10),
            rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes(),
        )
        got = inclusion.create_commitment(blob)
        shares = split_blob_into_shares(
            blob.namespace, blob.data, blob.share_version
        )
        arr = shares_to_array(shares)
        n = arr.shape[0]
        sizes = inclusion.merkle_mountain_range_sizes(
            n, subtree_width(n, DEFAULT_SUBTREE_ROOT_THRESHOLD)
        )
        ns = np.broadcast_to(
            np.frombuffer(blob.namespace.raw, dtype=np.uint8),
            (n, NAMESPACE_SIZE),
        )
        leaves = np.ascontiguousarray(np.concatenate([ns, arr], axis=1))
        roots, off = [], 0
        for s in sizes:
            roots.append(inclusion._nmt_root_host(leaves[off : off + s]))
            off += s
        assert got == nmt_ops.rfc6962_root_np(roots).tobytes(), nbytes


def _bytes_for_shares(n):
    """Blob length that fills exactly ``n`` sparse shares."""
    return 478 + 482 * (n - 1)


# 1 B, one byte either side of the first share and of the second, exact
# multiples of 482 past the first share, and share counts either side of
# every subtree-width step up to the threshold (ceil(n / 64) crossing a
# power of two: widths 1, 2, 4, ..., 64)
_COMMITMENT_SIZES = [1, 478, 479, 960, 961]
_COMMITMENT_SIZES += [_bytes_for_shares(n) for n in (3, 7, 12)]
for _n in (64, 128, 256, 512, 1024, 2048):
    _COMMITMENT_SIZES += [_bytes_for_shares(_n), _bytes_for_shares(_n) + 1]

# the share-commitment anchor of tests/test_reference_vectors.py: 5782
# bytes of 0x02 under namespace v0(0x01 * 10)
_ANCHOR_HEX = "3b0696ee3b902f2e2c91e338e866f4d6aa4876716dc76b91776ede1c683dbe2f"


def _anchor_blob():
    from celestia_tpu.da.blob import Blob
    from celestia_tpu.da.namespace import Namespace

    return Blob(Namespace.v0(b"\x01" * 10), b"\x02" * 5782)


def _host_commitment(blob):
    """blob_shares_array leaves, hashlib NMT roots, RFC-6962 fold."""
    from celestia_tpu.appconsts import DEFAULT_SUBTREE_ROOT_THRESHOLD
    from celestia_tpu.da import inclusion

    leaves = inclusion._blob_leaves(blob)
    sizes = inclusion._mountains(blob, DEFAULT_SUBTREE_ROOT_THRESHOLD)
    roots, off = [], 0
    for s in sizes:
        roots.append(inclusion._nmt_root_host(leaves[off : off + s]))
        off += s
    return nmt_ops.rfc6962_root_np(roots).tobytes()


@pytest.mark.parametrize("nthreads", [1, 8])
@pytest.mark.parametrize("nbytes", _COMMITMENT_SIZES)
def test_native_blob_commitments_match_host_path(nbytes, nthreads):
    """The native pass, which lays each share out from the blob's bytes
    as it hashes it, gives the bytes of the blob_shares_array + hashlib
    path for one blob of every boundary size."""
    from celestia_tpu.appconsts import DEFAULT_SUBTREE_ROOT_THRESHOLD
    from celestia_tpu.da import inclusion
    from celestia_tpu.da.blob import Blob
    from celestia_tpu.da.namespace import Namespace

    if not native.available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(nbytes)
    blob = Blob(
        Namespace.v0(b"\x07" * 10),
        rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes(),
    )
    got, tasks = inclusion._compute_commitments(
        [blob], DEFAULT_SUBTREE_ROOT_THRESHOLD, nthreads
    )
    assert got == [_host_commitment(blob)], nbytes
    assert tasks == len(inclusion._mountains(blob, DEFAULT_SUBTREE_ROOT_THRESHOLD))


@pytest.mark.parametrize("nthreads", [1, 8])
@pytest.mark.parametrize("count", [1, 40])
def test_native_blob_commitments_batch(count, nthreads):
    """Mixed batches: every blob's commitment is its own (no bleed
    between neighbours of different sizes and namespaces), the
    first blob is the reference-vector anchor, and the hashlib path
    agrees."""
    from celestia_tpu.appconsts import DEFAULT_SUBTREE_ROOT_THRESHOLD
    from celestia_tpu.da import inclusion
    from celestia_tpu.da.blob import Blob
    from celestia_tpu.da.namespace import Namespace

    if not native.available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(count)
    blobs = [_anchor_blob()]
    for i in range(1, count):
        nbytes = _COMMITMENT_SIZES[(7 * i) % len(_COMMITMENT_SIZES)]
        blobs.append(Blob(
            Namespace.v0(b"batch-%03d" % i),
            rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes(),
        ))
    got, _ = inclusion._compute_commitments(
        blobs, DEFAULT_SUBTREE_ROOT_THRESHOLD, nthreads
    )
    assert got[0].hex() == _ANCHOR_HEX
    assert got == [_host_commitment(b) for b in blobs]


def test_glv_split_invariant_and_bounds():
    """GLV decomposition (utils/secp256k1._glv_split): k1 + k2*lambda
    == k (mod N) with ~128-bit components, for random and boundary
    scalars.  Pure Python on purpose — lives here (not in
    test_secp_native.py) so a missing native library can never skip it
    and hide a lattice-constant regression."""
    import secrets

    from celestia_tpu.utils.secp256k1 import GLV_LAMBDA, _glv_split

    cases = [1, 2, N - 1, N // 2, GLV_LAMBDA, (1 << 128) - 1, 1 << 128]
    cases += [secrets.randbelow(N - 1) + 1 for _ in range(500)]
    for k in cases:
        k1, k2 = _glv_split(k)
        assert (k1 + k2 * GLV_LAMBDA - k) % N == 0, hex(k)
        assert abs(k1) < 1 << 129 and abs(k2) < 1 << 129, (
            hex(k), abs(k1).bit_length(), abs(k2).bit_length()
        )
