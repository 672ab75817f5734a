"""Cross-language wire contract (VERDICT r3 #9, specs/wire.md).

A standalone C++ program (native/wire_decoder.cpp — no repo linkage, no
third-party libraries) decodes this framework's wire bytes per the spec
alone: a signed tx, a BlobTx envelope, a DAH, and an AccountInfo query
response served by a LIVE node over gRPC.  Field-for-field agreement
with the Python encoder proves the schema is a real external contract,
not a Python implementation detail.
"""

import json
import subprocess
import time
from pathlib import Path

import pytest

from celestia_tpu.da.blob import Blob, BlobTx
from celestia_tpu.da.namespace import Namespace
from celestia_tpu.state.tx import Fee, MsgSend, Tx
from celestia_tpu.utils.secp256k1 import PrivateKey

REPO = Path(__file__).resolve().parents[1]
SRC = REPO / "native" / "wire_decoder.cpp"
BIN = REPO / "native" / "wire_decoder"


@pytest.fixture(scope="module")
def decoder():
    from celestia_tpu.utils.native import ensure_built

    assert ensure_built(SRC, BIN, ("-O2",)), "wire_decoder build failed"

    def run(mode: str, payload: str) -> dict:
        out = subprocess.run(
            [str(BIN), mode], input=payload, capture_output=True,
            text=True, timeout=30,
        )
        assert out.returncode == 0, out.stderr
        return json.loads(out.stdout)

    return run


def _signed_send_tx():
    key = PrivateKey.from_seed(b"wire-spec-alice")
    msg = MsgSend(key.public_key().address(), b"\x42" * 20, 123_456)
    tx = Tx(
        msgs=(msg,), fee=Fee(2_000, 90_000),
        pubkey=key.public_key().compressed(), sequence=7,
        account_number=3, memo="wire-spec",
    )
    return key, msg, tx.signed(key, "wire-chain-1")


def test_cpp_decodes_signed_tx(decoder):
    key, msg, tx = _signed_send_tx()
    got = decoder("tx", tx.marshal().hex())
    assert got["msgs"] == [
        {
            "type": 1,
            "from": msg.from_addr.hex(),
            "to": msg.to_addr.hex(),
            "amount": 123_456,
        }
    ]
    assert got["memo"] == "wire-spec"
    assert got["fee_amount"] == 2_000
    assert got["gas_limit"] == 90_000
    assert got["pubkey"] == key.public_key().compressed().hex()
    assert got["sequence"] == 7
    assert got["account_number"] == 3
    assert got["signature"] == tx.signature.hex()


def test_non_minimal_varint_rejected():
    """Canonical wire (specs/wire.md Primitives): 0x80 0x00 decodes to 0
    under lax LEB128 but MUST be rejected — sign_bytes covers the
    verbatim wire slices, so a second encoding of the same value would
    make signed txs malleable."""
    from celestia_tpu.da.shares import _read_varint

    assert _read_varint(b"\x00", 0) == (0, 1)
    assert _read_varint(b"\x80\x01", 0) == (128, 2)
    for bad in (b"\x80\x00", b"\xff\x00", b"\x80\x80\x00"):
        with pytest.raises(ValueError):
            _read_varint(bad, 0)


def test_cpp_rejects_non_minimal_varint(decoder):
    """The C++ decoder enforces the same canonical rule from the spec
    alone: a tx whose leading varint is padded must fail to decode."""
    key, msg, tx = _signed_send_tx()
    raw = tx.marshal()
    # re-encode the leading length varint of the body field non-minimally
    from celestia_tpu.da.shares import _read_varint

    length, pos = _read_varint(raw, 0)
    padded = bytes([raw[0] | 0x80, 0x00]) if raw[0] < 0x80 else None
    if padded is None:
        pytest.skip("leading varint already multi-byte")
    tampered = padded + raw[pos:]
    out = subprocess.run(
        [str(BIN), "tx"], input=tampered.hex(), capture_output=True,
        text=True, timeout=30,
    )
    assert out.returncode != 0


def test_cpp_encoded_tx_accepted_by_live_node(decoder):
    """Cross-language ENCODE (VERDICT r4 #5): the C++ tool builds and
    SIGNS a MsgSend from the spec alone (its own SHA-256 + secp256k1,
    no repo linkage); a live node must accept the bytes and move the
    funds.  With decode proven elsewhere, this closes the wire contract
    in both directions — a third party needs only specs/wire.md."""
    import numpy as np

    from celestia_tpu.da import dah as dah_mod
    from celestia_tpu.node.server import NodeServer
    from celestia_tpu.node.testnode import TestNode
    from celestia_tpu.client.remote import RemoteNode

    for k in (1, 2):  # warm jits before the producer thread starts
        dah_mod.extend_and_header(np.zeros((k, k, 512), dtype=np.uint8))
    key = PrivateKey.from_seed(b"cpp-live-sender")
    to = PrivateKey.from_seed(b"cpp-live-receiver").public_key().address()
    node = TestNode(funded_accounts=[(key, 10**9)])
    srv = NodeServer(node, block_interval_s=0.2)
    srv.start()
    try:
        r = RemoteNode(srv.address, timeout_s=120)
        acct_num, seq = node.account_info(key.public_key().address())
        inp = (
            f"{key.d.to_bytes(32, 'big').hex()} {node.chain_id} "
            f"{to.hex()} 5555 200 90000 {seq} {acct_num} from-cpp"
        )
        out = subprocess.run(
            [str(BIN), "encode-send"], input=inp, capture_output=True,
            text=True, timeout=60,
        )
        assert out.returncode == 0, out.stderr
        raw = bytes.fromhex(out.stdout.strip())
        res = r.broadcast_tx(raw)
        assert res.code == 0, f"live node rejected C++-built tx: {res.log}"
        deadline = time.time() + 60
        while node.app.bank.balance(to) != 5555:
            assert time.time() < deadline, "C++ tx never landed in a block"
            time.sleep(0.1)
        r.close()
    finally:
        srv.stop()


def test_cpp_decodes_utf8_memo(decoder):
    """Non-ASCII memos must survive the C++ leg byte-identically: the
    Python encoder writes memos as UTF-8 (state/tx.py Tx.marshal), so the
    decoder must pass well-formed sequences through rather than escaping
    each byte (which would diverge from the Python decode of the same
    wire bytes), while still emitting valid-UTF-8 JSON for quotes,
    control bytes, and backslashes."""
    key = PrivateKey.from_seed(b"wire-spec-utf8")
    msg = MsgSend(key.public_key().address(), b"\x01" * 20, 1)
    memo = 'héllo ✓ 🚀 "q\\b"\ttab'
    tx = Tx(
        msgs=(msg,), fee=Fee(1, 1),
        pubkey=key.public_key().compressed(), sequence=0,
        account_number=0, memo=memo,
    ).signed(key, "wire-chain-1")
    got = decoder("tx", tx.marshal().hex())
    assert got["memo"] == memo


def test_cpp_decodes_blobtx_envelope(decoder):
    _, _, tx = _signed_send_tx()
    blob = Blob(Namespace.v0(b"\x05" * 10), b"wire spec blob " * 10)
    env = BlobTx(tx=tx.marshal(), blobs=(blob,)).marshal()
    got = decoder("blobtx", env.hex())
    assert got["tx_bytes"] == len(tx.marshal())
    assert got["blobs"] == [
        {
            "namespace": blob.namespace.raw.hex(),
            "data_len": len(blob.data),
            "share_version": 0,
        }
    ]


def test_cpp_decodes_dah(decoder):
    import numpy as np

    from celestia_tpu.da import dah as dah_mod

    share = Namespace.v0(b"\x01" * 10).raw + b"\xff" * 483
    shares = np.frombuffer(share * 4, dtype=np.uint8).reshape(4, 512)
    eds = dah_mod.extend_shares(shares)
    dah = dah_mod.new_data_availability_header(eds)
    got = decoder("dah", dah.to_bytes().hex())
    assert got["row_roots"] == [r.hex() for r in dah.row_roots]
    assert got["col_roots"] == [c.hex() for c in dah.col_roots]


def test_cpp_rejects_trailing_bytes(decoder):
    _, _, tx = _signed_send_tx()
    out = subprocess.run(
        [str(BIN), "tx"], input=tx.marshal().hex() + "00",
        capture_output=True, text=True, timeout=30,
    )
    assert out.returncode == 1
    assert "trailing" in out.stderr


def test_pinned_hex_vector(decoder):
    """A frozen vector: any byte-level schema drift fails here even if
    encoder and decoder drift together."""
    key = PrivateKey.from_seed(b"wire-spec-pin")
    msg = MsgSend(key.public_key().address(), b"\x24" * 20, 42)
    tx = Tx(
        msgs=(msg,), fee=Fee(10, 100), pubkey=key.public_key().compressed(),
        sequence=0, account_number=0, memo="",
    ).signed(key, "pin-chain")
    raw = tx.marshal().hex()
    assert raw == (
        "30012c011432f8dab13ffb122f8f61179c14be7a779eb8b32114242424242424"
        "24242424242424242424242424242a0000270a642103884ea2c0690b7acdaa70"
        "dd93f358c425dd0d50f730bd714b460b2638a742ecb4000000409568f9264f9c"
        "65e6e2e985517ee5b38bb5688f4610402242908dec589feecb691b64ccd89aaa"
        "dbd60860bddb9c5601fea2f7c4baabc62c6196b2d7252f6cfe62"
    )
    got = decoder("tx", raw)
    assert got["msgs"][0]["amount"] == 42


def test_cpp_decodes_live_account_query(decoder):
    """The spec's JSON envelope: a real node's AccountInfo response over
    gRPC, decoded by the C++ program."""
    import grpc

    from celestia_tpu.node.server import NodeServer
    from celestia_tpu.node.testnode import TestNode

    key = PrivateKey.from_seed(b"wire-spec-acct")
    node = TestNode(funded_accounts=[(key, 10**9)], auto_produce=False)
    server = NodeServer(node, block_interval_s=None)
    server.start()
    try:
        channel = grpc.insecure_channel(server.address)
        call = channel.unary_unary(
            "/celestia.tpu.v1.Node/AccountInfo",
            request_serializer=lambda b: b,
            response_deserializer=lambda b: b,
        )
        raw = call(
            json.dumps(
                {"address": key.public_key().address().hex()}
            ).encode()
        )
        got = decoder("account", raw.decode())
        assert got["sequence"] == 0
        assert got["account_number"] >= 0
        channel.close()
    finally:
        server.stop()
