"""The App: ABCI-shaped state machine around the TPU DA pipeline.

Parity with /root/reference/app/: construction & keeper wiring (app.go:227-
664), CheckTx (check_tx.go:16-54), PrepareProposal (prepare_proposal.go:23-
96), ProcessProposal (process_proposal.go:24-157), FilterTxs
(validate_txs.go:29-97), Begin/EndBlocker + upgrade consumption
(app.go:670-708), InitChainer (app.go:711-726), MaxEffectiveSquareSize
(square_size.go:9-23), and genesis export (export.go:18-45).

The consensus engine above this surface is celestia_tpu/node (testnode-style
single-process driver); the DA compute below it is the fused device pipeline
(da/dah.py).  Every consensus-relevant computation here is integer/bytes
arithmetic or the bit-exact device kernels.
"""

from __future__ import annotations

import hashlib as _hashlib
import time as _time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from celestia_tpu.appconsts import (
    DEFAULT_MIN_GAS_PRICE,
    LATEST_VERSION,
    SHARE_SIZE,
    square_size_upper_bound,
)
from celestia_tpu.da import dah as dah_mod
from celestia_tpu.da.blob import BlobTx, unmarshal_blob_tx
from celestia_tpu.da.square import Square, build as build_square, construct as construct_square
from celestia_tpu.state import app_versions
from celestia_tpu.state.ante import AnteContext, AnteError, GasMeter, run_ante
from celestia_tpu.state.auth import AccountKeeper
from celestia_tpu.state.bank import BankKeeper, FEE_COLLECTOR
from celestia_tpu.state.modules.blob import BlobKeeper, validate_blob_tx
from celestia_tpu.state.modules.feegrant import FeeGrantKeeper
from celestia_tpu.state.modules.blobstream import BlobstreamKeeper
from celestia_tpu.state.modules.mint import MintKeeper
from celestia_tpu.state.modules.upgrade import UpgradeKeeper
from celestia_tpu.state.params import ParamBlockList, ParamsKeeper, set_default_params
from celestia_tpu.state.posthandler import PostContext, new_post_handler
from celestia_tpu.state.staking import StakingKeeper
from celestia_tpu.state.store import MultiStore
from celestia_tpu.state.tx import (
    Msg,
    MsgAuthzGrant,
    MsgAuthzRevoke,
    MsgCreateVestingAccount,
    MsgDelegate,
    MsgExec,
    MsgFundCommunityPool,
    MsgGrantAllowance,
    MsgParamChange,
    MsgPayForBlobs,
    MsgRegisterEVMAddress,
    MsgRevokeAllowance,
    MsgSend,
    MsgSetWithdrawAddress,
    MsgSignalVersion,
    MsgSubmitEvidence,
    MsgSubmitProposal,
    MsgTryUpgrade,
    MsgUndelegate,
    MsgUnjail,
    MsgVerifyInvariant,
    MsgVote,
    MsgWithdrawDelegatorReward,
    MsgWithdrawValidatorCommission,
    Tx,
    unmarshal_tx,
)
from celestia_tpu.utils import tracing
from celestia_tpu.utils.lru import LruCache, bytes_len_weigher
from celestia_tpu.utils.telemetry import Telemetry


def _decoded_weigher(key, value) -> int:
    """(tx, raw_inner) entries: the raw inner bytes dominate; the parsed
    Tx holds commitments/signatures, approximated by a flat overhead."""
    _, raw_inner = value
    return len(key) + len(raw_inner) + 512


STORE_NAMES = [
    "auth", "bank", "staking", "params", "blob", "upgrade", "blobstream",
    "mint", "gov", "meta", "feegrant", "authz", "distribution", "slashing",
    "evidence", "ibc",
]

_APP_VERSION_KEY = b"app_version"


@dataclass
class TxResult:
    code: int  # 0 = ok
    log: str
    gas_wanted: int
    gas_used: int
    events: List[dict] = field(default_factory=list)


def jsonable_events(events: List[dict]) -> List[dict]:
    """Typed msg events with bytes fields -> JSON-safe form (hex), for
    the tx index, the event-query routes and the block log."""

    def conv(v):
        if isinstance(v, bytes):
            return v.hex()
        if isinstance(v, dict):
            return {k: conv(x) for k, x in v.items()}
        if isinstance(v, (list, tuple)):
            return [conv(x) for x in v]
        return v

    return [conv(e) for e in events]


@dataclass
class PreparedProposal:
    block_txs: List[bytes]
    square_size: int
    data_root: bytes
    eds: "dah_mod.ExtendedDataSquare"
    dah: "dah_mod.DataAvailabilityHeader"
    # retained layout artifacts so the node can serve inclusion proofs from
    # the cached EDS without recompute (pkg/inclusion / proof querier role)
    square: Optional[object] = None
    wrappers: Optional[List[object]] = None


class App:
    """The celestia-tpu application (app.go App struct parity)."""

    def __init__(
        self,
        chain_id: str = "celestia-tpu-1",
        min_gas_price: float = DEFAULT_MIN_GAS_PRICE,
        v2_upgrade_height: Optional[int] = None,
    ):
        self.chain_id = chain_id
        self.min_gas_price = min_gas_price  # node-local CheckTx filter
        self.v2_upgrade_height = v2_upgrade_height  # v1 height-based path
        from celestia_tpu.ops import gf256 as _gf256

        self.codec = _gf256.active_codec()  # re-pinned by init_chain
        self.store = MultiStore(STORE_NAMES)
        self._wire_keepers()
        self.telemetry = Telemetry()
        self.block_time_ns = 0
        self.block_height = 0
        self.genesis_time_ns = 0
        # persistent CheckTx state, branched from committed state and reset
        # on every commit (baseapp checkState parity) — lets several pending
        # txs from one account chain their sequences in the mempool
        self._check_state: Optional[MultiStore] = None
        # verified-signature cache (tx-bytes hash -> True), bounded LRU:
        # Prepare->Process on one node and repeat validations of pooled
        # txs skip redundant EC multiplications (comet's tx cache role)
        self._sig_cache = LruCache("sig", 8192, weigher=bytes_len_weigher)
        # validated-tx cache (tx-bytes hash -> (tx, raw_inner)), bounded
        # LRU: BlobTx validation recomputes every blob's share commitment
        # — deterministic in the raw bytes, so CheckTx's verdict is
        # reusable verbatim in Prepare/Process for the same bytes (the
        # reference revalidates at each point; caching by exact bytes is
        # the consensus-safe shortcut).  Values hold only the parsed
        # inner tx (commitments, no blob payloads), so entries are small.
        self._decoded_cache = LruCache(
            "decoded", 8192, weigher=_decoded_weigher
        )
        # post-handler chain (posthandler.go:1-12 parity: empty default)
        self.post_handler = new_post_handler()

    def _wire_keepers(self, rebuild_ibc: bool = True) -> None:
        """Re-point every keeper at the current self.store.

        rebuild_ibc=False (the per-tx branch swap in deliver) reuses the
        existing IBC stack and only swaps its store/bank handles — a full
        rebuild rescans + JSON-decodes the whole "ibc" substore, which
        would be paid twice per delivered tx for state no msg can touch.
        Restores/imports keep the default full rebuild (rehydrate)."""
        self.accounts = AccountKeeper(self.store.store("auth"))
        self.bank = BankKeeper(self.store.store("bank"))
        self.params = ParamsKeeper(self.store.store("params"))
        self.staking = StakingKeeper(self.store.store("staking"), self.bank)
        self.blob = BlobKeeper(self.params)
        self.upgrade = UpgradeKeeper(self.store.store("upgrade"), self.staking)
        self.blobstream = BlobstreamKeeper(
            self.store.store("blobstream"), self.staking, self.params
        )
        self.mint = MintKeeper(self.store.store("mint"), self.bank)
        from celestia_tpu.state.modules.authz import AuthzKeeper
        from celestia_tpu.state.modules.distribution import DistributionKeeper

        self.feegrant = FeeGrantKeeper(self.store.store("feegrant"))
        self.authz = AuthzKeeper(self.store.store("authz"))
        self.distribution = DistributionKeeper(
            self.store.store("distribution"), self.bank, self.staking
        )
        self.distribution.register_hooks()
        from celestia_tpu.state.modules.evidence import EvidenceKeeper
        from celestia_tpu.state.modules.slashing import SlashingKeeper

        self.slashing = SlashingKeeper(self.store.store("slashing"), self.staking)
        self.evidence = EvidenceKeeper(self.store.store("evidence"), self.slashing)
        self.param_block_list = ParamBlockList()
        from celestia_tpu.state.modules.gov import GovKeeper

        self.gov = GovKeeper(
            self.store.store("gov"), self.bank, self.staking, self.params,
            self.param_block_list,
        )
        # IBC transfer stack with the token filter mounted (app.go:71-78);
        # channel handshakes are operator-driven (ibc.open_channel)
        from celestia_tpu.state.modules.ibc import IBCStack

        prior = getattr(self, "ibc", None)
        if not rebuild_ibc and prior is not None:
            prior.rebind(self.store.store("ibc"), self.bank)
        else:
            self.ibc = IBCStack(
                name=self.chain_id, bank=self.bank, filtered=True, app=self,
                store=self.store.store("ibc"),
            )

    # ------------------------------------------------------------------
    # version / sizing
    # ------------------------------------------------------------------

    @property
    def app_version(self) -> int:
        raw = self.store.store("meta").get(_APP_VERSION_KEY)
        return int.from_bytes(raw, "big") if raw else LATEST_VERSION

    def _set_app_version(self, v: int) -> None:
        self.store.store("meta").set(_APP_VERSION_KEY, v.to_bytes(8, "big"))
        # decoded-tx verdicts can be version-dependent (ante/blob rules
        # change across app versions): a version change invalidates them.
        # The signature cache survives — a signature over exact raw bytes
        # is version-independent — and the EDS cache keys on app_version,
        # so its stale entries simply stop matching.
        self._decoded_cache.clear()

    def next_height(self) -> int:
        """Height the next tx would execute at: the in-flight block during
        delivery, or the block about to be built during check/propose."""
        return max(self.block_height, self.store.last_height + 1)

    def max_effective_square_size(self) -> int:
        """min(gov cap, hard cap) — square_size.go:9-23."""
        gov = self.blob.gov_max_square_size()
        return min(gov, square_size_upper_bound(self.app_version))

    # ------------------------------------------------------------------
    # genesis
    # ------------------------------------------------------------------

    def init_chain(self, genesis: dict) -> None:
        """InitChainer parity: seed params, accounts, validators, mint state.

        genesis = {
          "chain_id", "app_version", "genesis_time_ns",
          "accounts": [{"address": hex, "balance": int}],
          "validators": [{"address": hex, "self_delegation": int}],
          "params": {subspace: {key: value}},
        }
        """
        self.chain_id = genesis.get("chain_id", self.chain_id)
        # The share codec is a consensus constant pinned at genesis
        # (ADR-012): "leopard-ff8" (default; parity-byte compatible with
        # the reference chain's Leopard codec) or "lagrange-gf256".
        # Persisted in-store so a disk-recovered node re-activates it
        # without a side channel.
        from celestia_tpu.ops import gf256 as _gf256

        codec = genesis.get("codec", _gf256.CODEC_LEOPARD)
        _gf256.set_active_codec(codec)  # raises on unknown codec
        self.codec = codec
        self.store.store("meta").set(b"codec", codec.encode())
        set_default_params(self.params)
        for subspace, kvs in genesis.get("params", {}).items():
            for k, v in kvs.items():
                self.params.set(subspace, k, v)
        self._set_app_version(genesis.get("app_version", LATEST_VERSION))
        self.genesis_time_ns = genesis.get(
            # celint: allow(consensus-determinism) — operator-side default
            # for a genesis file that omits the timestamp; the chosen value
            # is persisted in-store below and shipped in the genesis dump,
            # so every validator runs from the same recorded instant
            "genesis_time_ns", _time.time_ns()
        )
        # persisted in-store so a disk-recovered node needs no side channel
        # (identical across validators -> app-hash safe)
        self.store.store("meta").set(
            b"genesis_time_ns", self.genesis_time_ns.to_bytes(8, "big")
        )
        self.store.store("meta").set(b"chain_id", self.chain_id.encode())
        self.mint.init_genesis(self.genesis_time_ns)
        for acc in genesis.get("accounts", []):
            addr = bytes.fromhex(acc["address"])
            self.bank.mint(addr, acc["balance"])
            self.accounts.get_or_create(addr)
        for val in genesis.get("validators", []):
            addr = bytes.fromhex(val["address"])
            self.accounts.get_or_create(addr)
            shortfall = val["self_delegation"] - self.bank.balance(addr)
            if shortfall > 0:
                self.bank.mint(addr, shortfall)
            self.staking.create_validator(addr, val["self_delegation"])
        self.store.commit(1)  # genesis state at height 1

    # ------------------------------------------------------------------
    # CheckTx (mempool admission) — check_tx.go:16-54
    # ------------------------------------------------------------------

    def _get_check_state(self) -> MultiStore:
        if self._check_state is None:
            self._check_state = self.store.branch()
        return self._check_state

    def check_tx(self, raw: bytes, is_recheck: bool = False) -> TxResult:
        self.telemetry.incr("check_tx")
        key = _hashlib.sha256(raw).digest()
        btx = unmarshal_blob_tx(raw)
        # run the ante chain on a branch of the persistent check state;
        # only successful checks fold back (failed antes must not burn a
        # pending account's sequence/fee in the check state)
        check_state = self._get_check_state()
        branch = check_state.branch()
        try:
            if btx is not None:
                # reject BlobTx whose PFB is malformed; validate blobs fully
                # on first check only (not recheck)
                if is_recheck:
                    tx = unmarshal_tx(btx.tx)
                else:
                    tx = validate_blob_tx(btx, self.chain_id)
                    # the verdict is deterministic in the raw bytes:
                    # Prepare/Process reuse it instead of re-hashing the
                    # blob payloads (check_tx.go validates, then the
                    # proposal paths validate the same bytes again)
                    self._remember_decoded(key, tx, btx.tx)
                raw_inner = btx.tx
            else:
                tx = unmarshal_tx(raw)
                from celestia_tpu.state.ante import flat_msgs

                if any(isinstance(m, MsgPayForBlobs) for m in flat_msgs(tx)):
                    # PFB without blobs is never admissible (check_tx.go:30)
                    # — including authz-wrapped PFBs
                    return TxResult(1, "MsgPayForBlobs transaction missing blobs", 0, 0)
                raw_inner = raw
            # signature cache, both directions: a recheck / re-submission
            # of exact bytes this node already verified skips the EC
            # multiplication, and a fresh admission remembers its verdict
            # so the prepare/process legs hit (the cache key commits to
            # the FULL raw bytes, so a hit proves the same check)
            sig_ok = None
            if not tx.is_multisig() and self._sig_cache.get(key) is not None:
                sig_ok = True
                self.telemetry.incr("ingress_sig_cache_hit")
            ctx = AnteContext(
                tx=tx,
                raw_tx=raw_inner,
                accounts=AccountKeeper(branch.store("auth")),
                bank=BankKeeper(branch.store("bank")),
                params=ParamsKeeper(branch.store("params")),
                chain_id=self.chain_id,
                app_version=self.app_version,
                is_check_tx=True,
                is_recheck=is_recheck,
                min_gas_price=self.min_gas_price,
                sig_ok=sig_ok,
                height=self.next_height(),
                feegrant=FeeGrantKeeper(branch.store("feegrant")),
                time_ns=self.block_time_ns,
            )
            meter = run_ante(ctx)
            check_state.write_back(branch)
            if sig_ok is None and not tx.is_multisig():
                # ante succeeded => verify_signature verified these exact
                # bytes inline; admission now pre-pays the proposal legs
                self._remember_sig(key)
            return TxResult(0, "", tx.fee.gas_limit, meter.consumed)
        except (AnteError, ValueError) as e:
            self.telemetry.incr("check_tx_rejected")
            return TxResult(1, str(e), 0, 0)

    def check_txs_batch(
        self, raws: List[bytes], is_recheck: bool = False
    ) -> List[TxResult]:
        """Batched CheckTx: decode a chunk of mempool ingress, resolve
        every single-key signature in ONE threaded ``verify_batch`` pass,
        then run the ante chain per tx with the verdict pre-resolved.

        Reuses the ``_decode_proposal_txs`` discipline: decoded-tx cache
        probe by tx-bytes hash, batch commitment warming, a per-call
        ``batch_ok`` map immune to mid-call LRU eviction, sig-cache
        probes resolving to True, multisig falling back to inline
        verification inside the ante chain.  Dedupe is SIG-LEVEL only:
        ante still runs once per input IN ORDER against the shared check
        state, so a duplicated raw fails its second occurrence with the
        same sequence mismatch the sequential loop produces — results
        are positionally identical to ``[check_tx(r) for r in raws]``
        (pinned by tests/test_tx_ingress.py).
        """
        from celestia_tpu.state.ante import flat_msgs
        from celestia_tpu.utils.secp256k1 import verify_batch

        n = len(raws)
        self.telemetry.incr("check_tx", n)
        self.telemetry.incr("ingress_batch_calls")
        self.telemetry.incr("ingress_batch_txs", n)
        with tracing.span("ingress.batch", txs=n):
            # decode phase: check_tx semantics + decoded-cache probe,
            # with every fresh blob commitment warmed in one native call
            keys: List[bytes] = []
            parsed: List[tuple] = []  # (raw, key, btx_or_None, cache_hit)
            warm: List = []
            for raw in raws:
                key = _hashlib.sha256(raw).digest()
                keys.append(key)
                hit = self._decoded_cache.get(key)
                if hit is not None:
                    parsed.append((raw, key, None, hit))
                    continue
                btx = unmarshal_blob_tx(raw)
                if btx is not None and not is_recheck:
                    warm.extend(btx.blobs)
                parsed.append((raw, key, btx, None))
            if warm:
                from celestia_tpu.da.inclusion import warm_commitments

                warm_commitments(warm)
            decoded: List[tuple] = []  # (tx, raw_inner, err)
            for raw, key, btx, hit in parsed:
                if hit is not None:
                    decoded.append((hit[0], hit[1], None))
                    continue
                try:
                    if btx is not None:
                        if is_recheck:
                            tx = unmarshal_tx(btx.tx)
                        else:
                            tx = validate_blob_tx(btx, self.chain_id)
                            self._remember_decoded(key, tx, btx.tx)
                        raw_inner = btx.tx
                    else:
                        tx = unmarshal_tx(raw)
                        if any(
                            isinstance(m, MsgPayForBlobs)
                            for m in flat_msgs(tx)
                        ):
                            raise AnteError(
                                "MsgPayForBlobs transaction missing blobs"
                            )
                        raw_inner = raw
                    decoded.append((tx, raw_inner, None))
                except (AnteError, ValueError) as e:
                    decoded.append((None, None, e))
            # signature phase: batch_ok is THIS call's key -> verdict map
            # (cache hits resolve True, distinct fresh keys verify once,
            # output reads ONLY batch_ok — immune to LRU eviction)
            batch_ok: Dict[bytes, Optional[bool]] = {}
            live: List = []
            live_keys: List[bytes] = []
            for (tx, _raw_inner, err), key in zip(decoded, keys):
                if tx is None or tx.is_multisig() or key in batch_ok:
                    continue
                if self._sig_cache.get(key) is not None:
                    batch_ok[key] = True
                    self.telemetry.incr("ingress_sig_cache_hit")
                else:
                    batch_ok[key] = None
                    live.append(tx)
                    live_keys.append(key)
            if live:
                sig_results = verify_batch(
                    [tx.sign_bytes(self.chain_id) for tx in live],
                    [tx.signature for tx in live],
                    [tx.pubkey for tx in live],
                )
                self.telemetry.incr("ingress_batch_verified", len(live))
                for key, ok in zip(live_keys, sig_results):
                    batch_ok[key] = bool(ok)
                    if ok:
                        self._remember_sig(key)
            # ante phase: sequential, order-preserving, on the shared
            # check state (only successful checks fold back)
            check_state = self._get_check_state()
            results: List[TxResult] = []
            for raw, (tx, raw_inner, err), key in zip(raws, decoded, keys):
                if err is not None:
                    self.telemetry.incr("check_tx_rejected")
                    results.append(TxResult(1, str(err), 0, 0))
                    continue
                if tx.is_multisig():
                    sig_ok: Optional[bool] = None
                    self.telemetry.incr("ingress_multisig_inline")
                else:
                    sig_ok = batch_ok[key]
                branch = check_state.branch()
                try:
                    ctx = AnteContext(
                        tx=tx,
                        raw_tx=raw_inner,
                        accounts=AccountKeeper(branch.store("auth")),
                        bank=BankKeeper(branch.store("bank")),
                        params=ParamsKeeper(branch.store("params")),
                        chain_id=self.chain_id,
                        app_version=self.app_version,
                        is_check_tx=True,
                        is_recheck=is_recheck,
                        min_gas_price=self.min_gas_price,
                        sig_ok=sig_ok,
                        height=self.next_height(),
                        feegrant=FeeGrantKeeper(branch.store("feegrant")),
                        time_ns=self.block_time_ns,
                    )
                    meter = run_ante(ctx)
                    check_state.write_back(branch)
                    results.append(
                        TxResult(0, "", tx.fee.gas_limit, meter.consumed)
                    )
                except (AnteError, ValueError) as e:
                    self.telemetry.incr("check_tx_rejected")
                    results.append(TxResult(1, str(e), 0, 0))
            return results

    # ------------------------------------------------------------------
    # PrepareProposal — prepare_proposal.go:23-96
    # ------------------------------------------------------------------

    def _decode_proposal_txs(self, txs: List[bytes]):
        """Decode every proposal tx, then batch-verify all signatures in one
        threaded native secp256k1 pass (the per-tx EC multiplication is the
        dominant host cost of FilterTxs/ProcessProposal — the reference
        leans on C secp256k1 for the same reason, SURVEY.md §2.2).

        Yields (raw, tx, raw_inner, sig_ok, decode_error) per input tx.

        Verified signatures are cached by tx-bytes hash (bounded LRU):
        a proposer's own ProcessProposal re-check of the block it just
        built, and repeat validations of the same bytes across proposal
        rounds, skip the EC multiplications — the dominant per-block
        host cost.  Only a verifying (pubkey, sign_bytes, signature)
        triple derived from the EXACT raw bytes is ever cached, so a hit
        proves the same signature check.  (CheckTx and check_txs_batch
        populate the same cache on successful admission, so a proposal
        built from batched mempool ingress filters signature-warm.)
        """
        from celestia_tpu.utils.secp256k1 import verify_batch

        # ONE full-data hash per tx, shared by the decoded-tx cache and
        # the signature cache (the raw bytes are the dominant hash cost
        # for blob txs).
        decoded: List[tuple] = []
        tx_keys: List[bytes] = []
        # pass 1: unmarshal envelopes and batch-warm every blob commitment
        # in ONE native call (per-blob recompute inside validate_blob_tx
        # then hits the cache) — at proposal scale the per-blob native
        # crossings were a visible slice of FilterTxs
        parsed: List[tuple] = []  # (raw, key, btx_or_None, cache_hit)
        warm: List = []
        for raw in txs:
            key = _hashlib.sha256(raw).digest()
            tx_keys.append(key)
            hit = self._decoded_cache.get(key)
            if hit is not None:
                parsed.append((raw, key, None, hit))
                continue
            btx = unmarshal_blob_tx(raw)
            if btx is not None:
                warm.extend(btx.blobs)
            parsed.append((raw, key, btx, None))
        if warm:
            from celestia_tpu.da.inclusion import warm_commitments

            warm_commitments(warm)
        for raw, key, btx, hit in parsed:
            if hit is not None:
                decoded.append((raw, hit[0], hit[1], None))
                continue
            try:
                if btx is not None:
                    # full BlobTx validation incl. commitment recompute
                    tx = validate_blob_tx(btx, self.chain_id)
                    raw_inner = btx.tx
                else:
                    tx = unmarshal_tx(raw)
                    from celestia_tpu.state.ante import flat_msgs

                    if any(isinstance(m, MsgPayForBlobs) for m in flat_msgs(tx)):
                        raise AnteError("PFB without blobs")
                    raw_inner = raw
                decoded.append((raw, tx, raw_inner, None))
                self._remember_decoded(key, tx, raw_inner)
            except (AnteError, ValueError) as e:
                decoded.append((raw, None, None, e))
        # single-key txs batch-verify natively; multisig txs fall back to
        # inline verification inside the ante chain (sig_ok=None).
        # batch_ok is THIS call's key -> verdict map: cache hits resolve
        # to True, each distinct fresh key is verified once (duplicates
        # dedupe), and the output loop reads ONLY batch_ok — immune to
        # LRU evictions _remember_sig performs mid-call.
        batch_ok: Dict[bytes, Optional[bool]] = {}
        keys: List[Optional[bytes]] = []
        live: List[tuple] = []
        live_keys: List[bytes] = []
        for d, key in zip(decoded, tx_keys):
            if d[1] is None or d[1].is_multisig():
                keys.append(None)
                continue
            keys.append(key)
            if key in batch_ok:
                continue
            if self._sig_cache.get(key) is not None:
                batch_ok[key] = True
            else:
                batch_ok[key] = None  # to be verified below
                live.append(d)
                live_keys.append(key)
        sig_results = verify_batch(
            [tx.sign_bytes(self.chain_id) for _, tx, _, _ in live],
            [tx.signature for _, tx, _, _ in live],
            [tx.pubkey for _, tx, _, _ in live],
        )
        for key, ok in zip(live_keys, sig_results):
            batch_ok[key] = bool(ok)
            if ok:
                self._remember_sig(key)
        out = []
        for d, key in zip(decoded, keys):
            raw, tx, raw_inner, err = d
            if tx is None:
                sig_ok = False
            elif tx.is_multisig():
                sig_ok = None
            else:
                sig_ok = batch_ok[key]
            out.append((raw, tx, raw_inner, sig_ok, err))
        return out

    def _remember_sig(self, key: bytes) -> None:
        self._sig_cache.put(key, True)

    def _remember_decoded(self, key: bytes, tx, raw_inner: bytes) -> None:
        self._decoded_cache.put(key, (tx, raw_inner))

    # legacy re-cap surface (tests/test_sig_cache.py assigns these): the
    # unified LruCache trims immediately on re-cap, which subsumes the
    # old lazy next-insert eviction
    @property
    def _sig_cache_max(self) -> int:
        return self._sig_cache.max_entries

    @_sig_cache_max.setter
    def _sig_cache_max(self, n: int) -> None:
        self._sig_cache.set_max_entries(n)

    @property
    def _decoded_cache_max(self) -> int:
        return self._decoded_cache.max_entries

    @_decoded_cache_max.setter
    def _decoded_cache_max(self, n: int) -> None:
        self._decoded_cache.set_max_entries(n)

    # below this many proposal txs the signer-grouping + fold overhead
    # outweighs any parallel ante win; the sequential leg is already fast
    _FILTER_PARALLEL_MIN_TXS = 16

    def _filter_txs(
        self, txs: List[bytes], parallel: Optional[bool] = None
    ) -> List[bytes]:
        """FilterTxs parity (validate_txs.go:29-97): run the ante chain over
        each tx on one branched state, in priority order; drop failures.

        ``parallel`` — None auto-routes (multi-core host AND enough txs),
        True/False force a leg.  The parallel leg groups txs by ante
        footprint and runs independent groups through the hostpool; it
        degrades to the sequential leg on any hazard (see
        ``_filter_groups``) and is pinned byte-identical to it by
        tests/test_tx_ingress.py.
        """
        decoded = self._decode_proposal_txs(txs)
        if parallel is None:
            from celestia_tpu.utils import hostpool

            parallel = (
                hostpool.cpu_threads() > 1
                and len(decoded) >= self._FILTER_PARALLEL_MIN_TXS
            )
        if parallel:
            kept = self._filter_txs_parallel(decoded)
            if kept is not None:
                return kept
            self.telemetry.incr("ingress_parallel_fallback")
        return self._filter_txs_sequential(decoded)

    def _filter_txs_sequential(self, decoded: List[tuple]) -> List[bytes]:
        """The reference leg: one shared branch, shared keepers, txs in
        priority order.  NOTE a failed ante leaves its partial writes on
        the shared branch (fee already deducted before the failing
        decorator ran) — later txs from the same payer observe them; the
        parallel leg reproduces this exactly."""
        branch = self.store.branch()
        accounts = AccountKeeper(branch.store("auth"))
        bank = BankKeeper(branch.store("bank"))
        params = ParamsKeeper(branch.store("params"))
        kept: List[bytes] = []
        for raw, tx, raw_inner, sig_ok, err in decoded:
            if err is not None:
                self.telemetry.incr("prepare_proposal_dropped_tx")
                continue
            try:
                ctx = AnteContext(
                    tx=tx,
                    raw_tx=raw_inner,
                    accounts=accounts,
                    bank=bank,
                    params=params,
                    chain_id=self.chain_id,
                    app_version=self.app_version,
                    sig_ok=sig_ok,
                    height=self.next_height(),
                    feegrant=FeeGrantKeeper(branch.store("feegrant")),
                    time_ns=self.block_time_ns,
                )
                run_ante(ctx)
                kept.append(raw)
            except (AnteError, ValueError):
                self.telemetry.incr("prepare_proposal_dropped_tx")
                continue
        return kept

    def _filter_groups(self, decoded: List[tuple]) -> Optional[List[List[int]]]:
        """Union-find over ante footprints -> independent groups of decoded
        indices, or None when a hazard forces the sequential leg.

        The ante chain reads/writes ONLY the tx's footprint accounts
        (signer + fee granter: auth record, bank balance, feegrant key),
        reads params (read-only here), and credits FEE_COLLECTOR (never
        read by any verdict).  Hazards — cases where that independence
        argument does not hold — degrade to sequential:

        * footprint undeterminable (malformed pubkey);
        * a footprint account that does not exist yet: get_or_create
          would allocate from the GLOBAL account-number counter, a
          cross-group write;
        * a footprint naming FEE_COLLECTOR: its balance would then gate
          a verdict.
        """
        from celestia_tpu.state.ante import ante_footprint

        parent: Dict[bytes, bytes] = {}

        def find(a: bytes) -> bytes:
            while parent[a] != a:
                parent[a] = parent[parent[a]]
                a = parent[a]
            return a

        tx_root: List[Optional[bytes]] = [None] * len(decoded)
        for i, (_raw, tx, _raw_inner, _sig_ok, err) in enumerate(decoded):
            if err is not None:
                continue  # pure drop: touches no state, needs no group
            fp = ante_footprint(tx)
            if fp is None:
                return None
            for addr in fp:
                if addr == FEE_COLLECTOR:
                    return None
                if addr not in parent:
                    parent[addr] = addr
                    if self.accounts.get(addr) is None:
                        return None
            ra = find(fp[0])
            for addr in fp[1:]:
                rb = find(addr)
                if ra != rb:
                    parent[rb] = ra
            tx_root[i] = ra
        groups: Dict[bytes, List[int]] = {}
        for i, a in enumerate(tx_root):
            if a is None:
                continue
            groups.setdefault(find(a), []).append(i)
        return list(groups.values())

    def _filter_txs_parallel(
        self, decoded: List[tuple]
    ) -> Optional[List[bytes]]:
        """Hostpool-parallel FilterTxs: ante for independent-footprint
        groups runs concurrently against branch snapshots; verdicts are
        then replayed in a deterministic sequential fold that performs
        the actual write-backs in original priority order.  Returns None
        to degrade (grouping hazard, or a pool-layer failure)."""
        from celestia_tpu.utils import faults, hostpool

        groups = self._filter_groups(decoded)
        if groups is None or len(groups) <= 1:
            return None
        base = self.store.branch()
        height = self.next_height()

        def ante_group(idxs: List[int]) -> List[tuple]:
            # re-runnable after a WorkerDeath self-heal: every mutation is
            # confined to branches created INSIDE this call
            gbranch = base.branch()
            out = []
            for i in idxs:
                _raw, tx, raw_inner, sig_ok, _err = decoded[i]
                sub = gbranch.branch()
                ok = True
                try:
                    ctx = AnteContext(
                        tx=tx,
                        raw_tx=raw_inner,
                        accounts=AccountKeeper(sub.store("auth")),
                        bank=BankKeeper(sub.store("bank")),
                        params=ParamsKeeper(sub.store("params")),
                        chain_id=self.chain_id,
                        app_version=self.app_version,
                        sig_ok=sig_ok,
                        height=height,
                        feegrant=FeeGrantKeeper(sub.store("feegrant")),
                        time_ns=self.block_time_ns,
                    )
                    run_ante(ctx)
                except (AnteError, ValueError):
                    ok = False
                # fold the sub-branch back on failure TOO: the sequential
                # leg's shared keepers keep a failed ante's partial writes
                # (fee deducted before the failing decorator), and later
                # same-payer txs must observe them
                delta = sub.overlay_delta()
                gbranch.write_back(sub)
                out.append((i, ok, delta))
            return out

        with tracing.span(
            "ante.parallel",
            groups=len(groups),
            txs=sum(len(g) for g in groups),
        ):
            try:
                results = hostpool.run_sharded(ante_group, groups)
            except Exception as e:  # pool-layer failure: degrade, don't drop
                faults.note("ingress.parallel", e)
                return None
        verdicts: Dict[int, tuple] = {}
        for group_out in results:
            for i, ok, delta in group_out:
                verdicts[i] = (ok, delta)
        # deterministic sequential fold: write-backs in priority order on
        # ONE branch (discarded like the sequential leg's), kept list and
        # drop counters in original order
        fold = self.store.branch()
        kept: List[bytes] = []
        for i, (raw, tx, _raw_inner, _sig_ok, err) in enumerate(decoded):
            if err is not None or i not in verdicts:
                self.telemetry.incr("prepare_proposal_dropped_tx")
                continue
            ok, delta = verdicts[i]
            fold.apply_overlay_delta(delta)
            if ok:
                kept.append(raw)
            else:
                self.telemetry.incr("prepare_proposal_dropped_tx")
        self.telemetry.incr("ingress_parallel_groups", len(groups))
        return kept

    def _extend_block_cached(
        self, block_txs: List[bytes], square, leg: str
    ) -> Tuple["dah_mod.ExtendedDataSquare", "dah_mod.DataAvailabilityHeader"]:
        """ExtendBlock through the content-addressed EDS cache.

        The key commits to the FULL tx bytes + square size + app version +
        active codec — never to a claimed data root — so only a proposal
        whose square this node already extended honestly can hit.  The
        proposer's own ProcessProposal re-extend, round-restart
        re-proposals and repeated gossip validations of one block all
        collapse to a lookup; everything else (ante, signatures, square
        reconstruction, the root comparison) still runs in the caller.
        """
        from celestia_tpu.da import eds_cache
        from celestia_tpu.ops import gf256 as _gf256

        key = eds_cache.make_key(
            block_txs, square.size, self.app_version, _gf256.active_codec()
        )
        with tracing.span("extend", leg=leg, k=square.size) as sp:
            cached = eds_cache.get(key)
            if cached is not None:
                self.telemetry.incr(f"eds_cache_hit_{leg}")
                sp.annotate(eds_cache="hit")
                tracing.instant("eds_cache.hit", cat="cache", leg=leg)
                return cached
            self.telemetry.incr(f"eds_cache_miss_{leg}")
            sp.annotate(eds_cache="miss")
            tracing.instant("eds_cache.miss", cat="cache", leg=leg)
            eds, dah = self._extend_square_routed(square)
            eds_cache.put(key, eds, dah)
            return eds, dah

    def _extend_square_routed(
        self, square
    ) -> Tuple["dah_mod.ExtendedDataSquare", "dah_mod.DataAvailabilityHeader"]:
        """ExtendBlock through the multi-chip mesh when the provider says
        so (parallel/mesh.py: >1 device visible / explicit --mesh, and
        the square's rows divide the row axis), else the single-device
        path (da/dah.extend_block — host-native fast paths, row memo and
        the jax leg all unchanged).  Byte-identity between the two legs
        is test-pinned, so cache semantics and the data-root compare are
        oblivious to which one ran.

        Degradation ladder (specs/robustness.md): a sharded failure
        mid-flight poisons the mesh one-way (loud — recorded as a
        degradation) and THIS call falls through to the single-device
        path, so the block being extended still commits the same root it
        would have on the mesh."""
        from celestia_tpu.parallel import mesh as mesh_mod

        m = mesh_mod.mesh_for_square(square.size)
        if m is not None:
            from celestia_tpu.parallel import sharded

            try:
                out = sharded.extend_block_sharded(square, m)
            except Exception as e:
                mesh_mod.poison(
                    f"sharded extend failed at k={square.size}: {e!r}"
                )
                self.telemetry.incr("extend_mesh_degraded")
            else:
                self.telemetry.incr("extend_sharded")
                return out
        return dah_mod.extend_block(square)

    # ------------------------------------------------------------------
    # batched multi-block validation (state-sync catch-up leg)
    # ------------------------------------------------------------------

    def warm_extends_batched(
        self, blocks: List[Tuple[List[bytes], int]]
    ) -> int:
        """Pre-extend many blocks' squares in batched mesh dispatches,
        filling the content-addressed EDS cache — BASELINE.json config
        #5 made live: a validator replaying n same-k blocks pays one
        device dispatch per batch instead of one per block.

        ``blocks``: (block_txs, claimed_square_size) pairs.  The extend
        is a pure function of (txs, size, app_version, codec) — state-
        independent — so warming ahead of sequential replay is always
        sound: the per-block validation that follows (ante, signatures,
        strict reconstruction, root compare) runs unchanged and simply
        hits the warm cache on its extend leg.  Entries whose square
        cannot be rebuilt at the claimed size are skipped (the per-block
        validation will reject them with its usual reasons).  Never
        raises — any failure degrades to the per-block path (noted).
        Returns the number of squares warmed."""
        from celestia_tpu.da import eds_cache
        from celestia_tpu.ops import gf256 as _gf256
        from celestia_tpu.parallel import mesh as mesh_mod
        from celestia_tpu.utils import faults

        if mesh_mod.device_mesh() is None:
            return 0
        codec = _gf256.active_codec()
        bound = self.max_effective_square_size()
        # group uncached, rebuildable squares by k (one batch per size)
        by_k: Dict[int, List[Tuple[bytes, object]]] = {}
        cached_hits = 0
        for block_txs, claimed_size in blocks:
            try:
                key = eds_cache.make_key(
                    block_txs, claimed_size, self.app_version, codec
                )
                if eds_cache.CACHE.peek(key) is not None:
                    # counter-free probe that still refreshes recency:
                    # an already-cached window block must not sit
                    # LRU-oldest while the warm puts below evict it
                    cached_hits += 1
                    continue
                square, _txs, _w = construct_square(list(block_txs), bound)
                if square.size != claimed_size:
                    continue  # per-block validation rejects it properly
                by_k.setdefault(square.size, []).append((key, square))
            except Exception as e:
                faults.note("mesh.batch_warm", e)
                continue
        warmed = 0
        # the cache is the hand-off: entries warmed beyond its capacity
        # would evict each other before the per-block validations read
        # them, turning the batched dispatch into pure extra work — ONE
        # budget across every group (a later group's puts evict an
        # earlier group's entries just as surely as its own), with a
        # slot reserved for each already-cached window entry the peek
        # above refreshed (warm puts must not evict those either); the
        # overflow degrades to per-block extends, and the truncation is
        # counted, never silent
        budget = max(0, eds_cache.CACHE.max_entries - 1 - cached_hits)
        for k, items in sorted(by_k.items()):
            if budget <= 0:
                self.telemetry.incr(
                    "extend_batch_warm_truncated", len(items)
                )
                continue
            m = mesh_mod.mesh_for_batch(k, min(len(items), budget))
            if m is None:
                continue  # whole group takes the per-block path
            if len(items) > budget:
                self.telemetry.incr(
                    "extend_batch_warm_truncated", len(items) - budget
                )
                items = items[:budget]
            try:
                from celestia_tpu.parallel import sharded

                arr = np.stack(
                    [
                        sq.to_array().reshape(k, k, SHARE_SIZE)
                        for _key, sq in items
                    ]
                )
                # the shard_map leading dim must divide the data axis,
                # and the jitted program is SHAPE-specialized — pad to a
                # bucketed size (data_ax x next-pow2 chunks) by
                # repeating the last square (pad results dropped), so a
                # varying window never cold-compiles a fresh program
                # per distinct n: at most log2(window) programs per k
                data_ax = int(m.shape["data"])
                chunks = -(-len(items) // data_ax)  # ceil division
                if chunks > 1:
                    chunks = 1 << (chunks - 1).bit_length()
                pad = data_ax * chunks - len(items)
                if pad:
                    arr = np.concatenate([arr, arr[-1:].repeat(pad, 0)])
                pairs = sharded.extend_and_headers_sharded_batch(
                    arr, m, count_squares=len(items)
                )
                for (key, _sq), (eds, dah) in zip(items, pairs):
                    eds_cache.put(key, eds, dah)
                    warmed += 1
                budget -= len(items)
                self.telemetry.incr("extend_batched_blocks", len(items))
            except Exception as e:
                mesh_mod.poison(
                    f"batched sharded extend failed at k={k}: {e!r}"
                )
                self.telemetry.incr("extend_mesh_degraded")
                break  # poisoned: remaining groups take the per-block path
        return warmed

    def validate_blocks_batched(
        self,
        proposals: List[Tuple[List[bytes], int, bytes]],
        warm_only: bool = False,
    ) -> List[Tuple[bool, str]]:
        """ProcessProposal over many blocks with the extends batched:
        one sharded device dispatch per same-k group fills the EDS
        cache, then every block runs the FULL per-block validation
        (ante, signatures, strict reconstruction, root compare) in
        order — nothing is weakened, the extend leg just hits warm.

        ``proposals``: (block_txs, square_size, data_root) triples.
        ``warm_only=True`` skips the per-block validations and returns
        [] — the state-sync catch-up uses this (its adoption path runs
        process_proposal itself per block, against the then-current
        state; verdicts computed here against today's state could
        differ on state-dependent ante checks)."""
        self.warm_extends_batched(
            [(txs, size) for txs, size, _root in proposals]
        )
        if warm_only:
            return []
        return [
            self.process_proposal(list(txs), size, root)
            for txs, size, root in proposals
        ]

    def prepare_proposal(self, txs: List[bytes]) -> PreparedProposal:
        t0 = self.telemetry.clock()
        try:
            # per-height root span (utils/tracing.py): the whole prepare
            # leg with its phases as children, ring-buffered for trace_dump
            with tracing.block_span(
                "prepare_proposal", height=self.next_height(), txs=len(txs)
            ):
                return self._prepare_proposal_traced(txs, t0)
        finally:
            self.telemetry.measure_since("prepare_proposal", t0)

    def _prepare_proposal_traced(
        self, txs: List[bytes], t0: float
    ) -> PreparedProposal:
        with tracing.span("filter_txs", txs=len(txs)):
            kept = self._filter_txs(txs)
        t1 = self.telemetry.clock()
        with tracing.span("square_build", txs=len(kept)):
            square, block_txs, wrappers = build_square(
                kept, self.max_effective_square_size()
            )
        t2 = self.telemetry.clock()
        eds, dah = self._extend_block_cached(block_txs, square, "prepare")
        t3 = self.telemetry.clock()
        # per-phase budget (SURVEY §7 hard part c): host tx filtering,
        # host square assembly, device extension incl. transfer —
        # telemetry + last_prepare_breakdown let the bench isolate
        # the transfer from real host-side overhead
        self.last_prepare_breakdown = {
            "filter_ms": (t1 - t0) * 1000.0,
            "build_ms": (t2 - t1) * 1000.0,
            "extend_ms": (t3 - t2) * 1000.0,
        }
        for name, v in self.last_prepare_breakdown.items():
            self.telemetry.observe(f"prepare_proposal.{name}", v)
        return PreparedProposal(
            block_txs=block_txs,
            square_size=square.size,
            data_root=dah.hash,
            eds=eds,
            dah=dah,
            square=square,
            wrappers=wrappers,
        )

    # ------------------------------------------------------------------
    # ProcessProposal — process_proposal.go:24-157
    # ------------------------------------------------------------------

    def process_proposal(
        self, block_txs: List[bytes], square_size: int, data_root: bytes
    ) -> Tuple[bool, str]:
        """Returns (accept, reason).  Panics are caught -> REJECT
        (process_proposal.go:26-34)."""
        t0 = self.telemetry.clock()
        try:
            with tracing.block_span(
                "process_proposal",
                height=self.next_height(),
                txs=len(block_txs),
            ):
                return self._process_proposal_traced(
                    block_txs, square_size, data_root
                )
        except Exception as e:
            self.telemetry.incr("process_proposal_panic_reject")
            return False, f"proposal rejected: {e}"
        finally:
            self.telemetry.measure_since("process_proposal", t0)

    def _process_proposal_traced(
        self, block_txs: List[bytes], square_size: int, data_root: bytes
    ) -> Tuple[bool, str]:
        branch = self.store.branch()
        accounts = AccountKeeper(branch.store("auth"))
        bank = BankKeeper(branch.store("bank"))
        params = ParamsKeeper(branch.store("params"))
        with tracing.span("decode_and_ante", txs=len(block_txs)):
            for raw, tx, raw_inner, sig_ok, err in self._decode_proposal_txs(
                block_txs
            ):
                if err is not None:
                    return False, f"invalid tx in proposal: {err}"
                ctx = AnteContext(
                    tx=tx,
                    raw_tx=raw_inner,
                    accounts=accounts,
                    bank=bank,
                    params=params,
                    chain_id=self.chain_id,
                    app_version=self.app_version,
                    sig_ok=sig_ok,
                    height=self.next_height(),
                    feegrant=FeeGrantKeeper(branch.store("feegrant")),
                    time_ns=self.block_time_ns,
                )
                run_ante(ctx)
        # strict reconstruction — NOT skippable on a cache hit: the
        # square must be re-derivable from the tx bytes under the
        # CURRENT size bound, and only that reconstruction makes the
        # cached (txs -> EDS/DAH) mapping apply to this proposal
        with tracing.span("square_build", txs=len(block_txs)):
            square, re_txs, _ = construct_square(
                block_txs, self.max_effective_square_size()
            )
        if square.size != square_size:
            return False, (
                f"square size mismatch: computed {square.size}, "
                f"header says {square_size}"
            )
        _, dah = self._extend_block_cached(block_txs, square, "process")
        if dah.hash != data_root:
            self.telemetry.incr("process_proposal_rejected_data_root")
            return False, (
                f"data root mismatch: computed {dah.hash.hex()}, "
                f"header says {data_root.hex()}"
            )
        return True, ""

    # ------------------------------------------------------------------
    # Block execution (Begin/Deliver/End/Commit)
    # ------------------------------------------------------------------

    def begin_block(
        self,
        height: int,
        time_ns: int,
        proposer: Optional[bytes] = None,
        votes: Optional[List[Tuple[bytes, bool]]] = None,
    ) -> None:
        """BeginBlocker: mint this block's provision, then allocate the fee
        collector (previous block's fees + the fresh provision) through
        x/distribution using the previous commit's proposer/votes — the SDK
        mint-before-distribution BeginBlock order."""
        self.block_time_ns = time_ns
        self.block_height = height
        # the deterministic clock vesting locks are evaluated at — every
        # state branch (check/ante/deliver) reads it from the bank store
        self.bank.set_block_time(time_ns)
        self.mint.begin_blocker(time_ns)
        self.distribution.allocate_tokens(proposer, votes)
        if votes is not None:
            # liveness window update + downtime jailing (slashing BeginBlocker)
            self.slashing.begin_blocker(votes, height, time_ns)

    def deliver_tx(self, raw: bytes) -> TxResult:
        """Execute one block tx (blob txs execute their inner PFB only —
        blobs never touch state; keeper.go:42-57).

        Decode-once: the protobuf decode done by CheckTx / the proposal
        legs is reused by raw-bytes hash.  READ-ONLY consult — delivery
        skips blob validation by design (committed blobs never touch
        state), so it must never seed the cache the proposal legs treat
        as proof of full BlobTx validation."""
        key = _hashlib.sha256(raw).digest()
        hit = self._decoded_cache.get(key)
        if hit is not None:
            self.telemetry.incr("decoded_cache_hit_deliver")
            tx, raw_inner = hit
        else:
            btx = unmarshal_blob_tx(raw)
            if btx is not None:
                tx = unmarshal_tx(btx.tx)
                raw_inner = btx.tx
            else:
                tx = unmarshal_tx(raw)
                raw_inner = raw
        # Phase 1 (SDK runTx parity): the ante chain runs on its own branch;
        # on success its writes (fee deduction, sequence bump) persist even
        # if message execution later fails.
        ante_branch = self.store.branch()
        ctx = AnteContext(
            tx=tx,
            raw_tx=raw_inner,
            accounts=AccountKeeper(ante_branch.store("auth")),
            bank=BankKeeper(ante_branch.store("bank")),
            params=ParamsKeeper(ante_branch.store("params")),
            chain_id=self.chain_id,
            app_version=self.app_version,
            height=self.next_height(),
            feegrant=FeeGrantKeeper(ante_branch.store("feegrant")),
            time_ns=self.block_time_ns,
        )
        try:
            meter = run_ante(ctx)
        except AnteError as e:
            return TxResult(1, str(e), tx.fee.gas_limit, 0)
        self.store.write_back(ante_branch)
        # Phase 2: messages execute on a cache-wrap; a failure discards ALL
        # message writes (atomic tx execution) while keeping the ante's.
        msg_branch = self.store.branch()
        saved_store = self.store
        self.store = msg_branch
        self._wire_keepers(rebuild_ibc=False)
        events: List[dict] = []
        try:
            for m in tx.msgs:
                events.append(self._execute_msg(m, meter))
            # post-handler chain (app/posthandler parity): runs on the
            # message branch AFTER execution; a raise rolls the whole tx
            # back with the same atomicity as a message failure
            self.post_handler(
                PostContext(tx=tx, app=self, events=events, gas_meter=meter)
            )
        except Exception as e:
            return TxResult(
                2, f"msg execution failed: {e}", tx.fee.gas_limit, meter.consumed
            )
        else:
            saved_store.write_back(msg_branch)
            return TxResult(0, "", tx.fee.gas_limit, meter.consumed, events)
        finally:
            self.store = saved_store
            self._wire_keepers(rebuild_ibc=False)

    def _execute_msg(self, msg: Msg, gas_meter: GasMeter) -> dict:
        if isinstance(msg, MsgSend):
            self.bank.send(msg.from_addr, msg.to_addr, msg.amount)
            # a recipient seeing funds for the first time gets its auth
            # account here, deterministically in-block (the SDK's bank ->
            # auth.NewAccount behavior): clients can then query a stable
            # account number before signing their first tx
            self.accounts.get_or_create(msg.to_addr)
            return {
                "type": "transfer",
                "amount": msg.amount,
                "sender": msg.from_addr.hex(),
                "recipient": msg.to_addr.hex(),
            }
        if isinstance(msg, MsgPayForBlobs):
            return self.blob.pay_for_blobs(msg, gas_meter)
        if isinstance(msg, MsgDelegate):
            self.staking.delegate(msg.delegator, msg.validator, msg.amount)
            return {"type": "delegate", "amount": msg.amount}
        if isinstance(msg, MsgUndelegate):
            self.staking.undelegate(msg.delegator, msg.validator, msg.amount)
            return {"type": "undelegate", "amount": msg.amount}
        if isinstance(msg, MsgSignalVersion):
            self.upgrade.signal_version(msg.validator, msg.version, self.app_version)
            return {"type": "signal_version", "version": msg.version}
        if isinstance(msg, MsgTryUpgrade):
            scheduled = self.upgrade.try_upgrade(self.app_version)
            return {"type": "try_upgrade", "scheduled": scheduled}
        if isinstance(msg, MsgRegisterEVMAddress):
            self.blobstream.register_evm_address(msg.validator, msg.evm_address)
            return {"type": "register_evm_address"}
        if isinstance(msg, MsgParamChange):
            from celestia_tpu.state.modules.gov import GOV_MODULE_ADDR

            # Only the gov module account may execute a param change — the
            # reference routes ALL param changes through a passed proposal
            # (x/paramfilter/gov_handler.go:36-60); a user-signed
            # MsgParamChange must never write state.
            if msg.authority != GOV_MODULE_ADDR:
                raise ValueError(
                    "param change authority must be the gov module account; "
                    "submit a MsgSubmitProposal instead"
                )
            self.param_block_list.validate_change(msg.subspace, msg.key)
            import json as _json

            self.params.set(msg.subspace, msg.key, _json.loads(msg.value))
            return {"type": "param_change", "key": f"{msg.subspace}/{msg.key}"}
        if isinstance(msg, MsgSubmitProposal):
            pid = self.gov.submit_proposal(msg, self.block_height)
            return {"type": "submit_proposal", "proposal_id": pid}
        if isinstance(msg, MsgVote):
            self.gov.vote(msg, self.block_height)
            return {"type": "vote", "proposal_id": msg.proposal_id}
        if isinstance(msg, MsgGrantAllowance):
            from celestia_tpu.state.modules.feegrant import Allowance

            self.feegrant.grant(
                msg.granter,
                msg.grantee,
                Allowance(
                    kind=msg.kind,
                    spend_limit=msg.spend_limit,
                    expiration_ns=msg.expiration_ns,
                    period_ns=msg.period_ns,
                    period_spend_limit=msg.period_spend_limit,
                ),
            )
            return {"type": "grant_allowance"}
        if isinstance(msg, MsgRevokeAllowance):
            self.feegrant.revoke(msg.granter, msg.grantee)
            return {"type": "revoke_allowance"}
        if isinstance(msg, MsgAuthzGrant):
            from celestia_tpu.state.modules.authz import Authorization

            self.authz.grant(
                msg.granter,
                msg.grantee,
                Authorization(
                    msg_type=msg.msg_type,
                    spend_limit=msg.spend_limit,
                    expiration_ns=msg.expiration_ns,
                ),
            )
            return {"type": "authz_grant"}
        if isinstance(msg, MsgAuthzRevoke):
            self.authz.revoke(msg.granter, msg.grantee, msg.msg_type)
            return {"type": "authz_revoke"}
        if isinstance(msg, MsgWithdrawDelegatorReward):
            amount = self.distribution.withdraw_delegator_reward(
                msg.delegator, msg.validator
            )
            return {"type": "withdraw_rewards", "amount": amount}
        if isinstance(msg, MsgWithdrawValidatorCommission):
            amount = self.distribution.withdraw_validator_commission(msg.validator)
            return {"type": "withdraw_commission", "amount": amount}
        if isinstance(msg, MsgFundCommunityPool):
            self.distribution.fund_community_pool(msg.depositor, msg.amount)
            return {"type": "fund_community_pool", "amount": msg.amount}
        if isinstance(msg, MsgSetWithdrawAddress):
            self.distribution.set_withdraw_address(
                msg.delegator, msg.withdraw_address
            )
            return {"type": "set_withdraw_address"}
        if isinstance(msg, MsgUnjail):
            self.slashing.unjail(msg.validator, self.block_time_ns)
            return {"type": "unjail"}
        if isinstance(msg, MsgSubmitEvidence):
            from celestia_tpu.state.modules.evidence import Equivocation

            # the msg path is permissionless, so the evidence must PROVE
            # the double-sign against the validator's registered pubkey
            val_acc = self.accounts.get(msg.validator)
            slashed = self.evidence.submit(
                Equivocation(
                    msg.validator, msg.height, msg.time_ns,
                    msg.block_hash_a, msg.sig_a,
                    msg.block_hash_b, msg.sig_b,
                ),
                self.block_height,
                self.block_time_ns,
                chain_id=self.chain_id,
                pubkey=val_acc.pubkey if val_acc else b"",
            )
            return {"type": "submit_evidence", "slashed": slashed}
        if isinstance(msg, MsgVerifyInvariant):
            from celestia_tpu.state.invariants import (
                DEFAULT_INVARIANTS,
                GAS_COST_PER_INVARIANT,
                assert_invariants,
            )

            names = [msg.invariant] if msg.invariant else None
            gas_meter.consume(
                GAS_COST_PER_INVARIANT
                * (len(names) if names else len(DEFAULT_INVARIANTS)),
                "verify invariant",
            )
            results = assert_invariants(self, names)
            return {"type": "verify_invariant", "results": results}
        if isinstance(msg, MsgCreateVestingAccount):
            # fund a fresh account under a vesting schedule (the SDK's
            # MsgCreateVestingAccount: start = block time)
            self.bank.set_vesting_schedule(
                msg.to_addr, msg.amount, self.block_time_ns,
                msg.end_time_ns, msg.delayed,
            )
            self.bank.send(msg.from_addr, msg.to_addr, msg.amount)
            self.accounts.get_or_create(msg.to_addr)
            return {"type": "create_vesting_account", "amount": msg.amount}
        if isinstance(msg, MsgExec):
            inner_events = []
            for im in msg.inner:
                # every inner signer must have granted the grantee this
                # message type (authz MsgExec dispatch)
                for signer in im.signers():
                    self.authz.check_and_consume(
                        signer, msg.grantee, im, self.block_time_ns
                    )
                inner_events.append(self._execute_msg(im, gas_meter))
            return {"type": "exec", "inner": inner_events}
        raise ValueError(f"no handler for message {type(msg).__name__}")

    def end_block(self, height: int, time_ns: int) -> dict:
        """EndBlocker parity (app.go:675-708): module end-blockers, then
        upgrade consumption (v1 height-based or v2 signal-based)."""
        attestations = self.blobstream.end_blocker(height, time_ns)
        gov_events = self.gov.end_blocker(height, self)
        upgraded_to = None
        if self.app_version == 1 and self.v2_upgrade_height is not None:
            if height == self.v2_upgrade_height - 1:
                upgraded_to = 2
        else:
            pending = self.upgrade.should_upgrade()
            if pending is not None and pending > self.app_version:
                if pending in app_versions.supported_versions():
                    upgraded_to = pending
                else:
                    # quorum reached but this binary can't run the new
                    # version: keep the upgrade pending (operators must
                    # restart with the release that supports it)
                    self.telemetry.incr("upgrade_pending_unsupported")
        if upgraded_to is not None:
            log = app_versions.run_migrations(self, self.app_version, upgraded_to)
            self._set_app_version(upgraded_to)
            self.upgrade.consume_upgrade()
            self.telemetry.incr("upgrades")
            return {
                "attestations": attestations,
                "gov": gov_events,
                "upgraded_to": upgraded_to,
                "migrations": log,
            }
        return {"attestations": attestations, "gov": gov_events}

    def finalize_block(
        self,
        block_txs: List[bytes],
        height: int,
        time_ns: int,
        data_root: bytes,
        proposer: Optional[bytes] = None,
        votes: Optional[List[Tuple[bytes, bool]]] = None,
    ) -> Tuple[List[TxResult], dict, bytes]:
        """Begin -> deliver all -> end -> record data root -> commit.

        Returns (tx results, end-block response, app hash)."""
        self.begin_block(height, time_ns, proposer, votes)
        results = [self.deliver_tx(raw) for raw in block_txs]
        self.blobstream.record_data_root(height, data_root)
        end = self.end_block(height, time_ns)
        app_hash = self.store.commit(height)
        # reset the CheckTx state to the fresh committed state (baseapp
        # resets checkState on Commit; pending mempool txs get recheck'd)
        self._check_state = None
        return results, end, app_hash

    # ------------------------------------------------------------------
    # export / load (checkpoint-resume surface)
    # ------------------------------------------------------------------

    def export_genesis(self) -> dict:
        """ExportAppStateAndValidators parity (export.go:18-45)."""
        return {
            "chain_id": self.chain_id,
            "app_version": self.app_version,
            "genesis_time_ns": self.genesis_time_ns,
            "codec": self.codec,
            "state": self.store.export(),
        }

    def _restore_codec_from_meta(self) -> None:
        """Re-activate the codec a restored state was created under.
        Legacy state (pre-ADR-012, no persisted codec) was ALWAYS the
        lagrange codec — defaulting it to leopard would silently change
        parity bytes against the chain's own committed roots."""
        from celestia_tpu.ops import gf256 as _gf256

        raw = self.store.store("meta").get(b"codec")
        self.codec = raw.decode() if raw else _gf256.CODEC_LAGRANGE
        _gf256.set_active_codec(self.codec)

    @classmethod
    def import_genesis(cls, dump: dict, **kwargs) -> "App":
        app = cls(chain_id=dump["chain_id"], **kwargs)
        app.store = MultiStore.import_state(dump["state"])
        for name in STORE_NAMES:
            app.store.ensure_store(name)
        app._restore_codec_from_meta()
        if "codec" in dump:  # explicit dump key wins (they should agree)
            from celestia_tpu.ops import gf256 as _gf256

            app.codec = dump["codec"]
            _gf256.set_active_codec(app.codec)
        app._wire_keepers()
        app.genesis_time_ns = dump.get("genesis_time_ns", 0)
        app.store.commit(1)
        return app

    def load_height(self, height: int) -> None:
        """Roll back to a committed height (app.go:729 LoadHeight)."""
        self.store.load_height(height)
        self._wire_keepers()

    @classmethod
    def restore_from_snapshot(
        cls,
        chain_id: str,
        state: dict,
        height: int,
        expected_app_hash: bytes,
        genesis_time_ns: int = 0,
        **kwargs,
    ) -> "App":
        """Rebuild an App from a state-sync snapshot (the restore half of
        the reference's snapshot subsystem, root.go:227-243).  The restored
        multistore must reproduce the snapshot's recorded app hash."""
        app = cls(chain_id=chain_id, **kwargs)
        app.store = MultiStore.import_state(state)
        for name in STORE_NAMES:
            app.store.ensure_store(name)
        app._restore_codec_from_meta()
        app._wire_keepers()
        app.genesis_time_ns = genesis_time_ns
        got = app.store.app_hash()
        if got != expected_app_hash:
            raise ValueError(
                f"snapshot restore hash mismatch: state hashes to "
                f"{got.hex()}, snapshot recorded {expected_app_hash.hex()}"
            )
        app.store.commit_at(height, got)
        return app

    @classmethod
    def restore_from_disk(
        cls,
        state: "Dict[str, Dict[bytes, bytes]]",
        height: int,
        expected_app_hash: bytes,
        **kwargs,
    ) -> "App":
        """Rebuild an App from a recovered state.log (state.disk), the
        LoadLatestVersion role of app/app.go:657-661.  The replayed state
        must reproduce the last committed app hash or recovery refuses."""
        app = cls(**kwargs)
        app.store = MultiStore.from_raw(state)
        for name in STORE_NAMES:
            app.store.ensure_store(name)
        # identity first: _wire_keepers bakes chain_id into the IBC stack
        meta = app.store.store("meta")
        raw_ts = meta.get(b"genesis_time_ns")
        app.genesis_time_ns = int.from_bytes(raw_ts, "big") if raw_ts else 0
        raw_cid = meta.get(b"chain_id")
        if raw_cid:
            app.chain_id = raw_cid.decode()
        app._restore_codec_from_meta()
        app._wire_keepers()
        got = app.store.app_hash()
        if got != expected_app_hash:
            raise ValueError(
                f"disk recovery hash mismatch: replayed state hashes to "
                f"{got.hex()}, log recorded {expected_app_hash.hex()}"
            )
        app.store.commit_at(height, got)
        app.block_height = height
        return app
