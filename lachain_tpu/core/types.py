"""Chain data types: transactions, receipts, block headers, blocks.

Parity with the reference's proto layer
(/root/reference/src/Lachain.Proto: transaction.proto, block.proto) and the
tx-hashing rules (src/Lachain.Crypto/TransactionUtils.cs:1-107). Our wire
format is the framework's fixed-width codec; hashes are keccak256 over the
canonical encoding (chain-id mixed into the signing hash, EIP-155-style).

Senders. A transaction's sender is recovered from its signature, and one
routine does it for every caller, `_resolve_senders`: an answer is kept on
the object (`_sender_cache`, per chain id) and process-wide
(`_SENDER_MEMO`, by signing hash and signature, failures included), a miss
of both goes to the native library in one call that returns addresses, and
both are filled. `SignedTransaction.sender` (a list of one) and
`warm_sender_caches` (a batch) are that routine, so whichever way a node
learns a transaction (RPC, gossip, block sync) it recovers the signature
once, also when the block that holds it is decoded anew from the agreed
proposals and ordered in `create_header`.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

from ..crypto import ecdsa
from ..crypto.hashes import keccak256, merkle_root
from ..utils import metrics, tracing
from ..utils.serialization import (
    Reader,
    write_bytes,
    write_bytes_list,
    write_u32,
    write_u64,
    write_u256,
)

ADDRESS_BYTES = 20
ZERO_ADDRESS = b"\x00" * ADDRESS_BYTES
ZERO_HASH = b"\x00" * 32


@dataclass(frozen=True)
class Transaction:
    """A transfer / contract call (reference: transaction.proto Transaction)."""

    to: bytes  # 20 bytes; ZERO_ADDRESS + invocation => deploy
    value: int  # wei-style u256
    nonce: int
    gas_price: int
    gas_limit: int
    invocation: bytes = b""  # contract input

    def encode(self) -> bytes:
        return (
            self.to
            + write_u256(self.value)
            + write_u64(self.nonce)
            + write_u256(self.gas_price)
            + write_u64(self.gas_limit)
            + write_bytes(self.invocation)
        )

    @classmethod
    def decode(cls, data: bytes) -> "Transaction":
        r = Reader(data)
        to = r.raw(ADDRESS_BYTES)
        value = r.u256()
        nonce = r.u64()
        gas_price = r.u256()
        gas_limit = r.u64()
        invocation = r.bytes_()
        r.assert_eof()
        return cls(to, value, nonce, gas_price, gas_limit, invocation)

    def signing_hash(self, chain_id: int) -> bytes:
        """Hash to sign — chain id mixed in (EIP-155 shape,
        reference TransactionUtils.cs)."""
        return keccak256(self.encode() + write_u64(chain_id))


# The process-wide half of the sender caches (_resolve_senders below):
# (signing_hash, signature) -> recovered address; _MISS marks a signature
# that failed recovery so invalid txs don't retry the recover either
_MISS = object()
_SENDER_MEMO: dict = {}
_SENDER_MEMO_MAX = 65536  # entries; past it the memo is cleared whole


@dataclass(frozen=True)
class SignedTransaction:
    tx: Transaction
    signature: bytes  # 65-byte recoverable ECDSA

    def encode(self) -> bytes:
        # immutable value object: ordering, pooling, block assembly and
        # hashing all re-encode the same tx many times per era — memoize
        # (the reference's proto objects keep their serialized form too)
        cached = self.__dict__.get("_enc_cache")
        if cached is None:
            cached = write_bytes(self.tx.encode()) + write_bytes(
                self.signature
            )
            object.__setattr__(self, "_enc_cache", cached)
        return cached

    @classmethod
    def decode(cls, data: bytes) -> "SignedTransaction":
        r = Reader(data)
        tx = Transaction.decode(r.bytes_())
        sig = r.bytes_()
        r.assert_eof()
        out = cls(tx, sig)
        # assert_eof proved `data` IS the canonical encoding — seed the
        # memo so wire-decoded txs never pay the re-encode either
        object.__setattr__(out, "_enc_cache", data)
        return out

    def hash(self) -> bytes:
        cached = self.__dict__.get("_hash_cache")
        if cached is None:
            cached = keccak256(self.encode())
            object.__setattr__(self, "_hash_cache", cached)
        return cached

    def sender(self, chain_id: int) -> Optional[bytes]:
        """Recovered 20-byte sender address, or None if invalid. Ordering,
        execution and the pool all ask repeatedly: an object that holds
        its answer for this chain id returns it here, before any counter,
        scope or lock; one that does not goes through _resolve_senders as
        a list of one."""
        cached = self.__dict__.get("_sender_cache")
        if cached is not None and cached[0] == chain_id:
            return cached[1]
        _resolve_senders((self,), chain_id)
        return self.__dict__["_sender_cache"][1]


def _resolve_senders(stxs, chain_id: int) -> None:
    """THE sender resolver: `SignedTransaction.sender` and
    `warm_sender_caches` are this routine and nothing else, so a signature
    is recovered once a process however its transaction arrives. For each
    transaction, in order:

    1. the object's own `_sender_cache` (chain id, address): set here and
       only here; an object that holds this chain id is skipped;
    2. the process-wide `_SENDER_MEMO`, by (signing hash, signature), a
       failed recovery included (`_MISS`). A block's transactions are
       decoded anew from the agreed proposals, and an in-process devnet
       decodes one wire transaction into an object a validator: new
       objects, the same key. The memo is what makes a transaction a node
       admitted by gossip (`warm_sender_caches`) cost no second recovery
       when `create_header` orders its block (the reference keeps its
       recoveries in TransactionManager's verify cache,
       TransactionManager.cs:141-171);
    3. one call for every key both missed, each key once:
       `ecdsa.recover_address_batch_host`, the threaded native address
       entry at every size, never the chip route, so no recovered key is
       decompressed in Python for its keccak. The
       `ecdsa_recover` part of the loop thread's ledger is this step
       alone;
    4. the memo (cleared whole once it holds more than
       `_SENDER_MEMO_MAX` entries) and every waiting object's cache are
       filled.

    Counters, one `inc` a call at most each, none for a call step 1
    answered whole: `txpool_sender_memo_hits_total` (objects step 2
    answered), `txpool_sender_recoveries_total` (keys handed to step 3)
    and `txpool_sender_recovery_calls_total` (step 3's calls, so
    recoveries over calls is the keys a call). Recoveries over
    transactions committed is 1 in a healthy node."""
    pending: dict = {}  # memo key -> the objects that wait for it
    hits = 0
    for stx in stxs:
        cached = stx.__dict__.get("_sender_cache")
        if cached is not None and cached[0] == chain_id:
            continue
        key = (stx.tx.signing_hash(chain_id), stx.signature)
        addr = _SENDER_MEMO.get(key)
        if addr is None:
            pending.setdefault(key, []).append(stx)
            continue
        hits += 1
        object.__setattr__(
            stx, "_sender_cache", (chain_id, None if addr is _MISS else addr)
        )
    if hits:
        metrics.inc("txpool_sender_memo_hits_total", hits)
    if not pending:
        return
    keys = list(pending)
    with tracing.account("ecdsa_recover"):  # both caches missed
        addrs = ecdsa.recover_address_batch_host(
            [h for h, _ in keys], [sig for _, sig in keys]
        )
    metrics.inc("txpool_sender_recoveries_total", len(keys))
    metrics.inc("txpool_sender_recovery_calls_total")
    if len(_SENDER_MEMO) > _SENDER_MEMO_MAX:
        _SENDER_MEMO.clear()
    for key, addr in zip(keys, addrs):
        _SENDER_MEMO[key] = _MISS if addr is None else addr
        for stx in pending[key]:
            object.__setattr__(stx, "_sender_cache", (chain_id, addr))


def warm_sender_caches(stxs, chain_id: int) -> None:
    """Resolve many transactions' senders at once, so that the misses
    share one threaded native call — the pool/sync bulk-ingest path (role
    of the reference's background TransactionVerifier,
    Blockchain/Operations/TransactionVerifier.cs:23-72): gossip admission
    (`Node._on_pool_txs`), block sync, `la_sendRawTransactionBatch`, and
    every block's ordering (`BlockManager.order_transactions`, which
    `create_header` and `execute_block` go through). Safe to call with
    any mix: see `_resolve_senders`."""
    _resolve_senders(stxs, chain_id)


def sign_transaction(
    tx: Transaction, priv: bytes, chain_id: int
) -> SignedTransaction:
    return SignedTransaction(
        tx=tx, signature=ecdsa.sign_hash(priv, tx.signing_hash(chain_id))
    )


@dataclass(frozen=True)
class TransactionReceipt:
    """Execution result (reference: TransactionReceipt in transaction.proto +
    event.proto logs)."""

    tx_hash: bytes
    block_index: int
    index_in_block: int
    gas_used: int
    status: int  # 1 success, 0 failed
    sender: bytes = ZERO_ADDRESS
    return_data: bytes = b""

    def encode(self) -> bytes:
        return (
            self.tx_hash
            + write_u64(self.block_index)
            + write_u32(self.index_in_block)
            + write_u64(self.gas_used)
            + write_u32(self.status)
            + self.sender
            + write_bytes(self.return_data)
        )

    @classmethod
    def decode(cls, data: bytes) -> "TransactionReceipt":
        r = Reader(data)
        tx_hash = r.raw(32)
        block_index = r.u64()
        index_in_block = r.u32()
        gas_used = r.u64()
        status = r.u32()
        sender = r.raw(ADDRESS_BYTES)
        return_data = r.bytes_()
        r.assert_eof()
        return cls(
            tx_hash, block_index, index_in_block, gas_used, status,
            sender, return_data,
        )


@dataclass(frozen=True)
class BlockHeader:
    """Reference: block.proto BlockHeader (prev hash, merkle root, state hash,
    index, nonce)."""

    index: int
    prev_block_hash: bytes
    merkle_root: bytes  # over tx hashes
    state_hash: bytes
    nonce: int  # from the era's common coin (RootProtocol.cs:316-322)

    def encode(self) -> bytes:
        return (
            write_u64(self.index)
            + self.prev_block_hash
            + self.merkle_root
            + self.state_hash
            + write_u64(self.nonce)
        )

    @classmethod
    def decode(cls, data: bytes) -> "BlockHeader":
        r = Reader(data)
        index = r.u64()
        prev_h = r.raw(32)
        mroot = r.raw(32)
        shash = r.raw(32)
        nonce = r.u64()
        r.assert_eof()
        return cls(index, prev_h, mroot, shash, nonce)

    def hash(self) -> bytes:
        return keccak256(self.encode())


@dataclass(frozen=True)
class MultiSig:
    """Quorum of validator header signatures (reference: multisig.proto)."""

    signatures: Tuple[Tuple[int, bytes], ...]  # (validator index, ecdsa sig)

    def encode(self) -> bytes:
        out = write_u32(len(self.signatures))
        for idx, sig in self.signatures:
            out += write_u32(idx) + write_bytes(sig)
        return out

    @classmethod
    def decode(cls, data: bytes) -> "MultiSig":
        r = Reader(data)
        n = r.u32()
        sigs = tuple((r.u32(), r.bytes_()) for _ in range(n))
        r.assert_eof()
        return cls(sigs)


@dataclass(frozen=True)
class Block:
    header: BlockHeader
    tx_hashes: Tuple[bytes, ...]
    multisig: MultiSig

    def encode(self) -> bytes:
        return (
            write_bytes(self.header.encode())
            + write_bytes_list(list(self.tx_hashes))
            + write_bytes(self.multisig.encode())
        )

    @classmethod
    def decode(cls, data: bytes) -> "Block":
        r = Reader(data)
        header = BlockHeader.decode(r.bytes_())
        tx_hashes = tuple(r.bytes_list())
        multisig = MultiSig.decode(r.bytes_())
        r.assert_eof()
        return cls(header, tx_hashes, multisig)

    def hash(self) -> bytes:
        return self.header.hash()


# header creation and execute_block's header check both derive the merkle
# root over the same tx-hash list a few milliseconds apart; the pairwise
# keccak tree is ~15ms at 10k txs, so memo the last few (FIFO like the
# emulate memo; hashing the key tuple is ~30x cheaper than the tree)
_MERKLE_MEMO: dict = {}
_MERKLE_MEMO_MAX = 8


def tx_merkle_root(tx_hashes: Sequence[bytes]) -> bytes:
    key = tuple(tx_hashes)
    root = _MERKLE_MEMO.get(key)
    if root is None:
        root = merkle_root(list(key)) or ZERO_HASH
        _MERKLE_MEMO[key] = root
        while len(_MERKLE_MEMO) > _MERKLE_MEMO_MAX:
            _MERKLE_MEMO.pop(next(iter(_MERKLE_MEMO)))
    return root
