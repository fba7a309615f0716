"""The other N-1 members of a committee, for a validator hosted alone.

`CommitteeScript` stands in for the machines of validator 0's N-1 peers in
one HoneyBadgerBFT committee. Validator 0 runs in a NativeSimulatedNetwork
built with `committee=` (consensus/native_rt.py): the engine hosts it alone,
hands what it sends the committee to `react`, and takes the answers in its
own message types. Every peer is honest and answers at once: each message of
validator 0 is answered by the peers' message of the same protocol step
(its ECHO of slot j by theirs, its BVAL by theirs, its coin share by
theirs, ...), so the committee moves in lockstep with it, with no injected
delay.

What the peers send is built two ways:

* tables, in `setup` (what does not depend on validator 0): each peer's
  proposal, transfers from accounts of its own (`peer_balances` funds them
  at genesis; account a of a peer sends one transfer an era, its nonce the
  era less one, so that every proposal executes whole), TPKE-encrypted,
  RS-coded and Merkle-branched exactly as RBC does; the peers' VALs, ECHOs
  and READYs of those proposals; every peer's decryption share of every
  peer ciphertext; every peer's share of every coin the committee's
  agreements toss; the peers' BinaryBroadcast votes. `workers` > 1 builds
  the eras' tables in that many processes, which `start` sets going and
  `join` waits for, so that the caller can do other set-up meanwhile.
* live answers, in `react` (what does): echoes of validator 0's own shards,
  readies for its root, decryption shares of its ciphertext, and header
  signatures over the block it built, read from its own signed header.

Every share is computed with that peer's own key (consensus/keys.py
trusted_key_gen) and every shard carries its real branch, so validator 0
verifies, decodes, decrypts, combines, executes and signs exactly as among
real peers. Each answer runs under the span `script.react` (category
`script`, args era, kind, n); the counters committee_script_messages_total
(by kind; the unlabeled series is their sum) and
committee_script_seconds_total say what it delivered and what it cost.
"""
from __future__ import annotations

import random
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..utils import metrics, tracing
from . import messages as M
from .native_rt import (
    KIND_COIN,
    KIND_DECRYPTED,
    KIND_SIGNED_HEADER,
    MT_AUX,
    MT_BVAL,
    MT_CONF,
    MT_ECHO,
    MT_OPAQUE,
    MT_READY,
    MT_VAL,
    SeamRecord,
    encode_seam_record,
)

# the epochs a BinaryAgreement that decides 1 at epoch 3 runs (its lag
# rounds end past epoch 3 + 2 * EXTRA_ROUNDS) and the one real coin among
# them (binary_agreement.py coin_schedule)
BB_EPOCHS = (0, 2, 4, 6, 8)
COIN_EPOCH = 5
NONCE_COIN = (-1, 0)  # root_protocol.py's nonce coin

_BB_KIND = {MT_BVAL: "bval", MT_AUX: "aux", MT_CONF: "conf"}
_BB_VALUE = {MT_BVAL: 1, MT_AUX: 1, MT_CONF: 2}  # every peer votes 1


def _det(*parts) -> bytes:
    from ..crypto.hashes import keccak256

    return keccak256(b"|".join(str(p).encode() for p in parts))


def peer_account_key(seed: int, peer: int, account: int) -> bytes:
    from ..crypto import ecdsa

    d = int.from_bytes(_det("committee-account", seed, peer, account), "big")
    return (d % (ecdsa.N - 1) + 1).to_bytes(32, "big")


def peer_recipient(seed: int, peer: int, account: int) -> bytes:
    return _det("committee-recipient", seed, peer, account % 8)[:20]


def peer_transactions(
    seed: int, chain_id: int, era: int, peer: int, count: int
) -> list:
    """Peer `peer`'s proposal for `era`: one transfer from each of its
    `count` accounts, nonce era - 1."""
    from ..core.types import Transaction, sign_transaction

    return [
        sign_transaction(
            Transaction(
                to=peer_recipient(seed, peer, a),
                value=1,
                nonce=era - 1,
                gas_price=1,
                gas_limit=21000,
            ),
            peer_account_key(seed, peer, a),
            chain_id,
        )
        for a in range(count)
    ]


class _Rng:
    def __init__(self, *parts):
        self._r = random.Random(_det(*parts))

    def randbelow(self, k: int) -> int:
        return self._r.randrange(k)


@dataclass
class EraTable:
    """What the peers send validator 0 in one era that does not depend on
    it: blobs of seam records, n - 1 records each."""

    era: int
    plaintexts: Dict[int, bytes]  # slot -> the peer's proposal
    ciphertexts: Dict[int, bytes]  # slot -> its EncryptedShare bytes
    val: bytes  # every peer's VAL of its own proposal
    echo: Dict[int, bytes] = field(default_factory=dict)  # by slot
    ready: Dict[int, bytes] = field(default_factory=dict)
    dec: Dict[int, bytes] = field(default_factory=dict)
    coin: Dict[Tuple[int, int], bytes] = field(default_factory=dict)


@dataclass(frozen=True)
class _EraJob:
    n: int
    f: int
    era: int
    chain_id: int
    seed: int
    txs_per_peer: int
    tpke_pub: object
    tpke_x: Tuple[int, ...]  # by validator: the peers' TPKE key shares
    ts_x: Tuple[int, ...]  # by validator: their threshold-signature shares
    proposals: Optional[Dict[int, bytes]]  # slot -> plaintext, when given
    faults: Tuple[tuple, ...]
    threads: int = 0  # of each fixed-base call (0: the host's cores)


def _shares(base: bytes, xs: Sequence[int], threads: int = 0) -> List[bytes]:
    from ..crypto.native_backend import NativeBackend

    return NativeBackend().mul_fixed_base(base, xs, threads)


def _wrong(job: _EraJob, proto: str, index: tuple, peer: int) -> bool:
    return (proto, job.era, index, peer) in job.faults


def build_era(job: _EraJob) -> EraTable:
    """One era's table (runs in a worker process when asked)."""
    from ..core.block_producer import encode_tx_batch
    from ..crypto import bls12381 as bls
    from ..crypto import hashes
    from ..crypto import threshold_sig as ts
    from ..ops import rs_batch

    n, era = job.n, job.era
    peers = range(1, n)
    k = max(n - 2 * job.f, 1)
    plaintexts, ciphertexts = {}, {}
    for j in peers:
        if job.proposals is not None:
            plaintexts[j] = job.proposals[j]
        else:
            plaintexts[j] = encode_tx_batch(
                peer_transactions(job.seed, job.chain_id, era, j, job.txs_per_peer)
            )
        enc = job.tpke_pub.encrypt(
            plaintexts[j], share_id=j, rng=_Rng("committee-tpke", job.seed, era, j)
        )
        ciphertexts[j] = enc.to_bytes()
    shards = rs_batch.encode_batch([(ciphertexts[j], k, n) for j in peers])
    table = EraTable(era, plaintexts, ciphertexts, b"")
    vals = []
    for j, sh in zip(peers, shards):
        tree = hashes.merkle_tree(hashes.keccak256_batch(sh))
        vals.append(
            encode_seam_record(
                j, 0, MT_VAL, agreement=j, root=tree.root, shard_index=0,
                branch=tree.branches[0], data=sh[0], era=era,
            )
        )
        table.echo[j] = b"".join(
            encode_seam_record(
                p, 0, MT_ECHO, agreement=j, root=tree.root, shard_index=p,
                branch=tree.branches[p], data=sh[p], era=era,
            )
            for p in peers
        )
        table.ready[j] = ready_blob(n, era, j, tree.root)
        xs = [job.tpke_x[p] + _wrong(job, "dec", (j,), p) for p in peers]
        table.dec[j] = dec_blob(
            n, era, j, ciphertexts[j][: bls.G1_BYTES], xs, job.threads
        )
    table.val = b"".join(vals)
    coins = [(a, COIN_EPOCH) for a in range(n)] + [NONCE_COIN]
    for a, e in coins:
        msg = M.CoinId(era=era, agreement=a, epoch=e).to_bytes()
        h = bls.g2_to_bytes(ts._hash_to_sig_point(msg))
        xs = [job.ts_x[p] + _wrong(job, "coin", (a, e), p) for p in peers]
        table.coin[(a, e)] = b"".join(
            encode_seam_record(
                p, 0, MT_OPAQUE, agreement=a, epoch=e, opq_kind=KIND_COIN,
                data=sigma + p.to_bytes(4, "big"), era=era,
            )
            for p, sigma in zip(peers, _shares(h, xs, job.threads))
        )
    return table


def ready_blob(n: int, era: int, slot: int, root: bytes) -> bytes:
    return b"".join(
        encode_seam_record(p, 0, MT_READY, agreement=slot, root=root, era=era)
        for p in range(1, n)
    )


def dec_blob(
    n: int, era: int, slot: int, u: bytes, xs: Sequence[int], threads: int = 0
) -> bytes:
    """Peers 1..n-1's decryption shares of the ciphertext whose U is `u`
    (PartiallyDecryptedShare wire form: U^x, decryptor, slot)."""
    return b"".join(
        encode_seam_record(
            p, 0, MT_OPAQUE, agreement=slot, opq_kind=KIND_DECRYPTED,
            data=ui + p.to_bytes(4, "big") + slot.to_bytes(4, "big"), era=era,
        )
        for p, ui in zip(range(1, n), _shares(u, xs, threads))
    )


def _worker_init() -> None:
    from ..crypto import provider
    from ..crypto.native_backend import NativeBackend

    provider.set_backend(NativeBackend())


class CommitteeScript:
    """Validator 0's N-1 honest peers (see the module docstring).

    private_keys: by validator; entry 0, validator 0's own, is not read.
    proposals: (era, slot) -> the plaintext peer `slot` proposes, in place
    of its own transfers (the tests hand it what a real Devnet's peers
    proposed). faults: (proto, era, index, peer) tuples that make that
    peer's share wrong — proto "dec" with index (slot,), or "coin" with
    index (agreement, epoch) — for the tests that convict it. eras: how
    many eras `setup` builds tables for; an era past them is a problem,
    never an empty answer."""

    def __init__(
        self,
        public_keys,
        private_keys,
        *,
        chain_id: int,
        seed: int,
        txs_per_block: int,
        eras: int,
        proposals: Optional[Callable[[int, int], bytes]] = None,
        faults: Sequence[tuple] = (),
        workers: int = 0,
    ):
        self.n, self.f = public_keys.n, public_keys.f
        self.k = max(self.n - 2 * self.f, 1)
        self.public_keys = public_keys
        self._priv = private_keys
        self.chain_id = chain_id
        self.seed = seed
        self.txs_per_peer = max(txs_per_block // self.n, 1)
        self.eras = eras
        self._proposals = proposals
        self._faults = tuple(faults)
        self._workers = workers
        self._pool = None
        self._pending: list = []
        self.tables: Dict[int, EraTable] = {}
        self._bb: Dict[Tuple[int, int, int], bytes] = {}
        self._answered: set = set()
        # validator 0's own proposal, as the peers rebuild it from its VALs
        self._own_shards: Dict[int, Dict[int, bytes]] = {}
        self.own_ciphertexts: Dict[int, bytes] = {}
        self.problems: List[str] = []

    # -- set-up -----------------------------------------------------------------
    def peer_balances(self, amount: int = 10**24) -> Dict[bytes, int]:
        """Genesis funding of every peer account."""
        from ..crypto import ecdsa

        return {
            ecdsa.address_from_public_key(
                ecdsa.public_key_bytes(peer_account_key(self.seed, p, a))
            ): amount
            for p in range(1, self.n)
            for a in range(self.txs_per_peer)
        }

    def setup(self) -> None:
        """Every era's table (eras 1..self.eras) and the peers' votes."""
        self.start()
        self.join()

    def start(self) -> None:
        """Sets the tables' build going: in `workers` processes (one thread
        each) when asked, else at once in this one. The peers' votes are
        built here meanwhile."""
        eras = range(1, self.eras + 1)
        if self._workers > 1 and len(eras) > 1:
            import multiprocessing
            from concurrent.futures import ProcessPoolExecutor

            self._pool = ProcessPoolExecutor(
                max_workers=min(self._workers, len(eras)),
                mp_context=multiprocessing.get_context("spawn"),
                initializer=_worker_init,
            )
            self._pending = [
                self._pool.submit(build_era, self._job(era, threads=1))
                for era in eras
            ]
        else:
            self.tables = {era: build_era(self._job(era)) for era in eras}
        for a in range(self.n):
            for e in BB_EPOCHS:
                for t in _BB_KIND:
                    self._bb_blob(t, a, e)

    def join(self) -> float:
        """Waits for the tables `start` set going; returns the seconds it
        waited."""
        if self._pool is None:
            return 0.0
        t0 = time.monotonic()
        try:
            for fut in self._pending:
                table = fut.result()
                self.tables[table.era] = table
        finally:
            self._pool.shutdown(cancel_futures=True)
            self._pool, self._pending = None, []
        return time.monotonic() - t0

    def extend(self, eras: int) -> None:
        """Tables for the eras past self.eras up to `eras`, built at once in
        this process."""
        for era in range(self.eras + 1, eras + 1):
            self.tables[era] = build_era(self._job(era))
        self.eras = max(self.eras, eras)

    def close(self) -> None:
        """Stops the workers of a build nobody waited for."""
        if self._pool is not None:
            self._pool.shutdown(wait=True, cancel_futures=True)
            self._pool, self._pending = None, []

    def _job(self, era: int, threads: int = 0) -> _EraJob:
        priv = self._priv
        return _EraJob(
            n=self.n,
            f=self.f,
            era=era,
            chain_id=self.chain_id,
            seed=self.seed,
            txs_per_peer=self.txs_per_peer,
            tpke_pub=self.public_keys.tpke_pub,
            tpke_x=(0,) + tuple(p.tpke_priv.x_i for p in priv[1:]),
            ts_x=(0,) + tuple(p.ts_share.x_i for p in priv[1:]),
            proposals=(
                None
                if self._proposals is None
                else {j: self._proposals(era, j) for j in range(1, self.n)}
            ),
            faults=self._faults,
            threads=threads,
        )

    def _bb_blob(self, mtype: int, agreement: int, epoch: int) -> bytes:
        key = (mtype, agreement, epoch)
        blob = self._bb.get(key)
        if blob is None:
            blob = self._bb[key] = b"".join(
                encode_seam_record(
                    p, 0, mtype, agreement=agreement, epoch=epoch,
                    value=_BB_VALUE[mtype],
                )
                for p in range(1, self.n)
            )
        return blob

    # -- what the reference reads -----------------------------------------------
    def ciphertexts(self, era: int) -> Dict[int, bytes]:
        """Every slot's ciphertext of `era`: the peers' own and validator
        0's as the peers decoded it from its shards."""
        out = dict(self.tables[era].ciphertexts)
        if era in self.own_ciphertexts:
            out[0] = self.own_ciphertexts[era]
        return out

    # -- live answers -----------------------------------------------------------
    def react(self, records: Sequence[SeamRecord]) -> List[Tuple[int, bytes, int]]:
        """The peers' answers to what validator 0 sent them: (era, blob of
        seam records, how many) to queue, in order."""
        out: List[Tuple[int, bytes, int]] = []
        t0 = time.monotonic()
        i = 0
        while i < len(records):
            kind, era = self._kind(records[i]), records[i].era
            j = i
            while (
                j < len(records)
                and records[j].era == era
                and self._kind(records[j]) == kind
            ):
                j += 1
            if era not in self.tables:
                self.out_of_tables(era)
                i = j
                continue
            before = len(out)
            with tracing.span("script.react", "script", era=era, kind=kind) as sid:
                for r in records[i:j]:
                    self._answer(r, kind, out)
                n = sum(c for _e, _b, c in out[before:])
                tracing.annotate(sid, n=n)
            if n:
                metrics.inc("committee_script_messages_total", n, {"kind": kind})
                metrics.inc("committee_script_messages_total", n)
            i = j
        metrics.inc("committee_script_seconds_total", time.monotonic() - t0)
        return out

    def out_of_tables(self, era: int) -> None:
        """Records that validator 0 reached an era no table covers."""
        msg = f"the committee script holds eras 1..{self.eras}, not era {era}"
        if msg not in self.problems:
            self.problems.append(msg)

    @staticmethod
    def _kind(r: SeamRecord) -> str:
        if r.type in _BB_KIND:
            return _BB_KIND[r.type]
        if r.type == MT_VAL:
            return "val"
        if r.type == MT_ECHO:
            return "echo"
        if r.type == MT_READY:
            return "ready"
        return {KIND_COIN: "coin", KIND_DECRYPTED: "dec"}.get(r.opq_kind, "header")

    def _once(self, *key) -> bool:
        if key in self._answered:
            return False
        self._answered.add(key)
        return True

    def _answer(self, r: SeamRecord, kind: str, out: list) -> None:
        era, table, n = r.era, self.tables[r.era], self.n
        if kind == "val":
            # validator 0's shard for peer `target`: the peer echoes it, and
            # the peers' own proposals go out in the same step
            if self._once(era, "val"):
                out.append((era, table.val, n - 1))
            self._own_shards.setdefault(era, {})[r.target] = r.data
            out.append(
                (
                    era,
                    encode_seam_record(
                        r.target, 0, MT_ECHO, agreement=0,
                        shard_index=r.target, root=r.root, branch=r.branch,
                        data=r.data, era=era,
                    ),
                    1,
                )
            )
        elif kind == "echo":
            if r.agreement != 0 and self._once(era, "echo", r.agreement):
                out.append((era, table.echo[r.agreement], n - 1))
        elif kind == "ready":
            if self._once(era, "ready", r.agreement):
                blob = (
                    table.ready[r.agreement]
                    if r.agreement
                    else ready_blob(n, era, 0, r.root)
                )
                out.append((era, blob, n - 1))
        elif kind in ("bval", "aux", "conf"):
            if self._once(era, kind, r.agreement, r.epoch):
                out.append((era, self._bb_blob(r.type, r.agreement, r.epoch), n - 1))
        elif kind == "coin":
            key = (r.agreement, r.epoch)
            if self._once(era, "coin", key):
                if key in table.coin:
                    out.append((era, table.coin[key], n - 1))
                else:
                    self.problems.append(
                        f"era {era}: no table holds the peers' shares of coin {key}"
                    )
        elif kind == "dec":
            if self._once(era, "dec", r.agreement):
                out.append((era, self._dec(era, r.agreement), n - 1))
        elif self._once(era, "header"):
            out.append((era, self._signatures(era, r.data), n - 1))

    def _dec(self, era: int, slot: int) -> bytes:
        if slot:
            return self.tables[era].dec[slot]
        # validator 0's ciphertext, decoded from the shards it sent the peers
        from ..crypto import bls12381 as bls
        from ..ops import rs_batch

        shards = self._own_shards.get(era, {})
        full = [shards.get(i) for i in range(self.n)]
        ct = rs_batch.decode_batch([(full, self.k)])[0]
        if ct is None:
            raise RuntimeError(f"era {era}: validator 0's shards do not decode")
        self.own_ciphertexts[era] = ct
        xs = [self._priv[p].tpke_priv.x_i for p in range(1, self.n)]
        return dec_blob(self.n, era, 0, ct[: bls.G1_BYTES], xs)

    def _signatures(self, era: int, data: bytes) -> bytes:
        """Every peer's signature over the header validator 0 signed."""
        from ..core.types import BlockHeader
        from ..crypto import ecdsa

        hlen = int.from_bytes(data[:4], "big")
        header = data[4 : 4 + hlen]
        digest = BlockHeader.decode(header).hash()
        return b"".join(
            encode_seam_record(
                p, 0, MT_OPAQUE, opq_kind=KIND_SIGNED_HEADER,
                data=data[: 4 + hlen]
                + ecdsa.sign_hash(self._priv[p].ecdsa_priv, digest),
                era=era,
            )
            for p in range(1, self.n)
        )
