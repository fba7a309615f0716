"""Durable consensus send journal: persist-before-transmit.

Crash-recovery BFT must persist what it sent BEFORE transmitting, or a
restarted validator can equivocate against its pre-crash self (Miller et
al. 2016 §4.2 operates under a crash-fault model for honest nodes; the
discipline is Raft's persist-before-respond rule applied to consensus
sends). The exposure is concrete: BA AUX/CONF values and the signed block
header depend on message ARRIVAL ORDER, so a mid-era restart that re-runs
the era from scratch can legitimately derive a DIFFERENT value for a slot
it already voted on — and two signed values for one slot is Byzantine
behavior that honest peers will use against us.

This journal records every outbound consensus payload (era, target, wire
bytes) under the ``EntryPrefix.CONSENSUS_STATE`` keyspace, durable before
any frame that carries the payload leaves the node. The rule has two
halves, and ``tools/check_invariants.py`` rule P holds both:

  * ``record`` is called before the payload reaches the transport
    (``EraRouter._durable_send``) and submits the record to the KV's WAL;
  * ``barrier`` returns only once every submitted record is fsynced. A
    transport that delivers at once has no later moment to wait at, so
    ``record`` barriers itself before it returns. A transport with a frame
    boundary (``core/node.Node``: payloads queue on per-peer workers that
    transmit at their flush tick) takes ``frame_barrier()`` and hands it
    to the network, which calls it in front of every write to a socket
    (``network/worker.durable_before_wire``: a worker's frame, and a
    relay's reverse delivery to a client it has no worker for), and
    ``record`` only submits: the node's one thread waits for its fsync
    once a frame, not once a record, while the WAL writer group-commits
    the records between.

The node's hook runs the transaction pool's barrier beside this one
(``core/node.Node._frame_barrier``; ``core/tx_pool.py``): an admitted
transaction's crash-restore row is submitted to the same WAL and is durable
before the frame that gossips or proposes it, by the same wait.

A crash between ``record`` and the barrier leaves the record either absent
(not journaled, and no frame carried it) or present (recovery re-arms it
and the re-run re-sends it byte-identically): the states a crash between
``record`` and the flush tick always left. On a KV without an overlapping
WAL (sqlite, memory) ``write_batch_async`` is the synchronous write and
there is no ticket to wait for.

On restart the node replays the journal to:

  * re-arm the era router's "already sent" latches — when the re-run era
    reaches the same decision point again, the RECORDED bytes are re-sent,
    byte-identical, never a re-derived value;
  * re-seed the PR-2 retransmission outbox, so peers' ``message_request``s
    are served across the restart;
  * discover which eras were in flight, to rejoin them via
    ``message_request``.

Entries are pruned with the protocol GC (EraRouter.advance_era): an era
settled on-chain no longer needs its sends — recovery for peers is block
sync, not replay.

Key layout: ``CONSENSUS_STATE | era u64 | seq u64`` ->
``i64(target, -1 = broadcast) | bytes(payload wire bytes)``.
"""
from __future__ import annotations

from typing import Callable, Dict, Iterator, Optional, Tuple

from ..storage.crashpoints import crash_point
from ..storage.kv import EntryPrefix, KVStore, prefixed
from ..utils import metrics, tracing
from ..utils.serialization import Reader, write_bytes, write_i64, write_u64

from . import messages as M

_PREFIX = prefixed(EntryPrefix.CONSENSUS_STATE)


def send_slot(payload) -> Optional[tuple]:
    """The per-era decision slot a payload occupies — the unit of
    "already sent": one durable value per slot, re-sends must be
    byte-identical. The slot key identifies the decision point, NOT the
    value, except where the protocol legitimately sends both values
    (BVAL: a node may broadcast BVAL(0) and BVAL(1) in one epoch after
    seeing f+1 of the other — that is not equivocation, so the value is
    part of the slot). Returns None for unlatchable payloads (journaled,
    never substituted)."""
    if isinstance(payload, M.ValMessage):
        # one VAL per recipient shard (the sender's proposal commitment)
        return ("val", payload.rbc, payload.shard_index)
    if isinstance(payload, M.EchoMessage):
        return ("echo", payload.rbc)
    if isinstance(payload, M.ReadyMessage):
        return ("ready", payload.rbc)
    if isinstance(payload, M.BValMessage):
        return ("bval", payload.bb, payload.value)
    if isinstance(payload, M.AuxMessage):
        return ("aux", payload.bb)
    if isinstance(payload, M.ConfMessage):
        return ("conf", payload.bb)
    if isinstance(payload, M.CoinMessage):
        return ("coin", payload.coin)
    if isinstance(payload, M.DecryptedMessage):
        return ("dec", payload.hb, payload.share_id)
    if isinstance(payload, M.SignedHeaderMessage):
        # the big one: two signed headers for one era is classic equivocation
        return ("hdr", payload.root)
    return None


class ConsensusJournal:
    """Append-only send journal over the node's KV store.

    Records ride ``write_batch_async`` + ``write_barrier`` — the KV's WAL,
    fsynced — so a record is durable before the send it covers leaves the
    node (module docstring: where the wait for the fsync sits). Sequence
    numbers are per-era and continue across restarts (seeded from a prefix
    scan at construction), so replayed entries keep their original send
    order.
    """

    def __init__(self, kv: KVStore):
        self._kv = kv
        self._next_seq: Dict[int, int] = {}
        for era, seq, _target, _data in self.entries():
            if seq >= self._next_seq.get(era, 0):
                self._next_seq[era] = seq + 1
        # newest write_batch_async ticket not yet waited for (tickets are
        # WAL sequences: the newest covers every earlier one) and its era
        self._ticket = None
        self._ticket_era = 0
        # False once a frame boundary has taken the wait (frame_barrier)
        self._barrier_in_record = True

    def record(self, era: int, target: Optional[int], payload_bytes: bytes) -> None:
        """Append one send BEFORE it reaches the transport: durable on
        return, or — once frame_barrier() was taken — by the barrier in
        front of the frame that carries it."""
        with tracing.account("journal"), tracing.span(
            "journal.record", cat="journal", era=era
        ):
            seq = self._next_seq.get(era, 0)
            key = _PREFIX + write_u64(era) + write_u64(seq)
            value = write_i64(-1 if target is None else target) + write_bytes(
                payload_bytes
            )
            self._ticket = self._kv.write_batch_async([(key, value)])
            self._ticket_era = era
            self._next_seq[era] = seq + 1
            if self._barrier_in_record:
                self._wait()
        metrics.inc("consensus_journal_records_total")

    def barrier(self) -> None:
        """Return once every record submitted so far is durable. With no
        ticket pending it returns at once, without a call into the KV."""
        if self._ticket is None:
            return
        with tracing.account("journal"), tracing.span(
            "journal.barrier", cat="journal", era=self._ticket_era
        ):
            self._wait()

    def frame_barrier(self) -> Callable[[], None]:
        """Hand the wait to a transport with a frame boundary: the caller
        runs the returned hook before every frame leaves the node, and
        record() stops waiting itself."""
        self._barrier_in_record = False
        return self.barrier

    def _wait(self) -> None:
        if self._ticket is None:
            return
        crash_point("journal.barrier.pre")
        # forgotten only once durable: a barrier that raises (a failed WAL)
        # leaves the ticket, so no later frame passes on an empty one
        self._kv.write_barrier(self._ticket)
        self._ticket = None
        metrics.inc("consensus_journal_barriers_total")

    def entries(self) -> Iterator[Tuple[int, int, Optional[int], bytes]]:
        """Yield (era, seq, target, payload_bytes) in (era, seq) order.
        Undecodable values are skipped (reported by fsck, repaired there)."""
        for key, value in self._kv.scan_prefix(_PREFIX):
            tail = key[len(_PREFIX):]
            if len(tail) != 16:
                continue
            era = int.from_bytes(tail[:8], "big")
            seq = int.from_bytes(tail[8:], "big")
            try:
                r = Reader(value)
                target = r.i64()
                data = r.bytes_()
            except Exception:
                continue
            yield era, seq, (None if target < 0 else target), data

    def eras(self) -> list:
        """Distinct eras with journaled sends, ascending."""
        out = set()
        for era, _seq, _target, _data in self.entries():
            out.add(era)
        return sorted(out)

    def prune_below(self, era_cutoff: int) -> int:
        """Drop entries for eras < `era_cutoff` (the protocol-GC retention:
        settled eras recover by block sync, not replay). One batched
        delete; returns the number of entries dropped."""
        doomed = [
            key
            for key, _ in self._kv.scan_prefix(_PREFIX)
            if len(key) == len(_PREFIX) + 16
            and int.from_bytes(key[len(_PREFIX):len(_PREFIX) + 8], "big")
            < era_cutoff
        ]
        if doomed:
            self._kv.write_batch([], doomed)
            for era in [
                e for e in self._next_seq if e < era_cutoff
            ]:
                del self._next_seq[era]
            metrics.inc("consensus_journal_pruned_total", len(doomed))
        return len(doomed)
