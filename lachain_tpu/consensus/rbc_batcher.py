"""Era-scoped ReliableBroadcast flush batcher.

The TPKE analogue (crypto_batcher.py) fuses every validator's pending
verify+combine into one backend call at quiescence; this does the same for
RBC's Reed-Solomon work. Every pending sender encode and every pending
interpolate/re-encode/Merkle-recheck in an era flushes as ONE batched
matrix-product call into ops/rs_batch.py instead of N serial per-item
codec walks — wired into both the Python reliable_broadcast.py path and the
native engine's RbcHost shim (native_hosts.py).

Two structural wins beyond the fused call:

* Cross-validator dedupe. In-process there are N validators; at N-2F echoes
  each runs the SAME interpolation for the same (root, k, n). A Merkle root
  pins all n committed shards, and branch-verified shards make the verdict
  a pure function of the root: if the committed shards form a codeword,
  every k-subset decodes and re-encodes to the same result; if not, every
  subset ends in a bad-root verdict. The batcher therefore memoizes the
  post-recheck verdict per (root, k, n) per era and fans it out — n
  interpolations become 1.

* Verdict identity. The batched path computes exactly the verdict the
  inline protocol's scalar sequence would (rs.reencode -> Merkle recheck ->
  rs.decode; `scalar_verdict` below is that sequence, used inline where
  no batcher is attached) — tests/test_rs_batch.py pins block-hash identity
  batched-vs-serial on both engines. A failure inside the batched path is
  an error and propagates; it is not replayed on the scalar path.

Callback contract: `cb(payload_or_None)` for interpolations (None = bad
root), `cb(shards_list)` for encodes. Callbacks run inside flush and may
enqueue further protocol traffic (READY sends, deliveries).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

from ..crypto import hashes
from ..ops import rs, rs_batch
from ..utils import metrics, tracing

_BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)


def scalar_verdict(
    shards, k: int, root: bytes
) -> Optional[bytes]:
    """The inline interpolation sequence (reliable_broadcast.py
    _try_interpolate / consensus_rt.cpp try_interpolate): reconstruct,
    re-encode, recheck the Merkle commitment. Returns the payload, or None
    for any failure (the caller marks the root bad)."""
    reencoded = rs.reencode(shards, k)
    if reencoded is None:
        return None
    leaves = [hashes.keccak256(s) for s in reencoded]
    if hashes.merkle_root(leaves) != root:
        return None
    return rs.decode(shards, k)


class RbcEraBatcher:
    """Collects pending RBC encodes/interpolations; flush() runs each era's
    backlog through batched RS matrix products and fans results out."""

    def __init__(self):
        # era -> [(value, k, n, cb)]
        self._enc: Dict[int, List[tuple]] = {}
        # era -> [(key, shards, k, root, cb)]; key = (root, k, n)
        self._interp: Dict[int, List[tuple]] = {}
        # era -> {key: verdict}; the post-Merkle-recheck payload (or None)
        self._memo: Dict[int, Dict[tuple, Optional[bytes]]] = {}
        self.flushes = 0

    @property
    def pending(self) -> int:
        return sum(len(v) for v in self._enc.values()) + sum(
            len(v) for v in self._interp.values()
        )

    def pending_for(self, era: Optional[int]) -> int:
        if era is None:
            return self.pending
        return len(self._enc.get(era, ())) + len(self._interp.get(era, ()))

    def submit_encode(
        self, era: int, value: bytes, k: int, n: int, cb: Callable
    ) -> None:
        """Queue a sender-side encode; `cb(shards)` at the next flush."""
        self._enc.setdefault(era, []).append((value, k, n, cb))
        metrics.set_gauge("rbc_batcher_queue_depth", self.pending)

    def submit_interpolate(
        self,
        era: int,
        shards,
        k: int,
        n: int,
        root: bytes,
        cb: Callable,
    ) -> None:
        """Queue an interpolate+recheck; `cb(payload_or_None)` either
        immediately (verdict already memoized this era — the cross-validator
        dedupe) or at the next flush."""
        key = (root, k, n)
        memo = self._memo.get(era)
        if memo is not None and key in memo:
            metrics.inc("rbc_flush_memo_hits_total")
            cb(memo[key])
            return
        self._interp.setdefault(era, []).append((key, shards, k, root, cb))
        metrics.set_gauge("rbc_batcher_queue_depth", self.pending)

    def flush(self, era: Optional[int] = None) -> int:
        """Flush one era's submissions (None = every era with a backlog).
        Returns the number of submissions completed."""
        if era is None:
            eras = sorted(set(self._enc) | set(self._interp))
        else:
            eras = [era] if self.pending_for(era) else []
        done = 0
        for e in eras:
            done += self._flush_era(e)
        if done:
            metrics.set_gauge("rbc_batcher_queue_depth", self.pending)
        return done

    def _flush_era(self, era: int) -> int:
        encs = self._enc.pop(era, [])
        interps = self._interp.pop(era, [])
        if not encs and not interps:
            return 0
        memo = self._memo.setdefault(era, {})
        # drop verdicts for settled eras so a long devnet run stays bounded
        for stale in [e for e in self._memo if e < era - 2]:
            del self._memo[stale]
        # dedupe interpolations: first submission per key computes, the
        # rest ride the memo fan-out
        uniq: Dict[tuple, tuple] = {}
        waiters: Dict[tuple, List[Callable]] = {}
        order: List[tuple] = []
        for key, shards, k, root, cb in interps:
            if key not in uniq:
                uniq[key] = (shards, k, root)
                order.append(key)
            waiters.setdefault(key, []).append(cb)
        deduped = len(interps) - len(uniq)
        if deduped:
            metrics.inc("rbc_flush_deduped_total", deduped)
        with tracing.span(
            "rbc.flush",
            era=era,
            encodes=len(encs),
            interpolates=len(uniq),
            interpolates_submitted=len(interps),
        ):
            enc_out = self._run_encodes(era, encs)
            verdicts = self._run_interps(era, uniq, order)
        metrics.inc("rbc_flush_total")
        metrics.observe_hist(  # lint-allow: metric-name dimensionless batch-size distribution
            "rbc_batch_size", len(encs) + len(uniq), buckets=_BATCH_BUCKETS
        )
        self.flushes += 1
        # the answers go back into the engine one validator at a time; each
        # post is engine work outside any rt_run, so it gets its own name
        posts = len(encs) + len(interps)
        with tracing.span("rbc.fanout", "engine", era=era, posts=posts):
            for (_v, _k, _n, cb), shards in zip(encs, enc_out):
                cb(shards)
            for key in order:
                memo[key] = verdicts[key]
                for cb in waiters[key]:
                    cb(verdicts[key])
        return posts

    def _run_encodes(self, era: int, encs: List[tuple]) -> List[List[bytes]]:
        if not encs:
            return []
        return rs_batch.encode_batch(
            [(v, k, n) for (v, k, n, _cb) in encs], era=era
        )

    def _run_interps(
        self, era: int, uniq: Dict[tuple, tuple], order: List[tuple]
    ) -> Dict[tuple, Optional[bytes]]:
        verdicts: Dict[tuple, Optional[bytes]] = {}
        if not order:
            return verdicts
        payloads = rs_batch.decode_batch(
            [(uniq[key][0], uniq[key][1]) for key in order], era=era
        )
        # re-encode the successful reconstructions in one batch, then
        # recheck every Merkle commitment with ONE fused keccak call
        payload_of = dict(zip(order, payloads))
        ok_keys = [key for key, p in zip(order, payloads) if p is not None]
        reenc = rs_batch.encode_batch(
            [(payload_of[key], key[1], key[2]) for key in ok_keys],
            era=era,
        )
        flat = [s for shards in reenc for s in shards]
        flat_leaves = hashes.keccak256_batch(flat)
        off = 0
        roots_ok = {}
        for key, shards in zip(ok_keys, reenc):
            leaves = flat_leaves[off : off + len(shards)]
            off += len(shards)
            roots_ok[key] = hashes.merkle_root(leaves) == key[0]
        for key, payload in zip(order, payloads):
            verdicts[key] = (
                payload if payload is not None and roots_ok[key] else None
            )
        return verdicts
