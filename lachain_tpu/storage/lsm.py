"""LsmKV — the native LSM storage engine behind the KVStore seam.

Role of the reference's RocksDB context
(/root/reference/src/Lachain.Storage/RocksDbContext.cs:23-60): a log-
structured KV store with WAL-synced atomic batches. The engine itself is
C++ (storage/native/lsm.cpp, format v2): CRC-framed WAL segments written
and fsynced by a pipeline thread (group commit; the batch ack fires only
after the fsync) -> arena/skiplist memtable -> block-based SSTables with
per-table bloom filters and a shared block cache, flushed and compacted by
rate-limited background threads. Durability contract matches SqliteKV's
synchronous=FULL batches (same kill -9 guarantees, tests/test_lsm.py +
tests/test_crashpoints.py shape).

Single-op put/delete are WAL-synced one-op batches — same semantics as
SqliteKV's autocommit puts, with the fsync cost that implies; bulk paths
use write_batch exactly as they do over SqliteKV.

Crash-point sites (tests/test_crashpoints.py matrix): beyond the generic
kv.write_batch.pre/.post, write_batch visits three engine-specific points
that leave REAL torn state via the native partial-execution debug APIs
before dying — lsm.wal.encoded (torn record tail in the active WAL
segment), lsm.wal.fsynced (record durable but never acked/applied), and
lsm.compact.mid (merged SST renamed into place but the manifest swap
lost). Identical bytes on disk in both harness modes.

Set LACHAIN_LSM_LIB to load an alternate engine build (the ASan/UBSan
gate in tests/native/sanitize.sh runs the storage test slice against a
sanitizer-instrumented libllsm).
"""
from __future__ import annotations

import ctypes
import os
import signal
import struct
import threading
import weakref
from typing import Dict, Iterator, List, Optional, Tuple

from ..utils.native_build import ensure_built
from .kv import KVStore

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "native")
_lib_cache: list = [None]

# lsm_stats() slot order (keep in sync with Lsm::fill_stats)
_STAT_FIELDS = (
    "bloom_hits",       # filter ruled a table out (saved a block fetch)
    "bloom_misses",     # filter passed; a data block was consulted
    "cache_hits",
    "cache_misses",
    "wal_fsyncs",
    "wal_records",
    "compactions",
    "table_count",
    "memtable_bytes",
    "imm_memtables",
    "compact_backlog",  # tables beyond the compaction trigger point
    "trace_dropped",    # flight-recorder ring evictions
    "wal_replayed",     # records open() replayed from the WAL segments
    "wal_torn_bytes",   # torn tail open() cut off the active segment
)

# lsm.cpp trace record contract: 32-byte big-endian records, same frame as
# the consensus engine (u64 ts_ns, u64 dur_ns, u32 kind, u32 tid, u32 a, b)
_TRACE_RECORD = struct.Struct(">QQIIII")
_LK_NAMES = {
    20: "wal_encode",  # a = payload bytes
    21: "wal_fsync",   # a = group-commit records, b = bytes written
    22: "memtable_seal",  # a = bytes, b = new WAL segment
    23: "memtable_flush",  # a = bytes, b = sst seq
    24: "compaction",  # a = input tables, b = output seq
    25: "wait:fsync",  # caller blocked on durability; a = wait resource
}
_LT_NAMES = {0: "caller", 1: "wal-writer", 2: "flusher", 3: "compactor"}
# bytes-per-group-commit spread widely; record counts are small integers
_GROUP_COMMIT_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)
_next_trace_pid = iter(range(3, 1 << 30))  # pid 1 = python, 2 = consensus


def _load_lib():
    if _lib_cache[0] is not None:
        return _lib_cache[0]
    lib_path = os.environ.get("LACHAIN_LSM_LIB") or ensure_built(
        _NATIVE_DIR, "libllsm.so"
    )
    lib = ctypes.CDLL(lib_path)
    lib.lsm_open.restype = ctypes.c_void_p
    lib.lsm_open.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.lsm_open2.restype = ctypes.c_void_p
    lib.lsm_open2.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64,
        ctypes.c_uint64, ctypes.c_uint64,
    ]
    lib.lsm_close.argtypes = [ctypes.c_void_p]
    lib.lsm_write_batch.restype = ctypes.c_int
    lib.lsm_write_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.lsm_write_batch_async.restype = ctypes.c_uint64
    lib.lsm_write_batch_async.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.lsm_write_barrier.restype = ctypes.c_int
    lib.lsm_write_barrier.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.lsm_write_batch_partial.restype = ctypes.c_int
    lib.lsm_write_batch_partial.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int,
    ]
    lib.lsm_get.restype = ctypes.c_int
    lib.lsm_get.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_ubyte)),
        ctypes.POINTER(ctypes.c_size_t),
    ]
    lib.lsm_scan_prefix.restype = ctypes.c_int
    lib.lsm_scan_prefix.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_ubyte)),
        ctypes.POINTER(ctypes.c_size_t),
    ]
    lib.lsm_scan_from.restype = ctypes.c_int
    lib.lsm_scan_from.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_ubyte)),
        ctypes.POINTER(ctypes.c_size_t),
    ]
    lib.lsm_flush.restype = ctypes.c_int
    lib.lsm_flush.argtypes = [ctypes.c_void_p]
    lib.lsm_compact_now.restype = ctypes.c_int
    lib.lsm_compact_now.argtypes = [ctypes.c_void_p]
    lib.lsm_compact_partial.restype = ctypes.c_int
    lib.lsm_compact_partial.argtypes = [ctypes.c_void_p]
    lib.lsm_wait_compaction.restype = ctypes.c_int
    lib.lsm_wait_compaction.argtypes = [ctypes.c_void_p]
    lib.lsm_stats.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64), ctypes.c_int,
    ]
    lib.lsm_free.argtypes = [ctypes.POINTER(ctypes.c_ubyte)]
    lib.lsm_table_count.restype = ctypes.c_uint64
    lib.lsm_table_count.argtypes = [ctypes.c_void_p]
    lib.lsm_version.restype = ctypes.c_int
    assert lib.lsm_version() == 6
    lib.lsm_monotonic_ns.restype = ctypes.c_uint64
    lib.lsm_monotonic_ns.argtypes = []
    lib.lsm_trace_configure.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.lsm_trace_dropped.restype = ctypes.c_uint64
    lib.lsm_trace_dropped.argtypes = [ctypes.c_void_p]
    lib.lsm_trace_drain.restype = ctypes.c_uint64
    lib.lsm_trace_drain.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_ubyte),
        ctypes.c_uint64,
    ]
    _lib_cache[0] = lib
    return lib


def _encode_batch(
    puts: List[Tuple[bytes, bytes]], deletes: List[bytes]
) -> bytes:
    parts = [(len(puts) + len(deletes)).to_bytes(4, "little")]
    for k, v in puts:
        parts.append(
            b"\x00" + len(k).to_bytes(4, "little") + k
            + len(v).to_bytes(4, "little") + v
        )
    for k in deletes:
        parts.append(
            b"\x01" + len(k).to_bytes(4, "little") + k + b"\x00\x00\x00\x00"
        )
    return b"".join(parts)


class LsmKV(KVStore):
    """Durable KV on the native LSM engine (drop-in for SqliteKV)."""

    # WAL runs on its own writer thread -> write_batch_async genuinely
    # overlaps the record's encode+fsync with the caller's next work
    supports_async_batches = True

    def __init__(
        self,
        path: str,
        flush_threshold: int = 8 << 20,
        cache_bytes: int = 0,
        compact_tables: int = 0,
        compact_rate_mbps: int = 0,
    ):
        from ..utils import tracing

        self._lib = _load_lib()
        self._lock = threading.Lock()
        # the engine's open: manifest, orphan sweep, WAL replay into the
        # memtable (after a kill -9, everything since the last flush) and
        # the cut of a torn tail; one span an open, what a restart pays
        # before anything can read the store
        with tracing.span(
            "lsm.open", cat="storage", store=os.path.basename(path) or path
        ) as sid:
            self._h = self._lib.lsm_open2(
                path.encode(), flush_threshold, cache_bytes,
                compact_tables, compact_rate_mbps,
            )
            if not self._h:
                raise IOError(f"cannot open LSM store at {path!r}")
            found = self._read_stats()
            self.opened_with = {
                "wal_records": found["wal_replayed"],
                "repaired": found["wal_torn_bytes"],
            }
            tracing.annotate(sid, **self.opened_with)
        # flight recorder: size the engine ring, align its clock, register
        # with the merged tracer (own pid per store; engine thread roles
        # become named rows in the Chrome export)
        self._trace_offset = tracing.clock_offset(self._lib.lsm_monotonic_ns)
        self._trace_dropped_seen = 0
        self._trace_pid = next(_next_trace_pid)
        self._trace_source = f"lsm-{os.path.basename(path) or path}-{id(self):x}"
        self._lib.lsm_trace_configure(self._h, tracing.capacity())
        ref = weakref.ref(self)
        tracing.register_native_source(
            self._trace_source,
            lambda: [] if ref() is None else ref()._drain_trace(),
            lambda n: None if ref() is None else ref().trace_configure(n),
        )

    # -- flight recorder -------------------------------------------------------
    def trace_configure(self, capacity: int) -> None:
        """Resize the engine-side trace ring; 0 disables recording."""
        with self._lock:
            if self._h:
                self._lib.lsm_trace_configure(self._h, max(int(capacity), 0))

    def _decode_trace(self, raw: bytes) -> List[dict]:
        evs: List[dict] = []
        for i in range(0, len(raw) - (len(raw) % 32), 32):
            ts, dur, kind, tid, a, b = _TRACE_RECORD.unpack_from(raw, i)
            name = _LK_NAMES.get(kind, str(kind))
            is_wait = kind == 25  # LK_WAIT: caller-side durability stall
            evs.append(
                {
                    "name": name,
                    "cat": "native.wait" if is_wait else "native.lsm",
                    "start": ts / 1e9 + self._trace_offset,
                    "end": (ts + dur) / 1e9 + self._trace_offset,
                    "pid": self._trace_pid,
                    "pname": self._trace_source.rsplit("-", 1)[0],
                    "tid": tid,
                    "tname": _LT_NAMES.get(tid, str(tid)),
                    "args": {"resource": "fsync"} if is_wait
                    else {"a": a, "b": b},
                }
            )
            if is_wait:
                from ..utils import metrics

                metrics.observe_hist(
                    "wait_seconds", dur / 1e9, labels={"resource": "fsync"}
                )
            if kind == 21:  # LK_WAL_FSYNC: the never-published v2 numbers
                from ..utils import metrics

                metrics.observe_hist("lsm_wal_fsync_seconds", dur / 1e9)
                metrics.observe_hist(  # lint-allow: metric-name dimensionless record-count distribution
                    "lsm_wal_group_commit_records",
                    a,
                    buckets=_GROUP_COMMIT_BUCKETS,
                )
        return evs

    def _drain_trace(self) -> List[dict]:
        """Consume the engine trace ring -> merged-tracer event dicts;
        feeds the WAL fsync/group-commit histograms and publishes native
        ring-drop growth as trace_events_dropped_total deltas."""
        evs: List[dict] = []
        with self._lock:
            if not self._h:
                return []
            for _ in range(4):
                need = self._lib.lsm_trace_drain(self._h, None, 0)
                if need == 0:
                    break
                buf = (ctypes.c_ubyte * (need + 4096))()
                got = self._lib.lsm_trace_drain(self._h, buf, len(buf))
                if got <= len(buf):
                    evs = self._decode_trace(bytes(buf[:got]))
                    break
            dropped = int(self._lib.lsm_trace_dropped(self._h))
        if dropped > self._trace_dropped_seen:
            from ..utils import metrics

            metrics.inc(
                "trace_events_dropped_total",
                dropped - self._trace_dropped_seen,
                labels={"source": "lsm"},
            )
            self._trace_dropped_seen = dropped
        return evs

    def get(self, key: bytes) -> Optional[bytes]:
        val = ctypes.POINTER(ctypes.c_ubyte)()
        vlen = ctypes.c_size_t(0)
        r = self._lib.lsm_get(
            self._h, key, len(key), ctypes.byref(val), ctypes.byref(vlen)
        )
        if r < 0:
            raise IOError(f"LSM read failed for key {key!r}")
        if r != 1:
            return None
        try:
            return ctypes.string_at(val, vlen.value)
        finally:
            self._lib.lsm_free(val)

    def put(self, key: bytes, value: bytes) -> None:
        self.write_batch([(key, value)])

    def delete(self, key: bytes) -> None:
        self.write_batch([], [key])

    # engine-specific crash sites: leave genuinely torn native state via
    # the partial-execution debug APIs, THEN die the way the armed point
    # asks (InjectedCrash or real SIGKILL). The disk image is identical in
    # both modes, which is what makes the matrix verdicts comparable.
    _TORN_SITES = (("lsm.wal.encoded", 0), ("lsm.wal.fsynced", 1))

    def _visit_torn_sites(self, payload: bytes) -> None:
        from . import crashpoints

        session = crashpoints.active()
        if session is None:
            return
        for name, stage in self._TORN_SITES:
            point = session.visit(name)
            if point is not None:
                with self._lock:
                    rc = self._lib.lsm_write_batch_partial(
                        self._h, payload, len(payload), stage
                    )
                if rc != 0:
                    raise IOError(f"LSM partial write failed at {name}")
                self._die(point, name)
        point = session.visit("lsm.compact.mid")
        if point is not None:
            with self._lock:
                if self._lib.lsm_compact_partial(self._h) != 0:
                    raise IOError("LSM partial compaction failed")
            self._die(point, "lsm.compact.mid")

    @staticmethod
    def _die(point, name: str) -> None:
        from .crashpoints import MODE_SIGKILL, InjectedCrash

        if point.mode == MODE_SIGKILL:
            os.kill(os.getpid(), signal.SIGKILL)
        raise InjectedCrash(name, point.hit)

    def write_batch(
        self, puts: List[Tuple[bytes, bytes]], deletes: List[bytes] = ()
    ) -> None:
        from .crashpoints import crash_point

        crash_point("kv.write_batch.pre")
        payload = _encode_batch(list(puts), list(deletes))
        self._visit_torn_sites(payload)
        with self._lock:
            if self._lib.lsm_write_batch(self._h, payload, len(payload)) != 0:
                raise IOError("LSM write_batch failed")
        # no .mid point: the batch commits inside one native call — the
        # torn-WAL windows are the lsm.wal.* sites above
        crash_point("kv.write_batch.post")

    def write_batch_async(
        self, puts: List[Tuple[bytes, bytes]], deletes: List[bytes] = ()
    ) -> int:
        """Enqueue an atomic batch onto the WAL writer thread WITHOUT
        waiting for its fsync; returns the WAL seq as the barrier ticket.
        The streamed trie commit pipelines through this: chunk N+1's
        Python-side encode overlaps chunk N's write()+fsync(). A crash
        before the barrier can leave these batches durable but unacked —
        callers must only stream data that is SAFE to persist early
        (content-addressed trie nodes: orphans without a root record,
        fsck-clean, shrink reclaims them).

        Deliberately NOT a crash_point/torn-site surface: the generic
        kv.write_batch.* sites use traversal counts as matrix coordinates,
        and streamed chunks would shift every existing hit number. The
        mid-stream window has its own dedicated point
        (trie.merkle.subtree_streamed) in StateManager."""
        payload = _encode_batch(list(puts), list(deletes))
        with self._lock:
            seq = self._lib.lsm_write_batch_async(
                self._h, payload, len(payload)
            )
        if seq == 0:
            raise IOError("LSM write_batch_async failed")
        return int(seq)

    def write_barrier(self, ticket) -> None:
        """Block until the ticketed async batch's WAL record is fsynced."""
        if not ticket:
            return
        with self._lock:
            if self._lib.lsm_write_barrier(self._h, int(ticket)) != 0:
                raise IOError("LSM write_barrier failed")

    def scan_prefix(self, prefix: bytes) -> Iterator[Tuple[bytes, bytes]]:
        buf = ctypes.POINTER(ctypes.c_ubyte)()
        blen = ctypes.c_size_t(0)
        if (
            self._lib.lsm_scan_prefix(
                self._h, prefix, len(prefix),
                ctypes.byref(buf), ctypes.byref(blen),
            )
            != 0
        ):
            raise IOError("LSM scan failed")
        try:
            data = ctypes.string_at(buf, blen.value)
        finally:
            self._lib.lsm_free(buf)
        off = 4
        count = int.from_bytes(data[0:4], "little")
        for _ in range(count):
            klen = int.from_bytes(data[off : off + 4], "little")
            off += 4
            k = data[off : off + klen]
            off += klen
            vlen = int.from_bytes(data[off : off + 4], "little")
            off += 4
            v = data[off : off + vlen]
            off += vlen
            yield (k, v)

    def scan_from(
        self, prefix: bytes, after: bytes, limit: int
    ) -> List[Tuple[bytes, bytes]]:
        """Bounded native cursor page (the fast-sync snapshot primitive):
        the engine seeks its SSTable cursors and memtable skiplists to
        prefix+after and merges forward for `limit` live rows — O(seek +
        page) instead of the O(keyspace) full-prefix materialization the
        KVStore default pays via scan_prefix. Row identity with the
        default/SqliteKV pager is test-locked (tests/test_lsm.py)."""
        if limit <= 0:
            return []
        buf = ctypes.POINTER(ctypes.c_ubyte)()
        blen = ctypes.c_size_t(0)
        if (
            self._lib.lsm_scan_from(
                self._h, prefix, len(prefix), after, len(after),
                limit, ctypes.byref(buf), ctypes.byref(blen),
            )
            != 0
        ):
            raise IOError("LSM scan_from failed")
        try:
            data = ctypes.string_at(buf, blen.value)
        finally:
            self._lib.lsm_free(buf)
        out: List[Tuple[bytes, bytes]] = []
        off = 4
        for _ in range(int.from_bytes(data[0:4], "little")):
            klen = int.from_bytes(data[off : off + 4], "little")
            off += 4
            k = data[off : off + klen]
            off += klen
            vlen = int.from_bytes(data[off : off + 4], "little")
            off += 4
            out.append((k, data[off : off + vlen]))
            off += vlen
        return out

    def flush(self) -> None:
        """Seal the memtable and wait until it is a durable sorted table."""
        with self._lock:
            if self._lib.lsm_flush(self._h) != 0:
                raise IOError("LSM flush failed")

    def ingest(
        self, puts: List[Tuple[bytes, bytes]], chunk: int = 2000
    ) -> None:
        """Bulk-load (snapshot shipping / db import): batched writes, then
        seal the memtable so the imported keyspace is durable sorted
        tables — the verification read pass that follows (root walk,
        fsck) hits bloom-filtered SSTs instead of a giant memtable."""
        super().ingest(puts, chunk)
        if puts:
            self.flush()

    def compact(self) -> None:
        """Flush, then run one full merge to a single table (CLI/db verb)."""
        with self._lock:
            if self._lib.lsm_compact_now(self._h) != 0:
                raise IOError("LSM compaction failed")

    def wait_compaction(self) -> None:
        """Block until no background compaction is scheduled or running."""
        self._lib.lsm_wait_compaction(self._h)

    def table_count(self) -> int:
        return int(self._lib.lsm_table_count(self._h))

    def stats(self) -> Dict[str, int]:
        """Engine counters snapshot; publishes the read-path gauges
        (lsm_bloom_hits/misses, lsm_cache_hit_ratio, ...) as a side
        effect so an RPC metrics scrape after a commit sees them."""
        out = self._read_stats()
        self._publish_metrics(out)
        return out

    def _read_stats(self) -> Dict[str, int]:
        arr = (ctypes.c_uint64 * len(_STAT_FIELDS))()
        self._lib.lsm_stats(self._h, arr, len(_STAT_FIELDS))
        return dict(zip(_STAT_FIELDS, (int(v) for v in arr)))

    @staticmethod
    def _publish_metrics(stats: Dict[str, int]) -> None:
        from ..utils import metrics

        metrics.set_gauge("lsm_bloom_hits", stats["bloom_hits"])
        metrics.set_gauge("lsm_bloom_misses", stats["bloom_misses"])
        lookups = stats["cache_hits"] + stats["cache_misses"]
        metrics.set_gauge(
            "lsm_cache_hit_ratio",
            stats["cache_hits"] / lookups if lookups else 0.0,
        )
        metrics.set_gauge("lsm_table_count", stats["table_count"])
        metrics.set_gauge("lsm_compactions_total", stats["compactions"])
        metrics.set_gauge("lsm_wal_fsyncs_total", stats["wal_fsyncs"])
        metrics.set_gauge("lsm_wal_records_total", stats["wal_records"])
        # sustained non-zero backlog with compactions flat = starved compactor
        metrics.set_gauge("lsm_compaction_backlog", stats["compact_backlog"])

    def close(self) -> None:
        from ..utils import tracing

        # pull buffered engine events (and the fsync/group-commit histogram
        # samples they carry) into the merged tracer before the ring dies
        try:
            tracing.drain_native()
        except Exception:
            pass
        tracing.unregister_native_source(self._trace_source)
        with self._lock:
            if self._h:
                self._lib.lsm_close(self._h)
                self._h = None
