"""TCP transport hub: the CommunicationHub equivalent.

Parity with the reference's Go CommunicationHub + HubConnector
(/root/reference/src/Lachain.Networking/Hub/HubConnector.cs:26-105): the
node hands the hub signed `MessageBatch` blobs addressed to a peer public
key; the hub owns sockets, framing, dialing, and redelivery. The reference
relays through external hub nodes; here peers connect directly over
TCP/DCN (consensus traffic is control-plane KB-scale — ICI collectives are
not a transport, SURVEY.md §5).

Framing: 4-byte big-endian length + raw batch bytes.
"""
from __future__ import annotations

import asyncio
import inspect
import logging
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

from ..utils import metrics, tracing

logger = logging.getLogger(__name__)

MAX_FRAME = 1 << 26  # 64 MiB

# inbound frame sizes (bytes): worker batches cap at 64 KiB, sync replies
# and fast-sync chunks run far larger
_FRAME_BUCKETS = (
    64, 256, 1024, 4096, 16384, 65536, 262144, 1048576, 8388608,
)


def _accepts_conn_id(cb: Callable) -> bool:
    """True when `cb` can take the (data, conn_id) pair. Decided ONCE at
    construction — a per-frame try/except TypeError would also swallow
    genuine TypeErrors raised inside the handler."""
    try:
        sig = inspect.signature(cb)
    except (TypeError, ValueError):
        return True  # uninspectable (C callable): assume the full contract
    n_positional = 0
    for p in sig.parameters.values():
        if p.kind in (
            inspect.Parameter.POSITIONAL_ONLY,
            inspect.Parameter.POSITIONAL_OR_KEYWORD,
        ):
            n_positional += 1
        elif p.kind is inspect.Parameter.VAR_POSITIONAL:
            return True
    return n_positional >= 2


def _write_frame(writer: asyncio.StreamWriter, data: bytes) -> None:
    """Hand one framed batch to the wire. The loop's part `frame_write` is
    this synchronous write (the socket's send, or a copy into the
    transport's buffer), never the drain() the caller awaits after it."""
    with tracing.account("frame_write"):
        writer.write(len(data).to_bytes(4, "big") + data)
    metrics.inc("network_frames_total", labels={"dir": "out"})


@dataclass(frozen=True)
class PeerAddress:
    public_key: bytes  # 33-byte compressed ECDSA key (identity)
    host: str
    port: int


class Hub:
    """Owns the listening socket and outbound connections."""

    def __init__(
        self,
        host: str,
        port: int,
        on_batch: Callable[..., None],
        frame_filter=None,
    ):
        self.host = host
        self.port = port
        # injectable fault filter (network/faults.py TcpFrameFilter): decides
        # per-frame drop/delay/duplication so a seeded FaultPlan reproduces
        # a failure over real sockets. None = deliver everything.
        self.frame_filter = frame_filter
        self._fault_tasks: set = set()
        # called as on_batch(data, conn_id) when the callable accepts two
        # positional args, else on_batch(data) — conn_id identifies the
        # INBOUND connection the batch arrived on, for reverse delivery to
        # peers that cannot be dialed (NAT'd relay clients). Arity is
        # resolved once here so a 1-arg handler receives traffic instead
        # of raising TypeError on every frame.
        self.on_batch = on_batch
        self._pass_conn_id = _accepts_conn_id(on_batch)
        self._server: Optional[asyncio.AbstractServer] = None
        self._conns: Dict[Tuple[str, int], asyncio.StreamWriter] = {}
        self._conn_locks: Dict[Tuple[str, int], asyncio.Lock] = {}
        self._reader_tasks: set = set()
        self._inbound: Dict[int, asyncio.StreamWriter] = {}
        self._next_conn_id = 1

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._handle_inbound, self.host, self.port
        )
        addr = self._server.sockets[0].getsockname()
        self.port = addr[1]  # resolve port 0 -> actual

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
        for t in list(self._fault_tasks):
            t.cancel()
        self._fault_tasks.clear()
        # cancel inbound readers first: wait_closed() (3.12+) blocks until
        # every connection handler returns
        for t in list(self._reader_tasks):
            t.cancel()
        if self._reader_tasks:
            await asyncio.gather(*self._reader_tasks, return_exceptions=True)
        for w in list(self._conns.values()):
            w.close()
        self._conns.clear()
        if self._server is not None:
            await self._server.wait_closed()

    async def _read_frames(self, reader, conn_id) -> None:
        """Shared frame loop for both directions (batches are
        connection-agnostic; identity lives in the batch signature)."""
        while True:
            # no span here: each connection's reader waits while the thread
            # works for the others. What the NODE waits for the network is
            # its loop parked in select(), span era.net_idle
            header = await reader.readexactly(4)
            n = int.from_bytes(header, "big")
            if n > MAX_FRAME:
                raise ValueError("oversized frame")
            data = await reader.readexactly(n)
            metrics.observe_hist(
                "network_frame_bytes", n, buckets=_FRAME_BUCKETS
            )
            if self.frame_filter is not None and not self.frame_filter.inbound(
                data
            ):
                continue  # injected inbound suppression (crashed self)
            try:
                if self._pass_conn_id:
                    self.on_batch(data, conn_id)
                else:
                    self.on_batch(data)
            except Exception:
                logger.exception("batch handler failed")

    async def _handle_inbound(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        if not self._server.is_serving():
            # accepted before stop() closed the server, started after it
            # cancelled the readers: reading on would hold its
            # wait_closed() until the peer hangs up, and a fleet stops its
            # nodes one after another, so that peer is still running
            writer.close()
            return
        task = asyncio.current_task()
        if task is not None:
            self._reader_tasks.add(task)
        conn_id = self._next_conn_id
        self._next_conn_id += 1
        self._inbound[conn_id] = writer
        try:
            await self._read_frames(reader, conn_id)
        except (asyncio.IncompleteReadError, ConnectionError, ValueError):
            pass
        finally:
            self._inbound.pop(conn_id, None)
            writer.close()
            if task is not None:
                self._reader_tasks.discard(task)

    def _schedule_faulted(self, delay: float, send) -> None:
        """Run coroutine-factory `send` after `delay` (fault-injected
        latency); tracked so stop() cancels in-flight delayed frames."""

        async def later():
            await asyncio.sleep(delay)
            await send()

        t = asyncio.get_running_loop().create_task(later())
        self._fault_tasks.add(t)
        t.add_done_callback(self._fault_tasks.discard)

    async def _send_filtered(self, peer, data: bytes, send) -> bool:
        """Apply the frame filter to one outbound frame. `send` is an async
        thunk performing the real write. A dropped frame reports SUCCESS:
        injected loss must look like the network ate it, so repair can only
        come from the message-request/outbox-replay layer — a False here
        would let the worker's own requeue path mask the fault."""
        plan = self.frame_filter.outbound(peer, data)
        if not plan:
            return True
        ok = True
        sent_now = False
        for delay in plan:
            if delay > 0:
                self._schedule_faulted(delay, send)
            else:
                sent_now = True
                ok = await send() and ok
        return ok if sent_now else True

    async def send_on_conn(self, conn_id: int, data: bytes) -> bool:
        """Reverse delivery over a live INBOUND connection (the only path
        to a NAT'd peer: it dialed us, we answer on its socket)."""
        if self.frame_filter is not None:
            return await self._send_filtered(
                None, data, lambda: self._send_on_conn_now(conn_id, data)
            )
        return await self._send_on_conn_now(conn_id, data)

    async def _send_on_conn_now(self, conn_id: int, data: bytes) -> bool:
        writer = self._inbound.get(conn_id)
        if writer is None:
            return False
        try:
            _write_frame(writer, data)
            await writer.drain()
            return True
        except (ConnectionError, OSError):
            self._inbound.pop(conn_id, None)
            writer.close()
            return False

    async def _read_outbound(self, reader, key, my_writer) -> None:
        """Outbound connections are READ too: a relay answers a NAT'd
        node over the very connection the node dialed out (reverse
        delivery) — frames arriving there are ordinary batches."""
        try:
            await self._read_frames(reader, None)
        except (asyncio.IncompleteReadError, ConnectionError, ValueError,
                asyncio.CancelledError):
            pass
        finally:
            # close ONLY the connection this reader belongs to: a stale
            # reader waking after a re-dial must not kill the replacement
            my_writer.close()
            if self._conns.get(key) is my_writer:
                self._conns.pop(key, None)

    async def send_raw(self, peer: PeerAddress, data: bytes) -> bool:
        """Send one framed batch; dials on demand, drops the cached
        connection on failure (next send re-dials)."""
        if self.frame_filter is not None:
            return await self._send_filtered(
                peer, data, lambda: self._send_raw_now(peer, data)
            )
        return await self._send_raw_now(peer, data)

    async def _send_raw_now(self, peer: PeerAddress, data: bytes) -> bool:
        key = (peer.host, peer.port)
        lock = self._conn_locks.setdefault(key, asyncio.Lock())
        async with lock:
            writer = self._conns.get(key)
            for attempt in (0, 1):
                if writer is None:
                    try:
                        reader, writer = await asyncio.open_connection(
                            peer.host, peer.port
                        )
                        self._conns[key] = writer
                        t = asyncio.get_running_loop().create_task(
                            self._read_outbound(reader, key, writer)
                        )
                        self._reader_tasks.add(t)
                        t.add_done_callback(self._reader_tasks.discard)
                    except OSError:
                        return False
                try:
                    _write_frame(writer, data)
                    await writer.drain()
                    return True
                except (ConnectionError, OSError):
                    writer.close()
                    self._conns.pop(key, None)
                    writer = None
            return False
