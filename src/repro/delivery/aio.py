"""Async data plane — an event-loop registry front door plus a
multiplexing transport that survive 1k+ concurrent pullers.

The threaded :class:`~repro.delivery.net.SocketRegistryServer` spends one
thread per connection, which caps the delivery stack at a few hundred
clients.  This module is the scale seam:

  * :class:`AsyncRegistryServer` — a non-blocking TCP front door over the
    same thread-safe :class:`~repro.delivery.server.RegistryServer`
    handlers.  One asyncio event loop owns every connection; handler work
    (store reads, CDMT verification, journal commits) runs on a bounded
    worker pool of **O(cores)** threads, so ten thousand idle connections
    cost file descriptors, not stacks.  The wire protocol is the
    **multiplexed envelope** (``wire.encode_mux_request`` /
    ``encode_mux_response_*``): every request carries a stream id, every
    response message routes by it, so any number of request/response
    streams interleave over one connection.
  * **Fair scheduling** — a streamed WANT answer is produced one
    CHUNK_BATCH at a time, each batch a separate worker-pool job, and the
    per-connection writer lock is released between messages.  A
    thousand-chunk pull therefore shares the pool and the socket at frame
    granularity with everything else; one huge pull cannot starve a
    thousand small ones.
  * **Backpressure + admission control** — a connection may hold at most
    ``max_stream_inflight`` streams; past that the server stops *reading*
    it (TCP pushes back on the client, no unbounded buffering).  Globally,
    past ``max_inflight`` admitted requests the server **sheds**: the
    request is answered immediately with a typed ``ErrorCode.BUSY`` ERROR
    frame instead of stalling accepts, and ``async_shed_total`` counts it.
  * :class:`MuxSocketTransport` — a conforming
    :class:`~repro.delivery.transport.Transport` that multiplexes every
    exchange over a small set of shared connections (one reader thread per
    connection, not per request).  ``ImageClient.execute``'s pipelined
    batches interleave on the same sockets; byte accounting is exact
    socket bytes and ``quote_chunk_batches`` quotes the mux envelope to
    the byte, so plan == execute, same as the threaded transport.

Concurrency contract
    ``AsyncRegistryServer``'s connection and stream state is touched only
    from the event-loop thread (the one lock, ``_lifecycle_lock``, makes
    ``stop()`` idempotent across caller threads).  Handlers run on the
    worker pool and are thread-safe by the wrapped ``RegistryServer``'s
    contract; frames of one stream are produced serially, so the
    ``want_plan`` generator is never entered concurrently.
    ``MuxSocketTransport`` is thread-safe: any number of caller threads
    open streams concurrently; per-connection stream tables are guarded by
    the connection's lock and each stream hands its messages to exactly
    one waiting caller through its own queue.

Crash-recovery contract
    Identical to the threaded server: the front door owns no durable
    state.  Killing the process costs at most the in-flight requests —
    every client sees a dead connection and raises ``DeliveryError`` with
    nothing committed to its local store.
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import os
import queue
import socket
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.cdmt import CDMT, CDMTParams
from repro.core.errors import DeliveryError
from repro.core.registry import PushRejected, Registry
from repro.core.store import Recipe
from repro.obs import MetricsRegistry, MetricsSnapshot

from . import wire
from .net import (DEFAULT_TIMEOUT, _ConnectionClosed, _read_exact,
                  _read_frame, _read_uvarint, dispatch_request)
from .plan import SourceLeg
from .server import RegistryServer
from .transport import (REGISTRY_SOURCE, FetchResult, PushOutcome,
                        TransportMeter)

__all__ = ["AsyncRegistryServer", "AsyncServerStats", "MuxSocketTransport",
           "serve_registry_async"]

_DONE = object()          # sentinel: the want_plan frame iterator is spent


# ---------------------------------------------------------------- server


@dataclasses.dataclass
class AsyncServerStats:
    """Adapter view over the ``async_*`` metric series (same shape as the
    threaded server's :class:`~repro.delivery.net.SocketServerStats`, plus
    the load-shed counter)."""
    connections: int = 0
    requests: int = 0
    errors: int = 0                # streams answered with an ERROR frame
    sheds: int = 0                 # requests refused by admission control
    ingress_bytes: int = 0         # request envelopes read off sockets
    egress_bytes: int = 0          # response messages written to sockets

    def snapshot(self) -> "AsyncServerStats":
        return dataclasses.replace(self)


class _AioConn:
    """Per-connection event-loop state — touched only on the loop thread."""

    __slots__ = ("reader", "writer", "wlock", "sem", "tasks")

    def __init__(self, reader: asyncio.StreamReader,
                 writer: asyncio.StreamWriter, max_stream_inflight: int):
        self.reader = reader
        self.writer = writer
        # writer lock: released between messages, so concurrent streams
        # interleave on the socket at message granularity (the fairness
        # point of the mux framing)
        self.wlock = asyncio.Lock()
        # per-client backpressure: past this many in-flight streams the
        # read loop stops consuming the connection and TCP pushes back
        self.sem = asyncio.Semaphore(max_stream_inflight)
        self.tasks: set = set()


class AsyncRegistryServer:
    """Event-loop TCP front door over a :class:`RegistryServer`.

    Speaks the **multiplexed** envelope protocol (stream-id routed — see
    ``docs/WIRE_PROTOCOL.md``); the threaded
    :class:`~repro.delivery.net.SocketRegistryServer` remains the
    compatibility backend for plain-envelope clients.  ``port=0`` binds an
    ephemeral port; read ``address`` after construction.  The loop runs in
    one dedicated thread and handler work on ``workers`` pool threads
    (default ``os.cpu_count()``) — connection count never adds threads.

    ``idle_timeout`` (seconds, ``None`` = never) reaps connections that
    idle *between* requests, closing the unbounded-idle window pooled
    clients used to rely on; a well-behaved client redials transparently.
    """

    def __init__(self, server: RegistryServer, host: str = "127.0.0.1",
                 port: int = 0, backlog: int = 1024,
                 workers: Optional[int] = None,
                 max_inflight: int = 1024,
                 max_stream_inflight: int = 64,
                 idle_timeout: Optional[float] = None,
                 io_timeout: float = DEFAULT_TIMEOUT):
        self.server = server
        self.workers = workers if workers is not None \
            else max(2, os.cpu_count() or 2)
        self.max_inflight = max_inflight
        self.max_stream_inflight = max(1, max_stream_inflight)
        self.idle_timeout = idle_timeout
        self.io_timeout = io_timeout
        self.metrics = server.metrics
        m = self.metrics
        self._m_connections = m.counter(
            "async_connections_total", "TCP connections accepted").labels()
        self._m_open = m.gauge(
            "async_open_connections", "currently open connections").labels()
        self._m_requests = m.counter(
            "async_requests_total", "mux request envelopes read").labels()
        self._m_errors = m.counter(
            "async_errors_total",
            "streams answered with an ERROR frame").labels()
        self._m_shed = m.counter(
            "async_shed_total",
            "requests refused by admission control (BUSY)").labels()
        self._m_reaped = m.counter(
            "async_idle_reaped_total",
            "connections closed by the idle reaper").labels()
        self._m_ingress = m.counter(
            "async_ingress_bytes_total",
            "request envelope bytes read off sockets").labels()
        self._m_egress = m.counter(
            "async_egress_bytes_total",
            "response message bytes written to sockets").labels()
        self._m_inflight = m.gauge(
            "async_inflight_requests",
            "admitted requests not yet fully answered").labels()
        self._m_queue = m.gauge(
            "async_queue_depth",
            "handler jobs queued for a worker-pool thread").labels()
        self._m_wait = m.counter(
            "async_queue_wait_seconds_total",
            "seconds handler jobs waited from submission to a worker "
            "thread").labels()
        self._m_job = m.counter(
            "async_job_seconds_total",
            "seconds handler jobs ran on worker threads").labels()
        self._m_jobs = m.counter(
            "async_jobs_total", "handler jobs run on the worker pool").labels()
        lat = m.histogram(
            "async_request_seconds",
            "admission-to-last-byte stream latency (queueing included)",
            ("op",))
        self._m_lat = {op: lat.labels(op.name.lower()) for op in wire.Op}
        self._inflight = 0  # guarded-by: external(event-loop thread)
        self._conns: set = set()  # guarded-by: external(event-loop thread)
        self._stopped = False  # guarded-by: _lifecycle_lock
        self._lifecycle_lock = threading.Lock()
        self._pool = ThreadPoolExecutor(
            max_workers=self.workers, thread_name_prefix="async-registry")
        self._loop = asyncio.new_event_loop()
        self._thread = threading.Thread(target=self._run_loop,
                                        name="async-registry-loop",
                                        daemon=True)
        self._thread.start()
        fut = asyncio.run_coroutine_threadsafe(
            self._start(host, port, backlog), self._loop)
        self.address: Tuple[str, int] = fut.result(timeout=10)

    # ------------------------------------------------------------ lifecycle

    def _run_loop(self) -> None:
        asyncio.set_event_loop(self._loop)
        self._loop.run_forever()

    async def _start(self, host: str, port: int, backlog: int
                     ) -> Tuple[str, int]:
        self._aserver = await asyncio.start_server(
            self._serve_conn, host, port, backlog=backlog)
        return self._aserver.sockets[0].getsockname()[:2]

    def __enter__(self) -> "AsyncRegistryServer":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def stop(self) -> None:
        with self._lifecycle_lock:
            if self._stopped:
                return
            self._stopped = True
        fut = asyncio.run_coroutine_threadsafe(self._shutdown(), self._loop)
        with contextlib.suppress(Exception):
            fut.result(timeout=10)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=10)
        self._pool.shutdown(wait=True, cancel_futures=True)
        if not self._thread.is_alive():
            self._loop.close()

    async def _shutdown(self) -> None:
        self._aserver.close()
        await self._aserver.wait_closed()
        for conn in list(self._conns):
            for t in list(conn.tasks):
                t.cancel()
            conn.writer.close()

    @property
    def thread_count(self) -> int:
        """Threads this front door owns: the loop plus the worker pool —
        O(cores), independent of connection count (the scale claim the
        benchmark pins)."""
        return 1 + self.workers

    @property
    def stats(self) -> AsyncServerStats:
        return AsyncServerStats(
            connections=self._m_connections.value(),
            requests=self._m_requests.value(),
            errors=self._m_errors.value(),
            sheds=self._m_shed.value(),
            ingress_bytes=self._m_ingress.value(),
            egress_bytes=self._m_egress.value())

    def snapshot(self) -> AsyncServerStats:
        return self.stats

    # ----------------------------------------------------------- connection

    async def _serve_conn(self, reader: asyncio.StreamReader,
                          writer: asyncio.StreamWriter) -> None:
        sock = writer.get_extra_info("socket")
        if sock is not None:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        conn = _AioConn(reader, writer, self.max_stream_inflight)
        self._conns.add(conn)
        self._m_connections.inc()
        self._m_open.inc()
        try:
            while True:
                req = await self._read_request(reader)
                if req is None:
                    break                    # clean EOF / idle reaped
                op, sid, lineage, tag, frames, nbytes = req
                self._m_requests.inc()
                self._m_ingress.inc(nbytes)
                if self._inflight >= self.max_inflight:
                    # admission control: answer, don't stall the accept or
                    # read path — the client sees a typed, retryable error
                    self._m_shed.inc()
                    self._m_errors.inc()
                    await self._send_error(
                        conn, sid, wire.ErrorCode.BUSY,
                        f"server busy: {self._inflight} requests in "
                        f"flight (limit {self.max_inflight}) — retry")
                    continue
                await conn.sem.acquire()     # per-client backpressure
                task = self._loop.create_task(
                    self._answer(conn, sid, op, lineage, tag, frames))
                conn.tasks.add(task)
                task.add_done_callback(
                    lambda t, c=conn: self._stream_done(c, t))
        except _ConnectionClosed:
            pass                             # peer vanished mid-request
        except wire.WireError:
            # malformed envelope: the stream offset is unknowable, so the
            # only honest signal is a close (mux has no "current stream"
            # to attach an ERROR frame to)
            self._m_errors.inc()
        finally:
            for t in list(conn.tasks):
                t.cancel()
            with contextlib.suppress(OSError):
                conn.writer.close()
            self._conns.discard(conn)
            self._m_open.dec()

    def _stream_done(self, conn: _AioConn, task: "asyncio.Task") -> None:
        conn.tasks.discard(task)
        conn.sem.release()
        if task.cancelled():
            return
        if task.exception() is not None:
            # failure after a stream header was committed: close the
            # connection — every client stream on it fails loudly
            conn.writer.close()

    async def _read_request(self, reader: asyncio.StreamReader
                            ) -> Optional[Tuple[wire.Op, int, str, str,
                                                List[bytes], int]]:
        """One mux request envelope, or None on clean EOF / idle reap.
        The wait for the first byte honors ``idle_timeout``; once a
        request starts, the rest must arrive within ``io_timeout``."""
        try:
            if self.idle_timeout is not None:
                first = await asyncio.wait_for(reader.readexactly(1),
                                               self.idle_timeout)
            else:
                first = await reader.readexactly(1)
        except asyncio.IncompleteReadError:
            return None
        except asyncio.TimeoutError:
            self._m_reaped.inc()
            return None
        try:
            return await asyncio.wait_for(
                self._read_request_body(reader, first), self.io_timeout)
        except asyncio.IncompleteReadError as e:
            raise _ConnectionClosed(str(e)) from e
        except asyncio.TimeoutError as e:
            raise _ConnectionClosed("mid-request timeout") from e

    async def _read_request_body(self, reader: asyncio.StreamReader,
                                 first: bytes
                                 ) -> Tuple[wire.Op, int, str, str,
                                            List[bytes], int]:
        hdr = first + await reader.readexactly(7)
        nbytes = 8
        op, sid = wire.check_mux_request_header(hdr)
        lineage, nb = await self._aread_str(reader)
        nbytes += nb
        tag, nb = await self._aread_str(reader)
        nbytes += nb
        n_frames, nb = await self._aread_uvarint(reader)
        nbytes += nb
        if n_frames > wire.MAX_ENVELOPE_FRAMES:
            raise wire.WireError(f"request carries {n_frames} frames, "
                                 f"limit {wire.MAX_ENVELOPE_FRAMES}")
        frames: List[bytes] = []
        for _ in range(n_frames):
            size, nb = await self._aread_uvarint(reader)
            if size > wire.MAX_FRAME_BYTES:
                raise wire.WireError(f"frame of {size} bytes exceeds "
                                     f"{wire.MAX_FRAME_BYTES}")
            frames.append(await reader.readexactly(size))
            nbytes += nb + size
        return op, sid, lineage, tag, frames, nbytes

    @staticmethod
    async def _aread_uvarint(reader: asyncio.StreamReader
                             ) -> Tuple[int, int]:
        result = 0
        shift = 0
        for i in range(10):
            b = (await reader.readexactly(1))[0]
            result |= (b & 0x7F) << shift
            if not b & 0x80:
                return result, i + 1
            shift += 7
        raise wire.WireError("uvarint too long (>10 bytes)")

    async def _aread_str(self, reader: asyncio.StreamReader
                         ) -> Tuple[str, int]:
        n, nb = await self._aread_uvarint(reader)
        if n > wire.MAX_ROUTING_BYTES:
            raise wire.WireError(f"routing string of {n} bytes exceeds "
                                 f"{wire.MAX_ROUTING_BYTES}")
        return (await reader.readexactly(n)).decode("utf-8"), nb + n

    # -------------------------------------------------------------- answer

    async def _send(self, conn: _AioConn, data: bytes) -> None:
        async with conn.wlock:
            conn.writer.write(data)
            await conn.writer.drain()        # socket backpressure honored
        self._m_egress.inc(len(data))

    async def _send_error(self, conn: _AioConn, sid: int,
                          code: wire.ErrorCode, msg: str) -> None:
        await self._send(conn, wire.encode_mux_response_header(
            sid, wire.STATUS_ERROR, 1))
        await self._send(conn, wire.encode_mux_response_frame(
            sid, wire.encode_error(code, msg)))

    async def _run(self, fn, *args):
        """One handler job on the worker pool; the queue-depth gauge
        counts jobs submitted but not yet started, and the job counters
        add its wait for a thread and its run on one."""
        self._m_queue.inc()
        submitted = time.perf_counter()

        def job():
            self._m_queue.dec()
            started = time.perf_counter()
            self._m_wait.inc(started - submitted)
            try:
                return fn(*args)
            finally:
                self._m_job.inc(time.perf_counter() - started)
                self._m_jobs.inc()

        return await self._loop.run_in_executor(self._pool, job)

    async def _answer(self, conn: _AioConn, sid: int, op: wire.Op,
                      lineage: str, tag: str, frames: List[bytes]) -> None:
        self._inflight += 1
        self._m_inflight.inc()
        t0 = time.perf_counter()
        streamed = False
        try:
            if op in (wire.Op.WANT, wire.Op.SNAPSHOT_SHIP):
                if len(frames) != 1:
                    raise wire.WireError(
                        f"{op.name} request carries {len(frames)} body "
                        f"frame(s), expected 1")
                plan = (self.server.want_plan if op is wire.Op.WANT
                        else self.server.snapshot_plan)
                n, frame_iter = await self._run(plan, frames[0])
                await self._send(conn, wire.encode_mux_response_header(
                    sid, wire.STATUS_OK, n))
                streamed = True              # header out: count committed
                try:
                    while True:
                        # one frame per pool job: a huge WANT (or snapshot
                        # bootstrap) shares the workers — and the socket —
                        # at frame granularity
                        f = await self._run(next, frame_iter, _DONE)
                        if f is _DONE:
                            break
                        await self._send(
                            conn, wire.encode_mux_response_frame(sid, f))
                finally:
                    with contextlib.suppress(Exception):
                        frame_iter.close()
            else:
                out = await self._run(dispatch_request, self.server, op,
                                      lineage, tag, frames)
                await self._send(conn, wire.encode_mux_response_header(
                    sid, wire.STATUS_OK, len(out)))
                for f in out:
                    await self._send(
                        conn, wire.encode_mux_response_frame(sid, f))
        except asyncio.CancelledError:
            raise
        except Exception as e:
            if streamed or isinstance(e, (OSError, _ConnectionClosed)):
                # the frame count is committed (or the socket is gone):
                # any "error frame" now would decode as stream data.
                # Close — every client stream on this conn fails loudly.
                raise _ConnectionClosed(str(e)) from e
            code = (wire.ErrorCode.PUSH_REJECTED
                    if isinstance(e, PushRejected)
                    else wire.ErrorCode.WIRE if isinstance(e, wire.WireError)
                    else wire.ErrorCode.DELIVERY
                    if isinstance(e, DeliveryError)
                    else wire.ErrorCode.INTERNAL)
            self._m_errors.inc()
            await self._send_error(conn, sid, code,
                                   str(e) or type(e).__name__)
        finally:
            self._inflight -= 1
            self._m_inflight.dec()
            self._m_lat[op].observe(time.perf_counter() - t0)


# -------------------------------------------------------------- transport


class _Stream:
    """One in-flight client stream: the reader thread feeds messages in,
    exactly one caller thread consumes them."""

    __slots__ = ("q",)

    def __init__(self):
        self.q: "queue.Queue" = queue.Queue()


class _StaleStream(Exception):
    """The connection died before this stream's header arrived — the
    server never answered (an idle-reaped or freshly dead shared socket),
    so the exchange is safe to retry once on a new connection."""


class _MuxConn:
    """One shared client connection: a socket, a demultiplexing reader
    thread, and the stream table it routes into."""

    def __init__(self, address: Tuple[str, int], timeout: float):
        self.sock = socket.create_connection(address, timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock.settimeout(None)   # liveness is enforced per-stream
        self.rfile = self.sock.makefile("rb")
        self._lock = threading.Lock()
        self._send_lock = threading.Lock()
        self._streams: Dict[int, _Stream] = {}  # guarded-by: _lock
        self._next_id = 1  # guarded-by: _lock
        self._dead = False  # guarded-by: _lock
        self._reader = threading.Thread(target=self._read_loop,
                                        name="mux-transport-read",
                                        daemon=True)
        self._reader.start()

    # ------------------------------------------------------------- streams

    def register(self) -> Tuple[int, _Stream]:
        """Allocate a stream id and its message queue."""
        with self._lock:
            if self._dead:
                raise _ConnectionClosed("mux connection is dead")
            while self._next_id in self._streams:
                self._next_id = (self._next_id % wire.MAX_STREAM_ID) + 1
            sid = self._next_id
            self._next_id = (self._next_id % wire.MAX_STREAM_ID) + 1
            st = _Stream()
            self._streams[sid] = st
            return sid, st

    def unregister(self, sid: int) -> None:
        with self._lock:
            self._streams.pop(sid, None)

    def n_streams(self) -> int:
        with self._lock:
            return len(self._streams)

    def is_dead(self) -> bool:
        with self._lock:
            return self._dead

    def send(self, data: bytes) -> None:
        with self._send_lock:
            self.sock.sendall(data)

    # -------------------------------------------------------------- reader

    def _read_loop(self) -> None:
        try:
            while True:
                hdr = _read_exact(self.rfile, 8)
                msg_type, sid = wire.check_mux_response_header(hdr)
                if msg_type == wire.MUX_HEADER:
                    status = _read_exact(self.rfile, 1)[0]
                    if status not in (wire.STATUS_OK, wire.STATUS_ERROR):
                        raise wire.WireError(
                            f"unknown response status {status}")
                    n, nb = _read_uvarint(self.rfile)
                    item = ("hdr", status, n, 9 + nb)
                else:
                    f, nb = _read_frame(self.rfile)
                    item = ("frame", f, None, 8 + nb)
                with self._lock:
                    st = self._streams.get(sid)
                if st is not None:
                    st.q.put(item)
                # unknown id: the stream timed out and unregistered — drop
        except (_ConnectionClosed, OSError, wire.WireError) as e:
            self._fail(e)

    def _fail(self, exc: BaseException) -> None:
        """Mark dead and wake every waiting stream with the failure."""
        with self._lock:
            self._dead = True
            waiting = list(self._streams.values())
            self._streams.clear()
        for st in waiting:
            st.q.put(("err", exc, None, 0))
        self.close(join_reader=False)

    def close(self, join_reader: bool = True) -> None:
        with contextlib.suppress(OSError):
            self.sock.shutdown(socket.SHUT_RDWR)
        with contextlib.suppress(OSError):
            self.rfile.close()
        with contextlib.suppress(OSError):
            self.sock.close()
        if join_reader and self._reader is not threading.current_thread():
            self._reader.join(timeout=5)


class MuxSocketTransport:
    """:class:`Transport` over multiplexed TCP to an
    :class:`AsyncRegistryServer`.

    All exchanges share at most ``connections`` sockets; concurrent
    callers (``ImageClient.execute``'s pipelined batches, or a thousand
    pullers handed the same transport) interleave their streams on them.
    Byte accounting mirrors the threaded transport — request envelopes as
    control/want traffic, the full mux response (HEADER + FRAME messages)
    as the matching response category — and ``quote_chunk_batches`` makes
    a pull plan's quote byte-exact, stream ids being fixed-width.

    A stream whose connection dies *before its header arrived* was never
    answered (typically an idle-reaped shared socket); it is retried once
    on a fresh connection instead of surfacing ``DeliveryError``.
    """

    name = "mux"
    verifies_payloads = True       # decode_chunk_batch hashes every payload

    def __init__(self, address: Tuple[str, int], batch_chunks: int = 64,
                 timeout: float = DEFAULT_TIMEOUT, connections: int = 4,
                 metrics: Optional[MetricsRegistry] = None):
        self.address = (address[0], int(address[1]))
        self.batch_chunks = max(1, batch_chunks)
        self.timeout = timeout
        self.max_connections = max(1, connections)
        self._conns: List[_MuxConn] = []  # guarded-by: _lock
        self._closed = False  # guarded-by: _lock
        self._lock = threading.Lock()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._meter = TransportMeter(self.metrics, self.name)
        self._m_conns = self.metrics.gauge(
            "transport_pool_connections",
            "open shared/pooled connections", ("transport",)
        ).labels(self.name)
        self._m_streams = self.metrics.gauge(
            "transport_open_streams",
            "mux streams currently in flight", ("transport",)
        ).labels(self.name)
        # one unmetered INFO exchange: the server's response split, so
        # pull plans quote the streamed CHUNK_BATCH framing exactly
        _, frames, _ = self._exchange(wire.Op.INFO, "", "")
        self.response_batch_chunks = wire.decode_info(frames[0])

    # ------------------------------------------------------------ lifecycle

    def close(self) -> None:
        with self._lock:
            self._closed = True
            conns, self._conns = self._conns, []
        for c in conns:
            c.close()
        self._m_conns.set(0)

    def __enter__(self) -> "MuxSocketTransport":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ---------------------------------------------------------- connections

    def _lease_conn(self) -> _MuxConn:
        """The live shared connection with the fewest in-flight streams,
        dialing a new one while under the ``connections`` cap."""
        with self._lock:
            if self._closed:
                raise DeliveryError("mux transport is closed")
            self._conns = [c for c in self._conns if not c.is_dead()]
            live = self._conns
            if live:
                conn = min(live, key=_MuxConn.n_streams)
                # reuse when idle or at the cap; dial only when every
                # open connection is busy and there is room to grow
                if conn.n_streams() == 0 or len(live) >= self.max_connections:
                    return conn
            n_live = len(live)
        self._m_conns.set(n_live)        # dead ones just dropped
        try:
            conn = _MuxConn(self.address, self.timeout)
        except OSError as e:
            raise DeliveryError(
                f"mux transport: cannot connect to "
                f"{self.address[0]}:{self.address[1]} ({e})") from e
        surplus: Optional[_MuxConn] = None
        with self._lock:
            if self._closed:
                surplus, conn = conn, None
            elif len(self._conns) >= self.max_connections:
                # lost a dial race: someone else filled the last slot —
                # fold back onto the least-loaded existing connection
                surplus, conn = conn, min(self._conns,
                                          key=_MuxConn.n_streams)
            else:
                self._conns.append(conn)
            n = len(self._conns)
        if surplus is not None:
            surplus.close()
        self._m_conns.set(n)
        if conn is None:
            raise DeliveryError("mux transport is closed")
        return conn

    def _discard(self, conn: _MuxConn) -> None:
        with self._lock:
            if conn in self._conns:
                self._conns.remove(conn)
            n = len(self._conns)
        conn.close()
        self._m_conns.set(n)

    # -------------------------------------------------------------- streams

    def _begin(self, op: wire.Op, lineage: str, tag: str,
               frames: Sequence[bytes]
               ) -> Tuple[_MuxConn, int, _Stream, int]:
        """Open a stream: lease a connection, register an id, send the
        request.  A connection that turns out dead at send time is
        discarded and the send retried on a fresh one."""
        last: Optional[BaseException] = None
        for _ in range(2):
            conn = self._lease_conn()
            try:
                sid, st = conn.register()
            except _ConnectionClosed as e:
                last = e
                self._discard(conn)
                continue
            req = wire.encode_mux_request(op, sid, lineage, tag, frames)
            try:
                conn.send(req)
            except OSError as e:
                last = e
                conn.unregister(sid)
                self._discard(conn)
                continue
            self._m_streams.inc()
            return conn, sid, st, len(req)
        raise DeliveryError(
            f"mux transport: {op.name} to {self.address[0]}:"
            f"{self.address[1]}: cannot open a stream ({last})") from last

    def _finish(self, conn: _MuxConn, sid: int) -> None:
        conn.unregister(sid)
        self._m_streams.dec()

    def _next_item(self, op: wire.Op, st: _Stream, *,
                   header_pending: bool) -> Tuple[str, object, object, int]:
        """One message off the stream queue; transport failures surface as
        typed exceptions (:class:`_StaleStream` only while the header is
        still pending — the safe-to-retry window)."""
        try:
            kind, a, b, nbytes = st.q.get(timeout=self.timeout)
        except queue.Empty:
            raise DeliveryError(
                f"mux transport: {op.name} to {self.address[0]}:"
                f"{self.address[1]}: timed out after {self.timeout}s"
            ) from None
        if kind == "err":
            if isinstance(a, wire.WireError):
                raise wire.WireError(str(a))
            if header_pending:
                raise _StaleStream(str(a))
            raise DeliveryError(
                f"mux transport: {op.name} to {self.address[0]}:"
                f"{self.address[1]}: connection lost mid-stream ({a})")
        return kind, a, b, nbytes

    def _await_header(self, op: wire.Op, st: _Stream) -> Tuple[int, int, int]:
        kind, status, n, nbytes = self._next_item(op, st,
                                                  header_pending=True)
        if kind != "hdr":
            raise wire.WireError(f"mux stream began with a {kind} message, "
                                 f"expected its header")
        return status, n, nbytes

    def _await_frame(self, op: wire.Op, st: _Stream) -> Tuple[bytes, int]:
        kind, frame, _, nbytes = self._next_item(op, st,
                                                 header_pending=False)
        if kind != "frame":
            raise wire.WireError(f"mux stream carried a second header")
        return frame, nbytes

    # ------------------------------------------------------------- exchange

    def _exchange(self, op: wire.Op, lineage: str, tag: str,
                  frames: Sequence[bytes] = ()
                  ) -> Tuple[int, List[bytes], int]:
        """One multiplexed round-trip: ``(request_bytes, response_frames,
        response_bytes)``, retried once if the shared connection proved
        stale before the server answered."""
        try:
            return self._exchange_once(op, lineage, tag, frames)
        except _StaleStream:
            pass
        try:
            return self._exchange_once(op, lineage, tag, frames)
        except _StaleStream as e:
            raise DeliveryError(
                f"mux transport: {op.name} to {self.address[0]}:"
                f"{self.address[1]}: connection lost ({e})") from e

    def _exchange_once(self, op: wire.Op, lineage: str, tag: str,
                       frames: Sequence[bytes]
                       ) -> Tuple[int, List[bytes], int]:
        conn, sid, st, req_len = self._begin(op, lineage, tag, frames)
        try:
            status, n, resp_bytes = self._await_header(op, st)
            out: List[bytes] = []
            for _ in range(n):
                f, nb = self._await_frame(op, st)
                resp_bytes += nb
                out.append(f)
        finally:
            self._finish(conn, sid)
        if status == wire.STATUS_ERROR:
            self._raise_remote(out)
        return req_len, out, resp_bytes

    @staticmethod
    def _raise_remote(frames: Sequence[bytes]) -> None:
        if not frames:
            raise DeliveryError("remote error with no ERROR frame")
        code, msg = wire.decode_error(frames[0])
        if code is wire.ErrorCode.PUSH_REJECTED:
            raise PushRejected(msg)
        if code is wire.ErrorCode.WIRE:
            raise wire.WireError(msg)
        if code is wire.ErrorCode.BUSY:
            raise DeliveryError(f"server busy (load shed): {msg}")
        raise DeliveryError(msg)

    # ------------------------------------------------------------ transport

    # api-boundary
    def get_index(self, lineage: str, tag: str) -> Tuple[CDMT, int]:
        t0 = time.perf_counter()
        req_b, frames, resp_b = self._exchange(wire.Op.INDEX, lineage, tag)
        self._meter.rec("index", t0, index=req_b + resp_b)
        return wire.decode_index(frames[0]), req_b + resp_b

    # api-boundary
    def get_latest_index(self, lineage: str) -> Tuple[Optional[CDMT], int]:
        t0 = time.perf_counter()
        req_b, frames, resp_b = self._exchange(wire.Op.LATEST_INDEX,
                                               lineage, "")
        self._meter.rec("index", t0, index=req_b + resp_b)
        if not frames:
            return None, req_b + resp_b
        return wire.decode_index(frames[0]), req_b + resp_b

    # api-boundary
    def get_recipe(self, lineage: str, tag: str) -> Tuple[Recipe, int]:
        t0 = time.perf_counter()
        req_b, frames, resp_b = self._exchange(wire.Op.RECIPE, lineage, tag)
        self._meter.rec("recipe", t0, recipe=req_b + resp_b)
        return wire.decode_recipe(frames[0]), req_b + resp_b

    # api-boundary
    def fetch_chunks(self, lineage: str, tag: str,
                     fps: Sequence[bytes]) -> FetchResult:
        """One WANT stream; CHUNK_BATCH frames are decoded as the reader
        thread delivers them, so the hash-verify of one batch overlaps the
        socket reads of the next — and of every other in-flight stream."""
        t0 = time.perf_counter()
        want = wire.encode_want(fps)
        try:
            chunks, req_b, resp_b, error_frames = \
                self._fetch_once(lineage, tag, want)
        except _StaleStream:
            try:
                chunks, req_b, resp_b, error_frames = \
                    self._fetch_once(lineage, tag, want)
            except _StaleStream as e:
                raise DeliveryError(
                    f"mux transport: WANT to {self.address[0]}:"
                    f"{self.address[1]}: connection lost ({e})") from e
        if error_frames is not None:
            self._raise_remote(error_frames)
        leg = SourceLeg(source=REGISTRY_SOURCE, chunks=len(chunks),
                        chunk_bytes=resp_b, want_bytes=req_b, rounds=1)
        self._meter.rec_legs(t0, [leg])
        return FetchResult(chunks=chunks, legs=[leg])

    def _fetch_once(self, lineage: str, tag: str, want: bytes
                    ) -> Tuple[Dict[bytes, bytes], int, int,
                               Optional[List[bytes]]]:
        conn, sid, st, req_len = self._begin(wire.Op.WANT, lineage, tag,
                                             [want])
        chunks: Dict[bytes, bytes] = {}
        error_frames: Optional[List[bytes]] = None
        try:
            status, n, resp_bytes = self._await_header(wire.Op.WANT, st)
            if status == wire.STATUS_ERROR:
                error_frames = []
            for _ in range(n):
                f, nb = self._await_frame(wire.Op.WANT, st)
                resp_bytes += nb
                if error_frames is not None:
                    error_frames.append(f)
                else:
                    chunks.update(wire.decode_chunk_batch(f))
        finally:
            self._finish(conn, sid)
        return chunks, req_len, resp_bytes, error_frames

    # api-boundary
    def push(self, lineage: str, tag: str, recipe: Recipe,
             chunks: Dict[bytes, bytes], *,
             parent_version: Optional[int] = None,
             claimed_root: Optional[bytes] = None,
             claimed_params: Optional[CDMTParams] = None) -> PushOutcome:
        t0 = time.perf_counter()
        hdr = wire.encode_push_header(wire.PushHeader(
            lineage=lineage, tag=tag, root=claimed_root,
            parent_version=parent_version, params=claimed_params))
        recipe_frame = wire.encode_recipe(recipe)
        chunk_frames: List[bytes] = []
        fps = list(chunks)
        for start in range(0, len(fps), self.batch_chunks):
            part = {fp: chunks[fp]
                    for fp in fps[start:start + self.batch_chunks]}
            chunk_frames.append(wire.encode_chunk_batch(part))
        req_b, frames, resp_b = self._exchange(
            wire.Op.PUSH, lineage, tag, [hdr, recipe_frame] + chunk_frames)
        receipt = wire.decode_receipt(frames[0])
        # byte split matches the threaded transport: each body frame owns
        # its envelope length prefix; everything else rides header_bytes
        recipe_share = wire.uvarint_len(len(recipe_frame)) + len(recipe_frame)
        chunk_share = sum(wire.uvarint_len(len(f)) + len(f)
                          for f in chunk_frames)
        outcome = PushOutcome(
            receipt=receipt,
            header_bytes=req_b - recipe_share - chunk_share + resp_b,
            recipe_bytes=recipe_share,
            chunk_bytes=chunk_share,
            rounds=1 if chunks else 0)
        self._meter.rec("push", t0, index=outcome.header_bytes,
                        recipe=outcome.recipe_bytes,
                        chunk=outcome.chunk_bytes)
        return outcome

    # api-boundary
    def has_chunks(self, fps: Sequence[bytes]) -> Tuple[List[bytes], int]:
        t0 = time.perf_counter()
        req_b, frames, resp_b = self._exchange(wire.Op.HAS, "", "",
                                               [wire.encode_has(fps)])
        self._meter.rec("has", t0, want=req_b + resp_b)
        return wire.decode_missing(frames[0]), req_b + resp_b

    # api-boundary
    def tags(self, lineage: str) -> List[str]:
        t0 = time.perf_counter()
        _, frames, _ = self._exchange(wire.Op.TAGS, lineage, "",
                                      [wire.encode_tags_request(lineage)])
        self._meter.rec("tags", t0)
        return wire.decode_tag_list(frames[0])

    # api-boundary
    def notify_pulled(self, lineage: str, tag: str) -> None:
        pass

    # ------------------------------------------------------------- scraping

    def scrape_metrics(self) -> MetricsSnapshot:
        """One ``Op.METRICS`` exchange, unmetered (like the threaded
        transport) so ``transport_bytes_total`` stays report-exact."""
        _, frames, _ = self._exchange(wire.Op.METRICS, "", "")
        payload = wire.decode_metrics(frames[0])
        return MetricsSnapshot.from_json(payload.decode("utf-8"))

    # ---------------------------------------------------------- replication

    def ship_journal(self, replica: str, epoch: int, start: int,
                     limit: int = 512
                     ) -> Tuple[int, int, List[Tuple[int, bytes, bytes]]]:
        """One JOURNAL_SHIP exchange — same contract as the threaded
        transport's (checksum-verified records, nothing half-verified)."""
        _, frames, _ = self._exchange(
            wire.Op.JOURNAL_SHIP, "", "",
            [wire.encode_ship(replica, epoch, start, limit)])
        _, srv_epoch, head = wire.decode_repl_ack(frames[0])
        records = [wire.decode_record_frame(f) for f in frames[1:]]
        return srv_epoch, head, records

    def ack_journal(self, replica: str, epoch: int,
                    offset: int) -> Tuple[int, int]:
        _, frames, _ = self._exchange(
            wire.Op.REPL_ACK, "", "",
            [wire.encode_repl_ack(replica, epoch, offset)])
        _, srv_epoch, head = wire.decode_repl_ack(frames[0])
        return srv_epoch, head

    def replication_status(self) -> Tuple[int, int]:
        epoch, head, _ = self.ship_journal("", 0, 0, 0)
        return epoch, head

    def fetch_snapshot(self, replica: str = "standby"
                       ) -> Tuple[int, int, List[Tuple[int, bytes, bytes]]]:
        """One SNAPSHOT_SHIP exchange: the compacted state snapshot,
        streamed through the mux like a WANT response."""
        _, frames, _ = self._exchange(
            wire.Op.SNAPSHOT_SHIP, "", "",
            [wire.encode_snapshot(replica, 0, 0)])
        if not frames:
            raise wire.WireError("SNAPSHOT_SHIP response carried no frames")
        _, epoch, head = wire.decode_snapshot(frames[0])
        return epoch, head, [wire.decode_record_frame(f)
                             for f in frames[1:]]

    # -------------------------------------------------------------- quoting

    def quote_chunk_batches(self, sizes: Sequence[int]) -> int:
        """Exact socket bytes of one WANT stream's response for payloads
        ``sizes`` — CHUNK_BATCH frames at the server's split, wrapped in
        the mux HEADER + FRAME messages.  The stream id is fixed-width, so
        the quote needs no knowledge of which id will be allocated."""
        lens = wire.chunk_batch_frame_lens(sizes, self.response_batch_chunks)
        return wire.mux_response_envelope_bytes(lens)


def serve_registry_async(registry: Registry, host: str = "127.0.0.1",
                         port: int = 0, **server_kw) -> AsyncRegistryServer:
    """Convenience: wrap a bare :class:`Registry` in a frame-level
    :class:`RegistryServer` and put an event-loop front door on it."""
    return AsyncRegistryServer(RegistryServer(registry, **server_kw),
                               host=host, port=port)
