"""Asyncio TCP transport: per-peer outbound links + a frame server.

Topology is a full mesh of DIRECTIONAL links: node A's :class:`PeerLink`
to B carries every A->B packet; B's own link back carries B->A.  Inbound
connections are receive-only.  This keeps reconnect state strictly
per-outbound-link (no connection-dedup handshake) and means a one-way
partition degrades exactly one direction.

Delivery contract (SURVEY §2.10 MessageSink): **at-most-once, no ordering
assumptions, timeouts owned by the sink.**  A link buffers a BOUNDED queue
of frames while disconnected (drop-oldest beyond — the sink's request
timeout owns recovery, not the transport), sends each frame at most once,
and never replays on reconnect — so a reply racing a reconnect can only
arrive zero or one times, and the sink's pending-table pop makes dispatch
idempotent even against a reply racing its own timeout.

Peer state: a link is DOWN from the moment it has lost a connection and its
re-dial was refused or failed, and up again once its next hello has gone out.
Nothing is configured: it is what the link knows anyway.  While down it
keeps re-dialing on its backoff, takes no frame (``send`` counts and drops:
whoever waits for an answer has been told, see ``on_state``) and holds none.
A link that never had a connection is not down: its peer may be starting.
A peer that goes silent without closing its socket is not down either: the
sink's request timeout owns that case.

Write coalescing (r16): frames queued on a link within one event-loop
tick leave in ONE joined write — the r12 transport paid one ``write`` +
``drain`` round per frame, which at a dozen protocol frames per txn was a
first-order tax on the serving path.  The greedy drain is free (those
frames were already queued); on top of it a LINGER window lets a write
wait briefly for the next frame, priced off a once-per-process socket
write micro-probe exactly like the journal's group-commit window prices
its fsync batching (never a hard threshold): the linger may cost at most
``COALESCE_FACTOR`` write-syscalls' worth of latency, clamped.  Injected
socket faults keep their r12 per-FRAME draw rate (intensity invariant
under coalescing) while a ``conn_reset`` draw anywhere in a batch tears
the WHOLE coalesced write — the at-most-once contract already covers it
(nothing is replayed; the sink times the lost ops out), and the
fault-matrix net leg asserts zero duplicate replies under exactly this.

Reconnect: capped exponential backoff with deterministic jitter drawn from
a dedicated :class:`RandomSource` stream (same policy as the r07 device
quarantine backoff — co-failed links must not re-dial in lockstep).  When
a ``hello_frame`` is configured (the codec handshake, ``net.codec``), it
is sent first on every (re)connect before any queued frame.

Fault injection (``utils.faults`` socket kinds, armed per-process via
ACCORD_TPU_NET_FAULTS): ``conn_reset`` aborts the link mid-write,
``stalled_peer`` holds the writer for a drawn interval, ``slow_link``
delays each write — all drawn from the injected seeded source only.
"""

from __future__ import annotations

import asyncio
import os
import socket
import time
from typing import Callable, List, Optional

from ..utils import faults
from ..utils.random_source import RandomSource
from .framing import FrameDecoder, FrameError

# reconnect backoff: 50ms, 100ms, ... capped at 2s, plus up to 50% jitter
BACKOFF_BASE_MICROS = 50_000
BACKOFF_CAP_MICROS = 2_000_000
# frames buffered per link while disconnected (drop-oldest beyond)
LINK_QUEUE_FRAMES = 2048
# one coalesced write never exceeds this many bytes (a bound, not a
# target: the greedy drain stops here so a burst cannot build one
# pathological multi-MB write)
COALESCE_MAX_BYTES = 256 * 1024
# linger pricing: waiting for the next frame may cost at most this many
# measured write-syscalls' worth of latency, clamped to the window below
COALESCE_FACTOR = 8
COALESCE_MIN_MICROS = 0
COALESCE_MAX_MICROS = 1_000
# how long a closing FrameServer lets its accepted connections drain what
# is already written to them before it aborts them
CLOSE_GRACE_S = 1.0

_write_probe_cache: Optional[int] = None


def probe_write_micros(rounds: int = 32) -> int:
    """Median cost of one small socket write syscall, measured ONCE per
    process over a loopback socketpair — the price signal the coalescing
    linger is derived from (same discipline as the journal group-commit
    window's fsync micro-probe)."""
    global _write_probe_cache
    if _write_probe_cache is not None:
        return _write_probe_cache
    samples = []
    try:
        a, b = socket.socketpair()
        try:
            a.setblocking(False)
            payload = b"\x00" * 512
            for _ in range(rounds):
                t0 = time.perf_counter_ns()
                a.send(payload)
                samples.append((time.perf_counter_ns() - t0) // 1_000)
                # drain so the buffer never fills
                try:
                    b.recv(4096)
                except BlockingIOError:
                    pass
        finally:
            a.close()
            b.close()
    except OSError:
        samples = [5]
    samples.sort()
    _write_probe_cache = max(1, samples[len(samples) // 2])
    return _write_probe_cache


def coalesce_window_micros() -> int:
    """The priced linger window: COALESCE_FACTOR write-syscalls' worth of
    wall clock, clamped.  Env override ACCORD_TPU_COALESCE_US (0 disables
    the linger; the same-tick greedy drain always runs)."""
    env = os.environ.get("ACCORD_TPU_COALESCE_US")
    if env is not None:
        try:
            return max(0, int(env))
        except ValueError:
            pass
    return max(COALESCE_MIN_MICROS,
               min(COALESCE_MAX_MICROS,
                   probe_write_micros() * COALESCE_FACTOR))


def backoff_micros(attempt: int, jitter: RandomSource) -> int:
    """Backoff before reconnect ``attempt`` (0-based): capped exponential
    plus deterministic jitter in [0, base/2)."""
    base = min(BACKOFF_CAP_MICROS, BACKOFF_BASE_MICROS << min(attempt, 16))
    return base + jitter.next_int(max(base // 2, 1))


class PeerLink:
    """One outbound connection to a peer, kept alive forever.

    ``send`` enqueues a pre-encoded frame and never blocks the caller; the
    writer task drains the queue into the socket — coalescing every frame
    available within the priced linger window into one write — and
    reconnects with capped backoff on any failure.  Counters feed the
    serving stats surface."""

    def __init__(self, me: str, peer: str, host: str, port: int,
                 jitter: RandomSource,
                 max_queue: int = LINK_QUEUE_FRAMES,
                 hello_frame: Optional[bytes] = None,
                 linger_micros: Optional[int] = None):
        self.me = me
        self.peer = peer
        self.host = host
        self.port = port
        self._jitter = jitter
        self._queue: asyncio.Queue = asyncio.Queue(maxsize=max_queue)
        self._task: Optional[asyncio.Task] = None
        self._writer: Optional[asyncio.StreamWriter] = None
        self._wake = asyncio.Event()   # poke(): cut the backoff short
        self._hello = hello_frame
        self._linger_s = (coalesce_window_micros()
                          if linger_micros is None else linger_micros) / 1e6
        self.connected = False
        # the peer is known gone: a connection was lost and the re-dial
        # after it refused or failed; cleared by the next hello that leaves
        self.down = False
        # told (link, down) at every change of ``down``, on the loop
        self.on_state: Optional[Callable[["PeerLink", bool], None]] = None
        self.n_downs = 0
        self.n_ups = 0
        self.n_enqueued = 0        # frames ``send`` took
        self.n_down_drops = 0      # frames refused or let go while down
        self.n_connects = 0        # successful dials (first + re-)
        self.n_reconnects = 0      # successful dials after the first
        self.n_dial_failures = 0
        self.n_sent = 0
        self.n_writes = 0          # coalesced write syscall rounds
        self.n_frames_coalesced = 0  # frames that shared a write beyond
        #                              the first of their batch
        self.bytes_tx = 0
        self.n_dropped = 0         # frames dropped by the bounded queue
        self.n_reset_faults = 0    # injected conn_reset firings

    def start(self) -> None:
        self._task = asyncio.get_event_loop().create_task(self._run())

    def set_hello(self, frame: Optional[bytes],
                  announce: bool = False) -> None:
        """Replace the handshake frame used on future (re)connects —
        the elastic-serving path refreshes it whenever the node's epoch
        moves.  ``announce`` additionally sends the fresh hello down the
        LIVE link as an ordinary frame (receivers treat codec_hello as
        idempotent state), so peers learn the new epoch without waiting
        for a reconnect."""
        self._hello = frame
        if announce and frame is not None:
            self.send(frame)

    async def close(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except (asyncio.CancelledError, Exception):
                pass

    def poke(self) -> None:
        """The peer was heard from (its own link's hello came in): a link
        that waits out a backoff re-dials now, and starts its backoff over
        if that dial fails too.  (A link learns of its peer's death while
        idle and has backed off far by the time the peer is back.)"""
        if not self.connected:
            self._wake.set()

    async def _pause(self, micros: int) -> bool:
        """Sleep ``micros``, or until poked (True)."""
        try:
            await asyncio.wait_for(self._wake.wait(), micros / 1e6)
        except asyncio.TimeoutError:
            return False
        self._wake.clear()
        return True

    def abort(self) -> None:
        """Stop as a killed process stops: the connection is reset, what
        was queued is never sent, nothing says goodbye.  The link is dead
        afterwards (no re-dial)."""
        if self._task is not None:
            self._task.cancel()
        if self._writer is not None:
            self._writer.transport.abort()

    def send(self, frame: bytes) -> None:
        """Enqueue one frame (drop-oldest beyond the bound: the transport
        never buffers unboundedly — the sink's timeout owns recovery).  A
        link that knows its peer is down takes none."""
        if self.down:
            self.n_down_drops += 1
            return
        self.n_enqueued += 1
        while True:
            try:
                self._queue.put_nowait(frame)
                return
            except asyncio.QueueFull:
                try:
                    self._queue.get_nowait()
                    self.n_dropped += 1
                except asyncio.QueueEmpty:
                    pass

    async def _run(self) -> None:
        attempt = 0
        while True:
            try:
                reader, writer = await asyncio.open_connection(
                    self.host, self.port)
            except (OSError, asyncio.TimeoutError):
                self.n_dial_failures += 1
                if self.n_connects and not self.down:
                    # it had a connection, and cannot have it back
                    self._set_down(True)
                poked = await self._pause(
                    backoff_micros(attempt, self._jitter))
                attempt = 0 if poked else attempt + 1
                continue
            self.connected = True
            self._wake.clear()
            self._writer = writer
            self.n_connects += 1
            if self.n_connects > 1:
                self.n_reconnects += 1
            attempt = 0
            try:
                if self._hello is not None:
                    # codec handshake: announce this link's wire codec +
                    # format version before any protocol frame
                    writer.write(self._hello)
                    self.bytes_tx += len(self._hello)
                    await writer.drain()
                if self.down:
                    self._set_down(False)
                await self._serve(reader, writer)
            except (ConnectionError, OSError, asyncio.IncompleteReadError):
                pass
            finally:
                self.connected = False
                self._writer = None
                try:
                    writer.close()
                except Exception:
                    pass
            # brief jittered pause even on a clean drop so a flapping
            # acceptor isn't hammered at loop speed
            await self._pause(backoff_micros(0, self._jitter))

    def _set_down(self, down: bool) -> None:
        self.down = down
        if down:
            self.n_downs += 1
            # what was queued for the peer goes with it (never replayed:
            # the owner fails whatever waited on these frames)
            while not self._queue.empty():
                self._queue.get_nowait()
                self.n_down_drops += 1
        else:
            self.n_ups += 1
        if self.on_state is not None:
            self.on_state(self, down)

    async def _serve(self, reader: asyncio.StreamReader,
                     writer: asyncio.StreamWriter) -> None:
        """Pump the queue until the connection ends: a write fails, or the
        peer closes its end.  Nothing ever comes back on an outbound link,
        so the end of its read side IS the peer's close, and an idle link
        learns of it here and not at its next write."""
        loop = asyncio.get_event_loop()
        tasks = (loop.create_task(self._pump(writer)),
                 loop.create_task(self._until_peer_closes(reader)))
        try:
            await asyncio.wait(tasks, return_when=asyncio.FIRST_COMPLETED)
        finally:
            for t in tasks:
                t.cancel()
            # collects what the two ended with (a reset, a cancellation);
            # a cancellation of THIS task still propagates
            await asyncio.gather(*tasks, return_exceptions=True)

    @staticmethod
    async def _until_peer_closes(reader: asyncio.StreamReader) -> None:
        while await reader.read(4096):
            pass

    def _drain_batch(self, batch: List[bytes], budget: int) -> int:
        """Greedily move every queued frame into ``batch`` up to the byte
        budget; returns the bytes taken."""
        taken = 0
        while taken < budget:
            try:
                frame = self._queue.get_nowait()
            except asyncio.QueueEmpty:
                break
            batch.append(frame)
            taken += len(frame)
        return taken

    async def _pump(self, writer: asyncio.StreamWriter) -> None:
        while True:
            first = await self._queue.get()
            batch = [first]
            nbytes = len(first)
            nbytes += self._drain_batch(batch, COALESCE_MAX_BYTES - nbytes)
            if len(batch) == 1 and self._linger_s > 0:
                # nothing else queued: linger one priced window — a burst
                # mid-arrival coalesces instead of going out frame-by-
                # frame, and the window costs at most a few syscalls'
                # worth of latency by construction
                await asyncio.sleep(self._linger_s)
                nbytes += self._drain_batch(batch,
                                            COALESCE_MAX_BYTES - nbytes)
            # injected socket faults (seedable; see utils.faults) — drawn
            # per FRAME exactly as r12 did, so the configured fault
            # intensity is invariant under coalescing (a per-write draw
            # would concentrate the same probability into correlated
            # whole-batch kills and make the armed rate mean something
            # different at every batch depth).  The BLAST RADIUS is the
            # write: one reset draw anywhere in the batch tears the whole
            # coalesced write — the half-written-batch case the fault
            # matrix asserts never replays acked ops
            delay_micros = 0
            reset = False
            for _ in batch:
                if faults.socket_fault_fires("slow_link"):
                    delay_micros += faults.socket_fault_delay_micros(
                        "slow_link")
                if faults.socket_fault_fires("stalled_peer"):
                    delay_micros += faults.socket_fault_delay_micros(
                        "stalled_peer")
                if faults.socket_fault_fires("conn_reset"):
                    reset = True
            if delay_micros:
                await asyncio.sleep(delay_micros / 1e6)
            if reset:
                self.n_reset_faults += 1
                writer.transport.abort()   # batch lost, link reconnects
                raise ConnectionResetError("injected conn_reset")
            writer.write(batch[0] if len(batch) == 1 else b"".join(batch))
            self.n_sent += len(batch)
            self.n_writes += 1
            self.n_frames_coalesced += len(batch) - 1
            self.bytes_tx += nbytes
            await writer.drain()

    def stats(self) -> dict:
        return {"peer": self.peer, "connected": self.connected,
                "down": self.down, "downs": self.n_downs,
                "ups": self.n_ups, "enqueued": self.n_enqueued,
                "down_drops": self.n_down_drops,
                "connects": self.n_connects,
                "reconnects": self.n_reconnects,
                "dial_failures": self.n_dial_failures,
                "sent": self.n_sent, "writes": self.n_writes,
                "frames_coalesced": self.n_frames_coalesced,
                "bytes_tx": self.bytes_tx,
                "dropped": self.n_dropped,
                "reset_faults": self.n_reset_faults,
                "queued": self._queue.qsize()}


class FrameServer:
    """Accept loop: every inbound connection (peer or client) is split
    into frames and handed on — raw payload bytes to ``on_payload`` when
    wired (the server's pre-decode admission path), else decoded packets
    to ``on_packet``.  A framing/codec violation drops THAT connection
    only."""

    def __init__(self, host: str, port: int,
                 on_packet: Optional[Callable] = None,
                 on_close: Optional[
                     Callable[[asyncio.StreamWriter], None]] = None,
                 on_payload: Optional[Callable] = None):
        self.host = host
        self.port = port
        self.on_packet = on_packet
        self.on_payload = on_payload
        self.on_close = on_close
        self._server: Optional[asyncio.AbstractServer] = None
        self._writers: set = set()   # accepted and not yet gone
        self._stopped = False
        self.n_accepted = 0
        self.n_frame_errors = 0
        self.bytes_rx = 0

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._handle, self.host, self.port)

    def stop(self) -> None:
        """Stop accepting and delivering, without waiting: no frame reaches
        the callbacks after this.  What was accepted is closed here, since
        ``wait_closed()`` waits (Python 3.12) for every such connection and
        a peer or client may hold its end open for ever."""
        self._stopped = True
        if self._server is not None:
            self._server.close()
        for writer in self._writers:
            writer.close()

    def abort(self) -> None:
        """stop(), and what was accepted is reset, not closed: unsent
        replies are lost and the other ends read a reset, as from a killed
        process."""
        self.stop()
        for writer in self._writers:
            writer.transport.abort()

    async def close(self) -> None:
        self.stop()
        if self._server is None:
            return
        try:
            await asyncio.wait_for(self._server.wait_closed(), CLOSE_GRACE_S)
        except asyncio.TimeoutError:
            # a peer that stopped reading holds unsent replies in a
            # closing transport's buffer: give them up
            for writer in self._writers:
                writer.transport.abort()
            await self._server.wait_closed()

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        self.n_accepted += 1
        self._writers.add(writer)
        decoder = FrameDecoder()
        try:
            while not self._stopped:   # accepted as stop() ran: not served
                chunk = await reader.read(65536)
                if not chunk or self._stopped:
                    return
                self.bytes_rx += len(chunk)
                if self.on_payload is not None:
                    for payload in decoder.feed_raw(chunk):
                        self.on_payload(payload, writer)
                else:
                    for packet in decoder.feed(chunk):
                        self.on_packet(packet, writer)
        except (FrameError, ValueError):
            # FrameError = desynced length prefix; ValueError covers a
            # CodecError/garbage payload — either way this stream cannot
            # be trusted past this point
            self.n_frame_errors += 1
        except (ConnectionError, OSError, asyncio.IncompleteReadError):
            pass
        finally:
            self._writers.discard(writer)
            if self.on_close is not None:
                try:
                    self.on_close(writer)
                except Exception:
                    pass
            try:
                writer.close()
            except Exception:
                pass
