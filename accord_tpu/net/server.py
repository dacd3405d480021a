"""One real serving node: the Maelstrom node wiring behind a TCP socket loop.

``python -m accord_tpu.net.server --name n1 --listen 127.0.0.1:7001 \
    --peers n1=127.0.0.1:7001,n2=127.0.0.1:7002,n3=127.0.0.1:7003``

Reuses :class:`accord_tpu.maelstrom.node.MaelstromProcess` wholesale — the
same node wiring, wire codec, request/reply correlation and (r12-fixed)
sink-owned timeouts that speak to the Maelstrom harness over stdin/stdout —
behind an asyncio event loop: inbound frames (peer protocol traffic AND
client ``txn`` bodies) arrive over TCP, outbound packets route to per-peer
:class:`PeerLink`\\ s (reconnect + backoff) or back to the client connection
that sent the txn.  The process is single-threaded: protocol work, timers
and socket I/O all run on the loop, exactly like the reference Maelstrom
node's single listen loop.

The admission gate (``--admit-max`` / ``--target-p99-ms``) sits in front of
``coordinate`` via ``MaelstromProcess.admission``; shed replies are the
explicit ``Overloaded`` wire error (code 11, ``overloaded: true``,
``retry_after_ms``).  Control verbs (``ping`` / ``stats`` / ``dump``) serve
liveness probes, the serving stats surface (admission + per-link reconnect
counters) and flight-recorder post-mortem bundles without touching the
protocol path.

Socket faults arm from ACCORD_TPU_NET_FAULTS (see ``utils.faults``).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import sys
import time
from typing import Callable, Dict, Optional, Tuple

from typing import List

from .. import api
from ..local.fastpath import proto_fastpath_enabled
from ..obs import devprof
from ..utils import faults, invariants
from ..utils.random_source import RandomSource
from . import bootstrap as net_bootstrap
from . import codec as wire_codec
from .admission import AdmissionGate, device_health_of, rebalance_health_of
from .framing import FrameError, encode_frame, prefix_payload
from .codec import decode_payload
from .transport import FrameServer, PeerLink, coalesce_window_micros


class _Scheduled(api.Scheduled):
    __slots__ = ("handle", "cancelled")

    def __init__(self, handle=None):
        self.handle = handle
        self.cancelled = False

    def cancel(self) -> None:
        self.cancelled = True
        if self.handle is not None:
            self.handle.cancel()

    def is_cancelled(self) -> bool:
        return self.cancelled


class AsyncioScheduler(api.Scheduler):
    """api.Scheduler over the asyncio event loop (micros in, seconds out)."""

    def __init__(self, loop: asyncio.AbstractEventLoop,
                 times: Optional[dict] = None):
        self.loop = loop
        self.stopped = False
        # the serving loop's span table (NodeServer.loop_times): a fired
        # callback is one ``srv.timer`` span; beside it how many fired and
        # how late (fired - due, the loop's clock: a slow loop shows here
        # before it shows as a timeout or a recovery)
        self.times = times
        self.n_fires = 0
        self.lag_s = 0.0
        self.lag_max_s = 0.0

    def stop(self) -> None:
        """Nothing scheduled through here runs from now on, and nothing
        more is taken (a crash-stopped node's timers die with it)."""
        self.stopped = True

    def now(self, run: Callable[[], None]) -> None:
        if not self.stopped:
            self.loop.call_soon(run)

    def _fire(self, due: float, run: Callable[[], None]) -> None:
        lag = max(self.loop.time() - due, 0.0)
        self.n_fires += 1
        self.lag_s += lag
        if lag > self.lag_max_s:
            self.lag_max_s = lag
        with devprof.span("srv.timer", self.times):
            run()

    def once(self, delay_micros: int, run: Callable[[], None]) -> api.Scheduled:
        sched = _Scheduled()
        due = self.loop.time() + delay_micros / 1e6

        def fire():
            if not sched.cancelled and not self.stopped:
                self._fire(due, run)
        sched.handle = self.loop.call_at(due, fire)
        return sched

    def recurring(self, interval_micros: int,
                  run: Callable[[], None]) -> api.Scheduled:
        sched = _Scheduled()
        due = self.loop.time() + interval_micros / 1e6

        def fire():
            nonlocal due
            if sched.cancelled or self.stopped:
                return
            try:
                self._fire(due, run)
            finally:
                # reschedule even if run() raised: the timeout sweeper
                # rides this — if one sweep's failure callback blows up,
                # the node must keep detecting timeouts, not wedge with
                # every future dead-peer request pending forever
                due = self.loop.time() + interval_micros / 1e6
                sched.handle = self.loop.call_at(due, fire)
        sched.handle = self.loop.call_at(due, fire)
        return sched


class NodeServer:
    """One node process: FrameServer in, PeerLinks out, MaelstromProcess
    in the middle, AdmissionGate in front of coordinate."""

    def __init__(self, name: str, host: str, port: int,
                 peers: Dict[str, Tuple[str, int]],
                 stores: int = 2, shards: int = 16,
                 device_mode: Optional[bool] = False,
                 durability: bool = True,
                 admit_max: int = 64,
                 target_p99_ms: int = 1000,
                 min_budget: int = 4,
                 request_timeout_ms: Optional[int] = None,
                 journal_dir: Optional[str] = None,
                 journal_window_us: Optional[int] = None,
                 journal_snapshot_every: Optional[int] = None,
                 journal_segment_bytes: Optional[int] = None,
                 journal_sync: Optional[str] = None,
                 wire_codec_name: str = "binary",
                 members: Optional[List[str]] = None):
        self.name = name
        self.host = host
        self.port = port
        self.peers = {n: a for n, a in peers.items() if n != name}
        # epoch-1 membership (r17, elastic serving): the names the static
        # initial topology is built from.  Defaults to peers ∪ self (the
        # r12 behaviour); a node JOINING a live cluster spawns with the
        # EXISTING members only (--join / --members), so its epoch-1
        # topology byte-matches the cluster's and it becomes a member
        # only when an operator proposes the epoch that admits it.
        self.members = sorted(members, key=lambda n: (len(n), n)) \
            if members else None
        self.stores = stores
        self.shards = shards
        self.device_mode = device_mode
        self.durability = durability
        self.admit_max = admit_max
        self.target_p99_ms = target_p99_ms
        self.min_budget = min_budget
        self.request_timeout_ms = request_timeout_ms
        self.journal_dir = journal_dir
        self.journal_window_us = journal_window_us
        self.journal_snapshot_every = journal_snapshot_every
        self.journal_segment_bytes = journal_segment_bytes
        self.journal_sync = journal_sync
        # the peer wire codec: "binary" (the serving default; falls back
        # to json per-frame when msgpack is absent) or "json" (the debug
        # codec — human-greppable captures).  Clients are answered in the
        # codec THEY spoke (sniffed per frame), so a debug JSON client
        # against a binary cluster just works.
        if wire_codec_name == "binary" and not wire_codec.binary_available():
            print("[net] msgpack unavailable: --wire-codec binary serves "
                  "JSON frames", file=sys.stderr)
            wire_codec_name = "json"
        self.wire_codec = wire_codec_name
        self._start_ns = time.monotonic_ns()
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        self.links: Dict[str, PeerLink] = {}
        self._clients: Dict[str, asyncio.StreamWriter] = {}
        self._client_codec: Dict[str, str] = {}
        self._peer_hello: Dict[str, dict] = {}   # codec_hello per peer src
        self.proc = None
        self.journal = None
        self.gate: Optional[AdmissionGate] = None
        self.frame_server: Optional[FrameServer] = None
        # elastic serving (r17): the reconfiguration manager + the chunk
        # reassembler for snapshot-fed bootstrap streams
        self.reconfig = None
        self._chunks = net_bootstrap.ChunkReassembler()
        self._hello_frame: Optional[bytes] = None
        self._hello_epoch: Optional[int] = None
        self.n_chunk_streams_tx = 0
        self.n_chunk_frames_tx = 0
        self.n_client_replies = 0
        self.n_unroutable = 0
        self.n_reply_drops = 0
        # cross-request fused fan-out (r16): outbound peer bodies emitted
        # within one event-loop tick share one accord_batch envelope per
        # peer; client-reply frames to one connection share one write
        self._peer_pend: Dict[str, list] = {}
        self._client_pend: Dict = {}
        self._flush_scheduled = False
        self.n_batched_fanouts = 0     # envelopes sent (occupancy >= 2)
        self.n_batched_ops = 0         # sub-bodies riding envelopes
        self.batch_sizes: Dict[int, int] = {}   # envelope occupancy census
        self.n_unbatched_envelopes = 0  # envelopes received
        self.n_fast_sheds = 0          # sheds decided pre-body-decode
        self.scheduler: Optional[AsyncioScheduler] = None
        self.crashed = False           # crash_stop() ran: nothing in or out
        # what the serving loop spends its time on: span name -> [calls,
        # seconds] (obs.devprof.span; the node, its sink, stores,
        # dispatcher, scheduler and journal write the ``srv.*`` names
        # into this one table, on the loop's thread), and beside a
        # ``srv.req.<T>`` span the requests delivered under it.  Surfaced
        # as stats()["loop"]; no table, and so no clock, under
        # ACCORD_TPU_OBS=off
        on = devprof.enabled()
        self.loop_times: Optional[Dict[str, list]] = {} if on else None
        self.loop_members: Optional[Dict[str, int]] = {} if on else None

    def now_micros(self) -> int:
        return (time.monotonic_ns() - self._start_ns) // 1_000

    # a client that stops READING its socket must not grow the node's
    # memory: past this transport write-buffer bound its replies drop
    # (at-most-once delivery allows it; the client's timeout owns
    # recovery) — the admission contract is bounded resources everywhere
    CLIENT_WRITE_BUFFER_CAP = 4 * 1024 * 1024
    # finished txn span trees a node keeps (obs.spans.SpanRecorder's ring)
    SPAN_ROOTS_KEPT = 4096
    # most bodies one accord_batch envelope carries (a pathological tick
    # chunks instead of building a frame that courts MAX_FRAME)
    MAX_BATCH_OPS = 512

    def _write_bounded(self, dest: str,
                       writer: asyncio.StreamWriter, frame: bytes) -> bool:
        try:
            if (writer.transport.get_write_buffer_size()
                    > self.CLIENT_WRITE_BUFFER_CAP):
                self.n_reply_drops += 1
                return False
            writer.write(frame)
            return True
        except Exception:
            # evict BOTH maps: _client_gone derives its keys from
            # _clients, so a codec entry orphaned here would never be
            # reaped (one per departed client src, forever)
            self._clients.pop(dest, None)
            self._client_codec.pop(dest, None)
            return False

    # -- outbound -------------------------------------------------------------
    def _schedule_flush(self) -> None:
        if not self._flush_scheduled:
            self._flush_scheduled = True
            self.loop.call_soon(self._flush_tick)

    def _flush_tick(self) -> None:
        """End-of-tick flush: every peer's pending bodies leave as ONE
        frame (an accord_batch envelope when more than one — the shared
        fan-out N concurrent ops' PreAccept/Accept/Commit rounds ride),
        and every client connection's pending reply frames leave as one
        joined write.  Batching here is pure transport amortization: the
        receiver unbatches into the unchanged per-op protocol path."""
        with devprof.span("srv.flush_tick", self.loop_times):
            self._flush_scheduled = False
            if self._peer_pend:
                pend, self._peer_pend = self._peer_pend, {}
                for dest, bodies in pend.items():
                    # chunk a pathological tick: one envelope must never
                    # approach MAX_FRAME (a lost giant frame would take every
                    # rider with it; the queue bound already caps frames)
                    for at in range(0, len(bodies), self.MAX_BATCH_OPS):
                        chunk = bodies[at:at + self.MAX_BATCH_OPS]
                        if len(chunk) == 1:
                            body = chunk[0]
                        else:
                            body = {"type": "accord_batch", "msgs": chunk}
                            self.n_batched_fanouts += 1
                            self.n_batched_ops += len(chunk)
                        n = len(chunk)
                        self.batch_sizes[n] = self.batch_sizes.get(n, 0) + 1
                        try:
                            # no byte-overflow fallback needed here anymore:
                            # _send_peer_body chunk-streams ANY payload over
                            # CHUNK_THRESHOLD (1 MiB), so an envelope can
                            # never approach MAX_FRAME whole
                            self._send_peer_body(dest, body)
                        except Exception as exc:   # one peer's bad frame must
                            # not drop every OTHER peer's batch this tick
                            print(f"[{self.name}] batch encode to {dest} "
                                  f"failed: {exc!r}", file=sys.stderr)
            if self._client_pend:
                pend, self._client_pend = self._client_pend, {}
                for writer, (dest, frames) in pend.items():
                    self._write_bounded(
                        dest, writer,
                        frames[0] if len(frames) == 1 else b"".join(frames))

    def _send_peer_body(self, dest: str, body: dict) -> None:
        """Encode-once peer send: a body whose payload outgrows
        CHUNK_THRESHOLD leaves as an ``accord_chunk`` stream through the
        same coalescing link (the snapshot-fed bootstrap data plane —
        FetchSnapshotOk payloads scale with the donor's store); anything
        else is one length-prefixed frame exactly as before."""
        payload = wire_codec.encode_packet(
            {"src": self.name, "dest": dest, "body": body},
            self.wire_codec)
        link = self.links[dest]
        if len(payload) > net_bootstrap.CHUNK_THRESHOLD:
            frames = net_bootstrap.chunk_payload_frames(
                self.name, dest, payload, self.wire_codec)
            for f in frames:
                link.send(f)
            self.n_chunk_streams_tx += 1
            self.n_chunk_frames_tx += len(frames)
            return
        link.send(prefix_payload(payload))

    def _send_client(self, dest: str, writer, frame: bytes) -> None:
        """Queue one client-bound frame for the end-of-tick joined write
        (N txn_ok replies released by one journal group-commit fsync — or
        simply completing in one tick — cost one syscall, not N)."""
        ent = self._client_pend.get(writer)
        if ent is None:
            self._client_pend[writer] = (dest, [frame])
            self._schedule_flush()
        else:
            ent[1].append(frame)

    def _emit(self, dest, body: dict) -> None:
        if self.crashed:
            return
        if dest in self.links:
            # peer fan-out: batch within this event-loop tick — N ops'
            # protocol messages to one peer become one envelope, one
            # frame, one (coalesced) write
            pend = self._peer_pend.get(dest)
            if pend is None:
                self._peer_pend[dest] = [body]
                self._schedule_flush()
            else:
                pend.append(body)
            return
        writer = self._clients.get(dest)
        if writer is not None:
            # one reply frame to a client: the span's count is the
            # replies (txn_ok, errors, control answers) sent
            with devprof.span("srv.client_reply", self.loop_times):
                self.n_client_replies += 1
                self._send_client(dest, writer, encode_frame(
                    {"src": self.name, "dest": dest, "body": body},
                    self._client_codec.get(dest, "json")))
            return
        # init_ok to the synthetic "boot" client, or a reply to a client
        # whose connection is gone: at-most-once delivery — drop
        self.n_unroutable += 1

    def _client_gone(self, writer: asyncio.StreamWriter) -> None:
        """Connection closed: evict every client-src entry bound to this
        writer.  Without this the map grows one dead StreamWriter per
        client src forever (write() on a closed transport does not raise,
        so the lazy-evict path in _emit never fires), and replies to
        departed clients count as delivered instead of unroutable."""
        gone = [src for src, w in self._clients.items() if w is writer]
        for src in gone:
            del self._clients[src]
            self._client_codec.pop(src, None)
        self._client_pend.pop(writer, None)

    # -- inbound --------------------------------------------------------------
    def _on_payload(self, payload: bytes,
                    writer: asyncio.StreamWriter) -> None:
        """Raw frame payload in.  Binary frames carry a (kind, src,
        msg_id) prelude, so under overload a txn is SHED before its body
        — ops, datums, payload trees — is ever decoded: the shed stays
        the cheapest outcome the admission contract promises even now
        that decode is the next-biggest per-request cost.  JSON (debug
        codec) frames take the full-decode path below."""
        with devprof.span("srv.decode", self.loop_times):
            hdr = wire_codec.peek_header(payload)
            if hdr is not None and hdr[0] == wire_codec.KIND_TXN \
                    and self.gate is not None and self.proc is not None:
                _kind, src, msg_id = hdr
                self._clients[src] = writer
                self._client_codec[src] = "binary"
                if msg_id is not None and \
                        self.gate.inflight >= self.gate.effective_budget():
                    # duplicate of an already-answered request? the
                    # journaled at-most-once table replays it even under
                    # overload — dedupe outranks shedding (it costs one
                    # dict lookup)
                    j = self.proc.journal
                    stored = (j.replied_body(src, msg_id)
                              if j is not None
                              and hasattr(j, "replied_body") else None)
                    if stored is None:
                        admitted, reason, retry_ms = self.gate.try_admit()
                        if admitted:
                            # a release raced the peek: keep the slow
                            # path's single admission point authoritative
                            self.gate.unadmit()
                        else:
                            self.n_fast_sheds += 1
                            self.proc._reply_client(src, msg_id, {
                                "type": "error", "code": 11,
                                "text": "overloaded", "overloaded": True,
                                "reason": reason,
                                "retry_after_ms": retry_ms})
                            return
            try:
                packet = decode_payload(payload)
            except ValueError:
                raise   # FrameServer counts + drops this connection
        self._on_packet(packet, writer,
                        binary=payload[0] == wire_codec.MAGIC,
                        nbytes=len(payload))

    def _on_packet(self, packet: dict, writer: asyncio.StreamWriter,
                   binary: bool = False, nbytes: int = 0) -> None:
        body = packet.get("body") or {}
        typ = body.get("type")
        src = packet.get("src", "")
        if typ == "codec_hello":
            # link-handshake codec announcement (first frame after every
            # peer (re)connect): record it; an unsupported version is
            # surfaced loudly here AND in stats, instead of one silent
            # CodecError per frame.  r17: the hello may carry the peer's
            # current EPOCH — the reconfig manager uses it as the
            # catch-up/gossip trigger (epochless pre-r17 hellos and
            # mixed-epoch streams interoperate: the field is optional)
            self._peer_hello[src] = body
            link = self.links.get(src)
            if link is not None:
                link.poke()   # it dials us: our link to it need not wait
            v = body.get("version", 0)
            if v and v not in wire_codec.SUPPORTED_VERSIONS:
                print(f"[{self.name}] peer {src} announced unsupported "
                      f"wire codec version {v} (supported: "
                      f"{wire_codec.SUPPORTED_VERSIONS})", file=sys.stderr)
            if self.reconfig is not None:
                try:
                    self.reconfig.on_peer_hello(src, body)
                except Exception as exc:
                    print(f"[{self.name}] hello handler error: {exc!r}",
                          file=sys.stderr)
            return
        if typ in ("topo_new", "epoch_sync", "topo_fetch", "accord_chunk"):
            self._on_reconfig_verb(typ, src, body, writer)
            return
        if typ in ("ping", "stats", "dump", "reconfigure"):
            self._client_codec[src] = "binary" if binary else "json"
            self._control(typ, src, body, writer)
            return
        if typ == "txn":
            # remember the connection this client speaks on: its replies
            # (including sheds) route back over the same socket, in the
            # codec the client spoke
            self._clients[src] = writer
            self._client_codec[src] = "binary" if binary else "json"
        elif typ == "accord_batch":
            self.n_unbatched_envelopes += 1
        elif typ == "accord_rsp" and self.reconfig is not None:
            payload_doc = body.get("payload")
            if isinstance(payload_doc, dict) \
                    and payload_doc.get("_t") == "FetchSnapshotOk":
                # bootstrap data-plane accounting at the layer that
                # already KNOWS the byte length (direct frames and
                # reassembled chunk streams — the shapes a real snapshot
                # takes; a small one sharing an envelope goes uncounted
                # rather than paying a re-encode just to be weighed)
                self.reconfig.bootstrap_bytes_rx += nbytes
        try:
            self.proc.handle(packet)
        except Exception as exc:   # a poisoned packet must not kill the node
            print(f"[{self.name}] handler error on {typ}: {exc!r}",
                  file=sys.stderr)

    def _on_reconfig_verb(self, typ: str, src: str, body: dict,
                          writer: Optional[asyncio.StreamWriter]) -> None:
        """The reconfiguration gossip plane (peer-to-peer control):
        never touches the protocol path, never admission-gated.  Reached
        both from raw inbound frames and — via the process's
        control_fallback — from bodies that rode a peer accord_batch
        envelope."""
        try:
            if typ == "topo_new" and self.reconfig is not None:
                self.reconfig.on_topo_new(body.get("topology") or {},
                                          from_src=src)
            elif typ == "epoch_sync" and self.reconfig is not None:
                self.reconfig.on_epoch_sync(body.get("node") or src,
                                            int(body.get("epoch", 0)))
            elif typ == "topo_fetch" and self.reconfig is not None:
                self.reconfig.on_topo_fetch(body.get("node") or src,
                                            int(body.get("epoch", 0)))
            elif typ == "accord_chunk":
                # snapshot-fed bootstrap stream: reassemble; a completed
                # stream is one ordinary inner frame payload (either
                # codec), re-entering the normal dispatch
                inner = self._chunks.feed(body)
                if inner is not None:
                    try:
                        packet2 = decode_payload(inner)
                    except ValueError as exc:
                        print(f"[{self.name}] chunked payload "
                              f"undecodable: {exc!r}", file=sys.stderr)
                        return
                    self._on_packet(packet2, writer,
                                    binary=inner[0] == wire_codec.MAGIC,
                                    nbytes=len(inner))
        except Exception as exc:
            print(f"[{self.name}] reconfig handler error on {typ}: "
                  f"{exc!r}", file=sys.stderr)

    def _control_fallback(self, packet: dict) -> None:
        """Unknown bodies surfacing at the protocol unbatcher (reconfig
        gossip that shared an envelope with protocol traffic)."""
        body = packet.get("body") or {}
        typ = body.get("type")
        src = packet.get("src", "")
        if typ == "codec_hello":
            self._on_packet(packet, None)
        elif typ in ("topo_new", "epoch_sync", "topo_fetch",
                     "accord_chunk"):
            self._on_reconfig_verb(typ, src, body, None)

    def _control(self, typ: str, src: str, body: dict,
                 writer: asyncio.StreamWriter) -> None:
        msg_id = body.get("msg_id")
        if typ == "ping":
            reply = {"type": "pong", "in_reply_to": msg_id,
                     "name": self.name, "pid": os.getpid()}
        elif typ == "stats":
            reply = {"type": "stats_ok", "in_reply_to": msg_id,
                     "stats": self.stats()}
        elif typ == "reconfigure":
            # the operator verb (tools/reconfig.py): propose epoch N+1 —
            # add node / remove node / move a range.  The manager owns
            # validation, the durable-before-broadcast journal write and
            # the propagation; this path just correlates the reply.
            if self.reconfig is None:
                reply = {"type": "error", "code": 10,
                         "text": "reconfiguration disabled on this node"}
            else:
                try:
                    reply = self.reconfig.propose(body)
                except Exception as exc:
                    reply = {"type": "error", "code": 11, "text": repr(exc)}
            reply = dict(reply)
            reply["in_reply_to"] = msg_id
        else:   # dump: the flight-recorder post-mortems + metrics snapshot
            obs = self.proc.obs if self.proc is not None else None
            reply = {"type": "dump_ok", "in_reply_to": msg_id,
                     "flight": (json.loads(obs.flight.export_json())
                                if obs is not None and obs.flight is not None
                                else None),
                     "metrics": (obs.metrics.snapshot()
                                 if obs is not None else None)}
        self._send_client(src, writer, encode_frame(
            {"src": self.name, "dest": src, "body": reply},
            self._client_codec.get(src, "json")))

    # -- dynamic peer links (r17, elastic serving) ---------------------------
    def _mk_link(self, peer: str, host: str, port: int) -> PeerLink:
        import zlib
        # stable per-(me, peer) seed: hash() is salted per process,
        # crc32 is not — the backoff schedule must be reproducible
        jitter = RandomSource(
            0x7C9 ^ zlib.crc32(f"{self.name}->{peer}".encode()))
        link = PeerLink(self.name, peer, host, port, jitter,
                        hello_frame=self._hello_frame)
        link.on_state = self._on_link_state
        return link

    def _link_down(self, peer: str) -> bool:
        link = self.links.get(peer)
        return link is not None and link.down

    def _on_link_state(self, link: PeerLink, down: bool) -> None:
        """A link learned that its peer is gone (connection lost, re-dial
        refused) or back (its next hello left).  Gone: every callback
        pending on it fails at the next scheduler hop
        (MaelstromSink.fail_peer; never inside the link's own task), which
        is all a coordination needs to go on with the replicas that are
        left; what this tick still holds for it the link drops at the
        flush.  Back: nothing to do, the sink asks the link at every send."""
        from ..maelstrom.node import node_name_to_id
        event = "peer_down" if down else "peer_up"
        print(f"[{self.name}] {event}: {link.peer}", file=sys.stderr)
        proc = self.proc
        if proc is None or proc.sink is None:
            return
        obs = proc.obs
        if obs is not None:
            obs.metrics.counter("peer_link", node=self.name,
                                event=event).inc()
            if obs.flight is not None:
                obs.flight.record(proc.node.node_id, event, peer=link.peer)
        if down:
            peer = node_name_to_id(link.peer)
            proc.scheduler.now(lambda: proc.sink.fail_peer(peer))

    def ensure_link(self, peer: str, host: str, port: int) -> bool:
        """Dial-on-join: create (and start, when the loop is live) an
        outbound link to a peer learned from a topology doc.  Returns
        True when a NEW link was created."""
        if peer == self.name or peer in self.links:
            return False
        link = self._mk_link(peer, host, port)
        self.links[peer] = link
        self.peers[peer] = (host, port)
        if self.loop is not None:
            link.start()
        return True

    def drop_link(self, peer: str) -> None:
        """Drain-on-leave: close and forget the outbound link to a peer
        that is a member of no retained epoch.  Pending sink callbacks to
        it time out through the ordinary sweeper (the r13 tombstone heap
        compacts them); its inbound connection dies with its process."""
        link = self.links.pop(peer, None)
        self._peer_pend.pop(peer, None)
        if link is not None and self.loop is not None:
            self.loop.create_task(link.close())

    def refresh_hello(self) -> None:
        """Rebuild the codec_hello handshake frame with the node's
        CURRENT epoch and push it: future (re)connects announce it, and
        live links send it immediately as an ordinary idempotent frame —
        peers that slept through a reconfiguration see the epoch jump and
        fetch the gap (mixed-epoch interop: receivers accept hellos with
        or without the field)."""
        node = getattr(self.proc, "node", None) if self.proc else None
        epoch = node.topology_manager.epoch() if node is not None else None
        if epoch == self._hello_epoch and self._hello_frame is not None:
            return
        self._hello_epoch = epoch
        self._hello_frame = encode_frame(
            {"src": self.name, "dest": "", "body":
             wire_codec.hello_body(self.name, self.wire_codec,
                                   epoch=epoch)},
            self.wire_codec)
        for link in self.links.values():
            link.set_hello(self._hello_frame, announce=self.loop is not None)

    def batch_occupancy_p50(self) -> int:
        """Weighted median outbound per-peer batch size (1 = no sharing;
        the envelope census counts every flushed fan-out)."""
        return _weighted_median(self.batch_sizes)

    def store_group_occupancy_p50(self) -> int:
        """Weighted median ops per merged SafeCommandStore acquisition
        (r20 store-grouped execution), across this node's CommandStores
        (1 = no sharing; 0 with the knob off or before any drain)."""
        node = getattr(self.proc, "node", None) if self.proc else None
        if node is None:
            return 0
        merged: Dict[int, int] = {}
        for store in node.command_stores.stores:
            for size, n in store.group_sizes.items():
                merged[size] = merged.get(size, 0) + n
        return _weighted_median(merged)

    def stats(self) -> dict:
        proc = self.proc
        links = {n: l.stats() for n, l in sorted(self.links.items())}
        return {
            "name": self.name, "pid": os.getpid(),
            "uptime_micros": self.now_micros(),
            "admission": self.gate.stats() if self.gate else None,
            "links": links,
            "wire_codec": self.wire_codec,
            "peer_hello": dict(sorted(self._peer_hello.items())),
            "batching": {
                "batched_fanouts": self.n_batched_fanouts,
                "batched_ops": self.n_batched_ops,
                "batch_occupancy_p50": self.batch_occupancy_p50(),
                "unbatched_envelopes": self.n_unbatched_envelopes,
                "fast_sheds": self.n_fast_sheds,
                # r20 store-grouped execution (ACCORD_TPU_STORE_GROUP)
                "grouped_ops": getattr(getattr(proc, "node", None),
                                       "n_grouped_ops", 0),
                "group_fallbacks": getattr(getattr(proc, "node", None),
                                           "n_group_fallbacks", 0),
                "store_group_occupancy_p50":
                    self.store_group_occupancy_p50(),
            },
            "dispatch": (lambda d: None if d is None else {
                "flush_events": d.n_flush_events,
                "flush_members": d.n_flush_members,
                "flush_queries": d.n_flush_queries,
                "fused_launches": d.n_fused_launches,
            })(getattr(getattr(proc, "node", None), "dispatcher", None)),
            "device": self._device_stats(),
            "wire_bytes_tx": sum(l["bytes_tx"] for l in links.values()),
            "wire_bytes_rx": (self.frame_server.bytes_rx
                              if self.frame_server else 0),
            "frames_coalesced": sum(l["frames_coalesced"]
                                    for l in links.values()),
            "client_replies": self.n_client_replies,
            "coordination": self._coordination_stats(),
            "data": self._data_stats(),
            "peer_failures": self._peer_failure_stats(),
            "loop": self._loop_stats(),
            "unroutable": self.n_unroutable,
            "reply_drops": self.n_reply_drops,
            "frame_errors": (self.frame_server.n_frame_errors
                             if self.frame_server else 0),
            "pending_requests": (len(proc.sink.pending)
                                 if proc and proc.sink else 0),
            "failures": len(proc.failures) if proc else 0,
            "socket_faults": faults.active_socket_faults(),
            "journal": (self.journal.stats()
                        if self.journal is not None else None),
            # elastic serving (r17): the epoch lifecycle + bootstrap
            # stream surface the serve_bench rebalance rows read
            "reconfig": (self.reconfig.stats()
                         if self.reconfig is not None else None),
            "chunks": dict(self._chunks.stats(),
                           streams_tx=self.n_chunk_streams_tx,
                           chunk_frames_tx=self.n_chunk_frames_tx),
        }

    def _device_stats(self) -> Optional[dict]:
        """The attribution index's counters summed over this node's
        DeviceStates (DeviceState._attr_index): tokens re-read / flushes
        against ``attr_tokens`` (what the indexes hold) is how far the
        maintenance engages; with them the range queries' routes and the
        table syncs' crossings.  None on a node with the device path off."""
        node = getattr(self.proc, "node", None) if self.proc else None
        devs = [s.device for s in node.command_stores.stores
                if s.device is not None] if node is not None else []
        if not devs:
            return None
        return {
            "attr_refreshes": sum(d.n_attr_refreshes for d in devs),
            "attr_tokens_refreshed": sum(d.n_attr_tokens_refreshed
                                         for d in devs),
            "attr_device_builds": sum(d.n_attr_device_builds for d in devs),
            "attr_tokens": sum(d.n_attr_tokens for d in devs),
            "range_queries": sum(d.n_range_queries for d in devs),
            "range_device_queries": sum(d.n_range_device_queries
                                        for d in devs),
            # table syncs of the device routes (_DepsMirror.sync_device):
            # programs launched, arrays handed over
            "sync_launches": sum(d.n_sync_launches for d in devs),
            "sync_uploads": sum(d.n_sync_uploads for d in devs),
        }

    def _coordination_stats(self) -> Optional[dict]:
        """Which path this node's coordinated txns took (the PreAccept
        decision, obs/spans.decision) and the recoveries it started, from
        the counters obs.metrics already keeps; None while nothing counts
        the decisions (ACCORD_TPU_OBS=off, or before start())."""
        obs = self.proc.obs if self.proc is not None else None
        if obs is None or obs.spans is None:
            return None
        return {
            "fast": obs.metrics.peek_counter("txn_path", path="fast"),
            "slow": obs.metrics.peek_counter("txn_path", path="slow"),
            "recoveries": obs.metrics.counter_totals(
                "recoveries", by="event").get("attempt", 0),
            # the decided txns by TxnId.domain(), and the records this
            # node's scan replies carried to clients
            "range_txns": obs.metrics.peek_counter("txn_domain",
                                                   domain="range"),
            "key_txns": obs.metrics.peek_counter("txn_domain",
                                                 domain="key"),
            "scan_rows": self.proc.n_scan_rows,
        }

    def _peer_failure_stats(self) -> Optional[dict]:
        """How this node's request callbacks failed (at once on a link known
        down, with the link's drop while pending, by the sweeper's timeout),
        the frames its links did not take for a down peer, the reads whose
        first choice of replica was down, and the links' ``peer_down`` /
        ``peer_up`` events; None before start()."""
        from ..obs.metrics import PEER_COUNTERS
        proc = self.proc
        if proc is None or proc.sink is None:
            return None
        out = {k: getattr(proc.sink, attr) for k, attr in PEER_COUNTERS}
        out["down_drops"] = sum(l.n_down_drops for l in self.links.values())
        out["reads_to_down_replica"] = proc.node.n_reads_to_down_replica
        out["peer_down_events"] = sum(l.n_downs for l in self.links.values())
        out["peer_up_events"] = sum(l.n_ups for l in self.links.values())
        return out

    def _loop_stats(self) -> dict:
        """What the serving loop spent its time on so far: every ``srv.*``
        span as ``name: [calls, seconds]`` (inclusive of the spans nested
        under it; PERF.md §3 lists the sites), ``members`` (requests
        delivered under each ``srv.req.<T>`` span), and the timers: how
        many fired, the sum and the maximum of fired - due in seconds."""
        out = {name: list(cell)
               for name, cell in sorted((self.loop_times or {}).items())}
        sched = self.scheduler
        out["members"] = dict(sorted((self.loop_members or {}).items()))
        out["timer_fires"] = sched.n_fires if sched else 0
        out["timer_lag_s"] = sched.lag_s if sched else 0.0
        out["timer_lag_max_s"] = sched.lag_max_s if sched else 0.0
        return out

    def _data_stats(self) -> Optional[dict]:
        """Calls of, and host clock inside, this replica's data store's
        range read (KVDataStore.read_range); None before start()."""
        node = getattr(self.proc, "node", None) if self.proc else None
        if node is None:
            return None
        return {"scan_calls": node.data_store.scan_calls,
                "scan_host_s": node.data_store.scan_host_s}

    # -- lifecycle ------------------------------------------------------------
    async def start(self) -> None:
        import gc
        from ..maelstrom.node import MaelstromProcess
        from ..obs import Observability
        # cyclic-gc cadence tuned for a protocol server: the default gen-0
        # threshold (700 allocations) fires the collector thousands of
        # times per second under load, walking the same long-lived command
        # state every pass.  Freeze what start-up built (module graph,
        # jax, topology) out of the collector entirely and raise the
        # thresholds; cycles still collect, just in batches sized to the
        # allocation rate of real traffic.
        gc.collect()
        gc.freeze()
        gc.set_threshold(50_000, 25, 25)
        self.loop = asyncio.get_event_loop()
        faults.arm_socket_faults_from_env()
        faults.arm_disk_faults_from_env()
        scheduler = self.scheduler = AsyncioScheduler(self.loop,
                                                      self.loop_times)
        # a node serves for days: finished span trees leave through a ring
        obs = Observability(now=self.now_micros,
                            retire_roots=self.SPAN_ROOTS_KEPT)
        if self.journal_dir:
            # durable journal (r13): recover-or-create BEFORE the node
            # exists — the restored state rides into MaelstromProcess's
            # init handshake via the journal= parameter
            from ..journal import open_journal

            def _async_exec(work, done):
                # batch fsyncs run on a worker thread: milliseconds of
                # IO-wait must not stall the single protocol thread
                fut = self.loop.run_in_executor(None, work)
                fut.add_done_callback(lambda f: done(f.exception()))

            self.journal = open_journal(
                self.journal_dir,
                defer=lambda delay_s, fn: self.loop.call_later(
                    delay_s, lambda: None if self.crashed else fn()),
                window_micros=self.journal_window_us,
                snapshot_every=self.journal_snapshot_every,
                segment_bytes=self.journal_segment_bytes,
                metrics=obs.metrics,
                async_exec=_async_exec,
                sync_policy=self.journal_sync)
            self.journal.wal.times = self.journal.commit.times = \
                self.loop_times
        # elastic serving (r17): the reconfiguration manager owns the
        # epoch ledger, the topology gossip and the dynamic link
        # lifecycle; it recovers any journaled epoch history FIRST so a
        # node killed -9 mid-reconfiguration boots into the right epoch
        from .reconfig import ReconfigManager
        self.reconfig = ReconfigManager(self)
        self.reconfig.note_member(self.name, self.host, self.port)
        for peer, (host, port) in sorted(self.peers.items()):
            self.reconfig.note_member(peer, host, port)
        self.reconfig.load_journal_epochs(self.journal)
        self.proc = MaelstromProcess(
            emit=self._emit, scheduler=scheduler,
            now_micros=self.now_micros,
            num_stores=self.stores, shards=self.shards,
            device_mode=self.device_mode,
            durability=self.durability, obs=obs,
            journal=self.journal)
        self.proc.reconfig = self.reconfig
        self.proc.loop_times = self.loop_times
        self.proc.loop_members = self.loop_members
        self.proc.control_fallback = self._control_fallback
        self.proc.link_down = self._link_down
        if self.request_timeout_ms is not None:
            self.proc.request_timeout_micros = self.request_timeout_ms * 1000
        # admission gate in front of coordinate, composed with the r07
        # device ladder (quarantine lowers the budget) AND the r17
        # rebalance factor (a store mid-bootstrap prices the budget DOWN
        # — the join/leave load spike is absorbed as a cut, never a
        # collapse); when the r09 span trees are live the worst
        # per-phase p99 below the root joins the gate's own root
        # measurement in the AIMD signal (the root alone keeps
        # ACCORD_TPU_OBS=off working)
        from .admission import SpanPhaseP99
        phase_feed = (SpanPhaseP99(obs.metrics, root="txn").read
                      if obs.spans is not None else None)
        self.gate = AdmissionGate(
            max_inflight=self.admit_max,
            target_p99_micros=self.target_p99_ms * 1000,
            min_budget=self.min_budget,
            device_health=lambda: (device_health_of(self.proc.node)
                                   * rebalance_health_of(self.proc.node)),
            metrics=obs.metrics,
            phase_p99=phase_feed)
        self.proc.admission = self.gate
        # outbound links (deterministic per-(me, peer) jitter streams);
        # each link announces its wire codec + format version (+ current
        # epoch once the node is up — refresh_hello) as the first frame
        # after every (re)connect, and coalesces same-window frames into
        # one write priced off the write micro-probe
        self._hello_frame = encode_frame(
            {"src": self.name, "dest": "", "body":
             wire_codec.hello_body(self.name, self.wire_codec)},
            self.wire_codec)
        for peer, (host, port) in sorted(self.peers.items()):
            self.links[peer] = self._mk_link(peer, host, port)
        for link in self.links.values():
            link.start()
        # self-init BEFORE the frame server accepts: an inbound topo_new
        # racing a not-yet-initialized node would be dropped on the floor
        # (epoch-1 membership is self.members when set — a JOINING node
        # boots with the existing cluster's member list, itself excluded,
        # so every node's epoch 1 is byte-identical)
        names = self.members or sorted(set(self.peers) | {self.name},
                                       key=lambda n: (len(n), n))
        self.proc.handle({"src": "boot", "dest": self.name,
                          "body": {"type": "init", "msg_id": 0,
                                   "node_id": self.name,
                                   "node_ids": names}})
        self.refresh_hello()
        self.frame_server = FrameServer(self.host, self.port,
                                        on_close=self._client_gone,
                                        on_payload=self._on_payload)
        await self.frame_server.start()
        if self.journal is not None:
            # periodic snapshot check: bounds replay length and recycles
            # fully-snapshotted segments (the floor advance is the knob,
            # the 2s cadence is just how often we look)
            def snap_tick():
                try:
                    self.journal.maybe_snapshot(
                        data_store=self.proc.node.data_store,
                        busy=(self.gate is not None
                              and self.gate.inflight > 0))
                except Exception as exc:   # snapshotting must never kill
                    print(f"[{self.name}] snapshot tick failed: {exc!r}",
                          file=sys.stderr)
            scheduler.recurring(2_000_000, snap_tick)
        if any(s.device is not None
               for s in self.proc.node.command_stores.stores):
            # every store audits its device tick route now (the tick's
            # program is loaded before the first client, not by a store's
            # first tick in traffic) and once a period from then on,
            # whether or not its traffic schedules ticks
            from ..local.device_index import DeviceState

            def audit_routes():
                for s in self.proc.node.command_stores.stores:
                    if s.device is not None:
                        s.device.audit_route()
            audit_routes()
            scheduler.recurring(DeviceState.TICK_AUDIT_MICROS, audit_routes)
        print(f"[{self.name}] serving on {self.host}:{self.port} "
              f"peers={sorted(self.peers)} pid={os.getpid()} "
              f"journal={'on' if self.journal is not None else 'off'} "
              f"codec={self.wire_codec} "
              f"coalesce_us={coalesce_window_micros()}",
              file=sys.stderr, flush=True)

    def crash_stop(self) -> None:
        """Stop as a killed process stops, in a process that hosts other
        nodes too: the listening socket and every connection, in and out,
        are reset with no goodbye, what was queued is never sent, every
        timer (sweeper, progress logs, audit, snapshot, journal window) is
        dead, nothing is flushed and the journal is left as it lies.  An
        EMULATION: the object's memory stays, a restart is a new NodeServer
        on the same address.  Synchronous: when it returns, the node is
        silent."""
        self.crashed = True
        if self.scheduler is not None:
            self.scheduler.stop()
        if self.frame_server is not None:
            self.frame_server.abort()
        for link in self.links.values():
            link.abort()
        self._peer_pend.clear()
        self._client_pend.clear()
        if self.proc is not None and self.proc.node is not None:
            self.proc.node.alive = False

    async def close(self) -> None:
        # nothing in and nothing out (cancelling a link waits on the loop,
        # not on its peer), then the final flush, and only then the wait on
        # sockets: a graceful exit is durable whatever peers and clients do,
        # and what a timer does after the flush cannot leave the node
        if self.frame_server is not None:
            self.frame_server.stop()
        for link in self.links.values():
            await link.close()
        if self.journal is not None:
            try:
                if self.crashed:
                    # the files only: what the group commit still held is
                    # lost with the process
                    self.journal.wal.close()
                else:
                    self.journal.close()   # final flush (graceful exit only
            except OSError:                # — kill -9 relies on recovery)
                pass
        if self.frame_server is not None:
            await self.frame_server.close()


def _weighted_median(census: Dict[int, int]) -> int:
    total = sum(census.values())
    if not total:
        return 0
    seen = 0
    for size in sorted(census):
        seen += census[size]
        if seen * 2 >= total:
            return size
    return 0


def parse_addr(s: str) -> Tuple[str, int]:
    host, _, port = s.rpartition(":")
    return host or "127.0.0.1", int(port)


def parse_peers(s: str) -> Dict[str, Tuple[str, int]]:
    out = {}
    for part in s.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, addr = part.partition("=")
        out[name] = parse_addr(addr)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="accord-tpu TCP serving node")
    p.add_argument("--name", required=True)
    p.add_argument("--listen", required=True, help="host:port to bind")
    p.add_argument("--peers", required=True,
                   help="n1=host:port,n2=host:port,... (includes self)")
    p.add_argument("--stores", type=int, default=2)
    p.add_argument("--shards", type=int, default=16)
    p.add_argument("--device-mode", choices=("auto", "on", "off"),
                   default="off",
                   help="device kernels for deps scans (default off: host "
                        "route, fast cold start — the right default for "
                        "N processes sharing one small box)")
    p.add_argument("--no-durability", action="store_true")
    p.add_argument("--admit-max", type=int, default=64,
                   help="hard in-flight coordination budget")
    p.add_argument("--target-p99-ms", type=int, default=1000,
                   help="admission controller's sliding-p99 target")
    p.add_argument("--min-budget", type=int, default=4)
    p.add_argument("--request-timeout-ms", type=int, default=None,
                   help="sink-owned inter-node request timeout "
                        "(default: the Maelstrom adapter's 20s)")
    p.add_argument("--journal-dir", default=None,
                   help="durable journal directory: segmented WAL + "
                        "snapshots; a restart with the same dir recovers "
                        "the pre-crash command state (default: none — "
                        "kill -9 rejoins fresh-state)")
    p.add_argument("--journal-window-us", type=int, default=None,
                   help="group-commit batching window in micros "
                        "(default: priced off a once-per-process fsync "
                        "micro-probe)")
    p.add_argument("--journal-snapshot-every", type=int, default=None,
                   help="WAL records between snapshots (default 8192)")
    p.add_argument("--journal-segment-bytes", type=int, default=None,
                   help="WAL segment size (default 4MiB)")
    p.add_argument("--journal-sync", choices=("all", "client", "periodic"),
                   default=None,
                   help="what gates on the batch fsync: every protocol "
                        "reply (all), only the client txn_ok (client, "
                        "default — acked => durable; protocol promises "
                        "ride the page cache like Cassandra's periodic "
                        "commitlog), or nothing (periodic)")
    p.add_argument("--wire-codec", choices=("json", "binary"),
                   default="binary",
                   help="peer-link wire codec: versioned binary TLV "
                        "(default; compact + pre-decode admission) or "
                        "json (the debug codec — human-greppable "
                        "captures).  Frames are self-describing, so "
                        "mixed-codec clusters and clients interoperate")
    p.add_argument("--members", default=None,
                   help="epoch-1 member names, comma-separated (default: "
                        "every --peers name incl. self).  A node joining "
                        "a LIVE cluster must pass the existing members "
                        "(itself excluded) so its epoch-1 topology "
                        "byte-matches the cluster's; it becomes a member "
                        "when an operator proposes the admitting epoch "
                        "(tools/reconfig.py add)")
    p.add_argument("--join", action="store_true",
                   help="shorthand for --members = every --peers name "
                        "EXCEPT this node: boot as a non-member observer "
                        "awaiting the epoch that admits it")
    args = p.parse_args(argv)
    from ..ops.packing import startup
    startup()

    host, port = parse_addr(args.listen)
    device_mode = {"auto": None, "on": True, "off": False}[args.device_mode]
    peers = parse_peers(args.peers)
    members = None
    if args.members:
        members = [n.strip() for n in args.members.split(",") if n.strip()]
    elif args.join:
        members = [n for n in peers if n != args.name]
    # serving processes stand down the deep structural checks (the
    # documented invariants contract: "the simulator runs with full
    # paranoia while benchmarks run without" — r18 wired it: the O(n)
    # sortedness scans were a top-10 profile frame).  Assertions only
    # ever raise, so behavior is identical; ACCORD_TPU_PROTO_FASTPATH=off
    # restores them along with every other fast path.
    if proto_fastpath_enabled():
        invariants.PARANOID = False

    server = NodeServer(
        args.name, host, port, peers,
        stores=args.stores, shards=args.shards, device_mode=device_mode,
        durability=not args.no_durability,
        admit_max=args.admit_max, target_p99_ms=args.target_p99_ms,
        min_budget=args.min_budget,
        request_timeout_ms=args.request_timeout_ms,
        journal_dir=args.journal_dir,
        journal_window_us=args.journal_window_us,
        journal_snapshot_every=args.journal_snapshot_every,
        journal_segment_bytes=args.journal_segment_bytes,
        journal_sync=args.journal_sync,
        wire_codec_name=args.wire_codec,
        members=members)

    # ACCORD_TPU_NODE_PROFILE=<dir>: cProfile the whole node lifetime and
    # dump <dir>/<name>.pstats at clean shutdown (SIGTERM).  The serving
    # twin of tools/profile.py — attribution for per-op protocol CPU, the
    # quantity that now bounds the sim→wire gap (ROADMAP item 4).
    prof_dir = os.environ.get("ACCORD_TPU_NODE_PROFILE")
    profiler = None
    if prof_dir:
        import cProfile
        profiler = cProfile.Profile()
        profiler.enable()

    loop = asyncio.new_event_loop()
    asyncio.set_event_loop(loop)
    stop = asyncio.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:   # pragma: no cover - non-unix
            pass
    loop.run_until_complete(server.start())
    try:
        loop.run_until_complete(stop.wait())
    finally:
        loop.run_until_complete(server.close())
        loop.close()
        if profiler is not None:
            profiler.disable()
            os.makedirs(prof_dir, exist_ok=True)
            out = os.path.join(prof_dir, f"{args.name}.pstats")
            profiler.dump_stats(out)
            print(f"[profile] {out}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
