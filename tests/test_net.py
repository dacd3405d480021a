"""TCP serving surface (r12): framing, admission control, loopback
golden-frame byte-identity, the 2-process cluster smoke, kill-9 recovery,
and the (slow) open-loop overload sweep.

The sim remains THE correctness story — these tests cover the layer the
sim by construction cannot: real sockets (partial reads, coalesced
writes, resets), real processes (kill -9, reconnect backoff), and real
wall-clock queueing under open-loop overload (shed-not-collapse).
"""

import asyncio
import json

import pytest

from accord_tpu.net import codec as wcodec
from accord_tpu.net.admission import (AdmissionGate, Overloaded,
                                      device_health_of)
from accord_tpu.net.framing import (MAX_FRAME, FrameDecoder, FrameError,
                                    encode_frame)
from accord_tpu.net.transport import (BACKOFF_BASE_MICROS,
                                      BACKOFF_CAP_MICROS, backoff_micros)
from accord_tpu.utils import faults
from accord_tpu.utils.random_source import RandomSource


# ---------------------------------------------------------------------------
# framing: one frame survives ANY kernel segmentation
# ---------------------------------------------------------------------------

PACKETS = [
    {"src": "c1", "dest": "n1", "body": {"type": "init", "msg_id": 1,
                                         "node_id": "n1",
                                         "node_ids": ["n1", "n2"]}},
    {"src": "c1", "dest": "n1",
     "body": {"type": "txn", "msg_id": 2,
              "txn": [["append", 7, 1], ["r", 7, None]]}},
    # the four reference datum kinds on the client boundary
    {"src": "c1", "dest": "n1",
     "body": {"type": "txn", "msg_id": 3,
              "txn": [["append", 1, "s0"], ["append", 2, (1 << 33) + 5],
                      ["append", 3, 2.5], ["append", 4, {"hash": 77}]]}},
    {"src": "n1", "dest": "n2",
     "body": {"type": "accord_req", "msg_id": 9,
              "payload": {"_t": "PreAccept", "x": [1, 2, 3],
                          "nested": {"deep": ["a", None, True]}}}},
    {"src": "n2", "dest": "n1", "body": {"type": "accord_reply",
                                         "in_reply_to": 9,
                                         "payload": {"_t": "PreAcceptOk"}}},
    # unicode + empty body edges
    {"src": "cé", "dest": "n1", "body": {}},
]


def test_frame_roundtrip_each_packet():
    for pkt in PACKETS:
        dec = FrameDecoder()
        out = dec.feed(encode_frame(pkt))
        assert out == [pkt]
        assert dec.pending_bytes() == 0


def test_frame_decoder_partial_reads_byte_at_a_time():
    """The most hostile segmentation the kernel can produce: one byte per
    read, across every frame boundary."""
    blob = b"".join(encode_frame(p) for p in PACKETS)
    dec = FrameDecoder()
    out = []
    for i in range(len(blob)):
        out.extend(dec.feed(blob[i:i + 1]))
    assert out == PACKETS
    assert dec.pending_bytes() == 0


def test_frame_decoder_coalesced_single_read():
    """All frames in one read() — plus a trailing partial frame that must
    buffer, not deliver."""
    blob = b"".join(encode_frame(p) for p in PACKETS)
    tail = encode_frame(PACKETS[0])
    dec = FrameDecoder()
    out = dec.feed(blob + tail[:5])
    assert out == PACKETS
    assert dec.pending_bytes() == 5
    assert dec.feed(tail[5:]) == [PACKETS[0]]


def test_frame_decoder_random_segmentation():
    """Deterministic random chunking over the concatenated stream."""
    rs = RandomSource(13)
    blob = b"".join(encode_frame(p) for p in PACKETS * 3)
    dec = FrameDecoder()
    out, i = [], 0
    while i < len(blob):
        n = 1 + rs.next_int(17)
        out.extend(dec.feed(blob[i:i + n]))
        i += n
    assert out == PACKETS * 3


def test_frame_error_on_oversized_length():
    dec = FrameDecoder()
    bad = (MAX_FRAME + 1).to_bytes(4, "big") + b"x"
    with pytest.raises(FrameError):
        dec.feed(bad)


def test_frame_error_on_garbage_length():
    """TLS/HTTP bytes read as a length prefix must be rejected, not
    allocated."""
    dec = FrameDecoder()
    with pytest.raises(FrameError):
        dec.feed(b"\xffGET / HTTP/1.1\r\n")


def test_encode_rejects_oversized_payload():
    with pytest.raises(FrameError):
        encode_frame({"pad": "x" * (MAX_FRAME + 1)})


# ---------------------------------------------------------------------------
# the versioned binary wire codec (r16): cross-codec decode identity,
# pre-decode header peeking, and the golden pins that freeze the format
# ---------------------------------------------------------------------------

def test_binary_roundtrip_decodes_identically_to_json():
    """The codec-compatibility gate's core claim: every packet decodes to
    the SAME dict under both codecs, and re-encode under each codec is
    byte-stable."""
    for pkt in PACKETS:
        jb = wcodec.encode_packet(pkt, "json")
        bb = wcodec.encode_packet(pkt, "binary")
        assert bb[0] == wcodec.MAGIC and not wcodec.is_binary(jb)
        assert wcodec.decode_payload(jb) == pkt
        assert wcodec.decode_payload(bb) == pkt
        # decode -> re-encode is the identity on the bytes (both codecs)
        assert wcodec.encode_packet(wcodec.decode_payload(bb), "binary") == bb
        assert wcodec.encode_packet(wcodec.decode_payload(jb), "json") == jb


def test_binary_frames_interleave_with_json_on_one_stream():
    """Frames are self-describing: one connection may carry both codecs
    (debug JSON client against a binary cluster)."""
    dec = FrameDecoder()
    blob = b"".join(
        encode_frame(p, "binary" if i % 2 else "json")
        for i, p in enumerate(PACKETS))
    out = []
    for i in range(0, len(blob), 3):
        out.extend(dec.feed(blob[i:i + 3]))
    assert out == PACKETS


def test_binary_peek_header_reads_kind_src_msgid_without_body():
    pkt = {"src": "c9", "dest": "n1",
           "body": {"type": "txn", "msg_id": 41, "txn": [["r", 1, None]]}}
    payload = wcodec.encode_packet(pkt, "binary")
    assert wcodec.peek_header(payload) == (wcodec.KIND_TXN, "c9", 41)
    # JSON frames have no cheap header: peek declines, full decode path
    assert wcodec.peek_header(wcodec.encode_packet(pkt, "json")) is None
    # no msg_id -> None in the prelude
    p2 = wcodec.encode_packet(
        {"src": "n1", "dest": "n2", "body": {"type": "accord_batch",
                                             "msgs": []}}, "binary")
    assert wcodec.peek_header(p2) == (wcodec.KIND_BATCH, "n1", None)


def test_binary_unsupported_version_rejected():
    pkt = {"src": "a", "dest": "b", "body": {"type": "ping", "msg_id": 1}}
    payload = bytearray(wcodec.encode_packet(pkt, "binary"))
    payload[1] = 99   # a future format this build does not speak
    with pytest.raises(wcodec.CodecError):
        wcodec.decode_payload(bytes(payload))
    # ...and the frame decoder surfaces it as a stream error, not a hang
    dec = FrameDecoder()
    import struct
    with pytest.raises(ValueError):
        dec.feed(struct.pack(">I", len(payload)) + bytes(payload))


def test_binary_bigint_falls_back_to_json_per_frame():
    """An integer beyond msgpack's 64-bit range (arbitrary-precision
    timestamp words can exceed it in principle) must not fail the frame:
    the encoder falls back to JSON for THAT packet and the sniffing
    decoder takes it in stride."""
    pkt = {"src": "n1", "dest": "n2",
           "body": {"type": "accord_req", "msg_id": 1,
                    "payload": {"v": 1 << 80}}}
    payload = wcodec.encode_packet(pkt, "binary")
    assert not wcodec.is_binary(payload)   # JSON carried it
    assert wcodec.decode_payload(payload) == pkt


# The golden pins: hex bytes of the v1 binary encoding for a corpus
# covering all four datum kinds, a txn reply, a protocol request, a batch
# envelope, the control verbs and the codec_hello handshake.  An encoder
# change that alters ANY of these bytes without a version bump fails here
# (bump VERSION, keep decoding every older pin, and add new pins for the
# new version); a decoder change that mis-reads them fails the identity
# assertions.  Pins per version accumulate — that is the cross-version
# compatibility gate.
BINARY_PINS_V1 = [
    ("b10101026331026e31000000000000000383a474797065a374786ea66d73675f696403a374786e9493a6617070656e6401a2733093a6617070656e6402cf000000020000000593a6617070656e6403cb400400000000000093a6617070656e640481a4686173684d",
     {"src": "c1", "dest": "n1",
      "body": {"type": "txn", "msg_id": 3,
               "txn": [["append", 1, "s0"], ["append", 2, 8589934597],
                       ["append", 3, 2.5], ["append", 4, {"hash": 77}]]}}),
    ("b10100026e31026331000000000000000984a474797065a674786e5f6f6ba66d73675f696409ab696e5f7265706c795f746f03a374786e9193a172079301a27330cb4004000000000000",
     {"src": "n1", "dest": "c1",
      "body": {"type": "txn_ok", "msg_id": 9, "in_reply_to": 3,
               "txn": [["r", 7, [1, "s0", 2.5]]]}}),
    ("b10102026e31026e32000000000000001183a474797065aa6163636f72645f726571a66d73675f696411a77061796c6f616484a25f74a9507265416363657074a674786e5f696482a25f74a3544944a17693ce00010000ce0010001001a96d61785f65706f636801a96d696e5f65706f636801",
     {"src": "n1", "dest": "n2",
      "body": {"type": "accord_req", "msg_id": 17,
               "payload": {"_t": "PreAccept",
                           "txn_id": {"_t": "TID",
                                      "v": [65536, 1048592, 1]},
                           "max_epoch": 1, "min_epoch": 1}}}),
    ("b10105026e31026e32800000000000000082a474797065ac6163636f72645f6261746368a46d7367739283a474797065aa6163636f72645f726571a66d73675f696412a77061796c6f616482a25f74a25453a1769301020384a474797065aa6163636f72645f727370a66d73675f696413ab696e5f7265706c795f746f04a77061796c6f616482a25f74a342414ca17693050607",
     {"src": "n1", "dest": "n2",
      "body": {"type": "accord_batch",
               "msgs": [{"type": "accord_req", "msg_id": 18,
                         "payload": {"_t": "TS", "v": [1, 2, 3]}},
                        {"type": "accord_rsp", "msg_id": 19,
                         "in_reply_to": 4,
                         "payload": {"_t": "BAL", "v": [5, 6, 7]}}]}}),
    ("b10106026331026e31000000000000000182a474797065a470696e67a66d73675f696401",
     {"src": "c1", "dest": "n1", "body": {"type": "ping", "msg_id": 1}}),
    ("b10106026331026e31000000000000000282a474797065a57374617473a66d73675f696402",
     {"src": "c1", "dest": "n1", "body": {"type": "stats", "msg_id": 2}}),
    ("b10106026e3100800000000000000084a474797065ab636f6465635f68656c6c6fa466726f6da26e31a5636f646563a662696e617279a776657273696f6e01",
     {"src": "n1", "dest": "",
      "body": {"type": "codec_hello", "from": "n1", "codec": "binary",
               "version": 1}}),
    ("b101010363c3a9026e31fffffffffffffffb83a474797065a374786ea66d73675f6964fba374786e9193a172a4636cc3a9c0",
     {"src": "cé", "dest": "n1",
      "body": {"type": "txn", "msg_id": -5, "txn": [["r", "clé", None]]}}),
    # r17 elastic-serving frames: the operator verb, topology
    # propagation, sync-quorum gossip, the fetch side of the gossip,
    # one snapshot-stream chunk (pinned with the codec-agnostic base64
    # part representation — the binary codec may ALSO carry raw bytes,
    # covered by the chunk round-trip test below), and the epoch-bearing
    # codec_hello (the mixed-epoch interop handshake)
    ("b10106026331026e31000000000000000585a474797065ab7265636f6e666967757265a66d73675f696405a26f70a3616464a46e6f6465a26e34a461646472ae3132372e302e302e313a37303034",
     {"src": "c1", "dest": "n1",
      "body": {"type": "reconfigure", "msg_id": 5, "op": "add",
               "node": "n4", "addr": "127.0.0.1:7004"}}),
    ("b10106026e31026e32800000000000000082a474797065a8746f706f5f6e6577a8746f706f6c6f677984a565706f636802a6736861726473929400cd01f492020392020394cd01f4cd03e892030590a56e6f64657383a13293a26e31a93132372e302e302e31cd1b59a13393a26e32a93132372e302e302e31cd1b5aa13593a26e34a93132372e302e302e31cd1b5ca870726f706f736572a26e31",
     {"src": "n1", "dest": "n2",
      "body": {"type": "topo_new",
               "topology": {"epoch": 2,
                            "shards": [[0, 500, [2, 3], [2, 3]],
                                       [500, 1000, [3, 5], []]],
                            "nodes": {"2": ["n1", "127.0.0.1", 7001],
                                      "3": ["n2", "127.0.0.1", 7002],
                                      "5": ["n4", "127.0.0.1", 7004]},
                            "proposer": "n1"}}}),
    ("b10106026e32026e31800000000000000083a474797065aa65706f63685f73796e63a46e6f6465a26e32a565706f636802",
     {"src": "n2", "dest": "n1",
      "body": {"type": "epoch_sync", "node": "n2", "epoch": 2}}),
    ("b10106026e34026e31800000000000000083a474797065aa746f706f5f6665746368a46e6f6465a26e34a565706f636802",
     {"src": "n4", "dest": "n1",
      "body": {"type": "topo_fetch", "node": "n4", "epoch": 2}}),
    ("b10100026e31026e34800000000000000085a474797065ac6163636f72645f6368756e6ba3636964a46e312337a373657101a16e03a470617274b46332356863484e6f62335174596e6c305a584d3d",
     {"src": "n1", "dest": "n4",
      "body": {"type": "accord_chunk", "cid": "n1#7", "seq": 1, "n": 3,
               "part": "c25hcHNob3QtYnl0ZXM="}}),
    ("b10106026e3100800000000000000085a474797065ab636f6465635f68656c6c6fa466726f6da26e31a5636f646563a662696e617279a776657273696f6e01a565706f636803",
     {"src": "n1", "dest": "",
      "body": {"type": "codec_hello", "from": "n1", "codec": "binary",
               "version": 1, "epoch": 3}}),
]

ALL_BINARY_PINS = {1: BINARY_PINS_V1}


def test_binary_codec_golden_pins_freeze_the_format():
    assert set(ALL_BINARY_PINS) == set(wcodec.SUPPORTED_VERSIONS), \
        "every supported codec version must carry pins (and vice versa)"
    for version, pins in ALL_BINARY_PINS.items():
        for hex_bytes, pkt in pins:
            pinned = bytes.fromhex(hex_bytes)
            assert pinned[1] == version
            # decoder compatibility: every pinned frame of every
            # supported version decodes to the exact packet, forever
            assert wcodec.decode_payload(pinned) == pkt, \
                f"v{version} pin no longer decodes: {pkt}"
            # cross-codec identity: the JSON debug codec agrees
            assert wcodec.decode_payload(
                wcodec.encode_packet(pkt, "json")) == pkt
    # encoder freeze: the CURRENT version's pins are what the encoder
    # emits today — any byte change here is an unversioned format change
    for hex_bytes, pkt in ALL_BINARY_PINS[wcodec.VERSION]:
        assert wcodec.encode_packet(pkt, "binary").hex() == hex_bytes, \
            (f"binary encoding changed for {pkt} — bump codec.VERSION, "
             f"keep the old pins decoding, and pin the new bytes")


# ---------------------------------------------------------------------------
# reconnect backoff: capped exponential + deterministic jitter
# ---------------------------------------------------------------------------

def test_backoff_grows_and_caps():
    js = RandomSource(5)
    vals = [backoff_micros(a, js) for a in range(20)]
    # base doubles until the cap; jitter adds < base/2 on top
    assert vals[0] >= BACKOFF_BASE_MICROS
    assert vals[0] < BACKOFF_BASE_MICROS * 1.5
    for v in vals:
        assert v < BACKOFF_CAP_MICROS * 1.5
    assert max(vals) >= BACKOFF_CAP_MICROS


def test_backoff_deterministic_per_seed():
    a = [backoff_micros(i, RandomSource(9)) for i in range(8)]
    b = [backoff_micros(i, RandomSource(9)) for i in range(8)]
    c = [backoff_micros(i, RandomSource(10)) for i in range(8)]
    assert a == b
    assert a != c   # distinct streams desynchronize co-failed links


# ---------------------------------------------------------------------------
# admission gate: bounded budget + AIMD + ladder composition
# ---------------------------------------------------------------------------

def test_admission_hard_budget_bounds_inflight():
    g = AdmissionGate(max_inflight=4, min_budget=1)
    admits = [g.try_admit()[0] for _ in range(6)]
    assert admits == [True] * 4 + [False] * 2
    assert g.inflight == 4
    ok, reason, retry_ms = g.try_admit()
    assert not ok and reason == "inflight" and retry_ms >= 25
    g.release(1000)
    assert g.try_admit()[0]   # a freed slot admits again


def test_admission_release_never_goes_negative():
    g = AdmissionGate(max_inflight=2)
    g.try_admit()
    g.release(10)
    g.release(10)   # spurious double-release must not corrupt state
    assert g.inflight == 0
    assert all(g.try_admit()[0] for _ in range(2))


def test_admission_aimd_cuts_on_high_p99_and_recovers():
    # window == one adjust period so the recovery phase's fast samples
    # flush the overload samples out of the sliding p99 immediately
    g = AdmissionGate(max_inflight=32, target_p99_micros=1000, min_budget=2,
                      window=32)
    # drive completions far over target: budget shrinks multiplicatively
    for _ in range(3 * g.ADJUST_EVERY):
        ok, _, _ = g.try_admit()
        g.release(50_000)
    assert g.n_latency_cuts >= 3
    assert g.dyn_budget < 32
    cut = g.dyn_budget
    # now comfortably below target: budget recovers additively (+1/adjust)
    for _ in range(4 * g.ADJUST_EVERY):
        assert g.try_admit()[0]   # admit-release pairs: inflight 0 -> 1 -> 0
        g.release(100)
    assert g.dyn_budget > cut
    assert g.dyn_budget <= 32


def test_admission_budget_never_below_min():
    g = AdmissionGate(max_inflight=16, target_p99_micros=1, min_budget=3)
    for _ in range(20 * g.ADJUST_EVERY):
        if g.try_admit()[0]:
            g.release(10_000)
    assert g.effective_budget() >= 3
    assert g.try_admit()[0] or g.inflight >= 3


def test_admission_latency_shed_reason():
    g = AdmissionGate(max_inflight=32, target_p99_micros=1, min_budget=1)
    for _ in range(2 * g.ADJUST_EVERY):   # force cuts
        if g.try_admit()[0]:
            g.release(10_000)
    # fill the (cut) budget, then shed: the reason names the controller
    while g.try_admit()[0]:
        pass
    assert g.n_shed.get("latency", 0) >= 1
    assert g.stats()["shed"]["latency"] >= 1


def test_admission_quarantine_scales_budget_down():
    health = [1.0]
    g = AdmissionGate(max_inflight=8, min_budget=1,
                      device_health=lambda: health[0])
    assert g.effective_budget() == 8
    health[0] = 0.5   # half the stores quarantined -> half the budget
    assert g.effective_budget() == 4
    for _ in range(4):
        assert g.try_admit()[0]
    ok, reason, _ = g.try_admit()
    assert not ok and reason == "quarantine"
    health[0] = 1.0   # ladder restores -> budget restores
    assert g.effective_budget() == 8
    assert g.try_admit()[0]


def test_admission_unrecorded_release_frees_slot_without_teaching():
    """release(None) — the instant synchronous error paths — frees the
    slot but must NOT feed the AIMD latency window: poison traffic that
    fails in microseconds cannot argue the node is fast while genuine
    coordinations are slow."""
    g = AdmissionGate(max_inflight=8, target_p99_micros=1000, min_budget=1,
                      window=32)
    # genuine overload: window full of slow samples, budget cut
    for _ in range(2 * g.ADJUST_EVERY):
        g.try_admit()
        g.release(50_000)
    cut = g.dyn_budget
    assert cut < 8
    # a flood of instant failures frees slots but teaches nothing
    for _ in range(4 * g.ADJUST_EVERY):
        if g.try_admit()[0]:
            g.release(None, ok=False)
    assert g.dyn_budget == cut, "unrecorded releases moved the budget"
    assert g.inflight == 0
    assert g.sliding_p99() >= 50_000   # window still holds the truth


def test_admission_sliding_p99_reads_window():
    g = AdmissionGate(max_inflight=4, window=100)
    assert g.sliding_p99() is None
    for i in range(100):
        g.try_admit()
        g.release(i)
    assert 95 <= g.sliding_p99() <= 99


def test_admission_one_slow_completion_is_no_p99():
    """A p99 never rests on the single largest sample of its window: one
    txn over target among 32 cuts nothing, two do."""
    g = AdmissionGate(max_inflight=64, target_p99_micros=1_000_000)
    for i in range(g.ADJUST_EVERY):
        g.try_admit()
        g.release(1_500_000 if i == 7 else 600_000)
    assert g.n_latency_cuts == 0 and g.dyn_budget == 64
    for i in range(g.ADJUST_EVERY):
        g.try_admit()
        g.release(1_500_000 if i in (3, 20) else 600_000)
    assert g.n_latency_cuts == 1


def test_admission_a_cut_consumes_its_evidence():
    """The slow completions that caused a cut stay in the sliding window
    for hundreds of completions more; they are no reason to cut again, or a
    budget that does not bind drifts to its floor on one bad second."""
    g = AdmissionGate(max_inflight=64, target_p99_micros=1_000_000)
    for _ in range(g.ADJUST_EVERY):
        g.try_admit()
        g.release(2_000_000)
    assert g.n_latency_cuts == 1
    cut = g.dyn_budget
    for _ in range(8 * g.ADJUST_EVERY):
        g.try_admit()
        g.release(800_000)        # inside the hysteresis band: no move
    assert g.n_latency_cuts == 1 and g.dyn_budget == cut
    assert g.sliding_p99() == 2_000_000   # the read-out keeps the truth
    for _ in range(g.ADJUST_EVERY):
        g.try_admit()
        g.release(2_000_000)      # fresh evidence cuts again
    assert g.n_latency_cuts == 2


def test_span_phase_p99_is_clamped_by_its_window_not_by_the_lifetime():
    """One sample past a power of two, once in a node's life, must not make
    every later window whose p99 falls in that bucket read as the bucket's
    upper bound (1,048,575 us against a 1 s target)."""
    from accord_tpu.net.admission import SpanPhaseP99
    from accord_tpu.obs.metrics import MetricsRegistry
    m = MetricsRegistry()
    reader = SpanPhaseP99(m, root="txn")
    h = m.histogram("phase_micros", phase="read")
    for v in [1_200_000, 1_300_000] + [600_000] * 30:
        h.observe(v)
    assert reader.read() == 1_300_000      # its bucket's largest IN the
    for v in [700_000, 640_000] + [300_000] * 30:
        h.observe(v)
    assert reader.read() == 700_000        # window; not 1,048,575 at 1 s
    # the root is the gate's own to measure: left out where it is named
    root = m.histogram("phase_micros", phase="txn")
    for _ in range(32):
        root.observe(5_000_000)
        h.observe(100_000)
    assert reader.read() == 100_000
    assert SpanPhaseP99(m).read() == 5_000_000


def test_device_health_of_counts_quarantined_stores():
    class Dev:
        host_pinned = False
        _dev_quar_flushes = 0

    class Store:
        def __init__(self, dev):
            self.device = dev

    class Stores:
        pass

    class Node:
        command_stores = Stores()

    healthy, sick = Dev(), Dev()
    sick._dev_quar_flushes = 3
    Node.command_stores.stores = [Store(healthy), Store(sick)]
    assert device_health_of(Node()) == 0.5
    sick._dev_quar_flushes = 0
    assert device_health_of(Node()) == 1.0
    # host-mode stores (no device) count healthy
    Node.command_stores.stores = [Store(None)]

    class HostStore:
        device = None
    Node.command_stores.stores = [HostStore()]
    assert device_health_of(Node()) == 1.0


def test_overloaded_error_carries_retry_hint():
    exc = Overloaded(retry_after_ms=250, reason="latency")
    assert exc.retry_after_ms == 250
    assert exc.reason == "latency"


# ---------------------------------------------------------------------------
# socket faults: seedable, env-armed, deterministic
# ---------------------------------------------------------------------------

def test_socket_fault_env_spec_parse():
    armed = faults.arm_socket_faults_from_env(
        "conn_reset:0.25:7,slow_link:0.5:9")
    try:
        assert armed == {"conn_reset": 0.25, "slow_link": 0.5}
        assert faults.active_socket_faults() == armed
    finally:
        faults.clear_socket_faults()
    assert faults.active_socket_faults() == {}


def test_socket_fault_draws_deterministic():
    with faults.socket_fault("conn_reset", 0.3, RandomSource(21)):
        a = [faults.socket_fault_fires("conn_reset") for _ in range(64)]
    with faults.socket_fault("conn_reset", 0.3, RandomSource(21)):
        b = [faults.socket_fault_fires("conn_reset") for _ in range(64)]
    assert a == b
    assert any(a) and not all(a)
    # unarmed: no draws anywhere, never fires
    assert not faults.socket_fault_fires("conn_reset")


def test_socket_fault_delay_bounds():
    with faults.socket_fault("stalled_peer", 1.0, RandomSource(3)):
        for _ in range(16):
            d = faults.socket_fault_delay_micros("stalled_peer")
            assert 100_000 <= d < 600_000
    with faults.socket_fault("slow_link", 1.0, RandomSource(3)):
        for _ in range(16):
            assert 5_000 <= faults.socket_fault_delay_micros(
                "slow_link") < 60_000


def test_socket_fault_unknown_kind_rejected():
    with pytest.raises(ValueError):
        faults.inject_socket_fault("packet_gremlin", 0.5, RandomSource(1))


# ---------------------------------------------------------------------------
# golden frames over a REAL loopback socket: byte-identity through the
# kernel under partial reads and coalesced writes
# ---------------------------------------------------------------------------

def _loopback_roundtrip(frames, write_plan, codec="json"):
    """Echo ``frames`` (encoded bytes) through a real asyncio TCP loopback
    server using ``write_plan(blob) -> [chunk, ...]`` to segment the
    client->server stream; returns the decoded packets the server saw and
    the raw bytes the client got echoed back."""
    async def run():
        seen = []
        got = bytearray()
        done = asyncio.Event()
        want = sum(len(f) for f in frames)

        async def handle(reader, writer):
            dec = FrameDecoder()
            while True:
                chunk = await reader.read(7)   # tiny reads server-side too
                if not chunk:
                    break
                for pkt in dec.feed(chunk):
                    seen.append(pkt)
                    writer.write(encode_frame(pkt, codec))  # echo re-encoded
                    await writer.drain()
            writer.close()

        server = await asyncio.start_server(handle, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)

        async def read_back():
            while len(got) < want:
                chunk = await reader.read(65536)
                if not chunk:
                    break
                got.extend(chunk)
            done.set()

        task = asyncio.get_event_loop().create_task(read_back())
        for chunk in write_plan(b"".join(frames)):
            writer.write(chunk)
            await writer.drain()
        await asyncio.wait_for(done.wait(), 20)
        writer.close()
        server.close()
        await server.wait_closed()
        task.cancel()
        return seen, bytes(got)
    return asyncio.run(run())


def _golden_packets():
    """The golden frame corpus: Maelstrom client-boundary packets (all
    four datum kinds) + REAL inter-node protocol payloads captured from an
    in-process run through the full wire codec."""
    from accord_tpu import wire
    from accord_tpu.sim.cluster import Cluster
    from accord_tpu.sim.kvstore import KVDataStore, kv_txn
    from accord_tpu.sim.topology_factory import build_topology
    from accord_tpu.sim import cluster as cluster_mod

    pkts = list(PACKETS)
    topology = build_topology(1, (1, 2, 3), 3, 4)
    cluster = Cluster(topology=topology, seed=3,
                      data_store_factory=KVDataStore)
    captured = []
    orig = cluster_mod.NodeSink.send_with_callback

    def tap(self, to, request, cb):
        captured.append((self.node_id, to, request))
        return orig(self, to, request, cb)

    cluster_mod.NodeSink.send_with_callback = tap
    try:
        for i in range(3):
            cluster.nodes[1 + (i % 3)].coordinate(
                kv_txn([i * 10, (i + 1) * 10], {i * 10: (i,)})).begin(
                lambda r, f: None)
        cluster.run_until_quiescent()
    finally:
        cluster_mod.NodeSink.send_with_callback = orig
    assert len(captured) >= 10
    for n, (src, dst, req) in enumerate(captured[:24]):
        pkts.append({"src": f"n{src}", "dest": f"n{dst}",
                     "body": {"type": "accord_req", "msg_id": 1000 + n,
                              "payload": wire.encode(req)}})
    # r16: batch envelopes (real protocol payloads riding one frame) and
    # the codec_hello handshake join the corpus — the acceptance requires
    # envelopes round-tripping byte-identical over a real socket
    bodies = [p["body"] for p in pkts[-6:]]
    pkts.append({"src": "n1", "dest": "n2",
                 "body": {"type": "accord_batch", "msgs": bodies}})
    from accord_tpu.net.codec import hello_body
    pkts.append({"src": "n1", "dest": "n2",
                 "body": hello_body("n1", "binary")})
    return pkts


@pytest.mark.parametrize("codec", ["json", "binary"])
def test_golden_frames_roundtrip_loopback_byte_identical(codec):
    """Every golden wire frame (incl. batch envelopes + codec_hello)
    crosses a real kernel socket and comes back BYTE-IDENTICAL under BOTH
    codecs, under three segmentations: one-shot coalesced write, per-frame
    writes, and a deterministic shredder (partial frames across write
    boundaries).  The server decodes with 7-byte reads (forced partial
    reads) and re-encodes — so byte-identity also proves decode ->
    re-encode is the identity on every frame."""
    pkts = _golden_packets()
    frames = [encode_frame(p, codec) for p in pkts]
    want = b"".join(frames)

    def coalesced(blob):
        return [blob]

    def per_frame(_blob):
        return list(frames)

    def shredded(blob):
        rs = RandomSource(99)
        out, i = [], 0
        while i < len(blob):
            n = 1 + rs.next_int(23)
            out.append(blob[i:i + n])
            i += n
        return out

    for plan in (coalesced, per_frame, shredded):
        seen, got = _loopback_roundtrip(frames, plan, codec)
        assert seen == pkts, f"decode mismatch under {plan.__name__}"
        assert got == want, f"byte mismatch under {plan.__name__}"


# ---------------------------------------------------------------------------
# cross-request fused fan-out (r16): the batch envelope is protocol-
# invisible, the server batches per peer per tick, the link coalesces
# writes, and sheds decide pre-decode
# ---------------------------------------------------------------------------

def test_batch_envelope_protocol_invisible():
    """N bodies delivered in one accord_batch envelope must drive the
    EXACT same per-op protocol path as N separate frames: same emitted
    packets, same order, same replies."""
    from accord_tpu import api
    from accord_tpu.maelstrom.node import MaelstromProcess

    class Scheduler(api.Scheduler):
        def __init__(self):
            self.q = []

        def now(self, run):
            self.q.append(run)

        def once(self, delay, run):
            class S(api.Scheduled):
                cancelled = False

                def cancel(self):
                    self.cancelled = True

                def is_cancelled(self):
                    return self.cancelled
            return S()

        def recurring(self, interval, run):
            return self.once(interval, run)

        def drain(self):
            while self.q:
                self.q.pop(0)()

    def mk():
        sent = []
        sched = Scheduler()
        proc = MaelstromProcess(
            emit=lambda dest, body: sent.append((dest, body)),
            scheduler=sched, now_micros=lambda: 0,
            num_stores=2, device_mode=False, durability=False)
        proc.handle({"src": "boot", "dest": "n1",
                     "body": {"type": "init", "msg_id": 0, "node_id": "n1",
                              "node_ids": ["n1", "n2", "n3"]}})
        sched.drain()
        del sent[:]   # drop init_ok
        return proc, sched, sent

    txns = [{"type": "txn", "msg_id": 10 + i,
             "txn": [["append", 7 + i, i], ["r", 7 + i, None]]}
            for i in range(4)]
    solo_proc, solo_sched, solo_sent = mk()
    for body in txns:
        solo_proc.handle({"src": "c1", "dest": "n1", "body": body})
        solo_sched.drain()
    batch_proc, batch_sched, batch_sent = mk()
    batch_proc.handle({"src": "c1", "dest": "n1",
                       "body": {"type": "accord_batch", "msgs": txns}})
    batch_sched.drain()
    assert solo_sent == batch_sent, \
        "the envelope changed what the protocol emitted"
    assert len(batch_sent) > 0   # PreAccepts actually fanned out


def test_server_batches_peer_fanout_per_tick():
    """Bodies emitted to one peer within one event-loop tick leave as ONE
    accord_batch frame; a lone body stays a plain frame (no envelope
    overhead when there is nothing to share)."""
    from accord_tpu.net.server import NodeServer

    class FakeLink:
        def __init__(self):
            self.frames = []

        def send(self, frame):
            self.frames.append(frame)

    async def run():
        server = NodeServer("n1", "127.0.0.1", 0, {"n2": ("h", 1)})
        server.loop = asyncio.get_event_loop()
        link = FakeLink()
        server.links = {"n2": link}
        for i in range(3):
            server._emit("n2", {"type": "accord_req", "msg_id": i,
                                "payload": i})
        await asyncio.sleep(0)   # let the call_soon flush run
        server._emit("n2", {"type": "accord_req", "msg_id": 9,
                            "payload": 9})
        await asyncio.sleep(0)
        return server, link

    server, link = asyncio.run(run())
    assert len(link.frames) == 2
    dec = FrameDecoder()
    first, second = dec.feed(b"".join(link.frames))
    assert first["body"]["type"] == "accord_batch"
    assert [m["msg_id"] for m in first["body"]["msgs"]] == [0, 1, 2]
    assert second["body"]["msg_id"] == 9   # lone body: no envelope
    assert server.n_batched_fanouts == 1
    assert server.n_batched_ops == 3
    assert server.batch_sizes == {3: 1, 1: 1}
    assert server.batch_occupancy_p50() in (1, 3)


def test_fast_shed_decides_before_body_decode():
    """Under overload a binary txn frame is shed from its fixed-offset
    header alone.  Proof: the frame's BODY bytes are deliberately invalid
    msgpack — any attempt to decode them would raise — yet the shed reply
    still goes out, Overloaded, correlated to the right msg_id."""
    from accord_tpu.net.server import NodeServer

    class Gate:
        def __init__(self):
            self.inflight = 8
            self.sheds = 0

        def effective_budget(self):
            return 8

        def try_admit(self):
            self.sheds += 1
            return False, "inflight", 50

    class Proc:
        journal = None

        def __init__(self, server):
            self.server = server
            self._client_msg_id = 0

        def _reply_client(self, dest, in_reply_to, body):
            self._client_msg_id += 1
            body = dict(body)
            body["msg_id"] = self._client_msg_id
            body["in_reply_to"] = in_reply_to
            self.server._emit(dest, body)

    class W:
        class transport:
            @staticmethod
            def get_write_buffer_size():
                return 0

        written = []

        def write(self, data):
            W.written.append(data)

    async def run():
        server = NodeServer("n1", "127.0.0.1", 0, {})
        server.loop = asyncio.get_event_loop()
        server.gate = Gate()
        server.proc = Proc(server)
        # valid v1 prelude for a txn from c7 msg_id 33, then garbage that
        # no msgpack decoder would accept
        good = wcodec.encode_packet(
            {"src": "c7", "dest": "n1",
             "body": {"type": "txn", "msg_id": 33, "txn": []}}, "binary")
        # prelude = magic+ver+kind, len+src, len+dest, 8-byte msg_id
        body_off = 3 + 1 + len(b"c7") + 1 + len(b"n1") + 8
        poisoned = good[:body_off] + b"\xc1\xc1\xc1\xc1"   # 0xc1: never
        #                                                    valid msgpack
        w = W()
        server._on_payload(poisoned, w)
        await asyncio.sleep(0)   # tick flush for the client write
        return server, w

    server, w = asyncio.run(run())
    assert server.n_fast_sheds == 1
    assert server.gate.sheds == 1
    assert len(W.written) == 1
    reply = FrameDecoder().feed(W.written[0])[0]
    assert reply["body"]["overloaded"] is True
    assert reply["body"]["in_reply_to"] == 33
    assert reply["dest"] == "c7"


def test_peer_link_coalesces_queued_frames_into_one_write():
    """Frames queued on a PeerLink while it dials leave in ONE joined
    write once connected — and every frame arrives intact."""
    from accord_tpu.net.transport import PeerLink

    async def run():
        reads = []
        got = asyncio.Event()

        async def handle(reader, writer):
            while True:
                chunk = await reader.read(65536)
                if not chunk:
                    break
                reads.append(chunk)
                if sum(len(c) for c in reads) >= want:
                    got.set()
            writer.close()   # or wait_closed() below waits for ever (3.12)

        server = await asyncio.start_server(handle, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        frames = [encode_frame({"src": "a", "dest": "b",
                                "body": {"type": "accord_req", "msg_id": i,
                                         "payload": "x" * 50}}, "binary")
                  for i in range(6)]
        want = sum(len(f) for f in frames)
        link = PeerLink("a", "b", "127.0.0.1", port, RandomSource(3),
                        linger_micros=0)
        for f in frames:
            link.send(f)   # queued BEFORE the link ever connects
        link.start()
        await asyncio.wait_for(got.wait(), 10)
        await link.close()
        server.close()
        await server.wait_closed()
        return frames, reads, link

    frames, reads, link = asyncio.run(run())
    dec = FrameDecoder()
    out = []
    for chunk in reads:
        out.extend(dec.feed(chunk))
    assert [p["body"]["msg_id"] for p in out] == list(range(6))
    assert link.n_sent == 6
    assert link.n_writes < 6, "no write coalescing happened"
    assert link.n_frames_coalesced == 6 - link.n_writes
    assert link.bytes_tx == sum(len(f) for f in frames)


def test_coalesce_window_priced_not_thresholded():
    from accord_tpu.net.transport import (COALESCE_MAX_MICROS,
                                          coalesce_window_micros,
                                          probe_write_micros)
    w = coalesce_window_micros()
    assert 0 <= w <= COALESCE_MAX_MICROS
    assert probe_write_micros() >= 1
    import os
    os.environ["ACCORD_TPU_COALESCE_US"] = "123"
    try:
        assert coalesce_window_micros() == 123
    finally:
        del os.environ["ACCORD_TPU_COALESCE_US"]


@pytest.mark.parametrize("unread_reply_bytes", [0, 64 << 20])
def test_frame_server_close_does_not_wait_for_its_peers(unread_reply_bytes):
    """A client that sent a frame and never closes — and, in the second
    case, never reads the reply either, so the closing transport keeps
    unsent bytes — cannot hold FrameServer.close()."""
    from accord_tpu.net.transport import FrameServer

    async def run():
        seen = []
        got = asyncio.Event()

        def on_payload(payload, writer):
            seen.append(payload)
            if unread_reply_bytes:
                writer.write(b"\0" * unread_reply_bytes)
            got.set()

        server = FrameServer("127.0.0.1", 0, on_payload=on_payload)
        await server.start()
        port = server._server.sockets[0].getsockname()[1]
        _reader, writer = await asyncio.open_connection("127.0.0.1", port)
        frame = encode_frame(PACKETS[1], "binary")
        writer.write(frame)
        await writer.drain()
        await asyncio.wait_for(got.wait(), 10)
        t0 = asyncio.get_event_loop().time()
        await asyncio.wait_for(server.close(), 5.0)
        took = asyncio.get_event_loop().time() - t0
        writer.close()
        return seen, took, server

    seen, took, server = asyncio.run(run())
    assert len(seen) == 1
    assert took < 2.0
    assert not server._writers


@pytest.mark.parametrize("handler_is_reading", [True, False])
def test_frame_server_delivers_nothing_after_stop(handler_is_reading):
    """Bytes that reached a connection's buffer before stop() — with its
    handler already waiting in read(), or accepted and not yet started —
    are dropped: no frame reaches the callbacks after stop() returned."""
    from accord_tpu.net.transport import FrameServer

    class Writer:
        closed = False

        def close(self):
            self.closed = True

    async def run():
        seen = []
        server = FrameServer("127.0.0.1", 0,
                             on_payload=lambda p, w: seen.append(p))
        reader, writer = asyncio.StreamReader(), Writer()
        task = asyncio.get_event_loop().create_task(
            server._handle(reader, writer))
        if handler_is_reading:
            await asyncio.sleep(0)
        reader.feed_data(encode_frame(PACKETS[1], "binary"))
        server.stop()
        await asyncio.wait_for(task, 5.0)
        return seen, writer, server

    seen, writer, server = asyncio.run(run())
    assert seen == []
    assert writer.closed and not server._writers


def test_node_close_flushes_journal_before_it_waits_on_a_socket(tmp_path):
    """A NodeServer closed while a client still holds its connection has
    every appended record on disk BEFORE it waits for any transport, its
    outbound links are down before that flush (what a timer does after it
    cannot leave the node), and its close returns."""
    import gc
    from accord_tpu.journal import segment
    from accord_tpu.net.server import NodeServer

    def on_disk():
        n = nbytes = 0
        for path in tmp_path.glob("wal-*.seg"):
            for payload in segment.scan(str(path))[1]:
                n += 1
                nbytes += len(payload)
        return n, nbytes

    async def run():
        server = NodeServer("n1", "127.0.0.1", 0, {"n2": ("127.0.0.1", 1)},
                            members=["n1"], durability=False,
                            journal_dir=str(tmp_path))
        await server.start()
        port = server.frame_server._server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(encode_frame(
            {"src": "c1", "dest": "n1",
             "body": {"type": "txn", "msg_id": 1,
                      "txn": [["append", 7, 1]]}}, "binary"))
        await writer.drain()
        dec = FrameDecoder()
        replies = []
        while not replies:
            replies = dec.feed(await asyncio.wait_for(reader.read(65536), 20))
        at_wait, links_down_at_flush = [], []
        wait_on_sockets = server.frame_server.close
        final_flush = server.journal.close

        async def spy_wait():
            at_wait.append(on_disk())
            await wait_on_sockets()

        def spy_flush():
            links_down_at_flush.append(
                all(link._task.done() for link in server.links.values()))
            final_flush()
        server.frame_server.close = spy_wait
        server.journal.close = spy_flush
        await asyncio.wait_for(server.close(), 5.0)
        writer.close()
        return (replies[0], at_wait, links_down_at_flush,
                server.journal.wal.stats())

    thresholds = gc.get_threshold()
    try:
        reply, at_wait, links_down_at_flush, wal = asyncio.run(run())
    finally:
        gc.unfreeze()   # NodeServer.start() retunes the collector
        gc.set_threshold(*thresholds)
    assert reply["body"]["type"] == "txn_ok"
    assert links_down_at_flush == [True]
    assert wal["appended"] > 0 and wal["durable_seq"] == wal["tail_seq"]
    assert at_wait == [(wal["appended"], wal["bytes"])]
    assert on_disk() == (wal["appended"], wal["bytes"])


# ---------------------------------------------------------------------------
# the real cluster: 2-process loopback smoke (tier-1), kill-9 recovery,
# and the slow overload sweep
# ---------------------------------------------------------------------------

def test_tcp_cluster_smoke_two_nodes():
    """Tier-1: 2 OS processes on loopback TCP (binary codec default), 100
    client txns with retry-with-backoff, tight sink timeouts.  Full
    success, zero duplicate client replies, both nodes alive, and the r16
    serving counters live (wire bytes counted; fan-out batching active
    under concurrency)."""
    from accord_tpu.net.harness import run_smoke
    result = run_smoke(n_txns=100, n_nodes=2)
    assert result["ok"] == 100
    assert result["duplicate_replies"] == 0
    assert all(result["alive"].values())
    net = result["net"]
    assert net["wire_bytes_tx"] > 0 and net["wire_bytes_rx"] > 0
    assert net["batched_fanouts"] > 0, \
        "concurrent txns never shared a fan-out envelope"
    assert net["frames_coalesced"] > 0, \
        "no two frames ever shared a link write"


def test_tcp_cluster_smoke_json_debug_codec():
    """The JSON debug codec stays a first-class citizen: same smoke, same
    contract, --wire-codec json end to end."""
    from accord_tpu.net.harness import run_smoke
    result = run_smoke(n_txns=40, n_nodes=2, wire_codec="json")
    assert result["ok"] == 40
    assert result["duplicate_replies"] == 0
    assert all(result["alive"].values())


def test_kill9_recovery_and_rejoin():
    """Kill -9 one node of three mid-run: the survivors keep committing
    (quorum 2/3), no duplicate client replies ever, and the restarted
    node rejoins through the peers' reconnect backoff."""
    from accord_tpu.net.client import ClusterClient
    from accord_tpu.net.harness import (ServeCluster, _mk_ops, wait_ready)
    import random

    cluster = ServeCluster(n_nodes=3, request_timeout_ms=800)
    cluster.spawn_all()
    try:
        async def scenario():
            client = ClusterClient(cluster.addrs, timeout=8.0)
            try:
                await wait_ready(cluster, client)
                rng = random.Random(3)
                counter = [0]

                async def burst(n, nodes):
                    ok = 0
                    for i in range(n):
                        await client.submit_retry(
                            _mk_ops(rng, counter, 16), retries=12,
                            timeout=6.0, node=nodes[i % len(nodes)])
                        ok += 1
                    return ok

                # phase 1: all three nodes serving
                assert await burst(12, cluster.names) == 12
                # phase 2: kill -9 n2 mid-run; drive the survivors
                cluster.kill9("n2")
                assert await burst(12, ["n1", "n3"]) == 12
                assert cluster.procs["n2"].poll() is not None
                # phase 3: restart n2 (same name/port, fresh state) and
                # wait for it to serve again — the client re-dials, the
                # peers' outbound links reconnect through their backoff
                cluster.spawn("n2")
                await wait_ready(cluster, client)
                assert (await client.ping("n2"))["type"] == "pong"
                assert await burst(8, ["n1", "n3"]) == 8
                # peers reconnected to the restarted node
                reconnects = 0
                for name in ("n1", "n3"):
                    s = await client.stats(name)
                    link = s["links"]["n2"]
                    assert link["connected"], s["links"]
                    reconnects += link["reconnects"]
                assert reconnects >= 2, "peers never re-dialed n2"
                # the at-most-once contract held through kill+reconnect
                assert client.duplicate_replies() == 0
                return True
            finally:
                await client.close()

        assert asyncio.run(scenario())
        alive = cluster.alive()
        assert alive == {"n1": True, "n2": True, "n3": True}, alive
    finally:
        cluster.shutdown()


def test_malformed_txns_do_not_leak_admission_slots():
    """A txn that blows up AFTER admission (malformed op shape -> handler
    exception; unsupported verb -> code-10 error) must release its slot:
    admit_max such packets would otherwise wedge the node at 100% shed
    forever.  One node, budget 4, 3x-budget poison, then service must
    still work."""
    import asyncio as aio
    from accord_tpu.net.client import ClusterClient, TxnFailed
    from accord_tpu.net.harness import ServeCluster, wait_ready

    cluster = ServeCluster(n_nodes=1, admit_max=4, request_timeout_ms=800)
    cluster.spawn_all()
    try:
        async def scenario():
            client = ClusterClient(cluster.addrs, timeout=6.0)
            try:
                await wait_ready(cluster, client)
                conn = client.conns["n1"]
                for i in range(12):   # 3x the whole budget
                    if i % 2 == 0:
                        # crashes in the handler after admit: no reply
                        try:
                            await conn.request(
                                {"type": "txn", "txn": [["append"]]},
                                client.next_msg_id(), timeout=0.5)
                        except aio.TimeoutError:
                            pass
                    else:
                        # unsupported verb: explicit code-10 error reply
                        try:
                            await client.submit([["cas", 1, 2]])
                        except TxnFailed:
                            pass
                # all 12 slots must have been released: normal txns fit
                # the budget of 4 again (an Overloaded here = the leak)
                for _ in range(6):
                    body = await client.submit([["append", 3, 1]])
                    assert body["type"] == "txn_ok"
                stats = await client.stats("n1")
                adm = stats["admission"]
                assert adm["inflight"] == 0, adm
                return True
            finally:
                await client.close()

        assert aio.run(scenario())
        assert all(cluster.alive().values())
    finally:
        cluster.shutdown()


def test_kill9_restart_with_journal_recovers_state():
    """The r13 durability contract end to end, now under r16 batching:
    kill -9 a node mid-load — mid-coalesced-batch, since concurrent txns
    share fan-out envelopes and link writes by construction — restart it
    with the same --journal-dir: it recovers its pre-crash command state
    (WAL replay), answers a duplicate of an already-answered request from
    the journaled at-most-once table (the SAME reply, no re-coordination,
    the append lands exactly once), and zero duplicate client replies are
    ever observed."""
    import random
    import tempfile

    from accord_tpu.net.client import ClusterClient
    from accord_tpu.net.harness import ServeCluster, _mk_ops, wait_ready

    cluster = ServeCluster(n_nodes=3, request_timeout_ms=800,
                           journal_root=tempfile.mkdtemp(prefix="accord_jr_"))
    cluster.spawn_all()
    try:
        async def scenario():
            client = ClusterClient(cluster.addrs, timeout=8.0,
                                   codec="binary")
            try:
                await wait_ready(cluster, client)
                rng = random.Random(5)
                counter = [0]

                async def burst(n, nodes, width=4):
                    # CONCURRENT submits: same-tick txns share fan-out
                    # envelopes and coalesced writes, so the kill below
                    # lands mid-batch, not between lone frames
                    sem = asyncio.Semaphore(width)

                    async def one(i):
                        async with sem:
                            await client.submit_retry(
                                _mk_ops(rng, counter, 16), retries=12,
                                timeout=6.0, node=nodes[i % len(nodes)])
                    await asyncio.gather(*(one(i) for i in range(n)))

                # phase 1: journaled load through every node
                await burst(10, cluster.names)
                # the batching machinery is demonstrably active on the
                # node about to die (its journaled replies ride
                # coalesced writes)
                s = await client.stats("n2")
                assert s["wire_codec"] == "binary"
                assert s["batching"]["batched_fanouts"] > 0 \
                    or s["frames_coalesced"] > 0, s["batching"]
                # one append with a pinned msg_id so the SAME request can
                # be replayed across the death
                ops = [["append", 7, 424242], ["r", 7, None]]
                mid = client.next_msg_id()
                conn = client.conns["n2"]
                first = await conn.request({"type": "txn", "txn": ops},
                                           mid, timeout=6.0)
                assert first["type"] == "txn_ok", first
                # duplicate BEFORE the crash: the dedupe table answers
                dup = await conn.request({"type": "txn", "txn": ops},
                                         mid, timeout=6.0)
                assert dup["txn"] == first["txn"]
                s = await client.stats("n2")
                assert s["journal"]["registers"] > 0, s["journal"]
                assert s["journal"]["replied"] > 0
                # phase 2: kill -9 mid-run; survivors keep committing
                cluster.kill9("n2")
                await burst(6, ["n1", "n3"])
                # phase 3: restart with the SAME journal dir
                cluster.spawn("n2")
                await wait_ready(cluster, client)
                s = await client.stats("n2")
                jr = s["journal"]["replay"]
                assert jr["replayed"] > 0 or jr["snapshot_loaded"], jr
                assert s["journal"]["registers"] > 0, \
                    "pre-crash command state was not reconstructed"
                assert s["journal"]["replied"] > 0, \
                    "the at-most-once reply table did not survive"
                # duplicate AFTER the restart: the recovered table still
                # answers with the SAME reply — no re-coordination
                dup2 = await client.conns["n2"].request(
                    {"type": "txn", "txn": ops}, mid, timeout=6.0)
                assert dup2["txn"] == first["txn"]
                # ...and the append landed exactly once across
                # kill + restart + three deliveries of the same request
                # (retry: the freshly-rejoined node may still be
                # re-establishing its peer links)
                read = await client.submit_retry([["r", 7, None]],
                                                 node="n2", retries=12,
                                                 timeout=6.0)
                vals = read["txn"][0][2]
                assert vals.count(424242) == 1, vals
                # the restarted node serves fresh traffic
                await burst(6, cluster.names)
                assert client.duplicate_replies() == 0
                return True
            finally:
                await client.close()

        assert asyncio.run(scenario())
        assert all(cluster.alive().values())
    finally:
        cluster.shutdown()


def test_sink_tombstoned_heap_compacts_and_peer_death_times_out():
    """r13 sink fix: requests resolved long before their deadline must
    not leave tombstones occupying the heap for the remaining horizon
    (slow-read entries linger 10x the base timeout), and pending
    callbacks to a peer that dies mid-request must still resolve as
    Timeouts — compaction may never lose a live entry."""
    from accord_tpu.coordinate.errors import Timeout
    from accord_tpu.maelstrom.node import MaelstromSink
    from accord_tpu.primitives.timestamp import Timestamp

    class Proc:
        request_timeout_micros = 1_000_000

        def __init__(self):
            self.t = 0
            self.sent = []

        def now_micros(self):
            return self.t

        def emit_packet(self, to, body):
            self.sent.append((to, body))

    class CB:
        def __init__(self):
            self.ok = []
            self.fail = []

        def on_success(self, frm, reply):
            self.ok.append(frm)

        def on_failure(self, frm, exc):
            self.fail.append(exc)

    class Reply:
        def is_final(self):
            return True

    proc = Proc()
    sink = MaelstromSink(proc)
    req = Timestamp.from_values(1, 1, 1)   # any wire-encodable request
    # a burst of requests all resolved immediately: pre-fix, 500 dead
    # [deadline, tie, None] entries sit heaped for the full 1s horizon
    for i in range(500):
        sink.send_with_callback(2, req, CB())
        sink.on_response(2, i + 1, Reply())
    assert len(sink.pending) == 0
    assert len(sink._timeouts) <= 64, \
        f"{len(sink._timeouts)} tombstones leaked past the compaction bound"
    # now requests to a peer that dies (never replies): compaction must
    # have kept the machinery intact — they resolve as timeouts at the
    # horizon, not never
    cbs = [CB() for _ in range(5)]
    for cb in cbs:
        sink.send_with_callback(3, req, cb)
    proc.t = 2_000_000
    sink.sweep()
    for cb in cbs:
        assert len(cb.fail) == 1 and isinstance(cb.fail[0], Timeout)
    assert len(sink.pending) == 0
    # interleaved resolve/expire: tombstone accounting stays exact
    for i in range(200):
        sink.send_with_callback(2, req, CB())
        if i % 2 == 0:
            sink.on_response(2, sink._next_msg_id, Reply())
    proc.t = 4_000_000
    sink.sweep()
    assert len(sink.pending) == 0
    assert len(sink._timeouts) <= 64


def test_sink_recovery_callbacks_tombstone_and_time_out():
    """r14 satellite: the r07/r13 tombstone contract extended to the
    RECOVERY callbacks.  WaitOnCommit is a slow-read request (10x timeout
    horizon): a recovery that resolves its waits early must not leave
    tombstones heaped for the 10x horizon, and recovery requests
    (BeginRecovery fan-out, WaitOnCommit) pending against a dead peer must
    every one resolve as Timeout at their horizon — compaction may never
    lose a live recovery callback."""
    from accord_tpu.coordinate.errors import Timeout
    from accord_tpu.maelstrom.node import MaelstromSink
    from accord_tpu.messages.begin_recovery import BeginRecovery, WaitOnCommit
    from accord_tpu.primitives.keys import Route, RoutingKeys
    from accord_tpu.primitives.timestamp import (Ballot, Domain, TxnId,
                                                 TxnKind)

    class Proc:
        request_timeout_micros = 1_000_000

        def __init__(self):
            self.t = 0

        def now_micros(self):
            return self.t

        def emit_packet(self, to, body):
            pass

    class CB:
        def __init__(self):
            self.fail = []

        def on_success(self, frm, reply):
            pass

        def on_failure(self, frm, exc):
            self.fail.append(exc)

    class Reply:
        def is_final(self):
            return True

    txn_id = TxnId.create(1, 100, TxnKind.Write, Domain.Key, 1)
    wait = WaitOnCommit(txn_id, RoutingKeys.of(5))
    assert getattr(wait, "is_slow_read", False), \
        "WaitOnCommit lost its slow-read marking"
    proc = Proc()
    sink = MaelstromSink(proc)
    # a recovery storm's worth of WaitOnCommits all resolved promptly:
    # pre-compaction these tombstones would sit heaped for the 10x horizon
    for i in range(300):
        sink.send_with_callback(2, wait, CB())
        sink.on_response(2, i + 1, Reply())
    assert len(sink.pending) == 0
    assert len(sink._timeouts) <= 64, \
        f"{len(sink._timeouts)} slow-read tombstones leaked"
    # recovery requests against a peer that died mid-recovery: the
    # BeginRecovery fan-out times out at the base horizon, the
    # WaitOnCommit at its 10x horizon — neither lost by compaction
    from accord_tpu.sim.kvstore import kv_txn
    begin = BeginRecovery(txn_id, kv_txn([5], {}),
                          Route.full(5, RoutingKeys.of(5)), Ballot.ZERO)
    fast_cbs = [CB() for _ in range(4)]
    slow_cbs = [CB() for _ in range(4)]
    for cb in fast_cbs:
        sink.send_with_callback(3, begin, cb)
    for cb in slow_cbs:
        sink.send_with_callback(3, wait, cb)
    proc.t = 2_000_000          # past base horizon, before the 10x one
    sink.sweep()
    for cb in fast_cbs:
        assert len(cb.fail) == 1 and isinstance(cb.fail[0], Timeout)
    for cb in slow_cbs:
        assert cb.fail == [], "slow-read timed out at the base horizon"
    proc.t = 11_000_000         # past the 10x slow-read horizon
    sink.sweep()
    for cb in slow_cbs:
        assert len(cb.fail) == 1 and isinstance(cb.fail[0], Timeout)
    assert len(sink.pending) == 0
    assert len(sink._timeouts) <= 64


@pytest.mark.slow
def test_overload_sheds_instead_of_collapsing():
    """The graceful-overload assertion (slow tier): at ~3x saturation the
    cluster sheds explicitly, admitted p99 stays bounded, goodput holds,
    nobody dies."""
    from accord_tpu.net.client import ClusterClient
    from accord_tpu.net.harness import (ServeCluster, open_loop,
                                        saturation_probe, wait_ready)

    cluster = ServeCluster(n_nodes=3, admit_max=16, target_p99_ms=2500,
                           request_timeout_ms=3000)
    cluster.spawn_all()
    try:
        async def scenario():
            client = ClusterClient(cluster.addrs, timeout=10.0)
            try:
                await wait_ready(cluster, client, timeout=90.0)
                await saturation_probe(client, workers=4, duration=1.0,
                                       seed=3)   # warm
                probe = await saturation_probe(client, workers=60,
                                               duration=4.0, seed=42)
                at1 = await open_loop(client, rate=probe["rate"],
                                      duration=6.0, seed=17)
                at3 = await open_loop(client, rate=3 * probe["rate"],
                                      duration=6.0, seed=18)
                return probe, at1, at3, client.duplicate_replies()
            finally:
                await client.close()

        probe, at1, at3, dups = asyncio.run(scenario())
        assert at3.shed > 0, "no explicit sheds at 3x saturation"
        sat_p99 = max(x for x in (probe["p99_ms"], at1.latency_ms(0.99))
                      if x is not None)
        assert at3.latency_ms(0.99) <= 2.0 * sat_p99, \
            (at3.latency_ms(0.99), sat_p99)
        assert at3.goodput >= 0.8 * at1.goodput, (at3.goodput, at1.goodput)
        assert dups == 0
        assert all(cluster.alive().values())
    finally:
        cluster.shutdown()


@pytest.mark.slow
@pytest.mark.faults
@pytest.mark.parametrize("codec", ["json", "binary"])
@pytest.mark.parametrize("spec", ["conn_reset:0.04:5", "stalled_peer:0.03:5",
                                  "slow_link:0.25:5"])
def test_smoke_under_socket_faults(spec, codec):
    """Each socket-fault class x each wire codec, armed in every node
    process: the cluster recovers every txn (sink timeouts + reconnect
    backoff own recovery) with zero duplicate client replies — under
    conn_reset that includes a half-written coalesced batch dying on the
    wire: the at-most-once contract means the lost ops time out and
    retry, never replay.  tools/run_fault_matrix.sh runs the same legs
    with post-mortem dumps."""
    from accord_tpu.net.harness import run_smoke
    result = run_smoke(n_txns=60, n_nodes=2, net_faults=spec,
                       wire_codec=codec)
    assert result["ok"] == 60
    assert result["duplicate_replies"] == 0
    assert all(result["alive"].values())
