"""Durable journal (r13): segmented WAL, group commit, snapshots,
crash-point recovery byte-identity, disk faults, reply dedupe, and the
span-fed admission signal.

The crash contract under test everywhere: *recovery equals the replay of
the surviving prefix* — a kill -9 (modelled as a byte-level truncation of
the WAL at ANY offset, mid-frame included) may cost the un-fsynced tail,
but the recovered journal state must be byte-identical (canonical JSON)
to an in-memory replay of exactly the records that survived, and the
commands it reconstructs must match the live journal's reconstruction.
"""

import json
import os
import shutil

import pytest

from accord_tpu.journal import DurableJournal, JournaledKVDataStore
from accord_tpu.journal import record as rec_mod
from accord_tpu.journal import segment as seg_mod
from accord_tpu.journal import snapshot as snap_mod
from accord_tpu.journal.commit import GroupCommit
from accord_tpu.journal.wal import WriteAheadLog
from accord_tpu.utils import faults
from accord_tpu.utils.random_source import RandomSource


def _mk_journal(path, **kw):
    kw.setdefault("defer", None)
    kw.setdefault("window_micros", 0)
    return DurableJournal(str(path), **kw)


def _reference_state(docs, upto_seq, workdir):
    """Canonical state of an in-memory replay of records seq<=upto_seq."""
    ref_dir = os.path.join(str(workdir), "_ref")
    shutil.rmtree(ref_dir, ignore_errors=True)
    j = _mk_journal(ref_dir)
    j._replaying = True
    try:
        for doc in docs:
            if doc["s"] > upto_seq:
                break
            j.apply_record(doc)
    finally:
        j._replaying = False
    out = j.canonical_state_json()
    j.close()
    return out


# ---------------------------------------------------------------------------
# segment + WAL mechanics
# ---------------------------------------------------------------------------

def test_wal_append_reopen_roundtrip(tmp_path):
    w = WriteAheadLog(str(tmp_path / "j"), segment_bytes=512)
    docs = [{"k": "hlc", "b": i} for i in range(50)]
    for d in docs:
        w.append(d)
    w.sync()
    assert w.n_rolled > 0, "tiny segments must roll"
    w.close()
    r = WriteAheadLog(str(tmp_path / "j"), segment_bytes=512)
    assert [d["b"] for d in r.recovered] == list(range(50))
    assert [d["s"] for d in r.recovered] == list(range(1, 51))
    # appends continue the sequence
    assert r.append({"k": "hlc", "b": 99}) == 51
    r.close()


def test_wal_torn_tail_truncated_on_open(tmp_path):
    w = WriteAheadLog(str(tmp_path / "j"))
    for i in range(10):
        w.append({"k": "hlc", "b": i})
    w.sync()
    w.close()
    path = sorted(p for p in os.listdir(tmp_path / "j")
                  if p.startswith("wal-"))[0]
    full = (tmp_path / "j" / path).read_bytes()
    # chop mid-frame: the last record loses bytes
    (tmp_path / "j" / path).write_bytes(full[:-3])
    r = WriteAheadLog(str(tmp_path / "j"))
    assert len(r.recovered) == 9
    assert r.n_truncated_bytes > 0
    # the torn bytes are GONE from the file: new appends never interleave
    assert r.append({"k": "hlc", "b": 99}) == 10
    r.sync()
    r.close()
    r2 = WriteAheadLog(str(tmp_path / "j"))
    assert [d["b"] for d in r2.recovered][-1] == 99
    r2.close()


def test_wal_crc_corruption_truncates_and_drops_later_segments(tmp_path):
    w = WriteAheadLog(str(tmp_path / "j"), segment_bytes=256)
    for i in range(40):
        w.append({"k": "hlc", "b": i})
    w.sync()
    w.close()
    segs = sorted(p for p in os.listdir(tmp_path / "j")
                  if p.startswith("wal-"))
    assert len(segs) >= 3
    victim = tmp_path / "j" / segs[1]
    blob = bytearray(victim.read_bytes())
    blob[len(blob) // 2] ^= 0xFF          # flip one payload byte
    victim.write_bytes(bytes(blob))
    r = WriteAheadLog(str(tmp_path / "j"), segment_bytes=256)
    # prefix property: everything before the corruption survives, nothing
    # after it is mis-replayed (later segments dropped, counted)
    got = [d["b"] for d in r.recovered]
    assert got == list(range(len(got)))
    assert len(got) < 40
    assert r.n_dropped_segments > 0
    r.close()


def test_wal_recycles_fully_snapshotted_segments(tmp_path):
    w = WriteAheadLog(str(tmp_path / "j"), segment_bytes=256)
    for i in range(60):
        w.append({"k": "hlc", "b": i})
    w.sync()
    live_before = w.stats()["live_segments"]
    assert live_before >= 4
    dropped = w.drop_below(w.tail_seq)     # floor past every sealed record
    assert dropped > 0
    pool = [p for p in os.listdir(tmp_path / "j")
            if p.startswith("recycle-")]
    assert pool, "dropped segments should enter the recycle pool"
    # the next rolls REUSE pool files instead of allocating
    recycled_before = w.n_recycled
    for i in range(60):
        w.append({"k": "hlc", "b": 100 + i})
    assert w.n_recycled > recycled_before
    w.sync()
    w.close()
    # and the recovered stream is exactly the un-dropped suffix + new
    r = WriteAheadLog(str(tmp_path / "j"), segment_bytes=256)
    assert [d["s"] for d in r.recovered] == \
        sorted(d["s"] for d in r.recovered)
    r.close()


def test_wal_stale_recycled_segment_content_dropped(tmp_path):
    """A crash between recycling a pool file under a new wal-NN name and
    persisting its truncate+header can leave the OLD segment's CRC-valid
    frames under the new name.  Recovery must detect the identity
    mismatch (header seg index vs filename / base-seq continuity) and
    drop the stale bytes — never rewind tail_seq below the real tail and
    silently skip later appends as 'already snapshotted'."""
    w = WriteAheadLog(str(tmp_path / "j"), segment_bytes=512)
    for i in range(100):
        w.append({"k": "hlc", "b": i})
    w.sync()
    w.close()
    segs = sorted(p for p in os.listdir(tmp_path / "j")
                  if p.startswith("wal-"))
    assert len(segs) >= 3
    # model the crash: the LAST segment's file holds the FIRST segment's
    # old content (recycled file, truncate never persisted)
    stale = (tmp_path / "j" / segs[0]).read_bytes()
    (tmp_path / "j" / segs[-1]).write_bytes(stale)
    r = WriteAheadLog(str(tmp_path / "j"), segment_bytes=512)
    got = [d["b"] for d in r.recovered]
    # prefix property: everything before the stale file survives, the
    # stale frames are NOT replayed, and tail never rewinds
    assert got == list(range(len(got)))
    assert r.n_dropped_segments >= 1
    assert r.tail_seq == len(got)
    assert r.append({"k": "hlc", "b": 99}) == len(got) + 1
    r.close()


def test_wal_header_only_tail_after_compaction_keeps_sequence(tmp_path):
    """Predecessors all recycled below the snapshot floor + a torn write
    leaving the tail segment header-only: reopen must pin tail_seq at
    the header's base-1, never reissue sequence numbers under the floor
    (the next recovery would skip them as already-snapshotted)."""
    w = WriteAheadLog(str(tmp_path / "j"), segment_bytes=256)
    for i in range(30):
        w.append({"k": "hlc", "b": i})
    w.sync()
    tail = w.tail_seq
    w.drop_below(tail)                     # floor covers every sealed seg
    before_roll = w.n_rolled
    while w.n_rolled == before_roll:       # force a roll into a fresh seg
        w.append({"k": "hlc", "b": 99})
        w.sync()
    w.close()
    segs = sorted(p for p in os.listdir(tmp_path / "j")
                  if p.startswith("wal-"))
    last = tmp_path / "j" / segs[-1]
    header, payloads, _end, _size = seg_mod.scan(str(last))
    base = header[1]
    # torn write took the tail segment's records: header survives alone
    hdr_len = len(seg_mod.frame(seg_mod.header_payload(*header)))
    last.write_bytes(last.read_bytes()[:hdr_len])
    r = WriteAheadLog(str(tmp_path / "j"), segment_bytes=256)
    assert r.tail_seq == base - 1, \
        f"tail rewound to {r.tail_seq}; seqs under the floor would reissue"
    assert r.append({"k": "hlc", "b": 100}) == base
    r.close()


def test_frame_rejects_garbage_length(tmp_path):
    p = tmp_path / "x.seg"
    p.write_bytes(b"\xff\xff\xff\xff GET / HTTP/1.1\r\n")
    header, payloads, valid_end, _size = seg_mod.scan(str(p))
    assert header is None and payloads == [] and valid_end == 0


# ---------------------------------------------------------------------------
# versioned binary record codec (r16): the WAL-side twin of the wire
# codec's golden-frame gate.  The pins freeze the v1 bytes — an encoder
# change without a version bump fails here, and every SUPPORTED version's
# pins must keep decoding forever (journals on disk outlive processes).
# ---------------------------------------------------------------------------

WAL_RECORD_PINS_V1 = [
    ("b20184a16ba36d7367a16602a17084a25f74a9507265416363657074a674786e5f"
     "696482a25f74a3544944a17693ce00010000ce0010001001a96d61785f65706f63"
     "6801a96d696e5f65706f636801a17307",
     {"k": "msg", "f": 2,
      "p": {"_t": "PreAccept",
            "txn_id": {"_t": "TID", "v": [65536, 1048592, 1]},
            "max_epoch": 1, "min_epoch": 1}, "s": 7}),
    ("b20189a16ba3726567a373696400a17482a25f74a3544944a17693ce0001000010"
     "01a2737382a25f74a25353a1760da2657882a25f74a25453a17693ce0001000020"
     "02a2707282a25f74a342414ca17693000000a26163c0a2647582a25f74a3445552"
     "a17600a17308",
     {"k": "reg", "sid": 0, "t": {"_t": "TID", "v": [65536, 16, 1]},
      "ss": {"_t": "SS", "v": 13}, "ex": {"_t": "TS", "v": [65536, 32, 2]},
      "pr": {"_t": "BAL", "v": [0, 0, 0]}, "ac": None,
      "du": {"_t": "DUR", "v": 0}, "s": 8}),
    ("b20185a16ba57265706c79a3737263a26331a16d03a16284a474797065a674786e"
     "5f6f6ba66d73675f696409ab696e5f7265706c795f746f03a374786e9193a17207"
     "9301a27330cb4004000000000000a17309",
     {"k": "reply", "src": "c1", "m": 3,
      "b": {"type": "txn_ok", "msg_id": 9, "in_reply_to": 3,
            "txn": [["r", 7, [1, "s0", 2.5]]]}, "s": 9}),
    ("b20186a16ba56170706c79a3746f6bcd3039a1769301a27330cb40040000000000"
     "00a2617482a25f74a25453a17693ce000100003003a17482a25f74a3544944a176"
     "93ce000100001001a1730a",
     {"k": "apply", "tok": 12345, "v": [1, "s0", 2.5],
      "at": {"_t": "TS", "v": [65536, 48, 3]},
      "t": {"_t": "TID", "v": [65536, 16, 1]}, "s": 10}),
    # the columnar v2 reg row — what _drain_pending_registers actually
    # writes (over half of all WAL records); the keyed pin above is the
    # r13 legacy shape kept for decode-forever.  One plain executeAt,
    # one with the 4th-element TxnId tag (the fast path): reordering the
    # 'c' list or dropping the tag must fail here, not on replay.
    ("b20183a16ba3726567a163970393ce000100003001a74170706c69656493ce0001"
     "0000400293000000c0a84d616a6f72697479a17302",
     {"k": "reg", "c": [3, [65536, 48, 1], "Applied", [65536, 64, 2],
                        [0, 0, 0], None, "Majority"], "s": 2}),
    ("b20183a16ba3726567a163970093ce000100001001ab50726541636365707465"
     "6494ce00010000100101c0c0aa4e6f7444757261626c65a1730d",
     {"k": "reg", "c": [0, [65536, 16, 1], "PreAccepted",
                        [65536, 16, 1, 1], None, None, "NotDurable"],
      "s": 13}),
    ("b20183a16ba3686c63a162ce00100000a1730b",
     {"k": "hlc", "b": 1048576, "s": 11}),
    ("b20185a16ba2776da373696401a16491920064a17291920032a1730c",
     {"k": "wm", "sid": 1, "d": [[0, 100]], "r": [[0, 50]], "s": 12}),
]
ALL_WAL_RECORD_PINS = {1: WAL_RECORD_PINS_V1}


def test_wal_record_golden_pins_v1():
    assert rec_mod.VERSION in ALL_WAL_RECORD_PINS, \
        "a format bump must pin its new bytes here"
    for hexpin, doc in ALL_WAL_RECORD_PINS[rec_mod.VERSION]:
        assert rec_mod.encode_record(doc, "binary").hex() == hexpin, \
            f"encoder drift without a version bump (doc {doc['k']!r})"


def test_wal_record_all_versions_decode_forever():
    for ver, pins in ALL_WAL_RECORD_PINS.items():
        assert ver in rec_mod.SUPPORTED_VERSIONS
        for hexpin, doc in pins:
            assert rec_mod.decode_record(bytes.fromhex(hexpin)) == doc
            # the debug codec must carry the identical doc
            assert rec_mod.decode_record(
                rec_mod.encode_record(doc, "json")) == doc


def test_wal_record_big_int_falls_back_to_json():
    doc = {"k": "hlc", "b": 1 << 70, "s": 1}
    payload = rec_mod.encode_record(doc, "binary")
    assert payload[:1] == b"{", "out-of-range int must ride JSON"
    assert rec_mod.decode_record(payload) == doc


def test_wal_mixed_codec_journals_replay_identically(tmp_path):
    docs = [d for _h, d in WAL_RECORD_PINS_V1]
    states = {}
    for codec in ("json", "binary"):
        w = WriteAheadLog(str(tmp_path / codec), record_codec=codec)
        for d in docs:
            w.append({k: v for k, v in d.items() if k != "s"})
        w.sync()
        w.close()
        r = WriteAheadLog(str(tmp_path / codec))
        states[codec] = json.dumps(r.recovered, sort_keys=True)
        r.close()
    assert states["json"] == states["binary"]
    # one journal may MIX codecs (per-record fallback): reopen the binary
    # journal and append under json — the sniffing decode sees all
    w = WriteAheadLog(str(tmp_path / "binary"), record_codec="json")
    w.append({"k": "hlc", "b": 777})
    w.sync()
    w.close()
    r = WriteAheadLog(str(tmp_path / "binary"))
    assert len(r.recovered) == len(docs) + 1
    assert r.recovered[-1]["b"] == 777
    r.close()


def test_reg_record_r13_keyed_shape_still_replays(tmp_path):
    """Journals on disk outlive code: the pre-r16 wire-encoded reg row
    shape must keep installing registers forever, alongside the columnar
    v2 rows current code writes."""
    from accord_tpu.local.status import Durability, SaveStatus
    j = _mk_journal(tmp_path / "j")
    j._replaying = True
    j.apply_record({"k": "reg", "sid": 3,
                    "t": {"_t": "TID", "v": [65536, 16, 1]},
                    "ss": {"_t": "SaveStatus", "n": "Stable"},
                    "ex": {"_t": "TS", "v": [65536, 32, 2]},
                    "pr": {"_t": "BAL", "v": [0, 0, 0]},
                    "ac": None,
                    "du": {"_t": "Durability", "n": "NotDurable"},
                    "s": 1})
    j.apply_record({"k": "reg", "c": [
        3, [65536, 48, 1], "Applied", [65536, 64, 2],
        [0, 0, 0], None, "Majority"], "s": 2})
    j._replaying = False
    regs = j._registers[3]
    assert len(regs) == 2
    old, new = sorted(regs.items(), key=lambda kv: kv[0])
    assert old[1].save_status is SaveStatus.Stable
    assert old[1].accepted is None
    assert new[1].save_status is SaveStatus.Applied
    assert new[1].durability is Durability.Majority
    assert new[1].execute_at.lsb == 64
    j.close()


def test_wal_unknown_record_version_fails_open(tmp_path):
    from accord_tpu.journal.record import MAGIC, RecordError
    w = WriteAheadLog(str(tmp_path / "j"))
    w.append({"k": "hlc", "b": 1})
    w.sync()
    w.close()
    path = sorted(p for p in os.listdir(tmp_path / "j")
                  if p.startswith("wal-"))[0]
    seg = tmp_path / "j" / path
    import struct
    import zlib
    payload = bytes((MAGIC, 0x7F)) + b"\x80"
    fr = struct.pack(">II", len(payload), zlib.crc32(payload)) + payload
    seg.write_bytes(seg.read_bytes() + fr)
    with pytest.raises(RecordError):
        WriteAheadLog(str(tmp_path / "j"))


# ---------------------------------------------------------------------------
# group commit
# ---------------------------------------------------------------------------

def test_group_commit_one_fsync_acknowledges_batch(tmp_path):
    w = WriteAheadLog(str(tmp_path / "j"))
    timers = []
    gc = GroupCommit(w, defer=lambda d, fn: timers.append((d, fn)),
                     window_micros=1000)
    released = []
    for i in range(8):
        gc.append({"k": "hlc", "b": i})
        gc.after_durable(lambda i=i: released.append(i))
    assert released == [], "nothing durable before the window closes"
    assert len(timers) == 1, "ONE window timer for the whole batch"
    assert w.durable_seq == 0
    timers[0][1]()                         # window closes: one fsync
    assert released == list(range(8))
    assert w.durable_seq == w.tail_seq
    assert gc.n_flushes == 1
    assert gc.n_batch_records == 8
    # nothing pending: after_durable runs immediately
    gc.after_durable(lambda: released.append("now"))
    assert released[-1] == "now"
    w.close()


def test_group_commit_window_is_priced_not_hardcoded(tmp_path):
    from accord_tpu.journal.commit import (WINDOW_MAX_MICROS,
                                           WINDOW_MIN_MICROS,
                                           priced_window_micros)
    win = priced_window_micros(str(tmp_path))
    assert WINDOW_MIN_MICROS <= win <= WINDOW_MAX_MICROS
    # the probe is cached per device: a second read is identical
    assert priced_window_micros(str(tmp_path)) == win


def test_group_commit_failed_fsync_degrades_loudly_never_wedges(tmp_path):
    w = WriteAheadLog(str(tmp_path / "j"))
    gc = GroupCommit(w, defer=None, window_micros=0)
    gc.append({"k": "hlc", "b": 1})
    released = []
    with faults.disk_fault("failed_fsync", 1.0, RandomSource(3)):
        gc.append({"k": "hlc", "b": 2})
        gc.after_durable(lambda: released.append("x"))
    assert gc.failed, "fsync failure must mark the journal degraded"
    assert gc.n_fsync_failures == 1
    assert released == ["x"], \
        "a degraded journal releases waiters (availability over a " \
        "promise it can no longer keep)"
    # further appends are absorbed without raising
    gc.append({"k": "hlc", "b": 3})
    w.close()


# ---------------------------------------------------------------------------
# snapshots
# ---------------------------------------------------------------------------

def test_snapshot_roundtrip_and_torn_newest_falls_back(tmp_path):
    d = str(tmp_path / "j")
    os.makedirs(d)
    snap_mod.write_snapshot(d, 10, {"a": 1})
    snap_mod.write_snapshot(d, 20, {"a": 2})
    floor, state = snap_mod.load_latest(d)
    assert (floor, state) == (20, {"a": 2})
    # tear the newest: the runner-up must answer
    newest = os.path.join(d, "snap-%016d.snap" % 20)
    blob = open(newest, "rb").read()
    open(newest, "wb").write(blob[:len(blob) // 2])
    floor, state = snap_mod.load_latest(d)
    assert (floor, state) == (10, {"a": 1})


def test_snapshot_larger_than_one_record_is_framed_in_parts(tmp_path,
                                                            monkeypatch):
    """A replica that holds more state than one record may carry (YCSB E's
    100,000 1 KB records against MAX_RECORD = 64 MiB) still snapshots: the
    payload is written as consecutive CRC frames and joined on load; a tear
    in ANY of them falls back to the runner-up."""
    from accord_tpu.journal import segment as seg_mod
    monkeypatch.setattr(seg_mod, "MAX_RECORD", 1024)
    d = str(tmp_path / "j")
    os.makedirs(d)
    big = {"data": {str(k): "x" * 100 for k in range(200)}}
    snap_mod.write_snapshot(d, 10, {"small": 1})
    path = snap_mod.write_snapshot(d, 20, big)
    blob = open(path, "rb").read()
    assert len(blob) > 20 * 1024            # more than twenty frames
    assert snap_mod.load_latest(d) == (20, big)
    # corrupt one byte in the middle frame: the CRC of that frame fails
    mid = len(blob) // 2
    open(path, "wb").write(blob[:mid] + bytes([blob[mid] ^ 1])
                           + blob[mid + 1:])
    assert snap_mod.load_latest(d) == (10, {"small": 1})
    # cut at a frame boundary: every frame left is whole, the payload not
    open(path, "wb").write(blob[:seg_mod._HDR.size + 1024])
    assert snap_mod.load_latest(d) == (10, {"small": 1})
    with pytest.raises(seg_mod.SegmentError):
        seg_mod.frame(b"y" * 1025)          # a WAL record keeps its cap


def test_snapshot_keeps_only_last_two(tmp_path):
    d = str(tmp_path / "j")
    os.makedirs(d)
    for f in (10, 20, 30, 40):
        snap_mod.write_snapshot(d, f, {"f": f})
    snaps = [p for p in os.listdir(d) if p.endswith(".snap")]
    assert len(snaps) == 2
    assert snap_mod.load_latest(d)[0] == 40


def test_durable_journal_snapshot_bounds_replay(tmp_path):
    j = _mk_journal(tmp_path / "j", segment_bytes=512, debug_capture=True)
    for i in range(30):
        j.record_reply("c1", i, {"type": "txn_ok", "txn": [["r", 1, []]]})
    j.maybe_snapshot(force=True)
    for i in range(30, 40):
        j.record_reply("c1", i, {"type": "txn_ok", "txn": [["r", 1, []]]})
    want = j.canonical_state_json()
    j.close()
    r = _mk_journal(tmp_path / "j", segment_bytes=512)
    assert r.replay_stats["snapshot_loaded"]
    assert r.replay_stats["replayed"] == 10, \
        "only the post-floor tail replays"
    assert r.canonical_state_json() == want
    r.close()


# ---------------------------------------------------------------------------
# reply dedupe table (satellite: at-most-once across death)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not hasattr(os, "fork"), reason="POSIX only")
def test_fork_snapshot_offloads_capture_and_recovers(tmp_path):
    """The serving path's BGSAVE-shaped snapshot: with a loop + worker
    wired the capture forks; the parent's floor advances on reap and a
    fresh open recovers from the child-written file."""
    import asyncio

    async def run():
        loop = asyncio.get_running_loop()

        def _async_exec(work, done):
            fut = loop.run_in_executor(None, work)
            fut.add_done_callback(lambda f: done(f.exception()))

        j = DurableJournal(str(tmp_path / "j"),
                           defer=lambda s, fn: loop.call_later(s, fn),
                           window_micros=100, async_exec=_async_exec)
        j.reserve_hlc(50)          # real state: snapshot must carry it
        for i in range(50):
            j._append({"k": "wm", "sid": 0, "d": [[0, i]], "r": []})
        j.commit.flush(sync=True)
        tail = j.wal.tail_seq
        assert j.maybe_snapshot(force=True), "fork snapshot must launch"
        assert j._snap_inflight, "capture rides the child, not this tick"
        for _ in range(200):
            if not j._snap_inflight:
                break
            await asyncio.sleep(0.05)
        assert not j._snap_inflight, "snapshot child never reaped"
        assert j._snap_floor == tail
        j.close()

    asyncio.run(run())
    j2 = _mk_journal(tmp_path / "j")
    assert j2.replay_stats["snapshot_loaded"]
    assert j2.hlc_reserved == 50, \
        "state must come back from the child-written snapshot"
    j2.close()


def test_reply_table_recovers_and_bounds(tmp_path):
    j = _mk_journal(tmp_path / "j")
    body = {"type": "txn_ok", "txn": [["append", 5, 1]]}
    j.record_reply("c9", 17, body)
    assert j.replied_body("c9", 17) == body
    assert j.replied_body("c9", 18) is None
    j.close()
    r = _mk_journal(tmp_path / "j")
    assert r.replied_body("c9", 17) == body
    r.close()


def test_reply_table_eviction_cap(tmp_path, monkeypatch):
    from accord_tpu.journal import durable as durable_mod
    monkeypatch.setattr(durable_mod, "REPLIED_CAP", 8)
    j = _mk_journal(tmp_path / "j")
    try:
        for i in range(20):
            j.record_reply("c1", i, {"n": i})
        assert len(j.replied) == 8
        assert j.replied_body("c1", 0) is None
        assert j.replied_body("c1", 19) == {"n": 19}
    finally:
        j.close()


# ---------------------------------------------------------------------------
# the sim-driven crash-point sweep: >=200 seeded byte-level truncations
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sim_with_durable_journal(tmp_path_factory):
    """A 3-node sim cluster run entirely over on-disk DurableJournals
    (tiny segments, forced mid-run snapshot on node 1), plus the full
    record stream for reference replays."""
    from accord_tpu.sim.cluster import Cluster
    from accord_tpu.sim.kvstore import kv_txn
    from accord_tpu.sim.topology_factory import build_topology

    root = tmp_path_factory.mktemp("simwal")
    js = {nid: DurableJournal(str(root / f"n{nid}"), defer=None,
                              window_micros=0, segment_bytes=4096,
                              debug_capture=True)
          for nid in (1, 2, 3)}
    topology = build_topology(1, (1, 2, 3), 3, 4)
    cluster = Cluster(
        topology=topology, seed=11,
        data_store_factory=lambda nid: JournaledKVDataStore(nid, js[nid]),
        journal_factory=js.__getitem__)
    outs = []
    for i in range(8):
        node = 1 + (i % 3)
        key = 10 * (1 + i % 4)
        cluster.nodes[node].coordinate(
            kv_txn([key], {key: (f"v{i}",)})).begin(
                lambda r, f: outs.append((r, f)))
        cluster.run_until_quiescent()
        if i == 3:
            js[1].maybe_snapshot(data_store=cluster.nodes[1].data_store,
                                 force=True)
    assert all(f is None for _r, f in outs), outs
    assert cluster.failures == []
    return cluster, js, str(root)


def test_crash_point_sweep_byte_identity(sim_with_durable_journal,
                                         tmp_path):
    """>=200 seeded crash points (byte-level truncation of node 1's WAL,
    mid-frame included, below AND above the snapshot floor): every
    recovery is byte-identical to the replay of its surviving prefix."""
    cluster, js, root = sim_with_durable_journal
    docs = js[1].debug_records
    assert len(docs) > 100, "workload too small to sweep"
    src = os.path.join(root, "n1")
    seg_names = sorted(p for p in os.listdir(src) if p.startswith("wal-"))
    blobs = {p: open(os.path.join(src, p), "rb").read() for p in seg_names}
    other = [p for p in os.listdir(src) if not p.startswith("wal-")]
    total = sum(len(b) for b in blobs.values())
    floor, _snap = snap_mod.load_latest(src)
    assert floor > 0, "the mid-run snapshot must be on disk"
    rs = RandomSource(0xC4A5)
    # phase 1: recover every truncation case, collect (tail, state)
    cases = []
    for case_i in range(200):
        cut = rs.next_int(total) + 1
        case = tmp_path / "case"
        shutil.rmtree(case, ignore_errors=True)
        os.makedirs(case)
        for p in other:                     # snapshots ride along intact
            shutil.copy(os.path.join(src, p), case / p)
        left = cut
        for p in seg_names:
            take = min(left, len(blobs[p]))
            left -= take
            if take > 0:
                (case / p).write_bytes(blobs[p][:take])
        r = DurableJournal(str(case), defer=None, window_micros=0)
        tail = max(r.wal.tail_seq, floor)
        cases.append((case_i, cut, tail, r.canonical_state_json()))
        r.close()
    assert any(t > floor for _i, _c, t, _s in cases), \
        "sweep never crossed the snapshot floor"
    assert any(t <= floor for _i, _c, t, _s in cases) or floor <= 1
    # phase 2: ONE incremental reference replay, snapshotting the
    # canonical state at each distinct tail the sweep produced
    want = {}
    ref_dir = tmp_path / "_ref"
    shutil.rmtree(ref_dir, ignore_errors=True)
    ref = _mk_journal(ref_dir)
    ref._replaying = True
    need = sorted({t for _i, _c, t, _s in cases})
    di = 0
    try:
        for tail in need:
            while di < len(docs) and docs[di]["s"] <= tail:
                ref.apply_record(docs[di])
                di += 1
            want[tail] = ref.canonical_state_json()
    finally:
        ref._replaying = False
        ref.close()
    for case_i, cut, tail, got in cases:
        assert got == want[tail], \
            f"case {case_i} cut={cut}: recovered state != replay of " \
            f"surviving prefix (seq<={tail})"


def test_full_recovery_reconstructs_identical_commands(
        sim_with_durable_journal, tmp_path):
    """Cold recovery of the UNTRUNCATED directory reconstructs every
    command byte-equal (field-wise + wire-encoded variable parts) to the
    live journal's reconstruction — the serialization contract end to
    end through real protocol traffic."""
    from accord_tpu import wire
    cluster, js, root = sim_with_durable_journal
    case = tmp_path / "full"
    shutil.copytree(os.path.join(root, "n1"), case)
    r = DurableJournal(str(case), defer=None, window_micros=0)
    live = js[1]
    node = cluster.nodes[1]
    checked = 0
    for store in node.command_stores.unsafe_all_stores():
        sid = store.store_id
        assert r.registered_txns(sid) == live.registered_txns(sid)
        for txn_id in live.registered_txns(sid):
            a = live.reconstruct(store, txn_id, probe=True)
            b = r.reconstruct(store, txn_id, probe=True)
            assert (a is None) == (b is None), txn_id
            if a is None:
                continue
            assert a.save_status is b.save_status, txn_id
            assert a.execute_at == b.execute_at
            assert a.promised == b.promised
            assert a.accepted == b.accepted
            assert a.durability is b.durability
            for attr in ("route", "partial_deps", "writes", "result"):
                assert wire.encode(getattr(a, attr)) == \
                    wire.encode(getattr(b, attr)), (txn_id, attr)
            checked += 1
    assert checked >= 5
    # the recovered data log equals the live store's (install into a
    # throwaway plain KV store, compare value logs token by token)
    from accord_tpu.sim.kvstore import KVDataStore
    ds = node.data_store
    throwaway = KVDataStore(1)
    r.install_data(throwaway)
    assert {t: [e[2] for e in es] for t, es in throwaway.log.items()} == \
        {t: [e[2] for e in es] for t, es in ds.log.items()}
    assert r.canonical_state_json(ds) == live.canonical_state_json(ds)
    r.close()


def test_sim_restart_over_durable_journal(sim_with_durable_journal):
    """The sim's own restart path (Cluster.restart_node) runs unchanged
    over a DurableJournal — one reconstruction code path for simulated
    restarts and real kill -9 recovery."""
    from accord_tpu.sim.kvstore import kv_txn
    cluster, js, _root = sim_with_durable_journal
    cluster.restart_node(2)
    cluster.run_until_quiescent()
    assert cluster.failures == []
    out = []
    cluster.nodes[2].coordinate(
        kv_txn([10], {10: ("post-restart",)})).begin(
            lambda r, f: out.append((r, f)))
    cluster.run_until_quiescent()
    assert out and out[0][1] is None, out
    check = []
    cluster.nodes[2].coordinate(kv_txn([10], {})).begin(
        lambda r, f: check.append((r, f)))
    cluster.run_until_quiescent()
    vals = check[0][0].reads[10]
    assert "post-restart" in vals
    assert len(set(vals)) == len(vals), f"duplicate applies: {vals}"


# ---------------------------------------------------------------------------
# disk faults through the full stack (unit legs; the matrix runs
# python -m accord_tpu.journal.selftest for the seeded double-run sweep)
# ---------------------------------------------------------------------------

def test_torn_write_fault_truncates_cleanly(tmp_path):
    j = _mk_journal(tmp_path / "j", debug_capture=True)
    for i in range(10):
        j.record_reply("c1", i, {"n": i})
    with faults.disk_fault("torn_write", 1.0, RandomSource(5)):
        j.record_reply("c1", 99, {"n": 99})
    assert j.commit.failed, "a torn write degrades the journal"
    docs = j.debug_records
    j.wal._dirty = []                      # model the death: no close sync
    r = _mk_journal(tmp_path / "j")
    assert r.wal.n_truncated_bytes > 0
    assert r.canonical_state_json() == _reference_state(
        docs, r.wal.tail_seq, tmp_path)
    assert r.replied_body("c1", 99) is None, "the torn record must not replay"
    r.close()


def test_short_read_fault_recovers_prefix(tmp_path):
    j = _mk_journal(tmp_path / "j", debug_capture=True)
    for i in range(30):
        j.record_reply("c1", i, {"n": i})
    docs = j.debug_records
    j.close()
    with faults.disk_fault("short_read", 1.0, RandomSource(9)):
        r = _mk_journal(tmp_path / "j")
    tail = r.wal.tail_seq
    got = r.canonical_state_json()
    r.close()
    assert tail < 30
    assert got == _reference_state(docs, tail, tmp_path)


def test_disk_fault_env_spec_parse():
    armed = faults.arm_disk_faults_from_env("torn_write:0.25:7")
    try:
        assert armed == {"torn_write": 0.25}
        assert faults.active_disk_faults() == armed
    finally:
        faults.clear_disk_faults()
    assert faults.active_disk_faults() == {}
    with pytest.raises(ValueError):
        faults.inject_disk_fault("disk_gremlin", 0.5, RandomSource(1))


# ---------------------------------------------------------------------------
# HLC reservation: flush-before-issue survives the disk
# ---------------------------------------------------------------------------

def test_hlc_reservation_durable_across_recovery(tmp_path):
    j = _mk_journal(tmp_path / "j")
    j.reserve_hlc(5_000_000)
    # flush-before-issue: the reservation is ALREADY durable, no close
    assert j.wal.durable_seq == j.wal.tail_seq
    j.wal._dirty = []                      # model a kill -9
    r = _mk_journal(tmp_path / "j")
    assert r.hlc_reserved == 5_000_000, \
        "a restarted incarnation must start past every issued id"
    r.close()


# ---------------------------------------------------------------------------
# span-fed admission (satellite: ROADMAP item 4's second remainder)
# ---------------------------------------------------------------------------

def _fill_phase(metrics, phase, micros, n):
    h = metrics.histogram("phase_micros", phase=phase)
    for _ in range(n):
        h.observe(micros)


def test_span_phase_p99_reads_delta_windows():
    from accord_tpu.net.admission import SpanPhaseP99
    from accord_tpu.obs.metrics import MetricsRegistry
    m = MetricsRegistry()
    reader = SpanPhaseP99(m)
    assert reader.read() is None, "empty registry: no signal"
    _fill_phase(m, "txn", 50_000, 32)
    p = reader.read()
    assert p is not None and 32_000 <= p <= 70_000
    # no NEW samples since the last read: no signal (delta semantics)
    assert reader.read() is None
    # a single ballooning sub-phase drives the worst-of read-out
    _fill_phase(m, "txn", 1_000, 32)
    _fill_phase(m, "deps_wait", 900_000, 32)
    p = reader.read()
    assert p is not None and p >= 500_000
    # below MIN_SAMPLES: ignored
    _fill_phase(m, "accept", 10_000_000, 2)
    assert reader.read() is None


def test_admission_gate_prefers_span_feed_with_root_fallback():
    from accord_tpu.net.admission import AdmissionGate, SpanPhaseP99
    from accord_tpu.obs.metrics import MetricsRegistry
    m = MetricsRegistry()
    reader = SpanPhaseP99(m)
    g = AdmissionGate(max_inflight=32, target_p99_micros=10_000,
                      min_budget=2, window=64, phase_p99=reader.read)
    # root-window samples are FAST, span histograms are SLOW: the cut
    # must follow the span feed
    for i in range(g.ADJUST_EVERY):
        _fill_phase(m, "txn", 80_000, 1)
        g.try_admit()
        g.release(100)
    assert g.n_latency_cuts >= 1, "span feed over target must cut"
    assert g.stats()["p99_source"] == "spans"
    # spans go quiet (obs off / no samples): root window takes over and
    # recovers the budget (root samples are far below target)
    cut = g.dyn_budget
    for _ in range(4 * g.ADJUST_EVERY):
        g.try_admit()
        g.release(100)
    assert g.stats()["p99_source"] == "root"
    assert g.dyn_budget > cut


def test_admission_gate_without_feed_is_r12_behaviour():
    from accord_tpu.net.admission import AdmissionGate
    g = AdmissionGate(max_inflight=8, target_p99_micros=1000, min_budget=1,
                      window=32)
    for _ in range(2 * g.ADJUST_EVERY):
        g.try_admit()
        g.release(50_000)
    assert g.n_latency_cuts >= 1
    assert g.stats()["p99_source"] == "root"


# ---------------------------------------------------------------------------
# topology epoch records (r17, elastic serving)
# ---------------------------------------------------------------------------

def _topo_doc(epoch):
    from accord_tpu.net.reconfig import plan_join, topology_to_doc
    from accord_tpu.sim.topology_factory import build_topology
    t = build_topology(1, (2, 3, 4), 3, 4)
    for e in range(2, epoch + 1):
        t = plan_join(t, 4 + e)
    info = {n: (f"n{n - 1}", "127.0.0.1", 7000 + n) for n in t.nodes()}
    return topology_to_doc(t, info, proposer="n1")


def test_topology_records_recover_across_restart(tmp_path):
    """The epoch ledger is a journal fact: a node killed -9
    mid-reconfiguration — a proposal journaled but never broadcast
    included — recovers holding the exact ledger it had."""
    j = _mk_journal(tmp_path / "j")
    d2, d3 = _topo_doc(2), _topo_doc(3)
    j.record_topology(d2)
    j.record_topology(d2)          # idempotent re-ingest: one record
    j.record_topology(d3)
    j.commit.flush(sync=True)
    j.close()
    r = _mk_journal(tmp_path / "j")
    assert r.has_restored_state()
    assert [d["epoch"] for d in r.topologies()] == [2, 3]
    assert r.topologies()[0] == d2 and r.topologies()[1] == d3
    r.close()


def test_topology_records_survive_snapshot_floor(tmp_path):
    """A snapshot whose floor passes the topo records still restores the
    epoch history (the ledger rides encode_state/install_state)."""
    j = _mk_journal(tmp_path / "j")
    j.record_topology(_topo_doc(2))
    j.record_reply("c1", 1, {"type": "txn_ok", "txn": []})
    j.commit.flush(sync=True)
    assert j.maybe_snapshot(force=True)
    # drop every WAL segment below the floor, then recover: only the
    # snapshot carries the ledger now
    j.close()
    r = _mk_journal(tmp_path / "j")
    assert r.replay_stats["snapshot_loaded"]
    assert [d["epoch"] for d in r.topologies()] == [2]
    assert r.replied_body("c1", 1) is not None
    r.close()


def test_mid_reconfiguration_crash_point_sweep(tmp_path):
    """Recovery == replay of the surviving prefix WITH topology/epoch +
    bootstrap records in the stream: a byte-level truncation anywhere in
    a mid-reconfiguration WAL (epoch doc, bootstrap started, fence mark,
    next epoch, bootstrap done) recovers byte-identically to the replay
    of exactly the surviving records."""
    from accord_tpu.primitives.keys import Range, Ranges
    from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind

    src = tmp_path / "j"
    j = _mk_journal(src, debug_capture=True)
    ranges = Ranges([Range(0, 500)])
    fence = TxnId.create(2, 77, TxnKind.ExclusiveSyncPoint,
                         Domain.Range, 2)
    j.record_topology(_topo_doc(2))
    j.record_bootstrap(0, ranges, 2)
    j.record_bootstrapped_at(0, ranges, fence)
    j.reserve_hlc(1 << 20)
    j.record_topology(_topo_doc(3))
    j.record_bootstrap_done(0, ranges, 2)
    j.record_reply("c1", 5, {"type": "txn_ok", "txn": []})
    j.commit.flush(sync=True)
    docs = list(j.debug_records)
    j.close()
    seg_names = sorted(p for p in os.listdir(src) if p.startswith("wal-"))
    blobs = {p: (src / p).read_bytes() for p in seg_names}
    total = sum(len(b) for b in blobs.values())
    rs = RandomSource(0x7070)
    for case_i in range(40):
        cut = rs.next_int(total) + 1
        case = tmp_path / "case"
        shutil.rmtree(case, ignore_errors=True)
        os.makedirs(case)
        left = cut
        for p in seg_names:
            take = min(left, len(blobs[p]))
            left -= take
            if take > 0:
                (case / p).write_bytes(blobs[p][:take])
        r = _mk_journal(case)
        tail = r.wal.tail_seq
        got = r.canonical_state_json()
        r.close()
        assert got == _reference_state(docs, tail, tmp_path), \
            f"case {case_i} cut={cut}: mid-reconfiguration truncation " \
            f"did not recover to the surviving prefix (seq<={tail})"


def test_pre_epoch_record_journals_replay_forever(tmp_path):
    """Journals (and snapshots) written BEFORE the topology ledger
    existed keep replaying: no topo records, no 'topologies' state key —
    recovery tolerates both, forever."""
    j = _mk_journal(tmp_path / "j")
    j.record_reply("c1", 1, {"type": "txn_ok", "txn": []})
    j.reserve_hlc(4096)
    j.commit.flush(sync=True)
    j.close()
    r = _mk_journal(tmp_path / "j")
    assert r.topologies() == []
    assert r.replied_body("c1", 1) is not None
    # a pre-r17 snapshot state dict (no 'topologies' key) installs clean
    state = r.encode_state()
    state.pop("topologies")
    fresh = _mk_journal(tmp_path / "j2")
    fresh._replaying = True
    fresh.install_state(state)
    fresh._replaying = False
    assert fresh.topologies() == []
    assert fresh.replied_body("c1", 1) is not None
    fresh.close()
    r.close()
