"""Seconds the host spent dispatching per flush: the sum of
DeviceState.kernel_times' ``dispatch_*`` entries over the window / flushes.
HOST clock from the start of a part's table sync to its enqueued launch
(slot-table and bucket-index sync + upload, query upload, enqueue) — the
part of ``deps_query_batch_begin`` that a slow index sync shows in.  The
``sync_bucket_*`` kinds lie inside it and are not added."""

LAYER = "device dispatch"
UNIT = "ms/flush"
SOURCE = "program_span"
MOVES = "preaccept_rate"


def read(record):
    if record.get("driver") != "store" or not record["flushes"]:
        return None
    secs = [secs for kind, (_calls, secs)
            in record["counters"]["kernel_times"].items()
            if kind.startswith("dispatch_")]
    return sum(secs) * 1e3 / record["flushes"] if secs else None
