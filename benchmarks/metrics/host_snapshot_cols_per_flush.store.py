"""Host time per flush of the copy of every mirror column that a deferred
flush takes after its launch (``_DepsMirror.snapshot_cols``: a span a copy,
none a cache hit; it runs while the prefetch worker waits for the device):
``kernel_times`` ``snapshot_cols`` over the window / flushes.  HOST clock;
needs no trace.  None from a program without the kind."""

LAYER = "device dispatch"
UNIT = "ms/flush"
SOURCE = "program_span"
MOVES = "preaccept_rate"


def read(record):
    if record.get("driver") != "store" or not record["flushes"]:
        return None
    cell = record["counters"]["kernel_times"].get("snapshot_cols")
    if cell is None:
        return None
    return cell[1] * 1e3 / record["flushes"]
