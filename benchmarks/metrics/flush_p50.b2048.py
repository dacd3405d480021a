"""Median of one flush, deps_query_batch_begin to built deps, over the
window's flushes — the driver's own clock.  Per-layer in the b2048 cell
because a window holds only some tens of such flushes (no tail to speak of)."""

LAYER = "device dispatch"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "preaccept_rate"


def read(record):
    if record.get("driver") != "store" or not record["flush_s"]:
        return None
    from benchmarks.lib.stats import percentile
    return percentile(record["flush_s"], 0.5) * 1e3
