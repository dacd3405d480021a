"""Share of the window's deps queries of range-domain txns that a device
route answered: ``n_range_device_queries`` / ``n_range_queries`` over the
nodes' DeviceStates.  The adaptive router prices every flush; this is its
verdict on interval queries, apart from the key-domain queries that share
their flushes (``device_query_share.serve`` counts both)."""

LAYER = "device dispatch"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "commit_rate"


def read(record):
    c = record.get("counters") or {}
    if record.get("driver") != "served" or not c.get("n_range_queries"):
        return None
    return 100.0 * c["n_range_device_queries"] / c["n_range_queries"]
