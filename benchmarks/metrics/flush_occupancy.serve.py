"""Deps queries per dispatcher flush on the served path:
``dispatch.flush_queries / dispatch.flush_events`` (NodeServer.stats()),
summed over the three nodes, over the window."""

LAYER = "server loop + protocol"
UNIT = "query/flush"
SOURCE = "program_counter"
MOVES = "commit_rate"


def read(record):
    if record.get("driver") != "served" \
            or not record["server"]["flush_events"]:
        return None
    return record["server"]["flush_queries"] / record["server"]["flush_events"]
