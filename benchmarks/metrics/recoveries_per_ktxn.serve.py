"""Recoveries the nodes started per thousand acknowledged txns: the
``recoveries{event=attempt}`` counters (NodeServer.stats()["coordination"])
over the window / txn_ok in it x 1000."""

LAYER = "server loop + protocol"
UNIT = "1/ktxn"
SOURCE = "program_counter"
MOVES = "commit_p95"


def read(record):
    server = record.get("server") or {}
    if record.get("driver") != "served" or not record["acked"] \
            or "coordination_recoveries" not in server:
        return None
    return 1000.0 * server["coordination_recoveries"] / record["acked"]
