"""Records a scan reply carried to its client, on average: ``scan_rows``
(NodeServer.stats()["coordination"], counted where the reply is built) over
the range-domain txns coordinated in the window.  A record is 1,000 bytes:
this is the reply's size in KB, which the codec, the socket and the client
pay for in latency."""

LAYER = "client / wire"
UNIT = "rows/txn"
SOURCE = "program_counter"
MOVES = "commit_p95"


def read(record):
    server = record.get("server") or {}
    if record.get("driver") != "served" \
            or "coordination_scan_rows" not in server \
            or not server["coordination_range_txns"]:
        return None
    return server["coordination_scan_rows"] \
        / server["coordination_range_txns"]
