"""Share of the txns the nodes coordinated in the window whose footprint is
an interval: ``range_txns`` / (``range_txns`` + ``key_txns``) of
NodeServer.stats()["coordination"], counted by TxnId.domain() where the
PreAccept decision is.  YCSB E's mix puts it at 95 %: the range deps query,
range registration and the data store's ordered scan then do the work."""

LAYER = "server loop + protocol"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "commit_rate"


def read(record):
    server = record.get("server") or {}
    if record.get("driver") != "served" \
            or "coordination_range_txns" not in server:
        return None
    decided = server["coordination_range_txns"] \
        + server["coordination_key_txns"]
    return 100.0 * server["coordination_range_txns"] / decided \
        if decided else None
