"""Frames the survivors' links took for the crashed peer per acknowledged
txn: the sum of ``enqueued`` of every survivor's link to it
(NodeServer.stats()["links"]) over the window / txn_ok in it.  Near 0: a
link that knows its peer is down takes nothing, and the sink emits nothing
towards it."""

LAYER = "client / wire"
UNIT = "frame/txn"
SOURCE = "program_counter"
MOVES = "commit_rate"


def read(record):
    server = record.get("server") or {}
    if record.get("driver") != "served" \
            or "down_peer_enqueued" not in server or not record["acked"]:
        return None
    return server["down_peer_enqueued"] / record["acked"]
