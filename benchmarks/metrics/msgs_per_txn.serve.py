"""Peer messages the three nodes sent per acknowledged txn: the sum of every
link's ``sent`` (NodeServer.stats()) over the window / txn_ok in it."""

LAYER = "client / wire"
UNIT = "msg/txn"
SOURCE = "program_counter"
MOVES = "commit_rate"


def read(record):
    if record.get("driver") != "served" or not record["acked"]:
        return None
    return record["server"]["links_sent"] / record["acked"]
