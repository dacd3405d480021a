"""Processor time of the whole process per acknowledged txn:
``time.process_time()`` over the window / txn_ok in it.  The three nodes AND
the load generator share the process, so this is the cluster's host cost per
txn plus the client's, not one node's."""

LAYER = "server loop + protocol"
UNIT = "ms/txn"
SOURCE = "host_clock"
MOVES = "commit_rate"


def read(record):
    if record.get("driver") != "served" or not record["acked"]:
        return None
    return record["cpu_s"] * 1e3 / record["acked"]
