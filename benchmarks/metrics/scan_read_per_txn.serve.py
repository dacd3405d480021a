"""Host time inside the data stores' range read per acknowledged txn:
``scan_host_s`` of NodeServer.stats()["data"] (KVDataStore.read_range: the
bisect slice of the ordered token index and the versioned read of each key
in it), every replica's, over the window / txn_ok in it.  HOST clock.  It
has to stay flat in the records a store holds."""

LAYER = "data store"
UNIT = "ms/txn"
SOURCE = "host_clock"
MOVES = "commit_rate"


def read(record):
    server = record.get("server") or {}
    if record.get("driver") != "served" or not record["acked"] \
            or "data_scan_host_s" not in server:
        return None
    return server["data_scan_host_s"] * 1e3 / record["acked"]
