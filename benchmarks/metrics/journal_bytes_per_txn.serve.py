"""Journal bytes the three nodes appended per acknowledged txn: the WAL's
``bytes`` counter (journal.stats()) over the window / txn_ok in it."""

LAYER = "journal"
UNIT = "B/txn"
SOURCE = "program_counter"
MOVES = "commit_p95"


def read(record):
    if record.get("driver") != "served" or not record["acked"]:
        return None
    return record["server"]["journal_bytes"] / record["acked"]
