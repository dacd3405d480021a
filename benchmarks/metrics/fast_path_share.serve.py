"""Share of the window's PreAccept decisions that took the fast path:
fast / (fast + slow) of the ``txn_path`` decisions the five coordinators
counted (NodeServer.stats()["coordination"]).  Under contention PreAccept
replies disagree and the rest pay the Accept round."""

LAYER = "server loop + protocol"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "commit_p95"


def read(record):
    server = record.get("server") or {}
    if record.get("driver") != "served" or "coordination_fast" not in server:
        return None
    decided = server["coordination_fast"] + server["coordination_slow"]
    return 100.0 * server["coordination_fast"] / decided if decided else None
