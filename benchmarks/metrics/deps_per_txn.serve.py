"""Dependencies a coordinator made stable per txn: Δ ``deps_entries`` ÷ Δ
``deps_txns`` over the window, summed over the nodes
(``NodeServer.stats()["coordination"]``: the txns the Stable round of
``coordinate/execute.py`` sent, recovery's included, and the txns their
deps named).  Every txn of a hot row depends on the ones before it that
touched the row and are not yet known applied, so the deps grow with the
row's contention and its history.  None from a program without the
counters."""

LAYER = "server loop + protocol"
UNIT = "entries/txn"
SOURCE = "program_counter"
MOVES = "commit_p95"


def read(record):
    server = record.get("server") or {}
    if record.get("driver") != "served" \
            or "coordination_deps_txns" not in server \
            or not server["coordination_deps_txns"]:
        return None
    return (server["coordination_deps_entries"]
            / server["coordination_deps_txns"])
