"""Share of the window's failed request callbacks that failed AT ONCE,
because the transport knew their peer was down, and not by waiting out the
request timeout: failed_at_once / (failed_at_once + timed_out) over the
survivors' sinks (NodeServer.stats()["peer_failures"]).  100 is the design;
anything under it means some round still waited for the dead replica."""

LAYER = "client / wire"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "commit_p95"


def read(record):
    server = record.get("server") or {}
    if record.get("driver") != "served" \
            or "peer_failed_at_once" not in server:
        return None
    failed = server["peer_failed_at_once"] + server["peer_timed_out"]
    return 100.0 * server["peer_failed_at_once"] / failed if failed else None
