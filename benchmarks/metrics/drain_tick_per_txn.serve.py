"""Host time the stores' drain ticks cost the serving loop per acknowledged
txn: the sum of ``kernel_times`` ``drain_tick_dispatch`` (upload + enqueue of
the frontier sweep) and ``drain_tick_wait`` (the join on its result) over the
window / txn_ok in it.  HOST clock: what the loop paid, not kernel time."""

LAYER = "device dispatch"
UNIT = "ms/txn"
SOURCE = "program_span"
MOVES = "commit_rate"


def read(record):
    if record.get("driver") != "served" or not record["acked"]:
        return None
    kt = record["counters"]["kernel_times"]
    secs = sum(kt[kind][1] for kind in ("drain_tick_dispatch",
                                        "drain_tick_wait") if kind in kt)
    return secs * 1e3 / record["acked"]
