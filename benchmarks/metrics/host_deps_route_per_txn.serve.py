"""What the host deps route costs the serving loop per acknowledged txn: the
sum of the four ``kernel_times`` kinds PERF.md defines as that route
(``host_attr_index`` + ``dispatch_host`` + ``host_attr_filter`` +
``host_attr_finalize``; HOST clocks of ``DeviceState``) over the whole
window / txn_ok in it.  Needs no trace."""

LAYER = "device dispatch"
UNIT = "ms/txn"
SOURCE = "program_span"
MOVES = "commit_rate"

KINDS = ("host_attr_index", "dispatch_host", "host_attr_filter",
         "host_attr_finalize")


def read(record):
    if record.get("driver") != "served" or not record["acked"]:
        return None
    kinds = record["counters"]["kernel_times"]
    return sum(kinds.get(kind, (0, 0.0))[1] for kind in KINDS) * 1e3 \
        / record["acked"]
