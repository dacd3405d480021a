"""What the host route's index costs the serving loop per acknowledged txn:
``kernel_times`` ``host_index_build`` (a whole build of
``_DepsMirror.host_index``) + ``host_index_fold`` (a read that folded in the
entries registered since the last one), both inside ``dispatch_host``, over
the whole window / txn_ok in it.  HOST clock; needs no trace.  None on a
program without the kinds."""

LAYER = "device dispatch"
UNIT = "ms/txn"
SOURCE = "program_span"
MOVES = "commit_rate"

KINDS = ("host_index_build", "host_index_fold")


def read(record):
    if record.get("driver") != "served" or not record["acked"]:
        return None
    kinds = record["counters"]["kernel_times"]
    if not any(kind in kinds for kind in KINDS):
        return None
    return sum(kinds.get(kind, (0, 0.0))[1] for kind in KINDS) * 1e3 \
        / record["acked"]
