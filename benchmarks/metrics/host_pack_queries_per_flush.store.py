"""Host time per flush of packing the flush's queries
(``DeviceState._pack_queries``: ``dk.pack_query_matrix``, and
``_bucket_query_cols`` on the bucketed route): ``kernel_times``
``pack_queries`` over the window / flushes.  HOST clock; needs no trace.
None from a program without the kind."""

LAYER = "device dispatch"
UNIT = "ms/flush"
SOURCE = "program_span"
MOVES = "preaccept_rate"


def read(record):
    if record.get("driver") != "store" or not record["flushes"]:
        return None
    cell = record["counters"]["kernel_times"].get("pack_queries")
    if cell is None:
        return None
    return cell[1] * 1e3 / record["flushes"]
