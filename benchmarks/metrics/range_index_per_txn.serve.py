"""Host time spent keeping range txns findable per acknowledged txn:
``kernel_times`` ``range_index_sync`` (a range registration in the device
mirror's interval index, DeviceState.register, and in the store's own,
CommandStore.put_range_command / drop_range_command) over the window /
txn_ok in it.  HOST clock.  It has to stay flat in the range txns a store
ever witnessed.  Nothing to read on a program without the kind."""

LAYER = "device dispatch"
UNIT = "ms/txn"
SOURCE = "program_span"
MOVES = "commit_rate"


def read(record):
    if record.get("driver") != "served" or not record["acked"]:
        return None
    kinds = record["counters"]["kernel_times"]
    if "range_index_sync" not in kinds:
        return None
    return kinds["range_index_sync"][1] * 1e3 / record["acked"]
