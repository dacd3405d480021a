"""Live entries a replica store's deps index holds over the window: the
mean over the nodes' stores of ``DeviceState.n_live_slots`` (the mirror's
non-free, non-invalidated slots), read at the window's start and end and
averaged.  What the configuration promises (a deployment's live window,
100,000 txns a store) is this number; a program without the counter leaves
the record without it and this reads nothing."""

LAYER = "device dispatch"
UNIT = "entries"
SOURCE = "program_counter"
MOVES = "commit_rate"


def read(record):
    if record.get("driver") != "served":
        return None
    return record.get("live_slots_per_store")
