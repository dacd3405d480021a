"""The store surface DeviceState attribution touches, and its fill (copied
from bench.py's BenchStore / BenchSafe / build_headline_store): real
RedundantBefore floors over a slice of the keyspace plus CommandsForKey
state, populated through DeviceState.register(), the path the protocol's
transitions drive."""


class ReplicaStore:
    def __init__(self):
        from accord_tpu.local.redundant import RedundantBefore
        self.commands_for_key = {}
        self.redundant_before = RedundantBefore()

    class node:       # DeviceState touches .node for drain ticks only
        scheduler = None


class Safe:
    def __init__(self, store):
        self.store = store

    def redundant_before(self):
        return self.store.redundant_before


def new_store(floors):
    """(store, DeviceState, safe) with the configuration's floors: every
    ``every`` keys below ``below``, a range ``width`` wide is redundant
    before hlc ``hlc``."""
    from accord_tpu.local.device_index import DeviceState
    from accord_tpu.primitives.keys import Range, Ranges
    from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind

    store = ReplicaStore()
    floor_id = TxnId.create(1, int(floors["hlc"]),
                            TxnKind.ExclusiveSyncPoint, Domain.Range, 1)
    store.redundant_before.add_redundant(
        Ranges.of(*(Range(s, s + int(floors["width"]))
                    for s in range(0, int(floors["below"]),
                                   int(floors["every"])))), floor_id)
    return store, DeviceState(store), Safe(store)


def register(store, dev, txn):
    """A txn witnessed as PreAccepted: the device index and, for a point
    txn, each key's CommandsForKey."""
    from accord_tpu.local.commands_for_key import (CommandsForKey,
                                                   InternalStatus)
    dev.register(txn.tid, int(InternalStatus.PREACCEPTED), txn.keys)
    cfks = store.commands_for_key
    for t in txn.toks:
        cfk = cfks.get(t)
        if cfk is None:
            cfk = cfks[t] = CommandsForKey(t)
        cfk.update(txn.tid, InternalStatus.PREACCEPTED)


def truncate(store, dev, txn):
    """What local/cleanup.py's _release_indexes does for a truncated txn:
    free the device slot and drop the per-key entries."""
    dev.free(txn.tid)
    cfks = store.commands_for_key
    for t in txn.toks:
        cfk = cfks.get(t)
        if cfk is not None:
            cfk.remove(txn.tid)
