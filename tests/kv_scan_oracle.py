"""The data store's range read and snapshot as they were until PR 30: a
walk of EVERY token the store holds, per range.  KVDataStore now reads
bisect slices of an ordered token index; tests hold it to these."""


def read_range_full_walk(store, start, end, execute_at):
    vals = {}
    for token in list(store.tokens()):
        if start <= token < end:
            vals[token] = store.read_at(token, execute_at)
    return vals


def snapshot_full_walk(store, ranges):
    return {t: list(entries) for t, entries in store.log.items()
            if ranges.contains_token(t)}
