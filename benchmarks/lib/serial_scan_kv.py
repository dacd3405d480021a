"""serial_kv: the plain reference of a list-append key/value store.

A single-threaded store (a dict of lists) and the replay that feeds it.
Given what the clients of a concurrent run saw, ``replay`` CONSTRUCTS the
serial execution that strict serializability promises, runs it on the plain
store, and fails, naming the reason, where no such execution exists.

Input (keys and values are any hashable; times any comparable number):

- ``answered``: one ``(start, end, reads, appends)`` per acknowledged txn:
  submitted at ``start``, answered at ``end``; ``reads`` = ``{key: tuple}``,
  what the txn saw of each key it read, BEFORE its own appends; ``appends``
  = ``{key: tuple}``, the values it appended, in order.  A txn that scanned
  has a fifth member, ``scans``: one ``((lo, hi), rows)`` per scan of the
  half-open key range ``[lo, hi)``, ``rows`` = ``[(key, tuple), ...]`` as
  answered: the keys that held something, ascending.  Keys that are scanned
  must be mutually orderable.
- ``unanswered``: one ``(start, appends)`` per txn with appends whose answer
  never came (refused, failed, timed out): it took effect whole or not at
  all, and not before ``start``.
- ``finals``: ``{key: tuple}``, each key's list at the end.
- ``initial`` (optional): ``{key: tuple}``, what the store held before the
  first txn: it replays as one txn answered before every other began.

A scan is a read of EVERY key that ever holds anything in its range: a key
it did not return was read as empty.  So a scan that missed a record
inserted before it (a phantom) or returned a list shorter than one already
acknowledged (a stale scan) is ordered before that write by what it saw and
after it by real time, and fails like any stale read; in step (d) the plain
store scans its own keys in order and must return exactly the rows.

Steps: (a) each key's final list is that key's version order: no value
twice, every value from a txn of the input, every acknowledged append in
it, every read a prefix of it; (b) the txns are ordered by those positions
(the writer of position i before the writer of i+1; a read of length n after
the writer of position n-1 and before the writer of position n) AND by real
time (a txn answered before another was submitted comes first); (c) no such
order = a cycle, which is named; (d) the txns run in that order on the
plain store: every read must equal what the store held, and the end state
the final lists.

It imports nothing of the system it checks, and is independent of the cycle
checkers beside it (sim/verifier.py, sim/elle.py): those search a history
for anomalies, this builds the serial execution itself.  One copy lives in
accord_tpu/sim/ for the tests and one in benchmarks/lib/ for the benchmark;
tests/test_serial_kv.py holds the two to the same text."""

from bisect import bisect_left, insort


class NotSerial(AssertionError):
    """No serial execution explains the history.  ``kind`` is one of
    ``duplicate``, ``phantom``, ``missing-ack``, ``atomicity``,
    ``non-prefix``, ``cycle``, ``stale-read``, ``real-time``,
    ``read-mismatch``, ``final-mismatch``, ``scan-shape``,
    ``scan-mismatch``."""

    def __init__(self, kind, text):
        super().__init__(f"{kind}: {text}")
        self.kind = kind


class SerialKV:
    """The plain store: one txn at a time, reads before appends."""

    def __init__(self):
        self.lists = {}
        self.keys = []               # the keys of ``lists``, ascending

    def execute(self, read_keys, appends):
        seen = {k: tuple(self.lists.get(k, ())) for k in read_keys}
        for k, values in appends.items():
            if k not in self.lists:
                self.lists[k] = []
                insort(self.keys, k)
            self.lists[k].extend(values)
        return seen

    def scan(self, lo, hi):
        """The keys in [lo, hi) that hold something, ascending, with
        their lists."""
        held = self.keys[bisect_left(self.keys, lo):
                         bisect_left(self.keys, hi)]
        return [(k, tuple(self.lists[k])) for k in held if self.lists[k]]

    def state(self):
        return {k: tuple(v) for k, v in self.lists.items() if v}


class _Txn:
    __slots__ = ("name", "start", "end", "reads", "appends", "scans")

    def __init__(self, name, start, end, reads, appends, scans=()):
        self.name, self.start, self.end = name, start, end
        self.reads, self.appends, self.scans = reads, appends, scans


def _expand_scans(txns, finals):
    """A scan as reads: of every key of ``finals`` in its range (one it
    did not return was read as empty) and of every key it returned."""
    known = None
    for t in txns:
        for (lo, hi), rows in t.scans:
            if known is None:
                known = sorted(k for k, final in finals.items() if final)
            keys = [k for k, _values in rows]
            if any(not lo <= k < hi for k in keys) or any(
                    a >= b for a, b in zip(keys, keys[1:])):
                raise NotSerial("scan-shape", f"{t.name} scanned [{lo!r}, "
                                f"{hi!r}) and was answered the keys "
                                f"{keys[:8]!r}: not ascending inside it")
            seen = dict.fromkeys(
                known[bisect_left(known, lo):bisect_left(known, hi)], ())
            seen.update((k, tuple(values)) for k, values in rows)
            for k, values in seen.items():
                if tuple(t.reads.setdefault(k, values)) != values:
                    raise NotSerial("read-mismatch", f"{t.name} saw key "
                                    f"{k!r} as {t.reads[k]!r} and, in its "
                                    f"scan, as {values!r}")


def _version_orders(txns, finals):
    """Step (a).  Returns ``{key: [writer txn index per position]}`` and the
    set of unanswered txns that never took effect."""
    writer_of = {}
    for i, t in enumerate(txns):
        for k, values in t.appends.items():
            for v in values:
                if (k, v) in writer_of:
                    raise NotSerial("duplicate", f"value {v!r} of key {k!r} "
                                    f"is appended by "
                                    f"{txns[writer_of[k, v]].name} and by "
                                    f"{t.name}")
                writer_of[k, v] = i
    writers = {}
    for k, final in finals.items():
        if len(set(final)) != len(final):
            raise NotSerial("duplicate", f"key {k!r} holds a value twice: "
                            f"{final!r}")
        for pos, v in enumerate(final):
            if (k, v) not in writer_of:
                raise NotSerial("phantom", f"key {k!r} ends with {v!r} at "
                                f"position {pos}, which no txn appended")
        writers[k] = [writer_of[k, v] for v in final]
    dropped = set()
    for i, t in enumerate(txns):
        held = [v in finals.get(k, ()) for k, values in t.appends.items()
                for v in values]
        if t.end is None and not any(held):
            dropped.add(i)
            continue
        if not all(held):
            if t.end is None:
                raise NotSerial("atomicity", f"{t.name} took effect in "
                                f"part: {t.appends!r}")
            k, v = next((k, v) for k, values in t.appends.items()
                        for v in values if v not in finals.get(k, ()))
            raise NotSerial("missing-ack", f"{t.name} was acknowledged, yet "
                            f"its append {v!r} is not in key {k!r}'s final "
                            f"list")
        for k, values in t.appends.items():
            final = finals.get(k, ())
            at = final.index(values[0]) if values else 0
            if final[at:at + len(values)] != tuple(values):
                raise NotSerial("atomicity", f"{t.name}'s appends {values!r}"
                                f" to key {k!r} do not stand together in "
                                f"{final!r}")
        for k, seen in t.reads.items():
            final = finals.get(k, ())
            if final[:len(seen)] != tuple(seen):
                raise NotSerial("non-prefix", f"{t.name} read {seen!r} of "
                                f"key {k!r}, no prefix of the final "
                                f"{final!r}")
    return writers, dropped


def _edges(txns, writers, dropped):
    """Step (b): ``succ[a] = {b: kind}`` over txn indices 0..n-1 and, from n
    on, one barrier per answered txn in order of ``end``: a txn points at
    its own barrier, each barrier at the next, and the last barrier before a
    txn's ``start`` at that txn, so real time costs O(n) edges."""
    n = len(txns)
    succ = {}

    def add(a, b, kind):
        if a != b:
            succ.setdefault(a, {}).setdefault(b, kind)

    for k, row in writers.items():
        for pos in range(1, len(row)):
            add(row[pos - 1], row[pos], "ww")
    for i, t in enumerate(txns):
        for k, seen in t.reads.items():
            row = writers.get(k, ())
            if seen:
                add(row[len(seen) - 1], i, "wr")
            if len(seen) < len(row):
                add(i, row[len(seen)], "rw")
    done = sorted((t.end, i) for i, t in enumerate(txns) if t.end is not None)
    ends = [e for e, _i in done]
    for at, (_e, i) in enumerate(done):
        add(i, n + at, "rt")
        if at:
            add(n + at - 1, n + at, "rt")
    for i, t in enumerate(txns):
        if i in dropped:
            continue
        before = bisect_left(ends, t.start)      # ends strictly before start
        if before:
            add(n + before - 1, i, "rt")
    return succ, n + len(done)


def _name_cycle(txns, succ, left):
    """A cycle among the nodes a topological sort could not place, as
    ``(text, kinds)``; a run of barriers reads as one real-time edge."""
    left = set(left)
    pred = {}
    for a in left:
        for b in succ.get(a, ()):
            if b in left:
                pred.setdefault(b, a)
    node, seen = min(left), {}
    while node not in seen:          # every node left has a predecessor left
        seen[node] = len(seen)
        node = pred[node]
    cycle = [x for x in seen if seen[x] >= seen[node]]
    cycle.reverse()                  # predecessors were walked: forward now
    n = len(txns)
    parts, kinds = [], set()
    for at, a in enumerate(cycle):
        if a >= n:
            continue
        b = cycle[(at + 1) % len(cycle)]
        kind = "rt" if b >= n else succ[a][b]
        kinds.add(kind)
        parts.append(f"{txns[a].name} -{kind}->")
    return " ".join(parts + parts[:1])[:-len(" -xx->")], kinds


def _serial_order(txns, succ, n_nodes, dropped):
    """Step (c): Kahn's sort, oldest submission first among the ready."""
    import heapq
    indeg = [0] * n_nodes
    for a, row in succ.items():
        for b in row:
            indeg[b] += 1
    n = len(txns)

    def key(x):                      # barriers first: they hold nothing up
        return (txns[x].start, x) if x < n else (float("-inf"), x)

    ready = [(key(x), x) for x in range(n_nodes)
             if not indeg[x] and x not in dropped]
    heapq.heapify(ready)
    order, placed = [], 0
    while ready:
        _k, a = heapq.heappop(ready)
        placed += 1
        if a < n:
            order.append(a)
        for b in succ.get(a, ()):
            indeg[b] -= 1
            if not indeg[b]:
                heapq.heappush(ready, (key(b), b))
    if placed < n_nodes - len(dropped):
        left = [x for x in range(n_nodes) if indeg[x] and x not in dropped]
        text, kinds = _name_cycle(txns, succ, left)
        kind = ("cycle" if "rt" not in kinds
                else "stale-read" if "rw" in kinds else "real-time")
        raise NotSerial(kind, f"no serial order exists: {text}")
    return order


def replay(answered, unanswered, finals, initial=None):
    """Build the serial execution and run it on a fresh ``SerialKV``.
    Returns the order as indices: ``i`` for ``answered[i]``,
    ``len(answered) + j`` for ``unanswered[j]`` (those that took effect).
    Raises ``NotSerial``."""
    txns = [_Txn(f"txn {i}", s, e, dict(r), dict(a), *scans)
            for i, (s, e, r, a, *scans) in enumerate(answered)]
    txns += [_Txn(f"unanswered txn {j}", s, None, {}, dict(a))
             for j, (s, a) in enumerate(unanswered)]
    n_given = len(txns)
    if initial:
        before = float("-inf")       # answered before every other began
        txns.append(_Txn("the initial state", before, before, {},
                         dict(sorted(initial.items()))))
    finals = {k: tuple(v) for k, v in finals.items()}
    _expand_scans(txns, finals)
    writers, dropped = _version_orders(txns, finals)
    succ, n_nodes = _edges(txns, writers, dropped)
    order = _serial_order(txns, succ, n_nodes, dropped)
    store = SerialKV()
    for i in order:                  # step (d)
        t = txns[i]
        for (lo, hi), rows in t.scans:
            held = store.scan(lo, hi)
            if held != [(k, tuple(values)) for k, values in rows]:
                odd = sorted(set(dict(held)) ^ {k for k, _v in rows})[:5]
                raise NotSerial("scan-mismatch", f"{t.name} scanned "
                                f"[{lo!r}, {hi!r}) and was answered "
                                f"{len(rows)} rows; the serial store held "
                                f"{len(held)} there (keys apart: {odd!r})")
        seen = store.execute(t.reads, t.appends)
        for k, want in t.reads.items():
            if seen[k] != tuple(want):
                raise NotSerial("read-mismatch", f"{t.name} read "
                                f"{tuple(want)!r} of key {k!r}; the serial "
                                f"store held {seen[k]!r}")
    end = store.state()
    if end != {k: v for k, v in finals.items() if v}:
        odd = sorted(k for k in set(end) | set(finals)
                     if end.get(k, ()) != finals.get(k, ()))[:5]
        raise NotSerial("final-mismatch", f"the serial store ends unlike "
                        f"the final lists on keys {odd!r}")
    return [i for i in order if i < n_given]
