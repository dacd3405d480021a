"""Range-domain deps queries in the YCSB-E shape on every route: scans
(range reads: an interval as footprint, witnessing writes) over point
inserts, and inserts (key-domain writes) over the scans before them.  The
device routes give the host route's answer, builder for builder, anchored by
a brute force; ``n_range_queries`` / ``n_range_device_queries`` split the
range-domain queries from the key-domain ones, and keeping the mirror's
interval index is the timed kind ``range_index_sync``."""

import numpy as np
import pytest

from accord_tpu.local.commands_for_key import InternalStatus
from accord_tpu.primitives.keys import IntKey, Keys, Range, Ranges
from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
from tests import deps_oracle
from tests.conftest import make_device_state
from tests.test_routing import _brute, _unpack_builders

KEYSPACE = 50_000


def _build(seed, n=300):
    rng = np.random.default_rng(seed)
    store, dev, safe = make_device_state()
    entries = []
    hlcs = rng.choice(np.arange(1, 40 * n), size=n, replace=False)
    for i in range(n):
        if rng.random() < 0.8:       # a scan: one interval of 1-100 keys
            s = int(rng.integers(0, KEYSPACE - 5_000))
            toks, rngs = [], [Range(s, s + int(rng.integers(1, 5_000)))]
            tid = TxnId.create(1, int(hlcs[i]), TxnKind.Read, Domain.Range,
                               1 + int(rng.integers(0, 3)))
            keys = Ranges.of(*rngs)
        else:                        # an insert: one point
            toks, rngs = [int(rng.integers(0, KEYSPACE))], []
            tid = TxnId.create(1, int(hlcs[i]), TxnKind.Write, Domain.Key,
                               1 + int(rng.integers(0, 3)))
            keys = Keys([IntKey(t) for t in toks])
        dev.register(tid, int(InternalStatus.PREACCEPTED), keys)
        entries.append((tid, toks, rngs))
    qs = []
    for j in range(32):
        hlc = int(rng.integers(40 * n, 80 * n))
        if j % 4:
            s = int(rng.integers(0, KEYSPACE - 5_000))
            bound = TxnId.create(1, hlc, TxnKind.Read, Domain.Range, 1)
            toks, rngs = [], [Range(s, s + int(rng.integers(1, 5_000)))]
        else:
            bound = TxnId.create(1, hlc, TxnKind.Write, Domain.Key, 1)
            toks, rngs = [int(rng.integers(0, KEYSPACE))], []
        qs.append((bound, bound, bound.kind().witnesses(), toks, rngs))
    return store, dev, safe, entries, qs


@pytest.mark.parametrize("seed", [3, 17, 29])
def test_range_queries_agree_on_every_route_and_are_counted(seed):
    store, dev, safe, entries, qs = _build(seed)
    n_range = sum(1 for q in qs if q[0].domain() == Domain.Range)
    assert n_range == 24
    outs, counted = {}, {}
    for route in ("host", "device", "dense"):
        dev.route_override = route
        before = (dev.n_range_queries, dev.n_range_device_queries)
        outs[route] = deps_oracle.flush_builders(dev, safe, qs)
        counted[route] = (dev.n_range_queries - before[0],
                          dev.n_range_device_queries - before[1])
    base = _unpack_builders(outs["host"])
    assert _unpack_builders(outs["device"]) == base
    assert _unpack_builders(outs["dense"]) == base
    assert base == _unpack_builders(
        deps_oracle.reference_builders(dev, safe, qs))
    for q, got in zip(qs, deps_oracle.dep_ids(outs["host"])):
        assert got == _brute(entries, q)
    # a scan depends on the inserts into its range alone, an insert on the
    # scans over its key
    for q, got in zip(qs, deps_oracle.dep_ids(outs["host"])):
        want = Domain.Key if q[0].domain() == Domain.Range else Domain.Range
        assert all(tid.domain() == want or tid.kind() == TxnKind.Write
                   for tid in got)
    assert counted == {"host": (24, 0), "device": (24, 24),
                       "dense": (24, 24)}
    # every scan registered was one timed sync of the mirror's index
    n_scans = sum(1 for tid, _t, _r in entries
                  if tid.domain() == Domain.Range)
    calls, secs = dev.kernel_times["range_index_sync"]
    assert calls == n_scans and secs > 0


def test_the_adaptive_router_counts_what_it_chose():
    store, dev, safe, entries, qs = _build(5)
    routes = []
    dev.on_route = lambda route, nq: routes.append(route)
    deps_oracle.flush_builders(dev, safe, qs)
    assert dev.n_range_queries == 24
    assert dev.n_range_device_queries == (0 if routes == ["host"] else 24)
