"""The attribution index is MAINTAINED by token (PR 29): after every step
of a random history the index DeviceState._attr_index hands out answers
the host route's readers exactly as a from-scratch build of the deleted
eager form does (tests/attr_index_oracle.py), and assembles the same
padded device arrays byte for byte; a flush with no dirty token does no
per-token work whatever the index holds; an index handed out at a flush's
begin is never written afterwards; every CommandsForKey pivot mutation
marks its token."""

import numpy as np
import pytest

from accord_tpu.local.commands_for_key import CommandsForKey, InternalStatus
from accord_tpu.ops import deps_kernel as dk
from accord_tpu.ops.packing import to_i64
from accord_tpu.primitives.deps import DepsBuilder
from accord_tpu.primitives.keys import IntKey, Keys, Range, Ranges
from accord_tpu.primitives.timestamp import (Domain, Timestamp, TxnId,
                                             TxnKind)
from accord_tpu.utils.random_source import RandomSource

from tests.attr_index_oracle import build_reference
from tests.conftest import (DispatchTestNode, DispatchTestStoreShim,
                            make_device_state)
from tests.test_exact_collect import _build_attr_store
from tests.test_routing import _enqueue_flush, _unpack_builders

HOT = 40          # token space of the histories
# an epoch whose packed msb has the top bit set: the 128-bit compares are
# UNSIGNED on the packed words, and a signed compare would order these
# below epoch 1
EPOCHS = (1, (1 << 47) + 3)


class _History:
    """A store driven the way local/commands.py drives one (CommandsForKey
    update + DeviceState register / update_status, in either order), plus
    the maintenance writes (remove, prune, RedundantBefore) and the two
    orders a test store can produce that the protocol cannot: a
    CommandsForKey written with no word to the DeviceState, and a
    DeviceState told of a write whose CommandsForKey does not exist yet.
    ``registry`` is what the deleted form kept as ``_elide_tokens``."""

    def __init__(self, seed, epochs=EPOCHS, in_sync=False):
        # in_sync: only what the protocol can produce (every pivot the
        # CommandsForKey holds was driven on the DeviceState too — the
        # invariant the reference attribution of tests/deps_oracle.py,
        # which reads pivots off EVERY key, shares with the index)
        self.epochs, self.in_sync = epochs, in_sync
        self.rs = RandomSource(seed)
        self.store, self.dev, self.safe = make_device_state(mesh=None)
        self.registry = set()
        self.txns = []            # [tid, toks, status, exec_at, late_cfk]
        self.hlc = 10

    def cfk(self, t):
        c = self.store.commands_for_key.get(t)
        if c is None:
            c = self.store.commands_for_key[t] = CommandsForKey(t)
        return c

    def _next_id(self, kind, domain):
        self.hlc += 1 + self.rs.next_int(5)
        return TxnId.create(
            self.epochs[self.rs.next_int(len(self.epochs))], self.hlc, kind,
            domain, 1 + self.rs.next_int(4))

    def _tell(self, tid, toks, status, exec_at, late_cfk):
        """One transition on both sides, in either order."""
        def dev_side():
            if self.dev.deps.slot_of.get(tid) is None:
                return
            self.dev.update_status(tid, int(status), execute_at=exec_at)
            if InternalStatus.COMMITTED <= status <= InternalStatus.APPLIED \
                    and tid.kind().is_write() \
                    and tid.domain() == Domain.Key:
                self.registry.update(toks)

        def cfk_side():
            if late_cfk:
                return
            for t in toks:
                self.cfk(t).update(tid, status, execute_at=exec_at)

        first, second = (dev_side, cfk_side) if self.rs.next_int(2) \
            else (cfk_side, dev_side)
        first()
        second()

    def step(self):
        rs = self.rs
        draw = rs.next_int(100)
        live = [x for x in self.txns if x[2] is not None]
        if draw < 30 or not live:
            kind = TxnKind.Write if rs.next_int(10) < 8 else TxnKind.Read
            if rs.next_int(10) < 8:
                toks = sorted({rs.next_int(HOT)
                               for _ in range(1 + rs.next_int(3))})
                tid = self._next_id(kind, Domain.Key)
                keys = Keys([IntKey(t) for t in toks])
            else:
                s0 = rs.next_int(HOT)
                toks = []
                tid = self._next_id(kind, Domain.Range)
                keys = Ranges.of(Range(s0, s0 + 1 + rs.next_int(6)))
            late_cfk = bool(toks) and rs.next_int(12) == 0 \
                and not self.in_sync
            self.dev.register(tid, int(InternalStatus.PREACCEPTED), keys)
            if not late_cfk:
                for t in toks:
                    self.cfk(t).update(tid, InternalStatus.PREACCEPTED)
            self.txns.append([tid, toks, InternalStatus.PREACCEPTED, None,
                              late_cfk])
            return "register"
        x = live[rs.next_int(len(live))]
        tid, toks, status, exec_at, late_cfk = x
        if draw < 55:
            if exec_at is None or rs.next_int(4) == 0:
                # decided, or a decided write's executeAt MOVING
                exec_at = tid if rs.next_int(3) else Timestamp(
                    tid.msb, tid.lsb + ((1 + rs.next_int(40)) << 16),
                    tid.node)
            status = max(status, InternalStatus.COMMITTED
                         if rs.next_int(3) else InternalStatus.STABLE)
            x[2], x[3] = status, exec_at
            self._tell(tid, toks, status, exec_at, late_cfk)
            return "commit"
        if draw < 65 and exec_at is not None:
            x[2] = InternalStatus.APPLIED
            self._tell(tid, toks, InternalStatus.APPLIED, exec_at, late_cfk)
            return "apply"
        if draw < 72 and status < InternalStatus.COMMITTED:
            x[2] = InternalStatus.INVALIDATED
            self._tell(tid, toks, InternalStatus.INVALIDATED, None, late_cfk)
            return "invalidate"
        if draw < 80:
            # truncation (cleanup._release_indexes): the slot is freed and
            # the per-key entries dropped; the DeviceState hears of no
            # status
            self.dev.free(tid)
            for t in toks:
                c = self.store.commands_for_key.get(t)
                if c is not None:
                    c.remove(tid)
            x[2] = None
            return "remove"
        if draw < 86 and late_cfk:
            # the CommandsForKey appears after the DeviceState was told
            x[4] = False
            for t in toks:
                self.cfk(t).update(tid, status, execute_at=exec_at)
            return "late-cfk"
        if draw < 92 and self.store.commands_for_key:
            toks_held = sorted(self.store.commands_for_key)
            c = self.store.commands_for_key[
                toks_held[rs.next_int(len(toks_held))]]
            ids = c.txn_ids()
            if ids:
                c.set_prune_before(ids[rs.next_int(len(ids))])
                c.prune()
            return "prune"
        s0 = rs.next_int(HOT)
        self.store.redundant_before.add_redundant(
            Ranges.of(Range(s0, s0 + 1 + rs.next_int(10))),
            TxnId.create(1, 1 + rs.next_int(self.hlc),
                         TxnKind.ExclusiveSyncPoint, Domain.Range, 1))
        return "redundant-before"

    # -- probes -----------------------------------------------------------
    def probe_queries(self, n=6):
        rs = self.rs
        qs = []
        for _ in range(n):
            bound = TxnId.create(self.epochs[rs.next_int(len(self.epochs))],
                                 1 + rs.next_int(self.hlc + 20),
                                 TxnKind.Write, Domain.Key, 1)
            toks, rngs = [], []
            for _ in range(1 + rs.next_int(3)):
                if rs.next_int(10) < 7:
                    toks.append(rs.next_int(HOT))
                else:
                    s0 = rs.next_int(HOT)
                    rngs.append(Range(s0, s0 + 1 + rs.next_int(8)))
            qs.append((bound, bound, bound.kind().witnesses(), toks, rngs))
        q_m = 4
        qnp = dk.pack_query_matrix(
            [(sb, wit, t, r, tid) for (tid, sb, wit, t, r) in qs], q_m)
        return qs, qnp, q_m

    def probe_entries(self, nq, n=120):
        """Random (token, executeAt, query) entries: executeAts drawn AT,
        just below and just above the pivots the store holds, on tokens in
        and out of the index."""
        rs = self.rs
        pool = [ts for c in self.store.commands_for_key.values()
                for ts in c._committed_write_execs]
        tok = np.empty(n, np.int64)
        em = np.empty(n, np.int64)
        el = np.empty(n, np.int64)
        en = np.empty(n, np.int32)
        for i in range(n):
            tok[i] = rs.next_int(HOT + 4) - 2
            if pool and rs.next_int(4):
                ts = pool[rs.next_int(len(pool))]
                msb, lsb, node = ts.msb, ts.lsb + rs.next_int(3) - 1, \
                    ts.node + rs.next_int(3) - 1
            else:
                ts = TxnId.create(EPOCHS[rs.next_int(2)],
                                  1 + rs.next_int(self.hlc + 20),
                                  TxnKind.Write, Domain.Key, 1)
                msb, lsb, node = ts.msb, ts.lsb, ts.node
            em[i], el[i], en[i] = to_i64(msb), to_i64(lsb & ((1 << 64) - 1)), \
                node
        tb = np.array([rs.next_int(nq) for _ in range(n)], np.int64)
        return tok, em, el, en, tb


def _assert_same_index(h, where):
    aidx = h.dev._attr_index()
    ref = build_reference(h.dev, h.registry)
    qs, qnp, q_m = h.probe_queries()
    tok, em, el, en, tb = h.probe_entries(len(qs))
    assert (aidx.n_execs > 0) == (ref.u > 0), where
    assert np.array_equal(aidx.toks, ref.etok), where
    got = aidx.elide_decided(tok, em, el, en, tb, qnp)
    want = ref.elide_decided(tok, em, el, en, ref.rank_bounds(qnp)[tb]) \
        if ref.u else np.zeros(len(tok), bool)
    assert np.array_equal(got, want), where
    assert np.array_equal(aidx.keep_floor(tok, em, el, en),
                          ref.keep_floor(tok, em, el, en)), where
    floor = h.dev._batch_floor(qnp, q_m)[0]
    for f in (None, floor, TxnId.create(1, 7, TxnKind.ExclusiveSyncPoint,
                                        Domain.Range, 1)):
        assert aidx.floors_match(qnp, q_m, f) == \
            ref.floors_match(qnp, q_m, f), where
    assert np.array_equal(aidx.rank_bounds(qnp), ref.rank_bounds(qnp)), where
    assert len(aidx.pad) == len(ref.pad) == 11
    for i, (a, b) in enumerate(zip(aidx.pad, ref.pad)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape \
            and a.tobytes() == b.tobytes(), (where, i)
    return aidx


@pytest.mark.parametrize("seed", [5, 29, 71, 113])
def test_maintained_index_equals_from_scratch_build_after_every_step(seed):
    h = _History(seed)
    seen = set()
    for i in range(260):
        op = h.step()
        seen.add(op)
        _assert_same_index(h, f"seed={seed} step={i} op={op}")
    assert seen >= {"register", "commit", "apply", "invalidate", "remove",
                    "late-cfk", "prune", "redundant-before"}, seen
    assert h.dev.n_attr_refreshes > 0
    # every flush-side read above was O(dirty): far fewer token reads than
    # steps x tokens held
    assert h.dev.n_attr_tokens_refreshed < 260 * 4


@pytest.mark.parametrize("seed", [5, 29])
def test_product_flush_over_a_history_matches_the_reference(seed):
    """The whole flush over the maintained index, host route and device
    routes, builds the reference's Deps (tests/deps_oracle.py reads floors
    and pivots straight from the store) at points along a history."""
    from tests.test_routing import _attributed, _reference
    h = _History(seed, epochs=(1,), in_sync=True)
    for i in range(120):
        h.step()
        if i % 40 != 39:
            continue
        qs, _qnp, _q_m = h.probe_queries(8)
        want = _reference(h.dev, h.safe, qs)
        for route in ("host", "device", "dense"):
            h.dev.route_override = route
            assert _attributed(h.dev, h.safe, qs) == want, (seed, i, route)


def _committed_store(n_tokens):
    """A store with one committed key-domain write on each of n tokens."""
    store, dev, safe = make_device_state(mesh=None)
    tids = []
    for t in range(n_tokens):
        tid = TxnId.create(1, 10 + t, TxnKind.Write, Domain.Key, 1)
        dev.register(tid, int(InternalStatus.PREACCEPTED),
                     Keys([IntKey(t)]))
        c = store.commands_for_key[t] = CommandsForKey(t)
        c.update(tid, InternalStatus.COMMITTED, execute_at=tid)
        dev.update_status(tid, int(InternalStatus.COMMITTED),
                          execute_at=tid)
        tids.append(tid)
    return store, dev, safe, tids


def _point_queries(n_tokens, hlc=1 << 20):
    bound = TxnId.create(1, hlc, TxnKind.Write, Domain.Key, 1)
    return [(bound, bound, bound.kind().witnesses(), [t % n_tokens], [])
            for t in range(3)]


def test_flush_without_a_dirty_token_does_no_per_token_work(monkeypatch):
    reads = []
    real = CommandsForKey.packed_committed_execs

    def counted(self):
        reads.append(self.token)
        return real(self)

    monkeypatch.setattr(CommandsForKey, "packed_committed_execs", counted)
    per_size = {}
    for n in (50, 5000):
        store, dev, safe, tids = _committed_store(n)
        dev.route_override = "host"
        qs = _point_queries(n)
        flush = lambda: dev.deps_query_batch_attributed(   # noqa: E731
            safe, qs, [DepsBuilder() for _ in qs])
        flush()                               # reads every marked token
        assert len(dev._attr_index().toks) == n
        first = dev._attr_index()
        r0, k0 = dev.n_attr_refreshes, dev.n_attr_tokens_refreshed
        del reads[:]
        for _ in range(5):
            flush()
        assert dev._attr_index() is first     # the SAME index, O(1)
        assert (dev.n_attr_refreshes, dev.n_attr_tokens_refreshed) \
            == (r0, k0)
        assert reads == []
        # three tokens move: the next flush reads those three lists, in a
        # store of 50 as in one of 5,000
        for t in (3, 17, 41):
            tid = TxnId.create(1, 1 << 16, TxnKind.Write, Domain.Key, 2 + t)
            dev.register(tid, int(InternalStatus.PREACCEPTED),
                         Keys([IntKey(t)]))
            store.commands_for_key[t].update(
                tid, InternalStatus.COMMITTED, execute_at=tid)
            dev.update_status(tid, int(InternalStatus.COMMITTED),
                              execute_at=tid)
        flush()
        per_size[n] = (sorted(reads), dev.n_attr_refreshes - r0,
                       dev.n_attr_tokens_refreshed - k0)
        assert dev._attr_index() is not first
        assert dev.n_attr_device_builds == 0  # the host route asked for none
    assert per_size[50] == per_size[5000] == ([3, 17, 41], 1, 3)


def test_unmoved_lists_keep_the_index_and_its_device_image():
    """A token marked again with its list unchanged (Stable / Applied
    after Committed: the DeviceState is told, the pivots do not move)
    keeps the index and so the uploaded image."""
    store, dev, safe, tids = _committed_store(6)
    dev.route_override = "dense"
    qs = _point_queries(6)
    dev.deps_query_batch_attributed(safe, qs, [DepsBuilder() for _ in qs])
    first = dev._attr_index()
    assert dev.n_attr_device_builds == 1
    image = first.device()
    dev.update_status(tids[2], int(InternalStatus.APPLIED))
    store.commands_for_key[2].update(tids[2], InternalStatus.APPLIED)
    assert dev._attr_dirty == {2}
    dev.deps_query_batch_attributed(safe, qs, [DepsBuilder() for _ in qs])
    assert dev._attr_index() is first and first.device() is image
    assert dev.n_attr_device_builds == 1 and dev.n_attr_refreshes == 2


def _commit_over(store, dev, token, hlc):
    """A later committed write on ``token``: a new pivot that elides the
    decided deps below it."""
    tid = TxnId.create(1, hlc, TxnKind.Write, Domain.Key, 3)
    dev.register(tid, int(InternalStatus.PREACCEPTED),
                 Keys([IntKey(token)]))
    store.commands_for_key[token].update(tid, InternalStatus.COMMITTED,
                                         execute_at=tid)
    dev.update_status(tid, int(InternalStatus.COMMITTED), execute_at=tid)


@pytest.mark.parametrize("route", ["host", "dense", "device"])
def test_deferred_collect_reads_the_begin_time_index(route):
    """A handle begun before a commit collects against the index as it
    stood at begin, though a later flush refreshed the store's index."""
    outs = []
    for interleave in (False, True):
        dev, safe, qs = _build_attr_store(RandomSource(19), mesh=None)
        dev.route_override = route
        handle = dev.deps_query_batch_begin(qs)         # deferred
        held = handle[6]["aidx"]
        toks, packs = held.toks.copy(), list(held.packs)
        if interleave:
            for t in range(0, 24, 2):
                _commit_over(dev.store, dev, t, 60 * 90 + 1 + t)
            later = dev.deps_query_batch_begin(qs)
            assert later[6]["aidx"] is not held
            assert dev._attr_index() is later[6]["aidx"]
        builders = [DepsBuilder() for _ in qs]
        dev.deps_query_batch_end_attributed(safe, handle, builders)
        # never written after it was handed out
        assert np.array_equal(held.toks, toks)
        assert len(held.packs) == len(packs) \
            and all(a is b for a, b in zip(held.packs, packs))
        outs.append(_unpack_builders(builders))
        if interleave:
            # and the later flush does see the new pivots
            b2 = [DepsBuilder() for _ in qs]
            dev.deps_query_batch_end_attributed(safe, later, b2)
            assert dev.n_elided_decided > 0
    assert outs[0] == outs[1]


def test_fused_harvest_reads_the_begin_time_index():
    """The same through the dispatcher: commits that land between a fused
    launch and its harvest tasks (and a flush that refreshes the index
    meanwhile) do not move what the members harvest."""
    outs = []
    for interleave in (False, True):
        node = DispatchTestNode(fusion=True)
        stores = []
        for i, seed in enumerate((19, 43)):
            dev, safe, qs = _build_attr_store(RandomSource(seed), mesh=None)
            inner = dev.store
            dev.store = DispatchTestStoreShim(inner, node, i)
            dev.route_override = "dense"
            stores.append((dev, safe, qs))
        flushed = [_enqueue_flush(dev, qs) for dev, _safe, qs in stores]
        node.scheduler.q.pop(0)()           # the dispatcher's flush event
        assert node.dispatcher.n_fused_launches == 1
        if interleave:
            for dev, safe, qs in stores:
                held = dev._aidx
                for t in range(0, 24, 2):
                    _commit_over(dev.store, dev, t, 60 * 90 + 1 + t)
                assert dev._attr_index() is not held
        node.scheduler.run()                # the harvest tasks
        for builders, failures in flushed:
            assert not failures
        outs.append([_unpack_builders(b) for b, _f in flushed])
    assert outs[0] == outs[1]


def _cfk_write_sites():
    """Each public CommandsForKey write that changes the pivot list, as
    (name, prepare(cfk) -> act) — the sites of _elide_version bumps."""
    w1 = TxnId.create(1, 100, TxnKind.Write, Domain.Key, 1)
    w2 = TxnId.create(1, 200, TxnKind.Write, Domain.Key, 1)
    moved = Timestamp(w1.msb, w1.lsb + (5 << 16), w1.node)

    def insert_decided(c):
        return lambda: c.update(w1, InternalStatus.COMMITTED, execute_at=w1)

    def become_decided(c):
        c.update(w1, InternalStatus.PREACCEPTED)
        return lambda: c.update(w1, InternalStatus.COMMITTED, execute_at=w1)

    def execute_at_moves(c):
        c.update(w1, InternalStatus.COMMITTED, execute_at=w1)
        return lambda: c.update(w1, InternalStatus.STABLE, execute_at=moved)

    def invalidated_after_decided(c):
        c.update(w1, InternalStatus.COMMITTED, execute_at=w1)
        return lambda: c.update(w1, InternalStatus.INVALIDATED)

    def remove(c):
        c.update(w1, InternalStatus.COMMITTED, execute_at=w1)
        return lambda: c.remove(w1)

    def prune(c):
        c.update(w1, InternalStatus.APPLIED, execute_at=w1)
        c.update(w2, InternalStatus.COMMITTED, execute_at=w2)
        c.set_prune_before(w2)
        return lambda: c.prune()

    return [insert_decided, become_decided, execute_at_moves,
            invalidated_after_decided, remove, prune]


@pytest.mark.parametrize("site", _cfk_write_sites(),
                         ids=lambda f: f.__name__)
def test_every_pivot_mutation_marks_its_token(site):
    """Through each public CommandsForKey write that bumps _elide_version:
    the token lands in the attached DeviceState's dirty set, and the next
    index holds the list as the CommandsForKey has it."""
    store, dev, safe = make_device_state(mesh=None)
    token = 11
    c = store.commands_for_key[token] = CommandsForKey(token)
    # the index reads the key once (the store drove a decided write on it)
    seed_w = TxnId.create(1, 50, TxnKind.Write, Domain.Key, 2)
    dev.register(seed_w, int(InternalStatus.PREACCEPTED),
                 Keys([IntKey(token)]))
    c.update(seed_w, InternalStatus.COMMITTED, execute_at=seed_w)
    dev.update_status(seed_w, int(InternalStatus.COMMITTED),
                      execute_at=seed_w)
    act = site(c)
    dev._attr_index()
    assert c._elide_sink is dev._attr_dirty and not dev._attr_dirty
    v0 = c._elide_version
    act()
    assert c._elide_version > v0, "not a pivot mutation"
    assert dev._attr_dirty == {token}
    aidx = dev._attr_index()
    want = c.packed_committed_execs()
    assert aidx.toks.tolist() == [token] and aidx.packs[0] is want
    assert aidx.n_execs == len(want[0]) == len(c._committed_write_execs)
    ref = build_reference(dev, {token})
    assert all(np.asarray(a).tobytes() == np.asarray(b).tobytes()
               for a, b in zip(aidx.pad, ref.pad))


def test_no_mutation_of_the_pivot_list_escapes_cw_mutated():
    """The sink hangs on _cw_mutated: every statement of CommandsForKey
    that writes _committed_write_execs (_cw_add, _cw_drop, prune's
    rebuild) is followed, in its block, by the call — a new write site
    that forgot it would elide live deps."""
    import inspect
    import re
    from accord_tpu.local import commands_for_key as mod
    lines = inspect.getsource(mod.CommandsForKey).splitlines()
    writes = [i for i, ln in enumerate(lines)
              if re.search(r"(insort\(self\._committed_write_execs"
                           r"|self\._committed_write_execs\.\w+\("
                           r"|del self\._committed_write_execs"
                           r"|self\._committed_write_execs = )", ln)]
    assert len(writes) == 3, [lines[i] for i in writes]
    for i in writes:
        assert any("self._cw_mutated(" in ln for ln in lines[i:i + 10]), \
            lines[i]
