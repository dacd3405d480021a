"""Multi-chip shard parallelism over a jax.sharding.Mesh.

The reference's shard parallelism is key-space ranges -> one single-threaded
CommandStore each, with scatter-gather mapReduce across intersecting stores
(ref: accord-core/src/main/java/accord/local/CommandStores.java:575-643).
Here the analogue is the conflict-index slot dimension sharded across TPU
devices: every device owns a contiguous slice of the SoA table, deps queries
are replicated, each device scans its slice, and cross-shard combination
(the reference's ``Deps.merge`` over PreAccept replies, Deps.java:256) rides
ICI as all-gathers/maxes instead of host fan-in.

Collective pattern per protocol step:
- deps-calc: embarrassingly parallel over slots; dep-mask columns stay
  sharded; per-shard max-conflict is all-gathered and lex-max-reduced.
- drain: row-sharded blocking matrix; each fixpoint sweep all-gathers the
  applied frontier (one small bool vector), does the local masked matvec,
  and contributes its slice of the new frontier — the standard sharded
  matvec recurrence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.deps_kernel import (SLOT_APPLIED, SLOT_COMMITTED, SLOT_FREE,
                               SLOT_INVALIDATED, SLOT_STABLE, BucketTable,
                               DepsQuery, DepsTable, _entry_rows,
                               calculate_deps)
from ..ops.drain_kernel import DrainState
from ..ops.packing import masked_ts_max, ts_lt

STORE_AXIS = "store"


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(n_devices: int = None) -> Mesh:
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(devices, (STORE_AXIS,))


def shard_table(mesh: Mesh, table: DepsTable) -> DepsTable:
    """Place the slot dimension across the mesh; capacity must divide evenly."""
    from ..utils import faults
    faults.check("transfer", "shard_table upload")
    s1 = NamedSharding(mesh, P(STORE_AXIS))
    s2 = NamedSharding(mesh, P(STORE_AXIS, None))
    return DepsTable(
        msb=jax.device_put(table.msb, s1), lsb=jax.device_put(table.lsb, s1),
        node=jax.device_put(table.node, s1), kind=jax.device_put(table.kind, s1),
        status=jax.device_put(table.status, s1),
        lo=jax.device_put(table.lo, s2), hi=jax.device_put(table.hi, s2),
    )


def assemble_slices(mesh: Mesh, shards, shape, two_d: bool = False):
    """Zero-copy assembly of per-device slice buffers into ONE globally
    sharded array (the r21 store-shard residency path): each element of
    ``shards`` is a single-device array already resident on its mesh
    device, and make_array_from_single_device_arrays only records the
    placement — no bytes move.  ``shape`` is the global shape; ``two_d``
    selects the (slot, interval) layout whose second axis is unsharded."""
    spec = P(STORE_AXIS, None) if two_d else P(STORE_AXIS)
    return jax.make_array_from_single_device_arrays(
        tuple(shape), NamedSharding(mesh, spec), list(shards))


def sharded_calculate_deps(mesh: Mesh):
    """Build the pjit-ted cross-shard deps computation for ``mesh``.

    Returns fn(table, query, prune_msb, prune_lsb, prune_node) ->
    (dep_mask bool[B, N] column-sharded, max_conflict (msb, lsb, node)[B]
    replicated).  The prune floor is the store's RedundantBefore watermark,
    replicated to every shard.
    """
    table_specs = DepsTable(P(STORE_AXIS), P(STORE_AXIS), P(STORE_AXIS),
                            P(STORE_AXIS), P(STORE_AXIS),
                            P(STORE_AXIS, None), P(STORE_AXIS, None))
    query_specs = DepsQuery(P(), P(), P(), P(), P(None, None), P(None, None),
                            P(), P(), P())

    def local(table: DepsTable, query: DepsQuery, pm, pl, pn):
        dep_mask, (mm, ml, mn) = calculate_deps(table, query, pm, pl, pn)
        # cross-shard Deps.merge: gather every shard's max-conflict candidate
        # and reduce lexicographically (rides ICI; BASELINE.json config #5)
        gm = lax.all_gather(mm, STORE_AXIS, axis=0)   # [n_shards, B]
        gl = lax.all_gather(ml, STORE_AXIS, axis=0)
        gn = lax.all_gather(mn, STORE_AXIS, axis=0)
        nonzero = (gm != 0) | (gl != 0) | (gn != 0)
        mm2, ml2, mn2 = masked_ts_max(gm.swapaxes(0, 1), gl.swapaxes(0, 1),
                                      gn.swapaxes(0, 1), nonzero.swapaxes(0, 1))
        return dep_mask, (mm2, ml2, mn2)

    fn = _shard_map(local, mesh,
                    (table_specs, query_specs, P(), P(), P()),
                    (P(None, STORE_AXIS), (P(), P(), P())))
    jitted = jax.jit(fn)

    def call(table, query, prune_msb=None, prune_lsb=None, prune_node=None):
        if prune_msb is None:
            prune_msb = jnp.zeros((), jnp.int64)
            prune_lsb = jnp.zeros((), jnp.int64)
            prune_node = jnp.zeros((), jnp.int32)
        return jitted(table, query, prune_msb, prune_lsb, prune_node)

    return call


def sharded_drain(mesh: Mesh):
    """Row-sharded fixpoint drain: fn(state) -> (applied[N], newly[N]),
    both replicated on exit."""
    state_specs = DrainState(P(STORE_AXIS, None), P(STORE_AXIS),
                             P(STORE_AXIS), P(STORE_AXIS), P(STORE_AXIS),
                             P(STORE_AXIS))

    def local(state: DrainState):
        # exec timestamps of potential deps (columns) must be visible to every
        # row shard: gather them once up front.
        full_em = lax.all_gather(state.exec_msb, STORE_AXIS, axis=0, tiled=True)
        full_el = lax.all_gather(state.exec_lsb, STORE_AXIS, axis=0, tiled=True)
        full_en = lax.all_gather(state.exec_node, STORE_AXIS, axis=0, tiled=True)
        full_status = lax.all_gather(state.status, STORE_AXIS, axis=0, tiled=True)
        # blocking matrix with row-local exec vs full-column exec
        undecided = (full_status >= 0) & (full_status < SLOT_COMMITTED)
        dead = (full_status == SLOT_INVALIDATED) | (full_status == SLOT_FREE)
        exec_before = ts_lt(full_em[None, :], full_el[None, :], full_en[None, :],
                            state.exec_msb[:, None], state.exec_lsb[:, None],
                            state.exec_node[:, None])
        blocking = state.adj & (undecided[None, :] | exec_before |
                                state.awaits_all[:, None]) & ~dead[None, :]
        blk = blocking.astype(jnp.bfloat16)

        stable_local = state.status == SLOT_STABLE
        applied_local0 = state.status == SLOT_APPLIED

        def body(carry):
            applied_local, _ = carry
            applied_full = lax.all_gather(applied_local, STORE_AXIS, axis=0,
                                          tiled=True)
            unapplied = (~applied_full).astype(jnp.bfloat16)
            waiting = (blk @ unapplied) > 0.5
            ready = stable_local & ~applied_local & ~waiting
            return applied_local | ready, jnp.any(lax.all_gather(
                ready, STORE_AXIS, axis=0, tiled=True))

        applied_local, _ = lax.while_loop(lambda c: c[1], body,
                                          (applied_local0, jnp.bool_(True)))
        newly_local = applied_local & ~applied_local0
        return applied_local, newly_local

    fn = _shard_map(local, mesh, (state_specs,),
                    (P(STORE_AXIS), P(STORE_AXIS)))
    return jax.jit(fn)


_FRONTIER_CACHE = {}


def sharded_ready_frontier(mesh: Mesh):
    """Row-sharded single frontier sweep — the live ``DeviceState._tick``
    path under a mesh (the fixpoint variant above is ``sharded_drain``; the
    tick wants one sweep because the host re-validates and applies each
    candidate before the next sweep's statuses are known).  fn(state) ->
    ready bool[N] replicated."""
    key = tuple(d.id for d in mesh.devices.flat)
    fn = _FRONTIER_CACHE.get(key)
    if fn is not None:
        return fn
    state_specs = DrainState(P(STORE_AXIS, None), P(STORE_AXIS),
                             P(STORE_AXIS), P(STORE_AXIS), P(STORE_AXIS),
                             P(STORE_AXIS))

    def local(state: DrainState):
        full_em = lax.all_gather(state.exec_msb, STORE_AXIS, axis=0, tiled=True)
        full_el = lax.all_gather(state.exec_lsb, STORE_AXIS, axis=0, tiled=True)
        full_en = lax.all_gather(state.exec_node, STORE_AXIS, axis=0, tiled=True)
        full_status = lax.all_gather(state.status, STORE_AXIS, axis=0,
                                     tiled=True)
        undecided = (full_status >= 0) & (full_status < SLOT_COMMITTED)
        dead = (full_status == SLOT_INVALIDATED) | (full_status == SLOT_FREE)
        exec_before = ts_lt(full_em[None, :], full_el[None, :], full_en[None, :],
                            state.exec_msb[:, None], state.exec_lsb[:, None],
                            state.exec_node[:, None])
        blocking = state.adj & (undecided[None, :] | exec_before |
                                state.awaits_all[:, None]) & ~dead[None, :]
        applied = full_status == SLOT_APPLIED
        waiting = jnp.any(blocking & ~applied[None, :], axis=1)
        ready_local = (state.status == SLOT_STABLE) & ~waiting
        return lax.all_gather(ready_local, STORE_AXIS, axis=0, tiled=True)

    fn = jax.jit(_shard_map(local, mesh, (state_specs,), P()))
    _FRONTIER_CACHE[key] = fn
    return fn


def shard_bucket_table(mesh: Mesh, buckets: BucketTable) -> BucketTable:
    """Place a BucketTable's bucket-row and wide dimensions across the mesh
    (row counts must divide the device count evenly)."""
    from ..utils import faults
    faults.check("transfer", "shard_bucket_table upload")
    s2 = NamedSharding(mesh, P(STORE_AXIS, None))
    s1 = NamedSharding(mesh, P(STORE_AXIS))
    return BucketTable(*[jax.device_put(a, s2) for a in buckets[:8]],
                       *[jax.device_put(a, s1) for a in buckets[8:]])


def sharded_protocol_step(mesh: Mesh):
    """The fused multi-chip step: deps for a query batch + execution drain.

    This is the unit the driver dry-runs: one device step advancing a sharded
    store through PreAccept deps-calc and the execution frontier.
    """
    deps_fn = sharded_calculate_deps(mesh)
    drain_fn = sharded_drain(mesh)

    def step(table: DepsTable, query: DepsQuery, state: DrainState):
        dep_mask, max_conflict = deps_fn(table, query)
        applied, newly = drain_fn(state)
        return dep_mask, max_conflict, applied, newly

    return step


# -- device-resident attribution (r15): sharded attributed kernels ------------
#
# The attributed variants return ONE merged per-store CSR block instead of D
# per-shard blocks: every shard computes its slice's attributed entries, the
# shard results are all-gathered over ICI, and the cross-shard merge — the
# reference's ``Deps.merge`` — happens ON DEVICE: per-row concatenation in
# (row, code) order via one flat sort, cross-shard dedupe (bucketed only:
# slot-sharded dense slices are disjoint by construction), and a recompacted
# merged row_end.  The host downloads one replicated block (header int32[5+B]
# in the attributed layout, entries int64/int32[d * s]) and hands it straight
# to the shared block finalize — no host-side shard offsetting, no global
# triple dedupe pass.


def _merge_shard_blocks(hdrs, ents, b: int, s: int, codespace: int,
                        dedupe_key_m: int, dom=None, mq: int = None):
    """The on-device cross-shard merge: ``hdrs`` int32[d, 5+B], ``ents``
    [d, s] GLOBAL codes.  ``dedupe_key_m`` > 0 enables the bucketed
    cross-shard dedupe (identical codes + key-domain same-(slot, col)
    runs; needs ``dom``/``mq`` for the key-domain test).  Replicated
    output: (header int32[5+B], entries [d*s])."""
    d = hdrs.shape[0]
    totals = hdrs[:, 0].astype(jnp.int64)
    row_end = hdrs[:, 5:].astype(jnp.int64)                    # [d, B]
    pos = jnp.arange(s, dtype=jnp.int64)
    row_of = jax.vmap(lambda re: _entry_rows(re, s))(row_end)   # [d, s]
    live = pos[None, :] < totals[:, None]
    inf = jnp.int64(np.iinfo(np.int64).max)
    code = ents.astype(jnp.int64)
    comp = jnp.where(live, row_of * jnp.int64(codespace) + code, inf)
    comp = jnp.sort(comp.reshape(-1))                          # [d*s]
    keep = comp != inf
    if dedupe_key_m:
        first = jnp.concatenate([jnp.ones(1, bool), comp[1:] != comp[:-1]])
        pair = comp // jnp.int64(dedupe_key_m)                 # (row,slot,col)
        firstp = jnp.concatenate([jnp.ones(1, bool), pair[1:] != pair[:-1]])
        mcode = comp % jnp.int64(codespace)
        is_key = dom[jnp.clip(mcode // jnp.int64(mq), 0,
                              dom.shape[0] - 1)] == 0
        keep = keep & first & (~is_key | firstp)
    out_pos = jnp.cumsum(keep) - 1
    merged_row = jnp.where(keep, comp // jnp.int64(codespace), 0)
    counts = jnp.zeros(b, jnp.int64).at[merged_row].add(
        keep.astype(jnp.int64), mode="drop")
    m_end = jnp.cumsum(counts)
    out = jnp.full(d * s, -1, ents.dtype)
    out = out.at[jnp.where(keep, out_pos, d * s)].set(
        (comp % jnp.int64(codespace)).astype(ents.dtype), mode="drop")
    header = jnp.concatenate(
        [jnp.stack([m_end[-1], jnp.max(hdrs[:, 1].astype(jnp.int64)),
                    jnp.max(hdrs[:, 2].astype(jnp.int64)),
                    jnp.sum(hdrs[:, 3].astype(jnp.int64)),
                    jnp.sum(hdrs[:, 4].astype(jnp.int64))]).astype(jnp.int32),
         m_end.astype(jnp.int32)])
    return header, out


_ATTR_SH_CACHE = {}


def sharded_flat_attr(mesh: Mesh, m: int, s: int, k: int,
                      wide: bool = False, floors: bool = True,
                      elide: bool = True):
    """Mesh-sharded calculate_deps_flat_attr: slots sharded, attribution
    columns sharded ALONGSIDE the slots (each shard grades its own slice),
    the floor/elision index and query batch replicated.  Entries are
    globalized in-kernel (local code + shard offset) and merged on device;
    the host sees one block with GLOBAL slot codes.

    Returns fn(table, attr, aidx, qmat, rankb, pm, pl, pn) ->
    (header int32[5+B] replicated, entries [d*s] replicated)."""
    from ..ops import deps_kernel as dk
    dev_key = tuple(d.id for d in mesh.devices.flat)
    key = ("flat", dev_key, m, s, k, wide, floors, elide)
    fn = _ATTR_SH_CACHE.get(key)
    if fn is not None:
        return fn
    d = int(np.prod(list(mesh.shape.values())))
    table_specs = DepsTable(P(STORE_AXIS), P(STORE_AXIS), P(STORE_AXIS),
                            P(STORE_AXIS), P(STORE_AXIS),
                            P(STORE_AXIS, None), P(STORE_AXIS, None))
    attr_specs = dk.AttrCols(*([P(STORE_AXIS)] * 9))
    aidx_specs = dk.AttrIndex(*([P()] * 11))

    def local(table, attr, aidx, qmat, rankb, pm, pl, pn):
        hdr, ent = dk.flat_attr_local(table, attr, aidx, qmat, rankb,
                                      m, s, k, (pm, pl, pn), wide=wide,
                                      floors=floors, elide=elide)
        shard_n = table.msb.shape[0]
        m_t = table.lo.shape[1]
        off = lax.axis_index(STORE_AXIS).astype(ent.dtype) \
            * shard_n * m_t * m
        ent = jnp.where(ent >= 0, ent + off, ent)
        hdrs = lax.all_gather(hdr, STORE_AXIS, axis=0)        # [d, 5+B]
        ents = lax.all_gather(ent, STORE_AXIS, axis=0)        # [d, s]
        b = qmat.shape[0]
        codespace = d * shard_n * m_t * m
        return _merge_shard_blocks(hdrs, ents, b, s, codespace, 0)

    fn = jax.jit(_shard_map(local, mesh,
                            (table_specs, attr_specs, aidx_specs,
                             P(), P(), P(), P(), P()),
                            (P(), P())))
    _ATTR_SH_CACHE[key] = fn
    return fn


def sharded_bucketed_attr(mesh: Mesh, m: int, span: int, s: int, k: int,
                          m_t: int, keff: int, wide: bool = False,
                          floors: bool = True, elide: bool = True):
    """Mesh-sharded bucketed_attr: the bucket ROWS and the wide/straggler
    list are row-sharded across the mesh and the query batch is replicated;
    each shard probes only the bucket rows it owns (a query's global
    bucket-row columns are translated to shard-local rows inside the
    shard_map, rows outside the shard become "no bucket here"), so the
    union over shards is exactly the single-device bucketed answer.  The
    attribution columns are REPLICATED (the entries carry global slot ids,
    and a shard must grade slots whose rows it does not own), the
    floor/elision index replicated.  The on-device merge removes
    cross-shard duplicates (one triple reachable via bucket rows on
    different shards) and applies the key-domain (slot, col) dedupe across
    shards.  ``m_t`` is the owning table's interval width (codes scale on
    it) and ``keff`` the live bucket-occupancy slice, both static.

    The entry TOKEN (a key dep's own footprint point) lives in the
    slot-sharded interval table, so each shard contributes the tokens of
    the slots it owns and a psum assembles the full per-entry token
    column — the [N, M] interval matrix itself stays sharded.

    Returns fn(buckets, table, attr, aidx, qmat, rankb, pm, pl, pn) ->
    (header int32[5+B] replicated, entries [d*s] replicated)."""
    from ..ops import deps_kernel as dk
    dev_key = tuple(dv.id for dv in mesh.devices.flat)
    key = ("buck", dev_key, m, span, s, k, m_t, keff, wide,
           floors, elide)
    fn = _ATTR_SH_CACHE.get(key)
    if fn is not None:
        return fn
    d = int(np.prod(list(mesh.shape.values())))
    bucket_specs = BucketTable(*([P(STORE_AXIS, None)] * 8),
                               *([P(STORE_AXIS)] * 8))
    table_specs = DepsTable(P(STORE_AXIS), P(STORE_AXIS), P(STORE_AXIS),
                            P(STORE_AXIS), P(STORE_AXIS),
                            P(STORE_AXIS, None), P(STORE_AXIS, None))
    attr_specs = dk.AttrCols(*([P()] * 9))
    aidx_specs = dk.AttrIndex(*([P()] * 11))

    def local(buckets, table, attr, aidx, qmat, rankb, pm, pl, pn):
        off = lax.axis_index(STORE_AXIS).astype(jnp.int32) \
            * buckets.blo.shape[0]
        hdr_raw, ent = dk.bucketed_flat(None, buckets, qmat, m, span, s,
                                        k, (pm, pl, pn), row_offset=off,
                                        keff=keff, wide=wide, m_t=m_t)
        # per-entry token via cross-shard psum over the WHOLE gathered
        # entry set: every shard's entries reference global slots, so the
        # codes are all-gathered first, each shard contributes
        # lo[slot, col] for the slots its slice owns (zero elsewhere),
        # and the psum assembles the complete [d, s] token matrix — each
        # shard then attributes its own row
        ents_all = lax.all_gather(ent, STORE_AXIS, axis=0)   # [d, s]
        n_local = table.lo.shape[0]
        soff = lax.axis_index(STORE_AXIS).astype(jnp.int64) * n_local
        code = ents_all.astype(jnp.int64)
        mq = m_t * m
        slot = jnp.clip(code // mq, 0)
        col = jnp.clip(code % mq // m, 0, m_t - 1)
        mine = (slot >= soff) & (slot < soff + n_local) & (code >= 0)
        lslot = jnp.clip(slot - soff, 0, n_local - 1)
        tok_all = lax.psum(jnp.where(mine, table.lo[lslot, col], 0),
                           STORE_AXIS)                       # [d, s]
        me = lax.axis_index(STORE_AXIS)
        hdr, ent = dk._attr_post(None, attr, aidx, rankb, hdr_raw, ent,
                                 m_t, m, floors, elide, tok=tok_all[me])
        hdrs = lax.all_gather(hdr, STORE_AXIS, axis=0)
        ents = lax.all_gather(ent, STORE_AXIS, axis=0)
        b = qmat.shape[0]
        codespace = attr.dom.shape[0] * m_t * m
        return _merge_shard_blocks(hdrs, ents, b, s, codespace,
                                   m, dom=attr.dom, mq=m_t * m)

    fn = jax.jit(_shard_map(local, mesh,
                            (bucket_specs, table_specs, attr_specs,
                             aidx_specs, P(), P(), P(), P(), P()),
                            (P(), P())))
    _ATTR_SH_CACHE[key] = fn
    return fn


def sharded_fused_attr(mesh: Mesh, n_stores: int, m: int, s: int, k: int,
                       wide: bool = False, floors: bool = True,
                       elide: bool = True):
    """Batched-over-stores sharded_flat_attr — the r08 fused launch with
    the attribution pass and the on-device cross-shard merge.  Store row i
    of the outputs is the solo sharded_flat_attr answer for store i (codes
    on the GROUP interval width m_max).

    Returns fn(*tables, *attrs, *aidxs, qmats, rankbs, pm, pl, pn) ->
    (header int32[S, 5+B] replicated, entries [S, d*s] replicated)."""
    from ..ops import deps_kernel as dk
    dev_key = tuple(dv.id for dv in mesh.devices.flat)
    key = ("fused", dev_key, n_stores, m, s, k, wide, floors, elide)
    fn = _ATTR_SH_CACHE.get(key)
    if fn is not None:
        return fn
    d = int(np.prod(list(mesh.shape.values())))
    table_specs = DepsTable(P(STORE_AXIS), P(STORE_AXIS), P(STORE_AXIS),
                            P(STORE_AXIS), P(STORE_AXIS),
                            P(STORE_AXIS, None), P(STORE_AXIS, None))
    attr_specs = dk.AttrCols(*([P(STORE_AXIS)] * 9))
    aidx_specs = dk.AttrIndex(*([P()] * 11))
    in_specs = tuple([table_specs] * n_stores) \
        + tuple([attr_specs] * n_stores) \
        + tuple([aidx_specs] * n_stores) + (P(), P(), P(), P(), P())

    def local(*args):
        tables = args[:n_stores]
        attrs = args[n_stores:2 * n_stores]
        aidxs = args[2 * n_stores:3 * n_stores]
        qmats, rankbs, pm, pl, pn = args[3 * n_stores:]
        n_max = max(t.msb.shape[0] for t in tables)
        m_max = max(t.lo.shape[1] for t in tables)
        f_max = max(a.fbnd.shape[0] for a in aidxs)
        t_max = max(a.etok.shape[0] for a in aidxs)
        l_max = max(a.erank.shape[0] for a in aidxs)
        padded = [dk._pad_table_cols(tuple(t), n_max, m_max)
                  for t in tables]
        stacked = DepsTable(*(jnp.stack(col) for col in zip(*padded)))
        pa = [dk._pad_attr_cols(tuple(a), n_max) for a in attrs]
        stacked_a = dk.AttrCols(*(jnp.stack(col) for col in zip(*pa)))
        pi = [dk._pad_attr_index(a, f_max, t_max, l_max) for a in aidxs]
        stacked_i = dk.AttrIndex(*(jnp.stack(col) for col in zip(*pi)))
        hdr, ent = jax.vmap(
            lambda t, a, i, q, r, x, y, z: dk.flat_attr_local(
                t, a, i, q, r, m, s, k, (x, y, z), wide=wide,
                floors=floors, elide=elide)
        )(stacked, stacked_a, stacked_i, qmats, rankbs, pm, pl, pn)
        off = lax.axis_index(STORE_AXIS).astype(ent.dtype) \
            * n_max * m_max * m
        ent = jnp.where(ent >= 0, ent + off, ent)
        hdrs = lax.all_gather(hdr, STORE_AXIS, axis=0)       # [d, S, 5+B]
        ents = lax.all_gather(ent, STORE_AXIS, axis=0)       # [d, S, s]
        b = qmats.shape[1]
        codespace = d * n_max * m_max * m
        return jax.vmap(
            lambda h, e: _merge_shard_blocks(h, e, b, s, codespace, 0)
        )(hdrs.swapaxes(0, 1), ents.swapaxes(0, 1))

    fn = jax.jit(_shard_map(local, mesh, in_specs, (P(), P())))
    _ATTR_SH_CACHE[key] = fn
    return fn
