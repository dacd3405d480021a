"""The profiler: one CLI over the r09 obs subsystem, replacing the five
overlapping ad-hoc scripts (profile2 / profile_attr / profile_bench /
profile_hot / profile_hot2) this repo accreted across r04-r06.

    python tools/profile.py headline [--n 100000] [--trace t.json] [--cprofile]
        Phase breakdown of the headline deps-scan path (pack / upload /
        kernel / download / begin+collect / attribute / build) on the
        100k-in-flight workload — the old profile_bench/profile2 view —
        with every launch boundary also captured as a Chrome-trace slice.

    python tools/profile.py attr [--cprofile]
        Attribution hot-path focus on the same store (old profile_attr).

    python tools/profile.py hot [--cprofile]
        The hot-128 low-live-set regime: per-batch begin/collect/attr
        timings through the adaptive router (old profile_hot/profile_hot2).

    python tools/profile.py launches [--stores 16] [--trace t.json]
        The launch-coalescing regime: N CommandStores on one
        DeviceDispatcher, fused vs solo, exporting the launch TIMELINE as
        Chrome-trace JSON (open in chrome://tracing or ui.perfetto.dev) —
        the r09 acceptance artifact that makes the r08 win visible as a
        timeline, not just a counter.

    python tools/profile.py drain [--n 100000]
        The r19 drain-route view: dense/ELL fixpoint vs the log-depth
        doubling kernels side by side across chain depths, with the
        MEASURED fixpoint/doubling crossover printed next to the one the
        route model PRICES from its micro-probe slopes (plus a byte-
        equality spot check at every depth — the fixpoint is the oracle).

    python tools/profile.py serve [--nodes 3] [--duration 6] [--top 30]
        The r18 serving-path hunt: spawn the real TCP cluster under
        ``ACCORD_TPU_NODE_PROFILE``, drive it to closed-loop saturation,
        merge the per-node pstats dumps, and print the ranked per-op
        cost table (ms of protocol CPU per committed txn, by frame) plus
        the ``protocol_ms_per_txn`` scalar the BENCH config-6 row
        carries.

``--trace PATH`` arms obs.devprof for the timed section and writes the
Chrome trace there (any mode).  Counters print from the same
obs.metrics.index_counters key list the bench ``# index:`` line uses.
"""

import os
import sys

# run as a script, sys.path[0] is tools/ and THIS file shadows the stdlib
# ``profile`` module cProfile imports — drop that entry before anything else
_here = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path
               if os.path.abspath(p or os.getcwd()) != _here]
sys.path.insert(0, os.path.dirname(_here))

import argparse          # noqa: E402
import contextlib        # noqa: E402
import cProfile          # noqa: E402
import json              # noqa: E402,F401
import pstats            # noqa: E402
import time              # noqa: E402

import numpy as np  # noqa: E402

from accord_tpu.ops.packing import startup  # noqa: E402

startup()

from accord_tpu.obs import devprof  # noqa: E402
from accord_tpu.obs.metrics import index_counters  # noqa: E402


def phase(label, fn, reps=3):
    ts = []
    out = None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        ts.append(time.perf_counter() - t0)
    print(f"{label:28s} {min(ts) * 1e3:9.1f} ms", file=sys.stderr)
    return out


@contextlib.contextmanager
def maybe_trace(path):
    if path is None:
        yield None
        return
    with devprof.capture() as prof:
        yield prof
    prof.write_chrome(path)
    tr = prof.chrome_trace()
    print(f"# chrome trace: {path} ({len(tr['traceEvents'])} events: "
          f"{tr['otherData']['event_counts']})", file=sys.stderr)


def maybe_cprofile(enabled, fn, top=14, sort="tottime"):
    if not enabled:
        return None    # don't pay an un-timed, un-profiled extra pass
    pr = cProfile.Profile()
    pr.enable()
    out = fn()
    pr.disable()
    st = pstats.Stats(pr)
    st.sort_stats(sort)
    st.print_stats(top)
    return out


# ---------------------------------------------------------------------------
# store builders (shared by the modes; same shapes as bench.py)
# ---------------------------------------------------------------------------

def build_headline(n):
    """The headline 100k-in-flight store, built by the SAME
    bench.build_headline_store the benchmark uses — the profiler always
    explains exactly the store the bench times."""
    from bench import build_headline_store, build_workload

    KEYSPACE, M = 1_000_000, 8
    rng = np.random.default_rng(42)
    entries = build_workload(rng, n, KEYSPACE, M)
    t0 = time.time()
    store, dev, safe = build_headline_store(entries, KEYSPACE)
    print(f"build {time.time() - t0:.1f}s capacity={dev.deps.capacity}",
          file=sys.stderr)
    return store, dev, safe, KEYSPACE, M


def headline_queries(b, keyspace, m):
    from bench import make_queries
    return [(q[0], q[0], q[1], q[2], q[3])
            for q in make_queries(1000, b, keyspace, m)]


def build_hot():
    """Config 3's hot-128 low-live-set store + workload, via the shared
    bench.build_hot128_store (identical seeded bytes)."""
    from bench import build_hot128_store
    store, dev, safe, _entries, _floor, queries, _rate, _rng = \
        build_hot128_store()
    return store, dev, safe, queries


def print_index(dev):
    print("# index: " + " ".join(f"{k}={v}"
                                 for k, v in index_counters(dev).items()),
          file=sys.stderr)


# ---------------------------------------------------------------------------
# modes
# ---------------------------------------------------------------------------

def mode_headline(args):
    from accord_tpu.local.device_index import _pow2_at_least
    from accord_tpu.ops import deps_kernel as dk
    from accord_tpu.primitives.deps import DepsBuilder
    import jax
    import jax.numpy as jnp

    store, dev, safe, keyspace, m = build_headline(args.n)
    B = args.batch
    queries = headline_queries(B, keyspace, m)
    # warm: compile + learn s/k
    dev.deps_query_batch_attributed(safe, queries,
                                    [DepsBuilder() for _ in queries])
    dev.deps_query_batch_attributed(safe, queries,
                                    [DepsBuilder() for _ in queries])
    print(f"learned s={dev._batch_flat} k={dev._batch_k}", file=sys.stderr)

    with maybe_trace(args.trace):
        packed = [(sb, wit, toks, rngs, tid)
                  for (tid, sb, wit, toks, rngs) in queries]
        q_m = _pow2_at_least(max(len(t[3]) + len(t[4]) for t in queries))
        table = dev.deps.device_table()
        n = table.capacity
        m_t = dev.deps.max_intervals
        wide = dk.wide_codes(n, m_t, q_m)
        s, k = (min(dev._batch_flat, B * n * m_t * q_m),
                min(dev._batch_k, n * m_t * q_m))
        qnp = phase("pack_query_matrix",
                    lambda: dk.pack_query_matrix(packed, q_m))
        qmat = phase("upload(qmat)",
                     lambda: jax.block_until_ready(jnp.asarray(qnp)))
        out_dev = phase("kernel(dispatch+wait)", lambda: jax.block_until_ready(
            dk.calculate_deps_flat(table, qmat, q_m, s, k, wide)))
        hdr_np = phase("download(header)",
                       lambda: np.asarray(out_dev[0]))
        from accord_tpu.local.device_index import _fetch_entry_prefix
        phase("download(entry prefix)",
              lambda: _fetch_entry_prefix(out_dev[1], s, int(hdr_np[0])))
        res = phase("begin+collect(attributed)",
                    lambda: dev._batch_collect_attr(
                        dev.deps_query_batch_begin(queries)))
        tb, tj, tm, tq, ids, ivs, qnp2, q_m2, qs = res
        print(f"attributed entries: {len(tj)}", file=sys.stderr)

        def attr():
            builders = [DepsBuilder() for _ in queries]
            dev._finalize_attr_entries(tb, tj, tm, tq, ids, ivs, qnp2,
                                       q_m2, builders)
            return builders

        builders = phase("finalize(attributed)", attr)
        phase("build-all", lambda: [b.build() for b in builders])

        def full():
            dev.deps_query_batch_attributed(
                safe, queries, [DepsBuilder() for _ in queries])

        phase("FULL batch e2e", full)
        maybe_cprofile(args.cprofile,
                       lambda: (attr(), [b.build() for b in builders]))
    print_index(dev)


def mode_attr(args):
    """The flush under the lens: per-stage timing of the collect (decode
    of the in-kernel floored/elided CSR) and the thin shared finalize."""
    from accord_tpu.primitives.deps import DepsBuilder

    store, dev, safe, keyspace, m = build_headline(args.n)
    queries = headline_queries(args.batch, keyspace, m)
    dev.deps_query_batch_attributed(safe, queries,
                                    [DepsBuilder() for _ in queries])
    tb, tj, tm, tq, ids, ivs, qnp2, q_m2, _qs = \
        phase("collect(attributed)",
              lambda: dev._batch_collect_attr(
                  dev.deps_query_batch_begin(queries, immediate=True)))
    print(f"attributed entries: {len(tj)} "
          f"(elided t={dev.n_elided_transitive} d={dev.n_elided_decided})",
          file=sys.stderr)

    def finalize():
        builders = [DepsBuilder() for _ in queries]
        dev._finalize_attr_entries(tb, tj, tm, tq, ids, ivs, qnp2, q_m2,
                                   builders)

    finalize()   # warm
    phase("finalize(attributed)", finalize)

    maybe_cprofile(args.cprofile, finalize, top=args.top or 25,
                   sort="cumulative")
    print_index(dev)


def mode_hot(args):
    from accord_tpu.primitives.deps import DepsBuilder

    store, dev, safe, queries = build_hot()
    B3 = 256
    batches = [queries[i * B3:(i + 1) * B3] for i in range(4)]
    t0 = time.time()
    dev.deps_query_batch_attributed(safe, batches[0],
                                    [DepsBuilder() for _ in batches[0]])
    print(f"warmup {time.time() - t0:.1f}s s={dev._batch_flat} "
          f"k={dev._batch_k} wide={len(dev.deps.wide_entries)}",
          file=sys.stderr)
    with maybe_trace(args.trace):
        for bi, batch in enumerate(batches):
            t0 = time.time()
            handle = dev.deps_query_batch_begin(batch)
            t1 = time.time()
            builders = [DepsBuilder() for _ in batch]
            dev.deps_query_batch_end_attributed(safe, handle, builders)
            t2 = time.time()
            nd = sum(b.build().key_deps.relation_count() for b in builders)
            print(f"batch {bi}: begin={1e3 * (t1 - t0):.0f}ms "
                  f"collect+attr={1e3 * (t2 - t1):.0f}ms "
                  f"count={1e3 * (time.time() - t2):.0f}ms deps={nd}",
                  file=sys.stderr)

        def one():
            builders = [DepsBuilder() for _ in batches[0]]
            h = dev.deps_query_batch_begin(batches[0])
            dev.deps_query_batch_end_attributed(safe, h, builders)

        maybe_cprofile(args.cprofile, one, top=10)
    print_index(dev)


def mode_launches(args):
    """N stores x small flushes on one DeviceDispatcher: run the SAME
    workload solo-pinned then fused, print launches/1k-txn, and export the
    fused run's launch timeline as Chrome-trace JSON."""
    from bench import bench_launch_amortized_harness

    if args.pin_fused:
        # the fused-vs-solo pricing is wall-clock-calibrated and may
        # legitimately price fusion OUT on a loaded box; pin it so the
        # captured timeline always shows the coalesced shape
        from accord_tpu.local.dispatch import DeviceDispatcher
        DeviceDispatcher._fused_flush_pays = lambda self, hints: True

    res = {}
    for mode_name, fusion in (("solo", False), ("fused", True)):
        prof_ctx = maybe_trace(args.trace) if fusion else \
            contextlib.nullcontext()
        with prof_ctx:
            res[mode_name] = bench_launch_amortized_harness(
                stores=args.stores, rounds=args.rounds, fusion=fusion)
        r = res[mode_name]
        print(f"{mode_name:5s}: {r['qps']:.1f} txn/s "
              f"{1e3 * r['launches'] / r['nq']:.2f} launches/1k txn "
              f"(members/launch="
              f"{r['fused_members'] / max(r['launches'], 1):.1f})",
              file=sys.stderr)
    f, s = res["fused"], res["solo"]
    print(f"speedup_vs_solo={f['qps'] / s['qps']:.2f}x "
          f"launch_reduction={s['launches'] / max(f['launches'], 1):.1f}x",
          file=sys.stderr)
    if f["fused_members"] == 0:
        print("note: the calibrated pricing served every flush solo on "
              "this box/load — rerun with --pin-fused to capture the "
              "coalesced timeline regardless", file=sys.stderr)


def mode_serve(args):
    from accord_tpu.net.profiling import profiled_saturation_run

    res = profiled_saturation_run(
        n_nodes=args.nodes, duration=args.duration, top=args.top or 30,
        note=lambda msg: print(msg, file=sys.stderr))
    print(f"{'ms/txn':>8s} {'calls/txn':>10s} {'tottime_s':>10s}  frame",
          file=sys.stderr)
    for r in res["frames"]:
        print(f"{r['ms_per_txn']:8.3f} {r['calls_per_txn']:10.2f} "
              f"{r['tottime_s']:10.3f}  {r['frame']}", file=sys.stderr)
    stages = res.get("stage_ms_per_txn") or {}
    if stages:
        # r20: the pipeline-stage partition of protocol_ms_per_txn —
        # decode / scheduler hop / store setup / handler body / reply
        # encode — the attribution the grouped-vs-per-op A/B reads
        print("stage ms/txn: " + " ".join(
            f"{k}={v}" for k, v in stages.items()), file=sys.stderr)
    print(f"saturation={res['saturation_txns_per_sec']} txn/s "
          f"txns={res['txns']} "
          f"protocol_ms_per_txn={res['protocol_ms_per_txn']}",
          file=sys.stderr)
    # machine-readable summary on stdout (stderr carries the table)
    print(json.dumps({k: res[k] for k in
                      ("saturation_txns_per_sec", "txns",
                       "protocol_ms_per_txn", "stage_ms_per_txn",
                       "prof_dir")}))


def mode_drain(args):
    """r19 drain-route forensics: dense/ELL fixpoint vs the log-depth
    doubling kernels side by side at several chain depths, printing the
    MEASURED crossover next to the one the route model PRICES from its
    micro-probe — the two must broadly agree or the cost model is lying."""
    import jax
    import jax.numpy as jnp

    from accord_tpu.ops import drain_kernel as drk
    from accord_tpu.ops.deps_kernel import SLOT_STABLE

    depths = [64, 256, 1024, 4096] if args.n >= 100_000 else [64, args.n]
    cal = phase("route micro-probe", drk.drain_calibration, reps=1)
    print("probe slopes (s/elem): "
          + " ".join(f"{k}={v:.3e}" for k, v in cal.items()),
          file=sys.stderr)
    print(f"{'depth':>6s} {'ell_fix_ms':>11s} {'ell_dbl_ms':>11s} "
          f"{'dense_fix_ms':>13s} {'dense_sq_ms':>12s} "
          f"{'sweeps':>7s} {'rounds':>7s} {'measured':>9s} {'priced':>9s}",
          file=sys.stderr)
    measured_x, priced_x = None, None
    for n in depths:
        ell = drk._probe_chain_ell(n)
        dense = drk._probe_chain_dense(n)

        def t(fn, reps=3):
            fn()
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                ts.append(time.perf_counter() - t0)
            return min(ts) * 1e3

        t_ef = t(lambda: drk.drain_ell_levels(ell)[0])
        t_ed = t(lambda: drk.drain_ell_logdepth(ell)[0])
        t_df = t(lambda: drk.drain_levels(dense)[0])
        t_ds = t(lambda: drk.drain_dense_logsq(dense)[0])
        sweeps = int(np.asarray(drk.drain_ell_levels(ell)[2]))
        rounds = int(np.asarray(drk.drain_ell_logdepth(ell)[2]))
        d = ell.adj_idx.shape[1]
        cost_fix = sweeps * n * d * cal["c_sweep_ell"] * 1e3
        cost_dbl = rounds * n * d * cal["c_round_ell"] * 1e3
        measured = "doubling" if t_ed < t_ef else "fixpoint"
        priced = "doubling" if cost_dbl < cost_fix else "fixpoint"
        if measured == "doubling" and measured_x is None:
            measured_x = n
        if priced == "doubling" and priced_x is None:
            priced_x = n
        print(f"{n:6d} {t_ef:11.2f} {t_ed:11.2f} {t_df:13.2f} "
              f"{t_ds:12.2f} {sweeps:7d} {rounds:7d} {measured:>9s} "
              f"{priced:>9s}", file=sys.stderr)
        # byte-equality spot check at every depth — the fixpoint is the
        # standing oracle, a profiler run is a free extra witness
        af, nf, _ = drk.drain_ell_levels(ell)
        ad, nd, _ = drk.drain_ell_logdepth(ell)
        assert bool((af == ad).all() and (nf == nd).all()), \
            f"logdepth/fixpoint divergence at depth {n}"
    print(f"measured crossover: doubling wins from depth "
          f"{measured_x or '>max'}; priced crossover: depth "
          f"{priced_x or '>max'}", file=sys.stderr)
    print(json.dumps({"measured_crossover": measured_x,
                      "priced_crossover": priced_x,
                      "calibration": cal}))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("mode",
                   choices=["headline", "attr", "hot", "launches", "serve",
                            "drain"])
    p.add_argument("--n", type=int, default=100_000,
                   help="in-flight txns for headline/attr store")
    p.add_argument("--batch", type=int, default=2048)
    p.add_argument("--stores", type=int, default=16,
                   help="launches mode: CommandStores on the dispatcher")
    p.add_argument("--rounds", type=int, default=24)
    p.add_argument("--top", type=int, default=None)
    p.add_argument("--trace", default=None,
                   help="write a Chrome trace (chrome://tracing JSON) here")
    p.add_argument("--pin-fused", action="store_true",
                   help="launches mode: bypass the fused-vs-solo pricing "
                        "so the trace always shows coalesced launches")
    p.add_argument("--cprofile", action="store_true")
    p.add_argument("--nodes", type=int, default=3,
                   help="serve mode: cluster size")
    p.add_argument("--duration", type=float, default=6.0,
                   help="serve mode: saturation window seconds")
    args = p.parse_args(argv)
    {"headline": mode_headline, "attr": mode_attr,
     "hot": mode_hot, "launches": mode_launches,
     "serve": mode_serve, "drain": mode_drain}[args.mode](args)


if __name__ == "__main__":
    main()
