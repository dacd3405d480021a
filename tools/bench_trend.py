"""Trajectory sentinel: gate EVERY metric across the whole BENCH_*.json history.

    python tools/bench_trend.py [--dir .] [--threshold 0.5] [--all] \
        [--waivers tools/bench_waivers.json | --no-waivers] [ARTIFACT...]

`tools/bench_compare.py` diffs two hand-picked artifacts — which is exactly
how `hot128_chain_drain_txns_per_sec` collapsed 23,008 -> 196 txn/s between
r05 and r08 with nobody noticing: rounds r06/r07 emitted no artifact, so no
pairwise diff ever straddled the cliff.  This tool closes that hole by
loading *all* checked-in artifacts in round order and walking every
per-metric series between consecutive PRESENT points, so a regression can
never hide in an artifact gap again.

Series built per round (same parse as bench_compare):

- the headline metric (``headline.<name>``, higher is better),
- every config row by metric name (unit ``sim_ms`` = latency = lower is
  better, everything else higher is better),
- per-row ``vs_baseline`` (higher is better — this is the
  platform-independent health signal; a silent TPU->CPU flip moves raw
  txn/s 100x but moves vs_baseline only by the hardware's honest edge),
- per-row per-phase p50/p99 latencies and ``fast_path_rate``,
- the headline ``# index:`` counters — ``download_bytes`` is gated lower-is
  -better; the remaining counters are workload-scale dependent and are
  reported as drift in the default output (never gated), alongside any
  step the gate cannot examine because its base value is 0/absent.

A step beyond threshold in the bad direction is a VIOLATION unless
`tools/bench_waivers.json` carries a waiver for that exact (metric, from,
to) step; a waiver records the post-mortem verdict (e.g. the r05->r08 drain
collapse was a silent bench-platform change, ``# device=tpu`` ->
``# device=cpu``, not a code regression) so the gate stays loud for the
NEXT cliff while the explained one stops paging.

Exit status: 0 = every flagged step waived (or none), 1 = usage/parse
error, 2 = unwaived regression.  Run it on every bench-emitting PR, after
bench_compare.
"""

import argparse
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_compare import parse_artifact  # noqa: E402

ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")

# log2-bucketed phase latencies are 2x-granular by construction: only a
# >2x move is a signal at all (same rationale as bench_compare's 2x gate)
PHASE_THRESHOLD = 0.5

# gated ``# index:`` counters and their good direction; everything else
# on the line stays info-only (drift_notes).  r16 adds the serving
# counters (per-txn normalized in bench.py, so they trend comparably
# across rounds despite the box's wall-clock oscillation).
INDEX_GATED = {
    "download_bytes": "down",
    "wire_bytes_tx": "down",
    "wire_bytes_rx": "down",
    "frames_coalesced": "up",
    "batched_fanouts": "up",
    "batch_occupancy_p50": "up",
    # r17 elastic-serving counters: deliberately INFO-ONLY (None) — the
    # rebalance/bootstrap wall clocks ride the oscillating box's 2-4x
    # swing and the byte/range counts scale with the leg's data volume,
    # so a hard gate would manufacture waivers; drift_notes still
    # surfaces any big move with its history
    # r18: the profiled protocol CPU cost (microseconds/txn, from the
    # cProfile'd config-6 leg) gates lower-is-better — same tool every
    # round, so the profiler overhead cancels in the ratio
    "protocol_us_per_txn": "down",
    # r20 store-grouped execution: occupancy gates higher-is-better (the
    # amortization census the tentpole claims); grouped_ops and
    # group_fallbacks are INFO-ONLY — the grouped/fallback split is
    # workload-shape dependent (control verbs, reconfig gossip and
    # cross-epoch ops fall back per-op by design)
    "store_group_occupancy_p50": "up",
    "grouped_ops": None,
    "group_fallbacks": None,
    "epoch_current": None,
    "epochs_retired": None,
    "bootstrap_bytes_rx": None,
    "bootstrap_wall_ms": None,
    "handoff_ranges": None,
    # r19 drain-route counters: INFO-ONLY — the logdepth/fixpoint split is
    # workload-shape dependent by design (routing, never thresholds); the
    # gated signal is each drain row's fixpoint_sweeps series below
    "drain_logdepth": None,
    "drain_fixpoint": None,
    "drain_logdepth_failovers": None,
    "fused_front_evictions": None,
    # r21 store-sharded counters: INFO-ONLY — the headline bench's store
    # never breaches its budget, so these sit at 0 there (the gate is
    # tests/test_store_shard.py).  shard_merge_bytes scales with the flush
    # shape, quarantines with injected faults.
    "store_sharded_flushes": None,
    "slice_quarantines": None,
    "slice_restores": None,
    "shard_merge_bytes": None,
    "oom_recovered": None,
}


def discover(dirpath):
    """[(round, path)] for every BENCH_r*.json under dirpath, round order."""
    out = []
    for path in glob.glob(os.path.join(dirpath, "BENCH_r*.json")):
        m = ROUND_RE.search(path)
        if m:
            out.append((int(m.group(1)), path))
    return sorted(out)


def load_series(rounds):
    """{series_key: {"dir": "up"|"down", "points": [(round, value)]}} from
    [(round, path)].  Every key is gated except dir=None (info only)."""
    series = {}

    def add(key, rnd, val, direction):
        if val is None:
            return
        s = series.setdefault(key, {"dir": direction, "points": []})
        if direction is None:
            # opt-out wins for the WHOLE series: once any round marks a
            # row info-only ("gated": false), earlier rounds that predate
            # the marker must not re-gate it
            s["dir"] = None
        s["points"].append((rnd, val))

    for rnd, path in rounds:
        head, cfg, idx = parse_artifact(path, strict=False)
        if head is not None:
            add(f"headline.{head['metric']}", rnd, head.get("value"), "up")
        for m, row in cfg.items():
            # sim_ms AND wall-clock ms rows gate lower-is-better (the
            # r17 rebalance wall is a duration: up = worse); a row may
            # opt out of value gating entirely with "gated": false
            # (tracked info-only, like ungated index counters)
            latency = row.get("unit") in ("sim_ms", "ms")
            direction = (None if row.get("gated") is False
                         else "down" if latency else "up")
            add(m, rnd, row.get("value"), direction)
            add(f"{m}.vs_baseline", rnd, row.get("vs_baseline"), "up")
            # r19: device sweep/round counts gate lower-is-better across
            # the WHOLE history (safe: the series is constant 634/4097
            # from r11 through r18 — the r19 log-depth kernels are the
            # first change, and it must only ever move DOWN from here)
            add(f"{m}.fixpoint_sweeps", rnd, row.get("fixpoint_sweeps"),
                "down")
            add(f"{m}.fast_path_rate", rnd, row.get("fast_path_rate"), "up")
            for ph, pd in (row.get("phases_ms") or {}).items():
                add(f"{m}.phase[{ph}].p50_ms", rnd, pd.get("p50_ms"), "down")
                add(f"{m}.phase[{ph}].p99_ms", rnd, pd.get("p99_ms"), "down")
        for k, v in idx.items():
            add(f"index.{k}", rnd, v, INDEX_GATED.get(k))
    return series


def walk(series, threshold, latency_threshold):
    """Violations between consecutive present points of every gated series:
    [{key, from, to, old, new, ratio}]."""
    out = []
    for key, s in sorted(series.items()):
        if s["dir"] is None:
            continue
        thr = threshold
        if ".phase[" in key:
            thr = max(latency_threshold, PHASE_THRESHOLD)
        elif s["dir"] == "down":
            thr = latency_threshold
        pts = s["points"]
        for (r0, v0), (r1, v1) in zip(pts, pts[1:]):
            if not v0 or v1 is None:        # 0/None base: nothing to gate
                continue
            # "goodness" ratio: >1 improved, <1 regressed
            ratio = (v0 / v1 if s["dir"] == "down" and v1
                     else float("inf") if s["dir"] == "down"
                     else v1 / v0)
            if ratio < 1.0 - thr:
                out.append({"metric": key, "from": f"r{r0:02d}",
                            "to": f"r{r1:02d}", "old": v0, "new": v1,
                            "ratio": ratio})
    return out


def drift_notes(series, threshold):
    """Visible-but-ungated observations the default output must not hide
    (the whole tool exists because silent skips hide cliffs):

    - info-only series (dir=None — the workload-scale ``# index:``
      counters) whose step moved beyond threshold in EITHER direction;
    - steps of gated series the walker cannot ratio-examine because the
      base value is 0 (e.g. a phase p50 at the 0.0ms bucket floor).

    [{metric, from, to, old, new, tag}] — printed, never failed on."""
    out = []
    for key, s in sorted(series.items()):
        pts = s["points"]
        for (r0, v0), (r1, v1) in zip(pts, pts[1:]):
            if v1 is None:
                continue
            step = {"metric": key, "from": f"r{r0:02d}", "to": f"r{r1:02d}",
                    "old": v0, "new": v1}
            if not v0:
                if v1:                  # gated or not, the walker can't
                    out.append(dict(step, tag="zero-base"))  # ratio this
            elif s["dir"] is None and not (
                    1.0 - threshold <= v1 / v0 <= 1.0 + threshold):
                out.append(dict(step, tag="drift"))
    return out


def load_waivers(path):
    """[{metric, from, to, reason}] — absent file is an empty waiver set."""
    if not path or not os.path.exists(path):
        return []
    with open(path) as f:
        doc = json.load(f)
    return doc.get("waivers", doc) if isinstance(doc, (dict, list)) else []


def match_waiver(v, waivers):
    for w in waivers:
        if w.get("metric") == v["metric"] and w.get("from") == v["from"] \
                and w.get("to") == v["to"]:
            return w
    return None


def spark(points):
    """One-line series rendering: r05:23007.6 r08:196.0 ..."""
    return " ".join(f"r{r:02d}:{v}" for r, v in points)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="gate every metric across the whole BENCH trajectory")
    p.add_argument("artifacts", nargs="*",
                   help="explicit BENCH_r*.json paths (default: --dir glob)")
    p.add_argument("--dir", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))),
        help="directory holding BENCH_r*.json (default: repo root)")
    p.add_argument("--threshold", type=float, default=0.5,
                   help="allowed throughput drop fraction per step (default "
                        "0.5: cross-round runs straddle box oscillation, so "
                        "the trend gate is looser than bench_compare's 0.10 "
                        "same-session gate)")
    p.add_argument("--latency-threshold", type=float, default=0.5,
                   help="allowed latency growth fraction per step")
    p.add_argument("--waivers", default=None,
                   help="waiver file (default: tools/bench_waivers.json "
                        "next to this script)")
    p.add_argument("--no-waivers", action="store_true",
                   help="ignore the waiver file (the self-proof mode: the "
                        "known r05->r08 drain collapse must flag)")
    p.add_argument("--all", action="store_true",
                   help="print every series, not just flagged ones")
    args = p.parse_args(argv)

    if args.artifacts:
        rounds = []
        for path in args.artifacts:
            m = ROUND_RE.search(path)
            if not m:
                print(f"error: {path} does not look like BENCH_rNN.json",
                      file=sys.stderr)
                return 1
            rounds.append((int(m.group(1)), path))
        rounds.sort()
    else:
        rounds = discover(args.dir)
    if len(rounds) < 2:
        print("error: need >= 2 artifacts to trend", file=sys.stderr)
        return 1
    print(f"trending {len(rounds)} artifacts: "
          + " ".join(f"r{r:02d}" for r, _ in rounds))

    series = load_series(rounds)
    if args.all:
        for key, s in sorted(series.items()):
            tag = {"up": "^", "down": "v", None: "."}[s["dir"]]
            print(f"  [{tag}] {key}: {spark(s['points'])}")

    violations = walk(series, args.threshold, args.latency_threshold)
    notes = drift_notes(series, args.threshold)
    for n in notes:
        print(f"  {n['metric']}: {n['from']} {n['old']} -> {n['to']} "
              f"{n['new']} [{n['tag']}] (info, not gated)")
    waiver_path = args.waivers or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_waivers.json")
    waivers = [] if args.no_waivers else load_waivers(waiver_path)

    unwaived = []
    for v in violations:
        w = match_waiver(v, waivers)
        verdict = f"WAIVED ({w['reason']})" if w else "REGRESSION"
        print(f"  {v['metric']}: {v['from']} {v['old']} -> {v['to']} "
              f"{v['new']} [{v['ratio']:.4f}x] {verdict}")
        if not w:
            unwaived.append(v)
    if unwaived:
        print(f"\nFAIL: {len(unwaived)} unwaived regression step(s) in "
              f"{len({v['metric'] for v in unwaived})} series",
              file=sys.stderr)
        for v in unwaived:
            print(f"  {v['metric']} {v['from']}->{v['to']}: "
                  f"{v['old']} -> {v['new']}", file=sys.stderr)
        return 2
    n_gated = sum(1 for s in series.values() if s["dir"] is not None)
    print(f"\nok: {n_gated} gated series clean across "
          f"{len(rounds)} rounds ({len(violations)} waived)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
