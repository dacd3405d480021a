"""Compare two BENCH_*.json artifacts and fail on regression.

    python tools/bench_compare.py BENCH_r08.json BENCH_r09.json \
        [--threshold 0.10] [--latency-threshold 0.25]

Artifacts are the driver-captured records ({"tail": "<stdout+stderr>",
"parsed": {headline}}) this repo has emitted since r01 — or raw bench.py
output (headline JSON last line, ``# CONFIG {...}`` rows).  The diff
covers:

- the HEADLINE metric (higher is better; regression beyond --threshold
  fails),
- every config row present in BOTH artifacts, matched by metric name
  (unit ``sim_ms`` = latency = lower is better, gated by
  --latency-threshold; everything else = throughput = higher is better),
- the r09 observability fields where both sides carry them: per-phase
  p99 latencies (lower is better) and the fast-path rate (higher is
  better) — reported, and gated at 2x the base threshold since phase
  distributions are log-bucketed (2x-granular by construction),
- the r10 download-byte counters from the headline ``# index:`` line
  (``download_bytes`` / ``download_bytes_padded``): the two-stage
  compacted transfer's actual bytes are gated lower-is-better, and the
  compaction ratio prints for every artifact that carries them,
- the r11 ``vs_baseline`` columns on every config row both sides carry
  them (higher is better, base threshold) — the platform-independent
  health signal the drain rows were missing when the r05->r08 collapse
  slipped through,
- metrics present on only one side: "NEW" rows print as the baseline a
  future trend starts from, "GONE" rows print as a question — a deleted
  metric can be a regression hiding by deletion.  Neither fails the
  pairwise gate (``tools/bench_trend.py`` owns cross-round series).

Waivers (r12): a flagged step can be downgraded to WAIVED by an entry in
the ``compare_waivers`` list of ``tools/bench_waivers.json`` matching this
exact (metric, from-round, to-round) pair — rounds are parsed from the
``BENCH_rNN`` artifact filenames.  Same discipline as the trend sentinel's
``waivers``: the reason must record a forensic verdict, ``--no-waivers``
is the self-proof mode, and ``tests/test_bench_trend.py`` fails any waiver
that does not match a step this tool actually flags (no dead
documentation).  The lists are separate because the gates differ: the
pairwise gate is 10%, the trend gate 50% — a step can be pairwise noise
yet trend-visible, or vice versa.

Exit status: 0 = no regression, 1 = usage/parse error, 2 = regression
beyond threshold.  Every comparison prints either way — the tool is the
artifact diff first, the CI gate second.
"""

import argparse
import json
import os
import re
import sys


def parse_index_counters(text):
    """{counter: int} from the bench's ``# index: k=v ...`` lines (empty
    when the artifact predates a counter or the line).  r16 artifacts
    carry a SECOND line with the serving counters (emitted after the
    serving sweep runs); all lines merge, first occurrence of a key wins
    — byte-identical behavior for every single-line artifact."""
    out = {}
    found = False
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("# index:"):
            found = True
            for tok in line[len("# index:"):].split():
                if "=" in tok:
                    key, _, val = tok.partition("=")
                    if key in out:
                        continue
                    try:
                        out[key] = int(val)
                    except ValueError:
                        pass
    return out if found else {}


def parse_artifact(path, strict=True):
    """(headline dict, {metric_name: config_row}, index counters) from a
    driver artifact or raw bench output.  ``strict=False`` returns a None
    headline instead of exiting (bench_trend trends artifacts that predate
    the r06 last-line-headline contract — BENCH_r05 lost its headline)."""
    with open(path) as f:
        text = f.read()
    headline, configs = None, {}
    try:
        doc = json.loads(text)
        if isinstance(doc, dict) and "tail" in doc:
            headline = doc.get("parsed")
            text = doc["tail"]
    except ValueError:
        pass
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("# CONFIG "):
            try:
                row = json.loads(line[len("# CONFIG "):])
            except ValueError:
                continue
            if row.get("metric"):
                configs[row["metric"]] = row
        elif line.startswith("{"):
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if row.get("metric") and "config" not in row:
                headline = row
    if headline is None or headline.get("value") is None:
        if strict:
            raise SystemExit(f"error: no headline metric in {path}")
        headline = None
    return headline, configs, parse_index_counters(text)


def artifact_round(path):
    """"rNN" from a BENCH_rNN* filename, else None (waivers need both
    sides' rounds to match an entry — unround-named files never waive)."""
    m = re.search(r"BENCH_r(\d+)", os.path.basename(path))
    return f"r{int(m.group(1)):02d}" if m else None


def load_compare_waivers(path):
    """[{metric, from, to, reason}] from the ``compare_waivers`` key
    (absent file or key = empty set; the trend sentinel's ``waivers`` key
    is a different gate and is deliberately NOT read here)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return []
    return doc.get("compare_waivers", []) if isinstance(doc, dict) else []


def check(name, old, new, threshold, lower_is_better=False):
    """One comparison row; returns the failure message or None."""
    if old in (None, 0) or new is None:
        print(f"  {name:58s} {old!r:>12} -> {new!r:>12}  (skipped)")
        return None
    if lower_is_better:
        # new == 0 on a latency metric is a bucket-floor improvement
        # (sub-ms sim latencies round to 0.0), never a regression
        ratio = float("inf") if new == 0 else old / new
    else:
        ratio = new / old
    arrow = "v" if new < old else "^"
    verdict = "OK"
    fail = None
    if ratio < 1.0 - threshold:
        verdict = f"REGRESSION (-{(1 - ratio) * 100:.1f}% beyond "\
                  f"{threshold * 100:.0f}%)"
        fail = (name, f"{name}: {old} -> {new} ({verdict})")
    print(f"  {name:58s} {old:>12} -> {new:>12} {arrow} "
          f"[{ratio:.2f}x] {verdict}")
    return fail


def main(argv=None):
    p = argparse.ArgumentParser(
        description="diff two BENCH artifacts, exit 2 on regression")
    p.add_argument("old")
    p.add_argument("new")
    p.add_argument("--threshold", type=float, default=0.10,
                   help="allowed throughput regression fraction (default "
                        "0.10; this box's bench spread is ~1.15x)")
    p.add_argument("--latency-threshold", type=float, default=0.25,
                   help="allowed latency regression fraction (default 0.25)")
    p.add_argument("--waivers", default=None,
                   help="waiver file (default: tools/bench_waivers.json "
                        "next to this script; the compare_waivers list)")
    p.add_argument("--no-waivers", action="store_true",
                   help="ignore the waiver file (self-proof mode: a waived "
                        "step must still flag here)")
    args = p.parse_args(argv)

    old_head, old_cfg, old_idx = parse_artifact(args.old)
    new_head, new_cfg, new_idx = parse_artifact(args.new)
    failures = []

    print(f"headline ({args.old} -> {args.new}):")
    if old_head["metric"] != new_head["metric"]:
        print(f"  metric changed: {old_head['metric']} -> "
              f"{new_head['metric']} (compared anyway)")
    failures.append(check(new_head["metric"], old_head["value"],
                          new_head["value"], args.threshold))
    # r10 compacted downloads: actual bytes must not regress (lower is
    # better); the compaction ratio prints wherever the counters exist
    for tag, idx in (("old", old_idx), ("new", new_idx)):
        db, dp = idx.get("download_bytes"), idx.get("download_bytes_padded")
        if db is not None and dp is not None:
            # db == 0 prints too (an all-host/quarantined run is an
            # anomaly worth surfacing, not a pre-r10 artifact)
            ratio = db / dp if dp else float("nan")
            print(f"  download_bytes[{tag}]: {db} / padded {dp} "
                  f"(compaction {ratio:.3f}x)")
    if (old_idx.get("download_bytes") is not None
            and new_idx.get("download_bytes") is not None):
        failures.append(check("headline.download_bytes",
                              old_idx["download_bytes"],
                              new_idx["download_bytes"],
                              args.threshold, lower_is_better=True))
    # the r16 serving counters (per-txn normalized on the # index: line):
    # bytes gate lower-is-better, batching depth higher-is-better — all
    # at the wall-clock latency threshold, since the serving sweep rides
    # the same oscillating box as every platform row
    for key, lower in (("wire_bytes_tx", True), ("wire_bytes_rx", True),
                       ("frames_coalesced", False),
                       ("batched_fanouts", False),
                       ("batch_occupancy_p50", False),
                       # r20: median ops sharing one SafeCommandStore
                       # acquisition (store-grouped execution) — deeper
                       # groups amortize better
                       ("store_group_occupancy_p50", False),
                       # r18: profiled protocol CPU per txn (us) — same
                       # cProfile tooling every round, lower is better
                       ("protocol_us_per_txn", True)):
        if (old_idx.get(key) is not None
                and new_idx.get(key) is not None):
            failures.append(check(f"index.{key}", old_idx[key],
                                  new_idx[key], args.latency_threshold,
                                  lower_is_better=lower))
    # r17 elastic-serving counters: printed for the reviewer, not gated
    # (wall clocks ride the box oscillation; byte/range counts scale with
    # the leg's data volume — bench_trend carries them as drift notes)
    ela = [(k, old_idx.get(k), new_idx.get(k))
           for k in ("epoch_current", "epochs_retired",
                     "bootstrap_bytes_rx", "bootstrap_wall_ms",
                     "handoff_ranges")
           if old_idx.get(k) is not None or new_idx.get(k) is not None]
    if ela:
        print("  elastic (info-only): "
              + "  ".join(f"{k}: {o} -> {n}" for k, o, n in ela))
    # r20 store-group split: printed, not gated — the grouped/fallback
    # ratio tracks workload shape (control verbs and cross-epoch ops
    # fall back per-op by design); occupancy_p50 above is the gate
    sg = [(k, old_idx.get(k), new_idx.get(k))
          for k in ("grouped_ops", "group_fallbacks")
          if old_idx.get(k) is not None or new_idx.get(k) is not None]
    if sg:
        print("  store-group (info-only): "
              + "  ".join(f"{k}: {o} -> {n}" for k, o, n in sg))
    # r21 store-sharded counters: printed, not gated — the headline store
    # never breaches its budget (all zeros there); the gate is
    # tests/test_store_shard.py
    shd = [(k, old_idx.get(k), new_idx.get(k))
           for k in ("store_sharded_flushes", "slice_quarantines",
                     "slice_restores", "shard_merge_bytes", "oom_recovered")
           if old_idx.get(k) is not None or new_idx.get(k) is not None]
    if shd:
        print("  store-shard (info-only): "
              + "  ".join(f"{k}: {o} -> {n}" for k, o, n in shd))

    common = [m for m in old_cfg if m in new_cfg]
    print(f"config rows ({len(common)} common, "
          f"{len(new_cfg) - len(common)} new-only, "
          f"{len(old_cfg) - len(common)} old-only):")
    for m in common:
        o, n = old_cfg[m], new_cfg[m]
        if o.get("gated") is False or n.get("gated") is False:
            # rows that opt out of value gating IN-ROW (r17: the
            # rebalance wall clocks — 500ms-tick-quantized wall numbers
            # on the oscillating box; their note names the comparable
            # signals).  Printed, never failed.
            print(f"  {m:60s} {o.get('value')} -> {n.get('value')} "
                  f"(info-only: gated=false in-row)")
            continue
        # sim_ms (sim-time latencies) and ms (wall-clock durations) both
        # gate lower-is-better — a row measured in time that "goes up"
        # is a regression, never a win
        latency = o.get("unit") in ("sim_ms", "ms")
        failures.append(check(
            m, o.get("value"), n.get("value"),
            args.latency_threshold if latency else args.threshold,
            lower_is_better=latency))
        # vs_baseline is the platform-independent health signal (the r11
        # drain-forensics lesson: a silent bench-platform flip moves raw
        # txn/s 100x but moves vs_baseline only by the hardware's honest
        # edge) — gated higher-is-better wherever both sides carry it
        if o.get("vs_baseline") is not None \
                and n.get("vs_baseline") is not None:
            failures.append(check(f"{m}.vs_baseline",
                                  o["vs_baseline"], n["vs_baseline"],
                                  args.threshold))
        # r19: device sweep/round counts gate lower-is-better — the
        # log-depth drain's whole point is this number collapsing from
        # O(depth) to O(log depth); it must never creep back up
        if o.get("fixpoint_sweeps") is not None \
                and n.get("fixpoint_sweeps") is not None:
            failures.append(check(f"{m}.fixpoint_sweeps",
                                  o["fixpoint_sweeps"],
                                  n["fixpoint_sweeps"],
                                  args.latency_threshold,
                                  lower_is_better=True))
        # r09 observability fields (phase p99s lower-better, fast-path
        # rate higher-better), gated at 2x threshold: the histograms are
        # log-bucketed, so single-bucket jitter is expected
        op, np_ = o.get("phases_ms") or {}, n.get("phases_ms") or {}
        for phase in sorted(set(op) & set(np_)):
            failures.append(check(
                f"{m}.phase[{phase}].p99_ms",
                op[phase].get("p99_ms"), np_[phase].get("p99_ms"),
                2 * args.latency_threshold, lower_is_better=True))
        if o.get("fast_path_rate") is not None \
                and n.get("fast_path_rate") is not None:
            failures.append(check(f"{m}.fast_path_rate",
                                  o["fast_path_rate"], n["fast_path_rate"],
                                  2 * args.threshold))
    # a metric only one side carries is NEVER silently dropped: "new" rows
    # are where tomorrow's regressions start their series (bench_trend picks
    # them up from here), and a "gone" row may be a regression hiding by
    # deletion — both print loudly, neither fails this pairwise gate
    for m in sorted(set(new_cfg) - set(old_cfg)):
        print(f"  {m:58s} {'(new)':>12} -> "
              f"{new_cfg[m].get('value')!r:>12}  NEW (baseline for trend)")
    for m in sorted(set(old_cfg) - set(new_cfg)):
        print(f"  {m:58s} {old_cfg[m].get('value')!r:>12} -> "
              f"{'(gone)':>12}  GONE (was this intentional?)")
    failures = [f for f in failures if f]
    # waivers: downgrade flagged steps whose (metric, from, to) carry a
    # recorded forensic verdict — same discipline as the trend sentinel
    waiver_path = args.waivers or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_waivers.json")
    waivers = [] if args.no_waivers else load_compare_waivers(waiver_path)
    r_old, r_new = artifact_round(args.old), artifact_round(args.new)
    active, waived = [], []
    for name, msg in failures:
        w = next((w for w in waivers
                  if w.get("metric") == name and w.get("from") == r_old
                  and w.get("to") == r_new), None)
        (waived if w else active).append((name, msg, w))
    for name, _msg, w in waived:
        print(f"\nWAIVED {name} [{r_old}->{r_new}]: {w.get('reason', '')}")
    if active:
        print(f"\nFAIL: {len(active)} regression(s):", file=sys.stderr)
        for _name, msg, _w in active:
            print(f"  {msg}", file=sys.stderr)
        raise SystemExit(2)
    print("\nok: no regression beyond threshold"
          + (f" ({len(waived)} waived)" if waived else ""))


if __name__ == "__main__":
    main()
