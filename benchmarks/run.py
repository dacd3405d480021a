#!/usr/bin/env python3
"""The one command of the benchmark: run ONE cell once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which owns the chip; no JAX child; the platform is never set
here.  The cell's configuration, traffic mix and per-layer metrics are found
by the names BENCHMARK.json gives (see README.md).  Set-up lines go to stdout
first; the LAST stdout line is the contract's object and nothing more.  On a
machine without a TPU (or with fewer chips than the cell asks for) the exit
code is non-zero and no metric is printed."""

import time

T_PROCESS = time.perf_counter()     # setup_s counts from here

import argparse                      # noqa: E402
import importlib                     # noqa: E402
import importlib.util                # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import sys                           # noqa: E402
import traceback                     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# journals and the trace of a cell's latest run (emptied when it starts)
SCRATCH = os.path.join(ROOT, ".bench_run")


def _say(kind, **fields):
    print(json.dumps({"line": kind, **fields}, default=str), flush=True)


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def _metric_reader(name):
    """metrics/<name>.py, loaded by path (names hold dots)."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _entries(cache_dir):
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def _peak_bytes(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def main(argv=None, rehearsal=None):
    """``rehearsal`` (a dict of overrides: {"sizes": {...}, "traffic":
    {...}}) is for the CPU rehearsal tests only and has no command-line
    spelling: a rehearsal runs on whatever devices jax has and says
    ``"rehearsal": true`` in its last line, so it cannot be taken for a chip
    run."""
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)

    bench = _load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"run.py: no workload {args.workload!r} in BENCHMARK.json "
              f"(has {sorted(cells)})", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _load_json(ROOT, entry["file"])
    traffic = _load_json(HERE, "traffic", cell["traffic"] + ".json")
    if rehearsal:
        config["sizes"].update(rehearsal.get("sizes", {}))
        traffic.update(rehearsal.get("traffic", {}))

    try:
        from accord_tpu.ops.packing import startup
    except ImportError as e:
        print(f"run.py: the accord_tpu package is not beside benchmarks/ "
              f"({e})", file=sys.stderr)
        return 2
    cache_dir = startup()
    import jax
    # keep EVERY program in the persistent cache, in this process only (the
    # program's own startup() leaves jax's 1 s threshold: PERF.md)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if not rehearsal and (devices[0].platform != "tpu"
                          or len(devices) < cell["chips"]):
        print(f"run.py: {args.workload} needs {cell['chips']} TPU chip(s), "
              f"jax found {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        return 3
    from benchmarks.lib.compile_clock import COMPILE
    from benchmarks.lib import trace_reduce
    from benchmarks.lib.tracer import NoTracer, Tracer
    COMPILE.install()
    _say("start", workload=args.workload, seed=args.seed,
         seconds=args.seconds, trace=args.trace,
         compile_cache_dir=cache_dir,
         compile_cache_entries=_entries(cache_dir),
         import_s=time.perf_counter() - T_PROCESS)

    scratch = os.path.join(SCRATCH, args.workload)
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch, exist_ok=True)
    driver_mod = importlib.import_module(
        f"benchmarks.drivers.{config['driver']}")
    driver = driver_mod.Driver(config, traffic, args.seed, scratch)
    tracer = (Tracer(os.path.join(scratch, "trace"), driver.trace_slice_s)
              if args.trace else NoTracer())
    record, correct, trace = None, False, None
    try:
        driver.setup()
        _say("setup", **driver.info.get("setup", {}),
             compile=COMPILE.snap())
        driver.warm()
        _say("warm", **driver.info.get("warm", {}), compile=COMPILE.snap())
        setup_s = time.perf_counter() - T_PROCESS
        record = driver.window(args.seconds, tracer)
        correct = driver.check()
    except Exception:      # noqa: BLE001 — the run's boundary: reported, rc 1
        traceback.print_exc()
        driver.problems.append("the run raised (traceback on stderr)")
        correct = False
    finally:
        tracer.stop()
        driver.close()
    _say("info", **{k: v for k, v in driver.info.items()
                    if k not in ("setup", "warm")},
         problems=driver.problems, compile=COMPILE.snap(),
         compile_cache_entries=_entries(cache_dir))
    if record is None:
        return 1

    record["device_kind"] = devices[0].device_kind
    record["setup_s"] = setup_s
    if args.trace:
        path = tracer.trace_file()
        if path is None:
            print("run.py: the profiler wrote no trace", file=sys.stderr)
            return 1
        plain = trace_reduce.load(path)
        _say("trace", file=os.path.relpath(path, ROOT),
             bytes=os.path.getsize(path),
             outline=trace_reduce.outline(plain)[:60])
        trace = trace_reduce.reduce(plain, driver.annotations)
        _say("trace_reduced", **trace)
    record["trace"] = trace

    metrics = {}
    if args.trace:
        for m in bench["per_layer"]:
            if not _applies(m, args.workload):
                continue
            value = _metric_reader(m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(record["end_to_end"], setup_s=setup_s)
        for m in bench["end_to_end"]:
            if not _applies(m, args.workload):
                continue
            if values.get(m["name"]) is None:
                print(f"run.py: the driver gave no {m['name']}",
                      file=sys.stderr)
                return 1
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    _say("window", window_s=record["window_s"],
         end_to_end=record["end_to_end"], setup_s=setup_s,
         compile_in_window=record["compile"],
         counters=record["counters"])
    last = {"correct": bool(correct), "attempted": int(record["attempted"]),
            "failed": int(record["failed"]), "metrics": metrics,
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices),
                       "memory_peak_bytes": _peak_bytes(devices)}}
    if args.trace:
        last["device"]["busy_s"] = trace["busy_s"]
        last["device"]["window_s"] = trace["window_s"]
        last["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    if rehearsal:
        last["rehearsal"] = True
    print(json.dumps(last), flush=True)
    return 0 if correct else 1


if __name__ == "__main__":
    raise SystemExit(main())
