"""The yardstick's own code: generators, load loop, clocks, trace reduction,
byte counts and peaks.  Copied from bench.py / chip_smoke.py / net/harness.py
where those were sound (PERF.md has the verdict table); nothing here imports
them, so a later PR may change or delete the originals."""
