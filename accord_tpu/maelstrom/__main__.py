"""Real Maelstrom entry point: JSON lines on stdin, replies on stdout.

Rebuild of ref: accord-maelstrom/src/main/java/accord/maelstrom/Main.java
:145-243 (listen loop).  Run under the Maelstrom harness as e.g.:

    maelstrom test -w txn-list-append --bin accord-maelstrom-node ...

where the bin wraps ``python -m accord_tpu.maelstrom``.  Single-threaded:
stdin is polled with a timeout equal to the next due timer, so the timer
heap (progress log scans, callback timeout sweeper) fires without threads.
"""

from __future__ import annotations

import heapq
import itertools
import json
import os
import select
import sys
import time
from typing import Callable, List, Optional, Tuple

from .. import api
from .node import MaelstromProcess


class _Scheduled(api.Scheduled):
    def __init__(self):
        self.cancelled = False

    def cancel(self) -> None:
        self.cancelled = True

    def is_cancelled(self) -> bool:
        return self.cancelled


class WallClockScheduler(api.Scheduler):
    """Timer heap over the wall clock, drained by the stdin loop."""

    def __init__(self, now_micros: Callable[[], int]):
        self.now_micros = now_micros
        self._heap: List[Tuple[int, int, Callable[[], None]]] = []
        self._seq = itertools.count()

    def now(self, run: Callable[[], None]) -> None:
        heapq.heappush(self._heap, (self.now_micros(), next(self._seq), run))

    def once(self, delay_micros: int, run: Callable[[], None]) -> api.Scheduled:
        handle = _Scheduled()

        def fire():
            if not handle.cancelled:
                run()
        heapq.heappush(self._heap,
                       (self.now_micros() + delay_micros, next(self._seq), fire))
        return handle

    def recurring(self, interval_micros: int,
                  run: Callable[[], None]) -> api.Scheduled:
        handle = _Scheduled()

        def fire():
            if handle.cancelled:
                return
            run()
            heapq.heappush(self._heap, (self.now_micros() + interval_micros,
                                        next(self._seq), fire))
        heapq.heappush(self._heap, (self.now_micros() + interval_micros,
                                    next(self._seq), fire))
        return handle

    def next_deadline(self) -> Optional[int]:
        return self._heap[0][0] if self._heap else None

    def run_due(self) -> None:
        now = self.now_micros()
        while self._heap and self._heap[0][0] <= now:
            _, _, fn = heapq.heappop(self._heap)
            fn()


def main() -> None:
    from ..ops.packing import startup
    startup()
    start = time.monotonic_ns()

    def now_micros() -> int:
        return (time.monotonic_ns() - start) // 1_000

    scheduler = WallClockScheduler(now_micros)
    stdout = sys.stdout

    def emit(dest, body: dict) -> None:
        # (self-addressed sends never reach here: MaelstromProcess
        # intercepts dest == own-name and defers them internally)
        packet = {"src": proc.name, "dest": dest, "body": body}
        stdout.write(json.dumps(packet) + "\n")
        stdout.flush()

    proc = MaelstromProcess(emit=emit, scheduler=scheduler,
                            now_micros=now_micros)

    # Read the raw fd ourselves: select() cannot see lines already pulled
    # into a TextIOWrapper's buffer, which would stall burst-delivered
    # packets until the next timer deadline.
    fd = sys.stdin.fileno()
    buf = b""
    eof = False
    while not eof:
        scheduler.run_due()
        deadline = scheduler.next_deadline()
        timeout = (max(0.0, (deadline - now_micros()) / 1e6)
                   if deadline is not None else 1.0)
        ready, _, _ = select.select([fd], [], [], timeout)
        if ready:
            chunk = os.read(fd, 65536)
            if not chunk:
                eof = True
            buf += chunk
        while b"\n" in buf:
            line, buf = buf.split(b"\n", 1)
            line = line.strip()
            if not line:
                continue
            try:
                packet = json.loads(line)
            except json.JSONDecodeError:
                # a complete but malformed line: drop it loudly — prepending
                # it to the next line would poison the stream forever
                print(f"discarding malformed input line: {line[:200]!r}",
                      file=sys.stderr)
                continue
            proc.handle(packet)
    # EOF: the harness never closes stdin mid-test, so this is shutdown —
    # but in-flight coordinations may still need a few timer rounds to
    # reply (smoke tests pipe a fixed set of lines and read the output).
    # Drain until no coordination is in flight (recurring scans keep the
    # timer heap perpetually non-empty, so heap emptiness can't be the
    # condition), bounded by a grace window.
    grace_until = now_micros() + 2_000_000
    hard_stop = now_micros() + 30_000_000
    while now_micros() < min(grace_until, hard_stop):
        scheduler.run_due()
        busy = proc.node is not None and proc.node._coordinating
        deadline = scheduler.next_deadline()
        if busy:
            # live coordinations keep the grace window open (first-compile
            # of the device kernels can dominate the first txn); the hard
            # stop bounds a wedged coordination
            grace_until = now_micros() + 2_000_000
        else:
            # coordinations may not have STARTED yet (handle() defers via
            # scheduler.now()): only stop once nothing is due imminently
            if deadline is None or deadline > now_micros() + 10_000:
                break
        if deadline is None:
            break
        time.sleep(min(max(deadline - now_micros(), 0) / 1e6, 0.05))


if __name__ == "__main__":
    main()
