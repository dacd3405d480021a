"""Incremental interval index for range stabbing/overlap queries.

The role of ref: accord-core/src/main/java/accord/utils/SearchableRangeList
.java:19-48 (the CINTIA checkpointed list), which is immutable and rebuilt
from every entry after a mutation: right while range txns were epoch fences
and durability rounds, wrong once most of a store's txns are range scans.
This index is kept by WIDTH CLASS instead: an interval of width w lives in
the sorted row of class ``w.bit_length()``, so every entry of class c is
narrower than 2**c and one that holds a token starts in the 2**c tokens at
or below it.  An insert or a removal is one bisect into one row; a query is
two bisects a class (at most 64 classes, as many as there are widths in
use) plus the candidates in the window, never a walk of the index.  The
rebuilt list lives on as the tests' oracle (tests/range_index_oracle.py);
the device analogue is the bucketed footprint table of
accord_tpu.ops.deps_kernel.
"""

from __future__ import annotations

import bisect
from typing import Dict, Iterable, Iterator, List, Tuple


class RangeIndex:
    """Mutable index over (start, end, payload) half-open intervals;
    payloads of equal intervals must be mutually orderable."""

    __slots__ = ("_rows", "_n")

    def __init__(self, entries: Iterable[Tuple[int, int, object]] = ()):
        # width class -> entries sorted by (start, end, payload)
        self._rows: Dict[int, List[Tuple[int, int, object]]] = {}
        self._n = 0
        for start, end, payload in entries:
            self.add(start, end, payload)

    def __len__(self) -> int:
        return self._n

    def add(self, start: int, end: int, payload) -> None:
        row = self._rows.setdefault((end - start).bit_length(), [])
        bisect.insort(row, (start, end, payload))
        self._n += 1

    def remove(self, start: int, end: int, payload) -> None:
        cls = (end - start).bit_length()
        row = self._rows[cls]
        at = bisect.bisect_left(row, (start, end, payload))
        if row[at] != (start, end, payload):
            raise KeyError((start, end, payload))
        del row[at]
        self._n -= 1
        if not row:
            del self._rows[cls]

    def overlapping(self, lo: int,
                    hi: int) -> Iterator[Tuple[int, int, object]]:
        """Entries overlapping [lo, hi): of each class, those that start
        inside the window or in the 2**class tokens below it and end
        above ``lo``."""
        for cls, row in self._rows.items():
            for at in range(bisect.bisect_left(row, (lo - (1 << cls) + 1,)),
                            bisect.bisect_left(row, (hi,))):
                if row[at][1] > lo:
                    yield row[at]

    def stabbing(self, token: int) -> Iterator[Tuple[int, int, object]]:
        """Entries whose [start, end) contains ``token``."""
        return self.overlapping(token, token + 1)
