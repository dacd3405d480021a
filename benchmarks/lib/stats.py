"""Percentile as net/harness.py takes it (copied), on an unsorted list."""


def percentile(xs, q):
    """The value at rank ``int(len * q)`` of the sorted sample; None when
    the sample is empty."""
    if not xs:
        return None
    s = sorted(xs)
    return s[min(len(s) - 1, int(len(s) * q))]
