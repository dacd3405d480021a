"""The table of published peaks, keyed by ``device_kind``.  A device that is
not in the table is an error, never a default."""

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(device_kind, key):
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {_PATH}")
    return float(table[device_kind][key])
