"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

The numbers and their source are in ``peaks.json`` beside this file. A
device that is not in the table is an error: a share of a peak is never
taken against a default.
"""
from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def table() -> dict:
    with open(_PATH) as f:
        return json.load(f)


def peak(device_kind: str, what: str) -> float:
    devices = table()["devices"]
    if device_kind not in devices:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(devices)}")
    return float(devices[device_kind][what])
