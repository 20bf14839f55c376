"""The chip's peak rates, keyed by the device kind JAX reports.

A roofline share is the least time the chip could take for the work the
algorithm needs, over the time it took; the peaks are its denominator.
A device kind that is not in the table is an error: there is no default
chip.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks_for(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peak rates of one device kind."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]
