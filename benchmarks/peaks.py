"""The benchmark's own table of published peaks (``peaks.json``)."""

from __future__ import annotations

import json
import os


class UnknownDeviceKind(LookupError):
    """A device reported a kind the table has no published peaks for."""


def load(device_kind: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
    with open(path) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise UnknownDeviceKind(
            f"no published peaks for device_kind {device_kind!r} in "
            f"benchmarks/peaks.json (known: {sorted(table)})"
        )
    return table[device_kind]
