"""The table of peaks, keyed by ``device_kind``.  An unknown device is an
error, never a default."""

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks recorded for device_kind {device_kind!r}: add it to "
            f"{_PATH} with its source (known: {sorted(table)})"
        )
    return table[device_kind]
