"""The chip's published peaks, keyed by ``device_kind`` (``peaks.json``).
A device that is not in the table is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str) -> dict:
    devices = json.loads(TABLE.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(devices)}")
    return devices[device_kind]
