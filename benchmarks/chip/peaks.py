"""Per-chip peaks, keyed by JAX's ``device_kind`` (``peaks.json``)."""
from __future__ import annotations

import json
import pathlib

TABLE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    devices = json.loads(TABLE.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{TABLE.name} (has {sorted(devices)})")
    return devices[device_kind]
