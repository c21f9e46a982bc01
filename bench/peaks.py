"""Published peaks of each device kind, from ``peaks.json``."""

from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).resolve().parent / "peaks.json"


def for_kind(kind: str) -> dict:
    """Peaks of ``kind`` (as ``device.device_kind`` names it); a kind that
    is not in the table is an error, never a default."""
    devices = json.loads(_TABLE.read_text())["devices"]
    if kind not in devices:
        raise KeyError(f"no peaks for device kind {kind!r} in {_TABLE.name}")
    return devices[kind]
