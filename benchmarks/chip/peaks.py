"""Published peaks of the chips the benchmark runs on, keyed by
``device_kind`` (``peaks.json``). A kind that is not in the table is an
error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """No peaks are recorded for this ``device_kind``."""


def peaks(device_kind: str) -> dict:
    table = json.loads(TABLE.read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"{TABLE.name}; known: {sorted(table)}")
    return table[device_kind]
