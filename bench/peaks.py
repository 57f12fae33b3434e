"""Peak rates per chip, keyed by JAX's `device_kind` (`peaks.json`)."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def lookup(device_kind: str, table: Path = TABLE) -> dict:
    """The peaks of `device_kind`; a kind missing from the table is an
    error, never a default."""
    peaks = json.loads(table.read_text())
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{table.name}; known: {sorted(peaks)}")
    return peaks[device_kind]
