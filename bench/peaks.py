"""Peaks of each accelerator by ``device_kind`` (``peaks.json``).  A kind
that is not in the table is an error, not a default."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(kind: str) -> dict:
    with open(TABLE) as f:
        kinds = json.load(f)["kinds"]
    if kind not in kinds:
        raise KeyError(f"no peaks for device kind {kind!r} in {TABLE.name}; "
                       f"known: {sorted(kinds)}")
    return kinds[kind]
