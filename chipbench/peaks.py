"""Published peaks of each chip, by ``device_kind`` (``peaks.json``)."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def lookup(kind: str) -> dict:
    """The peaks of a device kind; a kind not in the table is an error."""
    table = json.loads(TABLE.read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {TABLE}; "
                       f"known: {sorted(table)}")
    return table[kind]
