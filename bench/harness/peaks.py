"""Published peaks of each chip, keyed by JAX's ``device_kind``.

The table and its sources live in ``peaks.json`` beside this file.  A
device that is not in it is an error, never a default.
"""

from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).with_name("peaks.json")


def peak(device_kind: str) -> dict:
    table = json.loads(_TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in {_TABLE.name}")
    return table[device_kind]
