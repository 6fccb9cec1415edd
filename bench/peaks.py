"""Published peaks of each accelerator, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def lookup(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    with open(PATH) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PATH}; known: {sorted(table)}")
    return table[device_kind]
