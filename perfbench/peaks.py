"""The chips' published peaks, keyed by JAX's ``device_kind``."""

from __future__ import annotations

import json
import os
from typing import Dict

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> Dict:
    """The peak table row of ``device_kind``; a chip not in the table is an
    error, never a default."""
    with open(PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(table)}")
    return table[device_kind]
