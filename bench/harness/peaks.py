"""The chip's published peaks, keyed by ``device_kind``. A device that is
not in ``peaks.json`` is an error, never a default."""
from __future__ import annotations

import json
import os
from typing import Dict

PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "peaks.json")


def for_kind(kind: str) -> Dict[str, float]:
    with open(PATH) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PATH}")
    return table[kind]
