"""Per-chip peaks, keyed by ``device_kind`` as JAX reports it."""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def lookup(kind: str, path: str = PATH) -> dict:
    """The peaks of one chip kind. A kind that is not in the table is an
    error: a share of a peak that nobody published is not a number."""
    with open(path) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[kind]
