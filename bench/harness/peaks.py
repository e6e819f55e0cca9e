"""Published peaks of each chip, keyed by the ``device_kind`` JAX reports."""
from __future__ import annotations

import json
import os
from typing import Dict

from harness.spec import BENCH_DIR


def peaks(device_kind: str) -> Dict:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
