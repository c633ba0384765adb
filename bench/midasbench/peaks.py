"""Published per-chip peaks, keyed by JAX's ``device_kind``."""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS_FILE = Path(__file__).resolve().parent.parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> Dict[str, float]:
    """``flops_per_s``, ``hbm_bytes_per_s`` and ``hbm_bytes`` of one chip.
    A kind missing from the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {path.name}; "
            f"known: {', '.join(sorted(table))}"
        )
    return {k: float(v) for k, v in table[device_kind].items()}
