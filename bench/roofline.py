"""Chip peaks and the least work of the phase-2 table build.

``peaks.json`` beside this file holds each chip's published peaks, keyed
by the ``device_kind`` JAX reports.  A chip that is not in it is an
error, never a default.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
F64_BYTES = 8


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; raises KeyError for a
    chip the table does not list."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def table_bytes(cells: int, versions: int, caches: int) -> int:
    """The least bytes one stacked DS_PGM table build moves: for each of
    ``cells`` decision cells a selection mask of ceil(n/8) bytes per
    (view version, indication pattern) row written, and the [V, n]
    float64 exclusion probabilities pi and nu read once."""
    written = cells * versions * (1 << caches) * -(-caches // 8)
    read = 2 * versions * caches * F64_BYTES
    return written + read


def table_seconds(cells: int, versions: int, caches: int,
                  device_kind: str) -> float:
    """The least time any implementation needs for those bytes."""
    return table_bytes(cells, versions, caches) \
        / peaks(device_kind)["hbm_bytes_per_s"]
