"""Operations and bytes the benchmark's work needs, from shapes alone.

These are the yardstick's numerators: a roofline share or a utilization
divides the least time this work could take at the chip's peaks
(``peaks.json``) by the time it took. They count what the algorithm
needs, not what a kernel happens to do, so a kernel that does more than it
must reads lower, never higher. A model's training FLOPs per token are
its reference module's (``models/<reference>.py``).
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``. A kind that is
    not in the table is an error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def save_scan_bytes(leaf_nbytes: Iterable[int], dirty_blocks: int,
                    block_bytes: int = 4096) -> int:
    """HBM bytes a delta save's scan must move: read every live leaf and
    its last-saved snapshot once, and write each dirty block once."""
    return 2 * sum(int(n) for n in leaf_nbytes) + dirty_blocks * block_bytes


def restore_scan_bytes(leaf_nbytes: Iterable[int], page_bytes: int) -> int:
    """HBM bytes a restore's verify-and-scatter must move: read each
    saved page once and write it once into the leaf's image."""
    pages = sum(-(-int(n) // page_bytes) for n in leaf_nbytes)
    return 2 * pages * page_bytes
