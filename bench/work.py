"""Operations and bytes the benchmark's work needs, from shapes alone.

These are the yardstick's numerators: a roofline share or a utilization
divides the least time this work could take at the chip's peaks
(``peaks.json``) by the time it took. They count what the algorithm needs,
not what a kernel happens to do, so a kernel that does more than it must
reads lower, never higher.
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


def mamba2_train_flops_per_token(cfg: Dict) -> float:
    """FLOPs of one training step per token of a Mamba-2 model as the
    configuration runs it: forward and backward (twice the forward), with
    nothing recomputed counted.

    Per layer and token, the forward's matrix products are the input
    projection (2 D E), the output projection (2 di D), and the chunked
    SSD scan with chunk length Q: C B^T inside the chunk (2 Q N), the
    decay-weighted sum over the chunk (2 Q H P), the chunk's end state
    (2 H P N) and the inter-chunk output (2 H P N); the depthwise
    convolution adds 2 K (di + 2 N). The tied output projection over the
    padded vocabulary adds 2 D V per token."""
    H, P, N = cfg["nheads"], cfg["headdim"], cfg["d_state"]
    D, L, K, Q = cfg["d_model"], cfg["n_layer"], cfg["d_conv"], cfg["chunk_size"]
    Q = min(Q, cfg["seq"])
    di = H * P
    E = 2 * di + 2 * N + H
    mult = cfg["pad_vocab_size_multiple"]
    V = -(-cfg["vocab_size"] // mult) * mult
    layer = (2 * D * E + 2 * di * D + 2 * Q * N + 2 * Q * H * P
             + 4 * H * P * N + 2 * K * (di + 2 * N))
    return 3.0 * (L * layer + 2 * D * V)


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
