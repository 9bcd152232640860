"""Pallas TPU kernels for the persistence layer's compute hot-spots.

The paper optimizes I/O; the on-device work of its adapted primitives is:
  - dirty_diff       — block-granular dirty bitmap (µLog dirty tracking)
  - popcnt_checksum  — Zero-log validity word (popcount, §3.3.1)
  - delta_pack       — gather/scatter dirty blocks (µLog content/replay)
  - flush_scan       — fused dirty bitmap + popcounts (two facts, one read)
  - flush_pack       — the whole save pass fused: diff+pack+checksum plus
                       on-device prefix-sum compaction, one HBM read
  - apply_unpack     — the whole restore pass fused: checksum-verify +
                       scatter + apply onto the base image, one HBM read
                       (flush_pack's inverse)

Each subpackage has kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
dispatch wrapper, per ``common.resolve_impl``: compiled Pallas on TPU, the
oracle elsewhere unless interpret mode is asked for), ref.py (pure-jnp
oracle). Kernels are validated in interpret mode against the oracles with
hypothesis-driven shape/dtype sweeps (tests/test_kernels.py), and compiled
for a described v5e at the Trainer's real shapes
(tests/test_tpu_compile.py).
"""

from repro.kernels.apply_unpack import ApplyUnpack, apply_unpack  # noqa: F401
from repro.kernels.delta_pack import apply_delta, pack_delta, pack_dirty  # noqa: F401
from repro.kernels.dirty_diff import dirty_blocks  # noqa: F401
from repro.kernels.flush_pack import FlushPack, flush_pack  # noqa: F401
from repro.kernels.flush_scan import flush_scan  # noqa: F401
from repro.kernels.popcnt_checksum import popcount_blocks, popcount_checksum  # noqa: F401
