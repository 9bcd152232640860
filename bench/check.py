"""The comparison that decides a run's ``correct``.

After the window the plain reference (``models/<reference>.py``) trains
the same first ``checked_steps`` steps from the same seed's weights and
tokens, in float32 at ``highest`` precision, and the run is held to it:

``loss_gap``        the largest gap of a step's loss, relative to the
                    reference's loss;
``grad_norm_gap``   the first step's gradient as the optimizer got it
                    (from the moments after one step): the worst leaf's
                    gap between the program's norm and the reference's,
                    over the larger of that leaf's reference norm and the
                    median leaf's;
``update_gap``      each leaf's change over the checked steps, measured
                    the same way, over the leaves whose reference gradient
                    is at least ``1e-3`` of the median leaf's (a smaller
                    one moves by rounding alone);
``restore_bytes_differing``  bytes in which the restored checkpoint
                    differs from the state that was saved (limit 0);
``restored_step_behind``     steps by which the restored checkpoint is
                    older than the newest one acknowledged (limit 0).

Each limit is in the configuration's ``limits``; ``PERF.md`` gives the
readings each was set from.
"""

from __future__ import annotations

import statistics
from typing import Dict

#: a leaf whose reference gradient is under this share of the median
#: leaf's moves by rounding alone and is left out of ``update_gap``
STILL = 1e-3


def norm_gap(prog: Dict[str, float], ref: Dict[str, float],
             keys=None) -> float:
    keys = sorted(ref) if keys is None else sorted(keys)
    if set(prog) != set(ref):
        return float("inf")
    med = statistics.median(ref[k] for k in keys)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys)


def moving_leaves(ref_grads: Dict[str, float]):
    med = statistics.median(ref_grads.values())
    return [k for k, g in ref_grads.items() if g >= STILL * med]


def readings(run, ref: Dict) -> Dict[str, float]:
    """The numbers compared, from the program's run and the reference's."""
    n = len(ref["losses"])
    losses = run.losses[:n]
    if len(losses) < n:
        loss_gap = float("inf")
    else:
        loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, ref["losses"]))
    return {
        "loss_gap": loss_gap,
        "grad_norm_gap": norm_gap(run.grad_norms, ref["grad_norms"]),
        "update_gap": norm_gap(run.change_norms, ref["change_norms"],
                               moving_leaves(ref["grad_norms"])),
    }


def compare(driver, run) -> Dict[str, Dict[str, float]]:
    """``{name: {"value": v, "limit": l}}`` for every number compared."""
    cfg = driver.cfg
    ref = driver.reference_readings()
    values = {k: float(v) for k, v in readings(run, ref).items()}
    values["restore_bytes_differing"] = int(run.restore_bytes_differing)
    values["restored_step_behind"] = int(run.restored_step_behind)
    limits = cfg["limits"]
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
