"""The save scan's share of its roofline, in %: the least time for the
bytes a delta save's scan must move (``work.save_scan_bytes``, counted
from the state and its dirty blocks) at the HBM peak, over the device time
of the save-scan kernel's program in the trace, for the saves that lie
wholly inside the traced window."""

#: XLA modules that run the save's scan
MODULES = ("flush_pack_device",)


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window()
    least = device = 0.0
    for span in run.trace.spans:
        name, _, step = span.name.partition("@")
        if name != "save" or not (lo <= span.start and span.end <= hi):
            continue
        step = int(step)
        if step not in run.save_dirty_blocks:
            continue
        t = run.trace_lib.module_time(run.trace, MODULES, within=[span])
        if t <= 0:
            continue
        device += t
        least += run.work.save_scan_bytes(
            run.leaf_nbytes, run.save_dirty_blocks[step]) \
            / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / device if device > 0 else None
