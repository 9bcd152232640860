"""The restore scan's share of its roofline, in %: the least time for the
bytes a restore's verify-and-scatter must move (``work.restore_scan_bytes``)
at the HBM peak, over the device time of the restore kernel's program in
the trace, for the resumes that lie wholly inside the traced window."""

#: XLA modules that run the restore's verify and scatter
MODULES = ("apply_unpack_device",)


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window()
    page = run.cfg["deployment"]["page_size"]
    least = device = 0.0
    for span in run.trace.spans:
        if span.name != "build" or not (lo <= span.start and span.end <= hi):
            continue
        t = run.trace_lib.module_time(run.trace, MODULES, within=[span])
        if t <= 0:
            continue
        device += t
        least += run.work.restore_scan_bytes(run.leaf_nbytes, page) \
            / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / device if device > 0 else None
