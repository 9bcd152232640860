"""A whole save's share of the chip's HBM peak, in %: the least time for
the bytes a delta save's scan must move (``work.save_scan_bytes``) at the
HBM peak, over the wall time of the saves that completed in the window
and whose dirty blocks the traced run counted. It bounds
``flush_pack_roofline`` from below over the whole save, so a kernel taken
off the save's path still has a share that a claim must move."""


def read(run):
    lo, hi = run.window
    least = wall = 0.0
    for s in run.spans.items:
        if s.name != "save" or not lo <= s.t1 <= hi \
                or s.step not in run.save_dirty_blocks:
            continue
        wall += s.seconds
        least += run.work.save_scan_bytes(
            run.leaf_nbytes, run.save_dirty_blocks[s.step]) \
            / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / wall if wall > 0 else None
