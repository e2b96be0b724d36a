"""Device step: share (%) of the chips' combined HBM roofline in a
sharded stream cell.  The bytes the traced calls' work needs
(harness/work.py, the same count as step_roofline.stream, whatever
implements the step) over the chips that ran times the peak bandwidth
(peaks.json) times the busy seconds per chip (the trace's mean over
those chips)."""

from harness.work import roofline_share


def read(r):
    if r.driver != "stream_sharded" or r.trace is None or not r.work_bytes:
        return None
    if r.trace.busy_s <= 0:
        return None
    return roofline_share(r.work_bytes, r.trace.busy_s,
                          r.trace.n_devices * r.peaks["hbm_bytes_per_s"])
