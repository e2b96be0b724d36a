"""Device step: share (%) of the HBM roofline.  The bytes the traced
calls' work needs (harness/work.py: per distinct key one read and one
write of the reference's i64 state, per request an i32 in and an allow
bit out) at the peak bandwidth (peaks.json; 819 GB/s on a v5e is the
sequential peak), over the device's busy seconds in the traced
window."""

from harness.work import roofline_share


def read(r):
    if r.driver != "stream" or r.trace is None or not r.work_bytes:
        return None
    if r.trace.busy_s <= 0:
        return None
    return roofline_share(r.work_bytes, r.trace.busy_s,
                          r.peaks["hbm_bytes_per_s"])
