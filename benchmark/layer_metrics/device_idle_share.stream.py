"""Device: share (%) of the traced window of a stream cell in which no
operation ran on the chip (profiler trace, harness/trace.py)."""


def read(r):
    if r.driver != "stream" or r.trace is None:
        return None
    return 100.0 * r.trace.idle_share
