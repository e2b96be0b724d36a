"""Device: share (%) of the traced window of a sharded stream cell in
which no operation ran on a chip, averaged over the chips that ran
anything (profiler trace, harness/trace.py)."""


def read(r):
    if r.driver != "stream_sharded" or r.trace is None:
        return None
    return 100.0 * r.trace.idle_share
