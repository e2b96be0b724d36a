"""Shard route: ns per decision the program's
``ratelimiter.stream.route`` timer spent across the window of a sharded
cell: the caller's pass that bins each chunk's keys by shard before the
lanes start.  A program without the timer reports nothing."""


def read(r):
    if r.driver != "stream_sharded":
        return None
    return r.per_decision_ns("ratelimiter.stream.route")
