"""Host slot index: ns per decision the program's
``ratelimiter.stream.assign`` timer spent across the window: the part
of the slot walk the caller waits for (an inline walk, or the wait on
the walk prefetched on a worker).  ``stream.index_ns_per_decision``
counts every walk second on every thread; this counts only the exposed
ones.  A program without the timer reports nothing."""


def read(r):
    if r.driver != "stream":
        return None
    return r.per_decision_ns("ratelimiter.stream.assign")
