"""Storage dispatch: ns per decision in the program's
``ratelimiter.stream.fetch`` timer across the window: the host
blocked on device results."""


def read(r):
    if r.driver != "stream":
        return None
    return r.per_decision_ns("ratelimiter.stream.fetch")
