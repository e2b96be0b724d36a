"""Storage dispatch: ns per decision the program's
``ratelimiter.stream.decide`` timer spent across the window: the drain
threads' host reconstruction of the decisions after each fetch.  A
program without the timer reports nothing."""


def read(r):
    if r.driver != "stream":
        return None
    return r.per_decision_ns("ratelimiter.stream.decide")
