"""Storage dispatch: ns per decision in the program's
``ratelimiter.stream.{route,pack,layout,enqueue}`` timers across the
window (host work that builds and enqueues each chunk)."""


def read(r):
    if r.driver != "stream":
        return None
    return r.per_decision_ns(*(f"ratelimiter.stream.{s}"
                               for s in ("route", "pack", "layout",
                                         "enqueue")))
