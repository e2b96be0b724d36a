"""Host slot index: ns per decision the program's
``ratelimiter.stream.index`` timer (slot walk and assign) spent across
the window."""


def read(r):
    if r.driver != "stream":
        return None
    return r.per_decision_ns("ratelimiter.stream.index")
