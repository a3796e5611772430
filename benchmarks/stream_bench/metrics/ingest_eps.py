"""Events per second the service sustains on a backlog: every event
delivered in the window, over the window, which closes at the return of
the first supervisor call that ends after ``--seconds``."""


def read(rec):
    w = rec["window"]
    if not w["backlog"] or w["t_close"] <= w["t_start"]:
        return None
    return w["fed"] / (w["t_close"] - w["t_start"])
