"""Median (nearest rank), over every event due in the window, of its due
time -> the return of the supervisor call that delivered its results."""
import math


def read(rec):
    w = rec["window"]
    if w["backlog"]:
        return None
    lat = sorted((d - u) * 1e3 for u, d in zip(w["due"], w["delivered"])
                 if d is not None)
    if not lat:
        return None
    return lat[max(1, math.ceil(0.50 * len(lat))) - 1]
