"""Share of the window's frontier dispatches (ingest and delete) that
overflowed their frontier capacity and fell back to the dense loop, from
the executor's ``frontier_stats`` counters."""


def read(rec):
    f = rec["frontier"]
    if not f.get("dispatches"):
        return None
    return f["fallbacks"] / f["dispatches"]
