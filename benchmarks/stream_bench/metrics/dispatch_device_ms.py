"""Device time of one frontier ingest or delete dispatch: the device's
executions of the jitted ``_ingest_frontier`` and ``_delete_frontier``
programs in the trace, summed, over their count."""

PROGRAMS = ("_ingest_frontier", "_delete_frontier")


def read(rec):
    trace = rec["trace"]
    if not trace or not trace.get("programs"):
        return None
    progs = [trace["programs"][p] for p in PROGRAMS]
    count = sum(p["count"] for p in progs)
    if not count:
        return None
    return sum(p["device_s"] for p in progs) / count * 1e3
