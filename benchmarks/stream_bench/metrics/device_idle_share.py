"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window."""


def read(rec):
    trace = rec["trace"]
    if not trace or not trace.get("busy_s") or not trace["window_s"]:
        return None
    return 1.0 - trace["busy_s"] / trace["window_s"]
