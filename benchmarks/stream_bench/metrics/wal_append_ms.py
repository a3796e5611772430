"""Mean host time of one WAL append (encode, write, flush, fsync) per
supervisor batch in the window, from the harness's span around
``WriteAheadLog.append``."""


def read(rec):
    spans = rec["spans"].get("wal_append")
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) * 1e3
