"""95th percentile (nearest rank) of an event's wait from its due time
to the start of the supervisor batch that carries it (the start of that
batch's WAL append)."""
import math


def read(rec):
    w = rec["window"]
    starts = [s for s, _e in rec["spans"].get("wal_append", [])]
    sizes = rec["window_batch_sizes"]
    if w["backlog"] or not starts or len(starts) != len(sizes):
        return None
    waits = []
    i = 0
    for start, size in zip(starts, sizes):
        for due in w["due"][i:i + size]:
            waits.append((start - due) * 1e3)
        i += size
    waits.sort()
    return waits[max(1, math.ceil(0.95 * len(waits))) - 1]
