"""Host time the executor spends blocked on device reads (the
``executor.sync.*`` spans: queued counter flushes, spill-ring and
overflow-table drains, expiry's liveness read) in the window, per
``executor.dispatch``."""
import programspans


def read(rec):
    dispatches = programspans.spans(rec, "executor.dispatch")
    names = programspans.span_names("executor.sync.")
    if not dispatches or names is None:
        return None
    total = 0.0
    for name in names:
        records = programspans.spans(rec, name)
        if records is None:
            return None
        total += programspans.total_ms(records)
    return total / len(dispatches)
