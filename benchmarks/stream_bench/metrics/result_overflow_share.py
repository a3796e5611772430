"""Share of the window's fetched dispatch results that overflowed the
device's row compaction, so that the whole ``(Q, N, N)`` mask crossed to
the host: the program's ``engine.result_overflows`` over
``engine.result_fetches``. A program without these counters reads
nothing."""
import programspans


def read(rec):
    fetches = programspans.counter_total(rec, "engine.result_fetches")
    overflows = programspans.counter_total(rec, "engine.result_overflows")
    if not fetches or overflows is None:
        return None
    return overflows / fetches
