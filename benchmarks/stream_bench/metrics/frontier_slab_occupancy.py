"""Share of the frontier slab's rows that the window's frontier rounds
relaxed: the program's ``frontier.rows_relaxed`` over
``frontier.slab_rows`` (q_cap * f_cap * rounds of every frontier dispatch
that did not fall back to the dense loop)."""
import programspans


def read(rec):
    slab = programspans.counter_total(rec, "frontier.slab_rows")
    relaxed = programspans.counter_total(rec, "frontier.rows_relaxed")
    if not slab or relaxed is None:
        return None
    return relaxed / slab
