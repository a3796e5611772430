"""Serving-thread time each snapshot costs in the window: the program's
``checkpoint.capture`` span (state densify and device->host copy, before
the writer thread takes over) plus the ``checkpoint.join`` spans that
wait for a writer outside a capture, over the captures."""
import programspans


def read(rec):
    captures = programspans.spans(rec, "checkpoint.capture")
    joins = programspans.spans(rec, "checkpoint.join")
    if not captures or joins is None:
        return None
    joins = [j for j in joins if j.parent != "checkpoint.capture"]
    return (programspans.total_ms(captures)
            + programspans.total_ms(joins)) / len(captures)
