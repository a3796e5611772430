"""Host time of one slot recycling at an expiry: the program's
``engine.recycle`` span (the scan of the interner for vertices the
expiry left without a live edge, and the device clear of their slots),
over the window's recyclings."""
import programspans


def read(rec):
    return programspans.mean_ms(programspans.spans(rec, "engine.recycle")
                                or [])
