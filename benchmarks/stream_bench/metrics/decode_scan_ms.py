"""Mean host time of the ``np.nonzero`` scan over a result mask's Q*N*N
cells, one per decode: the program's ``engine.decode_scan`` span, over
the window."""
import programspans


def read(rec):
    return programspans.mean_ms(programspans.spans(rec, "engine.decode_scan")
                                or [])
