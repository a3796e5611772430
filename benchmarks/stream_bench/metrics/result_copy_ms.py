"""Mean host time of one device->host copy of a finished dispatch's
result mask: the program's ``engine.result_copy`` span (the ``np.asarray``
after ``engine.result_wait`` has waited for the dispatch), over the
window."""
import programspans


def read(rec):
    return programspans.mean_ms(programspans.spans(rec, "engine.result_copy")
                                or [])
