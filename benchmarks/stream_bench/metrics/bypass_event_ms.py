"""Serving-thread time of one engine chunk that reaches no dispatch (every
event outside the queries' label alphabet, or a delete of vertices no
longer interned): the program's ``engine.intern`` spans valued 0 (the
label lookups) plus its ``engine.bypass`` spans (the clock advance),
over the count of ``engine.bypass``, in the window."""
import programspans


def read(rec):
    bypass = programspans.spans(rec, "engine.bypass")
    interns = programspans.spans(rec, "engine.intern")
    if not bypass or interns is None:
        return None
    lookups = [r for r in interns if r.value == 0]
    return (programspans.total_ms(lookups)
            + programspans.total_ms(bypass)) / len(bypass)
