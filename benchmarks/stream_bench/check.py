"""Whether the timed path's answers are correct: every batch the
supervisor delivered, fill and window alike, against the plain reference
(``plainref.py``) fed the same events in the same batches. Each number is
compared with its limit; every limit is 0, because the comparison is
exact."""
from __future__ import annotations

from typing import Dict, List, Tuple

import plainref
import workload

#: the numbers compared, each with its limit
LIMITS: Dict[str, int] = {
    "result_pairs_differing": 0,
    "invalidated_pairs_differing": 0,
    "batches_without_answer": 0,
    "events_never_answered": 0,
    "restarts_and_breaker_trips": 0,
}


def compare(cfg: dict, batches: List[list], results_by_lsn: dict,
            invalidated_by_lsn: dict, first_window_batch: int,
            undelivered: int, restarts_and_trips: int
            ) -> Tuple[Dict[str, int], Dict[str, int]]:
    """(numbers compared, counts for the record). ``batches[k]`` carries
    WAL lsn ``k + 1``."""
    queries = workload.queries(cfg)
    ref = plainref.PlainRPQ(queries, cfg["window"])
    diff_new = diff_inv = missing = 0
    bad_window_events = 0
    n_pairs = n_inv = 0
    for k, batch in enumerate(batches):
        new = {q: set() for q in queries}
        gone = {q: set() for q in queries}
        for event in batch:
            a, d = ref.apply(event)
            for q in queries:
                new[q] |= a[q]
                gone[q] |= d[q]
        got_new = results_by_lsn.get(k + 1)
        got_inv = invalidated_by_lsn.get(k + 1)
        if got_new is None or got_inv is None:
            missing += 1
            bad = True
        else:
            dn = sum(len(set(got_new.get(q, ())) ^ new[q]) for q in queries)
            di = sum(len(set(got_inv.get(q, ())) ^ gone[q]) for q in queries)
            diff_new += dn
            diff_inv += di
            bad = bool(dn or di)
        if bad and k >= first_window_batch:
            bad_window_events += len(batch)
        n_pairs += sum(len(v) for v in new.values())
        n_inv += sum(len(v) for v in gone.values())
    numbers = {
        "result_pairs_differing": diff_new,
        "invalidated_pairs_differing": diff_inv,
        "batches_without_answer": missing,
        "events_never_answered": undelivered,
        "restarts_and_breaker_trips": restarts_and_trips,
    }
    counts = {"batches_checked": len(batches),
              "reference_result_pairs": n_pairs,
              "reference_invalidated_pairs": n_inv,
              "window_events_failed": bad_window_events + undelivered}
    return numbers, counts


def correct(numbers: Dict[str, int]) -> bool:
    return all(numbers[k] <= lim for k, lim in LIMITS.items())
