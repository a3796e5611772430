"""The system under test, driven the way its users drive it.

The path is the service's public entry: ``ServiceSupervisor.run`` (WAL
append and fsync before dispatch, async snapshots, circuit breaker) ->
``PersistentQueryService.ingest`` -> ``BatchedDenseRPQEngine`` ->
executor -> contraction backend, configured from the cell's files.

The measured window is open-loop: each event has a due time on the wall
clock, fixed before the window opens. The loop hands every due event to
``run`` in waves of at most one supervisor tick (``batch_events`` x
``drain_batches`` events, the arrival chunk ``run`` itself uses), and an
event's latency runs from its due time to the return of the call that
delivered its results, so a stalled loop cannot hide the wait it causes.
"""
from __future__ import annotations

import os
import shutil
import time
from typing import Dict, List, Optional

import gen
import workload


class InputRanDry(RuntimeError):
    """A backlog mix ran out of events before its window closed."""


class Harness:
    """One supervised service, fed batch by batch, that remembers how its
    events were batched (the comparison replays the same batches)."""

    def __init__(self, cfg: dict, state_dir: str,
                 overrides: Optional[Dict[str, object]] = None):
        from repro.streaming.service import PersistentQueryService
        from repro.streaming.supervisor import (CircuitBreaker,
                                                ServiceSupervisor)

        queries = workload.queries(cfg)
        service_kw = dict(cfg["service"])
        register_kw = dict(cfg["register"])
        for k, v in (overrides or {}).items():
            (register_kw if k in register_kw else service_kw)[k] = v

        def make_service(**kw):
            svc = PersistentQueryService(window=cfg["window"],
                                         slide=cfg["slide"],
                                         **{**service_kw, **kw})
            for name, expr in queries.items():
                svc.register(name, expr, **register_kw)
            return svc

        sup = cfg["supervisor"]
        self.state_dir = state_dir
        self.batch_events = int(sup["batch_events"])
        self.wave_cap = self.batch_events * int(sup["drain_batches"])
        self.sup = ServiceSupervisor(
            make_service, state_dir,
            batch_events=self.batch_events,
            drain_batches=int(sup["drain_batches"]),
            ckpt_every=int(sup["ckpt_every"]),
            breaker=CircuitBreaker() if sup["circuit_breaker"] else None)
        #: the events of each WAL batch, in lsn order
        self.batches: List[List[gen.Event]] = []

    def feed(self, events: List[gen.Event], sgts: list) -> None:
        """Hand one wave (at most ``wave_cap`` events) to the supervisor."""
        for i in range(0, len(events), self.batch_events):
            self.batches.append(events[i:i + self.batch_events])
        self.sup.run(sgts)

    def warm(self, max_dead: int) -> None:
        """Compile what the window may meet first and the fill may not:
        the slot-recycling program for every count of dead slots up to
        ``max_dead`` (the program compiles one per count) and the clock
        advance of an event that reaches no dispatch. Only slots that no
        vertex holds are cleared and the clock is advanced to -inf, so
        nothing changes."""
        self.sup.service.queries  # materializes the dense group
        group = self.group
        free = [s for s, v in enumerate(group.vertex_of) if v is None]
        for k in range(1, min(max_dead, len(free)) + 1):
            group.executor.clear_slots(free[-k:])
        group.executor.advance_clock(float("-inf"))

    def fill(self, events: List[gen.Event], sgts: list) -> None:
        """Feed the live window's fill as backlog waves, without
        snapshots: they serve no request and would only write to disk.
        The supervisor's batch counter then restarts, so the window's
        snapshots fall every ``ckpt_every`` batches from its first one,
        whatever the rate: a faster service pays for as many snapshots
        per batch as a slower one."""
        if not hasattr(self.sup, "_dispatches"):
            raise RuntimeError("ServiceSupervisor no longer counts its "
                               "batches in _dispatches; the snapshot "
                               "cadence cannot be restarted")
        cadence = self.sup.ckpt_every
        self.sup.ckpt_every = len(events) + 1
        try:
            for i in range(0, len(events), self.wave_cap):
                self.feed(events[i:i + self.wave_cap],
                          sgts[i:i + self.wave_cap])
        finally:
            self.sup.ckpt_every = cadence
            self.sup._dispatches = 0
        self.warm_snapshot()

    def warm_snapshot(self) -> None:
        """Run the snapshot path once on the filled window, so that the
        window's snapshots find its programs compiled (the device
        densify of the dist runs op by op: ~57 small programs). It
        writes into a directory of its own, removed at once; the
        supervisor's checkpoints and WAL are untouched."""
        path = os.path.join(self.state_dir, "warm_snapshot")
        self.sup.service.snapshot(path, step=0)
        shutil.rmtree(path, ignore_errors=True)

    @property
    def group(self):
        return self.sup.service._group

    def close(self) -> None:
        self.sup.wal.close()


def to_sgts(events: List[gen.Event]) -> list:
    from repro.streaming.stream import SGT

    return [SGT(*e) for e in events]


def drive_window(h: Harness, body: List[gen.Event], sgts: list,
                 offsets: List[float], backlog: bool, seconds: float,
                 grace: float = 60.0, on_wait=None) -> Dict[str, object]:
    """Offer the body's events on their schedule for ``seconds``.

    A backlog mix keeps the input full until the first call that returns
    after ``seconds``; the window closes there. Any other mix offers the
    events due inside ``seconds`` and waits for each, at most ``grace``
    seconds past the close."""
    n = len(offsets)
    delivered: List[Optional[float]] = [None] * n
    t_start = time.perf_counter()
    due = [t_start + o for o in offsets]
    i = 0
    t_close = t_start
    while i < n:
        now = time.perf_counter()
        if backlog:
            if now - t_start >= seconds:
                break
            j = min(i + h.wave_cap, n)
        else:
            if now - t_start >= seconds + grace:
                break
            if due[i] > now:
                if on_wait is not None:
                    on_wait(due[i] - now)
                else:
                    time.sleep(due[i] - now)
                continue
            j = i + 1
            while j < n and j - i < h.wave_cap and due[j] <= now:
                j += 1
        h.feed(body[i:j], sgts[i:j])
        t_close = time.perf_counter()
        for k in range(i, j):
            delivered[k] = t_close
        i = j
    if backlog and i >= n:
        raise InputRanDry(f"all {n} offered events were taken within "
                          f"{t_close - t_start:.1f} s of a {seconds} s window")
    return {"t_start": t_start, "t_close": t_close, "due": due,
            "delivered": delivered, "fed": i, "backlog": backlog}
