"""Host spans the harness puts around the program's methods.

Each span wraps one method or function of the program, found by its
dotted path. A path that no longer resolves raises at install time, so a
renamed hook stops the run instead of reading as 0. Every call records
its host-clock interval and, while the profiler runs, a
``TraceAnnotation`` named ``bench.<span>`` that the trace reduction uses
to label the device's idle gaps."""
from __future__ import annotations

import functools
import importlib
import time
from typing import Callable, Dict, List, Tuple

#: spans every traced run installs: span name -> "module:attr.path"
BASE_SPANS: Dict[str, str] = {
    "wal_append": "repro.streaming.wal:WriteAheadLog.append",
    "service_ingest": "repro.streaming.service:PersistentQueryService.ingest",
    "dispatch_ingest": "repro.core.executor:Executor.ingest_batch",
    "dispatch_delete": "repro.core.executor:Executor.delete_batch",
    "decode": "repro.core.engine:BatchedDenseRPQEngine._decode_new_into",
    "expire": "repro.core.engine:BatchedDenseRPQEngine.expire",
    "snapshot": "repro.streaming.service:PersistentQueryService.snapshot",
    "snapshot_join": "repro.checkpoint.ckpt:wait_pending",
}


class HookMissing(RuntimeError):
    """A span's target is gone from the program."""


def _resolve(target: str) -> Tuple[object, str, Callable]:
    module_name, _, attr_path = target.partition(":")
    try:
        owner = importlib.import_module(module_name)
    except ImportError as e:
        raise HookMissing(f"span target {target}: {e}") from e
    *parents, leaf = attr_path.split(".")
    for part in parents:
        if not hasattr(owner, part):
            raise HookMissing(f"span target {target}: no {part!r}")
        owner = getattr(owner, part)
    if leaf not in vars(owner):
        raise HookMissing(f"span target {target}: {leaf!r} is not defined "
                          f"on {getattr(owner, '__name__', owner)!r}")
    return owner, leaf, vars(owner)[leaf]


def _wrap(fn: Callable, rec: List[Tuple[float, float]], annotation,
          label: str) -> Callable:
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with annotation(label):
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                rec.append((t0, time.perf_counter()))

    return wrapped


class Spans:
    """Installed span wrappers and what they recorded."""

    def __init__(self):
        self.records: Dict[str, List[Tuple[float, float]]] = {}
        self._undo: List[Tuple[object, str, object]] = []

    def install(self, spans: Dict[str, str]) -> None:
        from jax.profiler import TraceAnnotation

        for name, target in spans.items():
            owner, leaf, fn = _resolve(target)
            rec = self.records.setdefault(name, [])
            wrapped = _wrap(fn, rec, TraceAnnotation, "bench." + name)
            setattr(owner, leaf, wrapped)
            self._undo.append((owner, leaf, fn))

    def remove(self) -> None:
        for owner, leaf, fn in reversed(self._undo):
            setattr(owner, leaf, fn)
        self._undo.clear()
