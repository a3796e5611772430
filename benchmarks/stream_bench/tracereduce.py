"""Reduction of a profiler trace (``*.xplane.pb``) to the benchmark's
device numbers.

* busy time: the union of the intervals in which an XLA operation ran on
  a device, inside the window the host span ``bench.window`` marks,
  averaged over the devices that ran any;
* device time per jitted program: the device's module executions that
  start in the window and whose name contains the program's name,
  summed, with their count;
* the operations that took most device time, named
  ``<program>/<op>``, by their own time (less the ops nested in them);
* the device's idle time, each stretch of it labelled with the innermost
  ``bench.*`` host span open then (``untracked`` when none is), summed
  per label.

``jax.profiler.ProfileData`` reads the file, so nothing beyond JAX is
needed. Times in the trace are nanoseconds on one clock for the host and
the devices.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv: Interval, lo: float, hi: float) -> Optional[Interval]:
    s, e = max(iv[0], lo), min(iv[1], hi)
    return (s, e) if e > s else None


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """``jit__ingest_frontier(1234)`` -> ``_ingest_frontier``."""
    name = event_name.split("(", 1)[0]
    return name[len("jit_"):] if name.startswith("jit_") else name


def _in_modules(ops, modules):
    """Prefix each op's name with the module execution it ran in."""
    modules = sorted(modules, key=lambda m: m[1])
    out, k = [], 0
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while k < len(modules) and modules[k][2] <= s:
            k += 1
        if k < len(modules) and modules[k][1] <= s:
            name = module_name(modules[k][0]) + "/" + name
        out.append((name, s, e))
    return out


def _self_times(ops: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Device time per op name, less the time of the ops nested inside it
    (a conditional or a loop holds the ops of its body on the same line)."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [name, end, time of children, start]
    order = sorted(ops, key=lambda o: (o[1], -o[2]))
    for name, s, e in order:
        while stack and stack[-1][1] <= s:
            n, end, child, st = stack.pop()
            out[n] = out.get(n, 0.0) + (end - st - child)
            if stack:
                stack[-1][2] += end - st
        stack.append([name, e, 0.0, s])
    while stack:
        n, end, child, st = stack.pop()
        out[n] = out.get(n, 0.0) + (end - st - child)
        if stack:
            stack[-1][2] += end - st
    return out


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            for ev in line.events:
                yield ev.name, float(ev.start_ns), float(ev.start_ns
                                                         + ev.duration_ns)


def _host_spans(planes) -> List[Tuple[str, float, float]]:
    """The ``bench.*`` spans of the host thread that holds the window
    span (the harness's own thread, where they nest)."""
    for plane in planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            spans = [(ev.name, float(ev.start_ns),
                      float(ev.start_ns + ev.duration_ns))
                     for ev in line.events if ev.name.startswith("bench.")]
            if any(name == WINDOW_SPAN for name, _s, _e in spans):
                return spans
    raise ValueError(f"no {WINDOW_SPAN} span in the trace")


def _timeline(spans, lo: float, hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut into pieces, each labelled with the innermost span
    open in it (``untracked`` where none is)."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []
    t = lo

    def advance(until: float) -> None:
        nonlocal t
        until = min(until, hi)
        if until > t:
            label = stack[-1][0][len("bench."):] if stack else "untracked"
            out.append((t, until, label))
            t = until

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        if name == WINDOW_SPAN:
            continue
        while stack and stack[-1][1] <= s:
            advance(stack[-1][1])
            stack.pop()
        advance(s)
        stack.append((name, e))
    while stack:
        advance(stack[-1][1])
        stack.pop()
    advance(hi)
    return out


def reduce_planes(planes, programs: Sequence[str] = (), top: int = 10
                  ) -> Dict[str, object]:
    """The benchmark's numbers from a trace's planes (see module doc)."""
    planes = list(planes)
    host = _host_spans(planes)
    lo, hi = next((s, e) for name, s, e in host if name == WINDOW_SPAN)
    timeline = _timeline(host, lo, hi)
    busy_per_device: List[float] = []
    op_time: Dict[str, float] = {}
    prog: Dict[str, List[float]] = {p: [0, 0.0] for p in programs}
    idle: Dict[str, float] = {}
    for plane in planes:
        if not plane.name.startswith("/device:") or "CUSTOM" in plane.name:
            continue
        ops = []
        for name, s, e in _events(plane, OPS_LINE):
            iv = _clip((s, e), lo, hi)
            if iv is not None:
                ops.append((op_name(name),) + iv)
        if not ops:
            continue
        modules = list(_events(plane, MODULES_LINE))
        for name, t in _self_times(_in_modules(ops, modules)).items():
            op_time[name] = op_time.get(name, 0.0) + t
        busy = _union((s, e) for _n, s, e in ops)
        busy_per_device.append(sum(e - s for s, e in busy))
        for name, s, e in modules:
            if not lo <= s < hi:
                continue
            for p in programs:
                if p in name:
                    prog[p][0] += 1
                    prog[p][1] += e - s
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        k = 0
        for gs, ge in gaps:
            while k < len(timeline) and timeline[k][1] <= gs:
                k += 1
            j = k
            while j < len(timeline) and timeline[j][0] < ge:
                a, b, label = timeline[j]
                piece = min(b, ge) - max(a, gs)
                if piece > 0:
                    idle[label] = idle.get(label, 0.0) + piece
                j += 1
    if not busy_per_device:
        return {"window_s": (hi - lo) / 1e9, "busy_s": None}
    n_dev = len(busy_per_device)
    ns = 1e9
    return {
        "window_s": (hi - lo) / ns,
        "busy_s": sum(busy_per_device) / n_dev / ns,
        "devices": n_dev,
        "programs": {p: {"count": int(c) // n_dev if n_dev else 0,
                         "device_s": t / n_dev / ns}
                     for p, (c, t) in prog.items()},
        "device_ops": [[name, t / n_dev / ns] for name, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label, t / n_dev / ns] for label, t in sorted(
            idle.items(), key=lambda kv: -kv[1])[:top]],
    }


def reduce_file(path: str, programs: Sequence[str] = (), top: int = 10
                ) -> Dict[str, object]:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, programs, top)
