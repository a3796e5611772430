"""A cell's inputs, read from data files: the configuration (deployment)
under ``configs/`` and the traffic mix under ``traffic/``, found by the
names in ``BENCHMARK.json``. One general stream generator and one general
arrival schedule read them, so a new cell needs new data files only."""
from __future__ import annotations

import json
import math
import os
import random
from typing import Dict, List, Optional, Tuple

import gen

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class CellError(RuntimeError):
    """The benchmark's own files do not describe a runnable cell."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str) -> Tuple[dict, dict, dict]:
    """(cell entry, configuration, traffic mix) for a workload name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(known: {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, cfg, traffic


def metrics_for(bench: dict, workload: str, trace: bool) -> List[dict]:
    """The cell's end-to-end metrics (``trace`` false) or per-layer
    metrics (``trace`` true): entries without a ``workloads`` key apply
    to every cell."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if workload in m.get("workloads", [workload])]


def queries(cfg: dict) -> Dict[str, str]:
    return gen.table2_queries(cfg["label_map"])


def stream_params(cfg: dict, traffic: dict) -> dict:
    """The configuration's stream, with the traffic mix's overrides."""
    params = dict(cfg["stream"])
    params.update(traffic.get("stream", {}))
    return params


def fix_alphabet_share(edges: List[gen.Event], alphabet, share: float,
                       n: int) -> List[gen.Event]:
    """The first ``n`` timestamps of ``edges`` with the edges re-dealt so
    that the k-th timestamp carries a label of ``alphabet`` exactly when
    floor((k + 1) * share) > floor(k * share). Edges of each kind keep
    their order, so the stream stays prefix-stable, and every window
    holds the same number of edges that reach the device, whatever the
    seed (a seed would otherwise move that number, and the work, by a
    few per cent)."""
    inside = [e for e in edges if e[3] in alphabet]
    outside = [e for e in edges if e[3] not in alphabet]
    out, i, j = [], 0, 0
    for k in range(n):
        if math.floor((k + 1) * share) > math.floor(k * share):
            src, i = inside[i], i + 1
        else:
            src, j = outside[j], j + 1
        out.append((edges[k][0],) + src[1:])
    return out


def make_stream(cfg: dict, traffic: dict, seed: int, n_body: int
                ) -> Tuple[List[gen.Event], List[gen.Event]]:
    """(fill, body): the events that fill the live window before the
    measured window, and the next ``n_body`` events that it offers. The
    generators are prefix-stable, so every seed has the same sizes."""
    p = stream_params(cfg, traffic)
    fill_s = float(cfg["fill_stream_seconds"])
    n_edges = int(math.ceil(fill_s * p["rate"] * 1.5)) + 64 + n_body
    draw = gen.GENERATORS[p["generator"]]
    kwargs = {k: v for k, v in p.items()
              if k not in ("generator", "deletion_ratio", "alphabet_share")}
    share = p.get("alphabet_share")
    if share is None:
        edges = draw(n_edges=n_edges, seed=seed, **kwargs)
    else:
        # draw enough of each kind for the re-deal, with a wide margin
        edges = draw(n_edges=int(n_edges * 1.5) + 256, seed=seed, **kwargs)
        edges = fix_alphabet_share(
            edges, set(cfg["label_map"].values()), share, n_edges)
    events = gen.with_deletions(edges, p["deletion_ratio"], seed)
    n_fill = sum(1 for e in events if e[0] <= fill_s)
    body = events[n_fill:n_fill + n_body]
    if len(body) < n_body:
        raise CellError("stream shorter than the body the traffic needs")
    return events[:n_fill], body


def schedule(traffic: dict, seed: int, seconds: float
             ) -> Tuple[List[float], bool]:
    """Due offsets (seconds after the window opens) of the body's events,
    and whether the mix is a backlog (every event due at once, the run
    measures the rate the service sustains).

    ``poisson`` arrivals give every seed the same multiset of gaps (the
    exponential distribution's quantiles at ``rate_eps``) in an order
    drawn from the seed, so seeds differ in order and not in amount."""
    kind = traffic["arrivals"]
    if kind == "backlog":
        n = int(math.ceil(traffic["events_per_window_second"] * seconds))
        return [0.0] * n, True
    if kind == "poisson":
        rate = float(traffic["rate_eps"])
        n = int(rate * seconds)
        gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
        while sum(gaps) >= seconds and n > 1:
            n -= 1
            gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
        random.Random(seed * 1_000_003 + 7).shuffle(gaps)
        due, t = [], 0.0
        for g in gaps:
            t += g
            due.append(t)
        return due, False
    raise CellError(f"unknown arrivals {kind!r}")


def find_metric_reader(name: str) -> Optional[str]:
    path = os.path.join(HERE, "metrics", name + ".py")
    return path if os.path.exists(path) else None
