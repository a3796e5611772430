import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Dry-run of the PAPER'S TECHNIQUE on the production mesh: one bottleneck
relaxation round of the dense streaming-RPQ engine (the repeated unit of
ingest/expiry/delete closures — round count is data-dependent, so the
roofline is reported per round).

Distributed layout (DESIGN.md §4):
    dist (x, u, s): x -> (pod,)data, u -> model    (frontier)
    adj  (l, u, v): v -> model                      (timestamped adjacency)
Contraction over u needs the full frontier per chip -> the per-round
all-gather over 'model' is the engine's collective term (baseline; the ring
schedule is the §Perf hillclimb).

Run: PYTHONPATH=src python -m repro.launch.dryrun_rpq [--all]
"""
import argparse
import json
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.automaton import compile_query
from ..core.backend import BucketBackend, resolve_backend
from ..core.engine import _round_up
from ..core.semiring import (NEG_INF, BatchedTransitionTable, TransitionTable,
                             relax_round)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")

# engine cells: (name, n_slots, query, v-chunk)
RPQ_CELLS = [
    ("rpq_n4096_k2", 4096, "a . b*", 512),
    ("rpq_n8192_k3", 8192, "a . b* . c", 512),
    ("rpq_n16384_k2", 16384, "(a | b)*", 512),
]

N_LEVELS = 8  # |W|/beta buckets for the MXU mode (paper: 1-month/1-day ~ 30;
              # 8 keeps the napkin conservative)

F_CAP = 256   # frontier capacity the "batched-frontier" cell lowers: the
              # dirty-row slab is (Q, F, N, K) with F << N, so the round's
              # contraction prices O(J·F·N²) instead of O(J·N³)

ELL_CAP_ANALYTIC = 8    # degree cap for the padded-ELL adjacency napkin
SPILL_CAP_ANALYTIC = 256  # replicated spill-ring slots (16 B each)

# multi-query serving cell (mode="batched"): the Table-2 workload stacked
# into ONE (Q, N, N, K) relaxation — the BatchedDenseRPQEngine's round on
# the production mesh
BATCHED_QUERIES = ["a*", "a . b*", "a . b* . c*", "(a | b | c)*", "a . b* . c",
                   "a* . b*", "a . b . c*", "a? . b*"]


def _cost_dict(ca):
    """jax version compat: cost_analysis() returns a dict (>=0.5) or a
    one-element list of dicts (0.4.x)."""
    if isinstance(ca, (list, tuple)):
        return ca[0] if ca else {}
    return ca or {}


def make_ring_round(mesh, tt: TransitionTable, n_slots: int, multi_pod: bool):
    """Manual ring reduce-scatter(max) schedule via shard_map: each chip
    contracts its LOCAL u-block (dist and adj are co-sharded on u), then the
    partial results ring around the model axis with max-accumulation —
    bytes-on-wire ~1x frontier (vs 2x for all-reduce-max) and every hop can
    overlap with the next partial contraction on TPU.

    (The base term — direct edges from start transitions — is applied once
    per ingest outside the iterated round, so it is not part of this
    lowering.)"""
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape["model"]
    xa = ("pod", "data") if multi_pod else "data"

    def local_partial(dist_blk, adj_blk, j):
        # dist_blk: (x_l, u_l, K); adj_blk: (L, u_l, N) -> partial (x_l, N)
        s_ = tt.src[j]
        l_ = tt.lab[j]
        d_s = jax.lax.dynamic_index_in_dim(
            jnp.moveaxis(dist_blk, 2, 0), s_, axis=0, keepdims=False)
        a_l = jax.lax.dynamic_index_in_dim(adj_blk, l_, axis=0, keepdims=False)
        n = a_l.shape[1]
        vc = min(512, n)

        def per_chunk(c, out):
            a = jax.lax.dynamic_slice(a_l, (0, c * vc), (a_l.shape[0], vc))
            contrib = jnp.max(jnp.minimum(d_s[:, :, None], a[None, :, :]), axis=1)
            return jax.lax.dynamic_update_slice(out, contrib, (0, c * vc))

        return jax.lax.fori_loop(0, n // vc, per_chunk,
                                 jnp.full((d_s.shape[0], n), NEG_INF, jnp.float32))

    def body(dist_blk, adj_blk):
        def per_t(j, acc):
            part = local_partial(dist_blk, adj_blk, j)       # (x_l, N)
            upd = jnp.where(tt.dst_onehot[j][None, None, :] > 0,
                            part[:, :, None], NEG_INF)
            return jnp.maximum(acc, upd)

        x_l = dist_blk.shape[0]
        n = adj_blk.shape[2]
        part = jax.lax.fori_loop(
            0, tt.src.shape[0], per_t,
            jnp.full((x_l, n, tt.k), NEG_INF, jnp.float32))

        # ring reduce-scatter(max) over 'model': after tp-1 hops each chip
        # owns the fully-reduced u-block matching its dist_blk shard.
        idx = jax.lax.axis_index("model")
        u_l = n // tp
        perm = [(k, (k - 1) % tp) for k in range(tp)]

        def take(block_idx):
            start = (block_idx % tp) * u_l
            return jax.lax.dynamic_slice(part, (0, start, 0), (x_l, u_l, tt.k))

        def hop(i, acc):
            acc = jax.lax.ppermute(acc, "model", perm)
            return jnp.maximum(acc, take(idx + 2 + i))

        acc0 = take(idx + 1)
        out_blk = jax.lax.fori_loop(0, tp - 1, hop, acc0)
        return jnp.maximum(dist_blk, out_blk)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(xa, "model", None), P(None, "model", None)),
        out_specs=P(xa, "model", None),
        check_vma=False,
    )


def relax_round_vchunked(dist, adj, tt: TransitionTable, v_chunk: int):
    """One relaxation round, chunked over the OUTPUT v dim so the broadcast
    intermediate stays bounded and the u-contraction triggers the frontier
    all-gather (dist's u dim is model-sharded)."""
    n = dist.shape[0]

    def per_transition(j, acc):
        s = tt.src[j]
        l = tt.lab[j]
        dist_s = jax.lax.dynamic_index_in_dim(
            jnp.moveaxis(dist, 2, 0), s, axis=0, keepdims=False)      # (x, u)
        adj_l = jax.lax.dynamic_index_in_dim(adj, l, axis=0, keepdims=False)  # (u, v)

        def per_chunk(c, out):
            a = jax.lax.dynamic_slice(adj_l, (0, c * v_chunk), (n, v_chunk))
            contrib = jnp.max(
                jnp.minimum(dist_s[:, :, None], a[None, :, :]), axis=1
            )  # (x, v_chunk)
            return jax.lax.dynamic_update_slice(out, contrib, (0, c * v_chunk))

        contrib = jax.lax.fori_loop(
            0, n // v_chunk, per_chunk, jnp.full((n, n), NEG_INF, dist.dtype))
        contrib = jnp.where(tt.start_mask[j], jnp.maximum(contrib, adj_l), contrib)
        upd = jnp.where(tt.dst_onehot[j][None, None, :] > 0,
                        contrib[:, :, None], NEG_INF)
        return jnp.maximum(acc, upd)

    return jax.lax.fori_loop(0, tt.src.shape[0], per_transition, dist)


def run_rpq_cell(name: str, n_slots: int, query: str, v_chunk: int,
                 multi_pod: bool, force: bool = False,
                 mode: str = "baseline") -> Dict[str, Any]:
    from .dryrun import scrape_collectives  # shares the HLO scraper
    from .mesh import make_production_mesh, mesh_context

    os.makedirs(RESULTS_DIR, exist_ok=True)
    mesh_tag = "multipod" if multi_pod else "pod"
    path = os.path.join(RESULTS_DIR, f"{name}-{mode}__ingest_round__{mesh_tag}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    dfa = compile_query(query)
    tt = TransitionTable.from_dfa(dfa)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    xa = ("pod", "data") if multi_pod else "data"

    dtype = jnp.int32 if mode == "mxu" else jnp.float32
    # analytic metadata (semiring ops, k, alphabet, query tag) must describe
    # the program actually lowered — the batched mode stacks BATCHED_QUERIES,
    # not the cell's single query
    query_tag, meta_k, meta_labels = query, dfa.k, dfa.n_labels
    n_transitions = len(dfa.transitions())
    if mode.startswith("batched"):
        # Q stacked queries, shared adjacency — a thin wrapper over the
        # MeshExecutor round lowering (distributed/executor.py): the lane
        # axis is SHARDED over the data axes (padded with inert lanes to a
        # shard multiple, exactly the engine's bucketing), the vertex axis
        # over model, and the (Q,) per-lane convergence mask rides along as
        # a runtime input — a lane shard whose queries have all converged
        # skips its contraction entirely (lax.cond inside shard_map), which
        # is the production form of the masked round the
        # BatchedDenseRPQEngine iterates. A "batched-<backend>" mode lowers
        # the SAME cell with that contraction backend (e.g. batched-pallas,
        # batched-mxu_bucket), so the roofline prices whichever substrate
        # the engine is configured to run. "batched-frontier" lowers the
        # FRONTIER-restricted round instead: the (Q, F) dirty-row indices
        # ride as runtime inputs and the contraction touches an (F, N)
        # slab per transition — O(F·N²), the PR 5 per-event cost model.
        from ..distributed.executor import (batched_round_lowering,
                                            frontier_round_lowering)

        suffix = mode.split("-", 1)[1] if "-" in mode else "jnp"
        dfas = [compile_query(q) for q in BATCHED_QUERIES]
        labels = sorted(set().union(*[set(d.labels) for d in dfas]))
        btt = BatchedTransitionTable.from_dfas(dfas, labels)
        query_tag = f"batched[{len(dfas)}]: " + " ; ".join(BATCHED_QUERIES)
        meta_k, meta_labels = btt.k, len(labels)
        n_transitions = sum(len(d.transitions()) for d in dfas)
        q_axes = ("pod", "data") if multi_pod else ("data",)
        n_lane_shards = int(np.prod([mesh.shape[a] for a in q_axes]))
        q_cap = _round_up(len(dfas), n_lane_shards)
        if suffix == "frontier":
            round_fn, arg_specs, arg_shardings, dist_sh = \
                frontier_round_lowering(mesh, btt, q_cap, n_slots,
                                        min(F_CAP, n_slots), q_axes=q_axes)
        else:
            backend = (BucketBackend(n_levels=N_LEVELS, use_pallas=False)
                       if suffix == "mxu_bucket" else resolve_backend(suffix))
            round_fn, arg_specs, arg_shardings, dist_sh = \
                batched_round_lowering(mesh, btt, q_cap, n_slots,
                                       q_axes=q_axes, backend=backend)
        dist_spec, adj_spec = arg_specs[0], arg_specs[1]
    elif mode == "ring":
        dist_spec = jax.ShapeDtypeStruct((n_slots, n_slots, dfa.k), dtype)
        adj_spec = jax.ShapeDtypeStruct((dfa.n_labels, n_slots, n_slots), dtype)
        dist_sh = NamedSharding(mesh, P(xa, "model", None))
        adj_sh = NamedSharding(mesh, P(None, "model", None))  # u co-sharded
        arg_specs = (dist_spec, adj_spec)
        arg_shardings = (dist_sh, adj_sh)
        round_fn = make_ring_round(mesh, tt, n_slots, multi_pod)
    else:  # baseline | mxu
        dist_spec = jax.ShapeDtypeStruct((n_slots, n_slots, dfa.k), dtype)
        adj_spec = jax.ShapeDtypeStruct((dfa.n_labels, n_slots, n_slots), dtype)
        dist_sh = NamedSharding(mesh, P(xa, "model", None))
        adj_sh = NamedSharding(mesh, P(None, None, "model"))
        arg_specs = (dist_spec, adj_spec)
        arg_shardings = (dist_sh, adj_sh)

        def round_fn(dist, adj):
            if mode == "mxu":
                # level-quantized single-query round through the engine's
                # own BucketBackend contraction (the old hand-rolled
                # relax_round_mxu_bucket special case, deleted in PR 4):
                # pure-jnp T-dot decomposition so GSPMD can partition it
                out = relax_round(
                    dist, adj, tt,
                    BucketBackend(n_levels=N_LEVELS, use_pallas=False))
            else:
                out = relax_round_vchunked(dist, adj, tt, v_chunk)
            return jax.lax.with_sharding_constraint(out, dist_sh)

    t0 = time.monotonic()
    with mesh_context(mesh):
        lowered = jax.jit(round_fn, in_shardings=arg_shardings,
                          out_shardings=dist_sh).lower(*arg_specs)
    global_flops = _cost_dict(lowered.cost_analysis()).get("flops", 0.0)
    compiled = lowered.compile()
    t_total = time.monotonic() - t0
    ca = _cost_dict(compiled.cost_analysis())
    ma = compiled.memory_analysis()
    colls = scrape_collectives(compiled.as_text())
    state_bytes = (np.prod(dist_spec.shape) * 4 + np.prod(adj_spec.shape) * 4) / chips
    by_kind: Dict[str, float] = {}
    for c in colls:
        by_kind[c["kind"]] = by_kind.get(c["kind"], 0.0) + c["wire_bytes"]

    result = {
        "arch": f"{name}-{mode}", "shape": "ingest_round",
        "engine_mode": mode,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips, "kind": "rpq",
        "query": query_tag, "k": meta_k, "n_labels": meta_labels,
        "n_slots": n_slots,
        "ok": True,
        "compile_s": round(t_total, 2),
        "global_flops": global_flops,
        "device_flops": ca.get("flops", 0.0),
        "device_bytes": ca.get("bytes accessed", 0.0),
        "device_flops_extrap": ca.get("flops", 0.0),
        "device_bytes_extrap": ca.get("bytes accessed", 0.0),
        "global_flops_extrap": global_flops,
        "memory": {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
        },
        "state_bytes_per_chip": state_bytes,
        "peak_bytes_per_chip": state_bytes + getattr(ma, "temp_size_in_bytes", 0),
        "fits_hbm": bool(state_bytes + getattr(ma, "temp_size_in_bytes", 0)
                         <= 16 * 1024**3),
        "n_collectives": len(colls),
        # ring mode: the ppermute sits inside a fori_loop executed (tp-1)
        # times; HLO text counts the body once, so scale the wire model
        "collective_wire_bytes_extrap": sum(c["wire_bytes"] for c in colls)
        * ((mesh.shape["model"] - 1) if mode == "ring" else 1),
        "collectives_by_kind_extrap": by_kind,
        # semiring ops (max+min per MAC-equivalent) for the analytic term:
        # the frontier round contracts an (F, N) slab per transition row —
        # O(F·N²) — instead of the dense (N, N) row block's O(N³)
        "semiring_ops": (2.0 * n_transitions * min(F_CAP, n_slots) * n_slots**2
                         if mode.endswith("frontier")
                         else 2.0 * n_transitions * n_slots**3),
        "frontier_cap": min(F_CAP, n_slots) if mode.endswith("frontier") else 0,
        # every level-quantized lowering (single-query "mxu" AND the
        # batched bucket-backend cell) is priced by its EXECUTED boolean
        # dot count: BucketBackend allocates n_levels + 1 thresholds (the
        # extra level absorbs the origin-snap slack), so T+1 dots run
        "n_levels": (N_LEVELS if (mode == "mxu" or mode.endswith("mxu_bucket"))
                     else 0),
        "level_dots": (N_LEVELS + 1
                       if (mode == "mxu" or mode.endswith("mxu_bucket"))
                       else 0),
        # adjacency-layout napkin (PR 8, adj_layout="ell"): every lowered
        # cell here still carries the dense (L, N, N) slab — these analytic
        # twins price what the SAME cell's adjacency state and base-term
        # reads cost off the O(N²) wall (idx int32 + ts f32 rows at the
        # default degree cap, plus the replicated 16 B/slot spill ring)
        "adjacency": {
            "dense_bytes": 4.0 * meta_labels * n_slots**2,
            "ell_cap": ELL_CAP_ANALYTIC,
            "ell_bytes": (8.0 * meta_labels * n_slots * ELL_CAP_ANALYTIC
                          + 16.0 * SPILL_CAP_ANALYTIC),
            # gather-contract op count for the frontier round's base term:
            # O(J·F·E·N) instead of the slab's O(J·F·N²)
            "ell_gather_ops": (2.0 * n_transitions * min(F_CAP, n_slots)
                               * ELL_CAP_ANALYTIC * n_slots),
        },
    }
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="")
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod", "both"])
    ap.add_argument("--modes", default="baseline,mxu,ring,batched,batched-frontier")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    for (name, n, q, vc) in RPQ_CELLS:
        if args.cell and args.cell != name:
            continue
        for mp in meshes:
            for mode in args.modes.split(","):
                r = run_rpq_cell(name, n, q, vc, mp, force=args.force, mode=mode)
                print(f"[ok] {name}/{mode} x {'2x16x16' if mp else '16x16'}: "
                      f"compile {r['compile_s']}s, colls={r['n_collectives']}, "
                      f"wire {r['collective_wire_bytes_extrap']/2**20:.1f} MiB/round",
                      flush=True)


if __name__ == "__main__":
    main()
