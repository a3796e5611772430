"""Distributed dense-RPQ evaluation on a multi-device mesh (8 host devices
emulate the pod; on TPU the same code runs on the production mesh).

Demonstrates: sharded engine state (sources x data axis, targets x model
axis), GSPMD-inserted frontier collectives, result equivalence vs the
single-device engine.

    PYTHONPATH=src python examples/distributed_rpq.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import compile_query
from repro.core.engine import DenseRPQEngine, EngineArrays
from repro.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, mesh_context
from repro.streaming.generators import so_like


def main() -> None:
    enable_compile_cache()
    assert len(jax.devices()) == 8, jax.devices()
    mesh = make_host_mesh(model_axis=2)
    dfa = compile_query("a2q . c2a*")
    stream = so_like(n_vertices=48, n_edges=800, seed=9)

    # single-device baseline
    base = DenseRPQEngine(dfa, window=30.0, n_slots=64, batch_size=32)
    for batch in stream.batches(32):
        base.insert_batch([s.as_edge() for s in batch])

    # sharded engine: place state with NamedShardings; the jitted step is
    # sharding-agnostic (GSPMD partitions the relaxation + inserts the
    # frontier collectives)
    eng = DenseRPQEngine(dfa, window=30.0, n_slots=64, batch_size=32)
    with mesh_context(mesh):
        eng.arrays = EngineArrays(
            adj=jax.device_put(eng.arrays.adj, NamedSharding(mesh, P(None, None, "model"))),
            dist=jax.device_put(eng.arrays.dist, NamedSharding(mesh, P("data", "model", None))),
            emitted=jax.device_put(eng.arrays.emitted, NamedSharding(mesh, P("data", None))),
            now=jax.device_put(eng.arrays.now, NamedSharding(mesh, P())),
        )
        for batch in stream.batches(32):
            eng.insert_batch([s.as_edge() for s in batch])

    assert eng.results == base.results
    print(f"devices: {len(jax.devices())}, mesh: {dict(mesh.shape)}")
    print(f"results: {len(eng.results)} pairs (sharded == single-device)")
    print("dist sharding:", eng.arrays.dist.sharding)


if __name__ == "__main__":
    main()
