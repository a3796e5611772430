"""Compile the Pallas kernels and the full-size engine dispatches for a
described TPU v5e (no chip attached).

Interpret-mode tests cannot see what the TPU compiler refuses: blocks not
aligned to Mosaic's (8, 128) tiling, primitives Mosaic cannot lower,
scoped-VMEM overflows, programs larger than one chip's HBM. These compile
every kernel that has a Pallas route at the widths ``chip_smoke.py``'s
full-size phase runs (the SO deployment: eleven Table-2 queries, frontier
slabs, ELL and row-sparse layouts), and the frontier ingest/delete
dispatches at its vertex capacity, which must fit 16 GiB.

The topology is described inside a module fixture (never at import), and
the persistent compilation cache is off around these compiles: what they
would write cannot be read back without a chip.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from benchmarks.common import so_queries
from repro.core.automaton import compile_query
from repro.core.backend import PallasBackend
from repro.core.executor import (BatchedEngineArrays, _delete_frontier,
                                 _ingest_frontier)
from repro.core.semiring import BatchedTransitionTable
from repro.core.sparse_adj import EllAdjacency
from repro.core.sparse_dist import RowSparseDist
from repro.kernels.bucket.bucket import bucket_maxmin_fused
from repro.kernels.ell.ell import ell_gather_contract_fused
from repro.kernels.maxmin.maxmin import maxmin_matmul_fused
from repro.kernels.rowsparse.rowsparse import rowsparse_gather_fused

HBM_BYTES = 16 * 2**30          # one v5e chip
FULL = chip_smoke.FULL
N = FULL["n_slots"]
F, C, E = FULL["frontier_cap"], FULL["dist_cap"], FULL["ell_cap"]


@pytest.fixture(scope="module")
def so_table():
    dfas = [compile_query(e) for e in so_queries().values()]
    labels = sorted(set().union(*[set(d.labels) for d in dfas]))
    return BatchedTransitionTable.from_dfas(dfas, labels)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(sharding, fn, *shapes):
    args = [_spec(sharding, s, d) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


f32, i32 = jnp.float32, jnp.int32


def _kernel_cases(j, q, k):
    return {
        # dense-adjacency rounds: square (J, N, N) and skinny frontier slabs
        "maxmin_square": (maxmin_matmul_fused,
                          [((j, N, N), f32), ((j, N, N), f32)]),
        "maxmin_frontier": (maxmin_matmul_fused,
                            [((j, F, N), f32), ((j, N, N), f32)]),
        "bucket_square": (lambda a, b: bucket_maxmin_fused(a, b, n_levels=9),
                          [((j, N, N), i32), ((j, N, N), i32)]),
        "bucket_frontier": (lambda a, b: bucket_maxmin_fused(
                                a, b, n_levels=9),
                            [((j, F, N), i32), ((j, N, N), i32)]),
        # ELL gathers: frontier slab rows and the all-rows fallback round
        "ell_frontier": (ell_gather_contract_fused,
                         [((j, F, N), f32), ((j, N, E), i32),
                          ((j, N, E), f32)]),
        "ell_all_rows": (ell_gather_contract_fused,
                         [((j, N, N), f32), ((j, N, E), i32),
                          ((j, N, E), f32)]),
        # row-sparse gather of the Q*F frontier rows over E = N*K entries
        "rowsparse_frontier": (lambda i, t: rowsparse_gather_fused(i, t,
                                                                   N * k),
                               [((q * F, C), i32), ((q * F, C), f32)]),
    }


KERNELS = ["maxmin_square", "maxmin_frontier", "bucket_square",
           "bucket_frontier", "ell_frontier", "ell_all_rows",
           "rowsparse_frontier"]


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, one_chip, so_table):
    q = so_table.n_queries
    fn, shapes = _kernel_cases(int(so_table.qidx.shape[0]), q,
                               so_table.k)[name]
    _compile(one_chip, fn, *shapes)


def _dispatch_args(sharding, btt, q):
    s = lambda shape, dtype: _spec(sharding, shape, dtype)
    k, n_labels, spill, ovf = btt.k, 4, 256, 4096
    adj = EllAdjacency(s((n_labels, N, E), i32), s((n_labels, N, E), f32),
                       s((spill,), i32), s((spill,), i32), s((spill,), i32),
                       s((spill,), f32), s((), i32))
    dist = RowSparseDist(s((q, N, C), i32), s((q, N, C), f32), s((ovf,), i32),
                         s((ovf, N * k), f32), s((), i32), s((), i32))
    arrays = BatchedEngineArrays(adj, dist, s((q, N, N), jnp.bool_),
                                 s((), f32))
    btt_s = jax.tree_util.tree_map(
        lambda x: s(np.shape(x), jnp.asarray(x).dtype), btt)
    batch = dict(i=s((1,), i32), f=s((1,), f32), b=s((1,), jnp.bool_))
    tables = (btt_s, s((q, k), jnp.bool_), s((q,), f32),
              s((q,), jnp.bool_), s((), f32))
    return arrays, batch, tables


@pytest.mark.parametrize("op", ["ingest", "delete"])
def test_full_size_dispatch_fits_one_chip(op, one_chip, so_table):
    """The full-size phase's frontier dispatch (SO layout, Pallas
    backend) compiles for one v5e and fits its HBM."""
    q = so_table.n_queries
    arrays, b, tables = _dispatch_args(one_chip, so_table, q)
    backend = PallasBackend(interpret=False)
    sc = _spec(one_chip, (), f32)
    if op == "ingest":
        lowered = _ingest_frontier.lower(
            arrays, b["i"], b["i"], b["i"], b["f"], b["b"], sc, *tables,
            backend=backend, f_cap=F)
    else:
        lowered = _delete_frontier.lower(
            arrays, b["i"], b["i"], b["i"], b["b"], sc, *tables,
            backend=backend, f_cap=F)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2**30:.2f} GiB"
