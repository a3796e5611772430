"""Fused Pallas gather for row-sparse dist rows.

Grid ``(M/bm, E/bn)``: each step owns a ``(bn, bm)`` output tile and
the full ``(C, bm)`` slot block of its rows (slot capacity C is small —
it is the pow2 ``dist_cap`` — so the block always fits VMEM).  The
kernel sweeps the C slots with a ``fori_loop``, comparing each slot's
flattened key against the tile's entry range and max-folding the hits:
a compare-select per slot on a (bn, bm) vector register, never a
(bm, C, bn) broadcast, so VMEM stays O(bm * (C + bn)) at any capacity.

Slots and the output are slot-major / entry-major (the wrapper
transposes on the way in and out) so the per-slot dynamic read is one
sublane row, which Mosaic can address; the rows ``m`` run along lanes.

Every output tile is visited exactly once (no accumulation grid dim),
so no ``pl.when`` init is needed.  Free slots carry ``ts == zero`` and
annihilate under the max; m-padding rows carry key 0 with ``zero``
values, e-padding columns are sliced off — exact by the same argument
as the other semiring kernels (padding is the semiring zero).

Block sizes come from the shared ``pick_block_sizes`` table (rule R3);
the skinny (rows, E) shapes this kernel sees — a handful of gathered
frontier rows against E = N*K columns — are the narrow-m rows of the
table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..maxmin.maxmin import pick_block_sizes

NEG_INF = float("-inf")


def _r8(x: int) -> int:
    return max(x + (-x) % 8, 8)


def _rs_kernel(idx_ref, ts_ref, o_ref, *, bn, c_cap, zero):
    row0 = pl.program_id(1) * bn
    ents = (lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
            + row0)                          # (bn, bm) global entry ids
    zval = jnp.asarray(zero, o_ref.dtype)

    def body(c, acc):
        key = idx_ref[pl.ds(c, 1), :]        # (1, bm)
        val = ts_ref[pl.ds(c, 1), :].astype(acc.dtype)
        return jnp.maximum(acc, jnp.where(key == ents, val, zval))

    o_ref[...] = lax.fori_loop(
        0, c_cap, body, jnp.full(o_ref.shape, zero, o_ref.dtype))


@functools.partial(jax.jit,
                   static_argnames=("e", "zero", "bm", "bn", "interpret"))
def rowsparse_gather_fused(idx, ts, e: int, *, zero=NEG_INF, bm=None,
                           bn=None, interpret=False):
    """Fused densify of gathered slot rows: idx/ts (M, C) -> (M, E)."""
    m, c_cap = idx.shape
    t_bm, t_bn, _ = pick_block_sizes(m, c_cap, e)
    bm = bm or t_bm
    bn = bn or t_bn
    if interpret:
        bm = min(bm, _r8(m))
        bn = min(bn, _r8(e))

    m_pad = m + (-m) % bm
    e_pad = e + (-e) % bn
    idx_t = jnp.zeros((c_cap, m_pad), jnp.int32).at[:, :m].set(idx.T)
    ts_t = jnp.full((c_cap, m_pad), jnp.asarray(zero, ts.dtype),
                    ts.dtype).at[:, :m].set(ts.T)

    out = pl.pallas_call(
        functools.partial(_rs_kernel, bn=bn, c_cap=c_cap, zero=zero),
        grid=(m_pad // bm, e_pad // bn),
        in_specs=[
            pl.BlockSpec((c_cap, bm), lambda i, j: (0, i)),
            pl.BlockSpec((c_cap, bm), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((e_pad, m_pad), ts.dtype),
        interpret=interpret,
    )(idx_t, ts_t)
    return out[:e, :m].T
