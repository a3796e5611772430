"""Fused Pallas gather-contract over padded-ELL adjacency rows.

Grid ``(J, M/bm, U/bu)`` with the u-axis innermost: each step loads a
``(bu, bm)`` block of the row operand, stored u-major, and the ``bu * E``
ELL slots of transition ``j`` for that u-block, then walks the slots
performing ``o[idx[u, e], :] = max(o[idx[u, e], :], min(d[u, :], ts[u, e]))``
via single-row ``pl.ds`` read-modify-writes.  The output block spans the
full vertex width and is revisited across the u-grid (the same
accumulator pattern as the k-loop in ``kernels/maxmin``), initialized
to ``zero`` at the first u-step with ``pl.when``.

The operand and the output are u-major / v-major (the wrapper transposes
on the way in and out) so the per-slot dynamic index lands on the
sublane axis, which Mosaic can address per row; the slot tables ride in
SMEM, where per-slot scalar reads are native.

Block sizes come from the shared ``pick_block_sizes`` table (rule R3);
the scatter axis cannot be blocked, so only (m, u) tile.  Free slots
(``ts == zero``) self-annihilate under the min/max fold, so padding the
u-axis with free rows and the m-axis with ``zero`` rows is exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..maxmin.maxmin import pick_block_sizes

NEG_INF = float("-inf")


def _r8(x: int) -> int:
    return max(x + (-x) % 8, 8)


def _ell_kernel(idx_ref, ts_ref, d_ref, o_ref, *, bu, e_cap, zero):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, zero, o_ref.dtype)

    def body(i, _):
        col = idx_ref[0, 0, i]                        # SMEM scalars
        t = ts_ref[0, 0, i]
        d_row = d_ref[0, pl.ds(i // e_cap, 1), :]     # (1, bm)
        cand = jnp.minimum(d_row, t.astype(d_row.dtype))
        cur = o_ref[0, pl.ds(col, 1), :]
        o_ref[0, pl.ds(col, 1), :] = jnp.maximum(cur, cand)
        return 0

    lax.fori_loop(0, bu * e_cap, body, 0)


@functools.partial(jax.jit,
                   static_argnames=("zero", "bm", "bu", "interpret"))
def ell_gather_contract_fused(d, idx, ts, *, zero=NEG_INF, bm=None, bu=None,
                              interpret=False):
    """Batched fused gather-contract: d (J, M, U) x idx/ts (J, U, E)
    -> (J, M, N) with N == U."""
    j, m, u = d.shape
    e_cap = idx.shape[2]
    t_bm, _, t_bu = pick_block_sizes(m, u, u)
    bm = bm or t_bm
    bu = bu or t_bu
    if interpret:
        bm = min(bm, _r8(m))
        bu = min(bu, _r8(u))

    m_pad = m + (-m) % bm
    u_pad = u + (-u) % bu
    n_out = _r8(u)
    zval = jnp.asarray(zero, d.dtype)
    d_t = jnp.full((j, u_pad, m_pad), zval, d.dtype).at[:, :u, :m].set(
        jnp.swapaxes(d, 1, 2))
    idx_p = jnp.zeros((j, u_pad, e_cap), jnp.int32).at[:, :u, :].set(idx)
    ts_p = jnp.full((j, u_pad, e_cap), jnp.asarray(zero, ts.dtype),
                    ts.dtype).at[:, :u, :].set(ts)
    slots = (1, 1, bu * e_cap)

    out = pl.pallas_call(
        functools.partial(_ell_kernel, bu=bu, e_cap=e_cap, zero=zero),
        grid=(j, m_pad // bm, u_pad // bu),
        in_specs=[
            pl.BlockSpec(slots, lambda ji, mi, ui: (ji, 0, ui),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(slots, lambda ji, mi, ui: (ji, 0, ui),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bu, bm), lambda ji, mi, ui: (ji, ui, mi)),
        ],
        out_specs=pl.BlockSpec((1, n_out, bm), lambda ji, mi, ui: (ji, 0, mi)),
        out_shape=jax.ShapeDtypeStruct((j, n_out, m_pad), d.dtype),
        interpret=interpret,
    )(idx_p.reshape(j, 1, u_pad * e_cap), ts_p.reshape(j, 1, u_pad * e_cap),
      d_t)
    return jnp.swapaxes(out[:, :u, :m], 1, 2)
