"""Pallas TPU kernel: (max, min) bottleneck-semiring matmul.

C[i, j] = max_k min(A[i, k], B[k, j])

TPU mapping notes (DESIGN.md §2): the (max, min) semiring has no MXU
contraction, so this runs on the VPU; the kernel's job is the memory
schedule — HBM→VMEM tiling with a k-innermost accumulation grid so each
output tile stays resident in VMEM across k-steps. Block sizes keep the
(bm, bk, bn) broadcast intermediate within VMEM (bm*bk*bn*4B ≤ 8 MiB of
the 16 MiB scoped budget), and bk/bn are 128-aligned (Mosaic's lane
tiling).

The MXU-friendly alternative (bucketized boolean closure, used by the
engine's ``mxu_bucket`` mode) lives in ``kernels/bucket``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = float("-inf")

# Shape-aware block-size table: rows keyed by the M extent of the
# contraction. The dense round's operands are square-ish (M = N), but the
# frontier-restricted round feeds SKINNY (F, N) slabs — a fixed 128-row
# block would pad a F=16 slab 8x and waste 7/8 of every VPU tile. Small-M
# rows trade bm down and bn up (the broadcast intermediate bm*bk*bn*4B stays
# ≤ 8 MiB of the 16 MiB scoped VMEM either way). The M<=4 row serves the
# row-sparse dist gather: a Q·F row slab at tiny frontiers is a handful of
# rows against a WIDE N·K entry axis, so bn doubles again — the sweep over
# the entry axis halves its grid steps while bm*bn*4B stays a single VMEM
# tile.
#
# Mosaic tiles the last two block dims by (8, 128): bk is the LANE dim of
# the A block and bn of the B/output blocks, so both are multiples of 128
# (or clamp to the whole padded axis, which is also legal); bm and bk as
# sublane dims are multiples of 8.
_BLOCK_TABLE = (
    # (max M, (bm, bn, bk))
    (4,    (8, 512, 128)),
    (8,    (8, 256, 128)),
    (16,   (16, 256, 128)),
    (32,   (32, 256, 128)),
    (64,   (64, 128, 128)),
    (None, (128, 128, 128)),
)


def pick_block_sizes(m: int, k: int, n: int):
    """Derive (bm, bn, bk) from the operand shapes (table-driven).

    Blocks clamp to the 8-aligned (m, k) and 128-aligned (n) problem so a
    tiny engine never pays full-tile padding; results are bit-identical for
    ANY block choice (padding is the semiring zero), so this is purely a
    memory-schedule decision — regression-tested against the jnp oracle on
    odd/small shapes in tests/test_kernels.py."""
    def r8(x):
        return max(x + (-x) % 8, 8)

    def r128(x):
        return max(x + (-x) % 128, 128)

    for cap, (bm, bn, bk) in _BLOCK_TABLE:
        if cap is None or m <= cap:
            return (min(bm, r8(m)), min(bn, r128(n)), min(bk, r8(k)))
    raise AssertionError("unreachable: table ends with a None row")


def _maxmin_kernel(a_ref, b_ref, o_ref, *, bk: int):
    """Grid = (m/bm, n/bn, k/bk); k is the innermost (minor) grid dim so the
    o_ref tile is revisited with the same (i, j) while k sweeps."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, NEG_INF)

    a = a_ref[...]  # (bm, bk) VMEM tile
    b = b_ref[...]  # (bk, bn) VMEM tile
    # broadcast-min then max-reduce over k: (bm, bk, bn) stays in VMEM
    c = jnp.max(jnp.minimum(a[:, :, None], b[None, :, :]), axis=1)
    o_ref[...] = jnp.maximum(o_ref[...], c)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def maxmin_matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    bm: int = None,
    bn: int = None,
    bk: int = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """(max, min) matmul via pallas_call. a: (m, k), b: (k, n) -> (m, n).

    Inputs are padded (with -inf, the semiring zero) to block multiples.
    Block sizes default to the shape-aware table (:func:`pick_block_sizes`);
    pass explicit ints to pin them. ``interpret=True`` runs the kernel body
    in Python on CPU (validation path on this host; TPU is the deployment
    target).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    dtype = a.dtype
    abm, abn, abk = pick_block_sizes(m, k, n)
    bm, bn, bk = bm or abm, bn or abn, bk or abk
    mp, np_, kp = (-m) % bm, (-n) % bn, (-k) % bk
    if mp or kp:
        a = jnp.pad(a, ((0, mp), (0, kp)), constant_values=NEG_INF)
    if np_ or kp:
        b = jnp.pad(b, ((0, kp), (0, np_)), constant_values=NEG_INF)
    M, K = a.shape
    _, N = b.shape

    grid = (M // bm, N // bn, K // bk)
    out = pl.pallas_call(
        functools.partial(_maxmin_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), dtype),
        interpret=interpret,
    )(a, b)
    return out[:m, :n]


def maxmin_matmul_batched(a: jnp.ndarray, b: jnp.ndarray, **kw) -> jnp.ndarray:
    """Batched over a leading J dim (one slice per DFA transition).

    Legacy vmap form: one grid launch PER transition row. The engine's
    batched round uses :func:`maxmin_matmul_fused` instead (all rows share
    one launch); this stays as the conformance oracle for it."""
    return jax.vmap(lambda x, y: maxmin_matmul(x, y, **kw))(a, b)


def _maxmin_fused_kernel(a_ref, b_ref, o_ref):
    """Grid = (J, m/bm, n/bn, k/bk), k innermost (minor): the (1, bm, bn)
    output tile stays VMEM-resident across the k-sweep, and the leading J
    dim walks transition rows WITHIN one launch — row j+1's A/B tiles
    stream HBM→VMEM while row j drains, with no per-row launch/teardown
    (the cost the vmap-of-single-pair form pays J times per round)."""

    @pl.when(pl.program_id(3) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, NEG_INF)

    a = a_ref[0]  # (bm, bk) VMEM tile of row j
    b = b_ref[0]  # (bk, bn)
    c = jnp.max(jnp.minimum(a[:, :, None], b[None, :, :]), axis=1)
    o_ref[0] = jnp.maximum(o_ref[0], c)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def maxmin_matmul_fused(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    bm: int = None,
    bn: int = None,
    bk: int = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused batched (max, min) matmul: ONE pallas launch for all J rows.

    a: (J, m, k), b: (J, k, n) -> (J, m, n) with out[j] = maxmin(a[j], b[j]).
    This is the engine's batched-round contraction (one row per DFA
    transition): compared with ``vmap(maxmin_matmul)`` the whole round is a
    single grid, so each row's A/B tiles cross HBM→VMEM once per (i, j)
    output tile revisit instead of once per vmap instance, and the VPU sees
    an uninterrupted (J * m/bm * n/bn * k/bk)-step schedule.

    Block sizes default to the shape-aware table (:func:`pick_block_sizes`)
    — the frontier round's skinny (F, N) slabs get a small bm and a wide bn
    instead of 8x row padding. Inputs are padded with -inf (the semiring
    zero) to block multiples. In ``interpret`` mode (CPU validation) blocks
    clamp to the 8-aligned problem so small engines don't pay 128x128
    padding per row.
    """
    j, m, k = a.shape
    j2, k2, n = b.shape
    assert j == j2 and k == k2, (a.shape, b.shape)
    dtype = a.dtype
    abm, abn, abk = pick_block_sizes(m, k, n)
    bm, bn, bk = bm or abm, bn or abn, bk or abk
    if interpret:
        bm = min(bm, m + (-m) % 8)
        bn = min(bn, n + (-n) % 8)
        bk = min(bk, k + (-k) % 8)
    mp, np_, kp = (-m) % bm, (-n) % bn, (-k) % bk
    if mp or kp:
        a = jnp.pad(a, ((0, 0), (0, mp), (0, kp)), constant_values=NEG_INF)
    if np_ or kp:
        b = jnp.pad(b, ((0, 0), (0, kp), (0, np_)), constant_values=NEG_INF)
    _, M, K = a.shape
    _, _, N = b.shape

    grid = (j, M // bm, N // bn, K // bk)
    out = pl.pallas_call(
        _maxmin_fused_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda jj, i, jn, kk: (jj, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda jj, i, jn, kk: (jj, kk, jn)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda jj, i, jn, kk: (jj, i, jn)),
        out_shape=jax.ShapeDtypeStruct((j, M, N), dtype),
        interpret=interpret,
    )(a, b)
    return out[:, :m, :n]
