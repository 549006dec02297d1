"""(max,+) semiring matmul Pallas kernel.

The Max-Plus power iteration ``t_k = T (x) t_{k-1}`` (paper Eq. 4) and the
closure computations over large clustered SDFGs reduce to matmuls in the
(max,+) semiring:   C[i,j] = max_k (A[i,k] + B[k,j]).

TPU adaptation (DESIGN.md §3): the MXU implements only the (+,*) semiring,
so this kernel targets the VPU — blocks of A and B are staged in VMEM and
the reduction is an 8x128-vreg ``max`` over broadcast sums.  Block shapes
are multiples of (8, 128) so loads/stores stay register-aligned; K is the
minor grid dimension with a VMEM accumulator initialized to -inf and flushed
on the last K step.  Inside a block the K reduction walks ``unroll_k``-wide
chunks at static offsets (a Python loop, not ``fori_loop``): Mosaic has no
lowering for a ``dynamic_slice`` of a loaded block.

Neutral element is -inf: padding rows/cols with -inf keeps results exact for
non-multiple shapes (handled in ops.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = float("-inf")


def _reduce_chunks(a_ref, b_ref, acc, unroll_k: int):
    """``max(acc, A (x) B)`` for one (bm, bk) x (bk, bn) block pair."""
    for c in range(a_ref.shape[1] // unroll_k):
        ks = slice(c * unroll_k, (c + 1) * unroll_k)
        part = jnp.max(a_ref[:, ks][:, :, None] + b_ref[ks, :][None], axis=1)
        acc = jnp.maximum(acc, part)
    return acc


def _maxplus_kernel(a_ref, b_ref, out_ref, acc_ref, *, n_k: int, unroll_k: int):
    """One (bm, bn) output block; K iterated via grid dim 2."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref[...], NEG)

    # (bm, bk) x (bk, bn): reduce over k in sub-chunks to bound the
    # (bm, chunk, bn) VREG footprint
    acc_ref[...] = _reduce_chunks(
        a_ref.at[...], b_ref.at[...], acc_ref[...], unroll_k
    )

    @pl.when(k == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "unroll_k", "interpret"))
def maxplus_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    unroll_k: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """C = A (x) B in (max,+); shapes must be multiples of the block shape.

    Use :func:`repro.kernels.ops.maxplus_matmul` for arbitrary shapes
    (it pads with -inf) and for the CPU/interpret dispatch.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape {(m, k, n)} not a multiple of blocks {(bm, bk, bn)}"
    )
    assert bk % unroll_k == 0
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)

    return pl.pallas_call(
        functools.partial(_maxplus_kernel, n_k=n_k, unroll_k=unroll_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), a.dtype)],
        interpret=interpret,
    )(a, b)


# ----------------------------------------------------------------------
# batched variant: one grid dimension per candidate graph in the stack
# ----------------------------------------------------------------------
def _maxplus_bmm_kernel(a_ref, b_ref, out_ref, acc_ref, *, n_k: int, unroll_k: int):
    """One (bm, bn) output block of one batch element; K is grid dim 3."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref[...], NEG)

    acc_ref[...] = _reduce_chunks(
        a_ref.at[0], b_ref.at[0], acc_ref[...], unroll_k
    )

    @pl.when(k == n_k - 1)
    def _flush():
        out_ref[0] = acc_ref[...]


# ----------------------------------------------------------------------
# batched matvec: the Eq.-4 recursion x(k) = T (x) x(k-1) over a stack
# ----------------------------------------------------------------------
def _maxplus_bmv_kernel(a_ref, x_ref, out_ref, acc_ref, *, n_k: int):
    """One (bm,) output slice of one batch element; K is grid dim 2."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref[...], NEG)

    a = a_ref[0]          # (bm, bk)
    x = x_ref[0]          # (1, bk)
    part = jnp.max(a + x, axis=1)[None, :]          # (1, bm)
    acc_ref[...] = jnp.maximum(acc_ref[...], part)

    @pl.when(k == n_k - 1)
    def _flush():
        out_ref[0] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def maxplus_bmv(
    a: jax.Array,
    x: jax.Array,
    *,
    bm: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """y[g] = A[g] (x) x[g] in (max,+) for a stack of g matrix/vector pairs.

    The self-timed evolution workhorse: each power-iteration step of the
    whole candidate batch is one launch.  The reduction runs as a VPU max
    over the broadcast (bm, bk) sum — a vector has no MXU path anyway, and
    batching amortizes the launch.  Shapes must be block multiples; use
    :func:`repro.kernels.ops.maxplus_bmv` for arbitrary shapes.  The
    vectors ride as ``(g, 1, k)`` / ``(g, 1, m)`` so every block's last
    two dims are ``(1, 128)``-multiples of the full ``(1, k)`` trailing
    shape — a ``(1, bk)`` block of a ``(g, k)`` array breaks the TPU
    (8, 128) block rule.
    """
    g, m, k = a.shape
    g2, k2 = x.shape
    assert g == g2 and k == k2, (a.shape, x.shape)
    assert m % bm == 0 and k % bk == 0, (
        f"shape {(g, m, k)} not a multiple of blocks {(bm, bk)}"
    )
    n_k = k // bk
    grid = (g, m // bm, n_k)

    return pl.pallas_call(
        functools.partial(_maxplus_bmv_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda gg, i, kk: (gg, i, kk)),
            pl.BlockSpec((1, 1, bk), lambda gg, i, kk: (gg, 0, kk)),
        ],
        out_specs=pl.BlockSpec((1, 1, bm), lambda gg, i, kk: (gg, 0, i)),
        out_shape=jax.ShapeDtypeStruct((g, 1, m), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bm), a.dtype)],
        interpret=interpret,
    )(a, x[:, None, :])[:, 0, :]


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "unroll_k", "interpret")
)
def maxplus_bmm(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    unroll_k: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """C[g] = A[g] (x) B[g] in (max,+) for a stack of g matrices.

    The batch dimension becomes the major grid dimension — each candidate's
    blocks stream through VMEM independently with the same accumulator
    scheme as :func:`maxplus_matmul`.  Shapes must be block multiples; use
    :func:`repro.kernels.ops.maxplus_bmm` for arbitrary shapes.
    """
    g, m, k = a.shape
    g2, k2, n = b.shape
    assert g == g2 and k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape {(g, m, k, n)} not a multiple of blocks {(bm, bk, bn)}"
    )
    assert bk % unroll_k == 0
    n_k = k // bk
    grid = (g, m // bm, n // bn, n_k)

    return pl.pallas_call(
        functools.partial(_maxplus_bmm_kernel, n_k=n_k, unroll_k=unroll_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda gg, i, j, kk: (gg, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda gg, i, j, kk: (gg, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda gg, i, j, kk: (gg, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), a.dtype)],
        interpret=interpret,
    )(a, b)
