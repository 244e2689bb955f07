"""Fused AirComp aggregation kernel (Pallas, TPU target).

The server side of one AirComp round (core/aircomp.py, paper Eqs. 15-17)
needs three reductions over the stacked client-delta matrix [M, n_pad]:

  per-row squared norms   ‖Δ_i[:d]‖²          (for Δ_max, Eq. 15)
  masked Δ_max            max_{i∈M_t} ‖Δ_i‖²
  masked scaled mean      Σ_{i∈M_t} Δ_i / M_t  (the recovered update)

The pytree path pays one full read of the matrix for the norms
(``_delta_sq_norms``) and a second for the per-leaf ``einsum`` mean. This
kernel fuses both into ONE HBM pass: the grid walks column blocks, each
block loads all M rows once, accumulates the weighted row-combination into
the mean output and the per-row square partial sums into an [M] SMEM
output that stays resident across the grid (same cross-grid accumulation
pattern as ``zo_dirnorms``).

Δ_max and the Eq.-17 noise scale are then scalar work on the [M] norms,
and the noise itself is injected with a single ``zo_walk`` pass over the
d-sized mean (noise generated in-kernel from the counter convention) — the
M×d matrix is never touched again.

VMEM budget: the block is [M, block_rows, 128] fp32 — at the default 512
block rows that is M·256 KiB, double-buffered M·512 KiB: 25 MiB at the
paper's M=50, past the 16 MiB of scoped VMEM a TPU kernel gets by default.
The kernel therefore asks for the double-buffered footprint plus 8 MiB of
headroom (``vmem_limit_bytes``; v5e has 128 MiB of VMEM per core). Cohorts
past ~200 rows need a smaller ``block_rows``.

The mask/m_eff semantics live in the caller (core/aircomp.py): ``scale``
arrives as maskf/m_eff so masked-out rows contribute 0 to the mean; their
norms are still computed (the [M] output is dense) and masked out of
Δ_max by the caller.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.zo_axpy import BLOCK_ROWS, LANES, SMEM, _block_idx, _col

_HEADROOM = 8 * 2 ** 20


def _vmem_bytes(m, block_rows):
    """Double-buffered [M, rows, 128] input block + [rows, 128] mean block
    (fp32), plus headroom for the kernel's temporaries."""
    return 2 * (m + 1) * block_rows * LANES * 4 + _HEADROOM


def _reduce_kernel(scale_ref, d_ref, x_ref, mean_ref, sq_ref, *, m,
                   block_rows):
    i = pl.program_id(0)
    idx = _block_idx(i, block_rows, LANES)
    valid = idx < d_ref[0, 0].astype(jnp.uint32)

    @pl.when(i == 0)
    def _init():
        for mi in range(m):
            sq_ref[mi, 0] = jnp.float32(0.0)

    acc = jnp.zeros((block_rows, LANES), jnp.float32)
    for mi in range(m):  # static unroll: all M rows of this column block
        x = x_ref[mi].astype(jnp.float32)
        sq_ref[mi, 0] += jnp.sum(jnp.where(valid, x * x, 0.0))
        acc = acc + scale_ref[mi, 0] * x
    mean_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def aircomp_reduce(x3, scale, d_arr, *, interpret=False,
                   block_rows=BLOCK_ROWS):
    """One-pass (combined mean, per-row sq-norms) over x3 [M, R, 128].

    scale: fp32 [M] per-row weights (the caller folds mask and 1/m_eff in,
    so the first output IS the masked scaled mean). d_arr: int32 [1] valid
    flat length — padding indices ≥ d are excluded from the norms (the pad
    region of walked flat buffers is NOT zero, see DESIGN.md §8).
    Returns (mean [R, 128] fp32, sq [M] fp32).
    """
    m, r, lanes = x3.shape
    assert lanes == LANES and r % block_rows == 0, (x3.shape, block_rows)
    grid = (r // block_rows,)
    mean, sq = pl.pallas_call(
        functools.partial(_reduce_kernel, m=m, block_rows=block_rows),
        grid=grid,
        in_specs=[SMEM, SMEM,
                  pl.BlockSpec((m, block_rows, LANES), lambda i: (0, i, 0))],
        out_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
                   SMEM],
        out_shape=[jax.ShapeDtypeStruct((r, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((m, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(_vmem_bytes(m, block_rows), 16 * 2 ** 20)),
        interpret=interpret,
    )(_col(scale, jnp.float32), _col(d_arr, jnp.int32), x3)
    return mean, sq.reshape(m)
