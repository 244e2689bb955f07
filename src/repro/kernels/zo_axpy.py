"""Fused ZO parameter-streaming kernel (Pallas, TPU target).

FedZO's hot loop streams the whole parameter vector through the VPU several
times per estimator sample:

  perturb        x ← x + μ·v                 (before the perturbed forward)
  unperturb +    x ← x + a·v_n + b·v_{n+1}   (MeZO-style fused transition to
   next perturb                               the next direction: ONE pass
                                              over HBM instead of two)
  update         x ← x − η·Σ_n c_n v_n       (replayed from seeds)

These are pure HBM-bandwidth ops; the kernel's job is fusion (XLA will not
fuse across the loss-forward boundary) and explicit VMEM tiling. The largest
block is 8·128·64 = 64Ki elements (512 rows of 128 lanes) → 256 KiB fp32 per
stream, 3 streams ≈ 768 KiB of the ~16 MiB VMEM budget, leaving room for
double buffering. The flat engine sizes its block to the model
(``utils/flatparams.flat_geometry``): the fewest grid steps of at most 512
rows, so the pad region the kernels generate directions for is under one
sublane tile per step; the 512-row ``BLOCK_ROWS`` is the kernels' default
when called directly.

Inputs are the flattened 1-D parameter leaf (padded to a block multiple by
ops.py). ``zo_axpy2(x, u, v, a, b) = x + a·u + b·v`` is the general form;
``a`` and ``b`` are scalars prefetched to SMEM.

Flat-buffer hot path (DESIGN.md §7): on top of the materialized-direction
axpy kernels, this module carries the *in-kernel direction regeneration*
convention. A direction element is a pure function of
``(round_key, n, flat_index)`` via a counter-based Threefry-2x32
implemented in plain jnp uint32 ops — the same code path runs inside a
Pallas kernel body and outside it, so the perturb end (``zo_walk``), the
replay end (``zo_replay``) and the pure-JAX reference
(``counter_direction`` in core/estimator.py) are bit-identical. That is
what preserves the seed-compression wire format of core/seedcomm.py:
the wire message stays (key, coeffs) and every receiver regenerates the
directions from the counter convention.

- ``zo_walk``     x + a·v(n_prev) + b·v(n_next): the MeZO-style fused
                  transition x+μv_n → x+μv_{n+1} (a=−μ, b=+μ). This is
                  ``zo_axpy2`` with u, v generated in VMEM instead of
                  streamed from HBM: ONE read + ONE write of x per
                  direction, zero direction traffic.
- ``zo_replay``   x + Σ_n c_n·v_n accumulated in VMEM per block, one HBM
                  writeback per block — the whole b2-direction update in a
                  single pass over the parameter buffer.
- ``zo_dirnorms`` per-direction squared norms ‖g_n‖² (for the sphere
                  estimator's normalization) with ~zero HBM traffic: the
                  directions live only in VMEM, the output is [b2] floats.

The 2-D layout [rows, 128] (lane dim last) keeps the kernels inside the
TPU tiling constraints; ops.py does the flat↔2-D reshape + padding.
Scalar operands (keys, direction indices, coefficients, the valid length)
live whole in SMEM, where the TPU compiler allows dynamic scalar reads;
``zo_dirnorms`` accumulates its [b2] norms in an SMEM output across the
grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 8 * 128 * 64  # 64Ki elements: the largest grid step
LANES = 128
BLOCK_ROWS = BLOCK // LANES  # 512 rows of 128 lanes: the largest block
# Scalar operands ride in SMEM as [k, 1] columns: the whole column is one
# block, and under vmap the batch axis lands in front of the two trailing
# dims, so the batched block still spans them as the TPU compiler requires.
SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _col(a, dtype):
    """Scalar operand(s) as an SMEM [k, 1] column."""
    return jnp.asarray(a, dtype).reshape(-1, 1)


def _axpy2_kernel(ab_ref, x_ref, u_ref, v_ref, o_ref):
    a = ab_ref[0, 0]
    b = ab_ref[1, 0]
    x = x_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    o_ref[...] = (x + a * u + b * v).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def zo_axpy2(x, u, v, ab, *, interpret=False, block=BLOCK):
    """x + ab[0]·u + ab[1]·v on flat arrays (len divisible by ``block``).

    x: [N] any float dtype; u, v: [N] same-or-f32; ab: [2] f32 scalars.
    """
    (n,) = x.shape
    assert n % block == 0, (n, block)
    grid = (n // block,)
    spec = pl.BlockSpec((block,), lambda i: (i,))
    return pl.pallas_call(
        _axpy2_kernel,
        grid=grid,
        in_specs=[SMEM, spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n,), x.dtype),
        interpret=interpret,
    )(_col(ab, jnp.float32), x, u, v)


def _axpy_kernel(a_ref, x_ref, u_ref, o_ref):
    a = a_ref[0, 0]
    o_ref[...] = (x_ref[...].astype(jnp.float32)
                  + a * u_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def zo_axpy(x, u, a, *, interpret=False, block=BLOCK):
    """x + a[0]·u on flat arrays."""
    (n,) = x.shape
    assert n % block == 0, (n, block)
    grid = (n // block,)
    spec = pl.BlockSpec((block,), lambda i: (i,))
    return pl.pallas_call(
        _axpy_kernel,
        grid=grid,
        in_specs=[SMEM, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n,), x.dtype),
        interpret=interpret,
    )(_col(a, jnp.float32), x, u)


# ---------------------------------------------------------------------------
# counter-based direction convention (round_key, n, flat_index) → fp32
#
# Threefry-2x32 in plain jnp uint32 ops: the identical expression graph runs
# inside Pallas kernel bodies (VPU integer ops) and in ordinary traced JAX,
# so perturb, replay and reference ends agree bit-for-bit.

_THREEFRY_PARITY = 0x1BD11BDA
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def _rotl32(x, r: int):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32(k0, k1, c0, c1):
    """Threefry-2x32, 20 rounds. All args uint32 (scalars broadcast)."""
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_THREEFRY_PARITY))
    x0 = c0 + ks[0]
    x1 = c1 + ks[1]
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = _rotl32(x1, r)
            x1 = x0 ^ x1
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def _u24_to_f32(b):
    return (b >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)


def _bits_to_normal(b0, b1):
    """Box-Muller on two uint32 bit planes → one N(0,1) fp32 per element.

    The 24-bit mantissa fields go to float through int32: exact either way
    (they are < 2^24), and the TPU compiler has no uint32 → float32 cast."""
    u1 = _u24_to_f32(b0) * jnp.float32(2.0 ** -24) \
        + jnp.float32(2.0 ** -25)                         # (0, 1)
    u2 = _u24_to_f32(b1) * jnp.float32(2.0 ** -24)
    r = jnp.sqrt(jnp.float32(-2.0) * jnp.log(u1))
    return r * jnp.cos(jnp.float32(2.0 * 3.14159265358979323846) * u2)


def counter_gen(kind: str, k0, k1, n, idx):
    """Direction element(s) v_n[idx] for kind ∈ {normal, sign}.

    k0, k1: uint32 round-key words; n: direction index (uint32 scalar);
    idx: uint32 flat element indices, any shape. This IS the shared
    convention — every producer and consumer of a direction calls it.
    """
    b0, b1 = threefry2x32(k0, k1, n, idx)
    if kind == "sign":
        return jnp.where((b0 & jnp.uint32(1)) > 0,
                         jnp.float32(1.0), jnp.float32(-1.0))
    if kind == "normal":
        return _bits_to_normal(b0, b1)
    raise ValueError(f"unknown counter direction kind {kind!r}")


def counter_direction_flat(key2, n, count, *, kind="normal", start=0):
    """Pure-JAX (non-kernel) form: v_n[start:start+count] as fp32 [count].

    ``key2`` is ``jax.random.key_data(key)`` (uint32 [2]). Bit-identical to
    what the kernels below generate in VMEM for the same (key, n, index).
    """
    idx = (jnp.uint32(start)
           + jnp.arange(count, dtype=jnp.uint32))
    return counter_gen(kind, key2[0], key2[1],
                       jnp.asarray(n).astype(jnp.uint32), idx)


def _block_idx(i, rows, lanes):
    """uint32 flat indices of grid block i of a [R, lanes] view."""
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
    return ((i * rows + row) * lanes + col).astype(jnp.uint32)


# -- zo_walk: fused perturbation transition --------------------------------


def _walk_kernel(key_ref, nn_ref, ab_ref, x_ref, o_ref, *, kind):
    i = pl.program_id(0)
    rows, lanes = x_ref.shape
    idx = _block_idx(i, rows, lanes)
    k0, k1 = key_ref[0, 0], key_ref[1, 0]
    g_prev = counter_gen(kind, k0, k1, nn_ref[0, 0].astype(jnp.uint32), idx)
    g_next = counter_gen(kind, k0, k1, nn_ref[1, 0].astype(jnp.uint32), idx)
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = (x + ab_ref[0, 0] * g_prev
                  + ab_ref[1, 0] * g_next).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kind", "interpret", "block_rows"))
def zo_walk(x2, key2, nn, ab, *, kind="normal", interpret=False,
            block_rows=BLOCK_ROWS):
    """x + ab[0]·v(nn[0]) + ab[1]·v(nn[1]) with in-kernel direction regen.

    x2: [R, 128] (R divisible by block_rows); key2 uint32 [2]; nn int32 [2]
    direction indices; ab fp32 [2] coefficients (pass ab[0]=0 for the first
    perturbation of a walk). One read + one write of x: 1 HBM pass.
    """
    r, lanes = x2.shape
    assert lanes == LANES and r % block_rows == 0, (x2.shape, block_rows)
    grid = (r // block_rows,)
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_walk_kernel, kind=kind),
        grid=grid,
        in_specs=[SMEM, SMEM, SMEM, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((r, LANES), x2.dtype),
        interpret=interpret,
    )(_col(key2, jnp.uint32), _col(nn, jnp.int32), _col(ab, jnp.float32),
      x2)


# -- zo_replay: single-pass seed-replay update ------------------------------


def _replay_kernel(key_ref, c_ref, x_ref, o_ref, *, kind, b2):
    i = pl.program_id(0)
    rows, lanes = x_ref.shape
    idx = _block_idx(i, rows, lanes)
    k0, k1 = key_ref[0, 0], key_ref[1, 0]

    def body(n, acc):
        g = counter_gen(kind, k0, k1, n.astype(jnp.uint32), idx)
        return acc + c_ref[n, 0] * g

    acc = jax.lax.fori_loop(0, b2, body,
                            jnp.zeros((rows, lanes), jnp.float32))
    o_ref[...] = (x_ref[...].astype(jnp.float32) + acc).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kind", "interpret", "block_rows"))
def zo_replay(x2, key2, coeffs, *, kind="normal", interpret=False,
              block_rows=BLOCK_ROWS):
    """x + Σ_n coeffs[n]·v_n — the whole b2-direction update in ONE pass.

    The grid walks blocks; per block all b2 directions are regenerated and
    accumulated in VMEM (fp32), then written back once. coeffs: fp32 [b2]
    *effective* coefficients (caller folds in scale, 1/b2 and any
    per-direction norm factor).
    """
    r, lanes = x2.shape
    (b2,) = coeffs.shape
    assert lanes == LANES and r % block_rows == 0, (x2.shape, block_rows)
    grid = (r // block_rows,)
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_replay_kernel, kind=kind, b2=b2),
        grid=grid,
        in_specs=[SMEM, SMEM, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((r, LANES), x2.dtype),
        interpret=interpret,
    )(_col(key2, jnp.uint32), _col(coeffs, jnp.float32), x2)


# -- zo_dirnorms: per-direction ‖g_n‖² with no direction HBM traffic --------


def _dirnorm_kernel(key_ref, d_ref, o_ref, *, kind, block_rows):
    n = pl.program_id(0)
    i = pl.program_id(1)
    idx = _block_idx(i, block_rows, LANES)
    g = counter_gen(kind, key_ref[0, 0], key_ref[1, 0],
                    jnp.asarray(n).astype(jnp.uint32), idx)
    g = jnp.where(idx < d_ref[0, 0].astype(jnp.uint32), g, jnp.float32(0.0))
    part = jnp.sum(g * g)

    @pl.when(i == 0)
    def _init():
        o_ref[n, 0] = jnp.float32(0.0)

    o_ref[n, 0] += part


@functools.partial(jax.jit,
                   static_argnames=("b2", "n_pad", "kind", "interpret",
                                    "block_rows"))
def zo_dirnorms(key2, d, *, b2, n_pad, kind="normal", interpret=False,
                block_rows=BLOCK_ROWS):
    """[b2] squared norms ‖g_n[:d]‖² under the counter convention.

    d: int32 scalar (valid length; padding indices ≥ d are masked out).
    n_pad: padded total element count (block multiple). HBM traffic is just
    the [b2] output — directions never leave VMEM. The output stays in SMEM
    for the whole grid and each (n, block) step adds its partial sum.
    """
    assert n_pad % (block_rows * LANES) == 0, (n_pad, block_rows)
    grid = (b2, n_pad // (block_rows * LANES))
    return pl.pallas_call(
        functools.partial(_dirnorm_kernel, kind=kind, block_rows=block_rows),
        grid=grid,
        in_specs=[SMEM, SMEM],
        out_specs=SMEM,
        out_shape=jax.ShapeDtypeStruct((b2, 1), jnp.float32),
        interpret=interpret,
    )(_col(key2, jnp.uint32), _col(d, jnp.int32)).reshape(b2)
