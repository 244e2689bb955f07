"""FlatParams: the whole parameter pytree as ONE padded 1-D buffer.

FedZO's hot loop (perturb → forward → transition → replay) is pure
HBM-bandwidth work over the parameter vector. Doing it leafwise costs one
XLA op dispatch per leaf per pass and blocks the Pallas streaming kernels
(kernels/zo_axpy.py), which want a single flat array. ``FlatSpec`` caches
everything needed to flatten once and then unflatten *views* for free:

- ``flat_spec(params)``       → cached static spec (treedef, shapes,
                                dtypes, offsets, padded length)
- ``flatten(params, spec)``   → fp32 [n_pad] buffer, zero-padded to a
                                kernel-block multiple
- ``flat_geometry(params, br)`` → (spec, block_rows) the kernels run on;
                                ``br=0`` sizes the block from d
- ``unflatten(buf, spec)``    → pytree of reshaped slices cast back to the
                                original leaf dtypes (XLA slices of the
                                buffer — no copy until a consumer forces
                                layout)

The flat index of a scalar is its offset in leaf traversal order — this is
the index the counter-based direction convention (kernels/zo_axpy.py) is
keyed on, so a direction element is addressable identically from the flat
kernels and from the pytree reference path (DESIGN.md §7).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.zo_axpy import BLOCK_ROWS, LANES


@dataclass(frozen=True)
class FlatSpec:
    """Static description of a flattened pytree (hashable, jit-closure safe)."""
    treedef: object
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[str, ...]
    offsets: Tuple[int, ...]
    sizes: Tuple[int, ...]
    d: int                      # total valid scalar count
    n_pad: int                  # padded buffer length (block multiple)
    block: int                  # pad granularity in elements
    buf_dtype: str = "float32"


_SPEC_CACHE: dict = {}


def flat_spec(params, *, block: int = 0, buf_dtype="float32") -> FlatSpec:
    """Build (or fetch from cache) the FlatSpec for a pytree's structure."""
    block = block or BLOCK_ROWS * LANES
    leaves, treedef = jax.tree.flatten(params)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(str(jnp.dtype(l.dtype)) for l in leaves)
    key = (treedef, shapes, dtypes, block, str(jnp.dtype(buf_dtype)))
    hit = _SPEC_CACHE.get(key)
    if hit is not None:
        return hit
    sizes, offsets, off = [], [], 0
    for shp in shapes:
        n = 1
        for s in shp:
            n *= s
        offsets.append(off)
        sizes.append(n)
        off += n
    n_pad = off + ((-off) % block)
    spec = FlatSpec(treedef=treedef, shapes=shapes, dtypes=dtypes,
                    offsets=tuple(offsets), sizes=tuple(sizes), d=off,
                    n_pad=n_pad, block=block,
                    buf_dtype=str(jnp.dtype(buf_dtype)))
    _SPEC_CACHE[key] = spec
    return spec


def flatten(params, spec: FlatSpec):
    """Pytree → [n_pad] buffer in spec.buf_dtype (pad region zeroed)."""
    leaves = jax.tree.leaves(params)
    dt = jnp.dtype(spec.buf_dtype)
    parts = [l.reshape(-1).astype(dt) for l in leaves]
    pad = spec.n_pad - spec.d
    if pad:
        parts.append(jnp.zeros((pad,), dt))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def unflatten(buf, spec: FlatSpec):
    """[≥ d] buffer → pytree of views with the original shapes/dtypes."""
    out = []
    for shp, dt, off, n in zip(spec.shapes, spec.dtypes, spec.offsets,
                               spec.sizes):
        out.append(buf[off:off + n].reshape(shp).astype(jnp.dtype(dt)))
    return jax.tree.unflatten(spec.treedef, out)


def _sized_block_rows(d: int, buf_dtype="float32") -> int:
    """Kernel block rows sized to d: the fewest grid steps of at most
    ``BLOCK_ROWS`` rows that cover ceil(d/128) rows, each step's rows
    rounded up to the buffer dtype's sublane tile (8 rows of 32-bit, 16 of
    16-bit). The pad region is then under one tile per grid step — at
    d = 7,850 one block of 64 rows (n_pad 8,192) instead of 512 (65,536).
    """
    tile = 8 * max(1, 4 // jnp.dtype(buf_dtype).itemsize)
    rows = max(1, -(-d // LANES))
    steps = -(-rows // BLOCK_ROWS)
    per = -(-rows // steps)
    return -(-per // tile) * tile


def flat_geometry(params, block_rows: int = 0, *, buf_dtype="float32"):
    """(spec, block_rows) for a given kernel-block-rows setting.

    THE one mapping from a block-rows config to flat-buffer geometry. The
    perturb end (fedzo) and the replay end (seedcomm) must derive identical
    geometry for counter-convention seed replay to be bit-exact — both call
    this. block_rows=0 sizes the block from d (``_sized_block_rows``); the
    rows returned are always explicit, so every kernel of a run (walk,
    replay, dirnorms, the AirComp reduce and noise walk) sees one geometry
    and none re-pads to its own default block.
    """
    if not block_rows:
        d = sum(math.prod(l.shape) for l in jax.tree.leaves(params))
        block_rows = _sized_block_rows(d, buf_dtype)
    spec = flat_spec(params, block=block_rows * LANES, buf_dtype=buf_dtype)
    return spec, block_rows
