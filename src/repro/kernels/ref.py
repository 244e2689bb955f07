"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def axpy2_ref(x, u, v, ab):
    out = (x.astype(jnp.float32) + ab[0] * u.astype(jnp.float32)
           + ab[1] * v.astype(jnp.float32))
    return out.astype(x.dtype)


def axpy_ref(x, u, a):
    return (x.astype(jnp.float32) + a[0] * u.astype(jnp.float32)).astype(x.dtype)


def attention_ref(q, k, v, *, causal=True, window=0, scale=None):
    """Naive full-materialization attention. q [B, Hq, Sq, D]; k/v [B, Hkv, Sk, D]."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, Dv = v.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def zo_walk_ref(x2, key2, nn, ab, *, kind="normal"):
    """Oracle for zo_walk: elementwise, so whole-array = per-block bitwise."""
    from repro.kernels.zo_axpy import counter_gen
    r, lanes = x2.shape
    idx = jnp.arange(r * lanes, dtype=jnp.uint32).reshape(r, lanes)
    gp = counter_gen(kind, key2[0], key2[1], nn[0].astype(jnp.uint32), idx)
    gn = counter_gen(kind, key2[0], key2[1], nn[1].astype(jnp.uint32), idx)
    out = x2.astype(jnp.float32) + ab[0] * gp + ab[1] * gn
    return out.astype(x2.dtype)


def zo_replay_ref(x2, key2, coeffs, *, kind="normal"):
    """Oracle for zo_replay: same n-ascending fp32 accumulation order (and
    the same fori_loop structure, so jit compiles the same fp32 adds)."""
    from repro.kernels.zo_axpy import counter_gen
    r, lanes = x2.shape
    idx = jnp.arange(r * lanes, dtype=jnp.uint32).reshape(r, lanes)

    def body(n, acc):
        g = counter_gen(kind, key2[0], key2[1], n.astype(jnp.uint32), idx)
        return acc + coeffs[n] * g

    acc = jax.lax.fori_loop(0, coeffs.shape[0], body,
                            jnp.zeros((r, lanes), jnp.float32))
    return (x2.astype(jnp.float32) + acc).astype(x2.dtype)


def _ascending_sum(n, term, like):
    """term(0) + term(1) + ... + term(n-1), added in index order."""
    return jax.lax.fori_loop(0, n, lambda i, t: t + term(i),
                             jnp.zeros_like(like))


def zo_dirnorms_ref(key2, d, b2, n_pad, *, kind="normal", block_rows=None):
    """Oracle for zo_dirnorms: the same per-block partial sums, added in
    block order. Vectorized over the blocks, so it runs at full size."""
    from repro.kernels.zo_axpy import BLOCK_ROWS, LANES, counter_gen
    block_rows = block_rows or BLOCK_ROWS
    idx = jnp.arange(n_pad, dtype=jnp.uint32).reshape(-1, block_rows, LANES)
    valid = idx < jnp.asarray(d).astype(jnp.uint32)

    def one(n):
        g = counter_gen(kind, key2[0], key2[1], n, idx)
        part = jnp.sum(jnp.where(valid, g * g, 0.0), axis=(1, 2))
        return _ascending_sum(part.shape[0], lambda i: part[i], part[0])

    return jax.lax.map(one, jnp.arange(b2, dtype=jnp.uint32))


def aircomp_reduce_ref(x3, scale, d, *, block_rows=None):
    """Oracle for aircomp_reduce over x3 [M, R, 128]: the same per-block
    partial sums added in block order, and the mean accumulated over the
    rows in ascending order. Returns (mean [R, 128], sq [M])."""
    from repro.kernels.zo_axpy import BLOCK_ROWS, LANES
    block_rows = block_rows or BLOCK_ROWS
    m, r, lanes = x3.shape
    xb = x3.astype(jnp.float32).reshape(m, -1, block_rows, lanes)
    idx = jnp.arange(r * lanes, dtype=jnp.uint32).reshape(-1, block_rows,
                                                          lanes)
    valid = idx < jnp.asarray(d).astype(jnp.uint32)
    part = jnp.sum(jnp.where(valid, xb * xb, 0.0), axis=(2, 3))    # [M, nb]
    sq = _ascending_sum(part.shape[1], lambda i: part[:, i], part[:, 0])
    mean = _ascending_sum(
        m, lambda i: scale[i] * x3[i].astype(jnp.float32),
        x3[0].astype(jnp.float32))
    return mean, sq


def rmsnorm_ref(x, scale, *, eps=1e-6):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)
