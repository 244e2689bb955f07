"""jit'd public wrappers around the Pallas kernels: shape normalization,
padding to block multiples, pytree-level ZO helpers.

``interpret=None`` auto-selects from the default backend: on a TPU the
kernels are compiled by Mosaic (``tpu_custom_call`` in the program); on any
other backend they run in the Pallas interpreter, which is how the CPU test
suite runs them. The interpreter is for correctness only: its timings say
nothing about the chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rn
from repro.kernels import zo_aircomp as _zac
from repro.kernels import zo_axpy as _za


def _auto_interpret(interpret):
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def key_words(rng):
    """The uint32 [2] key of the counter convention, from a typed PRNG key
    or raw key data. Threefry keys are exactly these two words; the rbg
    family's four-word keys give their first two, the only words the
    kernels ever read."""
    if not jnp.issubdtype(jnp.asarray(rng).dtype, jnp.unsignedinteger):
        rng = jax.random.key_data(rng)
    return jnp.asarray(rng, jnp.uint32)[..., :2]


def _pad_to(x, m):
    n = x.shape[0]
    pad = (-n) % m
    if pad:
        x = jnp.pad(x, ((0, pad),))
    return x, n


def axpy2(x, u, v, a, b, *, interpret=None, block=None):
    """x + a·u + b·v for flat arrays of any length."""
    block = block or _za.BLOCK
    xp, n = _pad_to(x, block)
    up, _ = _pad_to(u, block)
    vp, _ = _pad_to(v, block)
    ab = jnp.asarray([a, b], jnp.float32).reshape(2)
    out = _za.zo_axpy2(xp, up, vp, ab, interpret=_auto_interpret(interpret),
                       block=block)
    return out[:n]


def tree_axpy2(x_tree, u_tree, v_tree, a, b, *, interpret=None):
    """Leafwise fused x + a·u + b·v (the MeZO unperturb-and-reperturb pass)."""
    def one(x, u, v):
        out = axpy2(x.reshape(-1), u.reshape(-1), v.reshape(-1), a, b,
                    interpret=interpret)
        return out.reshape(x.shape)
    return jax.tree.map(one, x_tree, u_tree, v_tree)


def _as2d(x, block_rows):
    """[N] → [R, 128] view (padding to a block multiple if needed)."""
    per = block_rows * _za.LANES
    xp, n = _pad_to(x, per)
    return xp.reshape(-1, _za.LANES), n


def zo_walk(x, key2, nn, ab, *, kind="normal", interpret=None,
            block_rows=None):
    """Fused perturbation transition on a flat [N] buffer.

    out = x + ab[0]·v(nn[0]) + ab[1]·v(nn[1]) with directions regenerated
    in-kernel from the counter convention (key2, n, index). One HBM pass.
    """
    block_rows = block_rows or _za.BLOCK_ROWS
    x2, n = _as2d(x, block_rows)
    nn = jnp.asarray(nn, jnp.int32).reshape(2)
    ab = jnp.asarray(ab, jnp.float32).reshape(2)
    out = _za.zo_walk(x2, key2, nn, ab, kind=kind,
                      interpret=_auto_interpret(interpret),
                      block_rows=block_rows)
    return out.reshape(-1)[:n]


def zo_replay(x, key2, coeffs, *, kind="normal", interpret=None,
              block_rows=None):
    """Single-pass Σ_n coeffs[n]·v_n update on a flat [N] buffer."""
    block_rows = block_rows or _za.BLOCK_ROWS
    x2, n = _as2d(x, block_rows)
    out = _za.zo_replay(x2, key2, jnp.asarray(coeffs, jnp.float32),
                        kind=kind, interpret=_auto_interpret(interpret),
                        block_rows=block_rows)
    return out.reshape(-1)[:n]


def zo_dirnorms(key2, d, *, b2, n_pad=None, kind="normal", interpret=None,
                block_rows=None):
    """[b2] squared direction norms ‖g_n[:d]‖² (counter convention)."""
    block_rows = block_rows or _za.BLOCK_ROWS
    per = block_rows * _za.LANES
    if n_pad is None:
        n_pad = d + ((-d) % per)
    assert n_pad % per == 0, (n_pad, per)
    return _za.zo_dirnorms(key2, d, b2=b2, n_pad=n_pad, kind=kind,
                           interpret=_auto_interpret(interpret),
                           block_rows=block_rows)


def aircomp_reduce(deltas, scale, d, *, interpret=None, block_rows=None):
    """Masked scaled row-combination + per-row ‖·[:d]‖² of deltas [M, N]
    in ONE pass over the matrix. Returns (mean [N] fp32, sq [M] fp32).

    ``scale`` [M] is the per-row mean weight (maskf/m_eff); ``d`` is the
    valid flat length (indices ≥ d excluded from the norms).
    """
    block_rows = block_rows or _za.BLOCK_ROWS
    per = block_rows * _za.LANES
    m, n = deltas.shape
    pad = (-n) % per
    if pad:
        deltas = jnp.pad(deltas, ((0, 0), (0, pad)))
    x3 = deltas.reshape(m, -1, _za.LANES)
    mean2, sq = _zac.aircomp_reduce(
        x3, jnp.asarray(scale, jnp.float32), jnp.asarray([d], jnp.int32),
        interpret=_auto_interpret(interpret), block_rows=block_rows)
    return mean2.reshape(-1)[:n], sq


def attention(q, k, v, *, causal=True, window=0, scale=None,
              block_q=128, block_k=128, interpret=None):
    """Flash attention on [B, S, H, D] layout (matches models/layers.py).

    Pads Sq/Sk up to block multiples; padded kv positions are masked out by
    the causal/positional mask (padded q rows are discarded on return).
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, Dv = v.shape
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    if pq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pk), (0, 0)))
    if not causal and pk:
        # non-causal: mask padded kv by position via a window over Sk
        raise NotImplementedError("pad non-causal kv not supported; "
                                  "choose block_k dividing Sk")
    out = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                              scale=scale, block_q=block_q, block_k=block_k,
                              interpret=_auto_interpret(interpret))
    return out[:, :, :Sq].transpose(0, 2, 1, 3)


def rmsnorm(x, scale, *, eps=1e-6, interpret=None, block_rows=128):
    """RMSNorm over the last dim of x [..., D]."""
    shp = x.shape
    x2 = x.reshape(-1, shp[-1])
    R = x2.shape[0]
    pad = (-R) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = _rn.rmsnorm(x2, scale, eps=eps, block_rows=block_rows,
                      interpret=_auto_interpret(interpret))
    return out[:R].reshape(shp)
