"""Pallas kernel microbenchmarks (interpret mode on CPU — µs numbers are for
regression tracking, not TPU projections) + seed-compression wire-size bench."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import timed
from repro.kernels import ops
from repro.kernels.zo_axpy import BLOCK


def run():
    rows = []
    n = 4 * BLOCK
    x = jax.random.normal(jax.random.key(0), (n,), jnp.float32)
    u = jax.random.normal(jax.random.key(1), (n,), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (n,), jnp.float32)
    _, us = timed(lambda: ops.axpy2(x, u, v, 0.1, -0.2), n=3)
    rows.append((f"kernels/zo_axpy2_n{n}", us, n * 4 * 4 / max(us, 1e-9)))  # B/µs
    key2 = jax.random.key_data(jax.random.key(0))
    _, us = timed(lambda: ops.zo_dirnorms(key2, n - 7, b2=20, n_pad=n), n=3)
    rows.append((f"kernels/zo_dirnorms_n{n}_b2_20", us, 20 * 4 / max(us, 1e-9)))

    q = jax.random.normal(jax.random.key(0), (1, 512, 4, 64), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (1, 512, 2, 64), jnp.float32)
    vv = jax.random.normal(jax.random.key(2), (1, 512, 2, 64), jnp.float32)
    _, us = timed(lambda: ops.attention(q, k, vv, causal=True), n=2)
    flops = 4 * 512 * 512 * 4 * 64 / 2  # causal half
    rows.append(("kernels/flash_attention_512", us, flops / max(us, 1e-9)))

    x2 = jax.random.normal(jax.random.key(3), (4096, 1024), jnp.float32)
    s2 = jnp.ones((1024,))
    _, us = timed(lambda: ops.rmsnorm(x2, s2), n=3)
    rows.append(("kernels/rmsnorm_4096x1024", us, x2.size * 4 / max(us, 1e-9)))

    # seed-compression wire bytes vs dense upload for one round (H=5, b2=20)
    from repro.core import seedcomm
    from repro.configs.base import FedZOConfig
    cfg = FedZOConfig(local_iters=5, b2=20)
    msg = seedcomm.compress(jax.random.key(0),
                            jnp.zeros((5, 20), jnp.float32), cfg)
    dense = 7850 * 4  # softmax-regression d
    rows.append(("seedcomm/wire_bytes_round", 0.0, seedcomm.wire_bytes(msg)))
    rows.append(("seedcomm/compression_vs_dense_softmax", 0.0,
                 dense / seedcomm.wire_bytes(msg)))
    rows.append(("seedcomm/compression_vs_dense_671b", 0.0,
                 671e9 * 4 / seedcomm.wire_bytes(msg)))
    return rows
