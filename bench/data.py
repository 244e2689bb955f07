"""Synthetic F-MNIST-shaped federations, made on the device from the seed.

The real F-MNIST files cannot be had offline. This generator keeps the
shape of the problem: ``n_train + n_test`` rows of ``n_features`` pixels in
``n_classes`` balanced classes, each row its class mean plus unit Gaussian
noise (squashed to [0, 1] pixels and reshaped for image models), and the
training rows dealt to ``n_clients`` clients as label-sorted shards, two
per client (McMahan et al., arXiv:1602.05629; the paper's Sec. V-B split).
It follows ``repro.data.synthetic`` in kind but not in stream: everything
is drawn with ``jax.random`` in one jitted call, so set-up never waits on
the host.

Like F-MNIST, the rows are one fixed data set (drawn from ``DATASET_SEED``);
the run's seed deals them to the clients. Every seed thus runs the same
work, and the in-scan eval's test rows, which the program compiles in as
constants, are the same for every seed, so one compiled program serves
them all.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

SHARDS_PER_CLIENT = 2
DATASET_SEED = 0


@partial(jax.jit, static_argnames=("n_train", "n_test", "n_features",
                                   "n_classes", "n_clients", "image_shape"))
def make_federation(deal_key, scale, *, n_train, n_test, n_features,
                    n_classes,
                    n_clients, image_shape=None):
    """Returns ``(clients, test)``: ``clients`` = {"x": [N, n, ...],
    "y": [N, n] int32} with n = n_train / N rows per client, ``test`` =
    {"x": [n_test, ...], "y": [n_test]}."""
    n_shards = n_clients * SHARDS_PER_CLIENT
    if n_train % n_shards:
        raise ValueError(f"{n_train} rows do not split into {n_shards} "
                         f"equal shards")
    n = n_train + n_test
    k_mu, k_y, k_x = jax.random.split(jax.random.key(DATASET_SEED), 3)
    mus = jax.random.normal(k_mu, (n_classes, n_features), jnp.float32)
    y = jax.random.permutation(k_y, jnp.arange(n, dtype=jnp.int32)
                               % n_classes)
    x = mus[y] * scale + jax.random.normal(k_x, (n, n_features),
                                           jnp.float32)
    if image_shape is not None:
        x = jax.nn.sigmoid(x).reshape((n,) + tuple(image_shape))
    xtr, ytr = x[:n_train], y[:n_train]
    order = jnp.argsort(ytr, stable=True)
    shard = n_train // n_shards
    shard_ids = jax.random.permutation(deal_key, n_shards)
    rows = (shard_ids.reshape(n_clients, SHARDS_PER_CLIENT, 1) * shard
            + jnp.arange(shard)).reshape(n_clients, -1)
    take = order[rows]
    clients = {"x": xtr[take], "y": ytr[take]}
    return clients, {"x": x[n_train:], "y": y[n_train:]}
