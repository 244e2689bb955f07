"""Device-resident federated client store with in-jit sampling.

The host server loop (fed/server.py) samples clients with host numpy and
stacks minibatches on the host every round — a host→device round trip that
stalls the compiled round engine. ``ClientStore`` moves the WHOLE federation
onto the device once: all N client datasets stacked into padded arrays with
per-client sizes, so both per-round draws happen inside jit:

- ``sample_participants`` — the M-of-N participation draw (Algorithm 1's
  uniform sampling) as a PRNG permutation prefix. No host sync,
  bit-reproducible from the experiment key chain.
- ``sample_batches`` — H minibatches of size b1 per sampled client, uniform
  with replacement over that client's OWN rows (the same distribution as
  the host ``data.synthetic.sample_local_batches``), gathered straight from
  the stacked arrays: one row gather of the round's M·H·b1 (client, row)
  pairs, so no op reads or copies a whole client shard.

Padding rows are never sampled: the per-client ``randint`` upper bound is
the client's true size, so the pad region is dead weight only
(N · (cap − n_i) rows — bounded by the most uneven client split).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class ClientStore(NamedTuple):
    """All N clients' data as stacked padded arrays (a pytree with leading
    [N, cap] axes) plus the true per-client row counts [N]."""
    data: Any
    sizes: jnp.ndarray

    @property
    def n_clients(self) -> int:
        return self.sizes.shape[0]

    @property
    def capacity(self) -> int:
        return jax.tree.leaves(self.data)[0].shape[1]


class CohortBatch(NamedTuple):
    """One round's staged cohort on the tiered path (sim/tiered.py): the M
    sampled clients' rows padded to the cohort's bucket capacity, plus
    their true sizes. In a segment stream every leaf carries an extra
    leading [S] rounds axis. ``avail`` is the host-replayed availability
    slice of a fault run; ``chan_h``/``chan_mask`` the host-replayed
    wireless-scenario realization (sim/channel.py) of a
    ``cfg.channel_model`` run. All three default None — no leaf, so the
    jit signature of runs without the optional processes is unchanged."""
    data: Any              # pytree, leaves [M, cap, ...]
    sizes: jnp.ndarray     # [M] int32 true row counts
    avail: Any = None      # [M] bool fault-chain slice, or None
    chan_h: Any = None     # [M] complex64 cohort fading slice, or None
    chan_mask: Any = None  # [M] bool transmit mask (sched ∧ battery), or None


def client_sizes(clients) -> list:
    """Validated per-client row counts for a list of client dataset
    pytrees — the shared front door of ``build_store`` and the tiered
    ``build_host_store`` (leaf row counts must agree within a client,
    dtypes must agree across clients)."""
    if not clients:
        raise ValueError("need at least one client dataset")
    sizes = []
    for i, c in enumerate(clients):
        ns = {int(np.shape(l)[0]) for l in jax.tree.leaves(c)}
        if len(ns) != 1:
            raise ValueError(
                f"client {i} has leaves with mismatched row counts: {ns}")
        sizes.append(ns.pop())
    leaves0 = jax.tree.leaves(clients[0])
    for i, c in enumerate(clients[1:], start=1):
        for j, (l0, l) in enumerate(zip(leaves0, jax.tree.leaves(c))):
            d0, d = np.asarray(l0).dtype, np.asarray(l).dtype
            if d0 != d:
                raise ValueError(
                    f"client {i} leaf {j} has dtype {d} but client 0 has "
                    f"{d0} — stacking would silently cast; make the client "
                    f"datasets dtype-uniform")
    return sizes


def stack_padded(leaves, cap: int) -> np.ndarray:
    """Stack ragged per-client leaves into ONE preallocated
    ``[len(leaves), cap, ...]`` zero-padded host buffer. Rows are copied
    straight into the buffer, so peak host memory is exactly the padded
    layout (pad bytes = Σ(cap − n_i)·row_bytes) — never a transient list
    of N individually padded copies."""
    head = np.asarray(leaves[0])
    out = np.zeros((len(leaves), cap) + head.shape[1:], head.dtype)
    for i, l in enumerate(leaves):
        out[i, :len(l)] = np.asarray(l)
    return out


def build_store(clients) -> ClientStore:
    """Stack a list of per-client dataset pytrees (e.g. {"x": [n_i, ...],
    "y": [n_i]}) into one device-resident ClientStore, zero-padding every
    client to the largest row count. Each leaf is assembled in a single
    preallocated host buffer and crosses to the device in ONE
    ``jax.device_put`` (a regression test pins both)."""
    sizes = client_sizes(clients)
    cap = max(sizes)

    def stack(*leaves):
        return jax.device_put(stack_padded(leaves, cap))

    return ClientStore(data=jax.tree.map(stack, *clients),
                       sizes=jnp.asarray(sizes, jnp.int32))


def sample_participants(key, n_clients: int, m: int):
    """Uniform M-of-N draw without replacement (paper Algorithm 1) as a
    PRNG permutation prefix — [m] int32 client ids, fully in-jit."""
    return jax.random.permutation(key, n_clients)[:m]


def sample_cohort_batches(data, sizes, key, h: int, b1: int):
    """Gather [M, H, b1, ...] stacked minibatches from an ALREADY-GATHERED
    cohort: ``data`` leaves [M, cap, ...], ``sizes`` [M] true row counts.

    The streamed-cohort twin of ``sample_batches`` and bit-identical to it
    on the same draw: the per-client key fan-out and randint bound depend
    only on ``key`` and the client's true size — never on the cohort's
    padded capacity — so a bucket-padded staged cohort samples the exact
    rows the full-capacity resident store would (pad rows are unreachable
    either way). It is ``sample_batches`` over the staged cohort with
    ``idx = arange(M)``."""
    m = sizes.shape[0]
    return sample_batches(ClientStore(data=data, sizes=sizes),
                          jnp.arange(m, dtype=jnp.int32), key, h, b1)


def sample_batches(store: ClientStore, idx, key, h: int, b1: int):
    """Gather [M, H, b1, ...] stacked minibatches for the sampled clients.

    Per client: (h, b1) row indices uniform with replacement over
    [0, sizes[i]) — the in-jit twin of the host ``sample_local_batches``
    (same distribution; the PRNG stream necessarily differs) — drawn from
    ``split(key, M)``, one key per cohort slot. Each leaf is read through
    its row-major [N·cap, ...] view at the flat row ids ``idx[m]·cap +
    row``: one gather of exactly the M·H·b1 rows the round uses, never a
    copy of the sampled clients' whole shards."""
    keys = jax.random.split(key, idx.shape[0])
    rows = jax.vmap(lambda k, n: jax.random.randint(k, (h, b1), 0, n))(
        keys, store.sizes[idx])
    flat = idx[:, None, None] * store.capacity + rows

    def gather(l):
        return l.reshape((-1,) + l.shape[2:])[flat]

    return jax.tree.map(gather, store.data)
