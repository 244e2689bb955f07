"""Device-sharded client fan-out: the simulated round over a ``clients``
mesh axis (DESIGN.md §9).

The flat simulation round materializes the M client deltas as one
[M, n_pad] matrix (core/fedzo.py §8). Here that matrix — and the vmapped
local phases that produce it — are split across devices with ``shard_map``:
each device runs M/n_dev local phases on its shard of the per-round batches
and then the flat server step (``aircomp.server_step_flat``) on its own
rows, with a ``CohortReduce`` that sums partial means and row counts with
``jax.lax.psum`` and maxes Δ_max with ``jax.lax.pmax`` across devices. So
the only cross-device exchange is one n_pad-sized psum of partial means plus
scalars, and the ops are those of ``fedzo.round_simulated``: on a 1-device
mesh the sharded round matches the unsharded one, which is what the
equivalence tests pin.

The returned round is a drop-in ``round_fn`` for
``sim.engine.make_round_step``, so a whole sharded experiment still runs as
ONE compiled scan.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import FedZOConfig
from repro.core.aircomp import CohortReduce, cohort_mask, server_step_flat
from repro.core.fedzo import local_plan
from repro.launch.mesh import make_clients_mesh  # noqa: F401  (re-export)
from repro.obs.trace import scope
from repro.utils.flatparams import flatten


def make_sharded_round(loss_fn, cfg: FedZOConfig, mesh: Mesh, *,
                       axis: str = "clients", store=None):
    """Signature-compatible replacement for ``fedzo.round_simulated``
    (flat/wide cfg only) with the M clients sharded over ``axis``.

    The round consumes only the per-round cohort batches, so it is
    store-tier agnostic: it runs unchanged under the device-resident
    engine AND the tiered cohort stream (sim/tiered.py). Passing the
    deployment's ``store=`` (either tier, or a client list — resolved
    through ``tiered.resolve_store``) validates the mesh split against
    the population at deployment time instead of first trace."""
    if not (cfg.flat_params or cfg.batch_directions):
        raise ValueError("the sharded round runs on the flat delta matrix — "
                         "set cfg.flat_params or cfg.batch_directions")
    n_dev = mesh.shape[axis]
    if store is not None:
        from repro.sim.tiered import resolve_store
        store = resolve_store(store, tier="auto")
        if cfg.n_participating > store.n_clients:
            raise ValueError(
                f"cfg.n_participating={cfg.n_participating} exceeds the "
                f"store's population N={store.n_clients}")
        if cfg.n_participating % n_dev:
            raise ValueError(
                f"n_participating={cfg.n_participating} must divide evenly "
                f"over the {n_dev}-device '{axis}' mesh axis")

    # psum and pmax are read through this module's ``jax`` as the round
    # traces
    reduce = CohortReduce(sum=lambda x: jax.lax.psum(x, axis),
                          max=lambda x: jax.lax.pmax(x, axis))

    def round_fn(loss_fn_, server_params, client_batches, client_rngs, cfg_,
                 *, channel_rng=None, momentum=None, weights=None,
                 faults=None, channel=None):
        if loss_fn_ is not loss_fn or cfg_ is not cfg:
            # the mesh deployment (phase choice, geometry, device split) is
            # bound at construction — a per-call substitution would silently
            # run the old program on the new config
            raise ValueError("make_sharded_round binds loss_fn and cfg at "
                             "deployment time; build a new sharded round to "
                             "run a different loss/config")
        M = client_rngs.shape[0]
        if M % n_dev:
            raise ValueError(f"n_participating={M} must divide evenly over "
                             f"the {n_dev}-device '{axis}' mesh axis")
        mask, noise_rng = cohort_mask(cfg, M, channel_rng, channel)
        spec, br, phase = local_plan(server_params, cfg)
        with scope("fedzo.local"):
            buf0 = flatten(server_params, spec)

        def body(b0, params, mom, noise_key, batches_l, rngs_l, mask_l, w_l,
                 fmask_l, corrupt_l):
            with scope("fedzo.local"):
                keys = jax.vmap(lambda r: jax.random.split(
                    r, cfg.local_iters))(rngs_l)

                def one_client(batches, ks):
                    buf, _, base = phase(loss_fn, b0, ks, batches)
                    return buf - b0, base

                deltas_l, losses_l = jax.vmap(one_client)(batches_l, keys)
            faults_l = (None if faults is None else
                        faults._replace(mask=fmask_l, corrupt=corrupt_l))
            new_params, stats, mom, _ = server_step_flat(
                deltas_l, params, cfg, spec, br, mask=mask_l,
                noise_rng=noise_key, weights=w_l, faults=faults_l,
                momentum=mom, reduce=reduce)
            return new_params, stats, mom, losses_l

        fmask, corrupt = ((None, None) if faults is None
                          else (faults.mask, faults.corrupt))
        new_params, stats, momentum, losses = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), P(), P()) + (P(axis),) * 6,
            out_specs=(P(), P(), P(), P(axis)),
            check_vma=False)(buf0, server_params, momentum, noise_rng,
                             client_batches, client_rngs, mask, weights,
                             fmask, corrupt)
        with scope("fedzo.aggregate"):
            metrics = {"mean_local_loss": jnp.mean(losses),
                       "first_loss": jnp.mean(losses[:, 0]), **stats}
        if momentum is not None:
            return new_params, metrics, momentum
        return new_params, metrics

    return round_fn
