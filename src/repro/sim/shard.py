"""Device-sharded client fan-out: the simulated round over a ``clients``
mesh axis (DESIGN.md §9).

The flat simulation round materializes the M client deltas as one
[M, n_pad] matrix (core/fedzo.py §8). Here that matrix — and the vmapped
local phases that produce it — are split across devices with ``shard_map``:
each device runs M/n_dev local phases on its shard of the per-round batches
and reduces its rows first (partial fused AirComp reduce or partial masked
einsum), so the only cross-device exchange is one n_pad-sized psum of
partial means plus the [M] row norms. Everything downstream of the reduce
(Δ_max, Eq.-17 noise, momentum, metrics) runs on the replicated result with
EXACTLY the ops of ``fedzo.round_simulated`` — on a 1-device mesh the
sharded round is bit-identical to the unsharded one, which is what the
equivalence test pins.

The returned round is a drop-in ``round_fn`` for
``sim.engine.make_round_step``, so a whole sharded experiment still runs as
ONE compiled scan.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import FedZOConfig
from repro.core.aircomp import P_TX, mask_stats, schedule_by_channel
from repro.core.fedzo import (_flat_phase_scan, _flat_setup,
                              _wide_phase_scan, _wide_setup)
from repro.kernels import ops as kops
from repro.launch.mesh import make_clients_mesh  # noqa: F401  (re-export)
from repro.obs.trace import scope
from repro.utils.flatparams import flatten, unflatten
from repro.utils.tree import tree_add


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

    def round_fn(loss_fn_, server_params, client_batches, client_rngs, cfg_,
                 *, channel_rng=None, momentum=None, weights=None,
                 faults=None):
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
        with scope("fedzo.local"):
            spec, br = (_wide_setup(server_params, cfg)
                        if cfg.batch_directions
                        else _flat_setup(server_params, cfg))
            buf0 = flatten(server_params, spec)

        mask = None
        noise_rng = channel_rng
        air_stats = {}
        if cfg.channel_schedule and channel_rng is not None:
            with scope("fedzo.cohort"):
                k_sched, noise_rng = jax.random.split(channel_rng)
                _, mask = schedule_by_channel(k_sched, M, cfg.h_min)
        use_air = cfg.aircomp and channel_rng is not None
        # size weighting rides the same per-row coefficient vector the mask
        # does, so the weighted round shards identically to the masked one
        use_rowcoef = mask is not None or weights is not None
        with scope("fedzo.aggregate"):
            maskf, m_div, m_sched = mask_stats(mask, M, weights)

        def local_deltas(b0, params, batches_l, rngs_l):
            keys = jax.vmap(lambda r: jax.random.split(
                r, cfg.local_iters))(rngs_l)

            if cfg.batch_directions:
                def one_client(batches, ks):
                    buf, _, base = _wide_phase_scan(loss_fn, b0, spec, ks,
                                                    batches, cfg,
                                                    like=params)
                    return buf - b0, base
            else:
                def one_client(batches, ks):
                    buf, _, base = _flat_phase_scan(loss_fn, b0, spec, br,
                                                    ks, batches, cfg)
                    return buf - b0, base

            return jax.vmap(one_client)(batches_l, keys)

        def shard_body(b0, params, batches_l, rngs_l, maskf_l):
            with scope("fedzo.local"):
                deltas_l, losses_l = local_deltas(b0, params, batches_l,
                                                  rngs_l)

            with scope("fedzo.aggregate"):
                if use_air:
                    part, sq_l = kops.aircomp_reduce(
                        deltas_l, maskf_l / m_div, spec.d, block_rows=br)
                    mean = jax.lax.psum(part, axis)
                elif use_rowcoef:
                    part = jnp.einsum("mn,m->n", deltas_l, maskf_l)
                    mean = jax.lax.psum(part, axis) / m_div
                    sq_l = jnp.zeros((deltas_l.shape[0],), jnp.float32)
                else:
                    part = jnp.sum(deltas_l, axis=0)
                    mean = jax.lax.psum(part, axis) / M
                    sq_l = jnp.zeros((deltas_l.shape[0],), jnp.float32)
            return mean, sq_l, losses_l

        def shard_body_faults(b0, params, batches_l, rngs_l, chan_l, w_l,
                              fmask_l, corrupt_l):
            """Fault variant: the guard verdict (and with it the surviving
            cohort and the mean divisor) is only known per-shard, so the
            scrub runs on each device's rows and the divisor is a psum of
            per-shard coefficient sums — mirroring ``mask_stats`` on the
            combined channel ∧ fault mask bit-for-bit on one device."""
            with scope("fedzo.local"):
                deltas_l, losses_l = local_deltas(b0, params, batches_l,
                                                  rngs_l)
            with scope("fedzo.aggregate"):
                deltas_l, ok_l = faults.model.scrub(deltas_l, fmask_l,
                                                    corrupt_l)
                combined_l = (chan_l & ok_l).astype(jnp.float32)
                n_sched = jax.lax.psum(jnp.sum(combined_l), axis)
                coef_l = combined_l * w_l
                if weights is None:
                    div = jnp.maximum(n_sched, 1.0)
                else:
                    div = jnp.maximum(jax.lax.psum(jnp.sum(coef_l), axis),
                                      1e-8)
                if use_air:
                    part, sq_l = kops.aircomp_reduce(deltas_l, coef_l / div,
                                                     spec.d, block_rows=br)
                    mean = jax.lax.psum(part, axis)
                else:
                    part = jnp.einsum("mn,m->n", deltas_l, coef_l)
                    mean = jax.lax.psum(part, axis) / div
                    sq_l = jnp.zeros((deltas_l.shape[0],), jnp.float32)
            return mean, sq_l, losses_l, coef_l, div, n_sched

        if faults is not None:
            chan = (jnp.ones((M,), jnp.bool_) if mask is None else mask)
            w = (jnp.ones((M,), jnp.float32) if weights is None
                 else weights.astype(jnp.float32))
            agg_flat, sq, losses, maskf, m_div, m_sched = jax.shard_map(
                shard_body_faults, mesh=mesh,
                in_specs=(P(), P(), P(axis), P(axis), P(axis), P(axis),
                          P(axis), P(axis)),
                out_specs=(P(), P(axis), P(axis), P(axis), P(), P()),
                check_vma=False)(buf0, server_params, client_batches,
                                 client_rngs, chan, w, faults.mask,
                                 faults.corrupt)
        else:
            agg_flat, sq, losses = jax.shard_map(
                shard_body, mesh=mesh,
                in_specs=(P(), P(), P(axis), P(axis), P(axis)),
                out_specs=(P(), P(axis), P(axis)),
                check_vma=False)(buf0, server_params, client_batches,
                                 client_rngs, maskf)

        with scope("fedzo.aggregate"):
            if use_air:
                # Δ_max / Eq.-17 noise on the replicated mean: literally the
                # tail of aircomp_aggregate_flat, fed by the psum'd partials
                sigma_w2 = P_TX / (10.0 ** (cfg.snr_db / 10.0))
                delta_max = jnp.max(jnp.where(maskf > 0, sq, 0.0))
                noise_var = sigma_w2 * delta_max / (
                    m_div ** 2 * float(spec.d) * P_TX * cfg.h_min ** 2)
                noise_std = jnp.sqrt(noise_var)
                agg_flat = kops.zo_walk(
                    agg_flat, kops.key_words(noise_rng),
                    jnp.zeros((2,), jnp.int32),
                    jnp.stack([noise_std, jnp.float32(0.0)]),
                    kind="normal", block_rows=br)
                air_stats = {"aircomp_noise_std": noise_std,
                             "delta_max": delta_max, "m_effective": m_sched}
            elif mask is not None or faults is not None:
                air_stats = {"m_effective": m_sched}
            if faults is not None:
                air_stats["m_corrupt"] = faults.n_corrupt

            agg = unflatten(agg_flat, spec)
            if momentum is not None and cfg.server_momentum > 0:
                momentum = jax.tree.map(
                    lambda m, g: (cfg.server_momentum * m
                                  + g).astype(m.dtype),
                    momentum, agg)
                agg = momentum
            new_params = tree_add(server_params, agg)
            metrics = {"mean_local_loss": jnp.mean(losses),
                       "first_loss": jnp.mean(losses[:, 0]), **air_stats}
        if momentum is not None:
            return new_params, metrics, momentum
        return new_params, metrics

    return round_fn
