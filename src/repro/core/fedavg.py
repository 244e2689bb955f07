"""FedAvg baseline (McMahan et al. 2017) — the paper's first-order comparison
(Sec. V-B, Figs. 3-5). Same round structure as FedZO with the stochastic
zeroth-order update replaced by an SGD step on jax.grad."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import FedZOConfig
from repro.core.aircomp import cohort_mask, server_step_tree
from repro.utils.tree import tree_axpy, tree_sub


def local_phase(loss_fn, params, batches, cfg: FedZOConfig):
    grad_fn = jax.value_and_grad(loss_fn)

    def body(p, batch):
        loss, g = grad_fn(p, batch)
        return tree_axpy(-cfg.lr, g, p), loss

    p_fin, losses = jax.lax.scan(body, params, batches)
    return p_fin, losses


def round_simulated(loss_fn, server_params, client_batches, cfg: FedZOConfig,
                    *, channel_rng=None, weights=None, faults=None,
                    channel=None):
    """One FedAvg round over M clients (batches leading axes [M, H, ...]).

    The FedZO round's cohort mask and server step
    (``aircomp.cohort_mask``, ``aircomp.server_step_tree``) after a
    first-order local phase: channel-truncation scheduling or the realized
    ``channel``, the size-weighted n_i/n mean under ``weights`` (the
    original FedAvg aggregation), and ``faults`` scrubbed and masked out,
    all with the FedZO round's semantics (DESIGN.md §12).
    """
    def one_client(batches):
        p_fin, losses = local_phase(loss_fn, server_params, batches, cfg)
        return tree_sub(p_fin, server_params), losses

    M = jax.tree.leaves(client_batches)[0].shape[0]
    mask, noise_rng = cohort_mask(cfg, M, channel_rng, channel)
    deltas, losses = jax.vmap(one_client)(client_batches)
    new_params, stats, _, _ = server_step_tree(
        deltas, server_params, cfg, mask=mask, noise_rng=noise_rng,
        weights=weights, faults=faults)
    return new_params, {"mean_local_loss": jnp.mean(losses), **stats}


def make_train_step(loss_fn, cfg: FedZOConfig):
    """Cross-silo first-order step (dry-run/roofline comparison baseline)."""
    grad_fn = jax.value_and_grad(loss_fn)

    def step(params, batch, rng):
        del rng
        loss, g = grad_fn(params, batch)
        return tree_axpy(-cfg.lr, g, params), {"loss": loss}

    return step
