"""The round's server step, and AirComp-assisted aggregation (paper
Section IV).

Every simulated round (``fedzo.round_simulated``, ``fedavg.round_simulated``
and the clients-mesh round of ``sim/shard.py``) ends in the server step of
this module: ``cohort_mask`` resolves who transmits before the local phase,
and ``server_step_tree`` (stacked pytree deltas) or ``server_step_flat`` (a
flat [M, n_pad] delta matrix) turns the client deltas into new params —
delta correction, fault scrub, the mean (AirComp, masked/weighted or plain)
and server momentum.

Uplink model: the M_t scheduled devices transmit α_i^t·Δ_i^t concurrently
over a flat-fading MAC; the server receives

    s^t = Σ_i h_i^t α_i^t Δ_i^t + n_t,      n_t ~ CN(0, σ_w² I_d)

with the COTAF-style transmit scalar (Eq. 15)

    α_i^t = (h_min / h_i^t) · sqrt(dP / Δ_max^t),  Δ_max^t = max_i ‖Δ_i^t‖²

which inverts the channel and normalizes by the *largest current update*, so
the effective noise shrinks as the algorithm converges (paper Remark 4).
After receive scaling the server holds  y^t = Δ̄^t + ñ_t  with

    ñ_t ~ CN(0, σ_w²·Δ_max / (M²·d·P·h_min²) I).                    (Eq. 17)

Three implementations:
- ``aircomp_aggregate``      — the equivalent real-noise form on a delta
  pytree (model deltas are real so the real projection of ñ applies,
  variance σ_eff²/2 per real dimension — we keep the paper's full variance
  as the conservative choice and verify equivalence in tests).
- ``aircomp_aggregate_flat`` — the same statistics on a flat [M, n_pad]
  delta matrix via the fused one-pass kernel (kernels/zo_aircomp.py): row
  norms + masked mean in one sweep of the matrix, Eq.-17 noise injected
  in-kernel (counter convention) with one pass over the d-sized mean. The
  flat server step (DESIGN.md §8) aggregates through this.
- ``aircomp_simulate_channel`` — the explicit complex simulation (per-device
  h_i, transmit scalars, superposition, AWGN, receive scaling) used by the
  tests to verify the closed form and the per-device energy constraint
  ‖α_i Δ_i‖² ≤ dP.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.obs.trace import scope
from repro.utils.flatparams import unflatten
from repro.utils.tree import tree_add, tree_scale, tree_size

# per-round per-device energy budget is d·P with P normalized to 1;
# SNR γ = P·h_min²/σ_w² is controlled through snr_db = 10·log10(P/σ_w²).
P_TX = 1.0


class CohortReduce(NamedTuple):
    """How a value over the cohort rows one device holds becomes the value
    over the whole cohort: ``sum`` for partial sums and counts, ``max`` for
    Δ_max. On one device both are the identity (``ONE_DEVICE``); the
    clients-mesh round (sim/shard.py) passes its cross-device psum and
    pmax, so the flat server step runs unchanged on each device's rows."""
    sum: Callable
    max: Callable


def _same(x):
    return x


ONE_DEVICE = CohortReduce(sum=_same, max=_same)


def cohort_mask(cfg, M, channel_rng, channel=None):
    """The round's channel mask over its M cohort rows, and the key left
    for the AirComp noise: ``(mask [M] bool or None, noise_rng)``.

    cfg.channel_schedule enables the paper's channel-truncation scheduling
    (Sec. IV-A): a Rayleigh draw from ``channel_rng`` masks out clients
    with |h| < h_min. ``channel`` (a ``sim.channel.RoundChannel``) is the
    round's realized wireless scenario (DESIGN.md §16): its transmit mask —
    time-correlated-fading scheduling ∧ battery gating — REPLACES the
    i.i.d. draw; ``channel=None`` keeps the draw bit-exactly. Under
    cfg.channel_schedule the key is split with or without a ``channel``, so
    ``noise_rng`` does not depend on it; it is None exactly when
    ``channel_rng`` is, which turns AirComp off. The rounds call this
    before their local phase; the draw runs under ``fedzo.cohort``.
    """
    mask, noise_rng = None, channel_rng
    if cfg.channel_schedule and channel_rng is not None:
        with scope("fedzo.cohort"):
            k_sched, noise_rng = jax.random.split(channel_rng)
            if channel is None:
                _, mask = schedule_by_channel(k_sched, M, cfg.h_min)
    if channel is not None:
        mask = channel.mask
    return mask, noise_rng


def schedule_by_channel(rng, n_devices, h_min):
    """Rayleigh channel draw + threshold scheduling M_t = {i : |h_i| ≥ h_min}.

    Returns (h [N] complex64, mask [N] bool). The paper treats this as
    uniform sampling (Sec. IV-A); tests check |h| ~ Rayleigh and the mask
    rate matches exp(-h_min²).
    """
    kr, ki = jax.random.split(rng)
    h = (jax.random.normal(kr, (n_devices,)) +
         1j * jax.random.normal(ki, (n_devices,))) / jnp.sqrt(2.0)
    return h.astype(jnp.complex64), jnp.abs(h) >= h_min


def mask_stats(mask, M, weights=None, reduce=ONE_DEVICE):
    """(maskf, m_div, m_sched) for a scheduling mask over M rows.

    ``m_div`` is the clamped mean/noise divisor (never 0, so an all-masked
    round degenerates to a zero update instead of NaN); ``m_sched`` is the
    TRUE scheduled-client count — this is what ``m_effective`` reports, so
    a 0-client round is distinguishable from a 1-client one. The one
    definition is shared by every aggregation path (pytree, fused-flat
    and the masked plain means of the server steps below). ``reduce``
    turns a device's row counts into the cohort's (``CohortReduce``).

    ``weights`` optionally carries FedAvg-style size weights (positive [M],
    normalized by the caller so uniform sizes give all-ones — see
    ``size_weights``): the returned per-row coefficients become
    ``mask·w`` and the divisor ``Σ mask·w``, so the aggregate is the
    weighted mean over the scheduled rows. ``m_sched`` stays the
    UNWEIGHTED scheduled count. With all-ones weights this is bit-for-bit
    the unweighted path.
    """
    maskf = (jnp.ones((M,), jnp.float32) if mask is None
             else mask.astype(jnp.float32))
    m_sched = reduce.sum(jnp.sum(maskf))
    if weights is None:
        return maskf, jnp.maximum(m_sched, 1.0), m_sched
    wf = maskf * weights.astype(jnp.float32)
    # clamp tiny (not 1.0): a lone scheduled client with weight 0.5 must be
    # divided by 0.5; an all-masked round still degenerates to zero update
    # (zero numerator, zero Δ_max → zero noise)
    return wf, jnp.maximum(reduce.sum(jnp.sum(wf)), 1e-8), m_sched


def size_weights(sizes):
    """FedAvg-style n_i/n client weights from row counts [M], normalized to
    mean 1 (so uniform sizes → all-ones and the weighted divisor matches
    the unweighted M in the Eq.-17 noise scale). Divide by the mean rather
    than multiply by its reciprocal: s / (M·s / M) is EXACTLY 1.0 for
    uniform sizes, keeping the documented bit-for-bit fallback."""
    w = sizes.astype(jnp.float32)
    return w / (jnp.sum(w) / w.shape[0])


def _delta_sq_norms(deltas):
    """‖Δ_i‖² for stacked deltas (leading M axis). -> [M]"""
    leaves = jax.tree.leaves(deltas)
    return sum(jnp.sum(jnp.square(l.astype(jnp.float32)),
                       axis=tuple(range(1, l.ndim))) for l in leaves)


def aircomp_aggregate(deltas, rng, *, snr_db, h_min, mask=None, weights=None):
    """Noisy mean of stacked deltas [M, ...] per Eq. 17.

    ``mask`` optionally marks which of the M rows actually transmit
    (channel-truncation scheduling); unmasked rows are excluded from both
    the mean and Δ_max. ``weights`` turns the mean into the FedAvg-style
    size-weighted mean (see ``mask_stats``); Δ_max and the noise scale
    keep their unweighted per-row norms — the channel doesn't know about
    statistical weighting, only the post-scaling divisor changes.
    """
    m_leaves = jax.tree.leaves(deltas)
    M = m_leaves[0].shape[0]
    d = tree_size(deltas) // M
    sigma_w2 = P_TX / (10.0 ** (snr_db / 10.0))

    sq = _delta_sq_norms(deltas)                       # [M]
    maskf, m_div, m_sched = mask_stats(mask, M, weights)
    delta_max = jnp.max(jnp.where(maskf > 0, sq, 0.0))

    noise_var = sigma_w2 * delta_max / (m_div ** 2 * float(d) * P_TX * h_min ** 2)
    noise_std = jnp.sqrt(noise_var)

    leaves, treedef = jax.tree.flatten(deltas)
    out = []
    for i, leaf in enumerate(leaves):
        mean = jnp.einsum("m...,m->...", leaf.astype(jnp.float32), maskf) / m_div
        k = jax.random.fold_in(rng, i)
        noisy = mean + noise_std * jax.random.normal(k, mean.shape, jnp.float32)
        out.append(noisy.astype(leaf.dtype))
    agg = jax.tree.unflatten(treedef, out)
    stats = {"aircomp_noise_std": noise_std, "delta_max": delta_max,
             "m_effective": m_sched}
    return agg, stats


def aircomp_aggregate_flat(deltas, rng, *, snr_db, h_min, d=None, mask=None,
                           weights=None, block_rows=None, interpret=None,
                           reduce=ONE_DEVICE):
    """Eq.-17 aggregation of a flat delta matrix [M, n_pad] (fused kernel).

    One HBM pass over the matrix yields the per-row squared norms and the
    masked scaled mean together (``kernels/zo_aircomp.py``); Δ_max and the
    noise scale are scalar work on the [M] norms, and the noise is one
    ``zo_walk`` pass over the mean with the N(0,1) field regenerated
    in-kernel. Same Δ_max / m_eff / noise_std as ``aircomp_aggregate``
    (the noise *realization* differs: counter convention vs per-leaf
    fold_in). ``d`` is the valid flat length (pad indices carry walk
    residue and are excluded from the norms); defaults to the full width.
    ``reduce`` sums the partial means and maxes Δ_max over devices when
    the rows are split across a mesh (``CohortReduce``).
    """
    M, n = deltas.shape
    d = n if d is None else d
    sigma_w2 = P_TX / (10.0 ** (snr_db / 10.0))
    maskf, m_div, m_sched = mask_stats(mask, M, weights, reduce)
    mean, sq = kops.aircomp_reduce(deltas, maskf / m_div, d,
                                   block_rows=block_rows, interpret=interpret)
    mean = reduce.sum(mean)
    delta_max = reduce.max(jnp.max(jnp.where(maskf > 0, sq, 0.0)))
    noise_var = sigma_w2 * delta_max / (m_div ** 2 * float(d) * P_TX * h_min ** 2)
    noise_std = jnp.sqrt(noise_var)
    out = kops.zo_walk(mean, kops.key_words(rng),
                       jnp.zeros((2,), jnp.int32),
                       jnp.stack([noise_std, jnp.float32(0.0)]),
                       kind="normal", block_rows=block_rows,
                       interpret=interpret)
    stats = {"aircomp_noise_std": noise_std, "delta_max": delta_max,
             "m_effective": m_sched}
    return out, stats


def aggregate_tree(deltas, cfg, *, mask=None, noise_rng=None, weights=None):
    """The cohort mean of stacked pytree deltas [M, ...] -> (agg, stats).

    AirComp's Eq.-17 noisy mean (``aircomp_aggregate``) when cfg.aircomp
    and the round has a ``noise_rng``; else the masked and/or size-weighted
    mean over the rows ``mask`` keeps (``mask_stats``), which reports
    ``m_effective`` whenever it runs, so a weighted-but-unscheduled round
    carries the same cohort-size column as every other path; else the
    plain mean.
    """
    M = jax.tree.leaves(deltas)[0].shape[0]
    if cfg.aircomp and noise_rng is not None:
        return aircomp_aggregate(deltas, noise_rng, snr_db=cfg.snr_db,
                                 h_min=cfg.h_min, mask=mask, weights=weights)
    if mask is not None or weights is not None:
        maskf, m_div, m_sched = mask_stats(mask, M, weights)
        agg = jax.tree.map(
            lambda x: (jnp.einsum("m...,m->...", x.astype(jnp.float32),
                                  maskf) / m_div).astype(x.dtype), deltas)
        return agg, {"m_effective": m_sched}
    return tree_scale(1.0 / M, jax.tree.map(lambda x: jnp.sum(x, 0),
                                            deltas)), {}


def _server_update(server_params, agg, cfg, stats, momentum, faults, reduce):
    """Server momentum, the new params and the poison count: the tail both
    server steps share."""
    if momentum is not None and cfg.server_momentum > 0:
        momentum = jax.tree.map(
            lambda m, g: (cfg.server_momentum * m + g).astype(m.dtype),
            momentum, agg)
        agg = momentum
    new_params = tree_add(server_params, agg)
    if faults is not None:
        # the mask is never None under faults, so the aggregation reported
        # m_effective (the surviving cohort); add the poison count
        stats["m_corrupt"] = reduce.sum(faults.n_corrupt)
    return new_params, stats, momentum


def server_step_tree(deltas, server_params, cfg, *, mask=None,
                     noise_rng=None, weights=None, faults=None, momentum=None,
                     state_fn=None, cstate=None):
    """The server step on stacked pytree deltas (leading [M] axes):
    ``(new_params, stats, momentum, cstate)``, under ``fedzo.aggregate``.

    - ``mask``, ``noise_rng``: the round's ``cohort_mask``. Masked rows are
      excluded from the mean and from Δ_max.
    - ``state_fn(deltas, cstate, spec) -> (deltas', cstate')``: a
      strategy's client-side delta correction (core/strategy.py, DESIGN.md
      §13), called with ``spec=None`` here; it runs first, so it composes
      with faults, AirComp, scheduling and weighting unchanged. ``cstate``
      (the cohort's strategy state) comes back updated, or as given.
    - ``faults`` (a ``sim.faults.RoundFaults``): the deltas are corrupted,
      then scrubbed, and the surviving-client mask composes with the
      channel mask, so dropped, straggling and poisoned clients are
      excluded exactly like channel-masked ones (DESIGN.md §12);
      ``m_corrupt`` joins the stats.
    - ``weights`` ([M] positive, mean-1 normalized — ``size_weights``):
      the FedAvg-style size-weighted mean n_i/n over the kept rows.
    - ``momentum``: FedOpt-style server momentum (beyond the paper), on
      when cfg.server_momentum > 0; the new state comes back, or None.

    The aggregation is ``aggregate_tree``; ``stats`` holds its
    ``m_effective`` and AirComp statistics.
    """
    with scope("fedzo.aggregate"):
        if state_fn is not None:
            deltas, cstate = state_fn(deltas, cstate, None)
        if faults is not None:
            deltas, fmask = faults.apply_tree(deltas)
            mask = fmask if mask is None else mask & fmask
        agg, stats = aggregate_tree(deltas, cfg, mask=mask,
                                    noise_rng=noise_rng, weights=weights)
        return (*_server_update(server_params, agg, cfg, stats, momentum,
                                faults, ONE_DEVICE), cstate)


def server_step_flat(deltas, server_params, cfg, spec, block_rows=None, *,
                     mask=None, noise_rng=None, weights=None, faults=None,
                     momentum=None, state_fn=None, cstate=None,
                     reduce=ONE_DEVICE):
    """The server step on a flat [M, n_pad] delta matrix laid out by
    ``spec``: ``(new_params, stats, momentum, cstate)``, under
    ``fedzo.aggregate``. The keywords are ``server_step_tree``'s
    (``state_fn`` gets ``spec``); the mean is the fused one-pass AirComp
    kernel (``aircomp_aggregate_flat``, with ``block_rows`` its kernel
    block), a masked/weighted einsum, or the plain mean, and it is
    unflattened once before momentum.

    ``reduce`` (``CohortReduce``) makes the step run on one device's rows
    of a cohort split across a mesh: the partial means, the row counts
    behind the divisor, Δ_max and the poison count go through it, and
    everything else (scrub, coefficients, noise, momentum) is row-local or
    replicated. With ``ONE_DEVICE`` it is the single-device step.
    """
    with scope("fedzo.aggregate"):
        if state_fn is not None:
            deltas, cstate = state_fn(deltas, cstate, spec)
        if faults is not None:
            deltas, fmask = faults.apply_flat(deltas)
            mask = fmask if mask is None else mask & fmask
        if cfg.aircomp and noise_rng is not None:
            agg, stats = aircomp_aggregate_flat(
                deltas, noise_rng, snr_db=cfg.snr_db, h_min=cfg.h_min,
                d=spec.d, mask=mask, weights=weights, block_rows=block_rows,
                reduce=reduce)
        elif mask is not None or weights is not None:
            maskf, m_div, m_sched = mask_stats(mask, deltas.shape[0],
                                               weights, reduce)
            agg = reduce.sum(jnp.einsum("mn,m->n", deltas, maskf)) / m_div
            stats = {"m_effective": m_sched}
        else:
            agg = (reduce.sum(jnp.sum(deltas, axis=0))
                   / reduce.sum(deltas.shape[0]))
            stats = {}
        return (*_server_update(server_params, unflatten(agg, spec), cfg,
                                stats, momentum, faults, reduce), cstate)


def aircomp_simulate_channel(deltas_flat, rng, *, snr_db, h_min, h=None):
    """Explicit complex-channel simulation on flat [M, d] deltas.

    Only the SCHEDULED devices (|h_i| ≥ h_min — Sec. IV-A channel
    truncation) transmit: a deep-fade device would need α_i = h_min/h_i > 1
    to invert its channel and blow through the d·P energy budget, so it
    stays silent and contributes to neither the superposition nor Δ_max.
    The receiver divides by the scheduled count (clamped, so an all-masked
    round degenerates to the zero update). ``h`` optionally supplies an
    externally-realized channel (e.g. a ``sim.ChannelModel`` chain state)
    instead of the fresh i.i.d. draw.

    Returns (y [d] real recovered update, diag dict with per-device transmit
    energies, the channel draw, and the scheduling mask). Used by tests to
    validate ``aircomp_aggregate`` and the per-device energy constraint.
    """
    M, d = deltas_flat.shape
    sigma_w2 = P_TX / (10.0 ** (snr_db / 10.0))
    k_h, k_n = jax.random.split(rng)
    if h is None:
        h, mask = schedule_by_channel(k_h, M, h_min)
    else:
        mask = jnp.abs(h) >= h_min
    maskf, m_div, m_sched = mask_stats(mask, M)
    sq = jnp.sum(jnp.square(deltas_flat), axis=1)
    delta_max = jnp.max(jnp.where(maskf > 0, sq, 0.0))  # scheduled rows only

    alpha = maskf * (h_min / h) \
        * jnp.sqrt(d * P_TX / jnp.maximum(delta_max, 1e-30))      # Eq. 15
    tx = alpha[:, None] * deltas_flat.astype(jnp.complex64)
    energies = jnp.sum(jnp.abs(tx) ** 2, axis=1)                  # ≤ d·P
    kr, ki = jax.random.split(k_n)
    noise = (jax.random.normal(kr, (d,)) + 1j * jax.random.normal(ki, (d,))) \
        * jnp.sqrt(sigma_w2 / 2.0)
    s = jnp.sum(h[:, None] * tx, axis=0) + noise                  # Eq. 14/16
    rx_scale = jnp.sqrt(delta_max / (d * P_TX * h_min ** 2)) / m_div
    y = jnp.real(rx_scale * s)                                    # Eq. 17
    return y, {"h": h, "mask": mask, "m_effective": m_sched,
               "tx_energy": energies, "delta_max": delta_max,
               "energy_budget": d * P_TX}
