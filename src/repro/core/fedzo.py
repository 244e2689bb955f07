"""FedZO (paper Algorithm 1) — derivative-free federated optimization.

Two deployment modes share this module:

1. **Simulation mode** (paper scale, Sec. V): N clients held in memory,
   ``round_simulated`` vmaps the H-step local phase over the M sampled
   clients and aggregates deltas (exact Algorithm 1, with optional AirComp
   channel distortion from ``core.aircomp``).

2. **Cross-silo mode** (framework scale): each TPU pod is one client.
   ``local_iterate`` is the jitted unit the dry-run lowers; the launcher
   loops H of them per round and aggregates across the ``pod`` mesh axis
   (dense psum, AirComp-noisy psum, or seed-compressed — core/seedcomm.py).

The local phase never materializes a gradient pytree: per direction it pays
one loss forward + one axpy, and the update is replayed from seeds
(DESIGN.md §3). ``jax.grad`` is never called.

With ``cfg.flat_params=True`` the local phase runs on the flat-buffer hot
path (DESIGN.md §7): the pytree is flattened ONCE per phase into a padded
1-D buffer, every perturb is a fused zo_walk transition (one HBM pass per
direction, directions regenerated in-kernel), and the b2-direction update
is a single zo_replay pass. The pytree path stays as the reference.

With ``cfg.batch_directions=True`` the local phase runs the batched-
direction ("wide") plan of the simulation engine (DESIGN.md §9): per
iterate ONE [b2, n_pad] direction block, the b2 perturbed forwards as one
vmap, the update as one matvec. Same estimator statistics; bit-identical
directions to the loop path under direction_conv="tree".
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import FedZOConfig
from repro.core import estimator
from repro.core.aircomp import (aircomp_aggregate, aircomp_aggregate_flat,
                                mask_stats, schedule_by_channel)
from repro.kernels import ops as kops
from repro.obs.trace import scope
from repro.utils.flatparams import (flat_geometry, flat_spec, flatten,
                                    unflatten)
from repro.utils.tree import tree_add, tree_scale, tree_sub


class LocalResult(NamedTuple):
    params: object        # x_i^{(t,H)}
    coeffs: jnp.ndarray   # [H, b2] estimator coefficients (seed-replayable)
    losses: jnp.ndarray   # [H] base losses along the trajectory


def _flat_setup(params, cfg: FedZOConfig):
    """(spec, block_rows kwarg) for the cfg's flat-buffer geometry."""
    return flat_geometry(params, cfg.flat_block_rows)


def _wide_setup(params, cfg: FedZOConfig):
    """Flat geometry for the batched-direction (wide) path.

    The wide phase never enters a Pallas kernel, so it pads only to the
    vector-lane width — NOT to the kernel block, whose extra rows every
    [b2, n_pad] direction block would pay for. The kernel geometry is kept
    only when the fused AirComp kernel consumes the delta matrix.
    """
    from repro.kernels.zo_axpy import LANES

    if cfg.aircomp:
        return _flat_setup(params, cfg)
    return flat_spec(params, block=LANES), None


def flat_layout(params, cfg: FedZOConfig):
    """The flat buffer a run's local phase uses, for its manifest:
    ``{"d", "n_pad", "block_rows"}`` (block_rows None where no kernel
    runs), or None on the pytree path. n_pad / d is the pad ratio."""
    if cfg.batch_directions:
        spec, br = _wide_setup(params, cfg)
    elif cfg.flat_params:
        spec, br = _flat_setup(params, cfg)
    else:
        return None
    return {"d": spec.d, "n_pad": spec.n_pad, "block_rows": br}


def flat_local_iterate(loss_fn, buf, spec, batch, rng, cfg: FedZOConfig,
                       block_rows=None):
    """One ZO update on the flat buffer: fused walk + single-pass replay.

    The sphere inv-norms are computed once and shared by both ends — the
    zo_dirnorms kernel regenerates all b2 directions, so running it twice
    would double the direction-generation compute of the iterate.
    """
    key2 = kops.key_words(rng)
    inv = estimator.flat_inv_norms(key2, spec, cfg.b2, cfg.estimator,
                                   block_rows=block_rows)
    coeffs, base = estimator.flat_coefficients(
        loss_fn, buf, spec, batch, rng, mu=cfg.mu, b2=cfg.b2,
        kind=cfg.estimator, central=cfg.central, block_rows=block_rows,
        inv=inv)
    buf = estimator.flat_apply_coefficients(
        buf, spec, rng, coeffs, scale=-cfg.lr, kind=cfg.estimator,
        block_rows=block_rows, inv=inv)
    return buf, coeffs, base


def local_iterate(loss_fn, params, batch, rng, cfg: FedZOConfig):
    """One stochastic zeroth-order update (Eq. 5-6): x ← x − η ∇̃F(x).

    Returns (new_params, coeffs [b2], base_loss). This is the unit the
    multi-pod dry-run lowers as ``train_step``. Dispatches to the flat
    hot path when cfg.flat_params is set.
    """
    if cfg.flat_params:
        spec, br = _flat_setup(params, cfg)
        buf = flatten(params, spec)
        buf, coeffs, base = flat_local_iterate(loss_fn, buf, spec, batch,
                                               rng, cfg, block_rows=br)
        return unflatten(buf, spec), coeffs, base
    ddt = jnp.dtype(cfg.direction_dtype)
    coeffs, base = estimator.coefficients(
        loss_fn, params, batch, rng, mu=cfg.mu, b2=cfg.b2, kind=cfg.estimator,
        direction_dtype=ddt, central=cfg.central, conv=cfg.direction_conv)
    new_params = estimator.apply_coefficients(
        params, rng, coeffs, scale=-cfg.lr, kind=cfg.estimator,
        direction_dtype=ddt, conv=cfg.direction_conv)
    return new_params, coeffs, base


def _flat_phase_scan(loss_fn, buf0, spec, br, keys, batches, cfg):
    """Scan H flat local iterates over a flat buffer — THE flat local
    phase, shared by ``local_phase`` and the flat round engine so the two
    can never walk different iterate protocols. Returns
    (final buf, coeffs [H, b2], losses [H])."""
    def fbody(carry, inp):
        k, batch = inp
        b, coeffs, base = flat_local_iterate(loss_fn, carry, spec, batch,
                                             k, cfg, block_rows=br)
        return b, (coeffs, base)

    buf, (coeffs, losses) = jax.lax.scan(fbody, buf0, (keys, batches))
    return buf, coeffs, losses


def _check_surrogate(cfg: FedZOConfig):
    if cfg.direction_conv in ("surrogate", "channel") \
            and not cfg.batch_directions:
        raise ValueError(
            f"direction_conv={cfg.direction_conv!r} runs on the batched-"
            f"direction (wide) local phase — set cfg.batch_directions=True")


def surrogate_queries(cfg: FedZOConfig) -> int:
    """Fresh perturbed-loss queries per local iterate under the surrogate
    estimator (direction_conv="surrogate"): ceil(b2·surrogate_fraction),
    at least 1. The single source of truth shared by the phase scan and the
    query-budget acceptance test."""
    return max(1, int(round(cfg.b2 * cfg.surrogate_fraction)))


def _surrogate_phase_scan(loss_fn, buf0, spec, keys, batches, cfg):
    """Trajectory-informed surrogate local phase (FedZOO-style,
    arXiv 2308.04077): instead of b2 fresh directions per iterate, pay only
    ``surrogate_queries(cfg)`` fresh ZO queries and blend the fresh estimate
    into a running surrogate gradient carried along the local trajectory:

        g ← β·g + (1−β)·ĝ_fresh,   x ← x − η·g

    The replay history already flowing through the phase (the per-iterate
    (direction, finite-difference) pairs) is what the surrogate memorizes —
    an exponentially-weighted rank-|history| fit, the cheap end of FedZOO's
    quadratic surrogate family. Returns (final buf, coeffs [H, b2q],
    losses [H]); the coeffs are NOT seed-replayable (seedcomm rejects
    non-tree wide convs already)."""
    mu = jnp.float32(cfg.mu)
    scale = estimator._scale_factor(spec.d, cfg.estimator)
    b2q = surrogate_queries(cfg)
    beta = jnp.float32(cfg.surrogate_beta)

    def step(carry, inp):
        buf, g_hat, t = carry
        k, batch = inp
        V, inv = estimator.direction_block(k, spec, b2q, kind=cfg.estimator,
                                           conv="block")
        base = estimator.query(loss_fn, buf, spec, batch)
        lp = jax.vmap(lambda v, s: estimator.query(
            loss_fn, buf + (mu * s) * v, spec, batch))(V, inv)
        if cfg.central:
            lm = jax.vmap(lambda v, s: estimator.query(
                loss_fn, buf - (mu * s) * v, spec, batch))(V, inv)
            coeffs = scale * (lp - lm).astype(jnp.float32) / (2 * mu)
        else:
            coeffs = scale * (lp - base).astype(jnp.float32) / mu
        g_fresh = ((coeffs * inv) @ V) / b2q
        # first iterate: no history yet, the surrogate IS the fresh estimate
        w = jnp.where(t == 0, 0.0, beta)
        g_hat = w * g_hat + (1.0 - w) * g_fresh
        buf = buf - cfg.lr * g_hat
        return (buf, g_hat, t + 1), (coeffs, base)

    (buf, _, _), (coeffs, losses) = jax.lax.scan(
        step, (buf0, jnp.zeros_like(buf0), jnp.int32(0)), (keys, batches))
    return buf, coeffs, losses


def _wide_phase_scan(loss_fn, buf0, spec, keys, batches, cfg, like=None):
    """Scan H batched-direction ("wide") iterates over a flat buffer — the
    simulation engine's local phase (DESIGN.md §9). Per step: ONE direction
    block [b2, n_pad], the b2 perturbed forwards as one vmap (XLA batches
    them), and the update as one matvec. Statistically identical to the
    loop estimator; walks its exact directions when direction_conv="tree".
    direction_conv="surrogate" swaps in the trajectory-informed surrogate
    phase (fewer fresh queries, EW-blended update direction);
    direction_conv="channel" perturbs along channel-driven gaussian
    directions (the one-point wireless estimator, arXiv 2401.17460).
    Returns (final buf, coeffs [H, b2], losses [H])."""
    if cfg.direction_conv == "surrogate":
        return _surrogate_phase_scan(loss_fn, buf0, spec, keys, batches, cfg)
    mu = jnp.float32(cfg.mu)
    conv = (cfg.direction_conv if cfg.direction_conv in ("tree", "channel")
            else "block")
    # the channel-driven one-point estimator (arXiv 2401.17460) perturbs
    # along raw fading-projection gaussians — gaussian statistics
    # (E[vvᵀ] = I) whatever cfg.estimator says, so the unbiasedness factor
    # is 1, not d (estimator.direction_block documents the convention)
    scale = (1.0 if conv == "channel"
             else estimator._scale_factor(spec.d, cfg.estimator))

    def step(buf, inp):
        k, batch = inp
        V, inv = estimator.direction_block(k, spec, cfg.b2,
                                           kind=cfg.estimator, conv=conv,
                                           like=like)
        base = estimator.query(loss_fn, buf, spec, batch)
        lp = jax.vmap(lambda v, s: estimator.query(
            loss_fn, buf + (mu * s) * v, spec, batch))(V, inv)
        if cfg.central:
            lm = jax.vmap(lambda v, s: estimator.query(
                loss_fn, buf - (mu * s) * v, spec, batch))(V, inv)
            coeffs = scale * (lp - lm).astype(jnp.float32) / (2 * mu)
        else:
            coeffs = scale * (lp - base).astype(jnp.float32) / mu
        buf = buf + (-cfg.lr / cfg.b2) * ((coeffs * inv) @ V)
        return buf, (coeffs, base)

    buf, (coeffs, losses) = jax.lax.scan(step, buf0, (keys, batches))
    return buf, coeffs, losses


def local_phase(loss_fn, params, batches, rng, cfg: FedZOConfig) -> LocalResult:
    """H local iterates (Algorithm 1 inner loop).

    ``batches`` is a pytree whose leaves have a leading [H] axis (the client
    pre-samples H minibatches of size b1). On the flat path the pytree is
    flattened once for the whole phase — the H·b2 perturb/update passes all
    run on the single flat buffer.
    """
    keys = jax.random.split(rng, cfg.local_iters)
    _check_surrogate(cfg)

    if cfg.batch_directions:
        spec, _ = _wide_setup(params, cfg)
        buf, coeffs, losses = _wide_phase_scan(
            loss_fn, flatten(params, spec), spec, keys, batches, cfg,
            like=params)
        return LocalResult(unflatten(buf, spec), coeffs, losses)

    if cfg.flat_params:
        spec, br = _flat_setup(params, cfg)
        buf, coeffs, losses = _flat_phase_scan(
            loss_fn, flatten(params, spec), spec, br, keys, batches, cfg)
        return LocalResult(unflatten(buf, spec), coeffs, losses)

    def body(carry, inp):
        p = carry
        k, batch = inp
        p, coeffs, base = local_iterate(loss_fn, p, batch, k, cfg)
        return p, (coeffs, base)

    p_fin, (coeffs, losses) = jax.lax.scan(body, params, (keys, batches))
    return LocalResult(p_fin, coeffs, losses)


def client_delta(loss_fn, params, batches, rng, cfg) -> tuple:
    """Δ_i = x_i^{(t,H)} − x^t plus the seed-replayable summary."""
    res = local_phase(loss_fn, params, batches, rng, cfg)
    return tree_sub(res.params, params), res


def round_simulated(loss_fn, server_params, client_batches, client_rngs,
                    cfg: FedZOConfig, *, channel_rng=None, momentum=None,
                    weights=None, faults=None, channel=None, cstate=None,
                    loss_wrap=None, state_fn=None):
    """One full communication round over the M sampled clients (vmapped).

    client_batches: pytree with leading [M, H, ...] axes.
    client_rngs:    [M] PRNG keys.
    ``momentum``: optional server-momentum state (FedOpt-style — beyond
    paper); pass a zeros-like tree and cfg.server_momentum > 0 to enable.
    Returns (new_server_params, metrics dict[, new_momentum]).

    With cfg.flat_params the whole round runs on the flat buffer
    (DESIGN.md §8): the server params are flattened ONCE, the flat local
    phase is vmapped over the M clients so the client deltas materialize
    as one [M, n_pad] matrix, and aggregation (masked mean or the fused
    one-pass AirComp kernel) happens on that matrix before a single
    unflatten.

    cfg.channel_schedule enables the paper's channel-truncation scheduling
    (Sec. IV-A): a Rayleigh draw from ``channel_rng`` masks out clients
    with |h| < h_min; masked rows are excluded from both the mean and
    Δ_max and ``m_effective`` is reported in the metrics.

    ``weights`` ([M] positive, mean-1 normalized — ``aircomp.size_weights``)
    switches every aggregation path to the FedAvg-style size-weighted mean
    n_i/n over the (scheduled) clients; the engine threads it from
    ``ClientStore.sizes`` under ``cfg.weight_by_size``.

    ``faults`` (a ``sim.faults.RoundFaults``) injects this round's realized
    client faults: the deltas are corrupted-then-scrubbed before
    aggregation and the surviving-client mask composes with the channel
    mask, so dropped/straggling/poisoned clients are excluded from the
    mean and Δ_max exactly like channel-masked ones (DESIGN.md §12).

    ``channel`` (a ``sim.channel.RoundChannel``) supplies this round's
    realized wireless scenario (DESIGN.md §16): its transmit mask —
    time-correlated-fading scheduling ∧ battery gating, advanced by the
    engine's ``ChannelModel`` carry step — REPLACES the i.i.d.
    ``schedule_by_channel`` draw, composing with faults and weights
    through the same ``mask_stats`` convention. ``channel=None`` keeps
    the per-round i.i.d. draw bit-exactly.

    Strategy hooks (core/strategy.py, DESIGN.md §13) — all default None,
    in which case every code path above is byte-for-byte the plain FedZO
    round:

    - ``cstate``: the [M, ...] per-client strategy state of the sampled
      cohort (SCAFFOLD control variates, FedDyn duals), vmapped alongside
      the batches; the (possibly updated) cohort state is appended to the
      return tuple whenever ``cstate`` is passed.
    - ``loss_wrap(loss_fn, cst) -> loss_fn'`` wraps the ZO loss query per
      client (proximal term, dynamic regularizer) — the estimator itself
      is untouched.
    - ``state_fn(deltas, cstate, spec) -> (deltas', cstate')`` is the
      client-side post-phase delta correction, applied in flat [M, n_pad]
      space on the flat/wide paths (``spec`` set) and on the stacked delta
      pytree otherwise (``spec=None``) — BEFORE fault corruption and the
      aggregation, so it composes with AirComp, scheduling, weighting,
      and the sharded reduce unchanged.
    """
    M = client_rngs.shape[0]
    _check_surrogate(cfg)
    new_cstate = cstate
    mask = None
    noise_rng = channel_rng
    air_stats = {}
    if cfg.channel_schedule and channel_rng is not None:
        with scope("fedzo.cohort"):
            k_sched, noise_rng = jax.random.split(channel_rng)
            if channel is None:
                _, mask = schedule_by_channel(k_sched, M, cfg.h_min)
    if channel is not None:
        # the scenario engine realized this round's channel already:
        # correlated-fading scheduling ∧ battery gating (sim/channel.py)
        mask = channel.mask

    if cfg.flat_params or cfg.batch_directions:
        with scope("fedzo.local"):
            spec, br = (_wide_setup(server_params, cfg)
                        if cfg.batch_directions
                        else _flat_setup(server_params, cfg))
            buf0 = flatten(server_params, spec)
            keys = jax.vmap(lambda r: jax.random.split(r, cfg.local_iters))(
                client_rngs)

            def one_client(batches, ks, cst=None):
                lf = (loss_wrap(loss_fn, cst) if loss_wrap is not None
                      else loss_fn)
                if cfg.batch_directions:
                    buf, _, base = _wide_phase_scan(lf, buf0, spec, ks,
                                                    batches, cfg,
                                                    like=server_params)
                else:
                    buf, _, base = _flat_phase_scan(lf, buf0, spec, br, ks,
                                                    batches, cfg)
                return buf - buf0, base

            if cstate is not None:
                deltas, losses = jax.vmap(one_client)(client_batches, keys,
                                                      cstate)
            else:
                deltas, losses = jax.vmap(one_client)(client_batches, keys)

        with scope("fedzo.aggregate"):
            if state_fn is not None:
                deltas, new_cstate = state_fn(deltas, cstate, spec)

            if faults is not None:
                deltas, fmask = faults.apply_flat(deltas)
                mask = fmask if mask is None else mask & fmask

            if cfg.aircomp and channel_rng is not None:
                agg_flat, air_stats = aircomp_aggregate_flat(
                    deltas, noise_rng, snr_db=cfg.snr_db, h_min=cfg.h_min,
                    d=spec.d, mask=mask, weights=weights, block_rows=br)
            elif mask is not None or weights is not None:
                maskf, m_div, m_sched = mask_stats(mask, M, weights)
                agg_flat = jnp.einsum("mn,m->n", deltas, maskf) / m_div
                # m_effective reports unconditionally: a weighted-but-
                # unscheduled round must carry the same cohort-size column
                # as every other aggregation path (history/CSV consistency)
                air_stats = {"m_effective": m_sched}
            else:
                agg_flat = jnp.mean(deltas, axis=0)
            agg = unflatten(agg_flat, spec)
    else:
        def one_client(batches, rng, cst=None):
            lf = loss_wrap(loss_fn, cst) if loss_wrap is not None else loss_fn
            delta, res = client_delta(lf, server_params, batches, rng, cfg)
            return delta, res.losses

        with scope("fedzo.local"):
            if cstate is not None:
                deltas, losses = jax.vmap(one_client)(client_batches,
                                                      client_rngs, cstate)
            else:
                deltas, losses = jax.vmap(one_client)(client_batches,
                                                      client_rngs)

        with scope("fedzo.aggregate"):
            if state_fn is not None:
                deltas, new_cstate = state_fn(deltas, cstate, None)

            if faults is not None:
                deltas, fmask = faults.apply_tree(deltas)
                mask = fmask if mask is None else mask & fmask

            if cfg.aircomp and channel_rng is not None:
                agg, air_stats = aircomp_aggregate(
                    deltas, noise_rng, snr_db=cfg.snr_db, h_min=cfg.h_min,
                    mask=mask, weights=weights)
            elif mask is not None or weights is not None:
                maskf, m_div, m_sched = mask_stats(mask, M, weights)
                agg = jax.tree.map(
                    lambda x: (jnp.einsum("m...,m->...",
                                          x.astype(jnp.float32),
                                          maskf) / m_div).astype(x.dtype),
                    deltas)
                air_stats = {"m_effective": m_sched}  # see flat-path comment
            else:
                agg = tree_scale(1.0 / M,
                                 jax.tree.map(lambda x: jnp.sum(x, 0),
                                              deltas))

    with scope("fedzo.aggregate"):
        if momentum is not None and cfg.server_momentum > 0:
            momentum = jax.tree.map(
                lambda m, g: (cfg.server_momentum * m + g).astype(m.dtype),
                momentum, agg)
            agg = momentum
        new_params = tree_add(server_params, agg)
        if faults is not None:
            # mask is never None under faults, so every branch above
            # reported m_effective (the surviving cohort); add the poison
            # count
            air_stats["m_corrupt"] = faults.n_corrupt
        metrics = {"mean_local_loss": jnp.mean(losses),
                   "first_loss": jnp.mean(losses[:, 0]), **air_stats}
    out = (new_params, metrics)
    if momentum is not None:
        out = out + (momentum,)
    if cstate is not None:
        out = out + (new_cstate,)
    return out


def make_pod_round_step(loss_fn_grouped, cfg: FedZOConfig, mesh) -> Callable:
    """Cross-silo FedZO round for the multi-pod mesh: each pod is one client.

    Pure-GSPMD formulation (the nested manual-axis formulation with
    independent per-pod directions crashes XLA's SPMD partitioner — see
    DESIGN.md §5): all pods share the round's perturbation directions
    (common random seeds — exactly the wire format of core/seedcomm.py), the
    batch is sharded over ('pod','data') so each pod's loss group is
    computed from its own silo data, and the only cross-pod exchange is the
    per-pod coefficient vector [n_pod, b2] (scalar psums). The dense-delta /
    AirComp uplink variant is costed separately by ``make_delta_agg_step``.

    With shared directions, per-pod local trajectories cannot diverge inside
    one jit program, so this round runs H=1 (FedSGD-ZO). The paper-faithful
    independent-direction, H>1 algorithm is exercised by the simulation mode
    (``round_simulated``) and by the per-pod single-silo ``make_train_step``
    programs that a real deployment would run on each pod slice.

    ``loss_fn_grouped(params, batch) -> [n_pod] per-pod losses``.
    signature: (params, batch, rng) -> (params, metrics)
    """
    from repro.core.estimator import _scale_factor
    from repro.utils.tree import tree_axpy, tree_size

    n_pod = mesh.shape["pod"]

    if cfg.flat_params:
        def flat_step(params, batch, rng):
            spec, br = _flat_setup(params, cfg)
            buf = flatten(params, spec)
            # sphere inv-norms computed ONCE and shared by both ends — the
            # same invariant flat_local_iterate documents (zo_dirnorms
            # regenerates all b2 directions, so running it twice doubles
            # the direction-generation compute of the step)
            inv = estimator.flat_inv_norms(
                kops.key_words(rng), spec, cfg.b2, cfg.estimator,
                block_rows=br)
            # flat_coefficients handles vector-valued (grouped) losses:
            # coeffs come back [b2, n_pod]
            coeffs, base = estimator.flat_coefficients(
                loss_fn_grouped, buf, spec, batch, rng,
                mu=cfg.mu, b2=cfg.b2, kind=cfg.estimator,
                central=cfg.central, block_rows=br, inv=inv)
            # the only cross-pod uplink: mean of per-pod coefficients
            c_mean = jnp.mean(coeffs, axis=1)               # [b2]
            buf = estimator.flat_apply_coefficients(
                buf, spec, rng, c_mean, scale=-cfg.lr, kind=cfg.estimator,
                block_rows=br, inv=inv)
            return unflatten(buf, spec), {
                "loss": jnp.mean(base), "per_pod_loss": base,
                "coeff_pod_spread": jnp.std(coeffs, axis=1).mean()}

        return flat_step

    def step(params, batch, rng):
        d = tree_size(params)
        scale = _scale_factor(d, cfg.estimator)
        base = loss_fn_grouped(params, batch)              # [n_pod]

        def body(n, acc):
            v = estimator._direction(rng, n, params, cfg.estimator,
                                     jnp.dtype(cfg.direction_dtype),
                                     cfg.direction_conv)
            lp = loss_fn_grouped(tree_axpy(cfg.mu, v, params), batch)
            c = scale * (lp - base).astype(jnp.float32) / cfg.mu  # [n_pod]
            return acc.at[n].set(c)

        coeffs = jax.lax.fori_loop(
            0, cfg.b2, body, jnp.zeros((cfg.b2, n_pod), jnp.float32))
        # federated aggregation: mean of per-pod coefficients (the entire
        # cross-pod uplink in seed-compression mode)
        c_mean = jnp.mean(coeffs, axis=1)                  # [b2]
        new_params = estimator.apply_coefficients(
            params, rng, c_mean, scale=-cfg.lr, kind=cfg.estimator,
            direction_dtype=jnp.dtype(cfg.direction_dtype),
            conv=cfg.direction_conv)
        return new_params, {"loss": jnp.mean(base),
                            "per_pod_loss": base,
                            "coeff_pod_spread": jnp.std(coeffs, axis=1).mean()}

    return step


def make_delta_agg_step(cfg: FedZOConfig, n_pod: int) -> Callable:
    """The dense-uplink aggregation program: per-pod model deltas (leading
    [n_pod] axis, sharded over ``pod``) -> mean delta (+ optional AirComp
    noise, Sec. IV). Lowered separately on the multi-pod mesh so the dry-run
    prices the full-d cross-pod all-reduce that AirComp / seed-compression
    eliminate. signature: (deltas, rng) -> tree
    """
    from repro.core.aircomp import aircomp_aggregate

    def step(deltas, rng):
        if cfg.aircomp:
            agg, _ = aircomp_aggregate(deltas, rng, snr_db=cfg.snr_db,
                                       h_min=cfg.h_min)
            return agg
        return jax.tree.map(lambda x: jnp.mean(x, axis=0), deltas)

    return step


def make_train_step(loss_fn, cfg: FedZOConfig) -> Callable:
    """jit-ready cross-silo train step: one local ZO iterate.

    signature: (params, batch, rng) -> (params, metrics)
    """
    def step(params, batch, rng):
        new_params, coeffs, base = local_iterate(loss_fn, params, batch, rng, cfg)
        return new_params, {"loss": base, "coeff_norm": jnp.linalg.norm(coeffs)}

    return step
