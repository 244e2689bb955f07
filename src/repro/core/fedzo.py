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
rides the same walk steps. The pytree path stays as the reference.

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
from repro.core import aircomp, estimator
from repro.kernels.zo_axpy import LANES
from repro.obs.trace import scope
from repro.utils.flatparams import (flat_geometry, flat_spec, flatten,
                                    unflatten)
from repro.utils.tree import tree_sub


class LocalResult(NamedTuple):
    params: object        # x_i^{(t,H)}
    coeffs: jnp.ndarray   # [H, b2] estimator coefficients (seed-replayable)
    losses: jnp.ndarray   # [H] base losses along the trajectory


def local_plan(params, cfg: FedZOConfig):
    """The local phase's plan for ``params`` under ``cfg``:
    ``(spec, block_rows, phase)``, or None on the pytree path (neither
    cfg.batch_directions nor cfg.flat_params).

    ``spec`` lays the params out as one flat buffer, ``block_rows`` is the
    kernel block (None where no kernel reads the buffer), and
    ``phase(loss_fn, buf0, keys, batches) -> (buf, coeffs [H, b2],
    losses [H])`` runs one client's H iterates from ``buf0``. Two plans:

    - wide (cfg.batch_directions, ``_wide_phase_scan``): it never enters a
      Pallas kernel, so it pads only to the vector-lane width — NOT to the
      kernel block, whose extra rows every [b2, n_pad] direction block
      would pay for. The kernel geometry is kept only when the fused
      AirComp kernel consumes the delta matrix;
    - flat (cfg.flat_params, ``_flat_phase_scan``): the fused ZO kernels
      on the cfg's kernel geometry (``flat_geometry``).
    """
    if cfg.batch_directions:
        if cfg.aircomp:
            spec, br = flat_geometry(params, cfg.flat_block_rows)
        else:
            spec, br = flat_spec(params, block=LANES), None

        def phase(loss_fn, buf0, keys, batches):
            return _wide_phase_scan(loss_fn, buf0, spec, keys, batches, cfg,
                                    like=params)
    elif cfg.flat_params:
        spec, br = flat_geometry(params, cfg.flat_block_rows)

        def phase(loss_fn, buf0, keys, batches):
            return _flat_phase_scan(loss_fn, buf0, spec, br, keys, batches,
                                    cfg)
    else:
        return None
    return spec, br, phase


def directions_per_iterate(cfg: FedZOConfig) -> int:
    """Direction vectors one client generates per local iterate of a flat
    plan. The wide plan draws its block once (fewer fresh ones under the
    surrogate estimator). The flat plan generates each direction once in
    ``zo_dirnorms`` (sphere only), twice per walk step (v_{n-1} and v_n;
    central mode walks twice a step), and once more in the closing call
    that adds the last direction's update term."""
    if cfg.batch_directions:
        return (surrogate_queries(cfg) if cfg.direction_conv == "surrogate"
                else cfg.b2)
    norms = cfg.b2 if cfg.estimator == "sphere" else 0
    return norms + (4 if cfg.central else 2) * cfg.b2 + 1


def flat_layout(params, cfg: FedZOConfig):
    """The flat buffer a run's local phase uses, for its manifest:
    ``{"d", "n_pad", "block_rows", "directions_per_iterate"}`` (block_rows
    None where no kernel runs), or None on the pytree path. n_pad / d is
    the pad ratio."""
    plan = local_plan(params, cfg)
    if plan is None:
        return None
    spec, br, _ = plan
    return {"d": spec.d, "n_pad": spec.n_pad, "block_rows": br,
            "directions_per_iterate": directions_per_iterate(cfg)}


def flat_local_iterate(loss_fn, buf, spec, batch, rng, cfg: FedZOConfig,
                       block_rows=None):
    """One ZO update on the flat buffer: the fused walk, which carries the
    seed-replay update along (``estimator.flat_update``)."""
    return estimator.flat_update(
        loss_fn, buf, spec, batch, rng, mu=cfg.mu, b2=cfg.b2, scale=-cfg.lr,
        kind=cfg.estimator, central=cfg.central, block_rows=block_rows)


def local_iterate(loss_fn, params, batch, rng, cfg: FedZOConfig):
    """One stochastic zeroth-order update (Eq. 5-6): x ← x − η ∇̃F(x).

    Returns (new_params, coeffs [b2], base_loss). This is the unit the
    multi-pod dry-run lowers as ``train_step``. Dispatches to the flat
    hot path when cfg.flat_params is set.
    """
    if cfg.flat_params:
        spec, br = flat_geometry(params, cfg.flat_block_rows)
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

    plan = local_plan(params, cfg)
    if plan is not None:
        spec, _, phase = plan
        buf, coeffs, losses = phase(loss_fn, flatten(params, spec), keys,
                                    batches)
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
    Returns (new_server_params, metrics dict[, new_momentum][, cstate]).

    The round is three steps: ``aircomp.cohort_mask`` resolves the round's
    channel mask (``channel_rng``, ``channel``); the local phase runs
    vmapped over the M clients; the server step turns the deltas into new
    params. With a ``local_plan`` (cfg.flat_params or
    cfg.batch_directions) the whole round runs on the flat buffer
    (DESIGN.md §8): the server params are flattened ONCE, the deltas
    materialize as one [M, n_pad] matrix and ``aircomp.server_step_flat``
    aggregates it before a single unflatten. Otherwise the pytree local
    phase and ``aircomp.server_step_tree`` run: the reference the goldens
    pin. ``momentum``, ``weights``, ``faults``, ``cstate`` and
    ``state_fn`` are the server step's (documented there); ``momentum``
    comes back when passed, ``cstate`` too.

    ``loss_wrap(loss_fn, cst) -> loss_fn'`` wraps each client's ZO loss
    query with its strategy state (proximal term, dynamic regularizer —
    core/strategy.py, DESIGN.md §13); ``cstate``, the [M, ...] cohort
    strategy state, is vmapped alongside the batches. With every hook None
    the round is byte-for-byte the plain FedZO round.
    """
    _check_surrogate(cfg)
    mask, noise_rng = aircomp.cohort_mask(cfg, client_rngs.shape[0],
                                          channel_rng, channel)
    step_kw = dict(mask=mask, noise_rng=noise_rng, weights=weights,
                   faults=faults, momentum=momentum, state_fn=state_fn,
                   cstate=cstate)
    per_client = () if cstate is None else (cstate,)

    def client_loss(cst):
        return loss_fn if loss_wrap is None else loss_wrap(loss_fn, cst)

    plan = local_plan(server_params, cfg)
    if plan is None:
        def one_client(batches, rng, cst=None):
            delta, res = client_delta(client_loss(cst), server_params,
                                      batches, rng, cfg)
            return delta, res.losses

        with scope("fedzo.local"):
            deltas, losses = jax.vmap(one_client)(client_batches,
                                                  client_rngs, *per_client)
        new_params, stats, momentum, new_cstate = aircomp.server_step_tree(
            deltas, server_params, cfg, **step_kw)
    else:
        spec, br, phase = plan
        with scope("fedzo.local"):
            buf0 = flatten(server_params, spec)
            keys = jax.vmap(lambda r: jax.random.split(r, cfg.local_iters))(
                client_rngs)

            def one_client(batches, ks, cst=None):
                buf, _, base = phase(client_loss(cst), buf0, ks, batches)
                return buf - buf0, base

            deltas, losses = jax.vmap(one_client)(client_batches, keys,
                                                  *per_client)
        new_params, stats, momentum, new_cstate = aircomp.server_step_flat(
            deltas, server_params, cfg, spec, br, **step_kw)

    with scope("fedzo.aggregate"):
        metrics = {"mean_local_loss": jnp.mean(losses),
                   "first_loss": jnp.mean(losses[:, 0]), **stats}
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
            spec, br = flat_geometry(params, cfg.flat_block_rows)
            buf = flatten(params, spec)
            # flat_update handles vector-valued (grouped) losses: coeffs
            # come back [b2, n_pod]; the update takes each direction's mean
            # over the pods, the only cross-pod uplink
            buf, coeffs, base = estimator.flat_update(
                loss_fn_grouped, buf, spec, batch, rng, mu=cfg.mu, b2=cfg.b2,
                scale=-cfg.lr, kind=cfg.estimator, central=cfg.central,
                block_rows=br, combine=jnp.mean)
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
    def step(deltas, rng):
        return aircomp.aggregate_tree(deltas, cfg, noise_rng=rng)[0]

    return step


def make_train_step(loss_fn, cfg: FedZOConfig) -> Callable:
    """jit-ready cross-silo train step: one local ZO iterate.

    signature: (params, batch, rng) -> (params, metrics)
    """
    def step(params, batch, rng):
        new_params, coeffs, base = local_iterate(loss_fn, params, batch, rng, cfg)
        return new_params, {"loss": base, "coeff_norm": jnp.linalg.norm(coeffs)}

    return step
