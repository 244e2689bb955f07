"""The plain reference: FedZO (arXiv 2201.09531, Algorithm 1) written out
in straightforward float32 JAX over the parameter pytree.

It imports nothing of the program. It follows the documented protocol the
engine keeps, so that the two walk the same trajectory up to round-off:

- the per-round key chain: ``key, k_part, k_batch, k_zo, k_chan[, k_chanm]
  = split(key, 5[+1])``; participants are ``permutation(k_part, N)[:M]``;
  client i's H minibatches are ``randint(split(k_batch, M)[i], (H, b1), 0,
  n_i)`` rows of its own data; client i's key is ``split(k_zo, M)[i]`` and
  its iterate keys ``split(key_i, H)``;
- the counter direction convention: element j of direction n under a key
  with words (k0, k1) is Box-Muller over the two words of
  Threefry-2x32((k0, k1), (n, j)), j the flat index in leaf order; sphere
  directions are these over their Euclidean norm;
- the sphere estimator c_n = d·(L(x + μ·v_n) − L(x))/μ and the update
  x ← x − (η/b2)·Σ_n c_n·v_n, H times per round;
- aggregation as the FedAvg size-weighted mean of the deltas; with AirComp
  (paper Sec. IV, Eq. 17) the mean over the scheduled clients plus Gaussian
  noise of variance σ²·Δ_max/(m²·d·h_min²), the noise field being
  direction 0 of the counter convention under the noise key;
- the wireless channel as the engine's integer AR(1) chain: Q.14 fading,
  an Irwin-Hall innovation of 24 22-bit words, Q.12 coefficients, and the
  |h| >= h_min test on exact integer magnitudes.

Model forwards are the config's own ``ref_logits``, computed with every
matmul and convolution at ``precision``: ``"highest"`` (full float32) for
the reference, or one of the lower precisions of ``_precise`` for the
controls that have to fail the comparison.
"""
from __future__ import annotations

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax.extend.random import threefry_2x32

HIGHEST = jax.lax.Precision.HIGHEST
CHANNEL_SALT = 0x6368


def _to_bf16(a):
    """float32 rounded to the nearest bfloat16 (ties to even), kept as
    float32. Integer ops, because XLA may fold an f32 -> bf16 -> f32
    round trip away as excess precision."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _precise(op, precision):
    """``op(a, b, p)`` at float32 (``highest``); at the backend's ``high``
    or ``default`` precision (on a TPU three bfloat16 passes, or one); or
    spelled out so that every backend computes the same, with float32
    accumulation: ``bf16x3``, the three passes hi*hi + hi*lo + lo*hi of
    operands split into bfloat16 high and low parts, or ``bf16``, one pass
    of operands rounded to bfloat16. (An op is bilinear, so the passes
    may be summed after it.)"""
    if precision == "highest":
        return lambda a, b: op(a, b, HIGHEST)
    if precision in ("high", "default"):
        p = getattr(jax.lax.Precision, precision.upper())
        return lambda a, b: op(a, b, p)
    if precision == "bf16":
        return lambda a, b: op(_to_bf16(a), _to_bf16(b), HIGHEST)
    if precision == "bf16x3":
        def three(a, b):
            ah, bh = _to_bf16(a), _to_bf16(b)
            al, bl = _to_bf16(a - ah), _to_bf16(b - bh)
            return (op(ah, bh, HIGHEST) + op(ah, bl, HIGHEST)
                    + op(al, bh, HIGHEST))
        return three
    raise ValueError(f"unknown precision {precision!r}")


def ops(precision):
    """The matmul and NHWC/HWIO 'SAME' convolution a reference forward
    uses, at ``precision``."""
    def mm(a, b, p):
        return jnp.matmul(a, b, precision=p)

    def cv(x, w, p):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=p)

    return SimpleNamespace(matmul=_precise(mm, precision),
                           conv=_precise(cv, precision))


def xent(logits, y):
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])


def counter_normal(key2, n, d):
    """Direction n of the counter convention, elements [0, d): float32."""
    idx = jnp.arange(d, dtype=jnp.uint32)
    bits = threefry_2x32(key2, jnp.concatenate(
        [jnp.full((d,), n, jnp.uint32), idx]))
    b0, b1 = bits[:d], bits[d:]
    u1 = ((b0 >> 8).astype(jnp.int32).astype(jnp.float32)
          * jnp.float32(2.0 ** -24) + jnp.float32(2.0 ** -25))
    u2 = (b1 >> 8).astype(jnp.int32).astype(jnp.float32) \
        * jnp.float32(2.0 ** -24)
    return jnp.sqrt(jnp.float32(-2.0) * jnp.log(u1)) * jnp.cos(
        jnp.float32(2.0 * math.pi) * u2)


def _key2(key):
    return jax.random.key_data(key).astype(jnp.uint32)[..., :2]


def _ravel(tree):
    return jnp.concatenate([l.reshape(-1) for l in jax.tree.leaves(tree)])


def _unravel(flat, like):
    leaves, treedef = jax.tree.flatten(like)
    out, off = [], 0
    for l in leaves:
        out.append(flat[off:off + l.size].reshape(l.shape))
        off += l.size
    return jax.tree.unflatten(treedef, out)


# -- the wireless channel chain ----------------------------------------------


def _innovation(key, n):
    u = jax.random.bits(key, (n, 2, 24), jnp.uint32)
    s = jnp.sum((u >> 10).astype(jnp.int32), axis=-1) - jnp.int32(12 << 22)
    return (s + 256) >> 9


def channel_init(run_key, n):
    return _innovation(jax.random.fold_in(run_key, CHANNEL_SALT), n)


def channel_step(key, h, idx, rho, h_min):
    """Advance the Q.14 AR(1) fading of all clients; return the new state
    and the cohort's transmit mask |h| >= h_min."""
    w = _innovation(key, h.shape[0])
    if rho > 0.0:
        rho_q = min(int(round(rho * 4096)), 4095)
        sigma_q = int(round(math.sqrt((1 << 24) - rho_q ** 2)))
        h = jnp.clip((rho_q * h + sigma_q * w + 2048) >> 12,
                     -((1 << 18) - 1), (1 << 18) - 1)
    else:
        h = w
    r = h[idx] >> 4
    mag = r[..., 0] * r[..., 0] + r[..., 1] * r[..., 1]
    thresh = jnp.int32(jnp.round(jnp.square(jnp.float32(h_min))
                                 * jnp.float32(1 << 20)))
    return h, mag >= thresh


# -- one client, one round ----------------------------------------------------


def local_phase(loss, params, batches, key, fz):
    """H sphere-estimator iterates from ``params``; returns (delta pytree,
    [H] base losses)."""
    x0 = _ravel(params)
    d = x0.shape[0]
    mu = jnp.float32(fz["mu"])
    keys = jax.random.split(key, fz["local_iters"])

    def iterate(x, inp):
        k, batch = inp
        key2 = _key2(k)
        base = loss(_unravel(x, params), batch)

        def coeff(n):
            g = counter_normal(key2, n, d)
            inv = 1.0 / (jnp.sqrt(jnp.sum(g * g)) + 1e-30)
            lp = loss(_unravel(x + mu * (inv * g), params), batch)
            return jnp.float32(d) * (lp - base) / mu, inv

        c, inv = jax.lax.map(coeff, jnp.arange(fz["b2"], dtype=jnp.uint32))
        step = jax.lax.fori_loop(
            0, fz["b2"],
            lambda n, acc: acc + (c[n] * inv[n])
            * counter_normal(key2, n.astype(jnp.uint32), d),
            jnp.zeros_like(x))
        return x - (fz["lr"] / fz["b2"]) * step, base

    x, bases = jax.lax.scan(iterate, x0, (keys, batches))
    return x - x0, bases


def make_rounds(model_logits, fz, *, precision="highest", fault=None):
    """A jitted ``rounds(params, state, clients, sizes) -> (params, state,
    metrics)`` running ``fz["segment_rounds"]`` reference rounds.

    ``fault`` plants a fault in the reference put in the program's place:
    ``"half_batch"`` drops the second half of every minibatch (the loss is
    the mean over the rest); ``"no_exchange"`` aggregates only the first
    1/chips of the cohort, as a clients mesh whose partial sums were never
    exchanged; ``"unchanged"`` returns the parameters untouched."""
    mo = ops(precision)
    chips = fz.get("mesh_clients", 1)

    def loss(p, batch):
        return xent(model_logits(p, batch["x"], mo), batch["y"])

    N, M, H, b1 = (fz["n_clients"], fz["n_participating"],
                   fz["local_iters"], fz["b1"])
    use_chan = fz.get("channel") is not None
    eval_every, seg = fz["eval_every"], fz["segment_rounds"]

    def one_round(carry, t):
        params, key, h, clients, sizes, test = carry
        ks = jax.random.split(key, 6 if use_chan else 5)
        key, k_part, k_batch, k_zo, k_chan = ks[:5]
        idx = jax.random.permutation(k_part, N)[:M]
        n_i = sizes[idx]

        def batch_of(k, i, n):
            rows = jax.random.randint(k, (H, b1), 0, n)
            return jax.tree.map(lambda a: a[i][rows], clients)

        batches = jax.vmap(batch_of)(jax.random.split(k_batch, M), idx, n_i)
        if fault == "half_batch":
            batches = jax.tree.map(lambda a: a[:, :, :b1 // 2], batches)
        w = n_i.astype(jnp.float32)
        w = w / (jnp.sum(w) / M) if fz["weight_by_size"] else jnp.ones_like(w)
        mask = jnp.ones((M,), bool)
        if use_chan:
            h, mask = channel_step(ks[5], h, idx, fz["channel"]["rho"],
                                   fz["h_min"])
        deltas, bases = jax.vmap(
            lambda b, k: local_phase(loss, params, b, k, fz))(
                batches, jax.random.split(k_zo, M))
        coef = mask.astype(jnp.float32) * w
        div = jnp.maximum(jnp.sum(coef), 1e-8)
        if fault == "no_exchange":
            coef = coef * (jnp.arange(M) < M // chips)
        mean = jnp.sum(coef[:, None] * deltas, axis=0) / div
        out = {"mean_local_loss": jnp.mean(bases),
               "first_loss": jnp.mean(bases[:, 0]),
               "m_effective": jnp.sum(mask.astype(jnp.float32))}
        if fz.get("aircomp"):
            d = mean.shape[0]
            sq = jnp.sum(deltas * deltas, axis=1)
            delta_max = jnp.max(jnp.where(coef > 0, sq, 0.0))
            sigma_w2 = 1.0 / (10.0 ** (fz["snr_db"] / 10.0))
            std = jnp.sqrt(sigma_w2 * delta_max
                           / (div ** 2 * float(d) * fz["h_min"] ** 2))
            noise_key = (jax.random.split(k_chan)[1]
                         if fz.get("channel_schedule") else k_chan)
            mean = mean + std * counter_normal(_key2(noise_key),
                                               jnp.uint32(0), d)
            out.update(delta_max=delta_max, aircomp_noise_std=std)
        if fault != "unchanged":
            params = jax.tree.map(jnp.add, params, _unravel(mean, params))
        ev = jax.lax.cond(
            (t % seg) % eval_every == 0,
            lambda p: xent(model_logits(p, test["x"], mo), test["y"]),
            lambda p: jnp.float32(jnp.nan), params)
        out["eval_loss"] = ev
        return (params, key, h, clients, sizes, test), out

    @jax.jit
    def rounds(params, key, h, clients, sizes, test, t0):
        (params, key, h, _, _, _), out = jax.lax.scan(
            one_round, (params, key, h, clients, sizes, test),
            t0 + jnp.arange(seg))
        return params, key, h, out

    return rounds


def run(model_logits, fz, params0, run_key, clients, sizes, test, steps, *,
        precision="highest", fault=None):
    """``steps`` segments of reference rounds from ``params0``. Returns the
    per-round metrics (numpy, concatenated over the segments) and the
    parameters after each segment."""
    import numpy as np

    rounds = make_rounds(model_logits, fz, precision=precision, fault=fault)
    h = (channel_init(run_key, fz["n_clients"]) if fz.get("channel")
         else jnp.zeros((fz["n_clients"], 2), jnp.int32))
    params, key = params0, run_key
    per_step, mets = [], []
    for s in range(steps):
        params, key, h, out = rounds(params, key, h, clients, sizes, test,
                                     jnp.int32(s * fz["segment_rounds"]))
        per_step.append(jax.tree.map(np.asarray, params))
        mets.append(jax.tree.map(np.asarray, out))
    metrics = {k: np.concatenate([m[k] for m in mets]) for k in mets[0]}
    return metrics, per_step
