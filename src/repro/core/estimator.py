"""Stochastic zeroth-order gradient estimators (paper Sec. II-B, Eq. 2).

The mini-batch estimator with b1 data samples and b2 directions:

    ∇̃F(x) = 1/(b1·b2) Σ_m Σ_n (d·v_n/μ) (F(x + μ v_n, ξ_m) − F(x, ξ_m)),
    v_n ~ U(S^{d-1})

Because the same minibatch {ξ_m} is used at both points, the m-average is
just the minibatch-mean loss, so the implementation evaluates the minibatch
loss once at x and once at each x + μ v_n.

Directions are *never stored*: each v_n is regenerated from
``fold_in(rng, n)`` (seed replay, see utils/tree.py). That gives two forms:

- ``estimate(...)``        → materialized gradient-estimate pytree
                             (paper-scale models; FedAvg-compatible API)
- ``coefficients(...)``    → only the b2 scalar coefficients
                             c_n = d·(L(x+μv_n) − L(x))/μ; the update
                             Σ c_n v_n / b2 is replayed later (big models,
                             seed-based delta compression, AirComp-free mode)

Variants beyond the paper's sphere estimator:
- ``gaussian``  (Nesterov-Spokoiny smoothing; MeZO-style)  — no d factor.
- ``coordinate`` (Kiefer-Wolfowitz-type, random coordinates) — d factor,
  v = e_i basis vectors; paper Table I compares against this family.
- ``rademacher`` (SPSA-style ±1 directions) — no d factor (E[vvᵀ] = I).
- ``central=True`` uses the two-sided difference
  (F(x+μv) − F(x−μv)) / 2μ — one extra query per direction buys an
  O(μ²) bias instead of O(μ) (standard ZO variance/bias trade).

Two direction *conventions* coexist (DESIGN.md §7):

- ``conv="tree"``    (default) per-leaf threefry keys via fold_in — the
                     original pytree path.
- ``conv="counter"`` the flat counter convention (round_key, n, flat
                     index) of kernels/zo_axpy.py, shared bit-for-bit with
                     the in-kernel generators of the flat-buffer hot path
                     (``flat_update`` / ``flat_apply_coefficients``
                     below). With this conv the pytree path and the fused
                     flat path walk the *same* directions, so their loss
                     trajectories agree to fp32 round-off — the
                     equivalence tests pin exactly that.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels.zo_axpy import LANES, counter_direction_flat
from repro.obs.trace import scope
from repro.utils.flatparams import FlatSpec, flat_spec, unflatten
from repro.utils.tree import (normal_like_tree, sphere_like_tree,
                              tree_add_normal, tree_axpy, tree_norm,
                              tree_random_sq_norm, tree_scale, tree_size,
                              tree_zeros_like)

# estimator kind → counter-convention generator kind (coordinate directions
# have no streaming generator; the flat path rejects them)
COUNTER_KINDS = {"sphere": "normal", "gaussian": "normal",
                 "rademacher": "sign"}


def sample_direction(rng, params, kind: str, dtype=jnp.float32):
    """One direction pytree v with E-factor folded into the caller's d-scale."""
    if kind == "sphere":
        return sphere_like_tree(rng, params, dtype=dtype)
    if kind == "gaussian":
        return normal_like_tree(rng, params, dtype=dtype)
    if kind == "rademacher":
        leaves, treedef = jax.tree.flatten(params)
        out = [jax.random.rademacher(jax.random.fold_in(rng, i), l.shape,
                                     dtype)
               for i, l in enumerate(leaves)]
        return jax.tree.unflatten(treedef, out)
    if kind == "coordinate":
        # one-hot at a uniformly random flat index, built leafwise
        d = tree_size(params)
        idx = jax.random.randint(rng, (), 0, d)
        leaves, treedef = jax.tree.flatten(params)
        out, off = [], 0
        for leaf in leaves:
            n = leaf.size
            flat = jnp.where(jnp.arange(n) == idx - off, 1.0, 0.0)
            out.append(flat.reshape(leaf.shape).astype(dtype))
            off += n
        return jax.tree.unflatten(treedef, out)
    raise ValueError(f"unknown estimator kind {kind!r}")


def counter_direction(rng, n, params, kind, dtype=jnp.float32):
    """Direction pytree v_n under the flat counter convention.

    The pure-JAX twin of the in-kernel generators: same
    (round_key, n, flat_index) → element map as zo_walk / zo_replay, so a
    pytree-path run with conv="counter" walks the flat path's directions.
    """
    ck = COUNTER_KINDS.get(kind)
    if ck is None:
        raise ValueError(f"counter convention does not support {kind!r}")
    spec = flat_spec(params)
    key2 = kops.key_words(rng)
    g = counter_direction_flat(key2, n, spec.d, kind=ck)
    if kind == "sphere":
        g = g * (1.0 / (jnp.linalg.norm(g) + 1e-30))
    out = [g[off:off + sz].reshape(shp).astype(dtype)
           for shp, off, sz in zip(spec.shapes, spec.offsets, spec.sizes)]
    return jax.tree.unflatten(spec.treedef, out)


def query(loss_fn, buf, spec: FlatSpec, batch):
    """One loss query of a flat buffer: unflatten plus the model's forward,
    under the ``fedzo.query`` scope, the forward alone under
    ``fedzo.forward`` inside it."""
    with scope("fedzo.query"):
        params = unflatten(buf, spec)
        with scope("fedzo.forward"):
            return loss_fn(params, batch)


def _query_tree(loss_fn, params, batch):
    with scope("fedzo.query"), scope("fedzo.forward"):
        return loss_fn(params, batch)


def _direction(rng, n, params, kind, dtype, conv):
    if conv == "counter":
        return counter_direction(rng, n, params, kind, dtype)
    return sample_direction(jax.random.fold_in(rng, n), params, kind, dtype)


def _scale_factor(d, kind):
    # unbiasedness factor: d for sphere/coordinate, 1 for gaussian/rademacher
    # (for which E[vv^T] = I without rescaling)
    return 1.0 if kind in ("gaussian", "rademacher") else float(d)


def stream_perturb(params, key, mag, kind="sphere", dtype=jnp.float32):
    """params + mag·v(key) WITHOUT materializing v (chunked RNG streaming —
    the big-model memory path, §Perf iteration 3). Bit-consistent with
    ``sample_direction`` up to float reassociation of the sphere scaling."""
    if kind == "coordinate":
        return tree_axpy(mag, sample_direction(key, params, kind), params)
    if kind == "sphere":
        inv = 1.0 / (jnp.sqrt(tree_random_sq_norm(key, params, dtype)) + 1e-30)
        return tree_add_normal(params, key, mag * inv, dtype)
    return tree_add_normal(params, key, mag, dtype)  # gaussian


def coefficients(loss_fn, params, batch, rng, *, mu, b2, kind="sphere",
                 base_loss=None, direction_dtype=jnp.float32, central=False,
                 conv="tree"):
    """The b2 coefficients c_n = scale·(L(x+μ v_n) − L(x))/μ  (fp32 [b2]).

    ``loss_fn(params, batch) -> scalar``. Directions are regenerated from
    ``fold_in(rng, n)`` (conv="tree") or the counter convention
    (conv="counter"); callers replay the same seeds to apply updates.
    ``central=True`` uses (L(x+μv) − L(x−μv)) / 2μ (O(μ²) smoothing bias,
    one extra forward per direction).
    """
    d = tree_size(params)
    scale = _scale_factor(d, kind)
    base = (_query_tree(loss_fn, params, batch) if base_loss is None
            else base_loss)

    def body(n, acc):
        # materialized direction + axpy measured Pareto-best on the XLA:CPU
        # buffer-assignment instrument (§Perf iteration 3: two-pass
        # streaming, chunked and rbg variants all refuted).
        v = _direction(rng, n, params, kind, direction_dtype, conv)
        lp = _query_tree(loss_fn, tree_axpy(mu, v, params), batch)
        if central:
            lm = _query_tree(loss_fn, tree_axpy(-mu, v, params), batch)
            c = scale * (lp - lm).astype(jnp.float32) / (2 * mu)
        else:
            c = scale * (lp - base).astype(jnp.float32) / mu
        return acc.at[n].set(c)

    coeffs = jax.lax.fori_loop(0, b2, body, jnp.zeros((b2,), jnp.float32))
    return coeffs, base


def apply_coefficients(params, rng, coeffs, *, scale=1.0, kind="sphere",
                       direction_dtype=jnp.float32, conv="tree"):
    """params + scale · Σ_n coeffs[n] · v_n / b2  (seed replay of v_n)."""
    b2 = coeffs.shape[0]

    def body(n, p):
        v = _direction(rng, n, params, kind, direction_dtype, conv)
        return tree_axpy(scale * coeffs[n] / b2, v, p)

    return jax.lax.fori_loop(0, b2, body, params)


# ---------------------------------------------------------------------------
# flat-buffer hot path (DESIGN.md §7): the fused perturbation walk, which
# carries the update too, and the single-pass seed replay over a FlatParams
# buffer, all directions regenerated in-kernel from the counter convention.


def flat_inv_norms(key2, spec: FlatSpec, b2, kind, *, interpret=None,
                   block_rows=None):
    """[b2] per-direction scale factors: 1/‖g_n‖ for sphere, else ones.

    Computed by the zo_dirnorms kernel — directions never touch HBM.
    """
    if kind != "sphere":
        return jnp.ones((b2,), jnp.float32)
    sq = kops.zo_dirnorms(key2, spec.d, b2=b2, n_pad=spec.n_pad,
                          kind="normal", interpret=interpret,
                          block_rows=block_rows)
    return 1.0 / (jnp.sqrt(sq) + 1e-30)


def flat_update(loss_fn, buf, spec: FlatSpec, batch, rng, *, mu, b2, scale,
                kind="sphere", central=False, interpret=None, block_rows=None,
                combine=None):
    """One ZO update of the flat buffer: the fused MeZO-style perturbation
    walk with the seed replay folded into it. Returns
    ``(buf + scale · Σ_n c̄_n·v_n / b2, coeffs [b2, ...], base)``.

    Instead of perturb-then-restore (two passes between forwards), each
    step transitions x+μv_{n-1} → x+μv_n directly with one zo_walk call
    (a=−μ, b=+μ): ONE read + ONE write of the parameter buffer per
    direction, zero direction HBM traffic. Numerically this is the pytree
    path with conv="counter" up to fp32 reassociation.

    The update rides the walk: step n adds eff_{n−1}·v_{n−1}, where
    eff_n = (scale/b2)·c̄_n·inv_n, into an fp32 accumulator from the
    direction it generates anyway (query n−1 has given c_{n−1} by then),
    and one closing call adds the last direction's term. That is
    ``flat_apply_coefficients``' n-ascending replay, bit for bit, without
    its b2 further generations per iterate. ``c̄_n = combine(c_n)``: c_n
    itself by default; the pod step passes the mean over its per-pod
    coefficients (``loss_fn`` may return a vector, and the coefficients
    get a matching trailing shape).
    """
    ck = COUNTER_KINDS.get(kind)
    if ck is None:
        raise ValueError(f"flat path does not support kind={kind!r}")
    key2 = kops.key_words(rng)
    dscale = _scale_factor(spec.d, kind)
    base = query(loss_fn, buf, spec, batch)
    inv = flat_inv_norms(key2, spec, b2, kind, interpret=interpret,
                         block_rows=block_rows)
    mu = jnp.float32(mu)
    step = jnp.float32(scale) / b2
    combine = combine or (lambda c: c)
    kw = dict(kind=ck, interpret=interpret, block_rows=block_rows)

    def eff(coeffs, n):
        return step * combine(coeffs[n]) * inv[n]

    def body(n, carry):
        xp, u, coeffs = carry
        prev = jnp.maximum(n - 1, 0)
        # state entering step n: x (n=0); x+μv_{n-1} (one-sided, n>0);
        # x−μv_{n-1} (central, n>0) — remove it and add +μv_n in one pass,
        # and add v_{n-1}'s update term (none yet at n=0)
        a = jnp.where(n == 0, 0.0, (mu if central else -mu) * inv[prev])
        b = mu * inv[n]
        e = jnp.where(n == 0, 0.0, eff(coeffs, prev))
        xp, u = kops.zo_walk(xp, key2, jnp.stack([prev, n]),
                             jnp.stack([a, b]), acc=(u, e), **kw)
        lp = query(loss_fn, xp, spec, batch)
        if central:
            xp = kops.zo_walk(xp, key2, jnp.stack([n, n]),
                              jnp.stack([-2 * mu * inv[n], jnp.float32(0.0)]),
                              **kw)
            lm = query(loss_fn, xp, spec, batch)
            c = dscale * (lp - lm).astype(jnp.float32) / (2 * mu)
        else:
            c = dscale * (lp - base).astype(jnp.float32) / mu
        return xp, u, coeffs.at[n].set(c)

    # u is carried in the kernel's [n_pad / 128, 128] layout (n_pad is a
    # block multiple), read back as [n_pad] once the walk has ended
    u = jnp.zeros((spec.n_pad // LANES, LANES), jnp.float32)
    _, u, coeffs = jax.lax.fori_loop(
        0, b2, body,
        (buf, u, jnp.zeros((b2,) + jnp.shape(base), jnp.float32)))
    u = kops.zo_walk(None, key2, jnp.stack([b2 - 1]), None,
                     acc=(u, eff(coeffs, b2 - 1)), **kw)
    new = buf.astype(jnp.float32) + u.reshape(-1)
    return new.astype(buf.dtype), coeffs, base


def flat_apply_coefficients(buf, spec: FlatSpec, rng, coeffs, *, scale=1.0,
                            kind="sphere", interpret=None, block_rows=None):
    """buf + scale · Σ_n coeffs[n]·v_n / b2 in a SINGLE pass (zo_replay).

    The seed replay of a receiver that holds only (key, coeffs) — the
    server side of core/seedcomm.py; a client's own update rides its walk
    (``flat_update``). The b2 directions are regenerated and accumulated
    in VMEM per block — one HBM read + write of the parameter buffer
    total, versus b2 sequential axpy passes on the pytree path.
    """
    ck = COUNTER_KINDS.get(kind)
    if ck is None:
        raise ValueError(f"flat path does not support kind={kind!r}")
    b2 = coeffs.shape[0]
    key2 = kops.key_words(rng)
    inv = flat_inv_norms(key2, spec, b2, kind, interpret=interpret,
                         block_rows=block_rows)
    eff = (jnp.float32(scale) / b2) * coeffs.astype(jnp.float32) * inv
    return kops.zo_replay(buf, key2, eff, kind=ck, interpret=interpret,
                          block_rows=block_rows)


def direction_block(rng, spec: FlatSpec, b2, *, kind="sphere", conv="block",
                    like=None, dtype=jnp.float32):
    """All b2 directions of one iterate as ONE [b2, n_pad] block, plus the
    [b2] per-direction scale factors (1/‖g_n‖ for sphere, ones otherwise).

    The batched-direction ("wide") estimator of the simulation engine
    (DESIGN.md §9). Three conventions:

    - conv="block": one PRNG call for the whole block — the fast path. The
      pad columns may carry generator residue; norms are taken over the
      valid [:, :spec.d] region only and pad residue in downstream updates
      is invisible to ``unflatten``.
    - conv="tree": per-direction per-leaf fold_in keys, bit-identical to
      ``sample_direction(fold_in(rng, n), ...)`` — the loop estimator's
      directions, used to prove wide-vs-loop trajectory equivalence.
      Requires ``like`` (a params pytree matching ``spec``).
    - conv="channel": the channel-driven one-point wireless estimator
      (arXiv 2401.17460) — the direction block is the real baseband
      projection of CN(0,1) fading randomness, i.e. a unit-variance
      gaussian block drawn with the channel innovation's key fan-out
      (``kr`` of ``split(rng)`` drives the in-phase component, exactly
      like ``sim.channel.ChannelModel._innovation``), so in a deployment
      the perturbation reuses the randomness the receiver already
      estimates and costs no direction downlink. Statistically a gaussian
      estimator: E[vvᵀ] = I, so ``inv`` is ones and the update scale must
      be the gaussian one (no d-factor, no sphere normalization) whatever
      ``kind`` says — the wide phase overrides it.
    """
    if kind == "coordinate":
        raise ValueError("batched-direction path does not support "
                         "kind='coordinate'")
    if conv == "channel":
        kr, _ki = jax.random.split(rng)
        V = jax.random.normal(kr, (b2, spec.n_pad), dtype)
        return V, jnp.ones((b2,), jnp.float32)
    if conv == "tree":
        if like is None:
            raise ValueError("conv='tree' direction blocks need the params "
                             "pytree (like=...) for per-leaf key derivation")
        from repro.utils.flatparams import flatten

        def one(k):
            if kind == "rademacher":
                g = sample_direction(k, like, kind, dtype)
            else:
                g = normal_like_tree(k, like, dtype=dtype)
            return flatten(g, spec)

        keys = jax.vmap(lambda n: jax.random.fold_in(rng, n))(jnp.arange(b2))
        V = jax.vmap(one)(keys)                              # [b2, n_pad]
    elif conv == "block":
        if kind == "rademacher":
            V = jax.random.rademacher(rng, (b2, spec.n_pad), dtype)
        else:
            V = jax.random.normal(rng, (b2, spec.n_pad), dtype)
    else:
        raise ValueError(f"unknown direction block conv {conv!r}")
    if kind == "sphere":
        inv = 1.0 / (jnp.linalg.norm(
            V[:, :spec.d].astype(jnp.float32), axis=1) + 1e-30)
    else:
        inv = jnp.ones((b2,), jnp.float32)
    return V, inv


def estimate(loss_fn, params, batch, rng, *, mu, b2, kind="sphere"):
    """Materialized gradient-estimate pytree (Eq. 2). Two tree passes per
    direction; used at paper scale and by tests/property checks."""
    coeffs, _ = coefficients(loss_fn, params, batch, rng, mu=mu, b2=b2,
                             kind=kind)
    grad = apply_coefficients(tree_zeros_like(params), rng, coeffs, kind=kind)
    return grad


def two_point_estimate(loss_fn, params, batch, rng, *, mu, kind="sphere"):
    """The classic two-point estimator (b1=b2=1 special case) used by the
    DZOPA / ZONE-S baselines before their mini-batch upgrade."""
    return estimate(loss_fn, params, batch, rng, mu=mu, b2=1, kind=kind)
