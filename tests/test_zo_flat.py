"""Flat-buffer ZO hot path (DESIGN.md §7): kernel bit-equivalence against
the interpreted references, and old-vs-new trajectory agreement.

The load-bearing claims pinned here:
  1. zo_replay / zo_walk are bit-identical to the pure-jnp references built
     from the SAME counter convention (per block, both direction kinds).
  2. flat_apply_coefficients == pytree apply_coefficients(conv="counter")
     up to fp32 reassociation.
  3. The fused flat local_iterate walks the same loss trajectory as the
     pytree path with conv="counter" on the softmax-regression model over
     ≥ 20 local iterates (fp32 tolerance) — the perf path changes HBM
     traffic, not the algorithm.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedZOConfig
from repro.core import estimator, fedzo, seedcomm
from repro.data.synthetic import make_classification
from repro.kernels import ops, ref
from repro.models.simple import softmax_init, softmax_loss
from repro.utils.flatparams import flat_spec, flatten, unflatten

BR = 4                      # small kernel blocks: 4 rows × 128 lanes = 512
KEY2 = jax.random.key_data(jax.random.key(1234))


# -- 1. kernel bit-equivalence ---------------------------------------------


@pytest.mark.parametrize("kind", ["normal", "sign"])
@pytest.mark.parametrize("nblocks", [1, 3])
def test_zo_replay_bit_equals_reference(kind, nblocks):
    n = nblocks * BR * 128
    x = jax.random.normal(jax.random.key(0), (n,), jnp.float32)
    coeffs = jnp.asarray(np.random.default_rng(2).normal(size=6), jnp.float32)
    out = ops.zo_replay(x, KEY2, coeffs, kind=kind, block_rows=BR)
    r = jax.jit(functools.partial(ref.zo_replay_ref, kind=kind))(
        x.reshape(-1, 128), KEY2, coeffs).reshape(-1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(r))


@pytest.mark.parametrize("kind", ["normal", "sign"])
@pytest.mark.parametrize("nblocks", [1, 3])
@pytest.mark.parametrize("acc", [None, "walk", "close"])
def test_zo_walk_bit_equals_reference(kind, nblocks, acc):
    """The walk alone, the walk carrying an accumulator u + e·v(nn[0]),
    and the accumulation alone (x=None, the closing call)."""
    n = nblocks * BR * 128
    x = jax.random.normal(jax.random.key(0), (n,), jnp.float32)
    # the accumulator in the kernel's [R, 128] layout
    u = jax.random.normal(jax.random.key(1), (n // 128, 128), jnp.float32)
    nn = jnp.asarray([3, 4], jnp.int32)
    ab = jnp.asarray([-0.25, 0.125], jnp.float32)
    e = jnp.float32(-0.375)
    xa = None if acc == "close" else x
    pair = None if acc is None else (u, e)
    out = ops.zo_walk(xa, KEY2, nn, ab, acc=pair, kind=kind, block_rows=BR)
    r = jax.jit(functools.partial(ref.zo_walk_ref, kind=kind))(
        None if xa is None else xa.reshape(-1, 128), KEY2, nn, ab, pair)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(r)):
        np.testing.assert_array_equal(np.asarray(a).reshape(-1),
                                      np.asarray(b).reshape(-1))


def test_zo_dirnorms_matches_reference_and_direct():
    from repro.kernels.zo_axpy import counter_direction_flat
    d, n_pad, b2 = 900, 2 * BR * 128, 5
    out = ops.zo_dirnorms(KEY2, d, b2=b2, n_pad=n_pad, block_rows=BR)
    r = ref.zo_dirnorms_ref(KEY2, d, b2, n_pad, block_rows=BR)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), rtol=1e-6)
    direct = jnp.stack([jnp.sum(counter_direction_flat(KEY2, n, d) ** 2)
                        for n in range(b2)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(direct), rtol=1e-5)


def test_walk_transition_reaches_fresh_perturbation():
    """x →(+μv0) →(−μv0,+μv1) ... lands where a fresh x+μv_n perturbation
    would, up to fp32 round-off — the MeZO transition introduces no drift
    beyond reassociation."""
    from repro.kernels.zo_axpy import counter_direction_flat
    n = BR * 128
    x = jax.random.normal(jax.random.key(3), (n,), jnp.float32)
    mu = 1e-3
    xp = x
    for k in range(6):
        a = 0.0 if k == 0 else -mu
        xp = ops.zo_walk(xp, KEY2, [max(k - 1, 0), k], [a, mu],
                         kind="normal", block_rows=BR)
    direct = x + mu * counter_direction_flat(KEY2, 5, n)
    np.testing.assert_allclose(np.asarray(xp), np.asarray(direct), atol=1e-6)


# -- 2. flat update == pytree counter-conv update ---------------------------


@pytest.mark.parametrize("kind", ["sphere", "gaussian", "rademacher"])
def test_flat_apply_matches_pytree_counter_conv(kind):
    params = {"a": jax.random.normal(jax.random.key(0), (300,)),
              "b": jax.random.normal(jax.random.key(1), (7, 11))}
    spec = flat_spec(params, block=BR * 128)
    coeffs = jnp.asarray(np.random.default_rng(3).normal(size=9), jnp.float32)
    rng = jax.random.key(77)

    flat = unflatten(estimator.flat_apply_coefficients(
        flatten(params, spec), spec, rng, coeffs, scale=-0.3, kind=kind,
        block_rows=BR), spec)
    tree = estimator.apply_coefficients(params, rng, coeffs, scale=-0.3,
                                        kind=kind, conv="counter")
    for a, b in zip(jax.tree.leaves(flat), jax.tree.leaves(tree)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_flat_rejects_coordinate():
    params = {"a": jnp.zeros((64,))}
    spec = flat_spec(params, block=BR * 128)
    with pytest.raises(ValueError):
        estimator.flat_apply_coefficients(
            flatten(params, spec), spec, jax.random.key(0),
            jnp.ones((2,)), kind="coordinate", block_rows=BR)


def test_seedcomm_wire_format_preserved_on_flat_path():
    """Same (key, coeffs) message; flat receiver reconstructs the flat
    client's delta exactly."""
    cfg = FedZOConfig(local_iters=4, lr=0.02, mu=1e-3, b2=5,
                      flat_params=True, flat_block_rows=BR)
    params = {"x": jnp.zeros((20,))}
    batches = {"target": jnp.ones((4, 20))}

    def loss(p, b):
        return 0.5 * jnp.sum((p["x"] - b["target"]) ** 2)

    rng = jax.random.key(42)
    delta, res = fedzo.client_delta(loss, params, batches, rng, cfg)
    msg = seedcomm.compress(rng, res.coeffs, cfg)
    assert seedcomm.wire_bytes(msg) < 120
    recon = seedcomm.reconstruct_delta(msg, params, cfg)
    for a, b in zip(jax.tree.leaves(delta), jax.tree.leaves(recon)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


# -- 3. trajectory equivalence on softmax regression ------------------------


@pytest.mark.slow
def test_flat_trajectory_matches_pytree_over_20_iterates():
    """Acceptance: the flat fused path's loss trajectory matches the pytree
    path (conv="counter", same directions) within fp32 tolerance over ≥ 20
    local iterates on the softmax-regression model."""
    x, y = make_classification(512, 784, 10, seed=0)
    batch = {"x": jnp.asarray(x[:256]), "y": jnp.asarray(y[:256])}
    params = softmax_init(None)

    base = FedZOConfig(b2=8, lr=1e-2, mu=1e-3, direction_conv="counter")
    cfg_tree = dataclasses.replace(base)
    cfg_flat = dataclasses.replace(base, flat_params=True)

    step_tree = jax.jit(fedzo.make_train_step(softmax_loss, cfg_tree))
    step_flat = jax.jit(fedzo.make_train_step(softmax_loss, cfg_flat))

    p_t, p_f = params, params
    losses_t, losses_f = [], []
    for t in range(22):
        k = jax.random.key(t)
        p_t, m_t = step_tree(p_t, batch, k)
        p_f, m_f = step_flat(p_f, batch, k)
        losses_t.append(float(m_t["loss"]))
        losses_f.append(float(m_f["loss"]))
    losses_t, losses_f = np.asarray(losses_t), np.asarray(losses_f)
    # both descend ...
    assert losses_t[-1] < losses_t[0]
    assert losses_f[-1] < losses_f[0]
    # ... along the same trajectory (fp32 round-off amplified by the 1/μ
    # difference quotient bounds the gap, not algorithmic divergence)
    np.testing.assert_allclose(losses_f, losses_t, rtol=2e-3, atol=2e-4)
    # final parameters agree too (looser: 22 compounded 1/μ amplifications)
    for a, b in zip(jax.tree.leaves(p_f), jax.tree.leaves(p_t)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


def test_pod_step_computes_dirnorms_once(monkeypatch):
    """Regression: the pod-step flat path once computed the inv-norms for
    the walk and again for the update, running the zo_dirnorms kernel
    twice per step. The step must compute the inv-norms exactly once."""
    calls = []
    orig = estimator.flat_inv_norms

    def counting(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(estimator, "flat_inv_norms", counting)
    cfg = FedZOConfig(b2=4, lr=0.05, mu=1e-3, flat_params=True,
                      flat_block_rows=BR)
    params = {"x": jnp.zeros((40,))}

    def loss_grouped(p, b):
        l = 0.5 * jnp.sum((p["x"] - b["target"]) ** 2)
        return jnp.stack([l, l * 1.01])

    class FakeMesh:
        shape = {"pod": 2}

    step = fedzo.make_pod_round_step(loss_grouped, cfg, FakeMesh())
    newp, _ = step(params, {"target": jnp.ones((40,))}, jax.random.key(0))
    assert jnp.all(jnp.isfinite(newp["x"]))
    assert len(calls) == 1, f"flat_inv_norms ran {len(calls)}× (want 1)"


def test_flat_local_phase_and_pod_step_run():
    """The flat path is wired through local_phase and make_pod_round_step."""
    cfg = FedZOConfig(local_iters=3, b2=4, lr=0.05, mu=1e-3,
                      flat_params=True, flat_block_rows=BR)
    params = {"x": jnp.zeros((40,))}
    batches = {"target": jnp.ones((3, 40))}

    def loss(p, b):
        return 0.5 * jnp.sum((p["x"] - b["target"]) ** 2)

    res = fedzo.local_phase(loss, params, batches, jax.random.key(0), cfg)
    assert res.coeffs.shape == (3, 4)
    assert float(res.losses[-1]) > 0

    class FakeMesh:
        shape = {"pod": 2}

    def loss_grouped(p, b):
        return jnp.stack([loss(p, b), loss(p, b) * 1.01])

    step = fedzo.make_pod_round_step(loss_grouped, cfg, FakeMesh())
    newp, metrics = step(params, {"target": jnp.ones((40,))},
                         jax.random.key(1))
    assert metrics["per_pod_loss"].shape == (2,)
    assert float(metrics["loss"]) > 0
    assert jnp.all(jnp.isfinite(newp["x"]))


@pytest.mark.parametrize("impl", ["threefry2x32", "rbg", "unsafe_rbg"])
def test_kernels_get_two_key_words_under_every_prng_impl(impl):
    """The kernels read a uint32 [2] key. Threefry keys are exactly that;
    the rbg family's four-word keys hand over their first two words, so
    the flat path stays well-defined under every cfg.prng_impl."""
    key = jax.random.key(5, impl=impl)
    words = ops.key_words(key)
    assert words.shape == (2,) and words.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(words),
                                  np.asarray(jax.random.key_data(key))[:2])
    np.testing.assert_array_equal(np.asarray(ops.key_words(words)),
                                  np.asarray(words))
    batched = jax.vmap(ops.key_words)(jax.random.split(key, 3))
    assert batched.shape == (3, 2)


# -- 4. the derived kernel geometry changes no number -----------------------

D_SOFTMAX = 7850            # the paper's softmax regression, 784·10 + 10


def test_derived_geometry_is_one_64_row_block_at_softmax_d():
    spec, br, _ = fedzo.local_plan(softmax_init(None),
                                   FedZOConfig(flat_params=True))
    assert (spec.d, br, spec.n_pad) == (D_SOFTMAX, 64, 8192)


@pytest.mark.parametrize("kernel", ["zo_walk", "zo_replay", "zo_dirnorms"])
def test_kernels_bit_equal_on_d_under_derived_geometry(kernel):
    """The counter convention keys each direction element on its global
    flat index, so v_n[:d] does not depend on the block shape: the derived
    64-row geometry gives the 512-row geometry's numbers, bit for bit."""
    d, b2 = D_SOFTMAX, 20
    x = jax.random.normal(jax.random.key(0), (d,), jnp.float32)

    def run(rows):
        if kernel == "zo_walk":
            return ops.zo_walk(x, KEY2, [3, 4], [-0.25, 0.125],
                               block_rows=rows)
        if kernel == "zo_replay":
            coeffs = jax.random.normal(jax.random.key(1), (b2,))
            return ops.zo_replay(x, KEY2, coeffs, block_rows=rows)
        return ops.zo_dirnorms(KEY2, d, b2=b2, block_rows=rows)

    np.testing.assert_array_equal(np.asarray(run(64))[:d],
                                  np.asarray(run(512))[:d])


def _softmax_run(flat_block_rows, **kw):
    from repro import sim
    from repro.data.synthetic import noniid_shards

    x, y = make_classification(160, 784, 10, seed=0)
    store = sim.build_store(noniid_shards(x, y, 4))
    cfg = FedZOConfig(n_devices=4, n_participating=2, local_iters=1, b1=8,
                      b2=2, lr=1e-3, mu=1e-3, seed=5, flat_params=True,
                      flat_block_rows=flat_block_rows, **kw)
    return sim.run_experiment(softmax_loss, softmax_init(None), store, cfg,
                              2)


@pytest.mark.parametrize("kw", [{}, {"aircomp": True, "snr_db": 0.0}],
                         ids=["mean", "aircomp"])
def test_run_experiment_same_under_derived_and_512_row_geometry(kw):
    """A flat run with flat_block_rows=0 (64 rows, n_pad 8,192) walks the
    512-row run's trajectory (n_pad 65,536): same losses, same params."""
    derived, wide = _softmax_run(0, **kw), _softmax_run(512, **kw)
    for name in derived.metrics:
        np.testing.assert_array_equal(np.asarray(derived.metrics[name]),
                                      np.asarray(wide.metrics[name]),
                                      err_msg=name)
    for a, b in zip(jax.tree.leaves(derived.params),
                    jax.tree.leaves(wide.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_seedcomm_round_trip_under_derived_geometry():
    """Perturb end and replay end both derive the geometry from d
    (flat_block_rows=0): the replayed delta is the client's."""
    cfg = FedZOConfig(local_iters=2, lr=1e-3, mu=1e-3, b2=3,
                      flat_params=True)
    params = softmax_init(None)
    x, y = make_classification(32, 784, 10, seed=1)
    batches = {"x": jnp.asarray(x).reshape(2, 16, 784),
               "y": jnp.asarray(y).reshape(2, 16)}
    rng = jax.random.key(42)
    delta, res = fedzo.client_delta(softmax_loss, params, batches, rng, cfg)
    recon = seedcomm.reconstruct_delta(seedcomm.compress(rng, res.coeffs,
                                                         cfg), params, cfg)
    for a, b in zip(jax.tree.leaves(delta), jax.tree.leaves(recon)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


# -- 5. the seed-replay update rides the perturbation walk ------------------


def _walk_then_replay(loss_fn, buf, spec, batch, rng, *, mu, b2, lr, kind,
                      central, block_rows, combine=None):
    """The reference composition the fused walk replaced: the perturbation
    walk alone, then one zo_replay pass of the (combined) coefficients
    (``flat_apply_coefficients``)."""
    ck = estimator.COUNTER_KINDS[kind]
    key2 = ops.key_words(rng)
    scale = estimator._scale_factor(spec.d, kind)
    base = estimator.query(loss_fn, buf, spec, batch)
    inv = estimator.flat_inv_norms(key2, spec, b2, kind,
                                   block_rows=block_rows)
    mu = jnp.float32(mu)

    def body(n, carry):
        xp, coeffs = carry
        prev = jnp.maximum(n - 1, 0)
        a = jnp.where(n == 0, 0.0, (mu if central else -mu) * inv[prev])
        xp = ops.zo_walk(xp, key2, jnp.stack([prev, n]),
                         jnp.stack([a, mu * inv[n]]), kind=ck,
                         block_rows=block_rows)
        lp = estimator.query(loss_fn, xp, spec, batch)
        if central:
            xp = ops.zo_walk(xp, key2, jnp.stack([n, n]),
                             jnp.stack([-2 * mu * inv[n], jnp.float32(0.0)]),
                             kind=ck, block_rows=block_rows)
            lm = estimator.query(loss_fn, xp, spec, batch)
            c = scale * (lp - lm).astype(jnp.float32) / (2 * mu)
        else:
            c = scale * (lp - base).astype(jnp.float32) / mu
        return xp, coeffs.at[n].set(c)

    _, coeffs = jax.lax.fori_loop(
        0, b2, body, (buf, jnp.zeros((b2,) + jnp.shape(base), jnp.float32)))
    c = coeffs if combine is None else combine(coeffs)
    new = estimator.flat_apply_coefficients(buf, spec, rng, c, scale=-lr,
                                            kind=kind, block_rows=block_rows)
    return new, coeffs, base


def _quadratic_loss(p, b):
    return 0.5 * jnp.mean((p["w"] - b["target"]) ** 2)


def _iterate_case(model):
    """(loss, params, batch): the softmax model at the paper's d, whose
    derived geometry is one 64-row block, or a d = 150,000 vector whose
    derived geometry is three grid steps."""
    if model == "softmax":
        x, y = make_classification(64, 784, 10, seed=0)
        return (softmax_loss, softmax_init(None),
                {"x": jnp.asarray(x[:16]), "y": jnp.asarray(y[:16])})
    d = 150_000
    return (_quadratic_loss,
            {"w": jax.random.normal(jax.random.key(2), (d,))},
            {"target": jax.random.normal(jax.random.key(3), (d,))})


@pytest.mark.parametrize("model", ["softmax", "three_steps"])
@pytest.mark.parametrize("kind", ["sphere", "gaussian"])
@pytest.mark.parametrize("central", [False, True],
                         ids=["one_sided", "central"])
def test_fused_iterate_bit_equals_walk_then_replay(model, kind, central):
    """The iterate whose walk carries the update lands on the walk +
    zo_replay composition bit for bit: new buffer, coefficients, loss."""
    loss, params, batch = _iterate_case(model)
    cfg = FedZOConfig(b2=5, lr=1e-2, mu=1e-3, flat_params=True,
                      estimator=kind, central=central)
    spec, br, _ = fedzo.local_plan(params, cfg)
    assert spec.n_pad // (br * 128) == (1 if model == "softmax" else 3)
    buf, rng = flatten(params, spec), jax.random.key(7)
    got = jax.jit(lambda b, k: fedzo.flat_local_iterate(
        loss, b, spec, batch, k, cfg, block_rows=br))(buf, rng)
    want = jax.jit(lambda b, k: _walk_then_replay(
        loss, b, spec, batch, k, mu=cfg.mu, b2=cfg.b2, lr=cfg.lr, kind=kind,
        central=central, block_rows=br))(buf, rng)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


class _FakeMesh:
    shape = {"pod": 2}


@pytest.mark.parametrize("central", [False, True],
                         ids=["one_sided", "central"])
def test_pod_step_bit_equals_walk_then_replay(central):
    """The pod step's update is the mean over pods of each direction's
    coefficient, known after its query: riding the walk, it gives the
    walk + replay composition's parameters bit for bit."""
    cfg = FedZOConfig(b2=4, lr=0.05, mu=1e-3, flat_params=True,
                      flat_block_rows=BR, central=central)
    params = {"x": jax.random.normal(jax.random.key(4), (900,))}
    batch = {"target": jnp.ones((900,))}

    def loss_grouped(p, b):
        l = 0.5 * jnp.sum((p["x"] - b["target"]) ** 2)
        return jnp.stack([l, l * 1.01])

    step = fedzo.make_pod_round_step(loss_grouped, cfg, _FakeMesh())
    rng = jax.random.key(5)
    newp, metrics = jax.jit(step)(params, batch, rng)
    spec = flat_spec(params, block=BR * 128)

    def ref_step(p, k):
        return _walk_then_replay(
            loss_grouped, flatten(p, spec), spec, batch, k, mu=cfg.mu,
            b2=cfg.b2, lr=cfg.lr, kind=cfg.estimator, central=central,
            block_rows=BR, combine=lambda c: jnp.mean(c, axis=1))

    want, _, base = jax.jit(ref_step)(params, rng)
    np.testing.assert_array_equal(np.asarray(newp["x"]),
                                  np.asarray(unflatten(want, spec)["x"]))
    np.testing.assert_array_equal(np.asarray(metrics["per_pod_loss"]),
                                  np.asarray(base))


ZO_KERNELS = ("zo_walk", "zo_replay", "zo_dirnorms")


def _generated(eqn, name):
    """Direction vectors one call of a ZO kernel generates: a walk step
    both v(nn[0]) and v(nn[1]), the closing call v(nn[0]) alone (its nn
    holds one index), zo_dirnorms and zo_replay all b2."""
    if name == "zo_walk":
        nn = next(v for v in eqn.invars if v.aval.dtype == jnp.int32)
        return nn.aval.shape[0]
    if name == "zo_dirnorms":
        return eqn.outvars[0].aval.shape[0]
    return eqn.invars[2].aval.shape[0]


def _kernel_calls(jaxpr, mult=1, out=None):
    """Calls of each ZO kernel a jaxpr makes when it runs, loops multiplied
    out by their trip counts, and the direction vectors they generate."""
    import collections
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        prim, name = eqn.primitive.name, eqn.params.get("name")
        if prim in ("jit", "pjit") and name in ZO_KERNELS:
            out[name] += mult
            out["directions"] += mult * _generated(eqn, name)
            continue
        assert prim not in ("while", "cond"), prim
        times = mult * (eqn.params["length"] if prim == "scan" else 1)
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                _kernel_calls(sub, times, out)
    return out


@pytest.mark.parametrize("central", [False, True],
                         ids=["one_sided", "central"])
def test_flat_local_phase_walks_and_never_replays(central):
    """The flat local phase makes H·(b2+1) zo_walk calls (H·(2·b2+1) in
    central mode), the closing call of each iterate included, and no
    zo_replay call; the directions its kernels generate are the manifest's
    ``directions_per_iterate`` per iterate."""
    h, b2 = 3, 4
    cfg = FedZOConfig(local_iters=h, b2=b2, lr=0.05, mu=1e-3,
                      flat_params=True, flat_block_rows=BR, central=central)
    params = {"x": jnp.zeros((40,))}
    batches = {"target": jnp.ones((h, 40))}

    def loss(p, b):
        return 0.5 * jnp.sum((p["x"] - b["target"]) ** 2)

    jaxpr = jax.make_jaxpr(lambda p, b, k: fedzo.local_phase(
        loss, p, b, k, cfg))(params, batches, jax.random.key(0))
    calls = _kernel_calls(jaxpr.jaxpr)
    assert calls["zo_walk"] == h * ((2 if central else 1) * b2 + 1)
    assert calls["zo_replay"] == 0
    assert calls["zo_dirnorms"] == h
    assert calls["directions"] == h * fedzo.directions_per_iterate(cfg)
    layout = fedzo.flat_layout(params, cfg)
    assert layout["directions_per_iterate"] == \
        fedzo.directions_per_iterate(cfg)


@pytest.mark.parametrize("kw,want", [
    ({}, 61),
    ({"estimator": "gaussian"}, 41),
    ({"central": True}, 101),
    ({"batch_directions": True, "direction_conv": "block"}, 20),
], ids=["sphere", "gaussian", "central", "wide"])
def test_flat_layout_reports_directions_per_iterate(kw, want):
    """At the paper's b2 = 20 a sphere client generates 20 directions for
    the norms, 40 in the walk and 1 in the closing call: 61, where the
    walk followed by a zo_replay pass generated 80."""
    cfg = FedZOConfig(b2=20, flat_params=True, **kw)
    layout = fedzo.flat_layout(softmax_init(None), cfg)
    assert layout["directions_per_iterate"] == want
