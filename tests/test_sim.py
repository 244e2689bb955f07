"""repro.sim: the fully-jitted federation engine (DESIGN.md §9).

Pins the subsystem's contracts:
- R in-jit rounds bit-match R host-driven ``FedServer.run_round`` calls
  under identical seeds (fedzo/fedavg, momentum, channel scheduling,
  AirComp, flat and wide local phases) — the two drivers share one round
  step and one key-chain protocol.
- ``ClientStore`` sampling: participation draws are uniform M-of-N without
  replacement; minibatch rows are uniform-with-replacement over each
  client's true size (the host ``sample_local_batches`` distribution),
  never touch padding, and are gathered straight from the store: bitwise
  the rows the whole-shard gather read, with no [M, cap] intermediate.
- The clients-axis shard_map round equals the single-device round on a
  1-device mesh (tight allclose — XLA fuses differently around the psum,
  so 1-ulp wiggle is expected; the math is identical).
- The batched-direction (wide) phase walks the loop estimator's exact
  directions under direction_conv="tree".
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sim
from repro.configs.base import FedZOConfig
from repro.core import aircomp, fedavg, fedzo, seedcomm
from repro.data.synthetic import (make_classification, noniid_shards,
                                  random_partition, sample_local_batches)
from repro.fed.server import FedServer, run_seed_compressed_round
from repro.models.simple import softmax_accuracy, softmax_init, softmax_loss

BR = 4  # small kernel blocks for CPU interpret mode


def _setup(n=640, n_clients=8, n_features=24, n_classes=4, seed=0):
    x, y = make_classification(n, n_features, n_classes, seed=seed)
    clients = noniid_shards(x, y, n_clients)
    return clients, sim.build_store(clients)


def _cfg(**kw):
    base = dict(n_devices=8, n_participating=4, local_iters=2, lr=1e-2,
                mu=1e-3, b1=8, b2=4, seed=3)
    base.update(kw)
    return FedZOConfig(**base)


def _assert_trees_bitequal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# engine ≡ host-driven rounds


@pytest.mark.parametrize("name,kw,algo", [
    ("plain", {}, "fedzo"),
    ("momentum", {"server_momentum": 0.9}, "fedzo"),
    ("aircomp_sched", {"aircomp": True, "snr_db": 10.0,
                       "channel_schedule": True}, "fedzo"),
    ("flat", {"flat_params": True, "flat_block_rows": BR}, "fedzo"),
    ("wide_block", {"batch_directions": True, "direction_conv": "block",
                    "prng_impl": "unsafe_rbg"}, "fedzo"),
    ("fedavg_sched", {"channel_schedule": True}, "fedavg"),
])
def test_engine_bitmatches_host_rounds(name, kw, algo):
    """R rounds inside one lax.scan == R FedServer.run_round calls, bit for
    bit — both drivers run the identical sim round step from the identical
    key chain."""
    clients, store = _setup()
    cfg = _cfg(**kw)
    p0 = softmax_init(None, 24, 4)
    host = FedServer(softmax_loss, p0, clients, cfg, algo=algo, store=store)
    for t in range(3):
        host.run_round(t)
    scanned = FedServer(softmax_loss, p0, clients, cfg, algo=algo,
                        store=store)
    scanned.run(3)
    _assert_trees_bitequal(host.params, scanned.params)
    assert len(scanned.history) == 3
    for hm, sm in zip(host.history, scanned.history):
        assert hm["mean_local_loss"] == sm["mean_local_loss"], (hm, sm)


def test_run_experiment_smoke_and_eval_cadence():
    """Fast-CI smoke for the scan path: a ≤5-round reduced experiment runs
    in one jit, descends, and evals in-scan every k rounds."""
    clients, store = _setup()
    cfg = sim.fast_sim_config(_cfg())
    test = {"x": jnp.asarray(np.concatenate([c["x"] for c in clients])),
            "y": jnp.asarray(np.concatenate([c["y"] for c in clients]))}
    res = sim.run_experiment(
        softmax_loss, softmax_init(None, 24, 4), store, cfg, 5,
        eval_fn=lambda p: {"acc": softmax_accuracy(p, test)}, eval_every=2)
    hist = sim.history(res)
    assert [h["round"] for h in hist] == [0, 1, 2, 3, 4]
    assert all(np.isfinite(h["mean_local_loss"]) for h in hist)
    assert hist[-1]["mean_local_loss"] < hist[0]["mean_local_loss"]
    # eval lands exactly on rounds 0, 2, 4
    assert [h["round"] for h in hist if "acc" in h] == [0, 2, 4]
    assert all(0.0 <= h["acc"] <= 1.0 for h in hist if "acc" in h)


def test_metrics_ring_buffer_keeps_last_rounds():
    clients, store = _setup()
    cfg = sim.fast_sim_config(_cfg())
    res = sim.run_experiment(softmax_loss, softmax_init(None, 24, 4), store,
                             cfg, 7, ring_size=3)
    hist = sim.history(res)
    assert [h["round"] for h in hist] == [4, 5, 6]
    full = sim.run_experiment(softmax_loss, softmax_init(None, 24, 4), store,
                              cfg, 7)
    tail = sim.history(full)[-3:]
    for a, b in zip(hist, tail):
        assert a == b, (a, b)


def test_engine_momentum_changes_trajectory():
    """cfg.server_momentum threads through the scan carry: a momentum run
    must diverge from a momentum-free run of the same seed."""
    clients, store = _setup()
    p0 = softmax_init(None, 24, 4)

    def final(mom):
        res = sim.run_experiment(softmax_loss, p0, store,
                                 _cfg(server_momentum=mom), 4, donate=False)
        return np.asarray(res.params["w"])

    assert np.abs(final(0.0) - final(0.9)).max() > 1e-8


def test_weighted_unscheduled_round_reports_m_effective():
    """``weight_by_size`` without channel scheduling runs the masked-mean
    branch with mask=None — it must STILL report ``m_effective`` (= M,
    nothing masked) so history/CSV columns stay consistent across the
    scenarios of one sweep. Regression: pre-fix the column silently
    vanished on exactly this path (fedzo pytree + flat, and fedavg)."""
    clients, store = _setup()
    p0 = softmax_init(None, 24, 4)
    cfg = _cfg()
    batches = sim.sample_batches(store, jnp.arange(4), jax.random.key(7),
                                 cfg.local_iters, cfg.b1)
    rngs = jax.random.split(jax.random.key(1), 4)
    w = aircomp.size_weights(store.sizes[:4])
    _, m_tree = fedzo.round_simulated(softmax_loss, p0, batches, rngs, cfg,
                                      weights=w)
    assert float(m_tree["m_effective"]) == 4.0
    cfgf = _cfg(flat_params=True, flat_block_rows=BR)
    _, m_flat = fedzo.round_simulated(softmax_loss, p0, batches, rngs, cfgf,
                                      weights=w)
    assert float(m_flat["m_effective"]) == 4.0
    _, m_avg = fedavg.round_simulated(softmax_loss, p0, batches, cfg,
                                      weights=w)
    assert float(m_avg["m_effective"]) == 4.0


# ---------------------------------------------------------------------------
# ClientStore sampling


def test_store_sampling_never_touches_padding():
    """Uneven clients → padded store; every gathered row must decode to a
    real (client, row<size) pair."""
    rng = np.random.default_rng(0)
    n, n_clients = 400, 5
    x = np.zeros((n, 1), np.float32)
    y = (np.arange(n) % 3).astype(np.int32)
    clients = random_partition(x, y, n_clients, seed=1, uneven=True)
    for i, c in enumerate(clients):       # encode (client, row) in the value
        c["x"] = np.asarray([[i * 10_000 + j] for j in range(len(c["y"]))],
                            np.float32)
    store = sim.build_store(clients)
    sizes = np.asarray(store.sizes)
    assert len(set(sizes.tolist())) > 1   # the split really is uneven

    idx = jnp.asarray([4, 0, 2])
    batches = jax.jit(lambda k: sim.sample_batches(store, idx, k, 3, 16))(
        jax.random.key(7))
    vals = np.asarray(batches["x"]).reshape(3, -1)
    for m, i in enumerate([4, 0, 2]):
        cl = (vals[m] // 10_000).astype(int)
        row = (vals[m] % 10_000).astype(int)
        assert (cl == i).all()
        assert (row < sizes[i]).all()


def test_store_minibatch_distribution_matches_host():
    """In-jit row sampling is uniform with replacement over the client's
    true size — the host sample_local_batches distribution."""
    clients, store = _setup(n=240, n_clients=4)
    n_i = int(store.sizes[1])
    draws = 400
    keys = jax.random.split(jax.random.key(0), draws)
    rows = jax.vmap(lambda k: jax.random.randint(k, (3, 8), 0,
                                                 store.sizes[1]))(keys)
    dev = np.bincount(np.asarray(rows).ravel(), minlength=n_i)
    host_rng = np.random.default_rng(0)
    host = np.zeros(n_i, np.int64)
    for _ in range(draws):
        b = sample_local_batches(clients[1], host_rng, 3, 8)
        # recover indices by matching row identity is overkill — the host
        # sampler IS rng.integers(0, n, (h, b1)); draw the same count
        host += np.bincount(host_rng.integers(0, n_i, (3, 8)).ravel(),
                            minlength=n_i)
        del b
    for counts in (dev, host):
        freq = counts / counts.sum()
        # all rows hit, no row wildly over-represented (uniform ±5 σ)
        p = 1.0 / n_i
        sigma = np.sqrt(p * (1 - p) / counts.sum())
        assert np.abs(freq - p).max() < 5 * sigma, np.abs(freq - p).max()


def test_participation_draw_uniform_without_replacement():
    clients, store = _setup()
    n, m = 8, 3
    draws = 600
    keys = jax.random.split(jax.random.key(1), draws)
    idx = np.asarray(jax.vmap(
        lambda k: sim.sample_participants(k, n, m))(keys))
    assert idx.shape == (draws, m)
    for row in idx[:50]:
        assert len(set(row.tolist())) == m      # without replacement
    freq = np.bincount(idx.ravel(), minlength=n) / (draws * m)
    assert np.abs(freq - 1 / n).max() < 0.05    # uniform marginals


def test_build_store_validates_ragged_clients():
    with pytest.raises(ValueError, match="mismatched row counts"):
        sim.build_store([{"x": np.zeros((4, 2)), "y": np.zeros((3,))}])


def _two_step_sample_batches(store, idx, key, h, b1):
    """The whole-shard form ``sample_batches`` replaced, kept as its oracle:
    gather the cohort's [M, cap, ...] shards, then draw and gather rows
    per client."""
    keys = jax.random.split(key, idx.shape[0])

    def one(d, n, k):
        rows = jax.random.randint(k, (h, b1), 0, n)
        return jax.tree.map(lambda l: l[rows], d)

    cohort = jax.tree.map(lambda l: l[idx], store.data)
    return jax.vmap(one)(cohort, store.sizes[idx], keys)


def _sampling_store(case):
    """Small stores of every leaf layout the engine samples from: cap 17,
    so no [M, H, b1] or [M·H·b1] shape can be mistaken for [M, cap]."""
    rng = np.random.default_rng(0)
    sizes = [17, 9, 13, 4, 17, 11] if case == "uneven" else [17] * 6

    def client(n):
        if case == "images":
            return {"x": rng.random((n, 28, 28, 1), np.float32),
                    "y": rng.integers(0, 10, n).astype(np.int32)}
        if case == "labels_pytree":
            return {"x": {"a": rng.normal(size=(n, 5)).astype(np.float32),
                          "b": rng.normal(size=(n, 2, 3)).astype(np.float32)},
                    "y": rng.integers(0, 10, n).astype(np.int32)}
        return {"x": rng.normal(size=(n, 7)).astype(np.float32)}

    return sim.build_store([client(n) for n in sizes])


SAMPLING_CASES = ["uneven", "features_2d", "images", "labels_pytree"]


@pytest.mark.parametrize("case", SAMPLING_CASES)
def test_sample_batches_bitequal_to_whole_shard_gather(case):
    """Gathering the round's (client, row) pairs straight from the store
    reads the very rows, bit for bit, that gathering the cohort's whole
    shards and then its rows did: the draws are unchanged."""
    store = _sampling_store(case)
    idx = jnp.asarray([3, 0, 5], jnp.int32)
    key = jax.random.key(11)
    want = jax.jit(lambda s, i, k: _two_step_sample_batches(s, i, k, 4, 5))(
        store, idx, key)
    got = jax.jit(lambda s, i, k: sim.sample_batches(s, i, k, 4, 5))(
        store, idx, key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for lw, lg in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert lg.shape == lw.shape and lg.dtype == lw.dtype
    _assert_trees_bitequal(want, got)


def _intermediate_shapes(jaxpr):
    """Every shape an equation of ``jaxpr`` (or of a jaxpr nested in one)
    produces."""
    out = []
    for eqn in jaxpr.eqns:
        out += [tuple(v.aval.shape) for v in eqn.outvars
                if hasattr(v.aval, "shape")]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _intermediate_shapes(sub)
    return out


@pytest.mark.parametrize("case", SAMPLING_CASES)
def test_sample_batches_builds_no_whole_shard_cohort(case):
    """No op of the jitted sampler builds an [M, cap, ...] intermediate: the
    round reads only the M·H·b1 rows it uses, never the cohort's whole
    shards. The detector is checked against the whole-shard form."""
    store = _sampling_store(case)
    idx = jnp.asarray([3, 0, 5], jnp.int32)
    m, cap = idx.shape[0], store.capacity

    def cohort_shaped(fn):
        jaxpr = jax.make_jaxpr(jax.jit(
            lambda s, i, k: fn(s, i, k, 4, 5)))(store, idx, jax.random.key(0))
        return [s for s in _intermediate_shapes(jaxpr.jaxpr)
                if s[:2] == (m, cap)]

    assert cohort_shaped(_two_step_sample_batches)
    assert cohort_shaped(sim.sample_batches) == []


# ---------------------------------------------------------------------------
# sharded round


@pytest.mark.parametrize("kw", [
    {"batch_directions": True, "direction_conv": "block"},
    {"batch_directions": True, "direction_conv": "block", "aircomp": True,
     "snr_db": 10.0, "channel_schedule": True},
    {"flat_params": True, "flat_block_rows": BR, "aircomp": True,
     "snr_db": 10.0},
    {"batch_directions": True, "direction_conv": "block",
     "weight_by_size": True},
    {"flat_params": True, "flat_block_rows": BR, "aircomp": True,
     "snr_db": 10.0, "channel_schedule": True,
     "channel_model": sim.ChannelModel.from_doppler(0.02)},
])
def test_sharded_round_matches_single_device(kw):
    """shard_map over a 1-device 'clients' mesh == the unsharded round.
    Tight allclose, not bitwise: the psum boundary changes XLA's fusion
    choices by ~1 ulp even though the reduction math is identical. Size
    weights and a realized ``ChannelModel`` channel reach both rounds as
    the engine hands them over, and both report the same metrics."""
    clients, store = _setup()
    cfg = _cfg(**kw)
    p0 = softmax_init(None, 24, 4)
    mesh = sim.make_clients_mesh()
    rf = sim.make_sharded_round(softmax_loss, cfg, mesh)
    batches = sim.sample_batches(store, jnp.arange(4), jax.random.key(7),
                                 cfg.local_iters, cfg.b1)
    rngs = jax.random.split(jax.random.key(1), 4)
    kc = jax.random.key(2)
    wkw = {}
    if cfg.weight_by_size:
        wkw["weights"] = aircomp.size_weights(jnp.asarray([50, 80, 110,
                                                           140]))
    if cfg.channel_model is not None:
        cm = cfg.channel_model
        _, wkw["channel"] = cm.step(
            jax.random.key(6), cm.init_state(8, jax.random.key(5)),
            jnp.arange(4), h_min=cfg.h_min, schedule=cfg.channel_schedule)
    ref = jax.jit(lambda p, b, r, c: fedzo.round_simulated(
        softmax_loss, p, b, r, cfg, channel_rng=c, **wkw))(p0, batches,
                                                           rngs, kc)
    got = jax.jit(lambda p, b, r, c: rf(
        softmax_loss, p, b, r, cfg, channel_rng=c, **wkw))(p0, batches,
                                                           rngs, kc)
    for la, lb in zip(jax.tree.leaves(ref[0]), jax.tree.leaves(got[0])):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=1e-7, rtol=1e-6)
    assert set(ref[1]) == set(got[1])
    for k in ref[1]:
        np.testing.assert_allclose(float(ref[1][k]), float(got[1][k]),
                                   rtol=1e-6)


def test_sharded_round_rejects_pytree_cfg():
    mesh = sim.make_clients_mesh()
    with pytest.raises(ValueError, match="flat"):
        sim.make_sharded_round(softmax_loss, _cfg(), mesh)


def test_sharded_round_inside_engine():
    """round_fn plugs into the scan engine: a sharded experiment runs as
    one jit and matches the unsharded engine on a 1-device mesh."""
    clients, store = _setup()
    cfg = _cfg(batch_directions=True, direction_conv="block")
    p0 = softmax_init(None, 24, 4)
    mesh = sim.make_clients_mesh()
    rf = sim.make_sharded_round(softmax_loss, cfg, mesh)
    res_s = sim.run_experiment(softmax_loss, p0, store, cfg, 3, round_fn=rf,
                               donate=False)
    res_u = sim.run_experiment(softmax_loss, p0, store, cfg, 3,
                               donate=False)
    np.testing.assert_allclose(np.asarray(res_s.params["w"]),
                               np.asarray(res_u.params["w"]),
                               atol=1e-6, rtol=1e-5)


# ---------------------------------------------------------------------------
# wide (batched-direction) estimator


def test_wide_phase_walks_loop_directions():
    """direction_conv="tree" makes the wide phase regenerate the loop
    estimator's exact direction bits, so one round agrees to the fp32
    reassociation of the batched forwards (amplified by d/μ)."""
    cfg_loop = _cfg(b2=6)
    cfg_wide = dataclasses.replace(cfg_loop, batch_directions=True)
    params = {"x": jnp.zeros((300,))}

    def quad(p, batch):
        return 0.5 * jnp.sum((p["x"] - batch["t"]) ** 2)

    batches = {"t": jnp.ones((4, 2, 300))}
    rngs = jax.random.split(jax.random.key(0), 4)
    p_l, m_l = fedzo.round_simulated(quad, params, batches, rngs, cfg_loop)
    p_w, m_w = fedzo.round_simulated(quad, params, batches, rngs, cfg_wide)
    np.testing.assert_allclose(float(m_w["mean_local_loss"]),
                               float(m_l["mean_local_loss"]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(p_w["x"]), np.asarray(p_l["x"]),
                               atol=1e-2, rtol=1e-3)


def test_wide_block_conv_descends():
    """The block convention (one PRNG call per iterate) is statistically
    sound: same quadratic descent as the loop estimator."""
    cfg = _cfg(batch_directions=True, direction_conv="block", b2=16,
               local_iters=3, lr=0.05)
    params = {"x": jnp.zeros((64,))}

    def quad(p, batch):
        return 0.5 * jnp.sum((p["x"] - batch["t"]) ** 2)

    res = fedzo.local_phase(quad, params, {"t": jnp.ones((3, 64))},
                            jax.random.key(0), cfg)
    assert float(res.losses[-1]) < float(res.losses[0])
    assert res.coeffs.shape == (3, 16)


# ---------------------------------------------------------------------------
# satellites: stacked seed compression, FedServer validation


def test_compress_stacked_matches_message_list():
    cfg = _cfg(local_iters=3, b2=5)
    coeffs = jnp.arange(2 * 3 * 5, dtype=jnp.float32).reshape(2, 3, 5)
    rngs = jax.random.split(jax.random.key(4), 2)
    stacked = seedcomm.compress_stacked(rngs, coeffs, cfg)
    singles = [seedcomm.compress(rngs[i], coeffs[i], cfg) for i in range(2)]
    np.testing.assert_array_equal(
        np.asarray(stacked["key"]),
        np.stack([np.asarray(m["key"]) for m in singles]))
    assert seedcomm.wire_bytes(stacked) == sum(
        seedcomm.wire_bytes(m) for m in singles)
    params = {"x": jnp.zeros((40,))}
    _assert_trees_bitequal(seedcomm.aggregate(stacked, params, cfg),
                           seedcomm.aggregate(singles, params, cfg))


def test_seed_compressed_round_has_no_python_message_loop():
    """Behavior pin for the batched compress: same results as before on a
    2-client round, wire bytes still dtype-exact."""
    cfg = _cfg(local_iters=2, b2=3)
    params = {"x": jnp.zeros((24,))}

    def quad(p, batch):
        return 0.5 * jnp.sum((p["x"] - batch["t"]) ** 2)

    batches = [{"t": jnp.ones((2, 24))} for _ in range(2)]
    rngs = list(jax.random.split(jax.random.key(0), 2))
    newp, wire, dense = run_seed_compressed_round(quad, params, batches,
                                                  rngs, cfg)
    assert wire == 2 * (8 + 2 * 3 * 4 + 4)
    assert dense == 2 * 24 * 4
    assert float(jnp.linalg.norm(newp["x"] - params["x"])) > 0


def test_seedcomm_rejects_engine_only_streams():
    """The engine's fast execution plan (block directions, rbg keys) is not
    wire-compatible with seed compression — both incompatibilities must
    fail loudly at the boundary, not replay uncorrelated directions or
    shape-error deep inside the scan."""
    cfg = sim.fast_sim_config(_cfg(local_iters=2, b2=3))
    coeffs = jnp.zeros((2, 3), jnp.float32)
    with pytest.raises(ValueError, match="8-byte threefry key"):
        seedcomm.compress(jax.random.key(0, impl=cfg.prng_impl), coeffs, cfg)
    msg = seedcomm.compress(jax.random.key(0), coeffs,
                            dataclasses.replace(cfg, prng_impl="threefry2x32"))
    with pytest.raises(ValueError, match="not seed-replayable"):
        seedcomm.reconstruct_delta(msg, {"x": jnp.zeros((8,))}, cfg)
    with pytest.raises(ValueError, match="not seed-replayable"):
        seedcomm.aggregate([msg], {"x": jnp.zeros((8,))}, cfg)


def test_sharded_round_rejects_foreign_cfg():
    clients, store = _setup()
    cfg = _cfg(batch_directions=True, direction_conv="block")
    rf = sim.make_sharded_round(softmax_loss, cfg, sim.make_clients_mesh())
    batches = sim.sample_batches(store, jnp.arange(4), jax.random.key(7),
                                 cfg.local_iters, cfg.b1)
    rngs = jax.random.split(jax.random.key(1), 4)
    with pytest.raises(ValueError, match="binds loss_fn and cfg"):
        rf(softmax_loss, softmax_init(None, 24, 4), batches, rngs,
           dataclasses.replace(cfg, snr_db=-3.0))


def test_fedserver_validates_federation_size():
    clients, _ = _setup(n_clients=8)
    with pytest.raises(ValueError, match="n_devices=12 but 8"):
        FedServer(softmax_loss, softmax_init(None, 24, 4), clients,
                  _cfg(n_devices=12))
    with pytest.raises(ValueError, match="n_participating=9 exceeds"):
        FedServer(softmax_loss, softmax_init(None, 24, 4), clients,
                  _cfg(n_participating=9))
    with pytest.raises(ValueError, match="client datasets"):
        FedServer(softmax_loss, softmax_init(None, 24, 4), None, _cfg())


# ---------------------------------------------------------------------------
# sweeps


def test_sweep_groups_static_shapes_and_vmaps_dynamics(tmp_path):
    """A {H} × {snr_db, seed} grid: two compiles (one per H), the snr/seed
    axis vmapped; per-scenario curves come back finite and the CSV lands."""
    clients, store = _setup()
    base = sim.fast_sim_config(_cfg(aircomp=True))
    scen = sim.scenario_grid(local_iters=(1, 2), snr_db=(0.0, 10.0),
                             seed=(0, 1))
    out = tmp_path / "sweep.csv"
    recs = sim.run_sweep(softmax_loss, softmax_init(None, 24, 4), store,
                         base, scen, 3, out_csv=str(out))
    assert len(recs) == 8
    for r in recs:
        assert r["metrics"]["mean_local_loss"].shape == (3,)
        assert np.isfinite(r["metrics"]["mean_local_loss"]).all()
    text = out.read_text().splitlines()
    assert text[0] == "scenario,round,metric,value"
    # every scenario × round × metric row present
    n_metrics = len(recs[0]["metrics"])
    assert len(text) == 1 + 8 * 3 * n_metrics


def test_sweep_split_normalizes_list_valued_statics():
    """A list-valued static override (e.g. a shape) must still produce a
    hashable static signature — the signature is the compile-group dict
    key. Regression: pre-fix this raised an opaque ``TypeError:
    unhashable type: 'list'`` from the group dict."""
    from repro.sim.sweep import _split
    static, dyn = _split({"local_iters": 2, "snr_db": 0.0,
                          "image_shape": [8, 8, 1]})
    assert dyn == {"snr_db": 0.0}
    groups = {}
    groups.setdefault(static, []).append("scenario")  # pre-fix: TypeError
    assert ("image_shape", (8, 8, 1)) in static
    assert ("local_iters", 2) in static
    # non-sequence unhashables get a targeted error naming the field
    with pytest.raises(TypeError, match="image_shape"):
        _split({"image_shape": {"h": 8}})


def test_sweep_scenarios_differ_by_snr():
    """The vmapped config axis really reaches the channel: high-noise and
    low-noise scenarios report different aircomp noise."""
    clients, store = _setup()
    base = sim.fast_sim_config(_cfg(aircomp=True))
    recs = sim.run_sweep(softmax_loss, softmax_init(None, 24, 4), store,
                         base, [{"snr_db": -10.0}, {"snr_db": 20.0}], 2)
    lo = recs[0]["metrics"]["aircomp_noise_std"].mean()
    hi = recs[1]["metrics"]["aircomp_noise_std"].mean()
    assert lo > hi > 0
