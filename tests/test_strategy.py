"""The composable ZO algorithm layer (core/strategy.py, DESIGN.md §13).

Pins the strategy-core contracts:

- registry dispatch: ``strategy.get`` fails loudly listing the registered
  names, the engine entry points accept a name / an instance /
  ``cfg.strategy``, and the legacy ``algo=`` kwarg warns deprecation;
- reductions: ZO-FedProx with ``prox_mu=0`` and ZO-FedDyn with
  ``dyn_alpha=0`` are bit-identical to plain FedZO (the hooks are
  statically elided), while positive coefficients change the trajectory;
- stateful strategies ride the durable carry: chunked ≡ single-shot
  bitwise and SIGKILL-and-resume restores every client's control/dual
  state bit-identically;
- the surrogate estimator (direction_conv="surrogate") pays ≤ half the
  fresh ZO queries per iterate and still reaches matched final loss /
  accuracy on the softmax golden task;
- sweeps carry the strategy as a static axis and the CSV rows stay
  distinguishable; ``ExperimentResult.history()`` rows name the strategy;
- the baselines (zo_sgd / DZOPA / ZONE-S) route through the shared
  estimator direction conventions — counter-convention trajectories are
  pinned and differ from the tree convention.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sim
from repro.configs.base import FedZOConfig
from repro.core import baselines, estimator, fedzo
from repro.core import strategy as strategy_mod
from repro.data.synthetic import make_classification, noniid_shards
from repro.fed.server import FedServer
from repro.models.simple import softmax_init, softmax_loss
from repro.sim import sweep


def _setup(n=640, n_clients=8, seed=0):
    x, y = make_classification(n, 24, 4, seed=seed)
    clients = noniid_shards(x, y, n_clients)
    return clients, sim.build_store(clients)


def _cfg(**kw):
    base = dict(n_devices=8, n_participating=4, local_iters=2, lr=1e-2,
                mu=1e-3, b1=8, b2=4, seed=3)
    base.update(kw)
    return FedZOConfig(**base)


def _assert_trees_bitequal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_results_bitequal(a, b):
    _assert_trees_bitequal(a.params, b.params)
    np.testing.assert_array_equal(jax.random.key_data(a.key),
                                  jax.random.key_data(b.key))
    assert sorted(a.metrics) == sorted(b.metrics)
    for k in a.metrics:
        np.testing.assert_array_equal(np.asarray(a.metrics[k]),
                                      np.asarray(b.metrics[k]), err_msg=k)


# ---------------------------------------------------------------------------
# registry + resolution


def test_registry_has_the_algorithm_family():
    for name in ("fedzo", "fedavg", "fedprox", "feddyn", "scaffold"):
        assert strategy_mod.get(name).name == name


def test_unknown_strategy_lists_registered_names():
    with pytest.raises(ValueError, match="unknown strategy 'sgd'"):
        strategy_mod.get("sgd")
    with pytest.raises(ValueError, match="fedprox"):
        strategy_mod.get("sgd")


def test_unknown_strategy_fails_at_round_step_build():
    clients, store = _setup()
    with pytest.raises(ValueError, match="registered strategies"):
        sim.make_round_step(softmax_loss, _cfg(strategy="fedsgd"))


def test_deprecated_algo_kwarg_warns():
    clients, store = _setup()
    p0 = softmax_init(None, 24, 4)
    with pytest.warns(DeprecationWarning, match="algo= string kwarg is "
                                                "deprecated"):
        sim.make_round_step(softmax_loss, _cfg(), algo="fedavg")
    with pytest.warns(DeprecationWarning):
        sim.run_experiment(softmax_loss, p0, store, _cfg(), 1, algo="fedzo",
                           donate=False)


def test_explicit_strategy_beats_cfg_and_accepts_instances():
    clients, store = _setup()
    p0 = softmax_init(None, 24, 4)
    cfg = _cfg(strategy="fedavg")
    res = sim.run_experiment(softmax_loss, p0, store, cfg, 2,
                             strategy="fedzo", donate=False)
    assert res.strategy == "fedzo"
    res2 = sim.run_experiment(softmax_loss, p0, store, cfg, 2,
                              strategy=strategy_mod.get("fedzo"),
                              donate=False)
    _assert_results_bitequal(res, res2)


# ---------------------------------------------------------------------------
# reductions: μ=0 / α=0 are bit-exact FedZO; positive values move


def test_fedprox_mu_zero_is_bitexact_fedzo():
    clients, store = _setup()
    p0 = softmax_init(None, 24, 4)
    ref = sim.run_experiment(softmax_loss, p0, store, _cfg(), 4, donate=False)
    got = sim.run_experiment(softmax_loss, p0, store,
                             _cfg(strategy="fedprox"), 4, donate=False)
    _assert_results_bitequal(ref, got)


def test_feddyn_alpha_zero_is_bitexact_fedzo():
    clients, store = _setup()
    p0 = softmax_init(None, 24, 4)
    ref = sim.run_experiment(softmax_loss, p0, store, _cfg(), 4, donate=False)
    got = sim.run_experiment(softmax_loss, p0, store,
                             _cfg(strategy="feddyn"), 4, donate=False)
    _assert_results_bitequal(ref, got)
    assert got.strategy_state is None  # α=0 carries no duals


@pytest.mark.parametrize("name,kw", [
    ("fedprox", {"prox_mu": 0.5}),
    ("feddyn", {"dyn_alpha": 0.5}),
    ("scaffold", {}),
])
def test_positive_coefficients_change_the_trajectory(name, kw):
    clients, store = _setup()
    p0 = softmax_init(None, 24, 4)
    ref = sim.run_experiment(softmax_loss, p0, store, _cfg(), 3, donate=False)
    got = sim.run_experiment(softmax_loss, p0, store,
                             _cfg(strategy=name, **kw), 3, donate=False)
    assert any(
        (np.asarray(a) != np.asarray(b)).any()
        for a, b in zip(jax.tree.leaves(ref.params),
                        jax.tree.leaves(got.params)))
    for leaf in jax.tree.leaves(got.params):
        assert np.all(np.isfinite(np.asarray(leaf)))


def test_fedprox_composes_with_server_momentum():
    clients, store = _setup()
    p0 = softmax_init(None, 24, 4)
    cfg = _cfg(strategy="fedprox", prox_mu=0.1, server_momentum=0.9)
    res = sim.run_experiment(softmax_loss, p0, store, cfg, 3, donate=False)
    assert res.momentum is not None
    assert np.isfinite(np.asarray(res.metrics["mean_local_loss"])).all()


@pytest.mark.parametrize("name", ["feddyn", "scaffold"])
def test_stateful_strategies_reject_server_momentum(name):
    clients, store = _setup()
    p0 = softmax_init(None, 24, 4)
    cfg = _cfg(strategy=name, dyn_alpha=0.1, server_momentum=0.9)
    with pytest.raises(ValueError, match="does not compose"):
        sim.run_experiment(softmax_loss, p0, store, cfg, 2, donate=False)
    with pytest.raises(ValueError, match="does not compose"):
        FedServer(softmax_loss, p0, clients, cfg, store=store)


# ---------------------------------------------------------------------------
# hook strategies vs custom round_fns / host-only servers


def test_hook_strategies_reject_custom_round_fn():
    def fake_round(*a, **k):  # pragma: no cover - must not be called
        raise AssertionError

    with pytest.raises(ValueError, match="custom round_fn"):
        sim.make_round_step(softmax_loss,
                            _cfg(strategy="fedprox", prox_mu=0.1),
                            round_fn=fake_round)


def test_hook_strategies_need_a_store_on_the_server():
    clients, _ = _setup()
    p0 = softmax_init(None, 24, 4)
    with pytest.raises(ValueError, match="store=ClientStore"):
        FedServer(softmax_loss, p0, clients,
                  _cfg(strategy="scaffold"))


def test_surrogate_requires_wide_phase():
    clients, store = _setup()
    p0 = softmax_init(None, 24, 4)
    cfg = _cfg(direction_conv="surrogate")
    with pytest.raises(ValueError, match="batch_directions"):
        sim.run_experiment(softmax_loss, p0, store, cfg, 2, donate=False)


# ---------------------------------------------------------------------------
# durability: strategy state survives chunking and SIGKILL-and-resume


@pytest.mark.parametrize("name,kw", [
    ("scaffold", {}),
    ("feddyn", {"dyn_alpha": 0.1}),
])
def test_chunked_matches_single_shot_with_state(name, kw, tmp_path):
    clients, store = _setup()
    cfg = _cfg(strategy=name, **kw)
    p0 = softmax_init(None, 24, 4)
    single = sim.run_experiment(softmax_loss, p0, store, cfg, 6,
                                donate=False)
    chunked = sim.run_experiment(
        softmax_loss, p0, store, cfg, 6, donate=False, checkpoint_every=4,
        checkpoint_dir=str(tmp_path / name))
    _assert_results_bitequal(single, chunked)
    _assert_trees_bitequal(single.strategy_state, chunked.strategy_state)


def test_kill_and_resume_restores_client_state_bitexact(tmp_path):
    """The preemption drill with per-client controls in the carry: stop
    scaffold after ONE segment (state survives only on disk), resume in a
    FRESH call, finish bit-identical to the uninterrupted run — including
    every client's control variate."""
    clients, store = _setup()
    cfg = _cfg(strategy="scaffold")
    p0 = softmax_init(None, 24, 4)
    d = str(tmp_path / "scaffold")
    single = sim.run_experiment(softmax_loss, p0, store, cfg, 6,
                                donate=False)
    part = sim.run_experiment(softmax_loss, p0, store, cfg, 6, donate=False,
                              checkpoint_every=2, checkpoint_dir=d,
                              max_segments=1)
    assert part.rounds == 2
    resumed = sim.run_experiment(softmax_loss, p0, store, cfg, 6,
                                 donate=False, checkpoint_every=2,
                                 checkpoint_dir=d, resume=True)
    assert resumed.rounds == 6
    _assert_results_bitequal(single, resumed)
    _assert_trees_bitequal(single.strategy_state, resumed.strategy_state)
    # the snapshot meta names the strategy
    from repro.checkpoint import checkpoint as ckpt
    with open(os.path.join(ckpt.latest_run_state(d), "meta.json")) as f:
        import json
        md = json.load(f)["meta"]
    assert md["strategy"] == "scaffold" and md["algo"] == "scaffold"


# ---------------------------------------------------------------------------
# surrogate estimator: ≤ half the queries, matched loss


def test_surrogate_halves_queries_at_matched_loss():
    """The FedZOO-style surrogate phase pays ceil(b2/2) fresh queries per
    iterate (vs b2) and still lands within a whisker of the plain wide
    FedZO run on the softmax golden task — matched final-window loss and
    matched final accuracy."""
    from repro.workloads import neural

    task = neural.make_task("softmax", n_train=320, n_test=96, n_clients=6,
                            n_features=24, n_classes=4, alpha=0.5)
    base = neural.default_config(
        task, n_participating=3, local_iters=2, b1=8, b2=4, lr=5e-2,
        mu=1e-3, seed=11, batch_directions=True, direction_conv="block",
        prng_impl="unsafe_rbg")
    surr = dataclasses.replace(base, direction_conv="surrogate")
    assert fedzo.surrogate_queries(surr) * 2 <= base.b2
    res_w = neural.run(task, base, 24, eval_every=4, eval_rows=96,
                       donate=False)
    res_s = neural.run(task, surr, 24, eval_every=4, eval_rows=96,
                       donate=False)
    lw = np.asarray(res_w.metrics["mean_local_loss"])
    ls = np.asarray(res_s.metrics["mean_local_loss"])
    assert ls[-4:].mean() <= lw[-4:].mean() * 1.35
    assert ls[-4:].mean() < 0.5 * ls[0]          # it genuinely trains
    acc_w = float(np.asarray(res_w.evals["test_acc"])[-1])
    acc_s = float(np.asarray(res_s.evals["test_acc"])[-1])
    assert acc_s >= acc_w - 0.05


def test_surrogate_fraction_knob_sets_query_budget():
    cfg = _cfg(b2=20, surrogate_fraction=0.25)
    assert fedzo.surrogate_queries(cfg) == 5
    assert fedzo.surrogate_queries(_cfg(b2=3, surrogate_fraction=0.0)) == 1


# ---------------------------------------------------------------------------
# sweeps + history carry the strategy name


def test_sweep_strategy_axis_and_csv_tags(tmp_path):
    clients, store = _setup()
    p0 = softmax_init(None, 24, 4)
    cfg = _cfg(prox_mu=0.5)
    grid = sweep.scenario_grid(strategy=("fedzo", "fedprox"),
                               lr=(1e-2, 2e-2))
    out = str(tmp_path / "sweep.csv")
    recs = sweep.run_sweep(softmax_loss, p0, store, cfg, grid, 3,
                           out_csv=out)
    assert sorted(r["strategy"] for r in recs) == \
        ["fedprox", "fedprox", "fedzo", "fedzo"]
    by = {(r["strategy"], r["scenario"]["lr"]):
          r["metrics"]["mean_local_loss"] for r in recs}
    assert (by[("fedzo", 1e-2)] != by[("fedprox", 1e-2)]).any()
    text = open(out).read().splitlines()
    assert text[0] == "scenario,round,metric,value"
    tags = {line.split(",")[0] for line in text[1:]}
    assert any("strategy=fedprox" in t for t in tags)
    assert any("strategy=fedzo" in t for t in tags)


def test_sweep_without_strategy_axis_still_tags_rows(tmp_path):
    clients, store = _setup()
    p0 = softmax_init(None, 24, 4)
    out = str(tmp_path / "plain.csv")
    sweep.run_sweep(softmax_loss, p0, store, _cfg(),
                    sweep.scenario_grid(lr=(1e-2,)), 2, out_csv=out)
    rows = open(out).read().splitlines()[1:]
    assert rows and all(r.startswith("lr=0.01;strategy=fedzo,")
                        for r in rows)


def test_history_rows_carry_strategy_name():
    clients, store = _setup()
    p0 = softmax_init(None, 24, 4)
    res = sim.run_experiment(softmax_loss, p0, store,
                             _cfg(strategy="feddyn", dyn_alpha=0.1), 3,
                             donate=False)
    rows = res.history()
    assert len(rows) == 3
    assert all(r["strategy"] == "feddyn" for r in rows)
    assert rows == sim.history(res)


# ---------------------------------------------------------------------------
# baselines route through the shared direction conventions (satellite fix)


def _counter_step(params, batch, rng, *, scale, mu=1e-3, b2=3):
    """The estimator's counter-convention ZO step: the reference each
    baseline must follow when asked for conv="counter"."""
    coeffs, base = estimator.coefficients(softmax_loss, params, batch, rng,
                                          mu=mu, b2=b2, conv="counter")
    return estimator.apply_coefficients(params, rng, coeffs, scale=scale,
                                        conv="counter"), base


def test_baselines_counter_convention_pinned():
    """dzopa/zone_s/zo_sgd honor the counter Threefry convention (they used
    to silently drop it): each counter run follows the estimator's own
    counter-convention step, and differs from its tree-convention twin."""
    x, y = make_classification(64, 12, 3, seed=4)
    p0 = softmax_init(None, 12, 3)
    batch = {"x": x[:16], "y": y[:16]}
    rng = jax.random.key(9)

    p, base_l = baselines.zo_sgd_step(softmax_loss, p0, batch, rng, lr=1e-2,
                                      mu=1e-3, b2=3, conv="counter")
    ref, _ = _counter_step(p0, batch, rng, scale=-1e-2)
    np.testing.assert_allclose(np.asarray(p["w"]), np.asarray(ref["w"]),
                               rtol=1e-5)
    np.testing.assert_allclose(float(base_l), 1.0986123, rtol=1e-6)
    p_tree, _ = baselines.zo_sgd_step(softmax_loss, p0, batch, rng, lr=1e-2,
                                      mu=1e-3, b2=3)
    assert (np.asarray(p_tree["w"]) != np.asarray(p["w"])).any()

    cfg = FedZOConfig(n_devices=4, lr=1e-2, mu=1e-3, b2=3,
                      direction_conv="counter")
    cp = jax.tree.map(lambda l: jnp.stack([l] * 4), p0)
    cb = {"x": x.reshape(4, 16, 12), "y": y.reshape(4, 16)}
    crngs = jax.random.split(jax.random.key(7), 4)
    mixed, ml = baselines.dzopa_round(softmax_loss, cp, cb, crngs, cfg)
    agents, _ = jax.vmap(lambda q, b, r: _counter_step(q, b, r, scale=-1e-2))(
        cp, cb, crngs)
    np.testing.assert_allclose(
        np.asarray(mixed["w"]),
        np.broadcast_to(np.asarray(agents["w"]).mean(0), (4, 12, 3)),
        rtol=1e-4)
    cfg_tree = dataclasses.replace(cfg, direction_conv="tree")
    mixed_t, _ = baselines.dzopa_round(softmax_loss, cp, cb, crngs, cfg_tree)
    assert (np.asarray(mixed_t["w"]) != np.asarray(mixed["w"])).any()

    pz, _ = baselines.zone_s_round(softmax_loss, p0, batch, rng, rho=500.0,
                                   mu=1e-3, b2=3, conv="counter")
    ref_z, _ = _counter_step(p0, batch, rng, scale=-1.0 / 500.0)
    np.testing.assert_allclose(np.asarray(pz["w"]), np.asarray(ref_z["w"]),
                               rtol=1e-5)
