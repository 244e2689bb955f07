"""Workload layer (repro.workloads, DESIGN.md §10) + the partitioner /
store / history edge-case fixes that make its heterogeneous variants safe.

- regression tests: ``random_partition`` uneven renormalization,
  ``noniid_shards`` remainder preservation, ``build_store`` dtype
  validation, ``history`` keeping ring-evicted eval rounds (each fails on
  the pre-fix code).
- size-weighted aggregation: the weighted ``mask_stats`` contract, the
  exact n_i/n aggregation identity, and engine ≡ host under
  ``cfg.weight_by_size``.
- both gradient-free workloads: engine-vs-host bit-match, eval curves,
  convergence smoke, and the attack SNR-sweep CSV.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sim
from repro.configs.base import FedZOConfig
from repro.core import fedzo
from repro.core.aircomp import aircomp_aggregate, mask_stats, size_weights
from repro.data.synthetic import (dirichlet_partition, make_classification,
                                  noniid_shards, random_partition)
from repro.fed.server import FedServer
from repro.models.simple import softmax_init, softmax_loss
from repro.workloads import attack, hypertune


def _assert_trees_bitequal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _partition_covers(clients, x, y):
    """Union of client rows == the full dataset (as multisets of rows)."""
    assert sum(len(c["y"]) for c in clients) == len(y)
    got = np.sort(np.concatenate([c["x"][:, 0] for c in clients]))
    np.testing.assert_array_equal(got, np.sort(x[:, 0]))


def _tagged(n):
    """Rows identifiable by value so coverage is checkable after shuffles."""
    x = np.arange(n, dtype=np.float32)[:, None].repeat(2, 1)
    y = (np.arange(n) % 3).astype(np.int32)
    return x, y


# ---------------------------------------------------------------------------
# partitioner regressions


def test_random_partition_uneven_counts_exact():
    """Pre-fix, the clamp-then-subtract count assignment could hand the
    last client 0 (or negative) rows — (n=12, 8 clients, seed=135) is such
    a draw. Every client must get ≥ 1 row and the union must be exact."""
    x, y = _tagged(12)
    clients = random_partition(x, y, 8, seed=135, uneven=True)
    sizes = [len(c["y"]) for c in clients]
    assert min(sizes) >= 1, sizes
    _partition_covers(clients, x, y)


def test_random_partition_uneven_invariants_grid():
    for n, nc, seed in [(12, 12, 3), (20, 10, 7), (40, 8, 0), (200, 10, 1)]:
        x, y = _tagged(n)
        clients = random_partition(x, y, nc, seed=seed, uneven=True)
        assert min(len(c["y"]) for c in clients) >= 1
        _partition_covers(clients, x, y)


def test_random_partition_even_keeps_remainder_rows():
    """The even path used to silently drop len(y) % n_clients tail rows."""
    x, y = _tagged(103)
    clients = random_partition(x, y, 10, seed=0, uneven=False)
    _partition_covers(clients, x, y)


def test_random_partition_rejects_more_clients_than_rows():
    x, y = _tagged(4)
    with pytest.raises(ValueError, match="at least one row"):
        random_partition(x, y, 5, uneven=True)


def test_noniid_shards_keeps_remainder_rows():
    """103 rows over 10 shards used to silently drop the 3 tail rows."""
    x, y = _tagged(103)
    clients = noniid_shards(x, y, 5, shards_per_client=2, seed=0)
    _partition_covers(clients, x, y)


def test_noniid_shards_even_split_unchanged():
    """Divisible datasets keep the original equal-shard protocol."""
    x, y = _tagged(120)
    clients = noniid_shards(x, y, 6, shards_per_client=2, seed=0)
    assert [len(c["y"]) for c in clients] == [20] * 6
    _partition_covers(clients, x, y)


def test_dirichlet_partition_covers_and_skews():
    x, y = make_classification(600, 8, 4, seed=0)
    x = np.concatenate([np.arange(600, dtype=np.float32)[:, None], x], 1)
    skew = dirichlet_partition(x, y, 6, alpha=0.1, seed=0)
    iid = dirichlet_partition(x, y, 6, alpha=1000.0, seed=0)
    for clients in (skew, iid):
        assert min(len(c["y"]) for c in clients) >= 1
        _partition_covers(clients, x, y)

    def mean_label_share(clients):
        # mean max-class share per client: 1/n_classes for iid, → 1 skewed
        shares = []
        for c in clients:
            counts = np.bincount(c["y"], minlength=4)
            shares.append(counts.max() / counts.sum())
        return np.mean(shares)

    assert mean_label_share(skew) > mean_label_share(iid) + 0.2


# ---------------------------------------------------------------------------
# store + history regressions


def test_build_store_rejects_mismatched_dtypes():
    with pytest.raises(ValueError, match="dtype"):
        sim.build_store([
            {"x": np.zeros((3, 2), np.float32), "y": np.zeros(3, np.int32)},
            {"x": np.zeros((4, 2), np.float64), "y": np.zeros(4, np.int32)},
        ])


def test_history_keeps_ring_evicted_eval_rounds():
    """rounds=8 with ring_size=3 keeps metric rows 5..7 only, but the
    in-scan evals of rounds 0/2/4 live in their own buffer — history must
    emit them as eval-only rows instead of dropping the curve's head."""
    x, y = make_classification(240, 12, 3, seed=0)
    clients = noniid_shards(x, y, 6)
    store = sim.build_store(clients)
    cfg = FedZOConfig(n_devices=6, n_participating=3, local_iters=2,
                      lr=1e-2, mu=1e-3, b1=8, b2=4, seed=3)
    p0 = softmax_init(None, 12, 3)
    ev = lambda p: {"probe": jnp.mean(p["w"])}  # noqa: E731
    ringed = sim.run_experiment(softmax_loss, p0, store, cfg, 8, eval_fn=ev,
                                eval_every=2, ring_size=3, donate=False)
    full = sim.run_experiment(softmax_loss, p0, store, cfg, 8, eval_fn=ev,
                              eval_every=2, donate=False)
    h_ring = sim.history(ringed)
    h_full = {h["round"]: h for h in sim.history(full)}
    assert [h["round"] for h in h_ring] == [0, 2, 4, 5, 6, 7]
    for h in h_ring:
        if h["round"] < 5:                  # evicted: eval-only rows
            assert set(h) == {"round", "probe", "strategy"}
        else:
            assert "mean_local_loss" in h
        if "probe" in h:
            assert h["probe"] == h_full[h["round"]]["probe"]


# ---------------------------------------------------------------------------
# size-weighted aggregation


def test_mask_stats_weighted_contract():
    mask = jnp.asarray([True, False, True, True])
    w = jnp.asarray([2.0, 1.0, 0.5, 0.5])
    maskf, m_div, m_sched = mask_stats(mask, 4, w)
    np.testing.assert_allclose(np.asarray(maskf), [2.0, 0.0, 0.5, 0.5])
    assert float(m_div) == 3.0
    assert float(m_sched) == 3.0            # UNWEIGHTED scheduled count
    # all-ones weights reproduce the unweighted path bit for bit
    mf_u, md_u, _ = mask_stats(mask, 4)
    mf_w, md_w, _ = mask_stats(mask, 4, jnp.ones(4))
    np.testing.assert_array_equal(np.asarray(mf_u), np.asarray(mf_w))
    assert float(md_u) == float(md_w)


def test_size_weights_mean_one():
    w = size_weights(jnp.asarray([10, 30, 20, 40]))
    np.testing.assert_allclose(np.asarray(w), [0.4, 1.2, 0.8, 1.6])
    assert abs(float(jnp.mean(w)) - 1.0) < 1e-6
    # uniform sizes are EXACTLY all-ones (the bit-for-bit fallback), even
    # where 1/s is inexact in fp32
    for s in (41, 77, 138):
        np.testing.assert_array_equal(
            np.asarray(size_weights(jnp.full((3,), s))), np.ones(3))


def test_weighted_aggregate_excludes_masked_and_weights_rest():
    rng = np.random.default_rng(0)
    base = rng.normal(size=(3, 64)).astype(np.float32)
    deltas = {"w": jnp.asarray(base)}
    mask = jnp.asarray([True, True, False])
    w = jnp.asarray([2.0, 1.0, 3.0])
    agg, stats = aircomp_aggregate(deltas, jax.random.key(0), snr_db=200.0,
                                   h_min=0.8, mask=mask, weights=w)
    expect = (2.0 * base[0] + 1.0 * base[1]) / 3.0
    np.testing.assert_allclose(np.asarray(agg["w"]), expect, atol=1e-4)
    assert float(stats["m_effective"]) == 2.0


def test_round_weighted_aggregation_identity():
    """round_simulated(weights=w) == x + Σ w_i Δ_i / Σ w_i with the Δ_i of
    the exact same local phases: client_delta vmapped as the round runs
    it, so both sides carry the same float32 rounding (an un-vmapped
    replay differs by a loss ulp over mu, ~3e-5 here)."""
    cfg = FedZOConfig(n_devices=2, n_participating=2, local_iters=2,
                      lr=1e-2, mu=1e-3, b1=4, b2=3, seed=0)
    params = {"x": jnp.zeros((20,))}

    def quad(p, batch):
        return 0.5 * jnp.sum((p["x"] - batch["t"]) ** 2)

    batches = {"t": jnp.stack([jnp.ones((2, 20)), -jnp.ones((2, 20))])}
    rngs = jax.random.split(jax.random.key(1), 2)
    w = jnp.asarray([1.5, 0.5])
    newp, _ = fedzo.round_simulated(quad, params, batches, rngs, cfg,
                                    weights=w)
    deltas, _ = jax.vmap(lambda b, r: fedzo.client_delta(
        quad, params, b, r, cfg))(batches, rngs)
    d = np.asarray(deltas["x"])
    assert np.abs(d[0] - d[1]).max() > 1e-3    # the weights matter
    expect = (1.5 * d[0] + 0.5 * d[1]) / 2.0
    np.testing.assert_allclose(np.asarray(newp["x"]), expect, atol=1e-6)


def _uneven_setup(n=300, n_clients=6, n_features=16, n_classes=4):
    x, y = make_classification(n, n_features, n_classes, seed=0)
    clients = random_partition(x, y, n_clients, seed=2, uneven=True)
    return clients, sim.build_store(clients)


@pytest.mark.parametrize("kw,algo", [
    ({}, "fedzo"),
    ({"aircomp": True, "snr_db": 10.0, "channel_schedule": True}, "fedzo"),
    ({"batch_directions": True, "direction_conv": "block",
      "prng_impl": "unsafe_rbg"}, "fedzo"),
    ({}, "fedavg"),
])
def test_weight_by_size_engine_bitmatches_host(kw, algo):
    """cfg.weight_by_size threads identically through the scan engine and
    the host-driven store rounds on every aggregation path."""
    clients, store = _uneven_setup()
    cfg = FedZOConfig(n_devices=6, n_participating=3, local_iters=2,
                      lr=1e-2, mu=1e-3, b1=8, b2=4, seed=5,
                      weight_by_size=True, **kw)
    p0 = softmax_init(None, 16, 4)
    host = FedServer(softmax_loss, p0, clients, cfg, algo=algo, store=store)
    for t in range(3):
        host.run_round(t)
    scanned = FedServer(softmax_loss, p0, clients, cfg, algo=algo,
                        store=store)
    scanned.run(3)
    _assert_trees_bitequal(host.params, scanned.params)


def test_weight_by_size_host_loop_without_store():
    """The per-round Python driver (clients list, no store) computes the
    same n_i/n weights from the host datasets — weighted runs complete and
    diverge from uniform ones on an uneven split."""
    clients, _ = _uneven_setup()

    def final(wbs):
        cfg = FedZOConfig(n_devices=6, n_participating=3, local_iters=2,
                          lr=1e-2, mu=1e-3, b1=8, b2=4, seed=5,
                          weight_by_size=wbs)
        srv = FedServer(softmax_loss, softmax_init(None, 16, 4), clients,
                        cfg)
        srv.run(2, driver="host")
        return np.asarray(srv.params["w"])

    assert np.abs(final(True) - final(False)).max() > 1e-8


def test_weight_by_size_changes_trajectory_on_uneven_split():
    clients, store = _uneven_setup()
    assert len(set(int(s) for s in store.sizes)) > 1
    p0 = softmax_init(None, 16, 4)

    def final(wbs):
        cfg = FedZOConfig(n_devices=6, n_participating=3, local_iters=2,
                          lr=1e-2, mu=1e-3, b1=8, b2=4, seed=5,
                          weight_by_size=wbs)
        res = sim.run_experiment(softmax_loss, p0, store, cfg, 3,
                                 donate=False)
        return np.asarray(res.params["w"])

    assert np.abs(final(True) - final(False)).max() > 1e-8


# ---------------------------------------------------------------------------
# attack workload

ATTACK_KW = dict(n_train=400, n_attack=96, n_clients=5, train_steps=120)


def test_attack_engine_bitmatches_host_rounds():
    task = attack.make_task(**ATTACK_KW)
    cfg = attack.default_config(task, local_iters=2, b2=4, b1=8,
                                n_participating=3, seed=7)
    loss = attack.attack_loss(task)
    p0 = attack.pert_init()
    host = FedServer(loss, p0, task.clients, cfg, store=task.store)
    for t in range(2):
        host.run_round(t)
    scanned = FedServer(loss, p0, task.clients, cfg, store=task.store)
    scanned.run(2)
    _assert_trees_bitequal(host.params, scanned.params)


def test_attack_workload_descends_with_inscan_eval_curve():
    task = attack.make_task(**ATTACK_KW)
    assert 0.5 < task.clean_accuracy <= 1.0
    cfg = sim.fast_sim_config(
        attack.default_config(task, local_iters=3, b2=6, b1=8))
    res = attack.run(task, cfg, 6, eval_every=2, donate=False)
    hist = sim.history(res)
    assert [h["round"] for h in hist] == [0, 1, 2, 3, 4, 5]
    evs = [h for h in hist if "attack_success" in h]
    assert [h["round"] for h in evs] == [0, 2, 4]
    assert all(0.0 <= h["attack_success"] <= 1.0 for h in evs)
    # the pooled CW objective descends (per-round minibatch loss is noisy)
    assert evs[-1]["eval_cw_loss"] < evs[0]["eval_cw_loss"]


def test_attack_sweep_emits_snr_curve_csv(tmp_path):
    task = attack.make_task(**ATTACK_KW)
    cfg = sim.fast_sim_config(
        attack.default_config(task, local_iters=2, b2=4, b1=8))
    out = tmp_path / "attack_snr.csv"
    recs = attack.run_sweep(task, cfg, snr_dbs=(-5.0, 15.0), seeds=(0, 1),
                            rounds=3, eval_every=2, out_csv=str(out))
    assert len(recs) == 4
    for r in recs:
        assert r["evals"]["attack_success"].shape == (2,)
        assert np.isfinite(r["metrics"]["mean_local_loss"]).all()
    lines = out.read_text().splitlines()
    assert lines[0] == "scenario,round,metric,value"
    assert any("attack_success" in ln for ln in lines[1:])
    # the vmapped snr axis reaches the channel
    lo = [r for r in recs if r["scenario"]["snr_db"] == -5.0]
    hi = [r for r in recs if r["scenario"]["snr_db"] == 15.0]
    assert (np.mean([r["metrics"]["aircomp_noise_std"].mean() for r in lo])
            > np.mean([r["metrics"]["aircomp_noise_std"].mean() for r in hi]))


# ---------------------------------------------------------------------------
# hypertune workload


def test_hypertune_engine_bitmatches_host_rounds():
    task = hypertune.make_task()
    cfg = hypertune.default_config(task, seed=11)
    loss = hypertune.tune_loss(task)
    p0 = hypertune.hp_init()
    host = FedServer(loss, p0, task.clients, cfg, store=task.store)
    for t in range(3):
        host.run_round(t)
    scanned = FedServer(loss, p0, task.clients, cfg, store=task.store)
    scanned.run(3)
    _assert_trees_bitequal(host.params, scanned.params)


def test_hypertune_converges_on_synthetic_task():
    """The tuner must improve the inner-trained validation loss from the
    deliberately mis-tuned start (and move the inner lr up toward useful
    magnitudes) — the convergence smoke of the acceptance criteria."""
    task = hypertune.make_task()
    cfg = sim.fast_sim_config(hypertune.default_config(task))
    res = hypertune.run(task, cfg, 10, eval_every=2, donate=False)
    evs = [h for h in sim.history(res) if "val_loss" in h]
    assert len(evs) == 5
    assert evs[-1]["val_loss"] < evs[0]["val_loss"] * 0.8
    assert evs[-1]["log_lr"] > evs[0]["log_lr"]
    assert np.isfinite([h["val_loss"] for h in evs]).all()


def test_hypertune_transform_clips_to_sane_band():
    lr, lam = hypertune.transform(jnp.asarray([50.0, -50.0]))
    assert float(lr) == pytest.approx(np.exp(hypertune.LOG_LR_RANGE[1]))
    assert float(lam) == pytest.approx(np.exp(hypertune.LOG_LAM_RANGE[0]))
