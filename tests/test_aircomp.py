"""AirComp transceiver tests (paper Section IV, Eqs. 14-17 + Remark 4),
including the channel-truncation mask semantics and the fused one-pass
aggregation kernel (kernels/zo_aircomp.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import hypothesis, st

from repro.configs.base import FedZOConfig
from repro.core import aircomp
from repro.core.aircomp import (aircomp_aggregate, aircomp_aggregate_flat,
                                aircomp_simulate_channel, schedule_by_channel)
from repro.kernels import ops, ref
from repro.sim.faults import FaultModel, RoundFaults
from repro.utils.flatparams import flat_geometry, flatten

BR = 4  # small kernel blocks for CPU interpret mode: 4 rows × 128 lanes

hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=10,
    suppress_health_check=list(hypothesis.HealthCheck))
hypothesis.settings.load_profile("ci")


def test_high_snr_recovers_mean():
    deltas = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(5, 64)),
                               dtype=jnp.float32)}
    agg, stats = aircomp_aggregate(deltas, jax.random.key(0), snr_db=200.0,
                                   h_min=0.8)
    np.testing.assert_allclose(np.asarray(agg["w"]),
                               np.asarray(jnp.mean(deltas["w"], 0)),
                               atol=1e-5)


def test_noise_variance_matches_eq17():
    """Empirical variance of the recovered update error ≈ σ_w²Δmax/(M²dPh²)."""
    rng = np.random.default_rng(1)
    M, d = 4, 256
    deltas = {"w": jnp.asarray(rng.normal(size=(M, d)), dtype=jnp.float32)}
    sq = np.sum(np.asarray(deltas["w"]) ** 2, axis=1)
    snr_db, h_min = 0.0, 0.8
    expected_var = 1.0 * sq.max() / (M ** 2 * d * 1.0 * h_min ** 2)
    errs = []
    mean = np.mean(np.asarray(deltas["w"]), axis=0)
    for s in range(200):
        agg, _ = aircomp_aggregate(deltas, jax.random.key(s), snr_db=snr_db,
                                   h_min=h_min)
        errs.append(np.asarray(agg["w"]) - mean)
    emp_var = np.var(np.stack(errs))
    assert 0.7 * expected_var < emp_var < 1.4 * expected_var, \
        (emp_var, expected_var)


def test_explicit_channel_matches_closed_form_variance():
    """The complex-channel simulation agrees with the Eq.17 closed form:
    the recovered update is exactly the scheduled-subset mean plus receiver
    noise whose variance matches σ_w²Δmax/(m²dPh²) under the complex→real
    projection (factor 1/2)."""
    rng = np.random.default_rng(2)
    M, d = 5, 512
    deltas = jnp.asarray(rng.normal(size=(M, d)), dtype=jnp.float32)
    errs, pred = [], []
    for s in range(100):
        y, diag = aircomp_simulate_channel(deltas, jax.random.key(s),
                                           snr_db=0.0, h_min=0.8)
        sched = np.abs(np.asarray(diag["h"])) >= 0.8
        if not sched.any():
            continue
        errs.append(np.asarray(y)
                    - np.asarray(deltas)[sched].mean(axis=0))
        pred.append(float(diag["delta_max"])
                    / (sched.sum() ** 2 * d * 0.8 ** 2) / 2.0)
    bias = np.abs(np.mean(np.stack(errs)))
    assert bias < 0.02, bias
    emp = np.var(np.stack(errs))
    expected = np.mean(pred)
    assert 0.7 * expected < emp < 1.4 * expected, (emp, expected)


def test_energy_constraint_for_scheduled_devices():
    """‖α_i Δ_i‖² ≤ dP for EVERY device: scheduled devices stay within the
    Eq.-15 budget, deep-fade devices (|h| < h_min) transmit nothing at all.
    Equal-norm rows make the old behavior unmistakable — any unscheduled
    row that transmitted would need α = h_min/|h| > 1 and blow through the
    budget. Regression: pre-fix the mask was ignored and unscheduled rows
    radiated over-budget energy."""
    rng = np.random.default_rng(3)
    base = rng.normal(size=(8, 128)).astype(np.float32)
    base /= np.linalg.norm(base, axis=1, keepdims=True)   # equal ‖Δ_i‖
    y, diag = aircomp_simulate_channel(jnp.asarray(base), jax.random.key(7),
                                       snr_db=0.0, h_min=0.8)
    scheduled = np.asarray(diag["mask"])
    assert 0 < scheduled.sum() < 8        # both populations present
    energy = np.asarray(diag["tx_energy"])
    assert np.all(energy <= diag["energy_budget"] * (1 + 1e-5)), energy
    np.testing.assert_array_equal(energy[~scheduled], 0.0)
    assert float(diag["m_effective"]) == scheduled.sum()


@hypothesis.given(st.floats(0.2, 1.5))
def test_schedule_rate_matches_rayleigh(h_min):
    """P(|h| ≥ h_min) = exp(-h_min²) for CN(0,1) channels."""
    h, mask = schedule_by_channel(jax.random.key(0), 20000, h_min)
    rate = float(jnp.mean(mask.astype(jnp.float32)))
    assert abs(rate - np.exp(-h_min ** 2)) < 0.02


def test_mask_excludes_rows_from_mean_and_delta_max():
    """Channel-truncation semantics: a masked-out row contributes to
    neither the mean nor Δ_max, and m_effective counts only scheduled
    rows. The masked row here has a huge norm so leakage into Δ_max (and
    hence the noise scale) would be unmistakable."""
    rng = np.random.default_rng(0)
    base = rng.normal(size=(4, 256)).astype(np.float32)
    base[2] *= 1e3                                 # the masked-out row
    deltas = {"w": jnp.asarray(base)}
    mask = jnp.asarray([True, True, False, True])
    agg, stats = aircomp_aggregate(deltas, jax.random.key(0), snr_db=200.0,
                                   h_min=0.8, mask=mask)
    expect = base[[0, 1, 3]].mean(axis=0)
    np.testing.assert_allclose(np.asarray(agg["w"]), expect, atol=1e-4)
    assert float(stats["m_effective"]) == 3.0
    sq = np.sum(base ** 2, axis=1)
    np.testing.assert_allclose(float(stats["delta_max"]),
                               sq[[0, 1, 3]].max(), rtol=1e-5)
    assert float(stats["delta_max"]) < sq[2]


def test_all_masked_round_degenerates_safely():
    """An all-masked round (no device scheduled) must not divide by zero:
    the aggregate is exactly zero (a no-op server update) with zero noise,
    in both the pytree and fused-flat implementations — and m_effective
    truthfully reports 0 (only the internal divisor is clamped)."""
    deltas = jnp.asarray(np.random.default_rng(1).normal(size=(3, 256)),
                         jnp.float32)
    mask = jnp.zeros((3,), bool)
    agg, stats = aircomp_aggregate({"w": deltas}, jax.random.key(0),
                                   snr_db=0.0, h_min=0.8, mask=mask)
    np.testing.assert_array_equal(np.asarray(agg["w"]),
                                  np.zeros_like(deltas[0]))
    assert float(stats["aircomp_noise_std"]) == 0.0
    assert float(stats["m_effective"]) == 0.0
    fagg, fstats = aircomp_aggregate_flat(deltas, jax.random.key(0),
                                          snr_db=0.0, h_min=0.8, mask=mask,
                                          block_rows=BR)
    np.testing.assert_array_equal(np.asarray(fagg),
                                  np.zeros_like(deltas[0]))
    assert float(fstats["aircomp_noise_std"]) == 0.0
    assert float(fstats["m_effective"]) == 0.0


def test_aircomp_reduce_kernel_matches_reference():
    """The fused kernel agrees with its pure-jnp oracle (same per-block,
    row-ascending partial-sum order) including the d-masking of padding."""
    m, blocks = 3, 2
    n = blocks * BR * 128
    d = n - 37                                      # exercise pad masking
    x = jax.random.normal(jax.random.key(0), (m, n), jnp.float32)
    scale = jnp.asarray([0.5, 0.0, 0.25], jnp.float32)
    mean, sq = ops.aircomp_reduce(x, scale, d, block_rows=BR)
    rmean, rsq = ref.aircomp_reduce_ref(x.reshape(m, -1, 128), scale, d,
                                        block_rows=BR)
    np.testing.assert_allclose(np.asarray(mean),
                               np.asarray(rmean).reshape(-1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sq), np.asarray(rsq), rtol=1e-6)
    # and with the direct formula
    direct_sq = np.sum(np.asarray(x[:, :d]) ** 2, axis=1)
    np.testing.assert_allclose(np.asarray(sq), direct_sq, rtol=1e-5)
    direct_mean = np.einsum("mn,m->n", np.asarray(x), np.asarray(scale))
    np.testing.assert_allclose(np.asarray(mean), direct_mean, atol=1e-5)


def test_fused_flat_matches_pytree_aggregate():
    """aircomp_aggregate_flat reproduces aircomp_aggregate exactly on the
    deterministic parts (mean, Δ_max, m_eff, noise_std) under a mask —
    only the noise realization differs (counter convention vs fold_in)."""
    deltas = jnp.asarray(np.random.default_rng(2).normal(size=(5, 640)),
                         jnp.float32)
    mask = jnp.asarray([True, False, True, True, False])
    agg_t, s_t = aircomp_aggregate({"w": deltas}, jax.random.key(1),
                                   snr_db=200.0, h_min=0.8, mask=mask)
    agg_f, s_f = aircomp_aggregate_flat(deltas, jax.random.key(1),
                                        snr_db=200.0, h_min=0.8, mask=mask,
                                        block_rows=BR)
    np.testing.assert_allclose(np.asarray(agg_f), np.asarray(agg_t["w"]),
                               atol=1e-5)
    for k in ("delta_max", "m_effective", "aircomp_noise_std"):
        np.testing.assert_allclose(float(s_f[k]), float(s_t[k]), rtol=1e-5)


# rows kept, size weights and faults of the shared server-step cases (M=5)
STEP_CASES = {
    "mean": {},
    "mask": {"mask": [True, False, True, True, False]},
    "weights": {"weights": [0.5, 1.5, 1.0, 0.25, 1.75]},
    "faults": {"weights": [0.5, 1.5, 1.0, 0.25, 1.75],
               "avail": [True, True, False, True, True],
               "corrupt": [False, True, False, False, False]},
    "aircomp": {"mask": [True, False, True, True, False], "aircomp": True},
}


@pytest.mark.parametrize("case", sorted(STEP_CASES))
def test_server_steps_agree_on_fixed_deltas(case):
    """``server_step_tree`` and ``server_step_flat`` turn the same hand-made
    deltas into the same params, momentum and metrics, and both equal
    x + β·m + Σ c_i Δ_i / Σ c_i over the rows kept (c = mask·w; a poisoned
    row is scrubbed, an unavailable one dropped). AirComp runs at 200 dB,
    where its noise is far below the tolerance and its statistics remain."""
    c = STEP_CASES[case]
    M, beta = 5, 0.5
    rng = np.random.default_rng(4)
    params = {"b": jnp.asarray(rng.normal(size=(3,)), jnp.float32),
              "w": jnp.asarray(rng.normal(size=(6, 3)), jnp.float32)}
    deltas = jax.tree.map(lambda p: jnp.asarray(
        rng.normal(size=(M,) + p.shape), jnp.float32), params)
    momentum = jax.tree.map(lambda p: jnp.full_like(p, 0.1), params)
    cfg = FedZOConfig(aircomp=c.get("aircomp", False), snr_db=200.0,
                      server_momentum=beta)
    kw = {"momentum": momentum}
    keep = np.ones(M, bool)
    w = np.ones(M, np.float32)
    if "mask" in c:
        kw["mask"] = jnp.asarray(c["mask"])
        keep &= c["mask"]
    if "weights" in c:
        w = np.asarray(c["weights"], np.float32)
        kw["weights"] = jnp.asarray(w)
    if "avail" in c:
        kw["faults"] = RoundFaults(FaultModel(p_corrupt=0.5),
                                   jnp.asarray(c["avail"]),
                                   jnp.asarray(c["corrupt"]))
        keep &= np.asarray(c["avail"]) & ~np.asarray(c["corrupt"])
    if cfg.aircomp:
        kw["noise_rng"] = jax.random.key(3)
    spec, br = flat_geometry(params, BR)
    flat = jax.vmap(lambda t: flatten(t, spec))(deltas)

    p_t, s_t, m_t, _ = aircomp.server_step_tree(deltas, params, cfg, **kw)
    p_f, s_f, m_f, _ = aircomp.server_step_flat(flat, params, cfg, spec, br,
                                                **kw)
    coef = keep * w
    for k in params:
        agg = np.einsum("m...,m->...", np.asarray(deltas[k]), coef) \
            / coef.sum()
        want_m = beta * 0.1 + agg
        for got_p, got_m in ((p_t, m_t), (p_f, m_f)):
            np.testing.assert_allclose(np.asarray(got_m[k]), want_m,
                                       atol=1e-5)
            np.testing.assert_allclose(np.asarray(got_p[k]),
                                       np.asarray(params[k]) + want_m,
                                       atol=1e-5)
    assert set(s_t) == set(s_f)
    for k in s_t:
        np.testing.assert_allclose(float(s_f[k]), float(s_t[k]), rtol=1e-5)
    if case != "mean":
        assert float(s_t["m_effective"]) == keep.sum()
    if "avail" in c:
        assert float(s_t["m_corrupt"]) == 1.0


def test_fused_flat_noise_variance_matches_closed_form():
    """The fused aggregation's error variance matches the Eq.-17 closed
    form σ_w²Δmax/(M²dPh²) — the same closed form the explicit complex
    simulation (aircomp_simulate_channel) is validated against."""
    rng = np.random.default_rng(3)
    M, d = 4, 512
    deltas = jnp.asarray(rng.normal(size=(M, d)), jnp.float32)
    sq = np.sum(np.asarray(deltas) ** 2, axis=1)
    snr_db, h_min = 0.0, 0.8
    expected_var = 1.0 * sq.max() / (M ** 2 * d * 1.0 * h_min ** 2)
    mean = np.mean(np.asarray(deltas), axis=0)
    f = jax.jit(lambda k: aircomp_aggregate_flat(
        deltas, k, snr_db=snr_db, h_min=h_min, block_rows=BR)[0])
    errs = [np.asarray(f(jax.random.key(s))) - mean for s in range(200)]
    emp_var = np.var(np.stack(errs))
    assert 0.7 * expected_var < emp_var < 1.4 * expected_var, \
        (emp_var, expected_var)


def test_noise_shrinks_as_updates_shrink():
    """Remark 4: the transceiver scales noise with Δmax, so late-training
    (small updates) sees proportionally small absolute noise."""
    big = {"w": 10.0 * jnp.ones((4, 64))}
    small = {"w": 0.1 * jnp.ones((4, 64))}
    _, s_big = aircomp_aggregate(big, jax.random.key(0), snr_db=0.0, h_min=0.8)
    _, s_small = aircomp_aggregate(small, jax.random.key(0), snr_db=0.0,
                                   h_min=0.8)
    ratio = float(s_big["aircomp_noise_std"] / s_small["aircomp_noise_std"])
    assert abs(ratio - 100.0) < 1.0  # ‖Δ‖ ratio is 100×
